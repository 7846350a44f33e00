"""The plain reference against the program's own forward pass at a tiny
size in float32 on the CPU (they must agree to rounding), and the numbers
compared."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import bench, weights as W
from chipbench.model import model_config
from chipbench.reference import check, moe_lm
from chipbench.tests.cpu_cell import TINY_MODEL  # noqa: F401  (puts src/ on the path)


@pytest.mark.parametrize("config", ["granite-moe-1b-a400m", "olmoe-1b-7b-pp2"])
def test_reference_matches_the_program_at_a_tiny_size(config):
    from repro.models import transformer as T
    model = bench.load_json(bench.PKG / "configs" / f"{config}.json")["model"]
    model = dict(model, n_layers=2, d_model=64, n_heads=4, head_dim=16,
                 n_kv_heads=2 if model["n_kv_heads"] < model["n_heads"] else 4,
                 moe_experts=8, moe_topk=2, moe_d_ff=32, d_ff=32, vocab=128,
                 moe_capacity_factor=4.0, dtype="float32")
    cfg = model_config(model)
    params, leaves = W.program_params(5, cfg, T.init_params)
    tokens = jnp.asarray(np.random.default_rng(0).integers(1, 128, 40), jnp.int32)
    with jax.default_matmul_precision("highest"):
        want, _ = T.forward_logits(params, tokens[None], cfg, remat=False)
    got = moe_lm.logits(W.make(5, leaves, cfg), tokens, moe_lm.model_key(model))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want[0]), atol=2e-4, rtol=2e-4)


def test_gap_stats():
    s = check.gap_stats([np.array([0.0, 0.5, 0.0]), np.array([0.0, 0.1])])
    assert s == pytest.approx({"widest_logit_gap": 0.5, "mean_logit_gap": 0.12,
                               "off_argmax_share": 0.4})
    assert check.gap_stats([])["widest_logit_gap"] > 1e9


def test_sample_holds_the_longest_and_the_preempted():
    lengths = {r: 10 + r for r in range(20)}
    rng = np.random.default_rng(3)
    chosen = check.choose_sample(list(range(20)), lengths.get, {3, 5, 7}, rng,
                                 lambda r: 10, max_requests=5, min_tokens=40,
                                 preempted_max=2)
    assert chosen[0] == 19
    assert len({3, 5, 7} & set(chosen)) == 2
    assert len(chosen) == 4           # 40 tokens reached
