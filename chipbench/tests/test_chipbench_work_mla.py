"""The work of the latent-attention cell against counts made by hand, the
driver's window work from the engine's counters, and the configuration
file tied to the program's own definition of the published model."""
import dataclasses

import pytest

from chipbench import bench, peaks, work, work_mla
from chipbench.drivers import serve_mla
from chipbench.model import model_config
from chipbench.reference import deepseek_v2
from chipbench.tests import cpu_cell  # noqa: F401  (puts src/ on the path)
from repro.models import layers as L

FILE = bench.load_json(bench.PKG / "configs" / "deepseek-v2-lite-pp6.json")
DSV2 = FILE["model"]


def test_decode_step_by_hand():
    # per layer, absorbed: wq 2048 x 3072, wkv_a 2048 x 576, W_UK and W_UV
    # 16 x 512 x 128 each, wo 2048 x 2048
    attn = 2048 * 3072 + 2048 * 576 + 2 * 16 * 512 * 128 + 2048 * 2048
    dense = 3 * 2048 * 10944
    moe = 2048 * 64 + 6 * 3 * 2048 * 1408 + 3 * 2048 * 2816
    per_token = 5 * attn + dense + 4 * moe + 2048 * 102400
    rows, context = 160, 160 * 1650
    w = work_mla.decode_step(DSV2, rows, context)
    # scores over 512 + 64 lanes, values over 512, 16 heads, 5 layers
    assert w.flops == pytest.approx(2 * rows * per_token + 2 * context * 16 * 1088 * 5)
    # bytes: every weight once (160 tokens touch all but 64 (58/64)^160 of
    # the experts), router in float32, each entry 576 values of 2 bytes
    params = 2048 * 3072 + 2048 * 576 + 512 + 512 * 4096 + 2048 * 2048
    experts = 64 * (1 - (1 - 6 / 64) ** 160)
    weights = (5 * params * 2 + dense * 2
               + 4 * (2048 * 64 * 4 + experts * 3 * 2048 * 1408 * 2
                      + 3 * 2048 * 2816 * 2)
               + 2048 * 102400 * 2)
    assert work_mla.entry_bytes(DSV2) == 5 * 576 * 2 == 5760
    assert w.bytes == pytest.approx(weights + (context + rows) * 5760)
    # the cell's sizes: 6.78 GB a step, 1.52 GB of it the latent cache
    assert w.bytes == pytest.approx(6.78e9, rel=2e-3)
    assert work.roofline_seconds(w, peaks.peak("TPU v5 lite"))[1] == "memory"


def test_prefill_by_hand():
    # expanded: W_kvb applied to every token (512 x 16 x 256)
    attn = 2048 * 3072 + 2048 * 576 + 512 * 4096 + 2048 * 2048
    dense = 3 * 2048 * 10944
    moe = 2048 * 64 + 6 * 3 * 2048 * 1408 + 3 * 2048 * 2816
    s = 300
    pairs = s * (s + 1) / 2
    w = work_mla.prefill(DSV2, s, pairs)
    assert w.flops == pytest.approx(2 * s * (5 * attn + dense + 4 * moe)
                                    + 2 * 2048 * 102400
                                    + 2 * pairs * 16 * (192 + 128) * 5)
    # two prompts of 150 tokens: the head twice, the weights read twice
    two = work_mla.prefill(DSV2, s, pairs, prompts=2)
    assert two.flops == pytest.approx(w.flops + 2 * 2048 * 102400)
    assert two.bytes > w.bytes


def test_window_work_from_the_counters():
    counters = {"decode_rows": 3 * 160, "decode_context_tokens": 3 * 264_000,
                "prefill_tokens": 500, "prefill_attn_pairs": 40_000}
    steps = {"decode": 3, "prefills": 4, "per_step": [(1.0, 1.0)] * 3,
             "prefill_flops": 1.0}
    out = serve_mla.window_work(DSV2, counters, steps)
    one = work_mla.decode_step(DSV2, 160, 264_000)
    assert out["per_step"] == [(one.flops, one.bytes)] * 3
    assert out["prefill_flops"] == work_mla.prefill(DSV2, 500, 40_000, 4).flops
    assert out["decode"] == 3 and out["prefills"] == 4


# the configuration file's copy of the published config.json, key by key,
# against the program's fields
PUBLISHED_KEYS = {"hidden_size": "d_model", "intermediate_size": "d_ff",
           "kv_lora_rank": "kv_lora_rank", "moe_intermediate_size": "moe_d_ff",
           "n_routed_experts": "moe_experts", "num_attention_heads": "n_heads",
           "num_experts_per_tok": "moe_topk", "num_hidden_layers": "n_layers",
           "num_key_value_heads": "n_kv_heads", "qk_nope_head_dim": "qk_nope_head_dim",
           "qk_rope_head_dim": "qk_rope_head_dim", "rms_norm_eps": "norm_eps",
           "rope_theta": "rope_theta",
           "v_head_dim": "v_head_dim", "vocab_size": "vocab",
           "first_k_dense_replace": "n_dense_layers",
           "max_position_embeddings": "max_seq",
           "norm_topk_prob": "moe_norm_topk",
           "tie_word_embeddings": "tie_embeddings"}
YARN = {"factor": "yarn_factor", "original_max_position_embeddings":
        "yarn_original_max_pos", "mscale": "yarn_mscale",
        "mscale_all_dim": "yarn_mscale_all_dim"}


def test_cell_config_is_the_published_model_cut_in_depth():
    from repro.configs import get_config
    published = get_config("deepseek-v2-lite")
    cut = model_config(DSV2)
    # the program's definition and the cell's differ in the name and in the
    # keys the file lists as reduced and assumed
    changed = {f.name for f in dataclasses.fields(published)
               if getattr(published, f.name) != getattr(cut, f.name)}
    allowed = {"name", "n_layers"} | set(FILE["assumed"])
    assert changed <= allowed, changed - allowed
    assert FILE["reduced"] == {"num_hidden_layers": [published.n_layers, cut.n_layers]}
    # the file's copy of config.json is the published model but for the
    # reduced key; the program reads each as the same number
    for key, field in PUBLISHED_KEYS.items():
        want = getattr(cut if key in FILE["reduced"] else published, field)
        assert FILE[key] == want, key
    for key, field in YARN.items():
        assert FILE["rope_scaling"][key] == getattr(published, field), key
    assert published.moe_shared_d_ff == FILE["n_shared_experts"] * FILE["moe_intermediate_size"]
    assert FILE["q_lora_rank"] is None and FILE["rope_scaling"]["type"] == "yarn"
    # published values the program and the reference hold as constants
    assert FILE["routed_scaling_factor"] == 1       # no routed scale applied
    beta = (FILE["rope_scaling"]["beta_fast"], FILE["rope_scaling"]["beta_slow"])
    assert beta == (L.YARN_BETA_FAST, L.YARN_BETA_SLOW)
    assert beta == (deepseek_v2.BETA_FAST, deepseek_v2.BETA_SLOW)
    assert published.param_count() == pytest.approx(15.7e9, rel=0.01)
    assert cut.param_count() == pytest.approx(2.84e9, rel=0.01)
