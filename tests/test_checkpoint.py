"""Async checkpoint manager: roundtrip, supersede/stale-discard, priority."""
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.checkpoint import CheckpointManager


def _tree(seed, dtype=jnp.float32):
    k = jax.random.PRNGKey(seed)
    return {"a": jax.random.normal(k, (32, 16), dtype),
            "b": {"w": jax.random.normal(k, (8,), dtype),
                  "s": jnp.asarray(seed, jnp.int32)}}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_roundtrip(tmp_path, dtype):
    m = CheckpointManager(tmp_path, n_targets=2)
    t = _tree(0, dtype)
    m.save_async(0, t)
    assert m.wait_for_commit(0, 30)
    step, got = m.restore(t)
    assert step == 0
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(got)):
        assert b.dtype == a.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    m.close()


def test_supersede_discards_stale_writes(tmp_path):
    # a slow writer + rapid saves: early steps' chunks are discarded at the
    # queue head because newer saves superseded them (paper §3.3.2)
    m = CheckpointManager(tmp_path, n_targets=1, max_inflight=1,
                          write_delay=0.05)
    for s in range(6):
        m.save_async(s, _tree(s))
    assert m.drain(60)
    assert m.stats["discarded_stale"] > 0
    last = m.latest_step()
    assert last == 5
    _, got = m.restore(_tree(0))
    np.testing.assert_allclose(np.asarray(got["a"]),
                               np.asarray(_tree(5)["a"]))
    m.close()


def test_restore_runs_while_writes_queued(tmp_path):
    m = CheckpointManager(tmp_path, n_targets=1, max_inflight=1,
                          write_delay=0.02)
    t = _tree(1)
    m.save_async(0, t)
    assert m.wait_for_commit(0, 30)
    for s in range(1, 5):
        m.save_async(s, _tree(s))
    t0 = time.monotonic()
    step, got = m.restore(t, step=0)          # HIGH priority overtakes
    dt = time.monotonic() - t0
    assert step == 0
    # must not wait for the whole backlog (4 saves x 3 chunks x 20ms each)
    assert dt < 0.2, f"restore waited {dt}s behind low-priority writes"
    m.drain(60)
    m.close()


def test_resume_to_different_structure_fails_loud(tmp_path):
    m = CheckpointManager(tmp_path)
    m.save_async(0, _tree(0))
    assert m.wait_for_commit(0, 30)
    with pytest.raises(Exception):
        m.restore({"different": jnp.zeros(3)})
    m.close()


def test_retention(tmp_path):
    m = CheckpointManager(tmp_path, keep=2)
    for s in range(5):
        m.save_async(s, _tree(s))
        assert m.wait_for_commit(s, 30)
    # wait_for_commit returns the moment the new manifest lands, which is
    # *before* the worker prunes old manifests (retention runs right after
    # the rename in the same _commit); drain() returns only once that whole
    # completion finished, so the glob below can't race the pruning
    assert m.drain(30)
    manifests = sorted(p.name for p in tmp_path.glob("manifest-*.json"))
    assert manifests == ["manifest-3.json", "manifest-4.json"]
    m.close()


def test_changed_keys_filter(tmp_path):
    m = CheckpointManager(tmp_path, n_targets=2)
    t = _tree(0)
    m.save_async(0, t)
    assert m.wait_for_commit(0, 30)
    w0 = m.stats["written"]
    m.save_async(1, t, changed={"a"})          # dirty-chunk tracking
    m.drain(30)
    assert m.stats["written"] == w0 + 1
    m.close()


def test_write_barrier_orders_durability(tmp_path):
    """Paper §3.4: everything before the barrier is durable after it."""
    m = CheckpointManager(tmp_path, n_targets=2, write_delay=0.01)
    for s in range(3):
        m.save_async(s, _tree(s))
    assert m.barrier(60)
    # all surviving (non-superseded) steps are committed now
    assert m.latest_step() == 2
    committed = sorted(int(p.stem.split("-")[1])
                       for p in tmp_path.glob("manifest-*.json"))
    drained = m.stats["written"] + m.stats["discarded_stale"]
    assert drained == 3 * 3  # 3 chunks per tree, none left in flight
    assert committed[-1] == 2
    m.close()


def test_elastic_restore_reshards(tmp_path):
    """Restore onto explicit (different) shardings — elastic resume path."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    m = CheckpointManager(tmp_path)
    t = _tree(4)
    m.save_async(0, t)
    assert m.wait_for_commit(0, 30)
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    sh = jax.tree.map(lambda _: NamedSharding(mesh, P()), t)
    step, got = m.restore(t, shardings=sh)
    assert step == 0
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(got)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b))
        assert b.sharding.is_equivalent_to(NamedSharding(mesh, P()), b.ndim)
    m.close()
