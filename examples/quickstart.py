"""Quickstart: the paper's machinery in 60 seconds.

1. SA-cache + GClock flush scores (the policy layer, pure JAX),
2. the dirty-page flusher filling dual-priority queues,
3. a tiny LM trained with the full stack (sharded step + async checkpoints).

  PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

import jax
import jax.numpy as jnp

# --- 1. the policy layer --------------------------------------------------
from repro.core import sa_cache
from repro.kernels.flush_score import flush_scores as flush_scores_kernel

cache = sa_cache.make_cache(num_sets=4, set_size=12)
for tag in range(40):                       # fill with pages, some dirty
    s = jnp.int32(tag % 4)
    _, _, slot, cache = sa_cache.insert(cache, s, jnp.int32(tag),
                                        jnp.bool_(tag % 3 == 0))
scores = sa_cache.flush_scores(cache)
print("flush scores (JAX twin):\n", np.asarray(scores))
kscores = flush_scores_kernel(cache.hits, cache.clock,
                              cache.tags != sa_cache.EMPTY,
                              interpret=jax.default_backend() != "tpu")
assert (np.asarray(kscores) == np.asarray(scores)).all()
print("Pallas flush_score kernel matches the policy layer\n")

# --- 2. flusher + dual-priority queues -------------------------------------
from repro.core.flusher import DirtyPageFlusher
from repro.core.io_queues import HIGH, LOW, DualQueue, IORequest


class View:                                  # minimal CacheView
    def dirty_count(self, s):
        return int((np.asarray(cache.dirty[s]) &
                    (np.asarray(cache.tags[s]) != -1)).sum())

    def flush_candidates(self, s):
        fs = np.asarray(sa_cache.flush_scores(cache))[s]
        d = np.asarray(cache.dirty[s])
        return sorted(((i, int(cache.tags[s, i]), int(fs[i]))
                       for i in range(12) if d[i]), key=lambda t: -t[2])

    def device_of(self, tag):
        return tag % 2


fl = DirtyPageFlusher(View(), n_devices=2, trigger=2)
for s in range(4):
    fl.note_write(s)
q = DualQueue(max_inflight=32, reserved=7)   # paper: 7 of 32 slots reserved
for fr in fl.make_requests(budget=8):
    q.submit(IORequest(payload=fr, priority=LOW))
q.submit(IORequest(payload="application read", priority=HIGH))
first = q.pop_next()
print("first issued request:", first.payload, "(HIGH overtakes the backlog)\n")

# --- 3. tiny end-to-end training ------------------------------------------
from repro.launch.train import main as train

losses = train(["--arch", "tinyllama-1.1b", "--preset", "smoke",
                "--steps", "20", "--batch", "8", "--seq", "64",
                "--lr", "3e-3"])
print(f"\ntrained 20 steps: loss {losses[0]:.3f} -> {losses[-1]:.3f}")
