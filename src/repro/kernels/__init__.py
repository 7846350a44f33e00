"""Pallas TPU kernels for the compute hot-spots.

Each kernel is a jitted function in <name>.py (pl.pallas_call + explicit
BlockSpec VMEM tiling) with a pure-jnp oracle in ref.py that the tests
allclose against. A kernel compiles to Mosaic for the TPU. ``interpret=True``
runs the same kernel body op by op on any backend; only the caller chooses
it (the CPU tests do). Without it, a call on any other backend raises.

flash_attention  train/prefill attention (causal/SWA/softcap/GQA) -- removes
                 the S^2 logits HBM round-trip that dominates the baseline
                 roofline memory term.
paged_attention  decode attention over the SA-cache-managed paged KV pool
                 (scalar-prefetched page table -- the serving engine's data
                 plane).
flush_score      the paper's SS3.3.1 GClock distance-score + rank over page
                 sets, vectorized sets-to-sublanes (the host-side hot loop of
                 SAFS adapted to the TPU VPU).
"""
