"""Bring-up check on a TPU: the paged-KV serving path at published widths.

  python chip_smoke.py                # one chip: serving + kernel check
  python chip_smoke.py --four-chips   # four chips: sharded training only

One chip: granite-moe-1b-a400m, unmodified (bf16, 24 layers, seeded random
weights), serves requests through ``ServeEngine`` with the flusher on and a
KV pool small enough to force offloads, preemptions and resume fetches. Its
tokens are checked against the dense prefill + decode path of the same
model, fed the same tokens. Then the Pallas paged-attention kernel runs on
the engine's own pools, page tables and lengths and is compared with
``paged_attention_ref``.

Four chips: the same model trains through the pieces of
``launch/train.py`` on a (4, 1) ("data", "model") mesh (FSDP over data,
experts spread over data with all-to-all): a few steps, one async
checkpoint save and a resume from it, and step 0's loss against a
forward-only pass on one chip.

Without a TPU the script exits nonzero. Every line but the last is
bring-up output, not a benchmark result. The last line is one JSON object:
{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}.
"""
from __future__ import annotations

import argparse
import dataclasses
import faulthandler
import functools
import json
import shutil
import sys
import threading
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.checkpoint import CheckpointManager  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.data import SyntheticLM, make_global_batch  # noqa: E402
from repro.distributed.sharding import data_spec  # noqa: E402
from repro.kernels.paged_attention import paged_attention  # noqa: E402
from repro.kernels.ref import paged_attention_ref  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.launch.mesh import make_host_mesh  # noqa: E402
from repro.launch.steps import make_loss_fn, make_train_step  # noqa: E402
from repro.launch.train import init_state  # noqa: E402
from repro.models import transformer as T  # noqa: E402
from repro.serving import ServeEngine  # noqa: E402

ARCH = "granite-moe-1b-a400m"
SEED = 0
WATCHDOG_S = 1100          # a stuck fetch or device call ends the run, nonzero

# Serving geometry. One page at these widths is 24 layers x 16 tokens x 8 KV
# heads x 64 x 2 B x (K, V) = 0.79 MB. The pool holds 12 sets x 4 ways = 48
# pages. Twelve requests of 40 or 100 prompt tokens plus 64 new ones need up
# to 11 pages each, so eight active rows want up to 88: the engine has to
# offload, preempt and fetch back. Two prompt lengths: two prefill compiles.
MAX_BATCH, PAGE, NUM_SETS, SET_SIZE, MAX_PAGES = 8, 16, 12, 4, 16
PROMPT_LENS, N_REQUESTS, MAX_NEW = (40, 100), 12, 64
MAX_STEPS = 2000

# Pallas vs reference: both read the same bf16 pages and accumulate in f32.
# The kernel rounds its output to bf16 (half an ulp: 2**-9 relative) and the
# MXU may round the softmax weights to bf16 (2**-9 of max|v|). The bound is
# twice their sum, relative to the largest |v| in the pool.
KERNEL_TOL = 2 ** -7
# Engine vs dense decode of the same bf16 model fed the same tokens: the
# engine batches 8 rows and gathers K/V from pages, the dense path runs one
# row over a ring buffer, so their bf16 roundings differ. Each engine token's
# logit must lie within this many logits of the dense path's maximum; a token
# unrelated to its context falls short by several logit standard deviations.
REF_TOL = 0.25

# Training on four chips: granite at B=8, S=2048; the state (bf16 params,
# f32 master, m, v: ~16 B/param, 21 GB) fits only when sharded.
TRAIN_BATCH, TRAIN_SEQ = 8, 2048
# Step 0's ce, four chips vs one. The sharded step routes each data shard's
# 4,096 tokens with its own expert capacity (1.25 x its share), the one-chip
# pass routes all 16,384 tokens against one capacity, so they drop different
# tokens at full experts. Skewed (Zipf) tokens fill some experts, and
# dropping moves ce by a few hundredths (the run prints by how much on one
# chip); the bound is a few times that.
CE_TOL = 0.1
# The same comparison with capacity for every token (factor experts/top-k)
# drops nothing, so only the sharding is left to differ: FSDP gathers,
# expert all-to-alls, bf16 sums taken in another order over 24 layers.
SHARD_TOL = 5e-3


def log(msg: str) -> None:
    print(msg, flush=True)


class ThreadErrors:
    """Collects exceptions raised in worker threads (the offload executor's),
    which would otherwise print and let the run end as if nothing failed."""

    def __init__(self):
        self.errors: list[BaseException] = []
        threading.excepthook = self._hook

    def _hook(self, args) -> None:
        self.errors.append(args.exc_value)
        log(f"thread {args.thread.name} raised {args.exc_value!r}")

    def check(self, phase: str) -> None:
        if self.errors:
            raise RuntimeError(f"{phase}: {len(self.errors)} worker thread(s) "
                               f"raised; first: {self.errors[0]!r}")


# --------------------------------------------------------------- one chip
def serve(cfg, params):
    """Serves the requests; returns (requests, stats, the busiest decode step's
    inputs: pools after its writes, page table, lengths, active rows)."""
    eng = ServeEngine(cfg, params, max_batch=MAX_BATCH, page_size=PAGE,
                      num_sets=NUM_SETS, set_size=SET_SIZE,
                      max_pages=MAX_PAGES, use_flusher=True)
    busiest: dict = {"rows": 0}
    decode = eng.step_fn

    def recording_step(params, pools, tokens, lengths, table, active):
        logits, new_pools = decode(params, pools, tokens, lengths, table,
                                   active)
        rows = int(np.sum(np.asarray(active)))
        if rows >= busiest["rows"]:          # latest wins: longest contexts
            busiest.update(rows=rows, pools=new_pools, table=table,
                           lengths=lengths + 1, active=active)
        return logits, new_pools

    eng.step_fn = recording_step
    rng = np.random.default_rng(SEED)
    rids = [eng.submit(rng.integers(1, cfg.vocab, PROMPT_LENS[i % 2]).tolist(),
                       max_new=MAX_NEW) for i in range(N_REQUESTS)]
    try:
        t0 = time.perf_counter()
        eng.step()
        jax.block_until_ready(eng.pools)
        first_s = time.perf_counter() - t0
        steps = 1
        t0 = time.perf_counter()
        while not all(eng.result(r).state == "done" for r in rids):
            if steps >= MAX_STEPS:
                raise RuntimeError(f"requests not done after {steps} steps")
            eng.step()
            steps += 1
        jax.block_until_ready(eng.pools)
        serve_s = time.perf_counter() - t0
        stats = eng.stats()
    finally:
        eng.close()
    reqs = [eng.result(r) for r in rids]
    tokens = sum(len(r.out) for r in reqs)
    log(f"first engine step: {first_s:.2f} s (compiles the decode step and "
        f"both prefill shapes, prefills the first {MAX_BATCH} requests)")
    log(f"serving: {len(reqs)} requests, {steps} engine steps, {tokens} "
        f"tokens generated, {serve_s:.2f} s after the first step")
    log(f"pool stats: {json.dumps(stats)}")
    return reqs, stats, busiest


def check_requests(cfg, reqs, stats) -> None:
    for r in reqs:
        if r.state != "done" or len(r.out) != MAX_NEW:
            raise RuntimeError(f"request {r.rid}: state {r.state}, "
                               f"{len(r.out)}/{MAX_NEW} tokens")
        if not all(0 <= t < cfg.vocab for t in r.out):
            raise RuntimeError(f"request {r.rid}: token outside the vocab")
    for key in ("offloads", "preemptions", "fetches"):
        if stats[key] <= 0:
            raise RuntimeError(f"pool never under pressure: {key} = 0")


def check_against_dense(cfg, params, reqs) -> None:
    """Feeds each request's prompt and generated tokens through the dense
    prefill + decode path and checks every engine token is (up to REF_TOL)
    that path's greedy choice at its position."""
    prefill = jax.jit(functools.partial(T.prefill, cfg=cfg),
                      static_argnames="max_seq")
    decode = jax.jit(functools.partial(T.decode_step, cfg=cfg))
    shortfall, agree, n = 0.0, 0, 0
    for r in reqs:
        logits, cache = prefill(params, jnp.asarray([r.prompt], jnp.int32),
                                max_seq=MAX_PAGES * PAGE)
        rows = [logits[0, -1]]
        for tok in r.out[:-1]:
            logits, cache = decode(params, jnp.asarray([[tok]], jnp.int32),
                                   cache)
            rows.append(logits[0, -1])
        lg = jnp.stack(rows)                                # (MAX_NEW, V)
        out = jnp.asarray(r.out, jnp.int32)
        chosen = jnp.take_along_axis(lg, out[:, None], axis=1)[:, 0]
        shortfall = max(shortfall, float(jnp.max(lg.max(-1) - chosen)))
        agree += int(jnp.sum(lg.argmax(-1) == out))
        n += len(r.out)
        logit_std = float(jnp.std(lg))
    log(f"dense-path check: {agree}/{n} engine tokens are its argmax; "
        f"largest shortfall from its max logit {shortfall:.4f} "
        f"(tolerance {REF_TOL}; logit std {logit_std:.3f})")
    if shortfall > REF_TOL:
        raise RuntimeError(f"engine tokens disagree with the dense path: "
                           f"shortfall {shortfall} > {REF_TOL}")


def check_paged_kernel(cfg, busiest) -> None:
    """The Pallas kernel on the busiest decode step's pages, one layer."""
    pos = next(i for i, s in enumerate(cfg.block) if s.kind == "attn")
    layer = cfg.n_blocks - 1
    active = np.asarray(busiest["active"])
    k = busiest["pools"][pos]["k"][layer]
    v = busiest["pools"][pos]["v"][layer]
    table = busiest["table"][active]
    lengths = busiest["lengths"][active]
    q = jax.random.normal(jax.random.PRNGKey(SEED),
                          (int(active.sum()), cfg.n_heads, cfg.head_dim),
                          jnp.bfloat16)
    got = paged_attention(q, k, v, table, lengths, softcap=cfg.attn_softcap,
                          interpret=False)
    with jax.default_matmul_precision("highest"):
        want = paged_attention_ref(q.astype(jnp.float32), k, v, table,
                                   lengths, softcap=cfg.attn_softcap)
    diff = float(jnp.max(jnp.abs(got.astype(jnp.float32) - want)))
    tol = KERNEL_TOL * float(jnp.max(jnp.abs(v)))
    log(f"paged kernel vs reference (layer {layer}, {int(active.sum())} rows, "
        f"lengths {np.asarray(lengths).tolist()}): max abs diff {diff:.6f}, "
        f"tolerance {tol:.6f}")
    if not np.isfinite(diff) or diff > tol:
        raise RuntimeError(f"paged kernel differs from the reference: "
                           f"{diff} > {tol}")


def one_chip() -> None:
    cfg = get_config(ARCH)
    t0 = time.perf_counter()
    params = jax.jit(T.init_params, static_argnums=1)(
        jax.random.PRNGKey(SEED), cfg)
    jax.block_until_ready(params)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    log(f"model: {ARCH}, {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.dtype}, {n_params:,} parameters")
    log(f"set-up: {time.perf_counter() - t0:.2f} s (parameter init, compile "
        f"included)")
    reqs, stats, busiest = serve(cfg, params)
    check_requests(cfg, reqs, stats)
    check_paged_kernel(cfg, busiest)
    check_against_dense(cfg, params, reqs)


# ------------------------------------------------------------ four chips
def spread(tree, n_devices: int, what: str) -> None:
    """Fails unless ``tree`` is split over all ``n_devices`` devices."""
    leaves = jax.tree.leaves(tree)
    per_dev: dict = {}
    for leaf in leaves:
        for shard in leaf.addressable_shards:
            per_dev[shard.device] = (per_dev.get(shard.device, 0)
                                     + shard.data.nbytes)
    split = sum(leaf.sharding.shard_shape(leaf.shape) != leaf.shape
                for leaf in leaves)
    gb = sorted(b / 1e9 for b in per_dev.values())
    log(f"{what}: {len(leaves)} arrays, {split} split; GB per device "
        f"{[round(g, 3) for g in gb]}")
    if len(per_dev) != n_devices or split == 0 or gb[-1] > 2 * gb[0]:
        raise RuntimeError(f"{what} is not spread over {n_devices} devices")


@jax.jit
def fingerprint(tree):
    return [jnp.stack([jnp.sum(x.astype(jnp.float32)),
                       jnp.sum(jnp.square(x.astype(jnp.float32)))])
            for x in jax.tree.leaves(tree)]


def four_chips() -> None:
    n = len(jax.devices())
    if n != 4:
        raise RuntimeError(f"--four-chips needs 4 devices, JAX found {n}")
    cfg = get_config(ARCH)
    mesh = make_host_mesh()
    log(f"mesh: {dict(mesh.shape)}")
    t0 = time.perf_counter()
    params, opt, shardings = init_state(cfg, mesh, SEED)
    jax.block_until_ready((params, opt))
    log(f"sharded init: {time.perf_counter() - t0:.2f} s")
    spread((params, opt), n, "train state")

    data = SyntheticLM(cfg.vocab, TRAIN_SEQ, TRAIN_BATCH, seed=SEED)
    dspec = data_spec(mesh, TRAIN_BATCH)

    def batch(step):
        return make_global_batch(data.batch(step), mesh, P(dspec[0], None))

    def ce(cfg, mesh, params, batch):
        loss_fn = make_loss_fn(cfg, mesh)
        return float(jax.jit(lambda p, b: loss_fn(p, b)[1]["ce"])(params, batch))

    # the one-chip references, before step 0 donates the parameters
    one = jax.devices()[0]
    dropless = dataclasses.replace(
        cfg, moe_capacity_factor=cfg.moe_experts / cfg.moe_topk)
    t0 = time.perf_counter()
    params_one = jax.device_put(params, one)
    batch_one = jax.device_put(data.batch(0), one)
    ref_ce = ce(cfg, None, params_one, batch_one)
    ref_dropless = ce(dropless, None, params_one, batch_one)
    del params_one
    shard_dropless = ce(dropless, mesh, params, batch(0))
    log(f"forward passes, compile included ({time.perf_counter() - t0:.2f} s):"
        f" one chip ce {ref_ce:.6f}, without drops {ref_dropless:.6f} "
        f"(drops move it {abs(ref_ce - ref_dropless):.6f}); four chips "
        f"without drops {shard_dropless:.6f}")
    diff = abs(shard_dropless - ref_dropless)
    log(f"ce without drops, four chips vs one: {diff:.6f} (tolerance "
        f"{SHARD_TOL})")
    if diff > SHARD_TOL:
        raise RuntimeError(f"sharded forward differs from one chip: {diff}")

    step_fn = jax.jit(make_train_step(cfg, mesh, warmup=1, total_steps=100),
                      donate_argnums=(0, 1))
    ces = []
    for s in range(2):
        t0 = time.perf_counter()
        params, opt, metrics = step_fn(params, opt, batch(s))
        ces.append(float(metrics["ce"]))
        log(f"step {s}: ce {ces[-1]:.6f}, grad norm "
            f"{float(metrics['grad_norm']):.4f}, "
            f"{time.perf_counter() - t0:.2f} s")
    spread((params, opt), n, "train state after 2 steps")
    diff = abs(ces[0] - ref_ce)
    log(f"step 0 ce, four chips vs one: |{ces[0]:.6f} - {ref_ce:.6f}| = "
        f"{diff:.6f} (tolerance {CE_TOL})")
    if diff > CE_TOL:
        raise RuntimeError(f"sharded loss differs from one chip: {diff}")

    ckpt_dir = ROOT / ".smoke_ckpt"
    shutil.rmtree(ckpt_dir, ignore_errors=True)
    ckpt = CheckpointManager(ckpt_dir)
    try:
        saved = [np.asarray(f) for f in fingerprint((params, opt))]
        t0 = time.perf_counter()
        ckpt.save_async(1, (params, opt))
        log(f"checkpoint of step 1: save_async returned after "
            f"{time.perf_counter() - t0:.2f} s")
        t0 = time.perf_counter()
        params, opt, metrics = step_fn(params, opt, batch(2))
        ce_cont = float(metrics["ce"])
        log(f"step 2 (while the checkpoint writes): ce {ce_cont:.6f}, "
            f"{time.perf_counter() - t0:.2f} s")
        t0 = time.perf_counter()
        if not ckpt.drain(600):
            raise RuntimeError("checkpoint writes did not finish")
        log(f"checkpoint drained {time.perf_counter() - t0:.2f} s later: "
            f"{json.dumps(ckpt.stats)}")
        t0 = time.perf_counter()
        step, (params, opt) = ckpt.restore((params, opt),
                                           shardings=shardings)
        jax.block_until_ready((params, opt))
        log(f"restored step {step} in {time.perf_counter() - t0:.2f} s")
    finally:
        ckpt.close()
        shutil.rmtree(ckpt_dir, ignore_errors=True)
    spread((params, opt), n, "restored train state")
    restored = [np.asarray(f) for f in fingerprint((params, opt))]
    if step != 1 or any((a != b).any() for a, b in zip(saved, restored)):
        raise RuntimeError("restored state differs from the saved one")
    params, opt, metrics = step_fn(params, opt, batch(2))
    ce_resumed = float(metrics["ce"])
    log(f"step 2 after resume: ce {ce_resumed:.6f} (without the restart "
        f"{ce_cont:.6f})")
    # the same program on the same state; the bound only leaves room for a
    # reduction order the runtime may choose anew
    if abs(ce_resumed - ce_cont) > 1e-5 * abs(ce_cont):
        raise RuntimeError("resumed run diverges from the uninterrupted one")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded training phase on 4 chips")
    args = ap.parse_args(argv)
    faulthandler.dump_traceback_later(WATCHDOG_S, exit=True)
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU; JAX found {dev.platform}")
    log(f"device: {dev.device_kind} x {len(devices)}")
    log(f"compile cache: {use_compile_cache()}")
    errors = ThreadErrors()
    if args.four_chips:
        four_chips()
    else:
        one_chip()
    errors.check("run")
    for d in devices:
        log(f"peak_bytes_in_use {d}: {d.memory_stats()['peak_bytes_in_use']:,}")
    faulthandler.cancel_dump_traceback_later()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
