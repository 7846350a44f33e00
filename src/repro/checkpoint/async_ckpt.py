"""Async sharded checkpointing built on the paper's machinery.

The mapping (DESIGN.md §2): checkpoint *chunks* are the dirty pages, storage
targets are the SSDs, and the training loop is the application whose writes
must never block.

  * every ``save_async(step, tree)`` marks all (changed) chunks dirty and
    enqueues LOW-priority writes on per-target dual queues (``core.io_queues``)
    — the train loop continues immediately (paper: flush requests fill the
    long queues);
  * a queued write is discarded at the queue head iff a NEWER save for the
    same chunk has been enqueued (paper §3.3.2 staleness: the page was
    re-dirtied and a fresher flush exists — writing the old version is
    wasted bandwidth);
  * ``restore`` reads run HIGH priority and overtake any backlog of writes
    (paper §3.2: reserved slots keep reads fast under write pressure);
  * a per-target budget (``max_inflight``) plus deep software queues absorb
    stragglers: one slow target (overloaded NFS shard, throttled disk) delays
    only its own chunks — exactly the unsynchronized-GC scenario.

A checkpoint step is COMMITTED by writing ``manifest-<step>.json`` after its
last chunk lands; superseded steps simply never commit (their chunks were
discarded), so restore always sees a consistent, complete step. Chunk files
are content-addressed by (key, step) so elastic restore to a different mesh
just re-shards the global arrays.
"""
from __future__ import annotations

import json
import threading
import time
from pathlib import Path
from typing import Any, Callable

import numpy as np

import jax

from repro.core.io_queues import HIGH, LOW, IOExecutor, IORequest


def flatten_with_paths(tree: Any) -> dict[str, Any]:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    out = {}
    for path, leaf in flat:
        key = "/".join(
            str(getattr(k, "key", getattr(k, "idx", getattr(k, "name", k))))
            for k in path)
        out[key] = leaf
    return out


class CheckpointManager:
    """Async checkpoint writer/reader over ``n_targets`` storage targets."""

    def __init__(self, directory: str | Path, *, n_targets: int = 4,
                 max_inflight: int = 2, reserved: int = 1, keep: int = 2,
                 write_delay: float = 0.0):
        self.dir = Path(directory)
        self.dir.mkdir(parents=True, exist_ok=True)
        self.n_targets = n_targets
        self.keep = keep
        # reserved slots must leave room for LOW-priority writes to flow
        reserved = max(0, min(reserved, max_inflight - 1))
        self._write_delay = write_delay          # fault-injection for tests
        self._lock = threading.Lock()
        self._latest_enqueued: dict[str, int] = {}   # key -> newest step queued
        self._remaining: dict[int, int] = {}         # step -> chunks not landed
        self._treedef = None
        self._committed: list[int] = []
        self.stats = {"written": 0, "discarded_stale": 0, "bytes": 0,
                      "saves": 0, "restores": 0}
        self._exec = IOExecutor(n_targets, self._do_io,
                                max_inflight=max_inflight, reserved=reserved)

    # ------------------------------------------------------------------ io
    def _chunk_path(self, key: str, step: int) -> Path:
        safe = key.replace("/", "__")
        return self.dir / f"{safe}-{step}.npy"

    def _do_io(self, target: int, payload: dict) -> None:
        if payload["op"] == "write":
            if self._write_delay:
                time.sleep(self._write_delay)
            np.save(self._chunk_path(payload["key"], payload["step"]),
                    payload["data"], allow_pickle=False)
            with self._lock:
                self.stats["written"] += 1
                self.stats["bytes"] += payload["data"].nbytes
                step = payload["step"]
                if step in self._remaining:
                    self._remaining[step] -= 1
                    if self._remaining[step] == 0:
                        self._commit(step)
        else:                                     # read (HIGH priority)
            payload["out"][payload["key"]] = np.load(
                self._chunk_path(payload["key"], payload["step"]))
            payload["done"].release()

    def _commit(self, step: int) -> None:
        """Called with lock held: all chunks of ``step`` are durable."""
        manifest = {"step": step,
                    "keys": sorted(k for k, s in self._latest_enqueued.items())}
        tmp = self.dir / f".manifest-{step}.tmp"
        tmp.write_text(json.dumps(manifest))
        tmp.rename(self.dir / f"manifest-{step}.json")
        self._committed.append(step)
        del self._remaining[step]
        # retention: drop chunk files of old committed steps
        for old in self._committed[:-self.keep]:
            for f in self.dir.glob(f"*-{old}.npy"):
                f.unlink(missing_ok=True)
            (self.dir / f"manifest-{old}.json").unlink(missing_ok=True)
        self._committed = self._committed[-self.keep:]

    # --------------------------------------------------------------- save
    def save_async(self, step: int, tree: Any,
                   changed: set[str] | None = None) -> None:
        """Enqueue a checkpoint of ``tree`` at ``step``; returns immediately.

        ``changed`` optionally names the dirty chunks (default: all) — the
        dirty-chunk filter for e.g. frozen towers or unchanged EMA copies.
        """
        with self._lock:
            if step in self._remaining or step in self._committed:
                return                       # duplicate save for this step
        host = {k: np.asarray(v) for k, v in flatten_with_paths(tree).items()
                if changed is None or k in changed}
        with self._lock:
            self.stats["saves"] += 1
            self._remaining[step] = len(host)
            for k in host:
                self._latest_enqueued[k] = step

        def make_stale(key: str, s: int) -> Callable[[Any], bool]:
            def is_stale(_payload) -> bool:
                with self._lock:
                    return self._latest_enqueued.get(key, s) > s
            return is_stale

        def on_discard(payload) -> None:
            with self._lock:
                self.stats["discarded_stale"] += 1
                step_d = payload["step"]
                if step_d in self._remaining:
                    self._remaining[step_d] -= 1
                    # a superseded step never commits; forget it when drained
                    if self._remaining[step_d] <= 0:
                        del self._remaining[step_d]

        for i, (k, v) in enumerate(sorted(host.items())):
            self._exec.submit(
                self._target_of(k),
                IORequest(payload={"op": "write", "key": k, "step": step,
                                   "data": v},
                          priority=LOW,
                          is_stale=make_stale(k, step),
                          on_discard=on_discard))

    def _target_of(self, key: str) -> int:
        return hash(key) % self.n_targets

    # ------------------------------------------------------------- restore
    def latest_step(self) -> int | None:
        steps = sorted(int(p.stem.split("-")[1])
                       for p in self.dir.glob("manifest-*.json"))
        return steps[-1] if steps else None

    def restore(self, like: Any, step: int | None = None,
                shardings: Any = None) -> tuple[int, Any]:
        """Blocking restore into the structure of ``like`` (a pytree or tree
        of ShapeDtypeStructs). Reads are HIGH priority: they overtake any
        write backlog. ``shardings`` optionally re-shards onto a (different)
        mesh — elastic resume."""
        if step is None:
            step = self.latest_step()
        if step is None:
            raise FileNotFoundError(f"no committed checkpoint in {self.dir}")
        manifest = json.loads((self.dir / f"manifest-{step}.json").read_text())
        out: dict[str, np.ndarray] = {}
        sem = threading.Semaphore(0)
        for k in manifest["keys"]:
            self._exec.submit(
                self._target_of(k),
                IORequest(payload={"op": "read", "key": k, "step": step,
                                   "out": out, "done": sem},
                          priority=HIGH))
        for _ in manifest["keys"]:
            sem.acquire()
        self.stats["restores"] += 1

        leaves_like = flatten_with_paths(like)
        # np.save keeps bfloat16 only as raw 2-byte voids: the template's
        # dtype gives the bytes their type back
        ordered = [out[k].view(leaf.dtype) for k, leaf in leaves_like.items()]
        treedef = jax.tree_util.tree_structure(like)
        if shardings is not None:
            sh_leaves = jax.tree_util.tree_leaves(shardings)
            arrs = [jax.device_put(a, s) for a, s in zip(ordered, sh_leaves)]
        else:
            arrs = [jax.numpy.asarray(a) for a in ordered]
        return step, jax.tree_util.tree_unflatten(treedef, arrs)

    # ------------------------------------------------------------- control
    def barrier(self, timeout: float = 120.0) -> bool:
        """Write barrier (paper §3.4): returns once every enqueued write has
        either landed or been discarded stale — everything submitted before
        the barrier is durable (or superseded) before anything after it.
        The paper's caveat holds: frequent barriers forfeit the flusher's
        reordering freedom, so use them at consistency points only."""
        return self._exec.drain(timeout)

    def wait_for_commit(self, step: int, timeout: float = 60.0) -> bool:
        deadline = time.monotonic() + timeout
        path = self.dir / f"manifest-{step}.json"
        while time.monotonic() < deadline:
            if path.exists():
                return True
            time.sleep(0.01)
        return False

    def drain(self, timeout: float = 120.0) -> bool:
        return self._exec.drain(timeout)

    def close(self) -> None:
        self._exec.shutdown()
