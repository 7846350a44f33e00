"""Serving driver: the program's ``ServeEngine`` under the cell's traffic.

Set-up: weights from the seed (one jitted call), the engine at the cell's
geometry, one request of every prompt length the traffic uses (so each
prefill shape is compiled or loaded from the cache), then the cell's own
traffic until the pool is in its steady state. Then the window opens for
``--seconds``: the harness submits each request when it is due (open loop)
or keeps the waiting queue topped up (backlog), and calls ``eng.step()``.
Then the program's state is freed and a sample of the requests that the
window finished is checked against the plain reference.

The harness's host spans (``jax.profiler.TraceAnnotation``, only in a traced
run) wrap its own calls into each layer: ``eng.step``, ``eng.submit``, and,
on the instance, ``eng.step_fn`` (decode), ``eng._prefill_row`` (admission of
a new request: prefill and its page writes), ``eng._preempt``, and the pool's
``offload_now``, ``offload_now_evicted`` and ``fetch``. Their host times are
added up in every run.
"""
from __future__ import annotations

import contextlib
import gc
import json
import time
from collections import defaultdict, deque

import numpy as np

from chipbench import bench, weights, work
from chipbench.model import model_config
from chipbench.reference import check
from chipbench.traffic import generate

# requests made for a run: far more than any window serves; the schedule is
# made lazily, block by block
SCHEDULE_CHUNK = 64
POPULATION_START = 10 ** 7      # warm-up requests: blocks no schedule reaches


class Spans:
    """Host time in each kind of harness span, plus the profiler's
    annotation when the run is traced."""

    def __init__(self, traced: bool):
        import jax
        self._ann = jax.profiler.TraceAnnotation if traced else None
        self.seconds = defaultdict(float)
        self.count = defaultdict(int)
        self.recording = False

    @contextlib.contextmanager
    def __call__(self, name: str):
        ann = self._ann(name) if self._ann else contextlib.nullcontext()
        t0 = time.perf_counter()
        with ann:
            yield
        if self.recording:
            self.seconds[name] += time.perf_counter() - t0
            self.count[name] += 1

    def wrap(self, obj, attr: str, name: str, before=None, after=None):
        inner = getattr(obj, attr)

        def wrapped(*args, **kwargs):
            if before:
                before(*args, **kwargs)
            with self(name):
                out = inner(*args, **kwargs)
            if after:
                after(*args, **kwargs)
            return out

        setattr(obj, attr, wrapped)


class Tracker:
    """When each token of each request reached the host."""

    def __init__(self):
        self.due: dict[int, float] = {}          # rid -> due time (host clock)
        self.token_times: dict[int, list] = defaultdict(list)
        self.seen: dict[int, int] = {}
        self.in_flight: set[int] = set()
        self.preempted: set[int] = set()
        self.first_token: dict[int, float] = {}

    def submitted(self, rid: int, due: float) -> None:
        self.due[rid] = due
        self.seen[rid] = 0
        self.in_flight.add(rid)

    def after_step(self, eng, t: float) -> None:
        done = []
        for rid in self.in_flight:
            req = eng.result(rid)
            n = len(req.out)
            if n > self.seen[rid]:
                self.token_times[rid].extend([t] * (n - self.seen[rid]))
                self.seen[rid] = n
            if req.state == "done":
                done.append(rid)
        for rid in done:
            self.in_flight.discard(rid)


def _percentile(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


def _warm_lengths(traffic: dict) -> list[int]:
    prompts, _, _ = generate.block_sizes(traffic)
    return sorted(set(int(p) for p in prompts))


def run(ctx: bench.Context) -> dict:
    import jax
    from repro.models import transformer as T
    from repro.serving import ServeEngine

    cell, traffic = ctx.cell.cell, ctx.cell.traffic
    model = ctx.cell.config["model"]
    cfg = model_config(model)
    eng_args = dict(cell["engine"])
    backlog = traffic["arrivals"] == "backlog"
    spans = Spans(ctx.trace)

    params, leaves = weights.program_params(ctx.seed, cfg, T.init_params)
    jax.block_until_ready(params)
    eng = ServeEngine(cfg, params, use_flusher=True, **eng_args)
    tracker = Tracker()
    # the work each call requires (chipbench/work.py), counted in the window
    steps = {"decode": 0, "per_step": [], "prefills": 0, "prefill_flops": 0.0}

    def on_decode(*_args, **_kw):
        if not spans.recording:
            return
        rows = [i for i, r in enumerate(eng._rows) if r is not None]
        context = int(sum(int(eng._lengths[i]) + 1 for i in rows))
        steps["decode"] += 1
        w = work.decode_step(model, len(rows), context)
        steps["per_step"].append((w.flops, w.bytes))

    def on_prefill(req, tokens):
        tracker.first_token.setdefault(req.rid, time.perf_counter())
        if spans.recording:
            steps["prefills"] += 1
            steps["prefill_flops"] += work.prefill(model, len(tokens)).flops

    spans.wrap(eng, "step_fn", "bench.decode", before=on_decode)
    spans.wrap(eng, "_prefill_row", "bench.prefill", after=on_prefill)
    spans.wrap(eng, "_preempt", "bench.preempt",
               before=lambda req: tracker.preempted.add(req.rid))
    spans.wrap(eng.pool, "offload_now", "bench.offload_now")
    spans.wrap(eng.pool, "offload_now_evicted", "bench.offload_evicted")
    spans.wrap(eng.pool, "fetch", "bench.fetch")

    def submit(r: generate.Request, due: float) -> int:
        with spans("bench.submit"):
            rid = eng.submit(list(r.prompt), max_new=r.max_new)
        tracker.submitted(rid, due)
        return rid

    def step() -> None:
        with spans("bench.step"):
            eng.step()
        tracker.after_step(eng, time.perf_counter())

    def busy() -> bool:
        return bool(eng._waiting) or any(r is not None for r in eng._rows)

    # ---- every prefill shape the traffic uses, one request each
    vocab = cfg.vocab
    warm_rng = generate.rng_for(ctx.seed, 3_000_000)
    for length in _warm_lengths(traffic):
        rid = eng.submit(warm_rng.integers(1, vocab, length).tolist(), max_new=2)
        while eng.result(rid).state != "done":
            eng.step()

    # ---- the schedule, and the warm-up that brings the pool to its steady state
    warm = cell["warmup"]
    sched: deque = deque()
    made = [0]

    def more() -> None:
        batch = generate.requests(traffic, ctx.seed, vocab, SCHEDULE_CHUNK,
                                  start_index=made[0])
        made[0] += SCHEDULE_CHUNK
        sched.extend(batch)

    pop_rng = generate.rng_for(ctx.seed, 4_000_000)
    n_pop = int(round(warm.get("population_s", 0) * traffic.get("rate_per_s", 0)))
    population = generate.requests(traffic, ctx.seed, vocab, n_pop,
                                   start_index=POPULATION_START)
    t_sched = time.perf_counter()
    for i, r in enumerate(population):
        # requests already part-way through their output when the schedule
        # starts, so that completions are spread as in the steady state
        left = max(1, int(round(r.max_new * (i + pop_rng.random()) / len(population))))
        submit(generate.Request(r.index, 0.0, r.prompt, left), t_sched)
    t_open = t_sched + warm["seconds"]
    t_close = t_open + ctx.seconds
    top_up = int(traffic.get("backlog_depth", 2) * eng_args["max_batch"])

    window_rids: list[int] = []
    queue: dict = {}
    compiles_at_open = None
    window_stats = {}
    trace_dir = None
    profiling = False
    window_span = None
    while True:
        t = time.perf_counter()
        if compiles_at_open is None and t >= t_open:
            compiles_at_open = ctx.compiles.count if ctx.compiles else 0
            window_stats["open"] = eng.stats()
            queue["open"] = len(eng._waiting)
            queue["rows_open"] = sum(r is not None for r in eng._rows)
            spans.recording = True
            if ctx.trace:
                trace_dir = ctx.out_dir / "trace"
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 1
                jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
                profiling = True
                window_span = jax.profiler.TraceAnnotation("bench.window")
                window_span.__enter__()
        if t >= t_close:
            if profiling:
                window_span.__exit__(None, None, None)
                jax.profiler.stop_trace()
            window_stats["close"] = eng.stats()
            queue["close"] = len(eng._waiting)
            queue["rows_close"] = sum(r is not None for r in eng._rows)
            window_stats["compiles"] = (ctx.compiles.count if ctx.compiles else 0) - compiles_at_open
            window_stats["spans_s"] = dict(spans.seconds)
            window_stats["spans_n"] = dict(spans.count)
            window_stats["steps"] = dict(steps)
            break
        # arrivals
        if backlog:
            while len(eng._waiting) < top_up:
                if not sched:
                    more()
                submit(sched.popleft(), t)
        else:
            if not sched:
                more()
            while sched and t_sched + sched[0].due_s <= t:
                r = sched.popleft()
                due = t_sched + r.due_s
                rid = submit(r, due)
                if t_open <= due < t_close:
                    window_rids.append(rid)
                if not sched:
                    more()
        if busy():
            step()
        else:
            wake = min([t_sched + sched[0].due_s] + [b for b in (t_open, t_close) if b > t])
            time.sleep(max(0.0, wake - time.perf_counter()))

    # ---- what the window measured
    window_s = t_close - t_open
    if backlog:       # the requests the window admitted
        window_rids = [r for r, tt in tracker.first_token.items()
                       if t_open <= tt < t_close]
    ttft = [tracker.first_token[r] - tracker.due[r] for r in window_rids
            if tracker.first_token.get(r, t_close) < t_close]
    gaps_ms, tokens = [], 0
    for rid, times in tracker.token_times.items():
        first = tracker.first_token.get(rid)
        prev = first
        for i, tt in enumerate(times):
            if i == 0:
                prev = first if first is not None else tt
                if t_open <= prev < t_close:
                    tokens += 1
                continue
            if t_open <= tt < t_close:
                tokens += 1
                gaps_ms.append((tt - prev) * 1e3)
            prev = tt
    limits = cell.get("slo", {})
    slo_met = 0
    for rid in window_rids:
        if rid not in tracker.first_token:
            continue
        ok = tracker.first_token[rid] - tracker.due[rid] <= limits.get("ttft_s", np.inf)
        times = tracker.token_times[rid]
        if len(times) > 1:
            tpot_ms = (times[-1] - tracker.first_token[rid]) * 1e3 / (len(times) - 1)
            ok = ok and tpot_ms <= limits.get("tpot_ms", np.inf)
        slo_met += ok
    e2e = {"tokens_per_s": tokens / window_s}
    if not backlog:
        if not ttft or not gaps_ms:
            raise bench.BenchError("the window served no request")
        for q in (50, 75, 90):
            e2e[f"ttft_p{q}_s"] = _percentile(ttft, q)
        for q in (50, 90, 95, 99):
            e2e[f"itl_p{q}_ms"] = _percentile(gaps_ms, q)
    counters = {k: window_stats["close"][k] - window_stats["open"][k]
                for k in window_stats["close"]}
    ctx.say(f"window: {window_s:.3f} s, {len(window_rids)} requests "
            f"{'admitted' if backlog else 'due'}, {tokens} tokens, "
            f"{window_stats['steps']['decode']} decode steps, "
            f"{window_stats['steps']['prefills']} prefills; pool counters "
            f"{counters}")
    ctx.say(f"compilations in the window: {window_stats['compiles']}")
    if not backlog:
        ctx.say(f"over {len(ttft)} first tokens and {len(gaps_ms)} gaps: "
                + json.dumps({k: v for k, v in e2e.items() if k != "tokens_per_s"}))

    # ---- the sample to check, chosen from the seed; the device's peak first
    device = bench.device_info(jax.devices())
    # requests the window finished: their last token reached the host in it
    finished = [rid for rid, times in tracker.token_times.items()
                if eng.result(rid).state == "done" and t_open <= times[-1] < t_close]
    sample = check.choose_sample(
        finished, lambda rid: len(eng.result(rid).prompt) + len(eng.result(rid).out),
        tracker.preempted, generate.rng_for(ctx.seed, 5_000_000),
        lambda rid: len(eng.result(rid).out), **cell["check"]["sample"])
    served = [(list(eng.result(r).prompt), list(eng.result(r).out), r in tracker.preempted)
              for r in sample]
    eng.close()
    del eng, params
    gc.collect()
    checks, readings = [], {}
    if ctx.check:
        checks, readings, check_info = check.serve_checks(
            ctx.seed, leaves, cfg, model, served, cell["check"]["limits"],
            control=ctx.control)
        ctx.say(check_info)

    return {
        "setup_s": t_open - ctx.t_start,
        "window_s": window_s,
        "e2e": e2e,
        "counters": counters,
        "spans_s": window_stats["spans_s"],
        "spans_n": window_stats["spans_n"],
        "steps": window_stats["steps"],
        "compiles_in_window": window_stats["compiles"],
        "requests": {"due": len(window_rids), "slo_met": slo_met},
        "trace_dir": trace_dir,
        "device": device,
        "checks": checks,
        "served": served,
        "readings": readings,
        "leaves": leaves,
        "queue": queue,
        # requests due (open loop) or admitted (backlog) in the window; a
        # request is late or on time, none is refused
        "attempted": len(window_rids),
        "failed": 0,
    }
