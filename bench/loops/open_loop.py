"""Serving loop: an open loop of requests through ``repro.serve.Engine``.

Set-up builds the model and its seeded weights, the engine with the pool
the configuration states, and runs one request at every prefill length
bucket the mix can send, so that the window compiles nothing. It ends by
freezing what it made out of the garbage collector's reach, as a
long-running server would, so that a full collection in the window walks
only what the window made (its pauses are counted apart). Load starts
``lead_s`` before the window. Each request is submitted once it is due and
timed from when it was due; each output token is stamped when the
``Engine.step()`` that produced it returns (the step ends in a blocking
sample). Every request due in the window is followed to its last token,
after the window if need be, while later arrivals keep the load up.

With ``--trace 1`` the profiler traces the last ``trace_s`` seconds of the
window. Stopping the profiler stalls the host for seconds, so it stops
only once the window has closed, and the engine's host-side per-layer
numbers are read over the part of the window before the trace began.

Once the window's requests are done and the peak memory is read, the
engine is freed and the plain reference checks a sample of the served
requests, drawn from the seed with the longest among them.
"""
from __future__ import annotations

import gc
import time
from collections import deque

import numpy as np

import traffic as T
from common import np_rng, percentile, span

DRAIN_LIMIT_S = 120.0


def _engine(c: dict, t: dict, lm, params, recorder):
    from repro.serve import Engine, EngineConfig, PoolConfig
    from repro.sharding import ShardPlan
    sv = c["serve"]
    page = sv["page_size"]
    max_len = t["prompt_len"]["max"] + t["output_len"]["max"]
    pcfg = PoolConfig(num_slots=t["slots"], page_size=page,
                      pages_per_slot=-(-max_len // page),
                      num_pages=sv["num_pages"],
                      quantized=sv["kv_dtype"] == "int8", bits=sv["kv_bits"])
    ecfg = EngineConfig(pool=pcfg, fused_attention=sv["fused_attention"],
                        prefill_bucket=sv["prefill_bucket"],
                        prefix_cache=sv["prefix_cache"])
    return Engine(lm, params, ecfg, ShardPlan(mesh=None), trace=recorder)


def _warm(eng, c: dict, t: dict, seed: int) -> int:
    """One request at each prefill bucket the mix's prompt lengths reach:
    compiles (or loads) every prefill, pool-write, decode and sampling
    program the window will run."""
    b = c["serve"]["prefill_bucket"]
    lo = t["prompt_len"]["min"] + (-t["prompt_len"]["min"]) % b
    hi = t["prompt_len"]["max"] + (-t["prompt_len"]["max"]) % b
    rng = np_rng(seed, 3)
    n = 0
    for length in range(lo, hi + 1, b):
        eng.submit(rng.integers(0, c["vocab_size"], length).tolist(),
                   max_new_tokens=2)
        n += 1
    eng.run()
    return n


class _Stamps:
    """Per-request token times, read off the engine after each step."""

    def __init__(self):
        self.times: dict[int, list[float]] = {}
        self.plen: dict[int, int] = {}
        self.open: set[int] = set()

    def add(self, rid: int, plen: int) -> None:
        self.times[rid] = []
        self.plen[rid] = plen
        self.open.add(rid)

    def read(self, eng, now: float) -> list[int]:
        """Stamp new tokens; returns the keys each decode token attended
        (prompt length + its index among the generated tokens)."""
        counts = {s.req.rid: len(s.generated) for s in eng.sched.slots if s}
        keys = []
        for rid in list(self.open):
            n = counts.get(rid)
            if n is None:
                done = eng._completions.get(rid)
                if done is None:
                    continue            # still queued
                n = len(done.tokens)
                self.open.discard(rid)
            ts = self.times[rid]
            for j in range(len(ts), n):
                ts.append(now)
                if j >= 1:
                    keys.append(self.plen[rid] + j)
        return keys


def setup(ctx):
    """The model, its seeded weights and a warm engine."""
    import program
    from repro.obs import TraceRecorder
    c, t = ctx.config, ctx.traffic
    lm = program.build(c)
    params = program.params(c, lm, ctx.seed)
    recorder = TraceRecorder(capacity=1 << 20)
    eng = _engine(c, t, lm, params, recorder)
    warmed = _warm(eng, c, t, ctx.seed)
    recorder.clear()
    gc.collect()
    gc.freeze()
    return eng, recorder, warmed


class _GcPauses:
    """Pauses of the garbage collector, by the host clock, from ``start()``
    to ``stop()``."""

    def __init__(self):
        self.pauses: list[tuple[float, float]] = []     # (end, seconds)
        self._t0 = 0.0

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.monotonic()
        else:
            now = time.monotonic()
            self.pauses.append((now, now - self._t0))

    def start(self) -> "_GcPauses":
        gc.callbacks.append(self)
        return self

    def stop(self) -> None:
        gc.callbacks.remove(self)


def serve(eng, reqs, t: dict, seconds: float, ctx=None) -> dict:
    """Offer ``reqs`` on their schedule: ``lead_s`` of load, then the
    window, then until every request due in the window has its last
    token. With ``ctx.trace`` the last ``trace_s`` seconds of the window
    are traced."""
    from repro.launch.compile_cache import compile_stats
    stamps = _Stamps()
    pending = deque(reqs)
    rid_req: dict = {}
    lateness, steps, queue = [], [], []
    start = time.monotonic()
    win_lo = start + t["lead_s"]
    win_hi = win_lo + seconds
    stats0 = None
    tracing = ctx is not None and ctx.trace
    trace_at = [None, None]
    trace_steps = [None, None]
    total_pages = eng.pcfg.total_pages
    gc_pauses = _GcPauses().start()
    while True:
        now = time.monotonic()
        if stats0 is None and now >= win_lo:
            stats0 = compile_stats()
        if tracing and trace_at[0] is None and now >= win_hi - t["trace_s"]:
            trace_at[0] = now
            trace_steps[0] = len(steps)
            ctx.start_trace()
        if trace_at[0] is not None and trace_at[1] is None and now >= win_hi:
            trace_at[1] = now
            trace_steps[1] = len(steps)
            ctx.stop_trace(now)
        while pending and start + pending[0].due <= now:
            r = pending.popleft()
            with span("engine.submit"):
                rid = eng.submit(r.prompt, max_new_tokens=r.max_new)
            rid_req[rid] = r
            stamps.add(rid, len(r.prompt))
            lateness.append(now - (start + r.due))
        waiting = sum(1 for rid in stamps.open if not stamps.times[rid])
        queue.append((now - start, waiting))
        if now >= win_hi and stats0 is not None \
                and not any(rid_req[rid].in_window for rid in stamps.open) \
                and not any(r.in_window for r in pending):
            break
        if now > win_hi + DRAIN_LIMIT_S:
            break
        if eng.sched.has_work():
            with span("engine.step"):
                eng.step()
            t1 = time.monotonic()
            steps.append({"t0": now, "t1": t1,
                          "decode_keys": stamps.read(eng, t1),
                          "pages": total_pages - eng.sched.alloc.free_pages})
        else:
            nxt = start + pending[0].due if pending else now + 0.01
            if stats0 is None:
                nxt = min(nxt, win_lo)
            if tracing and trace_at[1] is None:
                nxt = min(nxt, win_hi - t["trace_s"] if trace_at[0] is None
                          else win_hi)
            with span("wait"):
                time.sleep(max(0.0, nxt - time.monotonic()))
    if trace_at[0] is not None and trace_at[1] is None:
        trace_at[1] = time.monotonic()
        trace_steps[1] = len(steps)
        ctx.stop_trace(trace_at[1])
    gc_pauses.stop()
    stats1 = compile_stats()
    return {"stamps": stamps, "gc_pauses": gc_pauses.pauses, "rid_req": rid_req, "lateness": lateness,
            "steps": steps, "queue": queue, "start": start,
            "window": [win_lo, win_hi], "end": time.monotonic(),
            "trace_steps": trace_steps,
            "host_window": [win_lo, trace_at[0] or win_hi],
            "compiles": stats1["compiles"] - stats0["compiles"],
            "compile_s": stats1["compile_s"] - stats0["compile_s"]}


def latencies(out: dict):
    """(ttft, itl, failed, done_rids) over the requests due in the window:
    TTFT from when each was due; every gap between its tokens."""
    ttft, itl, failed, done = [], [], 0, []
    for rid, r in out["rid_req"].items():
        if not r.in_window:
            continue
        ts = out["stamps"].times[rid]
        if len(ts) < r.max_new:
            failed += 1
            continue
        done.append(rid)
        ttft.append(ts[0] - (out["start"] + r.due))
        itl.extend(b - a for a, b in zip(ts, ts[1:]))
    return ttft, itl, failed, done


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp

    c, t = ctx.config, ctx.traffic
    eng, recorder, warmed = setup(ctx)
    reqs = T.open_loop(t, ctx.seed, ctx.seconds, c["vocab_size"])
    window = [r for r in reqs if r.in_window]
    out = serve(eng, reqs, t, ctx.seconds, ctx)
    device = ctx.device_record()
    setup_s = out["window"][0] - ctx.t_process
    ttft, itl, failed, done_rids = latencies(out)
    rid_req = out["rid_req"]
    sent = {r.index for r in rid_req.values()}
    failed += sum(1 for r in window if r.index not in sent)

    events = [(e.ts, e.kind, e.fields) for e in recorder.events()
              if e.kind in ("submit", "admit", "prefill", "preempt")]
    pool_leaf = jax.tree.leaves(eng.pool["data"])[0]
    records = {
        "steps": out["steps"], "trace_steps": out["trace_steps"],
        "window": out["window"], "end": out["end"],
        "host_window": out["host_window"],
        "engine_events": events,
        "window_rids": [rid for rid, r in rid_req.items() if r.in_window],
        "num_slots": t["slots"], "pool_itemsize": pool_leaf.dtype.itemsize,
        "act_itemsize": jnp.dtype(c["torch_dtype"]).itemsize,
    }
    steps = out["steps"]
    lo, hi = out["window"]
    in_win = [s for s in steps if lo <= s["t0"] < hi]
    gc_win = [d for end, d in out["gc_pauses"] if lo <= end < hi]
    notes = {
        "requests_in_window": len(window), "requests_sent": len(rid_req),
        "warmup_requests": warmed,
        "compiles_in_window": out["compiles"],
        "compile_s_in_window": out["compile_s"],
        "submit_lateness_ms_p95": 1e3 * percentile(out["lateness"], 95),
        "submit_lateness_ms_max": 1e3 * max(out["lateness"]),
        "drain_s": out["end"] - out["window"][1],
        "preemptions": sum(1 for e in events if e[1] == "preempt"),
        "steps": len(steps),
        "mean_decode_batch": float(np.mean([len(s["decode_keys"])
                                            for s in steps])),
        "ttft_p50_ms": 1e3 * percentile(ttft, 50),
        "itl_p50_ms": 1e3 * percentile(itl, 50),
        "itl_samples": len(itl),
        "step_ms_max": 1e3 * max(s["t1"] - s["t0"] for s in in_win),
        "gc_pauses_in_window": len(gc_win),
        "gc_pause_ms_max": 1e3 * max(gc_win, default=0.0),
        "pool_pages": eng.pcfg.total_pages,
        "pool_pages_live_peak": max(s["pages"] for s in in_win),
    }

    # the served answers to check, drawn from the seed, longest first
    rng = np_rng(ctx.seed, 4)
    order = sorted(done_rids, key=lambda r: -(len(rid_req[r].prompt)
                                              + rid_req[r].max_new))
    sample = order[:1] + list(rng.permutation(order[1:]))
    picked, served = [], 0
    for rid in sample:
        if served >= t["check_tokens"]:
            break
        comp = eng._completions[rid]
        picked.append((comp.prompt, comp.tokens))
        served += len(comp.tokens)
    del eng
    gc.unfreeze()               # the engine's cycles hold the device buffers
    gc.collect()
    checks = ctx.check_served(picked)
    notes["checked_requests"] = len(picked)
    notes["checked_tokens"] = served

    metrics = {"setup_s": setup_s,
               "ttft_p95_ms": 1e3 * percentile(ttft, 95),
               "itl_p95_ms": 1e3 * percentile(itl, 95)}
    return {"metrics": metrics, "records": records, "notes": notes,
            "device": device, "checks": checks,
            "attempted": len(window), "failed": failed}
