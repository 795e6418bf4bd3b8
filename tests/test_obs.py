"""repro.obs acceptance tests — the unified telemetry layer:

(a) counter registry + the preserved ``fallback_count`` view,
(b) quant-health device aggregates agree BITWISE across codec backends,
(c) trace recorder: deterministic-clock lifecycle reconstructs a properly
    nested admit→preempt→resume→retire span tree, ring overflow keeps the
    newest events, JSONL and Chrome-trace exports round-trip,
(d) zero-overhead guarantees: an attached (or disabled) recorder leaves the
    engine's decode jaxpr byte-identical, and a health-off policy's decode
    and train-step jaxprs match a policy-free / health-free build,
(e) ServeMetrics edge cases: unknown-rid hooks don't crash, wall clock
    covers still-running requests, and health folds into ``summary()``.
"""
import itertools
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as C
from repro import numerics as N
from repro.models import build_lm, init_lm
from repro.obs import (CounterRegistry, TraceRecorder, check_nesting,
                       chrome_trace, fraction, kernel_costs, pow2_clip_stats,
                       read_jsonl, record_kernel_call, request_spans,
                       saturation_counts, scale_drift_stats, tree_sat_stats,
                       write_jsonl)
from repro.serve import Engine, EngineConfig, PoolConfig
from repro.serve.metrics import ServeMetrics
from repro.sharding import ShardPlan

PLAN = ShardPlan(mesh=None)


def _counter_clock():
    c = itertools.count()
    return lambda: float(next(c))


# ---------------------------------------------------------------------------
# (a) counters
# ---------------------------------------------------------------------------

def test_counter_registry_basics():
    r = CounterRegistry()
    r.inc("a.b")
    r.inc("a.b", 4)
    r.inc("z")
    assert r.get("a.b") == 5 and r.get("z") == 1 and r.get("missing") == 0
    assert r.snapshot("a.") == {"a.b": 5}
    r.reset("a.b")
    assert r.get("a.b") == 0 and r.get("z") == 1
    r.reset()
    assert r.snapshot() == {}


def test_kernel_cost_table_handles_dotted_names():
    # the global registry keeps kernel.<name>.<field>; <name> itself may be
    # dotted (pe1.pallas) — the table must split on the LAST dot only
    record_kernel_call("obs_test.pallas", bytes_moved=128, flops=7)
    record_kernel_call("obs_test.pallas")
    costs = kernel_costs()["obs_test.pallas"]
    assert costs["calls"] >= 2 and costs["bytes"] >= 128
    assert costs["flops"] >= 7


def test_fallback_count_is_a_registry_view():
    """``pallas_backend.fallback_count`` is now a view over the shared
    registry (``numerics.codec_fallback``) — both directions must agree."""
    from repro.numerics import pallas_backend as PB
    from repro.obs import registry
    PB.reset_fallback_count()
    assert PB.fallback_count() == 0
    registry.inc(PB.FALLBACK_COUNTER, 3)
    assert PB.fallback_count() == 3
    PB.reset_fallback_count()
    assert registry.get(PB.FALLBACK_COUNTER) == 0


# ---------------------------------------------------------------------------
# (b) quant-health aggregates — bitwise backend agreement
# ---------------------------------------------------------------------------

def test_clip_and_saturation_counts_bit_agree_across_backends():
    spec = N.QuantSpec("pow2", 8, 0, "int8", "per_tensor_max")
    x = jax.random.normal(jax.random.PRNGKey(0), (6, 64)) * 8
    sc = jnp.asarray(np.random.RandomState(1).randint(-4, 0, (6,)),
                     jnp.float32)
    clipped, total = pow2_clip_stats(x, sc, spec.bits)
    # manual oracle
    r = np.asarray(x) / np.exp2(np.asarray(sc))[:, None]
    lo, hi = N.qrange(8)
    assert int(total) == x.size
    assert int(clipped) == int(((r < lo) | (r > hi)).sum())
    # the counts are integer-exact: both backends' encodes agree bitwise,
    # and so do the saturation counts over them
    sat = {}
    for backend in N.BACKENDS:
        qt = N.encode(x, spec, sc, backend=backend)
        sat[backend] = tuple(int(v) for v in saturation_counts(qt))
    assert sat["reference"] == sat["pallas"]
    # every clipped value saturates (plus values exactly at the edge)
    assert sat["reference"][0] >= int(clipped)
    assert sat["reference"][1] == x.size


def test_clip_stats_valid_mask_and_drift():
    x = jnp.ones((4, 8)) * 1000.0           # everything clips at scale 2^0
    clipped, total = pow2_clip_stats(
        x, jnp.zeros((4,)), 8, valid=jnp.asarray([1, 1, 0, 0],
                                                 bool)[:, None])
    assert int(clipped) == 16 and int(total) == 16
    dsum, dn = scale_drift_stats(jnp.zeros((4,)),
                                 jnp.asarray([1.0, -2.0, 5.0, 0.0]),
                                 valid=jnp.asarray([1, 1, 0, 1], bool))
    assert float(dsum) == 3.0 and float(dn) == 3.0
    assert float(fraction(jnp.asarray(0), jnp.asarray(0))) == 0.0
    assert float(fraction(jnp.asarray(3), jnp.asarray(4))) == 0.75


def test_tree_sat_stats_counts_float_leaves_only():
    tree = {"w": jnp.ones((8, 4)) * 5.0,    # saturates a fixed tiny scale
            "idx": jnp.arange(3, dtype=jnp.int32)}
    spec = N.QuantSpec("pow2", 8, 0, "int8", "per_tensor_max")
    sat, tot = tree_sat_stats(tree, spec)
    assert int(tot) == 32                    # int leaf excluded
    # per-tensor-max scale is clip-free: only exact-edge values saturate
    sat2, _ = tree_sat_stats(tree, spec, scale_for=lambda g: jnp.asarray(-8.0))
    assert int(sat2) == 32                   # tiny fixed scale: all saturate


def test_fake_quant_stats_returns_value_and_counts():
    spec = N.QuantSpec("pow2", 8)
    x = jax.random.normal(jax.random.PRNGKey(3), (5, 16)) * 4
    y, (clipped, total) = N.fake_quant_stats(x, spec, jnp.asarray(-2.0))
    np.testing.assert_array_equal(
        np.asarray(y), np.asarray(N.fake_quant(x, spec, jnp.asarray(-2.0))))
    assert int(total) == x.size and int(clipped) >= 0


# ---------------------------------------------------------------------------
# (c) trace recorder
# ---------------------------------------------------------------------------

def _lifecycle_recorder() -> TraceRecorder:
    rec = TraceRecorder(clock=_counter_clock())
    rec.emit("submit", rid=1, prompt_len=4, max_new=8)          # t=0
    rec.emit("admit", rid=1, slot=0, pages=1)                   # t=1
    rec.emit("prefill", rid=1, slot=0, len=4, dur=1.0)          # t=2
    rec.emit("first_token", rid=1, slot=0)                      # t=3
    rec.emit("preempt", rid=1, slot=0, gen_len=2)               # t=4
    rec.emit("admit", rid=1, slot=1, pages=1)                   # t=5 resume
    rec.emit("prefill", rid=1, slot=1, len=6, dur=1.0)          # t=6
    rec.emit("retire", rid=1, slot=1, new_tokens=8,
             reason="max_new")                                  # t=7
    return rec


def test_lifecycle_span_nesting_admit_preempt_resume_retire():
    spans = request_spans(_lifecycle_recorder().events())
    s = spans[1]
    assert (s.start, s.end, s.dur) == (0.0, 7.0, 7.0)
    assert [c.name for c in s.children] == ["scheduled", "scheduled"]
    first, second = s.children
    assert first.fields["outcome"] == "preempted"
    assert (first.start, first.end) == (1.0, 4.0)
    assert second.fields["outcome"] == "retired"
    assert (second.start, second.end) == (5.0, 7.0)
    # prefill child sits inside its residency (start backdated by dur)
    assert [c.name for c in first.children] == ["prefill"]
    assert first.children[0].start == 1.0 and first.children[0].end == 2.0
    assert check_nesting(s)
    assert s.fields["reason"] == "max_new"


def test_ring_overflow_keeps_newest_and_counts_drops():
    rec = TraceRecorder(capacity=4, clock=_counter_clock())
    for i in range(10):
        rec.emit("decode_step", step=i)
    assert len(rec) == 4 and rec.dropped == 6
    assert [e.fields["step"] for e in rec.events()] == [6, 7, 8, 9]
    assert len(rec.events("decode_step")) == 4
    rec.clear()
    assert len(rec) == 0 and rec.dropped == 0
    with pytest.raises(ValueError):
        TraceRecorder(capacity=0)


def test_disabled_recorder_emits_nothing():
    rec = TraceRecorder(clock=_counter_clock())
    rec.enabled = False
    rec.emit("submit", rid=0)
    assert len(rec) == 0


def test_jsonl_round_trip(tmp_path):
    rec = _lifecycle_recorder()
    path = str(tmp_path / "trace.jsonl")
    assert write_jsonl(rec, path) == 8
    back = read_jsonl(path)
    assert [(e.ts, e.kind, e.fields) for e in back] == \
        [(e.ts, e.kind, e.fields) for e in rec.events()]


def test_chrome_trace_round_trips_and_rebases():
    doc = json.loads(json.dumps(chrome_trace(_lifecycle_recorder())))
    evs = doc["traceEvents"]
    assert len(evs) == 8
    # ts rebased to the first event; us units
    assert evs[0]["ts"] == 0.0
    # dur events (prefill) are complete slices backdated by their duration
    slices = [e for e in evs if e["ph"] == "X"]
    assert {e["name"] for e in slices} == {"prefill"}
    assert slices[0]["ts"] == 1e6 and slices[0]["dur"] == 1e6
    # request lifecycle: one async begin per admit, one end per
    # preempt/retire, shared id
    bars = [e for e in evs if e["ph"] in ("b", "e")]
    assert [e["ph"] for e in bars] == ["b", "e", "b", "e"]
    assert all(e["cat"] == "request" and e["id"] == 1 for e in bars)


# ---------------------------------------------------------------------------
# (d) zero overhead — jaxpr identity
# ---------------------------------------------------------------------------

def _serve_setup():
    cfg = C.get_reduced("internlm2-1.8b").replace(dtype="float32",
                                                  remat="none")
    lm = build_lm(cfg)
    params = init_lm(jax.random.PRNGKey(0), lm)
    return cfg, lm, params


def _decode_jaxpr(eng) -> str:
    B = eng.pcfg.num_slots
    table = jnp.zeros((B, eng.pcfg.pages_per_slot), jnp.int32)
    lens = jnp.ones((B,), jnp.int32)
    active = jnp.ones((B,), bool)
    tokens = jnp.zeros((B, 1), jnp.int32)
    return str(jax.make_jaxpr(eng._decode_impl)(
        eng.params, eng.pool, eng.spool, table, lens, active, tokens))


def test_recorder_and_health_off_leave_decode_jaxpr_identical():
    cfg, lm, params = _serve_setup()
    pcfg = PoolConfig(num_slots=2, page_size=8, pages_per_slot=3,
                      quantized=True)
    base = Engine(lm, params, EngineConfig(pool=pcfg), PLAN)
    traced = Engine(lm, params, EngineConfig(pool=pcfg), PLAN,
                    trace=TraceRecorder(clock=_counter_clock()))
    # a policy with health OFF resolves to the same pool numerics
    pol_off = N.NumericsPolicy(enable=True, health=False)
    off = Engine(lm, params, EngineConfig(pool=pcfg, policy=pol_off), PLAN)
    ref = _decode_jaxpr(base)
    assert _decode_jaxpr(traced) == ref, \
        "an attached recorder must not change the decode jaxpr"
    assert _decode_jaxpr(off) == ref, \
        "health=False must trace the exact health-free decode step"
    # sanity: switching health ON does change the program
    pol_on = N.NumericsPolicy(enable=True, health=True)
    on = Engine(lm, params, EngineConfig(pool=pcfg, policy=pol_on), PLAN)
    assert _decode_jaxpr(on) != ref


def test_train_step_health_gating_jaxpr_and_schema():
    import dataclasses

    from repro.configs.base import ModelConfig, TrainConfig
    from repro.launch.steps import init_train_state, make_train_step

    def build(health):
        cfg = ModelConfig(name="t", num_layers=1, d_model=32, num_heads=2,
                          num_kv_heads=2, d_ff=64, vocab_size=64,
                          remat="none", dtype="float32")
        cfg = cfg.replace(quant=dataclasses.replace(
            cfg.quant, enable=True, health=health))
        lm = build_lm(cfg)
        params = init_lm(jax.random.PRNGKey(0), lm)
        tcfg = TrainConfig(learning_rate=1e-3, total_steps=4)
        state = init_train_state(params, tcfg, policy=cfg.quant.policy())
        return make_train_step(lm, PLAN, tcfg), state

    batch = {"tokens": jnp.zeros((2, 8), jnp.int32),
             "labels": jnp.zeros((2, 8), jnp.int32)}
    step_off, state_off = build(False)
    step_on, state_on = build(True)
    jx_off = str(jax.make_jaxpr(step_off)(state_off, batch))
    jx_on = str(jax.make_jaxpr(step_on)(state_on, batch))
    assert jx_on != jx_off
    # schema: health metrics appear exactly when the policy asks
    _, m_off = jax.eval_shape(step_off, state_off, batch)
    _, m_on = jax.eval_shape(step_on, state_on, batch)
    assert "health" not in m_off
    h = m_on["health"]
    assert set(h["grad_edge"]) >= {"sat_fraction", "saturated", "total"}
    assert {"scale_log2", "mean_abs", "in_band"} <= set(h["activation"])


# ---------------------------------------------------------------------------
# (e) ServeMetrics + engine-driven trace
# ---------------------------------------------------------------------------

def test_metrics_unknown_rid_hooks_do_not_crash():
    m = ServeMetrics(clock=_counter_clock())
    m.request_finished(99, 5)               # never submitted
    m.request_first_token(7)
    m.request_admitted(7, prompt_len=3)
    s = m.summary()
    assert s["requests_completed"] == 1 and s["generated_tokens"] == 5


def test_metrics_wall_clock_covers_running_requests():
    clk = {"t": 0.0}
    m = ServeMetrics(clock=lambda: clk["t"])
    m.request_submitted(0)
    m.request_admitted(0, 4)                # t0 = 0
    clk["t"] = 10.0
    m.request_first_token(0)
    m.request_finished(0, 10)
    # a second request is still running: wall must extend past the last
    # finish or tokens_per_s is inflated
    m.request_submitted(1)
    m.request_admitted(1, 4)
    clk["t"] = 40.0
    s = m.summary()
    assert s["wall_s"] == 40.0
    assert s["tokens_per_s"] == pytest.approx(10 / 40.0)
    # once everything finished, wall snaps back to the last finish time
    m.request_finished(1, 4)
    assert m.summary()["wall_s"] == 40.0


def test_metrics_timeline_and_health_summary():
    m = ServeMetrics(clock=_counter_clock())
    m.num_slots = 4
    m.decode_step(4, free_pages=10, dur=0.5)
    m.decode_step(2, free_pages=6, dur=0.5)
    m.record_health("kv_cache", 3, 100)
    m.record_health("kv_cache", 1, 100)
    m.record_health("ssm_state", 0, 50, drift_sum=2.0, drift_n=4.0)
    s = m.summary()
    assert s["batch_fill_mean"] == 3.0 and s["batch_fill_frac"] == 0.75
    assert s["free_pages_min"] == 6
    kv = s["quant_health"]["kv_cache"]
    assert kv == {"clipped": 4, "total": 200, "clip_fraction": 0.02,
                  "scale_drift_log2": 0.0}
    assert s["quant_health"]["ssm_state"]["scale_drift_log2"] == 0.5


def test_engine_emits_trace_and_kv_health():
    cfg, lm, params = _serve_setup()
    pcfg = PoolConfig(num_slots=2, page_size=8, pages_per_slot=4,
                      quantized=True)
    pol = N.NumericsPolicy(enable=True, health=True)
    rec = TraceRecorder()
    eng = Engine(lm, params, EngineConfig(pool=pcfg, policy=pol), PLAN,
                 trace=rec)
    rng = np.random.RandomState(0)
    rids = [eng.submit(rng.randint(0, cfg.vocab_size, 6).tolist(),
                       max_new_tokens=4) for _ in range(3)]
    res = eng.run()
    assert sorted(res) == sorted(rids)
    kinds = {e.kind for e in rec}
    assert {"submit", "admit", "prefill", "first_token", "decode_step",
            "retire"} <= kinds
    assert {"page_alloc", "page_free"} <= kinds
    # every request span closes and nests
    spans = request_spans(rec.events())
    assert sorted(spans) == sorted(rids)
    for s in spans.values():
        assert s.end is not None and check_nesting(s)
    # decode steps carry durations and the batch-fill timeline matches
    steps = rec.events("decode_step")
    assert steps and all(e.fields["dur"] >= 0 for e in steps)
    assert len(eng.metrics.timeline) == len(steps)
    # kv-site quant health flowed into the summary with sane values
    kv = eng.summary()["quant_health"]["kv_cache"]
    assert kv["total"] > 0
    assert 0.0 <= kv["clip_fraction"] < 0.5


def _profiler_spans(logdir) -> list[tuple]:
    """(name, start_ns, end_ns, stats) of the ``repro.*`` events on the host
    plane of the profiler trace written under ``logdir``, in start order."""
    from pathlib import Path

    from jax.profiler import ProfileData
    pb, = Path(logdir).glob("plugins/profile/*/*.xplane.pb")
    out = []
    for plane in ProfileData.from_file(str(pb)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            out.extend((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                        dict(ev.stats)) for ev in line.events
                       if ev.name.startswith("repro.engine."))
    return sorted(out, key=lambda e: (e[1], -e[2]))


def test_engine_step_phases_in_profiler_trace(tmp_path):
    cfg, lm, params = _serve_setup()
    pcfg = PoolConfig(num_slots=2, page_size=8, pages_per_slot=4,
                      quantized=True)
    eng = Engine(lm, params, EngineConfig(pool=pcfg, prefill_bucket=8), PLAN)
    rng = np.random.RandomState(0)
    lens = [5, 11, 3]
    prompts = [rng.randint(0, cfg.vocab_size, n).tolist() for n in lens]
    for p in prompts:                   # compile every shape untraced
        eng.submit(p, max_new_tokens=3)
    eng.run()
    for p in prompts:
        eng.submit(p, max_new_tokens=3)
    steps = 0
    with jax.profiler.trace(str(tmp_path)):
        while eng.sched.has_work():
            eng.step()
            steps += 1
    spans = _profiler_spans(tmp_path)
    outer = [s for s in spans if s[0] == "repro.engine.step"]
    assert len(outer) == steps
    nums = [s[3]["step_num"] for s in outer]
    assert nums == sorted(nums) and nums[-1] < eng.metrics.decode_steps
    phases = [s for s in spans if s[0] != "repro.engine.step"]
    assert {s[0] for s in phases} == {
        "repro.engine.admit", "repro.engine.prefill", "repro.engine.pages",
        "repro.engine.dispatch", "repro.engine.sync",
        "repro.engine.bookkeeping"}
    # each phase lies inside exactly one step; a step's phases follow one
    # another without overlap
    for st in outer:
        kids = [s for s in phases if st[1] <= s[1] and s[2] <= st[2]]
        assert kids and all(a[2] <= b[1] for a, b in zip(kids, kids[1:]))
    assert sum(1 for s in phases for st in outer
               if st[1] <= s[1] and s[2] <= st[2]) == len(phases)
    pre = [s[3] for s in phases if s[0] == "repro.engine.prefill"]
    assert sorted(f["tokens"] for f in pre) == sorted(lens)
    assert all(f["computed"] == f["tokens"]
               and f["padded"] == -(-f["tokens"] // 8) * 8 for f in pre)
    assert {s[3]["rows"] for s in phases
            if s[0] == "repro.engine.dispatch"} <= {1, 2}
