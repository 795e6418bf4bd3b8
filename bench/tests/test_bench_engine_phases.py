"""The engine's step phases (``engine_phases``), on events made by hand and
on a small trace recorded on a TPU v5e (``fixtures/engine_spans.xplane.pb``,
made by ``tools/record_engine_spans.py``: two prompts served by a tiny
engine, each ``Engine.step()`` inside a ``bench.engine.step`` span)."""
from pathlib import Path

import pytest

import engine_phases as EP
import trace_reduce as TR

FIXTURE = Path(__file__).parent / "fixtures" / "engine_spans.xplane.pb"
MS = 1_000_000          # ns


def _by_hand(program: bool):
    """One engine step on device 0, times in ms: busy [10, 20], [30, 40],
    [60, 90] of the window [0, 100]; idle [0, 10], [20, 30], [40, 60],
    [90, 100]."""
    dev = {0: {"XLA Ops": [("a", 10 * MS, 10 * MS, ""),
                           ("b", 30 * MS, 10 * MS, ""),
                           ("c", 60 * MS, 30 * MS, "")],
               "XLA Modules": []}}
    host = [("bench.traced_window", 0, 100 * MS),
            ("bench.engine.step", 2 * MS, 92 * MS)]
    prog = [("repro.engine.step", 3, 90, {"step_num": 0}),
            ("repro.engine.admit", 3, 5, {}),
            ("repro.engine.prefill", 8, 22,
             {"rid": 1, "tokens": 400, "computed": 400, "padded": 512}),
            ("repro.engine.dispatch", 30, 28, {"rows": 3}),
            ("repro.engine.sync", 58, 34, {}),
            ("repro.engine.bookkeeping", 92, 1, {})]
    spans = EP.from_events([(n, s * MS, d * MS, f) for n, s, d, f in prog]
                           if program else [])
    return TR.from_events(dev, host), spans


def test_program_spans_kept_with_fields():
    red, spans = _by_hand(True)
    assert [s.name for s in spans][:2] == ["repro.engine.step",
                                           "repro.engine.admit"]
    assert len(spans) == 6
    pre = next(s for s in spans if s.name == "repro.engine.prefill")
    assert (pre.start, pre.end) == (8 * MS, 30 * MS)
    assert pre.fields == {"rid": 1, "tokens": 400, "computed": 400,
                          "padded": 512}
    # the reduction itself is the same with or without them
    assert [s.name for s in red.spans] == ["bench.engine.step"]
    assert EP.from_events([("bench.wait", 0, 1, {}), ("x", 0, 1, {})]) == []


def test_gaps_labelled_by_engine_phase():
    red, spans = _by_hand(True)
    assert EP.idle_gaps(red, spans) == [
        ["repro.engine.dispatch", pytest.approx(20e-3)],
        ["repro.engine.admit", pytest.approx(10e-3)],
        ["repro.engine.prefill", pytest.approx(10e-3)],
        ["none", pytest.approx(10e-3)]]
    # without the program's spans: exactly the reduction's own labels
    assert TR.idle_gaps(red) == [
        ["bench.engine.step", pytest.approx(20e-3)],
        ["bench.engine.step", pytest.approx(10e-3)],
        ["bench.engine.step", pytest.approx(10e-3)],
        ["none", pytest.approx(10e-3)]]
    assert EP.idle_gaps(red, []) == TR.idle_gaps(red)


def test_readers_by_hand():
    red, spans = _by_hand(True)
    # idle inside the step [3, 93]: 7 + 10 + 20 + 3 ms, one step
    assert EP.step_idle_ms(red, spans) == pytest.approx(40.0)
    # 22 ms of prefill for 400 prompt tokens
    assert EP.prefill_ms_per_1k_tokens(red, spans) == pytest.approx(55.0)


@pytest.mark.parametrize("reader", [EP.step_idle_ms,
                                    EP.prefill_ms_per_1k_tokens])
def test_reader_finds_nothing_without_program_spans(reader):
    red, spans = _by_hand(False)
    assert reader(red, spans) is None


def test_phase_table_by_hand():
    red, spans = _by_hand(True)
    tab = EP.phase_table(red, spans)
    assert tab["without_prefill"] == {"steps": 0}
    got = tab["with_prefill"]
    assert got["steps"] == 1
    want = {"step": [90, 40], "admit": [5, 5], "prefill": [22, 12],
            "dispatch": [28, 18], "sync": [34, 4], "bookkeeping": [1, 1],
            "(uncovered)": [0, 0]}
    assert got["ms"] == {k: [pytest.approx(v[0]), pytest.approx(v[1])]
                         for k, v in want.items()}
    assert tab["step_ms_p95_by_rows"] == pytest.approx(90.0)
    assert tab["tail_share_with_prefill"] == 1.0


@pytest.fixture(scope="module")
def recorded():
    return TR.from_xplane(str(FIXTURE)), EP.from_xplane(str(FIXTURE))


def test_fixture_names_stats_and_nesting(recorded):
    red, spans = recorded
    steps = [s for s in spans if s.name == EP.STEP]
    assert [s.fields["step_num"] for s in steps] == [3, 4, 5]
    # each engine step inside one harness span, each phase inside one step,
    # a step's phases one after another
    outer = [s for s in red.spans if s.name == "bench.engine.step"]
    assert len(outer) == 3
    assert all(b.start <= s.start and s.end <= b.end
               for b, s in zip(outer, steps))
    phases = [s for s in spans if s.name != EP.STEP]
    for st in steps:
        kids = [s for s in phases if st.start <= s.start and s.end <= st.end]
        assert all(a.end <= b.start for a, b in zip(kids, kids[1:]))
        assert {"repro.engine.admit", "repro.engine.pages",
                "repro.engine.dispatch", "repro.engine.sync",
                "repro.engine.bookkeeping"} <= {s.name for s in kids}
    assert sum(st.start <= s.start and s.end <= st.end
               for s in phases for st in steps) == len(phases)
    # the first step admits and prefills both prompts, at bucket 8
    pre = [s.fields for s in phases if s.name == EP.PREFILL]
    assert pre == [{"rid": 2, "tokens": 5, "computed": 5, "padded": 8},
                   {"rid": 3, "tokens": 11, "computed": 11, "padded": 16}]
    assert all(steps[0].start <= s.start and s.end <= steps[0].end
               for s in phases if s.name == EP.PREFILL)
    assert [s.fields for s in phases
            if s.name == "repro.engine.dispatch"] == [{"rows": 2}] * 3


def test_fixture_gaps_carry_engine_phases(recorded):
    red, spans = recorded
    labels = {g[0] for g in TR.idle_gaps(red, top=1000)}
    assert labels == {"bench.engine.step"}
    gaps = EP.idle_gaps(red, spans, top=1000)
    assert {g[0] for g in gaps} <= {s.name for s in spans}
    assert [g[1] for g in gaps] == [g[1] for g in TR.idle_gaps(red,
                                                                 top=1000)]
    assert EP.step_idle_ms(red, spans) == pytest.approx(12.0893, abs=1e-4)
    # 9.445 + 9.524 ms of prefill for 5 + 11 prompt tokens
    assert EP.prefill_ms_per_1k_tokens(red, spans) == pytest.approx(
        (9.445469 + 9.524049) / 16 * 1e3)
