"""The serving engine's step phases in a profiler trace.

The program marks each ``Engine.step()`` and its phases with host spans
named ``repro.engine.*`` (``repro.obs.trace`` gives the schema), with their
fields as event stats, on the trace's clock. ``trace_reduce`` keeps only
the harness's ``bench.*`` spans; this module reads the program's and sets
them beside the device's operations:

- ``idle_gaps``: the longest idle gaps of device 0, each labelled by the
  innermost ``bench.*`` or ``repro.*`` span covering its midpoint;
- ``step_idle_ms``: device-0 idle time inside ``repro.engine.step`` spans,
  over the number of those spans whose midpoint lies in the window;
- ``prefill_ms_per_1k_tokens``: the summed duration of the
  ``repro.engine.prefill`` spans whose midpoint lies in the window, over
  their summed ``tokens``, times 1,000;
- ``phase_table``: each phase's mean host time and the device's idle time
  inside it, per step, apart for steps that hold a prefill and steps that
  do not.

A trace without the program's spans gives ``None`` for both numbers, and
``idle_gaps`` then gives what ``trace_reduce.idle_gaps`` gives.
``tools/phases.py`` prints all of it for traced runs of a cell.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field

import trace_reduce as TR
from common import percentile

PREFIX = "repro."
STEP = "repro.engine.step"
PREFILL = "repro.engine.prefill"


@dataclass
class Span(TR.Op):
    fields: dict = field(default_factory=dict)


def from_events(host_events: list) -> list[Span]:
    """The program's spans from plain host events, each a tuple (name,
    start_ns, duration_ns, fields); events not named ``repro.*`` are
    dropped."""
    return sorted((Span(n, s, s + d, fields=dict(f))
                   for n, s, d, f in host_events if n.startswith(PREFIX)),
                  key=lambda sp: sp.start)


def from_xplane(path: str) -> list[Span]:
    """The program's spans on the host planes of a profiler trace."""
    from jax.profiler import ProfileData
    host = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            host.extend((ev.name, ev.start_ns, ev.duration_ns,
                         {k: v for k, v in ev.stats})
                        for ev in line.events if ev.name.startswith(PREFIX))
    return from_events(host)


def idle_gaps(red: TR.Reduced, spans: list[Span], top: int = 10
              ) -> list[list]:
    """``trace_reduce.idle_gaps`` with the program's spans among the
    labels."""
    return TR.idle_gaps(dataclasses.replace(red, spans=red.spans + spans),
                        top)


def _idle(red: TR.Reduced) -> list[tuple[float, float]]:
    """Every idle interval of device 0 inside the window."""
    out, cur = [], red.window[0]
    for s, e in TR.busy_intervals(red.ops[min(red.ops)], red.window):
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if cur < red.window[1]:
        out.append((cur, red.window[1]))
    return out


def _overlap(intervals, s: float, e: float) -> float:
    return sum(max(0.0, min(e, b) - max(s, a)) for a, b in intervals)


def _in_window(red: TR.Reduced, spans: list[Span], name: str) -> list[Span]:
    return [sp for sp in spans if sp.name == name
            and red.window[0] <= 0.5 * (sp.start + sp.end) <= red.window[1]]


def step_idle_ms(red: TR.Reduced, spans: list[Span]) -> float | None:
    """Device-0 idle ms inside ``repro.engine.step`` spans, per step."""
    steps = _in_window(red, spans, STEP)
    if not steps:
        return None
    idle = _idle(red)
    tot = sum(_overlap(idle, sp.start, sp.end)
              for sp in spans if sp.name == STEP)
    return tot * 1e-6 / len(steps)


def prefill_ms_per_1k_tokens(red: TR.Reduced, spans: list[Span]
                             ) -> float | None:
    """Host ms of the engine's prefills per 1,000 prompt tokens."""
    pre = _in_window(red, spans, PREFILL)
    tokens = sum(sp.fields.get("tokens", 0) for sp in pre)
    if not tokens:
        return None
    return sum(sp.end - sp.start for sp in pre) * 1e-6 / tokens * 1e3


def phase_table(red: TR.Reduced, spans: list[Span]) -> dict:
    """Mean ms per step of each phase (host time, and device-0 idle time
    inside it), for the steps in the window with a prefill and without;
    ``(uncovered)`` is step time outside every phase. Also, over the
    decode rows, the 95th percentile of step time and the share of the
    steps at or above it that hold a prefill."""
    idle = _idle(red)
    groups: dict[str, list[dict]] = {"with_prefill": [],
                                     "without_prefill": []}
    weighted = []
    for st in _in_window(red, spans, STEP):
        kids = [sp for sp in spans if sp.name != STEP
                and st.start <= sp.start and sp.end <= st.end]
        row = {"step": [st.end - st.start, _overlap(idle, st.start, st.end)]}
        for sp in kids:
            ph = sp.name[len("repro.engine."):]
            acc = row.setdefault(ph, [0.0, 0.0])
            acc[0] += sp.end - sp.start
            acc[1] += _overlap(idle, sp.start, sp.end)
        row["(uncovered)"] = [row["step"][i] - sum(
            v[i] for k, v in row.items() if k != "step") for i in (0, 1)]
        has_prefill = any(sp.name == PREFILL for sp in kids)
        groups["with_prefill" if has_prefill else "without_prefill"].append(
            row)
        rows = sum(sp.fields.get("rows", 0) for sp in kids
                   if sp.name == "repro.engine.dispatch")
        weighted.append((st.end - st.start, rows, has_prefill))
    out = {}
    for g, rows in groups.items():
        names = sorted({k for r in rows for k in r})
        out[g] = {"steps": len(rows),
                  "ms": {k: [sum(r.get(k, [0, 0])[i] for r in rows)
                             * 1e-6 / len(rows) for i in (0, 1)]
                         for k in names}} if rows else {"steps": 0}
    decode = [w for w in weighted if w[1]]
    if decode:
        expanded = [d for d, n, _ in decode for _ in range(n)]
        p95 = percentile(expanded, 95)
        tail = [w for w in decode if w[0] >= p95]
        out["step_ms_p95_by_rows"] = p95 * 1e-6
        out["tail_steps"] = len(tail)
        out["tail_share_with_prefill"] = (
            sum(1 for w in tail if w[2]) / len(tail))
    return out

