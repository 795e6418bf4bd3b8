"""From a profiler trace to the numbers the per-layer metrics read.

The JAX profiler writes an ``.xplane.pb``; ``jax.profiler.ProfileData``
reads it. Device planes are named ``/device:TPU:<n>``; on each, the line
``XLA Ops`` holds one event per operation that ran, and ``XLA Modules``
one per compiled program run. The harness's own host spans are the events
named ``bench.*`` on the host plane. Host and device events share the
trace's clock.

The traced window is the host span ``bench.traced_window``. Within it:

- busy time is the union of the operation intervals on a device (averaged
  over the devices used), idle the rest;
- an operation's time is the sum of its events' durations, by name;
- each idle gap is labelled by the innermost ``bench.*`` host span that
  covers its midpoint, or ``none`` where the host ran none of them.
"""
from __future__ import annotations

from dataclasses import dataclass, field

WINDOW_SPAN = "bench.traced_window"


@dataclass
class Op:
    name: str
    start: float            # ns, trace clock
    end: float
    scope: str = ""         # the op's source scope path, where the trace
                            # gives one (named scopes show in it)


@dataclass
class Reduced:
    window: tuple[float, float]
    ops: dict[int, list[Op]] = field(default_factory=dict)    # per device
    modules: dict[int, list[Op]] = field(default_factory=dict)
    spans: list[Op] = field(default_factory=list)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


_SCOPE_STATS = ("tf_op", "long_name")


def op_name(text: str) -> str:
    """An operation's name: on a TPU the event carries the HLO
    instruction (``%fusion.12 = bf16[...] fusion(...)``) and the name is
    what stands before `` =``; a named scope on a custom call shows there
    (``repro.ops.paged_attention.1``)."""
    if text.startswith("%"):
        return text[1:].split(" =", 1)[0]
    return text


def _stats(ev) -> dict:
    out = {}
    for k, v in ev.stats:
        out[k] = v
    return out


def from_events(device_lines: dict, host_events: list) -> Reduced:
    """Build the reduction from plain events: ``device_lines`` maps a device
    index to {"XLA Ops": [...], "XLA Modules": [...]}, each event a tuple
    (name, start_ns, duration_ns, scope); ``host_events`` are (name,
    start_ns, duration_ns). Only ``bench.*`` host events are kept."""
    spans = [Op(n, s, s + d) for n, s, d in host_events
             if n.startswith("bench.")]
    wins = [s for s in spans if s.name == WINDOW_SPAN]
    if not wins:
        raise ValueError(f"trace holds no {WINDOW_SPAN} span")
    w = (wins[0].start, wins[0].end)
    red = Reduced(window=w, spans=[s for s in spans if s.name != WINDOW_SPAN])
    for dev, lines in device_lines.items():
        for key, dest in (("XLA Ops", red.ops), ("XLA Modules", red.modules)):
            dest[dev] = sorted(
                (Op(n, s, s + d, sc) for n, s, d, sc in lines.get(key, [])
                 if s + d > w[0] and s < w[1]), key=lambda o: o.start)
    return red


def from_xplane(path: str) -> Reduced:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    device_lines, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and "Core" not in plane.name:
            dev = int(plane.name.rsplit(":", 1)[1])
            lines = {}
            for line in plane.lines:
                if line.name not in ("XLA Ops", "XLA Modules"):
                    continue
                evs = []
                for ev in line.events:
                    st = _stats(ev)
                    scope = next((str(st[k]) for k in _SCOPE_STATS
                                  if k in st), "")
                    evs.append((op_name(ev.name), ev.start_ns,
                                ev.duration_ns, scope))
                lines[line.name] = evs
            device_lines[dev] = lines
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((ev.name, ev.start_ns, ev.duration_ns)
                            for ev in line.events
                            if ev.name.startswith("bench."))
    if not device_lines:
        raise ValueError(f"{path}: no TPU device plane in the trace")
    return from_events(device_lines, host)


def _clip(ops: list[Op], w) -> list[tuple[float, float]]:
    return [(max(o.start, w[0]), min(o.end, w[1])) for o in ops
            if o.end > w[0] and o.start < w[1]]


def busy_intervals(ops: list[Op], w) -> list[tuple[float, float]]:
    """Union of the operation intervals inside the window, merged."""
    out: list[list[float]] = []
    for s, e in sorted(_clip(ops, w)):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_s(red: Reduced) -> float:
    """Seconds in which an operation ran, averaged over the devices."""
    if not red.ops:
        return 0.0
    tot = sum(sum(e - s for s, e in busy_intervals(ops, red.window))
              for ops in red.ops.values())
    return tot / len(red.ops) * 1e-9


def idle_gaps(red: Reduced, top: int = 10) -> list[list]:
    """The longest idle gaps of device 0, each [label, seconds], where the
    label is the innermost ``bench.*`` host span covering its midpoint."""
    dev = min(red.ops)
    busy = busy_intervals(red.ops[dev], red.window)
    gaps, cur = [], red.window[0]
    for s, e in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if cur < red.window[1]:
        gaps.append((cur, red.window[1]))
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for s, e in gaps[:top]:
        mid = 0.5 * (s + e)
        cover = [sp for sp in red.spans if sp.start <= mid <= sp.end]
        label = (min(cover, key=lambda sp: sp.end - sp.start).name
                 if cover else "none")
        out.append([label, (e - s) * 1e-9])
    return out


def op_seconds(red: Reduced) -> dict[str, float]:
    """Seconds per operation name, summed over events and averaged over
    devices, within the window."""
    tot: dict[str, float] = {}
    for ops in red.ops.values():
        for o in ops:
            s, e = max(o.start, red.window[0]), min(o.end, red.window[1])
            tot[o.name] = tot.get(o.name, 0.0) + (e - s) * 1e-9
    n = max(len(red.ops), 1)
    return {k: v / n for k, v in tot.items()}


def top_ops(red: Reduced, top: int = 10) -> list[list]:
    ranked = sorted(op_seconds(red).items(), key=lambda kv: -kv[1])
    return [[k, v] for k, v in ranked[:top]]


def scoped_seconds(red: Reduced, scope: str) -> float:
    """Seconds of the operations whose name or scope path holds ``scope``
    (a ``jax.named_scope`` shows in the scope path), averaged over
    devices."""
    tot = 0.0
    for ops in red.ops.values():
        for o in ops:
            if scope in o.scope or scope in o.name:
                s, e = max(o.start, red.window[0]), min(o.end, red.window[1])
                tot += max(0.0, e - s)
    return tot / max(len(red.ops), 1) * 1e-9


def module_runs(red: Reduced, fragment: str, dev: int | None = None
                ) -> list[Op]:
    """Runs of the compiled programs whose name holds ``fragment``, on one
    device (the first by default), whose midpoint lies inside the window
    (the device's clock may stand a millisecond off the host's)."""
    dev = min(red.modules) if dev is None else dev
    return [m for m in red.modules.get(dev, [])
            if fragment in m.name
            and red.window[0] <= 0.5 * (m.start + m.end) <= red.window[1]]
