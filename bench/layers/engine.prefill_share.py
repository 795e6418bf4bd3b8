"""Share of the window's wall time that the engine spent in inline
prefills: the ``dur`` of its ``prefill`` events that ended inside the
untraced part of the window, over that part (host clock)."""


def read(view):
    rec = view["records"]
    lo, hi = rec["host_window"]
    busy = sum(f["dur"] for ts, kind, f in rec["engine_events"]
               if kind == "prefill" and lo <= ts <= hi)
    return 100.0 * busy / (hi - lo)
