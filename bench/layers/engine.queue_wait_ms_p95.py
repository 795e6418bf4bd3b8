"""95th percentile of the engine's queue wait over the requests due in the
window and submitted before the trace began: from its ``submit`` event to
its first ``admit`` event (the engine's own trace recorder, host clock).
The harness submits each request when it is due and reports its own
lateness apart."""
from common import percentile


def read(view):
    rec = view["records"]
    want = set(rec["window_rids"])
    until = rec["host_window"][1]
    sub, adm = {}, {}
    for ts, kind, f in rec["engine_events"]:
        rid = f.get("rid")
        if rid not in want:
            continue
        if kind == "submit" and ts < until:
            sub[rid] = ts
        elif kind == "admit" and rid not in adm:
            adm[rid] = ts
    waits = [adm[r] - sub[r] for r in sub if r in adm]
    return 1e3 * percentile(waits, 95) if waits else None
