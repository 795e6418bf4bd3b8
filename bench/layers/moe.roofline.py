"""Roofline share of the expert layers (named scope ``repro.ops.moe``:
router, dispatch, routed and shared experts) in the decode steps of the
traced window: the least time the chip could take for their work, over
the device time of the decode step's operations under that scope
(``decode_scopes.py``).

The work of each traced step is counted from its ``moe_hits`` record
(``loops/open_loop_scoped.py``): for every expert layer the router and
the shared experts over the step's live rows, the weights of the held
experts that got a live token, and the FLOPs of the live token-expert
pairs routed here (the layout's ``moe_step_work``, weights at the
configuration's dtype). Least time of a step is the larger of its FLOPs
over the bf16 peak and its bytes over HBM bandwidth. Nothing is read where
the run recorded no hits or no scoped operations."""
import decode_scopes as DS
from common import layout_of

SCOPE = "repro.ops.moe"


def read(view):
    rec, c, pk = view["records"], view["config"], view["peaks"]
    hits, scopes = rec.get("moe_hits"), rec.get("decode_scopes")
    i0, i1 = rec["trace_steps"]
    steps = rec["steps"][i0:i1]
    if not hits or not scopes or not steps:
        return None
    secs = DS.seconds(view["trace"], scopes, SCOPE)
    lo, hi = steps[0]["t0"], steps[-1]["t1"]
    lay, w = layout_of(c), rec["act_itemsize"]
    least = 0.0
    for ts, rows, hit, here in hits:
        if lo <= ts <= hi:
            flops, nbytes = lay.moe_step_work(c, rows, hit, here, w, w)
            least += max(flops / pk["bf16_flops_per_s"],
                         nbytes / pk["hbm_bytes_per_s"])
    if secs <= 0 or least <= 0:
        return None
    return 100.0 * least / secs
