"""Model FLOP utilization of the decode steps in the traced window: the
operations their live rows need (``work.decode_token_flops``: every
projection and the head for each active slot, plus attention over that
slot's own context), over the decode-step program's device time times the
chip's bf16 peak."""
import trace_reduce as TR
import work


def read(view):
    rec, c = view["records"], view["config"]
    runs = TR.module_runs(view["trace"], "_decode_impl")
    i0, i1 = rec["trace_steps"]
    keys = [k for s in rec["steps"][i0:i1] for k in s["decode_keys"]]
    if not runs or not keys:
        return None
    flops = sum(work.decode_token_flops(c, k) for k in keys)
    secs = sum(r.end - r.start for r in runs) * 1e-9
    return 100.0 * flops / (secs * view["peaks"]["bf16_flops_per_s"])
