"""Roofline share of the paged-attention page walk (named scope
``repro.ops.paged_attention``) in the traced window: the least time the
chip could take for the walks of the live rows, over the kernel's device
time. Least time of each step is the larger of its operations over the
bf16 peak and its bytes over HBM bandwidth (``work.attention_flops``,
``work.paged_attention_bytes`` at the pool's storage width read at run
time); at these shapes the bytes bound it."""
import trace_reduce as TR
import work

SCOPE = "repro.ops.paged_attention"


def read(view):
    rec, c, pk = view["records"], view["config"], view["peaks"]
    secs = TR.scoped_seconds(view["trace"], SCOPE)
    i0, i1 = rec["trace_steps"]
    least = 0.0
    for s in rec["steps"][i0:i1]:
        if not s["decode_keys"]:
            continue
        flops = sum(work.attention_flops(c, k) for k in s["decode_keys"])
        nbytes = sum(work.paged_attention_bytes(
            c, k, rec["pool_itemsize"], rec["act_itemsize"])
            for k in s["decode_keys"])
        least += max(flops / pk["bf16_flops_per_s"],
                     nbytes / pk["hbm_bytes_per_s"])
    if secs <= 0 or least <= 0:
        return None
    return 100.0 * least / secs
