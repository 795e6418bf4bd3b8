"""Roofline share of the latent attention (named scope
``repro.ops.mla_attention``: the latent gather or walk, the absorbed
attention and the value up-projection) in the decode steps of the traced
window: the least time the chip could take for the live rows' attention,
over the device time of the decode step's operations under that scope
(``decode_scopes.py``).

Least time of each step is the larger of its FLOPs over the bf16 peak and
its bytes over HBM bandwidth, from each row's live keys
(``work.attention_flops``: per head the scores against the 576 latent and
rope values and the sum over the 512 latent values; and
``work.paged_attention_bytes``: each live key's 576 values at the pool's
storage width read at run time, the scales, the query in and the heads'
values out). The count is of the work, not of how the program does it, so
a later latent page walk is read against the same yardstick. Nothing is
read where the run recorded no scoped operations."""
import decode_scopes as DS
import work

SCOPE = "repro.ops.mla_attention"


def read(view):
    rec, c, pk = view["records"], view["config"], view["peaks"]
    scopes = rec.get("decode_scopes")
    if not scopes:
        return None
    secs = DS.seconds(view["trace"], scopes, SCOPE)
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
