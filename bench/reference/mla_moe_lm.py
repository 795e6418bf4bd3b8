"""Plain float32 reference of a DeepSeek-V3-style decoder as Moonlight
describes it (huggingface.co/moonshotai/Moonlight-16B-A3B, model_type
deepseek_v3): token embedding; per layer RMSNorm -> multi-head latent
attention -> residual; RMSNorm -> feed-forward -> residual; final RMSNorm;
untied head.

Latent attention in its published, non-absorbed form: q = x Wq, per head
[q_nope 128 | q_rope 64]; [c_kv 512 | k_rope 64] = x Wkv_a, c_kv
RMS-normed; per head [k_nope | v] = c_kv Wkv_b; k_rope, rotated, is shared
by every head; scores (q_nope k_nope + q_rope k_rope) / sqrt(192), causal
softmax, then the output projection of the heads' values. Rotary embedding
on the 64 rope values in the rotate-half layout; the published checkpoint
stores them as interleaved pairs and de-interleaves them before rotating,
a fixed permutation of Wq's and Wkv_a's rope columns, which with random
weights changes no arithmetic.

The feed-forward of the first ``first_k_dense_replace`` layers is a dense
SwiGLU. Each later layer routes: scores sigmoid(x W_router); the
``num_experts_per_tok`` largest of scores + correction bias are picked;
their weights are their unbiased scores normalised over the picked and
times ``routed_scaling_factor``. The layer's output is the weighted sum of
the picked experts this chip holds (``n_routed_experts`` of the router's
experts from ``expert_offset``; what the others would add is left out, as
one chip of the stated deployment computes it), plus the shared experts,
a SwiGLU of width ``moe_intermediate_size`` x ``n_shared_experts``.

No kernels, no cache, no batching: one sequence, every matmul in float32
at ``Precision.HIGHEST``. Weights come by seed from ``weights.py`` and
from the ``mla_moe`` layout's ``layer_weights``, one layer at a time
inside the layer loop. It imports nothing of the program.

The comparison (``served_gaps``) counts the positions whose routing the
reference decides by more than bfloat16 can resolve: at every expert
layer, each of the token's picks, where it or the score it beats is one
this chip holds, leads the best score left out by more than two bfloat16
steps of a score in [0.5, 1), 2**-7 (``ROUTE_MARGIN``). A program that
computes in bfloat16 may pick the other way at a narrower lead (its
router reads a hidden state that has drifted through the layers before),
and one flipped pick of a held expert moves the logits by as much as a
wrong program would, so such positions say nothing of the program.

``lowp=True`` is the control for the configuration's bfloat16 compute:
the same forward with every projection (the router's included) an int8 x
int8 product (per-row activation scales, per-column weight scales), one
step below it. The controls for its int8 KV storage and for its routing
are the program's own 4-bit pool (``kv_bits`` 4, ``tools/readings.py``)
and the program routing without the correction bias
(``tools/route_control.py``).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

import weights as W
from common import layout_for

LAYOUT = layout_for("mla_moe")

HI = jax.lax.Precision.HIGHEST
# the routing lead below which bfloat16 may pick the other way: two steps
# of a bfloat16 number in [0.5, 1), where the sigmoid scores that win lie
ROUTE_MARGIN = 2.0 ** -7


def _qsym(x, bits: int, axis: int):
    """Symmetric round-to-nearest onto a ``bits``-bit grid scaled by
    max|x| along ``axis``."""
    qmax = 2.0 ** (bits - 1) - 1
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / qmax
    s = jnp.where(s > 0, s, 1.0)
    return jnp.clip(jnp.round(x / s), -qmax - 1, qmax) * s


def _mm(x, w, lowp: bool):
    if lowp:
        x = _qsym(x, 8, -1)
        w = _qsym(w, 8, 0)
    return jnp.matmul(x, w, precision=HI)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, theta: float):
    """x: (T, ..., Dr), positions 0..T-1; halves rotated as pairs."""
    t, dr = x.shape[0], x.shape[-1]
    half = dr // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None]
    shape = (t,) + (1,) * (x.ndim - 2) + (half,)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def _swiglu(h, wg, wu, wd, lowp):
    g = _mm(h, wg, lowp)
    return _mm(g * jax.nn.sigmoid(g) * _mm(h, wu, lowp), wd, lowp)


def attention(x, w, c: dict, lowp: bool):
    """Latent attention of one layer over a (T, D) sequence."""
    t = x.shape[0]
    hh, nope, rope = (c["num_attention_heads"], c["qk_nope_head_dim"],
                      c["qk_rope_head_dim"])
    vd, lat, eps = c["v_head_dim"], c["kv_lora_rank"], c["rms_norm_eps"]
    h = _rms(x, w["attn_norm"], eps)
    q = _mm(h, w["wq"], lowp).reshape(t, hh, nope + rope)
    q_nope, q_rope = q[..., :nope], _rope(q[..., nope:], c["rope_theta"])
    kv = _mm(h, w["wkv_a"], lowp)
    c_kv = _rms(kv[:, :lat], w["kv_norm"], eps)
    k_rope = _rope(kv[:, lat:], c["rope_theta"])                # (T, rope)
    kvb = _mm(c_kv, w["wkv_b"], lowp).reshape(t, hh, nope + vd)
    k_nope, v = kvb[..., :nope], kvb[..., nope:]
    s = (jnp.einsum("qhd,khd->hqk", q_nope, k_nope, precision=HI)
         + jnp.einsum("qhd,kd->hqk", q_rope, k_rope, precision=HI))
    s = s / jnp.sqrt(jnp.float32(nope + rope))
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    a = jnp.einsum("hqk,khd->qhd", p, v, precision=HI).reshape(t, hh * vd)
    return x + _mm(a, w["wo"], lowp)


def experts(h, w, c: dict, lowp: bool):
    """The routed experts held here and the shared experts, over (T, D),
    and the (T,) routing margin of the picks (``_route_margin``)."""
    k = c["num_experts_per_tok"]
    scores = jax.nn.sigmoid(_mm(h, w["router"], lowp))           # (T, E)
    biased = scores + w["router_bias"]
    _, pick = jax.lax.top_k(biased, k)                           # (T, k)
    wts = jnp.take_along_axis(scores, pick, -1)
    if c["norm_topk_prob"]:
        wts = wts / jnp.sum(wts, -1, keepdims=True)
    wts = wts * c["routed_scaling_factor"]
    held = LAYOUT.held_experts(c)                                # (H,)
    comb = jnp.sum(jnp.where(pick[:, None, :] == held[None, :, None],
                             wts[:, None, :], 0.0), -1)          # (T, H)
    margin = _route_margin(biased, pick, held)

    def one(acc, e):
        y = _swiglu(h, w["expert_gate"][e], w["expert_up"][e],
                    w["expert_down"][e], lowp)
        return acc + comb[:, e, None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(h), jnp.arange(held.shape[0]))
    return out + _swiglu(h, w["shared_gate"], w["shared_up"],
                         w["shared_down"], lowp), margin


def _route_margin(biased, pick, held):
    """(T,) the least lead by which a token's picks beat the experts left
    out, over the pairs that change this chip's output: a pick that is
    held against any expert left out, and any pick against a held expert
    left out (a swap of two experts held elsewhere changes nothing here
    but the normalisation, by their near-equal scores)."""
    e = biased.shape[-1]
    picked = jnp.any(pick[:, :, None] == jnp.arange(e)[None, None], 1)
    is_held = jnp.zeros(e, bool).at[held].set(True)
    inf = jnp.float32(jnp.inf)
    low = jnp.min(jnp.where(picked, biased, inf), -1)
    low_held = jnp.min(jnp.where(picked & is_held, biased, inf), -1)
    top = jnp.max(jnp.where(picked, -inf, biased), -1)
    top_held = jnp.max(jnp.where(~picked & is_held, biased, -inf), -1)
    return jnp.minimum(low_held - top, low - top_held)


def layer(x, w, c: dict, lowp: bool, dense: bool):
    """One decoder layer over a (T, D) sequence in float32, and the (T,)
    routing margin of its expert layer (inf for a dense layer)."""
    x = attention(x, w, c, lowp)
    h = _rms(x, w["ffn_norm"], c["rms_norm_eps"])
    if dense:
        return (x + _swiglu(h, w["w_gate"], w["w_up"], w["w_down"], lowp),
                jnp.full(x.shape[:1], jnp.inf))
    y, margin = experts(h, w, c, lowp)
    return x + y, margin


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


@partial(jax.jit, static_argnames=("c_items", "lowp"))
def _logits(seed_arr, tokens, rows, c_items, lowp):
    """Logits at ``rows`` and, there, the least routing margin over the
    expert layers."""
    c = {k: dict(v) if isinstance(v, tuple) else v for k, v in c_items}
    dtype = jnp.dtype(c["torch_dtype"])
    n_dense = c["first_k_dense_replace"]
    x = W.embedding(seed_arr, c, dtype)[tokens].astype(jnp.float32)
    for i in range(n_dense):
        w = _f32(LAYOUT.layer_weights(seed_arr, c, i, dtype, dense=True))
        x, _ = layer(x, w, c, lowp, dense=True)

    def body(carry, i):
        x, margin = carry
        w = _f32(LAYOUT.layer_weights(seed_arr, c, i, dtype))
        x, m = layer(x, w, c, lowp, dense=False)
        return (x, jnp.minimum(margin, m)), None

    (x, margin), _ = jax.lax.scan(
        body, (x, jnp.full(x.shape[:1], jnp.inf)),
        jnp.arange(n_dense, c["num_hidden_layers"]))
    x = _rms(x[rows], W.final_norm(seed_arr, c), c["rms_norm_eps"])
    return (_mm(x, W.head(seed_arr, c, dtype).astype(jnp.float32), lowp),
            margin[rows])


def _static(c: dict) -> tuple:
    keys = ("hidden_size", "intermediate_size", "moe_intermediate_size",
            "num_attention_heads", "num_hidden_layers", "vocab_size",
            "rms_norm_eps", "rope_theta", "torch_dtype", "kv_lora_rank",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
            "first_k_dense_replace", "n_routed_experts", "n_shared_experts",
            "num_experts_per_tok", "norm_topk_prob", "routed_scaling_factor")
    ep = tuple(sorted((k, v) for k, v in c["expert_parallel"].items()
                      if isinstance(v, (int, float))))
    return tuple((k, c[k]) for k in keys) + (("expert_parallel", ep),)


def logits_at(seed: int, c: dict, tokens, rows, lowp: bool = False,
              length: int = 0):
    """Logits (len(rows), V) at positions ``rows`` of one sequence, which
    is padded at its end to ``length``, or to a multiple of 512, so that
    one length compiles (causal attention, per-token routing: the padding
    changes no earlier position)."""
    return _at(seed, c, tokens, rows, lowp, length)[0]


def _at(seed: int, c: dict, tokens, rows, lowp: bool, length: int):
    """``logits_at``'s logits and the (len(rows),) routing margins."""
    import numpy as np
    toks = np.asarray(tokens, np.int32)
    n = max(length, len(toks) + (-len(toks)) % 512)
    padded = np.zeros(n, np.int32)
    padded[:len(toks)] = toks
    rows = np.asarray(rows, np.int32)
    rows_p = np.zeros(len(rows) + (-len(rows)) % 512, np.int32)
    rows_p[:len(rows)] = rows
    lg, margin = _logits(W.seed_array(seed), padded, rows_p, _static(c),
                         lowp)
    return lg[:len(rows)], margin[:len(rows)]


def served_gaps(seed: int, c: dict, prompt, served, lowp: bool = False,
                length: int = 0):
    """At each position of the served tokens whose routing the reference
    decides by more than ``ROUTE_MARGIN``, the gap by which the served
    token's reference logit lies below the reference's best there (0 where
    it is the best). With ``lowp``, the control's reading instead: the gap
    of the token the low-precision forward puts first, at the same
    positions."""
    import numpy as np
    seq = list(prompt) + list(served[:-1])
    rows = np.arange(len(prompt) - 1, len(seq))
    ref, margin = _at(seed, c, seq, rows, False, length)
    pick = (jnp.argmax(logits_at(seed, c, seq, rows, lowp=True,
                                 length=length), axis=-1)
            if lowp else jnp.asarray(served))
    got = jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]
    gaps = np.asarray(jnp.max(ref, axis=-1) - got)
    return gaps[np.asarray(margin) > ROUTE_MARGIN]
