"""Plain float32 reference of a dense decoder with grouped-query attention,
as InternLM2 describes it (arXiv:2403.17297): token embedding; per layer
RMSNorm -> Q/K/V projections -> rotary embedding (theta from the config,
rotate-half layout) -> causal softmax attention, query head h reading
key/value head h // (heads / kv_heads) -> output projection -> residual;
RMSNorm -> SwiGLU feed-forward -> residual; final RMSNorm; untied head.

No kernels, no cache, no batching: one sequence, every matmul in float32
at ``Precision.HIGHEST``. Weights come by seed from ``weights.py`` and
from the ``dense_gqa`` layout's ``layer_weights``, one layer at a time
inside the layer loop, so the reference holds one layer's float32 weights
at once. It imports nothing of the program.

``lowp=True`` is the control for the configuration's bfloat16 compute:
the same forward with every projection an int8 x int8 product (per-row
activation scales, per-column weight scales), one step below it. The
control for its int8 KV storage is the program's own 4-bit pool
(``kv_bits`` 4), which ``tools/readings.py`` runs.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

import weights as W
from common import layout_for

LAYOUT = layout_for("dense_gqa")

HI = jax.lax.Precision.HIGHEST


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
    """x: (T, H, Dh), positions 0..T-1; halves rotated as pairs."""
    t, _, dh = x.shape
    half = dh // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def layer(x, w, c: dict, lowp: bool):
    """One decoder layer over a (T, D) sequence in float32."""
    t = x.shape[0]
    hq, hkv = c["num_attention_heads"], c["num_key_value_heads"]
    dh = c["hidden_size"] // hq
    eps = c["rms_norm_eps"]
    h = _rms(x, w["attn_norm"], eps)
    q = _rope(_mm(h, w["wq"], lowp).reshape(t, hq, dh), c["rope_theta"])
    k = _rope(_mm(h, w["wk"], lowp).reshape(t, hkv, dh), c["rope_theta"])
    v = _mm(h, w["wv"], lowp).reshape(t, hkv, dh)
    k = jnp.repeat(k, hq // hkv, axis=1)
    v = jnp.repeat(v, hq // hkv, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) / jnp.sqrt(
        jnp.float32(dh))
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    a = jnp.einsum("hqk,khd->qhd", p, v, precision=HI).reshape(t, hq * dh)
    x = x + _mm(a, w["wo"], lowp)
    h = _rms(x, w["ffn_norm"], eps)
    g = _mm(h, w["w_gate"], lowp)
    u = _mm(h, w["w_up"], lowp)
    return x + _mm(g * jax.nn.sigmoid(g) * u, w["w_down"], lowp)


@partial(jax.jit, static_argnames=("c_items", "lowp"))
def _logits(seed_arr, tokens, rows, c_items, lowp):
    c = dict(c_items)
    dtype = jnp.dtype(c["torch_dtype"])
    x = W.embedding(seed_arr, c, dtype)[tokens].astype(jnp.float32)

    def body(x, i):
        w = jax.tree.map(lambda a: a.astype(jnp.float32),
                         LAYOUT.layer_weights(seed_arr, c, i, dtype))
        return layer(x, w, c, lowp), None

    x, _ = jax.lax.scan(body, x, jnp.arange(c["num_hidden_layers"]))
    x = _rms(x[rows], W.final_norm(seed_arr, c), c["rms_norm_eps"])
    return _mm(x, W.head(seed_arr, c, dtype).astype(jnp.float32), lowp)


def _static(c: dict) -> tuple:
    keys = ("hidden_size", "intermediate_size", "num_attention_heads",
            "num_key_value_heads", "num_hidden_layers", "vocab_size",
            "rms_norm_eps", "rope_theta", "torch_dtype")
    return tuple((k, c[k]) for k in keys)


def logits_at(seed: int, c: dict, tokens, rows, lowp: bool = False,
              length: int = 0):
    """Logits (len(rows), V) at positions ``rows`` of one sequence, which
    is padded at its end to ``length``, or to a multiple of 512, so that
    one length compiles (causal attention: the padding changes no earlier
    position)."""
    import numpy as np
    toks = np.asarray(tokens, np.int32)
    n = max(length, len(toks) + (-len(toks)) % 512)
    padded = np.zeros(n, np.int32)
    padded[:len(toks)] = toks
    rows = np.asarray(rows, np.int32)
    rows_p = np.zeros(len(rows) + (-len(rows)) % 512, np.int32)
    rows_p[:len(rows)] = rows
    return _logits(W.seed_array(seed), padded, rows_p, _static(c),
                   lowp)[:len(rows)]


def served_gaps(seed: int, c: dict, prompt, served, lowp: bool = False,
                length: int = 0):
    """At each position of the served tokens, the gap by which the served
    token's reference logit lies below the reference's best there (0 where
    it is the best). With ``lowp``, the control's reading instead: the gap
    of the token the low-precision forward puts first."""
    import numpy as np
    seq = list(prompt) + list(served[:-1])
    rows = np.arange(len(prompt) - 1, len(seq))
    ref = logits_at(seed, c, seq, rows, length=length)
    pick = (jnp.argmax(logits_at(seed, c, seq, rows, lowp=True,
                                 length=length), axis=-1)
            if lowp else jnp.asarray(served))
    got = jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]
    return np.asarray(jnp.max(ref, axis=-1) - got)
