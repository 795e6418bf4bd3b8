"""Attention: GQA/MQA/MHA (chunked online-softmax), DeepSeek-V2 MLA
(naive prefill + absorbed decode), and decode-with-cache paths.

Memory discipline: full (S×T) score matrices are never materialized for long
sequences — ``chunked_attention`` runs an online-softmax scan over KV chunks
inside a scan over Q chunks (flash-attention dataflow in pure JAX; XLA maps
the inner matmuls to the MXU).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp

from ..configs.base import MLAConfig, ModelConfig
from . import common as C
from .common import SiteDef, apply_site, init_site, make_site, rope

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Core chunked attention
# ---------------------------------------------------------------------------

def _attn_one_qchunk(q, k, v, qpos, kpos, *, causal: bool, scale: float,
                     kv_chunk: int, plan=None):
    """Online softmax over KV chunks for one Q chunk.

    q: (B, Sq, Hq, D)   k/v: (B, T, Hkv, D)   qpos: (Sq,)  kpos: (T,)
    returns (B, Sq, Hq, D)

    KV heads are expanded to the full Hq inside the chunk loop so every
    einsum carries the full head dim — under TP the scores/probs buffers
    then shard over ``model`` on heads (GQA's folded (hkv, g) layout blocks
    that and replicates the O(S·ck) buffers on every shard — measured 5×
    memory-term regression; see EXPERIMENTS.md §Perf).
    """
    b, sq, hq, d = q.shape
    t, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    nchunks = t // kv_chunk

    def body(carry, inputs):
        m, l, acc = carry
        kc, vc, kp = inputs                     # (B, ck, Hkv, D), (ck,)
        if g > 1:
            kc = jnp.repeat(kc, g, axis=2)      # (B, ck, Hq, D)
            vc = jnp.repeat(vc, g, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kc,
                       preferred_element_type=jnp.float32) * scale
        mask = jnp.ones((sq, kv_chunk), bool)
        if causal:
            mask = qpos[:, None] >= kp[None, :]
        s = jnp.where(mask[None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p.astype(vc.dtype), vc,
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, hq, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hq, sq), jnp.float32)
    a0 = jnp.zeros((b, hq, sq, d), jnp.float32)
    cons = _chunk_constraint(plan, hq)
    ks = cons(k.reshape(b, nchunks, kv_chunk, hkv, d).swapaxes(0, 1))
    vs = cons(v.reshape(b, nchunks, kv_chunk, hkv, d).swapaxes(0, 1))
    kps = kpos.reshape(nchunks, kv_chunk)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (ks, vs, kps))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def _chunk_constraint(plan, hq: int):
    """Sharding constraint for chunk-stacked attention tensors
    (chunks, B, len, H, D). Without this the reshape+swapaxes around the
    online-softmax scans breaks head-sharding propagation and GSPMD
    replicates Q/K/V on every model shard (measured: 3.2 GB per-layer
    all-gathers on deepseek-v2 — EXPERIMENTS.md §Perf)."""
    if plan is None or plan.mesh is None:
        return lambda x: x
    from jax.sharding import PartitionSpec as P

    def f(x):
        dims = [None] * x.ndim
        dims[1] = plan.dp_axes
        # guard via the plan (dp-only meshes have no "model" axis at all)
        if plan.strategy == "tp" and plan.model_size() > 1 \
                and x.shape[3] % plan.model_size() == 0:
            dims[3] = "model"
        return plan.constrain(x, P(*dims))

    return f


def chunked_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      causal: bool, q_offset: jax.Array | int = 0,
                      q_chunk: int = 512, kv_chunk: int = 1024,
                      plan=None) -> jax.Array:
    """General attention. q: (B,S,Hq,D); k,v: (B,T,Hkv,D)."""
    b, s, hq, d = q.shape
    t = k.shape[1]
    scale = 1.0 / math.sqrt(d)
    q_chunk = min(q_chunk, s)
    kv_chunk = min(kv_chunk, t)
    # pad T to a multiple of kv_chunk (mask handles the tail via kpos >= t)
    t_pad = (-t) % kv_chunk
    if t_pad:
        k = jnp.pad(k, ((0, 0), (0, t_pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, t_pad), (0, 0), (0, 0)))
    kpos = jnp.arange(t + t_pad)
    kpos = jnp.where(kpos < t, kpos, jnp.iinfo(jnp.int32).max)  # mask padding
    if s == q_chunk:
        qpos = q_offset + jnp.arange(s)
        return _attn_one_qchunk(q, k, v, qpos, kpos, causal=causal,
                                scale=scale, kv_chunk=kv_chunk, plan=plan)
    assert s % q_chunk == 0, (s, q_chunk)
    nq = s // q_chunk
    cons = _chunk_constraint(plan, hq)

    # Nested remat: without this, differentiating the scan-of-scans saves
    # every (q-chunk × kv-chunk) probability matrix — an O(S²/chunk²) stack
    # that dominated HBM traffic (1 TB/device/layer-loop on deepseek-v2;
    # EXPERIMENTS.md §Perf iteration 4). Recompute p per chunk instead
    # (flash-attention backward dataflow).
    @partial(jax.checkpoint,
             policy=jax.checkpoint_policies.nothing_saveable)
    def qbody(_, qc_and_idx):
        qc, i = qc_and_idx
        qpos = q_offset + i * q_chunk + jnp.arange(q_chunk)
        out = _attn_one_qchunk(qc, k, v, qpos, kpos, causal=causal,
                               scale=scale, kv_chunk=kv_chunk, plan=plan)
        return None, out

    qs = cons(q.reshape(b, nq, q_chunk, hq, d).swapaxes(0, 1))
    _, outs = jax.lax.scan(qbody, None, (qs, jnp.arange(nq)))
    return cons(outs).swapaxes(0, 1).reshape(b, s, hq, d)


# ---------------------------------------------------------------------------
# GQA block
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class GQADef:
    q: SiteDef
    kv: SiteDef
    o: SiteDef
    num_heads: int          # padded head count used in the attention kernel
    num_kv_heads: int
    head_dim: int
    real_heads: int         # the arch's true head count (= num_heads unless
                            # padded for TP divisibility; pad rows are
                            # zero-init, their outputs are sliced before o,
                            # so their grads are exactly zero — arch-faithful)


def make_gqa(cfg: ModelConfig) -> GQADef:
    hd = cfg.resolved_head_dim
    hq = cfg.num_heads
    pad_to = getattr(cfg, "pad_heads_to", 0)
    hp = max(hq, pad_to) if pad_to else hq
    return GQADef(
        q=make_site(cfg, "attn_qkv", hp * hd, cfg.d_model),
        kv=make_site(cfg, "attn_qkv", 2 * cfg.num_kv_heads * hd, cfg.d_model),
        o=make_site(cfg, "attn_o", cfg.d_model, hq * hd),
        num_heads=hp, num_kv_heads=cfg.num_kv_heads, head_dim=hd,
        real_heads=hq)


def init_gqa(key: jax.Array, d: GQADef, cfg: ModelConfig) -> dict:
    kq, kkv, ko = jax.random.split(key, 3)
    return {"q": init_site(kq, d.q, cfg), "kv": init_site(kkv, d.kv, cfg),
            "o": init_site(ko, d.o, cfg)}


def gqa_qkv(params: dict, x: jax.Array, d: GQADef, cfg: ModelConfig,
            positions: jax.Array):
    b, s, _ = x.shape
    q = apply_site(params["q"], x, d.q, cfg).reshape(b, s, d.num_heads, d.head_dim)
    kv = apply_site(params["kv"], x, d.kv, cfg).reshape(
        b, s, 2, d.num_kv_heads, d.head_dim)
    k, v = kv[:, :, 0], kv[:, :, 1]
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    return q, k, v


def gqa_forward(params: dict, x: jax.Array, d: GQADef, cfg: ModelConfig,
                *, causal: bool, positions: jax.Array, plan=None) -> jax.Array:
    q, k, v = gqa_qkv(params, x, d, cfg, positions)
    out = chunked_attention(q, k, v, causal=causal, plan=plan)
    b, s = x.shape[:2]
    if d.real_heads != d.num_heads:
        out = out[:, :, :d.real_heads]
    return apply_site(params["o"], out.reshape(b, s, -1), d.o, cfg)


def len_positions(cur_len: jax.Array | int, b: int) -> jax.Array:
    """(B,1) query positions from a scalar or per-slot (B,) ``cur_len``."""
    cl = jnp.asarray(cur_len, jnp.int32)
    if cl.ndim == 0:
        return jnp.full((b, 1), cl, jnp.int32)
    return cl.reshape(b, 1)


def cache_append(cache_arr: jax.Array, new: jax.Array,
                 cur_len: jax.Array | int) -> jax.Array:
    """Write one new token at position ``cur_len`` along axis 1.

    cache_arr: (B, T, ...); new: (B, 1, ...). Scalar cur_len keeps the
    cheap dynamic_update_slice; a per-slot (B,) vector uses a one-hot
    scatter (each batch row writes at its own position)."""
    cl = jnp.asarray(cur_len, jnp.int32)
    new = new.astype(cache_arr.dtype)
    if cl.ndim == 0:
        return jax.lax.dynamic_update_slice_in_dim(cache_arr, new, cl, axis=1)
    b = cache_arr.shape[0]
    return cache_arr.at[jnp.arange(b), cl].set(new[:, 0])


def causal_len_mask(qpos: jax.Array, t: int) -> jax.Array:
    """(B, S, T) mask: key position visible iff kpos <= qpos."""
    kpos = jnp.arange(t)
    return kpos[None, None, :] <= qpos[:, :, None]


def gqa_decode_qkv(params: dict, x: jax.Array, d: GQADef, cfg: ModelConfig,
                   positions: jax.Array):
    """Project q and the new k/v for decode / chunked prefill.

    x: (B,S,D); positions: (B,S). Returns q (B,S,Hq,Dh), k/v (B,S,Hkv,Dh)."""
    b, s = x.shape[:2]
    q = apply_site(params["q"], x, d.q, cfg).reshape(b, s, d.num_heads, d.head_dim)
    kv = apply_site(params["kv"], x, d.kv, cfg).reshape(
        b, s, 2, d.num_kv_heads, d.head_dim)
    k_new, v_new = kv[:, :, 0], kv[:, :, 1]
    q = rope(q, positions, cfg.rope_theta)
    k_new = rope(k_new, positions, cfg.rope_theta)
    return q, k_new, v_new


def gqa_attend(q: jax.Array, k: jax.Array, v: jax.Array, d: GQADef,
               qpos: jax.Array) -> jax.Array:
    """Decode-style attention over a full cache with per-row lengths.

    q: (B,S,Hq,Dh); k,v: (B,T,Hkv,Dh); qpos: (B,S) absolute query positions
    (key position kpos attends iff kpos <= qpos). Returns (B,S,real*Dh)."""
    b, s = q.shape[:2]
    t = k.shape[1]
    scale = 1.0 / math.sqrt(d.head_dim)
    g = d.num_heads // d.num_kv_heads
    qg = q.reshape(b, s, d.num_kv_heads, g, d.head_dim)
    sc = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k,
                    preferred_element_type=jnp.float32) * scale
    mask = causal_len_mask(qpos, t)                       # (B, S, T)
    sc = jnp.where(mask[:, None, None], sc, NEG_INF)
    p = jax.nn.softmax(sc, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v.dtype), v)
    out = out.reshape(b, s, d.num_heads, d.head_dim)[:, :, :d.real_heads]
    return out.reshape(b, s, d.real_heads * d.head_dim)


def gqa_decode(params: dict, x: jax.Array, cache: dict, d: GQADef,
               cfg: ModelConfig, cur_len: jax.Array) -> tuple[jax.Array, dict]:
    """One-token decode. x: (B,1,D). cache: {"k","v"}: (B,T,Hkv,Dh).
    ``cur_len``: scalar shared length, or (B,) per-slot lengths."""
    b = x.shape[0]
    positions = len_positions(cur_len, b)
    q, k_new, v_new = gqa_decode_qkv(params, x, d, cfg, positions)
    k = cache_append(cache["k"], k_new, cur_len)
    v = cache_append(cache["v"], v_new, cur_len)
    out = gqa_attend(q, k, v, d, positions)
    y = apply_site(params["o"], out, d.o, cfg)
    return y, {"k": k, "v": v}


def gqa_init_cache(d: GQADef, batch: int, max_len: int, dtype) -> dict:
    return {
        "k": jnp.zeros((batch, max_len, d.num_kv_heads, d.head_dim), dtype),
        "v": jnp.zeros((batch, max_len, d.num_kv_heads, d.head_dim), dtype),
    }


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2) block
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class MLADef:
    q_down: SiteDef | None  # x -> q_lora (None without a q low-rank)
    q_up: SiteDef | None    # q_lora -> H * (nope + rope)
    q: SiteDef | None       # x -> H * (nope + rope), when q_lora_rank is None
    kv_down: SiteDef        # -> kv_lora + rope dim
    k_up: SiteDef           # kv_lora -> H * qk_nope
    v_up: SiteDef           # kv_lora -> H * v_head
    o: SiteDef
    num_heads: int
    m: MLAConfig


def make_mla(cfg: ModelConfig) -> MLADef:
    """DeepSeek-V2 MLA; with ``q_lora_rank`` None (Moonlight, DeepSeek-V2-
    Lite) q is one projection of x, with no q norm."""
    m = cfg.mla
    h = cfg.num_heads
    qdim = h * (m.qk_nope_head_dim + m.qk_rope_head_dim)
    low = m.q_lora_rank is not None
    return MLADef(
        q_down=make_site(cfg, "attn_qkv", m.q_lora_rank, cfg.d_model)
        if low else None,
        q_up=make_site(cfg, "attn_qkv", qdim, m.q_lora_rank) if low else None,
        q=None if low else make_site(cfg, "attn_qkv", qdim, cfg.d_model),
        kv_down=make_site(cfg, "attn_qkv", m.kv_lora_rank + m.qk_rope_head_dim,
                          cfg.d_model),
        k_up=make_site(cfg, "attn_qkv", h * m.qk_nope_head_dim, m.kv_lora_rank),
        v_up=make_site(cfg, "attn_qkv", h * m.v_head_dim, m.kv_lora_rank),
        o=make_site(cfg, "attn_o", cfg.d_model, h * m.v_head_dim),
        num_heads=h, m=m)


def init_mla(key: jax.Array, d: MLADef, cfg: ModelConfig) -> dict:
    ks = jax.random.split(key, 8)
    p = {}
    if d.q is None:
        p["q_down"] = init_site(ks[0], d.q_down, cfg)
        p["q_norm"] = {"scale": jnp.ones((d.m.q_lora_rank,), jnp.float32)}
        p["q_up"] = init_site(ks[1], d.q_up, cfg)
    else:
        p["q"] = init_site(ks[0], d.q, cfg)
    return {
        **p,
        "kv_down": init_site(ks[2], d.kv_down, cfg),
        "kv_norm": {"scale": jnp.ones((d.m.kv_lora_rank,), jnp.float32)},
        "k_up": init_site(ks[3], d.k_up, cfg),
        "v_up": init_site(ks[4], d.v_up, cfg),
        "o": init_site(ks[5], d.o, cfg),
    }


def _mla_q(params, x, d: MLADef, cfg, positions):
    b, s, _ = x.shape
    m = d.m
    if d.q is None:
        cq = apply_site(params["q_down"], x, d.q_down, cfg)
        cq = C.rms_norm(cq, params["q_norm"]["scale"], cfg.norm_eps)
        q = apply_site(params["q_up"], cq, d.q_up, cfg)
    else:
        q = apply_site(params["q"], x, d.q, cfg)
    q = q.reshape(b, s, d.num_heads, m.qk_nope_head_dim + m.qk_rope_head_dim)
    q_nope, q_rope = q[..., :m.qk_nope_head_dim], q[..., m.qk_nope_head_dim:]
    q_rope = rope(q_rope, positions, cfg.rope_theta)
    return q_nope, q_rope


def _mla_kv_latent(params, x, d: MLADef, cfg, positions):
    m = d.m
    ckv = apply_site(params["kv_down"], x, d.kv_down, cfg)
    c_kv, k_rope = ckv[..., :m.kv_lora_rank], ckv[..., m.kv_lora_rank:]
    c_kv = C.rms_norm(c_kv, params["kv_norm"]["scale"], cfg.norm_eps)
    k_rope = rope(k_rope[:, :, None, :], positions, cfg.rope_theta)[:, :, 0]
    return c_kv, k_rope


def mla_forward(params: dict, x: jax.Array, d: MLADef, cfg: ModelConfig, *,
                causal: bool, positions: jax.Array, plan=None) -> jax.Array:
    """Prefill/train path: reconstruct per-head K/V, run chunked attention."""
    b, s, _ = x.shape
    m = d.m
    q_nope, q_rope = _mla_q(params, x, d, cfg, positions)
    c_kv, k_rope = _mla_kv_latent(params, x, d, cfg, positions)
    k_nope = apply_site(params["k_up"], c_kv, d.k_up, cfg).reshape(
        b, s, d.num_heads, m.qk_nope_head_dim)
    v = apply_site(params["v_up"], c_kv, d.v_up, cfg).reshape(
        b, s, d.num_heads, m.v_head_dim)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                  (b, s, d.num_heads, m.qk_rope_head_dim))],
        axis=-1)
    if plan is not None:
        q = plan.heads_act(q)
        k = plan.heads_act(k)
        v = plan.heads_act(v)
    # pad v's head dim to match q/k for the shared kernel, slice after
    out = chunked_attention(q, k, jnp.pad(v, ((0, 0), (0, 0), (0, 0),
                                              (0, q.shape[-1] - v.shape[-1]))),
                            causal=causal, plan=plan)
    out = out[..., :m.v_head_dim].reshape(b, s, -1)
    return apply_site(params["o"], out, d.o, cfg)


def _absorb_weight(psite: dict, site, cfg: ModelConfig) -> jax.Array:
    """Dense (in, out) weight of a site, materializing TT factors if needed."""
    if "w" in psite:
        return psite["w"]
    from ..core import tt_layer as TL
    from ..core.ttm import ttm_to_dense
    cores = TL.effective_cores(psite, site.spec, cfg.tt, cfg.quant)
    return ttm_to_dense(cores, site.spec).T


def mla_decode_q(params: dict, x: jax.Array, d: MLADef, cfg: ModelConfig,
                 positions: jax.Array):
    """Absorbed decode queries. x: (B,S,D); positions (B,S).
    Returns q_abs (B,S,H,kv_lora) and q_rope (B,S,H,rope)."""
    m = d.m
    q_nope, q_rope = _mla_q(params, x, d, cfg, positions)
    # absorb k_up into q: q_abs = q_nope @ Wk^T per head
    wk = _absorb_weight(params["k_up"], d.k_up, cfg)  # (kv_lora, H*nope)
    wk = wk.reshape(m.kv_lora_rank, d.num_heads, m.qk_nope_head_dim)
    q_abs = jnp.einsum("bqhd,lhd->bqhl", q_nope, wk.astype(q_nope.dtype))
    return q_abs, q_rope


def mla_attend(params: dict, q_abs: jax.Array, q_rope: jax.Array,
               ckv: jax.Array, kr: jax.Array, d: MLADef, cfg: ModelConfig,
               qpos: jax.Array) -> jax.Array:
    """Latent-space attention. ckv: (B,T,kv_lora); kr: (B,T,rope);
    qpos: (B,S). Returns (B,S,H*v_head) pre-o-proj."""
    m = d.m
    t = ckv.shape[1]
    s_nope = jnp.einsum("bqhl,btl->bhqt", q_abs, ckv,
                        preferred_element_type=jnp.float32)
    s_rope = jnp.einsum("bqhd,btd->bhqt", q_rope, kr,
                        preferred_element_type=jnp.float32)
    sc = (s_nope + s_rope) * mla_softmax_scale(m)
    mask = causal_len_mask(qpos, t)                       # (B, S, T)
    sc = jnp.where(mask[:, None], sc, NEG_INF)
    p = jax.nn.softmax(sc, axis=-1)
    out_lat = jnp.einsum("bhqt,btl->bqhl", p.astype(ckv.dtype), ckv)
    return mla_value_up(params, out_lat, d, cfg)


def mla_softmax_scale(m: MLAConfig) -> float:
    """Score scale of absorbed MLA: 1/sqrt of the un-absorbed qk width."""
    return 1.0 / math.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim)


def mla_value_up(params: dict, out_lat: jax.Array, d: MLADef,
                 cfg: ModelConfig) -> jax.Array:
    """Latent attention output (B,S,H,kv_lora) -> per-head values through
    ``v_up``, (B,S,H*v_head) pre-o-proj."""
    m = d.m
    b, s = out_lat.shape[:2]
    wv = _absorb_weight(params["v_up"], d.v_up, cfg)
    wv = wv.reshape(m.kv_lora_rank, d.num_heads, m.v_head_dim)
    out = jnp.einsum("bqhl,lhd->bqhd", out_lat, wv.astype(out_lat.dtype))
    return out.reshape(b, s, -1)


def mla_decode(params: dict, x: jax.Array, cache: dict, d: MLADef,
               cfg: ModelConfig, cur_len: jax.Array) -> tuple[jax.Array, dict]:
    """Absorbed decode (beyond-paper efficiency, standard MLA practice):
    scores and values computed in the 512-d latent space; cache holds only
    (c_kv, k_rope) — the MLA memory win. ``cur_len``: scalar or (B,)."""
    b = x.shape[0]
    positions = len_positions(cur_len, b)
    q_abs, q_rope = mla_decode_q(params, x, d, cfg, positions)
    c_new, kr_new = _mla_kv_latent(params, x, d, cfg, positions)
    ckv = cache_append(cache["c_kv"], c_new, cur_len)
    kr = cache_append(cache["k_rope"], kr_new, cur_len)
    out = mla_attend(params, q_abs, q_rope, ckv, kr, d, cfg, positions)
    y = apply_site(params["o"], out, d.o, cfg)
    return y, {"c_kv": ckv, "k_rope": kr}


def mla_init_cache(d: MLADef, batch: int, max_len: int, dtype) -> dict:
    return {
        "c_kv": jnp.zeros((batch, max_len, d.m.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((batch, max_len, d.m.qk_rope_head_dim), dtype),
    }
