"""Fused paged-attention q-block kernel with in-kernel int8 dequantization.

The serve engine's hottest path used to gather every slot's *entire*
dequantized cache view (``kv_cache.gather_slots``: (B, max_len, *feat) fp32
per layer per tensor) before attending.  This module fuses the three steps —
page gather, pow-2 dequantize, attention — into one pass that walks each
slot's page list and accumulates online-softmax attention per page, so the
full-precision slot view is never materialized (the paper's §3.2 point that
low-precision storage only pays off when dequantization lives inside the
compute path; Tian et al. 2501.06663 make the same argument for transformer
attention caches).

The walk carries a q-block: S query rows per slot at consecutive positions
``lens[b] .. lens[b] + S - 1`` with a per-row causal length mask, so ONE
kernel serves single-token decode (S=1, the original dataflow), chunked
prefill (S=chunk), and k-token speculative verification (S=k+1).

Two implementations of the same dataflow:

- ``paged_attention_kernel``: the Pallas kernel.  Grid ``(num_slots,
  pages_per_slot)`` with the page table and length vector as scalar-prefetch
  operands — the BlockSpec index map chases the slot's page pointers, so
  each grid step DMAs exactly one int8 K and V page into VMEM, dequantizes
  with the slot's pow-2 scale in-register, and folds the page into the
  (m, l, acc) online-softmax state (one column per q row and query head,
  see ``_block_update``) held in VMEM scratch.  Grid steps for pages
  entirely above the block's LAST row (``lens[slot] + S - 1``) are
  predicated out (``pl.when``): a fully-masked
  page is the exact identity update, so short slots in a ragged batch skip
  their tail pages' dequant + MXU work for free (the grid is sized by
  ``pages_per_slot``, i.e. the longest possible slot).  Runs compiled on
  TPU; in interpret mode everywhere else (the differential-test oracle mode
  — see tests/test_paged_attention.py).
- ``paged_attention_jnp``: the identical page-walk written as a
  ``jax.lax.scan`` over pages in plain jnp.  Same per-page dequant, same
  online-softmax update order, so it is bit-locked against the kernel (the
  tests assert exact equality).  It is the engine's fused path off-TPU,
  where interpret-mode grid iteration would serialize poorly.

Numerics contract: per slot, query row j computes softmax(q_j·K^T * scale,
masked to ``pos <= lens[slot] + j``) @ V with KV heads expanded to the
query head count — the same math as ``gather_slots`` + ``models/attention
.py::gqa_attend`` with ``qpos = lens[slot] + j``, evaluated in f32 with an
online (per-page) softmax instead of a full-T one.  Greedy decode is
token-identical to the gather path; logits agree to float-roundoff
(asserted differentially).

Head-sharding contract: every head is independent (GQA groups the query
heads contiguously per KV head), so when the pool's KV-head axis is sharded
over the ``model`` mesh axis (``ShardPlan.shards_kv_heads``) the dispatcher
in ``ops.paged_attention`` shard_maps this walk — each device runs the
SAME kernel on its local head slice with zero collectives, and the
numerics above hold per shard unchanged.  Nothing in this module is
mesh-aware; the table/lens operands are replicated and page ids are global
(the page axis is never sharded).

Layouts (one attention sublayer, one layer of the scanned stack):

- q:        (B, S, Hq, Dh) f32 — S-row q-block per slot; a rank-3
            (B, Hq, Dh) q is accepted as the S=1 decode case and the
            result is returned rank-3 to match
- k/v data: (P+1, page, Hkv, Dh) int8 codes (quantized pool) or fp values;
            row ``P`` is the trash page absorbing inactive-slot writes.
            Or the stacked pool leaf (L, P+1, page, Hkv, Dh) with a
            ``layer`` scalar: the walk reads layer ``layer``'s pages
            straight out of the stack, so the caller never slices a
            per-layer slab. The decode step carries the whole pool through
            its layer scan and updates it in place; slicing a slab per
            layer for the kernel's operand (or scanning the pool as xs/ys)
            would copy the pool every step
- layer:    () int32, the stacked form only. The Pallas kernel takes it as
            a third scalar-prefetch operand and its page index map becomes
            ``(layer, tab[b, p], 0, 0, 0)`` with the layer dim squeezed,
            so the body sees the same (1, page, Hkv, Dh) page block
- scale:    (B,) f32 per-slot ``scale_log2`` (pow-2 grid, kv_cache site)
- table:    (B, pages_per_slot) int32 physical page ids (trash when unmapped)
- lens:     (B,) int32 position of the FIRST query row (row j attends keys
            at pos <= lens + j; unmapped pages sit entirely above the last
            row, so the mask also excludes trash-page junk for active slots)

TPU note: the kernel compiles for v5e at internlm2-1.8b widths (Hkv=8,
Dh=128, page 16, int8 and bf16 pages; tests/test_tpu_compile.py); the
interpret path takes any shape.  The wrapper in ``kernels/ops.py`` picks
the implementation and leaves the pool layout untouched — padding the pool
per step would re-materialize exactly the traffic this kernel exists to
avoid.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _norm_q(q: jax.Array):
    """Accept (B, Hq, Dh) [legacy S=1 decode] or (B, S, Hq, Dh); return the
    rank-4 view plus whether to squeeze the S axis back out of the result."""
    if q.ndim == 3:
        return q[:, None], True
    if q.ndim == 4:
        return q, False
    raise ValueError(f"q must be rank 3 or 4, got {q.shape}")


def _block_update(m, l, acc, qt, k, v, base_pos, first, scale, *, g):
    """One online-softmax step, shared VERBATIM by the Pallas kernel body
    (one slot, one page) and the jnp page-scan (a leading batch dim, a
    chunk of pages) — identical contractions modulo that leading dim, which
    the CPU/interpret lowering treats as an outer loop, is what keeps the
    two implementations bitwise-locked.

    Query-minor, head-major layout, so every contraction is a plain
    (rows x K) @ (K x cols) matmul with ONE batch dim, the KV head (the
    TPU's Mosaic matmul supports no more): qt (..., Hkv, Dh, M) holds KV
    head h's g query heads for every q row as columns, column c = s*g + j
    at position ``first + s``; k/v (..., Hkv, n, Dh) hold n key positions
    from ``base_pos``. Scores come out transposed, (..., Hkv, n, M), and
    the softmax runs over the key axis -2. KV heads are never expanded.
    (The (keys x K) @ (K x queries) form is also the one XLA's CPU backend
    lowers ``models/attention.py::gqa_attend``'s grouped einsums to, so the
    off-TPU fused path and the gather path round alike.)

    first: the slot's first q-row position, broadcastable against
    (..., 1, 1, 1); m/l: (..., Hkv, 1, M); acc: (..., Hkv, Dh, M)."""
    n, cols = k.shape[-2], qt.shape[-1]
    s = jnp.einsum("...hnd,...hdm->...hnm", k, qt,
                   preferred_element_type=jnp.float32) * scale
    kpos = base_pos + jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0)
    qrow = jax.lax.broadcasted_iota(jnp.int32, (1, cols), 1) // g
    s = jnp.where(kpos <= first + qrow, s, NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=-2, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m - m_new)
    l_new = l * corr + jnp.sum(p, axis=-2, keepdims=True)
    acc_new = acc * corr + jnp.einsum(
        "...hdn,...hnm->...hdm", jnp.swapaxes(v, -1, -2), p,
        preferred_element_type=jnp.float32)
    return m_new, l_new, acc_new


def _to_cols(q: jax.Array, hkv: int) -> jax.Array:
    """(B, S, Hq, Dh) -> (B, Hkv, Dh, S*g): each KV head's query heads as
    columns (GQA groups the query heads contiguously per KV head)."""
    b, sq, hq, dh = q.shape
    g = hq // hkv
    q = q.reshape(b, sq, hkv, g, dh).transpose(0, 2, 4, 1, 3)
    return q.reshape(b, hkv, dh, sq * g)


def _from_cols(o: jax.Array, sq: int) -> jax.Array:
    """Inverse of ``_to_cols``."""
    b, hkv, dh, cols = o.shape
    g = cols // sq
    o = o.reshape(b, hkv, dh, sq, g).transpose(0, 3, 1, 4, 2)
    return o.reshape(b, sq, hkv * g, dh)


def _pow2(scale_log2) -> jax.Array:
    return jnp.exp2(jnp.asarray(scale_log2, jnp.float32))


# ---------------------------------------------------------------------------
# Pallas kernel
# ---------------------------------------------------------------------------

def _pa_kernel(tab_ref, lens_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref,
               m_ref, l_ref, acc_ref, *, page_size: int, num_pages: int,
               quantized: bool, scale: float, groups: int, q_rows: int):
    b, p = pl.program_id(0), pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # per-slot early exit: pages whose first position sits above the LAST
    # q-block row (lens + S - 1) carry no attendable keys — every score
    # would mask to NEG_INF, making the online-softmax update the exact
    # identity (m_new = m, corr = 1, p = exp(NEG_INF - m) = 0), so
    # predicating the whole update out is bitwise-free and skips the dequant
    # + MXU work for short slots in a long-slot batch (the grid is sized by
    # the longest).
    @pl.when(p * page_size <= lens_ref[b] + (q_rows - 1))
    def _update():
        k = k_ref[0].astype(jnp.float32)                # (page, Hkv, Dh)
        v = v_ref[0].astype(jnp.float32)
        if quantized:
            # in-kernel pow-2 dequant: one multiply per element by the
            # slot's 2^scale (exponentiated by the wrapper), straight from
            # the int8 page in VMEM — no fp32 page round-trips through HBM
            k = k * ks_ref[b]
            v = v * vs_ref[b]
        m_new, l_new, acc_new = _block_update(
            m_ref[...], l_ref[...], acc_ref[...],
            q_ref[0].astype(jnp.float32), jnp.swapaxes(k, 0, 1),
            jnp.swapaxes(v, 0, 1), p * page_size, lens_ref[b], scale,
            g=groups)
        m_ref[...] = m_new
        l_ref[...] = l_new
        acc_ref[...] = acc_new

    @pl.when(p == num_pages - 1)
    def _emit():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                    ).astype(o_ref.dtype)


def _pa_kernel_layered(tab_ref, lens_ref, layer_ref, *refs, **kw):
    """``_pa_kernel`` for the stacked pool: the layer scalar is consumed by
    the page BlockSpec's index map, so the body never reads it."""
    del layer_ref
    _pa_kernel(tab_ref, lens_ref, *refs, **kw)


def paged_attention_kernel(q: jax.Array, kdata: jax.Array, vdata: jax.Array,
                           kscale: jax.Array, vscale: jax.Array,
                           table: jax.Array, lens: jax.Array, *,
                           page_size: int, quantized: bool,
                           interpret: bool = False,
                           layer: jax.Array | None = None) -> jax.Array:
    """Fused paged attention via Pallas. Shapes per module docstring
    (k/v per layer, or stacked with ``layer``); returns (B, S, Hq, Dh) in
    q.dtype ((B, Hq, Dh) for rank-3 q)."""
    q, squeeze = _norm_q(q)
    b, sq, hq, dh = q.shape
    pp = table.shape[1]
    hkv = kdata.shape[-2]
    assert hq % hkv == 0, (hq, hkv)
    cols = sq * (hq // hkv)
    # the page-pointer chase: block (pi of slot bi) is physical page
    # tab[bi, pi] — unmapped entries point at the trash page, whose
    # positions all sit above lens[bi] and mask to NEG_INF
    if layer is None:
        prefetch = (table, lens)
        page_spec = pl.BlockSpec((1, page_size, hkv, dh),
                                 lambda bi, pi, tab, ln: (tab[bi, pi], 0, 0, 0))
        body = _pa_kernel
    else:
        prefetch = (table, lens, jnp.reshape(layer, (1,)).astype(jnp.int32))
        page_spec = pl.BlockSpec(
            (None, 1, page_size, hkv, dh),
            lambda bi, pi, tab, ln, lay: (lay[0], tab[bi, pi], 0, 0, 0))
        body = _pa_kernel_layered
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),  # page table + lengths (+ layer)
        grid=(b, pp),
        in_specs=[
            pl.BlockSpec((1, hkv, dh, cols),
                         lambda bi, pi, *_: (bi, 0, 0, 0)),
            page_spec,
            page_spec,
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((1, hkv, dh, cols),
                               lambda bi, pi, *_: (bi, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((hkv, 1, cols), jnp.float32),    # running max
            pltpu.VMEM((hkv, 1, cols), jnp.float32),    # running denom
            pltpu.VMEM((hkv, dh, cols), jnp.float32),   # running numerator
        ],
    )
    kern = functools.partial(
        body, page_size=page_size, num_pages=pp, quantized=quantized,
        scale=1.0 / math.sqrt(dh), groups=hq // hkv, q_rows=sq)
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, dh, cols), q.dtype),
        interpret=interpret,
    )(*prefetch, _to_cols(q, hkv), kdata, vdata,
      _pow2(kscale), _pow2(vscale))
    out = _from_cols(out, sq)
    return out[:, 0] if squeeze else out


# ---------------------------------------------------------------------------
# jnp page-scan — the same dataflow in XLA (engine fallback off-TPU)
# ---------------------------------------------------------------------------

def paged_attention_jnp(q: jax.Array, kdata: jax.Array, vdata: jax.Array,
                        kscale: jax.Array, vscale: jax.Array,
                        table: jax.Array, lens: jax.Array, *,
                        page_size: int, quantized: bool,
                        page_chunk: int = 1,
                        layer: jax.Array | None = None) -> jax.Array:
    """Page-walk online-softmax q-block attention as a ``lax.scan`` over the
    page axis, in plain jnp.  Per step it loads ``page_chunk`` int8 pages
    per slot, dequantizes, and folds them into the (m, l, acc) state.  With
    ``page_chunk=1`` this is the kernel's exact per-page update order (the
    bit-lock the differential tests assert); larger chunks amortize the
    scan's dispatch overhead on non-TPU backends while peak residency stays
    bounded by the chunk — the (B, max_len, *feat) fp32 slot view is never
    materialized either way.  KV heads are never expanded: queries and
    pages take the kernel's layout (``_block_update``). With ``layer`` the
    k/v operands are the stacked (L, P+1, page, Hkv, Dh) pool and each step
    gathers ``kdata[layer, pages]``."""
    q, squeeze = _norm_q(q)
    b, sq, hq, dh = q.shape
    pp = table.shape[1]
    hkv = kdata.shape[-2]
    scale = 1.0 / math.sqrt(dh)
    c = max(1, min(page_chunk, pp))
    nsteps = -(-pp // c)
    # rebalance the chunk so tail padding stays minimal (36 pages at chunk
    # 16 would pad to 48 — 33% wasted positions; balanced: 3 chunks of 12,
    # zero pad). page_chunk=1 is unaffected (nsteps == pp), preserving the
    # bit-lock against the kernel.
    c = -(-pp // nsteps)
    if nsteps * c != pp:
        # pad the logical page axis with trash-page pointers; their
        # positions sit above every slot's length and mask to NEG_INF
        trash = kdata.shape[-4] - 1
        table = jnp.pad(table, ((0, 0), (0, nsteps * c - pp)),
                        constant_values=trash)
    qt = _to_cols(q.astype(jnp.float32), hkv)
    ks = _pow2(kscale)[:, None, None, None, None]
    vs = _pow2(vscale)[:, None, None, None, None]
    first = lens[:, None, None, None]
    n = c * page_size

    def heads(x):                               # (B, c, page, Hkv, Dh) ->
        return x.reshape(b, n, hkv, dh).transpose(0, 2, 1, 3)  # (B,Hkv,n,Dh)

    def body(carry, step):
        m, l, acc = carry
        pages = jax.lax.dynamic_slice_in_dim(table, step * c, c, axis=1)
        idx = pages if layer is None else (layer, pages)
        k = kdata[idx].astype(jnp.float32)
        v = vdata[idx].astype(jnp.float32)
        if quantized:
            k = k * ks
            v = v * vs
        return _block_update(m, l, acc, qt, heads(k), heads(v), step * n,
                             first, scale, g=hq // hkv), None

    m0 = jnp.full((b, hkv, 1, qt.shape[-1]), NEG_INF, jnp.float32)
    l0 = jnp.zeros(m0.shape, jnp.float32)
    a0 = jnp.zeros(qt.shape, jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), jnp.arange(nsteps))
    out = _from_cols((acc / jnp.maximum(l, 1e-30)).astype(q.dtype), sq)
    return out[:, 0] if squeeze else out
