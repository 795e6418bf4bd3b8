"""Latent-space paged attention for MLA decode, straight off the paged pool.

MLA (DeepSeek-V2 latent attention) caches one latent row ``c_kv`` and one
rope key ``k_rope`` per token, shared by every head. Absorbed decode scores
each head's ``q_abs`` (the query with ``k_up`` folded in) against ``c_kv``
and its ``q_rope`` against ``k_rope``, and sums the softmax-weighted
``c_kv`` rows in the latent space; the value up-projection (``v_up``) runs
after, in XLA. The gather path (``kv_cache.gather_slots`` +
``models/attention.py::mla_attend``) materialises every slot's whole latent
history, ``pages_per_slot`` pages, each layer. This walk reads only each
slot's live pages and never builds the (B, max_len, d) slot view.

Two implementations of one dataflow, bit-locked (the tests assert exact
equality of the kernel in interpret mode and the scan):

- ``mla_paged_attention_kernel``: the Pallas kernel. Grid over slots. A
  slot walks its live pages only, ``lens[b] // page + 1`` of them, in
  compute blocks of ``BLOCK_PAGES`` pages, as a ``fori_loop`` in the
  kernel; an inactive slot walks none and emits zeros. Pages are copied
  with manual DMAs from the stacked pool leaf in HBM at ``[layer,
  table[b, p]]`` into a two-deep VMEM buffer: the copy of the next block
  (of this slot, or the first block of the next active slot) is in flight
  while the current block computes. Pages of the last block beyond the
  slot's live pages are not copied; the length mask covers their rows.
- ``mla_paged_attention_jnp``: the same blocks as a ``lax.fori_loop`` over
  compute blocks for all slots at once, in plain jnp, up to the longest
  slot's block count (a slot past its own count keeps its state). The
  engine's walk off-TPU.

Layouts (one MLA sublayer; H heads, latent width dc, rope width dr):

- q_abs:  (B, H, dc), q_rope: (B, H, dr), in the compute dtype (bf16 on
          the chip, f32 in the CPU tests); pages are cast to it.
- ckv, krope: the stacked pool leaves (L, P+1, *page_shape) with a
          ``layer`` index, int8 codes or model-dtype values, in the
          ``kv_cache.page_shape`` layout: (page, d), or, for d under 128
          lanes, the page's tokens packed f = 128 / d to a row, (page / f,
          128), token f*r + j in lanes [j*d, (j+1)*d) of row r. Row P is
          the trash page.
- cscale, rscale: (B,) per-slot ``scale_log2`` of each leaf.
- table:  (B, pages_per_slot) int32 page ids (trash where unmapped).
- lens:   (B,) position of the new token (appended before the walk);
          keys at pos <= lens[b] are attended. active: (B,) bool.
- out:    (B, H, dc) latent output in q_abs.dtype (zeros for inactive
          slots).

Contraction layout: keys on the streaming side of the MXU, (keys x K) @
(K x heads), as ``paged_attention._block_update``. A packed leaf is scored
row-wise against a block-diagonal query, (rows x f*d) @ (f*d x f*H), whose
column group j holds the scores of tokens f*r + j; the kernel interleaves
the groups into token order with strided stores (the scan with a reshape,
the same moves). The value sum of a packed ``c_kv`` leaf (the reduced
test configs) takes the transposed route: p de-interleaved into groups,
accumulated into an (f*dc x f*H) block whose diagonal blocks are summed
at the end.

Numerics contract: the same math as the gather path, no weaker. An int8
code times its pow-2 scale is exact in bf16, and scaling an f32 sum by a
power of two is exact, so the walk casts codes to the compute dtype and
applies each slot's scale to the f32 scores and, once, to the latent
output: the dequantised values, rounded the same. Scores accumulate in
f32, ``(c_kv . q_abs * 2^sc + k_rope . q_rope * 2^sr) * sm_scale``; the
softmax runs online in f32; ``p`` is cast to the compute dtype for the
value product, which accumulates in f32. No key at or below a slot's
length is skipped.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .paged_attention import NEG_INF, _pow2

# pages in one compute block: 128 keys at pages of 16
BLOCK_PAGES = 8


def _packing(page_shape: tuple[int, ...], page_size: int) -> tuple[int, int]:
    """(tokens per stored row f, per-token width d) of one pool page."""
    rows, lanes = page_shape
    assert page_size % rows == 0, (page_shape, page_size)
    f = page_size // rows
    return f, lanes // f


def _block_diag(qt: jax.Array, f: int) -> jax.Array:
    """(B, d, H) -> (B, f*d, f*H) with ``qt`` on the f diagonal blocks."""
    if f == 1:
        return qt
    b, d, h = qt.shape
    out = jnp.zeros((b, f, d, f, h), qt.dtype)
    for j in range(f):
        out = out.at[:, j, :, j, :].set(qt)
    return out.reshape(b, f * d, f * h)


def _interleave_reshape(s: jax.Array, f: int, h: int) -> jax.Array:
    """(..., R, f*h) column groups -> (..., R*f, h) token order."""
    return s.reshape(s.shape[:-2] + (s.shape[-2] * f, h))


def _deinterleave_reshape(p: jax.Array, f: int) -> jax.Array:
    """Inverse of ``_interleave_reshape``."""
    n, h = p.shape[-2:]
    return p.reshape(p.shape[:-2] + (n // f, f * h))


def _block_update(m, l, acc, c, r, qc, qr, cs, rs, base, lens, *,
                  sm_scale: float, fc: int, fr: int, h: int,
                  interleave, deinterleave):
    """Fold one compute block of keys into the online-softmax state, shared
    verbatim by the kernel body (one slot) and the scan (a leading slot
    dim). c: (..., Rc, fc*dc) and r: (..., Rr, fr*dr) stored rows in the
    compute dtype; qc/qr their block-diagonal queries; cs/rs the slots'
    2^scale, broadcastable against (..., n, h); base the block's first
    position and lens the slots' lengths, broadcastable against (..., n,
    1). m/l: (..., 1, h); acc: (..., fc*dc, fc*h)."""
    def scores(rows, q, f):
        s = jnp.einsum("...rk,...kc->...rc", rows, q,
                       preferred_element_type=jnp.float32)
        return s if f == 1 else interleave(s, f, h)

    s = (scores(c, qc, fc) * cs + scores(r, qr, fr) * rs) * sm_scale
    n = s.shape[-2]
    pos = base + jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0)
    s = jnp.where(pos <= lens, s, NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=-2, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m - m_new)
    l_new = l * corr + jnp.sum(p, axis=-2, keepdims=True)
    pf = p if fc == 1 else deinterleave(p, fc)
    if fc > 1:
        corr = jnp.concatenate([corr] * fc, axis=-1)
    acc_new = acc * corr + jnp.einsum(
        "...rk,...rc->...kc", c, pf.astype(c.dtype),
        preferred_element_type=jnp.float32)
    return m_new, l_new, acc_new


def _emit(acc, l, cs, *, fc: int, h: int):
    """(..., fc*dc, fc*h) accumulator -> (..., dc, h) latent output."""
    if fc > 1:
        dc = acc.shape[-2] // fc
        acc = sum(acc[..., j * dc:(j + 1) * dc, j * h:(j + 1) * h]
                  for j in range(fc))
    return acc * cs / jnp.maximum(l, 1e-30)


def _operands(q_abs, q_rope, ckv, krope, cscale, rscale, table, lens,
              active, page_size, quantized):
    """What both implementations share: packings, block-diagonal queries,
    2^scales, the table padded to whole blocks, and each slot's live page
    and block counts."""
    b, h, _ = q_abs.shape
    fc, _ = _packing(ckv.shape[-2:], page_size)
    fr, _ = _packing(krope.shape[-2:], page_size)
    pp = table.shape[1]
    bp = min(BLOCK_PAGES, pp)
    ppad = -(-pp // bp) * bp
    if ppad != pp:
        table = jnp.pad(table, ((0, 0), (0, ppad - pp)),
                        constant_values=ckv.shape[1] - 1)
    dt = q_abs.dtype
    qc = _block_diag(jnp.swapaxes(q_abs, 1, 2), fc)
    qr = _block_diag(jnp.swapaxes(q_rope, 1, 2), fr)
    if quantized:
        cs, rs = _pow2(cscale), _pow2(rscale)
    else:
        cs = rs = jnp.ones((b,), jnp.float32)
    npages = jnp.where(active, lens // page_size + 1, 0).astype(jnp.int32)
    nblk = -(-npages // bp)
    return dict(b=b, h=h, fc=fc, fr=fr, bp=bp, ppad=ppad, dt=dt, qc=qc,
                qr=qr.astype(dt), cs=cs, rs=rs, table=table,
                npages=npages, nblk=nblk)


# ---------------------------------------------------------------------------
# Pallas kernel
# ---------------------------------------------------------------------------

def _mla_kernel(nblk_ref, cum_ref, nxt_ref, npg_ref, lens_ref, tab_ref,
                lay_ref, qc_ref, qr_ref, cs_ref, rs_ref, ckv_hbm, kr_hbm,
                o_ref, cbuf, rbuf, sems, m_ref, l_ref, acc_ref, s_ref, *,
                page_size: int, bp: int, ppad: int, sm_scale: float,
                fc: int, fr: int, h: int, dt):
    b = pl.program_id(0)
    num_slots = pl.num_programs(0)
    layer = lay_ref[0]

    @pl.when(b == 0)
    def _zero_buffers():
        # rows of the last block past a slot's live pages are not copied
        # and the length mask drops their scores; zeroed once, the buffers
        # hold finite values there (p = 0 times them adds nothing)
        cbuf[...] = jnp.zeros_like(cbuf)
        rbuf[...] = jnp.zeros_like(rbuf)

    def copies(slot, blk, buf):
        """(live, copy) for every page of block ``blk`` of ``slot``."""
        out = []
        for i in range(bp):
            pg = blk * bp + i
            page = tab_ref[slot * ppad + pg]
            live = pg < npg_ref[slot]
            out.append((live, pltpu.make_async_copy(
                ckv_hbm.at[layer, page], cbuf.at[buf, i], sems.at[0, buf])))
            out.append((live, pltpu.make_async_copy(
                kr_hbm.at[layer, page], rbuf.at[buf, i], sems.at[1, buf])))
        return out

    def start(slot, blk, buf):
        for live, cp in copies(slot, blk, buf):
            pl.when(live)(cp.start)

    def wait(slot, blk, buf):
        for live, cp in copies(slot, blk, buf):
            pl.when(live)(cp.wait)

    def interleave(s, f, hh):
        # column group j of the row scores -> rows j, j+f, ... in token
        # order: strided stores into the score scratch
        rows = s.shape[0]
        for j in range(f):
            s_ref[pl.ds(j, rows, stride=f), :] = s[:, j * hh:(j + 1) * hh]
        return s_ref[...]

    def deinterleave(p, f):
        s_ref[...] = p
        rows = p.shape[0] // f
        return jnp.concatenate([s_ref[pl.ds(j, rows, stride=f), :]
                                for j in range(f)], axis=-1)

    n = nblk_ref[b]

    @pl.when(n > 0)
    def _walk():
        # the first active slot starts its own first block; every later
        # one finds it in flight, started by the slot before it
        pl.when(cum_ref[b] == 0)(lambda: start(b, 0, 0))
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        cs, rs, ln = cs_ref[b], rs_ref[b], lens_ref[b]

        def body(k, carry):
            buf = (cum_ref[b] + k) % 2
            last = k + 1 >= n
            nslot = jnp.where(last, nxt_ref[b], b)
            nblk = jnp.where(last, 0, k + 1)
            pl.when(nslot < num_slots)(lambda: start(nslot, nblk, 1 - buf))
            wait(b, k, buf)
            c = cbuf[buf].astype(jnp.float32)
            c = c.reshape(-1, c.shape[-1]).astype(dt)
            r = rbuf[buf].astype(jnp.float32)
            r = r.reshape(-1, r.shape[-1]).astype(dt)
            m, l, acc = _block_update(
                m_ref[...], l_ref[...], acc_ref[...], c, r, qc_ref[0],
                qr_ref[0], cs, rs, k * (bp * page_size), ln,
                sm_scale=sm_scale, fc=fc, fr=fr, h=h,
                interleave=interleave, deinterleave=deinterleave)
            m_ref[...] = m
            l_ref[...] = l
            acc_ref[...] = acc
            return carry

        jax.lax.fori_loop(0, n, body, 0)
        o_ref[0] = _emit(acc_ref[...], l_ref[...], cs, fc=fc,
                         h=h).astype(o_ref.dtype)

    @pl.when(n == 0)
    def _inactive():
        o_ref[...] = jnp.zeros_like(o_ref)


def mla_paged_attention_kernel(q_abs, q_rope, ckv, krope, cscale, rscale,
                               table, lens, active, layer, *,
                               page_size: int, quantized: bool,
                               sm_scale: float, interpret: bool = False
                               ) -> jax.Array:
    """Latent page walk via Pallas; shapes per the module docstring.
    Returns (B, H, dc) in q_abs.dtype."""
    o = _operands(q_abs, q_rope, ckv, krope, cscale, rscale, table, lens,
                  active, page_size, quantized)
    b, h, fc, fr, bp, ppad = (o[k] for k in ("b", "h", "fc", "fr", "bp",
                                             "ppad"))
    dc = q_abs.shape[-1]
    nblk = o["nblk"]
    # buffer parity of each slot's first block, and the next slot with
    # blocks to walk (b when none), for the cross-slot prefetch
    cum = jnp.cumsum(nblk) - nblk
    idx = jnp.arange(b, dtype=jnp.int32)
    later = (idx[None, :] > idx[:, None]) & (nblk[None, :] > 0)
    nxt = jnp.min(jnp.where(later, idx[None, :], b), axis=1)
    prefetch = (nblk, cum.astype(jnp.int32), nxt.astype(jnp.int32),
                o["npages"], lens.astype(jnp.int32),
                o["table"].reshape(-1).astype(jnp.int32),
                jnp.reshape(layer, (1,)).astype(jnp.int32))
    qc, qr = o["qc"], o["qr"]
    n = bp * page_size
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1,) + qc.shape[1:], lambda i, *_: (i, 0, 0)),
            pl.BlockSpec((1,) + qr.shape[1:], lambda i, *_: (i, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, dc, h), lambda i, *_: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, bp) + ckv.shape[-2:], ckv.dtype),
            pltpu.VMEM((2, bp) + krope.shape[-2:], krope.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((1, h), jnp.float32),              # running max
            pltpu.VMEM((1, h), jnp.float32),              # running denom
            pltpu.VMEM((fc * dc, fc * h), jnp.float32),   # running numerator
            pltpu.VMEM((n, h), jnp.float32),              # token-order rows
        ],
    )
    kern = functools.partial(
        _mla_kernel, page_size=page_size, bp=bp, ppad=ppad,
        sm_scale=sm_scale, fc=fc, fr=fr, h=h, dt=o["dt"])
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, dc, h), q_abs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(*prefetch, qc, qr, o["cs"], o["rs"], ckv, krope)
    return jnp.swapaxes(out, 1, 2)


# ---------------------------------------------------------------------------
# jnp block scan -- the same dataflow in XLA (the engine's walk off-TPU)
# ---------------------------------------------------------------------------

def mla_paged_attention_jnp(q_abs, q_rope, ckv, krope, cscale, rscale,
                            table, lens, active, layer, *, page_size: int,
                            quantized: bool, sm_scale: float) -> jax.Array:
    """The kernel's blocks as a loop over compute blocks for every slot at
    once, gathering ``ckv[layer, pages]``; bit-identical to the kernel."""
    o = _operands(q_abs, q_rope, ckv, krope, cscale, rscale, table, lens,
                  active, page_size, quantized)
    b, h, fc, fr, bp, dt = (o[k] for k in ("b", "h", "fc", "fr", "bp",
                                           "dt"))
    dc = q_abs.shape[-1]
    nblk = o["nblk"]
    cs = o["cs"][:, None, None]
    rs = o["rs"][:, None, None]
    ln = lens[:, None, None]

    def rows(leaf, pages):
        x = leaf[layer, pages]                    # (B, bp, rows, lanes)
        return x.reshape(b, -1, x.shape[-1]).astype(dt)

    def body(k, state):
        pages = jax.lax.dynamic_slice_in_dim(o["table"], k * bp, bp, axis=1)
        new = _block_update(
            *state, rows(ckv, pages), rows(krope, pages), o["qc"], o["qr"],
            cs, rs, k * (bp * page_size), ln, sm_scale=sm_scale, fc=fc,
            fr=fr, h=h, interleave=_interleave_reshape,
            deinterleave=_deinterleave_reshape)
        walk = (k < nblk)[:, None, None]
        return tuple(jnp.where(walk, a, s) for a, s in zip(new, state))

    m0 = jnp.full((b, 1, h), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, 1, h), jnp.float32)
    a0 = jnp.zeros((b, fc * dc, fc * h), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, jnp.max(nblk), body, (m0, l0, a0))
    out = _emit(acc, l, cs, fc=fc, h=h).astype(q_abs.dtype)
    return jnp.swapaxes(out, 1, 2)
