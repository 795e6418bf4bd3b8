"""Pallas codec backend: fused quantize kernels behind the same
``encode / decode / fake_quant`` API as the reference backend.

Absorbs the old ``kernels/quantize.py`` fused fake-quant (one VMEM pass:
scale -> round -> clip -> dequantize — on the FPGA this is the implicit
writeback datapath of every PE) and adds code-producing encode / decode
kernels plus a blockwise-absmax kernel pair.

All entry points pad to block multiples *internally* and slice the result
back, so callers never pre-pad (the old ``quantize()`` asserted exact
(bm, bn) multiples — that footgun is gone). Kernels run compiled on TPU and
in interpret mode elsewhere, where the kernel body executes as jnp — which
is also why the backend is bit-identical to the reference codec (asserted
by tests/test_numerics.py).

Scale handling: the fused kernels take one scalar ``scale_log2`` through
SMEM (per-tensor pow-2 scale, the §3.2 scheme) OR a *multi-scale* array
following the leading-dim broadcast convention of ``codecs._bcast`` — one
scale per leading index, e.g. the KV pool's per-(layer, slot) scale arrays.
Multi-scale calls collapse to a (rows, cols) layout with one scale per row
and run a vectorized row-scale kernel (the per-page dequant datapath of the
fused paged-attention kernel, exposed as a standalone codec).  Only scale
shapes that do not broadcast against the leading dims fall back to the
reference codec; ``fallback_count()`` lets tests assert a path stayed
native (tests/test_numerics.py pins every KV-pool shape to zero fallbacks).
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..obs.counters import registry as _counters
from .codecs import (Pow2Reference, BlockwiseReference, _bcast, _p2fq_bwd,
                     _p2fq_fwd, register_codec)
from .spec import QTensor, QuantSpec, packed_trailing, qrange

# Calls that fell back to the reference codec because the scale array did
# not fit a kernel layout live in the obs counter registry under this name
# (incremented at trace time; tests reset + assert zero around pool-shaped
# calls). fallback_count()/reset_fallback_count() are kept as the
# long-standing API — they are now views over the registry counter.
FALLBACK_COUNTER = "numerics.codec_fallback"


def fallback_count() -> int:
    return _counters.get(FALLBACK_COUNTER)


def reset_fallback_count() -> None:
    _counters.reset(FALLBACK_COUNTER)


def _note_fallback() -> None:
    _counters.inc(FALLBACK_COUNTER)


def interpret_mode() -> bool:
    """Pallas interpret-mode switch shared by every kernel call site
    (kernels/ops.py and this backend): JAX_PALLAS_INTERPRET=1 forces
    interpret (the CI kernel-validation mode); otherwise interpret
    everywhere but TPU."""
    if os.environ.get("JAX_PALLAS_INTERPRET", "") == "1":
        return True
    return jax.default_backend() != "tpu"


def native_backend() -> bool:
    """True where Pallas kernels are the preferred lowering: a TPU backend
    (compiled), or JAX_PALLAS_INTERPRET=1 explicitly asking for kernel
    validation. One predicate so the codec, the pool, and the kernel
    wrapper can never route differently for the same configuration."""
    return (jax.default_backend() == "tpu"
            or os.environ.get("JAX_PALLAS_INTERPRET", "") == "1")


_interpret = interpret_mode


def _blk(dim: int, pref: int, floor: int) -> int:
    if dim >= pref:
        return pref
    return max(floor, ((dim + floor - 1) // floor) * floor)


def _pad2d(x: jax.Array, bm: int, bn: int) -> jax.Array:
    m, n = x.shape
    pm, pn = (-m) % bm, (-n) % bn
    if pm or pn:
        return jnp.pad(x, ((0, pm), (0, pn)))
    return x


def _as2d(flat: jax.Array, cols: int = 256) -> tuple[jax.Array, int]:
    """(n,) -> (rows, cols) zero-padded; returns (x2d, n)."""
    n = flat.shape[0]
    rows = -(-n // cols)
    pad = rows * cols - n
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(rows, cols), n


# ---------------------------------------------------------------------------
# pow2 kernels
# ---------------------------------------------------------------------------

def _p2_fq_kernel(x_ref, step_ref, o_ref, *, bits: int):
    scale = jnp.exp2(step_ref[0].astype(jnp.float32)).astype(x_ref.dtype)
    lo, hi = qrange(bits)
    x = x_ref[...]
    o_ref[...] = (jnp.clip(jnp.round(x / scale), lo, hi) * scale
                  ).astype(o_ref.dtype)


def _p2_enc_kernel(x_ref, step_ref, o_ref, *, bits: int):
    scale = jnp.exp2(step_ref[0].astype(jnp.float32))
    lo, hi = qrange(bits)
    x = x_ref[...].astype(jnp.float32)
    o_ref[...] = jnp.clip(jnp.round(x / scale), lo, hi).astype(o_ref.dtype)


def _p2_dec_kernel(q_ref, step_ref, o_ref):
    scale = jnp.exp2(step_ref[0].astype(jnp.float32))
    o_ref[...] = (q_ref[...].astype(jnp.float32) * scale).astype(o_ref.dtype)


def _elementwise_2d(kernel, x2d: jax.Array, step_log2, out_dtype, *,
                    bm: int = 256, bn: int = 256) -> jax.Array:
    """Grid-tiled elementwise pass with the scalar step in SMEM; pads the
    operand to (bm, bn) multiples internally and slices the result back."""
    m, n = x2d.shape
    xp = _pad2d(x2d, bm, bn)
    mp, np_ = xp.shape
    step = jnp.asarray(step_log2, jnp.float32).reshape(1)
    out = pl.pallas_call(
        kernel,
        grid=(mp // bm, np_ // bn),
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), out_dtype),
        interpret=_interpret(),
    )(xp, step)
    return out[:m, :n]


def _flat_call(kernel, x: jax.Array, step_log2, out_dtype) -> jax.Array:
    """Arbitrary-shape elementwise call: flatten -> 2D tile -> restore."""
    shape = x.shape
    x2d, n = _as2d(x.reshape(-1))
    bm = _blk(x2d.shape[0], 256, 8)
    out = _elementwise_2d(kernel, x2d, step_log2, out_dtype, bm=bm)
    return out.reshape(-1)[:n].reshape(shape)


# ---- multi-scale (one pow-2 scale per leading index) ----------------------

def _rowwise(x: jax.Array, scale) -> tuple[jax.Array, jax.Array] | None:
    """View (x, scale) as (rows, cols) with one scale per row.

    Accepts any scale following the ``codecs._bcast`` convention: after
    stripping trailing length-1 dims, ``scale.shape`` must broadcast against
    the same number of *leading* dims of ``x`` (each dim equal or 1).
    Returns (x2d, scale_row) or None when the convention doesn't hold
    (caller falls back to the reference codec)."""
    scale = jnp.asarray(scale)
    sh = list(scale.shape)
    while sh and sh[-1] == 1:
        sh.pop()
    if not sh or len(sh) > x.ndim:
        return None
    lead = x.shape[:len(sh)]
    if any(s not in (1, d) for s, d in zip(sh, lead)):
        return None
    rows = 1
    for d in lead:
        rows *= d
    srow = jnp.broadcast_to(scale.reshape(sh), lead).reshape(rows)
    return x.reshape(rows, -1), srow


def _p2_enc_rows_kernel(x_ref, s_ref, o_ref, *, bits: int):
    step = jnp.exp2(s_ref[...].astype(jnp.float32))     # (bm, 1) per-row
    lo, hi = qrange(bits)
    x = x_ref[...].astype(jnp.float32)
    o_ref[...] = jnp.clip(jnp.round(x / step), lo, hi).astype(o_ref.dtype)


def _p2_dec_rows_kernel(q_ref, s_ref, o_ref):
    step = jnp.exp2(s_ref[...].astype(jnp.float32))
    o_ref[...] = (q_ref[...].astype(jnp.float32) * step).astype(o_ref.dtype)


def _rowscale_call(kernel, x2d: jax.Array, srow: jax.Array,
                   out_dtype) -> jax.Array:
    """Grid-tiled pass with one f32 scale per row delivered as a (bm, 1)
    VMEM block (same layout as the blockwise decode kernel)."""
    r, c = x2d.shape
    bm = _blk(r, 256, 8)
    bn = _blk(c, 256, 128)
    xp = _pad2d(x2d, bm, bn)
    sp = _pad2d(srow.astype(jnp.float32).reshape(r, 1), bm, 1)
    mp, np_ = xp.shape
    out = pl.pallas_call(
        kernel,
        grid=(mp // bm, np_ // bn),
        in_specs=[pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
                  pl.BlockSpec((bm, 1), lambda i, j: (i, 0))],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), out_dtype),
        interpret=_interpret(),
    )(xp, sp)
    return out[:r, :c]


# ---- int4x2 packed (two codes per byte, packed along the trailing dim) ----
# The kernel bodies call the codec's own pack_int4/unpack_int4 (kernel-safe
# jnp; blocks are always even-width so the no-pad path runs) — ONE nibble
# layout owned by codecs.py, same single-implementation rule as the PE1
# epilogue.

def _p2_enc_packed_kernel(x_ref, step_ref, o_ref, *, bits: int):
    from .codecs import pack_int4
    scale = jnp.exp2(step_ref[0].astype(jnp.float32))
    lo, hi = qrange(bits)
    q = jnp.clip(jnp.round(x_ref[...].astype(jnp.float32) / scale), lo, hi)
    o_ref[...] = pack_int4(q)


def _p2_enc_packed_rows_kernel(x_ref, s_ref, o_ref, *, bits: int):
    from .codecs import pack_int4
    step = jnp.exp2(s_ref[...].astype(jnp.float32))      # (bm, 1) per-row
    lo, hi = qrange(bits)
    q = jnp.clip(jnp.round(x_ref[...].astype(jnp.float32) / step), lo, hi)
    o_ref[...] = pack_int4(q)


def _p2_dec_packed_kernel(q_ref, step_ref, o_ref):
    from .codecs import unpack_int4
    scale = jnp.exp2(step_ref[0].astype(jnp.float32))
    q = unpack_int4(q_ref[...], 2 * q_ref.shape[-1])
    o_ref[...] = (q.astype(jnp.float32) * scale).astype(o_ref.dtype)


def _p2_dec_packed_rows_kernel(q_ref, s_ref, o_ref):
    from .codecs import unpack_int4
    step = jnp.exp2(s_ref[...].astype(jnp.float32))
    q = unpack_int4(q_ref[...], 2 * q_ref.shape[-1])
    o_ref[...] = (q.astype(jnp.float32) * step).astype(o_ref.dtype)


def _rowwise_lastdim(x: jax.Array, scale) -> tuple | None:
    """View ``x`` as (rows, last) with one scale per row, KEEPING the
    logical trailing dim intact (the packed codec pairs nibbles along it —
    `_rowwise`'s full collapse would let pairs straddle row boundaries when
    the trailing dim is odd). None when the scale extends into the trailing
    dim (per-element scales: reference fallback)."""
    scale = jnp.asarray(scale)
    sh = list(scale.shape)
    while sh and sh[-1] == 1:
        sh.pop()
    if len(sh) > x.ndim - 1:
        return None
    lead = x.shape[:-1]
    if any(s not in (1, d) for s, d in zip(sh, lead)):
        return None
    rows = 1
    for d in lead:
        rows *= d
    srow = jnp.broadcast_to(
        scale.reshape(tuple(sh) + (1,) * (len(lead) - len(sh))),
        lead).reshape(rows)
    return x.reshape(rows, x.shape[-1]), srow


def _packed_call(kernel, x2d: jax.Array, srow_or_step, out_shape_cols: str,
                 rowwise: bool, out_dtype) -> jax.Array:
    """Grid-tiled packed pass. ``out_shape_cols``: "half" for encode
    ((bm, 2*bc) in -> (bm, bc) out), "double" for decode ((bm, bc) in ->
    (bm, 2*bc) out). Pads internally, slices back."""
    r, c = x2d.shape
    half = out_shape_cols == "half"
    pk = packed_trailing(c) if half else c   # packed (byte) cols
    bm = _blk(r, 256, 8)
    bc = _blk(pk, 256, 128)
    cp = -(-pk // bc) * bc                   # padded packed cols
    rp = -(-r // bm) * bm
    in_cols = 2 * cp if half else cp
    xp = jnp.zeros((rp, in_cols), x2d.dtype).at[:r, :c].set(x2d)
    in_block = (bm, 2 * bc) if half else (bm, bc)
    out_block = (bm, bc) if half else (bm, 2 * bc)
    if rowwise:
        sp = _pad2d(srow_or_step.astype(jnp.float32).reshape(r, 1), bm, 1)
        scale_spec = pl.BlockSpec((bm, 1), lambda i, j: (i, 0))
        scale_arg = sp
    else:
        scale_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
        scale_arg = jnp.asarray(srow_or_step, jnp.float32).reshape(1)
    out_cols = cp if half else 2 * cp
    out = pl.pallas_call(
        kernel,
        grid=(rp // bm, cp // bc),
        in_specs=[pl.BlockSpec(in_block, lambda i, j: (i, j)), scale_spec],
        out_specs=pl.BlockSpec(out_block, lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((rp, out_cols), out_dtype),
        interpret=_interpret(),
    )(xp, scale_arg)
    return out[:r, :pk] if half else out[:r]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _p2_fake_quant_pallas(x, scale_log2, bits):
    return _flat_call(functools.partial(_p2_fq_kernel, bits=bits), x,
                      scale_log2, x.dtype)


# same clipped-STE backward as the reference codec; the forward residual
# (the inside-range mask) is cheap enough to compute outside the kernel
_p2_fake_quant_pallas.defvjp(
    lambda x, s, bits: (_p2_fake_quant_pallas(x, s, bits),
                        _p2fq_fwd(x, s, bits)[1]),
    _p2fq_bwd)


def _p2_fq_rows_kernel(x_ref, s_ref, o_ref, *, bits: int):
    # per-row fused qdq in x.dtype — the multi-scale twin of _p2_fq_kernel,
    # matching the reference pow2_qdq grid (scale cast to x.dtype) exactly
    step = jnp.exp2(s_ref[...].astype(jnp.float32)).astype(x_ref.dtype)
    lo, hi = qrange(bits)
    x = x_ref[...]
    o_ref[...] = (jnp.clip(jnp.round(x / step), lo, hi) * step
                  ).astype(o_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _p2_fake_quant_rows(x, scale_log2, bits):
    x2d, srow = _rowwise(x, scale_log2)
    out = _rowscale_call(functools.partial(_p2_fq_rows_kernel, bits=bits),
                         x2d, srow, x.dtype)
    return out.reshape(x.shape)


# clipped STE with the reference's leading-dim broadcast semantics: the
# inside-range mask comes from _p2fq_fwd on the _bcast-shaped scale
_p2_fake_quant_rows.defvjp(
    lambda x, s, bits: (_p2_fake_quant_rows(x, s, bits),
                        _p2fq_fwd(x, _bcast(s, x.ndim), bits)[1]),
    _p2fq_bwd)


class Pow2Pallas(Pow2Reference):
    backend = "pallas"

    @staticmethod
    def _scalar(scale) -> bool:
        return jnp.ndim(scale) == 0 or getattr(scale, "size", 2) == 1

    def encode(self, x, spec: QuantSpec, scale):
        if spec.packed:
            return self._encode_packed(jnp.asarray(x), spec, scale)
        if self._scalar(scale):
            codes = _flat_call(
                functools.partial(_p2_enc_kernel, bits=spec.bits),
                x, scale, spec.jnp_storage)
            return QTensor(codes, jnp.asarray(scale), spec, x.shape)
        rw = _rowwise(jnp.asarray(x), scale)
        if rw is None:
            _note_fallback()
            return super().encode(x, spec, scale)
        x2d, srow = rw
        codes = _rowscale_call(
            functools.partial(_p2_enc_rows_kernel, bits=spec.bits),
            x2d, srow, spec.jnp_storage)
        return QTensor(codes.reshape(x.shape), jnp.asarray(scale), spec,
                       x.shape)

    def _encode_packed(self, x, spec: QuantSpec, scale):
        if x.ndim == 0:                       # scalars: no trailing dim to pack
            _note_fallback()
            return super().encode(x, spec, scale)
        if self._scalar(scale):
            x2d = x.reshape(-1, x.shape[-1])
            codes = _packed_call(
                functools.partial(_p2_enc_packed_kernel, bits=spec.bits),
                x2d, scale, "half", False, jnp.int8)
        else:
            rw = _rowwise_lastdim(x, scale)
            if rw is None:
                _note_fallback()
                return super().encode(x, spec, scale)
            x2d, srow = rw
            codes = _packed_call(
                functools.partial(_p2_enc_packed_rows_kernel, bits=spec.bits),
                x2d, srow, "half", True, jnp.int8)
        return QTensor(codes.reshape(x.shape[:-1] + (codes.shape[-1],)),
                       jnp.asarray(scale), spec, x.shape)

    def _decode_packed(self, qt: QTensor, dtype):
        last = qt.shape[-1] if qt.shape else 1
        if self._scalar(qt.scale):
            q2d = qt.codes.reshape(-1, qt.codes.shape[-1])
            out = _packed_call(_p2_dec_packed_kernel, q2d, qt.scale,
                               "double", False, dtype)
        else:
            rw = _rowwise_lastdim(qt.codes, qt.scale)
            if rw is None:
                _note_fallback()
                return super().decode(qt, dtype)
            q2d, srow = rw
            out = _packed_call(_p2_dec_packed_rows_kernel, q2d, srow,
                               "double", True, dtype)
        return out[:, :last].reshape(qt.shape).astype(dtype)

    def decode(self, qt: QTensor, dtype=jnp.float32):
        if qt.spec.packed:
            return self._decode_packed(qt, dtype)
        if self._scalar(qt.scale):
            return _flat_call(_p2_dec_kernel, qt.codes, qt.scale, dtype)
        rw = _rowwise(qt.codes, qt.scale)
        if rw is None:
            _note_fallback()
            return super().decode(qt, dtype)
        q2d, srow = rw
        out = _rowscale_call(_p2_dec_rows_kernel, q2d, srow, dtype)
        return out.reshape(qt.codes.shape)

    def fake_quant(self, x, spec: QuantSpec, scale):
        if self._scalar(scale):
            return _p2_fake_quant_pallas(x, scale, spec.bits)
        x = jnp.asarray(x)
        if _rowwise(x, scale) is None:
            # scale doesn't follow the leading-dim broadcast convention
            # (e.g. per-element scales): reference fallback, counted
            _note_fallback()
            return super().fake_quant(x, spec, scale)
        return _p2_fake_quant_rows(x, jnp.asarray(scale), spec.bits)


# ---------------------------------------------------------------------------
# blockwise kernels
# ---------------------------------------------------------------------------

def _bw_enc_kernel(x_ref, q_ref, s_ref, *, qmax: float):
    x = x_ref[...].astype(jnp.float32)                 # (bm, b)
    # the reference codec's exact expression (see BlockwiseReference)
    sc = jnp.max(jnp.abs(x), axis=1, keepdims=True) * jnp.float32(1.0 / qmax)
    q = jnp.round(x / jnp.maximum(sc, 1e-20))
    q_ref[...] = jnp.clip(q, -qmax, qmax).astype(q_ref.dtype)
    s_ref[...] = sc


def _bw_dec_kernel(q_ref, s_ref, o_ref):
    o_ref[...] = (q_ref[...].astype(jnp.float32) * s_ref[...]
                  ).astype(o_ref.dtype)


class BlockwisePallas(BlockwiseReference):
    backend = "pallas"

    def encode(self, x, spec: QuantSpec, scale=None):
        v = x.astype(jnp.float32)
        if v.ndim == 0:
            v = v[None]
        shape = v.shape
        from .codecs import blockwise_geometry
        b, nb, pad = blockwise_geometry(spec, shape[-1])
        if pad:
            v = jnp.pad(v, [(0, 0)] * (v.ndim - 1) + [(0, pad)])
        rows = 1
        for d in v.shape[:-1]:
            rows *= d
        x2d = v.reshape(rows, nb * b)
        bm = _blk(rows, 256, 8)
        xp = _pad2d(x2d, bm, b)
        mp = xp.shape[0]
        codes, sc = pl.pallas_call(
            functools.partial(_bw_enc_kernel, qmax=spec.qmax),
            grid=(mp // bm, nb),
            in_specs=[pl.BlockSpec((bm, b), lambda i, j: (i, j))],
            out_specs=[pl.BlockSpec((bm, b), lambda i, j: (i, j)),
                       pl.BlockSpec((bm, 1), lambda i, j: (i, j))],
            out_shape=[jax.ShapeDtypeStruct((mp, nb * b), spec.jnp_storage),
                       jax.ShapeDtypeStruct((mp, nb), jnp.float32)],
            interpret=_interpret(),
        )(xp)
        codes = codes[:rows].reshape(v.shape[:-1] + (nb * b,))
        sc = sc[:rows].reshape(v.shape[:-1] + (nb,))
        return QTensor(codes, sc, spec, shape)

    def decode(self, qt: QTensor, dtype=jnp.float32):
        nb = qt.scale.shape[-1]
        b = qt.codes.shape[-1] // nb
        rows = 1
        for d in qt.codes.shape[:-1]:
            rows *= d
        q2d = qt.codes.reshape(rows, nb * b)
        s2d = qt.scale.reshape(rows, nb)
        bm = _blk(rows, 256, 8)
        qp = _pad2d(q2d, bm, b)
        sp = _pad2d(s2d, bm, 1)
        mp = qp.shape[0]
        out = pl.pallas_call(
            _bw_dec_kernel,
            grid=(mp // bm, nb),
            in_specs=[pl.BlockSpec((bm, b), lambda i, j: (i, j)),
                      pl.BlockSpec((bm, 1), lambda i, j: (i, j))],
            out_specs=pl.BlockSpec((bm, b), lambda i, j: (i, j)),
            out_shape=jax.ShapeDtypeStruct((mp, nb * b), jnp.float32),
            interpret=_interpret(),
        )(qp, sp)
        flat = out[:rows].reshape(qt.codes.shape[:-1] + (nb * b,))
        sliced = flat[..., :qt.shape[-1]] if qt.shape else flat[..., :1]
        return sliced.reshape(qt.shape).astype(dtype)


register_codec("pow2", "pallas", Pow2Pallas())
register_codec("blockwise", "pallas", BlockwisePallas())
