"""Codec registry: ``encode / decode / fake_quant`` for every QuantSpec,
with selectable backends.

A *codec* implements one ``QuantSpec.kind`` on one backend:

- ``"reference"``: pure jnp — the numerics oracle, runs everywhere.
- ``"pallas"``: fused Pallas kernels (``numerics/pallas_backend.py``),
  bit-identical to the reference (asserted by tests/test_numerics.py);
  pads to TPU block multiples internally so callers never pre-pad.

The three operations:

- ``encode(x, spec, scale)`` -> QTensor of integer codes (+ scale metadata).
  pow2 takes the caller's ``scale_log2`` (scalar or broadcastable against
  x's leading dims); blockwise derives per-block scales from the data and
  ignores ``scale``.
- ``decode(qt, dtype)`` -> dequantized array in ``dtype``.
- ``fake_quant(x, spec, scale)`` -> quantize-dequantize in one step. For
  pow2 this is the paper's Q(.) with the clipped straight-through estimator
  in the backward pass (§3.2); for blockwise it is a plain-STE roundtrip
  (used outside autodiff anyway: optimizer state, gradient wire).

Exact numerics contracts (kept bit-identical to the pre-refactor sites):

- pow2 fake_quant computes in ``x.dtype`` with ``scale = exp2(k)`` cast to
  ``x.dtype`` (core/quant.py semantics — the grid the QAT tests pin down).
- pow2 encode/decode compute in f32 (serve/kv_cache.py semantics — codes
  are storage, not autodiff values).
- blockwise uses symmetric ±(2^{b-1}-1) codes with ``scale = absmax/qmax``
  floored at 1e-20 (optim/adam.py, optim/grad_compress.py semantics).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .spec import QTensor, QuantSpec, qrange


def pack_int4(q: jax.Array) -> jax.Array:
    """Pack int4 codes (values in [-8, 7]) two-per-byte along the trailing
    axis. Odd trailing dims get one zero pad nibble (the high nibble of the
    last byte). Returns int8 of shape ``q.shape[:-1] + (ceil(last/2),)``."""
    last = q.shape[-1]
    v = q.astype(jnp.int32)
    if last % 2:
        v = jnp.pad(v, [(0, 0)] * (v.ndim - 1) + [(0, 1)])
    lo = v[..., 0::2] & 0xF
    hi = v[..., 1::2] & 0xF
    return (lo | (hi << 4)).astype(jnp.int8)


def unpack_int4(packed: jax.Array, last: int) -> jax.Array:
    """Inverse of ``pack_int4``: int8 bytes -> int32 codes in [-8, 7] of
    trailing dim ``last`` (the pad nibble, if any, is sliced away)."""
    v = packed.astype(jnp.int32) & 0xFF
    lo = ((v & 0xF) ^ 8) - 8                 # sign-extend each nibble
    hi = ((v >> 4) ^ 8) - 8
    q = jnp.stack([lo, hi], axis=-1).reshape(
        packed.shape[:-1] + (packed.shape[-1] * 2,))
    return q[..., :last]


def _bcast(scale: jax.Array, ndim: int) -> jax.Array:
    """Right-pad ``scale``'s shape with 1s so it broadcasts against the
    *leading* dims of an ndim-D tensor (the kv-cache layout: one scale per
    (layer, slot), data (L, S, *feat))."""
    scale = jnp.asarray(scale)
    return scale.reshape(scale.shape + (1,) * (ndim - scale.ndim))


# ---------------------------------------------------------------------------
# pow2: fake-quant with clipped STE (the canonical §3.2 Q(.))
# ---------------------------------------------------------------------------

def pow2_qdq(x: jax.Array, scale_log2: jax.Array, bits: int) -> jax.Array:
    """Raw quantize-dequantize on the pow-2 grid in ``x.dtype`` — the Q(.)
    of paper Eq. (3), no gradient rule attached."""
    scale = jnp.exp2(scale_log2).astype(x.dtype)
    lo, hi = qrange(bits)
    return jnp.clip(jnp.round(x / scale), lo, hi) * scale


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def pow2_fake_quant(x: jax.Array, scale_log2: jax.Array, bits: int) -> jax.Array:
    """Quantize-dequantize on the pow-2 grid; clipped STE backward: the
    gradient passes where the pre-quant value was representable, zero
    outside (the paper's "clipped ReLU" STE)."""
    return pow2_qdq(x, scale_log2, bits)


def _p2fq_fwd(x, scale_log2, bits):
    scale = jnp.exp2(scale_log2).astype(x.dtype)
    lo, hi = qrange(bits)
    inside = (x / scale >= lo) & (x / scale <= hi)
    q = jnp.clip(jnp.round(x / scale), lo, hi)
    return q * scale, inside


def _p2fq_bwd(bits, inside, g):
    return (jnp.where(inside, g, 0.0).astype(g.dtype), None)


pow2_fake_quant.defvjp(_p2fq_fwd, _p2fq_bwd)


class Pow2Reference:
    """Reference jnp pow-2 codec."""
    kind = "pow2"
    backend = "reference"

    def encode(self, x: jax.Array, spec: QuantSpec,
               scale: jax.Array) -> QTensor:
        lo, hi = qrange(spec.bits)
        step = jnp.exp2(_bcast(scale, x.ndim))
        q = jnp.clip(jnp.round(x.astype(jnp.float32) / step), lo, hi)
        if spec.packed:
            # 0-d: pack as one (1,)-code row; decode's `shape or (1,)`
            # mirrors this (scalars carry one nibble + one pad nibble)
            return QTensor(pack_int4(q[None] if q.ndim == 0 else q),
                           jnp.asarray(scale), spec, x.shape)
        return QTensor(q.astype(spec.jnp_storage), jnp.asarray(scale), spec,
                       x.shape)

    def decode(self, qt: QTensor, dtype=jnp.float32) -> jax.Array:
        codes = qt.codes
        if qt.spec.packed:
            codes = unpack_int4(codes, qt.shape[-1] if qt.shape else 1)
        step = jnp.exp2(_bcast(qt.scale, codes.ndim))
        out = codes.astype(jnp.float32) * step
        return out.reshape(qt.shape).astype(dtype) if qt.spec.packed \
            else out.astype(dtype)

    def epilogue(self, acc: jax.Array, spec: QuantSpec,
                 scale_log2: jax.Array) -> jax.Array:
        """Requantize-on-writeback: the FPGA PE's fused epilogue, owned by
        the codec registry so `kernels/ttm_pe1.py` and the unfused
        encode→decode reference path share ONE round/clip/scale body
        (bit-identity asserted by tests/test_kernels.py). Kernel-safe:
        plain jnp on an f32 accumulator, no custom_vjp."""
        scale = jnp.exp2(jnp.asarray(scale_log2).astype(jnp.float32))
        lo, hi = qrange(spec.bits)
        return jnp.clip(jnp.round(acc / scale), lo, hi) * scale

    def fake_quant(self, x: jax.Array, spec: QuantSpec,
                   scale: jax.Array) -> jax.Array:
        # _bcast keeps the codec API's one scale convention across all
        # three ops: non-scalar scales broadcast against x's LEADING dims
        # (encode/decode semantics), not numpy trailing alignment — so a
        # per-layer (L, 1) scale means the same thing everywhere. Scalars
        # are unchanged (core/quant.py's QAT grid stays bit-identical).
        return pow2_fake_quant(x, _bcast(jnp.asarray(scale), x.ndim),
                               spec.bits)


# ---------------------------------------------------------------------------
# blockwise: per-block absmax along the last axis
# ---------------------------------------------------------------------------

def blockwise_geometry(spec: QuantSpec, last: int) -> tuple[int, int, int]:
    """(block, num_blocks, pad) along a last axis of size ``last``. The block
    clamps to the axis so the codes keep the leading shape of the input —
    shape preservation is what lets q8 optimizer state carry the SAME
    sharding as its parameter (see optim/adam.py)."""
    b = min(spec.block, max(1, last))
    nb = -(-last // b)
    return b, nb, nb * b - last


class BlockwiseReference:
    """Reference jnp blockwise-absmax codec (Dettmers-style)."""
    kind = "blockwise"
    backend = "reference"

    def encode(self, x: jax.Array, spec: QuantSpec,
               scale=None) -> QTensor:
        v = x.astype(jnp.float32)
        if v.ndim == 0:
            v = v[None]
        shape = v.shape
        b, nb, pad = blockwise_geometry(spec, shape[-1])
        if pad:
            v = jnp.pad(v, [(0, 0)] * (v.ndim - 1) + [(0, pad)])
        blocks = v.reshape(v.shape[:-1] + (nb, b))
        qmax = spec.qmax
        # times the f32 reciprocal, not "/ qmax": XLA's CPU code generator
        # may turn a division by a constant into this multiply in one fusion
        # and not in another, so the backends would round apart
        sc = jnp.max(jnp.abs(blocks), axis=-1) * jnp.float32(1.0 / qmax)
        q = jnp.round(blocks / jnp.maximum(sc, 1e-20)[..., None])
        codes = jnp.clip(q, -qmax, qmax).astype(spec.jnp_storage)
        return QTensor(codes.reshape(v.shape[:-1] + (nb * b,)), sc, spec,
                       shape)

    def decode(self, qt: QTensor, dtype=jnp.float32) -> jax.Array:
        nb = qt.scale.shape[-1]
        b = qt.codes.shape[-1] // nb
        blocks = qt.codes.astype(jnp.float32).reshape(
            qt.codes.shape[:-1] + (nb, b)) * qt.scale[..., None]
        flat = blocks.reshape(qt.codes.shape[:-1] + (nb * b,))
        out = flat[..., :qt.shape[-1]] if qt.shape else flat[..., :1]
        return out.reshape(qt.shape).astype(dtype)

    def fake_quant(self, x: jax.Array, spec: QuantSpec, scale=None) -> jax.Array:
        # plain STE: identity gradient (blockwise sites sit outside autodiff)
        y = self.decode(self.encode(x, spec), x.dtype)
        return x + jax.lax.stop_gradient(y - x)


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_CODECS: dict[tuple[str, str], object] = {
    ("pow2", "reference"): Pow2Reference(),
    ("blockwise", "reference"): BlockwiseReference(),
}

BACKENDS = ("reference", "pallas")


def register_codec(kind: str, backend: str, codec) -> None:
    _CODECS[(kind, backend)] = codec


def get_codec(spec: QuantSpec | str, backend: str = "reference"):
    """Codec for ``spec`` on ``backend``. The Pallas backend registers
    lazily on first request (keeps import light off-TPU)."""
    kind = spec if isinstance(spec, str) else spec.kind
    key = (kind, backend)
    if key not in _CODECS and backend == "pallas":
        from . import pallas_backend  # noqa: F401  (registers on import)
    if key not in _CODECS:
        raise KeyError(f"no codec for kind={kind!r} backend={backend!r}; "
                       f"registered: {sorted(_CODECS)}")
    return _CODECS[key]


# Module-level conveniences (the API most call sites use) -------------------

def encode(x: jax.Array, spec: QuantSpec, scale=None,
           backend: str = "reference") -> QTensor:
    return get_codec(spec, backend).encode(x, spec, scale)


def decode(qt: QTensor, dtype=jnp.float32,
           backend: str = "reference") -> jax.Array:
    return get_codec(qt.spec, backend).decode(qt, dtype)


def fake_quant(x: jax.Array, spec: QuantSpec, scale=None,
               backend: str = "reference") -> jax.Array:
    return get_codec(spec, backend).fake_quant(x, spec, scale)


def fake_quant_stats(x: jax.Array, spec: QuantSpec, scale=None,
                     backend: str = "reference"
                     ) -> tuple[jax.Array, tuple[jax.Array, jax.Array]]:
    """``fake_quant`` with a quant-health aux output: ``(y, (clipped,
    total))`` int32 counts of values outside the representable range.

    The counts are integer-exact functions of (x, scale), so the reference
    and Pallas backends agree BITWISE (tests/test_obs.py). For blockwise
    specs the scale is data-derived (absmax covers the range), so the aux
    reports saturated codes instead — the same "pinned at the grid edge"
    health signal."""
    from ..obs.counters import pow2_clip_stats, saturation_counts
    y = fake_quant(x, spec, scale, backend)
    if spec.kind == "pow2":
        return y, pow2_clip_stats(x, scale, spec.bits)
    return y, saturation_counts(get_codec(spec, backend).encode(x, spec,
                                                                scale))


def roundtrip(x: jax.Array, spec: QuantSpec, scale=None,
              backend: str = "reference") -> jax.Array:
    """decode(encode(x)) without STE — pure value quantization (used on
    optimizer state and the gradient wire, where no gradient flows)."""
    codec = get_codec(spec, backend)
    return codec.decode(codec.encode(x, spec, scale), x.dtype)


def per_tensor_max_scale_log2(x: jax.Array, spec: QuantSpec,
                              valid=None, reduce_axes=None) -> jax.Array:
    """``scale_policy="per_tensor_max"``: smallest pow-2 step whose ±qmax
    range covers max|x| (serve/kv_cache.py's prefill scale choice).

    ``valid``: optional bool mask broadcastable against x (rows to include).
    ``reduce_axes``: axes folded into the max (default: all).
    """
    a = jnp.abs(x.astype(jnp.float32))
    if valid is not None:
        a = a * valid
    maxabs = jnp.max(a) if reduce_axes is None else jnp.max(a, axis=reduce_axes)
    return jnp.ceil(jnp.log2(jnp.maximum(maxabs, 1e-8) / spec.qmax))
