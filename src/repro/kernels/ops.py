"""Jitted public wrappers around the Pallas kernels.

Handles padding to TPU-aligned block multiples (the TPU analogue of the
paper's "last dimension must be a multiple of 16" constraint), operand
re-layout for PE1, and interpret-mode selection (interpret=True on CPU where
the kernel body executes in Python for validation; compiled on real TPU).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..numerics.pallas_backend import interpret_mode as _interpret
from ..numerics.pallas_backend import native_backend
from ..obs.counters import record_kernel_call
from . import mla_paged_attention as MPA
from . import paged_attention as PA
from . import ttm_pe1, ttm_pe2, ttm_pe3


def _nbytes(*arrs) -> int:
    """Modeled bytes moved by a kernel call: operand + result footprints
    from static shape/dtype (works on tracers — recorded at trace time, one
    entry per compiled specialization; see obs.counters.record_kernel_call)."""
    return sum(int(np.prod(a.shape)) * jnp.dtype(a.dtype).itemsize
               for a in arrs)


def _pad_to(x: jax.Array, mults: tuple[int, ...]) -> jax.Array:
    pads = [(0, (-s) % m) for s, m in zip(x.shape, mults)]
    if any(p[1] for p in pads):
        return jnp.pad(x, pads)
    return x


def _blk(dim: int, pref: int, floor: int) -> int:
    """Pick a block size <= pref that is a multiple of `floor`."""
    if dim >= pref:
        return pref
    return max(floor, ((dim + floor - 1) // floor) * floor)


@functools.partial(jax.jit, static_argnames=("bits", "impl"))
def pe1(z: jax.Array, g: jax.Array, step_log2: float | None = None,
        bits: int | None = None, impl: str = "pallas") -> jax.Array:
    """PE1 (Eq. 5): Z(a,b,c) x G(b,d,c) -> (a,d), optional fused requantize
    (``bits`` selects the pow2 grid at ``step_log2``; the epilogue body is
    the codec registry's, shared with the unfused reference path).

    impl: "pallas" (the kernel; compiled on TPU, interpret elsewhere — PE1
    is a training kernel, so unlike ``paged_attention`` there is no hot
    off-TPU serve path to protect and the kernel stays the default) or
    "jnp" — the registry-composed reference (einsum + codec encode→decode),
    the oracle the differential tests pin the fused epilogue against.

    Re-layout: G(b,d,c) -> (b*c, d); Z(a,b,c) -> (a, b*c). Cores are KB-sized
    so the one-off G transpose is free relative to the contraction.
    """
    from ..numerics import QuantSpec
    spec = QuantSpec("pow2", bits) if bits is not None else None
    step = 0.0 if step_log2 is None else step_log2
    record_kernel_call(f"pe1.{impl}", bytes_moved=_nbytes(z, g)
                       + z.shape[0] * g.shape[1] * z.dtype.itemsize)
    if impl == "jnp":
        from ..numerics.codecs import get_codec
        from . import ref
        with jax.named_scope("repro.ops.pe1"):
            acc = ref.pe1_ref(z, g).astype(jnp.float32)
            if spec is not None:
                acc = get_codec(spec, "reference").epilogue(
                    acc, spec, jnp.asarray(step, jnp.float32))
            return acc.astype(z.dtype)
    if impl != "pallas":
        raise ValueError(f"unknown pe1 impl {impl!r}")
    a, b, c = z.shape
    b2, d, c2 = g.shape
    assert b == b2 and c == c2, (z.shape, g.shape)
    with jax.named_scope("repro.ops.pe1"):
        zf = z.reshape(a, b * c)
        gf = jnp.transpose(g, (0, 2, 1)).reshape(b * c, d)
        bm = _blk(a, 128, 8)
        bn = _blk(d, 128, 128)
        bk = _blk(b * c, 512, 128)
        zp = _pad_to(zf, (bm, bk))
        gp = _pad_to(gf, (bk, bn))
        out = ttm_pe1.pe1_matmul(zp, gp, bm=bm, bn=bn, bk=bk, spec=spec,
                                 step_log2=step, interpret=_interpret())
        return out[:a, :d]


@jax.jit
def pe2(z: jax.Array, g: jax.Array) -> jax.Array:
    """PE2 (Eq. 6): Z(a,b,c) x G(b,d) -> (a,d,c)."""
    a, b, c = z.shape
    b2, d = g.shape
    assert b == b2, (z.shape, g.shape)
    record_kernel_call("pe2", bytes_moved=_nbytes(z, g)
                       + a * d * c * z.dtype.itemsize)
    with jax.named_scope("repro.ops.pe2"):
        ba = _blk(a, 8, 8)
        bd = _blk(d, 128, 128)
        bc = _blk(c, 128, 128)
        zp = _pad_to(z, (ba, 1, bc))
        gp = _pad_to(g, (1, bd))
        out = ttm_pe2.pe2_batched(zp, gp, ba=ba, bd=bd, bc=bc,
                                  interpret=_interpret())
        return out[:a, :d, :c]


@jax.jit
def pe3(ybar: jax.Array, x: jax.Array) -> jax.Array:
    """PE3: Ybar(b,j) x X(b,i) -> What(j,i) (batch-contracted outer product)."""
    b, j = ybar.shape
    b2, i = x.shape
    assert b == b2, (ybar.shape, x.shape)
    record_kernel_call("pe3", bytes_moved=_nbytes(ybar, x)
                       + j * i * ybar.dtype.itemsize)
    with jax.named_scope("repro.ops.pe3"):
        bj = _blk(j, 128, 8)
        bi = _blk(i, 128, 128)
        bb = _blk(b, 256, 8)
        yp = _pad_to(ybar, (bb, bj))
        xp = _pad_to(x, (bb, bi))
        out = ttm_pe3.pe3_outer(yp, xp, bj=bj, bi=bi, bb=bb,
                                interpret=_interpret())
        return out[:j, :i]


@functools.partial(jax.jit, static_argnames=("bits",))
def quantize_fused(x: jax.Array, step_log2: jax.Array, bits: int) -> jax.Array:
    """Fused fake-quant over an arbitrary-shape tensor — the pow2 Pallas
    codec of ``repro.numerics`` (which pads/reshapes internally)."""
    from ..numerics import QuantSpec, fake_quant
    record_kernel_call("quantize_fused", bytes_moved=2 * _nbytes(x))
    with jax.named_scope("repro.ops.quantize_fused"):
        return fake_quant(x, QuantSpec("pow2", bits), step_log2,
                          backend="pallas")


def _paged_attention_dispatch(q, kdata, vdata, kscale, vscale, table, lens,
                              layer=None, *, page_size, quantized, impl,
                              page_chunk):
    """impl-resolved page walk on whatever head slice it is handed — the
    whole pool, or one device's head shard under ``shard_map``."""
    if impl == "pallas":
        with jax.named_scope("repro.ops.paged_attention"):
            return PA.paged_attention_kernel(
                q, kdata, vdata, kscale, vscale, table, lens,
                page_size=page_size, quantized=quantized,
                interpret=_interpret(), layer=layer)
    if impl == "jnp":
        if page_chunk is None:
            page_chunk = max(1, 256 // page_size)
        with jax.named_scope("repro.ops.paged_attention"):
            return PA.paged_attention_jnp(
                q, kdata, vdata, kscale, vscale, table, lens,
                page_size=page_size, quantized=quantized,
                page_chunk=page_chunk, layer=layer)
    raise ValueError(f"unknown paged_attention impl {impl!r}")


def paged_attention(q: jax.Array, kdata: jax.Array, vdata: jax.Array,
                    kscale: jax.Array, vscale: jax.Array, table: jax.Array,
                    lens: jax.Array, *, page_size: int, quantized: bool,
                    impl: str = "auto", page_chunk: int | None = None,
                    plan=None, layer: jax.Array | None = None) -> jax.Array:
    """Fused paged attention: per-page int8 dequant + online-softmax
    attention over each slot's page list (never materializes the fp32 slot
    view). q is (B, Hq, Dh) for single-token decode or (B, S, Hq, Dh) for a
    q-block (chunked prefill / k-token speculative verify); ``lens`` is the
    position of the first query row either way. k/v are one layer's
    (P+1, page, Hkv, Dh) pages, or, with ``layer`` given, the stacked
    (L, P+1, page, Hkv, Dh) pool leaf the walk indexes at that layer. See
    ``kernels/paged_attention.py`` for layouts.

    impl: "pallas" (the kernel; compiled on TPU, interpret elsewhere),
    "jnp" (the same dataflow as a page-scan in XLA), or "auto" — the kernel
    on TPU (or when JAX_PALLAS_INTERPRET=1 asks for kernel validation), the
    jnp page-scan on other backends where interpret-mode grid iteration
    would serialize the hot loop.

    page_chunk (jnp impl only): pages folded per online-softmax step.
    1 is bit-locked to the kernel's update order; None picks ~256 tokens
    per step to amortize dispatch overhead off-TPU.

    plan (``sharding.ShardPlan``): when its mesh shards the pool's KV-head
    axis over ``model`` (``plan.shards_kv_heads``), the walk runs inside a
    ``shard_map`` — each device walks its local head shard of the pages
    with its local q heads and no collective at all (GQA query heads group
    contiguously per KV head, so shard-local attention is exact; the per-
    slot scales/table/lens are replicated operands). Numerics are those of
    the unsharded walk on each head slice — identical update order per
    head, so decode stays token-identical to single-device.
    """
    if impl == "auto":
        impl = "pallas" if native_backend() else "jnp"
    # bytes actually touched by the page walk: the whole pool row array is
    # an operand, but only each slot's mapped pages move — model the table-
    # addressable footprint (B * pages_per_slot pages) plus q in and out
    pages_touched = table.shape[0] * table.shape[1]
    page_bytes = (int(np.prod(kdata.shape[-3:]))
                  + int(np.prod(vdata.shape[-3:]))
                  ) * jnp.dtype(kdata.dtype).itemsize
    record_kernel_call(f"paged_attention.{impl}",
                       bytes_moved=pages_touched * page_bytes
                       + 2 * _nbytes(q))
    f = functools.partial(_paged_attention_dispatch, page_size=page_size,
                          quantized=quantized, impl=impl,
                          page_chunk=page_chunk)
    extra = () if layer is None else (layer,)
    hkv = kdata.shape[-2]
    if plan is not None and plan.shards_kv_heads(hkv) \
            and q.shape[-2] % hkv == 0:
        from jax.sharding import PartitionSpec as P

        from ..sharding import shard_map
        # q's head axis is -2 in both ranks: (B, Hq, Dh) decode or
        # (B, S, Hq, Dh) q-block
        qspec = (P(None, "model", None) if q.ndim == 3
                 else P(None, None, "model", None))
        # pages: (P+1, page, Hkv, Dh), or the stacked pool with a leading
        # layer axis; the layer index is replicated
        pspec = P(*([None] * (kdata.ndim - 2)), "model", None)
        specs = (qspec, pspec, pspec,                  # q, k/v pages
                 P(None), P(None),                     # per-slot scales
                 P(None, None), P(None))               # table, lens
        f = shard_map(f, plan.mesh, in_specs=specs + (P(),) * len(extra),
                      out_specs=qspec)
    return f(q, kdata, vdata, kscale, vscale, table, lens, *extra)


def mla_paged_attention(q_abs: jax.Array, q_rope: jax.Array, ckv: jax.Array,
                        krope: jax.Array, cscale: jax.Array,
                        rscale: jax.Array, table: jax.Array,
                        lens: jax.Array, active: jax.Array,
                        layer: jax.Array, *, page_size: int,
                        quantized: bool, sm_scale: float,
                        impl: str = "auto") -> jax.Array:
    """MLA decode attention in the latent space, straight off the stacked
    int8 (or model-dtype) pool leaves at ``layer``, over each active slot's
    live pages: (B, H, kv_lora) latent output, before the value
    up-projection. See ``kernels/mla_paged_attention.py`` for layouts and
    the numerics contract.

    impl: "pallas" (the kernel; compiled on TPU, interpret elsewhere),
    "jnp" (the same blocks as a loop in XLA), or "auto" -- the kernel where
    Pallas is native, the loop elsewhere. Runs under the named scope
    ``repro.ops.mla_attention``. Unsharded: the latent leaves are
    replicated under a mesh (``ShardPlan.kv_page_spec``), where the engine
    keeps the gather path."""
    if impl == "auto":
        impl = "pallas" if native_backend() else "jnp"
    # bytes the walk may touch: every table-addressable page of both
    # leaves (it reads the live ones only), plus q in and the latent out
    pages = table.shape[0] * table.shape[1]
    page_bytes = (int(np.prod(ckv.shape[-2:])) * ckv.dtype.itemsize
                  + int(np.prod(krope.shape[-2:])) * krope.dtype.itemsize)
    record_kernel_call(f"mla_paged_attention.{impl}",
                       bytes_moved=pages * page_bytes
                       + 2 * _nbytes(q_abs) + _nbytes(q_rope))
    kw = dict(page_size=page_size, quantized=quantized, sm_scale=sm_scale)
    args = (q_abs, q_rope, ckv, krope, cscale, rscale, table, lens, active,
            layer)
    with jax.named_scope("repro.ops.mla_attention"):
        if impl == "pallas":
            return MPA.mla_paged_attention_kernel(*args,
                                                  interpret=_interpret(),
                                                  **kw)
        if impl == "jnp":
            return MPA.mla_paged_attention_jnp(*args, **kw)
    raise ValueError(f"unknown mla_paged_attention impl {impl!r}")


def ttm_matvec_kernels(cores, x, spec):
    """TTM forward chain routed through the PE kernels (kernel-path analogue
    of ``core.ttm.ttm_matvec``). Used in tests and kernel benchmarks."""
    from ..core.ttm import ttm_matvec_pe

    def k_pe1(z, g):
        return pe1(z, g)

    def k_pe2(z, g):
        return pe2(z, g)

    return ttm_matvec_pe(cores, x, spec, pe1=k_pe1, pe2=k_pe2)
