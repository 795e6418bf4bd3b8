"""Slot-paged KV-cache pool with pow-2 symmetric fixed-point storage.

The serving cache is a pool of fixed-size *pages* shared by all request
slots.  A slot owns an ordered list of pages (its row of the page table);
token position ``t`` of a slot lives at ``(page_table[slot, t // page_size],
t % page_size)``.  Pages are allocated lazily as a request's length crosses
page boundaries and returned to the free list when the request retires, so
pool memory scales with *live tokens*, not ``num_slots * max_len``.

Quantization (the paper's §3.2 numerics applied to serving): K/V entries are
stored as ``int8`` codes on a power-of-2 grid, ``x ≈ q * 2^scale_log2`` with
``q ∈ [-2^{b-1}, 2^{b-1}-1]``, one ``scale_log2`` per (layer, slot, tensor)
chosen from the prompt's K/V range at prefill and reused for decode appends
(decode K/V share the prompt's amplitude).  Dequantization happens on read,
immediately before the attention einsums — the resident cache is 1 byte per
element instead of 4, the ≥3.5× serving-memory version of the paper's 292×
training-memory result.

Everything here is jit-safe: writes are batched scatters via ``.at[]``,
reads are page-table gathers.  Inactive slots write to a reserved *trash
page* (index ``num_pages``) so one compiled step serves any live/dead slot
mix.  Host-side page accounting lives in ``serve/scheduler.py``.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ..numerics import (QTensor, QuantSpec, get_codec,
                        per_tensor_max_scale_log2, qrange)


def codec_backend() -> str:
    """Codec backend for the pool's encode/decode: the fused Pallas
    multi-scale kernels where they run natively (TPU, or forced kernel
    validation via JAX_PALLAS_INTERPRET=1), the jnp reference elsewhere —
    the two are bit-identical (tests/test_numerics.py), this only picks the
    faster lowering. (Deferred import: the pallas backend only loads when
    it is actually the selected lowering.)"""
    from ..numerics.pallas_backend import native_backend
    return "pallas" if native_backend() else "reference"


def _kv_spec(bits: int) -> QuantSpec:
    """The ``kv_cache`` site: pow-2 int8 codes, per-tensor-max scale chosen
    at prefill. One constructor so PoolConfig, the scale chooser, and the
    encode/decode paths can never diverge."""
    return QuantSpec("pow2", bits, 0, "int8", "per_tensor_max")


@dataclass(frozen=True)
class PoolConfig:
    """Geometry + numerics of the paged pool."""
    num_slots: int              # max concurrent requests (decode batch)
    page_size: int = 16         # tokens per page
    pages_per_slot: int = 8     # max pages one slot may hold
    num_pages: int = 0          # physical pages shared by all slots
                                # (0 => num_slots * pages_per_slot, no sharing)
    quantized: bool = False     # int8 pow-2 storage vs model-dtype storage
    bits: int = 8

    @property
    def spec(self) -> QuantSpec:
        """The ``kv_cache`` site spec this pool stores under."""
        return _kv_spec(self.bits)

    @property
    def max_len(self) -> int:
        return self.page_size * self.pages_per_slot

    @property
    def total_pages(self) -> int:
        return self.num_pages or self.num_slots * self.pages_per_slot

    @property
    def trash_page(self) -> int:
        """Reserved page absorbing writes from inactive/padded positions."""
        return self.total_pages

    def pages_for(self, n_tokens: int) -> int:
        return -(-max(n_tokens, 1) // self.page_size)


# ---------------------------------------------------------------------------
# Pool construction
# ---------------------------------------------------------------------------

def kv_feature_shapes(sub) -> dict[str, tuple[int, ...]]:
    """Per-token trailing feature shape of each cached tensor of a sublayer
    (the same layouts ``models/attention.py`` caches). Recurrent mixers
    (mamba/rwkv6) cache no per-token tensors — their O(1) state lives in
    the slot-indexed pool of ``serve/state_cache.py`` — so they map to {}."""
    if sub.mixer_kind == "attn_gqa":
        d = sub.mixer
        return {"k": (d.num_kv_heads, d.head_dim),
                "v": (d.num_kv_heads, d.head_dim)}
    if sub.mixer_kind == "attn_mla":
        m = sub.mixer.m
        return {"c_kv": (m.kv_lora_rank,), "k_rope": (m.qk_rope_head_dim,)}
    if sub.mixer_kind in ("mamba", "rwkv6"):
        return {}
    raise ValueError(f"unknown mixer kind {sub.mixer_kind!r}")


LANES = 128


def page_shape(page_size: int, feat: tuple[int, ...]) -> tuple[int, ...]:
    """Storage shape of one page of a cached tensor whose tokens each hold
    ``feat``: (page, *feat), or, for one feature axis narrower than a row
    of 128 lanes that tiles it, the page's tokens packed row-major into
    such rows, (page * d / 128, 128) -- the same values in the same order.

    The TPU tiles the last two axes of an array by rows of 128 lanes. A
    (page, 64) page (MLA's rope key) would fill half of each row, so the
    runtime lays such a leaf out with the page axis minor instead, and
    every program that indexes pages relays the whole leaf: two copies of
    it a decode step, temporaries that grow with the pool. Packed, the leaf
    keeps the row-major layout the page gathers and token scatters use."""
    if (len(feat) == 1 and feat[0] < LANES and LANES % feat[0] == 0
            and page_size * feat[0] % LANES == 0):
        return (page_size * feat[0] // LANES, LANES)
    return (page_size,) + tuple(feat)


def _packed(leaf: jax.Array, n_lead: int, pcfg: PoolConfig) -> bool:
    """Whether a pool leaf with ``n_lead`` axes before its pages' own
    (page axis, or layer and page axes) stores packed lane rows."""
    s = leaf.shape[n_lead:]
    return len(s) == 2 and s[-1] == LANES and s[0] != pcfg.page_size


def _page_feat(leaf: jax.Array, n_lead: int, pcfg: PoolConfig
               ) -> tuple[int, ...]:
    """The per-token feature shape a pool leaf stores."""
    s = leaf.shape[n_lead:]
    if _packed(leaf, n_lead, pcfg):
        return (s[0] * LANES // pcfg.page_size,)
    return tuple(s[1:])


def _set_tokens(leaf: jax.Array, lead: tuple, pages: jax.Array,
                offs: jax.Array, vals: jax.Array, pcfg: PoolConfig
                ) -> jax.Array:
    """``leaf.at[*lead, pages, offs].set(vals)``: write each token's values
    at (page, offset) in the leaf's page layout. ``lead`` holds the index
    of each axis before the page axis (the layer, or none); the index
    arrays broadcast against each other to ``vals.shape[:-len(feat)]``.
    A packed leaf takes each token's d values at their lanes of its row:
    the TPU compiler emits one scatter either way (a windowed
    ``lax.scatter`` into part of a row becomes a loop, one token a turn)."""
    if not _packed(leaf, len(lead) + 1, pcfg):
        return leaf.at[lead + (pages, offs)].set(vals)
    d = vals.shape[-1]
    flat = offs * d
    ex = lambda a: jnp.asarray(a)[..., None]
    idx = tuple(ex(a) for a in lead) + (
        ex(pages), ex(flat // LANES), ex(flat % LANES) + jnp.arange(d))
    return leaf.at[idx].set(vals)


def init_pool(lm, pcfg: PoolConfig) -> dict:
    """Allocate the device half of the pool for every attention sublayer of
    ``lm`` (recurrent sublayers get empty dicts: their state lives in the
    ``state_cache`` pool, keyed identically for the engine's layer scan).

    Returns {"data": {sub_i: {name: (L, P+1, *page_shape) int8|dtype}},
             "scale_log2": {sub_i: {name: (L, num_slots) f32}}}, L =
    ``lm.n_stack`` (leading layers take the first entries of sub_0).
    ``scale_log2`` is carried (zero) in fp mode too so the step function's
    pytree structure is independent of the numerics mode.
    """
    fp_dtype = jnp.dtype(lm.cfg.dtype)
    store = jnp.int8 if pcfg.quantized else fp_dtype
    L = lm.n_stack
    data: dict = {}
    scale: dict = {}
    for i, sub in enumerate(lm.period):
        feats = kv_feature_shapes(sub)
        data[f"sub_{i}"] = {
            name: jnp.zeros((L, pcfg.total_pages + 1)
                            + page_shape(pcfg.page_size, f), store)
            for name, f in feats.items()}
        scale[f"sub_{i}"] = {
            name: jnp.zeros((L, pcfg.num_slots), jnp.float32)
            for name in feats}
    return {"data": data, "scale_log2": scale}


def pool_bytes(pool: dict) -> int:
    """Resident bytes of the cache pool (storage + scales)."""
    return sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(pool))


def pool_bytes_fp32(pool: dict) -> int:
    """What the same pool's data would cost stored as f32 (scales excluded:
    an fp32 pool carries none) — the denominator of the cache-reduction
    figure and the ledger's ``kv_pool`` fp32 shadow."""
    return 4 * sum(int(np.prod(a.shape))
                   for a in jax.tree_util.tree_leaves(pool["data"]))


def page_nbytes(pool: dict, pcfg: PoolConfig) -> int:
    """Physical bytes of ONE page summed across every cached tensor of
    every layer (data leaves are (L, P+1, *page_shape): each of the P+1
    physical pages owns an equal 1/(P+1) slice).  Per-slot scale rows are
    page-independent and excluded.  This is the unit that turns the page
    table's logical-vs-physical mapped counts into verified bytes
    (``obs.ledger``: ``prefix_bytes_saved``)."""
    n = pcfg.total_pages + 1
    return sum(leaf.nbytes // n
               for leaf in jax.tree_util.tree_leaves(pool["data"]))


# ---------------------------------------------------------------------------
# Quantize / dequantize — the ``kv_cache`` site of the unified quantization
# API (pow-2 codec of repro.numerics; same grid as core/quant.py)
# ---------------------------------------------------------------------------

def choose_scale_log2(x: jax.Array, valid: jax.Array, bits: int) -> jax.Array:
    """Smallest pow-2 step covering max|x| over valid rows
    (``scale_policy="per_tensor_max"``: one scale per layer, from the
    prompt's K/V range at prefill).

    x: (L, S, *feat); valid: (S,) bool. Returns (L,) f32 integer-valued."""
    mask = valid.reshape((1, -1) + (1,) * (x.ndim - 2))
    return per_tensor_max_scale_log2(x, _kv_spec(bits), valid=mask,
                                     reduce_axes=tuple(range(1, x.ndim)))


def quantize(x: jax.Array, scale_log2: jax.Array, bits: int) -> jax.Array:
    """fp -> int8 codes; scale_log2 broadcast against x's leading dims.
    On the native-kernel backend this is the fused multi-scale encode (the
    pool's scatter-on-append quantizes in one Pallas pass)."""
    spec = _kv_spec(bits)
    return get_codec(spec, codec_backend()).encode(x, spec, scale_log2).codes


def dequantize(q: jax.Array, scale_log2: jax.Array, dtype) -> jax.Array:
    # decode is bits-independent (codes * 2^scale); the 8-bit default spec
    # selects the pow2 codec
    spec = _kv_spec(8)
    return get_codec(spec, codec_backend()).decode(
        QTensor(q, scale_log2, spec), dtype)


# ---------------------------------------------------------------------------
# Per-layer jit primitives (used inside the engine's layer scan)
# ---------------------------------------------------------------------------

def gather_slots(data_l: jax.Array, scale_l: jax.Array, table: jax.Array,
                 pcfg: PoolConfig, dtype, layer: jax.Array | None = None
                 ) -> jax.Array:
    """Materialize every slot's cache view for one layer.

    data_l: (P+1, *page_shape), or the stacked (L, P+1, *page_shape) leaf
    with ``layer`` (gathered straight out of the stack, no per-layer slab);
    scale_l: (num_slots,); table: (B, pages_per_slot). Returns (B,
    T=max_len, *feat) in ``dtype`` (dequantized on read).
    """
    idx = table if layer is None else (layer, table)
    feat = _page_feat(data_l, 1 if layer is None else 2, pcfg)
    g = data_l[idx]                                   # (B, pp, *page_shape)
    b = table.shape[0]
    g = g.reshape((b, pcfg.max_len) + feat)
    if pcfg.quantized:
        return dequantize(g, scale_l.reshape((b,) + (1,) * (g.ndim - 1)),
                          dtype)
    return g.astype(dtype)


def fused_attend(kdata_l: jax.Array, vdata_l: jax.Array, kscale_l: jax.Array,
                 vscale_l: jax.Array, q: jax.Array, table: jax.Array,
                 lens: jax.Array, pcfg: PoolConfig,
                 impl: str = "auto", plan=None,
                 layer: jax.Array | None = None) -> jax.Array:
    """GQA decode attention straight off the paged pool — the fused
    alternative to ``gather_slots`` + ``models/attention.py::gqa_attend``.

    The pool's device layout IS the kernel's: ``kdata_l``/``vdata_l`` are
    one layer's (P+1, page, Hkv, Dh) page array (row P = trash page), or,
    with ``layer`` given, the stacked (L, P+1, page, Hkv, Dh) pool leaf the
    walk reads at that layer. The decode step passes the stacked form: its
    layer scan carries the whole pool and updates it in place, and a
    per-layer slab for the kernel operand would copy it every step.
    ``table`` the (B, pages_per_slot) page-pointer rows, ``kscale_l``/
    ``vscale_l`` the (B,) per-slot pow-2 scales, ``lens`` the (B,) incoming
    token positions.  The kernel walks each slot's page list, dequantizes
    int8 pages in-kernel, and accumulates online-softmax attention per page
    — the (B, max_len, *feat) fp32 slot view is never materialized.

    q: (B, Hq, Dh) single-token decode, or (B, S, Hq, Dh) — a q-block
    (chunked prefill / k-token speculative verify) whose rows sit at
    positions ``lens .. lens + S - 1`` with a per-row causal mask. Returns
    the same rank in q.dtype.

    ``plan``: a ``ShardPlan`` whose mesh head-shards the pool
    (``plan.kv_page_spec``) makes the walk run shard_map'd per device on
    its local KV heads — see ``kernels/ops.py::paged_attention``.
    """
    from ..kernels.ops import paged_attention
    return paged_attention(q, kdata_l, vdata_l, kscale_l, vscale_l,
                           table, lens, page_size=pcfg.page_size,
                           quantized=pcfg.quantized, impl=impl, plan=plan,
                           layer=layer)


def append_token(data_l: jax.Array, scale_l: jax.Array, new: jax.Array,
                 table: jax.Array, lens: jax.Array, active: jax.Array,
                 pcfg: PoolConfig, layer: jax.Array | None = None
                 ) -> jax.Array:
    """Scatter one new token per slot at its own length.

    new: (B, 1, *feat) fp; inactive slots are redirected to the trash page.
    Decode appends reuse the slot's prefill scale (clipping into its range).
    data_l is one layer's (P+1, *page_shape) pages, or the stacked (L, P+1,
    *page_shape) leaf with ``layer``: one scatter at [layer, page, off],
    in place when the caller carries the pool.
    """
    b = new.shape[0]
    page_idx = lens // pcfg.page_size
    pages = jnp.take_along_axis(table, page_idx[:, None], axis=1)[:, 0]
    pages = jnp.where(active, pages, pcfg.trash_page)
    offs = lens % pcfg.page_size
    vals = new[:, 0]
    if pcfg.quantized:
        vals = quantize(vals, scale_l.reshape((b,) + (1,) * (vals.ndim - 1)),
                        pcfg.bits)
    else:
        vals = vals.astype(data_l.dtype)
    lead = () if layer is None else (layer,)
    return _set_tokens(data_l, lead, pages, offs, vals, pcfg)


def append_tokens(data_l: jax.Array, scale_l: jax.Array, new: jax.Array,
                  table: jax.Array, lens: jax.Array, active: jax.Array,
                  pcfg: PoolConfig) -> jax.Array:
    """Scatter S new tokens per slot at positions lens..lens+S-1 (the
    speculative-verify write: the incoming token plus the k draft tokens
    land in one batched scatter).

    new: (B, S, *feat) fp. Inactive slots and positions at/above
    ``max_len`` (a draft block overhanging the slot horizon) are redirected
    to the trash page. Like decode appends, values clip into the slot's
    prefill scale. Rejected tokens' K/V stay in the pool as junk above the
    slot's advanced length — the kernel's causal length mask never reads
    them, and later writes at those positions overwrite in place, so
    rollback needs no data movement (page bookkeeping only, see
    ``Scheduler.trim_unused``)."""
    b, s = new.shape[:2]
    pos = lens[:, None] + jnp.arange(s)[None, :]             # (B, S)
    page_idx = jnp.clip(pos // pcfg.page_size, 0, pcfg.pages_per_slot - 1)
    pages = jnp.take_along_axis(table, page_idx, axis=1)
    ok = active[:, None] & (pos < pcfg.max_len)
    pages = jnp.where(ok, pages, pcfg.trash_page)
    offs = pos % pcfg.page_size
    if pcfg.quantized:
        vals = quantize(new, scale_l.reshape((b,) + (1,) * (new.ndim - 1)),
                        pcfg.bits)
    else:
        vals = new.astype(data_l.dtype)
    return _set_tokens(data_l, (), pages, offs, vals, pcfg)


def append_health(new: jax.Array, scale_l: jax.Array, active: jax.Array,
                  pcfg: PoolConfig) -> tuple[jax.Array, jax.Array]:
    """(clipped, total) of one decode append against the slots' prefill-
    frozen scales — the ``kv_cache`` quant-health signal (repro.obs).

    Decode K/V reuse the prompt's scale (see ``append_token``), so a rising
    clip fraction means decode amplitudes outgrew the prefill range. Same
    shapes as ``append_token``: new (B, 1, *feat), scale_l (B,), active (B,)
    bool. Integer-exact — backends bit-agree."""
    from ..obs.counters import pow2_clip_stats
    vals = new[:, 0]
    valid = active.reshape((-1,) + (1,) * (vals.ndim - 1))
    return pow2_clip_stats(vals, scale_l, pcfg.bits, valid=valid)


def write_chunk(data_l: jax.Array, scale_l: jax.Array, vals: jax.Array,
                table_row: jax.Array, start: jax.Array, valid_len: jax.Array,
                slot: jax.Array, pcfg: PoolConfig
                ) -> tuple[jax.Array, jax.Array]:
    """Write a prefill chunk of one slot into one layer's pool.

    vals: (S, *feat) fp (positions start..start+S-1; only the first
    ``valid_len`` rows are real). The slot's scale must already be set (the
    first prefill chunk always goes through ``write_prefill``, which derives
    it); this chunk clips into that range. Returns (data_l, scale_l)."""
    s = vals.shape[0]
    pos = start + jnp.arange(s)
    valid = jnp.arange(s) < valid_len
    pages = table_row[pos // pcfg.page_size]
    pages = jnp.where(valid, pages, pcfg.trash_page)
    offs = pos % pcfg.page_size
    if pcfg.quantized:
        vals = quantize(vals, scale_l[slot][None], pcfg.bits)
    else:
        vals = vals.astype(data_l.dtype)
    return _set_tokens(data_l, (), pages, offs, vals, pcfg), scale_l


class PageRefs:
    """Host-side reference counts over the pool's physical pages.

    A page's count is the number of *readers* currently holding it mapped
    or reserved: every slot that acquired the page as a shared prefix page,
    plus the slot (if any) that reserved it as a COW-fork source.  Tree
    ownership itself (``serve/prefix.py``) is NOT a reference — a cached
    page with no live readers has count 0 and is evictable.  The allocator
    free list and this table are disjoint by construction: pages are handed
    to the refcount world only while allocated."""

    def __init__(self, num_pages: int):
        self._refs = np.zeros(num_pages, np.int32)

    def acquire(self, pages: list[int]) -> None:
        for p in pages:
            self._refs[p] += 1

    def release(self, pages: list[int]) -> None:
        for p in pages:
            self._refs[p] -= 1
            if self._refs[p] < 0:
                raise AssertionError(f"page {p} released below zero")

    def count(self, page: int) -> int:
        return int(self._refs[page])

    def unreferenced(self, pages: list[int]) -> bool:
        return all(self._refs[p] == 0 for p in pages)


def fork_page(pool: dict, src: jax.Array, dst: jax.Array) -> dict:
    """Copy-on-write page copy: duplicate physical page ``src`` into ``dst``
    for every cached tensor of every layer, codes (or fp values) verbatim.

    No dequant/requant round-trip happens — int8 codes are moved bit-exactly,
    so a forked page is indistinguishable from the donor's up to the fork
    point.  The reader's slot scale must be adopted from the donor's
    (``adopt_scales``) for those codes to decode to the donor's values."""
    data = dict(pool["data"])
    for key, kinds in data.items():
        new_d = dict(kinds)
        for name, arr in kinds.items():
            new_d[name] = arr.at[:, dst].set(arr[:, src])
        data[key] = new_d
    return {"data": data, "scale_log2": pool["scale_log2"]}


def snapshot_scales(pool: dict, slot: int) -> dict:
    """Host-side copy of one slot's per-layer scales: {key: {name: (L,) np}}.
    Taken after prefill so the prefix tree can hand the same decode grid to
    every future reader of the inserted pages."""
    return {key: {name: np.asarray(arr[:, slot])
                  for name, arr in kinds.items()}
            for key, kinds in pool["scale_log2"].items()}


def adopt_scales(pool: dict, slot: jax.Array, snap: dict) -> dict:
    """Set one slot's scale rows from a prefix node's snapshot (leaves (L,)).
    Shared int8 pages then decode under the exact grid they were written
    with; the reader's own suffix chunks and decode appends clip into it —
    the same contract chunked prefill already obeys."""
    scale = dict(pool["scale_log2"])
    for key, kinds in snap.items():
        new_s = dict(scale[key])
        for name, vals in kinds.items():
            new_s[name] = new_s[name].at[:, slot].set(vals)
        scale[key] = new_s
    return {"data": pool["data"], "scale_log2": scale}


def write_prefill(pool: dict, cache: dict, table_row: jax.Array,
                  slot: jax.Array, length: jax.Array, pcfg: PoolConfig
                  ) -> dict:
    """Scatter a whole-prompt prefill cache (from ``lm_forward``) into the
    pool for one slot, all layers at once.

    cache leaves: (L, 1, S, *feat) — the stacked per-layer caches the model
    returns. Rows past ``length`` (bucket padding) go to the trash page."""
    data, scale = dict(pool["data"]), dict(pool["scale_log2"])
    sample = next(iter(next(iter(cache.values())).values()))
    s = sample.shape[2]
    pos = jnp.arange(s)
    valid = pos < length
    pages = jnp.where(valid, table_row[pos // pcfg.page_size],
                      pcfg.trash_page)
    offs = pos % pcfg.page_size
    for key, kinds in cache.items():
        new_d = dict(data[key])
        new_s = dict(scale[key])
        for name, arr in kinds.items():
            vals = arr[:, 0]                             # (L, S, *feat)
            if pcfg.quantized:
                step = choose_scale_log2(vals, valid, pcfg.bits)   # (L,)
                new_s[name] = new_s[name].at[:, slot].set(step)
                vals = quantize(vals, step[:, None], pcfg.bits)
            else:
                vals = vals.astype(new_d[name].dtype)
            if vals.ndim == 3:
                # one feature axis (MLA's latent leaves): with the layer a
                # slice of the scatter, the TPU compiler lays the leaf out
                # again around it, a pool-sized temporary, so the layer is
                # an index too; (heads, dim) leaves scatter in place as a
                # slice (AOT compile for a v5e)
                layers = jnp.arange(vals.shape[0])[:, None]
                new_d[name] = _set_tokens(new_d[name], (layers,),
                                          pages[None], offs[None], vals,
                                          pcfg)
            else:
                new_d[name] = new_d[name].at[:, pages, offs].set(vals)
        data[key] = new_d
        scale[key] = new_s
    return {"data": data, "scale_log2": scale}
