"""Continuous-batching inference engine.

One fixed-shape jitted decode step serves the whole request stream: requests
occupy *slots* of a ``num_slots``-lane batch, each with its own length in a
per-slot ``cur_len`` vector; EOS / max-length retirement frees a slot (and
its cache pages) which the scheduler refills on the next iteration, so the
decode batch never drains to admit new work.  K/V live in the slot-paged,
optionally int8-quantized pool of ``serve/kv_cache.py`` and are dequantized
on read inside the per-layer scan.

Sublayer routing: attention sublayers read/write the slot-paged KV pool
(``serve/kv_cache.py``: the gather path, or with ``fused_attention`` the
page walk for GQA and the latent walk for MLA); SSM/RWKV
sublayers read/write the slot-indexed recurrent-state cache
(``serve/state_cache.py``) through the single-step decode entry points of
``models/ssm.py`` — so pure-SSM (rwkv6), hybrid (jamba) and all-attention
configs run under one continuous-batching regime.

Numerics contract: in fp (non-quantized) mode the engine's prefill is the
model's own ``lm_forward`` and its decode runs the exact attend helpers of
``models/attention.py`` (and the exact recurrence steps of
``models/ssm.py``) over the same cached values/state, so continuous-batched
greedy decode is token-identical to the static single-request reference
(asserted by tests/test_serve.py and tests/test_serve_state.py). MoE:
inactive decode slots, chunked-prefill tail padding, and whole-prompt
prefill bucket padding are all masked out of the router (zero combine
weight; see ``models/moe.py::_route`` and ``lm_forward(token_mask=...)``),
and every MoE sublayer is served dropless (``models/lm.py::dropless``), so
a token's output depends on no other token of its batch or prefill chunk.

Leading layers (``LMDef.lead``, e.g. Moonlight's dense layer 0) run before
the layer scan on the first entries of the same stacked pool; chunked
prefill, the prefix cache and speculative decoding scan the periods only
and refuse such a model.

Archs with recurrent state ignore ``prefill_bucket`` and pad no prefill
chunks: a pad token would contaminate the scan-carried state (attention can
trash-page a pad write; a recurrence cannot unwind one), so their prefill
shapes are exact-length.

Supported archs: every decoder family in the zoo — dense / MoE, GQA or
MLA, pure-SSM (rwkv6), hybrid (jamba). Frontend (vision/audio) archs are
an open roadmap item.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from dataclasses import dataclass
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels import ops
from ..numerics import NumericsPolicy
from ..models import attention as A
from ..models import ssm as S
from ..models.common import apply_site, rms_norm
from ..models.lm import (LMDef, dropless, embed_tokens, lm_forward,
                         sub_ffn_decode)
from ..obs.trace import span, step_span
from ..sharding import ShardPlan
from . import kv_cache as KC
from . import state_cache as SC
from .bucketing import CompileCache, bucket_len
from .kv_cache import PoolConfig
from .metrics import ServeMetrics
from .prefix import RadixPrefixCache
from .sampling import (SamplingParams, processed_probs, sample_from_probs,
                       sample_tokens, spec_accept)
from .scheduler import Request, Scheduler


class Completion(NamedTuple):
    rid: int
    prompt: list[int]
    tokens: list[int]           # generated tokens (first token included)


@dataclass(frozen=True)
class EngineConfig:
    pool: PoolConfig
    prefill_chunk: int = 0      # 0: whole-prompt prefill only
    prefill_bucket: int = 0     # pad prompts to a multiple of this to bound
                                # compile count (0: exact length). Pad
                                # tokens are masked out of MoE routing;
                                # archs with recurrent state ignore the
                                # bucket (pads would contaminate the
                                # scan-carried state)
    seed: int = 0
    policy: "NumericsPolicy | None" = None
                                # numerics policy: when set, its ``kv_cache``
                                # site overrides the pool's quantized/bits
                                # knobs (one owner for the system's numerics)
    fused_attention: bool = False
                                # decode attends straight off the paged
                                # pool (per-page in-kernel int8 dequant +
                                # online softmax) instead of gather_slots +
                                # attend: GQA sublayers through the page
                                # walk (decode and verify), MLA sublayers
                                # through the latent walk (decode without a
                                # mesh; MLA verify keeps the gather, see
                                # ``Engine._fused_for``)
    fused_impl: str = "auto"    # "auto" | "pallas" | "jnp" — see
                                # kernels/ops.py::paged_attention
    prefix_cache: bool = False
                                # radix-tree COW prefix sharing over the
                                # paged pool (serve/prefix.py). Attention-
                                # only archs; archs with recurrent state
                                # silently take the always-miss path (their
                                # O(1) state is not per-token addressable)
    max_prefill_shapes: int = 0
                                # bound on live jitted prefill shapes
                                # (whole-prompt + chunk widths); LRU-evicted
                                # beyond it (serve/bucketing.py). 0:
                                # unbounded (the pre-policy behavior)
    spec_k: int = 0             # speculative decoding: draft tokens
                                # proposed per step (0: off). Needs a draft
                                # model (Engine(..., draft=(lm, params)));
                                # the target verifies all k+1 positions in
                                # ONE q-block kernel call and rejection
                                # sampling accepts a prefix — greedy
                                # spec-decode is token-identical to
                                # non-speculative greedy. Attention-only
                                # draft AND target (recurrent state cannot
                                # roll back a rejected token)


# ---------------------------------------------------------------------------
# Per-sublayer serve bodies (shared by decode + chunked prefill)
# ---------------------------------------------------------------------------

def _project(pm: dict, h: jax.Array, sub, cfg, positions: jax.Array):
    """Queries + new cache entries for one sublayer. h: (B,S,D)."""
    if sub.mixer_kind == "attn_gqa":
        q, k_new, v_new = A.gqa_decode_qkv(pm, h, sub.mixer, cfg, positions)
        return {"q": q}, {"k": k_new, "v": v_new}
    q_abs, q_rope = A.mla_decode_q(pm, h, sub.mixer, cfg, positions)
    c_new, kr_new = A._mla_kv_latent(pm, h, sub.mixer, cfg, positions)
    return ({"q_abs": q_abs, "q_rope": q_rope},
            {"c_kv": c_new, "k_rope": kr_new})


def _attention(pm: dict, qd: dict, kv: dict, sub, cfg,
               positions: jax.Array) -> jax.Array:
    """Attention over gathered (dequantized) cache views, before the
    output projection."""
    if sub.mixer_kind == "attn_gqa":
        return A.gqa_attend(qd["q"], kv["k"], kv["v"], sub.mixer, positions)
    return A.mla_attend(pm, qd["q_abs"], qd["q_rope"], kv["c_kv"],
                        kv["k_rope"], sub.mixer, cfg, positions)


def _attend(pm: dict, qd: dict, kv: dict, sub, cfg,
            positions: jax.Array) -> jax.Array:
    """Attention over gathered (dequantized) cache views + output proj."""
    return apply_site(pm["o"], _attention(pm, qd, kv, sub, cfg, positions),
                      sub.mixer.o, cfg)


def _gather_scope(sub):
    """The named scope of a decode sublayer's MLA attention:
    ``repro.ops.mla_attention`` over the latent gather and absorbed
    attention, or the latent walk, and the value up-projection; none for
    GQA."""
    if sub.mixer_kind == "attn_mla":
        return jax.named_scope("repro.ops.mla_attention")
    return contextlib.nullcontext()


# ---------------------------------------------------------------------------
# Engine
# ---------------------------------------------------------------------------

class Engine:
    """Continuous-batching serving engine over a paged, quantized KV pool."""

    def __init__(self, lm: LMDef, params, ecfg: EngineConfig,
                 plan: ShardPlan | None = None, clock=time.monotonic,
                 trace=None, draft=None):
        cfg = lm.cfg
        if cfg.is_encoder:
            raise NotImplementedError("encoder-only archs have no decode path")
        if cfg.frontend != "none":
            raise NotImplementedError(
                "frontend (vision/audio) serving is an open roadmap item")
        for sub in lm.period:
            KC.kv_feature_shapes(sub)   # raises for unknown mixer kinds
        if lm.lead and (ecfg.prefill_chunk > 0 or ecfg.prefix_cache
                        or ecfg.spec_k > 0):
            raise NotImplementedError(
                "chunked prefill, the prefix cache and speculative decoding "
                "scan the layer periods only: a model with leading layers "
                "serves with whole-prompt prefill")
        lm = dropless(lm)
        # per-sublayer routing: attention -> paged KV pool, SSM/RWKV ->
        # slot-indexed recurrent-state cache
        self._attn_keys = tuple(
            f"sub_{i}" for i, sub in enumerate(lm.period)
            if sub.mixer_kind in ("attn_gqa", "attn_mla"))
        self._state_keys = tuple(
            f"sub_{i}" for i, sub in enumerate(lm.period)
            if sub.mixer_kind in ("mamba", "rwkv6"))
        self.lm = lm
        self.params = params
        self.ecfg = ecfg
        pcfg = ecfg.pool
        squant, sbits = pcfg.quantized, pcfg.bits
        if ecfg.policy is not None:
            kv = ecfg.policy.spec_for("kv_cache")
            pcfg = dataclasses.replace(pcfg, quantized=ecfg.policy.enable,
                                       bits=kv.bits)
            if self._state_keys:    # only validated where a state pool
                try:                # will actually exist
                    ss = ecfg.policy.spec_for("ssm_state")
                except KeyError:    # pre-ssm_state policy JSON: follow kv
                    ss = kv
                if (ss.kind, ss.storage_dtype) != ("pow2", "int8"):
                    raise NotImplementedError(
                        f"state cache stores pow2 int8 codes only, "
                        f"ssm_state site asks for "
                        f"{ss.kind}/{ss.storage_dtype}")
                squant, sbits = ecfg.policy.enable, ss.bits
        self.pcfg = pcfg
        self.scfg = SC.StateCacheConfig(quantized=squant, bits=sbits)
        self.plan = plan or ShardPlan(mesh=None)
        self.pool = KC.init_pool(lm, self.pcfg)
        self.spool = SC.init_state_pool(lm, self.pcfg.num_slots, self.scfg)
        # multi-device serving: place params and both pools by the plan —
        # KV pages head-sharded over ``model`` (plan.kv_page_spec), state
        # features over d_inner/heads (plan.state_spec), per-slot scales
        # replicated. The jitted step bodies re-assert these shardings on
        # their pool outputs (_ckv/_cst) so the donated buffers keep their
        # layout across steps; with no mesh both helpers are identity and
        # every jaxpr is unchanged (tests/test_obs.py byte-identity).
        self._pool_ns = self._spool_ns = None
        if self.plan.mesh is not None:
            self._pool_ns = self.plan.kv_pool_sharding(self.pool)
            self._spool_ns = self.plan.state_pool_sharding(self.spool)
            self.params = jax.device_put(
                self.params, self.plan.params_sharding_tree(self.params))
            self.pool = jax.device_put(self.pool, self._pool_ns)
            self.spool = jax.device_put(self.spool, self._spool_ns)
        # optional obs.TraceRecorder: host-side only — events are emitted
        # from the untraced step loop, never inside a jitted body, so an
        # attached recorder leaves every jaxpr unchanged (tests/test_obs.py
        # asserts the decode jaxpr is byte-identical with/without it)
        self.trace = trace
        # quant-health aggregates (repro.obs): Python-gated at trace time so
        # the disabled decode jaxpr is identical to a health-free build
        health = ecfg.policy is not None and ecfg.policy.health
        self._health_kv = health and pcfg.quantized and bool(self._attn_keys)
        self._health_state = health and squant and bool(self._state_keys)
        self._health = self._health_kv or self._health_state
        # held-expert hits of each decode step (engine event ``moe_hits``):
        # Python-gated so a model without experts compiles the same step
        self._moe_hits = any(sub.ffn_kind == "moe" for sub in lm.period)
        # prefix sharing needs per-token paged memory: attention-only archs
        # opt in; any recurrent sublayer routes every request down the
        # ordinary full-prefill miss path (the cache is simply absent)
        self._prefix = (RadixPrefixCache(self.pcfg.page_size,
                                         self.pcfg.total_pages, trace=trace)
                        if (ecfg.prefix_cache and self._attn_keys
                            and not self._state_keys) else None)
        # pure-SSM archs have no token-paged memory: admission is slot-only
        self.sched = Scheduler(self.pcfg, ecfg.prefill_chunk,
                               paged=bool(self._attn_keys), trace=trace,
                               prefix=self._prefix)
        self.metrics = ServeMetrics(clock=clock)
        self.metrics.num_slots = self.pcfg.num_slots
        self.metrics.cache_bytes = KC.pool_bytes(self.pool)
        self.metrics.cache_bytes_fp32 = KC.pool_bytes_fp32(self.pool)
        self.metrics.state_bytes = SC.pool_bytes(self.spool)
        self.metrics.state_bytes_fp32 = SC.pool_bytes_fp32(self.spool)
        # live memory ledger (repro.obs): every resident site reports in.
        # Pools are preallocated, so their byte totals are fixed at init;
        # what moves per phase is the prefix overlay (logical vs physical
        # mapped pages — the verified bytes behind ``pages_saved``) and the
        # compile-cache population. Host-side only, like the trace.
        from ..obs import MemoryLedger
        self.ledger = MemoryLedger()
        self._page_nbytes = (KC.page_nbytes(self.pool, self.pcfg)
                             if self._attn_keys else 0)
        self._params_nbytes = sum(
            int(l.nbytes) for l in jax.tree_util.tree_leaves(self.params))
        self._params_nbytes_fp32 = 4 * sum(
            int(l.size) for l in jax.tree_util.tree_leaves(self.params))
        self._key = jax.random.PRNGKey(ecfg.seed)
        self._nsample = 0
        self._completions: dict[int, Completion] = {}
        self._orig_prompt: dict[int, list[int]] = {}

        def make_prefill(length):
            """Whole-prompt prefill (the model's own forward): numerically
            the static-serving reference. One wrapper per padded length so
            the compile cache can evict whole executables;
            ``prefill_bucket`` bounds how many lengths occur. Bucket
            padding is masked out of the MoE router via ``token_mask``."""

            def prefill(params, tokens, length):
                mask = (jnp.arange(tokens.shape[1]) < length)[None]
                logits, _, cache = lm_forward(params, lm, self.plan,
                                              tokens=tokens,
                                              return_cache=True,
                                              token_mask=mask)
                return logits[0, length - 1][None], cache

            return jax.jit(prefill)

        def make_chunk(width):
            """Chunked-prefill step, one wrapper per chunk width — same
            eviction story as make_prefill."""
            return jax.jit(self._chunk_impl, donate_argnums=(1, 2))

        # bounded LRUs of live jitted prefill shapes (serve/bucketing.py);
        # the decode step is a single fixed shape and never evicts
        self._prefill_fns = CompileCache(make_prefill,
                                         max_live=ecfg.max_prefill_shapes)
        self._chunk_fns = CompileCache(make_chunk,
                                       max_live=ecfg.max_prefill_shapes)
        self._decode_jit = jax.jit(self._decode_impl, donate_argnums=(1, 2))
        self._write_prefill_jit = jax.jit(self._write_prefill_impl,
                                          donate_argnums=(0,),
                                          static_argnames=("pcfg",))
        self._write_state_jit = jax.jit(self._write_state_impl,
                                        donate_argnums=(0,),
                                        static_argnames=("scfg",))
        self._reset_state_jit = jax.jit(self._reset_state_impl,
                                        donate_argnums=(0,))
        self._fork_jit = jax.jit(self._fork_impl, donate_argnums=(0,))
        self._adopt_jit = jax.jit(self._adopt_impl, donate_argnums=(0,))
        self._sample_jit = jax.jit(sample_tokens)
        # ---- speculative decoding (ecfg.spec_k > 0) --------------------
        if ecfg.spec_k < 0:
            raise ValueError(f"spec_k must be >= 0, got {ecfg.spec_k}")
        self._spec = ecfg.spec_k > 0
        if self._spec and draft is None:
            raise ValueError("spec_k > 0 needs a draft model: "
                             "Engine(..., draft=(draft_lm, draft_params))")
        if self._spec:
            dlm, dparams = draft
            if dlm.lead:
                raise NotImplementedError(
                    "speculative decoding scans the draft's layer periods "
                    "only: a draft with leading layers is not supported")
            dlm = dropless(dlm)
            if self._state_keys:
                raise NotImplementedError(
                    "speculative decoding needs an attention-only TARGET: "
                    "recurrent state advanced through a rejected draft "
                    "token cannot be rolled back")
            for sub in dlm.period:
                if sub.mixer_kind not in ("attn_gqa", "attn_mla"):
                    raise NotImplementedError(
                        "speculative decoding needs an attention-only "
                        f"DRAFT (got mixer {sub.mixer_kind!r})")
            if dlm.cfg.vocab_size != cfg.vocab_size:
                raise ValueError(
                    f"draft vocab {dlm.cfg.vocab_size} != target vocab "
                    f"{cfg.vocab_size}")
            if self.plan.mesh is not None:
                raise NotImplementedError(
                    "draft-model sharding is an open roadmap item — run "
                    "speculative decoding mesh-less")
            self._draft = dlm
            self._draft_params = dparams
            self._draft_attn_keys = tuple(
                f"sub_{i}" for i, _ in enumerate(dlm.period))
            # the draft pool mirrors the target's geometry/numerics but
            # shares nothing: a STATIC identity page table (slot i owns
            # pages i*pp .. (i+1)*pp-1) removes every allocator interplay —
            # draft-side rollback is just the length vector not advancing,
            # and junk K/V above a slot's length is masked by the same
            # causal length mask as on the target side
            self._draft_pcfg = dataclasses.replace(self.pcfg, num_pages=0)
            self._draft_pool = KC.init_pool(dlm, self._draft_pcfg)
            pp = self._draft_pcfg.pages_per_slot
            self._draft_table = jnp.asarray(
                np.arange(self.pcfg.num_slots * pp,
                          dtype=np.int32).reshape(self.pcfg.num_slots, pp))
            self._draft_pool_bytes = KC.pool_bytes(self._draft_pool)
            self._draft_pool_bytes_fp32 = KC.pool_bytes_fp32(self._draft_pool)
            self._draft_params_nbytes = sum(
                int(l.nbytes) for l in jax.tree_util.tree_leaves(dparams))
            self._draft_params_nbytes_fp32 = 4 * sum(
                int(l.size) for l in jax.tree_util.tree_leaves(dparams))

            def make_draft_prefill(length):
                def dprefill(params, tokens, valid_len):
                    mask = (jnp.arange(tokens.shape[1]) < valid_len)[None]
                    _, _, cache = lm_forward(params, dlm, self.plan,
                                             tokens=tokens,
                                             return_cache=True,
                                             token_mask=mask)
                    return cache
                return jax.jit(dprefill)

            self._draft_prefill_fns = CompileCache(
                make_draft_prefill, max_live=ecfg.max_prefill_shapes)
            self._draft_propose_jit = jax.jit(self._draft_propose_impl,
                                              donate_argnums=(1,))
            self._verify_jit = jax.jit(self._verify_impl,
                                       donate_argnums=(1,))
            self._accept_jit = jax.jit(spec_accept)
        self._ledger_update("init")

    # ---- jitted step bodies -------------------------------------------
    def _ckv(self, pool):
        """Re-assert the KV pool's plan sharding on a jitted body's output
        so donation round-trips the layout (head-sharded pages stay head-
        sharded). Mesh-less engines: identity — jaxprs are unchanged."""
        if self._pool_ns is None:
            return pool
        return jax.tree.map(jax.lax.with_sharding_constraint, pool,
                            self._pool_ns)

    def _cst(self, spool):
        if self._spool_ns is None:
            return spool
        return jax.tree.map(jax.lax.with_sharding_constraint, spool,
                            self._spool_ns)

    def _write_prefill_impl(self, pool, cache, table_row, slot, length,
                            pcfg):
        return self._ckv(KC.write_prefill(pool, cache, table_row, slot,
                                          length, pcfg))

    def _write_state_impl(self, spool, cache, slot, scfg):
        return self._cst(SC.write_prefill(spool, cache, slot, scfg))

    def _reset_state_impl(self, spool, slot):
        return self._cst(SC.reset_slot(spool, slot))

    def _fork_impl(self, pool, src, dst):
        # COW fork on (possibly head-sharded) pages: the copy indexes the
        # unsharded page axis only, so each shard forks its own head slice
        # of the page — codes verbatim, no cross-device traffic
        return self._ckv(KC.fork_page(pool, src, dst))

    def _adopt_impl(self, pool, slot, snap):
        return self._ckv(KC.adopt_scales(pool, slot, snap))

    def _fused_for(self, sub, verify: bool = False) -> bool:
        """Whether one attention sublayer attends straight off the pool
        (``fused_attention``): GQA/MQA/MHA through the page walk, in decode
        and verify, head-sharded under a mesh; MLA through the latent walk
        in decode without a mesh. MLA keeps the gather path under a mesh
        (its latent leaves are replicated, ``ShardPlan.kv_page_spec``) and
        in verify (a q-block latent walk is an open roadmap item)."""
        if not self.ecfg.fused_attention:
            return False
        if sub.mixer_kind == "attn_gqa":
            return True
        return (sub.mixer_kind == "attn_mla" and not verify
                and self.plan.mesh is None)

    def attention_paths(self) -> dict:
        """{mixer kind: {"decode": path, "verify": path}} of the model's
        attention sublayers; a path is the op that attends
        (``paged_attention``, ``mla_paged_attention``) or ``gather``."""
        walk = {"attn_gqa": "paged_attention",
                "attn_mla": "mla_paged_attention"}
        out = {}
        for sub in self.lm.lead + self.lm.period:
            if sub.mixer_kind in walk:
                out[sub.mixer_kind] = {
                    phase: walk[sub.mixer_kind]
                    if self._fused_for(sub, verify=phase == "verify")
                    else "gather"
                    for phase in ("decode", "verify")}
        return out

    def _sub_decode(self, pp, x, dsub, ssub, table, lens, active, sub,
                    layer, health=None):
        """One attention sublayer of the batched decode step. ``dsub`` holds
        the sublayer's stacked (L, P+1, page, *feat) pool leaves and
        ``layer`` the scan's layer index: the append scatters into the
        stack and the attention reads it at ``layer``, so no per-layer slab
        is sliced out or written back. Returns (x, updated leaves), and
        with MoE hits on (``self._moe_hits``) the sublayer's int32 [held
        experts hit, tokens routed to them] third."""
        cfg = self.lm.cfg
        h = rms_norm(x, pp["norm1"]["scale"], cfg.norm_eps)
        positions = A.len_positions(lens, x.shape[0])
        qd, newd = _project(pp["mixer"], h, sub, cfg, positions)
        if health is not None and self._health_kv:
            # clip counts of this append vs the prefill-frozen slot scales
            for name, new in newd.items():
                health["kv"].append(
                    KC.append_health(new, ssub[name], active, self.pcfg))
        new_dsub = {name: KC.append_token(dsub[name], ssub[name], new, table,
                                          lens, active, self.pcfg,
                                          layer=layer)
                    for name, new in newd.items()}
        if self._fused_for(sub) and sub.mixer_kind == "attn_mla":
            # latent walk: attend off the int8 latent pages, each slot's
            # live pages only; the value up-projection stays in XLA, under
            # the same scope
            d = sub.mixer
            with _gather_scope(sub):
                lat = ops.mla_paged_attention(
                    qd["q_abs"][:, 0], qd["q_rope"][:, 0],
                    new_dsub["c_kv"], new_dsub["k_rope"], ssub["c_kv"],
                    ssub["k_rope"], table, lens, active, layer,
                    page_size=self.pcfg.page_size,
                    quantized=self.pcfg.quantized,
                    sm_scale=A.mla_softmax_scale(d.m),
                    impl=self.ecfg.fused_impl)
                out = A.mla_value_up(pp["mixer"], lat[:, None], d, cfg)
            out = apply_site(pp["mixer"]["o"], out, d.o, cfg)
        elif self._fused_for(sub):
            # fused path: attend straight off the int8 pages — per-page
            # dequant + online softmax inside the kernel, no gathered view
            d = sub.mixer
            b = x.shape[0]
            attn = KC.fused_attend(new_dsub["k"], new_dsub["v"], ssub["k"],
                                   ssub["v"], qd["q"][:, 0], table, lens,
                                   self.pcfg, impl=self.ecfg.fused_impl,
                                   plan=self.plan, layer=layer)
            attn = attn[:, :d.real_heads].reshape(b, 1,
                                                  d.real_heads * d.head_dim)
            out = apply_site(pp["mixer"]["o"], attn, d.o, cfg)
        else:
            with _gather_scope(sub):
                kv = {name: KC.gather_slots(new_dsub[name], ssub[name],
                                            table, self.pcfg, h.dtype,
                                            layer=layer)
                      for name in new_dsub}
                out = _attention(pp["mixer"], qd, kv, sub, cfg, positions)
            out = apply_site(pp["mixer"]["o"], out, sub.mixer.o, cfg)
        x, *hits = self._ffn(pp, x + out, sub, active)
        return (x, new_dsub, *hits)

    def _ffn(self, pp, x, sub, active):
        """The FFN half of a decode sublayer: (x,), and with MoE hits on
        (x, [held experts hit, tokens routed to them]). Inactive slots are
        masked out of the MoE router: their junk tokens count as no one's
        and route nowhere."""
        if self._moe_hits:
            return sub_ffn_decode(pp, x, sub, self.lm.cfg, self.plan,
                                  token_mask=active[:, None],
                                  with_hits=True)
        return (sub_ffn_decode(pp, x, sub, self.lm.cfg, self.plan,
                               token_mask=active[:, None]),)

    def _sub_decode_state(self, pp, x, sd, ss, active, sub, health=None):
        """One recurrent sublayer of the batched decode step: dequantize
        every slot's state, advance one token through the mixer's
        single-step entry point, requantize active lanes (inactive lanes
        keep their stored codes + scale). With MoE hits on, a mamba
        sublayer's hits third, as ``_sub_decode``'s."""
        cfg = self.lm.cfg
        shapes = SC.state_feature_shapes(sub, cfg)
        state = {name: SC.read_layer(sd[name], ss[name],
                                     SC.natural_dtype(kind, cfg), self.scfg)
                 for name, (_, kind) in shapes.items()}
        h = rms_norm(x, pp["norm1"]["scale"], cfg.norm_eps)
        hits = []
        if sub.mixer_kind == "mamba":
            out, new_state = S.mamba_decode_step(pp["mixer"], h, sub.mixer,
                                                 cfg, state)
            x, *hits = self._ffn(pp, x + out, sub, active)
        else:   # rwkv6: time-mix + channel-mix are the whole sublayer
            out, st1 = S.rwkv6_time_mix_step(pp["mixer"], h, sub.mixer, cfg,
                                             state)
            x = x + out
            h2 = rms_norm(x, pp["norm2"]["scale"], cfg.norm_eps)
            out2, st2 = S.rwkv6_channel_mix_step(pp["mixer"], h2, sub.mixer,
                                                 cfg, state)
            x = x + out2
            new_state = {**st1, **st2}
        nd, ns = {}, {}
        for name in shapes:
            if health is not None and self._health_state:
                # drift of the re-chosen per-slot scale vs the stored one
                health["state"].append(SC.write_health(
                    ss[name], new_state[name], active, self.scfg))
            nd[name], ns[name] = SC.write_layer(sd[name], ss[name],
                                                new_state[name], active,
                                                self.scfg)
        return (x, (nd, ns), *hits)

    def _decode_impl(self, params, pool, spool, table, lens, active, tokens):
        """One batched decode step. tokens: (B,1); lens/active: (B,).
        Returns (logits (B,V), new KV pool, new state pool) — plus, when
        quant-health is on (policy.health), a dict of per-site aggregates
        summed over layers. The health path is Python-gated so a disabled
        engine's jaxpr is byte-identical to a health-free build.

        The KV pool's data leaves ride in the layer scan's CARRY, whole and
        stacked (L, P+1, page, *feat); the scan runs over the layer index
        with the per-layer params and scale rows. Each layer appends its
        token with one scatter at [layer, page, off] and the page walk (or
        the gather fallback) reads the stack at that layer, so the donated
        pool is updated in place. Scanned as xs/ys instead, XLA slices each
        layer's slab in, writes it back into a fresh ys buffer and copies
        that out: several pool-sized copies a step, where the step should
        move one token per slot per layer. The recurrent-state pool is per
        slot and small, and stays xs/ys."""
        lm = self.lm
        x = embed_tokens(params, tokens, lm)
        data, scales = pool["data"], pool["scale_log2"]
        n_lead = len(lm.lead)
        lead_hits = []
        if n_lead:
            # leading layers: pool entries 0..n_lead-1 of the one stack
            # (quant-health counts the scanned layers only)
            data = dict(data)
            for j, sub in enumerate(lm.lead):
                res = self._sub_decode(
                    params["lead"][j], x, data["sub_0"],
                    {n: a[j] for n, a in scales["sub_0"].items()}, table,
                    lens, active, sub, jnp.int32(j))
                x, data["sub_0"] = res[:2]
                lead_hits += res[2:]
            scales = jax.tree.map(lambda a: a[n_lead:], scales)

        def body(carry, scan_in):
            x, data = carry
            layer, pp, sl, sd, ss = scan_in
            data, snew_d, snew_s = dict(data), {}, {}
            hc = {"kv": [], "state": []} if self._health else None
            hits = []
            for i, sub in enumerate(lm.period):
                key = f"sub_{i}"
                if sub.mixer_kind in ("mamba", "rwkv6"):
                    x, (nd, ns), *h = self._sub_decode_state(
                        pp[key], x, sd[key], ss[key], active, sub, health=hc)
                    hits += h
                    snew_d[key], snew_s[key] = nd, ns
                else:
                    x, data[key], *h = self._sub_decode(
                        pp[key], x, data[key], sl[key], table, lens, active,
                        sub, layer, health=hc)
                    hits += h
                    snew_d[key], snew_s[key] = sd[key], ss[key]
            ys = (snew_d, snew_s)
            if self._health:
                z32 = jnp.asarray(0, jnp.int32)
                zf = jnp.asarray(0.0, jnp.float32)
                ys += ((sum((s[0] for s in hc["kv"]), z32),
                        sum((s[1] for s in hc["kv"]), z32),
                        sum((s[0] for s in hc["state"]), z32),
                        sum((s[1] for s in hc["state"]), z32),
                        sum((s[2] for s in hc["state"]), zf),
                        sum((s[3] for s in hc["state"]), zf)),)
            if self._moe_hits:
                ys += (sum(hits, jnp.zeros((2,), jnp.int32)),)
            return (x, data), ys

        (x, new_data), ys = jax.lax.scan(
            body, (x, data),
            (jnp.arange(n_lead, lm.n_stack, dtype=jnp.int32),
             params["layers"], scales, spool["data"], spool["scale_log2"]))
        new_sdata, new_sscale = ys[:2]
        x = rms_norm(x, params["final_norm"]["scale"], lm.cfg.norm_eps)
        logits = apply_site(params["head"], x, lm.head, lm.cfg)
        out = (logits[:, 0],
               self._ckv({"data": new_data,
                          "scale_log2": pool["scale_log2"]}),
               self._cst({"data": new_sdata, "scale_log2": new_sscale}))
        if self._health:
            # per-layer ys stacked on axis 0: fold to per-step totals
            keys = ("kv_clipped", "kv_total", "state_clipped", "state_total",
                    "state_drift_sum", "state_drift_n")
            out = out + ({k: jnp.sum(v) for k, v in zip(keys, ys[2])},)
        if self._moe_hits:
            # [held experts hit, tokens routed to them], summed over layers
            out = out + (jnp.sum(ys[-1], axis=0) + sum(lead_hits),)
        return out

    def _chunk_impl(self, params, pool, spool, tokens, table, slot, start,
                    valid_len):
        """Chunked-prefill step for one slot. Attention sublayers write the
        chunk's K/V into the pool and attend over the slot's full history;
        recurrent sublayers scan the chunk from the slot's carried state and
        write the end-of-chunk state back (stateful archs pad no chunks, so
        ``valid_len == S`` for them). tokens: (1,S)."""
        lm = self.lm
        cfg = lm.cfg
        s = tokens.shape[1]
        table_row = table[slot]
        positions = (start + jnp.arange(s))[None]          # (1,S)
        chunk_mask = (jnp.arange(s) < valid_len)[None]     # (1,S) real tokens
        x = embed_tokens(params, tokens, lm)

        def attn_sub(x, spp, dsub, ssub, sub):
            h = rms_norm(x, spp["norm1"]["scale"], cfg.norm_eps)
            qd, newd = _project(spp["mixer"], h, sub, cfg, positions)
            nd, ns, kv = {}, {}, {}
            for name, new in newd.items():
                dlay, slay = KC.write_chunk(
                    dsub[name], ssub[name], new[0], table_row, start,
                    valid_len, slot, self.pcfg)
                nd[name], ns[name] = dlay, slay
                kv[name] = KC.gather_slots(dlay, slay[slot][None],
                                           table_row[None], self.pcfg,
                                           h.dtype)
            x = x + _attend(spp["mixer"], qd, kv, sub, cfg, positions)
            # chunk tail padding is masked out of the MoE router
            x = sub_ffn_decode(spp, x, sub, cfg, self.plan,
                               token_mask=chunk_mask)
            return x, nd, ns

        def state_sub(x, spp, sdsub, sssub, sub):
            shapes = SC.state_feature_shapes(sub, cfg)
            st = {name: SC.read_layer(sdsub[name][slot][None],
                                      sssub[name][slot][None],
                                      SC.natural_dtype(kind, cfg), self.scfg)
                  for name, (_, kind) in shapes.items()}
            h = rms_norm(x, spp["norm1"]["scale"], cfg.norm_eps)
            if sub.mixer_kind == "mamba":
                out, new_st = S.mamba_forward(spp["mixer"], h, sub.mixer,
                                              cfg, st)
                x = x + out
                x = sub_ffn_decode(spp, x, sub, cfg, self.plan,
                                   token_mask=chunk_mask)
            else:   # rwkv6
                out, st1 = S.rwkv6_time_mix(spp["mixer"], h, sub.mixer, cfg,
                                            st)
                x = x + out
                h2 = rms_norm(x, spp["norm2"]["scale"], cfg.norm_eps)
                out2, st2 = S.rwkv6_channel_mix(spp["mixer"], h2, sub.mixer,
                                                cfg, st)
                x = x + out2
                new_st = {**st1, **st2}
            nd, ns = {}, {}
            for name in shapes:
                nd[name], ns[name] = SC.write_slot(
                    sdsub[name], sssub[name], new_st[name][0], slot,
                    self.scfg)
            return x, nd, ns

        def body(x, scan_in):
            pp, dl, sl, sd, ss = scan_in
            new_d, new_s, snew_d, snew_s = {}, {}, {}, {}
            for i, sub in enumerate(lm.period):
                key = f"sub_{i}"
                if sub.mixer_kind in ("mamba", "rwkv6"):
                    x, nd, ns = state_sub(x, pp[key], sd[key], ss[key], sub)
                    snew_d[key], snew_s[key] = nd, ns
                    new_d[key], new_s[key] = dl[key], sl[key]
                else:
                    x, nd, ns = attn_sub(x, pp[key], dl[key], sl[key], sub)
                    new_d[key], new_s[key] = nd, ns
                    snew_d[key], snew_s[key] = sd[key], ss[key]
            return x, (new_d, new_s, snew_d, snew_s)

        x, (new_data, new_scale, new_sdata, new_sscale) = jax.lax.scan(
            body, x, (params["layers"], pool["data"], pool["scale_log2"],
                      spool["data"], spool["scale_log2"]))
        x = rms_norm(x, params["final_norm"]["scale"], cfg.norm_eps)
        logits = apply_site(params["head"], x, lm.head, cfg)
        last = logits[0, valid_len - 1][None]              # (1,V)
        return (last,
                self._ckv({"data": new_data, "scale_log2": new_scale}),
                self._cst({"data": new_sdata, "scale_log2": new_sscale}))

    # ---- speculative decoding bodies -----------------------------------
    def _sub_verify(self, pp, x, dsub, ssub, table, lens, active, positions,
                    tmask, sub):
        """One attention sublayer of the verify step: append the whole
        (k+1)-row block's K/V in one batched scatter, then attend every row
        in ONE q-block kernel call — ``_sub_decode`` generalized from S=1.
        Row j sits at position lens+j and attends causally through itself
        (the same append-then-attend self-inclusive semantics as decode)."""
        cfg = self.lm.cfg
        h = rms_norm(x, pp["norm1"]["scale"], cfg.norm_eps)
        qd, newd = _project(pp["mixer"], h, sub, cfg, positions)
        new_dsub = {name: KC.append_tokens(dsub[name], ssub[name], new,
                                           table, lens, active, self.pcfg)
                    for name, new in newd.items()}
        if self._fused_for(sub, verify=True):
            d = sub.mixer
            b, s = x.shape[:2]
            attn = KC.fused_attend(new_dsub["k"], new_dsub["v"], ssub["k"],
                                   ssub["v"], qd["q"], table, lens,
                                   self.pcfg, impl=self.ecfg.fused_impl,
                                   plan=self.plan)
            attn = attn[:, :, :d.real_heads].reshape(
                b, s, d.real_heads * d.head_dim)
            out = apply_site(pp["mixer"]["o"], attn, d.o, cfg)
        else:
            kv = {name: KC.gather_slots(new_dsub[name], ssub[name], table,
                                        self.pcfg, h.dtype)
                  for name in new_dsub}
            out = _attend(pp["mixer"], qd, kv, sub, cfg, positions)
        x = x + out
        return sub_ffn_decode(pp, x, sub, cfg, self.plan,
                              token_mask=tmask), new_dsub

    def _verify_impl(self, params, pool, table, lens, active, tokens):
        """Target forward over the (B, S=k+1) verify block: the incoming
        token plus the k draft proposals, all scored in one step. The
        q-block twin of ``_decode_impl`` — attention-only archs (enforced
        at init), no health/state branches. Returns ((B, S, V) logits, new
        KV pool); rejected positions' K/V stay as junk above the slot's
        advanced length (see ``kv_cache.append_tokens``)."""
        lm = self.lm
        b, s = tokens.shape
        x = embed_tokens(params, tokens, lm)
        positions = lens[:, None] + jnp.arange(s)[None]
        tmask = jnp.broadcast_to(active[:, None], (b, s))

        def body(x, scan_in):
            pp, dl, sl = scan_in
            new = {}
            for i, sub in enumerate(lm.period):
                key = f"sub_{i}"
                x, nd = self._sub_verify(pp[key], x, dl[key], sl[key],
                                         table, lens, active, positions,
                                         tmask, sub)
                new[key] = nd
            return x, new

        x, new_data = jax.lax.scan(
            body, x, (params["layers"], pool["data"], pool["scale_log2"]))
        x = rms_norm(x, params["final_norm"]["scale"], lm.cfg.norm_eps)
        logits = apply_site(params["head"], x, lm.head, lm.cfg)
        return logits, self._ckv({"data": new_data,
                                  "scale_log2": pool["scale_log2"]})

    def _draft_step(self, dparams, dpool, table, lens, active, tokens):
        """One S=1 decode step of the draft model over its private pool
        (static identity table) — ``_decode_impl`` minus the state/health
        branches (the draft is attention-only by construction). Appends go
        through ``append_tokens`` for its past-horizon trash redirect: a
        draft block overhanging ``max_len`` must not scribble on pages."""
        dlm = self._draft
        cfg = dlm.cfg
        x = embed_tokens(dparams, tokens, dlm)
        positions = A.len_positions(lens, x.shape[0])

        def body(x, scan_in):
            pp, dl, sl = scan_in
            new = {}
            for i, sub in enumerate(dlm.period):
                key = f"sub_{i}"
                h = rms_norm(x, pp[key]["norm1"]["scale"], cfg.norm_eps)
                qd, newd = _project(pp[key]["mixer"], h, sub, cfg,
                                    positions)
                nd = {name: KC.append_tokens(dl[key][name], sl[key][name],
                                             new_, table, lens, active,
                                             self._draft_pcfg)
                      for name, new_ in newd.items()}
                kv = {name: KC.gather_slots(nd[name], sl[key][name], table,
                                            self._draft_pcfg, h.dtype)
                      for name in nd}
                x = x + _attend(pp[key]["mixer"], qd, kv, sub, cfg,
                                positions)
                x = sub_ffn_decode(pp[key], x, sub, cfg, self.plan,
                                   token_mask=active[:, None])
                new[key] = nd
            return x, new

        x, new_data = jax.lax.scan(
            body, x, (dparams["layers"], dpool["data"],
                      dpool["scale_log2"]))
        x = rms_norm(x, dparams["final_norm"]["scale"], cfg.norm_eps)
        logits = apply_site(dparams["head"], x, dlm.head, cfg)
        return logits[:, 0], {"data": new_data,
                              "scale_log2": dpool["scale_log2"]}

    def _draft_propose_impl(self, dparams, dpool, table, lens, active,
                            tokens, key, temp, topk, topp):
        """k draft decode steps (unrolled: k is small and static). Each
        proposal is sampled from the PROCESSED draft distribution Q
        (temperature/top-k/top-p applied) and Q itself is kept — the
        rejection test needs the exact proposal distribution, and greedy
        slots need their one-hots. Returns ((B, k) tokens, (B, k, V)
        probs, new draft pool)."""
        toks, probs = [], []
        cur = tokens
        for i in range(self.ecfg.spec_k):
            logits, dpool = self._draft_step(dparams, dpool, table,
                                             lens + i, active, cur)
            qp = processed_probs(logits, temp, topk, topp)
            t = sample_from_probs(qp, jax.random.fold_in(key, i))
            toks.append(t)
            probs.append(qp)
            cur = t[:, None]
        # trailing cache-fill step: each step above appends its INCOMING
        # token, so after k steps the last proposal d_k has no K/V in the
        # draft pool — and when the target accepts all k, the next round
        # resumes at lens+k+1 and would attend over a zero hole at lens+k.
        # Feed d_k once more (logits discarded) to complete the span; for
        # rejected slots the write is junk above the final length, exactly
        # like the target's own rejected rows.
        _, dpool = self._draft_step(dparams, dpool, table,
                                    lens + self.ecfg.spec_k, active, cur)
        return jnp.stack(toks, axis=1), jnp.stack(probs, axis=1), dpool

    def _draft_prefill(self, slot: int, st) -> None:
        """Whole-prompt prefill of the draft model for one slot. The draft
        always recomputes the full prompt (no chunking, no prefix sharing —
        it is a fraction of the target's cost by construction); bucket
        padding bounds its compiled shapes like the target's prefill."""
        toks = st.req.prompt
        padded = toks + [0] * (bucket_len(len(toks),
                                          self.ecfg.prefill_bucket)
                               - len(toks))
        tok_arr = jnp.asarray(padded, jnp.int32)[None]
        cache = self._draft_prefill_fns.get(len(padded))(
            self._draft_params, tok_arr, jnp.int32(len(toks)))
        self._draft_pool = self._write_prefill_jit(
            self._draft_pool,
            {k: cache[k] for k in self._draft_attn_keys},
            self._draft_table[slot], jnp.int32(slot),
            jnp.int32(len(toks)), pcfg=self._draft_pcfg)

    def _spec_step(self, active_slots: list[int]) -> None:
        """One speculative iteration over the current batch: k draft
        proposals per slot, ONE q-block verify call on the target,
        rejection sampling per slot (accepted prefix + bonus/replacement
        token), then page-level rollback (``trim_unused``). Every emitted
        token is a valid target sample, so greedy slots emit exactly the
        non-speculative greedy sequence (one-hot distributions make each
        accept/replace decision deterministic)."""
        sched = self.sched
        k = self.ecfg.spec_k
        with span("engine.dispatch", rows=len(active_slots)):
            table = jnp.asarray(sched.page_table)
            lens = jnp.asarray(sched.lens_vector())
            active = jnp.asarray(sched.active_mask())
            tokens = jnp.asarray(sched.tokens_vector())
            sp = [sched.slots[s].req.sampling if sched.slots[s]
                  else SamplingParams() for s in range(self.pcfg.num_slots)]
            temp = jnp.asarray([p.temperature for p in sp], jnp.float32)
            topk = jnp.asarray([p.top_k for p in sp], jnp.int32)
            topp = jnp.asarray([p.top_p for p in sp], jnp.float32)
            dkey = jax.random.fold_in(self._key, self._nsample)
            self._nsample += 1
            akey = jax.random.fold_in(self._key, self._nsample)
            self._nsample += 1
            t0 = self.trace.clock() if self.trace is not None else 0.0
            dtoks, dprobs, self._draft_pool = self._draft_propose_jit(
                self._draft_params, self._draft_pool, self._draft_table,
                lens, active, tokens, dkey, temp, topk, topp)
            blk = jnp.concatenate([tokens, dtoks], axis=1)   # (B, k+1)
            vlogits, self.pool = self._verify_jit(self.params, self.pool,
                                                  table, lens, active, blk)
        with span("engine.sync"):
            acc_len, next_tok = self._accept_jit(vlogits, dprobs, dtoks,
                                                 akey, temp, topk, topp)
            acc = np.asarray(acc_len)
            nxt = np.asarray(next_tok)
            dt = np.asarray(dtoks)
        with span("engine.bookkeeping"):
            dur = (self.trace.clock() - t0) if self.trace is not None \
                else None
            accepted = emitted = 0
            for slot in active_slots:
                st = sched.slots[slot]
                a = int(acc[slot])
                accepted += a
                # eos / max_new truncate the emission mid-prefix: tokens past
                # the stop never leave the engine (their K/V junk sits above
                # the slot's final length and the slot retires anyway)
                for tok in [int(t) for t in dt[slot, :a]] + [int(nxt[slot])]:
                    st.generated.append(tok)
                    st.last_token = tok
                    emitted += 1
                    if st.done():
                        break
                sched.trim_unused(slot)
                if st.done():
                    self._finish(slot)
            free_pages = sched.alloc.free_pages if sched.paged else None
            self.metrics.decode_step(emitted, free_pages=free_pages, dur=dur)
            self.metrics.spec_step(len(active_slots), k * len(active_slots),
                                   accepted, emitted)
            self._ledger_update("decode")
            if self.trace is not None:
                self.trace.emit("spec_step", step=self.metrics.decode_steps,
                                n_active=len(active_slots),
                                proposed=k * len(active_slots),
                                accepted=accepted, emitted=emitted,
                                free_pages=free_pages, dur=dur)

    # ---- memory ledger -------------------------------------------------
    def _ledger_update(self, phase: str | None = None) -> None:
        """Refresh every serve-side ledger site (host ints only — never
        called from a jitted body).  Counted sites are the real resident
        allocations; the prefix pages are an *uncounted* overlay of
        ``kv_pool`` (their bytes live inside the pool) whose logical-vs-
        physical split turns page sharing into verified bytes."""
        led = self.ledger
        if phase is not None:
            led.set_phase(phase)
        led.set("params", self._params_nbytes,
                fp32=self._params_nbytes_fp32)
        led.set("kv_pool", self.metrics.cache_bytes,
                fp32=self.metrics.cache_bytes_fp32)
        led.set("state_pool", self.metrics.state_bytes,
                fp32=self.metrics.state_bytes_fp32)
        if self._spec:
            led.set("draft_params", self._draft_params_nbytes,
                    fp32=self._draft_params_nbytes_fp32)
            led.set("draft_kv_pool", self._draft_pool_bytes,
                    fp32=self._draft_pool_bytes_fp32)
        if self.sched.paged:
            logical, physical = self.sched.mapped_page_stats()
            pb = self._page_nbytes
            led.set("prefix_pages_logical", logical * pb, counted=False,
                    pages=logical)
            led.set("prefix_pages_physical", physical * pb, counted=False,
                    pages=physical)
            led.set("prefix_bytes_saved", (logical - physical) * pb,
                    counted=False)
        if self._prefix is not None:
            stats = self._prefix.bytes_stats(self._page_nbytes)
            led.set("prefix_tree", stats["bytes"], counted=False,
                    pages=stats["pages"], pages_pinned=stats["pages_pinned"],
                    nodes=stats["nodes"])
        cc = self._prefill_fns.site()
        ch = self._chunk_fns.site()
        led.set("compile_cache", 0, counted=False,
                entries=cc["entries"] + ch["entries"],
                max_live=cc["max_live"],
                evictions=cc["evictions"] + ch["evictions"])

    # ---- request lifecycle --------------------------------------------
    def submit(self, prompt: list[int], max_new_tokens: int = 32,
               sampling: SamplingParams | None = None,
               eos_id: int = -1) -> int:
        req = Request(prompt=list(prompt), max_new_tokens=max_new_tokens,
                      sampling=sampling or SamplingParams(), eos_id=eos_id)
        rid = self.sched.submit(req)
        self._orig_prompt[rid] = list(prompt)
        self.metrics.request_submitted(rid)
        if self.trace is not None:
            self.trace.emit("submit", rid=rid, prompt_len=len(prompt),
                            max_new=max_new_tokens)
        return rid

    def _sample(self, logits: jax.Array, slots: list[int], also=None):
        """Sample one token per row of ``logits`` with the slots' params.
        With ``also`` (a device array), fetch it in the same read as the
        tokens and return (tokens, also) as numpy."""
        sp = [self.sched.slots[s].req.sampling if self.sched.slots[s]
              else SamplingParams() for s in slots]
        key = jax.random.fold_in(self._key, self._nsample)
        self._nsample += 1
        toks = self._sample_jit(
            logits, key,
            jnp.asarray([p.temperature for p in sp], jnp.float32),
            jnp.asarray([p.top_k for p in sp], jnp.int32),
            jnp.asarray([p.top_p for p in sp], jnp.float32))
        if also is not None:
            return jax.device_get((toks, also))
        return np.asarray(toks)

    def _prefill_plan(self, st) -> list[tuple[int, int, int]]:
        """(start, end, width) of each prefill chunk the prompt runs as:
        the whole prompt, its chunks, or the suffix after a prefix hit;
        ``width`` is the tokens the chunk runs, padding included."""
        plen, resume = st.prompt_len, st.prefix_len
        if resume > 0:
            c = self.ecfg.prefill_chunk
            chunks = ([(s, min(s + c, plen)) for s in range(resume, plen, c)]
                      if c > 0 else [(resume, plen)])
        else:
            chunks = self.sched.prefill_chunks(plen)
        out = []
        for ci, (c0, c1) in enumerate(chunks):
            n = c1 - c0
            if self._state_keys:
                # stateful archs run exact-length (see module docstring)
                width = n
            elif (ci == 0 and c0 == 0) or self.ecfg.prefill_chunk <= 0:
                width = bucket_len(n, self.ecfg.prefill_bucket)
            else:
                width = self.ecfg.prefill_chunk
            out.append((c0, c1, width))
        return out

    def _do_prefill(self, slot: int, st, plan) -> None:
        plen = st.prompt_len
        t0 = self.trace.clock() if self.trace is not None else 0.0
        self._ledger_update("prefill")
        table = jnp.asarray(self.sched.page_table)
        stateful = bool(self._state_keys)
        if stateful:
            # reset-on-admit: the slot may hold a retired/preempted
            # request's state. The first prefill chunk overwrites every
            # tensor anyway, so this is hygiene against future partial-
            # write paths (e.g. restore_slot interplay), not correctness
            # today — and the donated jit makes it an in-place scatter,
            # not a pool copy.
            self.spool = self._reset_state_jit(self.spool, jnp.int32(slot))
        resume = st.prefix_len
        if resume > 0:
            # prefix-cache hit: positions < resume are already resident on
            # shared pages (plus an optional COW-forked partial page whose
            # int8 codes were copied verbatim). Adopt the donor's scales so
            # those codes decode on their own grid, then compute only the
            # suffix via the chunked path — exactly the numerics a cache-off
            # engine with a chunk boundary at ``resume`` would produce.
            if self.pcfg.quantized and st.prefix_scales is not None:
                snap = {key: {n: jnp.asarray(v) for n, v in kinds.items()}
                        for key, kinds in st.prefix_scales.items()}
                self.pool = self._adopt_jit(self.pool, jnp.int32(slot), snap)
            if st.fork is not None:
                src, dst = st.fork
                self.pool = self._fork_jit(self.pool, jnp.int32(src),
                                           jnp.int32(dst))
                self.metrics.cow_forked()
                if self.trace is not None:
                    self.trace.emit("cow_fork", rid=st.req.rid, slot=slot,
                                    src_page=src, dst_page=dst,
                                    tokens=resume % self.pcfg.page_size)
            self.metrics.prefix_hit(resume, resume // self.pcfg.page_size)
            if self.trace is not None:
                self.trace.emit("cache_hit", rid=st.req.rid, slot=slot,
                                hit_tokens=resume, prompt_len=plen)
        last_logits = None
        for ci, (c0, c1, width) in enumerate(plan):
            toks = st.req.prompt[c0:c1]
            padded = toks + [0] * (width - len(toks))
            if self.trace is not None and len(plan) > 1:
                self.trace.emit("prefill_chunk", rid=st.req.rid, slot=slot,
                                start=c0, len=c1 - c0)
            if ci == 0 and c0 == 0:
                # whole-chunk model forward (exact reference numerics),
                # then scatter the returned cache into the pools. Stateful
                # archs run exact-length (a pad token would contaminate the
                # scan-carried state; see module docstring) — bucket
                # padding applies to attention-only archs, masked out of
                # the MoE router via lm_forward's token_mask.
                tok_arr = jnp.asarray(padded, jnp.int32)[None]
                last_logits, cache = self._prefill_fns.get(len(padded))(
                    self.params, tok_arr, jnp.int32(len(toks)))
                if self._attn_keys:
                    self.pool = self._write_prefill_jit(
                        self.pool, {k: cache[k] for k in self._attn_keys},
                        table[slot], jnp.int32(slot),
                        jnp.int32(len(toks)), pcfg=self.pcfg)
                if stateful:
                    self.spool = self._write_state_jit(
                        self.spool, {k: cache[k] for k in self._state_keys},
                        jnp.int32(slot), scfg=self.scfg)
            else:
                # later chunks — and the whole computed suffix of a prefix
                # hit — go through the chunked step, padded to a stable
                # width (the chunk size, or the bucketed suffix length when
                # chunking is off) so compiled shapes stay bounded
                tok_arr = jnp.asarray(padded, jnp.int32)[None]
                last_logits, self.pool, self.spool = self._chunk_fns.get(
                    len(padded))(
                    self.params, self.pool, self.spool, tok_arr, table,
                    jnp.int32(slot), jnp.int32(c0), jnp.int32(len(toks)))
        self.metrics.prefill(plen, computed=plen - resume)
        if self._spec:
            # the draft tracks the slot from position 0: full-prompt
            # prefill into its private pool (a preempted request re-enters
            # here with its generated prefix folded in, so the draft cache
            # is rebuilt consistently too)
            self._draft_prefill(slot, st)
        tok = int(self._sample(last_logits, [slot])[0])
        st.generated.append(tok)
        st.last_token = tok
        self.metrics.request_first_token(st.req.rid)
        if self._prefix is not None:
            # donate the fully-covered prompt pages to the radix tree so
            # future requests can share them (codes + scales as written)
            scales = (KC.snapshot_scales(self.pool, slot)
                      if self.pcfg.quantized else None)
            self.sched.commit_prefix(slot, scales)
        if self.trace is not None:
            self.trace.emit("prefill", rid=st.req.rid, slot=slot, len=plen,
                            dur=self.trace.clock() - t0)
            self.trace.emit("first_token", rid=st.req.rid, slot=slot)

    def _finish(self, slot: int) -> None:
        st = self.sched.retire(slot)
        rid = st.req.rid
        full = st.req.prompt + st.generated
        orig = self._orig_prompt[rid]
        tokens = full[len(orig):]
        self._completions[rid] = Completion(rid, orig, tokens)
        self.metrics.request_finished(rid, len(tokens))
        if self.trace is not None:
            reason = ("max_new"
                      if len(st.generated) >= st.req.max_new_tokens
                      else "eos")
            self.trace.emit("retire", rid=rid, slot=slot,
                            new_tokens=len(tokens), reason=reason)

    # ---- engine iteration ---------------------------------------------
    def step(self) -> None:
        """One engine iteration: admit + prefill, then one batched decode.
        The iteration and its phases are spans in the profiler's trace
        (``repro.engine.*``; schema in ``repro.obs.trace``)."""
        with step_span(self.metrics.decode_steps):
            self._step()

    def _step(self) -> None:
        sched = self.sched
        while True:
            with span("engine.admit"):
                adm = sched.try_admit()
                if adm is None:
                    break
                slot, st = adm
                self.metrics.request_admitted(st.req.rid, st.prompt_len)
                if self.trace is not None:
                    self.trace.emit("admit", rid=st.req.rid, slot=slot,
                                    pages=len(sched.slot_pages[slot]))
            plan = self._prefill_plan(st)
            with span("engine.prefill", rid=st.req.rid, tokens=st.prompt_len,
                      computed=st.prompt_len - st.prefix_len,
                      padded=sum(w for _, _, w in plan)):
                self._do_prefill(slot, st, plan)
            if st.done():
                with span("engine.bookkeeping"):
                    self._finish(slot)

        active_slots = [i for i, s in enumerate(sched.slots) if s is not None]
        if not active_slots:
            return
        # lazily map the page(s) each active slot is about to write — one
        # for plain decode, the k+1 verify span for speculative decoding;
        # preempt the youngest slot if the pool is exhausted
        need = self.ecfg.spec_k + 1 if self._spec else 1
        with span("engine.pages"):
            for slot in list(active_slots):
                if sched.slots[slot] is None:
                    continue
                while not (sched.ensure_page(slot) if need == 1
                           else sched.ensure_span(slot, need)):
                    # capture the victim before retire clears its slot state
                    yst = (sched.slots[sched.admission_order[-1]]
                           if len(sched.admission_order) > 1 else None)
                    evicted = sched.preempt_youngest()
                    if evicted is None:
                        raise RuntimeError(
                            "KV pool exhausted and nothing to preempt — "
                            "increase num_pages/pages_per_slot")
                    self.metrics.preempted()
                    if self.trace is not None:
                        self.trace.emit("preempt", rid=yst.req.rid,
                                        slot=evicted,
                                        gen_len=len(yst.generated))
                    if evicted == slot:
                        break
        active_slots = [i for i, s in enumerate(sched.slots) if s is not None]
        if not active_slots:
            return
        if self._spec:
            self._spec_step(active_slots)
            return

        with span("engine.dispatch", rows=len(active_slots)):
            table = jnp.asarray(sched.page_table)
            lens = jnp.asarray(sched.lens_vector())
            active = jnp.asarray(sched.active_mask())
            tokens = jnp.asarray(sched.tokens_vector())
            t0 = self.trace.clock() if self.trace is not None else 0.0
            logits, self.pool, self.spool, *extra = self._decode_jit(
                self.params, self.pool, self.spool, table, lens, active,
                tokens)
            health = extra.pop(0) if self._health else None
            hits = extra.pop(0) if self._moe_hits else None
        with span("engine.sync"):
            if hits is None:
                toks = self._sample(logits, list(range(self.pcfg.num_slots)))
            else:
                toks, hits = self._sample(
                    logits, list(range(self.pcfg.num_slots)), also=hits)
        with span("engine.bookkeeping"):
            dur = (self.trace.clock() - t0) if self.trace is not None \
                else None
            free_pages = sched.alloc.free_pages if sched.paged else None
            for slot in active_slots:
                st = sched.slots[slot]
                tok = int(toks[slot])
                st.generated.append(tok)
                st.last_token = tok
                if st.done():
                    self._finish(slot)
            self.metrics.decode_step(len(active_slots),
                                     free_pages=free_pages, dur=dur)
            self._ledger_update("decode")
            if self.trace is not None:
                self.trace.emit("decode_step", step=self.metrics.decode_steps,
                                n_active=len(active_slots),
                                free_pages=free_pages, dur=dur)
                if hits is not None:
                    self.trace.emit("moe_hits",
                                    step_num=self.metrics.decode_steps,
                                    rows=len(active_slots),
                                    experts_hit=int(hits[0]),
                                    tokens_here=int(hits[1]))
            if health is not None:
                if self._health_kv:
                    self.metrics.record_health(
                        "kv_cache", int(health["kv_clipped"]),
                        int(health["kv_total"]))
                if self._health_state:
                    self.metrics.record_health(
                        "ssm_state", int(health["state_clipped"]),
                        int(health["state_total"]),
                        float(health["state_drift_sum"]),
                        float(health["state_drift_n"]))

    def run(self) -> dict[int, Completion]:
        """Drive until every submitted request has completed."""
        while self.sched.has_work():
            self.step()
        return dict(self._completions)

    def summary(self) -> dict:
        # fold lazily-owned counters into the metrics before summarizing
        if self._prefix is not None:
            self.metrics.prefix_evictions = self._prefix.evictions
        self.metrics.compile_evictions = (self._prefill_fns.evictions
                                          + self._chunk_fns.evictions)
        if self.trace is not None:
            self.metrics.trace_dropped = self.trace.dropped
        from ..obs import registry
        self.metrics.counter_totals = registry.snapshot()
        self._ledger_update()
        if self.plan.mesh is not None:
            self.ledger.record_devices(self.pool, self.spool, self.params)
        out = self.metrics.summary()
        out["attention_paths"] = self.attention_paths()
        mem = self.ledger.summary()
        mem["reconcile"] = self.ledger.reconcile()
        out["memory"] = mem
        return out
