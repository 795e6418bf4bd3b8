"""Unified LM: every arch in the zoo is an instance of this module.

Structure: embed → leading layers → scan over *periods* of sublayers →
final norm → head. A period is a fixed pattern of sublayers (1 for
homogeneous stacks; 8 for Jamba's 7-Mamba+1-attention interleave). Layer
params are stacked on a leading axis and consumed by ``lax.scan`` (compile
time independent of depth), with configurable remat. Leading layers
(DeepSeek's ``first_k_dense_replace``: the same mixer with a dense FFN of
its own width) run unrolled before the scan, with params of their own
(``params["lead"]``); their caches take the first entries of the period's
stacked caches, so every cache and pool is one stack of ``n_stack`` layers.

Every weight matrix is a *site* (dense or TT-factorized per config); TT
sites contribute the rank-shrinkage prior and receive closed-form λ updates.
"""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..sharding import ShardPlan
from . import attention as A
from . import ffn as F
from . import moe as M
from . import ssm as S
from .common import (SiteDef, apply_site, init_site, make_site, rms_norm,
                     site_lambda_update, site_prior_loss)


@dataclass(frozen=True)
class SubDef:
    mixer_kind: str          # "attn_gqa" | "attn_mla" | "mamba" | "rwkv6"
    mixer: Any
    ffn_kind: str | None     # "ffn" | "moe" | None (rwkv has its own)
    ffn: Any


@dataclass(frozen=True)
class LMDef:
    cfg: ModelConfig
    embed: SiteDef | None    # None when frontend replaces token embedding
    head: SiteDef
    period: tuple[SubDef, ...]
    n_periods: int
    lead: tuple[SubDef, ...] = ()   # leading layers, before the periods
                                    # (single-sublayer periods only)

    @property
    def n_stack(self) -> int:
        """Depth of the stacked caches and pools: the leading layers, then
        one entry per period."""
        return len(self.lead) + self.n_periods


# ---------------------------------------------------------------------------
# Construction
# ---------------------------------------------------------------------------

def build_lm(cfg: ModelConfig) -> LMDef:
    subs: list[SubDef] = []

    def mixer_for(kind: str) -> tuple[str, Any]:
        if kind == "attn":
            if cfg.attn_kind == "mla":
                return "attn_mla", A.make_mla(cfg)
            return "attn_gqa", A.make_gqa(cfg)
        if kind == "mamba":
            return "mamba", S.make_mamba(cfg)
        if kind == "rwkv6":
            return "rwkv6", S.make_rwkv6(cfg)
        raise ValueError(kind)

    def ffn_for(use_moe: bool) -> tuple[str | None, Any]:
        if use_moe and cfg.moe.num_experts > 0:
            return "moe", M.make_moe(cfg)
        return "ffn", F.make_ffn(cfg)

    lead: tuple[SubDef, ...] = ()
    n_lead = cfg.first_dense_layers
    if n_lead and cfg.family not in ("dense", "moe"):
        raise NotImplementedError(
            f"leading dense layers on a {cfg.family!r} stack")

    if cfg.family == "ssm_rwkv6":
        mk, mx = mixer_for("rwkv6")
        subs.append(SubDef(mk, mx, None, None))
        n_periods = cfg.num_layers
    elif cfg.family == "hybrid_jamba":
        for pos in range(cfg.period):
            kind = "attn" if pos in cfg.attn_positions else "mamba"
            mk, mx = mixer_for(kind)
            fk, fd = ffn_for(pos in cfg.moe_positions)
            subs.append(SubDef(mk, mx, fk, fd))
        assert cfg.num_layers % cfg.period == 0
        n_periods = cfg.num_layers // cfg.period
    else:  # dense / moe / encoder
        mk, mx = mixer_for("attn")
        fk, fd = ffn_for(cfg.moe.num_experts > 0)
        subs.append(SubDef(mk, mx, fk, fd))
        n_periods = cfg.num_layers - n_lead
        lead = (SubDef(mk, mx, "ffn",
                       F.make_ffn(cfg, cfg.dense_d_ff or None)),) * n_lead

    embed = None
    if cfg.frontend != "audio":
        embed = make_site(cfg, "embed", cfg.vocab_size, cfg.d_model)
    head = make_site(cfg, "head", cfg.vocab_size, cfg.d_model)
    return LMDef(cfg, embed, head, tuple(subs), n_periods, lead)


def dropless(lm: LMDef) -> LMDef:
    """``lm`` with every MoE sublayer routing dropless, as a server routes:
    with a capacity, a served token's output would depend on the other
    tokens of its prefill chunk or decode batch."""
    def one(sub: SubDef) -> SubDef:
        if sub.ffn_kind != "moe" or sub.ffn.dropless:
            return sub
        return dataclasses.replace(
            sub, ffn=dataclasses.replace(sub.ffn, dropless=True))

    return dataclasses.replace(lm, period=tuple(map(one, lm.period)),
                               lead=tuple(map(one, lm.lead)))


def _init_sub(key: jax.Array, sub: SubDef, cfg: ModelConfig) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    p: dict = {"norm1": {"scale": jnp.ones((cfg.d_model,), jnp.float32)}}
    if sub.mixer_kind == "attn_gqa":
        p["mixer"] = A.init_gqa(k1, sub.mixer, cfg)
    elif sub.mixer_kind == "attn_mla":
        p["mixer"] = A.init_mla(k1, sub.mixer, cfg)
    elif sub.mixer_kind == "mamba":
        p["mixer"] = S.init_mamba(k1, sub.mixer, cfg)
    elif sub.mixer_kind == "rwkv6":
        p["mixer"] = S.init_rwkv6(k1, sub.mixer, cfg)
        p["norm2"] = {"scale": jnp.ones((cfg.d_model,), jnp.float32)}
        return p
    if sub.ffn_kind is not None:
        p["norm2"] = {"scale": jnp.ones((cfg.d_model,), jnp.float32)}
        if sub.ffn_kind == "moe":
            p["moe"] = M.init_moe(k2, sub.ffn, cfg)
        else:
            p["ffn"] = F.init_ffn(k2, sub.ffn, cfg)
    return p


def init_lm(key: jax.Array, lm: LMDef) -> dict:
    cfg = lm.cfg
    ke, kl, kh = jax.random.split(key, 3)
    params: dict = {}
    if lm.embed is not None:
        # embedding stored as (V, D) table (dense) or TT site
        if lm.embed.use_tt:
            params["embed"] = init_site(ke, lm.embed, cfg)
        else:
            sigma = 1.0 / math.sqrt(cfg.d_model)
            params["embed"] = {"w": (jax.random.normal(
                ke, (cfg.vocab_size, cfg.d_model), jnp.float32) * sigma
            ).astype(jnp.dtype(cfg.dtype))}

    def init_period(k):
        ks = jax.random.split(k, len(lm.period))
        return {f"sub_{i}": _init_sub(ks[i], sub, cfg)
                for i, sub in enumerate(lm.period)}

    params["layers"] = jax.vmap(init_period)(
        jax.random.split(kl, lm.n_periods))
    if lm.lead:
        kd = jax.random.split(jax.random.fold_in(kl, 1), len(lm.lead))
        params["lead"] = [_init_sub(kd[j], sub, cfg)
                          for j, sub in enumerate(lm.lead)]
    params["final_norm"] = {"scale": jnp.ones((cfg.d_model,), jnp.float32)}
    params["head"] = init_site(kh, lm.head, cfg)
    return params


# ---------------------------------------------------------------------------
# Embedding
# ---------------------------------------------------------------------------

def embed_tokens(params: dict, tokens: jax.Array, lm: LMDef) -> jax.Array:
    cfg = lm.cfg
    if lm.embed is not None and lm.embed.use_tt:
        from ..core.tt_layer import effective_cores
        from ..core.ttm import ttm_matvec
        # TT embedding lookup: one-hot-free digit-select contraction
        return tt_embed_lookup(params["embed"], tokens, lm.embed, cfg)
    table = params["embed"]["w"]
    return table[tokens].astype(jnp.dtype(cfg.dtype))


def tt_embed_lookup(eparams: dict, tokens: jax.Array, site: SiteDef,
                    cfg: ModelConfig) -> jax.Array:
    """Row lookup in a TT-represented (V, D) table.

    V is factored over the cores' J dims; each token id is decomposed into
    mixed-radix digits (j_1..j_d); the row is the product of the selected
    core slices — O(Σ R I R) FLOPs per token instead of a (V·D) table in
    memory (the paper's technique applied to embeddings; cf. Khrulkov 2019).
    """
    from ..core.tt_layer import effective_cores
    spec = site.spec
    cores = effective_cores(eparams, spec, cfg.tt, cfg.quant)
    shape = tokens.shape
    ids = tokens.reshape(-1)
    # mixed-radix digits, most-significant first (row-major over j_dims)
    digits = []
    rem = ids
    for n in range(spec.d - 1, -1, -1):
        digits.append(rem % spec.j_dims[n])
        rem = rem // spec.j_dims[n]
    digits = digits[::-1]

    m = jnp.ones((ids.shape[0], 1, 1), jnp.float32)      # (T, prefix=1, R0=1)
    for n in range(spec.d):
        g = cores[n].astype(jnp.float32)                 # (R, J, I, R')
        gsel = g[:, digits[n]]                           # (R, T, I, R')
        gsel = jnp.moveaxis(gsel, 1, 0)                  # (T, R, I, R')
        m = jnp.einsum("tpr,trik->tpik", m, gsel)
        m = m.reshape(ids.shape[0], -1, g.shape[3])      # (T, prefix*I, R')
    out = m[..., 0]                                      # (T, D)
    return out.reshape(shape + (spec.in_dim,)).astype(jnp.dtype(cfg.dtype))


# ---------------------------------------------------------------------------
# Forward / decode bodies
# ---------------------------------------------------------------------------

def _sub_forward(pp: dict, x: jax.Array, sub: SubDef, cfg: ModelConfig,
                 plan: ShardPlan, positions: jax.Array, *,
                 return_cache: bool, token_mask: jax.Array | None = None):
    """One sublayer (mixer + optional ffn). Returns (x, aux, cache_entry).

    ``token_mask``: optional (B, S) bool of real tokens — serve-prefill
    bucket padding is masked out of the MoE router so pad tokens never
    consume expert capacity (see ``moe_forward``)."""
    aux = jnp.zeros((), jnp.float32)
    cache = {}
    h = rms_norm(x, pp["norm1"]["scale"], cfg.norm_eps)
    causal = not cfg.is_encoder
    if sub.mixer_kind == "attn_gqa":
        q, k, v = A.gqa_qkv(pp["mixer"], h, sub.mixer, cfg, positions)
        q = plan.heads_act(q)
        k = plan.kv_full(k)
        v = plan.kv_full(v)
        out = A.chunked_attention(q, k, v, causal=causal, plan=plan)
        b, s = h.shape[:2]
        if sub.mixer.real_heads != sub.mixer.num_heads:
            out = out[:, :, :sub.mixer.real_heads]
        out = apply_site(pp["mixer"]["o"], out.reshape(b, s, -1),
                         sub.mixer.o, cfg)
        if return_cache:
            cache = {"k": k, "v": v}
    elif sub.mixer_kind == "attn_mla":
        out = A.mla_forward(pp["mixer"], h, sub.mixer, cfg, causal=causal,
                            positions=positions, plan=plan)
        if return_cache:
            c_kv, k_rope = A._mla_kv_latent(pp["mixer"], h, sub.mixer, cfg,
                                            positions)
            cache = {"c_kv": c_kv, "k_rope": k_rope}
    elif sub.mixer_kind == "mamba":
        out, st = S.mamba_forward(pp["mixer"], h, sub.mixer, cfg, None)
        if return_cache:
            cache = st
    elif sub.mixer_kind == "rwkv6":
        out, st = S.rwkv6_time_mix(pp["mixer"], h, sub.mixer, cfg, None)
        x = plan.hidden(x + out)
        h2 = rms_norm(x, pp["norm2"]["scale"], cfg.norm_eps)
        out2, st2 = S.rwkv6_channel_mix(pp["mixer"], h2, sub.mixer, cfg, None)
        x = plan.hidden(x + out2)
        if return_cache:
            cache = {**st, **st2}
        return x, aux, cache
    x = plan.hidden(x + out)
    if sub.ffn_kind is not None:
        h = rms_norm(x, pp["norm2"]["scale"], cfg.norm_eps)
        if sub.ffn_kind == "moe":
            out, a = M.moe_forward(pp["moe"], h, sub.ffn, cfg,
                                   mesh=plan.mesh, dp_axes=plan.dp_axes,
                                   token_mask=token_mask)
            aux = aux + a
        else:
            out = F.ffn_forward(pp["ffn"], h, sub.ffn, cfg)
        x = plan.hidden(x + out)
    return x, aux, cache


def _act_quant_edge(x: jax.Array, scales: dict, cfg: ModelConfig) -> jax.Array:
    """Policy-owned ``activation`` site for the zoo LMs: fake-quant the
    residual stream forward at ``act_bits`` and the incoming activation-
    gradient backward at ``grad_bits`` (clipped STE), with the SHARED
    managed scales from the ``TrainState.scales`` tree — the same §3.2/§3.3
    edge the FMNIST MLP carries per-tensor, scaled to one scale-owner per
    site across the whole stack (the policy's managed ScaleState)."""
    from ..core.quant import quant_edge_shared
    return quant_edge_shared(x, scales["activation"], scales["grad_edge"],
                             cfg.quant.act_bits, cfg.quant.grad_bits)


def _remat_wrap(fn, cfg: ModelConfig):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        pol = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    else:
        pol = jax.checkpoint_policies.nothing_saveable
    return jax.checkpoint(fn, policy=pol)


def lm_forward(params: dict, lm: LMDef, plan: ShardPlan, *,
               tokens: jax.Array | None = None,
               embeds: jax.Array | None = None,
               return_cache: bool = False,
               token_mask: jax.Array | None = None,
               scales: dict | None = None):
    """Train/prefill forward.

    tokens: (B, S) int32 and/or embeds: (B, P, D) frontend outputs (vlm:
    embeds are prepended to token embeddings; audio: embeds replace them).
    token_mask: optional (B, S) bool of real positions — padding (serve
    whole-prompt prefill buckets) is excluded from MoE capacity routing.
    scales: optional NumericsPolicy managed scale-state tree
    (``TrainState.scales``). When given (and ``cfg.quant.enable``) the
    ``activation`` site goes live: the residual stream is fake-quantized at
    every sublayer boundary (plus the embedding output) with the shared
    managed scales, and the return gains a 4th element ``obs`` — the
    per-layer mean |activation| statistic the scale manager consumes
    (``policy.update_scales(scales, obs)`` in the train step).
    Returns (logits, aux, cache|None) or (logits, aux, cache|None, obs);
    cache leaves are stacked (n_stack, B, S, ...), leading layers first.
    """
    cfg = lm.cfg
    # the edge quantizes fwd AND bwd, so both managed sites must be present
    # (a custom policy may demote either to fixed/per-tensor-max scales)
    quant_acts = (scales is not None and cfg.quant.enable
                  and "activation" in scales and "grad_edge" in scales)
    if embeds is not None and tokens is not None:
        xt = embed_tokens(params, tokens, lm)
        x = jnp.concatenate([embeds.astype(xt.dtype), xt], axis=1)
    elif embeds is not None:
        x = embeds.astype(jnp.dtype(cfg.dtype))
    else:
        x = embed_tokens(params, tokens, lm)
    b, s, _ = x.shape
    x = plan.hidden(x)
    if quant_acts:
        x = _act_quant_edge(x, scales, cfg)
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))

    def sub_step(x, pp, sub):
        x, a, c = _sub_forward(pp, x, sub, cfg, plan, positions,
                               return_cache=return_cache,
                               token_mask=token_mask)
        if quant_acts:
            x = _act_quant_edge(x, scales, cfg)
        return x, a, c

    def amean_of(x):
        return jnp.mean(jnp.abs(jax.lax.stop_gradient(x).astype(jnp.float32)))

    aux, amean = jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)
    lead_caches = []
    for pp, sub in zip(params.get("lead", ()), lm.lead):
        x, a, c = _remat_wrap(partial(sub_step, sub=sub), cfg)(x, pp)
        aux = aux + a
        if quant_acts:
            amean = amean + amean_of(x)
        lead_caches.append(c)

    def body(carry, pp):
        x, aux, amean = carry
        caches = {}
        for i, sub in enumerate(lm.period):
            x, a, c = sub_step(x, pp[f"sub_{i}"], sub)
            aux = aux + a
            caches[f"sub_{i}"] = c
        if quant_acts:
            amean = amean + amean_of(x)
        return (x, aux, amean), caches

    body = _remat_wrap(body, cfg)
    (x, aux, amean), caches = jax.lax.scan(body, (x, aux, amean),
                                           params["layers"])
    if lead_caches:
        caches = {"sub_0": jax.tree.map(
            lambda c, *ls: jnp.concatenate([jnp.stack(ls), c]),
            caches["sub_0"], *lead_caches)}
    x = rms_norm(x, params["final_norm"]["scale"], cfg.norm_eps)
    logits = apply_site(params["head"], x, lm.head, cfg)
    if cfg.logits_softcap > 0:
        logits = jnp.tanh(logits / cfg.logits_softcap) * cfg.logits_softcap
    logits = plan.logits(logits)
    cache = caches if return_cache else None
    if scales is None:
        return logits, aux, cache
    obs = {"activation": (amean / lm.n_stack)[None]} if quant_acts else {}
    return logits, aux, cache, obs


def sub_ffn_decode(pp: dict, x: jax.Array, sub: SubDef, cfg: ModelConfig,
                   plan: ShardPlan,
                   token_mask: jax.Array | None = None,
                   with_hits: bool = False):
    """Post-mixer FFN/MoE half of a sublayer (shared by the static decode
    path and repro.serve's paged decode/chunk steps).

    ``token_mask``: optional (B, S) bool of real tokens — inactive serve
    slots / prefill-chunk padding are masked out of the MoE router so junk
    tokens never consume expert capacity (dense FFN ignores it: per-token
    math can't interfere across rows). ``with_hits``: also return the MoE
    layer's int32 [held experts hit, tokens routed to them] (zeros for a
    dense FFN; see ``moe_forward``)."""
    none = [jnp.zeros((2,), jnp.int32)] if with_hits else []
    if sub.ffn_kind is None:
        return (x, *none) if with_hits else x
    h = rms_norm(x, pp["norm2"]["scale"], cfg.norm_eps)
    if sub.ffn_kind == "moe":
        out, _, *hits = M.moe_forward(pp["moe"], h, sub.ffn, cfg,
                                      mesh=plan.mesh, dp_axes=plan.dp_axes,
                                      token_mask=token_mask,
                                      with_hits=with_hits)
    else:
        out, hits = F.ffn_forward(pp["ffn"], h, sub.ffn, cfg), none
    return (x + out, *hits) if with_hits else x + out


def _sub_decode(pp: dict, x: jax.Array, cc: dict, sub: SubDef,
                cfg: ModelConfig, plan: ShardPlan, cur_len: jax.Array):
    h = rms_norm(x, pp["norm1"]["scale"], cfg.norm_eps)
    if sub.mixer_kind == "attn_gqa":
        out, cnew = A.gqa_decode(pp["mixer"], h, cc, sub.mixer, cfg, cur_len)
        cnew = {k: plan.cache_kv(v) for k, v in cnew.items()}
    elif sub.mixer_kind == "attn_mla":
        out, cnew = A.mla_decode(pp["mixer"], h, cc, sub.mixer, cfg, cur_len)
        cnew = {k: plan.cache_kv(v) for k, v in cnew.items()}
    elif sub.mixer_kind == "mamba":
        out, cnew = S.mamba_forward(pp["mixer"], h, sub.mixer, cfg, cc)
    elif sub.mixer_kind == "rwkv6":
        out, st = S.rwkv6_time_mix(pp["mixer"], h, sub.mixer, cfg, cc)
        x = x + out
        h2 = rms_norm(x, pp["norm2"]["scale"], cfg.norm_eps)
        out2, st2 = S.rwkv6_channel_mix(pp["mixer"], h2, sub.mixer, cfg, cc)
        return x + out2, {**st, **st2}
    x = x + out
    return sub_ffn_decode(pp, x, sub, cfg, plan), cnew


def lm_decode_step(params: dict, cache: dict, tokens: jax.Array,
                   cur_len: jax.Array, lm: LMDef, plan: ShardPlan):
    """One-token decode. tokens: (B,1). cache leaves stacked (n_stack, ...).
    ``cur_len``: scalar shared position, or a per-slot (B,) vector — each
    batch row then appends/attends at its own length (the continuous-
    batching primitive; see repro.serve). Returns (logits, new_cache)."""
    cfg = lm.cfg
    x = embed_tokens(params, tokens, lm)
    x = plan.constrain(x, jax.sharding.PartitionSpec(plan.dp_axes, None, None))

    n_lead = len(lm.lead)
    lead_new = []
    for j, sub in enumerate(lm.lead):
        cc = jax.tree.map(lambda a: a[j], cache["sub_0"])
        x, cnew = _sub_decode(params["lead"][j], x, cc, sub, cfg, plan,
                              cur_len)
        lead_new.append(cnew)

    def body(x, scan_in):
        pp, cc = scan_in
        new_cc = {}
        for i, sub in enumerate(lm.period):
            x, cnew = _sub_decode(pp[f"sub_{i}"], x, cc[f"sub_{i}"], sub,
                                  cfg, plan, cur_len)
            new_cc[f"sub_{i}"] = cnew
        return x, new_cc

    if n_lead:
        cache = jax.tree.map(lambda a: a[n_lead:], cache)
    x, new_cache = jax.lax.scan(body, x, (params["layers"], cache))
    if n_lead:
        new_cache = {"sub_0": jax.tree.map(
            lambda c, *ls: jnp.concatenate([jnp.stack(ls), c]),
            new_cache["sub_0"], *lead_new)}
    x = rms_norm(x, params["final_norm"]["scale"], cfg.norm_eps)
    logits = apply_site(params["head"], x, lm.head, cfg)
    return logits, new_cache


def lm_init_cache(lm: LMDef, batch: int, max_len: int, plan: ShardPlan) -> dict:
    cfg = lm.cfg
    dtype = jnp.dtype(cfg.dtype)

    def one_sub(sub: SubDef) -> dict:
        if sub.mixer_kind == "attn_gqa":
            c = A.gqa_init_cache(sub.mixer, batch, max_len, dtype)
        elif sub.mixer_kind == "attn_mla":
            c = A.mla_init_cache(sub.mixer, batch, max_len, dtype)
        elif sub.mixer_kind == "mamba":
            c = S.mamba_init_state(sub.mixer, batch, dtype)
        else:
            c = S.rwkv6_init_state(sub.mixer, batch, cfg.d_model, dtype)
        return c

    def stack(c):
        return jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (lm.n_stack,) + a.shape), c)

    return {f"sub_{i}": stack(one_sub(sub))
            for i, sub in enumerate(lm.period)}


def lm_cache_pspec(lm: LMDef, cache: dict, plan: ShardPlan):
    """PartitionSpec tree for a decode cache: seq-sharded over data when
    plan.seq_sharded_cache (long-context SP), else batch over dp / heads
    over model where divisible."""
    from jax.sharding import PartitionSpec as P

    def spec_for(a: jax.Array) -> P:
        if plan.mesh is None:
            return P()
        # leading axis = period stack, axis 1 = batch, axis 2 = seq/feature
        rest = (None,) * (a.ndim - 3)
        if plan.seq_sharded_cache and a.ndim >= 3 and \
                a.shape[2] % plan.mesh.shape["data"] == 0 and a.shape[2] > 1024:
            return P(None, None, "data", *rest)
        if a.shape[1] % _dpsize(plan) == 0 and a.shape[1] >= _dpsize(plan):
            return P(None, plan.dp_axes, None, *rest)
        return P()

    return jax.tree.map(spec_for, cache)


def _dpsize(plan: ShardPlan) -> int:
    if plan.mesh is None:
        return 1
    n = 1
    for ax in plan.dp_axes:
        n *= plan.mesh.shape[ax]
    return n


# ---------------------------------------------------------------------------
# TT-site walking (prior loss, λ update, param counting)
# ---------------------------------------------------------------------------

def _sub_sites(sub: SubDef):
    """Yield (path within the sublayer's params, SiteDef) of its sites."""
    mk = sub.mixer_kind
    names = {"attn_gqa": ("q", "kv", "o"),
             "attn_mla": ("q", "q_down", "q_up", "kv_down", "k_up", "v_up",
                          "o"),
             "mamba": ("in_proj", "x_proj", "dt_proj", "out_proj"),
             "rwkv6": ("r", "k", "v", "g", "o", "w_lora_a", "w_lora_b",
                       "ffn_k", "ffn_v", "ffn_r")}[mk]
    for n in names:
        site = getattr(sub.mixer, n)
        if site is not None:
            yield ("mixer", n), site
    if sub.ffn_kind == "ffn":
        for n in ("gate", "up", "down"):
            yield ("ffn", n), getattr(sub.ffn, n)
    elif sub.ffn_kind == "moe":
        for n in ("router", "gate", "up", "down"):
            yield ("moe", n), getattr(sub.ffn, n)
        if sub.ffn.shared is not None:
            for n in ("gate", "up", "down"):
                yield ("moe", "shared", n), getattr(sub.ffn.shared, n)


def _walk_sites(lm: LMDef):
    """Yield (params_path_tuple, SiteDef) for every weight site."""
    if lm.embed is not None:
        yield ("embed",), lm.embed
    for j, sub in enumerate(lm.lead):
        for path, site in _sub_sites(sub):
            yield ("lead", j) + path, site
    for i, sub in enumerate(lm.period):
        for path, site in _sub_sites(sub):
            yield ("layers", f"sub_{i}") + path, site
    yield ("head",), lm.head


def _get_path(params, path):
    node = params
    for p in path:
        node = node[p]
    return node


def lm_prior_loss(params: dict, lm: LMDef) -> jax.Array:
    total = jnp.zeros((), jnp.float32)
    for path, site in _walk_sites(lm):
        if site.use_tt:
            total = total + site_prior_loss(_get_path(params, path), site, lm.cfg)
    return total


def lm_lambda_update(params: dict, lm: LMDef) -> dict:
    if not lm.cfg.tt.enable or not lm.cfg.tt.rank_adapt:
        return params
    import copy
    new = jax.tree.map(lambda a: a, params)  # shallow-ish copy of structure

    def set_path(tree, path, value):
        node = tree
        for p in path[:-1]:
            node = node[p]
        node[path[-1]] = value

    for path, site in _walk_sites(lm):
        if site.use_tt:
            old = _get_path(new, path)
            set_path(new, path, site_lambda_update(old, site, lm.cfg))
    return new


def lm_param_counts(params: dict, lm: LMDef) -> dict:
    """Dense-equivalent vs actual vs live (post-pruning) parameter counts."""
    from ..core import rank_adapt as RA
    dense = 0
    actual = 0
    live = 0
    for path, site in _walk_sites(lm):
        stack = lm.n_periods if path[0] == "layers" else 1
        mult = stack
        if site.use_tt:
            p = _get_path(params, path)
            spec = site.spec
            dense += site.out_dim * site.in_dim * mult
            actual += spec.num_params * mult
            lambdas = [p[f"lambda_{n}"] for n in range(spec.d - 1)
                       if f"lambda_{n}" in p]
            if lambdas and lambdas[0].ndim > 0:
                # stacked: count live ranks per stack entry
                import numpy as np
                th = lm.cfg.tt.prune_threshold
                for s_i in range(mult if lambdas[0].ndim > 1 else 1):
                    eff = []
                    for lam in lambdas:
                        l = lam[s_i] if lam.ndim > 1 else lam
                        eff.append(int(jnp.sum(l > th * jnp.max(l))))
                    ranks = [1] + eff + [1]
                    live += sum(ranks[n] * spec.j_dims[n] * spec.i_dims[n]
                                * ranks[n + 1] for n in range(spec.d))
            else:
                live += spec.num_params * mult
        else:
            n = site.out_dim * site.in_dim * mult
            dense += n
            actual += n
            live += n
    return {"dense": dense, "tt": actual, "live": live,
            "compression": dense / max(live, 1)}
