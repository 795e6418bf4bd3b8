"""Mixture-of-Experts FFN with expert parallelism.

Routing: top-k softmax (optionally normalized over selected), capacity-based
token dropping (GShard semantics), switch-style load-balance aux loss.

Distribution: experts are sharded over the ``model`` mesh axis. The baseline
dispatch runs under ``shard_map``: tokens are data-sharded and replicated
across the model axis; each model shard gathers (top-C per local expert) only
the tokens routed to ITS experts, runs the expert GLU, scatter-adds into a
local output, and a single ``psum`` over the model axis combines. Collective
volume per MoE layer = one psum of the (tokens × d_model) activation — the
§Perf hillclimb replaces this with an index-based exchange (see
EXPERIMENTS.md).

Single-device (smoke-test) path: same math without shard_map.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..configs.base import ModelConfig
from ..sharding import shard_map
from .common import SiteDef, apply_site, init_site, make_site, silu


@dataclass(frozen=True)
class MoEDef:
    router: SiteDef
    gate: SiteDef           # per-expert, stacked on axis 0
    up: SiteDef
    down: SiteDef
    shared: "FFNLike | None"
    num_experts: int
    top_k: int
    capacity_factor: float
    d_ff: int


@dataclass(frozen=True)
class FFNLike:
    gate: SiteDef
    up: SiteDef
    down: SiteDef


def make_moe(cfg: ModelConfig, d_ff: int | None = None) -> MoEDef:
    f = d_ff or cfg.d_ff
    m = cfg.moe
    shared = None
    if m.num_shared > 0:
        fs = f * m.num_shared
        shared = FFNLike(
            gate=make_site(cfg, "ffn", fs, cfg.d_model),
            up=make_site(cfg, "ffn", fs, cfg.d_model),
            down=make_site(cfg, "ffn", cfg.d_model, fs))
    return MoEDef(
        router=make_site(cfg, "ffn", m.num_experts, cfg.d_model),
        gate=make_site(cfg, "expert", f, cfg.d_model),
        up=make_site(cfg, "expert", f, cfg.d_model),
        down=make_site(cfg, "expert", cfg.d_model, f),
        shared=shared, num_experts=m.num_experts, top_k=m.top_k,
        capacity_factor=m.capacity_factor, d_ff=f)


def init_moe(key: jax.Array, d: MoEDef, cfg: ModelConfig) -> dict:
    ks = jax.random.split(key, 8)
    e = d.num_experts

    def stack_init(k, site):
        return jax.vmap(lambda kk: init_site(kk, site, cfg))(
            jax.random.split(k, e))

    p = {
        "router": init_site(ks[0], d.router, cfg),
        "gate": stack_init(ks[1], d.gate),
        "up": stack_init(ks[2], d.up),
        "down": stack_init(ks[3], d.down),
    }
    if d.shared is not None:
        p["shared"] = {
            "gate": init_site(ks[4], d.shared.gate, cfg),
            "up": init_site(ks[5], d.shared.up, cfg),
            "down": init_site(ks[6], d.shared.down, cfg),
        }
    return p


def _route(params, x2d, d: MoEDef, cfg: ModelConfig, mask=None):
    """x2d: (T, D) -> (topk_idx (T,k), topk_w (T,k), aux_loss).

    ``mask``: optional (T,) bool of *real* tokens. Masked tokens (inactive
    serve slots, prefill padding) get zero combine weight — so they never
    win a capacity slot against a real token in ``_dispatch_local``'s
    top-C selection — and are excluded from the load-balance statistics.
    """
    logits = apply_site(params["router"], x2d.astype(jnp.float32),
                        d.router, cfg).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    topk_w, topk_idx = jax.lax.top_k(probs, d.top_k)
    topk_w = topk_w / jnp.maximum(jnp.sum(topk_w, axis=-1, keepdims=True), 1e-9)
    # switch aux loss: E * sum_e f_e * p_e
    e = d.num_experts
    dispatch = jax.nn.one_hot(topk_idx[:, 0], e)     # count top-1 for f_e
    if mask is not None:
        mf = mask.astype(jnp.float32)[:, None]
        topk_w = topk_w * mf
        n = jnp.maximum(jnp.sum(mf), 1.0)
        f_e = jnp.sum(dispatch * mf, axis=0) / n
        p_e = jnp.sum(probs * mf, axis=0) / n
    else:
        f_e = jnp.mean(dispatch, axis=0)
        p_e = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(f_e * p_e)
    return topk_idx, topk_w.astype(x2d.dtype), aux


def _expert_glu(eparams, xe, d: MoEDef, cfg: ModelConfig):
    """xe: (E_loc, C, D) through per-expert GLU; eparams leaves stacked (E_loc, ...)."""
    def one(ep, xi):
        g = apply_site(ep["gate"], xi, d.gate, cfg)
        u = apply_site(ep["up"], xi, d.up, cfg)
        return apply_site(ep["down"], silu(g) * u, d.down, cfg)

    return jax.vmap(one)(eparams, xe)


def _dispatch_local(x2d, topk_idx, topk_w, eparams, d: MoEDef, cfg: ModelConfig,
                    e_start: jax.Array, e_local: int, capacity: int):
    """Gather top-C tokens for each of ``e_local`` experts starting at
    ``e_start``, run the expert GLU, scatter-add back. Pure function of
    local data — used both single-device and inside shard_map."""
    t = x2d.shape[0]
    # score of each token for each local expert (0 if not routed)
    eids = e_start + jnp.arange(e_local)                      # (E_loc,)
    # (T, k) routed-to-expert match -> weight, else 0
    match = (topk_idx[None, :, :] == eids[:, None, None])     # (E_loc, T, k)
    w_tok = jnp.sum(jnp.where(match, topk_w[None].astype(jnp.float32), 0.0),
                    axis=-1)                                  # (E_loc, T)
    # top-C tokens per expert (capacity dropping; ties broken by token order)
    cw, cidx = jax.lax.top_k(w_tok, capacity)                 # (E_loc, C)
    valid = cw > 0.0
    xe = x2d[cidx.reshape(-1)].reshape(e_local, capacity, -1) # (E_loc, C, D)
    ye = _expert_glu(eparams, xe, d, cfg)                     # (E_loc, C, D)
    ye = ye * (cw * valid)[..., None].astype(ye.dtype)
    out = jnp.zeros_like(x2d)
    out = out.at[cidx.reshape(-1)].add(
        ye.reshape(-1, ye.shape[-1]), mode="drop")
    return out


def moe_forward(params: dict, x: jax.Array, d: MoEDef, cfg: ModelConfig, *,
                mesh=None, dp_axes=("data",), ep_axis: str = "model",
                token_mask: jax.Array | None = None,
                capacity_tokens: int | None = None
                ) -> tuple[jax.Array, jax.Array]:
    """x: (B, S, D) -> (out, aux_loss).

    If ``mesh`` has a >1-sized ``ep_axis``, runs the shard_map EP path;
    otherwise the single-shard path (same math, e_start=0, all experts local).

    ``token_mask``: optional (B, S) bool of real tokens; masked tokens
    (inactive serve slots, chunked-prefill padding) are dropped from the
    router so they cannot consume expert capacity (see ``_route``).

    ``capacity_tokens``: optional static token-count basis for expert
    capacity (serve chunked-prefill parity — see ``_capacity``). On the EP
    path it is the *global* basis applied per shard unscaled; the clamp to
    local tokens still bounds ``top_k``'s k.
    """
    b, s, dm = x.shape
    x2d = x.reshape(b * s, dm)
    mask = None if token_mask is None else token_mask.reshape(b * s)
    topk_idx, topk_w, aux = _route(params, x2d, d, cfg, mask)
    eparams = {"gate": params["gate"], "up": params["up"], "down": params["down"]}

    ep = 1
    if mesh is not None and ep_axis in mesh.shape:
        ep = mesh.shape[ep_axis]

    if ep == 1:
        cap = _capacity(b * s, d, capacity_tokens)
        out = _dispatch_local(x2d, topk_idx, topk_w, eparams, d, cfg,
                              jnp.int32(0), d.num_experts, cap)
    else:
        e_local = d.num_experts // ep
        # tokens per shard: the token block shards over dp_axes on whichever
        # of (batch, seq) divides (decode steps with batch < dp replicate);
        # each model shard sees its full local token block and only its
        # e_local experts — capacity is per (data-shard, expert).
        dp = 1
        for ax in dp_axes:
            dp *= mesh.shape.get(ax, 1)
        if b % dp == 0 and b >= dp:
            tok_spec = P(dp_axes, None, None)
            t_loc = (b // dp) * s
        elif s % dp == 0 and s >= dp:
            tok_spec = P(None, dp_axes, None)
            t_loc = b * (s // dp)
        else:
            tok_spec = P(None, None, None)
            t_loc = b * s
        cap = _capacity(t_loc, d, capacity_tokens)

        # combine: reduce-scatter the partial expert outputs along the seq
        # dim straight into the sequence-parallel layout (half the wire
        # bytes of an all-reduce, and the result already matches
        # plan.hidden's seq-sharding) — in bf16, not the f32 the
        # combine-weights produced.
        s_loc = x.shape[1]
        use_scatter = s_loc % ep == 0 and s_loc >= ep
        out_spec = tok_spec
        if use_scatter:
            out_spec = P(tok_spec[0], ep_axis, None) if tok_spec[1] is None \
                else tok_spec  # seq already sharded by dp: keep psum

        def shard_fn(x_loc, ti_loc, tw_loc, ep_loc):
            rank = jax.lax.axis_index(ep_axis)
            out_loc = _dispatch_local(
                x_loc.reshape(-1, dm), ti_loc.reshape(-1, d.top_k),
                tw_loc.reshape(-1, d.top_k), ep_loc, d, cfg,
                rank * e_local, e_local, cap)
            out_loc = out_loc.astype(x_loc.dtype).reshape(x_loc.shape)
            if use_scatter and out_spec is not tok_spec:
                return jax.lax.psum_scatter(out_loc, ep_axis,
                                            scatter_dimension=1, tiled=True)
            return jax.lax.psum(out_loc, ep_axis)

        out = shard_map(
            shard_fn, mesh,
            (tok_spec, tok_spec, tok_spec,
             jax.tree.map(lambda _: P(ep_axis), eparams)),
            out_spec,
        )(x, topk_idx.reshape(b, s, d.top_k),
          topk_w.reshape(b, s, d.top_k), eparams)
        out = out.reshape(b * s, dm)

    out = out.reshape(b, s, dm)
    if d.shared is not None:
        sh = params["shared"]
        g = apply_site(sh["gate"], x, d.shared.gate, cfg)
        u = apply_site(sh["up"], x, d.shared.up, cfg)
        out = out + apply_site(sh["down"], silu(g) * u, d.shared.down, cfg)
    return out, aux


def _capacity(tokens_per_shard: int, d: MoEDef,
              capacity_tokens: int | None = None) -> int:
    """Per-expert capacity: cf * tokens * k / E, rounded up to 8, clamped to
    the local token count (decode steps have very few tokens).

    ``capacity_tokens`` overrides the token basis without changing the
    clamp — the serve engine's chunked-prefill capacity parity: capacity
    derives from the FULL prompt length, so a chunk never spuriously drops
    a token that whole-prompt routing would have kept (the clamp keeps
    ``top_k``'s k <= the visible token count; whenever the full-prompt
    capacity covers the chunk, per-chunk routing keeps everything, exactly
    like an un-capacity-bound whole-prompt pass)."""
    basis = capacity_tokens if capacity_tokens is not None else \
        tokens_per_shard
    cap = int(d.capacity_factor * basis * d.top_k / d.num_experts)
    cap = max(8, cap)
    cap = (cap + 7) // 8 * 8
    return min(cap, tokens_per_shard)
