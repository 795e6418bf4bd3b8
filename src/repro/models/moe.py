"""Mixture-of-Experts FFN with expert parallelism.

Routing: top-k softmax, or DeepSeek-V3's sigmoid ``noaux_tc`` (a per-expert
correction bias picks the experts, the unbiased scores weigh them),
optionally normalized over the selected and scaled; capacity-based token
dropping (GShard semantics) or dropless dispatch; switch-style load-balance
aux loss.

Held share: a layer may hold ``experts_held`` experts starting at
``expert_offset`` (one chip's share of an expert-parallel deployment). It
routes over all ``num_experts`` and returns its own experts' part plus the
shared experts; what the experts held elsewhere would add is left out.

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
    num_experts: int        # experts the router spans
    top_k: int
    capacity_factor: float
    d_ff: int
    scoring: str = "softmax"
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    dropless: bool = False
    experts_held: int = 0   # resolved by make_moe: the stacked expert count
    expert_offset: int = 0


@dataclass(frozen=True)
class FFNLike:
    gate: SiteDef
    up: SiteDef
    down: SiteDef


def make_moe(cfg: ModelConfig, d_ff: int | None = None) -> MoEDef:
    f = d_ff or cfg.d_ff
    m = cfg.moe
    if m.n_group != 1:
        raise NotImplementedError(
            f"group-limited routing (n_group={m.n_group}) is not implemented")
    if m.scoring not in ("softmax", "sigmoid"):
        raise ValueError(f"unknown MoE scoring {m.scoring!r}")
    held = m.experts_held or m.num_experts
    if not 0 <= m.expert_offset <= m.num_experts - held:
        raise ValueError(f"held experts {m.expert_offset}..+{held} outside "
                         f"the router's {m.num_experts}")
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
        capacity_factor=m.capacity_factor, d_ff=f, scoring=m.scoring,
        norm_topk_prob=m.norm_topk_prob,
        routed_scaling_factor=m.routed_scaling_factor, dropless=m.dropless,
        experts_held=held, expert_offset=m.expert_offset)


def init_moe(key: jax.Array, d: MoEDef, cfg: ModelConfig) -> dict:
    ks = jax.random.split(key, 8)
    e = d.experts_held

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
    if d.scoring == "sigmoid":
        # noaux_tc's correction bias: learned by a balancing rule, not by
        # gradient; zero at init
        p["router_bias"] = jnp.zeros((d.num_experts,), jnp.float32)
    return p


def _route(params, x2d, d: MoEDef, cfg: ModelConfig, mask=None):
    """x2d: (T, D) -> (topk_idx (T,k), topk_w (T,k), aux_loss).

    Softmax scoring picks the k largest probabilities. Sigmoid scoring
    (DeepSeek-V3 ``noaux_tc``) picks the k largest of ``sigmoid(logits) +
    router_bias`` and weighs them by their unbiased sigmoid scores. The k
    weights are then normalized over themselves (``norm_topk_prob``) and
    scaled by ``routed_scaling_factor``.

    ``mask``: optional (T,) bool of *real* tokens. Masked tokens (inactive
    serve slots, prefill padding) get zero combine weight — so they never
    win a capacity slot against a real token in ``_dispatch_local``'s
    top-C selection — and are excluded from the load-balance statistics.
    """
    logits = apply_site(params["router"], x2d.astype(jnp.float32),
                        d.router, cfg).astype(jnp.float32)
    if d.scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        _, topk_idx = jax.lax.top_k(scores + params["router_bias"], d.top_k)
        topk_w = jnp.take_along_axis(scores, topk_idx, axis=-1)
        probs = scores / jnp.sum(scores, axis=-1, keepdims=True)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        topk_w, topk_idx = jax.lax.top_k(probs, d.top_k)
    if d.norm_topk_prob:
        topk_w = topk_w / jnp.maximum(jnp.sum(topk_w, axis=-1, keepdims=True),
                                      1e-9)
    if d.routed_scaling_factor != 1.0:
        topk_w = topk_w * d.routed_scaling_factor
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


def _held_weights(topk_idx, topk_w, d: MoEDef, e_start, e_local: int):
    """(E_loc, T) combine weight of each token for each of ``e_local``
    experts starting at ``e_start`` (0 where not routed), and the (E_loc,
    T) routed indicator."""
    eids = e_start + jnp.arange(e_local)                      # (E_loc,)
    match = (topk_idx[None, :, :] == eids[:, None, None])     # (E_loc, T, k)
    w_tok = jnp.sum(jnp.where(match, topk_w[None].astype(jnp.float32), 0.0),
                    axis=-1)
    return w_tok, jnp.any(match, axis=-1)


def _glu(ep, x, d: MoEDef, cfg: ModelConfig):
    """One expert's SwiGLU; ``ep`` holds its gate/up/down sites."""
    g = apply_site(ep["gate"], x, d.gate, cfg)
    u = apply_site(ep["up"], x, d.up, cfg)
    return apply_site(ep["down"], silu(g) * u, d.down, cfg)


def _expert_glu(eparams, xe, d: MoEDef, cfg: ModelConfig):
    """xe: (E_loc, C, D) through per-expert GLU; eparams leaves stacked (E_loc, ...)."""
    return jax.vmap(lambda ep, xi: _glu(ep, xi, d, cfg))(eparams, xe)


def _dispatch_local(x2d, topk_idx, topk_w, eparams, d: MoEDef, cfg: ModelConfig,
                    e_start: jax.Array, e_local: int, capacity: int):
    """Gather top-C tokens for each of ``e_local`` experts starting at
    ``e_start``, run the expert GLU, scatter-add back. Pure function of
    local data — used both single-device and inside shard_map."""
    w_tok, _ = _held_weights(topk_idx, topk_w, d, e_start, e_local)
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


def _dispatch_dropless(x2d, w_tok, eparams, d: MoEDef, cfg: ModelConfig):
    """Every held expert over every token, combined by ``w_tok`` (E_loc,
    T): no token is dropped at any load, and a token's output does not
    depend on the other tokens of its batch."""
    ye = jax.vmap(lambda ep: _glu(ep, x2d, d, cfg))(eparams)  # (E_loc,T,D)
    return jnp.einsum("et,etd->td", w_tok.astype(ye.dtype), ye,
                      preferred_element_type=jnp.float32).astype(x2d.dtype)


def moe_forward(params: dict, x: jax.Array, d: MoEDef, cfg: ModelConfig, *,
                mesh=None, dp_axes=("data",), ep_axis: str = "model",
                token_mask: jax.Array | None = None,
                capacity_tokens: int | None = None, with_hits: bool = False):
    """x: (B, S, D) -> (out, aux_loss), and with ``with_hits`` a third
    value: int32 [held experts that received a real token, (token, held
    expert) assignments of real tokens].

    If ``mesh`` has a >1-sized ``ep_axis``, runs the shard_map EP path;
    otherwise the single-shard path over the held experts. ``d.dropless``
    runs every held expert over every token (no capacity); otherwise
    experts keep their top-C tokens.

    ``token_mask``: optional (B, S) bool of real tokens; masked tokens
    (inactive serve slots, chunked-prefill padding) are dropped from the
    router so they cannot consume expert capacity (see ``_route``).

    ``capacity_tokens``: optional static token-count basis for expert
    capacity (see ``_capacity``). On the EP path it is the *global* basis
    applied per shard unscaled; the clamp to local tokens still bounds
    ``top_k``'s k.
    """
    with jax.named_scope("repro.ops.moe"):
        return _moe_forward(params, x, d, cfg, mesh, dp_axes, ep_axis,
                            token_mask, capacity_tokens, with_hits)


def _moe_forward(params, x, d: MoEDef, cfg: ModelConfig, mesh, dp_axes,
                 ep_axis, token_mask, capacity_tokens, with_hits):
    b, s, dm = x.shape
    x2d = x.reshape(b * s, dm)
    mask = None if token_mask is None else token_mask.reshape(b * s)
    topk_idx, topk_w, aux = _route(params, x2d, d, cfg, mask)
    eparams = {"gate": params["gate"], "up": params["up"], "down": params["down"]}

    ep = 1
    if mesh is not None and ep_axis in mesh.shape:
        ep = mesh.shape[ep_axis]
    if ep > 1 and (d.dropless or d.experts_held != d.num_experts):
        raise NotImplementedError(
            "the expert exchange across chips covers capacity routing over "
            "all experts only (no dropless dispatch, no held share)")
    e0 = jnp.int32(d.expert_offset)
    hits = None
    if ep == 1 and (d.dropless or with_hits):
        w_tok, routed = _held_weights(topk_idx, topk_w, d, e0, d.experts_held)
        if with_hits:
            if mask is not None:
                routed = routed & mask[None]
            hits = jnp.stack([jnp.sum(jnp.any(routed, axis=1)),
                              jnp.sum(routed)]).astype(jnp.int32)

    if ep == 1 and d.dropless:
        out = _dispatch_dropless(x2d, w_tok, eparams, d, cfg)
    elif ep == 1:
        cap = _capacity(b * s, d, capacity_tokens)
        out = _dispatch_local(x2d, topk_idx, topk_w, eparams, d, cfg,
                              e0, d.experts_held, cap)
    else:
        if with_hits:
            raise NotImplementedError("held-expert hits on the EP path")
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
    if with_hits:
        return out, aux, hits
    return out, aux


def _capacity(tokens_per_shard: int, d: MoEDef,
              capacity_tokens: int | None = None) -> int:
    """Per-expert capacity: cf * tokens * k / E, rounded up to 8, clamped to
    the local token count (decode steps have very few tokens).

    ``capacity_tokens`` overrides the token basis without changing the
    clamp: pieces of a sequence routed with the whole sequence's basis keep
    every token the whole pass would keep, as long as that pass is not
    itself capacity-bound (the clamp keeps ``top_k``'s k <= the visible
    token count). A server routes dropless instead (``models/lm.py::
    dropless``)."""
    basis = capacity_tokens if capacity_tokens is not None else \
        tokens_per_shard
    cap = int(d.capacity_factor * basis * d.top_k / d.num_experts)
    cap = max(8, cap)
    cap = (cap + 7) // 8 * 8
    return min(cap, tokens_per_shard)
