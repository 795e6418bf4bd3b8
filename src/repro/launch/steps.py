"""Train / serve step factories — the functions the dry-run lowers and the
real launcher runs. One code path for every arch in the zoo."""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from ..configs.base import TrainConfig
from ..models.lm import (LMDef, lm_decode_step, lm_forward, lm_lambda_update,
                         lm_prior_loss)
from ..numerics import (NumericsPolicy, fake_quant,
                        per_tensor_max_scale_log2)
from ..optim import (AdamState, adam_update, clip_by_global_norm, init_adam,
                     lr_at)
from ..sharding import ShardPlan


class TrainState(NamedTuple):
    params: Any
    opt: AdamState
    step: jax.Array
    residual: Any = None     # grad-compression error feedback (optional)
    scales: Any = None       # NumericsPolicy managed scale-state tree
                             # ({site: ScaleState}, optional)


def init_train_state(params, tcfg: TrainConfig,
                     policy: NumericsPolicy | None = None) -> TrainState:
    residual = None
    if tcfg.grad_compress:
        residual = tuple(
            jnp.zeros(p.shape, jnp.float32)
            if jnp.issubdtype(p.dtype, jnp.floating) else None
            for p in jax.tree_util.tree_leaves(params))
    scales = None
    if policy is not None and policy.enable:
        scales = policy.init_scales()
    return TrainState(params, init_adam(params, tcfg),
                      jnp.zeros((), jnp.int32), residual, scales)


def train_state_sites(state: TrainState) -> dict[str, dict]:
    """Byte accounting of one concrete TrainState, keyed by ``obs.ledger``
    site: params, int8 Adam moments, grad-wire error-feedback residual,
    managed scale state.  Host-side only (reads ``.nbytes`` off concrete
    arrays — never call inside a jitted body).

    The fp32 shadow here is elementwise — what the *same tensors* would
    cost in f32.  The paper's Table-1 dense baseline (dense weights vs TT
    factors) is a modelling choice the benches supply per-site instead."""
    from ..optim.adam import moment_nbytes
    from ..optim.grad_compress import residual_nbytes
    p_res = p_fp32 = 0
    for leaf in jax.tree_util.tree_leaves(state.params):
        p_res += int(leaf.nbytes)
        p_fp32 += 4 * int(leaf.size)
    m_res, m_fp32 = moment_nbytes(state.opt)
    out = {
        "params": {"bytes": p_res, "fp32_bytes": p_fp32},
        "optimizer_moment": {"bytes": m_res, "fp32_bytes": m_fp32},
    }
    r = residual_nbytes(state.residual)
    if r:
        out["grad_residual"] = {"bytes": r, "fp32_bytes": r}
    if state.scales is not None:
        s = sum(int(l.nbytes)
                for l in jax.tree_util.tree_leaves(state.scales))
        out["scale_state"] = {"bytes": s, "fp32_bytes": s}
    return out


def _quantize_grad_edge(grads, scales, policy: NumericsPolicy):
    """The ``grad_edge`` site at the step level: round the weight-gradient
    tree onto the grad_bits pow-2 grid (paper: 16-bit gradients).

    Each gradient leaf is its own tensor-site, so each gets a
    per-tensor-max scale — the grid always covers max|g| and rounding is
    clip-free (a pooled scale would persistently clip large-magnitude
    leaves such as embedding/norm grads). The policy's managed
    ``grad_edge`` ScaleState still advances on the tree-wide magnitude:
    it is the §3.3 statistic the activation-gradient edges
    (``core.quant.quant_edge``) share."""
    if scales is None or "grad_edge" not in scales:
        return grads, scales
    spec = policy.spec_for("grad_edge")

    def is_f(g):
        return hasattr(g, "dtype") and g.dtype != jax.dtypes.float0 \
            and jnp.issubdtype(g.dtype, jnp.floating)

    def q(g):
        if not is_f(g):
            return g
        step = per_tensor_max_scale_log2(g, spec)
        return fake_quant(g, spec, step)

    gq = jax.tree.map(q, grads)
    leaves = [g for g in jax.tree_util.tree_leaves(grads) if is_f(g)]
    tot = sum(jnp.sum(jnp.abs(g.astype(jnp.float32))) for g in leaves)
    cnt = sum(g.size for g in leaves)
    gm = (tot / jnp.maximum(cnt, 1))[None]
    return gq, policy.update_scales(scales, {"grad_edge": gm})


def _train_health(grads, scales, policy: NumericsPolicy) -> dict:
    """Per-site quant-health aggregates of one train step (repro.obs).

    Traced only when ``policy.health`` is on — the default step's jaxpr is
    byte-identical to a health-free build (Python gate, no dead device
    code). ``grads`` is the tree entering the grad_edge quantizer:
    ``sat_fraction`` counts codes pinned at the 16-bit grid edge under the
    per-tensor-max scales the quantizer itself uses (clip-free by
    construction, so saturation here means values AT max|g|). Managed-site
    ScaleStates report their §3.3 statistic and whether it sits inside the
    policy's target band."""
    from ..obs.counters import fraction, tree_sat_stats
    sat, tot = tree_sat_stats(grads, policy.spec_for("grad_edge"))
    health = {"grad_edge": {"sat_fraction": fraction(sat, tot),
                            "saturated": sat, "total": tot}}
    for site, st in scales.items():
        health.setdefault(site, {})
        health[site]["scale_log2"] = st.log2.astype(jnp.float32)
        health[site]["mean_abs"] = st.mean_abs
        health[site]["in_band"] = jnp.asarray(
            (st.mean_abs >= policy.target_lo)
            & (st.mean_abs <= policy.target_hi), jnp.float32)
    return health


def _ce_loss(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Mean CE over positions with label >= 0."""
    logits = logits.astype(jnp.float32)
    mask = (labels >= 0).astype(jnp.float32)
    lab = jnp.maximum(labels, 0)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, lab[..., None], axis=-1)[..., 0]
    ce = (logz - gold) * mask
    return jnp.sum(ce) / jnp.maximum(jnp.sum(mask), 1.0)


def make_loss_fn(lm: LMDef, plan: ShardPlan, tcfg: TrainConfig,
                 batch_shards: int = 1):
    """Loss over one batch. ``loss_fn(params, batch, scales=None)``: with a
    managed scale-state tree (``TrainState.scales``) the forward runs the
    policy's ``activation`` quant edges and the aux output carries the
    observed activation statistic alongside the metrics:
    ``loss, (metrics, obs) = loss_fn(...)``.

    ``batch_shards``: how many replicas each see one shard of the global
    batch (the dp step). The prior is scaled per token of the GLOBAL batch,
    so it is the same on every replica and matches the one-device loss."""
    cfg = lm.cfg

    def loss_fn(params, batch, scales=None):
        kwargs = {}
        if cfg.frontend == "audio":
            kwargs["embeds"] = batch["frames"]
        elif cfg.frontend == "vision":
            kwargs["embeds"] = batch["patches"]
            kwargs["tokens"] = batch["tokens"]
        else:
            kwargs["tokens"] = batch["tokens"]
        if scales is not None:
            logits, aux, _, obs = lm_forward(params, lm, plan,
                                             scales=scales, **kwargs)
        else:
            logits, aux, _ = lm_forward(params, lm, plan, **kwargs)
            obs = {}
        labels = batch["labels"]
        if cfg.frontend == "vision":
            # loss on the text positions only (the last len(labels) positions)
            logits = logits[:, -labels.shape[1]:]
        ce = _ce_loss(logits, labels)
        loss = ce + cfg.moe.router_aux_coef * aux
        prior = jnp.zeros((), jnp.float32)
        if cfg.tt.enable and cfg.tt.rank_adapt:
            # Eq. (1): CE mean + prior; prior scaled per-token so its
            # gradient pressure is batch-size independent.
            denom = (float(labels.shape[0] * labels.shape[1] * batch_shards)
                     * tcfg.total_steps)
            prior = lm_prior_loss(params, lm) / denom
        metrics = {"ce": ce, "aux": aux, "prior": prior}
        return loss + prior, (metrics, obs)

    return loss_fn


def make_train_step(lm: LMDef, plan: ShardPlan, tcfg: TrainConfig):
    loss_fn = make_loss_fn(lm, plan, tcfg)
    policy = lm.cfg.quant.policy()

    def train_step(state: TrainState, batch):
        (loss, (metrics, obs)), grads = jax.value_and_grad(
            loss_fn, has_aux=True, allow_int=True)(state.params, batch,
                                                   state.scales)
        scales = state.scales
        if scales is not None and obs:
            # §3.3 activation scale manager: advance on the forward's
            # observed mean |activation| (lm_forward's ``activation`` edges)
            scales = policy.update_scales(scales, obs)
        residual = state.residual
        if tcfg.grad_compress:
            # int8-valued grads + error feedback BEFORE the DP reduce:
            # the all-reduce then moves 1/4 the wire bytes — the ``dp_wire``
            # site of the numerics policy (optim/grad_compress); on real
            # meshes ``grad_compress.psum_int8`` is the shard_map collective
            # that puts the int8 codes themselves on the wire
            from ..optim.grad_compress import compress_decompress
            grads, residual = compress_decompress(
                grads, residual, policy.spec_for("dp_wire"))
        # pre-quant grads held only when health tracing is on (Python gate:
        # the default step's jaxpr carries no health ops at all)
        want_health = policy.health and policy.enable and scales is not None
        pre_edge = grads if want_health else None
        grads, scales = _quantize_grad_edge(grads, scales, policy)
        if tcfg.grad_clip > 0:
            grads, gnorm = clip_by_global_norm(grads, tcfg.grad_clip)
        else:
            gnorm = jnp.zeros((), jnp.float32)
        lr = lr_at(state.step, tcfg)
        params, opt = adam_update(state.params, grads, state.opt, lr, tcfg)
        # closed-form Eq.(4) rank-hyperparameter update (no-op if TT off)
        params = lm_lambda_update(params, lm)
        metrics = dict(metrics, loss=loss, gnorm=gnorm, lr=lr)
        if want_health:
            metrics["health"] = _train_health(pre_edge, scales, policy)
        return TrainState(params, opt, state.step + 1, residual,
                          scales), metrics

    return train_step


def init_dp_train_state(params, tcfg: TrainConfig, plan: ShardPlan,
                        policy: NumericsPolicy | None = None) -> TrainState:
    """TrainState for ``make_dp_train_step``: residual leaves carry a
    leading ``(dp_size,)`` replica axis — each data-parallel replica keeps
    its own error-feedback residual (it quantized its own local gradient),
    while params/opt/scales stay replicated (the wire's summed codes are
    bitwise identical on every replica)."""
    st = init_train_state(params, tcfg, policy)
    if st.residual is not None:
        n = plan.dp_size()
        st = st._replace(residual=tuple(
            None if r is None else jnp.zeros((n,) + r.shape, r.dtype)
            for r in st.residual))
    return st


def make_dp_train_step(lm: LMDef, plan: ShardPlan, tcfg: TrainConfig):
    """Data-parallel ``shard_map`` train step whose ONLY payload-sized
    collective is the int8 gradient wire (``optim.grad_compress.psum_int8``,
    PR 5) — the explicit-collective realization of the paper's low-precision
    training story at the cluster level.

    The plan's mesh must be dp-only (every axis in ``plan.dp_axes`` — e.g.
    the 1-D ``("data",)`` mesh): inside the body each replica holds the full
    (replicated) params and its batch shard, runs the mesh-less forward/
    backward locally, and reduces gradients through ``psum_int8_tree`` —
    blockwise pmax scales (payload/1024 f32 elements) + int8 codes on an
    ``all_gather``, summed in a widened int32 accumulator. Everything after
    the wire (grad_edge quantizer, clipping, adam, lambda update) is local
    arithmetic on bitwise-replicated values, so no f32 gradient, parameter,
    or optimizer tensor ever crosses a collective; the only other
    collectives are scalar ``pmean``s of loss/metrics/activation stats.
    tests/test_distributed.py walks the jaxpr and asserts exactly this.

    State convention: ``init_dp_train_state`` (residual leaves lead with a
    ``(dp_size,)`` replica axis, sharded over the dp axes; everything else
    replicated). Batch leaves shard their leading (batch) dim.

    Numerics contract vs ``make_train_step`` (the mesh-less path): identical
    forward/backward math; the wire replaces ``compress_decompress`` — same
    blockwise int8 grid, with the block scale chosen by cross-replica pmax
    instead of locally, i.e. exactly the PR 5 ``psum_int8`` semantics the
    ``wire`` test pins bitwise.
    """
    if plan.mesh is None:
        raise ValueError("make_dp_train_step needs a plan with a real mesh")
    extra = [a for a in plan.mesh.shape if a not in plan.dp_axes]
    if extra:
        raise ValueError(
            f"make_dp_train_step is dp-only: mesh axes {extra} are not in "
            f"dp_axes {plan.dp_axes} (use make_train_step's GSPMD path for "
            f"tensor/context parallelism)")
    if not tcfg.grad_compress:
        raise ValueError("the dp shard_map step IS the int8 wire — "
                         "enable tcfg.grad_compress")
    from ..optim.grad_compress import psum_int8_tree
    from ..sharding import shard_map
    # the body sees per-replica local shards: the model runs mesh-less
    # (a with_sharding_constraint cannot reference manual mesh axes)
    axis = plan.dp_axis()
    ndev = plan.dp_size()
    loss_fn = make_loss_fn(lm, ShardPlan(mesh=None), tcfg, batch_shards=ndev)
    policy = lm.cfg.quant.policy()
    wire_spec = policy.spec_for("dp_wire")

    def is_f(g):
        return hasattr(g, "dtype") and g.dtype != jax.dtypes.float0 \
            and jnp.issubdtype(g.dtype, jnp.floating)

    def local_step(state: TrainState, batch):
        res_local = None if state.residual is None else tuple(
            None if r is None else r[0] for r in state.residual)
        (loss, (metrics, obs)), grads = jax.value_and_grad(
            loss_fn, has_aux=True, allow_int=True)(state.params, batch,
                                                   state.scales)
        # scalar cross-replica means — bytes on the wire: a handful of f32s
        loss = jax.lax.pmean(loss, axis)
        metrics = jax.tree.map(lambda m: jax.lax.pmean(m, axis), metrics)
        scales = state.scales
        if scales is not None and obs:
            obs = jax.tree.map(lambda o: jax.lax.pmean(o, axis), obs)
            scales = policy.update_scales(scales, obs)
        # THE payload collective: int8 codes + pmax block scales
        summed, new_res = psum_int8_tree(grads, res_local, axis, wire_spec)
        grads = jax.tree.map(lambda g: g / ndev if is_f(g) else g, summed)
        want_health = policy.health and policy.enable and scales is not None
        pre_edge = grads if want_health else None
        grads, scales = _quantize_grad_edge(grads, scales, policy)
        if tcfg.grad_clip > 0:
            grads, gnorm = clip_by_global_norm(grads, tcfg.grad_clip)
        else:
            gnorm = jnp.zeros((), jnp.float32)
        lr = lr_at(state.step, tcfg)
        params, opt = adam_update(state.params, grads, state.opt, lr, tcfg)
        params = lm_lambda_update(params, lm)
        metrics = dict(metrics, loss=loss, gnorm=gnorm, lr=lr)
        if want_health:
            metrics["health"] = _train_health(pre_edge, scales, policy)
        residual = None if new_res is None else tuple(
            None if r is None else r[None] for r in new_res)
        return TrainState(params, opt, state.step + 1, residual,
                          scales), metrics

    from jax.sharding import PartitionSpec as P
    dp = P(plan.dp_axes)
    state_specs = TrainState(params=P(), opt=P(), step=P(),
                             residual=dp, scales=P())

    def train_step(state: TrainState, batch):
        batch_specs = jax.tree.map(
            lambda b: P(plan.dp_axes, *([None] * (jnp.ndim(b) - 1))), batch)
        f = shard_map(local_step, plan.mesh,
                      in_specs=(state_specs, batch_specs),
                      out_specs=(state_specs, P()))
        return f(state, batch)

    return train_step


def make_grad_accum_train_step(lm: LMDef, plan: ShardPlan, tcfg: TrainConfig,
                               n_micro: int):
    """Gradient-accumulation variant: batch leading dim = n_micro.

    Numerics contract: identical to ``make_train_step`` after the gradient
    average — compression/error-feedback, the grad_edge quantizer, and
    clipping all apply to the accumulated mean gradient, and the residual /
    scale trees are carried exactly as in the non-accum step (asserted by
    tests/test_numerics.py)."""
    loss_fn = make_loss_fn(lm, plan, tcfg)
    policy = lm.cfg.quant.policy()

    def train_step(state: TrainState, batch):
        def micro(carry, mb):
            gsum, lsum, osum = carry
            (loss, (_, obs)), g = jax.value_and_grad(
                loss_fn, has_aux=True, allow_int=True)(state.params, mb,
                                                       state.scales)
            gsum = jax.tree.map(
                lambda a, b: a + b if hasattr(b, "dtype")
                and b.dtype != jax.dtypes.float0 else a, gsum, g)
            if "activation" in obs:
                osum = osum + obs["activation"]
            return (gsum, lsum + loss, osum), None

        zeros = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32)
            if jnp.issubdtype(p.dtype, jnp.floating) else
            jnp.zeros((), jnp.float32), state.params)
        (gsum, lsum, osum), _ = jax.lax.scan(
            micro, (zeros, jnp.zeros(()), jnp.zeros((1,))), batch)
        grads = jax.tree.map(lambda g: g / n_micro, gsum)
        scales = state.scales
        if scales is not None and "activation" in scales \
                and lm.cfg.quant.enable:
            scales = policy.update_scales(
                scales, {"activation": osum / n_micro})
        residual = state.residual
        if tcfg.grad_compress:
            from ..optim.grad_compress import compress_decompress
            grads, residual = compress_decompress(
                grads, residual, policy.spec_for("dp_wire"))
        want_health = policy.health and policy.enable and scales is not None
        pre_edge = grads if want_health else None
        grads, scales = _quantize_grad_edge(grads, scales, policy)
        if tcfg.grad_clip > 0:
            grads, gnorm = clip_by_global_norm(grads, tcfg.grad_clip)
        else:
            gnorm = jnp.zeros((), jnp.float32)
        lr = lr_at(state.step, tcfg)
        params, opt = adam_update(state.params, grads, state.opt, lr, tcfg)
        params = lm_lambda_update(params, lm)
        metrics = {"loss": lsum / n_micro, "gnorm": gnorm, "lr": lr}
        if want_health:
            metrics["health"] = _train_health(pre_edge, scales, policy)
        return TrainState(params, opt, state.step + 1, residual,
                          scales), metrics

    return train_step


def make_prefill_step(lm: LMDef, plan: ShardPlan):
    cfg = lm.cfg

    def prefill(params, batch):
        kwargs = {}
        if cfg.frontend == "audio":
            kwargs["embeds"] = batch["frames"]
        elif cfg.frontend == "vision":
            kwargs["embeds"] = batch["patches"]
            kwargs["tokens"] = batch["tokens"]
        else:
            kwargs["tokens"] = batch["tokens"]
        logits, _, cache = lm_forward(params, lm, plan, return_cache=True,
                                      **kwargs)
        return logits[:, -1:], cache

    return prefill


def make_serve_step(lm: LMDef, plan: ShardPlan):
    """Decode step. ``cur_len``: scalar shared position, or a per-slot (B,)
    vector — one compiled step then decodes a batch of requests at
    *different* positions (the continuous-batching primitive; the decode
    paths in models/attention.py scatter each row at its own length and
    mask per-row)."""

    def serve_step(params, cache, tokens, cur_len):
        return lm_decode_step(params, cache, tokens,
                              jnp.asarray(cur_len, jnp.int32), lm, plan)

    return serve_step
