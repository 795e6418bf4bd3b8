"""Partition rules: DP / FSDP / TP / EP / SP expressed as one ShardPlan.

Two strategies (selectable per arch config; see DESIGN.md §5):

- ``tp``  — Megatron-style tensor parallelism over the ``model`` axis
            (heads / ffn / vocab / experts / d_inner), batch over
            ``(pod, data)``, FSDP of weights over ``data``.
- ``cp``  — context parallelism: activations sharded over ``model`` on the
            *sequence* dim; weights fully sharded (ZeRO-3) over
            ``(data, model)``. Used for archs whose head count does not
            divide the model axis (yi-34b / llava: 56 heads vs 16).

Decode adds SP: the KV cache / recurrent state is sharded over ``data`` on
the sequence dim when batch < data axis (long_500k, batch=1).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def shard_map(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checks off — one wrapper for every
    explicit-collective site (MoE expert dispatch, the int8 gradient wire,
    the head-sharded page walk)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _div(n: int, mesh: Mesh | None, axis) -> bool:
    """True when dim ``n`` can shard over mesh ``axis``: every named axis
    exists on the mesh (a dp-only 1-D mesh has no ``model`` axis — absent
    axes mean "don't shard", not KeyError) and ``n`` divides evenly."""
    if mesh is None or axis is None:
        return False
    axes = axis if isinstance(axis, tuple) else (axis,)
    if any(a not in mesh.shape for a in axes):
        return False
    size = int(np.prod([mesh.shape[a] for a in axes]))
    return n % size == 0 and n >= size


# Param-leaf names that stay replicated even when >= 2-D (stacking adds a
# leading layer axis to 1-D vectors): biases, norm gains, rwkv6 decay/bonus
# and token-shift mixes, mamba conv/A/D, TT wscales. Projection matrices
# ("w" under q/kv/o/gate/up/down/... sites) are deliberately absent — every
# one of them must receive a non-trivial spec (tests/test_sharding.py audits
# the whole zoo for this).
_REPLICATED_LEAVES = frozenset({
    "b", "bias", "scale", "wscale_log2", "ln_x_scale",
    "w0", "u", "mu_x", "mu_ffn", "A_log", "D", "conv_w", "conv_b",
    "router_bias",
})


@dataclass(frozen=True)
class ShardPlan:
    mesh: Mesh | None = None
    strategy: str = "tp"                  # "tp" | "cp"
    dp_axes: tuple[str, ...] = ("data",)  # ("pod","data") multi-pod
    seq_sharded_cache: bool = False       # long-context decode SP

    # ---- helpers -----------------------------------------------------
    def dp_axis(self) -> str | tuple[str, ...]:
        """Mesh axis name(s) for data-parallel collectives (``lax.psum`` /
        ``all_gather`` inside shard_map — e.g. the int8 gradient wire,
        ``optim.grad_compress.psum_int8``)."""
        return self.dp_axes if len(self.dp_axes) > 1 else self.dp_axes[0]

    def dp_size(self) -> int:
        if self.mesh is None:
            return 1
        n = 1
        for ax in self.dp_axes:
            n *= self.mesh.shape[ax]
        return n

    def ns(self, spec: P) -> NamedSharding | None:
        if self.mesh is None:
            return None
        return NamedSharding(self.mesh, spec)

    def constrain(self, x: jax.Array, spec: P) -> jax.Array:
        if self.mesh is None:
            return x
        return jax.lax.with_sharding_constraint(x, self.ns(spec))

    # ---- activations -------------------------------------------------
    def hidden(self, x: jax.Array) -> jax.Array:
        """(B, S, D) residual stream.

        Both strategies shard the sequence dim over ``model`` between blocks
        (Megatron-LM sequence parallelism): residuals and the remat/scan
        checkpoints shrink 16×, which is what lets train_4k fit HBM. GSPMD
        inserts the all-gather before attention/FFN and the reduce-scatter
        after (same wire volume as the classic TP all-reduce pair)."""
        if _div(x.shape[1], self.mesh, "model"):
            return self.constrain(x, P(self.dp_axes, "model", None))
        return self.constrain(x, P(self.dp_axes, None, None))

    def heads_act(self, x: jax.Array) -> jax.Array:
        """(B, S, H, Dh) attention interior."""
        if self.mesh is None:
            return x
        if self.strategy == "tp" and _div(x.shape[2], self.mesh, "model"):
            return self.constrain(x, P(self.dp_axes, None, "model", None))
        if self.strategy == "cp" and _div(x.shape[1], self.mesh, "model"):
            return self.constrain(x, P(self.dp_axes, "model", None, None))
        return self.constrain(x, P(self.dp_axes, None, None, None))

    def kv_full(self, x: jax.Array) -> jax.Array:
        """KV replicated along seq (cp strategy all-gathers before attention)."""
        if self.mesh is None:
            return x
        if self.strategy == "tp" and _div(x.shape[2], self.mesh, "model"):
            return self.constrain(x, P(self.dp_axes, None, "model", None))
        return self.constrain(x, P(self.dp_axes, None, None, None))

    def ffn_act(self, x: jax.Array) -> jax.Array:
        """(B, S, F)"""
        if self.mesh is None:
            return x
        if self.strategy == "tp" and _div(x.shape[-1], self.mesh, "model"):
            return self.constrain(x, P(self.dp_axes, None, "model"))
        if self.strategy == "cp" and _div(x.shape[1], self.mesh, "model"):
            return self.constrain(x, P(self.dp_axes, "model", None))
        return self.constrain(x, P(self.dp_axes, None, None))

    def logits(self, x: jax.Array) -> jax.Array:
        """(B, S, V)"""
        if self.mesh is None:
            return x
        if _div(x.shape[-1], self.mesh, "model"):
            return self.constrain(x, P(self.dp_axes, None, "model"))
        return self.constrain(x, P(self.dp_axes, None, None))

    def cache_kv(self, x: jax.Array) -> jax.Array:
        """(B, T, H, Dh) or (B, T, L) decode caches."""
        if self.mesh is None:
            return x
        if self.seq_sharded_cache and _div(x.shape[1], self.mesh, "data"):
            rest = (None,) * (x.ndim - 2)
            return self.constrain(x, P(None, "data", *rest))
        if x.ndim >= 3 and self.strategy == "tp" \
                and _div(x.shape[2], self.mesh, "model"):
            rest = (None,) * (x.ndim - 3)
            return self.constrain(x, P(self.dp_axes, None, "model", *rest))
        rest = (None,) * (x.ndim - 1)
        return self.constrain(x, P(self.dp_axes, *rest))

    # ---- serving pools -------------------------------------------------
    def model_size(self) -> int:
        if self.mesh is None or "model" not in self.mesh.shape:
            return 1
        return int(self.mesh.shape["model"])

    def shards_kv_heads(self, hkv: int) -> bool:
        """True when the paged pool's KV-head axis is sharded over ``model``
        — the condition under which the fused page walk runs per-device on
        its local heads (query heads group contiguously per KV head, so a
        head-shard of q attends exactly to its own head-shard of pages)."""
        return self.strategy == "tp" and _div(hkv, self.mesh, "model")

    def kv_page_spec(self, shape: tuple[int, ...]) -> P:
        """One KV-pool data leaf (L, P+1, page, *feat): GQA leaves
        (..., Hkv, Dh) shard the KV-head axis over ``model``; MLA latent
        leaves (..., latent) and non-divisible head counts replicate. The
        page axis is never sharded — COW forks (``kv_cache.fork_page``) and
        trash-page scatters address whole pages and stay shard-local."""
        dims = [None] * len(shape)
        if len(shape) == 5 and self.shards_kv_heads(shape[3]):
            dims[3] = "model"
        return P(*dims)

    def state_spec(self, name: str, shape: tuple[int, ...]) -> P:
        """One state-pool data leaf (L, num_slots, *feat): the feature axis
        carrying d_inner / heads shards over ``model`` — mamba ``conv``
        (..., d_inner) and ``h`` (..., d_inner, d_state); rwkv6 ``shift``
        (..., 1, d_model) and ``wkv`` (..., H, hd, hd)."""
        dims = [None] * len(shape)
        if self.strategy != "tp" or len(shape) < 3:
            return P(*dims)
        ax = 2 if name in ("h", "wkv") else len(shape) - 1
        if _div(shape[ax], self.mesh, "model"):
            dims[ax] = "model"
        return P(*dims)

    def kv_pool_pspec(self, pool) -> Any:
        """PartitionSpec tree for a ``serve/kv_cache.py`` pool: data leaves
        by ``kv_page_spec``; per-(layer, slot) scale rows replicated (every
        head shard decodes its codes under the same pow-2 grid)."""
        return {"data": jax.tree.map(lambda a: self.kv_page_spec(a.shape),
                                     pool["data"]),
                "scale_log2": jax.tree.map(lambda a: P(*([None] * a.ndim)),
                                           pool["scale_log2"])}

    def state_pool_pspec(self, pool) -> Any:
        """PartitionSpec tree for a ``serve/state_cache.py`` pool."""
        def leaf(path, a):
            name = str(getattr(path[-1], "key", path[-1]))
            return self.state_spec(name, a.shape)

        return {"data": jax.tree_util.tree_map_with_path(leaf, pool["data"]),
                "scale_log2": jax.tree.map(lambda a: P(*([None] * a.ndim)),
                                           pool["scale_log2"])}

    def kv_pool_sharding(self, pool) -> Any:
        return jax.tree.map(self.ns, self.kv_pool_pspec(pool),
                            is_leaf=lambda s: isinstance(s, P))

    def state_pool_sharding(self, pool) -> Any:
        return jax.tree.map(self.ns, self.state_pool_pspec(pool),
                            is_leaf=lambda s: isinstance(s, P))

    # ---- parameters ---------------------------------------------------
    def param_spec(self, path: str, shape: tuple[int, ...]) -> P:
        """PartitionSpec for one param leaf, identified by its tree path."""
        if self.mesh is None:
            return P()
        # stacked layer/period/expert leading axes are never sharded except
        # the explicit expert axis handled below.
        n_lead = 0
        parts = path.split("/")
        name = parts[-1]
        is_expert = any(p in ("gate", "up", "down") for p in parts) and \
            "moe" in parts
        is_stacked = "layers" in parts
        if len(shape) < 2:
            return P()
        # TT cores / lambdas / norms / small vectors: replicated. Exact
        # names (not prefixes): a prefix match would silently replicate any
        # future >= 2-D leaf that happens to share a first letter ("up" vs
        # "u", "beta" vs "b", "damp" vs "D"). Only the genuinely numbered
        # TT families (core_N / lambda_N) match by prefix.
        if name in _REPLICATED_LEAVES or name.startswith(("core_", "lambda_")):
            return P()

        dims: list[Any] = [None] * len(shape)
        body = shape
        lead = 0
        if is_stacked:
            lead += 1
        if is_expert:
            # (..., E, in, out): expert axis sharded over model
            if _div(shape[lead], self.mesh, "model"):
                dims[lead] = "model"
            eff = shape[lead + 1:]
            if len(eff) == 2:
                if self.strategy == "tp":
                    if _div(eff[0], self.mesh, "data"):
                        dims[lead + 1] = "data"
                else:
                    if _div(eff[0], self.mesh, "data"):
                        dims[lead + 1] = "data"
            return P(*dims)
        body = shape[lead:]
        if len(body) != 2:
            return P(*dims)
        din, dout = body
        if self.strategy == "cp":
            # ZeRO-3: fully shard the larger dim over (data, model)
            if _div(din, self.mesh, ("data", "model")) and din >= dout:
                dims[lead] = ("data", "model")
            elif _div(dout, self.mesh, ("data", "model")):
                dims[lead + 1] = ("data", "model")
            elif _div(din, self.mesh, "data"):
                dims[lead] = "data"
            return P(*dims)
        # tp: decide which dim is the "parallel" one by site name
        out_parallel = any(k in parts for k in
                           ("q", "kv", "gate", "up", "in_proj", "dt_proj",
                            "head", "r", "k", "v", "g", "ffn_k", "ffn_r",
                            "x_proj", "q_up", "k_up", "v_up", "q_down",
                            "kv_down", "router"))
        in_parallel = any(k in parts for k in
                          ("o", "down", "out_proj", "ffn_v"))
        if "embed" in parts:
            # (V, D): vocab over model, D over data (fsdp)
            if _div(din, self.mesh, "model"):
                dims[lead] = "model"
            if _div(dout, self.mesh, "data"):
                dims[lead + 1] = "data"
            return P(*dims)
        if out_parallel and _div(dout, self.mesh, "model"):
            dims[lead + 1] = "model"
            if _div(din, self.mesh, "data"):
                dims[lead] = "data"
        elif in_parallel and _div(din, self.mesh, "model"):
            dims[lead] = "model"
            if _div(dout, self.mesh, "data"):
                dims[lead + 1] = "data"
        else:
            # fallback FSDP over data on the larger divisible dim
            if _div(din, self.mesh, "data") and din >= dout:
                dims[lead] = "data"
            elif _div(dout, self.mesh, "data"):
                dims[lead + 1] = "data"
        return P(*dims)

    def params_pspec_tree(self, params) -> Any:
        """PartitionSpec tree matching a params pytree. A single
        ``tree_map_with_path`` pass: each leaf's spec is computed in place
        from its own path, so distinct paths can never collide (the previous
        implementation keyed a dict by "/"-joined path strings and rebuilt
        the tree from it — two paths stringifying identically silently
        overwrote each other's spec)."""
        def spec(path, leaf):
            key = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                           for p in path)
            return self.param_spec(key, leaf.shape)

        return jax.tree_util.tree_map_with_path(spec, params)

    def params_sharding_tree(self, params) -> Any:
        spec_tree = self.params_pspec_tree(params)
        return jax.tree.map(lambda s: self.ns(s), spec_tree,
                            is_leaf=lambda s: isinstance(s, P))


def make_plan(mesh: Mesh | None, strategy: str = "tp",
              multi_pod: bool = False,
              seq_sharded_cache: bool = False) -> ShardPlan:
    dp = ("pod", "data") if multi_pod else ("data",)
    return ShardPlan(mesh=mesh, strategy=strategy, dp_axes=dp,
                     seq_sharded_cache=seq_sharded_cache)
