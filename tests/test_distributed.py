"""Distributed-numerics equivalence on a forced 8-device CPU mesh.

Each case runs in a subprocess (the device count must be set before jax
initializes) and asserts that the sharded computation matches the
single-device reference: TP, CP, EP (shard_map MoE), the sharded train
step, the int8 gradient wire, and the dp-only wire step on a TT model with
the rank prior.
"""
import os
import subprocess
import sys
from pathlib import Path

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.configs.base import ModelConfig, MoEConfig, TrainConfig
from repro.models import build_lm, init_lm, lm_forward
from repro.models import moe as M
from repro.sharding import ShardPlan, make_plan
from repro.launch.mesh import make_mesh
from repro.launch.steps import init_train_state, make_train_step
from jax.sharding import NamedSharding, PartitionSpec as P

CASE = "%s"
mesh = make_mesh((4, 2), ("data", "model"))

if CASE in ("tp", "cp"):
    cfg = ModelConfig(name="t", num_layers=2, d_model=64, num_heads=4,
                      num_kv_heads=2, d_ff=128, vocab_size=96,
                      remat="none", dtype="float32")
    lm = build_lm(cfg)
    params = init_lm(jax.random.PRNGKey(0), lm)
    toks = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, 96)
    ref, _, _ = lm_forward(params, lm, ShardPlan(mesh=None), tokens=toks)
    plan = make_plan(mesh, CASE)
    f = jax.jit(lambda p, t: lm_forward(p, lm, plan, tokens=t)[0])
    out = f(params, toks)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)
    print("OK", CASE)

elif CASE == "ep":
    cfg = ModelConfig(name="m", d_model=32, d_ff=64, dtype="float32",
                      moe=MoEConfig(num_experts=8, top_k=2,
                                    capacity_factor=8.0))
    mdef = M.make_moe(cfg)
    params = M.init_moe(jax.random.PRNGKey(0), mdef, cfg)
    x = jax.random.normal(jax.random.PRNGKey(2), (8, 16, 32))
    ref, _ = M.moe_forward(params, x, mdef, cfg)
    f = jax.jit(lambda p, x: M.moe_forward(p, x, mdef, cfg, mesh=mesh,
                                           dp_axes=("data",))[0])
    out = f(params, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)
    print("OK ep")

elif CASE == "wire":
    # psum_int8 under a 2-device dp mesh: (a) the reduced gradient matches
    # the single-device grad_compress semantics (shared pmax block scale,
    # codes summed in a widened int32 accumulator, decoded once), (b) the
    # device-local error-feedback residual is preserved, (c) the ONLY
    # payload-sized collective operand is int8 — the dp_wire bytes really
    # are int8 on the wire.
    from jax.sharding import Mesh
    from repro.optim.grad_compress import WIRE_SPEC, psum_int8_tree
    from repro.numerics.codecs import blockwise_geometry
    from repro.sharding import ShardPlan, shard_map

    plan = ShardPlan(mesh=None, dp_axes=("data",))
    assert plan.dp_axis() == "data" and ShardPlan(
        mesh=None, dp_axes=("pod", "data")).dp_axis() == ("pod", "data")

    mesh2 = Mesh(np.array(jax.devices()[:2]), ("data",))
    ndev = 2
    shapes = [(1500,), (7, 129), ()]
    key = jax.random.PRNGKey(0)
    gs = {f"g{i}": jax.random.normal(jax.random.fold_in(key, i),
                                     (ndev,) + s) * (i + 1)
          for i, s in enumerate(shapes)}
    rs = {f"g{i}": 0.01 * jax.random.normal(jax.random.fold_in(key, 100 + i),
                                            (ndev,) + s)
          for i, s in enumerate(shapes)}

    def local(g, r):
        g1 = jax.tree.map(lambda a: a[0], g)
        r1 = jax.tree.map(lambda a: a[0], r)
        out, nr = psum_int8_tree(g1, tuple(jax.tree_util.tree_leaves(r1)),
                                 "data", WIRE_SPEC)
        nr_tree = jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(g1), list(nr))
        return out, jax.tree.map(lambda a: a[None], nr_tree)

    f = shard_map(local, mesh2, in_specs=(P("data"), P("data")),
                         out_specs=(P(), P("data")))
    out, nres = jax.jit(f)(gs, rs)

    # single-device oracle: the SAME per-shard quantize + widened code sum,
    # written as plain jnp over the stacked per-device axis — no mesh, no
    # collectives. The shard_map path must match it BITWISE: the int8 wire
    # changes where the bytes travel, not the values.
    @jax.jit
    def ref_leaf(gd, rd):                   # (ndev, *s) each
        flat = (gd.astype(jnp.float32) + rd).reshape(ndev, -1)
        n = flat.shape[1]
        b, nb, pad = blockwise_geometry(WIRE_SPEC, n)
        blocks = jnp.pad(flat, ((0, 0), (0, pad))).reshape(ndev, nb, b)
        sc = jnp.max(jnp.abs(blocks), axis=-1) / WIRE_SPEC.qmax
        sc = jnp.maximum(jnp.max(sc, axis=0), 1e-20)    # shared (pmax) scale
        codes = jnp.clip(jnp.round(blocks / sc[None, :, None]), -127, 127)
        total = jnp.sum(codes.astype(jnp.int32), axis=0)  # widened accum
        summed = (total.astype(jnp.float32) * sc[:, None]).reshape(-1)[:n]
        res = (blocks - codes * sc[None, :, None]).reshape(ndev, -1)[:, :n]
        return summed.reshape(gd.shape[1:]), res.reshape(gd.shape)

    for name, s in zip(sorted(gs), shapes):
        ref_sum, ref_res = ref_leaf(gs[name], rs[name])
        np.testing.assert_array_equal(np.asarray(out[name]),
                                      np.asarray(ref_sum))
        np.testing.assert_allclose(np.asarray(nres[name]),
                                   np.asarray(ref_res), atol=1e-6)
        # and the sum is the real gradient sum within quantization error
        exact = np.asarray(gs[name] + rs[name]).sum(0)
        tol = 2 * ndev * max(np.abs(np.asarray(gs[name])).max() / 127, 1e-6)
        np.testing.assert_allclose(np.asarray(out[name]), exact, atol=tol)

    # wire dtype: walk the jaxpr (incl. the shard_map body) — every
    # all_gather operand must be int8
    jaxpr = jax.make_jaxpr(f)(gs, rs)

    def walk(jx, found):
        for eqn in jx.eqns:
            if "all_gather" in eqn.primitive.name:
                found.append(eqn.invars[0].aval.dtype)
            for v in eqn.params.values():
                inner = getattr(v, "jaxpr", v)
                if hasattr(inner, "eqns"):
                    walk(inner, found)
        return found

    gathers = walk(jaxpr.jaxpr, [])
    assert gathers and all(d == jnp.dtype(jnp.int8) for d in gathers), gathers
    print("OK wire", len(gathers))

elif CASE == "train":
    cfg = ModelConfig(name="t", num_layers=2, d_model=64, num_heads=4,
                      num_kv_heads=2, d_ff=128, vocab_size=96,
                      remat="full", dtype="float32")
    lm = build_lm(cfg)
    tcfg = TrainConfig(total_steps=5, warmup_steps=1, grad_clip=1.0)
    params = init_lm(jax.random.PRNGKey(0), lm)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, 96),
             "labels": jax.random.randint(jax.random.PRNGKey(2), (8, 16), 0, 96)}
    # reference single-device
    s0 = init_train_state(params, tcfg)
    _, m_ref = make_train_step(lm, ShardPlan(mesh=None), tcfg)(s0, batch)
    # sharded
    plan = make_plan(mesh, "tp")
    pspec = plan.params_pspec_tree(params)
    pshard = jax.tree.map(lambda s: NamedSharding(mesh, s), pspec,
                          is_leaf=lambda s: isinstance(s, P))
    params_sh = jax.device_put(params, pshard)
    s1 = init_train_state(params_sh, tcfg)
    step = jax.jit(make_train_step(lm, plan, tcfg))
    s1, m_sh = step(s1, batch)
    np.testing.assert_allclose(float(m_sh["loss"]), float(m_ref["loss"]),
                               rtol=2e-3)
    print("OK train", float(m_sh["loss"]))

elif CASE == "dp_tt":
    # the dp-only int8-wire step on a TT model with the rank prior: each
    # replica sees 1/8 of the batch, but the prior is per token of the
    # global batch, so the step-1 loss (pre-update) is the one-device loss
    from repro.configs.base import TTConfig
    from repro.launch.mesh import make_dp_mesh
    from repro.launch.steps import init_dp_train_state, make_dp_train_step
    cfg = ModelConfig(name="t", num_layers=2, d_model=32, num_heads=2,
                      num_kv_heads=2, d_ff=64, vocab_size=64, remat="none",
                      dtype="float32",
                      tt=TTConfig(enable=True, d=3, max_rank=4,
                                  min_elements=1024))
    lm = build_lm(cfg)
    tcfg = TrainConfig(total_steps=4, warmup_steps=1, grad_compress=True)
    params = init_lm(jax.random.PRNGKey(0), lm)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, 64),
             "labels": jax.random.randint(jax.random.PRNGKey(2), (8, 16), 0, 64)}
    _, m_ref = jax.jit(make_train_step(lm, ShardPlan(mesh=None), tcfg))(
        init_train_state(params, tcfg), batch)
    plan = make_plan(make_dp_mesh(8), "tp")
    _, m_dp = jax.jit(make_dp_train_step(lm, plan, tcfg))(
        init_dp_train_state(params, tcfg, plan), batch)
    # a per-replica prior would add 7x this to the dp loss
    assert float(m_ref["prior"]) > 0.1 * float(m_ref["ce"]), m_ref
    np.testing.assert_allclose(float(m_dp["prior"]), float(m_ref["prior"]),
                               rtol=1e-6)
    np.testing.assert_allclose(float(m_dp["loss"]), float(m_ref["loss"]),
                               rtol=1e-5)
    print("OK dp_tt", float(m_dp["loss"]))
"""


@pytest.mark.parametrize("case", ["tp", "cp", "ep", "train", "wire",
                                  "dp_tt"])
def test_sharded_equivalence(case):
    r = subprocess.run(
        [sys.executable, "-c", SCRIPT % case],
        capture_output=True, text=True, timeout=600,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "HOME": os.environ.get("HOME", ""),
             # pin the platform: the forced 8-device host mesh is a CPU
             # construct, and without this a libtpu install spins on TPU
             # metadata discovery inside the cleared env
             "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS", "cpu")},
        cwd=Path(__file__).resolve().parents[1])
    assert r.returncode == 0, f"stdout={r.stdout}\nstderr={r.stderr[-3000:]}"
    assert f"OK" in r.stdout
