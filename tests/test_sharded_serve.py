"""Multi-device serving + dp-only training on a forced 8-device CPU mesh.

Each case runs in a subprocess (device count must be set before jax
initializes) and asserts the tentpole contract: the sharded path is
TOKEN-IDENTICAL (serving) / loss-identical on step one (training) to the
mesh-less reference:

(a) engine decode, gather + fused paged-attention, on a TP mesh that shards
    the paged KV pool over KV heads — attention arch (1x8) and the jamba
    hybrid (4x2, state pool sharded over d_inner, MoE over the model axis),
(b) prefix-cache admission + COW forks on head-sharded pages, and the
    TP engine's compiled decode step updating the head-sharded pool in
    place (no pool-sized copy, slice or update; temporaries under one
    pool leaf's shard),
(c) the dp-only shard_map train step: step-1 loss bitwise vs the mesh-less
    step, and a jaxpr walk proving the int8 gradient wire is the ONLY
    payload-sized collective in the step.
"""
import os
import subprocess
import sys
from pathlib import Path

import pytest

pytestmark = pytest.mark.slow

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
import repro.configs as C
from repro.models import build_lm, init_lm
from repro.serve import Engine, EngineConfig, PoolConfig
from repro.launch.mesh import make_mesh
from repro.sharding import ShardPlan, make_plan

CASE = "%s"
assert len(jax.devices()) == 8


def setup(arch, **over):
    cfg = C.get_reduced(arch).replace(dtype="float32", remat="none", **over)
    lm = build_lm(cfg)
    return cfg, lm, init_lm(jax.random.PRNGKey(0), lm)


def prompts_for(cfg, n=4, lo=8, hi=16, seed=3):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, cfg.vocab_size,
                        int(rng.randint(lo, hi + 1))).tolist()
            for _ in range(n)]


def run_engine(lm, params, plan, prompts, pcfg, gen=12, **ecfg_kw):
    eng = Engine(lm, params, EngineConfig(pool=pcfg, **ecfg_kw), plan)
    rids = [eng.submit(p, max_new_tokens=gen) for p in prompts]
    res = eng.run()
    return [res[r].tokens for r in rids], eng.summary()


if CASE in ("engine_attn", "engine_jamba"):
    if CASE == "engine_attn":
        # 8 KV heads on a (1, 8) mesh: one KV head (2 query heads) per device
        cfg, lm, params = setup("internlm2-1.8b", d_model=256, num_heads=16,
                                num_kv_heads=8, d_ff=160)
        mesh = make_mesh((1, 8), ("data", "model"))
    else:
        # hybrid: attn KV heads (2) and mamba d_inner (128) shard over
        # model=2; the 4-expert MoE rides the same mesh. All 8 devices used.
        cfg, lm, params = setup("jamba-1.5-large")
        mesh = make_mesh((4, 2), ("data", "model"))
    pcfg = PoolConfig(num_slots=2, page_size=8, pages_per_slot=4,
                      quantized=True)
    prompts = prompts_for(cfg)
    ref, _ = run_engine(lm, params, ShardPlan(mesh=None), prompts, pcfg)
    for fused in (False, True):
        got, _ = run_engine(lm, params, make_plan(mesh, "tp"), prompts, pcfg,
                            fused_attention=fused)
        assert got == ref, (fused, got, ref)
        print("OK", CASE, "fused" if fused else "gather", "token-identical")

elif CASE == "decode_in_place":
    import sys
    sys.path.insert(0, "tests")
    from hlo_pool import decode_pool_report
    cfg, lm, params = setup("internlm2-1.8b", d_model=256, num_heads=16,
                            num_kv_heads=8, d_ff=160)
    plan = make_plan(make_mesh((1, 8), ("data", "model")), "tp")
    pcfg = PoolConfig(num_slots=4, page_size=4, pages_per_slot=16,
                      num_pages=4096, quantized=True)
    for fused in (False, True):
        eng = Engine(lm, params, EngineConfig(pool=pcfg,
                                              fused_attention=fused), plan)
        bad, temp, leaf = decode_pool_report(eng)
        assert not bad, bad
        assert temp < leaf, (fused, temp, leaf)
        print("OK", CASE, "fused" if fused else "gather", "temp", temp,
              "leaf shard", leaf)

elif CASE == "prefix":
    cfg, lm, params = setup("internlm2-1.8b", d_model=256, num_heads=16,
                            num_kv_heads=8, d_ff=160)
    mesh = make_mesh((1, 8), ("data", "model"))
    # one 20-token base: full-path reuse + two mid-page divergences, so the
    # sharded path must take COW forks on head-sharded pages
    rng = np.random.RandomState(7)
    v = cfg.vocab_size
    base = rng.randint(0, v, 20).tolist()
    sfx = [rng.randint(0, v, 6).tolist() for _ in range(3)]
    prompts = [base + sfx[0], base + sfx[1], base[:18] + sfx[2],
               base + sfx[0][:3] + sfx[1][:3]]
    pcfg = PoolConfig(num_slots=2, page_size=8, pages_per_slot=4,
                      quantized=True)
    ref, _ = run_engine(lm, params, ShardPlan(mesh=None), prompts, pcfg,
                        gen=6)
    got, s = run_engine(lm, params, make_plan(mesh, "tp"), prompts, pcfg,
                        gen=6, prefix_cache=True)
    assert got == ref, (got, ref)
    assert s["prefix_hit_tokens"] > 0 and s["cow_forks"] > 0, s
    assert s["prefill_tokens"] == s["prompt_tokens"] - s["prefix_hit_tokens"]
    # memory ledger on the sharded path: reconciled totals and a per-device
    # breakdown covering all 8 forced devices, each holding at least the
    # pool bytes the engine reports for it
    mem = s["memory"]
    assert mem["reconcile"]["ok"], mem["reconcile"]
    assert mem["sites"]["prefix_bytes_saved"]["peak_bytes"] > 0, mem["sites"]
    per_dev = mem["per_device"]
    assert len(per_dev) == 8, per_dev
    assert sum(per_dev.values()) >= mem["sites"]["kv_pool"]["bytes"], per_dev
    print("OK prefix hits", s["prefix_hit_tokens"], "forks", s["cow_forks"],
          "ledger devices", len(per_dev))

elif CASE == "dp_train":
    from jax.sharding import PartitionSpec as P
    from repro.configs.base import TrainConfig
    from repro.launch.mesh import make_dp_mesh
    from repro.launch.steps import (init_dp_train_state, init_train_state,
                                    make_dp_train_step, make_train_step)

    cfg, lm, params = setup("internlm2-1.8b")
    tcfg = TrainConfig(total_steps=4, warmup_steps=1, grad_clip=1.0,
                       grad_compress=True)
    b1 = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0,
                                       cfg.vocab_size),
          "labels": jax.random.randint(jax.random.PRNGKey(2), (8, 16), 0,
                                       cfg.vocab_size)}
    b2 = {"tokens": jax.random.randint(jax.random.PRNGKey(3), (8, 16), 0,
                                       cfg.vocab_size),
          "labels": jax.random.randint(jax.random.PRNGKey(4), (8, 16), 0,
                                       cfg.vocab_size)}
    # mesh-less reference: same int8 + error-feedback wire semantics,
    # quantized on one device (compress_decompress)
    s_ref = init_train_state(params, tcfg)
    ref_step = jax.jit(make_train_step(lm, ShardPlan(mesh=None), tcfg))
    s_ref, m1_ref = ref_step(s_ref, b1)
    _, m2_ref = ref_step(s_ref, b2)

    plan = make_plan(make_dp_mesh(8), "tp")
    state = init_dp_train_state(params, tcfg, plan)
    step = jax.jit(make_dp_train_step(lm, plan, tcfg))
    # step-1 loss is pre-update: forward math must be bitwise-stable
    # across shard_map, so it matches the mesh-less loss exactly
    state, m1 = step(state, b1)
    np.testing.assert_allclose(float(m1["loss"]), float(m1_ref["loss"]),
                               rtol=0, atol=1e-6)
    # step 2 sees wire-vs-single-device quantization differences in the
    # updated params; the losses stay close
    _, m2 = step(state, b2)
    np.testing.assert_allclose(float(m2["loss"]), float(m2_ref["loss"]),
                               rtol=2e-2)
    print("OK dp_train loss", float(m1["loss"]))

    # jaxpr walk: every payload-sized collective operand is the int8 wire's
    # all_gather — gradients cross the wire as int8 codes and NOTHING else
    # payload-sized moves between replicas (scale pmax rows and scalar
    # loss/metric pmeans are tens of bytes)
    COLL = ("all_gather", "psum", "pmax", "pmin", "pmean", "all_to_all",
            "reduce_scatter", "ppermute", "all_reduce")
    jx = jax.make_jaxpr(make_dp_train_step(lm, plan, tcfg))(state, b1)

    def walk(j, found):
        for eqn in j.eqns:
            if any(c in eqn.primitive.name for c in COLL):
                a = eqn.invars[0].aval
                found.append((eqn.primitive.name, a.dtype,
                              a.size * a.dtype.itemsize))
            for v in eqn.params.values():
                inner = getattr(v, "jaxpr", v)
                if hasattr(inner, "eqns"):
                    walk(inner, found)
        return found

    colls = walk(jx.jaxpr, [])
    big = [c for c in colls if c[2] >= 2048]
    assert big, colls
    assert all(n == "all_gather" and d == jnp.dtype(jnp.int8)
               for n, d, _ in big), big
    print("OK dp_train wire:", len(big), "payload collectives, all int8",
          len(colls) - len(big), "small")
"""

CASES = ["engine_attn", "engine_jamba", "prefix", "dp_train",
         "decode_in_place"]


@pytest.mark.parametrize("case", CASES)
def test_sharded_serve(case):
    r = subprocess.run(
        [sys.executable, "-c", SCRIPT % case],
        capture_output=True, text=True, timeout=600,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "HOME": os.environ.get("HOME", ""),
             # pin the platform: the forced 8-device mesh is a CPU
             # construct (see test_distributed.py)
             "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS", "cpu")},
        cwd=Path(__file__).resolve().parents[1])
    assert r.returncode == 0, f"stdout={r.stdout}\nstderr={r.stderr[-3000:]}"
    assert "OK" in r.stdout
