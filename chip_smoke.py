"""Chip smoke test: drive the serve and TT-train paths once on a TPU, at
internlm2-1.8b's published widths, through the entry points a user calls.

    python chip_smoke.py             # one chip: serve, FMNIST TT, LM TT train
    python chip_smoke.py --chips 4   # TP-sharded serve + dp int8-wire train,
                                     # each beside its one-device comparator

Weights are random, made from ``--seed``. Each phase prints one JSON line
with its numbers and ``"ok"``; the first phase that fails ends the run with
a non-zero exit. The last line is ``{"ok": true, "device": {...}}`` and is
printed only when every phase passed on a TPU. There is no CPU fallback: on
any other platform, or with ``JAX_PALLAS_INTERPRET`` set (interpret mode
even on a TPU), the script exits non-zero before any phase.

The phase functions take a config, so ``tests/test_chip_smoke.py`` runs
them on the CPU at ``get_reduced`` widths.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "internlm2-1.8b"

# ops.paged_attention(impl="pallas") vs the jnp page walk under "highest"
# matmul precision, as a fraction of max|V|. If the kernel's f32 matmuls
# run as one bf16 MXU pass, q, k, p and v each carry a relative rounding
# error up to 2^-9: the softmax weights then move by ~2^-8 relative and the
# output, a convex combination of V rows, by ~2^-8 * max|V| < 4e-3 * max|V|.
# 1e-2 leaves room for that; a wrong page, head or mask moves the output by
# a sizeable fraction of max|V|.
KERNEL_TOL = 1e-2
# TP-sharded vs one-device first decode logits, f32 at "highest" matmul
# precision, as a fraction of max|logit|: the two differ only in the
# summation order of the row-parallel psums (o-proj over 2048, down-proj
# over 8192) in each of 24 layers, ~1e-6 relative each; a misplaced head
# shard or a doubled psum is an O(1) error.
TP_TOL = 1e-3
# dp-sharded vs one-device step-1 loss (pre-update forward, f32): the
# batch split and the loss pmean change only reduction order, ~1e-6
# relative; a doubled or dropped shard moves the loss by O(1/4).
DP_TOL = 1e-4


def _emit(rec: dict) -> dict:
    print(json.dumps(rec), flush=True)
    return rec


def _peak_bytes(dev) -> int | None:
    return (dev.memory_stats() or {}).get("peak_bytes_in_use")


def _collectives(hlo: str) -> dict:
    """Collective ops in a compiled program's HLO text, by kind."""
    return {c: hlo.count(f" {c}(") for c in
            ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
             "collective-permute")}


def _stats_delta(before: dict) -> dict:
    from repro.launch.compile_cache import compile_stats
    now = compile_stats()
    return {k: now[k] - before[k] for k in
            ("compile_s", "compiles", "cache_hits", "cache_misses")}


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

def kernel_vs_jnp(lm, pool, pcfg, *, q_rows: int, seed: int) -> dict:
    """Max |pallas - jnp| of one paged-attention call over layer 0 of the
    pool a serve run left behind (every physical page mapped, ragged lens),
    against ``KERNEL_TOL * max|V|``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.ops import paged_attention

    mixer = lm.period[0].mixer
    kd = pool["data"]["sub_0"]["k"][0]
    vd = pool["data"]["sub_0"]["v"][0]
    ks = pool["scale_log2"]["sub_0"]["k"][0]
    vs = pool["scale_log2"]["sub_0"]["v"][0]
    b, pp, page = pcfg.num_slots, pcfg.pages_per_slot, pcfg.page_size
    table = jnp.arange(b * pp, dtype=jnp.int32).reshape(b, pp)
    rng = np.random.default_rng(seed)
    lens = jnp.asarray(rng.integers(0, pp * page - q_rows + 1, b), jnp.int32)
    q = jnp.asarray(rng.normal(size=(b, q_rows, mixer.num_heads,
                                     mixer.head_dim)), jnp.float32)
    args = (q, kd, vd, ks, vs, table, lens)
    kw = dict(page_size=page, quantized=pcfg.quantized)
    out = paged_attention(*args, impl="pallas", **kw)
    with jax.default_matmul_precision("highest"):
        ref = paged_attention(*args, impl="jnp", page_chunk=1, **kw)
    v = vd.astype(jnp.float32)
    if pcfg.quantized:
        v = v * jnp.exp2(jnp.max(vs))
    vmax = float(jnp.max(jnp.abs(v)))
    diff = float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref)))
    return {"q_rows": q_rows, "max_abs_diff": diff, "max_abs_v": vmax,
            "tol": KERNEL_TOL * vmax,
            "ok": bool(np.isfinite(diff) and diff <= KERNEL_TOL * vmax)}


def serve_phase(cfg, *, slots: int = 8, prompt_len: int = 256,
                gen_len: int = 32, page_size: int = 16, seed: int = 0) -> dict:
    """Greedy requests of a fixed prompt length through ``Engine.run()`` on
    an int8 paged pool with the fused paged-attention kernel."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch.compile_cache import compile_stats
    from repro.models import build_lm, init_lm
    from repro.numerics import pallas_backend
    from repro.serve import Engine, EngineConfig, PoolConfig
    from repro.sharding import ShardPlan

    dev = jax.devices()[0]
    before = compile_stats()
    t0 = time.perf_counter()
    lm = build_lm(cfg)
    params = init_lm(jax.random.PRNGKey(seed), lm)
    pcfg = PoolConfig(num_slots=slots, page_size=page_size,
                      pages_per_slot=-(-(prompt_len + gen_len) // page_size)
                      + 1, quantized=True)
    pallas_backend.reset_fallback_count()
    eng = Engine(lm, params, EngineConfig(pool=pcfg, fused_attention=True),
                 ShardPlan(mesh=None))
    rng = np.random.default_rng(seed)
    rids = [eng.submit(rng.integers(0, cfg.vocab_size, prompt_len).tolist(),
                       max_new_tokens=gen_len) for _ in range(slots)]
    done = eng.run()
    jax.block_until_ready(eng.pool)
    wall = time.perf_counter() - t0
    comp = _stats_delta(before)
    tokens = sum(len(done[r].tokens) for r in rids if r in done)
    completed = sum(r in done and len(done[r].tokens) == gen_len
                    for r in rids)
    fallbacks = pallas_backend.fallback_count()

    sched = eng.sched
    decode_args = (eng.params, eng.pool, eng.spool,
                   jnp.asarray(sched.page_table),
                   jnp.asarray(sched.lens_vector()),
                   jnp.asarray(sched.active_mask()),
                   jnp.asarray(sched.tokens_vector()))
    hlo = eng._decode_jit.lower(*decode_args).compile().as_text()
    custom_call = "tpu_custom_call" in hlo
    checks = [kernel_vs_jnp(lm, eng.pool, pcfg, q_rows=s, seed=seed + s)
              for s in (1, 5)]
    on_tpu = dev.platform == "tpu"
    ok = (completed == slots and tokens == slots * gen_len and fallbacks == 0
          and (custom_call or not on_tpu) and all(c["ok"] for c in checks))
    return _emit({
        "phase": "serve", "arch": cfg.name, "dtype": cfg.dtype,
        "layers": cfg.num_layers, "d_model": cfg.d_model,
        "requests": slots, "completed": completed, "tokens": tokens,
        "prompt_len": prompt_len, "gen_len": gen_len,
        "compile_s": comp["compile_s"], "run_s": wall - comp["compile_s"],
        "compiles": comp["compiles"], "cache_hits": comp["cache_hits"],
        "peak_bytes_in_use": _peak_bytes(dev),
        "codec_fallbacks": fallbacks, "decode_has_tpu_custom_call":
        custom_call, "kernel_vs_jnp": checks, "ok": ok})


def fmnist_phase(*, steps: int = 50, batch: int = 64, seed: int = 0) -> dict:
    """The paper's own step (Appendix B): the two-layer TT MLP with 4/8/16-bit
    quantization and rank adaptation on the synthetic FashionMNIST data, as
    ``examples/train_fmnist_tt.py`` builds it. The loss must fall."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.base import TrainConfig
    from repro.data import fashion_like
    from repro.launch.compile_cache import compile_stats
    from repro.models import mlp_tt as MLP
    from repro.optim import adam as A

    before = compile_stats()
    t0 = time.perf_counter()
    d = MLP.make_mlp(prior=True, quantize=True)
    params = MLP.init_mlp(jax.random.PRNGKey(seed), d)
    tcfg = TrainConfig(learning_rate=3e-3, weight_decay=0.0)
    opt = A.init_adam(params, tcfg)
    step = jax.jit(MLP.mlp_train_step(d, tcfg))
    xs, ys = fashion_like(steps * batch, seed=seed + 1)
    losses = []
    for i in range(steps):
        b = {"x": jnp.asarray(xs[i * batch:(i + 1) * batch]),
             "y": jnp.asarray(ys[i * batch:(i + 1) * batch])}
        params, opt, loss = step(params, opt, b)
        losses.append(float(loss))
    wall = time.perf_counter() - t0
    comp = _stats_delta(before)
    first, last = float(np.mean(losses[:5])), float(np.mean(losses[-5:]))
    ok = bool(np.all(np.isfinite(losses)) and last < first)
    return _emit({"phase": "fmnist_tt", "steps": steps, "batch": batch,
                  "loss_first5": first, "loss_last5": last,
                  "compile_s": comp["compile_s"],
                  "run_s": wall - comp["compile_s"], "ok": ok})


def lm_train_phase(cfg, *, steps: int = 4, batch: int = 4, seq: int = 256,
                   seed: int = 0) -> dict:
    """``repro.launch.train.train()`` on the TT variant (``--tt``) of the
    config. Losses must be finite and steps after the first must not
    recompile the step. Checkpoints go to a fresh temporary directory.

    4 x 256 tokens: the step compiled for a v5e needs 5.4 GB of state and
    6.6 GB of temporaries; at 4 x 512 the TT contractions' rank-16 minor
    dim, padded to 128 lanes, takes it to 19 GB, past the chip's 16."""
    import jax
    import numpy as np

    from repro import configs as C
    from repro.configs.base import TrainConfig
    from repro.launch.compile_cache import compile_stats
    from repro.launch.train import train

    dev = jax.devices()[0]
    cfg = C.with_tt(cfg, max_rank=32)
    before = compile_stats()
    t0 = time.perf_counter()
    with tempfile.TemporaryDirectory(prefix="chip_smoke_ckpt_") as ckpt:
        tcfg = TrainConfig(total_steps=steps, warmup_steps=1, seed=seed,
                           ckpt_dir=ckpt, ckpt_every=0, log_every=1)
        _, losses = train(cfg, C.get_strategy(ARCH), tcfg, batch=batch,
                          seq=seq)
    wall = time.perf_counter() - t0
    comp = _stats_delta(before)
    step_compiles = compile_stats()["by_fun"].get("jit(train_step)", 0) \
        - before["by_fun"].get("jit(train_step)", 0)
    ok = (len(losses) == steps and bool(np.all(np.isfinite(losses)))
          and step_compiles == 1)
    return _emit({"phase": "lm_train_tt", "arch": cfg.name,
                  "dtype": cfg.dtype, "tt_max_rank": cfg.tt.max_rank,
                  "steps": steps, "batch": batch, "seq": seq,
                  "losses": losses, "train_step_compiles": step_compiles,
                  "compile_s": comp["compile_s"],
                  "run_s": wall - comp["compile_s"],
                  "peak_bytes_in_use": _peak_bytes(dev), "ok": ok})


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def tp_serve_phase(cfg, mesh, *, slots: int = 4, prompt_len: int = 64,
                   page_size: int = 16, seed: int = 0) -> dict:
    """TP-sharded serving on a (1, n) ("data", "model") mesh — the pool's
    KV heads split over ``model`` and the fused page walk under shard_map —
    against one device: first decode logits, f32, "highest" precision."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import build_lm, init_lm
    from repro.serve import Engine, EngineConfig, PoolConfig
    from repro.sharding import ShardPlan, make_plan

    cfg = cfg.replace(dtype="float32")
    lm = build_lm(cfg)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size, prompt_len).tolist()
               for _ in range(slots)]
    pcfg = PoolConfig(num_slots=slots, page_size=page_size,
                      pages_per_slot=-(-prompt_len // page_size) + 1)
    ecfg = EngineConfig(pool=pcfg, fused_attention=True)
    plan = make_plan(mesh, "tp")
    # one f32 copy (7.3 GB at full width) on the first device, shared by
    # the one-device engine; the TP engine places its own sharded copy
    params = init_lm(jax.random.PRNGKey(seed), lm)

    def first_decode_logits(plan):
        with jax.default_matmul_precision("highest"):
            eng = Engine(lm, params, ecfg, plan)
            seen = []
            sample = eng._sample_jit
            eng._sample_jit = lambda logits, *a: (
                seen.append(np.asarray(logits)), sample(logits, *a))[1]
            for p in prompts:
                eng.submit(p, max_new_tokens=2)
            eng.step()              # admit + prefill all, then one decode
            hlo = (eng._decode_jit.lower(
                eng.params, eng.pool, eng.spool,
                jnp.asarray(eng.sched.page_table),
                jnp.asarray(eng.sched.lens_vector()),
                jnp.asarray(eng.sched.active_mask()),
                jnp.asarray(eng.sched.tokens_vector())).compile().as_text()
                   if plan.mesh is not None else "")
            return seen[-1], hlo

    ref, _ = first_decode_logits(ShardPlan(mesh=None))
    out, hlo = first_decode_logits(plan)
    diff = float(np.max(np.abs(out - ref)))
    scale = float(np.max(np.abs(ref)))
    ok = bool(np.isfinite(diff) and diff <= TP_TOL * scale)
    return _emit({"phase": "tp_serve", "arch": cfg.name,
                  "mesh": dict(mesh.shape), "kv_heads_per_chip":
                  cfg.num_kv_heads // mesh.shape["model"],
                  "max_abs_diff": diff, "max_abs_logit": scale,
                  "tol": TP_TOL * scale,
                  "decode_collectives": _collectives(hlo),
                  "ok": ok})


def dp_train_phase(cfg, mesh, *, batch: int = 4, seq: int = 128,
                   seed: int = 0) -> dict:
    """The dp-only int8-wire train step (``steps.make_dp_train_step``) on a
    ("data",) mesh against the one-device step: step-1 loss, f32, and the
    collectives in the compiled step.

    One sequence per replica: the one-device f32 step compiled for a v5e
    takes 8.6 GB of state and 3.8 GB of temporaries at 4 x 128 tokens,
    and 7.5 GB of temporaries at 8 x 128, past the chip's 16 GB."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro import configs as C
    from repro.configs.base import TrainConfig
    from repro.launch.steps import (init_dp_train_state, init_train_state,
                                    make_dp_train_step, make_train_step)
    from repro.launch.train import make_batch_fn
    from repro.models import build_lm, init_lm
    from repro.sharding import ShardPlan, make_plan

    cfg = C.with_tt(cfg.replace(dtype="float32"), max_rank=32)
    lm = build_lm(cfg)
    tcfg = TrainConfig(total_steps=2, warmup_steps=1, grad_compress=True)
    b = jax.tree.map(jnp.asarray, make_batch_fn(cfg, batch, seq, seed)(0))
    key = jax.random.PRNGKey(seed)

    # states are built inside jit and donated to the step: a full-width f32
    # TT state is ~9 GB, two of them do not fit one chip's 16 GB
    ref_step = jax.jit(make_train_step(lm, ShardPlan(mesh=None), tcfg),
                       donate_argnums=(0,))
    ref = float(ref_step(jax.jit(lambda k: init_train_state(
        init_lm(k, lm), tcfg))(key), b)[1]["loss"])

    plan = make_plan(mesh, "tp")

    def init(k):
        return init_dp_train_state(init_lm(k, lm), tcfg, plan)

    # params/opt replicated, the per-replica residual split over "data"
    shard = jax.tree.map(lambda _: NamedSharding(mesh, P()),
                         jax.eval_shape(init, key))
    if shard.residual is not None:
        shard = shard._replace(residual=jax.tree.map(
            lambda _: NamedSharding(mesh, P(plan.dp_axes)), shard.residual))
    state = jax.jit(init, out_shardings=shard)(key)
    step = jax.jit(make_dp_train_step(lm, plan, tcfg),
                   donate_argnums=(0,)).lower(state, b).compile()
    hlo = step.as_text()
    loss = float(step(state, b)[1]["loss"])
    ok = bool(math.isfinite(loss) and abs(loss - ref) <= DP_TOL * abs(ref))
    return _emit({"phase": "dp_train", "arch": cfg.name,
                  "mesh": dict(mesh.shape), "loss": loss, "loss_ref": ref,
                  "tol": DP_TOL * abs(ref),
                  "collectives": _collectives(hlo),
                  "ok": ok})


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the TP-serve and dp-train paths, each "
                         "beside its one-device comparator")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if os.environ.get("JAX_PALLAS_INTERPRET"):
        print("chip_smoke: JAX_PALLAS_INTERPRET is set; it forces Pallas "
              "interpret mode even on a TPU", file=sys.stderr)
        return 2
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devs[0].platform}",
              file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"found {len(devs)}", file=sys.stderr)
        return 2

    from repro import configs as C
    from repro.launch.compile_cache import compile_stats, enable_compile_cache
    from repro.launch.mesh import make_dp_mesh, make_mesh

    _emit({"phase": "setup", "compile_cache": enable_compile_cache()})
    compile_stats()                     # start counting compiles
    cfg = C.get_config(ARCH)
    if args.chips == 4:
        phases = [lambda: tp_serve_phase(
                      cfg, make_mesh((1, 4), ("data", "model")),
                      seed=args.seed),
                  lambda: dp_train_phase(cfg, make_dp_mesh(4),
                                         seed=args.seed)]
    else:
        phases = [lambda: serve_phase(cfg, seed=args.seed),
                  lambda: fmnist_phase(seed=args.seed),
                  lambda: lm_train_phase(cfg, seed=args.seed)]
    for phase in phases:
        if not phase()["ok"]:
            return 1
        # an Engine sits in reference cycles (its jit closures hold self):
        # free the phase's device buffers before the next phase allocates
        gc.collect()
    total = compile_stats()
    total.pop("by_fun")
    _emit({"phase": "total", **total})
    dev = devs[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
