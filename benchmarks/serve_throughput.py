"""Serving-throughput sweep: batch slots × quantized-vs-fp KV pool, plus a
fused-vs-gather paged-attention decode sweep (``--fused``) and an
SSM/hybrid recurrent-state serving sweep (``--ssm``).

Default mode drives the continuous-batching engine over a fixed request mix
on a reduced config and records tokens/s, TTFT/latency percentiles and
resident cache bytes. ``--fused`` instead sweeps context lengths and times
the batched decode step on the gather path (``gather_slots`` materializes
the fp32 slot view) vs the fused paged-attention path (per-page in-kernel
dequant + online softmax), recording measured decode tokens/s per cell and
a modeled KV-byte ratio (the gather path moves ~9x the HBM bytes per decode
step on an int8 pool: 1B codes read + 4B fp32 view written + 4B re-read by
attention, vs 1B codes read once). Emits one JSON document (the
bench-trajectory format) to stdout or ``--out``.

``--ssm`` drives an SSM or hybrid arch through the engine (fp32 vs int8
recurrent-state cache) against the legacy static-batch greedy loop
baseline, recording tokens/s and resident state bytes — the ≥3.5×
state-byte reduction acceptance measurement — into ``BENCH_ssm_serve.json``.

    PYTHONPATH=src python benchmarks/serve_throughput.py
    PYTHONPATH=src python benchmarks/serve_throughput.py \
        --arch deepseek-v2-236b --slots 2 4 --out /tmp/serve_bench.json
    PYTHONPATH=src python benchmarks/serve_throughput.py --fused \
        --out BENCH_paged_attn.json
    PYTHONPATH=src python benchmarks/serve_throughput.py --ssm \
        --arch rwkv6-1.6b --out BENCH_ssm_serve.json
    XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \
        python benchmarks/serve_throughput.py --mesh 1x8
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import numpy as np


def _history_append(doc) -> None:
    """Append this run to the bench-history ledger (git SHA + timestamp);
    ``benchmarks/history.py gate`` reads it in CI."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import history
    entry = history.append_entry(doc)
    print(f"[history] {entry['bench']} @ {entry['git_sha'][:9]} -> "
          f"{history.history_path()}", file=sys.stderr)


def plan_for(mesh: str | None):
    """``DxM`` -> a TP ShardPlan on a (data, model) dev mesh (shards the
    paged pool over KV heads and params per the plan); None/"" -> the
    mesh-less single-device plan."""
    from repro.launch.mesh import make_mesh
    from repro.sharding import ShardPlan, make_plan
    if not mesh:
        return ShardPlan(mesh=None)
    d, m = (int(x) for x in mesh.split("x"))
    return make_plan(make_mesh((d, m), ("data", "model")), "tp")


def bench_cell(lm, params, plan, *, slots: int, quantized: bool,
               requests: int, prompt_len: int, gen_len: int,
               page_size: int, trace=None, health: bool = False) -> dict:
    """One (slots, kv-mode) engine run. ``trace``: shared
    ``repro.obs.TraceRecorder`` (cells are delimited by ``bench_cell``
    marker events); ``health`` switches on the in-engine quant-health
    aggregates — quantized cells only (the policy would otherwise force
    the fp32 cell's pool to int8)."""
    from repro.serve import Engine, EngineConfig, PoolConfig

    horizon = prompt_len + gen_len
    pcfg = PoolConfig(num_slots=slots, page_size=page_size,
                      pages_per_slot=-(-horizon // page_size) + 1,
                      quantized=quantized)
    policy = None
    if health and quantized:
        from repro.numerics import NumericsPolicy
        policy = NumericsPolicy(enable=True, health=True)
    if trace is not None:
        trace.emit("bench_cell", slots=slots,
                   kv="int8" if quantized else "fp32")
    eng = Engine(lm, params, EngineConfig(pool=pcfg, policy=policy), plan,
                 trace=trace)
    rng = np.random.RandomState(0)
    for _ in range(requests):
        plen = int(rng.randint(max(prompt_len // 2, 1), prompt_len + 1))
        eng.submit(rng.randint(0, lm.cfg.vocab_size, plen).tolist(),
                   max_new_tokens=gen_len)
    t0 = time.time()
    eng.run()
    wall = time.time() - t0
    s = eng.summary()
    return {
        "slots": slots,
        "kv_cache": "int8" if quantized else "fp32",
        "requests": requests,
        "wall_s": wall,
        "tokens_per_s": s["tokens_per_s"],
        "ttft_p50_s": s["ttft_p50_s"],
        "ttft_p95_s": s["ttft_p95_s"],
        "ttft_queue_p50_s": s["ttft_queue_p50_s"],
        "ttft_compute_p50_s": s["ttft_compute_p50_s"],
        "latency_p50_s": s["latency_p50_s"],
        "latency_p95_s": s["latency_p95_s"],
        "batch_fill_mean": s["batch_fill_mean"],
        "batch_fill_frac": s["batch_fill_frac"],
        "free_pages_min": s["free_pages_min"],
        "cache_bytes": s["cache_bytes"],
        "cache_reduction_vs_fp32": s["cache_reduction"],
        "preemptions": s["preemptions"],
        "quant_health": s["quant_health"],
        "memory": s["memory"],
    }


def run_sweep(arch: str, slots_list: list[int], requests: int,
              prompt_len: int, gen_len: int, page_size: int,
              trace=None, health: bool = False, mesh: str = "") -> dict:
    import repro.configs as C
    from repro.models import build_lm, init_lm

    cfg = C.get_reduced(arch).replace(dtype="float32", remat="none")
    lm = build_lm(cfg)
    params = init_lm(jax.random.PRNGKey(0), lm)
    plan = plan_for(mesh)
    cells = []
    for slots in slots_list:
        for quantized in (False, True):
            cells.append(bench_cell(
                lm, params, plan, slots=slots, quantized=quantized,
                requests=requests, prompt_len=prompt_len, gen_len=gen_len,
                page_size=page_size, trace=trace, health=health))
            print(f"  slots={slots} kv={cells[-1]['kv_cache']}: "
                  f"{cells[-1]['tokens_per_s']:.1f} tok/s, "
                  f"{cells[-1]['cache_bytes']} cache bytes",
                  file=sys.stderr)
    return {"bench": "serve_throughput", "arch": arch,
            "prompt_len": prompt_len, "gen_len": gen_len,
            "page_size": page_size, "mesh": mesh or "1",
            "cells": cells}


def _decode_timer(lm, params, plan, *, fused: bool, ctx: int, slots: int,
                  page_size: int, quantized: bool):
    """Build an engine at a fixed context depth and return a closure timing
    its jitted batched decode step (the path the fused kernel owns; host
    scheduling/sampling are identical across paths and excluded)."""
    import jax.numpy as jnp
    from repro.serve import Engine, EngineConfig, PoolConfig

    horizon = ctx + 40
    pcfg = PoolConfig(num_slots=slots, page_size=page_size,
                      pages_per_slot=-(-horizon // page_size) + 1,
                      quantized=quantized)
    eng = Engine(lm, params, EngineConfig(pool=pcfg, fused_attention=fused),
                 plan)
    rng = np.random.RandomState(0)
    for _ in range(slots):
        eng.submit(rng.randint(0, lm.cfg.vocab_size, ctx).tolist(),
                   max_new_tokens=30)
    eng.step()                          # admit + prefill + compile decode
    sched = eng.sched
    args = (jnp.asarray(sched.page_table), jnp.asarray(sched.lens_vector()),
            jnp.asarray(sched.active_mask()),
            jnp.asarray(sched.tokens_vector()))
    state = {"pool": eng.pool, "spool": eng.spool}

    def one():
        # pool + state pool are donated (argnums 1,2): rebind both each call
        logits, state["pool"], state["spool"] = eng._decode_jit(
            eng.params, state["pool"], state["spool"], *args)
        return logits

    def timed(steps: int) -> float:
        jax.block_until_ready(one())    # warm
        t0 = time.time()
        for _ in range(steps):
            logits = one()
        jax.block_until_ready(logits)
        return time.time() - t0

    return timed


def bench_decode_pair(lm, params, plan, *, ctx: int, slots: int,
                      page_size: int, quantized: bool, steps: int,
                      reps: int = 3) -> list[dict]:
    """Time gather vs fused decode at one context depth with interleaved
    repetitions (decorrelates machine noise); keeps the best rep of each."""
    timers = {impl: _decode_timer(lm, params, plan, fused=(impl == "fused"),
                                  ctx=ctx, slots=slots, page_size=page_size,
                                  quantized=quantized)
              for impl in ("gather", "fused")}
    best = {impl: float("inf") for impl in timers}
    for _ in range(reps):
        for impl, timed in timers.items():
            best[impl] = min(best[impl], timed(steps))
    return [{
        "ctx": ctx,
        "impl": impl,
        "decode_ms_per_step": 1e3 * best[impl] / steps,
        "decode_tokens_per_s": steps * slots / best[impl],
    } for impl in ("gather", "fused")]


def modeled_kv_bytes(lm, *, ctx: int, slots: int, quantized: bool) -> dict:
    """Per-decode-step KV-path HBM bytes of each attention path (the
    roofline-style model the ≥1.3x long-context target comes from; on CPU
    the Pallas kernel runs in interpret mode, so measured wall-clock there
    validates dataflow, not the TPU roofline)."""
    from repro.serve.kv_cache import kv_feature_shapes
    code = 1 if quantized else 4
    feat = 0
    for sub in lm.period:
        for shp in kv_feature_shapes(sub).values():
            f = 1
            for d in shp:
                f *= d
            feat += f
    elems = lm.n_periods * slots * ctx * feat
    # gather: codes read + fp32 view written + fp32 view read by attend
    gather = elems * (code + 4 + 4)
    # fused: codes read once, dequantized in-register
    fused = elems * code
    return {"gather_bytes": gather, "fused_bytes": fused,
            "bytes_ratio": gather / fused}


def run_fused_sweep(arch: str, ctxs: list[int], slots: int, page_size: int,
                    quantized: bool, steps: int, mesh: str = "") -> dict:
    import repro.configs as C
    from repro.models import build_lm, init_lm
    from repro.numerics.pallas_backend import interpret_mode as _interpret
    from repro.numerics.pallas_backend import native_backend as _native

    cfg = C.get_reduced(arch).replace(dtype="float32", remat="none")
    lm = build_lm(cfg)
    params = init_lm(jax.random.PRNGKey(0), lm)
    plan = plan_for(mesh)
    cells, speedup, modeled = [], {}, {}
    for ctx in ctxs:
        pair_cells = bench_decode_pair(
            lm, params, plan, ctx=ctx, slots=slots, page_size=page_size,
            quantized=quantized, steps=steps)
        cells.extend(pair_cells)
        pair = {c["impl"]: c for c in pair_cells}
        for c in pair_cells:
            print(f"  ctx={ctx} {c['impl']}: "
                  f"{c['decode_tokens_per_s']:.1f} tok/s "
                  f"({c['decode_ms_per_step']:.2f} ms/step)",
                  file=sys.stderr)
        speedup[str(ctx)] = (pair["fused"]["decode_tokens_per_s"]
                             / pair["gather"]["decode_tokens_per_s"])
        modeled[str(ctx)] = modeled_kv_bytes(lm, ctx=ctx, slots=slots,
                                             quantized=quantized)
    return {"bench": "paged_attention", "arch": arch, "slots": slots,
            "page_size": page_size,
            "kv_cache": "int8" if quantized else "fp32",
            "backend": jax.default_backend(),
            # label derived from the SAME predicate the engine's auto
            # selection uses (native_backend: TPU, or forced kernel
            # validation via JAX_PALLAS_INTERPRET=1 — interpret-mode
            # timings are dataflow validation, not performance); off-TPU
            # the fused path is the jnp page-scan. The modeled bytes ratio
            # carries the HBM-roofline expectation the >=1.3x long-context
            # target comes from.
            "fused_impl": ("pallas-interpret" if _interpret()
                           else "pallas") if _native()
                          else "jnp-page-scan",
            "decode_steps_timed": steps, "cells": cells,
            "measured_speedup_fused_vs_gather": speedup,
            "modeled_kv_hbm_bytes": modeled,
            "target": {"ctx<=512": "fused >= gather",
                       "ctx>=2048": ">=1.3x (HBM roofline; see modeled)"}}


def _static_loop_cell(lm, params, plan, *, batch: int, prompt_len: int,
                      gen_len: int) -> dict:
    """Legacy static-batch greedy loop (the pre-state-cache serving path
    for SSM/hybrid archs): whole-batch prefill, scalar-position decode, no
    admission/retirement. The baseline the engine cells compare against."""
    import jax.numpy as jnp
    from repro.launch.steps import make_prefill_step, make_serve_step

    prompt = jax.random.randint(jax.random.PRNGKey(1), (batch, prompt_len),
                                0, lm.cfg.vocab_size)
    prefill = jax.jit(make_prefill_step(lm, plan))
    logits, cache = prefill(params, {"tokens": prompt})

    # grow only the per-token attention leaves (keyed by name: recurrent
    # state axes can coincide with prompt_len — e.g. reduced-jamba d_inner)
    def pad_seq(path, a):
        leaf = path[-1].key if hasattr(path[-1], "key") else None
        if leaf in ("k", "v", "c_kv", "k_rope") and a.shape[2] == prompt_len:
            pad = [(0, 0)] * a.ndim
            pad[2] = (0, gen_len)
            return jnp.pad(a, pad)
        return a

    cache = jax.tree_util.tree_map_with_path(pad_seq, cache)
    cache_bytes = sum(a.nbytes
                      for a in jax.tree_util.tree_leaves(cache))
    step = jax.jit(make_serve_step(lm, plan))
    tok = jnp.argmax(logits[:, -1:], -1).astype(jnp.int32)
    logits, cache = step(params, cache, tok, jnp.int32(prompt_len))  # warm
    jax.block_until_ready(logits)
    t0 = time.time()
    n = 0
    for i in range(1, gen_len - 1):
        logits, cache = step(params, cache, tok, jnp.int32(prompt_len + i))
        tok = jnp.argmax(logits[:, -1:], -1).astype(jnp.int32)
        n += batch
    jax.block_until_ready(tok)
    wall = time.time() - t0
    return {"mode": "static_loop", "state": "fp32", "batch": batch,
            "tokens_per_s": n / max(wall, 1e-9),
            "cache_bytes": cache_bytes}


def run_ssm_sweep(arch: str, slots: int, requests: int, prompt_len: int,
                  gen_len: int, page_size: int) -> dict:
    """Engine (fp32-state vs int8-state) vs static-loop baseline for an
    SSM/hybrid arch. Emits the BENCH_ssm_serve document."""
    import repro.configs as C
    from repro.models import build_lm, init_lm
    from repro.serve import Engine, EngineConfig, PoolConfig
    from repro.sharding import ShardPlan

    cfg = C.get_reduced(arch).replace(dtype="float32", remat="none")
    lm = build_lm(cfg)
    recurrent = [s.mixer_kind for s in lm.period
                 if s.mixer_kind in ("mamba", "rwkv6")]
    if not recurrent:
        raise SystemExit(f"--ssm wants an SSM/hybrid arch, {arch} has no "
                         f"recurrent sublayers")
    params = init_lm(jax.random.PRNGKey(0), lm)
    plan = ShardPlan(mesh=None)
    cells = [_static_loop_cell(lm, params, plan, batch=slots,
                               prompt_len=prompt_len, gen_len=gen_len)]
    print(f"  static loop: {cells[0]['tokens_per_s']:.1f} tok/s, "
          f"{cells[0]['cache_bytes']} cache bytes", file=sys.stderr)
    horizon = prompt_len + gen_len
    state_bytes = {}
    for quantized in (False, True):
        pcfg = PoolConfig(num_slots=slots, page_size=page_size,
                          pages_per_slot=-(-horizon // page_size) + 1,
                          quantized=quantized)
        eng = Engine(lm, params, EngineConfig(pool=pcfg), plan)
        rng = np.random.RandomState(0)
        for _ in range(requests):
            plen = int(rng.randint(max(prompt_len // 2, 1), prompt_len + 1))
            eng.submit(rng.randint(0, lm.cfg.vocab_size, plen).tolist(),
                       max_new_tokens=gen_len)
        t0 = time.time()
        eng.run()
        wall = time.time() - t0
        s = eng.summary()
        state = "int8" if quantized else "fp32"
        state_bytes[state] = s["state_bytes"]
        cells.append({
            "mode": "engine", "state": state, "slots": slots,
            "requests": requests, "wall_s": wall,
            "tokens_per_s": s["tokens_per_s"],
            "ttft_p50_s": s["ttft_p50_s"],
            "latency_p50_s": s["latency_p50_s"],
            "state_bytes": s["state_bytes"],
            "state_bytes_fp32": s["state_bytes_fp32"],
            "state_reduction_vs_fp32": s["state_reduction"],
            "cache_bytes": s["cache_bytes"],
            "preemptions": s["preemptions"],
            "memory": s["memory"],
        })
        print(f"  engine state={state}: {s['tokens_per_s']:.1f} tok/s, "
              f"{s['state_bytes']} state bytes "
              f"({s['state_reduction']:.2f}x vs fp32)", file=sys.stderr)
    return {"bench": "ssm_serve", "arch": arch,
            "mixers": sorted(set(recurrent)), "slots": slots,
            "prompt_len": prompt_len, "gen_len": gen_len,
            "page_size": page_size, "backend": jax.default_backend(),
            "state_reduction_int8": (state_bytes["fp32"]
                                     / max(state_bytes["int8"], 1)),
            "target": {"state_reduction_int8": ">=3.5x"},
            "cells": cells}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-1.8b")
    ap.add_argument("--slots", type=int, nargs="+", default=[2, 4, 8])
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--prompt-len", type=int, default=24)
    ap.add_argument("--gen-len", type=int, default=16)
    ap.add_argument("--page-size", type=int, default=None,
                    help="tokens per page (default: 8; 16 for the full "
                         "--fused sweep)")
    ap.add_argument("--fused", action="store_true",
                    help="fused-vs-gather paged-attention decode sweep "
                         "(emits the BENCH_paged_attn document)")
    ap.add_argument("--ssm", action="store_true",
                    help="SSM/hybrid engine vs static-loop sweep "
                         "(emits the BENCH_ssm_serve document)")
    ap.add_argument("--ctx", type=int, nargs="+", default=[128, 512, 2048])
    ap.add_argument("--decode-steps", type=int, default=12)
    ap.add_argument("--fp-pool", action="store_true",
                    help="fused sweep on an fp32 pool instead of int8")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny fused sweep for CI (ctx 64, few steps)")
    ap.add_argument("--trace-out", default="",
                    help="default sweep only: record per-step engine events "
                         "(admit/prefill/decode/preempt/retire/page "
                         "alloc-free) to this JSONL and switch on the "
                         "quant-health aggregates for int8 cells; the BENCH "
                         "doc grows a 'telemetry' key")
    ap.add_argument("--mesh", default="",
                    help="DxM (data, model) dev mesh for the default and "
                         "--fused sweeps — runs the engine on the TP plan "
                         "(KV pool sharded over KV heads). Needs D*M "
                         "devices, e.g. XLA_FLAGS="
                         "--xla_force_host_platform_device_count=8 "
                         "--mesh 1x8 on CPU")
    ap.add_argument("--out", default="")
    args = ap.parse_args()

    trace = None
    if args.trace_out:
        if args.fused or args.ssm:
            raise SystemExit("--trace-out drives the default engine sweep "
                             "(not --fused/--ssm)")
        from repro.obs import TraceRecorder
        trace = TraceRecorder()

    if args.ssm:
        requests = 4 if args.smoke else args.requests
        plen = 8 if args.smoke else args.prompt_len
        glen = 6 if args.smoke else args.gen_len
        doc = run_ssm_sweep(args.arch, slots=args.slots[0],
                            requests=requests, prompt_len=plen,
                            gen_len=glen, page_size=args.page_size or 8)
    elif args.fused:
        ctxs = [64] if args.smoke else args.ctx
        steps = 4 if args.smoke else args.decode_steps
        page = args.page_size or (8 if args.smoke else 16)
        doc = run_fused_sweep(args.arch, ctxs, slots=args.slots[0],
                              page_size=page,
                              quantized=not args.fp_pool, steps=steps,
                              mesh=args.mesh)
    else:
        doc = run_sweep(args.arch, args.slots, args.requests,
                        args.prompt_len, args.gen_len, args.page_size or 8,
                        trace=trace, health=trace is not None,
                        mesh=args.mesh)
    if trace is not None:
        from repro.numerics.pallas_backend import fallback_count
        from repro.obs import kernel_costs, write_jsonl
        n = write_jsonl(trace, args.trace_out)
        doc["telemetry"] = {
            "trace_jsonl": args.trace_out,
            "trace_events": n,
            "trace_capacity": trace.capacity,
            "trace_dropped": trace.dropped,
            "codec_fallbacks": fallback_count(),
            "kernel_costs": kernel_costs(),
        }
        print(f"  wrote {n} trace events to {args.trace_out}",
              file=sys.stderr)
    text = json.dumps(doc, indent=2)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")
        print(f"wrote {args.out}", file=sys.stderr)
        _history_append(doc)
    else:
        print(text)


if __name__ == "__main__":
    main()
