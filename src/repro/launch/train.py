"""End-to-end training driver.

Works at every scale: single CPU device (reduced/quickstart configs), a dev
mesh, or the production pod meshes. Includes the fault-tolerance loop:
atomic async checkpointing + resume, SIGTERM emergency save, step-time EWMA
straggler monitor, prefetching input pipeline.

    PYTHONPATH=src python -m repro.launch.train --arch internlm2-1.8b \
        --reduced --steps 50 --batch 8 --seq 128
    PYTHONPATH=src python -m repro.launch.train --arch lm100m --steps 200
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from .. import configs as C
from ..ckpt import (AsyncCheckpointer, install_preemption_handler,
                    latest_step, load, step_path)
from ..configs.base import ModelConfig, TrainConfig
from ..data import Prefetcher, host_shard_info, lm_batch
from ..models.lm import build_lm, init_lm, lm_param_counts
from ..sharding import make_plan
from .compile_cache import enable_compile_cache
from .mesh import make_dp_mesh, make_mesh
from .steps import (init_dp_train_state, init_train_state,
                    make_dp_train_step, make_train_step)

# a ~100M-param dense config for the end-to-end example driver
LM100M = ModelConfig(name="lm100m", num_layers=12, d_model=768, num_heads=12,
                     num_kv_heads=12, d_ff=3072, vocab_size=32768,
                     remat="none", dtype="float32")


class StragglerMonitor:
    """EWMA step-time monitor; flags steps slower than ``factor``× the mean.
    At fleet scale the flag feeds the orchestration layer (preempt/replace);
    here it logs — the hook point is what matters."""

    def __init__(self, factor: float = 2.0, decay: float = 0.95):
        self.mean = None
        self.factor = factor
        self.decay = decay
        self.flagged = 0

    def observe(self, dt: float) -> bool:
        slow = self.mean is not None and dt > self.factor * self.mean
        self.mean = dt if self.mean is None else \
            self.decay * self.mean + (1 - self.decay) * dt
        self.flagged += int(slow)
        return slow


def get_model_cfg(name: str, reduced: bool) -> tuple[ModelConfig, str]:
    if name == "lm100m":
        return LM100M, "tp"
    cfg = C.get_reduced(name) if reduced else C.get_config(name)
    if reduced:
        cfg = cfg.replace(dtype="float32", remat="none")
    return cfg, C.get_strategy(name)


def make_batch_fn(cfg: ModelConfig, batch: int, seq: int, seed: int):
    shard, num_shards = host_shard_info()

    def fn(step: int) -> dict:
        b = lm_batch(step, batch=batch, seq=seq, vocab=cfg.vocab_size,
                     shard=shard, num_shards=num_shards, seed=seed)
        if cfg.frontend == "audio":
            rng = np.random.default_rng(step)
            frames = rng.normal(size=(b["tokens"].shape[0], seq,
                                      cfg.d_model)).astype(np.float32)
            return {"frames": frames, "labels": b["labels"] % cfg.vocab_size}
        if cfg.frontend == "vision":
            npatch = max(4, seq // 4)
            rng = np.random.default_rng(step)
            patches = rng.normal(size=(b["tokens"].shape[0], npatch,
                                       cfg.d_model)).astype(np.float32)
            return {"patches": patches, "tokens": b["tokens"],
                    "labels": b["labels"]}
        return b

    return fn


def _record_train_state(ledger, state) -> None:
    """Fold one concrete TrainState into the memory ledger (host-side; runs
    between steps, never inside the jitted body)."""
    from .steps import train_state_sites
    for site, row in train_state_sites(state).items():
        ledger.set(site, row["bytes"], fp32=row["fp32_bytes"])


def train(cfg: ModelConfig, strategy: str, tcfg: TrainConfig, *,
          batch: int, seq: int, mesh=None, verbose: bool = True,
          trace=None, ledger=None):
    """``trace``: optional ``repro.obs.TraceRecorder`` — when attached the
    loop emits one host-side ``train_step`` event per step (step, loss,
    dur, and the step's quant-health aggregates when the policy traces
    them). No recorder → the loop is byte-for-byte the old one.

    ``ledger``: optional ``repro.obs.MemoryLedger`` (one is created
    internally when None) — the loop records the TrainState's allocation
    sites (params / int8 moments / wire residual / scale state) at init and
    after every step, so per-phase peak watermarks and the live
    reduction-vs-f32 figure cover the whole run.  Host-side only: the
    jitted step is untouched."""
    plan = make_plan(mesh, strategy)
    lm = build_lm(cfg)
    key = jax.random.PRNGKey(tcfg.seed)
    params = init_lm(key, lm)
    # the numerics policy owns the managed scale-state tree (threaded
    # through TrainState; no-op scales=None when quantization is off)
    dp_only = (mesh is not None and tcfg.grad_compress
               and all(a in plan.dp_axes for a in mesh.shape))
    if dp_only:
        # dp-only mesh: the explicit shard_map step — the int8 wire is the
        # only payload-sized collective (see steps.make_dp_train_step)
        state = init_dp_train_state(params, tcfg, plan,
                                    policy=cfg.quant.policy())
        step_fn = jax.jit(make_dp_train_step(lm, plan, tcfg),
                          donate_argnums=(0,))
    else:
        state = init_train_state(params, tcfg, policy=cfg.quant.policy())
        step_fn = jax.jit(make_train_step(lm, plan, tcfg),
                          donate_argnums=(0,))

    if ledger is None:
        from ..obs import MemoryLedger
        ledger = MemoryLedger()
    _record_train_state(ledger, state)     # "init" watermark

    ckpt = AsyncCheckpointer(tcfg.ckpt_dir)
    start = 0
    resume = latest_step(tcfg.ckpt_dir)
    if resume is not None:
        state, meta = load(step_path(tcfg.ckpt_dir, resume), like=state)
        start = int(meta.get("step", resume))
        if verbose:
            print(f"[train] resumed from step {start}")

    def emergency():
        ckpt.save(int(state.step), state, {"emergency": True})
        ckpt.wait()

    install_preemption_handler(emergency)

    batch_fn = make_batch_fn(cfg, batch, seq, tcfg.seed)
    prefetch = Prefetcher(batch_fn, start)
    monitor = StragglerMonitor()
    losses = []
    t_start = time.time()
    try:
        for step, np_batch in prefetch:
            if step >= tcfg.total_steps:
                break
            t0 = time.time()
            jb = jax.tree.map(jnp.asarray, np_batch)
            state, metrics = step_fn(state, jb)
            loss = float(metrics["loss"])
            losses.append(loss)
            dt = time.time() - t0
            ledger.set_phase("train_step")
            _record_train_state(ledger, state)
            slow = monitor.observe(dt)
            if trace is not None:
                ev = {"step": step, "loss": loss, "dur": dt}
                if "health" in metrics:
                    h = metrics["health"]
                    ev["grad_sat_fraction"] = float(
                        h["grad_edge"]["sat_fraction"])
                    if "activation" in h:
                        ev["act_scale_log2"] = float(
                            h["activation"]["scale_log2"])
                        ev["act_in_band"] = float(h["activation"]["in_band"])
                trace.emit("train_step", **ev)
            if verbose and (step % tcfg.log_every == 0 or slow):
                extra = "  [STRAGGLER]" if slow else ""
                print(f"[train] step {step} loss {loss:.4f} "
                      f"ce {float(metrics['ce']):.4f} {dt*1e3:.0f}ms{extra}")
            if tcfg.ckpt_every and step > 0 and step % tcfg.ckpt_every == 0:
                ckpt.save(step, state, {"loss": loss})
        ckpt.save(int(state.step), state, {"final": True})
        ckpt.wait()
    finally:
        prefetch.close()
        ckpt.close()
    if verbose and losses:
        counts = lm_param_counts(state.params, lm)
        print(f"[train] done: {len(losses)} steps in "
              f"{time.time()-t_start:.1f}s  first-loss {losses[0]:.4f} "
              f"last-loss {losses[-1]:.4f}")
        print(f"[train] params dense-equiv {counts['dense']:.3e} "
              f"live {counts['live']:.3e} "
              f"compression {counts['compression']:.1f}x")
        if mesh is not None:
            ledger.record_devices(state.params, state.opt, state.residual)
        rec = ledger.reconcile()
        wm = ledger.watermark("train_step") or ledger.watermark("init")
        print(f"[train] memory {ledger.total()/1e6:.2f} MB live "
              f"({ledger.reduction_vs_fp32():.1f}x vs same-shape f32), "
              f"train-step watermark {wm['total_bytes']/1e6:.2f} MB, "
              f"reconcile {'ok' if rec['ok'] else 'FAILED'} "
              f"(ledger covers {rec['coverage_frac']:.0%} of "
              f"{rec['live_bytes']/1e6:.2f} MB live arrays)")
    return state, losses


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="lm100m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--tt", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=200)
    ap.add_argument("--mesh", default=None,
                    help="e.g. 2x2 for a (data, model) dev mesh, or a bare "
                         "device count (e.g. 8) for the dp-only 1-D mesh "
                         "(with --grad-compress: the shard_map int8-wire "
                         "step)")
    ap.add_argument("--grad-compress", action="store_true",
                    help="int8 + error-feedback gradient wire (dp_wire)")
    ap.add_argument("--trace-out", default=None,
                    help="write per-step train_step trace events (JSONL)")
    args = ap.parse_args()

    enable_compile_cache()
    cfg, strategy = get_model_cfg(args.arch, args.reduced)
    if args.tt:
        cfg = C.with_tt(cfg, max_rank=32)
    if args.trace_out and cfg.quant.enable:
        # trace run: also switch on the in-step quant-health aggregates
        import dataclasses
        cfg = cfg.replace(quant=dataclasses.replace(cfg.quant, health=True))
    tcfg = TrainConfig(learning_rate=args.lr, total_steps=args.steps,
                       warmup_steps=max(5, args.steps // 20),
                       ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
                       grad_compress=args.grad_compress)
    mesh = None
    if args.mesh:
        if "x" in args.mesh:
            d, m = (int(x) for x in args.mesh.split("x"))
            mesh = make_mesh((d, m), ("data", "model"))
        else:
            mesh = make_dp_mesh(int(args.mesh))
    trace = None
    if args.trace_out:
        from ..obs import TraceRecorder
        trace = TraceRecorder()
    train(cfg, strategy, tcfg, batch=args.batch, seq=args.seq, mesh=mesh,
          trace=trace)
    if trace is not None:
        from ..obs import write_jsonl
        n = write_jsonl(trace, args.trace_out)
        print(f"[train] wrote {n} trace events to {args.trace_out}")


if __name__ == "__main__":
    main()
