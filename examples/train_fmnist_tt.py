"""The paper's experiment end-to-end (Appendix B): two-layer tensorized MLP,
rank-adaptive prior, 4/8/16-bit quantized training with automatic scale
selection, BinaryConnect — on the synthetic FashionMNIST drop-in.

Prints the Table-1 row for the proposed method.

    PYTHONPATH=src python examples/train_fmnist_tt.py [--steps 600]
"""
import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs.base import TrainConfig
from repro.data import fashion_like
from repro.models import mlp_tt as MLP
from repro.optim import adam as A
from repro.optim.binaryconnect import quantize_for_deploy


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=600)
    ap.add_argument("--no-prior", action="store_true")
    ap.add_argument("--no-quant", action="store_true")
    args = ap.parse_args()

    d = MLP.make_mlp(prior=not args.no_prior, quantize=not args.no_quant)
    params = MLP.init_mlp(jax.random.PRNGKey(0), d)
    tcfg = TrainConfig(learning_rate=3e-3, weight_decay=0.0)
    opt = A.init_adam(params, tcfg)
    xs, ys = fashion_like(8192, seed=1)
    xt, yt = fashion_like(2048, seed=2)

    # Adam + Eq. (4) λ update + §3.3 scale manager
    step = jax.jit(MLP.mlp_train_step(d, tcfg))

    bsz, t0 = 64, time.time()
    for i in range(args.steps):
        lo = (i * bsz) % (len(ys) - bsz)
        batch = {"x": jnp.asarray(xs[lo:lo + bsz]),
                 "y": jnp.asarray(ys[lo:lo + bsz])}
        params, opt, loss = step(params, opt, batch)
        if i % 100 == 0:
            logits = MLP.mlp_forward(params, jnp.asarray(xt), d)
            acc = float((jnp.argmax(logits, -1) == jnp.asarray(yt)).mean())
            print(f"step {i:4d}  loss {float(loss):.4f}  test acc {acc:.3f}")

    dt = (time.time() - t0) / args.steps
    logits = MLP.mlp_forward(params, jnp.asarray(xt), d)
    acc = float((jnp.argmax(logits, -1) == jnp.asarray(yt)).mean())
    if d.tt.rank_adapt:
        eff1, eff2 = MLP.effective_ranks(params, d)
        c = MLP.param_counts(d, eff1, eff2)
        print(f"\neffective ranks: L1 {eff1}  L2 {eff2}")
    else:
        c = MLP.param_counts(d)
    bits = c["fixed_bits"] if d.qc.enable else c["float_bits"]
    print(f"test acc {acc:.3f}   params {c['tt_params']:,}   "
          f"memory {bits:,} bits   "
          f"reduction {c['dense_bits']/bits:.0f}x vs dense "
          f"(paper: 292x, 84.86% on real FMNIST)")
    print(f"{dt*1e3:.1f} ms/batch-64 on this CPU "
          f"(paper: 90 ms on the FPGA, 5340 ms on a Pi 3B)")
    deploy = quantize_for_deploy(params, d.qc)   # 4-bit cores for inference
    _ = deploy
    if d.qc.enable:
        # packed int4x2 deploy artifact: two codes per byte on disk
        from repro.ckpt import export_tt_deploy
        stats = export_tt_deploy("/tmp/fmnist_tt_deploy.ckpt", params)
        print(f"deploy export: {stats['packed_bytes']:,} B packed int4 "
              f"cores ({stats['reduction_x']:.1f}x vs fp32) "
              f"-> /tmp/fmnist_tt_deploy.ckpt")


if __name__ == "__main__":
    main()
