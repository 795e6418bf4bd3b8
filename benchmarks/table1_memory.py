"""Paper Table 1: model parameters + memory bits for the five methods.

The parameter/memory columns are analytic (exact reproduction); accuracy
columns come from training on the synthetic FashionMNIST drop-in
(directional validation — the real dataset is not available offline).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.configs.base import TrainConfig
from repro.data import fashion_like
from repro.models import mlp_tt as MLP
from repro.optim import adam as A


def train_once(prior: bool, quantize: bool, steps: int = 400, lr=3e-3):
    d = MLP.make_mlp(prior=prior, quantize=quantize)
    params = MLP.init_mlp(jax.random.PRNGKey(0), d)
    tcfg = TrainConfig(learning_rate=lr, weight_decay=0.0)
    opt = A.init_adam(params, tcfg)
    xs, ys = fashion_like(4096, seed=1)
    xq, yq = fashion_like(1024, seed=2)

    step = jax.jit(MLP.mlp_train_step(d, tcfg))

    bsz = 64
    for i in range(steps):
        lo = (i * bsz) % (len(ys) - bsz)
        b = {"x": jnp.asarray(xs[lo:lo + bsz]), "y": jnp.asarray(ys[lo:lo + bsz])}
        params, opt, loss = step(params, opt, b)
    tr = MLP.mlp_forward(params, jnp.asarray(xs[:1024]), d)
    tr_acc = float((jnp.argmax(tr, -1) == jnp.asarray(ys[:1024])).mean())
    te = MLP.mlp_forward(params, jnp.asarray(xq), d)
    te_acc = float((jnp.argmax(te, -1) == jnp.asarray(yq)).mean())
    return params, d, tr_acc, te_acc


def run() -> list[str]:
    rows = []
    d = MLP.make_mlp()
    base = MLP.param_counts(d)
    # vanilla (dense) row — analytic
    rows.append(f"table1/vanilla_params,{base['dense_params']},paper=4.67e5")
    rows.append(f"table1/vanilla_bits,{base['dense_bits']},paper=1.49e7")
    for name, prior, quant, paper_bits in (
            ("float_noprior", False, False, 4.74e5),
            ("fixed_noprior", False, True, 6.13e4),
            ("float_prior", True, False, 3.46e5),
            ("fixed_prior", True, True, 5.11e4)):
        t0 = time.time()
        params, dd, tr, te = train_once(prior, quant, steps=250)
        if prior:
            eff = MLP.effective_ranks(params, dd)
            c = MLP.param_counts(dd, *eff)
        else:
            c = MLP.param_counts(dd)
        bits = c["fixed_bits"] if quant else c["float_bits"]
        red = base["dense_bits"] / bits
        rows.append(
            f"table1/{name},{(time.time()-t0)*1e6:.0f},"
            f"params={c['tt_params']} bits={bits} paper_bits={paper_bits:.3g}"
            f" reduction={red:.0f}x train_acc={tr:.3f} test_acc={te:.3f}")
    return rows


if __name__ == "__main__":
    for r in run():
        print(r)
