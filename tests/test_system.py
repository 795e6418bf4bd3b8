"""End-to-end behaviour tests: the paper's FMNIST experiment (all five
Table-1 configurations), LM training loss decrease, TT-LM compression during
training, trainer resume."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as C
from repro.configs.base import TrainConfig
from repro.data import fashion_like
from repro.models import mlp_tt as MLP
from repro.optim import adam as A


def _train_mlp(prior: bool, quantize: bool, steps: int = 250,
               batch: int = 64, lr: float = 3e-3, seed: int = 0):
    d = MLP.make_mlp(prior=prior, quantize=quantize)
    params = MLP.init_mlp(jax.random.PRNGKey(seed), d)
    tcfg = TrainConfig(learning_rate=lr, weight_decay=0.0)
    opt = A.init_adam(params, tcfg)
    xs, ys = fashion_like(batch * 64, seed=1)
    xq, yq = fashion_like(512, seed=2)

    step = jax.jit(MLP.mlp_train_step(d, tcfg))

    losses = []
    for i in range(steps):
        lo = (i * batch) % (len(ys) - batch)
        b = {"x": jnp.asarray(xs[lo:lo + batch]),
             "y": jnp.asarray(ys[lo:lo + batch])}
        params, opt, loss = step(params, opt, b)
        losses.append(float(loss))
    logits = MLP.mlp_forward(params, jnp.asarray(xq), d)
    acc = float((jnp.argmax(logits, -1) == jnp.asarray(yq)).mean())
    return params, d, losses, acc


def test_fmnist_float_with_prior_trains_and_compresses():
    params, d, losses, acc = _train_mlp(prior=True, quantize=False)
    assert losses[-1] < losses[0]
    assert acc > 0.55, acc     # synthetic 10-class: chance = 0.1
    eff1, eff2 = MLP.effective_ranks(params, d)
    assert sum(eff1) + sum(eff2) <= 16 * 4   # some shrink from init rank 16


def test_fmnist_fixed_with_prior_proposed_method():
    """The paper's proposed configuration: 4-bit cores + prior."""
    params, d, losses, acc = _train_mlp(prior=True, quantize=True)
    assert losses[-1] < losses[0]
    assert acc > 0.45, acc      # quantized: small degradation allowed
    counts = MLP.param_counts(d, *MLP.effective_ranks(params, d))
    # paper Table 1: fixed+prior ~5.11e4 bits, >=243x vs dense 1.49e7
    assert counts["fixed_bits"] <= 61264
    assert counts["dense_bits"] / counts["fixed_bits"] >= 240


def test_fmnist_quantized_close_to_float():
    _, _, lf, acc_f = _train_mlp(prior=False, quantize=False, steps=200)
    _, _, lq, acc_q = _train_mlp(prior=False, quantize=True, steps=200)
    assert acc_q > acc_f - 0.2, (acc_f, acc_q)   # small quantization gap


def test_table1_analytic_counts_match_paper():
    d = MLP.make_mlp()
    c = MLP.param_counts(d)
    assert c["tt_params"] == 14794                 # paper: 1.48e4
    assert c["float_bits"] == 473408               # paper: 4.74e5
    assert c["fixed_bits"] == 61264                # paper: 6.13e4
    assert abs(c["dense_bits"] - 1.49e7) / 1.49e7 < 0.01
    assert c["dense_bits"] / c["fixed_bits"] > 242  # paper: 243x


def test_lm_training_loss_decreases():
    from repro.launch.train import LM100M, train
    cfg = LM100M.replace(num_layers=2, d_model=128, num_heads=4,
                         num_kv_heads=4, d_ff=256, vocab_size=512)
    tcfg = TrainConfig(learning_rate=1e-3, total_steps=30, warmup_steps=3,
                       ckpt_dir="/tmp/repro_test_lm_ckpt", ckpt_every=0,
                       log_every=1000)
    import shutil
    shutil.rmtree(tcfg.ckpt_dir, ignore_errors=True)
    state, losses = train(cfg, "tp", tcfg, batch=8, seq=64, verbose=False)
    assert losses[-1] < losses[0] - 0.2, (losses[0], losses[-1])


def test_trainer_resume_from_checkpoint(tmp_path):
    from repro.launch.train import LM100M, train
    cfg = LM100M.replace(num_layers=1, d_model=64, num_heads=4,
                         num_kv_heads=4, d_ff=128, vocab_size=256)
    tcfg = TrainConfig(learning_rate=1e-3, total_steps=10, warmup_steps=2,
                       ckpt_dir=str(tmp_path), ckpt_every=5, log_every=1000)
    train(cfg, "tp", tcfg, batch=4, seq=32, verbose=False)
    tcfg2 = TrainConfig(learning_rate=1e-3, total_steps=15, warmup_steps=2,
                        ckpt_dir=str(tmp_path), ckpt_every=5, log_every=1000)
    state, losses = train(cfg, "tp", tcfg2, batch=4, seq=32, verbose=False)
    assert int(state.step) == 15
    assert len(losses) == 5          # resumed at 10, ran 5 more
