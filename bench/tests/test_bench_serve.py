"""The serving cell's reference and its comparison, on the CPU at the
program's ``get_reduced`` widths of internlm2-1.8b:

- the plain reference's logits equal the program's forward in float32;
- through the engine (prefill, then decode off the int8 paged pool), the
  served tokens' widest and mean gaps below the reference's best are
  small, and the control (the program with its 4-bit KV pool) reads
  ``correct: false`` and exceeds both;
- a run whose tokens are altered where they are produced reads
  ``correct: false``.
"""
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import common
import run
import tiny

REF = common.load_module(common.BENCH / "reference" / "dense_gqa_lm.py",
                         "bench_reference_test")
# at these widths a sound bf16 run reads a served gap of at most about
# 0.045 and a mean gap of at most 0.0005, the program's 4-bit KV pool 0.8
# to 1.7 and 0.07 to 0.13 (seeds 3-6 and 2**31 + 9); the cell's own limits
# are set at its own size in limits/<cell>.json
TINY_LIMITS = {"served_logit_gap": 0.15, "served_logit_gap_mean": 0.005}
BENCH = {"end_to_end": [{"name": "ttft_p95_ms", "unit": "ms"},
                        {"name": "itl_p95_ms", "unit": "ms"},
                        {"name": "setup_s", "unit": "s"}],
         "per_layer": []}


def test_reference_equals_program_forward():
    import program
    from repro.models import lm_forward
    from repro.sharding import ShardPlan
    c = tiny.serve_config()
    lm = program.build(c)
    params = program.params(c, lm, 5)
    toks = np.random.default_rng(0).integers(0, c["vocab_size"], 37)
    with jax.default_matmul_precision("highest"):
        want, _, _ = lm_forward(params, lm, ShardPlan(mesh=None),
                                tokens=jnp.asarray(toks)[None])
    got = REF.logits_at(5, c, toks, np.arange(37))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want[0]),
                               atol=2e-5 * float(jnp.max(jnp.abs(want))))


def _cell(seed, sample_hook=None, monkeypatch=None, kv_bits=8):
    c = tiny.serve_config()
    c["torch_dtype"] = "bfloat16"
    c["serve"]["kv_bits"] = kv_bits
    t = tiny.serve_traffic()
    picked = []
    orig = run.Context.check_served

    def check_served(self, p):
        picked.extend(p)
        return orig(self, p)

    monkeypatch.setattr(run.Context, "check_served", check_served)
    if sample_hook is not None:
        from repro.serve import Engine
        monkeypatch.setattr(Engine, "_sample", sample_hook(Engine._sample))
    args = SimpleNamespace(seed=seed, seconds=0.8, trace=0)
    out = run.run_cell(args, BENCH, {"name": "tiny", "chips": 1}, c, t,
                       TINY_LIMITS, jax.devices()[:1])
    return out, picked, c


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 9])
def test_sound_run_is_correct_and_control_is_not(seed, monkeypatch):
    out, _, _ = _cell(seed, monkeypatch=monkeypatch)
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 5
    ctl, _, _ = _cell(seed, monkeypatch=monkeypatch, kv_bits=4)
    assert not ctl["correct"] and ctl["failed"] == 0
    for name, lim in TINY_LIMITS.items():
        assert out["checks"][name]["value"] <= lim \
            < ctl["checks"][name]["value"]


def test_altered_token_is_not_correct(monkeypatch):
    def broken(sample):
        def f(self, logits, slots):
            return (sample(self, logits, slots) + 1) % logits.shape[-1]
        return f

    out, _, _ = _cell(4, sample_hook=broken, monkeypatch=monkeypatch)
    assert not out["correct"]
    assert out["checks"]["served_logit_gap"]["value"] \
        > TINY_LIMITS["served_logit_gap"]
