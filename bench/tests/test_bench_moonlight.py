"""The Moonlight cell's layout, reference and comparison on the CPU, at a
tiny Moonlight (3 layers: 1 dense + 2 MoE; d 64; 8 routed experts, 4 held;
top-2; 1 shared; no q low-rank):

- the layout's tree is the program's (``program._check_tree``);
- the plain reference's logits equal the program's forward in float32;
- through the engine (prefill, then decode off the paged pool), the served
  tokens' gaps below the reference's best are tiny with a float32 pool and
  small with the int8 pool, and the routing control (the program picking
  experts without the correction bias) reads ``correct: false``;
- the traced records a scoped loop adds: one ``moe_hits`` record per
  decode step, and the decode step's scope map naming both scopes;
- the layout's counts equal a hand count at the published widths.
"""
import copy
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import common
import program
import run
import tiny

REF = common.load_module(common.BENCH / "reference" / "mla_moe_lm.py",
                         "bench_reference_mla_moe_test")
LAYOUT = common.layout_for("mla_moe")
WIDTHS = {"num_hidden_layers": 3, "hidden_size": 64, "num_attention_heads": 4,
          "num_key_value_heads": 4, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
          "qk_rope_head_dim": 8, "v_head_dim": 16, "intermediate_size": 96,
          "moe_intermediate_size": 32, "n_routed_experts": 4,
          "n_shared_experts": 1, "num_experts_per_tok": 2, "vocab_size": 64}
# at these widths, over the positions whose routing the reference decides
# by more than its ROUTE_MARGIN, a sound bf16 run reads a widest gap of at
# most 0.027 and a mean gap of at most 0.0016, the routing control 2.26 to
# 2.87 and 0.34 to 0.82 (seeds 3, 5, 11 and 2**31 + 9); the cell's own
# limits are set at its own size in limits/<cell>.json
TINY_LIMITS = {"served_logit_gap": 1.0, "served_logit_gap_mean": 0.1}
BENCH = {"end_to_end": [{"name": "ttft_p95_ms", "unit": "ms"},
                        {"name": "itl_p95_ms", "unit": "ms"},
                        {"name": "setup_s", "unit": "s"}],
         "per_layer": []}


def tiny_config(dtype="float32", kv="int8") -> dict:
    c = copy.deepcopy(common.load_config("moonlight-16b-a3b"))
    c.update(WIDTHS, torch_dtype=dtype)
    c["expert_parallel"].update(router_experts=8, expert_offset=4,
                                router_bias_std=0.3)
    c["serve"].update(page_size=4, num_pages=64, prefill_bucket=16,
                      kv_dtype=kv)
    return c


def tiny_traffic() -> dict:
    t = tiny.serve_traffic()
    t["kind"] = common.load_traffic("serve-chat-moonlight")["kind"]
    return t


def test_layout_tree_is_the_programs():
    c = tiny_config()
    lm = program.build(c)
    assert len(lm.lead) == 1 and lm.n_periods == 2
    params = program.params(c, lm, 5)           # runs program._check_tree
    moe = params["layers"]["sub_0"]["moe"]
    assert moe["gate"]["w"].shape == (2, 4, 64, 32)
    assert moe["router"]["w"].shape == (2, 64, 8)
    assert moe["router_bias"].shape == (2, 8)
    assert "q" in params["lead"][0]["mixer"]
    assert "q_down" not in params["lead"][0]["mixer"]


def test_reference_equals_program_forward():
    from repro.models import lm_forward
    from repro.models.lm import dropless
    from repro.sharding import ShardPlan
    c = tiny_config()
    lm = dropless(program.build(c))     # as the engine serves it
    params = program.params(c, lm, 5)
    toks = np.random.default_rng(0).integers(0, c["vocab_size"], 37)
    with jax.default_matmul_precision("highest"):
        want, _, _ = lm_forward(params, lm, ShardPlan(mesh=None),
                                tokens=jnp.asarray(toks)[None])
    got = REF.logits_at(5, c, toks, np.arange(37))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want[0]),
                               atol=2e-5 * float(jnp.max(jnp.abs(want))))


def _cell(seed, monkeypatch, dtype="float32", kv="int8", unbiased=False):
    c, t = tiny_config(dtype, kv), tiny_traffic()
    if unbiased:
        orig = program.params

        def params(c_, lm, seed_):
            p = orig(c_, lm, seed_)
            moe = p["layers"]["sub_0"]["moe"]
            moe["router_bias"] = jnp.zeros_like(moe["router_bias"])
            return p

        monkeypatch.setattr(program, "params", params)
    args = SimpleNamespace(seed=seed, seconds=0.8, trace=0)
    return run.run_cell(args, BENCH, {"name": "tiny", "chips": 1}, c, t,
                        TINY_LIMITS, jax.devices()[:1])


def _traced_records(seed, monkeypatch) -> dict:
    """The records of a traced run of the scoped loop, with the profiler
    left off (its trace is read on the chip only)."""
    c, t = tiny_config(), tiny_traffic()
    monkeypatch.setattr(run.Context, "start_trace", lambda self: None)
    monkeypatch.setattr(run.Context, "stop_trace", lambda self, now: None)
    args = SimpleNamespace(seed=seed, seconds=0.8, trace=1)
    ctx = run.Context(args, {"name": "tiny", "chips": 1}, c, t,
                      jax.devices()[:1], TINY_LIMITS)
    return common.loop_for(t["kind"]).run(ctx)["records"]


def test_route_margin_counts_the_pairs_that_change_this_chip():
    """The reference's routing margin, by hand: 8 experts, 4..7 held, top 2."""
    held = jnp.arange(4, 8)
    biased = jnp.array([
        # pick 1 and expert 2, both held elsewhere, tie: that changes
        # nothing here; held 4, left out, trails pick 1 by 0.3
        [0.90, 0.80, 0.80 - 1e-4, 0.1, 0.50, 0.1, 0.1, 0.1],
        # held pick 5 leads the best left out (2) by 0.01
        [0.90, 0.1, 0.70, 0.1, 0.1, 0.71, 0.1, 0.1],
        # held pick 7 leads held 6, left out, by 0.002
        [0.1, 0.1, 0.1, 0.95, 0.1, 0.1, 0.698, 0.70],
    ], jnp.float32)
    pick = jax.lax.top_k(biased, 2)[1]
    got = np.asarray(REF._route_margin(biased, pick, held))
    np.testing.assert_allclose(got, [0.30, 0.01, 0.002], atol=1e-6)
    assert REF.ROUTE_MARGIN == 2.0 ** -7


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 9])
def test_sound_run_is_correct_and_route_control_is_not(seed, monkeypatch):
    exact = _cell(seed, monkeypatch, kv="float32")
    assert exact["correct"] and exact["failed"] == 0
    assert exact["checks"]["served_logit_gap"]["value"] < 1e-3
    out = _cell(seed, monkeypatch, dtype="bfloat16")
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 5
    ctl = _cell(seed, monkeypatch, dtype="bfloat16", unbiased=True)
    assert not ctl["correct"] and ctl["failed"] == 0
    assert ctl["checks"]["served_logit_gap_mean"]["value"] \
        > out["checks"]["served_logit_gap_mean"]["value"]


def test_scoped_loop_records_hits_and_scopes(monkeypatch):
    rec = _traced_records(11, monkeypatch)
    hits = rec["moe_hits"]
    steps = [s for s in rec["steps"] if s["decode_keys"]]
    assert hits and len(hits) >= len(steps)
    for _, rows, hit, here in hits:
        # 2 expert layers x 4 held experts; each live row picks 2 of 8
        assert 0 < rows <= 4 and 0 <= hit <= 8 and hit <= here
        assert here <= rows * 2 * 2
    scopes = set(rec["decode_scopes"].values())
    assert {"repro.ops.moe", "repro.ops.mla_attention"} <= scopes


def test_counts_at_published_widths():
    c = common.load_config("moonlight-16b-a3b")
    attn = 2048 * 3072 + 2048 * 576 + 512 * 4096 + 2048 * 2048
    assert attn == 13_762_560
    dense = attn + 3 * 2048 * 11264
    moe = attn + 2048 * 64 + 3 * 2048 * 2816 + 8 * 3 * 2048 * 1408
    assert LAYOUT.layer_params(c) == moe == 100_401_152
    assert LAYOUT.held_params(c) == dense + 26 * moe + 2 * 163840 * 2048 \
        == 3_364_487_168
    # 10 keys: 27 layers x 2 x 16 heads x (512 + 64 + 512) x 10
    assert LAYOUT.attention_flops(c, 10) == 27 * 2 * 16 * 1088 * 10
    # per layer: 10 keys x 576 int8, 2 f32 scales, q 16 x 576 and out
    # 16 x 128 in bf16
    assert LAYOUT.paged_attention_bytes(c, 10, 1, 2) == 27 * (
        5760 + 8 + 2 * 16 * 576 + 2 * 16 * 128)
    flops, nbytes = LAYOUT.moe_step_work(c, 10, 100, 150, 2, 2)
    shared = 2048 * 64 + 3 * 2048 * 2816
    assert flops == 2 * (26 * 10 * shared + 150 * 3 * 2048 * 1408)
    assert nbytes == 26 * (2 * shared + 4 * 64 + 2 * 10 * 2048 * 2) \
        + 100 * 3 * 2048 * 1408 * 2


def test_readers_on_a_made_trace():
    """Both readers on a trace made by hand: two decode-step runs, each
    with one operation under each scope and one under none."""
    import trace_reduce as TR
    c = common.load_config("moonlight-16b-a3b")
    ms = 1_000_000
    ops, mods = [], []
    for k in range(2):
        t0 = 10 * ms + 40 * ms * k
        mods.append(("jit__decode_impl", t0, 20 * ms, ""))
        ops += [("fusion.1", t0, 4 * ms, ""), ("fusion.2", t0 + 5 * ms,
                                               6 * ms, ""),
                ("fusion.3", t0 + 12 * ms, 3 * ms, "")]
    ops.append(("fusion.1", 95 * ms, 4 * ms, ""))     # outside the step
    red = TR.from_events({0: {"XLA Ops": ops, "XLA Modules": mods}},
                         [(TR.WINDOW_SPAN, 0, 100 * ms)])
    scopes = {"fusion.1": "repro.ops.mla_attention",
              "fusion.2": "repro.ops.moe"}
    steps = [{"t0": 1.0, "t1": 1.03, "decode_keys": [400, 500]},
             {"t0": 1.04, "t1": 1.07, "decode_keys": [401, 501]}]
    rec = {"trace_steps": [0, 2], "steps": steps, "pool_itemsize": 1,
           "act_itemsize": 2, "decode_scopes": scopes,
           "moe_hits": [(1.01, 2, 60, 70), (1.05, 2, 58, 70),
                        (2.0, 2, 50, 60)]}
    view = {"records": rec, "trace": red, "config": c,
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    moe = common.layer_reader("moe.roofline").read(view)
    lay = LAYOUT
    least = sum(max(f / 197e12, b / 819e9) for f, b in (
        lay.moe_step_work(c, 2, h, n, 2, 2) for h, n in ((60, 70),
                                                         (58, 70))))
    assert moe == pytest.approx(100 * least / 0.012)
    mla = common.layer_reader("mla_attention_roofline").read(view)
    least = sum(max(sum(lay.attention_flops(c, k) for k in s["decode_keys"])
                    / 197e12,
                    sum(lay.paged_attention_bytes(c, k, 1, 2)
                        for k in s["decode_keys"]) / 819e9) for s in steps)
    assert mla == pytest.approx(100 * least / 0.008)
    # a run with no scoped operations or hits reads nothing
    view["records"] = dict(rec, decode_scopes={}, moe_hits=[])
    assert common.layer_reader("moe.roofline").read(view) is None
    assert common.layer_reader("mla_attention_roofline").read(view) is None
