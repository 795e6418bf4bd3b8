"""What Moonlight-16B-A3B asks of the program, at a tiny Moonlight (3
layers: 1 dense + 2 MoE; d 64; 8 routed experts, 4 held; top-2; 1 shared;
no q low-rank):

(b) the expert share: the parts that the shares of the routed experts give
    add up to the uncut layer, with the shared experts counted once;
(c) sigmoid ``noaux_tc`` routing equals a per-token numpy routing, and the
    correction bias changes some picks and none of the weights;
(d) dropless dispatch: a router forced onto one expert loses no token;
(e) ``lm_forward`` and the decode steps agree across the leading dense
    layer, and the engine serves the model token for token as the static
    decode does, and refuses the paths that scan the periods only.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as C
from repro.models import build_lm, init_lm, lm_decode_step, lm_forward
from repro.models import moe as M
from repro.obs import TraceRecorder
from repro.serve import Engine, EngineConfig, PoolConfig
from repro.sharding import ShardPlan

PLAN = ShardPlan(mesh=None)


def _cfg(**moe):
    """The tiny Moonlight, its layers dropless as the engine serves them."""
    cfg = C.get_reduced("moonlight-16b-a3b").replace(dtype="float32",
                                                     remat="none")
    return cfg.replace(moe=dataclasses.replace(
        cfg.moe, **{"dropless": True, **moe}))


def _moe(cfg, key=0, bias_std=0.5):
    """A MoE layer of ``cfg`` with a drawn correction bias."""
    d = M.make_moe(cfg)
    p = M.init_moe(jax.random.PRNGKey(key), d, cfg)
    p["router_bias"] = bias_std * jax.random.normal(
        jax.random.PRNGKey(key + 1), (cfg.moe.num_experts,))
    return d, p


def _x(cfg, t=48, key=7):
    return jax.random.normal(jax.random.PRNGKey(key), (1, t, cfg.d_model))


def test_expert_shares_add_up_to_the_uncut_layer():
    full_cfg = _cfg(experts_held=0)
    d_full, p_full = _moe(full_cfg)
    assert d_full.experts_held == 8
    x = _x(full_cfg)
    want, _ = M.moe_forward(p_full, x, d_full, full_cfg)
    sh = p_full["shared"]
    shared = M._glu(sh, x, d_full.shared, full_cfg)
    parts = []
    for off in (0, 4):
        cfg = _cfg(experts_held=4, expert_offset=off)
        d = M.make_moe(cfg)
        p = dict(p_full, **{k: jax.tree.map(lambda a: a[off:off + 4],
                                            p_full[k])
                            for k in ("gate", "up", "down")})
        out, _ = M.moe_forward(p, x, d, cfg)
        parts.append(out - shared)
    got = sum(parts) + shared
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    # each share alone is a part, not the whole
    assert np.abs(np.asarray(parts[0] + shared - want)).max() > 1e-3


def _np_route(x, w, bias, k, scale):
    scores = 1.0 / (1.0 + np.exp(-(x @ w)))
    pick = np.argsort(-(scores + bias), axis=-1, kind="stable")[:, :k]
    wts = np.take_along_axis(scores, pick, -1)
    return pick, wts / wts.sum(-1, keepdims=True) * scale


def test_sigmoid_noaux_tc_routing_matches_numpy():
    cfg = _cfg()
    d, p = _moe(cfg)
    x = np.asarray(_x(cfg)[0])
    w, bias = np.asarray(p["router"]["w"]), np.asarray(p["router_bias"])
    k, scale = cfg.moe.top_k, cfg.moe.routed_scaling_factor
    idx, wts, _ = M._route(p, jnp.asarray(x), d, cfg)
    pick, want = _np_route(x, w, bias, k, scale)
    assert (np.sort(np.asarray(idx), -1) == np.sort(pick, -1)).all()
    order = np.argsort(np.asarray(idx), -1)
    np.testing.assert_allclose(
        np.take_along_axis(np.asarray(wts), order, -1),
        np.take_along_axis(want, np.argsort(pick, -1), -1), rtol=1e-5)
    # without the bias: other picks for some tokens, and the weights of
    # each pick are its unbiased score in both cases
    idx0, wts0, _ = M._route(dict(p, router_bias=jnp.zeros_like(bias)),
                             jnp.asarray(x), d, cfg)

    def by_expert(i, w):
        o = np.argsort(np.asarray(i), -1)
        return (np.take_along_axis(np.asarray(i), o, -1),
                np.take_along_axis(np.asarray(w), o, -1))

    (i1, w1), (i0, w0) = by_expert(idx, wts), by_expert(idx0, wts0)
    same = (i0 == i1).all(-1)
    assert 0 < same.sum() < len(same)
    np.testing.assert_allclose(w0[same], w1[same], rtol=1e-6)
    np.testing.assert_allclose(np.asarray(wts).sum(-1), scale, rtol=1e-5)


def test_dropless_loses_no_token_on_one_expert():
    cfg = _cfg(experts_held=0, num_shared=0)
    d, p = _moe(cfg)
    # the bias forces every token's first pick onto held expert 3
    p["router_bias"] = p["router_bias"].at[3].set(100.0)
    x = _x(cfg, t=64)
    out, _ = M.moe_forward(p, x, d, cfg)
    idx, wts, _ = M._route(p, x[0], d, cfg)
    assert (np.asarray(idx)[:, 0] == 3).all()
    ep = [jax.tree.map(lambda a: a[e], {k: p[k] for k in ("gate", "up",
                                                          "down")})
          for e in range(d.experts_held)]
    want = np.zeros((64, cfg.d_model), np.float32)
    for t in range(64):
        for j in range(cfg.moe.top_k):
            e = int(idx[t, j])
            y = M._glu(ep[e], x[0, t], d, cfg)
            want[t] += float(wts[t, j]) * np.asarray(y)
    np.testing.assert_allclose(np.asarray(out[0]), want, rtol=1e-4,
                               atol=1e-5)
    # the same load through a capacity drops tokens of expert 3
    capped = dataclasses.replace(d, dropless=False)
    out_c, _ = M.moe_forward(p, x, capped, cfg)
    assert np.abs(np.asarray(out_c[0]) - want).max() > 1e-3


def test_forward_and_decode_agree_across_the_dense_layer():
    cfg = _cfg()
    lm = build_lm(cfg)
    assert [s.ffn_kind for s in lm.lead] == ["ffn"] and lm.n_stack == 3
    assert lm.lead[0].ffn.gate.out_dim == cfg.dense_d_ff
    params = init_lm(jax.random.PRNGKey(0), lm)
    moe = params["layers"]["sub_0"]["moe"]
    moe["router_bias"] = 0.5 * jax.random.normal(jax.random.PRNGKey(3),
                                                 moe["router_bias"].shape)
    b, s = 2, 12
    toks = jax.random.randint(jax.random.PRNGKey(1), (b, s), 0,
                              cfg.vocab_size)
    ref, _, cache = lm_forward(params, lm, PLAN, tokens=toks,
                               return_cache=True)
    assert cache["sub_0"]["c_kv"].shape[0] == 3
    from repro.models import lm_init_cache
    c = lm_init_cache(lm, b, s, PLAN)
    outs = []
    for t in range(s):
        lg, c = lm_decode_step(params, c, toks[:, t:t + 1], jnp.int32(t),
                               lm, PLAN)
        outs.append(lg[:, 0])
    np.testing.assert_allclose(np.asarray(jnp.stack(outs, 1)),
                               np.asarray(ref), rtol=1e-4, atol=1e-4)
    # the prefill's dense-layer cache is the decode's pool layer 0
    np.testing.assert_allclose(np.asarray(c["sub_0"]["c_kv"][0]),
                               np.asarray(cache["sub_0"]["c_kv"][0]),
                               rtol=1e-5, atol=1e-5)


def _static_greedy(lm, params, prompt, n):
    toks = jnp.asarray(prompt, jnp.int32)[None]
    logits, _, _ = lm_forward(params, lm, PLAN, tokens=toks)
    out = [int(jnp.argmax(logits[0, -1]))]
    for _ in range(n - 1):
        toks = jnp.concatenate([toks, jnp.asarray([[out[-1]]])], axis=1)
        logits, _, _ = lm_forward(params, lm, PLAN, tokens=toks)
        out.append(int(jnp.argmax(logits[0, -1])))
    return out


def test_engine_serves_the_dense_layer_and_counts_hits():
    cfg = _cfg()
    lm = build_lm(cfg)
    params = init_lm(jax.random.PRNGKey(0), lm)
    rng = np.random.RandomState(5)
    prompts = [rng.randint(0, cfg.vocab_size, n).tolist() for n in (7, 13)]
    rec = TraceRecorder()
    eng = Engine(lm, params, EngineConfig(pool=PoolConfig(
        num_slots=2, page_size=4, pages_per_slot=6, quantized=False)),
        PLAN, trace=rec)
    rids = [eng.submit(p, max_new_tokens=5) for p in prompts]
    res = eng.run()
    for rid, p in zip(rids, prompts):
        assert res[rid].tokens == _static_greedy(lm, params, p, 5)
    hits = [e.fields for e in rec.events() if e.kind == "moe_hits"]
    assert len(hits) == eng.metrics.decode_steps
    for h in hits:
        # 2 expert layers x 4 held; each live row picks 2 of 8 experts
        assert 0 < h["rows"] <= 2
        assert 0 <= h["experts_hit"] <= min(8, h["tokens_here"])
        assert h["tokens_here"] <= h["rows"] * 2 * 2
    assert sum(h["tokens_here"] for h in hits) > 0


def test_engine_serves_the_same_tokens_from_packed_pages():
    """Pages of 16 pack both latent leaves into 128-lane rows (32 x 16 and
    8 x 16 values a page; ``kv_cache.page_shape``), pages of 4 only the
    latent: the int8 engine serves the same tokens from either, and the
    float engine the static decode's."""
    cfg = _cfg()
    lm = build_lm(cfg)
    params = init_lm(jax.random.PRNGKey(0), lm)
    rng = np.random.RandomState(6)
    prompts = [rng.randint(0, cfg.vocab_size, n).tolist() for n in (9, 21)]

    def serve(page, quantized):
        eng = Engine(lm, params, EngineConfig(pool=PoolConfig(
            num_slots=2, page_size=page, pages_per_slot=32 // page,
            quantized=quantized)), PLAN)
        rows = {n: a.shape[-2:] for n, a in eng.pool["data"]["sub_0"].items()}
        rids = [eng.submit(p, max_new_tokens=6) for p in prompts]
        res = eng.run()
        return rows, [res[r].tokens for r in rids]

    rows, packed = serve(16, True)
    assert rows == {"c_kv": (4, 128), "k_rope": (1, 128)}
    rows, mixed = serve(4, True)
    assert rows == {"c_kv": (1, 128), "k_rope": (4, 8)}
    assert packed == mixed
    _, fp = serve(16, False)
    assert fp == [_static_greedy(lm, params, p, 6) for p in prompts]


def test_engine_serves_the_same_tokens_through_the_latent_walk():
    """The dense layer 0 and the MoE layers, from an int8 pool of packed
    pages (latent and rope keys in 128-lane rows): the latent page walk
    serves the gather path's greedy tokens, with requests that cross pages
    and the walk's blocks of 8 pages (128 tokens)."""
    cfg = _cfg()
    lm = build_lm(cfg)
    params = init_lm(jax.random.PRNGKey(0), lm)
    rng = np.random.RandomState(8)
    prompts = [rng.randint(0, cfg.vocab_size, n).tolist()
               for n in (124, 5, 17)]

    def serve(fused):
        eng = Engine(lm, params, EngineConfig(
            pool=PoolConfig(num_slots=2, page_size=16, pages_per_slot=9,
                            quantized=True),
            fused_attention=fused), PLAN)
        rids = [eng.submit(p, max_new_tokens=8) for p in prompts]
        res = eng.run()
        return eng, [res[r].tokens for r in rids]

    eng, walked = serve(True)
    assert eng.attention_paths()["attn_mla"]["decode"] == \
        "mla_paged_attention"
    assert {n: a.shape[-2:] for n, a in eng.pool["data"]["sub_0"].items()} \
        == {"c_kv": (4, 128), "k_rope": (1, 128)}
    assert walked == serve(False)[1]


@pytest.mark.parametrize("ekw", [{"prefill_chunk": 8},
                                 {"prefix_cache": True}])
def test_engine_refuses_period_only_paths(ekw):
    cfg = _cfg()
    lm = build_lm(cfg)
    params = init_lm(jax.random.PRNGKey(0), lm)
    pcfg = PoolConfig(num_slots=2, page_size=4, pages_per_slot=6)
    with pytest.raises(NotImplementedError, match="leading layers"):
        Engine(lm, params, EngineConfig(pool=pcfg, **ekw), PLAN)


def test_group_limited_routing_is_refused():
    with pytest.raises(NotImplementedError, match="n_group"):
        M.make_moe(_cfg(n_group=2))
