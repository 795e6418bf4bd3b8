"""Prefix-cache acceptance tests (serve/prefix.py + scheduler/engine wiring):

(a) radix-tree mechanics in isolation — match/insert/split, the
    len(prompt)-1 cap, refcount pinning vs LRU eviction,
(b) the COW/scale pool primitives carry codes bitwise,
(c) engine decode with the prefix cache enabled is token-identical to
    cache-disabled decode — fp32 and int8, including COW divergence
    mid-page, eviction under page pressure, and preempt/resume,
(d) an int8 cache hit is exactly a cache-off run with a chunk boundary at
    the resume position (the bitwise-recompute contract),
(e) stateful archs (recurrent sublayers) bypass the cache entirely,
(f) the bounded compile cache evicts jitted prefill shapes without
    changing tokens; MoE chunked-prefill capacity parity routes chunks
    like whole-prompt at capacity-bound loads (model level), and the
    engine, serving MoE dropless, gives chunked and whole-prompt prefill
    the same tokens.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as C
from repro.configs.base import ModelConfig, MoEConfig
from repro.models import build_lm, init_lm
from repro.models import moe as M
from repro.serve import (CompileCache, Engine, EngineConfig, PoolConfig,
                         RadixPrefixCache, bucket_len)
from repro.serve import kv_cache as KC
from repro.sharding import ShardPlan

PLAN = ShardPlan(mesh=None)


def _setup(arch="internlm2-1.8b"):
    cfg = C.get_reduced(arch).replace(dtype="float32", remat="none")
    lm = build_lm(cfg)
    params = init_lm(jax.random.PRNGKey(0), lm)
    return cfg, lm, params


def _run(lm, params, prompts, pcfg, gens, **ekw):
    """One engine over ``prompts`` (submitted in order); returns the token
    lists in submission order plus the summary."""
    eng = Engine(lm, params, EngineConfig(pool=pcfg, **ekw), PLAN)
    rids = [eng.submit(p, max_new_tokens=g) for p, g in zip(prompts, gens)]
    res = eng.run()
    return [res[r].tokens for r in rids], eng.summary()


# ---------------------------------------------------------------------------
# (a) radix-tree mechanics, no engine
# ---------------------------------------------------------------------------

def test_radix_match_insert_split():
    pc = RadixPrefixCache(page_size=4, num_pages=16)
    A = list(range(100, 112))               # 12 tokens = 3 pages
    assert pc.match(A) is None              # empty tree
    assert pc.insert(A, [0, 1, 2], scales=None) == [0, 1, 2]
    # extension of the cached path: all 3 pages shared, no fork
    m = pc.match(A + [1, 2])
    assert (m.shared_pages, m.fork_src, m.resume) == ([0, 1, 2], None, 12)
    # the exact cached prompt: capped at len-1, so the last page forks
    m2 = pc.match(A)
    assert m2.shared_pages == [0, 1] and m2.resume == 11
    assert (m2.fork_src, m2.fork_tokens) == (2, 3)
    # mid-page divergence at position 6: one shared page + a 2-token fork
    B = A[:6] + [999, 998] + A[8:]
    mb = pc.match(B)
    assert mb.shared_pages == [0] and (mb.fork_src, mb.fork_tokens) == (1, 2)
    assert mb.resume == 6
    # inserting the diverging path splits the edge at the page boundary:
    # page 0 stays shared, pages 3,4 are newly donated
    assert pc.insert(B, [0, 3, 4], scales=None) == [3, 4]
    assert pc.num_nodes() == 3 and pc.owned_pages == {0, 1, 2, 3, 4}
    # both paths still match in full after the split
    assert pc.match(A + [7]).shared_pages == [0, 1, 2]
    assert pc.match(B + [7]).shared_pages == [0, 3, 4]


def test_radix_refcounts_pin_against_eviction():
    pc = RadixPrefixCache(page_size=4, num_pages=16)
    A = list(range(50, 62))
    pc.insert(A, [5, 6, 7], scales=None)
    m = pc.match(A)                         # shared [5,6], fork 7
    pc.acquire(m)
    # every owned page is either shared or the fork source: nothing to evict
    assert pc.evict(99) == []
    pc.release(m.shared_pages + [m.fork_src])
    freed = pc.evict(99)
    assert sorted(freed) == [5, 6, 7]
    assert pc.owned_pages == set() and pc.num_nodes() == 0
    assert pc.evictions >= 1 and pc.pages_evicted == 3
    assert pc.match(A + [1]) is None


def test_radix_lru_evicts_coldest_leaf_first():
    pc = RadixPrefixCache(page_size=2, num_pages=16)
    pc.insert([1, 2, 3, 4], [0, 1], scales=None)
    pc.insert([1, 2, 9, 9], [0, 2], scales=None)    # splits; leaves [1],[2]
    pc.match([1, 2, 3, 4, 5])               # warm the [3,4] branch
    freed = pc.evict(1)
    assert freed == [2]                     # the colder [9,9] leaf goes first


# ---------------------------------------------------------------------------
# (b) pool primitives: COW copy and scale adoption are bitwise
# ---------------------------------------------------------------------------

def test_fork_page_and_adopt_scales_bitwise():
    _, lm, _ = _setup()
    pcfg = PoolConfig(num_slots=2, page_size=4, pages_per_slot=2,
                      quantized=True)
    pool = KC.init_pool(lm, pcfg)
    k = jax.random.PRNGKey(3)
    fill = {"data": {}, "scale_log2": {}}
    for key in pool["data"]:
        fill["data"][key], fill["scale_log2"][key] = {}, {}
        for name, arr in pool["data"][key].items():
            k, k1, k2 = jax.random.split(k, 3)
            fill["data"][key][name] = jax.random.randint(
                k1, arr.shape, -128, 128, jnp.int32).astype(arr.dtype)
            sarr = pool["scale_log2"][key][name]
            fill["scale_log2"][key][name] = jax.random.randint(
                k2, sarr.shape, -6, 3).astype(sarr.dtype)
    before = jax.tree.map(np.asarray, fill)
    forked = KC.fork_page(fill, jnp.int32(1), jnp.int32(3))
    for key in forked["data"]:
        for name, arr in forked["data"][key].items():
            arr = np.asarray(arr)
            old = before["data"][key][name]
            np.testing.assert_array_equal(arr[:, 3], old[:, 1])   # verbatim
            keep = [p for p in range(arr.shape[1]) if p != 3]
            np.testing.assert_array_equal(arr[:, keep], old[:, keep])
            np.testing.assert_array_equal(       # scales: fork leaves alone
                np.asarray(forked["scale_log2"][key][name]),
                before["scale_log2"][key][name])
    snap = KC.snapshot_scales(forked, 0)
    dev = {key: {n: jnp.asarray(v) for n, v in kinds.items()}
           for key, kinds in snap.items()}
    adopted = KC.adopt_scales(forked, jnp.int32(1), dev)
    for key in adopted["scale_log2"]:
        for name, arr in adopted["scale_log2"][key].items():
            arr = np.asarray(arr)
            np.testing.assert_array_equal(arr[:, 1], arr[:, 0])


# ---------------------------------------------------------------------------
# (c) engine: prefix-on decode == prefix-off decode, token for token
# ---------------------------------------------------------------------------

def _shared_prefix_prompts(cfg, seed=7):
    """Four prompts over one 20-token base: a full-path reuse, a divergence
    at 20 (mid-page COW on page 2 of an 8-token page), and a divergence at
    18 (mid-page COW inside the base itself)."""
    rng = np.random.RandomState(seed)
    v = cfg.vocab_size
    base = rng.randint(0, v, 20).tolist()
    sfx = [rng.randint(0, v, 6).tolist() for _ in range(3)]
    return [base + sfx[0],
            base + sfx[1],
            base[:18] + sfx[2],
            base + sfx[0][:3] + sfx[1][:3]]


@pytest.mark.parametrize("quantized", [False, True])
def test_prefix_on_matches_off(quantized):
    cfg, lm, params = _setup()
    pcfg = PoolConfig(num_slots=2, page_size=8, pages_per_slot=4,
                      quantized=quantized)
    prompts = _shared_prefix_prompts(cfg)
    gens = [6, 6, 6, 6]
    off, s_off = _run(lm, params, prompts, pcfg, gens)
    on, s_on = _run(lm, params, prompts, pcfg, gens, prefix_cache=True)
    assert on == off
    assert s_off["prefix_hit_tokens"] == 0
    assert s_on["prefix_hit_tokens"] > 0
    assert s_on["cow_forks"] > 0            # both mid-page divergences
    assert s_on["pages_saved"] > 0
    assert 0.0 < s_on["prefix_hit_rate"] < 1.0
    # the hit tokens were NOT recomputed
    assert s_on["prefill_tokens"] == (s_on["prompt_tokens"]
                                      - s_on["prefix_hit_tokens"])


def test_prefix_on_matches_off_chunked_prefill():
    """Suffix recompute through the chunked path (prefill_chunk > 0) is
    still token-identical."""
    cfg, lm, params = _setup()
    pcfg = PoolConfig(num_slots=2, page_size=8, pages_per_slot=4,
                      quantized=False)
    prompts = _shared_prefix_prompts(cfg)
    gens = [5, 5, 5, 5]
    off, _ = _run(lm, params, prompts, pcfg, gens, prefill_chunk=8)
    on, s_on = _run(lm, params, prompts, pcfg, gens, prefill_chunk=8,
                    prefix_cache=True)
    assert on == off
    assert s_on["prefix_hit_tokens"] > 0


def test_prefix_eviction_under_pressure_matches_off():
    """A pool too small to cache every base forces LRU leaf eviction; the
    decode stream stays identical to the cache-off engine."""
    cfg, lm, params = _setup()
    rng = np.random.RandomState(11)
    v = cfg.vocab_size
    bases = [rng.randint(0, v, 8).tolist() for _ in range(4)]
    prompts = [bases[i % 4] + rng.randint(0, v, 4).tolist()
               for i in range(10)]
    gens = [4] * 10
    pcfg = PoolConfig(num_slots=2, page_size=4, pages_per_slot=6,
                      quantized=True, num_pages=14)
    off, _ = _run(lm, params, prompts, pcfg, gens)
    on, s_on = _run(lm, params, prompts, pcfg, gens, prefix_cache=True)
    assert on == off
    assert s_on["prefix_hit_tokens"] > 0
    assert s_on["prefix_evictions"] > 0


def test_prefix_preempt_resume_matches_off():
    """Pool exhaustion mid-decode preempts the youngest slot (releasing its
    refs); on re-admission its folded prompt hits the cache again. Tokens
    stay identical to the cache-off engine (which serializes instead)."""
    cfg, lm, params = _setup()
    rng = np.random.RandomState(13)
    v = cfg.vocab_size
    base = rng.randint(0, v, 8).tolist()
    prompts = [base + rng.randint(0, v, 2).tolist() for _ in range(2)]
    gens = [5, 5]
    pcfg = PoolConfig(num_slots=2, page_size=4, pages_per_slot=4,
                      quantized=False, num_pages=5)
    off, _ = _run(lm, params, prompts, pcfg, gens)
    on, s_on = _run(lm, params, prompts, pcfg, gens, prefix_cache=True)
    assert on == off
    assert s_on["prefix_hit_tokens"] > 0
    assert s_on["preemptions"] > 0


# ---------------------------------------------------------------------------
# (d) int8 hit == cache-off run with a chunk boundary at resume (bitwise
#     recompute contract: shared codes verbatim + adopted donor scales)
# ---------------------------------------------------------------------------

def test_quantized_hit_equals_chunk_boundary_recompute():
    cfg, lm, params = _setup()
    rng = np.random.RandomState(17)
    v = cfg.vocab_size
    donor = rng.randint(0, v, 16).tolist()          # exactly 2 full pages
    follower = donor + rng.randint(0, v, 7).tolist()
    pcfg = PoolConfig(num_slots=2, page_size=8, pages_per_slot=4,
                      quantized=True)
    # cache-off reference: chunked prefill with a boundary at 16, so the
    # follower's first 16 positions quantize on scales chosen from exactly
    # those 16 tokens — the same grid the donor's whole-prompt prefill chose
    eng_off = Engine(lm, params,
                     EngineConfig(pool=pcfg, prefill_chunk=16), PLAN)
    r_off = eng_off.submit(follower, max_new_tokens=5)
    ref = eng_off.run()[r_off].tokens

    eng_on = Engine(lm, params,
                    EngineConfig(pool=pcfg, prefill_chunk=16,
                                 prefix_cache=True), PLAN)
    eng_on.submit(donor, max_new_tokens=1)
    eng_on.run()
    r_on = eng_on.submit(follower, max_new_tokens=5)
    got = eng_on.run()[r_on].tokens
    assert got == ref
    s = eng_on.summary()
    assert s["prefix_hit_tokens"] == 16 and s["cow_forks"] == 0


# ---------------------------------------------------------------------------
# (e) stateful archs bypass: no cache is constructed, requests take the
#     ordinary full-prefill miss path
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["rwkv6-1.6b", "jamba-1.5-large"])
def test_stateful_arch_bypasses_prefix_cache(arch):
    cfg, lm, params = _setup(arch)
    rng = np.random.RandomState(19)
    v = cfg.vocab_size
    base = rng.randint(0, v, 12).tolist()
    prompts = [base + rng.randint(0, v, 3).tolist() for _ in range(2)]
    gens = [3, 3]
    pcfg = PoolConfig(num_slots=2, page_size=8, pages_per_slot=4,
                      quantized=False)
    eng = Engine(lm, params,
                 EngineConfig(pool=pcfg, prefix_cache=True), PLAN)
    assert eng._prefix is None              # documented miss path
    rids = [eng.submit(p, max_new_tokens=g) for p, g in zip(prompts, gens)]
    res = eng.run()
    on = [res[r].tokens for r in rids]
    off, s_off = _run(lm, params, prompts, pcfg, gens)
    assert on == off
    s = eng.summary()
    assert s["prefix_hit_tokens"] == 0 and s["cow_forks"] == 0
    assert s["prefill_tokens"] == s["prompt_tokens"]


# ---------------------------------------------------------------------------
# (f) satellites: bounded compile cache; MoE chunked-prefill capacity parity
# ---------------------------------------------------------------------------

def test_bucket_len_and_compile_cache_lru():
    assert bucket_len(7, 0) == 7 and bucket_len(7, 8) == 8
    assert bucket_len(8, 8) == 8 and bucket_len(9, 8) == 16
    calls = []
    cc = CompileCache(lambda k: calls.append(k) or f"fn{k}", max_live=2)
    assert cc.get(1) == "fn1" and cc.get(2) == "fn2" and cc.get(1) == "fn1"
    assert calls == [1, 2] and cc.evictions == 0
    cc.get(3)                               # evicts 2 (1 was touched last)
    assert cc.evictions == 1 and sorted(cc.keys) == [1, 3]
    cc.get(2)                               # rebuild: factory again, evicts 1
    assert calls == [1, 2, 3, 2] and cc.evictions == 2
    unbounded = CompileCache(lambda k: k, max_live=0)
    for i in range(8):
        unbounded.get(i)
    assert unbounded.evictions == 0 and len(unbounded) == 8


def test_compile_cache_eviction_in_engine():
    """max_prefill_shapes=1 with three distinct prompt lengths forces
    evictions; tokens match the unbounded engine."""
    cfg, lm, params = _setup()
    rng = np.random.RandomState(23)
    prompts = [rng.randint(0, cfg.vocab_size, n).tolist()
               for n in (9, 11, 13)]
    gens = [3, 3, 3]
    pcfg = PoolConfig(num_slots=1, page_size=8, pages_per_slot=4,
                      quantized=False)
    free, s_free = _run(lm, params, prompts, pcfg, gens)
    tight, s_tight = _run(lm, params, prompts, pcfg, gens,
                          max_prefill_shapes=1)
    assert tight == free
    assert s_free["compile_evictions"] == 0
    assert s_tight["compile_evictions"] > 0


def test_moe_capacity_parity_unit():
    """Chunked routing == whole-prompt routing iff capacity derives from
    the full token count. Construction: top_k=1 with 5 prototype rows whose
    top-1 experts are distinct, demands sized so the whole-prompt capacity
    (16) covers every expert but the legacy per-chunk capacity (8) does
    not."""
    cfg = ModelConfig(name="m", d_model=32, d_ff=64, dtype="float32",
                      moe=MoEConfig(num_experts=8, top_k=1,
                                    capacity_factor=2.0))
    mdef = M.make_moe(cfg)
    params = M.init_moe(jax.random.PRNGKey(0), mdef, cfg)
    cand = jax.random.normal(jax.random.PRNGKey(1), (64, cfg.d_model))
    top1 = np.asarray(M._route(params, cand, mdef, cfg)[0][:, 0])
    protos, used = [], set()
    for i in range(64):
        if int(top1[i]) not in used:
            used.add(int(top1[i]))
            protos.append(np.asarray(cand[i]))
        if len(protos) == 5:
            break
    assert len(protos) == 5, "need 5 distinct top-1 experts"
    a, b, c, d, e = protos
    # chunk 1 routes 12 tokens to expert(a): > chunk cap 8, <= whole cap 16
    rows = [a] * 12 + [b] * 4 + [c] * 16 + [d] * 16 + [e] * 16
    x = jnp.asarray(np.stack(rows))[None]           # (1, 64, D)
    whole, _ = M.moe_forward(params, x, mdef, cfg)
    pieces = [x[:, i:i + 16] for i in range(0, 64, 16)]
    legacy = jnp.concatenate(
        [M.moe_forward(params, p, mdef, cfg)[0] for p in pieces], axis=1)
    parity = jnp.concatenate(
        [M.moe_forward(params, p, mdef, cfg, capacity_tokens=64)[0]
         for p in pieces], axis=1)
    np.testing.assert_allclose(np.asarray(parity), np.asarray(whole),
                               rtol=2e-5, atol=2e-5)
    assert np.abs(np.asarray(legacy) - np.asarray(whole)).max() > 1e-3
    # the legacy chunk dropped exactly the capacity-overflow rows (ties
    # break by token order: tokens 8..11 of the 12-token run lose)
    dropped = np.linalg.norm(np.asarray(legacy)[0, 8:12], axis=-1)
    kept = np.linalg.norm(np.asarray(whole)[0, 8:12], axis=-1)
    assert (dropped < 1e-6).all() and (kept > 1e-6).all()


def test_moe_engine_chunked_parity_flag():
    """Engine-level: chunked prefill and whole-prompt prefill produce
    identical tokens on an MoE arch whose config routes with a capacity:
    the engine serves every MoE sublayer dropless, so no token's expert
    output depends on which other tokens share its prefill pass (the
    whole 24-token prompt overflows an expert's capacity of 8 here, where
    each 8-token chunk cannot)."""
    cfg, lm, params = _setup("moonshot-v1-16b")
    assert not cfg.moe.dropless
    pcfg = PoolConfig(num_slots=2, page_size=8, pages_per_slot=4,
                      quantized=False)
    rng = np.random.RandomState(29)
    prompt = rng.randint(0, cfg.vocab_size, 24).tolist()
    outs = []
    for chunk in (0, 8):
        eng = Engine(lm, params,
                     EngineConfig(pool=pcfg, prefill_chunk=chunk), PLAN)
        rid = eng.submit(prompt, max_new_tokens=6)
        outs.append(eng.run()[rid].tokens)
    assert outs[0] == outs[1]
