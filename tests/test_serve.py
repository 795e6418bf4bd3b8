"""repro.serve acceptance tests:

(a) continuous-batched fp32 decode of staggered requests is token-identical
    to the per-request static-batch reference,
(b) the int8 KV pool stays within the pow-2 quantization tolerance and cuts
    cache bytes >= 3.5x vs fp32,
(c) slots are recycled (N > num_slots requests complete), lazily-paged pools
    preempt and still finish every request,
(d) the compiled decode step updates the KV pool in place: no pool-sized
    copy, broadcast, slice or update, and temporaries under one pool leaf.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as C
from repro.launch.steps import make_prefill_step, make_serve_step
from repro.models import build_lm, init_lm, lm_decode_step
from repro.serve import (Engine, EngineConfig, PoolConfig, SamplingParams,
                         Scheduler, Request)
from repro.serve import kv_cache as KC
from repro.serve.sampling import sample_tokens
from repro.sharding import ShardPlan

PLAN = ShardPlan(mesh=None)


def _setup(arch="internlm2-1.8b"):
    cfg = C.get_reduced(arch).replace(dtype="float32", remat="none")
    lm = build_lm(cfg)
    params = init_lm(jax.random.PRNGKey(0), lm)
    return cfg, lm, params


def _prompts(cfg, n, lo, hi, seed=3):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, cfg.vocab_size,
                        int(rng.randint(lo, hi + 1))).tolist()
            for _ in range(n)]


def _static_greedy(lm, params, prompt, gen_len, max_len):
    """Per-request reference: whole-prompt prefill + scalar-cur_len greedy
    decode on the non-paged cache path."""
    prefill = jax.jit(make_prefill_step(lm, PLAN))
    toks = jnp.asarray(prompt, jnp.int32)[None]
    logits, cache = prefill(params, {"tokens": toks})
    p = len(prompt)

    def pad_seq(a):
        if a.ndim >= 3 and a.shape[2] == p:
            pad = [(0, 0)] * a.ndim
            pad[2] = (0, max_len - p)
            return jnp.pad(a, pad)
        return a

    cache = jax.tree.map(pad_seq, cache)
    tok = int(jnp.argmax(logits[0, -1]))
    out = [tok]
    for j in range(gen_len - 1):
        lg, cache = lm_decode_step(params, cache,
                                   jnp.asarray([[tok]], jnp.int32),
                                   jnp.int32(p + j), lm, PLAN)
        tok = int(jnp.argmax(lg[0, -1]))
        out.append(tok)
    return out


# ---------------------------------------------------------------------------
# (a) fp32 continuous batching == static reference, token for token
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.parametrize("arch", ["internlm2-1.8b", "deepseek-v2-236b"])
def test_continuous_batching_matches_static_decode(arch):
    cfg, lm, params = _setup(arch)
    page = 8
    pcfg = PoolConfig(num_slots=2, page_size=page, pages_per_slot=4,
                      quantized=False)
    eng = Engine(lm, params, EngineConfig(pool=pcfg), PLAN)
    # staggered: 4 requests on 2 slots with different prompt/gen lengths
    prompts = _prompts(cfg, 4, 8, 16)
    gens = [8, 5, 7, 6]
    rids = [eng.submit(p, max_new_tokens=g)
            for p, g in zip(prompts, gens)]
    res = eng.run()
    assert sorted(res) == sorted(rids)
    for rid, prompt, g in zip(rids, prompts, gens):
        ref = _static_greedy(lm, params, prompt, g, pcfg.max_len)
        assert res[rid].tokens == ref, (
            f"{arch} req {rid}: engine {res[rid].tokens} != static {ref}")


def test_chunked_prefill_matches_whole_prompt():
    cfg, lm, params = _setup()
    pcfg = PoolConfig(num_slots=2, page_size=8, pages_per_slot=6,
                      quantized=False)
    prompt = _prompts(cfg, 1, 24, 24)[0]
    outs = []
    for chunk in (0, 8):
        eng = Engine(lm, params,
                     EngineConfig(pool=pcfg, prefill_chunk=chunk), PLAN)
        rid = eng.submit(prompt, max_new_tokens=6)
        outs.append(eng.run()[rid].tokens)
    assert outs[0] == outs[1]


def test_vectorized_serve_step_matches_scalar():
    """Per-slot cur_len vector on the NON-paged path: two rows decoding at
    different positions match the per-request scalar steps."""
    cfg, lm, params = _setup()
    b, max_len = 2, 32
    lens = [7, 13]
    rng = np.random.RandomState(0)
    toks = rng.randint(0, cfg.vocab_size, (b, 1))
    step_v = jax.jit(make_serve_step(lm, PLAN))

    # build per-row caches from real prefills so the comparison is live data
    prefill = jax.jit(make_prefill_step(lm, PLAN))
    caches, scalar_logits = [], []
    for r in range(b):
        prompt = rng.randint(0, cfg.vocab_size, (1, lens[r]))
        _, cache = prefill(params, {"tokens": jnp.asarray(prompt)})

        def pad_seq(a, p=lens[r]):
            if a.ndim >= 3 and a.shape[2] == p:
                pad = [(0, 0)] * a.ndim
                pad[2] = (0, max_len - p)
                return jnp.pad(a, pad)
            return a

        cache = jax.tree.map(pad_seq, cache)
        lg, _ = lm_decode_step(params, cache,
                               jnp.asarray(toks[r:r + 1], jnp.int32),
                               jnp.int32(lens[r]), lm, PLAN)
        caches.append(cache)
        scalar_logits.append(np.asarray(lg[0]))
    batched_cache = jax.tree.map(
        lambda *xs: jnp.concatenate(xs, axis=1), *caches)
    lg_v, _ = step_v(params, batched_cache, jnp.asarray(toks, jnp.int32),
                     jnp.asarray(lens, jnp.int32))
    np.testing.assert_allclose(np.asarray(lg_v), np.stack(scalar_logits),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# (b) quantized pool: tolerance + bytes reduction
# ---------------------------------------------------------------------------

def test_quantized_pool_bytes_and_tolerance():
    cfg, lm, params = _setup()
    mk = lambda q: PoolConfig(num_slots=2, page_size=8, pages_per_slot=4,
                              quantized=q)
    engines = {q: Engine(lm, params, EngineConfig(pool=mk(q)), PLAN)
               for q in (False, True)}
    # >= 3.5x cache-byte reduction (int8 payload + tiny scale vectors)
    fp_bytes = engines[False].metrics.cache_bytes
    q_bytes = engines[True].metrics.cache_bytes
    assert fp_bytes / q_bytes >= 3.5, (fp_bytes, q_bytes)
    assert engines[True].summary()["cache_reduction"] >= 3.5

    # dequantized K/V within the pow-2 step tolerance of the fp values:
    # run the same prompt through both pools and compare slot 0's *prompt*
    # pages (decode pages may hold different greedy continuations)
    prompt = _prompts(cfg, 1, 16, 16)[0]
    for q, eng in engines.items():
        eng.submit(prompt, max_new_tokens=4)
        eng.run()
    npages = len(prompt) // 8          # fully-written prompt pages
    for key in engines[False].pool["data"]:
        for name in engines[False].pool["data"][key]:
            # slot 0 was admitted first -> owns the low page indices
            fp = np.asarray(
                engines[False].pool["data"][key][name][:, :npages])
            qd = engines[True].pool["data"][key][name][:, :npages]
            sc = engines[True].pool["scale_log2"][key][name][:, 0]
            deq = np.asarray(KC.dequantize(
                qd, sc[:, None, None], jnp.float32))
            step = np.exp2(np.asarray(sc))
            # |dequant - fp| <= step/2 elementwise (round-to-nearest grid),
            # allowing clip at the symmetric range edge
            err = np.abs(deq - fp)
            bound = (step / 2 + 1e-6).reshape(-1, 1, 1, *([1] * (fp.ndim - 3)))
            _, hi = KC.qrange(8)
            clipped = np.abs(fp) >= np.exp2(
                np.asarray(sc)).reshape(bound.shape) * hi
            assert (err <= bound)[~clipped].all(), (key, name, err.max())


def test_quantized_decode_close_to_fp32():
    """End-to-end: greedy tokens from the int8 pool agree with fp32 for the
    first steps (STE-style tolerance, not exactness)."""
    cfg, lm, params = _setup()
    prompt = _prompts(cfg, 1, 16, 16)[0]
    outs = {}
    for q in (False, True):
        pcfg = PoolConfig(num_slots=1, page_size=8, pages_per_slot=4,
                          quantized=q)
        eng = Engine(lm, params, EngineConfig(pool=pcfg), PLAN)
        rid = eng.submit(prompt, max_new_tokens=3)
        outs[q] = eng.run()[rid].tokens
    # first token comes from the (unquantized) prefill logits: always equal
    assert outs[True][0] == outs[False][0]


# ---------------------------------------------------------------------------
# (c) slot recycling / continuous admission
# ---------------------------------------------------------------------------

def test_slot_recycling_completes_more_requests_than_slots():
    cfg, lm, params = _setup()
    pcfg = PoolConfig(num_slots=2, page_size=8, pages_per_slot=3,
                      quantized=True)
    eng = Engine(lm, params, EngineConfig(pool=pcfg), PLAN)
    prompts = _prompts(cfg, 5, 6, 12)
    rids = [eng.submit(p, max_new_tokens=5) for p in prompts]
    res = eng.run()
    assert sorted(res) == sorted(rids)
    assert all(len(res[r].tokens) == 5 for r in rids)
    s = eng.summary()
    assert s["requests_completed"] == 5
    assert s["ttft_p95_s"] >= s["ttft_p50_s"] >= 0


def test_preemption_under_page_pressure():
    cfg, lm, params = _setup()
    # shared pool with fewer pages than slots*pages_per_slot forces eviction
    pcfg = PoolConfig(num_slots=3, page_size=4, pages_per_slot=10,
                      num_pages=12, quantized=False)
    eng = Engine(lm, params, EngineConfig(pool=pcfg), PLAN)
    rids = [eng.submit(p, max_new_tokens=14)
            for p in _prompts(cfg, 3, 8, 10)]
    res = eng.run()
    assert all(len(res[r].tokens) == 14 for r in rids)
    assert eng.summary()["preemptions"] >= 1


# ---------------------------------------------------------------------------
# (d) the decode step's pool traffic
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fused", [False, True])
def test_decode_step_updates_pool_in_place(fused):
    """The pool rides in the decode scan's carry: the compiled step holds no
    pool-shaped copy, broadcast, dynamic-slice or dynamic-update-slice, and
    its temporaries stay under one pool leaf. The pool is made large
    (4,096 pages) so one leaf outweighs every other temporary; a step that
    scans the pool as xs/ys holds about three leaves of temporaries."""
    from hlo_pool import decode_pool_report
    cfg, lm, params = _setup()
    assert all(sub.mixer_kind == "attn_gqa" for sub in lm.period)
    pcfg = PoolConfig(num_slots=4, page_size=4, pages_per_slot=16,
                      num_pages=4096, quantized=True)
    eng = Engine(lm, params, EngineConfig(pool=pcfg, fused_attention=fused),
                 PLAN)
    bad, temp, leaf = decode_pool_report(eng)
    assert not bad, "\n".join(bad)
    assert temp < leaf, (temp, leaf)


# ---------------------------------------------------------------------------
# unit: scheduler + sampling
# ---------------------------------------------------------------------------

def test_scheduler_page_accounting():
    pcfg = PoolConfig(num_slots=2, page_size=4, pages_per_slot=4)
    sched = Scheduler(pcfg)
    sched.submit(Request(prompt=[1] * 6, max_new_tokens=4))
    slot, st = sched.try_admit()
    assert sched.alloc.free_pages == pcfg.total_pages - 2  # 7 tokens -> 2 pages
    st.generated.append(1)
    st.last_token = 1
    while st.cur_len < 10:
        assert sched.ensure_page(slot)
        st.generated.append(1)
    sched.retire(slot)
    assert sched.alloc.free_pages == pcfg.total_pages
    assert (sched.page_table == pcfg.trash_page).all()


def test_pool_invariants_random_walks():
    """Deterministic seed sweep of the pool-isolation walker (the
    hypothesis property test in test_property.py drives the same walker
    with generated seeds; this keeps it exercised on bare environments)."""
    from pool_walk import run_pool_walk
    for seed in range(10):
        run_pool_walk(seed, steps=40)


def test_sampling_modes():
    key = jax.random.PRNGKey(0)
    logits = jnp.asarray(np.random.RandomState(0).randn(4, 50) * 3,
                         jnp.float32)
    # greedy rows (temp<=0) equal argmax regardless of other knobs
    toks = sample_tokens(logits, key,
                         jnp.zeros(4), jnp.zeros(4, jnp.int32), jnp.ones(4))
    np.testing.assert_array_equal(np.asarray(toks),
                                  np.asarray(jnp.argmax(logits, -1)))
    # top_k=1 is argmax even at high temperature
    toks = sample_tokens(logits, key, jnp.full((4,), 5.0),
                         jnp.ones(4, jnp.int32), jnp.ones(4))
    np.testing.assert_array_equal(np.asarray(toks),
                                  np.asarray(jnp.argmax(logits, -1)))
    # tiny top_p keeps only the head of the distribution
    toks = sample_tokens(logits, key, jnp.full((4,), 1.0),
                         jnp.zeros(4, jnp.int32), jnp.full((4,), 1e-6))
    np.testing.assert_array_equal(np.asarray(toks),
                                  np.asarray(jnp.argmax(logits, -1)))
    # samples stay in-vocab and per-slot streams differ from each other
    toks = sample_tokens(jnp.zeros((4, 50)), key, jnp.full((4,), 1.0),
                         jnp.zeros(4, jnp.int32), jnp.ones(4))
    assert ((np.asarray(toks) >= 0) & (np.asarray(toks) < 50)).all()
