"""Differential harness for the fused paged-attention decode kernel.

The jnp gather-then-attend path (``kv_cache.gather_slots`` +
``models/attention.py::gqa_attend``) is the numerics oracle; the fused
implementations (Pallas kernel in interpret mode, and the bit-locked jnp
page-scan the engine uses off-TPU) must agree with it:

(a) kernel vs oracle on synthetic pools: logits to float-roundoff over
    ragged ``cur_len``s, MHA/GQA/MQA head layouts, int8 + fp storage;
(b) kernel vs jnp page-scan (page_chunk=1): BIT-identical — same per-page
    online-softmax update order, so the two stay locked as kernels multiply;
(c) engine level: fused continuous-batched greedy decode is token-identical
    to the gather engine over staggered ragged requests (prompts and
    generations crossing page boundaries), in fp32 and int8 pools;
(d) preemption + resume under page pressure keeps fused == gather;
(e) MLA archs take the latent page walk in decode (its own kernel,
    ``kernels/mla_paged_attention.py``, differential tests in
    tests/test_mla_walk.py) and still match the gather path;
(f) q-block generalization (S query rows at positions lens..lens+S-1 with a
    per-row causal mask — chunked prefill / speculative verify): kernel vs
    oracle over S x heads x storage, BIT-locked to the jnp page-scan, and
    rank-3 decode == rank-4 S=1;
(g) the stacked pool: a call on the (L, P+1, page, Hkv, Dh) leaf with a
    ``layer`` index is BIT-identical to the per-layer call on
    ``data[layer]``, for both fused impls.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as C
from repro.kernels import paged_attention as PA
from repro.kernels.ops import paged_attention
from repro.models import build_lm, init_lm
from repro.models.attention import gqa_attend
from repro.serve import Engine, EngineConfig, PoolConfig
from repro.serve import kv_cache as KC
from repro.serve.kv_cache import PoolConfig as PC
from repro.sharding import ShardPlan

PLAN = ShardPlan(mesh=None)


# ---------------------------------------------------------------------------
# (a)+(b) kernel-level differential on synthetic pools
# ---------------------------------------------------------------------------

def _synthetic_pool(seed, *, b, pp, page, hkv, hq, dh, quantized):
    """Random paged pool + table + ragged lens; returns kernel args and the
    gather-reference args."""
    rng = np.random.RandomState(seed)
    total = b * pp                       # one page of slack per slot
    if quantized:
        kd = jnp.asarray(rng.randint(-128, 128, (total + 1, page, hkv, dh)),
                         jnp.int8)
        vd = jnp.asarray(rng.randint(-128, 128, (total + 1, page, hkv, dh)),
                         jnp.int8)
        ks = jnp.asarray(rng.randint(-6, 1, (b,)), jnp.float32)
        vs = jnp.asarray(rng.randint(-6, 1, (b,)), jnp.float32)
    else:
        kd = jnp.asarray(rng.randn(total + 1, page, hkv, dh), jnp.float32)
        vd = jnp.asarray(rng.randn(total + 1, page, hkv, dh), jnp.float32)
        ks = jnp.zeros((b,), jnp.float32)
        vs = jnp.zeros((b,), jnp.float32)
    table = jnp.asarray(rng.permutation(total).reshape(b, pp), jnp.int32)
    # ragged: first/mid/last positions incl. exact page boundaries
    lens = jnp.asarray(rng.randint(0, pp * page, (b,)), jnp.int32)
    lens = lens.at[0].set(0).at[-1].set(pp * page - 1)
    if b > 2:
        lens = lens.at[1].set(page)     # exactly one full page + boundary
    q = jnp.asarray(rng.randn(b, hq, dh), jnp.float32)
    return q, kd, vd, ks, vs, table, lens


def _gather_reference(q, kd, vd, ks, vs, table, lens, *, page, quantized):
    """The oracle: materialize every slot's dequantized view, full-softmax
    attend (gather_slots + gqa_attend semantics)."""
    from dataclasses import dataclass

    b, hq, dh = q.shape
    pp = table.shape[1]
    hkv = kd.shape[2]
    pcfg = PC(num_slots=b, page_size=page, pages_per_slot=pp,
              quantized=quantized)

    @dataclass
    class D:
        num_heads: int
        num_kv_heads: int
        head_dim: int
        real_heads: int

    k = KC.gather_slots(kd, ks, table, pcfg, jnp.float32)
    v = KC.gather_slots(vd, vs, table, pcfg, jnp.float32)
    out = gqa_attend(q[:, None], k, v, D(hq, hkv, dh, hq), lens[:, None])
    return out.reshape(b, hq, dh)


@pytest.mark.parametrize("hq,hkv", [(4, 4), (6, 2), (3, 1)])  # MHA/GQA/MQA
@pytest.mark.parametrize("quantized", [False, True])
def test_kernel_matches_gather_reference(hq, hkv, quantized):
    args = _synthetic_pool(0, b=4, pp=5, page=8, hkv=hkv, hq=hq, dh=16,
                           quantized=quantized)
    ref = _gather_reference(*args, page=8, quantized=quantized)
    out = PA.paged_attention_kernel(*args, page_size=8, quantized=quantized,
                                    interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("quantized", [False, True])
def test_kernel_bit_locked_to_jnp_page_scan(quantized):
    """page_chunk=1 page-scan replays the kernel's exact update order —
    the two fused implementations must agree BITWISE."""
    args = _synthetic_pool(1, b=3, pp=4, page=8, hkv=2, hq=4, dh=16,
                           quantized=quantized)
    kout = PA.paged_attention_kernel(*args, page_size=8,
                                     quantized=quantized, interpret=True)
    jout = PA.paged_attention_jnp(*args, page_size=8, quantized=quantized,
                                  page_chunk=1)
    np.testing.assert_array_equal(np.asarray(kout), np.asarray(jout))


def test_chunked_page_scan_matches_reference():
    """Larger page_chunks (the off-TPU perf setting, incl. a non-dividing
    chunk that pads the logical page axis with trash pointers) stay within
    float-roundoff of the oracle."""
    args = _synthetic_pool(2, b=4, pp=5, page=8, hkv=2, hq=4, dh=16,
                           quantized=True)
    ref = _gather_reference(*args, page=8, quantized=True)
    for chunk in (2, 3, 5):
        out = PA.paged_attention_jnp(*args, page_size=8, quantized=True,
                                     page_chunk=chunk)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)


def test_ops_wrapper_impl_selection():
    args = _synthetic_pool(3, b=2, pp=3, page=8, hkv=2, hq=4, dh=16,
                           quantized=True)
    a = paged_attention(*args, page_size=8, quantized=True, impl="pallas")
    b = paged_attention(*args, page_size=8, quantized=True, impl="jnp",
                        page_chunk=1)
    c = paged_attention(*args, page_size=8, quantized=True, impl="auto")
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_allclose(np.asarray(c), np.asarray(a),
                               rtol=1e-5, atol=1e-5)
    with pytest.raises(ValueError):
        paged_attention(*args, page_size=8, quantized=True, impl="nope")


def test_kernel_under_jit_and_scan():
    """The engine calls the kernel inside a jitted per-layer scan — the
    pallas_call must trace cleanly under both."""
    args = _synthetic_pool(4, b=2, pp=3, page=8, hkv=2, hq=4, dh=16,
                           quantized=True)
    q, kd, vd, ks, vs, table, lens = args
    f = jax.jit(functools.partial(PA.paged_attention_kernel, page_size=8,
                                  quantized=True, interpret=True))
    direct = f(q, kd, vd, ks, vs, table, lens)

    def body(carry, _):
        return carry, f(q, kd, vd, ks, vs, table, lens)

    _, scanned = jax.lax.scan(body, 0, jnp.arange(2))
    np.testing.assert_array_equal(np.asarray(direct), np.asarray(scanned[0]))
    np.testing.assert_array_equal(np.asarray(direct), np.asarray(scanned[1]))


# ---------------------------------------------------------------------------
# (f) q-block differential: S query rows per slot (chunked prefill /
#     speculative k-token verify) against the same oracles
# ---------------------------------------------------------------------------

def _synthetic_qblock(seed, *, b, pp, page, hkv, hq, dh, s, quantized):
    """Random paged pool + a (B, S, Hq, Dh) q-block whose rows sit at
    positions lens..lens+s-1 (every row within the slot horizon). lens
    still hits first/boundary/last-fitting positions."""
    q0, kd, vd, ks, vs, table, lens = _synthetic_pool(
        seed, b=b, pp=pp, page=page, hkv=hkv, hq=hq, dh=dh,
        quantized=quantized)
    rng = np.random.RandomState(seed + 100)
    hi = pp * page - s                  # last start where all rows fit
    lens = jnp.asarray(rng.randint(0, hi + 1, (b,)), jnp.int32)
    lens = lens.at[0].set(0).at[-1].set(hi)
    if b > 2:
        lens = lens.at[1].set(page - 1)     # rows straddle a page boundary
    q = jnp.asarray(rng.randn(b, s, hq, dh), jnp.float32)
    return q, kd, vd, ks, vs, table, lens


def _gather_reference_qblock(q, kd, vd, ks, vs, table, lens, *, page,
                             quantized):
    """Oracle: dequantized gather + full-softmax attend with per-row causal
    positions (row j of slot b attends cache positions <= lens[b]+j)."""
    from dataclasses import dataclass

    b, s, hq, dh = q.shape
    pp = table.shape[1]
    hkv = kd.shape[2]
    pcfg = PC(num_slots=b, page_size=page, pages_per_slot=pp,
              quantized=quantized)

    @dataclass
    class D:
        num_heads: int
        num_kv_heads: int
        head_dim: int
        real_heads: int

    k = KC.gather_slots(kd, ks, table, pcfg, jnp.float32)
    v = KC.gather_slots(vd, vs, table, pcfg, jnp.float32)
    positions = lens[:, None] + jnp.arange(s)[None]
    out = gqa_attend(q, k, v, D(hq, hkv, dh, hq), positions)
    return out.reshape(b, s, hq, dh)


@pytest.mark.parametrize("hq,hkv", [(4, 4), (6, 2), (3, 1)])  # MHA/GQA/MQA
@pytest.mark.parametrize("quantized", [False, True])
@pytest.mark.parametrize("s", [1, 4, 8])    # decode / spec-verify / chunk
def test_qblock_kernel_matches_gather_reference(hq, hkv, quantized, s):
    args = _synthetic_qblock(5, b=4, pp=5, page=8, hkv=hkv, hq=hq, dh=16,
                             s=s, quantized=quantized)
    ref = _gather_reference_qblock(*args, page=8, quantized=quantized)
    out = PA.paged_attention_kernel(*args, page_size=8, quantized=quantized,
                                    interpret=True)
    assert out.shape == args[0].shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("quantized", [False, True])
@pytest.mark.parametrize("s", [1, 4, 8])
def test_qblock_kernel_bit_locked_to_jnp_page_scan(quantized, s):
    """The q-block kernel and the page-chunk=1 jnp scan share one block
    update (``PA._block_update``) — BITWISE equal for every S."""
    args = _synthetic_qblock(6, b=3, pp=4, page=8, hkv=2, hq=4, dh=16,
                             s=s, quantized=quantized)
    kout = PA.paged_attention_kernel(*args, page_size=8,
                                     quantized=quantized, interpret=True)
    jout = PA.paged_attention_jnp(*args, page_size=8, quantized=quantized,
                                  page_chunk=1)
    np.testing.assert_array_equal(np.asarray(kout), np.asarray(jout))


@pytest.mark.parametrize("s", [3, 6])
def test_qblock_chunked_page_scan_matches_reference(s):
    args = _synthetic_qblock(7, b=4, pp=5, page=8, hkv=2, hq=4, dh=16,
                             s=s, quantized=True)
    ref = _gather_reference_qblock(*args, page=8, quantized=True)
    for chunk in (2, 3, 5):
        out = PA.paged_attention_jnp(*args, page_size=8, quantized=True,
                                     page_chunk=chunk)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("quantized", [False, True])
def test_qblock_rank3_equals_rank4_s1(quantized):
    """Rank-3 (B, Hq, Dh) decode queries are the S=1 q-block squeezed:
    both fused impls must return bitwise-identical values for both ranks."""
    q, kd, vd, ks, vs, table, lens = _synthetic_pool(
        8, b=3, pp=4, page=8, hkv=2, hq=4, dh=16, quantized=quantized)
    for fn in (functools.partial(PA.paged_attention_kernel, interpret=True),
               functools.partial(PA.paged_attention_jnp, page_chunk=1)):
        r3 = fn(q, kd, vd, ks, vs, table, lens, page_size=8,
                quantized=quantized)
        r4 = fn(q[:, None], kd, vd, ks, vs, table, lens, page_size=8,
                quantized=quantized)
        assert r3.shape == q.shape
        assert r4.shape == (3, 1, 4, 16)
        np.testing.assert_array_equal(np.asarray(r3),
                                      np.asarray(r4[:, 0]))


def test_qblock_rows_match_sequential_single_token_calls():
    """Row j of a q-block call equals an S=1 call issued at lens+j — the
    property that makes ONE verify call equivalent to k+1 sequential decode
    steps over the same pool."""
    s = 4
    args = _synthetic_qblock(9, b=3, pp=5, page=8, hkv=2, hq=4, dh=16,
                             s=s, quantized=True)
    q, kd, vd, ks, vs, table, lens = args
    blk = PA.paged_attention_kernel(*args, page_size=8, quantized=True,
                                    interpret=True)
    for j in range(s):
        row = PA.paged_attention_kernel(q[:, j], kd, vd, ks, vs, table,
                                        lens + j, page_size=8,
                                        quantized=True, interpret=True)
        np.testing.assert_allclose(np.asarray(blk[:, j]), np.asarray(row),
                                   rtol=1e-6, atol=1e-6)


def test_qblock_ops_wrapper_rank4():
    args = _synthetic_qblock(10, b=2, pp=3, page=8, hkv=2, hq=4, dh=16,
                             s=3, quantized=True)
    a = paged_attention(*args, page_size=8, quantized=True, impl="pallas")
    b = paged_attention(*args, page_size=8, quantized=True, impl="jnp",
                        page_chunk=1)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# (g) stacked pool + layer index == per-layer pool, bitwise
# ---------------------------------------------------------------------------

LAYERS = 3


@pytest.mark.parametrize("impl", ["pallas", "jnp"])
@pytest.mark.parametrize("layer", [0, LAYERS - 1])
@pytest.mark.parametrize("hq,hkv", [(4, 4), (6, 2), (3, 1)])  # MHA/GQA/MQA
@pytest.mark.parametrize("dtype", ["int8", "bfloat16"])
@pytest.mark.parametrize("s", [1, 4])       # decode / q-block
def test_stacked_pool_layer_index_equals_per_layer_call(impl, layer, hq, hkv,
                                                        dtype, s):
    """The decode step hands the page walk the whole stacked pool leaf and
    the scan's layer index; the walk must read exactly what the per-layer
    call reads from ``data[layer]``. Each layer of the stack holds other
    pages, so a wrong layer cannot pass."""
    quantized = dtype == "int8"
    layers = [_synthetic_qblock(20 + i, b=3, pp=4, page=8, hkv=hkv, hq=hq,
                                dh=16, s=s, quantized=quantized)
              for i in range(LAYERS)]
    q, _, _, ks, vs, table, lens = layers[layer]
    if s == 1:
        q = q[:, 0]                     # the engine's rank-3 decode query
    kd = jnp.stack([a[1] for a in layers]).astype(dtype)
    vd = jnp.stack([a[2] for a in layers]).astype(dtype)
    if impl == "pallas":
        fn = functools.partial(PA.paged_attention_kernel, interpret=True)
    else:
        fn = functools.partial(PA.paged_attention_jnp, page_chunk=1)
    kw = dict(page_size=8, quantized=quantized)
    per_layer = fn(q, kd[layer], vd[layer], ks, vs, table, lens, **kw)
    stacked = fn(q, kd, vd, ks, vs, table, lens, layer=jnp.int32(layer),
                 **kw)
    assert stacked.shape == q.shape
    np.testing.assert_array_equal(np.asarray(stacked), np.asarray(per_layer))


# ---------------------------------------------------------------------------
# (c)-(e) engine-level differential
# ---------------------------------------------------------------------------

def _setup(arch="internlm2-1.8b"):
    cfg = C.get_reduced(arch).replace(dtype="float32", remat="none")
    lm = build_lm(cfg)
    params = init_lm(jax.random.PRNGKey(0), lm)
    return cfg, lm, params


def _prompts(cfg, n, lo, hi, seed=7):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, cfg.vocab_size,
                        int(rng.randint(lo, hi + 1))).tolist()
            for _ in range(n)]


def _run_engine(lm, params, pcfg, prompts, gens, **ekw):
    eng = Engine(lm, params, EngineConfig(pool=pcfg, **ekw), PLAN)
    rids = [eng.submit(p, max_new_tokens=g) for p, g in zip(prompts, gens)]
    res = eng.run()
    return [res[r].tokens for r in rids], eng


@pytest.mark.parametrize("quantized", [False, True])
@pytest.mark.parametrize("impl", ["pallas", "jnp"])
def test_fused_engine_token_identical_to_gather(quantized, impl):
    """Staggered ragged requests on 2 slots; page_size 4 so prompts and
    generations cross several page boundaries mid-request."""
    cfg, lm, params = _setup()
    pcfg = PoolConfig(num_slots=2, page_size=4, pages_per_slot=8,
                      quantized=quantized)
    prompts = _prompts(cfg, 4, 5, 15)
    gens = [8, 5, 7, 6]
    ref, _ = _run_engine(lm, params, pcfg, prompts, gens)
    out, _ = _run_engine(lm, params, pcfg, prompts, gens,
                         fused_attention=True, fused_impl=impl)
    assert out == ref, (impl, quantized, out, ref)


@pytest.mark.parametrize("impl", ["pallas", "jnp"])
def test_fused_engine_after_preemption_and_resume(impl):
    """Shared pool smaller than slots*pages_per_slot forces preemption;
    the resumed (re-prefilled) requests must still match token-for-token."""
    cfg, lm, params = _setup()
    pcfg = PoolConfig(num_slots=3, page_size=4, pages_per_slot=10,
                      num_pages=12, quantized=False)
    prompts = _prompts(cfg, 3, 8, 10, seed=11)
    gens = [14, 14, 14]
    ref, ref_eng = _run_engine(lm, params, pcfg, prompts, gens)
    out, eng = _run_engine(lm, params, pcfg, prompts, gens,
                           fused_attention=True, fused_impl=impl)
    assert eng.summary()["preemptions"] >= 1
    assert ref_eng.summary()["preemptions"] >= 1
    assert out == ref


def test_mla_arch_takes_the_latent_walk():
    """deepseek-v2 (MLA) with the fused flag on: decode takes the latent
    page walk (``ops.mla_paged_attention``) and serves the gather path's
    greedy tokens; speculative verify keeps the gather path for MLA."""
    from repro.obs import registry
    cfg, lm, params = _setup("deepseek-v2-236b")
    assert any(sub.mixer_kind == "attn_mla" for sub in lm.period)
    pcfg = PoolConfig(num_slots=2, page_size=8, pages_per_slot=4,
                      quantized=False)
    prompts = _prompts(cfg, 2, 8, 12, seed=13)
    gens = [5, 6]
    ref, _ = _run_engine(lm, params, pcfg, prompts, gens)
    before = registry.snapshot("kernel.mla_paged_attention.").copy()
    out, eng = _run_engine(lm, params, pcfg, prompts, gens,
                           fused_attention=True)
    mla = [sub for sub in lm.period if sub.mixer_kind == "attn_mla"]
    assert all(eng._fused_for(sub) for sub in mla)
    assert not any(eng._fused_for(sub, verify=True) for sub in mla)
    assert eng.summary()["attention_paths"] == {
        "attn_mla": {"decode": "mla_paged_attention", "verify": "gather"}}
    assert registry.snapshot("kernel.mla_paged_attention.") != before
    assert out == ref


def test_fused_chunked_prefill_matches_whole_prompt():
    """Chunked prefill writes + fused decode reads coexist on one pool."""
    cfg, lm, params = _setup()
    pcfg = PoolConfig(num_slots=2, page_size=8, pages_per_slot=6,
                      quantized=True)
    prompt = _prompts(cfg, 1, 24, 24, seed=17)[0]
    outs = []
    for chunk in (0, 8):
        eng = Engine(lm, params,
                     EngineConfig(pool=pcfg, prefill_chunk=chunk,
                                  fused_attention=True), PLAN)
        rid = eng.submit(prompt, max_new_tokens=6)
        outs.append(eng.run()[rid].tokens)
    assert outs[0] == outs[1]
