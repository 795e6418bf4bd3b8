"""repro.numerics acceptance tests:

(a) reference and Pallas codec backends are BIT-IDENTICAL (codes, decode,
    fake-quant) on pow2 and blockwise specs, with no caller-side padding,
(b) NumericsPolicy round-trips through JSON (incl. the QuantConfig
    back-compat constructor),
(c) grad-accum with grad_compress=True has the same residual semantics as
    the non-accum step (the bug this PR fixed),
(d) MoE router masking: masked (inactive-slot) tokens cannot consume
    expert capacity.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import numerics as N
from repro.configs.base import MoEConfig, ModelConfig, QuantConfig, TrainConfig

# ---------------------------------------------------------------------------
# (a) cross-backend bit-identity
# ---------------------------------------------------------------------------

POW2_SHAPES = [(7,), (37, 130), (3, 5, 33)]


@pytest.mark.parametrize("shape", POW2_SHAPES)
@pytest.mark.parametrize("bits", [4, 8, 16])
def test_pow2_backends_bit_identical(shape, bits):
    spec = N.QuantSpec("pow2", bits, 0, "int8" if bits <= 8 else "int16",
                       "fixed")
    x = jax.random.normal(jax.random.PRNGKey(0), shape) * 5
    step = jnp.asarray(-3.0)
    qr = N.encode(x, spec, step, backend="reference")
    qp = N.encode(x, spec, step, backend="pallas")
    np.testing.assert_array_equal(np.asarray(qr.codes), np.asarray(qp.codes))
    np.testing.assert_array_equal(np.asarray(N.decode(qr)),
                                  np.asarray(N.decode(qp, backend="pallas")))
    fr = N.fake_quant(x, spec, step, backend="reference")
    fp = N.fake_quant(x, spec, step, backend="pallas")
    np.testing.assert_array_equal(np.asarray(fr), np.asarray(fp))


@pytest.mark.parametrize("shape,block", [((1000,), 256), ((5, 777), 256),
                                         ((2, 3, 50), 16), ((4096,), 1024)])
def test_blockwise_backends_bit_identical(shape, block):
    spec = N.QuantSpec("blockwise", 8, block, "int8", "per_tensor_max")
    x = jax.random.normal(jax.random.PRNGKey(1), shape) * 9
    qr = N.encode(x, spec, backend="reference")
    qp = N.encode(x, spec, backend="pallas")
    np.testing.assert_array_equal(np.asarray(qr.codes), np.asarray(qp.codes))
    np.testing.assert_array_equal(np.asarray(qr.scale), np.asarray(qp.scale))
    np.testing.assert_array_equal(np.asarray(N.decode(qr)),
                                  np.asarray(N.decode(qp, backend="pallas")))


# Every (data, scale) layout the KV pool feeds the pow2 codec (see
# serve/kv_cache.py): write_prefill (L, S, *feat) w/ per-layer (L, 1)
# scales, append_token (B, *feat) w/ (B, 1...) scales, gather_slots
# (B, max_len, *feat) w/ (B, 1...) scales; feat is (Hkv, Dh) for GQA and
# (rank,) / (rope,) for MLA. Plus a (L, S) per-(layer, slot) grid.
KV_POOL_SCALE_SHAPES = [
    ((3, 24, 2, 8), (3, 1)),            # write_prefill, GQA feat
    ((3, 24, 16), (3, 1)),              # write_prefill, MLA c_kv feat
    ((4, 2, 8), (4, 1, 1)),             # append_token, GQA feat
    ((4, 16), (4, 1)),                  # append_token, MLA feat
    ((4, 32, 2, 8), (4, 1, 1, 1)),      # gather/decode, GQA feat
    ((4, 32, 16), (4, 1, 1)),           # gather/decode, MLA feat
    ((3, 5, 2, 8, 4), (3, 5)),          # per-(layer, slot) scale grid
]


@pytest.mark.parametrize("xshape,sshape", KV_POOL_SCALE_SHAPES)
def test_pow2_multiscale_bit_identity_no_fallback(xshape, sshape):
    """The vectorized multi-scale Pallas pow2 kernels are BIT-identical to
    the reference for every KV-pool scale layout — and none of these calls
    may take the reference fallback (the gap this closes: non-scalar scales
    used to silently drop to the reference codec)."""
    from repro.numerics import pallas_backend as PB
    spec = N.QuantSpec("pow2", 8, 0, "int8", "per_tensor_max")
    x = jax.random.normal(jax.random.PRNGKey(5), xshape) * 4
    sc = jnp.asarray(np.random.RandomState(6).randint(-6, 2, sshape),
                     jnp.float32)
    PB.reset_fallback_count()
    qr = N.encode(x, spec, sc, backend="reference")
    qp = N.encode(x, spec, sc, backend="pallas")
    np.testing.assert_array_equal(np.asarray(qr.codes), np.asarray(qp.codes))
    np.testing.assert_array_equal(np.asarray(N.decode(qr)),
                                  np.asarray(N.decode(qp, backend="pallas")))
    assert PB.fallback_count() == 0, \
        "KV-pool-shaped scales must run the vectorized kernel natively"


# int4x2 packed storage: two codes per byte along the trailing dim (the
# tt_factor deploy format). Odd trailing dims carry one zero pad nibble.
INT4X2_CASES = [
    ((7,), None),                        # 1-D, odd
    ((6,), None),                        # 1-D, even
    ((5, 9), None),                      # odd trailing
    ((4, 130), (4, 1)),                  # per-row scales
    ((3, 4, 11), (3, 1)),                # per-layer scales, odd trailing
    ((2, 3, 6), (2, 3)),                 # per-(layer, slot) grid
]


@pytest.mark.parametrize("shape,sshape", INT4X2_CASES)
def test_int4x2_roundtrip_bit_identity_no_fallback(shape, sshape):
    """Packed int4 pack/unpack round-trip: reference and Pallas backends
    bit-identical (codes AND decode), packed codes are exactly
    ceil(last/2) bytes per row, values identical to the unpacked int8
    4-bit spec, and no call drops to the reference fallback."""
    from repro.numerics import pallas_backend as PB
    spec = N.QuantSpec("pow2", 4, 0, "int4x2", "fixed")
    x = jax.random.normal(jax.random.PRNGKey(11), shape) * 0.5
    sc = jnp.asarray(-3.0) if sshape is None else jnp.asarray(
        np.random.RandomState(3).randint(-5, 0, sshape), jnp.float32)
    PB.reset_fallback_count()
    qr = N.encode(x, spec, sc, backend="reference")
    qp = N.encode(x, spec, sc, backend="pallas")
    assert qr.codes.dtype == jnp.int8
    assert qr.codes.shape == shape[:-1] + (-(-shape[-1] // 2),)
    np.testing.assert_array_equal(np.asarray(qr.codes), np.asarray(qp.codes))
    dr = N.decode(qr)
    np.testing.assert_array_equal(np.asarray(dr),
                                  np.asarray(N.decode(qp, backend="pallas")))
    assert PB.fallback_count() == 0, \
        "packed codec must run the Pallas kernels natively"
    # cross-spec: same VALUES as the unpacked int8-stored 4-bit spec
    unpacked = N.QuantSpec("pow2", 4, 0, "int8", "fixed")
    np.testing.assert_array_equal(
        np.asarray(dr), np.asarray(N.decode(N.encode(x, unpacked, sc))))
    # nbytes halves (modulo the scale metadata)
    assert qr.nbytes() <= N.encode(x, unpacked, sc).nbytes() // 2 + 4 + \
        np.asarray(sc).nbytes


def test_int4x2_pack_unpack_exact():
    """pack/unpack primitives: exact inverse over the full nibble range,
    pad nibble lands in the high half of the last byte."""
    from repro.numerics.codecs import pack_int4, unpack_int4
    q = jnp.asarray([[-8, -1, 0, 7, 3], [1, 2, -3, 4, -5]], jnp.int32)
    p = pack_int4(q)
    assert p.shape == (2, 3) and p.dtype == jnp.int8
    np.testing.assert_array_equal(np.asarray(unpack_int4(p, 5)),
                                  np.asarray(q))
    # odd trailing dim: high nibble of the last byte is the zero pad
    assert (np.asarray(p)[:, -1].astype(np.int32) & 0xF0 == 0).all()


def test_int4x2_spec_validation():
    with pytest.raises(ValueError):
        N.QuantSpec("pow2", 8, 0, "int4x2")          # nibble can't hold 8 bits
    with pytest.raises(ValueError):
        N.QuantSpec("blockwise", 4, 64, "int4x2")    # pow2 only
    spec = N.QuantSpec("pow2", 4, 0, "int4x2")
    assert spec.packed and spec.jnp_storage == jnp.dtype(jnp.int8)
    assert N.QuantSpec.from_json_dict(spec.to_json_dict()) == spec
    # analytic accounting counts two codes per byte
    assert N.spec_nbytes(spec, (4, 9)) == 4 * 5 + 4
    # 0-d tensors pack as one nibble + one pad nibble on both backends
    for backend in N.BACKENDS:
        qt = N.encode(jnp.asarray(0.5), spec, jnp.asarray(-3.0),
                      backend=backend)
        assert qt.codes.shape == (1,) and qt.shape == ()
        assert float(N.decode(qt)) == 0.5      # 4 * 2^-3: exact on the grid


def test_int4x2_hypothesis_roundtrip():
    """Property form of the round-trip over random shapes (odd/even
    trailing dims) and scale layouts."""
    hyp = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st
    from repro.numerics import pallas_backend as PB
    spec = N.QuantSpec("pow2", 4, 0, "int4x2", "fixed")

    @settings(max_examples=25, deadline=None)
    @given(lead=st.integers(1, 5), last=st.integers(1, 17),
           per_row=st.booleans(), seed=st.integers(0, 2 ** 16))
    def check(lead, last, per_row, seed):
        x = jax.random.normal(jax.random.PRNGKey(seed), (lead, last)) * 0.5
        sc = jnp.asarray(
            np.random.RandomState(seed).randint(-5, 0, (lead, 1)),
            jnp.float32) if per_row else jnp.asarray(-3.0)
        PB.reset_fallback_count()
        qr = N.encode(x, spec, sc, backend="reference")
        qp = N.encode(x, spec, sc, backend="pallas")
        np.testing.assert_array_equal(np.asarray(qr.codes),
                                      np.asarray(qp.codes))
        np.testing.assert_array_equal(
            np.asarray(N.decode(qr)),
            np.asarray(N.decode(qp, backend="pallas")))
        assert qr.codes.shape == (lead, -(-last // 2))
        assert PB.fallback_count() == 0

    check()


def test_pow2_fake_quant_shares_leading_dim_convention():
    """One scale convention across all three codec ops: a per-layer (L, 1)
    scale means the same thing to fake_quant as to encode/decode (leading-
    dim broadcast), on both backends. Before the fix fake_quant applied
    numpy trailing-dim alignment and raised (or silently mis-scaled) on
    exactly the shapes encode accepts."""
    spec = N.QuantSpec("pow2", 8)
    x = jax.random.normal(jax.random.PRNGKey(9), (3, 6, 4)) * 2
    sc = jnp.asarray([[-3.0], [-2.0], [0.0]])               # (L, 1)
    fq = N.fake_quant(x, spec, sc)
    rt = N.decode(N.encode(x, spec, sc), jnp.float32)
    np.testing.assert_array_equal(np.asarray(fq), np.asarray(rt))
    np.testing.assert_array_equal(
        np.asarray(fq), np.asarray(N.fake_quant(x, spec, sc,
                                                backend="pallas")))


def test_pow2_nonconforming_scale_still_falls_back():
    """A scale that does not follow the leading-dim broadcast convention is
    routed to the reference codec and the fallback counter records it (the
    differential harness relies on the counter to prove native coverage)."""
    from repro.numerics import pallas_backend as PB
    spec = N.QuantSpec("pow2", 8)
    x = jax.random.normal(jax.random.PRNGKey(7), (4, 6))
    PB.reset_fallback_count()
    with pytest.raises(Exception):
        # (3,) matches no leading dim of (4, 6): the reference cannot
        # broadcast it either — but the fallback must be taken (counted)
        # before the reference raises
        N.encode(x, spec, jnp.zeros((3,)), backend="pallas")
    assert PB.fallback_count() == 1


def test_kv_cache_pool_quant_no_fallback(monkeypatch):
    """serve/kv_cache quantize/dequantize with pool-shaped per-slot scales
    route through the native multi-scale kernels when the pallas backend is
    selected, bit-identical to the default reference path."""
    from repro.numerics import pallas_backend as PB
    from repro.serve import kv_cache as KC
    x = jax.random.normal(jax.random.PRNGKey(8), (3, 8, 2, 4)) * 2
    sc = KC.choose_scale_log2(x, jnp.ones((8,), bool), 8)       # (3,)
    # reference side must really be the reference backend, even when the
    # whole process runs under the CI kernel-validation env
    monkeypatch.delenv("JAX_PALLAS_INTERPRET", raising=False)
    assert KC.codec_backend() == "reference" or \
        jax.default_backend() == "tpu"
    ref_codes = KC.quantize(x, sc[:, None], 8)
    ref_deq = KC.dequantize(ref_codes, sc[:, None], jnp.float32)
    monkeypatch.setenv("JAX_PALLAS_INTERPRET", "1")
    assert KC.codec_backend() == "pallas"
    PB.reset_fallback_count()
    codes = KC.quantize(x, sc[:, None], 8)
    deq = KC.dequantize(codes, sc[:, None], jnp.float32)
    assert PB.fallback_count() == 0
    np.testing.assert_array_equal(np.asarray(codes), np.asarray(ref_codes))
    np.testing.assert_array_equal(np.asarray(deq), np.asarray(ref_deq))


def test_pallas_fake_quant_has_clipped_ste():
    spec = N.QuantSpec("pow2", 4)
    x = jnp.asarray([-0.3, 0.0, 0.4, 50.0, -50.0])
    g = jax.grad(lambda v: jnp.sum(
        N.fake_quant(v, spec, jnp.asarray(-4.0), backend="pallas")))(x)
    # scale 2^-4: representable |x| <= 8*2^-4 = 0.5
    assert float(g[0]) == 1.0 and float(g[2]) == 1.0
    assert float(g[3]) == 0.0 and float(g[4]) == 0.0


def test_pallas_fake_quant_multiscale_no_fallback():
    """Non-scalar (rowwise-conforming) scales route fake_quant through the
    fused Pallas kernel — bit-identical values AND gradients (clipped STE)
    vs the reference, with zero reference fallbacks. Before the fix every
    non-scalar scale silently dropped to the reference codec."""
    from repro.numerics import pallas_backend as PB
    spec = N.QuantSpec("pow2", 8)
    x = jax.random.normal(jax.random.PRNGKey(12), (4, 6, 8)) * 6
    # row 0 (scale 2^-3) represents |x| <= 127/8: one element far past it,
    # so the clipped side of the STE mask is exercised whatever the draw
    x = x.at[0, 0, 0].set(40.0)
    sc = jnp.asarray([[-3.0], [-1.0], [0.0], [2.0]])            # (L, 1)
    PB.reset_fallback_count()
    fp = N.fake_quant(x, spec, sc, backend="pallas")
    assert PB.fallback_count() == 0, \
        "leading-dim scales must run the fused rowwise kernel natively"
    fr = N.fake_quant(x, spec, sc, backend="reference")
    np.testing.assert_array_equal(np.asarray(fp), np.asarray(fr))
    # gradients: clipped straight-through mask, identical across backends
    gp = jax.grad(lambda v: jnp.sum(
        N.fake_quant(v, spec, sc, backend="pallas")))(x)
    gr = jax.grad(lambda v: jnp.sum(
        N.fake_quant(v, spec, sc, backend="reference")))(x)
    np.testing.assert_array_equal(np.asarray(gp), np.asarray(gr))
    assert set(np.unique(np.asarray(gp))) <= {0.0, 1.0}
    assert 0.0 in np.asarray(gp) and 1.0 in np.asarray(gp)


def test_pallas_kernel_pads_internally():
    """The old kernel asserted exact (bm, bn) multiples; any shape works now."""
    from repro.kernels.quantize import quantize
    x = jax.random.normal(jax.random.PRNGKey(2), (37, 130))
    out = quantize(x, jnp.asarray(-3.0), 8)
    assert out.shape == x.shape
    ref = N.fake_quant(x, N.QuantSpec("pow2", 8), jnp.asarray(-3.0))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_qtensor_nbytes_and_pytree():
    spec = N.QuantSpec("blockwise", 8, 256)
    x = jax.random.normal(jax.random.PRNGKey(3), (16, 512))
    qt = N.encode(x, spec)
    assert qt.nbytes() == 16 * 512 + 16 * 2 * 4          # codes + scales
    assert qt.nbytes() < x.nbytes / 3.5
    # pytree: map/flatten preserve the container and its static aux
    qt2 = jax.tree.map(lambda a: a, qt)
    assert isinstance(qt2, N.QTensor) and qt2.spec == spec
    np.testing.assert_allclose(np.asarray(qt.dequantize()), np.asarray(x),
                               atol=float(qt.scale.max()) + 1e-6)


# ---------------------------------------------------------------------------
# (b) policy JSON round-trip
# ---------------------------------------------------------------------------

def test_policy_json_roundtrip():
    pol = N.NumericsPolicy(enable=True)
    assert N.NumericsPolicy.from_json(pol.to_json()) == pol
    # plain-dict path (what a config file would store)
    d = json.loads(json.dumps(pol.to_json_dict()))
    assert N.NumericsPolicy.from_json_dict(d) == pol


def test_quant_config_is_policy_constructor():
    qc = QuantConfig(enable=True, weight_bits=4, act_bits=8, grad_bits=16)
    pol = qc.policy()
    assert pol.enable
    assert pol.spec_for("tt_factor").bits == 4
    assert pol.spec_for("activation").bits == 8
    assert pol.spec_for("grad_edge").bits == 16
    assert pol.spec_for("optimizer_moment").kind == "blockwise"
    assert pol.spec_for("dp_wire").block == 1024
    assert pol.spec_for("kv_cache").scale_policy == "per_tensor_max"
    assert set(pol.managed_sites()) == {"activation", "grad_edge"}
    assert N.NumericsPolicy.from_json(pol.to_json()) == pol


def test_policy_sites_cover_all_known_sites():
    pol = N.NumericsPolicy()
    for site in N.SITES:
        assert pol.spec_for(site) is not None
    with pytest.raises(KeyError):
        pol.spec_for("nonexistent")


def test_all_sites_share_one_codec_registry():
    """The acceptance claim: every site's spec resolves to a registered
    codec on both backends."""
    pol = N.NumericsPolicy(enable=True)
    for site in N.SITES:
        for backend in N.BACKENDS:
            assert N.get_codec(pol.spec_for(site), backend) is not None


# ---------------------------------------------------------------------------
# (c) grad-accum residual semantics == non-accum step
# ---------------------------------------------------------------------------

def _tiny_lm():
    from repro.models import build_lm, init_lm
    cfg = ModelConfig(name="t", num_layers=1, d_model=32, num_heads=2,
                      num_kv_heads=2, d_ff=64, vocab_size=64,
                      remat="none", dtype="float32")
    lm = build_lm(cfg)
    params = init_lm(jax.random.PRNGKey(0), lm)
    return cfg, lm, params


def test_grad_accum_matches_non_accum_with_compression():
    """n_micro=1 grad-accum must be the SAME update as the plain step:
    compression applied, residual carried (the fixed bug: it silently
    dropped both)."""
    from repro.launch.steps import (init_train_state,
                                    make_grad_accum_train_step,
                                    make_train_step)
    from repro.sharding import ShardPlan
    cfg, lm, params = _tiny_lm()
    tcfg = TrainConfig(total_steps=5, warmup_steps=1, grad_compress=True)
    plan = ShardPlan(mesh=None)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 64),
             "labels": jax.random.randint(jax.random.PRNGKey(2), (2, 16), 0, 64)}
    step = jax.jit(make_train_step(lm, plan, tcfg))
    astep = jax.jit(make_grad_accum_train_step(lm, plan, tcfg, 1))
    s0 = init_train_state(params, tcfg)
    s1, m1 = step(s0, batch)
    s2, m2 = astep(s0, jax.tree.map(lambda a: a[None], batch))
    assert s2.residual is not None
    for a, b in zip(jax.tree_util.tree_leaves(s1.params),
                    jax.tree_util.tree_leaves(s2.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6)
    for a, b in zip(s1.residual, s2.residual):
        if a is None:
            assert b is None
            continue
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                               rtol=1e-6)


def test_grad_accum_error_feedback_accumulates():
    """Residual must change step over step (error feedback is live) and
    feed back into the next update."""
    from repro.launch.steps import init_train_state, make_grad_accum_train_step
    from repro.sharding import ShardPlan
    cfg, lm, params = _tiny_lm()
    tcfg = TrainConfig(total_steps=5, warmup_steps=1, grad_compress=True)
    astep = jax.jit(make_grad_accum_train_step(lm, ShardPlan(mesh=None),
                                               tcfg, 2))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(3), (2, 2, 16), 0, 64),
             "labels": jax.random.randint(jax.random.PRNGKey(4), (2, 2, 16), 0, 64)}
    state = init_train_state(params, tcfg)
    state, _ = astep(state, batch)
    r1 = [np.asarray(r) for r in state.residual if r is not None]
    state, _ = astep(state, batch)
    r2 = [np.asarray(r) for r in state.residual if r is not None]
    assert any(np.abs(a - b).max() > 0 for a, b in zip(r1, r2))
    assert any(np.abs(r).max() > 0 for r in r2)


# ---------------------------------------------------------------------------
# (d) MoE router masking
# ---------------------------------------------------------------------------

def test_moe_mask_prevents_capacity_theft():
    """Junk (masked) tokens must not displace real tokens from expert
    capacity: with the mask on, the real tokens' outputs are independent
    of the junk tokens' content."""
    from repro.models.common import apply_site
    from repro.models.moe import make_moe, init_moe, moe_forward
    cfg = ModelConfig(name="m", num_layers=1, d_model=32, num_heads=2,
                      num_kv_heads=2, d_ff=64, vocab_size=64, dtype="float32",
                      # tight capacity (8 slots/expert, 16 tokens wanting
                      # k=2 experts each) so junk that the router sends to
                      # one expert with weight 1 fills that expert when
                      # unmasked
                      moe=MoEConfig(num_experts=2, top_k=2,
                                    capacity_factor=0.5))
    d = make_moe(cfg)
    p = init_moe(jax.random.PRNGKey(0), d, cfg)
    b, s = 1, 16
    x = jax.random.normal(jax.random.PRNGKey(1), (b, s, 32))
    # half the tokens are "inactive slots" carrying junk. Junk is built
    # along the router's expert-0-minus-expert-1 direction, 60 logits past
    # any real token: the router gives it weight exactly 1.0 (f32) for
    # expert 0 (junk_a) or expert 1 (junk_b), so unmasked junk takes all 8
    # slots of that expert from the real tokens — by construction, not by
    # the draw.
    def gap(v):
        lg = apply_site(p["router"], v[None], d.router, cfg)[0]
        return lg[0] - lg[1]

    u = jax.grad(gap)(jnp.zeros(32))
    push = (60.0 + jnp.abs(jax.vmap(gap)(x[0])).max()) * u / jnp.dot(u, u)
    mask = jnp.asarray([True] * 8 + [False] * 8)[None]
    junk_a = x.at[:, 8:].add(push)
    junk_b = x.at[:, 8:].add(-push)

    out_a, _ = moe_forward(p, junk_a, d, cfg, token_mask=mask)
    out_b, _ = moe_forward(p, junk_b, d, cfg, token_mask=mask)
    # real tokens: identical regardless of junk content
    np.testing.assert_allclose(np.asarray(out_a[:, :8]),
                               np.asarray(out_b[:, :8]),
                               rtol=1e-5, atol=1e-5)
    # masked tokens contribute nothing (zero combine weight)
    np.testing.assert_allclose(np.asarray(out_a[:, 8:]), 0.0, atol=1e-6)

    # sanity: WITHOUT the mask the junk steals capacity -> real tokens keep
    # only expert 1 under junk_a and only expert 0 under junk_b (the
    # pre-fix behavior)
    noma, _ = moe_forward(p, junk_a, d, cfg)
    nomb, _ = moe_forward(p, junk_b, d, cfg)
    assert np.abs(np.asarray(noma[:, :8]) - np.asarray(nomb[:, :8])).max() \
        > 1e-4


def test_moe_all_active_mask_is_identity():
    """An all-true mask must not change routing (serve fp32 parity)."""
    from repro.models.moe import make_moe, init_moe, moe_forward
    cfg = ModelConfig(name="m", num_layers=1, d_model=32, num_heads=2,
                      num_kv_heads=2, d_ff=64, vocab_size=64, dtype="float32",
                      moe=MoEConfig(num_experts=4, top_k=2))
    d = make_moe(cfg)
    p = init_moe(jax.random.PRNGKey(0), d, cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 32))
    out0, aux0 = moe_forward(p, x, d, cfg)
    out1, aux1 = moe_forward(p, x, d, cfg,
                             token_mask=jnp.ones((2, 8), bool))
    np.testing.assert_array_equal(np.asarray(out0), np.asarray(out1))
    np.testing.assert_allclose(float(aux0), float(aux1), rtol=1e-6)


# ---------------------------------------------------------------------------
# unified-site regression: the five migrated call sites hit the codecs
# ---------------------------------------------------------------------------

def test_adam_int8_state_is_qtensor():
    from repro.optim import adam as A
    p = {"w": jax.random.normal(jax.random.PRNGKey(1), (4, 300))}
    st = A.init_adam(p, TrainConfig(opt_state_dtype="int8"))
    (m,) = [m for m in st.m if m is not None]
    assert isinstance(m, N.QTensor)
    assert m.spec.kind == "blockwise" and m.spec.block == A.BLOCK
    # shape-preserving: leading dims match the param's
    assert m.codes.shape[:-1] == (4,)


def test_engine_pool_numerics_follow_policy():
    """EngineConfig.policy: the kv_cache site owns the pool's numerics."""
    import repro.configs as C
    from repro.models import build_lm, init_lm
    from repro.serve import Engine, EngineConfig, PoolConfig
    from repro.sharding import ShardPlan
    cfg = C.get_reduced("internlm2-1.8b").replace(dtype="float32",
                                                  remat="none")
    lm = build_lm(cfg)
    params = init_lm(jax.random.PRNGKey(0), lm)
    pol = N.NumericsPolicy(enable=True)
    eng = Engine(lm, params,
                 EngineConfig(pool=PoolConfig(num_slots=2, quantized=False),
                              policy=pol), ShardPlan(mesh=None))
    assert eng.pcfg.quantized and eng.pcfg.bits == \
        pol.spec_for("kv_cache").bits
    assert eng.pcfg.spec == pol.spec_for("kv_cache")
    leaf = next(iter(next(iter(eng.pool["data"].values())).values()))
    assert leaf.dtype == jnp.int8


def test_kv_cache_quant_routes_through_codec():
    from repro.serve import kv_cache as KC
    pcfg = KC.PoolConfig(num_slots=2, quantized=True)
    assert pcfg.spec == N.QuantSpec("pow2", 8, 0, "int8", "per_tensor_max")
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 8, 4)) * 2
    valid = jnp.ones((8,), bool)
    sc = KC.choose_scale_log2(x, valid, 8)
    codes = KC.quantize(x, sc[:, None], 8)
    deq = KC.dequantize(codes, sc[:, None], jnp.float32)
    step = np.exp2(np.asarray(sc)).reshape(3, 1, 1)
    assert codes.dtype == jnp.int8
    assert (np.abs(np.asarray(deq) - np.asarray(x)) <= step / 2 + 1e-6).all()
