"""Compile rehearsal for a TPU v5e that is described, not attached.

The main-path Pallas kernels are compiled for one chip of a ``v5e:2x2``
topology at the serve smoke's widths (internlm2-1.8b: 8 slots, 16 query /
8 KV heads, head dim 128, pages of 16): the fused paged-attention kernel,
the KV pool's row-scale codec, and PE1–PE3; the latent page walk at
Moonlight's widths, alone and inside the decode step. Each compiled program must
contain the kernel (``tpu_custom_call``). Nothing runs: this catches what
the TPU compiler refuses (unsupported layouts, block shapes, VMEM use)
without chip time.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and the fixture skips
where it cannot be described. The codec and PE wrappers choose interpret
mode from ``jax.default_backend()`` (the CPU here), so the tests switch the
names those wrappers call to compiled mode.
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels import paged_attention as PA
from repro.numerics import QuantSpec, get_codec, pallas_backend

B, HQ, HKV, DH, PAGE, PP = 8, 16, 8, 128, 16, 19
POOL_PAGES = B * PP + 1
LAYERS = 24


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "can't describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_mode(monkeypatch):
    monkeypatch.setattr(pallas_backend, "_interpret", lambda: False)
    monkeypatch.setattr(ops, "_interpret", lambda: False)


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("dtype", [jnp.int8, jnp.bfloat16])
@pytest.mark.parametrize("q_rows", [1, 5])
def test_paged_attention_kernel(one_chip, q_rows, dtype):
    kern = functools.partial(PA.paged_attention_kernel, page_size=PAGE,
                             quantized=dtype == jnp.int8, interpret=False)
    page = ((POOL_PAGES, PAGE, HKV, DH), dtype)
    _compile(kern, one_chip, ((B, q_rows, HQ, DH), jnp.bfloat16), page, page,
             ((B,), jnp.float32), ((B,), jnp.float32), ((B, PP), jnp.int32),
             ((B,), jnp.int32))


@pytest.mark.parametrize("dtype", [jnp.int8, jnp.bfloat16])
@pytest.mark.parametrize("q_rows", [1, 5])
def test_paged_attention_kernel_stacked_pool(one_chip, q_rows, dtype):
    """The decode step's form: the stacked (L, P+1, page, Hkv, Dh) pool
    leaf and a layer index, read through the page BlockSpec's index map."""
    kern = functools.partial(PA.paged_attention_kernel, page_size=PAGE,
                             quantized=dtype == jnp.int8, interpret=False)
    page = ((LAYERS, POOL_PAGES, PAGE, HKV, DH), dtype)
    _compile(lambda q, k, v, ks, vs, t, n, layer: kern(
                 q, k, v, ks, vs, t, n, layer=layer),
             one_chip, ((B, q_rows, HQ, DH), jnp.bfloat16), page, page,
             ((B,), jnp.float32), ((B,), jnp.float32), ((B, PP), jnp.int32),
             ((B,), jnp.int32), ((), jnp.int32))


# Moonlight's latent attention: 16 heads, latent 512, rope 64 (packed two
# tokens to a 128-lane row), int8 pages of 16, 160 pages a slot, 32 slots,
# the stacked leaf of 27 layers
MLA_B, MLA_H, MLA_DC, MLA_DR, MLA_PP, MLA_L = 32, 16, 512, 64, 160, 27
MLA_POOL = MLA_B * MLA_PP + 1
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_SCOPE = re.compile(r"(?:^|/)(repro\.ops\.[A-Za-z0-9_]+)(?:/|$)")


def _assert_walk_scoped(text):
    """Every kernel of a compiled program runs under the latent attention's
    scope, innermost, as the benchmark's scope map reads it."""
    calls = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert calls
    for ln in calls:
        m = _OP_NAME.search(ln)
        assert m and _SCOPE.findall(m.group(1))[-1:] == [
            "repro.ops.mla_attention"], ln[:300]


@pytest.mark.parametrize("dtype", [jnp.int8, jnp.bfloat16])
def test_mla_paged_attention_kernel(one_chip, compiled_mode, dtype):
    """The latent walk at Moonlight's widths, through the op the engine
    calls: compiled, and named for the benchmark's scope map."""
    walk = functools.partial(
        ops.mla_paged_attention, page_size=PAGE,
        quantized=dtype == jnp.int8, sm_scale=192 ** -0.5, impl="pallas")
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in (
        ((MLA_B, MLA_H, MLA_DC), jnp.bfloat16),
        ((MLA_B, MLA_H, MLA_DR), jnp.bfloat16),
        ((MLA_L, MLA_POOL, PAGE, MLA_DC), dtype),
        ((MLA_L, MLA_POOL, PAGE * MLA_DR // 128, 128), dtype),
        ((MLA_B,), jnp.float32), ((MLA_B,), jnp.float32),
        ((MLA_B, MLA_PP), jnp.int32), ((MLA_B,), jnp.int32),
        ((MLA_B,), jnp.bool_), ((), jnp.int32))]
    compiled = jax.jit(walk).lower(*args).compile()
    _assert_walk_scoped(compiled.as_text())
    # the pool leaves go to the kernel as they are: no temporaries
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 20


SPEC = QuantSpec("pow2", 8, 0, "int8", "per_tensor_max")


@pytest.mark.parametrize("site,shape,scale", [
    # decode append: one token per slot, one scale per slot
    ("append", (B, HKV, DH), (B, 1, 1)),
    # whole-prompt prefill write: every layer, one scale per layer
    ("prefill", (LAYERS, 256, HKV, DH), (LAYERS, 1)),
])
def test_kv_codec_encode(one_chip, compiled_mode, site, shape, scale):
    codec = get_codec(SPEC, "pallas")
    _compile(lambda x, s: codec.encode(x, SPEC, s).codes, one_chip,
             (shape, jnp.bfloat16), (scale, jnp.float32))


def test_kv_codec_decode(one_chip, compiled_mode):
    """The gather path's dequantize: a (B, max_len, Hkv, Dh) slot view."""
    from repro.numerics import QTensor
    codec = get_codec(SPEC, "pallas")
    _compile(lambda q, s: codec.decode(QTensor(q, s, SPEC), jnp.bfloat16),
             one_chip, ((B, PP * PAGE, HKV, DH), jnp.int8),
             ((B, 1, 1, 1), jnp.float32))


@pytest.mark.parametrize("bits", [None, 8])
def test_pe1(one_chip, compiled_mode, bits):
    _compile(lambda z, g: ops.pe1(z, g, bits=bits), one_chip,
             ((128, 16, 16), jnp.float32), ((16, 32, 16), jnp.float32))


def test_pe2(one_chip, compiled_mode):
    _compile(ops.pe2, one_chip, ((64, 16, 16), jnp.float32),
             ((16, 32), jnp.float32))


def test_pe3(one_chip, compiled_mode):
    _compile(ops.pe3, one_chip, ((256, 64), jnp.float32),
             ((256, 128), jnp.float32))


def test_mla_decode_step_moves_no_pool_sized_copy(one_chip, compiled_mode):
    """Moonlight's decode step at its latent widths (512 latent, 64 rope,
    int8 pages of 16), through the latent walk, compiled for one v5e: no
    instruction copies, broadcasts, slices or updates a whole pool leaf,
    and the temporaries stay under the smallest leaf. An int8 page of
    64-wide rope keys is laid out page-minor by the TPU runtime unless its
    tokens are packed into 128-lane rows (``kv_cache.page_shape``), and the
    step then copies the leaf in and out whole, temporaries that grow with
    the pool. The walk's kernel is in the step, under the scope the
    benchmark's scope map counts as the latent attention."""
    import dataclasses
    import numpy as np

    import repro.configs as C
    from hlo_pool import pool_shapes, pool_sized_ops
    from repro.models import build_lm, init_lm
    from repro.serve import Engine, EngineConfig, PoolConfig
    from repro.sharding import ShardPlan

    cfg = C.get_reduced("moonlight-16b-a3b")
    cfg = cfg.replace(dtype="bfloat16", remat="none", mla=dataclasses.replace(
        C.get_config("moonlight-16b-a3b").mla))
    lm = build_lm(cfg)
    params = init_lm(jax.random.PRNGKey(0), lm)
    pcfg = PoolConfig(num_slots=B, page_size=16, pages_per_slot=PP,
                      num_pages=4096, quantized=True)
    eng = Engine(lm, params, EngineConfig(pool=pcfg, fused_attention=True,
                                          fused_impl="pallas"),
                 ShardPlan(mesh=None))
    assert eng.attention_paths()["attn_mla"]["decode"] == \
        "mla_paged_attention"
    spec = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                          sharding=one_chip)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
    compiled = eng._decode_jit.lower(
        jax.tree.map(spec, eng.params), jax.tree.map(spec, eng.pool),
        jax.tree.map(spec, eng.spool), i32(B, PP), i32(B),
        jax.ShapeDtypeStruct((B,), jnp.bool_, sharding=one_chip),
        i32(B, 1)).compile()
    shapes = pool_shapes(eng.pool)
    text = compiled.as_text()
    bad = pool_sized_ops(text, shapes)
    assert not bad, "\n".join(bad)
    _assert_walk_scoped(text)
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < min(shapes.values()), (temp, shapes)
    assert np.prod(eng.pool["data"]["sub_0"]["k_rope"].shape[-2:]) == 16 * 64
