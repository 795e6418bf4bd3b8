"""Differential tests of the latent page walk for MLA decode
(``kernels/mla_paged_attention.py``).

On synthetic stacked pools, read at a layer index, with ragged lengths that
cross page and compute-block boundaries, a slot at ``max_len - 1`` and an
inactive slot:

(a) the Pallas kernel in interpret mode and the jnp block scan agree
    BITWISE, and an inactive slot's output is finite zeros;
(b) both agree with the gather path (``kv_cache.gather_slots`` +
    ``models/attention.py::mla_attend``) to float round-off, after the
    value up-projection.

Over int8 and model-dtype (bf16) pools, and three page layouts: Moonlight's
widths (latent rows of 512, rope keys packed two tokens to a 128-lane row),
and the reduced configs' pages of 16 and of 4 (latent rows packed, rope
keys packed or not; ``kv_cache.page_shape``).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as C
from repro.configs.base import MLAConfig
from repro.kernels import mla_paged_attention as MPA
from repro.models import attention as A
from repro.serve import kv_cache as KC
from repro.serve.kv_cache import PoolConfig

LAYERS, LAYER = 3, 1

# (heads, latent, rope, page, pages a slot): Moonlight's widths, with 12
# pages a slot so the table pads to two blocks of 8; the reduced widths
LAYOUTS = {"moonlight": (16, 512, 64, 16, 12),
           "reduced_page16": (4, 32, 8, 16, 5),
           "reduced_page4": (4, 32, 8, 4, 19)}


def _case(layout, pool, seed=0):
    h, dc, dr, page, pp = LAYOUTS[layout]
    quantized = pool == "int8"
    dt = jnp.float32 if quantized else jnp.bfloat16
    b = 5
    pcfg = PoolConfig(num_slots=b, page_size=page, pages_per_slot=pp,
                      quantized=quantized)
    rng = np.random.RandomState(seed)
    npages = pcfg.total_pages + 1

    def leaf(d):
        shape = (LAYERS, npages) + KC.page_shape(page, (d,))
        if quantized:
            return jnp.asarray(rng.randint(-128, 128, shape), jnp.int8)
        return jnp.asarray(rng.randn(*shape), dt)

    ckv, krope = leaf(dc), leaf(dr)
    scales = [jnp.asarray(rng.randint(-8, -4, (b,)) if quantized
                          else np.zeros(b), jnp.float32) for _ in range(2)]
    # ragged: the last position of the horizon, a page edge, one past it,
    # the end of a block of 8 pages, a short slot (inactive)
    lens = np.array([pp * page - 1, 2 * page - 1, 2 * page,
                     min(8 * page, pp * page - 1), 3])
    active = np.array([True, True, True, True, False])
    perm = rng.permutation(pcfg.total_pages).reshape(b, pp)
    live = np.arange(pp)[None] <= (lens // page)[:, None]
    table = np.where(live & active[:, None], perm, pcfg.trash_page)
    qa = jnp.asarray(rng.randn(b, h, dc) * 0.1, dt)
    qr = jnp.asarray(rng.randn(b, h, dr) * 0.1, dt)
    args = (qa, qr, ckv, krope, *scales, jnp.asarray(table, jnp.int32),
            jnp.asarray(lens, jnp.int32), jnp.asarray(active),
            jnp.int32(LAYER))
    return pcfg, args


def _mla(h, dc, dr, dt):
    """An MLA sublayer of these widths and its params (for ``v_up``)."""
    cfg = C.get_reduced("moonlight-16b-a3b").replace(
        num_heads=h, num_kv_heads=h, dtype=jnp.dtype(dt).name,
        mla=MLAConfig(kv_lora_rank=dc, q_lora_rank=None, qk_nope_head_dim=16,
                      qk_rope_head_dim=dr, v_head_dim=16))
    d = A.make_mla(cfg)
    return cfg, d, A.init_mla(jax.random.PRNGKey(3), d, cfg)


@pytest.mark.parametrize("pool", ["int8", "bf16"])
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_walk_kernel_and_scan_agree_bitwise_and_with_gather(layout, pool):
    pcfg, args = _case(layout, pool)
    h, dc, dr = LAYOUTS[layout][:3]
    cfg, d, params = _mla(h, dc, dr, args[0].dtype)
    kw = dict(page_size=pcfg.page_size, quantized=pcfg.quantized,
              sm_scale=A.mla_softmax_scale(d.m))
    kern = MPA.mla_paged_attention_kernel(*args, interpret=True, **kw)
    scan = MPA.mla_paged_attention_jnp(*args, **kw)
    np.testing.assert_array_equal(np.asarray(kern), np.asarray(scan))
    active = np.asarray(args[-2])
    dead = np.asarray(kern.astype(jnp.float32))[~active]
    assert np.all(np.isfinite(dead)) and not dead.any()

    qa, qr, ckv, krope, cs, rs, table, lens = args[:8]
    layer = args[-1]
    kv = [KC.gather_slots(leaf, s, table, pcfg, qa.dtype, layer=layer)
          for leaf, s in ((ckv, cs), (krope, rs))]
    want = A.mla_attend(params, qa[:, None], qr[:, None], *kv, d, cfg,
                        lens[:, None])
    got = A.mla_value_up(params, kern[:, None], d, cfg)
    tol = 1e-5 if qa.dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(
        np.asarray(got[active], np.float32),
        np.asarray(want[active], np.float32), rtol=tol, atol=tol)


def test_walk_reads_the_layer_it_is_given():
    """The stacked leaf at layer i is the per-layer leaf ``data[i]``: the
    walk at layer 1 equals the walk of a pool whose only layer is layer 1,
    and differs from layer 0's."""
    pcfg, args = _case("reduced_page16", "int8", seed=1)
    kw = dict(page_size=pcfg.page_size, quantized=True, sm_scale=0.25)
    walk = jax.jit(functools.partial(MPA.mla_paged_attention_jnp, **kw))
    out = walk(*args)
    ckv, krope = args[2], args[3]
    alone = walk(*args[:2], ckv[LAYER:LAYER + 1], krope[LAYER:LAYER + 1],
                 *args[4:9], jnp.int32(0))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(alone))
    other = walk(*args[:9], jnp.int32(0))
    assert not np.array_equal(np.asarray(out), np.asarray(other))


def test_walk_cost_follows_live_pages():
    """The scan walks as many blocks as the longest active slot needs, and
    the kernel's per-slot block counts follow each slot's length."""
    pcfg, args = _case("moonlight", "int8")
    o = MPA._operands(*args[:9], pcfg.page_size, True)
    lens, active = np.asarray(args[7]), np.asarray(args[8])
    want = np.where(active, -(-(lens // pcfg.page_size + 1) // 8), 0)
    np.testing.assert_array_equal(np.asarray(o["nblk"]), want)
    assert pcfg.pages_per_slot == 12 and o["ppad"] == 16
