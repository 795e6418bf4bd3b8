"""The pool's page layout: a cached tensor with one feature axis narrower
than a 128-lane row (MLA's rope key, 64 wide) stores each page's tokens
packed row-major into lane rows (``kv_cache.page_shape``). Every write and
the gather must see the same tokens as the plain (page, d) layout: the
packed leaf, read back as (page, d), equals the plain one bit for bit."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.serve import PoolConfig
from repro.serve import kv_cache as KC

L, PAGES, PAGE, D, B = 3, 9, 16, 64, 4


@pytest.mark.parametrize("page,feat,want", [
    (16, (64,), (8, 128)),        # Moonlight's rope key
    (16, (8,), (1, 128)),
    (4, (32,), (1, 128)),
    (16, (512,), (16, 512)),      # Moonlight's latent: already whole rows
    (16, (128,), (16, 128)),
    (4, (8,), (4, 8)),            # a page fills less than one row
    (16, (48,), (16, 48)),        # 48 does not tile a row
    (16, (8, 64), (16, 8, 64)),   # (heads, dim): the page walk's layout
])
def test_page_shape(page, feat, want):
    assert KC.page_shape(page, feat) == want


def _pcfg(quantized):
    return PoolConfig(num_slots=B, page_size=PAGE, pages_per_slot=2,
                      num_pages=PAGES - 1, quantized=quantized)


def _leaves(quantized):
    """(packed, plain) leaves holding the same random tokens."""
    dtype = jnp.int8 if quantized else jnp.float32
    plain = jax.random.randint(jax.random.PRNGKey(0), (L, PAGES, PAGE, D),
                               -128, 128).astype(dtype)
    return plain.reshape((L, PAGES) + KC.page_shape(PAGE, (D,))), plain


def _inputs():
    k = jax.random.split(jax.random.PRNGKey(1), 3)
    table = jnp.asarray([[1, 2], [3, 4], [5, 6], [7, 0]], jnp.int32)
    lens = jnp.asarray([0, 17, 5, 31], jnp.int32)
    active = jnp.asarray([True, True, False, True])
    scale = jnp.full((L, B), -3.0, jnp.float32)
    return k, table, lens, active, scale


@pytest.mark.parametrize("quantized", [True, False])
@pytest.mark.parametrize("op", ["append_token", "append_token_stacked",
                                "append_tokens", "write_chunk",
                                "write_prefill"])
def test_packed_pages_hold_the_plain_layout(op, quantized):
    pcfg = _pcfg(quantized)
    packed, plain = _leaves(quantized)
    k, table, lens, active, scale = _inputs()
    layer = jnp.int32(1)

    def run(leaf):
        if op == "append_token":
            new = jax.random.normal(k[0], (B, 1, D))
            return KC.append_token(leaf[1], scale[1], new, table, lens,
                                   active, pcfg)
        if op == "append_token_stacked":
            new = jax.random.normal(k[0], (B, 1, D))
            return KC.append_token(leaf, scale[1], new, table, lens, active,
                                   pcfg, layer=layer)
        if op == "append_tokens":
            new = jax.random.normal(k[0], (B, 3, D))
            return KC.append_tokens(leaf[1], scale[1], new, table, lens,
                                    active, pcfg)
        if op == "write_chunk":
            vals = jax.random.normal(k[1], (12, D))
            return KC.write_chunk(leaf[1], scale[1], vals, table[1],
                                  jnp.int32(9), jnp.int32(10), jnp.int32(1),
                                  pcfg)[0]
        cache = {"sub_0": {"r": jax.random.normal(k[2], (L, 1, 32, D))}}
        pool = {"data": {"sub_0": {"r": leaf}},
                "scale_log2": {"sub_0": {"r": scale}}}
        return KC.write_prefill(pool, cache, table[2], jnp.int32(2),
                                jnp.int32(21), pcfg)["data"]["sub_0"]["r"]

    got, want = run(packed), run(plain)
    assert got.shape == packed.shape[-got.ndim:]
    np.testing.assert_array_equal(np.asarray(got).reshape(want.shape),
                                  np.asarray(want))
    stacked = got.ndim == 4
    view = KC.gather_slots(got, scale[1], table, pcfg, jnp.float32,
                           layer=layer if stacked else None)
    view_plain = KC.gather_slots(want, scale[1], table, pcfg, jnp.float32,
                                 layer=layer if stacked else None)
    assert view.shape == (B, pcfg.max_len, D)
    np.testing.assert_array_equal(np.asarray(view), np.asarray(view_plain))
