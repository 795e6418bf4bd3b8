"""ShardPlan latent-mesh regressions (no multi-device mesh needed):

(a) ``_div`` on an absent mesh axis answers "don't shard", not KeyError —
    the dp-only 1-D mesh is a first-class citizen,
(b) the replicate-guard in ``param_spec`` matches exact leaf names; a
    zoo-wide audit asserts every >= 2-D projection leaf in every registered
    config gets a non-trivial spec on an 8-way model mesh,
(c) ``params_pspec_tree`` is a single in-place tree_map_with_path pass —
    distinct tree paths can never collide through their "/"-joined strings.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

import repro.configs as C
from repro.launch.mesh import make_mesh
from repro.models import build_lm, init_lm, lm_forward
from repro.sharding import ShardPlan, _REPLICATED_LEAVES, _div, make_plan

MESH8 = AbstractMesh((1, 8), ("data", "model"))
DP8 = AbstractMesh((8,), ("data",))


# ---------------------------------------------------------------------------
# (a) absent mesh axes
# ---------------------------------------------------------------------------

def test_div_absent_axis_is_false_not_keyerror():
    assert _div(64, DP8, "model") is False          # was: KeyError
    assert _div(64, DP8, ("pod", "data")) is False  # partially absent tuple
    assert _div(64, DP8, "data") is True
    assert _div(63, DP8, "data") is False
    assert _div(4, DP8, "data") is False            # smaller than the axis
    assert _div(64, None, "data") is False
    assert _div(64, DP8, None) is False


def test_param_spec_on_dp_only_mesh():
    plan = ShardPlan(mesh=DP8, strategy="tp")
    # every site that used to index mesh.shape["model"] directly
    for key, shape in [("layers/attn/q/w", (64, 512)),
                       ("layers/ffn/up/w", (64, 96)),
                       ("embed/w", (256, 64))]:
        spec = plan.param_spec(key, shape)
        assert "model" not in jax.tree_util.tree_leaves(tuple(spec))
    assert plan.model_size() == 1
    assert plan.shards_kv_heads(8) is False
    assert plan.kv_page_spec((2, 9, 8, 8, 16)) == P(None, None, None, None,
                                                    None)
    assert plan.state_spec("h", (2, 4, 128, 8)) == P(None, None, None, None)


def test_dp_only_mesh_forward_matches_meshless():
    """lm_forward under a dp-only mesh (the KeyError repro: _div and the
    attention chunk constraint both indexed the absent ``model`` axis)."""
    cfg = C.get_reduced("internlm2-1.8b").replace(dtype="float32",
                                                  remat="none")
    lm = build_lm(cfg)
    params = init_lm(jax.random.PRNGKey(0), lm)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                              cfg.vocab_size)
    ref, _, _ = lm_forward(params, lm, ShardPlan(mesh=None), tokens=toks)
    mesh = make_mesh((1,), ("data",))
    out, _, _ = jax.jit(
        lambda p, t: lm_forward(p, lm, make_plan(mesh, "tp"), tokens=t))(
            params, toks)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# (b) replicate-guard: exact names + zoo-wide audit
# ---------------------------------------------------------------------------

def _leaf_items(shapes):
    for path, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]:
        key = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                       for p in path)
        yield key, leaf


@pytest.mark.parametrize("arch", sorted(C.ARCHS))
def test_param_spec_zoo_audit(arch):
    """Every >= 2-D leaf that is not an exact-name replicated vector (or a
    TT core/lambda) must receive a non-trivial spec on an 8-way model mesh.
    The old bare-prefix guard ("b", "u", "D", ...) would silently replicate
    any future projection leaf sharing a first letter — this audit turns
    that class of bug into a test failure."""
    cfg = C.get_config(arch)
    lm = build_lm(cfg)
    shapes = jax.eval_shape(lambda k: init_lm(k, lm), jax.random.PRNGKey(0))
    plan = ShardPlan(mesh=MESH8, strategy=C.get_strategy(arch))
    audited = 0
    for key, leaf in _leaf_items(shapes):
        name = key.split("/")[-1]
        if leaf.ndim < 2 or name in _REPLICATED_LEAVES \
                or name.startswith(("core_", "lambda_")):
            continue
        spec = plan.param_spec(key, leaf.shape)
        assert any(ax is not None for ax in spec), \
            f"{arch}: projection leaf {key} {leaf.shape} replicated by " \
            f"{plan.strategy} plan: {spec}"
        audited += 1
    assert audited > 0


def test_replicated_leaves_stay_replicated():
    plan = ShardPlan(mesh=MESH8, strategy="tp")
    # stacked 2-D forms of the replicated vectors (leading layer axis)
    for name, shape in [("b", (2, 64)), ("u", (2, 4, 16)),
                        ("mu_x", (2, 1, 64)), ("A_log", (2, 128)),
                        ("conv_w", (2, 4, 128)), ("wscale_log2", (2, 8)),
                        ("core_0", (1, 4, 8, 8)), ("lambda_3", (2, 2))]:
        assert plan.param_spec(f"layers/x/{name}", shape) == P(), name


# ---------------------------------------------------------------------------
# (c) single-pass params_pspec_tree
# ---------------------------------------------------------------------------

def test_params_pspec_tree_no_path_collision():
    """Two distinct tree paths whose "/"-joined strings are identical must
    each get their own spec (the old dict-keyed double-flatten overwrote one
    with the other)."""
    plan = ShardPlan(mesh=MESH8, strategy="tp")
    a = jax.ShapeDtypeStruct((64, 512), jnp.float32)
    b = jax.ShapeDtypeStruct((2, 64, 512), jnp.float32)
    params = {"layers": {"attn/q": {"w": a}},
              "layers/attn": {"q": {"w": b}}}   # both join to layers/attn/q/w
    specs = plan.params_pspec_tree(params)
    assert jax.tree_util.tree_structure(
        specs, is_leaf=lambda s: isinstance(s, P)) == \
        jax.tree_util.tree_structure(params)
    assert specs["layers"]["attn/q"]["w"] == \
        plan.param_spec("layers/attn/q/w", a.shape)
    assert specs["layers/attn"]["q"]["w"] == \
        plan.param_spec("layers/attn/q/w", b.shape)
    # and the two shapes really do yield different specs
    assert specs["layers"]["attn/q"]["w"] != specs["layers/attn"]["q"]["w"]


def test_params_pspec_tree_matches_per_leaf_param_spec():
    cfg = C.get_reduced("jamba-1.5-large").replace(dtype="float32",
                                                   remat="none")
    lm = build_lm(cfg)
    shapes = jax.eval_shape(lambda k: init_lm(k, lm), jax.random.PRNGKey(0))
    plan = ShardPlan(mesh=MESH8, strategy="tp")
    specs = plan.params_pspec_tree(shapes)
    flat_specs = dict(
        ("/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                  for p in path), s)
        for path, s in jax.tree_util.tree_flatten_with_path(
            specs, is_leaf=lambda s: isinstance(s, P))[0])
    for key, leaf in _leaf_items(shapes):
        assert flat_specs[key] == plan.param_spec(key, leaf.shape), key
