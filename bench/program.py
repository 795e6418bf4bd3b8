"""The one place that knows the system under test: how a configuration file
becomes a ``repro`` model, and how the seeded weights of ``weights.py`` are
laid into that model's parameter tree.

The tree built here is checked leaf by leaf against the tree the program's
own ``init_lm`` would make (shapes and dtypes), so a change of the
program's layout fails loudly here instead of serving other numbers.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

import weights as W
from common import program_on_path

program_on_path()

from repro import configs as C  # noqa: E402
from repro.models import build_lm, init_lm  # noqa: E402

# configuration key -> repro ModelConfig field
_FIELDS = {"num_hidden_layers": "num_layers", "hidden_size": "d_model",
           "num_attention_heads": "num_heads",
           "num_key_value_heads": "num_kv_heads",
           "intermediate_size": "d_ff", "vocab_size": "vocab_size",
           "rms_norm_eps": "norm_eps", "rope_theta": "rope_theta",
           "max_position_embeddings": "max_seq_len",
           "torch_dtype": "dtype"}


def model_config(c: dict):
    """The repro ``ModelConfig`` a configuration file describes."""
    return C.get_config(c["program"]["arch"]).replace(
        **{f: c[k] for k, f in _FIELDS.items()},
        tie_embeddings=c["tie_word_embeddings"])


def build(c: dict):
    return build_lm(model_config(c))


def _check_tree(mine, lm) -> None:
    want = jax.eval_shape(lambda: init_lm(jax.random.PRNGKey(0), lm))
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                       mine)
    if jax.tree.structure(want) != jax.tree.structure(got):
        raise ValueError("the program's parameter tree changed:\n"
                         f"{jax.tree.structure(want)}\nvs\n"
                         f"{jax.tree.structure(got)}")
    bad = [(w, g) for w, g in zip(jax.tree.leaves(want), jax.tree.leaves(got))
           if (w.shape, w.dtype) != (g.shape, g.dtype)]
    if bad:
        raise ValueError(f"parameter leaves differ from the program's: {bad}")


def dense_params(c: dict, lm, seed: int):
    """The seeded weights of a dense GQA decoder in the program's tree, made
    on the device in one jitted call, in the dtype they are served in."""
    dtype = jnp.dtype(c["torch_dtype"])
    n = c["num_hidden_layers"]

    def make(seed_arr):
        def layer(i):
            w = W.dense_layer(seed_arr, c, i, dtype)
            return {"sub_0": {
                "norm1": {"scale": w["attn_norm"]},
                "mixer": {"q": {"w": w["wq"]},
                          "kv": {"w": jnp.concatenate([w["wk"], w["wv"]],
                                                      axis=1)},
                          "o": {"w": w["wo"]}},
                "norm2": {"scale": w["ffn_norm"]},
                "ffn": {"gate": {"w": w["w_gate"]}, "up": {"w": w["w_up"]},
                        "down": {"w": w["w_down"]}}}}

        return {"embed": {"w": W.embedding(seed_arr, c, dtype)},
                "layers": jax.vmap(layer)(jnp.arange(n)),
                "final_norm": {"scale": W.final_norm(seed_arr, c)},
                "head": {"w": W.head(seed_arr, c, dtype)}}

    params = jax.jit(make)(W.seed_array(seed))
    _check_tree(params, lm)
    return params
