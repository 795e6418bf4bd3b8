"""Layout of a dense decoder with grouped-query attention (InternLM2 and
its kind): which ``repro`` model a configuration file describes, its
per-layer tensors by name, how they lie in the program's parameter tree,
and the work a decode step needs.

A configuration names this file with ``"program": {"layout": "dense_gqa"}``.
The program is imported only inside ``model_config`` and ``params``, so a
plain reference can draw its tensors from ``layer_weights`` here and still
import nothing of the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

import weights as W

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
    from common import program_on_path
    program_on_path()
    from repro import configs as C
    return C.get_config(c["program"]["arch"]).replace(
        **{f: c[k] for k, f in _FIELDS.items()},
        tie_embeddings=c["tie_word_embeddings"])


def shapes(c: dict) -> dict:
    """Per-layer matrix shapes, (fan_in, fan_out)."""
    d, f = c["hidden_size"], c["intermediate_size"]
    dh = d // c["num_attention_heads"]
    hq, hkv = c["num_attention_heads"], c["num_key_value_heads"]
    return {"wq": (d, hq * dh), "wk": (d, hkv * dh), "wv": (d, hkv * dh),
            "wo": (hq * dh, d), "w_gate": (d, f), "w_up": (d, f),
            "w_down": (f, d)}


def layer_weights(seed_arr, c: dict, layer, dtype) -> dict:
    """One decoder layer's weights (``layer`` may be traced)."""
    d = c["hidden_size"]
    out = {name: W.matrix(seed_arr, name, shape, dtype, layer)
           for name, shape in shapes(c).items()}
    out["attn_norm"] = W.norm_scale(seed_arr, "attn_norm", d, layer)
    out["ffn_norm"] = W.norm_scale(seed_arr, "ffn_norm", d, layer)
    return out


def params(c: dict, lm, seed: int):
    """The seeded weights in the program's tree, made on the device in one
    jitted call, in the dtype they are served in."""
    dtype = jnp.dtype(c["torch_dtype"])
    n = c["num_hidden_layers"]

    def make(seed_arr):
        def layer(i):
            w = layer_weights(seed_arr, c, i, dtype)
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

    return jax.jit(make)(W.seed_array(seed))


# ---- work counts (``work.py`` forwards to these) ----

def _dims(c: dict):
    d = c["hidden_size"]
    hq, hkv = c["num_attention_heads"], c["num_key_value_heads"]
    return d, hq, hkv, d // hq, c["intermediate_size"], c["vocab_size"]


def matrices(c: dict) -> dict:
    """(out, in) of each projection of one decoder layer."""
    d, hq, hkv, dh, f, _ = _dims(c)
    return {"q": (hq * dh, d), "kv": (2 * hkv * dh, d), "o": (d, hq * dh),
            "gate": (f, d), "up": (f, d), "down": (d, f)}


def layer_params(c: dict) -> int:
    return sum(o * i for o, i in matrices(c).values())


def head_params(c: dict) -> int:
    return c["hidden_size"] * c["vocab_size"]


def attention_flops(c: dict, keys: int) -> int:
    """One query row against ``keys`` cached positions, all layers:
    scores and the weighted sum of values."""
    _, hq, _, dh, _, _ = _dims(c)
    return c["num_hidden_layers"] * 4 * hq * dh * keys


def decode_token_flops(c: dict, keys: int) -> int:
    """One decoded token: every projection, the head, and attention over
    its own live context."""
    return (2 * (c["num_hidden_layers"] * layer_params(c) + head_params(c))
            + attention_flops(c, keys))


def paged_attention_bytes(c: dict, keys: int, kv_itemsize: int,
                          act_itemsize: int) -> int:
    """Bytes one decode row's page walk must move, all layers: its live
    keys and values at the pool's storage width, one scale per tensor,
    the query in and the output back."""
    _, hq, hkv, dh, _, _ = _dims(c)
    per_layer = (2 * keys * hkv * dh * kv_itemsize + 2 * 4
                 + 2 * hq * dh * act_itemsize)
    return c["num_hidden_layers"] * per_layer
