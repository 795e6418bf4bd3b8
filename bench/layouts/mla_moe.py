"""Layout of a DeepSeek-V3-style decoder (Moonlight and its kind): latent
attention (MLA), leading dense layers, then layers of routed and shared
experts with sigmoid ``noaux_tc`` routing; one chip holds a share of the
routed experts. Which ``repro`` model a configuration file describes, its
per-layer tensors by name, how they lie in the program's parameter tree,
and the work a decode step needs.

A configuration names this file with ``"program": {"layout": "mla_moe"}``.
The program is imported only inside ``model_config`` and ``params``, so a
plain reference can draw its tensors from ``layer_weights`` here and still
import nothing of the program.

The tensors are the published ones: ``wkv_b`` maps the latent to each
head's [k_nope | v], as the checkpoint's ``kv_b_proj`` does; ``params``
splits it into the program's ``k_up`` and ``v_up``. Routed experts are
drawn by their global index, so every chip's share comes from one model.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

import weights as W

# configuration key -> repro ModelConfig field
_FIELDS = {"num_hidden_layers": "num_layers", "hidden_size": "d_model",
           "num_attention_heads": "num_heads",
           "num_key_value_heads": "num_kv_heads",
           "moe_intermediate_size": "d_ff", "intermediate_size": "dense_d_ff",
           "first_k_dense_replace": "first_dense_layers",
           "vocab_size": "vocab_size", "rms_norm_eps": "norm_eps",
           "rope_theta": "rope_theta",
           "max_position_embeddings": "max_seq_len", "torch_dtype": "dtype"}
_MLA = ("kv_lora_rank", "q_lora_rank", "qk_nope_head_dim",
        "qk_rope_head_dim", "v_head_dim")


def _check(c: dict) -> None:
    """Refuse what this layout does not describe."""
    want = {"topk_method": "noaux_tc", "scoring_func": "sigmoid",
            "n_group": 1, "topk_group": 1, "moe_layer_freq": 1,
            "q_lora_rank": None, "num_nextn_predict_layers": 0}
    bad = {k: c[k] for k, v in want.items() if c[k] != v}
    if bad:
        raise NotImplementedError(f"mla_moe layout: unsupported {bad}")


def model_config(c: dict):
    """The repro ``ModelConfig`` a configuration file describes: the
    router spans ``expert_parallel.router_experts`` and this chip holds
    ``n_routed_experts`` of them from ``expert_parallel.expert_offset``
    (the engine serves it dropless)."""
    import dataclasses

    from common import program_on_path
    program_on_path()
    from repro import configs as C
    from repro.configs.base import MLAConfig
    _check(c)
    ep = c["expert_parallel"]
    base = C.get_config(c["program"]["arch"])
    moe = dataclasses.replace(
        base.moe, num_experts=ep["router_experts"],
        top_k=c["num_experts_per_tok"], num_shared=c["n_shared_experts"],
        scoring=c["scoring_func"], norm_topk_prob=c["norm_topk_prob"],
        routed_scaling_factor=c["routed_scaling_factor"],
        n_group=c["n_group"],
        experts_held=c["n_routed_experts"], expert_offset=ep["expert_offset"])
    return base.replace(
        **{f: c[k] for k, f in _FIELDS.items()},
        tie_embeddings=c["tie_word_embeddings"],
        mla=MLAConfig(**{k: c[k] for k in _MLA}), moe=moe)


def _dims(c: dict):
    d, h = c["hidden_size"], c["num_attention_heads"]
    nope, rope, v = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                     c["v_head_dim"])
    return d, h, nope, rope, v, c["kv_lora_rank"]


def attention_shapes(c: dict) -> dict:
    """One layer's attention matrices, (fan_in, fan_out)."""
    d, h, nope, rope, v, lat = _dims(c)
    return {"wq": (d, h * (nope + rope)), "wkv_a": (d, lat + rope),
            "wkv_b": (lat, h * (nope + v)), "wo": (h * v, d)}


def ffn_shapes(c: dict, dense: bool) -> dict:
    """The dense layer's SwiGLU, or an expert layer's router and shared
    experts, (fan_in, fan_out); one routed expert is ``expert_shapes``."""
    d = c["hidden_size"]
    if dense:
        f = c["intermediate_size"]
        return {"w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)}
    fs = c["moe_intermediate_size"] * c["n_shared_experts"]
    return {"router": (d, c["expert_parallel"]["router_experts"]),
            "shared_gate": (d, fs), "shared_up": (d, fs),
            "shared_down": (fs, d)}


def expert_shapes(c: dict) -> dict:
    d, f = c["hidden_size"], c["moe_intermediate_size"]
    return {"expert_gate": (d, f), "expert_up": (d, f),
            "expert_down": (f, d)}


def held_experts(c: dict):
    """Global indices of the routed experts this chip holds."""
    ep = c["expert_parallel"]
    return ep["expert_offset"] + jnp.arange(c["n_routed_experts"])


def layer_weights(seed_arr, c: dict, layer, dtype, dense: bool = False
                  ) -> dict:
    """One decoder layer's weights (``layer``, its global index, may be
    traced): attention, then the dense SwiGLU or the router, its
    correction bias (float32, N(0, ``router_bias_std``)), the shared
    experts and the held routed experts stacked (held, fan_in, fan_out)."""
    d, lat = c["hidden_size"], c["kv_lora_rank"]
    out = {name: W.matrix(seed_arr, name, shape, dtype, layer)
           for name, shape in {**attention_shapes(c),
                               **ffn_shapes(c, dense)}.items()}
    out["attn_norm"] = W.norm_scale(seed_arr, "attn_norm", d, layer)
    out["kv_norm"] = W.norm_scale(seed_arr, "kv_norm", lat, layer)
    out["ffn_norm"] = W.norm_scale(seed_arr, "ffn_norm", d, layer)
    if not dense:
        ep = c["expert_parallel"]
        out["router_bias"] = ep["router_bias_std"] * jax.random.normal(
            W.tensor_key(seed_arr, "router_bias", layer),
            (ep["router_experts"],), jnp.float32)
        n = ep["router_experts"]
        for name, shape in expert_shapes(c).items():
            out[name] = jax.vmap(lambda e, name=name, shape=shape: W.matrix(
                seed_arr, name, shape, dtype, layer * n + e))(held_experts(c))
    return out


def params(c: dict, lm, seed: int):
    """The seeded weights in the program's tree, made on the device in one
    jitted call, in the dtype they are served in."""
    dtype = jnp.dtype(c["torch_dtype"])
    n_dense = c["first_k_dense_replace"]
    _, h, nope, _, v, lat = _dims(c)

    def sub(w, ffn):
        kvb = w["wkv_b"].reshape(lat, h, nope + v)
        return {"norm1": {"scale": w["attn_norm"]},
                "mixer": {"q": {"w": w["wq"]},
                          "kv_down": {"w": w["wkv_a"]},
                          "kv_norm": {"scale": w["kv_norm"]},
                          "k_up": {"w": kvb[..., :nope].reshape(lat, -1)},
                          "v_up": {"w": kvb[..., nope:].reshape(lat, -1)},
                          "o": {"w": w["wo"]}},
                "norm2": {"scale": w["ffn_norm"]}, **ffn}

    def make(seed_arr):
        def dense_layer(i):
            w = layer_weights(seed_arr, c, i, dtype, dense=True)
            return sub(w, {"ffn": {"gate": {"w": w["w_gate"]},
                                   "up": {"w": w["w_up"]},
                                   "down": {"w": w["w_down"]}}})

        def moe_layer(i):
            w = layer_weights(seed_arr, c, i, dtype)
            moe = {"router": {"w": w["router"]},
                   "router_bias": w["router_bias"],
                   "gate": {"w": w["expert_gate"]},
                   "up": {"w": w["expert_up"]},
                   "down": {"w": w["expert_down"]},
                   "shared": {"gate": {"w": w["shared_gate"]},
                              "up": {"w": w["shared_up"]},
                              "down": {"w": w["shared_down"]}}}
            return {"sub_0": sub(w, {"moe": moe})}

        return {"embed": {"w": W.embedding(seed_arr, c, dtype)},
                "lead": [dense_layer(i) for i in range(n_dense)],
                "layers": jax.vmap(moe_layer)(
                    jnp.arange(n_dense, c["num_hidden_layers"])),
                "final_norm": {"scale": W.final_norm(seed_arr, c)},
                "head": {"w": W.head(seed_arr, c, dtype)}}

    return jax.jit(make)(W.seed_array(seed))


# ---- work counts (``work.py`` forwards to these) ----

def _n(shapes: dict) -> int:
    return sum(i * o for i, o in shapes.values())


def layer_params(c: dict) -> int:
    """Parameters of one expert layer's matrices held here: attention,
    router, shared experts and the held routed experts."""
    return (_n(attention_shapes(c)) + _n(ffn_shapes(c, False))
            + c["n_routed_experts"] * _n(expert_shapes(c)))


def dense_layer_params(c: dict) -> int:
    return _n(attention_shapes(c)) + _n(ffn_shapes(c, True))


def head_params(c: dict) -> int:
    return c["hidden_size"] * c["vocab_size"]


def held_params(c: dict) -> int:
    """Every matrix this chip holds: the layers, the embedding and the
    head (norm scales and correction biases left out)."""
    n_dense = c["first_k_dense_replace"]
    n_moe = c["num_hidden_layers"] - n_dense
    return (n_dense * dense_layer_params(c) + n_moe * layer_params(c)
            + 2 * head_params(c))


def attention_flops(c: dict, keys: int) -> int:
    """One query row against ``keys`` cached positions, all layers, in the
    latent space: per head the scores against the 512 latent and 64 rope
    values and the weighted sum of the 512 latent values."""
    _, h, _, rope, _, lat = _dims(c)
    return c["num_hidden_layers"] * 2 * h * (lat + rope + lat) * keys


def routed_here_per_token(c: dict) -> float:
    """Routed experts a token reaches on this chip on average: its
    ``num_experts_per_tok`` picks over the router's experts, of which this
    chip holds ``n_routed_experts``."""
    return (c["num_experts_per_tok"] * c["n_routed_experts"]
            / c["expert_parallel"]["router_experts"])


def decode_token_flops(c: dict, keys: int) -> int:
    """One decoded token: every projection of every layer (the expert
    layers' router, shared experts and, on average, the routed experts it
    reaches here), the head, and attention over its own live context."""
    n_dense = c["first_k_dense_replace"]
    n_moe = c["num_hidden_layers"] - n_dense
    per_moe = (_n(attention_shapes(c)) + _n(ffn_shapes(c, False))
               + routed_here_per_token(c) * _n(expert_shapes(c)))
    proj = n_dense * dense_layer_params(c) + n_moe * per_moe + head_params(c)
    return int(2 * proj) + attention_flops(c, keys)


def paged_attention_bytes(c: dict, keys: int, kv_itemsize: int,
                          act_itemsize: int) -> int:
    """Bytes one decode row's latent attention must move, all layers: each
    live key's latent and rope values at the pool's storage width, one
    scale per cached tensor, the absorbed query (latent + rope per head)
    in, and each head's value out after the up-projection."""
    _, h, _, rope, v, lat = _dims(c)
    per_layer = (keys * (lat + rope) * kv_itemsize + 2 * 4
                 + h * (lat + rope) * act_itemsize + h * v * act_itemsize)
    return c["num_hidden_layers"] * per_layer


def moe_step_work(c: dict, rows: int, experts_hit: int, tokens_here: int,
                  w_itemsize: int, act_itemsize: int) -> tuple[int, int]:
    """(FLOPs, bytes) one decode step's expert layers need: for every
    expert layer the router and the shared experts over the ``rows`` live
    tokens, and the held experts each step's ``moe_hits`` names
    (``experts_hit`` of them read, summed over layers; ``tokens_here``
    token-expert pairs computed); activations in and out."""
    n_moe = c["num_hidden_layers"] - c["first_k_dense_replace"]
    d = c["hidden_size"]
    shared = _n(ffn_shapes(c, False))
    expert = _n(expert_shapes(c))
    flops = 2 * (n_moe * rows * shared + tokens_here * expert)
    nbytes = (n_moe * (shared * w_itemsize
                       + 4 * c["expert_parallel"]["router_experts"]
                       + 2 * rows * d * act_itemsize)
              + experts_hit * expert * w_itemsize)
    return flops, nbytes
