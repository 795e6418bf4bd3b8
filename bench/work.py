"""Operations and bytes that the algorithm needs, computed from shapes.

These count the work a step has to do, not what a compiler emitted: a
change that makes the program do more or less work than this does not
move the yardstick. Matmuls count 2 operations per multiply-add. A
configuration is the dict of its file (``configs/<name>.json``).
"""
from __future__ import annotations


def _dims(c: dict):
    d = c["hidden_size"]
    hq, hkv = c["num_attention_heads"], c["num_key_value_heads"]
    return d, hq, hkv, d // hq, c["intermediate_size"], c["vocab_size"]


def dense_matrices(c: dict) -> dict:
    """(out, in) of each projection of one decoder layer."""
    d, hq, hkv, dh, f, _ = _dims(c)
    return {"q": (hq * dh, d), "kv": (2 * hkv * dh, d), "o": (d, hq * dh),
            "gate": (f, d), "up": (f, d), "down": (d, f)}


def layer_params(c: dict) -> int:
    return sum(o * i for o, i in dense_matrices(c).values())


def head_params(c: dict) -> int:
    return c["hidden_size"] * c["vocab_size"]

def attention_flops(c: dict, keys: int) -> int:
    """One query row against ``keys`` cached positions, all layers:
    scores and the weighted sum of values."""
    _, hq, _, dh, _, _ = _dims(c)
    return c["num_hidden_layers"] * 4 * hq * dh * keys


def decode_token_flops(c: dict, keys: int) -> int:
    """One decoded token of a dense decoder: every projection, the head,
    and attention over its own live context."""
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
