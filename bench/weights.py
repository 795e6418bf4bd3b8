"""Random weights from a seed, by tensor name and layer.

A configuration's layout (``layouts/<kind>.py``) draws its per-layer
tensors with these helpers, and both the system under test and the plain
references take their weights from there and from here, so the two see the
same numbers without either reading what the other made. Each tensor is drawn from its own key, folded from the seed's
two words, the tensor's name and its layer index. The seed enters as a
traced array, so one compiled program makes the weights of every seed.

Matrices are drawn in float32 and rounded once to the dtype they are
served in; a reference that computes in float32 upcasts those rounded
values. Norm scales are float32 near 1.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np

from common import seed_words


def seed_array(seed: int) -> np.ndarray:
    return np.asarray(seed_words(seed), np.uint32)


def tensor_key(seed_arr, name: str, layer=0):
    k = jax.random.PRNGKey(0)
    k = jax.random.fold_in(k, seed_arr[0])
    k = jax.random.fold_in(k, seed_arr[1])
    k = jax.random.fold_in(k, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    return jax.random.fold_in(k, layer)


def matrix(seed_arr, name: str, shape, dtype, layer=0, std=None):
    """A (fan_in, fan_out) weight, Glorot-normal unless ``std`` is given."""
    if std is None:
        std = (2.0 / (shape[-2] + shape[-1])) ** 0.5
    w = jax.random.normal(tensor_key(seed_arr, name, layer), shape,
                          jnp.float32) * std
    return w.astype(dtype)


def norm_scale(seed_arr, name: str, dim: int, layer=0):
    return 1.0 + 0.1 * jax.random.normal(tensor_key(seed_arr, name, layer),
                                         (dim,), jnp.float32)


def embedding(seed_arr, c: dict, dtype):
    d, v = c["hidden_size"], c["vocab_size"]
    return matrix(seed_arr, "embed", (v, d), dtype, std=d ** -0.5)


def head(seed_arr, c: dict, dtype):
    return matrix(seed_arr, "head", (c["hidden_size"], c["vocab_size"]),
                  dtype)


def final_norm(seed_arr, c: dict):
    return norm_scale(seed_arr, "final_norm", c["hidden_size"])
