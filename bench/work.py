"""Operations and bytes that the algorithm needs, computed from shapes.

These count the work a step has to do, not what a compiler emitted: a
change that makes the program do more or less work than this does not
move the yardstick. Matmuls count 2 operations per multiply-add. A
configuration is the dict of its file (``configs/<name>.json``); each
count is its layout's (``layouts/<kind>.py``), which knows the shapes.
"""
from __future__ import annotations

from common import layout_of


def layer_params(c: dict) -> int:
    """Parameters of one layer's projections."""
    return layout_of(c).layer_params(c)


def head_params(c: dict) -> int:
    return layout_of(c).head_params(c)


def attention_flops(c: dict, keys: int) -> int:
    """One query row against ``keys`` cached positions, all layers."""
    return layout_of(c).attention_flops(c, keys)


def decode_token_flops(c: dict, keys: int) -> int:
    """One decoded token: every projection, the head, and attention over
    its own live context."""
    return layout_of(c).decode_token_flops(c, keys)


def paged_attention_bytes(c: dict, keys: int, kv_itemsize: int,
                          act_itemsize: int) -> int:
    """Bytes one decode row's page walk must move, all layers."""
    return layout_of(c).paged_attention_bytes(c, keys, kv_itemsize,
                                              act_itemsize)
