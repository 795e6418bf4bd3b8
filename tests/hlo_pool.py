"""Pool-traffic guard for the engine's compiled decode step.

The decode step must update the KV pool in place: one scatter per cached
tensor per layer, no pool-sized copy, broadcast, slice or update. This
compiles ``Engine._decode_jit`` for the engine's own pools and lists every
instruction of the optimized HLO (fused computations included) whose result
has a pool data leaf's shape and whose opcode would move the whole leaf.
Under a mesh the compiled HLO is per device, so each leaf's shard shape
counts too. Shared by tests/test_serve.py and the forced-mesh subprocess of
tests/test_sharded_serve.py.
"""
import re

import jax
import numpy as np

POOL_SIZED = ("copy", "broadcast", "dynamic-slice", "dynamic-update-slice")
HLO_DTYPE = {"int8": "s8", "float32": "f32", "bfloat16": "bf16"}
_INSTR = re.compile(r"=\s+(\w+)\[([\d,]*)\](?:\{[^}]*\})?\s+([\w-]+)\(")


def pool_shapes(pool: dict) -> dict[tuple[str, tuple[int, ...]], int]:
    """{(HLO dtype, dims): bytes} of every KV-pool data leaf, whole and, on
    a mesh, one device's shard."""
    out = {}
    for leaf in jax.tree_util.tree_leaves(pool["data"]):
        dt = HLO_DTYPE[leaf.dtype.name]
        for shape in (leaf.shape, leaf.sharding.shard_shape(leaf.shape)):
            out[(dt, tuple(shape))] = int(np.prod(shape)) * leaf.itemsize
    return out


def pool_sized_ops(hlo_text: str, shapes) -> list[str]:
    """Instructions of ``hlo_text`` that copy, broadcast, slice or update a
    whole array of one of ``shapes``."""
    bad = []
    for line in hlo_text.splitlines():
        m = _INSTR.search(line)
        if m and m.group(3) in POOL_SIZED:
            dims = tuple(int(d) for d in m.group(2).split(",") if d)
            if (m.group(1), dims) in shapes:
                bad.append(line.strip()[:160])
    return bad


def decode_pool_report(eng) -> tuple[list[str], int, int]:
    """(pool-sized ops, temp bytes, bytes of the smallest pool leaf on one
    device) of the engine's decode step, compiled for its own pools and a
    full batch of active slots."""
    b = eng.pcfg.num_slots
    table = np.full((b, eng.pcfg.pages_per_slot), eng.pcfg.trash_page,
                    np.int32)
    compiled = eng._decode_jit.lower(
        eng.params, eng.pool, eng.spool, table, np.zeros(b, np.int32),
        np.ones(b, bool), np.zeros((b, 1), np.int32)).compile()
    shapes = pool_shapes(eng.pool)
    return (pool_sized_ops(compiled.as_text(), shapes),
            compiled.memory_analysis().temp_size_in_bytes,
            min(shapes.values()))
