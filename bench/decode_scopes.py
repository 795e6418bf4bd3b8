"""Device time of the decode step's operations under one of the program's
named scopes (``repro.ops.*``, ``jax.named_scope``).

A TPU trace names each operation by its instruction in the compiled
program (``fusion.12``) and carries no source scope, so the scope of each
instruction is read from the compiled decode step's own text, where each
instruction keeps the scope path of the code that made it
(``metadata={op_name="jit(_decode_impl)/while/body/repro.ops.moe/..."}``;
a fusion keeps its root's). ``scopes_of`` makes that map once a traced run
has served; ``seconds`` sums, in the traced window, the device time of the
operations inside runs of the decode step that the map puts under a scope.
Instruction names are unique within one compiled program, and only
operations inside the decode step's runs are looked up.
"""
from __future__ import annotations

import re

import trace_reduce as TR

MODULE = "_decode_impl"
_LINE = re.compile(r'^\s*(?:ROOT )?%(\S+) = .*?op_name="([^"]*)"')
_SCOPE = re.compile(r"(?:^|/)(repro\.ops\.[A-Za-z0-9_]+)(?:/|$)")


def scope_map(hlo_text: str) -> dict[str, str]:
    """{instruction name: innermost ``repro.ops.*`` scope} over a compiled
    program's text, for the instructions made under such a scope."""
    out = {}
    for line in hlo_text.splitlines():
        m = _LINE.match(line)
        if m:
            scopes = _SCOPE.findall(m.group(2))
            if scopes:
                out[m.group(1)] = scopes[-1]
    return out


def scopes_of(eng) -> dict[str, str]:
    """The scope map of an engine's compiled decode step, for the shapes
    it serves (the compilation it already made, or the persistent cache's
    copy of it)."""
    import jax.numpy as jnp
    s = eng.sched
    lowered = eng._decode_jit.lower(
        eng.params, eng.pool, eng.spool, jnp.asarray(s.page_table),
        jnp.asarray(s.lens_vector()), jnp.asarray(s.active_mask()),
        jnp.asarray(s.tokens_vector()))
    return scope_map(lowered.compile().as_text())


def seconds(red: TR.Reduced, scopes: dict[str, str], scope: str) -> float:
    """Device-0 seconds, in the window, of the operations inside runs of
    the decode step that ``scopes`` puts under ``scope``."""
    if not red.modules:
        return 0.0
    dev = min(red.modules)
    runs = TR.module_runs(red, MODULE, dev)
    ops = red.ops.get(dev, [])
    tot, j = 0.0, 0
    for r in runs:
        while j < len(ops) and ops[j].start < r.start:
            j += 1
        k = j
        while k < len(ops) and ops[k].start < r.end:
            o = ops[k]
            if scopes.get(o.name) == scope:
                s, e = max(o.start, red.window[0]), min(o.end, red.window[1])
                tot += max(0.0, e - s)
            k += 1
        j = k
    return tot * 1e-9
