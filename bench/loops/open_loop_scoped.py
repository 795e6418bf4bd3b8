"""The open loop (``loops/open_loop.py``), run as it is, with two more
records for per-layer readers of the program's own counters and named
scopes:

- ``moe_hits``: the engine's ``moe_hits`` events, (host time, live rows,
  held experts that got a live token, live token-expert pairs routed to
  them), one per decode step of a model with experts, summed over its
  layers, on the clock of the loop's step stamps;
- ``decode_scopes``: in a traced run, the compiled decode step's map from
  instruction name to ``repro.ops.*`` scope (``decode_scopes.py``), made
  once the window has closed and before the engine is freed.

Set-up, serving and latencies are the open loop's, so ``tools/sweep.py``
sweeps a cell of this kind as it sweeps any open-loop cell.
"""
from __future__ import annotations

import decode_scopes as DS
from common import BENCH, load_module

_BASE = load_module(BENCH / "loops" / "open_loop.py", "bench_loop_open_loop_base")
setup, serve, latencies = _BASE.setup, _BASE.serve, _BASE.latencies


def run(ctx) -> dict:
    seen = {}

    def setup_seen(ctx_):
        eng, recorder, warmed = setup(ctx_)
        seen["recorder"] = recorder
        return eng, recorder, warmed

    def serve_seen(eng, reqs, t, seconds, ctx_=None):
        out = serve(eng, reqs, t, seconds, ctx_)
        if ctx_ is not None and ctx_.trace:
            seen["scopes"] = DS.scopes_of(eng)
        return out

    _BASE.setup, _BASE.serve = setup_seen, serve_seen
    try:
        res = _BASE.run(ctx)
    finally:
        _BASE.setup, _BASE.serve = setup, serve
    res["records"]["moe_hits"] = [
        (e.ts, e.fields["rows"], e.fields["experts_hit"],
         e.fields["tokens_here"])
        for e in seen["recorder"].events() if e.kind == "moe_hits"]
    res["records"]["decode_scopes"] = seen.get("scopes", {})
    return res
