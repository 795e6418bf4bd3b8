"""The serving engine's step phases in traced runs of a cell, on the chip.

    python3 bench/tools/phases.py --workload <name> --seeds 511,512,513 \
        [--seconds 50]

For every seed, the cell as ``run.py --trace 1`` drives it, and two lines
of JSON: ``run.py``'s result line, then what ``engine_phases`` reads from
the same trace (the program's ``repro.engine.*`` spans): ``step_idle_ms``,
``prefill_ms_per_1k_tokens``, the idle gaps labelled by engine phase, and
the phase table. First, one line with the cost of one span, opened and
closed, with the profiler off and on (``span_cost_us``).
"""
from __future__ import annotations

import argparse
import gc
import json
import shutil
import sys
import time
from pathlib import Path
from types import SimpleNamespace

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import common  # noqa: E402
import engine_phases as EP  # noqa: E402
import run  # noqa: E402
import trace_reduce as TR  # noqa: E402


def _span_cost_us(n: int = 100_000) -> dict:
    """Microseconds per span with two fields, opened and closed, with the
    profiler off and on."""
    import jax
    from repro.obs import span

    def per_span() -> float:
        t = time.perf_counter()
        for i in range(n):
            with span("engine.cost", rid=i, tokens=1):
                pass
        return (time.perf_counter() - t) / n * 1e6

    off = per_span()
    tmp = run.TRACE_DIR / "span_cost"
    jax.profiler.start_trace(str(tmp))
    on = per_span()
    jax.profiler.stop_trace()
    shutil.rmtree(tmp, ignore_errors=True)
    return {"off": off, "on": on}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=50.0)
    a = ap.parse_args()
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("phases: needs a TPU", file=sys.stderr)
        return 2
    bench = common.load_benchmark()
    wl = common.find_workload(bench, a.workload)
    c, t = common.load_config(wl["config"]), common.load_traffic(wl["traffic"])
    limits = json.loads((common.BENCH / "limits"
                         / f"{wl['name']}.json").read_text())
    common.program_on_path()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    devices = devices[:wl["chips"]]
    print(json.dumps({"span_cost_us": _span_cost_us()}), flush=True)

    seen = {}
    reduce = TR.from_xplane

    def tap(path):
        seen["spans"] = EP.from_xplane(path)
        seen["red"] = reduce(path)
        return seen["red"]

    TR.from_xplane = tap
    for seed in [int(s) for s in a.seeds.split(",")]:
        seen.clear()
        args = SimpleNamespace(seed=seed, seconds=a.seconds, trace=1)
        out = run.run_cell(args, bench, wl, c, t, limits, devices)
        print(json.dumps(out), flush=True)
        red, spans = seen["red"], seen["spans"]
        print(json.dumps({
            "seed": seed,
            "engine.step_idle_ms": EP.step_idle_ms(red, spans),
            "engine.prefill_ms_per_1k_tokens":
                EP.prefill_ms_per_1k_tokens(red, spans),
            "idle_gaps": EP.idle_gaps(red, spans),
            "phases": EP.phase_table(red, spans)}), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
