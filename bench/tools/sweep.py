"""Find the highest rate an open-loop cell sustains, on the chip.

    python3 bench/tools/sweep.py --workload <name> --rates 2,3,4 --seconds 30

One process, one warm engine: for each rate, the cell's traffic at that
rate for a window of ``--seconds``, then a line of JSON: requests due and
served, TTFT and ITL percentiles, and the queue of requests due but not yet
given their first token, averaged over the first and the last third of
the window. A rate is sustained where that queue does not grow.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path
from types import SimpleNamespace

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import common  # noqa: E402
from common import percentile  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    a = ap.parse_args()
    import jax
    if jax.devices()[0].platform != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 2
    bench = common.load_benchmark()
    wl = common.find_workload(bench, a.workload)
    c, t = common.load_config(wl["config"]), common.load_traffic(wl["traffic"])
    common.program_on_path()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    import traffic as T
    drv = common.loop_for(t["kind"])
    ctx = SimpleNamespace(config=c, traffic=t, seed=a.seed, trace=False)
    eng, _, _ = drv.setup(ctx)
    for rate in (float(r) for r in a.rates.split(",")):
        tr = dict(t, rate_per_s=rate, tail_s=0.0)
        reqs = T.open_loop(tr, a.seed, a.seconds, c["vocab_size"])
        out = drv.serve(eng, reqs, tr, a.seconds)
        eng.run()                       # finish what arrived after
        ttft, itl, failed, done = drv.latencies(out)
        lo, hi = (w - out["start"] for w in out["window"])
        third = (hi - lo) / 3
        q1 = [n for ts, n in out["queue"] if lo <= ts < lo + third]
        q3 = [n for ts, n in out["queue"] if hi - third <= ts < hi]
        steps = out["steps"]
        print(json.dumps({
            "rate": rate, "due": sum(r.in_window for r in reqs),
            "done": len(done), "failed": failed,
            "queue_first_third": sum(q1) / max(len(q1), 1),
            "queue_last_third": sum(q3) / max(len(q3), 1),
            "ttft_p50_ms": 1e3 * percentile(ttft, 50),
            "ttft_p95_ms": 1e3 * percentile(ttft, 95),
            "itl_p50_ms": 1e3 * percentile(itl, 50),
            "itl_p95_ms": 1e3 * percentile(itl, 95),
            "drain_s": out["end"] - out["window"][1],
            "steps_per_s": len(steps) / (out["end"] - out["start"]),
            "compiles": out["compiles"]}), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
