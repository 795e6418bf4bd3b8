"""Run-to-run spread of a cell's end-to-end metrics, from which its bounds
are set.

    python3 bench/tools/spread.py --workload <name> --seeds 1,2,3,4,5,6 \
        [--sets 2] [--seconds 50] [--out DIR]

Runs ``bench/run.py`` once per seed in each set, each run a process of its
own, one after the other (this process never touches JAX, so each run has
the chip to itself). Every set uses the same seeds. Prints each run's
result line, then for each metric and set the median and the spread: the
distance between the first and third quartile (``statistics.quantiles``,
n=4) as a share of the median; and the wider of the sets' spreads. With
``--out`` each run's standard output and error are kept there.
"""
from __future__ import annotations

import argparse
import json
import statistics
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def spread(values: list[float]) -> tuple[float, float]:
    """(median, quartile distance over the median)."""
    q1, med, q3 = statistics.quantiles(values, n=4)
    return med, (q3 - q1) / med


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--seconds", type=int, default=0)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--out", default="")
    a = ap.parse_args()
    seeds = [int(s) for s in a.seeds.split(",")]
    seconds = a.seconds or json.loads(
        (ROOT / "BENCHMARK.json").read_text())["run_seconds"]
    out = Path(a.out) if a.out else None
    if out:
        out.mkdir(parents=True, exist_ok=True)
    runs: list[list[dict]] = []
    for k in range(a.sets):
        runs.append([])
        for seed in seeds:
            p = subprocess.run(
                [sys.executable, "bench/run.py", "--workload", a.workload,
                 "--seed", str(seed), "--seconds", str(seconds),
                 "--trace", str(a.trace)],
                cwd=ROOT, capture_output=True, text=True)
            if out:
                stem = out / f"set{k}_seed{seed}"
                stem.with_suffix(".out").write_text(p.stdout)
                stem.with_suffix(".err").write_text(p.stderr)
            lines = p.stdout.strip().splitlines()
            res = json.loads(lines[-1]) if p.returncode == 0 and lines else {}
            print(json.dumps({"set": k, "seed": seed, "rc": p.returncode,
                              **res}), flush=True)
            if res:
                runs[k].append(res)
    names = sorted({m for rs in runs for r in rs for m in r["metrics"]})
    for name in names:
        per = []
        for k, rs in enumerate(runs):
            vals = [r["metrics"][name]["value"] for r in rs
                    if name in r["metrics"]]
            if len(vals) >= 2:
                med, sp = spread(vals)
                per.append(sp)
                print(json.dumps({"metric": name, "set": k, "median": med,
                                  "spread": sp, "values": vals}))
        if per:
            print(json.dumps({"metric": name, "widest_spread": max(per)}))
    correct = sum(bool(r.get("correct")) for rs in runs for r in rs)
    print(json.dumps({"runs": sum(len(rs) for rs in runs),
                      "correct": correct}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
