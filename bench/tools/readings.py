"""Readings that a cell's limits are set from, on the chip, in one process.

    python3 bench/tools/readings.py --workload <name> --seeds 1,2,3 \
        [--seconds 15] [--control 1,2] [--kv-bits 4]

For every seed, the whole cell as a run drives it (set-up, a short window
of ``--seconds`` at its own load, the drain and the comparison with the
plain reference), and one line of JSON with what ``run.py`` decides:
``correct`` and each compared number beside its limit.

The controls go through the same comparison and have to read
``correct: false``:

- ``--control``: for these seeds, also the reference with int8 x int8
  products, one step below the configuration's bfloat16, read at every
  position of the same served tokens (a line with ``"control":
  "int8_products"``);
- ``--kv-bits 4``: the program itself with its 4-bit KV pool, one step
  below the configuration's int8 storage; every line is then a control
  (``"control": "kv_bits_4"``).
"""
from __future__ import annotations

import argparse
import copy
import gc
import json
import sys
from pathlib import Path
from types import SimpleNamespace

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import common  # noqa: E402
import run  # noqa: E402


def _line(seed, checks, **extra) -> str:
    return json.dumps({"seed": seed, **extra,
                       "correct": all(c["ok"] for c in checks),
                       "checks": {c["name"]: {"value": c["value"],
                                              "limit": c["limit"]}
                                  for c in checks}})


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", default="")
    ap.add_argument("--kv-bits", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=15.0)
    a = ap.parse_args()
    seeds = [int(s) for s in a.seeds.split(",")]
    controls = {int(s) for s in a.control.split(",") if s}
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("readings: needs a TPU", file=sys.stderr)
        return 2
    bench = common.load_benchmark()
    wl = common.find_workload(bench, a.workload)
    c, t = common.load_config(wl["config"]), common.load_traffic(wl["traffic"])
    label = {}
    if a.kv_bits:
        c = copy.deepcopy(c)
        c["serve"]["kv_bits"] = a.kv_bits
        label = {"control": f"kv_bits_{a.kv_bits}"}
    limits = json.loads((common.BENCH / "limits"
                         / f"{wl['name']}.json").read_text())
    common.program_on_path()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    devices = devices[:wl["chips"]]

    picked = []
    orig = run.Context.check_served

    def keep(self, p, lowp=False):
        picked.extend(p)
        return orig(self, p, lowp)

    run.Context.check_served = keep
    for seed in seeds:
        picked.clear()
        args = SimpleNamespace(seed=seed, seconds=a.seconds, trace=0)
        out = run.run_cell(args, bench, wl, c, t, limits, devices)
        print(json.dumps({"seed": seed, **label, "correct": out["correct"],
                          "attempted": out["attempted"],
                          "failed": out["failed"], "checks": out["checks"],
                          "tokens": sum(len(s) for _, s in picked)}),
              flush=True)
        if seed in controls:
            ctx = run.Context(args, wl, c, t, devices, limits)
            print(_line(seed, orig(ctx, list(picked), lowp=True),
                        control="int8_products"), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
