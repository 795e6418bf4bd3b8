"""The benchmark's one command.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the chip this process finds, and
prints as its last line one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` also
``breakdown``, and last ``checks``, each compared number with its limit.
With ``--trace 0`` the metrics are the cell's end-to-end metrics; with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
first ``trace_s`` seconds of the window and from the run's own records.
Lines before the last carry what else the run saw (compiles in the window,
generator lateness, memory analysis); the checks are also the last lines
on standard error.

It exits non-zero, and prints no result, on any platform but a TPU, with
fewer chips than the cell asks for, or with ``JAX_PALLAS_INTERPRET`` set.
"""
from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

import common  # noqa: E402

TRACE_DIR = common.ROOT / ".bench_trace"


class Context:
    """What a load loop gets: the cell's files, the run's settings, and the
    services of the harness (trace, device record, output checks)."""

    def __init__(self, args, workload, config, traffic, devices, limits):
        self.seed = args.seed
        self.seconds = args.seconds
        self.trace = bool(args.trace)
        self.workload = workload
        self.config = config
        self.traffic = traffic
        self.devices = devices
        self.limits = limits
        self.t_process = T_PROCESS
        self.traced_window = None
        self._annotation = None
        self._reference = None

    def start_trace(self) -> None:
        import jax
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(str(TRACE_DIR))
        self._annotation = common.span("traced_window")
        self._annotation.__enter__()
        self.traced_window = [time.monotonic(), None]

    def stop_trace(self, now: float) -> None:
        import jax
        self._annotation.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.traced_window[1] = now

    def device_record(self) -> dict:
        return common.device_record(self.devices)

    def check(self, name: str, value: float) -> dict:
        """One compared number against its limit (``limits/<cell>.json``):
        correct while value <= limit."""
        lim = self.limits[name]
        return {"name": name, "value": value, "limit": lim,
                "ok": bool(value == value and value <= lim)}

    def reference(self):
        """The configuration's plain reference (``reference/<name>.py``)."""
        if self._reference is None:
            self._reference = common.load_module(
                common.BENCH / "reference" / f"{self.config['reference']}.py",
                "bench_reference")
        return self._reference

    def served_gaps(self, picked, lowp: bool = False):
        """Over the ``(prompt, served)`` pairs picked, position by position,
        the gap of each served token below the reference's best; with
        ``lowp`` the control's reading at the same positions."""
        import numpy as np
        ref = self.reference()
        t = self.traffic
        length = t["prompt_len"]["max"] + t["output_len"]["max"]
        return np.concatenate([
            ref.served_gaps(self.seed, self.config, prompt, served,
                            lowp=lowp, length=length)
            for prompt, served in picked])

    def check_served(self, picked, lowp: bool = False) -> list[dict]:
        """Over the pairs picked, the widest gap of the served tokens below
        the reference's best, and the mean gap over every position, which
        grows as the square of the logits' error where the widest grows as
        the error itself (``tools/readings.py`` reads the controls with
        ``lowp``)."""
        gaps = self.served_gaps(picked, lowp)
        return [self.check("served_logit_gap", float(gaps.max())),
                self.check("served_logit_gap_mean", float(gaps.mean()))]


def _fail(msg: str) -> int:
    print(f"bench/run.py: {msg}", file=sys.stderr)
    return 2


def _per_layer(bench: dict, wl: dict, res: dict, ctx: Context) -> dict:
    import peaks
    import trace_reduce as TR
    files = sorted(TRACE_DIR.glob("plugins/profile/*/*.xplane.pb"))
    if not files:
        raise RuntimeError("the profiler wrote no trace")
    red = TR.from_xplane(str(files[-1]))
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    reports = {m["name"] for m in bench["end_to_end"]
               if wl["name"] in m.get("workloads", [wl["name"]])}
    view = {"records": res["records"], "trace": red, "config": ctx.config,
            "traffic": ctx.traffic,
            "peaks": peaks.peaks_for(ctx.devices[0].device_kind)}
    out = {}
    for m in bench["per_layer"]:
        if wl["name"] not in m.get("workloads", [wl["name"]]) \
                or m["moves"] not in reports:
            continue
        v = common.layer_reader(m["name"]).read(view)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    res["device"]["busy_s"] = TR.busy_s(red)
    res["device"]["window_s"] = red.window_s
    res["breakdown"] = {"device_ops": TR.top_ops(red),
                        "idle_gaps": TR.idle_gaps(red)}
    return out


def run_cell(args, bench: dict, wl: dict, config: dict, traffic: dict,
             limits: dict, devices) -> dict:
    """Everything after the look for a chip: drive the cell, read its
    metrics, compare its outputs. Returns the result line's object."""
    common.program_on_path()
    from repro.launch.compile_cache import compile_stats
    compile_stats()
    ctx = Context(args, wl, config, traffic, devices, limits)
    res = common.loop_for(traffic["kind"]).run(ctx)
    gc.collect()
    if args.trace:
        metrics = _per_layer(bench, wl, res, ctx)
    else:
        metrics = {m["name"]: {"value": res["metrics"][m["name"]],
                               "unit": m["unit"]}
                   for m in bench["end_to_end"]
                   if wl["name"] in m.get("workloads", [wl["name"]])}
    total = compile_stats()
    total.pop("by_fun")
    print(json.dumps({"notes": res["notes"], "compiles": total}), flush=True)
    checks = res["checks"]
    out = {"correct": all(c["ok"] for c in checks) and res["failed"] == 0,
           "attempted": res["attempted"], "failed": res["failed"],
           "metrics": metrics, "device": res["device"]}
    if "breakdown" in res:
        out["breakdown"] = res["breakdown"]
    out["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                     for c in checks}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if os.environ.get("JAX_PALLAS_INTERPRET"):
        return _fail("JAX_PALLAS_INTERPRET is set; it forces Pallas "
                     "interpret mode even on a TPU")
    bench = common.load_benchmark()
    wl = common.find_workload(bench, args.workload)
    config = common.load_config(wl["config"])
    traffic = common.load_traffic(wl["traffic"])
    limits = json.loads((common.BENCH / "limits"
                         / f"{wl['name']}.json").read_text())
    import jax
    common.program_on_path()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        return _fail(f"needs a TPU, JAX found {devices[0].platform}")
    if len(devices) < wl["chips"]:
        return _fail(f"the cell needs {wl['chips']} chips, JAX found "
                     f"{len(devices)}")
    out = run_cell(args, bench, wl, config, traffic, limits,
                   devices[:wl["chips"]])
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
