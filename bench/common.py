"""Shared pieces of the benchmark: where things are, how a name resolves to
its files, seeds, spans, percentiles and the device record.

Everything a cell needs is found by the names that ``BENCHMARK.json``
gives: a workload names a configuration (``configs/<name>.json``) and a
traffic mix (``traffic/<name>.json``); the traffic file's ``kind`` names
the load loop (``loops/<kind>.py``); a per-layer metric's name is its
reader (``layers/<name>.py``); a configuration's ``program.layout`` names
its architecture's layout (``layouts/<kind>.py``: the program's model
config, the per-layer tensors, their place in the program's tree, and the
work counts that ``work.py`` forwards to). Adding a cell, a configuration
or a metric adds files and entries and edits none of these.

A configuration of a new architecture adds ``configs/<name>.json``, its
layout ``layouts/<kind>.py``, its plain reference ``reference/<name>.py``,
the limits of each of its cells ``limits/<cell>.json``, readers
``layers/<metric>.py`` for any metric its cells add, and its entries in
``BENCHMARK.json``.
"""
from __future__ import annotations

import functools
import importlib.util
import json
import math
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
SRC = ROOT / "src"


def program_on_path() -> None:
    """Make the system under test (``repro``, under ``src/``) importable."""
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))


def load_benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def find_workload(bench: dict, name: str) -> dict:
    for wl in bench["workloads"]:
        if wl["name"] == name:
            return wl
    raise KeyError(f"no workload named {name!r} in BENCHMARK.json")


def load_config(name: str) -> dict:
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def load_traffic(name: str) -> dict:
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


def load_module(path: Path, name: str):
    """Import one file by path (names may hold dots and dashes)."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def loop_for(kind: str):
    return load_module(BENCH / "loops" / f"{kind}.py", f"bench_loop_{kind}")


@functools.cache
def layout_for(kind: str):
    """The layout module ``layouts/<kind>.py``, loaded once a process:
    ``work.py`` asks for it at every count."""
    path = BENCH / "layouts" / f"{kind}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no layout {kind!r}: {path} is missing")
    return load_module(path, f"bench_layout_{kind}")


def layout_of(c: dict):
    """The layout a configuration file names (``program.layout``)."""
    return layout_for(c["program"]["layout"])


def layer_reader(metric: str):
    return load_module(BENCH / "layers" / f"{metric}.py",
                       "bench_layer_" + metric.replace(".", "_")
                       .replace("-", "_"))


def seed_words(seed: int) -> tuple[int, int]:
    """Two 32-bit words of a seed of any size (seeds may exceed 2**31)."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF


def np_rng(seed: int, stream: int):
    """A numpy generator for one named stream of one seed."""
    import numpy as np
    lo, hi = seed_words(seed)
    return np.random.default_rng([lo, hi, stream])


def leaf_name(path) -> str:
    """A parameter leaf's name from its tree path: ``head/w``."""
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between order
    statistics, over every value given."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def span(name: str):
    """A host span in the profiler's own trace (``bench.<name>``), so the
    reduction can say what the host did during a device idle gap."""
    import jax
    return jax.profiler.TraceAnnotation(f"bench.{name}")


def device_record(devices) -> dict:
    """What JAX reports of the devices a cell used, with the peak bytes in
    use on the fullest of them."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": int(max(peaks))}

