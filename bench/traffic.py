"""The one traffic generator: every mix is a data file of parameters that
this module reads (``traffic/<name>.json``).

A mix of ``"kind": "open_loop"`` is a stream of requests due at fixed
times, sent whether or not the server keeps up. Its inter-arrival gaps,
prompt lengths and output lengths are the stratified quantiles of the
stated distributions, put in an order drawn from the mix's own
``schedule_seed``. So every run offers the same requests at the same times,
and the run's seed draws only their prompt tokens (and the weights): a tail
latency then moves with the system, not with the burst one seed's order
happens to make.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import numpy as np
from scipy.special import ndtri

from common import np_rng


def stratified(dist: dict, n: int) -> np.ndarray:
    """n values at the mid-quantiles (i + 0.5) / n of a distribution:
    {"dist": "lognormal", "median", "sigma", "min", "max"} (rounded to
    whole numbers and clipped) or {"dist": "exponential", "mean"}."""
    u = (np.arange(n) + 0.5) / n
    if dist["dist"] == "lognormal":
        z = ndtri(u)
        x = dist["median"] * np.exp(dist["sigma"] * z)
        return np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)
    if dist["dist"] == "exponential":
        return -dist["mean"] * np.log1p(-u)
    raise ValueError(f"unknown distribution {dist['dist']!r}")


@dataclass
class Request:
    index: int
    due: float              # seconds after the load starts
    prompt: list[int]
    max_new: int
    in_window: bool


def open_loop(t: dict, seed: int, seconds: float, vocab: int
              ) -> list[Request]:
    """Requests due over the lead-in, the window and the drain that
    follows it. The lead-in and the tail keep the load steady on both
    sides; only requests due inside the window are measured. The schedule
    comes from ``t["schedule_seed"]``, the prompt tokens from ``seed``."""
    rate = t["rate_per_s"]
    horizon = t["lead_s"] + seconds + t["tail_s"]
    n = int(math.ceil(rate * horizon))
    gaps = stratified({"dist": "exponential", "mean": 1.0 / rate}, n)
    plens = stratified(t["prompt_len"], n)
    olens = stratified(t["output_len"], n)
    rng = np_rng(t["schedule_seed"], 1)
    due = np.cumsum(rng.permutation(gaps))
    plens, olens = rng.permutation(plens), rng.permutation(olens)
    lo, hi = t["lead_s"], t["lead_s"] + seconds
    tok = np_rng(seed, 2)
    return [Request(i, float(due[i]),
                    tok.integers(0, vocab, int(plens[i])).tolist(),
                    int(olens[i]), bool(lo <= due[i] < hi))
            for i in range(n)]
