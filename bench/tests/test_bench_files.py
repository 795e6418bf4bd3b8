"""Every entry of BENCHMARK.json resolves to its files by name (a
configuration to its layout too), each traffic mix generates from a seed,
and the command refuses to run where there is no TPU."""
import ast
import json
import os
import re
import shutil
import subprocess
import sys
from collections import Counter

import pytest

import common
import traffic as T

BENCH = common.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"]
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51


@pytest.mark.parametrize("wl", BENCH["workloads"], ids=lambda w: w["name"])
def test_workload_resolves(wl):
    assert NAME.match(wl["name"]) and wl["chips"] in (1, 4)
    cfg = next(c for c in BENCH["configs"] if c["name"] == wl["config"])
    assert (common.ROOT / cfg["file"]).is_file()
    c = common.load_config(wl["config"])
    assert c["name"] == wl["config"] and c["source"] == cfg["source"]
    assert (common.BENCH / "reference" / f"{c['reference']}.py").is_file()
    t = common.load_traffic(wl["traffic"])
    assert (common.BENCH / "loops" / f"{t['kind']}.py").is_file()
    limits = json.loads((common.BENCH / "limits"
                         / f"{wl['name']}.json").read_text())
    assert limits and all(v > 0 for v in limits.values())
    e2e = [m["name"] for m in BENCH["end_to_end"]
           if wl["name"] in m.get("workloads", [wl["name"]])]
    assert "setup_s" in e2e and len(e2e) >= 2
    layers = [m for m in BENCH["per_layer"]
              if wl["name"] in m.get("workloads", [wl["name"]])]
    assert layers and all(m["moves"] in e2e for m in layers)


@pytest.mark.parametrize("m", BENCH["per_layer"], ids=lambda m: m["name"])
def test_per_layer_reader(m):
    assert NAME.match(m["name"])
    assert callable(common.layer_reader(m["name"]).read)
    assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}


def test_end_to_end_bounds():
    for m in BENCH["end_to_end"]:
        assert NAME.match(m["name"]) and m["better"] in ("lower", "higher")
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 + 12345, 2 ** 40 + 3])
def test_open_loop_same_work_every_seed(seed):
    t = common.load_traffic("serve-chat")
    reqs = T.open_loop(t, seed, 20.0, 1000)
    again = T.open_loop(t, seed, 20.0, 1000)
    assert [r.prompt for r in reqs] == [r.prompt for r in again]
    base = T.open_loop(t, 1, 20.0, 1000)
    # the schedule is the mix's: the same times and lengths in the same
    # order for every seed; the seed draws only the tokens
    assert [(r.due, len(r.prompt), r.max_new, r.in_window) for r in reqs] \
        == [(r.due, len(r.prompt), r.max_new, r.in_window) for r in base]
    assert seed == 1 or [r.prompt for r in reqs] != [r.prompt for r in base]
    lens = [len(r.prompt) for r in reqs]
    p = t["prompt_len"]
    assert min(lens) >= p["min"] and max(lens) <= p["max"]
    assert sorted(lens)[len(lens) // 2] == pytest.approx(p["median"], rel=0.1)
    assert all(0 <= tok < 1000 for r in reqs for tok in r.prompt)


@pytest.mark.parametrize("schedule_seed", [0, 2 ** 33 + 5])
def test_schedule_seed_orders_the_same_work(schedule_seed):
    t = common.load_traffic("serve-chat")
    base = T.open_loop(t, 3, 20.0, 1000)
    other = T.open_loop(dict(t, schedule_seed=schedule_seed), 3, 20.0, 1000)
    assert [r.due for r in other] != [r.due for r in base]
    assert Counter(len(r.prompt) for r in other) == \
        Counter(len(r.prompt) for r in base)
    assert Counter(r.max_new for r in other) == \
        Counter(r.max_new for r in base)
    assert other[-1].due == pytest.approx(base[-1].due)


@pytest.mark.parametrize("wl", BENCH["workloads"], ids=lambda w: w["name"])
def test_traffic_generates(wl):
    t = common.load_traffic(wl["traffic"])
    c = common.load_config(wl["config"])
    reqs = T.open_loop(t, 5, BENCH["run_seconds"], c["vocab_size"])
    assert sum(r.in_window for r in reqs) > 0
    assert max(tok for r in reqs for tok in r.prompt) < c["vocab_size"]


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    wl = BENCH["workloads"][0]["name"]
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", wl, "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_refuses_without_tpu():
    p = _run(common.ROOT)
    assert p.returncode != 0 and "{" not in p.stdout


def test_refuses_interpret_mode():
    p = _run(common.ROOT, {"JAX_PALLAS_INTERPRET": "1"})
    assert p.returncode != 0 and "{" not in p.stdout


def test_refuses_without_the_program(tmp_path):
    shutil.copy(common.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(common.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0 and "{" not in p.stdout


LAYOUT_API = ("model_config", "layer_weights", "params", "layer_params",
              "head_params", "attention_flops", "decode_token_flops",
              "paged_attention_bytes")


@pytest.mark.parametrize("cfg", BENCH["configs"], ids=lambda c: c["name"])
def test_config_layout_resolves(cfg):
    c = common.load_config(cfg["name"])
    path = common.BENCH / "layouts" / f"{c['program']['layout']}.py"
    lay = common.layout_of(c)
    assert all(callable(getattr(lay, f, None)) for f in LAYOUT_API)
    # the program is imported inside functions only, so a reference may
    # take its tensors from here
    top = ast.parse(path.read_text()).body
    names = [a.name for n in top if isinstance(n, ast.Import)
             for a in n.names]
    names += [n.module for n in top if isinstance(n, ast.ImportFrom)]
    assert not [m for m in names if m.split(".")[0] in ("repro", "program")]


def test_unknown_layout_names_the_missing_file():
    with pytest.raises(FileNotFoundError,
                       match=r"layouts/no_such_kind\.py is missing"):
        common.layout_for("no_such_kind")
