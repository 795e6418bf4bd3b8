"""``chip_smoke.py`` rehearsed on the CPU: its phase functions at
``get_reduced`` widths (Pallas kernels in interpret mode; the compiled-
kernel check only applies on a TPU), and its refusal to report success
anywhere but on a TPU."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402
import repro.configs as C  # noqa: E402

CFG = C.get_reduced(chip_smoke.ARCH)


def test_serve_phase_reduced():
    rec = chip_smoke.serve_phase(CFG, slots=2, prompt_len=24, gen_len=6,
                                 page_size=8)
    assert rec["ok"], rec
    assert rec["completed"] == 2 and rec["tokens"] == 12
    assert rec["codec_fallbacks"] == 0
    assert [c["q_rows"] for c in rec["kernel_vs_jnp"]] == [1, 5]


def test_fmnist_phase_loss_falls():
    rec = chip_smoke.fmnist_phase(steps=20)
    assert rec["ok"], rec
    assert rec["loss_last5"] < rec["loss_first5"]


def test_lm_train_phase_reduced():
    rec = chip_smoke.lm_train_phase(CFG, steps=3, batch=2, seq=32)
    assert rec["ok"], rec
    assert rec["train_step_compiles"] == 1
    assert rec["tt_max_rank"] == 32


def test_multi_device_phases_reduced():
    """The --chips 4 phases on forced CPU devices: a (1, 2) TP mesh (the
    reduced config has 2 KV heads) and a 4-way dp mesh."""
    code = (
        "import sys; sys.path.insert(0, '.');"
        "import chip_smoke, repro.configs as C;"
        "from repro.launch.mesh import make_dp_mesh, make_mesh;"
        f"cfg = C.get_reduced({chip_smoke.ARCH!r});"
        "a = chip_smoke.tp_serve_phase(cfg, make_mesh((1, 2), "
        "('data', 'model')), slots=2, prompt_len=16, page_size=8);"
        "b = chip_smoke.dp_train_phase(cfg, make_dp_mesh(4), batch=8, "
        "seq=16);"
        "sys.exit(0 if a['ok'] and b['ok'] else 1)")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="src",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr[-3000:]
    recs = [json.loads(l) for l in r.stdout.splitlines()
            if l.startswith("{")]
    tp = next(x for x in recs if x["phase"] == "tp_serve")
    assert tp["kv_heads_per_chip"] == 1
    dp = next(x for x in recs if x["phase"] == "dp_train")
    assert dp["collectives"]["all-gather"] >= 1, dp


def test_main_refuses_without_tpu(capsys):
    rc = chip_smoke.main([])
    out = capsys.readouterr().out
    assert rc != 0
    assert '"ok": true' not in out


@pytest.mark.parametrize("alone", [False, True])
def test_script_fails_off_chip(tmp_path, alone):
    """As a script, in the checkout and in a directory holding only the
    script: non-zero exit and no success line."""
    if alone:
        shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
        cwd = tmp_path
    else:
        cwd = ROOT
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd, env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout
