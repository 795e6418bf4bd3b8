"""Record the small profiler trace of the engine's step phases that
``bench/tests`` read, on a TPU.

    python3 bench/tools/record_engine_spans.py OUT_DIR

Serves two short prompts on a tiny engine (the program's reduced
internlm2-1.8b widths, two slots, prefill bucket 8) once to compile, then
again under the profiler, each ``Engine.step()`` inside a
``bench.engine.step`` span and all of it inside ``bench.traced_window``;
writes ``engine_spans.xplane.pb`` to OUT_DIR together with a text summary
of the program's ``repro.*`` host events and their statistics.
"""
from __future__ import annotations

import glob
import json
import shutil
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import common  # noqa: E402

PROMPTS = (5, 11)


def main(out: str) -> int:
    import jax
    import numpy as np
    common.program_on_path()
    import repro.configs as C
    from repro.models import build_lm, init_lm
    from repro.serve import Engine, EngineConfig, PoolConfig
    from repro.sharding import ShardPlan

    if jax.devices()[0].platform != "tpu":
        print("record_engine_spans: needs a TPU", file=sys.stderr)
        return 2
    cfg = C.get_reduced("internlm2-1.8b").replace(dtype="float32",
                                                  remat="none")
    lm = build_lm(cfg)
    params = init_lm(jax.random.PRNGKey(0), lm)
    pcfg = PoolConfig(num_slots=2, page_size=8, pages_per_slot=4,
                      quantized=True)
    eng = Engine(lm, params, EngineConfig(pool=pcfg, prefill_bucket=8),
                 ShardPlan(mesh=None))
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab_size, n).tolist() for n in PROMPTS]
    for p in prompts:
        eng.submit(p, max_new_tokens=4)
    eng.run()
    for p in prompts:
        eng.submit(p, max_new_tokens=4)
    tmp = Path(out) / "raw"
    shutil.rmtree(tmp, ignore_errors=True)
    jax.profiler.start_trace(str(tmp))
    with common.span("traced_window"):
        while eng.sched.has_work():
            with common.span("engine.step"):
                eng.step()
    jax.profiler.stop_trace()
    pb = sorted(glob.glob(str(tmp / "plugins/profile/*/*.xplane.pb")))[-1]
    shutil.copy(pb, Path(out) / "engine_spans.xplane.pb")
    from jax.profiler import ProfileData
    lines = []
    for plane in ProfileData.from_file(pb).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(("repro.engine.", "bench.")):
                    st = {k: str(v)[:80] for k, v in ev.stats}
                    lines.append(f"{plane.name} {line.name!r} {ev.name} "
                                 f"start={ev.start_ns} dur={ev.duration_ns} "
                                 f"stats={json.dumps(st)}")
    (Path(out) / "engine_spans.txt").write_text("\n".join(lines))
    shutil.rmtree(tmp, ignore_errors=True)
    print("\n".join(lines))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
