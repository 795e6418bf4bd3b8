"""Record the small profiler trace that ``bench/tests`` reduce, on a TPU.

    python3 bench/tools/record_fixture.py OUT_DIR

Runs a jitted ``_decode_impl`` holding the program's paged-attention page
walk (named scope ``repro.ops.paged_attention``) and a matmul, between two
host spans, a few times; writes the ``.xplane.pb`` to OUT_DIR together with
a text summary of its planes, lines, events and their statistics, which is
how one reads what a trace on this chip holds.
"""
from __future__ import annotations

import glob
import json
import shutil
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import common  # noqa: E402


def main(out: str) -> int:
    import jax
    import jax.numpy as jnp
    common.program_on_path()
    from repro.kernels.ops import paged_attention

    if jax.devices()[0].platform != "tpu":
        print("record_fixture: needs a TPU", file=sys.stderr)
        return 2
    b, hq, hkv, dh, page, pp = 2, 4, 2, 128, 16, 4
    k = jax.random.PRNGKey(0)
    q = jax.random.normal(k, (b, hq, dh), jnp.float32)
    kd = jax.random.randint(k, (b * pp + 1, page, hkv, dh), -100, 100,
                            jnp.int8)
    sc = jnp.full((b,), -6.0, jnp.float32)
    table = jnp.arange(b * pp, dtype=jnp.int32).reshape(b, pp)
    lens = jnp.asarray([20, 50], jnp.int32)
    w = jax.random.normal(k, (512, 512), jnp.bfloat16)

    def _decode_impl(q, kd, table, lens, w):
        o = paged_attention(q, kd, kd, sc, sc, table, lens, page_size=page,
                            quantized=True, impl="pallas")
        return o, jnp.tanh(w @ w)

    f = jax.jit(_decode_impl)
    jax.block_until_ready(f(q, kd, table, lens, w))
    tmp = Path(out) / "raw"
    shutil.rmtree(tmp, ignore_errors=True)
    jax.profiler.start_trace(str(tmp))
    with common.span("traced_window"):
        for _ in range(3):
            with common.span("engine.step"):
                jax.block_until_ready(f(q, kd, table, lens, w))
            with common.span("wait"):
                time.sleep(0.002)
    jax.profiler.stop_trace()
    pb = sorted(glob.glob(str(tmp / "plugins/profile/*/*.xplane.pb")))[-1]
    shutil.copy(pb, Path(out) / "fixture.xplane.pb")
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(pb)
    lines = []
    for plane in pd.planes:
        lines.append(f"PLANE {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            lines.append(f"  LINE {line.name!r} events={len(evs)}")
            for ev in evs[:12]:
                st = {kk: str(v)[:160] for kk, v in ev.stats}
                lines.append(f"    EV {ev.name!r} start={ev.start_ns} "
                             f"dur={ev.duration_ns} stats={json.dumps(st)}")
    (Path(out) / "summary.txt").write_text("\n".join(lines))
    shutil.rmtree(tmp, ignore_errors=True)
    print("\n".join(lines[:400]))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
