"""The routing control of a cell whose model routes with a correction bias
(sigmoid ``noaux_tc``): the program picks its experts without the bias,
the plain reference keeps it. It has to read ``correct: false``.

    python3 bench/tools/route_control.py --workload <name> --seeds 1,2,3 \
        [--seconds 15]

Runs ``tools/readings.py`` in this process, with the program's weights
(``program.params``) made as ever and then every ``router_bias`` leaf set
to zero. Its first line says so; every line after it is a control.
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import common  # noqa: E402
import program  # noqa: E402


def main() -> int:
    import jax
    import jax.numpy as jnp
    made = program.params

    def unbiased(c, lm, seed):
        return jax.tree_util.tree_map_with_path(
            lambda path, a: (jnp.zeros_like(a)
                             if common.leaf_name(path).endswith("router_bias")
                             else a), made(c, lm, seed))

    program.params = unbiased
    print(json.dumps({"control": "route_without_bias"}), flush=True)
    readings = common.load_module(common.BENCH / "tools" / "readings.py",
                                  "bench_tool_readings")
    return readings.main()


if __name__ == "__main__":
    sys.exit(main())
