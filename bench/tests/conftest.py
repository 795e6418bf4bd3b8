"""CPU tests of the benchmark's own code: the harness modules import each
other by bare name from ``bench/``, and the program from ``src/``."""
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
for p in (BENCH, BENCH.parent / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
