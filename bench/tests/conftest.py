"""The benchmark's own tests run on the CPU at small sizes:

    JAX_PLATFORMS=cpu PYTHONPATH=src python -m pytest bench/tests -q
"""

import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
for p in (BENCH, BENCH.parent / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
