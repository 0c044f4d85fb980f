"""The benchmark's own tests: ``pytest bench/tests`` on the CPU.

They sit outside the repository's tier-1 ``testpaths``.  The harness
and the program are imported from this checkout."""

import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
for p in (BENCH / "metrics", BENCH, BENCH.parent / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
