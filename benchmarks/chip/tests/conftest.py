"""CPU tests of the chip benchmark: ``python -m pytest benchmarks/chip/tests``
from the repository root.  They put the benchmark's directory and the
program's ``src`` on the import path, and hold JAX to the CPU."""

import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = Path(__file__).resolve().parents[1]
for p in (HERE, HERE.parents[1] / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
