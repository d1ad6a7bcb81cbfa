"""Tests of the benchmark itself, run by hand on the CPU:

    JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests

The repository's own suite (``testpaths = tests``) does not collect them.
"""

from __future__ import annotations

import sys
from pathlib import Path

CHIP = Path(__file__).resolve().parents[1]
ROOT = CHIP.parents[1]
for p in (CHIP / "generators", CHIP / "queries", CHIP, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
if str(ROOT) not in sys.path:
    sys.path.append(str(ROOT))
