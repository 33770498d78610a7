"""The benchmark's own tests: on the CPU, at tiny sizes, outside tier 1.

    JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests
"""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
CHIP = Path(__file__).resolve().parents[1]
ROOT = CHIP.parents[1]
for p in (str(ROOT / "src"), str(CHIP)):
    if p not in sys.path:
        sys.path.insert(0, p)
