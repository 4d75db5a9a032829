"""Puts the benchmark's own modules (``benchmarks/chip``, its traffic
generators and its model families) on the path."""

import sys
from pathlib import Path

CHIP = Path(__file__).resolve().parents[2] / "benchmarks" / "chip"
for p in (CHIP, CHIP / "traffic", CHIP / "families"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
