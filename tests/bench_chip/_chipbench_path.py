"""Puts the chip benchmark's harness directory on ``sys.path``."""
import sys
from pathlib import Path

HARNESS = Path(__file__).resolve().parents[2] / "benchmarks" / "chip"
if str(HARNESS) not in sys.path:
    sys.path.insert(0, str(HARNESS))
