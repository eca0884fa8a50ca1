"""The benchmark's own tests, on the CPU: ``python -m pytest bench/tests``.

The Pallas kernel runs in the interpreter here (``REPRO_PALLAS_INTERPRET``,
read by the program's ``kernels/ops.py``).
"""
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(1, str(BENCH.parent / "src"))
os.environ.setdefault("REPRO_PALLAS_INTERPRET", "1")
