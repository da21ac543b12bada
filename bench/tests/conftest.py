"""CPU tests of the benchmark: run with

    JAX_PLATFORMS=cpu PYTHONPATH=src python -m pytest -q bench/tests
"""
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent.parent
for p in (BENCH, BENCH.parent / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
# the compiled step body (jitted XLA on the CPU), bit-exact with the chip's
os.environ.setdefault("REPRO_AP_KERNEL_VARIANT", "onehot_packed")
os.environ.setdefault("REPRO_AP_INTERPRET", "0")
