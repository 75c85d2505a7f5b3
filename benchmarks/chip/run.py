"""Run one benchmark cell on the chip this process is started on:

  python3 benchmarks/chip/run.py --workload mixtral-8x7b.prefill \
      --seed 12345 --seconds 51 --trace 0

Prints set-up phases, compile-cache counts and the compared numbers beside
their limits on standard error, and one JSON result as the last line of
standard output. Exits non-zero, with no result, where JAX finds no TPU or
fewer chips than the cell asks for.
"""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from benchmarks.chip import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(root=ROOT))
