"""Run one benchmark cell once, on the accelerator the machine holds.

    python3 bench/run.py --workload fig7.saturate --seed 7 --seconds 30 \
        --trace 0

The last line of standard output is the result (a JSON object); the
compared numbers and their limits are also the last lines of standard
error. It exits non-zero, with no result, when JAX finds no TPU or
fewer chips than the cell asks for.
"""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main())
