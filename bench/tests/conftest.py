"""The benchmark's own tests run on the CPU:

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest bench/tests -q

XLA:CPU contracts a*b + c into FMAs, which neither the TPU nor the
reference does; these tests compile the program without FMA
instructions so that the CPU rounds every step as the chip does."""
import os
import sys
from pathlib import Path

os.environ["XLA_FLAGS"] = " ".join(
    [os.environ.get("XLA_FLAGS", ""), "--xla_cpu_max_isa=AVX"]).strip()
ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
