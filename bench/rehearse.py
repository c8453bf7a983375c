"""CPU rehearsal of every cell at tiny sizes: the harness's whole path
(set-up, window, trace, check, result line) without a chip.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py [workload ...]

The sizes below only exercise the paths; no number it prints is a
speed of anything.
"""
import json
import os
import sys
from pathlib import Path

# XLA:CPU contracts a*b + c into FMAs, which the TPU and the reference
# do not; without FMA instructions the CPU rounds every step alike
os.environ["XLA_FLAGS"] = " ".join(
    [os.environ.get("XLA_FLAGS", ""), "--xla_cpu_max_isa=AVX"]).strip()

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench import harness  # noqa: E402

#: per configuration: the configuration keys a rehearsal shrinks
TINY = {
    "fig7_cluster": {"n_chassis": 4, "batch_size": 32, "history_vms": 300,
                     "forest": {"n_trees": 8, "depth": 6}},
    "table4_campus": {"n_chassis": 16, "check_chassis": 3},
}


def main(argv) -> int:
    bench = harness.load_json(ROOT / "BENCHMARK.json")
    names = argv or [w["name"] for w in bench["workloads"]]
    (ROOT / ".bench_out").mkdir(exist_ok=True)
    for name in names:
        cell = harness.find(bench["workloads"], name, "workload")
        for trace in (0, 1):
            out = harness.run(name, 1234567891234, 2.0, bool(trace),
                              require_tpu=False,
                              overrides=TINY[cell["config"]])
            print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
