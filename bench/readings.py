"""Readings the limits of `correct` are set from: the program's numbers
and the control's (the reference in bfloat16, put in the program's
place) over several seeds of one cell, at the cell's own size and load,
in one process.

    python3 bench/readings.py --workload fig7.saturate --seconds 30 \
        --seeds 101 102 103

One JSON line per seed on standard output.
"""
import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", default="bf16")
    args = ap.parse_args(argv)
    (ROOT / ".bench_out").mkdir(exist_ok=True)
    for seed in args.seeds:
        keep = {}
        out = harness.run(args.workload, seed, args.seconds, False,
                          control=args.control, keep=keep)
        print(json.dumps({"seed": seed, "correct": out["correct"],
                          "metrics": out["metrics"],
                          "program": keep["checks"],
                          "control": out["control_checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
