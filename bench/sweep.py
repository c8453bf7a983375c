"""The knee of a serve cell: the highest open-loop rate with no growing
backlog, by a short run at each rate of a sweep (one process).

    python3 bench/sweep.py --workload fig7.steady --seconds 10 \
        --rates 1500 2000 2500 3000 --seed 5

Pushes are synchronous, so a backlog shows as the generator falling
behind its stamps: per rate it prints the decided rate, the p95
latency and the generator's lag over the first and the last tenth of
the window (a lag that grows through the window is a growing backlog).
"""
import argparse
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    (ROOT / ".bench_out").mkdir(exist_ok=True)
    for rate in args.rates:
        keep = {}
        out = harness.run(args.workload, args.seed, args.seconds, False,
                          traffic_overrides={"vm_rate_per_s": rate},
                          keep=keep)
        w = keep["win"]
        lag = w["gen_lag_s"]
        k = max(len(lag) // 10, 1)
        print(json.dumps({
            "rate": rate, "correct": out["correct"],
            "decided_per_s": w["decided"] / w["seconds"],
            "p95_ms": float(np.percentile(w["latency_s"], 95)) * 1e3,
            "lag_first_ms": float(np.mean(lag[:k])) * 1e3,
            "lag_last_ms": float(np.mean(lag[-k:])) * 1e3,
            "metrics": out["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
