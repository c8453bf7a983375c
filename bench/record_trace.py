"""Record a short device trace of one cell as the fixture of
`bench/tests/test_trace_reduce.py::test_recorded_trace`: the events of
the traced window and what `bench.trace_reduce.reduce` made of them.

    python3 bench/record_trace.py --workload fig7.saturate --seed 5 \
        --seconds 0.3 --out bench/tests/data/trace_small.json
"""
import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench import harness, trace_reduce  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    (ROOT / ".bench_out").mkdir(exist_ok=True)
    keep = {}
    harness.run(args.workload, args.seed, args.seconds, True, keep=keep)
    ev = keep["trace_events"]
    lo, hi = next((s, e) for n, s, e in ev["spans"]
                  if n == trace_reduce.WINDOW_SPAN)

    def inside(events):
        return [list(x) for x in events if x[2] > lo and x[1] < hi]
    trace = {"chips": {k: {kk: inside(vv) for kk, vv in v.items()}
                       for k, v in ev["chips"].items()},
             "spans": inside(ev["spans"])}
    red = trace_reduce.reduce(ev)
    expect = {k: red[k] for k in ("window_s", "busy_s", "modules_s",
                                  "ops_s")}
    Path(args.out).write_text(json.dumps({"trace": trace,
                                          "expect": expect}))
    print(json.dumps({"events": sum(len(vv) for v in trace["chips"].values()
                                    for vv in v.values()),
                      "spans": len(trace["spans"]), **expect}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
