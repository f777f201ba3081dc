"""Find the highest arrival rate an open-loop cell sustains, on the chip.

    python3 bench/sweep.py --workload <cell> --seconds <s> --start <rate> \\
        [--steps 3] [--seed n]

Runs the cell's mix at rates that double from `--start` until the
backlog grows, then bisects `--steps` times between the last rate held
and the first one lost.  A rate is held when every session due in the
window finished within it or the drain, and the median latency of the
last quarter of arrivals is under twice that of the first quarter.  Each
trial is one run of the harness in this process.  Give it windows longer
than the mix's longest session, or a growing backlog stays hidden.  The
cell's rate is then written into its mix by hand, below the result.
"""
import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def trial(workload, rate, seconds, seed):
    from bench import harness
    cell = harness.load_cell(workload)
    cell["traffic"]["rate_per_s"] = rate
    cell["traffic"]["sample"] = 0
    res = harness.run(workload, seed, seconds, False,
                      t_start=time.perf_counter(), cell=cell)
    recs = sorted(res["records"], key=lambda r: r["spec"]["due"])
    lat = res["latency"]
    q = max(1, len(recs) // 4)
    first = statistics.median(lat[:q]) if lat else 0.0
    last = statistics.median(lat[-q:]) if lat else 0.0
    held = res["failed"] == 0 and last < 2.0 * first
    row = dict(rate=rate, sessions=len(recs), failed=res["failed"],
               p50=statistics.median(lat), p95=sorted(lat)[
                   int(0.95 * (len(lat) - 1))],
               first_quarter=first, last_quarter=last, held=held,
               lateness_max=max(res["lateness"]),
               occupancy=res["stats"].occupancy)
    print(json.dumps(row), flush=True)
    return held


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--start", type=float, required=True)
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--seed", type=int, default=2 ** 31 + 1)
    args = ap.parse_args()
    lo, hi, rate = None, None, args.start
    while hi is None and rate < 1e4:
        if trial(args.workload, rate, args.seconds, args.seed):
            lo, rate = rate, 2 * rate
        else:
            hi = rate
    for _ in range(args.steps if lo and hi else 0):
        mid = (lo + hi) / 2
        if trial(args.workload, mid, args.seconds, args.seed):
            lo = mid
        else:
            hi = mid
    print(json.dumps(dict(highest_held=lo, lowest_lost=hi)))


if __name__ == "__main__":
    main()
