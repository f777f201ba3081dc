#!/usr/bin/env python3
"""The on-chip benchmark: one cell, one run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Makes the cell's inputs from the seed, warms every shape the window will
use, drives the serving driver for `--seconds`, checks a sample of the
answers against the plain reference, and prints the numbers on standard
error and one JSON result as the last line of standard output: with
`--trace 0` the cell's end-to-end metrics, with `--trace 1` its per-layer
metrics from a profiler trace of the window.  With no TPU, or fewer chips
than the cell asks for, it exits 1 and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def percentile(values, p):
    """Nearest rank: the smallest value with at least p% at or below."""
    s = sorted(values)
    return s[max(0, math.ceil(p / 100.0 * len(s)) - 1)]


def end_to_end(res) -> dict:
    out = {"setup_s": res["setup_s"]}
    if "iters" in res:
        out["iters_per_s"] = res["iters"] / res["window_s"]
    if res.get("latency"):
        out["latency_p50_s"] = percentile(res["latency"], 50)
        out["latency_p95_s"] = percentile(res["latency"], 95)
    return out


def read_metric(name, ctx):
    """The reader `bench/metrics/<name>.py`, else that of the name without
    its last ".<part>": one reader serves a quantity split by cell."""
    path = os.path.join(ROOT, "bench", "metrics", name + ".py")
    if not os.path.exists(path):
        path = os.path.join(ROOT, "bench", "metrics",
                            name.rsplit(".", 1)[0] + ".py")
    spec = importlib.util.spec_from_file_location("bench_metric", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness, peaks
    from bench import trace as trace_lib

    cell = harness.load_cell(args.workload)
    res = harness.run(args.workload, args.seed, args.seconds,
                      bool(args.trace), t_start=T_START, cell=cell)
    log = harness.log
    spec, w = cell["spec"], cell["workload"]

    clock = res["compile_log"]
    setup = [c for c in clock.compiles if c[0] < T_START + res["setup_s"]]
    log(f"set-up: {res['setup_s']:.3f} s, {len(setup)} compiles "
        f"({sum(c[2] for c in setup):.3f} s), persistent cache "
        f"{clock.hits} hits, {clock.writes} writes")
    for _, name, secs in sorted(setup, key=lambda c: -c[2])[:12]:
        log(f"  compile {name}: {secs:.3f} s")
    inw = res["compiles_in_window"]
    log(f"compiles inside the window: {len(inw)} "
        f"{sorted({c[1] for c in inw})}")
    recs = res["records"]
    log(f"window: {res['window_s']:.3f} s, {len(recs)} sessions submitted, "
        f"{sum(r['finish'] is not None for r in recs)} finished; driver "
        f"{res['stats'].slices} slices, occupancy "
        f"{res['stats'].occupancy:.3f}")
    if res.get("lateness"):
        late = res["lateness"]
        log(f"generator lateness: mean {sum(late) / len(late) * 1e3:.3f} ms,"
            f" max {max(late) * 1e3:.3f} ms")
    log(f"device memory peak: {res['memory_peak_bytes']} bytes")
    for s, gap in res["gaps"]:
        log(f"  session {s['index']} {s['rule']} budget {s['budget']} "
            f"size {s['size']}: phi_gap {gap:.6e}")

    device = dict(res["device"], memory_peak_bytes=res["memory_peak_bytes"])
    line = dict(correct=bool(res["correct"]), attempted=res["attempted"],
                failed=res["failed"])
    e2e = end_to_end(res)
    if args.trace:
        tr = res["trace"]
        device["busy_s"] = trace_lib.busy_s(tr)
        lo, hi = trace_lib.window(tr)
        device["window_s"] = (hi - lo) / 1e9
        ctx = dict(trace=tr, spans=res["spans"], config=cell["config"],
                   traffic=cell["traffic"],
                   peaks=peaks.peaks(res["device"]["kind"]))
        metrics = {}
        for m in spec["per_layer"]:
            # listed for this cell, or listed for none and moving a
            # metric this cell reports
            if w["name"] in m.get("workloads",
                                  [w["name"]] if m["moves"] in e2e else []):
                value = read_metric(m["name"], ctx)
                if value is not None:
                    metrics[m["name"]] = dict(value=value, unit=m["unit"])
        line["metrics"] = metrics
        line["device"] = device
        line["breakdown"] = trace_lib.breakdown(tr)
    else:
        line["metrics"] = {m["name"]: dict(value=e2e[m["name"]],
                                           unit=m["unit"])
                           for m in spec["end_to_end"]
                           if m["name"] in e2e
                           and w["name"] in m.get("workloads", [w["name"]])}
        line["device"] = device
    for name, c in res["checks"].items():
        log(f"check {name}: {c['value']!r} limit {c['limit']!r}")
    line["checks"] = res["checks"]
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
