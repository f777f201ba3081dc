"""Readings for the limits of `correct`, on the chip at a cell's own size.

    python3 bench/tests/control.py --workload <cell> --seconds <s> \\
        --seeds <a,b,...> [--out control_<cell>.json]

For each seed, in one process: one run of the cell (its timed path, as
`bench/run.py` drives it), which gives the program's gap to the
reference for each sampled session (the lower reading) and its
`correct`.  Then the control takes the program's place: the same
reference computed at the next precision below the configuration's,
"high" (three bfloat16 passes) for float32 at "highest", goes through
the same comparison (`harness.check`) as the program's answers, and its
gaps (the upper reading) and its `correct`, which has to be false, are
recorded.  Prints one JSON line per session and one per seed, and writes
them all to `--out`.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    from bench import compare, harness
    from bench.reference import gmm_vb

    rows = []
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        cell = harness.load_cell(args.workload)
        res = harness.run(args.workload, seed, args.seconds, False,
                          t_start=t0, cell=cell)
        inp = res["inputs"]
        names = gmm_vb.block_names(inp.K, inp.D)
        specs = [spec for spec, _ in res["gaps"]]
        wants = [harness.reference_phi(inp, s, "highest") for s in specs]
        ctrls = [harness.reference_phi(inp, s, "high") for s in specs]
        answers = [({"spec": s}, c, s["budget"]) for s, c in zip(specs, ctrls)]
        checks, gaps = harness.check(inp, answers, cell["limits"],
                                     failed=0, wants=wants)
        control_correct = all(v["value"] <= v["limit"]
                              for v in checks.values())
        for (spec, prog), (_, ctrl), c in zip(res["gaps"], gaps, ctrls):
            row = dict(seed=seed, index=spec["index"], rule=spec["rule"],
                       budget=spec["budget"], size=spec["size"],
                       program=prog, control=ctrl,
                       control_blocks=compare.block_gaps(
                           c, wants[specs.index(spec)], names))
            rows.append(row)
            print(json.dumps(row), flush=True)
        row = dict(seed=seed, program_correct=res["correct"],
                   program_checks=res["checks"],
                   control_correct=control_correct, control_checks=checks,
                   compiles_in_window=len(res["compiles_in_window"]),
                   setup_s=res["setup_s"])
        rows.append(row)
        print(json.dumps(row), flush=True)
        del res, inp
    if args.out:
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
