"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  ``--full`` restores the
paper's exact experiment sizes (50 nodes, 2000-3000 iterations; the 300 MC
trials are NOT replicated — see README "Quickstart" / EXPERIMENTS.md);
default settings are reduced-but-faithful for the CPU container.

``--json PATH`` additionally emits a machine-readable snapshot:
``{name: {us_per_call, derived}}`` plus a ``failed`` list.  It DEFAULTS to
``BENCH_engine.json`` at the repo root — that file is committed, so the
perf trajectory accumulates in-tree across PRs instead of living only in
CI artifacts (pass ``--json /dev/null`` to opt out).  ``--only`` matches
comma-separated prefixes against either the benchmark name or its group
(``paper_fig`` selects every fig*/table* reproduction).
"""
import argparse
import json
import os
import sys
import traceback

# self-bootstrapping: runnable as `python benchmarks/run.py` without any
# PYTHONPATH setup (repo root for `benchmarks`, src/ for `repro`)
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (_ROOT, os.path.join(_ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated benchmark-name or group prefixes")
    ap.add_argument("--json", metavar="PATH",
                    default=os.path.join(_ROOT, "BENCH_engine.json"),
                    help="also write {name: {us_per_call, derived}} JSON "
                         "(default: BENCH_engine.json at the repo root, "
                         "which is committed so the perf trajectory "
                         "accumulates across PRs)")
    args, _ = ap.parse_known_args()

    from repro import runtime
    runtime.use_compile_cache()
    from benchmarks import consensus_bench, gmm_backend_bench, kernel_bench, \
        linreg_bench, minibatch_bench, paper_figures, roofline, \
        svrg_bench, topology_scale_bench, vb_service_bench, \
        weights_ablation
    # (group, name, fn) — group is an --only alias for a family of benches
    benches = ([("paper_fig", f.__name__, f) for f in paper_figures.ALL]
               + [("weights_ablation", "weights_ablation",
                   weights_ablation.run),
                  ("linreg_generality", "linreg_generality",
                   linreg_bench.run),
                  ("kernel_bench", "kernel_bench", kernel_bench.run),
                  ("gmm_backend", "gmm_backend", gmm_backend_bench.run),
                  ("minibatch_vb", "minibatch_vb", minibatch_bench.run),
                  ("svrg_vb", "svrg_vb", svrg_bench.run),
                  ("vb_service", "vb_service_throughput",
                   vb_service_bench.run),
                  ("vb_driver", "vb_driver_poisson",
                   vb_service_bench.run_poisson),
                  ("vb_mixed", "vb_service_mixed",
                   vb_service_bench.run_mixed_fleet),
                  ("consensus_lm", "consensus_lm", consensus_bench.run),
                  ("consensus_vb", "consensus_vb", consensus_bench.vb_run),
                  ("topology_scale", "topology_scale",
                   topology_scale_bench.run),
                  ("roofline", "roofline", roofline.run)])
    if args.only:
        pre = tuple(args.only.split(","))
        benches = [b for b in benches
                   if b[0].startswith(pre) or b[1].startswith(pre)]

    print("name,us_per_call,derived")
    results, failed = {}, []
    for _group, bname, bench in benches:
        try:
            for name, us, derived in bench(full=args.full):
                print(f"{name},{us:.1f},{derived}")
                sys.stdout.flush()
                results[name] = {"us_per_call": us, "derived": derived}
        except Exception:
            failed.append(bname)
            print(f"{bname},nan,FAILED")
            traceback.print_exc()
    if args.json and args.json != "/dev/null":
        # merge into an existing snapshot (partial --only runs must not
        # wipe the committed trajectory's other rows)
        merged = {}
        if os.path.exists(args.json):
            try:
                with open(args.json) as f:
                    merged = json.load(f).get("results", {})
            except (ValueError, OSError) as e:
                print(f"WARNING: could not parse existing {args.json} "
                      f"({e}); its rows will be lost", file=sys.stderr)
        merged.update(results)
        with open(args.json, "w") as f:
            json.dump({"results": merged, "failed": failed}, f, indent=1,
                      default=float)
    if failed:
        raise SystemExit(1)


if __name__ == '__main__':
    main()
