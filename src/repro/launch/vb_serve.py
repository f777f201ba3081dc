"""VB serving launcher: a fleet of sensor-network sessions through
`serving.vb_service.VBService`.

    PYTHONPATH=src python -m repro.launch.vb_serve \
        --sessions 2 --budgets 30,60 --nodes 8 --per-node 20 --slice 16

Each session is an independent synthetic sensor network (the paper's
Sec. V-A generator with a different seed); `--budgets` gives the
per-session iteration budgets (cycled when shorter than `--sessions` —
heterogeneous budgets exercise the per-session gating), `--tol` enables
early stop, `--topology mixed` alternates diffusion and adaptive ADMM
fleets, `--push-at` demonstrates mid-flight data arrival, and
`--ckpt-dir` saves + restores + re-runs session 0 to demonstrate the
checkpoint path (asserting bit-exactness with the uninterrupted run).

Continuous batching (serving/driver.py): `--max-fleet` fixes the fleet
capacity — later arrivals queue until an eviction frees a slot, with
zero recompilation — and `--arrive-at` staggers session admission to
the given slice boundaries (cycled), demonstrating mid-flight join.

Bucketed admission (docs/bucketed-admission.md): `--per-node` and
`--taus` take comma-separated lists (cycled over sessions), so a MIXED
fleet — several data shapes, several Robbins-Monro taus — still lands
in one compiled fleet group per capacity rung; `--bucket` selects the
ladder ("pow2", a growth factor like 1.25, or "none" for legacy
exact-shape grouping).  The run ends by printing the `DriverStats`
counters plus the per-bucket occupancy/padding breakdown.
"""
import argparse
import os

K, D = 3, 2          # the paper's Sec. V-A mixture


def build_requests(*, sessions: int, nodes: int, per_node, budgets,
                   taus=(), topology: str = "mixed", minibatch: int = 0,
                   tol: float = 0.0) -> list:
    """The fleet this launcher serves, one `VBRequest` per session.

    Session i is the paper's Sec. V-A GMM (K=3, D=2) on `nodes` nodes
    with `per_node[i % len]` points per node (generator seed i) and the
    last point of every node left free for `push_data`; `budgets` and
    `taus` cycle the same way.  `topology="mixed"` alternates dSVB
    diffusion and adaptive dVB-ADMM."""
    from repro.core import engine, expfam, network
    from repro.core import model as model_lib
    from repro.data import stream, synthetic
    from repro.serving.vb_service import VBRequest

    prior = expfam.noninformative_prior(K, D, beta0=0.1, w0_scale=10.0)
    adj, _ = network.random_geometric_graph(nodes, seed=0)
    W = network.nearest_neighbor_weights(adj)
    mdl = model_lib.GMMModel(prior, K, D)
    topos = {"diffusion": engine.Diffusion(W),
             "admm": engine.ADMMConsensus(adj, adaptive_rho=True),
             "ring": engine.RingDiffusion()}
    order = ["diffusion", "admm"] if topology == "mixed" else [topology]
    mb = stream.MinibatchSpec(minibatch) if minibatch else None
    requests = []
    for i in range(sessions):
        data = synthetic.paper_synthetic(
            n_nodes=nodes, n_per_node=per_node[i % len(per_node)], seed=i)
        # leave one free slot per node so push_data has capacity
        mask = data.mask.at[:, -1].set(0.0)
        topo = topos[order[i % len(order)]]
        sched = engine.Schedule()
        if taus and getattr(topo, "uses_schedule", True):
            sched = engine.Schedule(tau=taus[i % len(taus)])
        requests.append(VBRequest(model=mdl, data=(data.x, mask),
                                  topology=topo, schedule=sched,
                                  n_iters=budgets[i % len(budgets)],
                                  minibatch=mb, tol=tol))
    return requests


def serve(svc, *, push_at: int = 0) -> int:
    """Drive `svc` until every session is done; after `push_at` slices
    (0 = never) append one fresh point to node 0 of the first session.
    Returns the number of slices."""
    import numpy as np

    n_slices = 0
    while True:
        left = svc.step_slice()
        n_slices += 1
        if push_at and n_slices == push_at:
            rid0 = svc.sessions[0]
            rng = np.random.default_rng(123)
            svc.push_data(rid0, node=0, points=rng.normal(size=(1, D)))
            print(f"[slice {n_slices}] pushed 1 fresh point to "
                  f"{rid0} node 0")
        if left == 0:
            return n_slices


def checkpoint_roundtrip(svc, rid: str, request, ckpt_dir: str):
    """Save session `rid`, restore it into a fresh service, assert the
    restored state is bit-exact, then run it one more slice.  Returns
    (path, t at save, t after the extra slice)."""
    import numpy as np

    from repro.serving.vb_service import VBService

    os.makedirs(ckpt_dir, exist_ok=True)
    path = os.path.join(ckpt_dir, f"{rid}.npz")
    svc.save_session(rid, path)
    svc2 = VBService(slice_iters=svc.slice_iters)
    rid_r = svc2.submit(request, restore_from=path)
    st0, st_r = svc.status(rid), svc2.status(rid_r)
    if st_r.t != st0.t or not np.array_equal(np.asarray(st0.phi),
                                             np.asarray(st_r.phi)):
        raise RuntimeError(f"restored {rid} differs from the saved session "
                           f"(t {st_r.t} vs {st0.t})")
    svc2.extend_budget(rid_r, svc.slice_iters)
    svc2.run()
    return path, st0.t, svc2.status(rid_r).t


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sessions", type=int, default=2)
    ap.add_argument("--budgets", default="30,60",
                    help="comma-separated per-session iteration budgets")
    ap.add_argument("--nodes", type=int, default=8)
    ap.add_argument("--per-node", default="20",
                    help="comma-separated per-node sample counts (cycled; "
                         "mixed values exercise bucketed admission)")
    ap.add_argument("--taus", default="",
                    help="comma-separated schedule taus (cycled over the "
                         "sessions whose topology has a natural-gradient "
                         "step; empty = the default tau)")
    ap.add_argument("--bucket", default="pow2",
                    help='admission ladder: "pow2", a growth factor '
                         '(e.g. 1.25), or "none"')
    ap.add_argument("--slice", type=int, default=16)
    ap.add_argument("--tol", type=float, default=0.0)
    ap.add_argument("--topology", default="mixed",
                    choices=["diffusion", "admm", "ring", "mixed"])
    ap.add_argument("--minibatch", type=int, default=0,
                    help="streaming minibatch size (0 = full batch)")
    ap.add_argument("--push-at", type=int, default=0,
                    help="after this many slices, append 1 fresh point "
                         "to node 0 of session 0 (0 = off)")
    ap.add_argument("--ckpt-dir", default=None,
                    help="save/restore session 0 through this directory "
                         "and assert the resumed run is bit-exact")
    ap.add_argument("--max-fleet", type=int, default=0,
                    help="fixed fleet capacity (continuous batching; "
                         "0 = power-of-two auto-growth)")
    ap.add_argument("--arrive-at", default="",
                    help="comma-separated slice boundaries at which each "
                         "session joins (cycled; empty = all at once)")
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="enable telemetry and dump a Chrome trace "
                         "(chrome://tracing / Perfetto) of the run — "
                         "driver slices, compiles, checkpoint writes, "
                         "admission/eviction markers — at drain")
    ap.add_argument("--metrics", default=None, metavar="OUT.prom",
                    help="enable telemetry and dump the metrics "
                         "snapshot (Prometheus text format) at drain")
    args = ap.parse_args()

    from repro import runtime, telemetry
    from repro.serving.vb_service import VBService

    runtime.use_compile_cache()
    runtime.use_platform_precision()
    if args.trace or args.metrics:
        telemetry.enable()

    requests = build_requests(
        sessions=args.sessions, nodes=args.nodes,
        per_node=[int(p) for p in args.per_node.split(",")],
        budgets=[int(b) for b in args.budgets.split(",")],
        taus=[float(t) for t in args.taus.split(",")] if args.taus else [],
        topology=args.topology, minibatch=args.minibatch, tol=args.tol)
    arrivals = ([int(a) for a in args.arrive_at.split(",")]
                if args.arrive_at else [0])
    bucket = (None if args.bucket == "none"
              else "pow2" if args.bucket == "pow2" else float(args.bucket))

    svc = VBService(slice_iters=args.slice,
                    max_fleet=args.max_fleet or None, bucket=bucket)
    by_rid = {}
    for i, req in enumerate(requests):
        rid = svc.submit(req, arrive_at=arrivals[i % len(arrivals)])
        by_rid[rid] = req
    n_slices = serve(svc, push_at=args.push_at)

    print(f"{'session':9s} {'topology':22s} {'iters':>6s} {'budget':>7s} "
          f"{'conv':>5s} {'final delta':>12s}")
    for rid in svc.sessions:
        st = svc.status(rid)
        topo = type(by_rid[rid].topology).__name__
        print(f"{rid:9s} {topo:22s} {st.t:6d} {st.budget:7d} "
              f"{str(st.converged):>5s} {st.delta:12.3e}")

    if args.ckpt_dir:
        rid0 = svc.sessions[0]
        path, t0, t1 = checkpoint_roundtrip(svc, rid0, by_rid[rid0],
                                            args.ckpt_dir)
        print(f"checkpoint: saved {rid0} at t={t0} -> {path}, "
              f"restored bit-exact, extended to t={t1}")

    st = svc.stats()
    print(f"driver: {st.slices} slices, {st.compiles} compiles, "
          f"{st.admitted} admitted, {st.evicted} evicted, "
          f"occupancy {st.occupancy:.2f} "
          f"(padding waste {st.padding_waste:.2f}), "
          f"{st.checkpoints} background checkpoints")
    for b in st.buckets:
        print(f"  bucket {b.label}: {b.admitted} admitted over "
              f"{b.slots} slot(s), occupancy {b.occupancy:.2f}, "
              f"data padding {b.data_pad_frac:.2f}")
    print(f"served {args.sessions} session(s) in {n_slices} slice(s)")

    if args.trace:
        telemetry.export_chrome_trace(args.trace)
        names = ", ".join(telemetry.tracer().span_names())
        print(f"telemetry: wrote {len(telemetry.tracer())} trace events "
              f"to {args.trace} ({names})")
    if args.metrics:
        with open(args.metrics, "w") as f:
            f.write(telemetry.to_prometheus())
        print(f"telemetry: wrote {len(telemetry.registry())} metric "
              f"series to {args.metrics}")


if __name__ == "__main__":
    main()
