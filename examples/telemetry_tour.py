"""Telemetry tour: metrics, spans, device taps and the bench gate.

One small script that exercises every layer of `repro.telemetry`
(docs/observability.md is the companion reference):

1. **Host telemetry** around a driver run — `telemetry.enable()` turns
   on the metrics registry and span tracer; a 4-session continuous-
   batching fleet then leaves behind scheduler counters (admissions,
   evictions, checkpoint writes), fleet-health gauges (queue depth,
   occupancy, padding waste) and a Chrome trace with `driver/slice`,
   `driver/compile`, `driver/sync` and `driver/checkpoint` spans.
2. **Diag-slot series** — a solo ADMM `vb_run` files its per-iteration
   KL / consensus / rho / residual series into the tap buffer (no jaxpr
   change: the scan emits them anyway).
3. **Device taps** — `taps.enable()` BEFORE tracing inserts
   `io_callback` taps inside the compiled step, streaming the same
   series out mid-flight; the jaxpr difference is shown.
4. **Exports** — the Chrome trace (`chrome://tracing` / Perfetto), the
   Prometheus text dump and the JSON-lines snapshot land in /tmp, and
   the perf gate (`tools/bench_gate.py`) self-checks the committed
   baseline.

    PYTHONPATH=src python examples/telemetry_tour.py
"""
import json
import os
import subprocess
import sys

import numpy as np

from repro import runtime
from repro import telemetry
from repro.core import engine, expfam, network
from repro.core import model as model_lib
from repro.data import synthetic
from repro.serving.vb_service import VBRequest, VBService
from repro.telemetry import taps

runtime.use_platform_precision()


def main() -> None:
    telemetry.enable()
    K, D, n_nodes = 3, 2, 8
    prior = expfam.noninformative_prior(K, D, beta0=0.1, w0_scale=10.0)
    mdl = model_lib.GMMModel(prior, K, D)
    adj, _ = network.random_geometric_graph(n_nodes, seed=0)
    W = network.nearest_neighbor_weights(adj)

    # -- 1. a traced continuous-batching fleet ---------------------------
    svc = VBService(slice_iters=8, max_fleet=2,
                    ckpt_dir="/tmp/telemetry-tour-ckpt", ckpt_every=2)
    os.makedirs("/tmp/telemetry-tour-ckpt", exist_ok=True)
    for s in range(4):
        d = synthetic.paper_synthetic(n_nodes=n_nodes, n_per_node=12,
                                      seed=s)
        svc.submit(VBRequest(model=mdl, data=(d.x, d.mask),
                             topology=engine.Diffusion(W),
                             n_iters=24 + 8 * (s % 2)))
    svc.run()
    st = svc.stats()
    print(f"driver: {st.slices} slices, {st.admitted} admitted, "
          f"{st.evicted} evicted, {st.checkpoints} checkpoints "
          f"({st.checkpoint_errors} errors), occupancy "
          f"{st.occupancy:.2f}")

    # -- 2. diag-slot series from a solo ADMM run ------------------------
    d = synthetic.paper_synthetic(n_nodes=n_nodes, n_per_node=12, seed=9)
    engine.run_vb(mdl, (d.x, d.mask),
                  engine.ADMMConsensus(adj, adaptive_rho=True),
                  n_iters=40)
    t_kl, kl = taps.series("vb_run/kl_mean")
    t_rho, rho = taps.series("vb_run/admm_rho")
    print(f"diag-slot series: kl_mean over t={t_kl[0]}..{t_kl[-1]} "
          f"(final {kl[-1]:.2f}), rho final {rho[-1]:.3f}")

    # -- 3. device taps: enabled at trace time, visible in the jaxpr -----
    import jax

    def kl_probe(phi):
        taps.tap("tour/phi_norm", (phi ** 2).sum())
        return phi * 2.0

    def kl_probe_tapped(phi):              # separate fn: fresh trace
        taps.tap("tour/phi_norm", (phi ** 2).sum())
        return phi * 2.0

    off = str(jax.make_jaxpr(kl_probe)(np.ones(3)))
    with taps.enabled_scope():
        on = str(jax.make_jaxpr(kl_probe_tapped)(np.ones(3)))
        jax.jit(kl_probe_tapped)(np.ones(3)).block_until_ready()
    print(f"device taps: io_callback in jaxpr off={'io_callback' in off} "
          f"on={'io_callback' in on}, records="
          f"{taps.counts().get('tour/phi_norm')}")

    # -- 4. exports + the bench gate -------------------------------------
    trace_path = telemetry.export_chrome_trace("/tmp/telemetry_tour.json")
    n_events = len(json.load(open(trace_path))["traceEvents"])
    with open("/tmp/telemetry_tour.prom", "w") as f:
        f.write(telemetry.to_prometheus())
    with open("/tmp/telemetry_tour.jsonl", "w") as f:
        f.write(telemetry.to_jsonl())
    print(f"exports: {n_events} trace events -> {trace_path}, "
          f"{len(telemetry.registry())} series -> "
          "/tmp/telemetry_tour.prom|.jsonl")

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    gate = os.path.join(root, "tools", "bench_gate.py")
    if os.path.exists(os.path.join(root, "BENCH_engine.json")):
        r = subprocess.run([sys.executable, gate, "--quiet"], cwd=root)
        print(f"bench gate self-check exit code: {r.returncode}")
        assert r.returncode == 0

    assert {"driver/slice", "driver/compile",
            "driver/checkpoint"} <= set(telemetry.tracer().span_names())
    print("telemetry tour OK")


if __name__ == "__main__":
    main()
