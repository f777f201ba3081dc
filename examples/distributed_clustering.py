"""Distributed clustering on the real-data surrogates (Sec. V-D).

Reproduces the Table I / Table II comparisons: cVB vs noncoop-VB vs
nsg-dVB vs dSVB vs dVB-ADMM on the atmosphere- and ionosphere-shaped
datasets (offline surrogates), then demos the engine API directly:
`ADMMConsensus(adaptive_rho=True)` — the adaptive-penalty consensus
subsystem — with its `ConsensusDiagnostics` summary printed (see
docs/admm-convergence.md for how to read it).

    PYTHONPATH=src python examples/distributed_clustering.py
"""
import jax
import jax.numpy as jnp

from repro import runtime
from repro.core import algorithms, engine, expfam, network
from repro.core import model as model_lib
from repro.data import datasets

import sys
sys.path.insert(0, ".")
from benchmarks import common  # noqa: E402

runtime.use_platform_precision()


def run_table(name, data, K, D, n_iters, rho, tau):
    s = common.setup_gmm(data, K, D, graph_seed=11, beta0=0.05, w0=5.0)
    kw = dict(n_iters=n_iters, K=K, D=D, init_q=s["init_q"])
    rows = {}
    rows["cVB"] = algorithms.run_cvb(data.x, data.mask, s["prior"], **kw)
    rows["noncoop-VB"] = algorithms.run_noncoop(data.x, data.mask,
                                                s["prior"], **kw)
    rows["nsg-dVB"] = algorithms.run_nsg_dvb(data.x, data.mask, s["W"],
                                             s["prior"], **kw)
    rows["dSVB"] = algorithms.run_dsvb(data.x, data.mask, s["W"],
                                       s["prior"], tau=tau, **kw)
    rows["dVB-ADMM"] = algorithms.run_dvb_admm(data.x, data.mask, s["adj"],
                                               s["prior"], rho=rho, **kw)
    rows["dVB-ADMM (adaptive)"] = algorithms.run_dvb_admm(
        data.x, data.mask, s["adj"], s["prior"], rho=rho,
        adaptive_rho=True, **kw)
    print(f"\n=== {name} ===")
    print(f"{'algorithm':22s} {'accuracy':>9s}")
    for alg, run in rows.items():
        acc = common.accuracy(data, run.phi, K, D)
        print(f"{alg:22s} {acc:9.4f}")
    return rows["dVB-ADMM (adaptive)"]


def print_diagnostics(run: engine.VBRun) -> None:
    """Final ConsensusDiagnostics summary of an adaptive dVB-ADMM run."""
    d = run.consensus_diag
    opened = float(d.dual_on[-1]) > 0.0
    on_at = int(jnp.argmax(d.dual_on)) if opened else -1
    print("\n--- ConsensusDiagnostics summary (adaptive dVB-ADMM) ---")
    print(f"dual warmup gate : "
          + (f"opened at iteration {on_at}" if opened else "never opened"))
    print(f"kappa (final)    : {float(d.kappa[-1]):.3f}")
    print(f"rho trajectory   : {float(jnp.mean(d.rho[0])):.3g} -> "
          f"{float(jnp.mean(d.rho[-1])):.3g}")
    print(f"primal residual  : {float(jnp.mean(d.primal_resid[-1])):.3e}")
    print(f"dual residual    : {float(jnp.mean(d.dual_resid[-1])):.3e}")
    print(f"eigen-clip fired : {int(jnp.sum(d.clip_count))} node-iterations"
          f" ({int(jnp.sum(d.reset_count))} dual resets)")


def engine_api_demo(data, K, D, n_iters=300):
    """The same run, written against engine.run_vb directly (the
    Model x Topology x Executor API from docs/ARCHITECTURE.md)."""
    s = common.setup_gmm(data, K, D, graph_seed=11, beta0=0.05, w0=5.0)
    mdl = model_lib.GMMModel(s["prior"], K, D)
    topo = engine.ADMMConsensus(s["adj"], rho=1.0, adaptive_rho=True)
    phi0 = jnp.broadcast_to(expfam.pack_natural(s["init_q"]),
                            (data.x.shape[0], mdl.flat_dim))
    run = engine.run_vb(mdl, (data.x, data.mask), topo, n_iters=n_iters,
                        init_phi=phi0)
    acc = common.accuracy(data, run.phi, K, D)
    print(f"\nengine.run_vb(GMMModel, ADMMConsensus(adaptive_rho=True)): "
          f"accuracy {acc:.4f}")
    print_diagnostics(run)


if __name__ == "__main__":
    atmosphere = datasets.atmosphere_surrogate(n_nodes=20)
    run_table("Table I: atmosphere (1600 x 3, 2 classes, 20 nodes)",
              atmosphere, 2, 3, 400, rho=1.0, tau=0.2)
    run_table("Table II: ionosphere (340 x 34, 2 classes, 20 nodes)",
              datasets.ionosphere_surrogate(n_nodes=20), 2, 34, 300,
              rho=16.0, tau=0.2)
    engine_api_demo(atmosphere, 2, 3)
