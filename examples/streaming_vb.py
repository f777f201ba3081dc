"""Streaming dSVB over a failing sensor network — minibatches + link drops.

The paper's Algorithm 1 run the way a real sensor network would: each node
estimates its local VBM optimum from a small reshuffled minibatch of its
buffer every iteration (`MinibatchSpec` — unbiased stochastic natural
gradients under the Robbins-Monro eta_t), while the communication links
independently fail with probability `--link-drop` per iteration (the
diffusion weights renormalise over whatever neighbourhood is still up,
and `ADMMConsensus` couples only live links, reporting the surviving
fraction in `ConsensusDiagnostics.link_frac`).

    PYTHONPATH=src python examples/streaming_vb.py            # CI smoke size
    PYTHONPATH=src python examples/streaming_vb.py --full     # paper size
"""
import argparse

import jax
import jax.numpy as jnp

from repro import runtime
from repro.core import algorithms, engine, expfam, gmm, network, refperm
from repro.core import model as model_lib
from repro.data import stream, synthetic

runtime.use_platform_precision()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-sized instance (50 nodes, 2000 iters)")
    ap.add_argument("--link-drop", type=float, default=0.2)
    args = ap.parse_args()

    n_nodes = 50 if args.full else 10
    n_per = 100 if args.full else 40
    n_iters = 2000 if args.full else 150
    batch = max(4, n_per // 5)

    K, D = 3, 2
    data = synthetic.paper_synthetic(n_nodes=n_nodes, n_per_node=n_per,
                                     seed=0)
    adj, _ = network.random_geometric_graph(n_nodes, seed=0)
    W = network.nearest_neighbor_weights(adj)
    prior = expfam.noninformative_prior(K, D, beta0=0.1, w0_scale=10.0)
    x_all, labels_all = data.flat
    ref = refperm.permuted_refs(gmm.ground_truth_posterior(
        x_all, labels_all, prior, K))
    init_q = algorithms._perturbed_init(prior, data.x, jax.random.PRNGKey(0))
    phi0 = jnp.broadcast_to(expfam.pack_natural(init_q),
                            (n_nodes, expfam.flat_dim(K, D)))
    mdl = model_lib.GMMModel(prior, K, D)
    spec = stream.MinibatchSpec(batch_size=batch, seed=0)
    kw = dict(n_iters=n_iters, init_phi=phi0, ref_phi=ref)

    print(f"{n_nodes} nodes x {n_per} pts, minibatch B={batch}, "
          f"link-drop p={args.link_drop}, {n_iters} iters\n")

    runs = {
        "dSVB full-batch, static net": engine.run_vb(
            mdl, (data.x, data.mask), engine.Diffusion(W), **kw),
        "dSVB streaming, static net": engine.run_vb(
            mdl, (data.x, data.mask), engine.Diffusion(W),
            minibatch=spec, **kw),
        "dSVB streaming, failing links": engine.run_vb(
            mdl, (data.x, data.mask),
            engine.Diffusion(W, link_drop=args.link_drop, link_seed=1),
            minibatch=spec, **kw),
    }
    admm = engine.run_vb(
        mdl, (data.x, data.mask),
        engine.ADMMConsensus(adj, adaptive_rho=True,
                             link_drop=args.link_drop, link_seed=1),
        minibatch=spec, n_iters=n_iters, init_phi=phi0, ref_phi=ref)
    runs["dVB-ADMM adaptive, streaming + failing links"] = admm

    print(f"{'run':46s} {'final KL':>10s} {'node spread':>12s}")
    for name, r in runs.items():
        print(f"{name:46s} {float(r.kl_mean[-1]):10.3f} "
              f"{float(r.kl_std[-1]):12.4f}")

    lf = admm.consensus_diag.link_frac
    print(f"\nADMM effective connectivity (link_frac): "
          f"mean {float(jnp.mean(lf)):.3f}, "
          f"min {float(jnp.min(lf)):.3f} "
          f"(nominal {1 - args.link_drop:.2f} expected)")
    assert bool(jnp.all(jnp.isfinite(runs[
        "dSVB streaming, failing links"].phi))), "streaming run diverged"
    print("\nOK: streaming + failing-link runs finished finite")


if __name__ == "__main__":
    main()
