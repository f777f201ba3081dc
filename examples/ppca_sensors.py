"""Distributed Bayesian PPCA: sensors learn a shared latent subspace.

Every sensor observes noisy D-dimensional points living on the same
Q-dimensional subspace; diffusion dSVB through the generic engine
recovers the loading-matrix column space (principal-angle cosines ~ 1).
The `models/ppca.py` adapter is a ONE-block `blocks.BlockModel` — a bank
of D Normal-Gamma rows, the Bayesian-linear-regression family with
inferred latent covariates — so the whole engine/serving stack runs it
unchanged (docs/model-zoo.md), including streaming minibatches with the
SVRG control variate.

    PYTHONPATH=src python examples/ppca_sensors.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro import runtime
from repro.core import engine, network
from repro.data import stream
from repro.models import ppca

runtime.use_platform_precision()

N_NODES, N_PER, D, Q = 6, 40, 5, 2

x, mask, W_true = ppca.sample_sensors(N_NODES, N_PER, D=D, Q=Q, seed=1)
mdl = ppca.PPCAModel(ppca.prior(D, Q))
init_q = ppca.perturbed_init(mdl.prior, jax.random.PRNGKey(5))
phi0 = jnp.broadcast_to(mdl.pack(init_q), (N_NODES, mdl.flat_dim))

adj, _ = network.random_geometric_graph(N_NODES, seed=3)
W = network.metropolis_weights(adj)
data = (jnp.asarray(x), jnp.asarray(mask))


def subspace_cosines(phi):
    """Principal-angle cosines between estimated and true column spaces."""
    q = mdl.unpack(phi[0])
    u_est, _, _ = np.linalg.svd(np.asarray(q.m), full_matrices=False)
    u_true, _, _ = np.linalg.svd(np.asarray(W_true), full_matrices=False)
    return np.linalg.svd(u_est.T @ u_true, compute_uv=False)


print(f"{N_NODES} sensors x {N_PER} points, D={D} observed, "
      f"Q={Q} latent dims")

out = engine.run_vb(mdl, data, engine.Diffusion(W), n_iters=30,
                    init_phi=phi0)
cos = subspace_cosines(out.phi)
print(f"full-batch dSVB     cosines = {np.round(cos, 4)}  "
      f"consensus err = {float(out.consensus_err[-1]):.2e}")
assert np.min(cos) > 0.99, cos

# streaming: each node sees a 10-point window per iteration; the SVRG
# control variate keeps the stochastic iterates near the full-batch path
out_s = engine.run_vb(mdl, data, engine.Diffusion(W), n_iters=120,
                      init_phi=phi0,
                      minibatch=stream.MinibatchSpec(
                          10, seed=2, control_variate="svrg"))
cos_s = subspace_cosines(out_s.phi)
print(f"streaming dSVB+SVRG cosines = {np.round(cos_s, 4)}")
assert np.min(cos_s) > 0.99, cos_s

print("OK")
