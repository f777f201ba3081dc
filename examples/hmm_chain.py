"""Distributed VB for hidden Markov chains over a sensor network.

Each sensor records a handful of Gaussian-emission HMM chains; the
network runs diffusion dSVB and dVB-ADMM through the generic engine and
recovers the shared transition matrix and emission means — the
`models/hmm.py` adapter is a three-block `blocks.BlockModel` composition
(Dirichlet initial-state + Dirichlet transition rows + the GMM
Normal-Wishart emission bank), so NO engine code knows it exists
(docs/model-zoo.md).

    PYTHONPATH=src python examples/hmm_chain.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro import runtime
from repro.core import engine, network
from repro.models import hmm

runtime.use_platform_precision()

K, D, N_NODES = 3, 2, 6

x, mask, pi_true, A_true, means_true = hmm.sample_chains(
    N_NODES, n_chains=20, length=20, K=K, D=D, seed=0)
prior = hmm.noninformative_prior(K, D, beta0=0.1, w0_scale=10.0)
mdl = hmm.HMMModel(prior)
init_q = hmm.perturbed_init(prior, jnp.asarray(x), jax.random.PRNGKey(7))
phi0 = jnp.broadcast_to(mdl.pack(init_q), (N_NODES, mdl.flat_dim))

adj, _ = network.random_geometric_graph(N_NODES, seed=3)
W = network.metropolis_weights(adj)
data = (jnp.asarray(x), jnp.asarray(mask))


def transition_error(phi):
    """max |A_est - A_true| after matching labels by emission mean."""
    q = mdl.unpack(phi[0])
    est = np.asarray(q.m)
    perm = [int(np.argmin(np.sum((est - mu) ** 2, -1)))
            for mu in means_true]
    if sorted(perm) != list(range(K)):
        return float("inf")                       # label collapse
    A = np.asarray(q.trans / jnp.sum(q.trans, -1, keepdims=True))
    return float(np.max(np.abs(A[np.ix_(perm, perm)] - A_true)))


print(f"{N_NODES} sensors x {x.shape[1]} chains x {x.shape[2]} steps, "
      f"K={K} states, D={D} emissions")
for name, topo in [("dSVB (diffusion)", engine.Diffusion(W)),
                   ("dVB-ADMM", engine.ADMMConsensus(adj))]:
    out = engine.run_vb(mdl, data, topo, n_iters=80, init_phi=phi0)
    err = transition_error(out.phi)
    print(f"{name:18s} max|A_est - A_true| = {err:.4f}  "
          f"consensus err = {float(out.consensus_err[-1]):.2e}")
    assert err < 0.1, f"{name} failed to recover the transition matrix"

print("OK")
