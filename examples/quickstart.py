"""Quickstart: the paper in ~50 lines, via the session API.

Distributed variational-Bayes estimation of a Gaussian mixture over a
50-node sensor network — dSVB (Algorithm 1) and dVB-ADMM (Algorithm 2)
against the centralised VB reference, using the paper's Sec. V-A setup.

Each estimator is an explicit SESSION: `engine.vb_init(model, data,
topology, ...)` opens it as a checkpointable `VBState` (the Bayesian-GMM
`ConjugateExpModel` composed with a `FusionCenter`, `Diffusion(W)` or
`ADMMConsensus(adj)` topology — see docs/ARCHITECTURE.md for the
equation -> code map) and `engine.vb_run(state, n)` advances it.  The
paper's algorithms are online recursions, so the run below is split into
two halves with full observability in between — the result is bit-exact
with the unsplit run (`engine.run_vb` is the one-shot wrapper).  Swap in
`model.LinRegModel` + the same topologies for the linear-regression
instance, pass `executor=engine.MeshExecutor(mesh, "data")` to shard the
node axis, or serve many such sessions at once with
`serving.vb_service.VBService` (see README).

    PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp

from repro import runtime
from repro.core import algorithms, engine, expfam, gmm, network, refperm
from repro.core import model as model_lib
from repro.data import synthetic

runtime.use_platform_precision()

K, D, N_NODES, N_ITERS = 3, 2, 50, 800

# 1. sensor network + imbalanced per-node observations (Sec. V-A)
data = synthetic.paper_synthetic(n_nodes=N_NODES, n_per_node=100, seed=0)
adj, _ = network.random_geometric_graph(N_NODES, seed=0)
weights = network.nearest_neighbor_weights(adj)          # Eq. 47

# 2. conjugate prior + ground-truth posterior for the Eq. 46 metric
prior = expfam.noninformative_prior(K, D, beta0=0.1, w0_scale=10.0)
x_all, labels_all = data.flat
ref = refperm.permuted_refs(gmm.ground_truth_posterior(
    x_all, labels_all, prior, K))
init_q = algorithms._perturbed_init(prior, data.x, jax.random.PRNGKey(0))
mdl = model_lib.GMMModel(prior, K, D)
phi0 = jnp.broadcast_to(expfam.pack_natural(init_q), (N_NODES, mdl.flat_dim))

# 3. open one session per estimator.  Plain Algorithm 2 diverges on
#    imbalanced instances (dual wind-up — docs/admm-convergence.md);
#    adaptive_rho=True enables the consensus subsystem that fixes it.
kw = dict(init_phi=phi0, ref_phi=ref)
sessions = {
    "cVB": engine.vb_init(mdl, (data.x, data.mask), engine.FusionCenter(),
                          schedule=engine.ONE_SHOT, metric_nodes=1, **kw),
    "dSVB": engine.vb_init(mdl, (data.x, data.mask),
                           engine.Diffusion(weights),
                           schedule=engine.Schedule(tau=0.2), **kw),
    "dVB-ADMM (plain)": engine.vb_init(
        mdl, (data.x, data.mask), engine.ADMMConsensus(adj, rho=0.5), **kw),
    "dVB-ADMM (adaptive)": engine.vb_init(
        mdl, (data.x, data.mask),
        engine.ADMMConsensus(adj, rho=0.5, adaptive_rho=True), **kw),
}

# 4. run each session in two halves — pausing mid-run costs nothing and
#    changes nothing (bit-exact resume; checkpoint with ckpt.save(state))
print(f"{'algorithm':22s} {'KL to ground truth':>20s} {'node spread':>12s}")
for name, state in sessions.items():
    state, first = engine.vb_run(state, N_ITERS // 2)
    # ... a serving system would checkpoint / admit data here ...
    state, second = engine.vb_run(state, N_ITERS - N_ITERS // 2)
    assert int(state.t) == N_ITERS
    kl_std = 0.0 if name == "cVB" else float(second.kl_std[-1])
    print(f"{name:22s} {float(second.kl_mean[-1]):20.3f} {kl_std:12.4f}")
    sessions[name] = state

q = expfam.unpack_natural(sessions["dVB-ADMM (adaptive)"].phi[0], K, D)
print("\nestimated mixture means (node 0, adaptive dVB-ADMM):")
print(q.m)
print("ground truth:")
print(synthetic.PAPER_MU)
