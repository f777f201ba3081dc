"""Sparse 1000-node sensor networks: edge lists, gossip, hierarchy.

The paper's experiments stop at 50 sensors with a dense (N, N) mixing
matrix; this example runs the same Bayesian-GMM VB engine on a
1000-node random geometric graph held as a `network.SparseGraph` (edge
lists + `segment_sum` combines — O(E + N) memory, no N x N array
anywhere; see docs/sparse-topologies.md):

  * `Diffusion(sparse_nearest_neighbor_weights(g))` — Eq. 47 diffusion
    on the edge list (bit-parity with the dense oracle at small N),
  * `PairwiseGossip(g, p_activate=0.3)` — asynchronous randomized
    gossip, each link active i.i.d. per iteration, deterministic in
    (seed, t) so sessions split/resume bit-exactly,
  * `HierarchicalFusion(gateway_of, region_of)` — sensor -> gateway ->
    region fusion over a balanced two-level partition.

    PYTHONPATH=src python examples/sparse_network.py
"""
import numpy as np

from repro import runtime
from repro.core import engine, expfam, gmm, network, refperm
from repro.core import model as model_lib
from repro.data import synthetic

runtime.use_platform_precision()

N, K, D, ITERS = 1000, 3, 2, 60

data = synthetic.paper_synthetic(n_nodes=N, n_per_node=20, seed=0)
prior = expfam.noninformative_prior(K, D, beta0=0.1, w0_scale=10.0)
mdl = model_lib.GMMModel(prior, K, D)
x_all, labels = data.flat
ref = refperm.permuted_refs(gmm.ground_truth_posterior(x_all, labels,
                                                       prior, K))

# edge-list graph: the N=10k-capable builder (threshold-derived radius,
# never materialises an (N, N) matrix)
g, _pos = network.random_geometric_edges(N, seed=0)
print(f"graph: {g!r}, mean degree "
      f"{2 * g.n_undirected / g.n_nodes:.1f}")

gw, rg = network.two_level_partition(N, n_gateways=64, n_regions=8)
topologies = [
    ("sparse diffusion",
     engine.Diffusion(network.sparse_nearest_neighbor_weights(g))),
    ("pairwise gossip p=0.3",
     engine.PairwiseGossip(g, p_activate=0.3, seed=5)),
    ("hierarchical 64 gw / 8 regions",
     engine.HierarchicalFusion(gw, rg)),
]

for name, topo in topologies:
    run = engine.run_vb(mdl, (data.x, data.mask), topo, n_iters=ITERS,
                        ref_phi=ref, schedule=engine.Schedule())
    print(f"{name:32s} KL {float(run.kl_mean[0]):9.0f} -> "
          f"{float(run.kl_mean[-1]):9.0f}   consensus err "
          f"{float(run.consensus_err[-1]):.3g}")

# gossip sessions resume bit-exactly: the activation pattern is a
# function of the ABSOLUTE iteration index carried in VBState.t
topo = engine.PairwiseGossip(g, p_activate=0.3, seed=5)
s = engine.vb_init(mdl, (data.x, data.mask), topo,
                   schedule=engine.Schedule())
s, _ = engine.vb_run(s, ITERS // 2)
s, _ = engine.vb_run(s, ITERS - ITERS // 2)
full = engine.run_vb(mdl, (data.x, data.mask), topo, n_iters=ITERS,
                     schedule=engine.Schedule())
assert np.array_equal(np.asarray(s.phi), np.asarray(full.phi))
print("gossip split/resume: bit-exact")
