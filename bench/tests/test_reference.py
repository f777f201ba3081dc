"""The plain reference against the program's own solo path, in float64
on the CPU, where both must agree to rounding: dSVB and adaptive
dVB-ADMM on a small Sec. V-A instance."""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


@pytest.mark.parametrize("rule", ["dsvb", "admm"])
def test_reference_matches_program_in_f64(rule):
    import jax
    jax.config.update("jax_enable_x64", True)
    import jax.numpy as jnp

    from bench import compare, data
    from bench.reference import gmm_vb
    from repro.core import engine, expfam
    from repro.core import model as model_lib

    K, D, N, T = 3, 2, 8, 20
    x, mask = data.paper_pool(data.key(5, 0), count=1, n_nodes=N,
                              n_points=T, free_slots=1, dtype=jnp.float64)
    x, mask = x[0], mask[0]
    prior = data.noninformative_prior(K, D, beta0=0.1, w0_scale=10.0,
                                      dtype=jnp.float64)
    adj = data.geometric_graph(N, 0, side=3.5 * np.sqrt(N / 50), radius=0.8)
    W = data.nearest_neighbor_weights(adj)
    phi0 = jnp.broadcast_to(data.perturbed_init(data.key(5, 1), x, prior,
                                                K=K, D=D),
                            (N, gmm_vb.flat_dim(K, D)))
    graph = W if rule == "dsvb" else adj
    want = gmm_vb.run(x, mask, phi0, prior, jnp.asarray(graph),
                      jnp.asarray(0.2), 60, rule=rule, K=K, D=D,
                      node_block=N)
    mdl = model_lib.GMMModel(expfam.GMMPosterior(**prior), K, D)
    topo = (engine.Diffusion(jnp.asarray(W)) if rule == "dsvb"
            else engine.ADMMConsensus(jnp.asarray(adj), adaptive_rho=True))
    sched = engine.Schedule(tau=0.2) if rule == "dsvb" else engine.Schedule()
    got = engine.run_vb(mdl, (x, mask), topo, n_iters=60, schedule=sched,
                        init_phi=phi0).phi
    assert compare.phi_gap(got, want, gmm_vb.block_names(K, D)) < 1e-10
