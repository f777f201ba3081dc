"""Topology scale sweep: dense oracle vs sparse edge-list combines at
N in {50, 1k, 10k} (ROADMAP item 3).

For each network size this times one VB iteration (us/iter, compiled,
KL metric included) and records the KL-vs-iterations trajectory for the
sparse diffusion, pairwise-gossip, and hierarchical-fusion topologies —
plus the dense-matrix diffusion oracle where it still fits (50, 1k; at
10k the dense mixing matrix alone would be 800 MB, which is the point
of the sparse path).  The committed 10k row carries the scale contract
itself: the lowered sparse step contains NO (N, N) tensor — per-
iteration memory is O(E + N), independent of N^2 — asserted against the
StableHLO text, not inferred.

Everything is seeded (data, graph, gossip activation), so the committed
BENCH_engine.json rows are reproducible bit-for-bit on the same stack.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro import runtime
from repro.core import engine, expfam, gmm, network, refperm
from repro.core import model as model_lib
from repro.data import synthetic

from benchmarks import common

K, D = 3, 2
N_PER = 20
N_SWEEP = (50, 1_000, 10_000)
DENSE_MAX = 1_000            # largest N the dense oracle still runs at


def _iters(n: int, full: bool) -> int:
    if n <= 50:
        return 400 if full else 100
    if n <= 1_000:
        return 120 if full else 40
    return 60 if full else 16


def _setup(n: int):
    data = synthetic.paper_synthetic(n_nodes=n, n_per_node=N_PER, seed=0)
    prior = expfam.noninformative_prior(K, D, beta0=0.1, w0_scale=10.0)
    mdl = model_lib.GMMModel(prior, K, D)
    x_all, labels = data.flat
    ref_q = gmm.ground_truth_posterior(x_all, labels, prior, K)
    ref_phis = refperm.permuted_refs(ref_q)
    g, _pos = network.random_geometric_edges(n, seed=0)
    return data, mdl, ref_phis, g


def _time_run(mdl, data, topo, n_iters, ref_phis):
    fn = jax.jit(lambda x, m: engine.run_vb(
        mdl, (x, m), topo, n_iters=n_iters, ref_phi=ref_phis,
        schedule=engine.Schedule()).kl_mean)
    fn(data.x, data.mask)                        # compile
    kl, wall = common.timed(fn, data.x, data.mask)
    kl = np.asarray(kl)
    return kl, common.us_per_iter(wall, n_iters)


def _no_dense_matrix_in_hlo(topo, n: int) -> bool:
    """The memory contract: the lowered combine has no (N, N) tensor."""
    sds = jax.ShapeDtypeStruct((n, expfam.flat_dim(K, D)), jnp.float64)
    txt = jax.jit(lambda v: topo.combine(v, t=1)).lower(sds).as_text()
    return f"{n}x{n}" not in txt


def run(full=False):
    runtime.use_platform_precision()
    rows, payload = [], {}
    for n in N_SWEEP:
        n_iters = _iters(n, full)
        data, mdl, ref_phis, g = _setup(n)
        sw = network.sparse_nearest_neighbor_weights(g)
        n_gw = max(1, n // 16)
        gw, rg = network.two_level_partition(n, n_gw, max(1, n_gw // 8))
        topos = [
            ("sparse_diffusion", engine.Diffusion(sw)),
            ("gossip", engine.PairwiseGossip(g, p_activate=0.3, seed=5)),
            ("hierarchical", engine.HierarchicalFusion(gw, rg)),
        ]
        if n <= DENSE_MAX:
            W = network.nearest_neighbor_weights(
                jnp.asarray(g.to_dense()))
            topos.insert(0, ("dense_diffusion", engine.Diffusion(W)))
        for tname, topo in topos:
            kl, us = _time_run(mdl, data, topo, n_iters, ref_phis)
            name = f"topology_scale_{tname}_n{n}"
            derived = (f"edges={g.n_undirected} n_iters={n_iters} "
                       f"kl0={kl[0]:.1f} kl_final={kl[-1]:.2f}")
            if tname != "dense_diffusion":
                no_nxn = _no_dense_matrix_in_hlo(topo, n)
                assert no_nxn, f"{name}: (N,N) tensor leaked into HLO"
                if n > DENSE_MAX:
                    derived += (f" no_nxn_hlo={no_nxn}"
                                f" dense_bytes_avoided={8 * n * n}")
            rows.append((name, us, derived))
            payload[f"{tname}_n{n}"] = {
                "us_per_iter": us, "n_iters": n_iters,
                "edges": g.n_undirected, "kl_vs_iters": kl.tolist(),
            }
    common.save("topology_scale_bench", payload)
    return rows
