"""Shared benchmark machinery for the paper-figure reproductions."""
from __future__ import annotations

import itertools
import json
import os
import time

import jax
import numpy as np

from repro import runtime
from repro.core import algorithms, expfam, gmm, network, refperm

OUTDIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "experiments", "benchmarks")


def setup_gmm(data, K, D, *, seed=0, graph_seed=0, beta0=0.1, w0=10.0):
    runtime.use_platform_precision()
    prior = expfam.noninformative_prior(K, D, beta0=beta0, w0_scale=w0)
    n = data.x.shape[0]
    adj, _ = network.random_geometric_graph(n, seed=graph_seed)
    W = network.nearest_neighbor_weights(adj)
    x_all, labels_all = data.flat
    ref = gmm.ground_truth_posterior(x_all, labels_all, prior, K)
    ref_phis = (refperm.permuted_refs(ref) if K <= 6 else None)
    init_q = algorithms._perturbed_init(prior, data.x,
                                        jax.random.PRNGKey(seed))
    return dict(prior=prior, adj=adj, W=W, ref_phis=ref_phis, init_q=init_q)


def timed(fn, *args, **kw):
    t0 = time.time()
    out = fn(*args, **kw)
    jax.block_until_ready(out)
    return out, time.time() - t0


def us_per_iter(wall_s: float, n_iters: int, n_repeat: int = 1) -> float:
    return wall_s / (n_iters * n_repeat) * 1e6


def accuracy(data, phi_nodes, K, D) -> float:
    """Mean clustering accuracy over nodes, best label permutation."""
    x_all, labels = data.flat
    labels = np.asarray(labels)
    accs = []
    for i in range(phi_nodes.shape[0]):
        q = expfam.unpack_natural(phi_nodes[i], K, D)
        pred = np.asarray(gmm.predict_labels(x_all, q))
        best = max(np.mean(np.asarray([p[c] for c in pred]) == labels)
                   for p in itertools.permutations(range(K)))
        accs.append(best)
    return float(np.mean(accs))


def save(name: str, payload: dict):
    os.makedirs(OUTDIR, exist_ok=True)
    with open(os.path.join(OUTDIR, name + ".json"), "w") as f:
        json.dump(payload, f, indent=1, default=float)


def load(name: str) -> dict | None:
    """Read back a prior `save` (cross-benchmark handoff), None if absent."""
    path = os.path.join(OUTDIR, name + ".json")
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f)
