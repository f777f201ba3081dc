"""Generality benchmark: the framework on a SECOND conjugate-exponential
model (Bayesian linear regression, Normal-Gamma) — paper contribution 1.

Reports the max-over-nodes KL to the exact pooled Bayesian posterior for
dSVB and dVB-ADMM at matched iteration budgets.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import common
from repro import runtime
from repro.core import linreg, network


def run(full=False):
    runtime.use_platform_precision()
    D, n_nodes, ni = 6, 50 if full else 20, 40
    rng = np.random.default_rng(0)
    w_true = rng.normal(size=D)
    X = rng.normal(size=(n_nodes, ni, D))
    y = X @ w_true + rng.normal(size=(n_nodes, ni)) * 0.4
    X, y = jnp.asarray(X), jnp.asarray(y)
    q0 = linreg.prior(D)
    mask = jnp.ones((ni,), X.dtype)
    phi_star = jnp.stack([
        linreg.local_optimum(X[i], y[i], mask, q0, float(n_nodes))
        for i in range(n_nodes)])
    ref = linreg.pooled_posterior(X.reshape(-1, D), y.reshape(-1), q0)
    adj, _ = network.random_geometric_graph(n_nodes, seed=1)
    W = network.nearest_neighbor_weights(adj)

    n_iters = 2000 if full else 400
    t0 = time.time()
    phi_d = linreg.run_dsvb(phi_star, W, n_iters=n_iters, tau=0.1)
    phi_a = linreg.run_admm(phi_star, adj, n_iters=n_iters, rho=0.5)
    jax.block_until_ready((phi_d, phi_a))
    wall = time.time() - t0

    kl_d = max(float(linreg.kl(linreg.unpack(phi_d[i], D), ref))
               for i in range(n_nodes))
    kl_a = max(float(linreg.kl(linreg.unpack(phi_a[i], D), ref))
               for i in range(n_nodes))
    common.save("linreg_generality", {"kl_dsvb": kl_d, "kl_admm": kl_a,
                                      "n_iters": n_iters})
    return [("linreg_generality", common.us_per_iter(wall, 2 * n_iters),
             f"maxKL_to_pooled dsvb={kl_d:.2e} admm={kl_a:.2e}")]
