"""Second conjugate-exponential instance: distributed Bayesian linear
regression with Normal-Gamma conjugacy.

The paper's framework claims generality over conjugate-exponential models
(contribution 1); the GMM is its worked example.  This module instantiates
the same machinery for the classic WSN task of linear parameter estimation
(cf. the diffusion-LMS line of work the paper builds on [8]):

    y_ij = w^T x_ij + eps,   eps ~ N(0, lambda^{-1})
    lambda ~ Gamma(a0, b0),  w | lambda ~ N(m0, (lambda V0)^{-1})

The model has NO local latent variables, so the VBE step is trivial and the
local optimum phi*_i (Eq. 18) is an explicit function of the replicated
local sufficient statistics (X^T X, X^T y, y^T y, n).  The paper's VBM
consensus machinery applies verbatim in the natural-parameter space:

    u(w, lambda) = [ln lambda, lambda, lambda w, lambda w w^T]
    phi = [a - 1 + D/2,  -(b + m^T V m / 2),  V m,  -V/2]

cVB is exact single-shot averaging (Eq. 20); dSVB (Eq. 27) and dVB-ADMM
(Eqs. 38a/39/40) converge to the exact pooled Bayesian posterior —
verified in tests/test_linreg.py against the closed-form solution.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import expfam
from jax.scipy.special import digamma, gammaln

from repro.core import engine


class NGPosterior(NamedTuple):
    """Normal-Gamma hyperparameters: lambda~Ga(a,b), w|lambda~N(m,(l V)^-1)."""

    m: jnp.ndarray   # (D,)
    V: jnp.ndarray   # (D, D)  precision scale
    a: jnp.ndarray   # ()
    b: jnp.ndarray   # ()

    @property
    def D(self) -> int:
        return self.m.shape[-1]


def prior(D: int, *, a0: float = 1.0, b0: float = 1.0, v0: float = 1e-2,
          dtype=None) -> NGPosterior:
    """Broad Normal-Gamma prior; `dtype` defaults to the enabled float
    precision (f64 under x64)."""
    if dtype is None:
        dtype = jnp.result_type(float)
    return NGPosterior(m=jnp.zeros((D,), dtype),
                       V=jnp.eye(D, dtype=dtype) * v0,
                       a=jnp.asarray(a0, dtype), b=jnp.asarray(b0, dtype))


def flat_dim(D: int) -> int:
    return 2 + D + D * D


#: block names of the flat Normal-Gamma message, in `block_labels` order:
#: n1 (Gamma shape), n2 (Gamma rate carrier), n3 (V m), n4 (-V/2).
BLOCK_NAMES = ("shape", "rate", "mean", "precision")


def block_labels(D: int):
    """(P,) int32 block-type label per coordinate (cf. expfam.block_labels);
    a host (numpy) array — static structure, usable inside jit."""
    import numpy as np
    return np.asarray([0, 1] + [2] * D + [3] * (D * D), np.int32)


def pack(q: NGPosterior) -> jnp.ndarray:
    n1 = q.a - 1.0 + q.D / 2.0
    n2 = -(q.b + 0.5 * q.m @ q.V @ q.m)
    n3 = q.V @ q.m
    n4 = -0.5 * q.V
    return jnp.concatenate([n1[None], n2[None], n3, n4.reshape(-1)])


def unpack(phi: jnp.ndarray, D: int) -> NGPosterior:
    n1, n2 = phi[0], phi[1]
    n3 = phi[2:2 + D]
    V = -2.0 * phi[2 + D:].reshape(D, D)
    m = jnp.linalg.solve(V, n3)
    a = n1 + 1.0 - D / 2.0
    b = -n2 - 0.5 * m @ V @ m
    return NGPosterior(m=m, V=V, a=a, b=b)


def log_partition(q: NGPosterior) -> jnp.ndarray:
    """A(phi) = ln Gamma(a) - a ln b - 1/2 ln|V| + D/2 ln 2pi."""
    return (gammaln(q.a) - q.a * jnp.log(q.b)
            - 0.5 * jnp.linalg.slogdet(q.V)[1]
            + q.D / 2.0 * jnp.log(2.0 * jnp.pi))


def expected_stats(q: NGPosterior):
    """E[u] = (E[ln l], E[l], E[l w], E[l w w^T])."""
    e_loglam = digamma(q.a) - jnp.log(q.b)
    e_lam = q.a / q.b
    e_lw = e_lam * q.m
    e_lww = jnp.linalg.inv(q.V) + e_lam * jnp.outer(q.m, q.m)
    return e_loglam, e_lam, e_lw, e_lww


def kl(q: NGPosterior, p: NGPosterior) -> jnp.ndarray:
    """KL(q||p) via the exp-family identity (Eq. 46 analogue)."""
    e_loglam, e_lam, e_lw, e_lww = expected_stats(q)
    dq, dp = pack(q), pack(p)
    D = q.D
    inner = ((dq[0] - dp[0]) * e_loglam + (dq[1] - dp[1]) * e_lam
             + (dq[2:2 + D] - dp[2:2 + D]) @ e_lw
             + jnp.sum((dq[2 + D:] - dp[2 + D:]).reshape(D, D) * e_lww))
    return inner - log_partition(q) + log_partition(p)


# ---------------------------------------------------------------------------
# Local optimum (Eq. 18) from replicated local sufficient statistics
# ---------------------------------------------------------------------------
def local_optimum(X, y, mask, q0: NGPosterior, replication: float):
    """phi*_i for node data (X (Ni,D), y (Ni,)) replicated `N` times."""
    w = mask
    # data-axis sums via expfam.ordered_sum (not einsum) so mask-zero
    # padding slots appended by the serving layer's bucketed admission
    # contribute exact +0.0 — the statistics stay BIT-equal to the
    # unpadded computation (see gmm.sufficient_stats).
    Xw = X * w[:, None]                                 # (n, D)
    XtX = expfam.ordered_sum(Xw[:, :, None] * X[:, None, :]) * replication
    Xty = expfam.ordered_sum(Xw * y[:, None]) * replication
    yty = expfam.ordered_sum((y * y * w)[:, None])[0] * replication
    n = expfam.ordered_sum(w[:, None])[0] * replication
    V = q0.V + XtX
    m = jnp.linalg.solve(V, q0.V @ q0.m + Xty)
    a = q0.a + n / 2.0
    b = q0.b + 0.5 * (yty + q0.m @ q0.V @ q0.m - m @ V @ m)
    return pack(NGPosterior(m=m, V=V, a=a, b=b))


def pooled_posterior(X_all, y_all, q0: NGPosterior) -> NGPosterior:
    """Exact Bayesian posterior on the pooled data — the reference."""
    mask = jnp.ones(X_all.shape[0], X_all.dtype)
    return unpack(local_optimum(X_all, y_all, mask, q0, 1.0),
                  q0.D)


# ---------------------------------------------------------------------------
# Distributed estimators — engine wrappers.  No local latents means phi*_i
# is constant across iterations, so the LinRegModel adapter treats the
# precomputed (N, P) phi* stack as the per-node "data" and the engine runs
# exactly the paper's consensus dynamics (Eqs. 27 / 38a+39) on it.  The
# single implementation of those equations lives in core/engine.py.
# ---------------------------------------------------------------------------
def _fixed_point_model(phi_star: jnp.ndarray):
    from repro.core import model as model_lib
    return model_lib.LinRegModel.from_flat_dim(phi_star.shape[-1])


def run_cvb(phi_star: jnp.ndarray) -> jnp.ndarray:
    """Eq. 20: fusion-centre average (exact in one step)."""
    return engine.FusionCenter().combine(phi_star)[0]


def run_dsvb(phi_star, weights, *, n_iters: int, tau: float = 0.2,
             d0: float = 1.0):
    """Eq. 27 with fixed local optima; returns (N, P) final iterates.
    Nodes start at their own local optimum (noncoop state)."""
    run = engine.run_vb(_fixed_point_model(phi_star), phi_star,
                        engine.Diffusion(weights), n_iters=n_iters,
                        schedule=engine.Schedule(tau=tau, d0=d0),
                        init_phi=phi_star, diagnostics=False)
    return run.phi


def run_admm(phi_star, adj, *, n_iters: int, rho: float = 0.5,
             xi: float = 0.05):
    """Eqs. 38a + 39 with fixed local optima."""
    run = engine.run_vb(_fixed_point_model(phi_star), phi_star,
                        engine.ADMMConsensus(adj, rho=rho, xi=xi,
                                             project=False),
                        n_iters=n_iters, init_phi=phi_star,
                        diagnostics=False)
    return run.phi
