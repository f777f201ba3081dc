"""Bayesian Gaussian-mixture model — the paper's application (Sec. IV + App. A).

Each node i holds data x_i of shape (Ni, D).  The local generative model uses
the *replicated* likelihood P({x_i}_N | ...) = prod_j prod_k N(x | mu, L)^(N y),
so every local count is scaled by the network size N (Appendix A: R_ik =
N * sum_j r_ijk, etc.).

`local_vbm_optimum` computes responsibilities given the current global
posterior and returns the *local optimum* natural parameters phi*_{theta,i}
(Eq. 18) — i.e. the hyperparameter update of Appendix A packed via
expfam.pack_natural.  The five algorithms in core/algorithms.py differ only
in what they do with the stack {phi*_i}.

This module is the REFERENCE implementation of the hot path (naive
three-pass einsums over the data).  The engine's production compute layer
is `core/backends.py`: the fused single-pass Pallas kernel
(`kernels/gmm_estep.py`) is parity-tested against the functions here
(tests/test_backends.py, tests/test_kernels.py) and selected via
`GMMModel(..., backend="fused")` / `run_vb(..., backend="fused")`.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import expfam
from repro.core.expfam import GMMPosterior


class SuffStats(NamedTuple):
    """Replicated sufficient statistics of Appendix A (per component)."""

    R: jnp.ndarray       # (K,)        R_k   = N * sum_j r_jk
    sum_x: jnp.ndarray   # (K, D)      N * sum_j r_jk x_j       (= R_k xbar_k)
    sum_xx: jnp.ndarray  # (K, D, D)   N * sum_j r_jk x_j x_j^T


def responsibilities(x: jnp.ndarray, q: GMMPosterior,
                     mask: jnp.ndarray | None = None,
                     logdet_W: jnp.ndarray | None = None) -> jnp.ndarray:
    """r_jk (Bishop 10.46 / Appendix A), shape (Ni, K).

    ln rho_jk = E[ln pi_k] + 1/2 E[ln|L_k|] - D/2 ln 2pi
                - 1/2 E[(x_j - mu_k)^T L_k (x_j - mu_k)]

    `logdet_W` (K,): log|W| where the caller has it from unpacking
    (`expfam.unpack_natural_logdet`); otherwise W is factored again.
    """
    D = x.shape[-1]
    e_logpi = expfam.dirichlet_expected_log(q.alpha)              # (K,)
    e_logdet = expfam.wishart_expected_logdet(q.W, q.nu, logdet_W)  # (K,)
    diff = x[:, None, :] - q.m[None, :, :]                        # (Ni, K, D)
    maha = jnp.einsum("jki,kil,jkl->jk", diff, q.W, diff)         # (Ni, K)
    e_quad = D / q.beta[None, :] + q.nu[None, :] * maha
    log_rho = (e_logpi[None, :] + 0.5 * e_logdet[None, :]
               - 0.5 * D * jnp.log(2.0 * jnp.pi) - 0.5 * e_quad)
    r = jax.nn.softmax(log_rho, axis=-1)
    if mask is not None:
        r = r * mask[:, None]
    return r


def estep_terms(q: GMMPosterior, dtype=None,
                logdet_W: jnp.ndarray | None = None):
    """Per-component terms consumed by the fused VBE kernel
    (kernels/gmm_estep.py) — the expanded form of the Appendix-A
    log-responsibility:

      log_prior (K,)   = E[ln pi] + 1/2 E[ln|L|] - D/2 ln 2pi
      Wn (K, D, D)     = nu W          (E[Lambda])
      b  (K, D)        = nu W m        (E[Lambda mu])
      c  (K,)          = D/beta + nu m^T W m   (E[mu^T Lambda mu])

    so that ln rho_jk = log_prior_k - (x^T Wn x - 2 x^T b + c) / 2,
    identical (up to f.p. reassociation) to `responsibilities`.
    `logdet_W` as in `responsibilities`.
    """
    D = q.D
    e_logpi = expfam.dirichlet_expected_log(q.alpha)
    e_logdet = expfam.wishart_expected_logdet(q.W, q.nu, logdet_W)
    log_prior = e_logpi + 0.5 * e_logdet - 0.5 * D * jnp.log(2.0 * jnp.pi)
    Wn = q.nu[:, None, None] * q.W
    b = jnp.einsum("kde,ke->kd", Wn, q.m)
    c = D / q.beta + jnp.einsum("kd,kd->k", q.m, b)
    if dtype is not None:
        log_prior, Wn, b, c = (a.astype(dtype) for a in (log_prior, Wn, b, c))
    return log_prior, Wn, b, c


def sufficient_stats(x: jnp.ndarray, r: jnp.ndarray,
                     replication: float) -> SuffStats:
    """Replicated stats (Appendix A).  `replication` is the network size N.

    The data-axis reductions go through `expfam.ordered_sum` (multiply
    then fixed-chunk sequential sum) rather than einsum contractions:
    XLA re-tiles a dot_general (and even a plain reduce) when the axis
    length changes, so mask-zero padding slots appended by the serving
    layer's bucketed admission (serving/admission.py) would perturb the
    last ulp.  `ordered_sum` pins the association order, keeping padded
    statistics BIT-equal to the unpadded computation.
    """
    R = replication * expfam.ordered_sum(r)                       # (K,)
    rx = r[:, :, None] * x[:, None, :]                            # (j, K, D)
    sum_x = replication * expfam.ordered_sum(rx)                  # (K, D)
    sum_xx = replication * expfam.ordered_sum(
        rx[:, :, :, None] * x[:, None, None, :])                  # (K, D, D)
    return SuffStats(R=R, sum_x=sum_x, sum_xx=sum_xx)


def _vbm_update(stats: SuffStats, prior: GMMPosterior, eps: float):
    """Hyperparameter updates of Appendix A given (replicated) stats, with
    the Wishart scale as its inverse: (alpha, m, beta, W^{-1}, nu)."""
    R = stats.R
    alpha = prior.alpha + R
    beta = prior.beta + R
    nu = prior.nu + R
    xbar = stats.sum_x / (R[:, None] + eps)                       # (K, D)
    m = (prior.beta[:, None] * prior.m + stats.sum_x) / beta[:, None]
    # R*S = sum_xx - R xbar xbar^T ;  prior cross term beta0 R/(beta0+R)(..)
    RS = stats.sum_xx - R[:, None, None] * (xbar[:, :, None] * xbar[:, None, :])
    diff = xbar - prior.m
    cross = (prior.beta * R / (prior.beta + R))[:, None, None] * (
        diff[:, :, None] * diff[:, None, :])
    W0_inv = jnp.linalg.inv(prior.W)
    W_inv = W0_inv + RS + cross
    W_inv = 0.5 * (W_inv + jnp.swapaxes(W_inv, -1, -2))
    return alpha, m, beta, W_inv, nu


def posterior_from_stats(stats: SuffStats, prior: GMMPosterior,
                         eps: float = 1e-12) -> GMMPosterior:
    """Hyperparameter updates of Appendix A given (replicated) stats."""
    alpha, m, beta, W_inv, nu = _vbm_update(stats, prior, eps)
    return GMMPosterior(alpha=alpha, m=m, beta=beta,
                        W=jnp.linalg.inv(W_inv), nu=nu)


def natural_from_stats(stats: SuffStats, prior: GMMPosterior,
                       eps: float = 1e-12) -> jnp.ndarray:
    """`pack_natural(posterior_from_stats(stats, prior))` without the
    W^{-1} -> W -> W^{-1} round trip: the message's n2 carries the W^{-1}
    the update builds, so nothing per node is inverted (the local VBM
    optimum phi*, Eq. 18)."""
    alpha, m, beta, W_inv, nu = _vbm_update(stats, prior, eps)
    return jnp.concatenate([alpha - 1.0,
                            expfam.nw_pack_winv(m, beta, W_inv, nu)])


def local_vbm_optimum(x: jnp.ndarray, q_global: GMMPosterior,
                      prior: GMMPosterior, replication: float,
                      mask: jnp.ndarray | None = None,
                      logdet_W: jnp.ndarray | None = None) -> jnp.ndarray:
    """One VBE step + local VBM optimum -> phi*_{theta,i}  (Eqs. 17a, 18).

    Returns the flat natural-parameter message of Eq. 45.  `logdet_W` as
    in `responsibilities`.
    """
    with jax.named_scope("vb/vbe"):
        r = responsibilities(x, q_global, mask, logdet_W)
        stats = sufficient_stats(x, r, replication)
    with jax.named_scope("vb/vbm"):
        return natural_from_stats(stats, prior)


# vmapped over a leading node axis: x (Nnodes, Ni, D), phi (Nnodes, P)
def local_vbm_optimum_nodes(x: jnp.ndarray, phi: jnp.ndarray,
                            prior: GMMPosterior, replication: float,
                            K: int, D: int,
                            mask: jnp.ndarray | None = None) -> jnp.ndarray:
    def one(xi, phii, mi):
        with jax.named_scope("vb/terms"):
            q, logdet_W = expfam.unpack_natural_logdet(phii, K, D)
        return local_vbm_optimum(xi, q, prior, replication, mi, logdet_W)

    if mask is None:
        mask = jnp.ones(x.shape[:2], x.dtype)
    return jax.vmap(one)(x, phi, mask)


def elbo(x: jnp.ndarray, q: GMMPosterior, prior: GMMPosterior,
         replication: float = 1.0) -> jnp.ndarray:
    """Local variational lower bound L_i (Eq. 15) up to y-entropy terms.

    Used for monitoring / tests (monotonicity of centralised VB), not inside
    the algorithms themselves.
    """
    r = responsibilities(x, q)
    D = x.shape[-1]
    e_logpi = expfam.dirichlet_expected_log(q.alpha)
    e_logdet = expfam.wishart_expected_logdet(q.W, q.nu)
    diff = x[:, None, :] - q.m[None, :, :]
    maha = jnp.einsum("jki,kil,jkl->jk", diff, q.W, diff)
    e_quad = D / q.beta[None, :] + q.nu[None, :] * maha
    log_rho = (e_logpi[None, :] + 0.5 * e_logdet[None, :]
               - 0.5 * D * jnp.log(2.0 * jnp.pi) - 0.5 * e_quad)
    e_loglik = replication * jnp.sum(r * log_rho)
    ent_y = -replication * jnp.sum(r * jnp.log(r + 1e-30))
    kl_theta = expfam.gmm_kl(q, prior)
    return e_loglik + ent_y - kl_theta


def ground_truth_posterior(x_all: jnp.ndarray, labels: jnp.ndarray,
                           prior: GMMPosterior, K: int) -> GMMPosterior:
    """Closed-form conjugate posterior given the *true* component labels
    (Sec. V-A: available for synthetic data) — the reference of Eq. 46."""
    r = jax.nn.one_hot(labels, K, dtype=x_all.dtype)              # (Ntot, K)
    stats = sufficient_stats(x_all, r, replication=1.0)
    return posterior_from_stats(stats, prior)


def predict_labels(x: jnp.ndarray, q: GMMPosterior) -> jnp.ndarray:
    """Hard cluster assignment under the variational posterior."""
    return jnp.argmax(responsibilities(x, q), axis=-1)
