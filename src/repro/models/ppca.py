"""PPCAModel: Bayesian probabilistic PCA / factor analysis over the block
layer.

Fourth member of the conjugate-exponential family — the distributed-VB
stress model of the D-MFVI line of work (Babagholami-Mohamadabadi et al.):
each sensor observes T iid D-dimensional points generated from a shared
Q-dimensional latent subspace,

    z_j ~ N(0, I_Q),
    x_jd | z_j ~ N(w_d^T z_j, lambda_d^{-1}),   d = 1..D

with the fully conjugate per-row Normal-Gamma prior lambda_d ~ Gamma,
w_d | lambda_d ~ N(m0, (lambda_d V0)^{-1}).  The global posterior over the
loading matrix is a BANK of D independent Normal-Gamma rows — exactly
`blocks.NormalGammaBlock(Q, rows=D)`, the same family as Bayesian linear
regression with the latent coordinates z as the (inferred) design matrix.
The adapter is a one-block `blocks.BlockModel`; the hyper container is a
`linreg.NGPosterior` with a leading rows axis.

VBE step (per node): with the current loading posterior, each point's
latent factor is Gaussian with shared covariance

    Sigma_z = (I_Q + sum_d E[lambda_d w_d w_d^T])^{-1},
    mu_j    = Sigma_z sum_d E[lambda_d w_d] x_jd,

VBM optimum (per row d): the Bayesian-linreg update of core/linreg.py with
the replicated latent statistics Szz = sum_j w_j (Sigma_z + mu_j mu_j^T),
Szx_d = sum_j w_j mu_j x_jd, Sxx_d = sum_j w_j x_jd^2, n = sum_j w_j —
Eqs. 17a/18 once more.  The flat natural parameters are LINEAR in these
statistics (the linreg algebra), and the statistics are linear in the
mask, so streaming minibatches and the SVRG control variate stay exactly
unbiased, and bucketed-admission padding (mask zero) adds nothing.

Data convention: the protocol default `(x (N, T, D), mask (N, T))`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import blocks, linreg
from repro.core.linreg import NGPosterior


def prior(D: int, Q: int, *, a0: float = 1.0, b0: float = 1.0,
          v0: float = 1e-2, dtype=None) -> NGPosterior:
    """Row-stacked broad Normal-Gamma prior over the (D, Q) loading matrix
    (`dtype`: see `linreg.prior`)."""
    one = linreg.prior(Q, a0=a0, b0=b0, v0=v0, dtype=dtype)
    return NGPosterior(
        m=jnp.broadcast_to(one.m, (D, Q)),
        V=jnp.broadcast_to(one.V, (D, Q, Q)),
        a=jnp.broadcast_to(one.a, (D,)),
        b=jnp.broadcast_to(one.b, (D,)))


def latent_posterior(x: jnp.ndarray, q: NGPosterior):
    """VBE step on one node: (T, D) points + rows posterior ->
    (Sigma_z (Q, Q), mu (T, Q)) of the per-point latent factors."""
    Q = q.m.shape[-1]
    e_lam = q.a / q.b                                              # (D,)
    V_inv = jnp.linalg.inv(q.V)                                    # (D, Q, Q)
    e_lww = V_inv + e_lam[:, None, None] * (
        q.m[:, :, None] * q.m[:, None, :])                         # (D, Q, Q)
    sigma_inv = jnp.eye(Q, dtype=x.dtype) + jnp.sum(e_lww, axis=0)
    sigma = jnp.linalg.inv(sigma_inv)                              # (Q, Q)
    A = e_lam[:, None] * q.m                                       # (D, Q)
    mu = (x @ A) @ sigma.T                                         # (T, Q)
    return sigma, mu


class PPCAModel(blocks.BlockModel):
    """Bank-of-Normal-Gamma-rows factor analysis (Bayesian PPCA)."""

    def __init__(self, prior: NGPosterior, D: int | None = None,
                 Q: int | None = None):
        self.prior = prior
        self.D = D if D is not None else prior.m.shape[0]
        self.Q = Q if Q is not None else prior.m.shape[-1]
        self.blocks = (blocks.NormalGammaBlock(self.Q, rows=self.D),)

    def split_hyper(self, q: NGPosterior) -> tuple:
        return (q,)

    def join_hyper(self, parts: tuple) -> NGPosterior:
        return parts[0]

    def local_optimum(self, data, phi_nodes, replication):
        x, mask = data
        return jax.vmap(lambda xi, mi, phii: self._local_one(
            xi, mi, phii, replication))(x, mask, phi_nodes)

    def _local_one(self, x, w, phi, replication):
        """One node: (T, D) points + (T,) scaled mask -> phi* (P,)."""
        q = self.unpack(phi)
        sigma, mu = latent_posterior(x, q)

        # replicated latent statistics
        p0 = self.prior
        wx = x * w[:, None]                                        # (T, D)
        muw = mu * w[:, None]                                      # (T, Q)
        n = jnp.sum(w) * replication
        Szz = (jnp.sum(muw[:, :, None] * mu[:, None, :], axis=0)
               * replication + n * sigma)                          # (Q, Q)
        Szx = jnp.sum(
            wx[:, :, None] * mu[:, None, :], axis=0) * replication  # (D, Q)
        Sxx = jnp.sum(wx * x, axis=0) * replication                # (D,)

        def row(V0, m0, a0, b0, szx, sxx):
            V = V0 + Szz
            m = jnp.linalg.solve(V, V0 @ m0 + szx)
            a = a0 + n / 2.0
            b = b0 + 0.5 * (sxx + m0 @ V0 @ m0 - m @ V @ m)
            return NGPosterior(m=m, V=V, a=a, b=b)

        q_new = jax.vmap(row)(p0.V, p0.m, p0.a, p0.b, Szx, Sxx)
        return self.pack(q_new)


def perturbed_init(prior: NGPosterior, key, scale: float = 0.1) -> NGPosterior:
    """Random-restart initialisation: the prior with the loading-row means
    jittered (cf. hmm.perturbed_init).  The zero-mean prior is a fixed
    point of the VB iteration — m = 0 makes every latent mean 0, which
    keeps m = 0 — so runs must start off it."""
    m = prior.m + scale * jax.random.normal(key, prior.m.shape,
                                            prior.m.dtype)
    return prior._replace(m=m)


# ---------------------------------------------------------------------------
# Synthetic sensor subspace data (examples + tests)
# ---------------------------------------------------------------------------
def sample_sensors(n_nodes: int, n_per_node: int, *, D: int = 6, Q: int = 2,
                   seed: int = 0, noise: float = 0.1, dtype=np.float64):
    """Ground-truth PPCA data: one shared (D, Q) loading matrix, iid latent
    factors per point, per-dimension noise 1/lambda = noise^2.  Returns
    (x (N, T, D), mask (N, T), W_true (D, Q))."""
    rng = np.random.default_rng(seed)
    W_true = rng.normal(size=(D, Q)) / np.sqrt(Q)
    z = rng.normal(size=(n_nodes, n_per_node, Q))
    x = z @ W_true.T + noise * rng.normal(size=(n_nodes, n_per_node, D))
    return (x.astype(dtype), np.ones((n_nodes, n_per_node), dtype),
            W_true.astype(dtype))
