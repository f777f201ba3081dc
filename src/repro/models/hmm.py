"""HMMModel: conjugate hidden Markov chains over the block layer.

Third member of the conjugate-exponential family the engine serves — the
model D-MFVI-style distributed VB papers use to stress transition-structure
conjugacy.  Each sensor observes S iid chains of length L:

    z_1 ~ Cat(pi),  z_{l+1} | z_l ~ Cat(A[z_l]),  x_l | z_l ~ N(mu_k, L_k^-1)

with the fully conjugate prior pi ~ Dir, A[k] ~ Dir per row, (mu_k, L_k) ~
Normal-Wishart.  The global posterior factorises into exactly three
exponential-family blocks, so the adapter is a `blocks.BlockModel`
composition with ZERO new engine/serving code:

    DirichletBlock(K, rows=1, "pi")     initial-state weights
    DirichletBlock(K, rows=K, "trans")  one Dirichlet per transition row
    NormalWishartBlock(K, D)            the GMM emission bank (reused)

The VBE step is Beal's variational forward-backward: sub-normalised
parameters exp E[ln pi], exp E[ln A], exp E[ln emission] feed a standard
log-space alpha/beta recursion, giving per-chain state marginals gamma and
pairwise marginals xi.  The VBM optimum adds the replicated expected counts
to the prior — Dirichlet counts for pi (gamma_1) and A (sum_l xi_l), and
the GMM sufficient statistics (gmm.sufficient_stats on the gamma-weighted
flattened chains) for the emissions: Eqs. 17a/18 verbatim, three blocks at
once.

Data convention: `(x (N, S, L, D), mask (N, S))` — axis 1 is the SAMPLE
axis (whole chains are the iid unit), so the protocol-level streaming /
padding / append plumbing applies unchanged: minibatches subsample chains
with unbiased T/B rescaling (per-chain statistics are linear in the scaled
mask), and bucketed-admission padding appends mask-zero chains whose
statistics are zero.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.scipy.special import logsumexp

from repro.core import blocks, expfam, gmm
from repro.core.expfam import GMMPosterior, NWParams


class HMMPosterior(NamedTuple):
    """Hyperparameters of the three-block HMM posterior."""

    pi: jnp.ndarray     # (K,)     Dirichlet over the initial state
    trans: jnp.ndarray  # (K, K)   one Dirichlet per transition row
    m: jnp.ndarray      # (K, D)   Normal-Wishart emission bank
    beta: jnp.ndarray   # (K,)
    W: jnp.ndarray      # (K, D, D)
    nu: jnp.ndarray     # (K,)

    @property
    def K(self) -> int:
        return self.pi.shape[-1]

    @property
    def D(self) -> int:
        return self.m.shape[-1]


def noninformative_prior(K: int, D: int, *, alpha0: float = 1.0,
                         trans0: float = 1.0, beta0: float = 1.0,
                         nu0: float | None = None, w0_scale: float = 1.0,
                         dtype=None) -> HMMPosterior:
    """Broad conjugate prior: uniform Dirichlets + the GMM emission prior.
    `dtype` defaults to the enabled float precision (f64 under x64)."""
    if dtype is None:
        dtype = jnp.result_type(float)
    g = expfam.noninformative_prior(K, D, alpha0=alpha0, beta0=beta0,
                                    nu0=nu0, w0_scale=w0_scale, dtype=dtype)
    return HMMPosterior(pi=g.alpha, trans=jnp.full((K, K), trans0, dtype),
                        m=g.m, beta=g.beta, W=g.W, nu=g.nu)


def _emission_loglik(x: jnp.ndarray, nw: NWParams) -> jnp.ndarray:
    """(L, D) chain -> (L, K) expected emission log-densities
    E[ln N(x_l | mu_k, L_k^-1)] (the Appendix-A responsibility terms minus
    the mixing weight)."""
    D = x.shape[-1]
    e_logdet = expfam.wishart_expected_logdet(nw.W, nw.nu)         # (K,)
    diff = x[:, None, :] - nw.m[None, :, :]                        # (L, K, D)
    maha = jnp.einsum("jki,kil,jkl->jk", diff, nw.W, diff)
    e_quad = D / nw.beta[None, :] + nw.nu[None, :] * maha
    return (0.5 * e_logdet[None, :]
            - 0.5 * D * jnp.log(2.0 * jnp.pi) - 0.5 * e_quad)


def forward_backward(log_emit: jnp.ndarray, log_pi: jnp.ndarray,
                     log_A: jnp.ndarray):
    """Variational forward-backward on ONE chain, in log space.

    log_emit (L, K), log_pi (K,) = E[ln pi], log_A (K, K) = E[ln A]
    (sub-normalised: Beal's VBEM uses the exponentials of expected logs).
    Returns (gamma (L, K) state marginals, xi (L-1, K, K) pairwise
    marginals, both normalised).
    """
    L, K = log_emit.shape

    def fstep(la, le):
        la_new = logsumexp(la[:, None] + log_A, axis=0) + le
        return la_new, la_new

    la0 = log_pi + log_emit[0]
    _, las = jax.lax.scan(fstep, la0, log_emit[1:])
    log_alpha = jnp.concatenate([la0[None], las])                  # (L, K)

    def bstep(lb, le):
        lb_new = logsumexp(log_A + (le + lb)[None, :], axis=1)
        return lb_new, lb_new

    _, lbs = jax.lax.scan(bstep, jnp.zeros((K,), log_emit.dtype),
                          log_emit[1:], reverse=True)
    log_beta = jnp.concatenate([lbs, jnp.zeros((1, K), log_emit.dtype)])

    gamma = jax.nn.softmax(log_alpha + log_beta, axis=-1)          # (L, K)
    lx = (log_alpha[:-1, :, None] + log_A[None]
          + (log_emit[1:] + log_beta[1:])[:, None, :])             # (L-1,K,K)
    xi = jax.nn.softmax(lx.reshape(L - 1, K * K),
                        axis=-1).reshape(L - 1, K, K)
    return gamma, xi


class HMMModel(blocks.BlockModel):
    """Dirichlet(pi) x Dirichlet-rows(A) x Normal-Wishart emission HMM."""

    def __init__(self, prior: HMMPosterior, K: int | None = None,
                 D: int | None = None):
        self.prior = prior
        self.K = K if K is not None else prior.K
        self.D = D if D is not None else prior.D
        self.blocks = (blocks.DirichletBlock(self.K, name="pi"),
                       blocks.DirichletBlock(self.K, rows=self.K,
                                             name="trans"),
                       blocks.NormalWishartBlock(self.K, self.D))

    def split_hyper(self, q: HMMPosterior) -> tuple:
        return (q.pi[None], q.trans,
                NWParams(m=q.m, beta=q.beta, W=q.W, nu=q.nu))

    def join_hyper(self, parts: tuple) -> HMMPosterior:
        pi, trans, nw = parts
        return HMMPosterior(pi=pi[0], trans=trans, m=nw.m, beta=nw.beta,
                            W=nw.W, nu=nw.nu)

    def local_optimum(self, data, phi_nodes, replication):
        x, mask = data
        return jax.vmap(lambda xi, mi, phii: self._local_one(
            xi, mi, phii, replication))(x, mask, phi_nodes)

    def _local_one(self, x, w, phi, replication):
        """One node: (S, L, D) chains + (S,) scaled mask -> phi* (P,)."""
        K, D = self.K, self.D
        S, L = x.shape[0], x.shape[1]
        q = self.unpack(phi)
        log_pi = expfam.dirichlet_expected_log(q.pi)                # (K,)
        log_A = expfam.dirichlet_expected_log(q.trans)              # (K, K)
        nw = NWParams(m=q.m, beta=q.beta, W=q.W, nu=q.nu)

        def per_chain(xc):
            return forward_backward(_emission_loglik(xc, nw), log_pi, log_A)

        gamma, xi = jax.vmap(per_chain)(x)      # (S, L, K), (S, L-1, K, K)

        # Expected counts, replicated (Appendix-A style); the chain axis
        # is the sample axis.
        pi_counts = replication * jnp.sum(
            w[:, None] * gamma[:, 0, :], axis=0)                    # (K,)
        trans_counts = replication * jnp.sum(
            w[:, None, None] * jnp.sum(xi, axis=1), axis=0)         # (K, K)

        # Emission block: gamma-weighted chains, flattened to one sample
        # axis (row-major keeps padded chains at the tail), reuse the GMM
        # statistics + Appendix-A VBM update verbatim.
        r = (w[:, None, None] * gamma).reshape(S * L, K)
        stats = gmm.sufficient_stats(x.reshape(S * L, D), r, replication)
        prior_g = GMMPosterior(alpha=self.prior.pi, m=self.prior.m,
                               beta=self.prior.beta, W=self.prior.W,
                               nu=self.prior.nu)
        emis = gmm.posterior_from_stats(stats, prior_g)

        return self.pack(HMMPosterior(
            pi=self.prior.pi + pi_counts,
            trans=self.prior.trans + trans_counts,
            m=emis.m, beta=emis.beta, W=emis.W, nu=emis.nu))


def perturbed_init(prior: HMMPosterior, x: jnp.ndarray, key,
                   spread: float = 1.0) -> HMMPosterior:
    """Random-restart initialisation: the prior with emission means
    scattered over the data range (cf. algorithms._perturbed_init) — the
    exchangeable-component symmetry of the prior is a fixed point of the
    VB iteration, so runs must start off it."""
    K, D = prior.K, prior.D
    flat = x.reshape(-1, D)
    lo, hi = jnp.min(flat, axis=0), jnp.max(flat, axis=0)
    m = lo + (hi - lo) * jax.random.uniform(key, (K, D), prior.m.dtype)
    return prior._replace(m=prior.m + spread * (m - prior.m))


# ---------------------------------------------------------------------------
# Synthetic sensor chains (examples + tests)
# ---------------------------------------------------------------------------
def sample_chains(n_nodes: int, n_chains: int, length: int, *,
                  K: int = 3, D: int = 2, seed: int = 0,
                  self_loop: float = 0.8, sep: float = 4.0,
                  dtype=np.float64):
    """Ground-truth HMM chains per sensor: sticky uniform-offdiagonal
    transitions, well-separated spherical Gaussian emissions.  Returns
    (x (N, S, L, D), mask (N, S), pi_true, A_true, means)."""
    rng = np.random.default_rng(seed)
    pi = np.full(K, 1.0 / K)
    A = np.full((K, K), (1.0 - self_loop) / (K - 1))
    np.fill_diagonal(A, self_loop)
    ang = 2.0 * np.pi * np.arange(K) / K
    means = np.zeros((K, D))
    circ = sep * np.stack([np.cos(ang), np.sin(ang)], -1)
    means[:, :min(D, 2)] = circ[:, :min(D, 2)]
    x = np.zeros((n_nodes, n_chains, length, D), dtype)
    for i in range(n_nodes):
        for s in range(n_chains):
            z = rng.choice(K, p=pi)
            for l in range(length):
                x[i, s, l] = means[z] + rng.normal(size=D)
                z = rng.choice(K, p=A[z])
    mask = np.ones((n_nodes, n_chains), dtype)
    return x, mask, pi, A, means
