"""Exponential-family machinery for the conjugate-exponential VB framework.

The paper (Hua & Li, Eq. 7-11) optimises variational posteriors directly in
the *natural-parameter space* of a conjugate-exponential model.  This module
implements that space for the two families the Bayesian GMM needs:

* Dirichlet over mixing coefficients       pi ~ Dir(alpha)
* Normal-Wishart over (mu_k, Lambda_k)     (mu, L) ~ NW(m, beta, W, nu)

plus the flat packing/unpacking used as the *message* exchanged between nodes
(Eq. 45): phi_theta = [phi_pi, phi_{mu_1,L_1}, ..., phi_{mu_K,L_K}].

Layout of the flat natural-parameter vector for K components in D dims::

    [ alpha-1 (K) | per-component blocks (K * (2 + D + D*D)) ]
    block_k = [ n1, n4, n3 (D), vec(n2) (D*D) ]
      n1 = (nu - D) / 2
      n2 = -1/2 W^{-1} - beta/2 m m^T        (symmetric, stored dense)
      n3 = beta m
      n4 = -beta / 2

All functions are pure jnp and vectorise over arbitrary leading axes of the
hyperparameter pytrees (we use a leading K axis, and algorithms add a leading
node axis on the flat vectors).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.scipy.special import digamma, gammaln, multigammaln


# ---------------------------------------------------------------------------
# Hyperparameter container for the GMM global posterior q(pi) prod_k q(mu,L)
# ---------------------------------------------------------------------------
class GMMPosterior(NamedTuple):
    """Hyperparameters of Dir(alpha) x prod_k NW(m, beta, W, nu)."""

    alpha: jnp.ndarray  # (K,)
    m: jnp.ndarray      # (K, D)
    beta: jnp.ndarray   # (K,)
    W: jnp.ndarray      # (K, D, D)  Wishart scale matrix
    nu: jnp.ndarray     # (K,)       Wishart dof

    @property
    def K(self) -> int:
        return self.alpha.shape[-1]

    @property
    def D(self) -> int:
        return self.m.shape[-1]


def noninformative_prior(K: int, D: int, *, alpha0: float = 1.0,
                         beta0: float = 1.0, nu0: float | None = None,
                         w0_scale: float = 1.0, m0: jnp.ndarray | None = None,
                         dtype=None) -> GMMPosterior:
    """Broad conjugate prior (paper Sec. V: 'non-informative priors').
    `dtype` defaults to the enabled float precision (f64 under x64)."""
    if dtype is None:
        dtype = jnp.result_type(float)
    if nu0 is None:
        nu0 = float(D)
    if m0 is None:
        m0 = jnp.zeros((D,), dtype)
    return GMMPosterior(
        alpha=jnp.full((K,), alpha0, dtype),
        m=jnp.broadcast_to(m0.astype(dtype), (K, D)),
        beta=jnp.full((K,), beta0, dtype),
        W=jnp.broadcast_to(jnp.eye(D, dtype=dtype) * w0_scale, (K, D, D)),
        nu=jnp.full((K,), nu0, dtype),
    )


class NWParams(NamedTuple):
    """Hyperparameters of a bank of K Normal-Wishart factors — the GMM
    posterior minus its Dirichlet part.  This is the hyper container of
    `blocks.NormalWishartBlock`; every nw_* function in this module is
    written against the (m, beta, W, nu) surface, so it accepts either an
    `NWParams` or a full `GMMPosterior`."""

    m: jnp.ndarray      # (K, D)
    beta: jnp.ndarray   # (K,)
    W: jnp.ndarray      # (K, D, D)
    nu: jnp.ndarray     # (K,)

    @property
    def K(self) -> int:
        return self.beta.shape[-1]

    @property
    def D(self) -> int:
        return self.m.shape[-1]


# ---------------------------------------------------------------------------
# Natural parameters <-> hyperparameters  (Eq. 45 + Appendix B)
# ---------------------------------------------------------------------------
def flat_dim(K: int, D: int) -> int:
    return K + K * (2 + D + D * D)


#: names of the natural-parameter blocks of the flat GMM message, in the
#: order of the `block_labels` ids: the Dirichlet block, then per-component
#: n1 (nu), n4 (beta), n3 (beta*m) and n2 (the W^-1 carrier).
BLOCK_NAMES = ("alpha", "nu", "beta", "mean", "winv")


def block_labels(K: int, D: int):
    """(P,) int32 block-type label per coordinate of the flat message.

    The flat natural-parameter vector mixes coordinates whose magnitudes
    differ by orders (alpha ~ counts, n2 ~ -W^-1/2): per-block views let
    the consensus layer compute residual norms and penalties per block
    instead of letting the big blocks drown the small ones
    (`engine.ADMMConsensus(per_block=True)`).  Labels index `BLOCK_NAMES`.
    Returned as a host (numpy) array: it is static packing structure, and
    consumers use it inside jit (block counts must stay concrete).
    """
    import numpy as np
    per = [1, 2] + [3] * D + [4] * (D * D)
    return np.asarray([0] * K + per * K, np.int32)


def spd_inv_logdet(A: jnp.ndarray):
    """(inv(A), log det A) over the trailing two axes of a symmetric
    positive-definite A, from ONE Cholesky factorisation A = L L^T: the
    inverse by two triangular solves against the identity (L Y = I, then
    L^T A^-1 = Y), the log-determinant as 2 sum log diag(L).  A must be
    SPD, as every W^{-1} this serves is (a convex combination of valid
    natural parameters, or the output of `nw_project`, whose eigenvalue
    floor is sized to survive unpack's rounding): for an A that is not,
    both results are NaN where a pivoted LU would return a finite,
    meaningless inverse."""
    L = jax.lax.linalg.cholesky(A)
    eye = jnp.broadcast_to(jnp.eye(A.shape[-1], dtype=A.dtype), A.shape)
    y = jax.lax.linalg.triangular_solve(L, eye, left_side=True, lower=True)
    inv = jax.lax.linalg.triangular_solve(L, y, left_side=True, lower=True,
                                          transpose_a=True)
    logdet = 2.0 * jnp.sum(
        jnp.log(jnp.diagonal(L, axis1=-2, axis2=-1)), -1)
    return inv, logdet


def nw_pack_winv(m, beta, W_inv, nu) -> jnp.ndarray:
    """The packing core: Normal-Wishart hyperparameters, with the scale
    matrix given by its INVERSE W^{-1}, -> the per-component
    [n1, n4, n3, vec(n2)] blocks of Eq. 45, flattened.  n2 carries W^{-1}
    itself, so a caller that holds W^{-1} (the VBM update builds it)
    packs without inverting anything."""
    K, D = beta.shape[-1], m.shape[-1]
    n1 = (nu - D) / 2.0                                              # (K,)
    n4 = -beta / 2.0                                                 # (K,)
    n3 = beta[:, None] * m                                           # (K, D)
    mmT = m[:, :, None] * m[:, None, :]
    n2 = -0.5 * W_inv - 0.5 * beta[:, None, None] * mmT              # (K, D, D)
    blocks = jnp.concatenate(
        [n1[:, None], n4[:, None], n3, n2.reshape(K, D * D)], axis=-1)
    return blocks.reshape(-1)


def nw_pack(q) -> jnp.ndarray:
    """Normal-Wishart bank -> its flat natural-parameter segment (Eq. 45).
    Accepts an `NWParams` or a `GMMPosterior` (only m/beta/W/nu are read);
    inverts W once and calls `nw_pack_winv`."""
    return nw_pack_winv(q.m, q.beta, jnp.linalg.inv(q.W), q.nu)


def nw_unpack_logdet(seg: jnp.ndarray, K: int, D: int):
    """Flat Normal-Wishart segment -> (NWParams, log|W| (K,)).

    One Cholesky factorisation of the carried (SPD) W^{-1} gives both W
    and log|W| = -log|W^{-1}| (`spd_inv_logdet`), so the E-step's
    E[ln|Lambda|] needs no second one (`wishart_expected_logdet(...,
    logdet_W=)`)."""
    blocks = seg.reshape(K, 2 + D + D * D)
    n1 = blocks[:, 0]
    n4 = blocks[:, 1]
    n3 = blocks[:, 2:2 + D]
    n2 = blocks[:, 2 + D:].reshape(K, D, D)
    beta = -2.0 * n4
    m = n3 / beta[:, None]
    nu = 2.0 * n1 + D
    mmT = m[:, :, None] * m[:, None, :]
    W_inv = -2.0 * n2 - beta[:, None, None] * mmT
    W, logdet_W_inv = spd_inv_logdet(W_inv)
    return NWParams(m=m, beta=beta, W=W, nu=nu), -logdet_W_inv


def nw_unpack(seg: jnp.ndarray, K: int, D: int) -> NWParams:
    """Flat Normal-Wishart segment -> NWParams (inverse of `nw_pack`)."""
    return nw_unpack_logdet(seg, K, D)[0]


def pack_natural(q: GMMPosterior) -> jnp.ndarray:
    """GMMPosterior -> flat natural-parameter message (Eq. 45)."""
    return jnp.concatenate([q.alpha - 1.0, nw_pack(q)])


def unpack_natural_logdet(phi: jnp.ndarray, K: int, D: int):
    """Flat natural-parameter message -> (GMMPosterior, log|W| (K,)), from
    one factorisation per component (`nw_unpack_logdet`)."""
    alpha = phi[:K] + 1.0
    nw, logdet_W = nw_unpack_logdet(phi[K:], K, D)
    return (GMMPosterior(alpha=alpha, m=nw.m, beta=nw.beta, W=nw.W, nu=nw.nu),
            logdet_W)


def unpack_natural(phi: jnp.ndarray, K: int, D: int) -> GMMPosterior:
    """Flat natural-parameter message -> GMMPosterior (inverse of pack)."""
    return unpack_natural_logdet(phi, K, D)[0]


def nw_project(seg: jnp.ndarray, K: int, D: int, *,
               min_beta: float = 1e-6, min_eig: float = 1e-8) -> jnp.ndarray:
    """Projection of a flat Normal-Wishart segment onto its domain: clamps
    beta and nu and projects the W^{-1} carrier onto the PSD cone by
    eigenvalue clipping (the closest point in Frobenius norm).

    A component already in the domain comes back bit-unchanged: only the
    coordinates of a component whose clamp or clip fired are rebuilt.  The
    eigh round trip and the nu round trip are not exact (in f32 their
    error at the norms VB reaches is far above ADMM's `clip_tol`), and
    the adaptive consensus reads any change as an eigen-clip."""
    blocks = seg.reshape(K, 2 + D + D * D)
    n1 = blocks[:, 0]
    n4 = jnp.minimum(blocks[:, 1], -min_beta / 2.0)   # beta >= min_beta
    n3 = blocks[:, 2:2 + D]
    n2 = blocks[:, 2 + D:].reshape(K, D, D)
    beta = -2.0 * n4
    m = n3 / beta[:, None]
    nu_min = (D - 1.0) + 1e-3
    n1 = jnp.where(2.0 * n1 + D < nu_min, (nu_min - D) / 2.0, n1)
    mmT = m[:, :, None] * m[:, None, :]
    W_inv = -2.0 * n2 - beta[:, None, None] * mmT
    W_inv = 0.5 * (W_inv + jnp.swapaxes(W_inv, -1, -2))
    eigval, eigvec = jnp.linalg.eigh(W_inv)
    # The floor must survive the round trip to the W^{-1} that unpack
    # factors by Cholesky: the eigh rebuild errs by ~eps ||W^-1||, and
    # unpack's -2 n2 - beta m m^T cancels ~eps beta |m|^2 more.  So it is
    # relative, and in f32 a few D eps of that scale (the 1e-10 term only
    # binds in f64); an absolute 1e-8 would not survive at large norms.
    lam_max = jnp.max(jnp.abs(eigval), -1, keepdims=True)
    scale = lam_max + beta[:, None] * jnp.sum(m * m, -1, keepdims=True)
    eps = jnp.finfo(W_inv.dtype).eps
    floor = jnp.maximum(jnp.maximum(min_eig, 1e-10 * lam_max),
                        4.0 * D * eps * scale)
    clip = jnp.any(eigval < floor, axis=-1) | (n4 != blocks[:, 1])
    eigval = jnp.maximum(eigval, floor)
    W_inv = jnp.einsum("kij,kj,klj->kil", eigvec, eigval, eigvec)
    n2 = jnp.where(clip[:, None, None],
                   -0.5 * W_inv - 0.5 * beta[:, None, None] * mmT, n2)
    blocks = jnp.concatenate(
        [n1[:, None], n4[:, None], n3, n2.reshape(K, D * D)], axis=-1)
    return blocks.reshape(-1)


def project_to_domain(phi: jnp.ndarray, K: int, D: int, *,
                      min_alpha: float = 1e-3, min_beta: float = 1e-6,
                      min_eig: float = 1e-8) -> jnp.ndarray:
    """Euclidean projection of a natural-parameter point onto (the interior
    of) the domain Omega (Eq. 38b).

    Omega requires alpha_k > 0, beta_k > 0, nu_k > D - 1 and W^{-1} > 0.
    The Dirichlet and Normal-Wishart segments project independently (the
    domain is a product set), so this is the concatenation of the two
    per-family projections — exactly how `blocks.BlockModel` composes them.
    """
    alpha = jnp.maximum(phi[:K] + 1.0, min_alpha)
    return jnp.concatenate([alpha - 1.0,
                            nw_project(phi[K:], K, D, min_beta=min_beta,
                                       min_eig=min_eig)])


def in_domain(phi: jnp.ndarray, K: int, D: int) -> jnp.ndarray:
    """Boolean: does phi lie in the natural-parameter domain Omega (Eq. 8)?"""
    q = unpack_natural(phi, K, D)
    # Use eigenvalues of the W^{-1} implied by the raw coordinates.
    blocks = phi[K:].reshape(K, 2 + D + D * D)
    n2 = blocks[:, 2 + D:].reshape(K, D, D)
    beta = -2.0 * blocks[:, 1]
    m = blocks[:, 2:2 + D] / beta[:, None]
    W_inv = -2.0 * n2 - beta[:, None, None] * (m[:, :, None] * m[:, None, :])
    eigs = jnp.linalg.eigvalsh(0.5 * (W_inv + jnp.swapaxes(W_inv, -1, -2)))
    ok = (
        jnp.all(q.alpha > 0)
        & jnp.all(q.beta > 0)
        & jnp.all(q.nu > q.D - 1)
        & jnp.all(eigs > 0)
    )
    return ok


# ---------------------------------------------------------------------------
# Log-partition functions A(phi) and expected sufficient statistics (Eq. 10a)
# ---------------------------------------------------------------------------
def dirichlet_log_partition(alpha: jnp.ndarray) -> jnp.ndarray:
    return jnp.sum(gammaln(alpha), -1) - gammaln(jnp.sum(alpha, -1))


def dirichlet_expected_log(alpha: jnp.ndarray) -> jnp.ndarray:
    """E[ln pi_k] = psi(alpha_k) - psi(sum alpha)."""
    return digamma(alpha) - digamma(jnp.sum(alpha, -1, keepdims=True))


def wishart_expected_logdet(W: jnp.ndarray, nu: jnp.ndarray,
                            logdet_W: jnp.ndarray | None = None
                            ) -> jnp.ndarray:
    """E[ln |Lambda|] for Lambda ~ W(W, nu)  (Appendix A).  `logdet_W`,
    where the caller already has log|W| (`unpack_natural_logdet`), saves
    the factorisation of W."""
    D = W.shape[-1]
    j = jnp.arange(1, D + 1, dtype=W.dtype)
    if logdet_W is None:
        logdet_W = jnp.linalg.slogdet(W)[1]
    return (jnp.sum(digamma((nu[..., None] + 1.0 - j) / 2.0), -1)
            + D * jnp.log(2.0) + logdet_W)


def nw_log_partition(q: GMMPosterior) -> jnp.ndarray:
    """A(phi_k) for each Normal-Wishart component (Appendix B), shape (K,)."""
    D = q.D
    return (-D / 2.0 * jnp.log(q.beta)
            + q.nu / 2.0 * jnp.linalg.slogdet(q.W)[1]
            + q.nu * D / 2.0 * jnp.log(2.0)
            + multigammaln(q.nu / 2.0, D))


def nw_expected_stats(q: GMMPosterior):
    """E[u] = (E[ln|L|], E[L], E[L mu], E[mu^T L mu]) per component."""
    e_logdet = wishart_expected_logdet(q.W, q.nu)                  # (K,)
    e_L = q.nu[:, None, None] * q.W                                # (K, D, D)
    e_Lmu = jnp.einsum("kij,kj->ki", e_L, q.m)                     # (K, D)
    e_quad = q.D / q.beta + jnp.einsum("ki,kij,kj->k", q.m, e_L, q.m)
    return e_logdet, e_L, e_Lmu, e_quad


def gmm_log_partition(q: GMMPosterior) -> jnp.ndarray:
    """A(phi) of the joint Dir x prod NW global distribution (scalar)."""
    return dirichlet_log_partition(q.alpha) + jnp.sum(nw_log_partition(q))


def nw_expected_stats_flat(q) -> jnp.ndarray:
    """E[u] of the Normal-Wishart bank laid out exactly like `nw_pack`:
    per-component [E ln|L|, E mu'L mu, E L mu, vec(E L)], flattened."""
    K, D = q.beta.shape[-1], q.m.shape[-1]
    e_logdet, e_L, e_Lmu, e_quad = nw_expected_stats(q)
    blocks = jnp.concatenate(
        [e_logdet[:, None], e_quad[:, None], e_Lmu, e_L.reshape(K, D * D)],
        axis=-1)
    return blocks.reshape(-1)


def expected_sufficient_stats(q: GMMPosterior) -> jnp.ndarray:
    """grad_phi A(phi) laid out exactly like the flat packing.

    By Eq. 10a this is E[u(z)]; verified against jax.grad of the packed
    log-partition in the test-suite (a strong invariant of the packing).
    """
    e_logpi = dirichlet_expected_log(q.alpha)                      # (K,)
    return jnp.concatenate([e_logpi, nw_expected_stats_flat(q)])


# ---------------------------------------------------------------------------
# KL divergences (Appendix B) -- the paper's performance metric (Eq. 46)
# ---------------------------------------------------------------------------
def dirichlet_kl(alpha: jnp.ndarray, alpha_hat: jnp.ndarray) -> jnp.ndarray:
    e_logpi = dirichlet_expected_log(alpha)
    return (jnp.sum((alpha - alpha_hat) * e_logpi)
            - dirichlet_log_partition(alpha)
            + dirichlet_log_partition(alpha_hat))


def nw_kl(q: GMMPosterior, p: GMMPosterior) -> jnp.ndarray:
    """sum_k KL(NW(q_k) || NW(p_k)) via the exp-family identity
    KL = (phi_q - phi_p)^T E_q[u] - A(phi_q) + A(phi_p)."""
    def nat(qq: GMMPosterior):
        n1 = (qq.nu - qq.D) / 2.0
        W_inv = jnp.linalg.inv(qq.W)
        mmT = qq.m[:, :, None] * qq.m[:, None, :]
        n2 = -0.5 * W_inv - 0.5 * qq.beta[:, None, None] * mmT
        n3 = qq.beta[:, None] * qq.m
        n4 = -qq.beta / 2.0
        return n1, n2, n3, n4

    q1, q2, q3, q4 = nat(q)
    p1, p2, p3, p4 = nat(p)
    e_logdet, e_L, e_Lmu, e_quad = nw_expected_stats(q)
    inner = ((q1 - p1) * e_logdet
             + jnp.einsum("kij,kij->k", q2 - p2, e_L)
             + jnp.einsum("ki,ki->k", q3 - p3, e_Lmu)
             + (q4 - p4) * e_quad)
    return jnp.sum(inner - nw_log_partition(q) + nw_log_partition(p))


def gmm_kl(q: GMMPosterior, p: GMMPosterior) -> jnp.ndarray:
    """d(phi, phi_hat) of Eq. 46: KL(Q(theta|phi) || P(theta|phi_hat))."""
    return dirichlet_kl(q.alpha, p.alpha) + nw_kl(q, p)


def gmm_kl_flat(phi: jnp.ndarray, phi_hat: jnp.ndarray, K: int, D: int):
    return gmm_kl(unpack_natural(phi, K, D), unpack_natural(phi_hat, K, D))
