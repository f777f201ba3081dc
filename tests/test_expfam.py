"""Exponential-family invariants (unit + hypothesis property tests)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip(
    "hypothesis", reason="hypothesis not available in this environment")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core import expfam, gmm


@pytest.fixture(autouse=True, scope="module")
def _x64():
    """x64 for the VB numerics in THIS module only (restored afterwards so
    the float32 framework-layer tests aren't affected)."""
    jax.config.update("jax_enable_x64", True)
    yield
    jax.config.update("jax_enable_x64", False)


def random_posterior(rng, K, D):
    m = rng.normal(size=(K, D)) * 3
    beta = rng.uniform(0.5, 20, K)
    nu = rng.uniform(D + 1.0, D + 50, K)
    A = rng.normal(size=(K, D, D)) * 0.3
    W = np.einsum("kij,klj->kil", A, A) + np.eye(D) * 0.5
    alpha = rng.uniform(0.5, 30, K)
    return expfam.GMMPosterior(alpha=jnp.asarray(alpha), m=jnp.asarray(m),
                               beta=jnp.asarray(beta), W=jnp.asarray(W),
                               nu=jnp.asarray(nu))


@settings(max_examples=20, deadline=None)
@given(st.integers(2, 5), st.integers(1, 4), st.integers(0, 10_000))
def test_pack_unpack_roundtrip(K, D, seed):
    q = random_posterior(np.random.default_rng(seed), K, D)
    q2 = expfam.unpack_natural(expfam.pack_natural(q), K, D)
    for a, b in zip(q, q2):
        np.testing.assert_allclose(a, b, rtol=1e-8, atol=1e-8)


@settings(max_examples=15, deadline=None)
@given(st.integers(2, 4), st.integers(1, 3), st.integers(0, 10_000))
def test_grad_log_partition_is_expected_stats(K, D, seed):
    """Eq. 10a: grad_phi A(phi) == E[u(z)] — pins the packing layout."""
    q = random_posterior(np.random.default_rng(seed), K, D)
    phi = expfam.pack_natural(q)
    gA = jax.grad(lambda p: expfam.gmm_log_partition(
        expfam.unpack_natural(p, K, D)))(phi)
    es = expfam.expected_sufficient_stats(q)
    np.testing.assert_allclose(gA, es, rtol=1e-6, atol=1e-8)


@settings(max_examples=15, deadline=None)
@given(st.integers(2, 4), st.integers(1, 3), st.integers(0, 10_000),
       st.integers(0, 10_000))
def test_kl_properties(K, D, s1, s2):
    q = random_posterior(np.random.default_rng(s1), K, D)
    p = random_posterior(np.random.default_rng(s2), K, D)
    klqq = float(expfam.gmm_kl(q, q))
    klqp = float(expfam.gmm_kl(q, p))
    assert abs(klqq) < 1e-6
    assert klqp > -1e-8


def test_kl_zero_iff_equal_and_positive_when_not():
    q = random_posterior(np.random.default_rng(0), 3, 2)
    p = q._replace(m=q.m + 0.5)
    assert float(expfam.gmm_kl(q, p)) > 1e-3


@settings(max_examples=15, deadline=None)
@given(st.integers(2, 4), st.integers(1, 3), st.integers(0, 10_000))
def test_projection_lands_in_domain(K, D, seed):
    """Eq. 38b: after projection the point is in Omega, and projecting a
    point already in Omega is (near) identity."""
    rng = np.random.default_rng(seed)
    q = random_posterior(rng, K, D)
    phi = expfam.pack_natural(q)
    assert bool(expfam.in_domain(phi, K, D))
    proj = expfam.project_to_domain(phi, K, D)
    np.testing.assert_allclose(proj, phi, rtol=1e-6, atol=1e-8)
    # corrupt mildly (the ADMM scenario, Sec. III-B): nu below D-1 and a
    # W^{-1} pushed indefinite via its n2 block
    bad = np.asarray(phi).copy()
    bad[K] = -(D + 1.0) / 2.0                # n1 => nu = -1 < D - 1
    blk = 2 + D + D * D
    n2_start = K + 2 + D
    bad[n2_start:n2_start + D * D] += np.eye(D).reshape(-1) * 10.0
    bad = jnp.asarray(bad)
    assert not bool(expfam.in_domain(bad, K, D))
    fixed = expfam.project_to_domain(bad, K, D)
    assert bool(expfam.in_domain(fixed, K, D))


def test_dirichlet_expected_log_matches_mc():
    alpha = jnp.asarray([2.0, 5.0, 1.0])
    rng = np.random.default_rng(0)
    samples = rng.dirichlet(np.asarray(alpha), size=200_000)
    mc = np.log(samples).mean(0)
    np.testing.assert_allclose(expfam.dirichlet_expected_log(alpha), mc,
                               atol=5e-3)


def test_flat_dim():
    for K, D in [(3, 2), (2, 5), (10, 52)]:
        q = random_posterior(np.random.default_rng(0), K, D)
        assert expfam.pack_natural(q).shape == (expfam.flat_dim(K, D),)


def _stats_with_conditioned_winv(D, K=3, cond=1e3, seed=0):
    """Replicated statistics whose VBM update's W^-1 = R C + (tiny prior
    and cross terms), C a random rotation of eigenvalues spread over
    `cond`: cond(W^-1) about `cond`, the order a real-width node reaches."""
    rng = np.random.default_rng(seed)
    R = rng.uniform(5e3, 2e4, K)
    xbar = rng.normal(size=(K, D)) * 0.1
    Q = np.linalg.qr(rng.normal(size=(K, D, D)))[0]
    C = np.einsum("kij,j,klj->kil", Q, np.logspace(-np.log10(cond), 0, D), Q)
    sum_xx = R[:, None, None] * (C + xbar[:, :, None] * xbar[:, None, :])
    stats = gmm.SuffStats(R=jnp.asarray(R), sum_x=jnp.asarray(R[:, None] * xbar),
                          sum_xx=jnp.asarray(sum_xx))
    prior = expfam.noninformative_prior(K, D, beta0=0.1, w0_scale=1e4,
                                        dtype=jnp.float64)
    return stats, prior


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


@pytest.mark.parametrize("D", [2, 52])
def test_direct_pack_and_single_factorisation(D):
    """The VBM post-stage packs the W^-1 it builds (`natural_from_stats`)
    and unpack takes log|W| from its one factorisation: the same numbers
    as the W^-1 -> W -> W^-1 round trip and `slogdet(W)` in f64, and a
    closer n2 block in f32, where the round trip loses digits to cond(W^-1)."""
    K = 3
    stats, prior = _stats_with_conditioned_winv(D, K)
    winv_block = expfam.block_labels(K, D) == expfam.BLOCK_NAMES.index("winv")

    direct = gmm.natural_from_stats(stats, prior)
    round_trip = expfam.pack_natural(gmm.posterior_from_stats(stats, prior))
    scale = float(jnp.max(jnp.abs(direct)))
    np.testing.assert_allclose(direct, round_trip, rtol=0, atol=1e-12 * scale)

    q, logdet_W = expfam.unpack_natural_logdet(direct, K, D)
    np.testing.assert_allclose(logdet_W, jnp.linalg.slogdet(q.W)[1],
                               rtol=1e-12, atol=1e-12 * D)
    cond = np.linalg.cond(np.asarray(q.W))           # = cond(W^-1)
    assert np.all((cond > 3e2) & (cond < 3e3)), cond

    s32, p32 = _f32(stats), _f32(prior)
    err_direct = np.max(np.abs(np.asarray(
        gmm.natural_from_stats(s32, p32), np.float64) - direct)[winv_block])
    err_round = np.max(np.abs(np.asarray(
        expfam.pack_natural(gmm.posterior_from_stats(s32, p32)),
        np.float64) - direct)[winv_block])
    assert err_direct < err_round, (err_direct, err_round)
