"""Golden-parity tests for the unified Model x Topology x Executor engine.

The legacy per-algorithm loops (the seed's core/algorithms.py and
core/linreg.py) are re-implemented INLINE here, straight from the paper's
equations, and the engine-backed `run_*` wrappers must reproduce them to
tight tolerance on both conjugate-exponential instances.  A subprocess test
asserts the shard_map executor matches the single-array executor through
the same step function.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import algorithms, engine, expfam, gmm, linreg, network
from repro.core import model as model_lib
from repro.data import synthetic


@pytest.fixture(autouse=True, scope="module")
def _x64():
    jax.config.update("jax_enable_x64", True)
    yield
    jax.config.update("jax_enable_x64", False)


K, D, N_NODES, N_ITERS = 3, 2, 8, 15


@pytest.fixture(scope="module")
def setup():
    data = synthetic.paper_synthetic(n_nodes=N_NODES, n_per_node=20, seed=2)
    prior = expfam.noninformative_prior(K, D, beta0=0.1, w0_scale=10.0)
    adj, _ = network.random_geometric_graph(N_NODES, seed=4)
    W = network.nearest_neighbor_weights(adj)
    init_q = algorithms._perturbed_init(prior, data.x, jax.random.PRNGKey(3))
    return data, prior, adj, W, init_q


def _legacy_init(prior, init_q, n_nodes):
    phi0 = expfam.pack_natural(init_q)
    return jnp.broadcast_to(phi0, (n_nodes,) + phi0.shape)


# ---------------------------------------------------------------------------
# GMM goldens: the seed's loops, written out longhand
# ---------------------------------------------------------------------------
def _legacy_dsvb(x, mask, weights, prior, init_q, *, n_iters, tau=0.2,
                 d0=1.0):
    n = x.shape[0]
    phi = _legacy_init(prior, init_q, n)
    for t in range(n_iters):
        phi_star = gmm.local_vbm_optimum_nodes(x, phi, prior, float(n),
                                               K, D, mask)
        eta = 1.0 / (d0 + tau * (t + 1.0))                       # Eq. 29
        varphi = phi + eta * (phi_star - phi)                    # Eq. 27a
        phi = weights @ varphi                                   # Eq. 27b
    return phi


def _legacy_admm(x, mask, adj, prior, init_q, *, n_iters, rho=0.5, xi=0.05,
                 project=True):
    n = x.shape[0]
    deg = jnp.sum(adj, axis=1)
    phi = _legacy_init(prior, init_q, n)
    lam = jnp.zeros_like(phi)
    for t in range(n_iters):
        phi_star = gmm.local_vbm_optimum_nodes(x, phi, prior, float(n),
                                               K, D, mask)
        neigh = adj @ phi
        phi_hat = (phi_star - 2.0 * lam
                   + rho * (deg[:, None] * phi + neigh))         # Eq. 38a
        phi_hat = phi_hat / (1.0 + 2.0 * rho * deg)[:, None]
        if project:                                              # Eq. 38b
            phi_new = jax.vmap(
                lambda p: expfam.project_to_domain(p, K, D))(phi_hat)
        else:
            phi_new = phi_hat
        kappa = 1.0 - 1.0 / (1.0 + xi * (t + 1.0)) ** 2          # Eq. 40
        resid = deg[:, None] * phi_new - adj @ phi_new
        lam = lam + kappa * rho / 2.0 * resid                    # Eq. 39
        phi = phi_new
    return phi


def test_dsvb_matches_legacy_loop(setup):
    data, prior, adj, W, init_q = setup
    want = _legacy_dsvb(data.x, data.mask, W, prior, init_q,
                        n_iters=N_ITERS)
    got = algorithms.run_dsvb(data.x, data.mask, W, prior, n_iters=N_ITERS,
                              K=K, D=D, init_q=init_q).phi
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-10, atol=1e-10)


def test_admm_matches_legacy_loop(setup):
    data, prior, adj, W, init_q = setup
    want = _legacy_admm(data.x, data.mask, adj, prior, init_q,
                        n_iters=N_ITERS)
    got = algorithms.run_dvb_admm(data.x, data.mask, adj, prior,
                                  n_iters=N_ITERS, K=K, D=D,
                                  init_q=init_q).phi
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-10, atol=1e-10)


def test_cvb_noncoop_nsg_match_legacy_loops(setup):
    data, prior, adj, W, init_q = setup
    n = data.x.shape[0]

    # cVB: phi <- mean_i phi*_i (Eq. 20), single shared iterate
    phi = _legacy_init(prior, init_q, n)
    for _ in range(N_ITERS):
        phi_star = gmm.local_vbm_optimum_nodes(data.x, phi, prior, float(n),
                                               K, D, data.mask)
        phi = jnp.broadcast_to(jnp.mean(phi_star, 0), phi.shape)
    got = algorithms.run_cvb(data.x, data.mask, prior, n_iters=N_ITERS,
                             K=K, D=D, init_q=init_q).phi
    np.testing.assert_allclose(np.asarray(got), np.asarray(phi),
                               rtol=1e-10, atol=1e-10)

    # noncoop: phi_i <- phi*_i with UNreplicated data
    phi = _legacy_init(prior, init_q, n)
    for _ in range(N_ITERS):
        phi = gmm.local_vbm_optimum_nodes(data.x, phi, prior, 1.0, K, D,
                                          data.mask)
    got = algorithms.run_noncoop(data.x, data.mask, prior, n_iters=N_ITERS,
                                 K=K, D=D, init_q=init_q).phi
    np.testing.assert_allclose(np.asarray(got), np.asarray(phi),
                               rtol=1e-10, atol=1e-10)

    # nsg-dVB: phi <- W phi*
    phi = _legacy_init(prior, init_q, n)
    for _ in range(N_ITERS):
        phi_star = gmm.local_vbm_optimum_nodes(data.x, phi, prior, float(n),
                                               K, D, data.mask)
        phi = W @ phi_star
    got = algorithms.run_nsg_dvb(data.x, data.mask, W, prior,
                                 n_iters=N_ITERS, K=K, D=D, init_q=init_q).phi
    np.testing.assert_allclose(np.asarray(got), np.asarray(phi),
                               rtol=1e-10, atol=1e-10)


def test_run_metrics_match_direct_engine_call(setup):
    """The wrapper's VBRun metrics == a direct engine.run_vb call."""
    data, prior, adj, W, init_q = setup
    from repro.core import refperm
    x_all, labels = data.flat
    ref = refperm.permuted_refs(gmm.ground_truth_posterior(
        x_all, labels, prior, K))
    run_w = algorithms.run_dsvb(data.x, data.mask, W, prior,
                                n_iters=N_ITERS, K=K, D=D, ref_phi=ref,
                                init_q=init_q)
    mdl = model_lib.GMMModel(prior, K, D)
    phi0 = _legacy_init(prior, init_q, data.x.shape[0])
    run_e = engine.run_vb(mdl, (data.x, data.mask), engine.Diffusion(W),
                          n_iters=N_ITERS, init_phi=phi0, ref_phi=ref)
    np.testing.assert_allclose(run_w.phi, run_e.phi, rtol=1e-12)
    np.testing.assert_allclose(run_w.kl_nodes, run_e.kl_nodes, rtol=1e-10)
    np.testing.assert_allclose(run_w.kl_mean, run_e.kl_mean, rtol=1e-10)
    assert run_e.consensus_err.shape == (N_ITERS,)
    assert bool(jnp.all(run_e.consensus_err >= 0))


# ---------------------------------------------------------------------------
# Linear-regression goldens (the seed's fixed-point consensus loops)
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def linreg_setup():
    rng = np.random.default_rng(1)
    Dl, n_nodes, ni = 3, 10, 25
    w_true = rng.normal(size=Dl)
    X = rng.normal(size=(n_nodes, ni, Dl))
    y = X @ w_true + rng.normal(size=(n_nodes, ni)) * 0.3
    X, y = jnp.asarray(X), jnp.asarray(y)
    q0 = linreg.prior(Dl)
    mask = jnp.ones((ni,), X.dtype)
    phi_star = jnp.stack([
        linreg.local_optimum(X[i], y[i], mask, q0, float(n_nodes))
        for i in range(n_nodes)])
    adj, _ = network.random_geometric_graph(n_nodes, seed=6)
    return phi_star, adj, network.nearest_neighbor_weights(adj)


def test_linreg_dsvb_matches_legacy_loop(linreg_setup):
    phi_star, adj, W = linreg_setup
    tau, d0, T = 0.1, 1.0, 50
    phi = phi_star
    for t in range(T):
        eta = 1.0 / (d0 + tau * (t + 1.0))
        varphi = phi + eta * (phi_star - phi)
        phi = W @ varphi
    got = linreg.run_dsvb(phi_star, W, n_iters=T, tau=tau)
    np.testing.assert_allclose(np.asarray(got), np.asarray(phi),
                               rtol=1e-10, atol=1e-12)


def test_linreg_admm_matches_legacy_loop(linreg_setup):
    phi_star, adj, W = linreg_setup
    rho, xi, T = 0.5, 0.05, 50
    deg = jnp.sum(adj, axis=1)
    phi, lam = phi_star, jnp.zeros_like(phi_star)
    for t in range(T):
        neigh = adj @ phi
        phi_new = (phi_star - 2.0 * lam
                   + rho * (deg[:, None] * phi + neigh))
        phi_new = phi_new / (1.0 + 2.0 * rho * deg)[:, None]
        kap = 1.0 - 1.0 / (1.0 + xi * (t + 1.0)) ** 2
        resid = deg[:, None] * phi_new - adj @ phi_new
        lam = lam + kap * rho / 2.0 * resid
        phi = phi_new
    got = linreg.run_admm(phi_star, adj, n_iters=T, rho=rho, xi=xi)
    np.testing.assert_allclose(np.asarray(got), np.asarray(phi),
                               rtol=1e-10, atol=1e-12)


def test_linreg_cvb_is_fusion_mean(linreg_setup):
    phi_star, *_ = linreg_setup
    np.testing.assert_allclose(np.asarray(linreg.run_cvb(phi_star)),
                               np.asarray(jnp.mean(phi_star, 0)), rtol=1e-14)


def test_linreg_model_from_raw_data(linreg_setup):
    """LinRegModel also accepts raw (X, y, mask) node data."""
    rng = np.random.default_rng(0)
    Dl, n_nodes, ni = 3, 6, 20
    X = jnp.asarray(rng.normal(size=(n_nodes, ni, Dl)))
    y = jnp.asarray(X @ rng.normal(size=Dl)
                    + rng.normal(size=(n_nodes, ni)) * 0.3)
    mask = jnp.ones((n_nodes, ni), X.dtype)
    q0 = linreg.prior(Dl)
    mdl = model_lib.LinRegModel(q0)
    phi_star = mdl.local_optimum((X, y, mask), None, float(n_nodes))
    want = jnp.stack([
        linreg.local_optimum(X[i], y[i], mask[i], q0, float(n_nodes))
        for i in range(n_nodes)])
    np.testing.assert_allclose(np.asarray(phi_star), np.asarray(want),
                               rtol=1e-12)


# ---------------------------------------------------------------------------
# Sharded executor == single-array executor (same step function)
# ---------------------------------------------------------------------------
CODE_EXECUTOR_EQUIV = r"""
from repro import runtime
runtime.use_platform_precision()
import jax
from repro.core import expfam
import jax.numpy as jnp
from repro.core import engine, network
from repro.core import model as model_lib
from repro.data import synthetic

data = synthetic.paper_synthetic(n_nodes=8, n_per_node=30, seed=9)
K, D = 3, 2
prior = expfam.noninformative_prior(K, D, beta0=0.1, w0_scale=10.0)
adj, _ = network.random_geometric_graph(8, seed=5)
W = network.nearest_neighbor_weights(adj)
mesh = jax.make_mesh((4,), ("data",))
mdl = model_lib.GMMModel(prior, K, D)
mexec = engine.MeshExecutor(mesh, "data")

for name, topo, kw in [
    ("diffusion", engine.Diffusion(W), dict(schedule=engine.Schedule())),
    ("ring", engine.RingDiffusion(), dict(schedule=engine.Schedule())),
    ("admm", engine.ADMMConsensus(adj), {}),
    ("admm-adaptive", engine.ADMMConsensus(adj, adaptive_rho=True), {}),
    ("admm-adaptive-pb",
     engine.ADMMConsensus(adj, adaptive_rho=True, per_block=True), {}),
    ("fusion", engine.FusionCenter(), dict(schedule=engine.ONE_SHOT)),
]:
    a = engine.run_vb(mdl, (data.x, data.mask), topo, n_iters=25, **kw)
    b = engine.run_vb(mdl, (data.x, data.mask), topo, n_iters=25,
                      executor=mexec, **kw)
    err = float(jnp.max(jnp.abs(a.phi - b.phi)))
    assert err < 1e-8, f"{name} phi err {err}"
    cerr = float(jnp.max(jnp.abs(a.consensus_err - b.consensus_err)))
    assert cerr < 1e-8, f"{name} consensus err {cerr}"
    if a.consensus_diag is not None:
        for f in engine.ConsensusDiagnostics._fields:
            da = getattr(a.consensus_diag, f).astype(jnp.float64)
            db = getattr(b.consensus_diag, f).astype(jnp.float64)
            derr = float(jnp.max(jnp.abs(da - db)))
            assert derr < 1e-8, f"{name} diag {f} err {derr}"
print("OK")
"""


def test_mesh_executor_matches_single_array(subproc):
    out = subproc(CODE_EXECUTOR_EQUIV, n_devices=4)
    assert "OK" in out
