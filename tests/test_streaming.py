"""Streaming minibatch stochastic VB + time-varying networks.

Pins the two contracts of the streaming subsystem (data/stream.py +
engine.run_vb(minibatch=) + the topologies' link schedules):

1. *Full-batch degeneracy*: `MinibatchSpec(batch_size = n_per_node)`
   reproduces the full-batch run to rounding
   (`conftest.assert_same_posterior`) on all five estimators and both
   executors.
2. *Minibatch natural gradients are unbiased*: the GMM natural parameters
   are linear in the sufficient statistics, and the scaled-mask rescaling
   (n_i/|B_i|) makes the minibatch statistics unbiased, so the
   seed-averaged minibatch phi* must converge to the full-batch phi*.

Plus the time-varying-network laws: all-links-down == Isolated,
keep-everything == static, determinism in the link seed, effective
connectivity observable via ConsensusDiagnostics.link_frac, and
executor equivalence with both features on.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import assert_same_posterior
from repro.core import algorithms, engine, expfam, linreg, network
from repro.core import model as model_lib
from repro.data import stream, synthetic


@pytest.fixture(autouse=True, scope="module")
def _x64():
    jax.config.update("jax_enable_x64", True)
    yield
    jax.config.update("jax_enable_x64", False)


K, D, N_NODES, N_PER, N_ITERS = 3, 2, 8, 20, 15


@pytest.fixture(scope="module")
def setup():
    data = synthetic.paper_synthetic(n_nodes=N_NODES, n_per_node=N_PER,
                                     seed=2)
    prior = expfam.noninformative_prior(K, D, beta0=0.1, w0_scale=10.0)
    adj, _ = network.random_geometric_graph(N_NODES, seed=4)
    W = network.nearest_neighbor_weights(adj)
    init_q = algorithms._perturbed_init(prior, data.x, jax.random.PRNGKey(3))
    phi0 = jnp.broadcast_to(expfam.pack_natural(init_q),
                            (N_NODES, expfam.flat_dim(K, D)))
    mdl = model_lib.GMMModel(prior, K, D)
    return data, prior, adj, W, phi0, mdl


def _estimators(adj, W):
    return [
        ("cvb", engine.FusionCenter(), dict(schedule=engine.ONE_SHOT)),
        ("noncoop", engine.Isolated(),
         dict(schedule=engine.ONE_SHOT, replication=1.0)),
        ("nsg_dvb", engine.Diffusion(W), dict(schedule=engine.ONE_SHOT)),
        ("dsvb", engine.Diffusion(W), dict(schedule=engine.Schedule())),
        ("dvb_admm", engine.ADMMConsensus(adj), {}),
    ]


# ---------------------------------------------------------------------------
# 1. full-batch degeneracy
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("est", ["cvb", "noncoop", "nsg_dvb", "dsvb",
                                 "dvb_admm"])
def test_full_batch_spec_is_bit_identical(setup, est):
    data, prior, adj, W, phi0, mdl = setup
    name, topo, kw = next(e for e in _estimators(adj, W) if e[0] == est)
    a = engine.run_vb(mdl, (data.x, data.mask), topo, n_iters=N_ITERS,
                      init_phi=phi0, **kw)
    b = engine.run_vb(mdl, (data.x, data.mask), topo, n_iters=N_ITERS,
                      init_phi=phi0,
                      minibatch=stream.MinibatchSpec(batch_size=N_PER),
                      **kw)
    assert_same_posterior(b.phi, a.phi)
    assert_same_posterior(b.kl_nodes, a.kl_nodes)


def test_full_batch_degeneracy_is_identity_gather(setup):
    """With batch_size covering the node, the sorted selection is the
    identity permutation and the scaled mask IS the base mask."""
    data, *_ = setup
    keys = stream.node_keys(N_NODES, seed=7)
    idx, mb = stream.minibatch_select(keys, data.mask, jnp.asarray(5),
                                      N_PER)
    np.testing.assert_array_equal(
        np.asarray(idx), np.broadcast_to(np.arange(N_PER), (N_NODES, N_PER)))
    np.testing.assert_array_equal(np.asarray(mb), np.asarray(data.mask))


# ---------------------------------------------------------------------------
# 2. unbiasedness of the stochastic natural-gradient direction
# ---------------------------------------------------------------------------
def test_minibatch_phi_star_is_unbiased(setup):
    """E_seeds[phi*_minibatch] -> phi*_full: the natural parameters are
    linear in the sufficient statistics and the n_i/|B| mask scaling makes
    the statistics unbiased under without-replacement sampling.  The
    seed-averaged deviation must sit inside the Monte-Carlo confidence
    band (5 standard errors) on EVERY coordinate — a missing or wrong
    rescale (e.g. forgetting n_i/|B|) shifts coordinates by O(phi*),
    hundreds of standard errors."""
    data, prior, adj, W, phi0, mdl = setup
    rep = float(N_NODES)
    full = np.asarray(mdl.local_optimum((data.x, data.mask), phi0, rep))

    B, n_seeds = 10, 400
    acc = jnp.zeros_like(jnp.asarray(full))
    acc2 = jnp.zeros_like(acc)

    @jax.jit
    def one(seed):
        keys = stream.node_keys(N_NODES, seed)
        idx, mb = stream.minibatch_select(keys, data.mask, jnp.asarray(0), B)
        data_t = mdl.take_minibatch((data.x, data.mask), idx, mb)
        return mdl.local_optimum(data_t, phi0, rep)

    for s in range(n_seeds):
        p = one(s)
        acc = acc + p
        acc2 = acc2 + p * p
    mean_mb = np.asarray(acc) / n_seeds
    var = np.maximum(np.asarray(acc2) / n_seeds - mean_mb ** 2, 0.0)
    se = np.sqrt(var / n_seeds)
    dev = np.abs(mean_mb - full)
    # 5-sigma band, plus a tiny absolute floor for zero-variance coords
    assert np.all(dev <= 5.0 * se + 1e-9 * (np.abs(full) + 1.0)), \
        float(np.max(dev / (se + 1e-12)))
    # sanity: the band itself is tight relative to phi* on average, so the
    # check above has teeth
    assert np.median(se / (np.abs(full) + 1.0)) < 0.05


def test_selection_scaling_on_ragged_nodes():
    """Ragged nodes under epoch reshuffling: windows of one epoch cover
    the sample slots (exactly once when B divides the capacity T), indices
    are sorted, and every selected valid point carries the constant
    unbiased weight T/B — a slot lands in a window with probability B/T,
    so T/B is the importance weight that keeps the statistics unbiased
    even for windows that hit few (or zero) valid points."""
    n, T, B = 5, 12, 4
    n_chunks = T // B
    mask = np.zeros((n, T))
    n_valid = [3, 8, 12, 1, 10]
    for i, v in enumerate(n_valid):
        mask[i, :v] = 1.0
    mask = jnp.asarray(mask)
    keys = stream.node_keys(n, seed=1)
    seen = [[] for _ in range(n)]
    for t in range(n_chunks):                     # one full epoch
        idx, mb = stream.minibatch_select(keys, mask, jnp.asarray(t), B)
        assert idx.shape == (n, B) and mb.shape == (n, B)
        mb_np, idx_np = np.asarray(mb), np.asarray(idx)
        for i, v in enumerate(n_valid):
            assert (np.diff(idx_np[i]) >= 0).all()          # sorted
            picked = idx_np[i][mb_np[i] > 0]
            assert (picked < v).all()                       # valid only
            np.testing.assert_allclose(mb_np[i][mb_np[i] > 0], T / B)
            seen[i].extend(idx_np[i].tolist())
    for i, v in enumerate(n_valid):                # epoch = exact cover
        assert sorted(seen[i]) == list(range(T))
    # B does not divide T: the wrapped windows still cover every slot at
    # least once per epoch (overlap instead of a silently-dropped tail)
    B2 = 5
    n_chunks2 = -(-T // B2)
    seen2 = set()
    for t in range(n_chunks2):
        idx, _ = stream.minibatch_select(keys, mask, jnp.asarray(t), B2)
        seen2.update(np.asarray(idx)[0].tolist())
    assert seen2 == set(range(T))


def test_minibatch_determinism_and_variation(setup):
    data, prior, adj, W, phi0, mdl = setup
    spec = stream.MinibatchSpec(batch_size=6, seed=11)
    kw = dict(n_iters=N_ITERS, init_phi=phi0, schedule=engine.Schedule())
    a = engine.run_vb(mdl, (data.x, data.mask), engine.Diffusion(W),
                      minibatch=spec, **kw)
    b = engine.run_vb(mdl, (data.x, data.mask), engine.Diffusion(W),
                      minibatch=spec, **kw)
    np.testing.assert_array_equal(np.asarray(a.phi), np.asarray(b.phi))
    c = engine.run_vb(mdl, (data.x, data.mask), engine.Diffusion(W),
                      minibatch=stream.MinibatchSpec(batch_size=6, seed=12),
                      **kw)
    assert float(jnp.max(jnp.abs(a.phi - c.phi))) > 0.0
    # successive iterations draw different batches
    keys = stream.node_keys(N_NODES, 11)
    i0, _ = stream.minibatch_select(keys, data.mask, jnp.asarray(0), 6)
    i1, _ = stream.minibatch_select(keys, data.mask, jnp.asarray(1), 6)
    assert bool(jnp.any(i0 != i1))


def test_minibatch_api_validation(setup):
    data, prior, adj, W, phi0, mdl = setup
    with pytest.raises(ValueError, match="batch_size"):
        engine.run_vb(mdl, (data.x, data.mask), engine.Diffusion(W),
                      n_iters=2, minibatch=stream.MinibatchSpec(0))
    # LinRegModel streams raw data but refuses a precomputed phi* stack
    lr = model_lib.LinRegModel(linreg.prior(2))
    phi_star = jnp.stack([lr.init_phi() + 1.0, lr.init_phi() - 1.0])
    with pytest.raises(ValueError, match="phi\\* stack"):
        engine.run_vb(lr, phi_star, engine.FusionCenter(), n_iters=2,
                      schedule=engine.ONE_SHOT,
                      minibatch=stream.MinibatchSpec(4))


def test_linreg_streaming_full_batch_parity():
    rng = np.random.default_rng(1)
    Dl, n, ni = 3, 6, 15
    X = jnp.asarray(rng.normal(size=(n, ni, Dl)))
    y = jnp.asarray(X @ rng.normal(size=Dl) + rng.normal(size=(n, ni)) * 0.3)
    mask = jnp.ones((n, ni))
    lr = model_lib.LinRegModel(linreg.prior(Dl))
    a = engine.run_vb(lr, (X, y, mask), engine.FusionCenter(), n_iters=5,
                      schedule=engine.ONE_SHOT)
    b = engine.run_vb(lr, (X, y, mask), engine.FusionCenter(), n_iters=5,
                      schedule=engine.ONE_SHOT,
                      minibatch=stream.MinibatchSpec(batch_size=ni))
    assert_same_posterior(b.phi, a.phi)
    c = engine.run_vb(lr, (X, y, mask), engine.FusionCenter(), n_iters=5,
                      schedule=engine.ONE_SHOT,
                      minibatch=stream.MinibatchSpec(batch_size=5))
    assert bool(jnp.all(jnp.isfinite(c.phi)))


# ---------------------------------------------------------------------------
# 3. time-varying networks
# ---------------------------------------------------------------------------
def test_all_links_down_is_isolated(setup):
    """link_drop=1.0 (or an identity keep mask) disconnects everyone:
    diffusion renormalises to the identity combine, ADMM's neighbour sums
    and degrees vanish."""
    data, prior, adj, W, phi0, mdl = setup
    kw = dict(n_iters=N_ITERS, init_phi=phi0, schedule=engine.Schedule())
    iso = engine.run_vb(mdl, (data.x, data.mask), engine.Isolated(), **kw)
    dead = engine.run_vb(mdl, (data.x, data.mask),
                         engine.Diffusion(W, link_drop=1.0), **kw)
    np.testing.assert_allclose(np.asarray(dead.phi), np.asarray(iso.phi),
                               rtol=1e-12, atol=1e-12)
    ring_dead = engine.run_vb(
        mdl, (data.x, data.mask),
        engine.RingDiffusion(link_mask_fn=lambda t: jnp.zeros(N_NODES)),
        **kw)
    np.testing.assert_allclose(np.asarray(ring_dead.phi),
                               np.asarray(iso.phi), rtol=1e-12, atol=1e-12)
    admm_dead = engine.run_vb(mdl, (data.x, data.mask),
                              engine.ADMMConsensus(adj, link_drop=1.0),
                              n_iters=N_ITERS, init_phi=phi0)
    assert bool(jnp.all(admm_dead.consensus_diag.link_frac == 0.0))
    assert bool(jnp.all(jnp.isfinite(admm_dead.phi)))


def test_keep_everything_matches_static(setup):
    data, prior, adj, W, phi0, mdl = setup
    n = N_NODES
    kw = dict(n_iters=N_ITERS, init_phi=phi0, schedule=engine.Schedule())
    static = engine.run_vb(mdl, (data.x, data.mask), engine.Diffusion(W),
                           **kw)
    keep_all = engine.run_vb(
        mdl, (data.x, data.mask),
        engine.Diffusion(W, link_mask_fn=lambda t: jnp.ones((n, n))), **kw)
    np.testing.assert_allclose(np.asarray(keep_all.phi),
                               np.asarray(static.phi), rtol=1e-12)
    ring_static = engine.run_vb(mdl, (data.x, data.mask),
                                engine.RingDiffusion(), **kw)
    ring_keep = engine.run_vb(
        mdl, (data.x, data.mask),
        engine.RingDiffusion(link_mask_fn=lambda t: jnp.ones(n)), **kw)
    np.testing.assert_allclose(np.asarray(ring_keep.phi),
                               np.asarray(ring_static.phi), rtol=1e-12)
    admm_static = engine.run_vb(mdl, (data.x, data.mask),
                                engine.ADMMConsensus(adj), n_iters=N_ITERS,
                                init_phi=phi0)
    admm_keep = engine.run_vb(
        mdl, (data.x, data.mask),
        engine.ADMMConsensus(adj, link_mask_fn=lambda t: jnp.ones((n, n))),
        n_iters=N_ITERS, init_phi=phi0)
    np.testing.assert_allclose(np.asarray(admm_keep.phi),
                               np.asarray(admm_static.phi), rtol=1e-12)


def test_link_drop_determinism_and_diagnostics(setup):
    data, prior, adj, W, phi0, mdl = setup
    topo = lambda: engine.ADMMConsensus(adj, link_drop=0.3, link_seed=5)
    a = engine.run_vb(mdl, (data.x, data.mask), topo(), n_iters=25,
                      init_phi=phi0)
    b = engine.run_vb(mdl, (data.x, data.mask), topo(), n_iters=25,
                      init_phi=phi0)
    np.testing.assert_array_equal(np.asarray(a.phi), np.asarray(b.phi))
    lf = np.asarray(a.consensus_diag.link_frac)
    assert lf.shape == (25,)
    assert (lf >= 0.0).all() and (lf <= 1.0).all()
    assert 0.45 < lf.mean() < 0.9        # ~70% of links up on average
    assert lf.std() > 0.0                # genuinely time-varying
    c = engine.run_vb(mdl, (data.x, data.mask),
                      engine.ADMMConsensus(adj, link_drop=0.3, link_seed=6),
                      n_iters=25, init_phi=phi0)
    assert float(jnp.max(jnp.abs(a.phi - c.phi))) > 0.0


def test_link_keep_matrix_properties():
    key = jax.random.PRNGKey(0)
    keep = network.link_keep_matrix(key, jnp.asarray(3), 10, 0.4)
    keep_np = np.asarray(keep)
    np.testing.assert_array_equal(keep_np, keep_np.T)       # symmetric
    np.testing.assert_array_equal(np.diag(keep_np), 1.0)    # self always up
    assert set(np.unique(keep_np)) <= {0.0, 1.0}
    # deterministic in (key, t); varies across t
    again = network.link_keep_matrix(key, jnp.asarray(3), 10, 0.4)
    np.testing.assert_array_equal(np.asarray(again), keep_np)
    other = network.link_keep_matrix(key, jnp.asarray(4), 10, 0.4)
    assert (np.asarray(other) != keep_np).any()


def test_link_schedule_validation():
    with pytest.raises(ValueError, match="not both"):
        engine.RingDiffusion(link_drop=0.5, link_mask_fn=lambda t: None)
    with pytest.raises(ValueError, match="probability"):
        engine.ADMMConsensus(jnp.eye(2), link_drop=1.5)


# ---------------------------------------------------------------------------
# 4. executor equivalence with streaming + failing links on
# ---------------------------------------------------------------------------
CODE_STREAMING_EXEC = r"""
from repro import runtime
runtime.use_platform_precision()
import jax
import jax.numpy as jnp
import numpy as np
from repro.core import engine, expfam, network
from repro.core import model as model_lib
from repro.data import stream, synthetic

data = synthetic.paper_synthetic(n_nodes=8, n_per_node=24, seed=9)
K, D = 3, 2
prior = expfam.noninformative_prior(K, D, beta0=0.1, w0_scale=10.0)
adj, _ = network.random_geometric_graph(8, seed=5)
W = network.nearest_neighbor_weights(adj)
mesh = jax.make_mesh((4,), ("data",))
mexec = engine.MeshExecutor(mesh, "data")
mdl = model_lib.GMMModel(prior, K, D)
mb = stream.MinibatchSpec(batch_size=8, seed=3)

# the full-batch degeneracy holds under the mesh executor too
# (conftest.assert_same_posterior, inlined)
full = engine.run_vb(mdl, (data.x, data.mask), engine.Diffusion(W),
                     n_iters=15, executor=mexec)
fb = engine.run_vb(mdl, (data.x, data.mask), engine.Diffusion(W),
                   n_iters=15, executor=mexec,
                   minibatch=stream.MinibatchSpec(batch_size=24))
gap = float(jnp.max(jnp.abs(fb.phi - full.phi)))
assert gap <= 1e-12 * float(jnp.max(jnp.abs(full.phi))), \
    f"mesh full-batch spec gap {gap}"

# ADMM runs with the Eq. 38b projection use SHORT horizons: the eigen-clip
# is a discontinuous branch, and once the noisy streaming trajectory grazes
# it, the executors' inherent ulp-level reassociation differences flip the
# branch and the trajectories split chaotically (pre-existing sensitivity,
# not a layout bug — the projection-free ADMM below runs the full horizon).
for name, topo, n_it, kw in [
    ("dsvb-mb", engine.Diffusion(W), 20, dict(schedule=engine.Schedule())),
    ("dsvb-mb-drop", engine.Diffusion(W, link_drop=0.3, link_seed=2), 20,
     dict(schedule=engine.Schedule())),
    ("ring-mb-drop", engine.RingDiffusion(link_drop=0.3, link_seed=2), 20,
     dict(schedule=engine.Schedule())),
    ("admm-mb-drop", engine.ADMMConsensus(adj, link_drop=0.3, link_seed=2),
     8, {}),
    ("admm-mb-drop-noproj",
     engine.ADMMConsensus(adj, link_drop=0.3, link_seed=2, project=False),
     25, {}),
    ("admm-adaptive-mb-drop",
     engine.ADMMConsensus(adj, adaptive_rho=True, link_drop=0.3,
                          link_seed=2), 8, {}),
]:
    a = engine.run_vb(mdl, (data.x, data.mask), topo, n_iters=n_it,
                      minibatch=mb, **kw)
    b = engine.run_vb(mdl, (data.x, data.mask), topo, n_iters=n_it,
                      minibatch=mb, executor=mexec, **kw)
    err = float(jnp.max(jnp.abs(a.phi - b.phi)))
    assert err < 1e-8, f"{name} phi err {err}"
    if a.consensus_diag is not None:
        for f in engine.ConsensusDiagnostics._fields:
            da = getattr(a.consensus_diag, f).astype(jnp.float64)
            db = getattr(b.consensus_diag, f).astype(jnp.float64)
            derr = float(jnp.max(jnp.abs(da - db)))
            assert derr < 1e-8, f"{name} diag {f} err {derr}"
print("OK")
"""


def test_streaming_executor_equivalence(subproc):
    out = subproc(CODE_STREAMING_EXEC, n_devices=4)
    assert "OK" in out
