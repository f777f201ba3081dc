"""Model-zoo acceptance suite: HMM + PPCA through every stack layer.

The two PR-9 adapters (`models/hmm.py`, `models/ppca.py`) are plain
`blocks.BlockModel` compositions, so they must drop into the whole stack
with zero engine/serving special-casing:

* **golden parity** — the engine-backed runs reproduce the paper loops
  (Eqs. 27a/27b diffusion, Eqs. 38a/39/40 ADMM) written out longhand over
  `model.local_optimum`, to 1e-10;
* **every topology** — bucketed-admission padding leaves the posterior
  the same to rounding (`conftest.assert_same_posterior`) under all six
  dense topologies plus the sparse gossip/hierarchical ones;
* **both executors** — a subprocess run pins MeshExecutor == single-array;
* **streaming + SVRG** — full-batch minibatch specs reproduce the batch
  run to rounding; `control_variate="svrg"` stays finite, carries no
  anchor at full batch, and survives session split/resume bit-exactly;
* **sessions / checkpoints** — vb_init/vb_run split and ckpt round-trips
  are bit-exact;
* **serving** — mixed-capacity HMM/PPCA sessions bucket into shared
  VBService fleets, each the same to rounding as its solo run;
* **backend capability** — `backend="fused"` on a non-GMM model warns and
  falls back to the reference backend (same numbers), instead of crashing
  inside the kernel.
"""
import os
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import assert_same_posterior
from repro.checkpoint import ckpt
from repro.core import engine, network
from repro.data import stream
from repro.models import hmm as hmm_lib
from repro.models import ppca as ppca_lib
from repro.serving.vb_service import VBRequest, VBService


@pytest.fixture(autouse=True, scope="module")
def _x64():
    jax.config.update("jax_enable_x64", True)
    yield
    jax.config.update("jax_enable_x64", False)


K, D_HMM, N_NODES = 3, 2, 6
D_PPCA, Q = 5, 2


@pytest.fixture(scope="module")
def hmm_setup():
    x, mask, _, A_true, _ = hmm_lib.sample_chains(N_NODES, 10, 8, K=K,
                                                  D=D_HMM, seed=0)
    prior = hmm_lib.noninformative_prior(K, D_HMM, beta0=0.1, w0_scale=10.0)
    init_q = hmm_lib.perturbed_init(prior, jnp.asarray(x),
                                    jax.random.PRNGKey(7))
    mdl = hmm_lib.HMMModel(prior)
    adj, _ = network.random_geometric_graph(N_NODES, seed=3)
    W = network.metropolis_weights(adj)
    phi0 = jnp.broadcast_to(mdl.pack(init_q), (N_NODES, mdl.flat_dim))
    return mdl, (jnp.asarray(x), jnp.asarray(mask)), adj, W, phi0, A_true


@pytest.fixture(scope="module")
def ppca_setup():
    x, mask, W_true = ppca_lib.sample_sensors(N_NODES, 24, D=D_PPCA, Q=Q,
                                              seed=1)
    mdl = ppca_lib.PPCAModel(ppca_lib.prior(D_PPCA, Q))
    init_q = ppca_lib.perturbed_init(mdl.prior, jax.random.PRNGKey(5))
    adj, _ = network.random_geometric_graph(N_NODES, seed=3)
    W = network.metropolis_weights(adj)
    phi0 = jnp.broadcast_to(mdl.pack(init_q), (N_NODES, mdl.flat_dim))
    return mdl, (jnp.asarray(x), jnp.asarray(mask)), adj, W, phi0, W_true


# ---------------------------------------------------------------------------
# Golden parity: paper loops longhand over model.local_optimum
# ---------------------------------------------------------------------------
def _legacy_dsvb(mdl, data, W, phi0, *, n_iters, tau=0.2, d0=1.0):
    phi = phi0
    for t in range(n_iters):
        phi_star = mdl.local_optimum(data, phi, float(phi.shape[0]))
        eta = 1.0 / (d0 + tau * (t + 1.0))                       # Eq. 29
        varphi = phi + eta * (phi_star - phi)                    # Eq. 27a
        phi = W @ varphi                                         # Eq. 27b
    return phi


def _legacy_admm(mdl, data, adj, phi0, *, n_iters, rho=0.5, xi=0.05):
    deg = jnp.sum(adj, axis=1)
    phi, lam = phi0, jnp.zeros_like(phi0)
    for t in range(n_iters):
        phi_star = mdl.local_optimum(data, phi, float(phi.shape[0]))
        neigh = adj @ phi
        phi_hat = (phi_star - 2.0 * lam
                   + rho * (deg[:, None] * phi + neigh))         # Eq. 38a
        phi_hat = phi_hat / (1.0 + 2.0 * rho * deg)[:, None]
        phi_new = jax.vmap(mdl.project_to_domain)(phi_hat)       # Eq. 38b
        kappa = 1.0 - 1.0 / (1.0 + xi * (t + 1.0)) ** 2          # Eq. 40
        resid = deg[:, None] * phi_new - adj @ phi_new
        lam = lam + kappa * rho / 2.0 * resid                    # Eq. 39
        phi = phi_new
    return phi


def _parity_case(setup):
    return setup[0], setup[1], setup[2], setup[3], setup[4]


@pytest.mark.parametrize("which", ["hmm", "ppca"])
def test_diffusion_matches_legacy_loop(which, hmm_setup, ppca_setup):
    mdl, data, adj, W, phi0 = _parity_case(
        hmm_setup if which == "hmm" else ppca_setup)
    want = _legacy_dsvb(mdl, data, W, phi0, n_iters=8)
    got = engine.run_vb(mdl, data, engine.Diffusion(W), n_iters=8,
                        init_phi=phi0).phi
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-10, atol=1e-10)


@pytest.mark.parametrize("which", ["hmm", "ppca"])
def test_admm_matches_legacy_loop(which, hmm_setup, ppca_setup):
    mdl, data, adj, W, phi0 = _parity_case(
        hmm_setup if which == "hmm" else ppca_setup)
    want = _legacy_admm(mdl, data, adj, phi0, n_iters=8)
    got = engine.run_vb(mdl, data, engine.ADMMConsensus(adj), n_iters=8,
                        init_phi=phi0).phi
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-10, atol=1e-10)


# ---------------------------------------------------------------------------
# Convergence sanity on ground truth
# ---------------------------------------------------------------------------
def test_hmm_recovers_transitions():
    """Diffusion VB on sticky ground-truth chains recovers the transition
    matrix (up to the label permutation the seeds pin to identity)."""
    x, mask, _, A_true, means = hmm_lib.sample_chains(
        N_NODES, 20, 20, K=K, D=D_HMM, seed=0)
    prior = hmm_lib.noninformative_prior(K, D_HMM, beta0=0.1, w0_scale=10.0)
    mdl = hmm_lib.HMMModel(prior)
    init_q = hmm_lib.perturbed_init(prior, jnp.asarray(x),
                                    jax.random.PRNGKey(7))
    adj, _ = network.random_geometric_graph(N_NODES, seed=3)
    W = network.metropolis_weights(adj)
    phi0 = jnp.broadcast_to(mdl.pack(init_q), (N_NODES, mdl.flat_dim))
    out = engine.run_vb(mdl, (jnp.asarray(x), jnp.asarray(mask)),
                        engine.Diffusion(W), n_iters=80, init_phi=phi0)
    q = mdl.unpack(out.phi[0])
    # match estimated components to truth by emission means
    est_means = np.asarray(q.m)
    perm = [int(np.argmin(np.sum((est_means - mu) ** 2, -1)))
            for mu in means]
    assert sorted(perm) == list(range(K)), "label collapse"
    A_est = np.asarray(q.trans / jnp.sum(q.trans, -1, keepdims=True))
    assert np.max(np.abs(A_est[np.ix_(perm, perm)] - A_true)) < 0.1


def test_ppca_recovers_subspace(ppca_setup):
    mdl, data, adj, W, phi0, W_true = ppca_setup
    out = engine.run_vb(mdl, data, engine.Diffusion(W), n_iters=30,
                        init_phi=phi0)
    q = mdl.unpack(out.phi[0])
    # column spaces align: principal angles between estimated and true
    # loading subspaces are ~0
    u_est, _, _ = np.linalg.svd(np.asarray(q.m), full_matrices=False)
    u_true, _, _ = np.linalg.svd(np.asarray(W_true), full_matrices=False)
    cos = np.linalg.svd(u_est.T @ u_true, compute_uv=False)
    assert np.min(cos) > 0.99, cos


# ---------------------------------------------------------------------------
# Every topology: padding bit-invisibility (the bucketed contract)
# ---------------------------------------------------------------------------
def _dense_topologies(adj, W):
    return [
        ("fusion", engine.FusionCenter(), engine.ONE_SHOT),
        ("isolated", engine.Isolated(), engine.Schedule()),
        ("ring", engine.RingDiffusion(), engine.Schedule(tau=0.1)),
        ("diffusion", engine.Diffusion(W), engine.Schedule()),
        ("admm", engine.ADMMConsensus(adj), engine.Schedule()),
        ("admm-adaptive", engine.ADMMConsensus(adj, adaptive_rho=True),
         engine.Schedule()),
    ]


def _sparse_topologies(adj):
    g = network.SparseGraph.from_dense(adj)
    gw, rg = network.two_level_partition(N_NODES, 3, 1)
    return [
        ("gossip", engine.PairwiseGossip(g, p_activate=0.5, seed=2),
         engine.Schedule()),
        ("hierarchical", engine.HierarchicalFusion(gw, rg),
         engine.Schedule()),
    ]


@pytest.mark.parametrize("which", ["hmm", "ppca"])
def test_padding_bit_equal_every_topology(which, hmm_setup, ppca_setup):
    setup = hmm_setup if which == "hmm" else ppca_setup
    mdl, data, adj, W, phi0 = _parity_case(setup)
    cap = data[0].shape[1]
    padded = mdl.pad_to_capacity(data, cap + 5)
    assert padded[0].shape[1] == cap + 5
    assert padded[-1].shape == (N_NODES, cap + 5)
    for name, topo, sched in (_dense_topologies(adj, W)
                              + _sparse_topologies(adj)):
        a = engine.run_vb(mdl, data, topo, n_iters=6, schedule=sched,
                          init_phi=phi0)
        b = engine.run_vb(mdl, padded, topo, n_iters=6, schedule=sched,
                          init_phi=phi0)
        assert_same_posterior(b.phi, a.phi, err_msg=f"{which}/{name}")


# ---------------------------------------------------------------------------
# Streaming + SVRG
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("which", ["hmm", "ppca"])
@pytest.mark.parametrize("cv", [None, "svrg"])
def test_full_batch_spec_is_bit_identical(which, cv, hmm_setup, ppca_setup):
    """batch_size >= capacity reproduces the batch run to rounding — with
    SVRG requested too: the anchor machinery must be structurally absent
    in the degenerate case, not approximately cancelling."""
    setup = hmm_setup if which == "hmm" else ppca_setup
    mdl, data, adj, W, phi0 = _parity_case(setup)
    cap = data[0].shape[1]
    a = engine.run_vb(mdl, data, engine.Diffusion(W), n_iters=6,
                      init_phi=phi0)
    b = engine.run_vb(mdl, data, engine.Diffusion(W), n_iters=6,
                      init_phi=phi0,
                      minibatch=stream.MinibatchSpec(cap, seed=0,
                                                     control_variate=cv))
    assert_same_posterior(b.phi, a.phi)


@pytest.mark.parametrize("which", ["hmm", "ppca"])
def test_svrg_minibatch_runs_finite(which, hmm_setup, ppca_setup):
    setup = hmm_setup if which == "hmm" else ppca_setup
    mdl, data, adj, W, phi0 = _parity_case(setup)
    cap = data[0].shape[1]
    out = engine.run_vb(mdl, data, engine.Diffusion(W), n_iters=2 * cap,
                        init_phi=phi0,
                        minibatch=stream.MinibatchSpec(
                            cap // 2, seed=1, control_variate="svrg"))
    assert np.all(np.isfinite(np.asarray(out.phi)))
    assert np.all(np.isfinite(np.asarray(out.kl_nodes)))


def test_svrg_split_resume_bit_exact(hmm_setup):
    """Anchors ride in StreamState, so an SVRG session split across
    vb_run calls (crossing an epoch boundary = anchor refresh) matches
    the unsplit run bit-for-bit."""
    mdl, data, adj, W, phi0 = _parity_case(hmm_setup)
    cap = data[0].shape[1]
    spec = stream.MinibatchSpec(cap // 2, seed=3, control_variate="svrg")
    n = cap + 3      # crosses the first epoch boundary
    whole = engine.vb_init(mdl, data, engine.Diffusion(W), minibatch=spec,
                           init_phi=phi0)
    whole, _ = engine.vb_run(whole, n)
    split = engine.vb_init(mdl, data, engine.Diffusion(W), minibatch=spec,
                           init_phi=phi0)
    split, _ = engine.vb_run(split, n // 2)
    split, _ = engine.vb_run(split, n - n // 2)
    np.testing.assert_array_equal(np.asarray(whole.phi),
                                  np.asarray(split.phi))
    np.testing.assert_array_equal(np.asarray(whole.stream.anchor_phi),
                                  np.asarray(split.stream.anchor_phi))
    np.testing.assert_array_equal(np.asarray(whole.stream.anchor_full),
                                  np.asarray(split.stream.anchor_full))


def test_svrg_unknown_control_variate_rejected(hmm_setup):
    mdl, data, adj, W, _ = _parity_case(hmm_setup)
    with pytest.raises(ValueError, match="control_variate"):
        engine.vb_init(mdl, data, engine.Diffusion(W),
                       minibatch=stream.MinibatchSpec(
                           4, control_variate="saga"))


# ---------------------------------------------------------------------------
# Sessions + checkpoints
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("which", ["hmm", "ppca"])
def test_split_resume_bit_exact(which, hmm_setup, ppca_setup):
    setup = hmm_setup if which == "hmm" else ppca_setup
    mdl, data, adj, W, phi0 = _parity_case(setup)
    whole = engine.run_vb(mdl, data, engine.ADMMConsensus(adj), n_iters=8,
                          init_phi=phi0)
    s = engine.vb_init(mdl, data, engine.ADMMConsensus(adj), init_phi=phi0)
    s, _ = engine.vb_run(s, 5)
    s, _ = engine.vb_run(s, 3)
    np.testing.assert_array_equal(np.asarray(whole.phi), np.asarray(s.phi))


def test_checkpoint_roundtrip_hmm(hmm_setup, tmp_path):
    mdl, data, adj, W, phi0 = _parity_case(hmm_setup)
    cap = data[0].shape[1]
    mk = lambda: engine.vb_init(
        mdl, data, engine.Diffusion(W), init_phi=phi0,
        minibatch=stream.MinibatchSpec(cap // 2, seed=2,
                                       control_variate="svrg"))
    s = mk()
    s, _ = engine.vb_run(s, 5)
    path = os.path.join(tmp_path, "hmm.npz")
    ckpt.save(path, s)
    restored = ckpt.restore(path, mk())
    assert int(restored.t) == 5
    s, _ = engine.vb_run(s, 7)
    restored, _ = engine.vb_run(restored, 7)
    np.testing.assert_array_equal(np.asarray(s.phi),
                                  np.asarray(restored.phi))


# ---------------------------------------------------------------------------
# Serving: bucketed fleets, model-generic
# ---------------------------------------------------------------------------
def test_mixed_capacity_hmm_sessions_share_fleet(hmm_setup):
    """HMM sessions with per-node chain counts 9/10 round to one rung:
    one group, one compiled slice, each the same to rounding as its solo
    run — proving the serving stack needed zero model-specific code."""
    mdl, _, adj, W, _ = _parity_case(hmm_setup)
    datasets = []
    for i, s_chains in enumerate([9, 10]):
        x, mask, _, _, _ = hmm_lib.sample_chains(N_NODES, s_chains, 8, K=K,
                                                 D=D_HMM, seed=10 + i)
        datasets.append((jnp.asarray(x), jnp.asarray(mask)))
    topo = engine.Diffusion(W)
    svc = VBService(slice_iters=4, max_fleet=4)
    rids = [svc.submit(VBRequest(model=mdl, data=d, topology=topo,
                                 n_iters=8)) for d in datasets]
    out = svc.run()
    assert len(svc._groups) == 1 and svc.stats().compiles == 1
    for d, rid in zip(datasets, rids):
        solo = engine.run_vb(mdl, d, topo, n_iters=8)
        assert_same_posterior(out[rid].phi, solo.phi, err_msg=rid)


def test_mixed_capacity_ppca_sessions_share_fleet(ppca_setup):
    mdl, _, adj, W, phi0 = _parity_case(ppca_setup)
    datasets = []
    for i, t in enumerate([21, 29]):        # both round to rung 32
        x, mask, _ = ppca_lib.sample_sensors(N_NODES, t, D=D_PPCA, Q=Q,
                                             seed=20 + i)
        datasets.append((jnp.asarray(x), jnp.asarray(mask)))
    topo = engine.RingDiffusion()
    svc = VBService(slice_iters=4, max_fleet=4)
    rids = [svc.submit(VBRequest(model=mdl, data=d, topology=topo,
                                 n_iters=8, init_phi=phi0))
            for d in datasets]
    out = svc.run()
    assert len(svc._groups) == 1 and svc.stats().compiles == 1
    for d, rid in zip(datasets, rids):
        solo = engine.run_vb(mdl, d, topo, n_iters=8, init_phi=phi0)
        assert_same_posterior(out[rid].phi, solo.phi, err_msg=rid)


# ---------------------------------------------------------------------------
# Backend capability check
# ---------------------------------------------------------------------------
def test_fused_backend_falls_back_for_non_gmm(hmm_setup):
    """The Pallas GMM kernel cannot serve an HMM: `Backend.supports`
    catches the mismatch and the session degrades to the reference
    backend with a warning — results equal the plain run."""
    mdl, data, adj, W, phi0 = _parity_case(hmm_setup)
    plain = engine.run_vb(mdl, data, engine.Diffusion(W), n_iters=4,
                          init_phi=phi0)
    with pytest.warns(UserWarning, match="falling back to the reference"):
        fb = engine.run_vb(mdl, data, engine.Diffusion(W), n_iters=4,
                           backend="fused", init_phi=phi0)
    np.testing.assert_array_equal(np.asarray(plain.phi),
                                  np.asarray(fb.phi))


def test_gmm_fused_backend_still_supported():
    from repro.core import backends
    from repro.core import model as model_lib
    from repro.core.expfam import noninformative_prior
    mdl = model_lib.GMMModel(noninformative_prior(3, 2), 3, 2)
    assert backends.FusedBackend().supports(mdl)
    assert not backends.FusedBackend().supports(object())
    assert backends.ReferenceBackend().supports(object())


# ---------------------------------------------------------------------------
# Both executors: shard_map == single-array, whole zoo (subprocess)
# ---------------------------------------------------------------------------
CODE_ZOO_EXECUTOR_EQUIV = r"""
from repro import runtime
runtime.use_platform_precision()
import jax
import jax.numpy as jnp
from repro.core import engine, network
from repro.data import stream
from repro.models import hmm as hmm_lib
from repro.models import ppca as ppca_lib

adj, _ = network.random_geometric_graph(8, seed=3)
W = network.metropolis_weights(adj)
mesh = jax.make_mesh((4,), ("data",))
mexec = engine.MeshExecutor(mesh, "data")

x, mask, _, _, _ = hmm_lib.sample_chains(8, 8, 8, K=3, D=2, seed=0)
hmm = hmm_lib.HMMModel(
    hmm_lib.noninformative_prior(3, 2, beta0=0.1, w0_scale=10.0))
hdata = (jnp.asarray(x), jnp.asarray(mask))
hq = hmm_lib.perturbed_init(hmm.prior, jnp.asarray(x),
                            jax.random.PRNGKey(7))
hphi0 = jnp.broadcast_to(hmm.pack(hq), (8, hmm.flat_dim))

px, pmask, _ = ppca_lib.sample_sensors(8, 16, D=5, Q=2, seed=1)
ppca = ppca_lib.PPCAModel(ppca_lib.prior(5, 2))
pdata = (jnp.asarray(px), jnp.asarray(pmask))
pq = ppca_lib.perturbed_init(ppca.prior, jax.random.PRNGKey(5))
pphi0 = jnp.broadcast_to(ppca.pack(pq), (8, ppca.flat_dim))

cases = [("hmm", hmm, hdata, hphi0), ("ppca", ppca, pdata, pphi0)]
topos = [("diffusion", engine.Diffusion(W), {}),
         ("ring", engine.RingDiffusion(), {}),
         ("admm", engine.ADMMConsensus(adj), {}),
         ("fusion", engine.FusionCenter(),
          dict(schedule=engine.ONE_SHOT))]
for mname, mdl, data, phi0 in cases:
    cap = data[0].shape[1]
    for tname, topo, kw in topos:
        a = engine.run_vb(mdl, data, topo, n_iters=8, init_phi=phi0, **kw)
        b = engine.run_vb(mdl, data, topo, n_iters=8, init_phi=phi0,
                          executor=mexec, **kw)
        err = float(jnp.max(jnp.abs(a.phi - b.phi)))
        assert err < 1e-8, f"{mname}/{tname} phi err {err}"
    # streaming SVRG path through shard_map (anchor specs included)
    spec = stream.MinibatchSpec(cap // 2, seed=4, control_variate="svrg")
    a = engine.run_vb(mdl, data, engine.Diffusion(W), n_iters=cap + 2,
                      init_phi=phi0, minibatch=spec)
    b = engine.run_vb(mdl, data, engine.Diffusion(W), n_iters=cap + 2,
                      init_phi=phi0, minibatch=spec, executor=mexec)
    err = float(jnp.max(jnp.abs(a.phi - b.phi)))
    assert err < 1e-8, f"{mname}/svrg phi err {err}"
print("OK")
"""


def test_zoo_mesh_executor_matches_single_array(subproc):
    out = subproc(CODE_ZOO_EXECUTOR_EQUIV, n_devices=4)
    assert "OK" in out
