"""Dense/sparse topology parity + the sparse-scale contracts.

The dense (N, N) path is the parity ORACLE: every sparse edge-list
combine (`Diffusion`/`RingDiffusion`/`ADMMConsensus` over
`network.SparseGraph`) must reproduce it to <= 1e-9 at N=50 in f64
across all five paper estimators and both executors; the fused Pallas
backend is f32-only so its bar is the KL-trajectory rtol<=1e-4
convention of tests/test_backends.py.  The new scenario topologies pin
their anchor limits (gossip with every edge active == dense diffusion;
a single-region hierarchy with zero self/gateway weight == fusion
centre) and the absolute-t resume contract
(vb_run(s, a+b) == vb_run(vb_run(s, a), b), bit-exact).  Finally the
scale contract itself: the sparse combine must lower WITHOUT any (N, N)
intermediate, and the 10k-node geometric builder must connect in
bounded attempts.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine, expfam, gmm, network, refperm
from repro.core import model as model_lib
from repro.data import synthetic
from repro.serving.vb_service import VBRequest, VBService


@pytest.fixture(autouse=True, scope="module")
def _x64():
    jax.config.update("jax_enable_x64", True)
    yield
    jax.config.update("jax_enable_x64", False)


K, D, N = 3, 2, 50
N_ITERS = 25
TOL = 1e-9                 # the dense-oracle bar (f64, reference backend)


@pytest.fixture(scope="module")
def setup():
    data = synthetic.paper_synthetic(n_nodes=N, n_per_node=20, seed=2)
    prior = expfam.noninformative_prior(K, D, beta0=0.1, w0_scale=10.0)
    adj, _ = network.random_geometric_graph(N, seed=4)
    g = network.SparseGraph.from_dense(np.asarray(adj))
    mdl = model_lib.GMMModel(prior, K, D)
    return data, mdl, np.asarray(adj, np.float64), g


def _estimators(adj, g):
    """The five paper estimators as (name, dense topology, sparse
    topology, run_vb kwargs).  cvb/noncoop have no graph — dense ==
    sparse there by construction, kept so the suite literally covers
    all five."""
    W = network.nearest_neighbor_weights(jnp.asarray(adj))
    sw = network.sparse_nearest_neighbor_weights(g)
    return [
        ("cvb", engine.FusionCenter(), engine.FusionCenter(),
         dict(schedule=engine.ONE_SHOT)),
        ("noncoop", engine.Isolated(), engine.Isolated(),
         dict(schedule=engine.ONE_SHOT, replication=1.0)),
        ("nsg_dvb", engine.Diffusion(W), engine.Diffusion(sw),
         dict(schedule=engine.ONE_SHOT)),
        ("dsvb", engine.Diffusion(W), engine.Diffusion(sw),
         dict(schedule=engine.Schedule())),
        ("dvb_admm", engine.ADMMConsensus(jnp.asarray(adj)),
         engine.ADMMConsensus(g), {}),
    ]


ESTIMATORS = ["cvb", "noncoop", "nsg_dvb", "dsvb", "dvb_admm"]


@pytest.mark.parametrize("est", ESTIMATORS)
def test_sparse_matches_dense_oracle(setup, est):
    data, mdl, adj, g = setup
    _, dense, sparse, kw = next(e for e in _estimators(adj, g)
                                if e[0] == est)
    a = engine.run_vb(mdl, (data.x, data.mask), dense,
                      n_iters=N_ITERS, **kw)
    b = engine.run_vb(mdl, (data.x, data.mask), sparse,
                      n_iters=N_ITERS, **kw)
    np.testing.assert_allclose(np.asarray(b.phi), np.asarray(a.phi),
                               rtol=TOL, atol=TOL)


def test_sparse_matches_dense_metropolis_and_adaptive(setup):
    """The weight variants not in the 5-estimator list: Metropolis
    diffusion and the adaptive-rho ADMM subsystem."""
    data, mdl, adj, g = setup
    pairs = [
        (engine.Diffusion(network.metropolis_weights(jnp.asarray(adj))),
         engine.Diffusion(network.sparse_metropolis_weights(g)),
         dict(schedule=engine.Schedule())),
        (engine.ADMMConsensus(jnp.asarray(adj), adaptive_rho=True,
                              per_block=True),
         engine.ADMMConsensus(g, adaptive_rho=True, per_block=True), {}),
    ]
    for dense, sparse, kw in pairs:
        a = engine.run_vb(mdl, (data.x, data.mask), dense,
                          n_iters=N_ITERS, **kw)
        b = engine.run_vb(mdl, (data.x, data.mask), sparse,
                          n_iters=N_ITERS, **kw)
        np.testing.assert_allclose(np.asarray(b.phi), np.asarray(a.phi),
                                   rtol=TOL, atol=TOL)


def test_ring_sparse_matches_dense_with_link_drop(setup):
    """SparseGraph.ring orders link k as (k, k+1 mod N) — the
    ring_link_keep coin order — so the edge-list ring replays the
    IDENTICAL failure sequence as the roll-based ring, not just the
    same distribution."""
    data, mdl, _, _ = setup
    for drop in (0.0, 0.4):
        a = engine.run_vb(mdl, (data.x, data.mask),
                          engine.RingDiffusion(link_drop=drop, link_seed=3),
                          n_iters=N_ITERS, schedule=engine.Schedule())
        b = engine.run_vb(mdl, (data.x, data.mask),
                          engine.RingDiffusion(
                              graph=network.SparseGraph.ring(N),
                              link_drop=drop, link_seed=3),
                          n_iters=N_ITERS, schedule=engine.Schedule())
        np.testing.assert_allclose(np.asarray(b.phi), np.asarray(a.phi),
                                   rtol=TOL, atol=TOL)


def test_gossip_all_edges_active_is_dense_diffusion(setup):
    """PairwiseGossip with p_activate=1 averages over the FULL
    neighbourhood with Eq. 47 weights == dense nearest-neighbour
    Diffusion on the same graph."""
    data, mdl, adj, g = setup
    W = network.nearest_neighbor_weights(jnp.asarray(adj))
    a = engine.run_vb(mdl, (data.x, data.mask), engine.Diffusion(W),
                      n_iters=N_ITERS, schedule=engine.Schedule())
    b = engine.run_vb(mdl, (data.x, data.mask),
                      engine.PairwiseGossip(g, p_activate=1.0),
                      n_iters=N_ITERS, schedule=engine.Schedule())
    np.testing.assert_allclose(np.asarray(b.phi), np.asarray(a.phi),
                               rtol=TOL, atol=TOL)


def test_hierarchy_degenerates_to_fusion_center(setup):
    """One region, w_self = w_gateway = 0: every node gets the global
    mean — exactly FusionCenter."""
    data, mdl, _, _ = setup
    gw, rg = network.two_level_partition(N, 1, 1)
    a = engine.run_vb(mdl, (data.x, data.mask), engine.FusionCenter(),
                      n_iters=N_ITERS, schedule=engine.ONE_SHOT)
    b = engine.run_vb(mdl, (data.x, data.mask),
                      engine.HierarchicalFusion(gw, rg, w_self=0.0,
                                                w_gateway=0.0),
                      n_iters=N_ITERS, schedule=engine.ONE_SHOT)
    np.testing.assert_allclose(np.asarray(b.phi), np.asarray(a.phi),
                               rtol=TOL, atol=TOL)


def test_gossip_contracts_disagreement(setup):
    """Repeated randomized gossip averaging reaches consensus (the
    mechanism behind the combine), and — every row being a convex
    combination of the active neighbourhood — the consensus point stays
    inside the convex hull of the starting iterates."""
    _, _, _, g = setup
    topo = engine.PairwiseGossip(g, p_activate=0.3, seed=5)
    x0 = np.random.default_rng(0).normal(size=(N, 5))
    x = jnp.asarray(x0)
    for t in range(600):
        x = topo.combine(x, t=t)
    x = np.asarray(x)
    assert np.abs(x - x.mean(0, keepdims=True)).max() < 1e-5
    # every combine row is a convex combination, so the consensus value
    # must lie inside the convex hull of the starting iterates
    assert np.all(x.min(0) >= x0.min(0) - 1e-9)
    assert np.all(x.max(0) <= x0.max(0) + 1e-9)


# ---------------------------------------------------------------------------
# Absolute-t resume contract: bit-exact split/resume for the new
# topologies (gossip activation + link schedules key on VBState.t)
# ---------------------------------------------------------------------------
def _bitequal(a, b, what):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb), what
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=what)


def test_split_resume_bitexact_new_topologies(setup):
    data, mdl, adj, g = setup
    sw = network.sparse_nearest_neighbor_weights(g)
    gw, rg = network.two_level_partition(N, 8, 2)
    a, b = 17, 23
    for name, topo_fn in [
        ("gossip", lambda: engine.PairwiseGossip(g, p_activate=0.4,
                                                 seed=11)),
        ("hier", lambda: engine.HierarchicalFusion(gw, rg)),
        ("sparse-diffusion-drop",
         lambda: engine.Diffusion(sw, link_drop=0.3, link_seed=7)),
        ("sparse-admm-drop",
         lambda: engine.ADMMConsensus(g, adaptive_rho=True,
                                      link_drop=0.2)),
    ]:
        full = engine.vb_init(mdl, (data.x, data.mask), topo_fn(),
                              schedule=engine.Schedule())
        full, _ = engine.vb_run(full, a + b)
        split = engine.vb_init(mdl, (data.x, data.mask), topo_fn(),
                               schedule=engine.Schedule())
        split, _ = engine.vb_run(split, a)
        split, _ = engine.vb_run(split, b)
        _bitequal(full.phi, split.phi, f"{name}: phi")
        _bitequal(full.carry, split.carry, f"{name}: carry")


# ---------------------------------------------------------------------------
# Mesh executor: sparse combines under shard_map == single-array
# ---------------------------------------------------------------------------
CODE_MESH_SPARSE = r"""
from repro import runtime
runtime.use_platform_precision()
import jax
from repro.core import expfam
import numpy as np, jax.numpy as jnp
from repro.core import engine, network
from repro.core import model as model_lib
from repro.data import synthetic

N = 50
data = synthetic.paper_synthetic(n_nodes=N, n_per_node=20, seed=2)
prior = expfam.noninformative_prior(3, 2, beta0=0.1, w0_scale=10.0)
mdl = model_lib.GMMModel(prior, 3, 2)
adj, _ = network.random_geometric_graph(N, seed=4)
g = network.SparseGraph.from_dense(np.asarray(adj))
sw = network.sparse_nearest_neighbor_weights(g)
gw, rg = network.two_level_partition(N, 8, 2)
mesh = jax.make_mesh((2,), ("data",))
mexec = engine.MeshExecutor(mesh, "data")

for name, topo, kw in [
    ("sparse-diffusion", engine.Diffusion(sw),
     dict(schedule=engine.Schedule())),
    ("sparse-diffusion-drop", engine.Diffusion(sw, link_drop=0.3,
                                               link_seed=7),
     dict(schedule=engine.Schedule())),
    ("sparse-ring", engine.RingDiffusion(
        graph=network.SparseGraph.ring(N), link_drop=0.2),
     dict(schedule=engine.Schedule())),
    ("sparse-admm", engine.ADMMConsensus(g, adaptive_rho=True,
                                         per_block=True), {}),
    ("gossip", engine.PairwiseGossip(g, p_activate=0.4, seed=5),
     dict(schedule=engine.Schedule())),
    ("hier", engine.HierarchicalFusion(gw, rg),
     dict(schedule=engine.Schedule())),
]:
    a = engine.run_vb(mdl, (data.x, data.mask), topo, n_iters=20, **kw)
    b = engine.run_vb(mdl, (data.x, data.mask), topo, n_iters=20,
                      executor=mexec, **kw)
    np.testing.assert_allclose(np.asarray(b.phi), np.asarray(a.phi),
                               rtol=1e-9, atol=1e-9, err_msg=name)
print("OK")
"""


def test_mesh_executor_matches_single_array_sparse(subproc):
    out = subproc(CODE_MESH_SPARSE, n_devices=2)
    assert "OK" in out


# ---------------------------------------------------------------------------
# Fused Pallas backend on sparse topologies (f32; the kernel owns the
# estep, the combine is dtype-generic) — tests/test_backends.py bar
# ---------------------------------------------------------------------------
def test_fused_backend_sparse_parity():
    jax.config.update("jax_enable_x64", False)
    try:
        data = synthetic.paper_synthetic(n_nodes=16, n_per_node=30, seed=9,
                                         dtype=np.float32)
        prior = expfam.noninformative_prior(K, D, beta0=0.1, w0_scale=10.0,
                                            dtype=jnp.float32)
        mdl = model_lib.GMMModel(prior, K, D)
        adj, _ = network.random_geometric_graph(16, seed=4)
        g = network.SparseGraph.from_dense(np.asarray(adj))
        sw = network.sparse_nearest_neighbor_weights(g)
        x_all, labels = data.flat
        ref_q = gmm.ground_truth_posterior(x_all, labels, prior, K)
        ref_phis = refperm.permuted_refs(ref_q)
        gw, rg = network.two_level_partition(16, 4, 2)
        for topo in (engine.Diffusion(sw),
                     engine.PairwiseGossip(g, p_activate=0.5, seed=3),
                     engine.HierarchicalFusion(gw, rg)):
            a = engine.run_vb(mdl, (data.x, data.mask), topo, n_iters=20,
                              ref_phi=ref_phis, backend="reference",
                              schedule=engine.Schedule())
            b = engine.run_vb(mdl, (data.x, data.mask), topo, n_iters=20,
                              ref_phi=ref_phis, backend="fused",
                              schedule=engine.Schedule())
            np.testing.assert_allclose(np.asarray(b.kl_mean),
                                       np.asarray(a.kl_mean),
                                       rtol=1e-4, atol=1e-4)
    finally:
        jax.config.update("jax_enable_x64", True)


# ---------------------------------------------------------------------------
# The scale contract: no (N, N) intermediate on the sparse path
# ---------------------------------------------------------------------------
def test_sparse_combine_lowering_has_no_dense_matrix():
    """Lower a sparse-diffusion iterate combine at N=2048 and assert no
    2048x2048 tensor appears anywhere in the StableHLO; the dense
    combine (the oracle) of course has one — proving the probe bites."""
    n = 2048
    g = network.SparseGraph.ring(n)
    sw = network.sparse_nearest_neighbor_weights(g)
    topo = engine.Diffusion(sw, link_drop=0.1)
    sds = jax.ShapeDtypeStruct((n, 8), jnp.float64)
    txt = jax.jit(lambda v: topo.combine(v, t=3)).lower(sds).as_text()
    assert f"{n}x{n}" not in txt

    dense = engine.Diffusion(jnp.eye(n, dtype=jnp.float64))
    txt_d = jax.jit(lambda v: dense.combine(v, t=3)).lower(sds).as_text()
    assert f"{n}x{n}" in txt_d


def test_gossip_and_hier_lowering_has_no_dense_matrix():
    n = 2048
    g = network.SparseGraph.ring(n)
    gw, rg = network.two_level_partition(n, 64, 8)
    sds = jax.ShapeDtypeStruct((n, 8), jnp.float64)
    for topo in (engine.PairwiseGossip(g, p_activate=0.3),
                 engine.HierarchicalFusion(gw, rg)):
        txt = jax.jit(lambda v: topo.combine(v, t=0)).lower(sds).as_text()
        assert f"{n}x{n}" not in txt


# ---------------------------------------------------------------------------
# Large-N geometric builders: threshold radius, bounded retries
# ---------------------------------------------------------------------------
def test_geometric_edges_match_dense_small():
    for n, seed in [(16, 3), (50, 0), (50, 7), (100, 1)]:
        adj, pos = network.random_geometric_graph(n, seed=seed)
        g, pos_e = network.random_geometric_edges(n, seed=seed)
        np.testing.assert_array_equal(np.asarray(pos), np.asarray(pos_e))
        np.testing.assert_array_equal(np.asarray(g.to_dense()),
                                      np.asarray(adj, np.float64))


def test_default_radius_unchanged_at_paper_scale():
    """The threshold-derived default must NOT change the paper-scale
    graphs: below the crossover (N ~ 128) the legacy 0.8 still wins."""
    for n in (8, 16, 50, 100):
        side = network._paper_side(n, None)
        assert network._resolve_radius(n, side, None) == 0.8


def test_geometric_10k_builds_in_bounded_attempts():
    """Regression for the N=10k connectivity stall: the threshold-derived
    radius (~sqrt(log n / n) scaling) must connect on the FIRST attempt —
    the old constant 0.8 sat below the connectivity threshold there and
    the rejection loop re-sampled forever."""
    n = 10_000
    side = network._paper_side(n, None)
    assert network._resolve_radius(n, side, None) > 0.8  # threshold active
    g, pos = network.random_geometric_edges(n, seed=0, max_tries=1)
    assert g.n_nodes == n and pos.shape == (n, 2)
    assert int(np.asarray(g.deg).min()) >= 1


# ---------------------------------------------------------------------------
# Serving composition: sparse/gossip sessions through VBService
# ---------------------------------------------------------------------------
def test_vb_service_batches_sparse_sessions(setup):
    """Gossip + sparse-diffusion sessions run through `VBService` and
    match solo runs — `SparseGraph` rides through the structural
    signature, and structurally-equal fresh topologies still share one
    fleet group."""
    data, mdl, _, g = setup
    sched = engine.Schedule()
    svc = VBService(slice_iters=5)
    rids = [svc.submit(VBRequest(
        model=mdl, data=(data.x, data.mask),
        topology=engine.PairwiseGossip(g, p_activate=0.5, seed=7),
        n_iters=10, schedule=sched)) for _ in range(2)]
    rids.append(svc.submit(VBRequest(
        model=mdl, data=(data.x, data.mask),
        topology=engine.Diffusion(
            network.sparse_nearest_neighbor_weights(g)),
        n_iters=10, schedule=sched)))
    assert len(svc._groups) == 2        # 2 gossip tenants batch into one
    out = svc.run()
    for rid, topo in zip(rids, [
            engine.PairwiseGossip(g, p_activate=0.5, seed=7),
            engine.PairwiseGossip(g, p_activate=0.5, seed=7),
            engine.Diffusion(network.sparse_nearest_neighbor_weights(g))]):
        st = out[rid]
        assert st.done and st.t == 10
        solo = engine.run_vb(mdl, (data.x, data.mask), topo, n_iters=10,
                             schedule=sched)
        np.testing.assert_allclose(np.asarray(st.phi),
                                   np.asarray(solo.phi),
                                   rtol=TOL, atol=TOL, err_msg=rid)
