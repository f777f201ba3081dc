"""Bucketed ragged-fleet admission (PR 7 acceptance suite).

The contract under test, end to end:

* **ladder** — `admission.bucket_capacity` / `bucket_for` round per-node
  data capacities up to geometric rungs;
* **padding is invisible** — `model.pad_to_capacity` adds mask-zero
  slots, which add nothing to any statistic: the padded session has the
  same posterior as the unpadded solo `vb_run` to rounding
  (`conftest.assert_same_posterior`), on every topology, both executors
  and both GMM compute backends, and a leaked padding point fails that
  bound;
* **mixed shapes share a fleet** — sessions whose capacities round to
  one rung land in ONE fleet group (one compiled slice fn), each still
  the same posterior as its solo run to rounding;
* **mixed hyper share a fleet** — tau/rho become per-slot fleet arrays
  (`engine.hyper_names`), so sessions differing only in those schedule
  knobs also share the group;
* **overflow re-buckets** — `push_data` beyond the rung evicts, regrows
  to the next rung and re-admits under the absolute-t resume contract
  (trajectory replayable with vb_init/vb_run);
* `static_signature` signs small arrays by content (regression: it used
  to sign by object identity, splitting equal-config groups).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import assert_same_posterior
from repro.core import engine, expfam, linreg, network
from repro.core import model as model_lib
from repro.data import stream, synthetic
from repro.serving import admission
from repro.serving.vb_service import VBRequest, VBService


@pytest.fixture(autouse=True, scope="module")
def _x64():
    jax.config.update("jax_enable_x64", True)
    yield
    jax.config.update("jax_enable_x64", False)


K, D, N_NODES = 3, 2, 8


@pytest.fixture(scope="module")
def setup():
    prior = expfam.noninformative_prior(K, D, beta0=0.1, w0_scale=10.0)
    mdl = model_lib.GMMModel(prior, K, D)
    adj, _ = network.random_geometric_graph(N_NODES, seed=4)
    W = network.nearest_neighbor_weights(adj)
    return mdl, adj, W


def _gmm_data(n_per_node, seed=0):
    d = synthetic.paper_synthetic(n_nodes=N_NODES, n_per_node=n_per_node,
                                  seed=seed)
    return d.x, d.mask


# ---------------------------------------------------------------------------
# The ladder
# ---------------------------------------------------------------------------
def test_bucket_capacity_ladder():
    assert [admission.bucket_capacity(n) for n in (1, 8, 9, 25, 64, 65)] \
        == [8, 8, 16, 32, 64, 128]
    # finer tensor2tensor-style rungs: at most ~25% padded slots (above
    # the min_size floor, where everything rounds up to the first rung)
    caps = {n: admission.bucket_capacity(n, growth=1.25)
            for n in range(8, 200)}
    assert all(c >= n and (c - n) / c < 0.25 + 1e-9
               for n, c in caps.items())
    assert admission.bucket_capacity(25, growth=1.25) == 28
    # tiny growth factors still make a strictly increasing ladder
    assert admission.bucket_capacity(9, growth=1.01) > 8
    with pytest.raises(ValueError):
        admission.bucket_capacity(0)
    with pytest.raises(ValueError):
        admission.bucket_capacity(5, growth=1.0)


def test_bucket_for_rounds_capacity_axis_only():
    a = admission.shape_signature((jnp.zeros((4, 25, 2)),
                                   jnp.zeros((4, 25))))
    b = admission.shape_signature((jnp.zeros((4, 32, 2)),
                                   jnp.zeros((4, 32))))
    c = admission.shape_signature((jnp.zeros((4, 33, 2)),
                                   jnp.zeros((4, 33))))
    assert admission.bucket_for(a) == admission.bucket_for(b)
    assert admission.bucket_for(a) != admission.bucket_for(c)   # next rung
    # node axis (axis 0) and trailing axes are never bucketed
    d = admission.shape_signature((jnp.zeros((5, 25, 2)),
                                   jnp.zeros((5, 25))))
    assert admission.bucket_for(a) != admission.bucket_for(d)
    # 1-D leaves (e.g. a phi* row) pass through untouched
    e = admission.shape_signature(jnp.zeros((25,)))
    assert admission.bucket_for(e) == e


# ---------------------------------------------------------------------------
# static_signature: content digest for small arrays (id() regression)
# ---------------------------------------------------------------------------
def test_static_signature_small_arrays_by_content(setup):
    mdl, adj, W = setup
    # two separately-built equal-valued topologies sign EQUAL
    assert admission.static_signature(engine.Diffusion(W.copy())) \
        == admission.static_signature(engine.Diffusion(W.copy()))
    W2 = np.asarray(W).copy()
    W2[0, 0] += 1e-3
    assert admission.static_signature(engine.Diffusion(W)) \
        != admission.static_signature(engine.Diffusion(W2))


def test_static_signature_large_arrays_by_identity():
    big = np.zeros((1 << 14, 3))        # > DIGEST_MAX_BYTES
    assert big.nbytes > admission.DIGEST_MAX_BYTES
    assert admission.static_signature(big) \
        != admission.static_signature(big.copy())   # conservative split
    assert admission.static_signature(big) == admission.static_signature(big)
    small = big[:4].copy()
    assert admission.static_signature(small) \
        == admission.static_signature(small.copy())


def test_static_signature_ignore_lifted_attrs(setup):
    mdl, adj, W = setup
    a = engine.ADMMConsensus(adj, rho=0.3)
    b = engine.ADMMConsensus(adj, rho=0.9)
    lifted = engine.lifted_attr_names(a)
    assert "rho" in lifted
    assert admission.static_signature(a) != admission.static_signature(b)
    assert admission.static_signature(a, ignore=lifted) \
        == admission.static_signature(b, ignore=lifted)


# ---------------------------------------------------------------------------
# pad_to_capacity: padded solo run == unpadded solo run, to rounding
# ---------------------------------------------------------------------------
def _topologies(adj, W):
    return [
        ("fusion", engine.FusionCenter(), engine.ONE_SHOT),
        ("isolated", engine.Isolated(), engine.Schedule()),
        ("ring", engine.RingDiffusion(), engine.Schedule(tau=0.1)),
        ("diffusion", engine.Diffusion(W), engine.Schedule()),
        ("admm", engine.ADMMConsensus(adj), engine.Schedule()),
        ("admm-adaptive", engine.ADMMConsensus(adj, adaptive_rho=True),
         engine.Schedule()),
    ]


def test_gmm_padding_bit_equal_every_topology(setup):
    """Padding a session's data buffers to the ladder rung with mask-zero
    slots leaves phi the same to rounding, for every estimator."""
    mdl, adj, W = setup
    data = _gmm_data(25)
    padded = mdl.pad_to_capacity(data, admission.bucket_capacity(25))
    assert padded[0].shape == (N_NODES, 32, D)
    for name, topo, sched in _topologies(adj, W):
        a = engine.run_vb(mdl, data, topo, n_iters=12, schedule=sched)
        b = engine.run_vb(mdl, padded, topo, n_iters=12, schedule=sched)
        assert_same_posterior(b.phi, a.phi, err_msg=name)


def test_gmm_padding_bit_equal_fused_backend(setup):
    """Both compute backends keep the same guarantee."""
    mdl, adj, W = setup
    data = _gmm_data(25)
    padded = mdl.pad_to_capacity(data, 32)
    for backend in ("reference", "fused"):
        a = engine.run_vb(mdl, data, engine.RingDiffusion(), n_iters=8,
                          backend=backend)
        b = engine.run_vb(mdl, padded, engine.RingDiffusion(), n_iters=8,
                          backend=backend)
        assert_same_posterior(b.phi, a.phi, err_msg=backend)


def test_leaked_padding_point_fails_same_posterior(setup):
    """The rounding bound separates rounding from an admission fault: one
    padding slot that leaks into the statistics (mask 1 on a copy of a
    real point) moves phi far outside it."""
    mdl, adj, W = setup
    data = _gmm_data(25)
    x, mask = mdl.pad_to_capacity(data, 32)
    leaked = (x.at[0, 25].set(x[0, 0]), mask.at[0, 25].set(1.0))
    a = engine.run_vb(mdl, data, engine.RingDiffusion(), n_iters=8)
    b = engine.run_vb(mdl, leaked, engine.RingDiffusion(), n_iters=8)
    gap = float(jnp.max(jnp.abs(b.phi - a.phi)) / jnp.max(jnp.abs(a.phi)))
    assert gap > 1e-6, gap
    with pytest.raises(AssertionError, match="max gap"):
        assert_same_posterior(b.phi, a.phi)


def test_linreg_padding_bit_equal(setup):
    mdl, adj, W = setup
    rng = np.random.default_rng(3)
    Dl, ni = 3, 13
    X = jnp.asarray(rng.normal(size=(N_NODES, ni, Dl)))
    y = jnp.asarray(X @ rng.normal(size=Dl)
                    + rng.normal(size=(N_NODES, ni)) * 0.3)
    mask = jnp.ones((N_NODES, ni), X.dtype)
    lr = model_lib.LinRegModel(linreg.prior(Dl))
    padded = lr.pad_to_capacity((X, y, mask), 16)
    assert padded[0].shape == (N_NODES, 16, Dl)
    a = engine.run_vb(lr, (X, y, mask), engine.RingDiffusion(), n_iters=10)
    b = engine.run_vb(lr, padded, engine.RingDiffusion(), n_iters=10)
    np.testing.assert_array_equal(np.asarray(a.phi), np.asarray(b.phi))


def test_linreg_phi_star_stack_not_padddable(setup):
    """A precomputed phi* stack has no sample axis: pad_to_capacity must
    refuse (the driver then falls back to exact-signature grouping)."""
    lr = model_lib.LinRegModel(linreg.prior(2))
    phi_star = jnp.stack([lr.init_phi() + 1.0, lr.init_phi() - 1.0])
    with pytest.raises(ValueError):
        lr.pad_to_capacity(phi_star, 16)
    with pytest.raises(ValueError):
        lr.pad_to_capacity((jnp.zeros((2, 5, 2)), jnp.zeros((2, 5)),
                            jnp.ones((2, 5))), 4)   # capacity < T


# ---------------------------------------------------------------------------
# Driver: mixed shapes / mixed hyper share one compiled fleet
# ---------------------------------------------------------------------------
def test_mixed_shapes_share_one_fleet_bit_equal_solo(setup):
    """Four sessions with per-node capacities 9/10/13/16 all round to
    rung 16: ONE group, ONE trace, every result the same to rounding as
    the solo run on its own unpadded data."""
    mdl, adj, W = setup
    sizes = [9, 10, 13, 16]
    datasets = [_gmm_data(n, seed=i) for i, n in enumerate(sizes)]
    topo = engine.RingDiffusion()
    svc = VBService(slice_iters=6, max_fleet=4)
    rids = [svc.submit(VBRequest(model=mdl, data=d, topology=topo,
                                 n_iters=18)) for d in datasets]
    out = svc.run()
    st = svc.stats()
    assert len(svc._groups) == 1 and st.compiles == 1, st
    assert len(st.buckets) == 1
    b = st.buckets[0]
    assert b.bucket_capacity == 16 and b.label.endswith("/cap16")
    assert b.admitted == 4
    # mean mask-zero padding fraction: ((16-9)+(16-10)+(16-13)+0)/16/4
    assert b.data_pad_frac == pytest.approx((7 + 6 + 3 + 0) / 16 / 4)
    for d, rid in zip(datasets, rids):
        solo = engine.run_vb(mdl, d, topo, n_iters=18)
        assert_same_posterior(out[rid].phi, solo.phi, err_msg=rid)


def test_mixed_tau_share_one_fleet_bit_equal_solo(setup):
    """Sessions differing only in the schedule's tau (lifted to a
    per-slot fleet array) share the group and still match their solo
    runs to rounding."""
    mdl, adj, W = setup
    data = _gmm_data(12)
    taus = [0.2, 0.05, 1.0]
    topo = engine.RingDiffusion()
    svc = VBService(slice_iters=5, max_fleet=4)
    rids = [svc.submit(VBRequest(model=mdl, data=data, topology=topo,
                                 n_iters=15,
                                 schedule=engine.Schedule(tau=tau)))
            for tau in taus]
    out = svc.run()
    assert len(svc._groups) == 1 and svc.stats().compiles == 1
    for tau, rid in zip(taus, rids):
        solo = engine.run_vb(mdl, data, topo, n_iters=15,
                             schedule=engine.Schedule(tau=tau))
        assert_same_posterior(out[rid].phi, solo.phi, err_msg=f"tau={tau}")


def test_mixed_rho_admm_share_one_fleet(setup):
    """ADMM sessions differing only in rho (and shape, via the ladder)
    share one group, each the same to rounding as its solo run."""
    mdl, adj, W = setup
    cases = [(10, 0.3), (13, 0.8), (16, 0.5)]
    svc = VBService(slice_iters=5, max_fleet=4)
    rids = [svc.submit(VBRequest(model=mdl, data=_gmm_data(n, seed=n),
                                 topology=engine.ADMMConsensus(adj, rho=r),
                                 n_iters=12))
            for n, r in cases]
    out = svc.run()
    assert len(svc._groups) == 1 and svc.stats().compiles == 1
    for (n, r), rid in zip(cases, rids):
        solo = engine.run_vb(mdl, _gmm_data(n, seed=n),
                             engine.ADMMConsensus(adj, rho=r), n_iters=12)
        assert_same_posterior(out[rid].phi, solo.phi, err_msg=f"{n}, {r}")


def test_eta_fixed_never_shares_with_scheduled(setup):
    """ONE_SHOT (eta_fixed=1.0) compiles a different step than the
    Robbins-Monro ramp — those sessions must NOT share a group."""
    mdl, adj, W = setup
    data = _gmm_data(12)
    svc = VBService(slice_iters=5, max_fleet=2)
    svc.submit(VBRequest(model=mdl, data=data, topology=engine.Isolated(),
                         n_iters=8))
    svc.submit(VBRequest(model=mdl, data=data, topology=engine.Isolated(),
                         n_iters=8, schedule=engine.ONE_SHOT))
    svc.run()
    assert len(svc._groups) == 2


def test_minibatch_sessions_not_bucketed(setup):
    """Streaming sessions key epoch permutations on the TRUE capacity, so
    they keep exact-shape grouping (and different capacities stay in
    different groups) — still matching their solo streaming runs to
    rounding."""
    mdl, adj, W = setup
    sizes = [10, 13]
    mb = stream.MinibatchSpec(5, seed=2)
    svc = VBService(slice_iters=5, max_fleet=2)
    rids = [svc.submit(VBRequest(model=mdl, data=_gmm_data(n, seed=n),
                                 topology=engine.RingDiffusion(),
                                 n_iters=10, minibatch=mb))
            for n in sizes]
    out = svc.run()
    assert len(svc._groups) == 2
    labels = [b.label for b in svc.stats().buckets]
    assert all(lab.endswith("/exact") for lab in labels), labels
    for n, rid in zip(sizes, rids):
        solo = engine.run_vb(mdl, _gmm_data(n, seed=n),
                             engine.RingDiffusion(), n_iters=10,
                             minibatch=mb)
        assert_same_posterior(out[rid].phi, solo.phi)


def test_bucket_none_keeps_exact_grouping_and_buffer_full(setup):
    """Legacy mode: bucket=None groups by exact signature and push_data
    overflow is still a hard error."""
    mdl, adj, W = setup
    svc = VBService(slice_iters=5, max_fleet=2, bucket=None)
    rids = [svc.submit(VBRequest(model=mdl, data=_gmm_data(n, seed=n),
                                 topology=engine.RingDiffusion(),
                                 n_iters=8)) for n in (10, 13)]
    svc.run()
    assert len(svc._groups) == 2
    with pytest.raises(ValueError, match="buffer full"):
        svc.push_data(rids[0], node=0,
                      points=np.zeros((100, D)))


# ---------------------------------------------------------------------------
# Overflow -> eviction -> re-admission into the next rung
# ---------------------------------------------------------------------------
def test_push_data_overflow_rebuckets_with_exact_replay(setup):
    """A full rung-8 session receives 3 points mid-flight: the driver
    evicts it, regrows the buffers to rung 16, re-admits, and the final
    phi matches the replayed vb_init/vb_run trajectory (run 5 iters on
    the old buffers, regrow, run the remaining 15) to rounding."""
    mdl, adj, W = setup
    data = _gmm_data(8)                       # rung 8, zero padding slots
    topo = engine.RingDiffusion()
    pts = np.asarray(
        np.random.default_rng(7).normal(size=(3, D)), np.float64)

    svc = VBService(slice_iters=5, max_fleet=2)
    rid = svc.submit(VBRequest(model=mdl, data=data, topology=topo,
                               n_iters=20))
    assert svc.step_slice() == 1              # t=5, mid-flight
    svc.push_data(rid, node=1, points=pts)    # overflow -> re-bucket
    out = svc.run()
    assert out[rid].done and out[rid].t == 20
    st = svc.stats()
    assert st.evicted >= 2                    # overflow eviction + final
    assert any(b.bucket_capacity == 16 for b in st.buckets), st.buckets

    # replay the exact trajectory through the public session API
    s = engine.vb_init(mdl, data, topo)
    s, _ = engine.vb_run(s, 5)
    grown = mdl.append_node_data(mdl.pad_to_capacity(data, 16), 1, pts)
    s2 = engine.vb_init(mdl, grown, topo)
    s2 = s2.replace(phi=s.phi, t=s.t, carry=s.carry)
    s2, _ = engine.vb_run(s2, 15)
    assert_same_posterior(out[rid].phi, s2.phi)


def test_replace_data_pads_to_rung(setup):
    """replace_data on a bucketed session accepts any data that pads to
    the session's rung (here: fewer true samples than the original)."""
    mdl, adj, W = setup
    x, mask = _gmm_data(13)                   # rung 16
    svc = VBService(slice_iters=5, max_fleet=2)
    rid = svc.submit(VBRequest(model=mdl, data=(x, mask),
                               topology=engine.RingDiffusion(), n_iters=10))
    svc.run()
    svc.replace_data(rid, (x[:, :9], mask[:, :9]))      # pads 9 -> 16
    out = svc.run()
    solo = engine.run_vb(mdl, (x[:, :9], mask[:, :9]),
                         engine.RingDiffusion(), n_iters=10)
    # the replayed tail ran on the replaced buffers from the old phi, so
    # only shapes/convergence are asserted here; numerics are covered by
    # the padding-invariance tests above
    assert out[rid].done and np.asarray(out[rid].phi).shape == solo.phi.shape


# ---------------------------------------------------------------------------
# Mesh executor: the bucketed fleet composes with shard_map
# ---------------------------------------------------------------------------
CODE_MESH_BUCKETED = r"""
from repro import runtime
runtime.use_platform_precision()
import jax
import jax.numpy as jnp
import numpy as np
from repro.core import engine, expfam, network
from repro.core import model as model_lib
from repro.data import synthetic
from repro.serving.vb_service import VBRequest, VBService

K, D, N = 3, 2, 8
prior = expfam.noninformative_prior(K, D, beta0=0.1, w0_scale=10.0)
mdl = model_lib.GMMModel(prior, K, D)
mesh = jax.make_mesh((4,), ("data",))
mexec = engine.MeshExecutor(mesh, "data")
topo = engine.RingDiffusion()

sizes = [9, 10, 13, 16]
datasets = [synthetic.paper_synthetic(n_nodes=N, n_per_node=n, seed=i)
            for i, n in enumerate(sizes)]
taus = [0.2, 0.1, 0.2, 0.1]
svc = VBService(slice_iters=6, max_fleet=4, executor=mexec)
rids = [svc.submit(VBRequest(model=mdl, data=(d.x, d.mask), topology=topo,
                             n_iters=18, schedule=engine.Schedule(tau=tau)))
        for d, tau in zip(datasets, taus)]
out = svc.run()
assert len(svc._groups) == 1 and svc.stats().compiles == 1, svc.stats()
for d, tau, rid in zip(datasets, taus, rids):
    solo = engine.run_vb(mdl, (d.x, d.mask), topo, n_iters=18,
                         schedule=engine.Schedule(tau=tau))
    # conftest.assert_same_posterior, inlined
    gap = float(jnp.max(jnp.abs(out[rid].phi - solo.phi)))
    assert gap <= 1e-12 * float(jnp.max(jnp.abs(solo.phi))), (rid, gap)
print("MESH-BUCKETED-OK")
"""


def test_bucketed_fleet_on_mesh_executor(subproc):
    """Mixed shapes AND mixed tau in one shard_mapped fleet: one trace,
    each session the same to rounding as its solo single-array run."""
    assert "MESH-BUCKETED-OK" in subproc(CODE_MESH_BUCKETED, n_devices=4)
