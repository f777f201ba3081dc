"""Session/state API + serving layer tests.

The resumability contract: `VBState.t` is the ABSOLUTE iteration count and
every per-iteration source of randomness (minibatch reshuffling epochs,
link-failure schedules, the eta_t/kappa_t ramps) is a function of it, so

    vb_run(s, a + b)  ==  vb_run(vb_run(s, a)[0], b)      (bit-exact)

for every topology — including the ADMM adaptive-rho dual/gate state and
link-drop schedules — plus checkpoint save -> restore -> continue parity
through checkpoint/ckpt.py, the carried epoch-permutation stream state
matching the stateless sampler, and the `VBService` fleet semantics
(same-shape batching == solo runs, per-session budgets/early-stop,
mid-flight data arrival, checkpoint restore).
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import ckpt
from repro.core import engine, expfam, network
from repro.core import model as model_lib
from repro.data import stream, synthetic
from repro.serving.vb_service import VBRequest, VBService


@pytest.fixture(autouse=True, scope="module")
def _x64():
    jax.config.update("jax_enable_x64", True)
    yield
    jax.config.update("jax_enable_x64", False)


K, D, N_NODES = 3, 2, 8


@pytest.fixture(scope="module")
def setup():
    data = synthetic.paper_synthetic(n_nodes=N_NODES, n_per_node=20, seed=2)
    prior = expfam.noninformative_prior(K, D, beta0=0.1, w0_scale=10.0)
    adj, _ = network.random_geometric_graph(N_NODES, seed=4)
    W = network.nearest_neighbor_weights(adj)
    mdl = model_lib.GMMModel(prior, K, D)
    return data, mdl, adj, W


def _assert_trees_bitequal(a, b, what):
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb), what
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=what)


def _topo_configs(adj, W):
    """(name, topology factory, vb_init kwargs) — every topology, with
    link-drop / minibatch riding along where they apply."""
    return [
        ("fusion", lambda: engine.FusionCenter(),
         dict(schedule=engine.ONE_SHOT)),
        ("isolated", lambda: engine.Isolated(),
         dict(schedule=engine.ONE_SHOT, replication=1.0)),
        ("diffusion-mb-drop",
         lambda: engine.Diffusion(W, link_drop=0.3, link_seed=5),
         dict(minibatch=stream.MinibatchSpec(7, seed=3))),
        ("ring-drop",
         lambda: engine.RingDiffusion(link_drop=0.25, link_seed=6), {}),
        ("admm-plain", lambda: engine.ADMMConsensus(adj), {}),
        ("admm-adaptive-pb",
         lambda: engine.ADMMConsensus(adj, adaptive_rho=True,
                                      per_block=True), {}),
    ]


# ---------------------------------------------------------------------------
# Split-resume bit-exactness: vb_run(s, a+b) == vb_run(vb_run(s, a), b)
# ---------------------------------------------------------------------------
def test_split_resume_bit_exact_every_topology(setup):
    data, mdl, adj, W = setup
    a, b = 37, 63
    for name, topo, kw in _topo_configs(adj, W):
        full = engine.vb_init(mdl, (data.x, data.mask), topo(), **kw)
        full, run_full = engine.vb_run(full, a + b)

        split = engine.vb_init(mdl, (data.x, data.mask), topo(), **kw)
        split, run_a = engine.vb_run(split, a)
        assert int(split.t) == a, name
        split, run_b = engine.vb_run(split, b)
        assert int(split.t) == a + b, name

        _assert_trees_bitequal(full.phi, split.phi, f"{name}: phi")
        _assert_trees_bitequal(full.carry, split.carry, f"{name}: carry")
        _assert_trees_bitequal(full.stream, split.stream,
                               f"{name}: stream")
        _assert_trees_bitequal(full.diag, split.diag, f"{name}: diag")
        # the per-iteration trajectories also tile exactly
        _assert_trees_bitequal(
            run_full.kl_nodes,
            jnp.concatenate([run_a.kl_nodes, run_b.kl_nodes]),
            f"{name}: kl trajectory")
        _assert_trees_bitequal(
            run_full.consensus_err,
            jnp.concatenate([run_a.consensus_err, run_b.consensus_err]),
            f"{name}: consensus trajectory")


def test_single_stepping_matches_scan(setup):
    """vb_step x n == vb_run(s, n) bit-exactly (the serving quantum)."""
    data, mdl, adj, W = setup
    topo = engine.ADMMConsensus(adj, adaptive_rho=True)
    s_scan = engine.vb_init(mdl, (data.x, data.mask), topo,
                            minibatch=stream.MinibatchSpec(9, seed=1))
    s_scan, _ = engine.vb_run(s_scan, 5)
    s_step = engine.vb_init(mdl, (data.x, data.mask), topo,
                            minibatch=stream.MinibatchSpec(9, seed=1))
    for _ in range(5):
        s_step = engine.vb_step(s_step)
    _assert_trees_bitequal(s_scan.phi, s_step.phi, "phi")
    _assert_trees_bitequal(s_scan.carry, s_step.carry, "carry")
    _assert_trees_bitequal(s_scan.stream, s_step.stream, "stream")
    assert int(s_step.t) == 5


def test_run_vb_wrapper_is_session_path(setup):
    data, mdl, adj, W = setup
    run_w = engine.run_vb(mdl, (data.x, data.mask), engine.Diffusion(W),
                          n_iters=20)
    state = engine.vb_init(mdl, (data.x, data.mask), engine.Diffusion(W))
    _, run_s = engine.vb_run(state, 20)
    _assert_trees_bitequal(run_w.phi, run_s.phi, "phi")
    _assert_trees_bitequal(run_w.kl_nodes, run_s.kl_nodes, "kl")


def test_carried_stream_state_matches_stateless_sampler(setup):
    """The epoch-permutation carry (ROADMAP follow-up: no per-iteration
    O(T log T) redraw) is bit-exact with the stateless oracle."""
    data, mdl, *_ = setup
    B = 6
    keys = stream.node_keys(N_NODES, seed=11)
    st = stream.init_state(N_NODES, 11, data.mask.shape[1])
    for t in range(25):
        ta = jnp.asarray(t)
        i_ref, m_ref = stream.minibatch_select(keys, data.mask, ta, B)
        st, i_new, m_new = stream.advance(st, data.mask, ta, B)
        np.testing.assert_array_equal(np.asarray(i_ref), np.asarray(i_new))
        np.testing.assert_array_equal(np.asarray(m_ref), np.asarray(m_new))
    assert int(st.epoch) == 24 // -(-data.mask.shape[1] // B)


# ---------------------------------------------------------------------------
# Checkpointing: save -> restore -> continue == uninterrupted
# ---------------------------------------------------------------------------
def test_checkpoint_restore_continue_parity(setup, tmp_path):
    data, mdl, adj, W = setup
    mk = lambda: engine.vb_init(
        mdl, (data.x, data.mask),
        engine.ADMMConsensus(adj, adaptive_rho=True),
        minibatch=stream.MinibatchSpec(7, seed=1))
    s = mk()
    s, _ = engine.vb_run(s, 9)
    path = os.path.join(tmp_path, "state.npz")
    ckpt.save(path, s)
    restored = ckpt.restore(path, mk())     # fresh session re-attached
    assert int(restored.t) == 9
    s, _ = engine.vb_run(s, 11)
    restored, _ = engine.vb_run(restored, 11)
    _assert_trees_bitequal(s.phi, restored.phi, "phi")
    _assert_trees_bitequal(s.carry, restored.carry, "carry")
    _assert_trees_bitequal(s.stream, restored.stream, "stream")


def test_vbstate_validation(setup):
    data, mdl, adj, W = setup
    state = engine.vb_init(mdl, (data.x, data.mask), engine.Diffusion(W))
    with pytest.raises(ValueError, match="shapes/dtypes"):
        state.with_data((data.x[:, :5], data.mask))
    # with_data round-trips and keeps the evolving state
    state2 = state.with_data((data.x + 1.0, data.mask))
    assert state2.session.data[0].shape == data.x.shape
    with pytest.raises(ValueError, match="session"):
        engine.vb_run(engine.VBState(state.phi, state.t), 1)


# ---------------------------------------------------------------------------
# VBService: fleets
# ---------------------------------------------------------------------------
def test_service_fleet_matches_solo_with_heterogeneous_budgets(setup):
    data, mdl, adj, W = setup
    datasets = [synthetic.paper_synthetic(n_nodes=N_NODES, n_per_node=20,
                                          seed=s) for s in range(3)]
    budgets = [17, 26, 40]
    svc = VBService(slice_iters=10)
    # a FRESH (but structurally equal, same W array) topology per request
    # must still land every tenant in ONE fleet group
    rids = [svc.submit(VBRequest(model=mdl, data=(d.x, d.mask),
                                 topology=engine.Diffusion(W), n_iters=n))
            for d, n in zip(datasets, budgets)]
    assert len(svc._groups) == 1
    topo = engine.Diffusion(W)
    out = svc.run()
    for d, n, rid in zip(datasets, budgets, rids):
        st = out[rid]
        assert st.done and st.t == n and st.budget == n
        solo = engine.run_vb(mdl, (d.x, d.mask), topo, n_iters=n)
        err = float(jnp.max(jnp.abs(solo.phi - st.phi)))
        assert err < 1e-8, (rid, err)


def test_service_16_session_mixed_topology_fleet(setup):
    """The acceptance scenario: a 16-session mixed-topology fleet with
    per-session early stop, mid-flight data arrival and checkpoint
    restore, all in one service."""
    data, mdl, adj, W = setup
    topos = [engine.Diffusion(W),
             engine.ADMMConsensus(adj, adaptive_rho=True)]
    svc = VBService(slice_iters=6)
    rids = []
    for i in range(16):
        d = synthetic.paper_synthetic(n_nodes=N_NODES, n_per_node=10,
                                      seed=i)
        mask = d.mask.at[:, -2:].set(0.0)       # free slots for arrival
        # session 4 gets a long budget + loose tol: it must EARLY-stop
        # inside its fleet while its fleet-mates run to their budgets
        rids.append(svc.submit(VBRequest(
            model=mdl, data=(d.x, mask), topology=topos[i % 2],
            n_iters=300 if i == 4 else 12 + (i % 4) * 6,
            tol=5e-2 if i == 4 else 0.0)))
    assert len(svc._groups) == 2                # one fleet per topology
    svc.step_slice()
    svc.push_data(rids[3], node=1,
                  points=np.random.default_rng(0).normal(size=(2, D)))
    out = svc.run()
    for i, rid in enumerate(rids):
        st = out[rid]
        assert st.done, rid
        if i != 4:
            assert st.t == st.budget == 12 + (i % 4) * 6
    assert out[rids[4]].converged and out[rids[4]].t < 300


def test_service_early_stop_freezes_state(setup):
    data, mdl, adj, W = setup
    svc = VBService(slice_iters=5)
    rid = svc.submit(VBRequest(model=mdl, data=(data.x, data.mask),
                               topology=engine.Diffusion(W),
                               n_iters=400, tol=1e-2))
    out = svc.run()
    st = out[rid]
    assert st.converged and st.done and st.t < 400
    assert st.delta < 1e-2
    # the frozen state equals a solo run of exactly st.t iterations
    solo = engine.run_vb(mdl, (data.x, data.mask), engine.Diffusion(W),
                         n_iters=st.t)
    assert float(jnp.max(jnp.abs(solo.phi - st.phi))) < 1e-8


def test_service_push_and_replace_data(setup):
    data, mdl, adj, W = setup
    mask = data.mask.at[:, 15:].set(0.0)        # free capacity everywhere
    svc = VBService(slice_iters=4)
    rid = svc.submit(VBRequest(model=mdl, data=(data.x, mask),
                               topology=engine.Diffusion(W), n_iters=8))
    svc.step_slice()
    before = np.asarray(svc.status(rid).phi)
    svc.push_data(rid, node=2,
                  points=np.random.default_rng(1).normal(size=(3, D)))
    out = svc.run()
    # the appended points changed the remaining trajectory
    assert not np.allclose(before, np.asarray(out[rid].phi))
    # overflowing a node's buffer is no longer an error: the bucketed
    # driver regrows the session to a larger ladder rung (the buffer-full
    # ValueError still surfaces with bucket=None — tests/test_bucketed.py)
    svc.push_data(rid, node=2, points=np.zeros((100, D)))
    with pytest.raises(ValueError, match="signature mismatch"):
        svc.replace_data(rid, (data.x[:3], mask[:3]))   # wrong node count
    svc.replace_data(rid, (data.x, mask))
    svc.extend_budget(rid, 4)
    out = svc.run()
    assert out[rid].t == 12


def test_service_checkpoint_restore_bit_exact(setup, tmp_path):
    data, mdl, adj, W = setup
    req = VBRequest(model=mdl, data=(data.x, data.mask),
                    topology=engine.Diffusion(W), n_iters=30,
                    minibatch=stream.MinibatchSpec(7, seed=1))
    svc_a = VBService(slice_iters=10)
    rid_a = svc_a.submit(req)
    svc_a.step_slice()
    path = os.path.join(tmp_path, "sess.npz")
    svc_a.save_session(rid_a, path)
    svc_b = VBService(slice_iters=10)
    rid_b = svc_b.submit(req, restore_from=path)
    assert svc_b.status(rid_b).t == 10
    out_a, out_b = svc_a.run(), svc_b.run()
    _assert_trees_bitequal(out_a[rid_a].phi, out_b[rid_b].phi, "phi")


def test_service_rejects_bad_requests(setup):
    data, mdl, adj, W = setup
    svc = VBService(slice_iters=4)
    with pytest.raises(ValueError, match="n_iters"):
        svc.submit(VBRequest(model=mdl, data=(data.x, data.mask),
                             topology=engine.Diffusion(W), n_iters=0))
    with pytest.raises(KeyError):
        svc.status("nope")
    with pytest.raises(ValueError, match="slice_iters"):
        VBService(slice_iters=0)


# ---------------------------------------------------------------------------
# Mesh executor: session resume + fleet service under shard_map
# ---------------------------------------------------------------------------
CODE_MESH_SESSION = r"""
from repro import runtime
runtime.use_platform_precision()
import jax
from repro.core import expfam
import jax.numpy as jnp
from repro.core import engine, network
from repro.core import model as model_lib
from repro.data import synthetic, stream
from repro.serving.vb_service import VBRequest, VBService

K, D, N = 3, 2, 8
data = synthetic.paper_synthetic(n_nodes=N, n_per_node=20, seed=9)
prior = expfam.noninformative_prior(K, D, beta0=0.1, w0_scale=10.0)
adj, _ = network.random_geometric_graph(N, seed=5)
W = network.nearest_neighbor_weights(adj)
mdl = model_lib.GMMModel(prior, K, D)
mesh = jax.make_mesh((4,), ("data",))
mexec = engine.MeshExecutor(mesh, "data")

# 1. vb_run split-resume under the mesh executor == unsplit single-array
for topo_fn, mb in [
    (lambda: engine.Diffusion(W, link_drop=0.3, link_seed=5),
     stream.MinibatchSpec(7, seed=3)),
    (lambda: engine.ADMMConsensus(adj, adaptive_rho=True), None),
]:
    s = engine.vb_init(mdl, (data.x, data.mask), topo_fn(), executor=mexec,
                       minibatch=mb)
    s, _ = engine.vb_run(s, 11)
    s, _ = engine.vb_run(s, 14)
    solo = engine.run_vb(mdl, (data.x, data.mask), topo_fn(), n_iters=25,
                         minibatch=mb)
    err = float(jnp.max(jnp.abs(solo.phi - s.phi)))
    assert err < 1e-8, err

# 2. VBService fleet with the node axis sharded (vmap inside shard_map)
svc = VBService(slice_iters=9, executor=mexec)
datasets = [synthetic.paper_synthetic(n_nodes=N, n_per_node=20, seed=s)
            for s in range(3)]
topo = engine.RingDiffusion()
rids = [svc.submit(VBRequest(model=mdl, data=(d.x, d.mask), topology=topo,
                             n_iters=20)) for d in datasets]
out = svc.run()
for d, r in zip(datasets, rids):
    solo = engine.run_vb(mdl, (d.x, d.mask), topo, n_iters=20)
    err = float(jnp.max(jnp.abs(solo.phi - out[r].phi)))
    assert err < 1e-8, (r, err)
print("OK")
"""


def test_mesh_session_and_service(subproc):
    out = subproc(CODE_MESH_SESSION, n_devices=4)
    assert "OK" in out
