"""Continuous-batching driver tests (serving/driver.py).

The two acceptance properties:

* **no mid-flight recompilation** — sessions join and leave a
  partially-full fixed-capacity fleet and the slice function traces
  exactly once per group configuration (`DriverStats.compiles`);
* **driver scheduling is invisible to the numerics** — a session's
  trajectory is a pure function of its own absolute `t` (the engine's
  resumability contract), so driver-scheduled sessions have the same
  posterior as a solo `vb_run` of the same length to rounding (the fleet
  is a different program — see docs/continuous-batching.md), and are
  bit-INVARIANT to the arrival/eviction pattern for every topology (the
  same fleet program either way).

Plus the scheduler mechanics: arrival staggering, fleet-full queueing,
the background thread, background checkpoint writes, eviction lifecycle
edges, and the LM engine sharing the same primitives.
"""
import os

import jax
import numpy as np
import pytest

from conftest import assert_same_posterior
from repro.checkpoint import ckpt
from repro.core import engine, expfam, network
from repro.core import model as model_lib
from repro.data import synthetic
from repro.serving import driver as drv
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
    datasets = [synthetic.paper_synthetic(n_nodes=N_NODES, n_per_node=10,
                                          seed=s) for s in range(5)]
    return mdl, adj, W, datasets


# ---------------------------------------------------------------------------
# Scheduling primitives
# ---------------------------------------------------------------------------
def test_arrival_queue_fifo_and_readiness():
    q = drv.ArrivalQueue()
    q.push("a", 0)
    q.push("b", 2)
    q.push("c", 0)
    assert len(q) == 3 and q.next_arrival() == 0
    ready = q.pop_ready(0)
    assert [e[2] for e in ready] == ["a", "c"]     # FIFO within a tick
    assert q.pop_ready(1) == []
    q.push_entry(ready[0])                          # requeue keeps position
    assert [e[2] for e in q.pop_ready(2)] == ["a", "b"]


def test_slot_table_reuse_lowest_first():
    t = drv.SlotTable(3)
    assert [t.alloc(r) for r in "xyz"] == [0, 1, 2]
    assert t.alloc("w") is None and t.n_occupied == 3
    assert t.free(1) == "y"
    assert t.alloc("w") == 1                        # lowest free slot
    assert sorted(t.occupied()) == [(0, "x"), (1, "w"), (2, "z")]
    t.grow(5)
    assert t.capacity == 5 and t.alloc("v") == 3


# ---------------------------------------------------------------------------
# Acceptance: join/leave without recompilation, same posterior as solo
# ---------------------------------------------------------------------------
def test_join_leave_no_recompile_and_bit_equal_solo(setup):
    """5 ring sessions with mixed budgets flow through a 3-slot fleet:
    the slice fn traces ONCE, and every session's final phi matches a
    solo vb_run of its own length to rounding."""
    mdl, adj, W, datasets = setup
    topo = engine.RingDiffusion()
    budgets = [16, 24, 40, 16, 24]
    svc = VBService(slice_iters=8, max_fleet=3)
    rids = [svc.submit(VBRequest(model=mdl, data=(d.x, d.mask),
                                 topology=topo, n_iters=n),
                       arrive_at=2 if i == 4 else 0)
            for i, (d, n) in enumerate(zip(datasets, budgets))]
    st = svc.stats()
    assert st.admitted == 3 and st.queue_depth == 2   # fleet full
    out = svc.run()
    st = svc.stats()
    assert st.compiles == 1, st                        # ONE trace, ever
    assert st.admitted == 5 and st.evicted == 5
    assert st.queue_depth == 0 and st.active == 0
    for d, n, rid in zip(datasets, budgets, rids):
        s = out[rid]
        assert s.done and s.evicted and s.t == n
        solo = engine.run_vb(mdl, (d.x, d.mask), topo, n_iters=n)
        assert_same_posterior(s.phi, solo.phi, err_msg=rid)


def test_one_trace_per_group_config(setup):
    """Two topology groups while sessions join/leave: one trace each."""
    mdl, adj, W, datasets = setup
    svc = VBService(slice_iters=6, max_fleet=2)
    for i, d in enumerate(datasets[:4]):
        svc.submit(VBRequest(
            model=mdl, data=(d.x, d.mask),
            topology=engine.RingDiffusion() if i % 2 else
            engine.FusionCenter(),
            n_iters=10 + 6 * i,
            schedule=engine.Schedule() if i % 2 else engine.ONE_SHOT))
    svc.run()
    assert len(svc._groups) == 2
    assert svc.stats().compiles == 2, svc.stats()


def test_scheduling_invariance_matmul_topologies(setup):
    """Diffusion/ADMM (matmul combines): the scheduling QUANTUM is
    bit-invisible — the same admission into the same slots driven with
    different slice lengths (different eviction boundaries, with slots
    going idle at different ticks) runs the same fleet program and gives
    bit-identical phi — and the result matches solo to rounding."""
    mdl, adj, W, datasets = setup
    budgets = [12, 18, 24]
    for topo_fn in (lambda: engine.Diffusion(W),
                    lambda: engine.ADMMConsensus(adj, adaptive_rho=True)):
        runs = []
        for slice_iters in (6, 9):
            svc = VBService(slice_iters=slice_iters, max_fleet=3)
            topo = topo_fn()
            rids = [svc.submit(VBRequest(model=mdl, data=(d.x, d.mask),
                                         topology=topo, n_iters=n))
                    for d, n in zip(datasets, budgets)]
            out = svc.run()
            assert svc.stats().compiles == 1
            runs.append([np.asarray(out[r].phi) for r in rids])
        for a, b in zip(*runs):
            np.testing.assert_array_equal(a, b)
        for d, n, a in zip(datasets, budgets, runs[0]):
            solo = engine.run_vb(mdl, (d.x, d.mask), topo_fn(), n_iters=n)
            assert_same_posterior(a, solo.phi)


# ---------------------------------------------------------------------------
# Eviction lifecycle edges (what VBService must preserve forever)
# ---------------------------------------------------------------------------
def test_extend_budget_on_converged_evicted_session(setup):
    mdl, adj, W, datasets = setup
    d = datasets[0]
    svc = VBService(slice_iters=5, max_fleet=2)
    rid = svc.submit(VBRequest(model=mdl, data=(d.x, d.mask),
                               topology=engine.RingDiffusion(),
                               n_iters=400, tol=1e-2))
    out = svc.run()
    assert out[rid].converged and out[rid].evicted
    t_conv = out[rid].t
    svc.extend_budget(rid, 10)          # un-latch + re-queue + re-admit
    st = svc.status(rid)
    assert not st.converged and not st.done and st.budget == 410
    out = svc.run()
    # converges again at the same delta (state was frozen bit-exactly)
    assert out[rid].converged and out[rid].t >= t_conv


def test_push_data_unlatches_finished_session(setup):
    mdl, adj, W, datasets = setup
    d = datasets[1]
    mask = d.mask.at[:, -4:].set(0.0)           # room for arrivals
    svc = VBService(slice_iters=5, max_fleet=2)
    rid = svc.submit(VBRequest(model=mdl, data=(d.x, mask),
                               topology=engine.RingDiffusion(),
                               n_iters=300, tol=1e-2))
    out = svc.run()
    assert out[rid].converged and out[rid].evicted
    phi_before = np.asarray(out[rid].phi)
    svc.push_data(rid, node=1,
                  points=np.random.default_rng(0).normal(size=(3, D)))
    st = svc.status(rid)
    assert not st.converged and not st.done      # back in the queue
    out = svc.run()
    assert out[rid].done
    assert not np.allclose(phi_before, np.asarray(out[rid].phi))


def test_status_and_save_on_evicted_slot(setup, tmp_path):
    """An evicted session stays fully observable and checkpointable,
    and its slot is already recycled by a later arrival."""
    mdl, adj, W, datasets = setup
    svc = VBService(slice_iters=4, max_fleet=1)
    topo = engine.RingDiffusion()
    r0 = svc.submit(VBRequest(model=mdl, data=(datasets[0].x,
                                               datasets[0].mask),
                              topology=topo, n_iters=8))
    r1 = svc.submit(VBRequest(model=mdl, data=(datasets[1].x,
                                               datasets[1].mask),
                              topology=topo, n_iters=8))
    svc.step_slice()
    svc.step_slice()                    # r0 done+evicted, r1 admitted
    st0 = svc.status(r0)
    assert st0.evicted and st0.done and st0.t == 8
    path = svc.save_session(r0, os.path.join(tmp_path, "evicted.npz"))
    svc_b = VBService(slice_iters=4)
    rb = svc_b.submit(VBRequest(model=mdl,
                                data=(datasets[0].x, datasets[0].mask),
                                topology=topo, n_iters=8),
                      restore_from=path)
    stb = svc_b.status(rb)              # restored-finished: retired as-is
    assert stb.done and stb.t == 8 and stb.evicted
    np.testing.assert_array_equal(np.asarray(st0.phi), np.asarray(stb.phi))
    out = svc.run()
    assert out[r1].done and out[r1].t == 8


def test_async_checkpoints_and_background_thread(setup, tmp_path):
    mdl, adj, W, datasets = setup
    ckpt_dir = os.path.join(tmp_path, "auto")
    svc = VBService(slice_iters=5, max_fleet=2, ckpt_dir=ckpt_dir,
                    ckpt_every=1)
    svc.start()                         # background scheduler thread
    rids = [svc.submit(VBRequest(model=mdl, data=(d.x, d.mask),
                                 topology=engine.RingDiffusion(),
                                 n_iters=20)) for d in datasets[:3]]
    svc.drain()
    svc.stop()
    stats = svc.stats()
    assert stats.checkpoints > 0
    for rid in rids:
        st = svc.status(rid)
        assert st.done and st.t == 20 and st.latency_s > 0.0
        assert os.path.exists(os.path.join(ckpt_dir, f"{rid}.npz"))
    # an explicitly-async save lands after flush and restores bit-exactly
    path = svc.save_session(rids[0], os.path.join(tmp_path, "a.npz"),
                            wait=False)
    svc.driver.flush_checkpoints()
    restored = ckpt.restore(path, svc.driver._finished[rids[0]]["record"])
    np.testing.assert_array_equal(np.asarray(restored["phi"]),
                                  np.asarray(svc.status(rids[0]).phi))


def test_padding_waste_accounting(setup):
    """ROADMAP item 1 groundwork: a half-empty fixed fleet reports its
    idle-masked slot fraction."""
    mdl, adj, W, datasets = setup
    svc = VBService(slice_iters=5, max_fleet=4)
    d = datasets[0]
    svc.submit(VBRequest(model=mdl, data=(d.x, d.mask),
                         topology=engine.RingDiffusion(), n_iters=10))
    svc.run()
    st = svc.stats()
    assert st.occupancy == pytest.approx(0.25)      # 1 of 4 slots working
    assert st.padding_waste == pytest.approx(0.75)
    assert st.padding_waste == pytest.approx(1.0 - st.occupancy)
