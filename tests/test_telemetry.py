"""Telemetry subsystem tests (src/repro/telemetry/ + tools/bench_gate.py).

The two tentpole acceptance pins:

* **disabled is invisible** — with telemetry off (and with host
  telemetry ON but device taps off) the engine step jaxpr is
  byte-identical to the uninstrumented trace, and a driver run keeps
  its compiles==1 contract;
* **enabled is complete** — a traced driver run yields a loadable
  Chrome trace with slice/compile/checkpoint spans and a metrics
  snapshot carrying occupancy / queue-depth / padding-waste gauges and
  eviction counters.

Plus the satellites: CheckpointWriter failure isolation (a failing
write must not kill the scheduler; it increments an error counter that
surfaces in DriverStats), the backend-fallback warn-once bugfix, and
the bench gate's pass-on-baseline / fail-on-degraded behavior.
"""
import importlib.util
import json
import os
import re
import threading
import warnings

import jax
import numpy as np
import pytest

from repro import telemetry
from repro.core import engine, expfam, network
from repro.core import model as model_lib
from repro.data import stream as stream_lib
from repro.data import synthetic
from repro.serving import driver as drv
from repro.serving.vb_service import VBRequest, VBService
from repro.telemetry import taps

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True, scope="module")
def _x64():
    jax.config.update("jax_enable_x64", True)
    yield
    jax.config.update("jax_enable_x64", False)


@pytest.fixture(autouse=True)
def _clean_telemetry():
    """Telemetry state is process-global: every test starts and ends
    disabled and empty so nothing leaks across tests (or suites)."""
    telemetry.disable()
    taps.disable()
    telemetry.reset()
    yield
    telemetry.disable()
    taps.disable()
    telemetry.reset()


K, D, N_NODES = 3, 2, 8


@pytest.fixture(scope="module")
def setup():
    prior = expfam.noninformative_prior(K, D, beta0=0.1, w0_scale=10.0)
    mdl = model_lib.GMMModel(prior, K, D)
    adj, _ = network.random_geometric_graph(N_NODES, seed=4)
    W = network.nearest_neighbor_weights(adj)
    data = synthetic.paper_synthetic(n_nodes=N_NODES, n_per_node=10,
                                     seed=0)
    return mdl, adj, W, data


# ---------------------------------------------------------------------------
# MetricsRegistry
# ---------------------------------------------------------------------------
def test_registry_counter_gauge_histogram():
    reg = telemetry.MetricsRegistry()
    reg.counter("req_total", route="vb").inc()
    reg.counter("req_total", route="vb").inc(2)
    reg.counter("req_total", route="lm").inc()
    reg.gauge("depth").set(7)
    reg.histogram("lat_s", bounds=(0.1, 1.0)).observe(0.05)
    reg.histogram("lat_s", bounds=(0.1, 1.0)).observe(5.0)
    rows = {(r["name"], tuple(sorted(r["labels"].items()))): r
            for r in reg.snapshot()}
    assert rows[("req_total", (("route", "vb"),))]["value"] == 3.0
    assert rows[("req_total", (("route", "lm"),))]["value"] == 1.0
    assert rows[("depth", ())]["value"] == 7.0
    hist = rows[("lat_s", ())]
    assert hist["count"] == 2 and hist["buckets"]["+Inf"] == 1

    # JSON-lines: one parseable object per series
    lines = [json.loads(line) for line in reg.to_jsonl().splitlines()]
    assert len(lines) == len(reg.snapshot())

    prom = reg.to_prometheus()
    assert 'req_total{route="vb"} 3' in prom
    assert "# TYPE lat_s histogram" in prom
    assert 'lat_s_bucket{le="+Inf"} 2' in prom

    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("req_total", route="vb")


def test_registry_thread_safety():
    reg = telemetry.MetricsRegistry()

    def work():
        for _ in range(1000):
            reg.counter("n").inc()

    threads = [threading.Thread(target=work) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert reg.counter("n").value == 8000.0


def test_module_helpers_noop_when_disabled():
    telemetry.inc("x_total")
    telemetry.set_gauge("g", 1.0)
    telemetry.observe("h", 0.5)
    telemetry.instant("ev")
    with telemetry.span("s"):
        pass
    assert len(telemetry.registry()) == 0
    assert len(telemetry.tracer()) == 0
    with telemetry.enabled_scope():
        telemetry.inc("x_total")
        with telemetry.span("s"):
            pass
    assert len(telemetry.registry()) == 1
    assert telemetry.tracer().span_names() == ["s"]


# ---------------------------------------------------------------------------
# Tracer
# ---------------------------------------------------------------------------
def test_tracer_chrome_export_nesting(tmp_path):
    tr = telemetry.Tracer()
    with tr.span("outer", k=8):
        with tr.span("inner"):
            tr.instant("mark", rid="s0")
    path = tr.export_chrome_trace(str(tmp_path / "trace.json"))
    doc = json.load(open(path))
    events = doc["traceEvents"]
    assert {e["name"] for e in events} == {"outer", "inner", "mark"}
    by_name = {e["name"]: e for e in events}
    outer, inner = by_name["outer"], by_name["inner"]
    assert outer["ph"] == "X" and by_name["mark"]["ph"] == "i"
    # nesting = time containment on one tid
    assert outer["tid"] == inner["tid"]
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-6
    assert outer["args"] == {"k": 8}


# ---------------------------------------------------------------------------
# Device taps + the jaxpr pin (tentpole acceptance)
# ---------------------------------------------------------------------------
def _step_jaxpr(mdl, data, topo, **kw):
    """The session-step jaxpr string.  `session_step_fn` returns a fresh
    closure per call, so each invocation is a fresh trace — no trace
    cache can mask a gating bug."""
    state = engine.vb_init(mdl, data, topo, **kw)
    fn = engine.session_step_fn(state.session)
    return str(jax.make_jaxpr(fn)(state.session.data, state.phi,
                                  state.carry, state.stream, state.t))


def _scan_jaxpr(mdl, data, topo, n_iters=3, **kw):
    """The vb_run scan jaxpr (the path carrying the kl/msd/rho taps);
    a fresh closure per call, same cache-safety argument as above."""
    state = engine.vb_init(mdl, data, topo, **kw)
    ses = state.session

    def fn(phi, carry, st, t):
        return engine._scan_steps(
            ses.model, ses.data, ses.topology, ses.schedule,
            ses.replication, ses.ref_phi, n_iters, phi, carry, t0=t,
            stream0=st, diagnostics=ses.diagnostics,
            metric_nodes=ses.metric_nodes, minibatch=ses.minibatch)

    return str(jax.make_jaxpr(fn)(state.phi, state.carry, state.stream,
                                  state.t))


def test_disabled_and_host_enabled_jaxprs_identical(setup):
    """The pin: neither the default-off state nor host-only telemetry
    may change the compiled program; only taps.enable() may (and then
    io_callback must actually appear where a tap site exists)."""
    mdl, adj, W, data = setup
    spec = stream_lib.MinibatchSpec(4, seed=1, control_variate="svrg")
    for jaxpr_of, topo, kw in (
            (_step_jaxpr, engine.Diffusion(W), {"minibatch": spec}),
            (_step_jaxpr, engine.ADMMConsensus(adj), {}),
            (_scan_jaxpr, engine.ADMMConsensus(adj), {}),
            (_scan_jaxpr, engine.Diffusion(W), {})):
        base = jaxpr_of(mdl, (data.x, data.mask), topo, **kw)
        with telemetry.enabled_scope():
            host_on = jaxpr_of(mdl, (data.x, data.mask), topo, **kw)
        assert host_on == base          # byte-identical
        assert "io_callback" not in base


def test_taps_insert_io_callback_where_sites_exist(setup):
    """taps.enable() inserts io_callback in every path with a tap site:
    the vb_run scan (kl/msd/rho taps) and the streaming session step
    (epoch + SVRG-anchor taps).  A plain full-batch session step has no
    tap sites, so its jaxpr stays untouched even with taps on."""
    mdl, adj, W, data = setup
    spec = stream_lib.MinibatchSpec(4, seed=1, control_variate="svrg")
    plain = _step_jaxpr(mdl, (data.x, data.mask),
                        engine.ADMMConsensus(adj))
    with taps.enabled_scope():
        assert "io_callback" in _scan_jaxpr(
            mdl, (data.x, data.mask), engine.ADMMConsensus(adj))
        assert "io_callback" in _scan_jaxpr(
            mdl, (data.x, data.mask), engine.Diffusion(W))
        assert "io_callback" in _step_jaxpr(
            mdl, (data.x, data.mask), engine.Diffusion(W),
            minibatch=spec)
        assert _step_jaxpr(mdl, (data.x, data.mask),
                           engine.ADMMConsensus(adj)) == plain


def test_tap_series_from_scan(setup):
    """Taps inside the engine scan stream per-iteration series out in
    absolute-t order (unordered io_callback + t-indexed records)."""
    mdl, adj, W, data = setup
    with taps.enabled_scope():
        state = engine.vb_init(mdl, (data.x, data.mask),
                               engine.ADMMConsensus(adj))
        state, _ = engine.vb_run(state, 6)
        state, _ = engine.vb_run(state, 6)       # resumed: absolute t
        jax.block_until_ready(state.phi)
    ts, kl = taps.series("vb/kl_mean")
    assert ts.tolist() == list(range(12))
    assert kl.shape == (12,) and np.all(np.isfinite(kl))
    ts_r, rho = taps.series("vb/admm_rho")
    assert ts_r.tolist() == list(range(12)) and np.all(rho > 0)


def test_vb_run_diag_slot_series(setup):
    """Host telemetry alone (no device taps) files the scan's own
    outputs as vb_run/* series — no recompilation, absolute-t indexed."""
    mdl, adj, W, data = setup
    with telemetry.enabled_scope():
        state = engine.vb_init(mdl, (data.x, data.mask),
                               engine.ADMMConsensus(adj, adaptive_rho=True))
        state, _ = engine.vb_run(state, 10)
        state, _ = engine.vb_run(state, 5)
    ts, kl = taps.series("vb_run/kl_mean")
    assert ts.tolist() == list(range(15)) and kl.shape == (15,)
    for name in ("vb_run/consensus_msd", "vb_run/admm_rho",
                 "vb_run/admm_primal_resid", "vb_run/admm_dual_resid"):
        ts_n, vals = taps.series(name)
        assert ts_n.tolist() == list(range(15)), name
        assert np.all(np.isfinite(vals)), name


def test_taps_record_series_ordering():
    taps.record_series("s", np.arange(6.0).reshape(3, 2),
                       ts=np.array([7, 5, 6]))
    ts, vals = taps.series("s")
    assert ts.tolist() == [5, 6, 7]
    assert vals[0].tolist() == [2.0, 3.0]        # sorted by t
    assert sorted(taps.names()) == ["s"]
    taps.clear()
    assert taps.names() == []


# ---------------------------------------------------------------------------
# Driver integration (enabled path + compile-count pin)
# ---------------------------------------------------------------------------
def _run_fleet(mdl, W, tmp_path, n_sessions=3, ckpt=True):
    svc = VBService(slice_iters=8, max_fleet=2,
                    ckpt_dir=str(tmp_path) if ckpt else None,
                    ckpt_every=2 if ckpt else 0)
    for s in range(n_sessions):
        d = synthetic.paper_synthetic(n_nodes=N_NODES, n_per_node=10,
                                      seed=s)
        svc.submit(VBRequest(model=mdl, data=(d.x, d.mask),
                             topology=engine.RingDiffusion(),
                             n_iters=16 + 8 * (s % 2)))
    svc.run()
    return svc.stats()


def test_traced_driver_run_spans_and_metrics(setup, tmp_path):
    """Enabled-path acceptance: slice/compile/checkpoint spans on the
    timeline; occupancy/queue-depth/padding-waste gauges and eviction
    counters in the snapshot; compiles stays 1 (telemetry does not
    perturb the no-recompilation contract)."""
    mdl, adj, W, data = setup
    telemetry.enable()
    st = _run_fleet(mdl, W, tmp_path)
    assert st.compiles == 1 and st.evicted == 3
    assert st.checkpoints > 0 and st.checkpoint_errors == 0

    names = set(telemetry.tracer().span_names())
    assert {"driver/slice", "driver/compile", "driver/sync",
            "driver/checkpoint", "driver/admit",
            "driver/evict"} <= names

    trace = telemetry.tracer().to_chrome()
    assert json.dumps(trace)                     # loadable
    slice_evs = [e for e in trace["traceEvents"]
                 if e["name"] == "driver/slice"]
    assert len(slice_evs) == st.slices
    assert all(e["ph"] == "X" and e["dur"] >= 0 for e in slice_evs)

    rows = {r["name"]: r for r in telemetry.snapshot()}
    for gauge in ("driver_occupancy", "driver_queue_depth",
                  "driver_padding_waste", "driver_active",
                  "driver_capacity"):
        assert gauge in rows, gauge
    assert rows["driver_evicted_total"]["value"] == 3.0
    assert rows["driver_admitted_total"]["value"] == 3.0
    assert rows["driver_checkpoints_total"]["value"] == st.checkpoints
    assert 0.0 <= rows["driver_occupancy"]["value"] <= 1.0
    prom = telemetry.to_prometheus()
    assert "driver_checkpoint_write_seconds_bucket" in prom


def test_disabled_driver_leaves_no_telemetry(setup, tmp_path):
    mdl, adj, W, data = setup
    st = _run_fleet(mdl, W, tmp_path, ckpt=False)
    assert st.compiles == 1
    assert len(telemetry.registry()) == 0
    assert len(telemetry.tracer()) == 0


# ---------------------------------------------------------------------------
# Program spans on the profiler trace, named scopes, compile counter
# ---------------------------------------------------------------------------
DRIVER_SPANS = {"driver/tick", "driver/submit", "driver/admit",
                "driver/evict", "driver/slice", "driver/sync"}


def _profiled_fleet(mdl, W, trace_dir):
    """A small driver run under the profiler; the host plane's events
    (thread, name, start_ns, end_ns) as `bench.trace.load` reads them."""
    import sys
    sys.path.insert(0, ROOT)
    from bench import trace as trace_lib

    jax.profiler.start_trace(trace_dir)
    try:
        _run_fleet(mdl, W, trace_dir, ckpt=False)
    finally:
        jax.profiler.stop_trace()
    return trace_lib.load(trace_dir)["host"]


def test_driver_spans_land_on_the_profiler_trace(setup, tmp_path):
    """Enabled spans enter a TraceAnnotation named exactly as the span
    (args become stats, not part of the name), so they sit on the xplane
    host plane on the device clock; disabled, none appear."""
    mdl, adj, W, data = setup
    with telemetry.enabled_scope():
        host = _profiled_fleet(mdl, W, str(tmp_path / "on"))
    names = {n for _, n, _, _ in host}
    assert DRIVER_SPANS <= names
    assert not any(n.startswith("driver/") and n not in DRIVER_SPANS
                   | {"driver/compile"} for n in names)
    ticks = [(th, s, e) for th, n, s, e in host if n == "driver/tick"]
    slices = [(th, s, e) for th, n, s, e in host if n == "driver/slice"]
    assert slices and all(any(th == tt and ts <= s and e <= te
                              for tt, ts, te in ticks)
                          for th, s, e in slices)
    # the Tracer still holds them, for tick_gap_ms and Chrome export
    assert DRIVER_SPANS <= set(telemetry.tracer().span_names())

    telemetry.reset()
    host = _profiled_fleet(mdl, W, str(tmp_path / "off"))
    assert not any(n.startswith("driver/") for _, n, _, _ in host)


def _slice_program_text(mdl, topo):
    """The driver's compiled slice program as HLO text; its ops carry the
    scope path in `metadata={op_name=...}`."""
    d = drv.VBDriver(slice_iters=2, max_fleet=2)
    for s in range(2):
        x = synthetic.paper_synthetic(n_nodes=N_NODES, n_per_node=10,
                                      seed=s)
        d.submit(VBRequest(model=mdl, data=(x.x, x.mask), topology=topo,
                           n_iters=4))
    g = next(iter(d._groups.values()))
    return g._slice_fn(2).lower(
        g.data, g.phi, g.carry, g.stream, g.t, g.conv, g.budget, g.tol,
        g.delta, g.hyper).compile().as_text()


@pytest.mark.parametrize("backend", ["reference", "fused"])
def test_slice_program_carries_named_scopes(setup, backend):
    mdl, adj, W, data = setup
    prior = expfam.noninformative_prior(K, D, beta0=0.1, w0_scale=10.0)
    mdl = model_lib.GMMModel(prior, K, D, backend=backend)
    # op_name paths: "jit(slice_fn)/while/body/.../vmap(vb/vbm)/jit(inv)/..."
    def scopes(topo):
        text = _slice_program_text(mdl, topo)
        return set(re.findall(r"vb/[a-z]+(?=[/)])", text))

    assert scopes(engine.Diffusion(W)) == {"vb/terms", "vb/vbe", "vb/vbm",
                                           "vb/combine"}
    assert "vb/project" in scopes(engine.ADMMConsensus(adj))


def test_compile_counter_counts_a_fresh_jit_once():
    def _tripled(x):
        return 3 * x + 1

    def _halved(x):
        return x / 2

    f, g = jax.jit(_tripled), jax.jit(_halved)
    g(np.arange(3.0))                    # disabled: not counted
    with telemetry.enabled_scope():
        f(np.arange(3.0))
        f(np.arange(3.0))
        g(np.arange(3.0))
    rows = {r["labels"]["fun"]: r["value"] for r in telemetry.snapshot()
            if r["name"] == "jax_compiles_total"}
    assert rows == {"jit(_tripled)": 1.0}
    (ev,) = [e for e in telemetry.tracer().events
             if e["name"] == "compile/jit(_tripled)"]
    assert ev["ph"] == "X" and ev["dur"] > 0
    assert ev["ts"] + ev["dur"] <= telemetry.tracer().now_us()


# ---------------------------------------------------------------------------
# CheckpointWriter failure handling (satellite)
# ---------------------------------------------------------------------------
def _blocked_dir(tmp_path) -> str:
    """A checkpoint 'directory' that is actually a regular file, so
    ckpt.save's makedirs raises deterministically on every write."""
    blocker = tmp_path / "blocker"
    blocker.write_text("not a directory")
    return str(blocker / "sub")


def test_checkpoint_writer_failure_counts_and_survives(setup, tmp_path):
    mdl, adj, W, data = setup
    w = drv.CheckpointWriter()
    bad = os.path.join(_blocked_dir(tmp_path), "x.npz")
    pending = w.submit({"t": np.int64(3)}, bad)
    with pytest.raises(OSError):
        pending.wait()
    assert w.errors == 1 and w.completed == 0
    # the daemon thread survived: a good write still lands
    good = str(tmp_path / "ok.npz")
    assert w.submit({"t": np.int64(3)}, good).wait() == good
    assert w.completed == 1 and w.errors == 1
    assert os.path.exists(good)


def test_driver_autosave_failure_does_not_kill_scheduler(setup, tmp_path):
    """Every periodic autosave fails, yet the fleet drains normally and
    the failures surface in DriverStats.checkpoint_errors (previously
    they vanished: autosaves never wait() on their futures)."""
    mdl, adj, W, data = setup
    svc = VBService(slice_iters=8, max_fleet=2,
                    ckpt_dir=_blocked_dir(tmp_path), ckpt_every=1)
    rids = []
    for s in range(3):
        d = synthetic.paper_synthetic(n_nodes=N_NODES, n_per_node=10,
                                      seed=s)
        rids.append(svc.submit(VBRequest(
            model=mdl, data=(d.x, d.mask),
            topology=engine.RingDiffusion(), n_iters=16)))
    out = svc.run()
    st = svc.stats()
    assert all(out[r].done for r in rids)        # scheduler survived
    assert st.checkpoint_errors > 0
    assert st.checkpoints == 0
    # explicit save_session(wait=True) still raises to the caller
    with pytest.raises(OSError):
        svc.save_session(rids[0],
                         os.path.join(_blocked_dir(tmp_path), "s.npz"))


def test_driver_stats_has_checkpoint_errors_default():
    """LM Engine.stats() builds DriverStats without the new field — the
    appended default must keep that call site valid."""
    st = drv.DriverStats(slices=1, compiles=1, admitted=1, evicted=0,
                         queue_depth=0, active=1, capacity=2,
                         occupancy=0.5, padding_waste=0.5, checkpoints=0)
    assert st.checkpoint_errors == 0


# ---------------------------------------------------------------------------
# Backend-fallback warn-once (satellite bugfix)
# ---------------------------------------------------------------------------
def test_backend_fallback_warns_once_and_counts():
    from repro.core import linreg

    mdl = model_lib.LinRegModel(linreg.prior(2))
    phi_star = np.stack([np.asarray(mdl.init_phi()) + 1.0,
                         np.asarray(mdl.init_phi()) - 1.0])
    telemetry.enable()
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        for _ in range(3):
            engine.vb_init(mdl, phi_star, engine.FusionCenter(),
                           backend="fused")
    fallback = [w for w in caught
                if "falling back to the reference backend"
                in str(w.message)]
    assert len(fallback) == 1                    # once per session...
    rows = {r["name"]: r for r in telemetry.snapshot()}
    assert rows["backend_fallback_total"]["value"] == 3.0  # ...all counted
    assert rows["backend_fallback_total"]["labels"]["backend"] == "fused"

    telemetry.reset()                            # new session: warns again
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        engine.vb_init(mdl, phi_star, engine.FusionCenter(),
                       backend="fused")
    assert any("falling back" in str(w.message) for w in caught)


# ---------------------------------------------------------------------------
# Bench gate
# ---------------------------------------------------------------------------
def _load_gate():
    spec = importlib.util.spec_from_file_location(
        "bench_gate", os.path.join(ROOT, "tools", "bench_gate.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_bench_gate_passes_on_committed_baseline():
    gate = _load_gate()
    baseline = gate.load(os.path.join(ROOT, "BENCH_engine.json"))
    failures, checks = gate.gate(baseline, baseline,
                                 max_ratio=gate.DEFAULT_MAX_RATIO)
    assert failures == []
    assert checks                                # something was checked


def test_bench_gate_fails_on_degraded_rows():
    gate = _load_gate()
    baseline = gate.load(os.path.join(ROOT, "BENCH_engine.json"))

    slow = json.loads(json.dumps(baseline))
    slow["results"]["vb_driver_poisson"]["us_per_call"] *= 100
    failures, _ = gate.gate(baseline, slow,
                            max_ratio=gate.DEFAULT_MAX_RATIO)
    assert any("TIMING" in f and "vb_driver_poisson" in f
               for f in failures)

    broken = json.loads(json.dumps(baseline))
    broken["results"]["vb_driver_poisson"]["derived"] = (
        broken["results"]["vb_driver_poisson"]["derived"]
        .replace("compiles=1", "compiles=5")
        .replace("speedup_vs_sync=2.4x", "speedup_vs_sync=0.9x"))
    failures, _ = gate.gate(baseline, broken,
                            max_ratio=gate.DEFAULT_MAX_RATIO)
    assert sum("DERIVED" in f for f in failures) == 2

    failed = json.loads(json.dumps(baseline))
    failed["failed"] = ["svrg_vb"]
    failures, _ = gate.gate(baseline, failed,
                            max_ratio=gate.DEFAULT_MAX_RATIO)
    assert any("bench FAILED" in f for f in failures)


def test_bench_gate_parse_derived():
    gate = _load_gate()
    d = gate.parse_derived(
        "speedup_vs_sync=2.4x compiles=1 degen_bitexact=True "
        "p50_latency_s=0.05 label=GMM/N8 bare x=")
    assert d["speedup_vs_sync"] == 2.4
    assert d["compiles"] == 1.0
    assert d["degen_bitexact"] is True
    assert d["label"] == "GMM/N8"
    assert "bare" not in d and "x" not in d
    assert gate._check_rule(2.4, ">=", 2.0)
    assert not gate._check_rule(5, "<=", 1)
    assert gate._check_rule(True, "==", True)


def test_bench_gate_empty_fresh_fails():
    gate = _load_gate()
    failures, _ = gate.gate({"results": {}}, {"results": {}},
                            max_ratio=4.0)
    assert any("nothing was gated" in f for f in failures)
