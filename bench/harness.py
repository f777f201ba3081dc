"""One run of one cell: set-up, the measured window, the comparison with
the plain reference, and the metrics.

A cell is an entry of `workloads` in BENCHMARK.json.  Its configuration
(`bench/configs/<config>.json`), its traffic mix
(`bench/traffic/<mix>.json`), its limits (`bench/limits/<cell>.json`) and
each per-layer metric (`bench/metrics/<metric>.py`) are files of their
own, found by name; nothing here names a cell.
"""
from __future__ import annotations

import contextlib
import gc
import json
import os
import shutil
import sys
import time

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
TRACE_DIR = os.path.join(ROOT, ".bench", "trace")
# a session due in the window is waited for up to a minute past its close
DRAIN_S = 60.0


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def read_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(workload: str) -> dict:
    spec = read_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    return dict(spec=spec, workload=w,
                config=read_json(ROOT, conf["file"]),
                traffic=read_json(BENCH, "traffic", w["traffic"] + ".json"),
                limits=read_json(BENCH, "limits", workload + ".json"))


class CompileLog:
    """Backend compiles (a persistent-cache hit counts, with its
    retrieval time) and cache hits and writes, from `jax.monitoring`."""

    def __init__(self):
        import jax
        self.compiles = []          # (perf_counter, fun_name, seconds)
        self.hits = 0
        self.writes = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles.append((time.perf_counter(),
                                  kw.get("fun_name", "?"), duration))

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.writes += 1

    def between(self, t0, t1) -> list:
        return [c for c in self.compiles if t0 <= c[0] <= t1]


# ---------------------------------------------------------------------------
# inputs
# ---------------------------------------------------------------------------
class Inputs:
    """Everything a run hands the program, made from the seed."""

    def __init__(self, cell: dict, seed: int, dtype):
        import jax
        import jax.numpy as jnp

        from bench import data as data_lib
        from repro.core import engine, expfam
        from repro.core import model as model_lib

        conf, traffic = cell["config"], cell["traffic"]
        self.conf, self.traffic, self.seed = conf, traffic, seed
        K, D, N = conf["K"], conf["D"], conf["nodes"]
        self.K, self.D, self.N = K, D, N
        self.prior = data_lib.noninformative_prior(K, D, dtype=dtype,
                                                   **conf["prior"])
        # the network is part of the deployment, not of the run: the
        # program compiles its weights into the step, so a graph drawn
        # from the run's seed would compile anew in every run
        g = conf["graph"]
        self.adj = data_lib.geometric_graph(N, g["seed"], side=g["side"],
                                            radius=g["radius"])
        self.weights = data_lib.nearest_neighbor_weights(self.adj)

        from bench import loadgen
        sizes = loadgen.pool_sizes(traffic, conf)
        self.pool = [None] * len(sizes)
        self.centres = [None] * len(sizes)
        k = data_lib.key(seed, 0)
        for size in sorted(set(sizes)):
            idx = [i for i, s in enumerate(sizes) if s == size]
            if conf["data"] == "coil20":
                x, mask, centres = data_lib.coil20_pool(
                    jax.random.fold_in(k, size), count=len(idx), n_nodes=N,
                    n_points=size, K=K, D=D, dtype=dtype)
                for j, i in enumerate(idx):
                    self.centres[i] = centres[j]
            else:
                x, mask = data_lib.paper_pool(
                    jax.random.fold_in(k, size), count=len(idx), n_nodes=N,
                    n_points=size, free_slots=conf["free_slots"],
                    dtype=dtype)
            for j, i in enumerate(idx):
                self.pool[i] = (x[j], mask[j])
            del x, mask
        jax.block_until_ready(self.pool)

        # the program's own containers, holding the inputs above
        p = self.prior
        prog_prior = expfam.GMMPosterior(alpha=p["alpha"], m=p["m"],
                                         beta=p["beta"], W=p["W"],
                                         nu=p["nu"])
        self.model = model_lib.GMMModel(prog_prior, K, D,
                                        backend=backend(traffic["backend"]))
        self.topologies = {
            "dsvb": engine.Diffusion(jnp.asarray(self.weights, dtype)),
            "admm": engine.ADMMConsensus(jnp.asarray(self.adj, dtype),
                                         adaptive_rho=True)}

    def init_phi(self, spec):
        import jax.numpy as jnp

        from bench import data as data_lib
        k = data_lib.key(self.seed, 2, spec["index"])
        init = self.conf["init"]
        if init["kind"] == "centres":
            phi = data_lib.centred_init(
                k, self.centres[spec["entry"]], self.prior,
                float(init["noise"]), K=self.K, D=self.D)
        else:
            phi = data_lib.perturbed_init(k, self.pool[spec["entry"]][0],
                                          self.prior, K=self.K, D=self.D)
        return jnp.broadcast_to(phi, (self.N, phi.shape[0]))

    def request(self, spec, init=None):
        """The session as the program takes it; it runs its whole budget
        (no early stop)."""
        from repro.core import engine
        from repro.serving.vb_service import VBRequest
        schedule = (engine.Schedule(tau=spec["tau"]) if spec["rule"] == "dsvb"
                    else engine.Schedule())
        return VBRequest(model=self.model, data=self.pool[spec["entry"]],
                         topology=self.topologies[spec["rule"]],
                         schedule=schedule, n_iters=spec["budget"],
                         init_phi=self.init_phi(spec) if init is None
                         else init)


def backend(spec):
    """The compute backend a mix names: a name the program resolves, or
    {"name": "fused", "accum_dtype": ...}, a fused backend whose precision
    policy spells its dtype as a NumPy dtype."""
    if isinstance(spec, str):
        return spec
    from repro.core import backends
    policy = backends.PrecisionPolicy(accum_dtype=np.dtype(spec["accum_dtype"]))
    return backends.FusedBackend(precision=policy)


def _warm_specs(inp: Inputs) -> list:
    """One session of each (rule, tau, points per node) the mix can send,
    with a budget of one slice."""
    from bench import loadgen
    tr = inp.traffic
    pool = loadgen.pool_sizes(tr, inp.conf)
    return [dict(index=1_000_000 + i, tenant=None, due=None,
                 budget=tr["slice_iters"], rule=rule, tau=tau,
                 size=size, entry=pool.index(size))
            for i, ((rule, tau), size) in enumerate(
                (k, size) for ks in loadgen.kinds(tr) for k in ks
                for size in sorted(set(pool)))]


class Profile:
    """The profiler over the last `seconds` of the window.  The loop calls
    it with the time into the window; once `lead` seconds have passed it
    starts the profiler, the program's telemetry spans and the
    "bench/window" annotation that the trace's readers take as the
    window, so that a mix whose fleet fills slowly is traced full."""

    def __init__(self, window_s: float, seconds: float):
        self.lead = max(0.0, window_s - seconds)
        self.ctx = None

    def __call__(self, now: float) -> None:
        if self.ctx is not None or now < self.lead:
            return
        import jax
        from bench import trace as trace_lib
        from repro import telemetry
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        os.makedirs(TRACE_DIR)
        telemetry.enable()
        telemetry.reset()
        jax.profiler.start_trace(TRACE_DIR)
        self.ctx = jax.profiler.TraceAnnotation(trace_lib.WINDOW)
        self.ctx.__enter__()

    def stop(self) -> list:
        """Stops the profiler; returns the telemetry spans."""
        import jax
        from repro import telemetry
        self.ctx.__exit__(None, None, None)
        jax.profiler.stop_trace()
        spans = list(telemetry.tracer().events)
        telemetry.disable()
        return spans


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------
def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, cell: dict | None = None,
        require_chips: int | None = None) -> dict:
    """Run one cell once; returns the result line's fields plus the
    numbers the earlier lines print.  `cell` replaces the files found by
    name (tests run small copies); `require_chips` None takes the cell's."""
    from repro import runtime

    runtime.use_compile_cache()
    import jax

    cell = cell or load_cell(workload)
    chips = cell["workload"]["chips"] if require_chips is None \
        else require_chips
    devices = jax.devices()
    if chips and (devices[0].platform != "tpu" or len(devices) < chips):
        raise SystemExit(f"bench: the cell needs {chips} TPU chip(s); JAX "
                         f"found {len(devices)} {devices[0].platform} "
                         "device(s)")
    dtype = runtime.use_platform_precision()
    clock = CompileLog()

    from bench import loadgen
    from repro.serving.driver import VBDriver

    tr = cell["traffic"]
    inp = Inputs(cell, seed, dtype)
    driver = VBDriver(max_fleet=tr["max_fleet"], slice_iters=tr["slice_iters"])

    # warm every shape the window uses: submit, admit, the slice, the
    # flag sync, evict and the status reads
    warm = [driver.submit(inp.request(s)) for s in _warm_specs(inp)]
    for rid in warm:
        driver.status(rid)
    driver.drain()
    for rid in warm:
        driver.status(rid)
    if tr["loop"] == "open":
        sessions = loadgen.open_sessions(tr, inp.conf, seed, seconds)
        inits = {s["index"]: inp.init_phi(s) for s in sessions}
        jax.block_until_ready(inits)

        def submit(spec):
            return driver.submit(inp.request(spec, inits[spec["index"]]))
    else:
        tenants = loadgen.ClosedTenants(tr, inp.conf, seed)

        def submit(spec):
            return driver.submit(inp.request(spec))
    del warm
    gc.collect()

    if trace:
        # a mix of many small operations traces the last part of its
        # window, once its fleet has filled, so that reading the trace
        # stays within a run's time
        profile = Profile(seconds, float(tr.get("trace_seconds", seconds)))
        annotate = jax.profiler.TraceAnnotation
    else:
        profile = lambda now: None
        annotate = lambda name: contextlib.nullcontext()
    t_setup = time.perf_counter()
    setup_s = t_setup - t_start
    if tr["loop"] == "open":
        win = loadgen.open_loop(driver, sessions, submit, seconds, annotate,
                                profile)
    else:
        win = loadgen.closed_loop(driver, tenants, submit, seconds, annotate,
                                  profile)
    if trace:
        spans = profile.stop()
    if tr["loop"] == "open":
        loadgen.drain(driver, win, DRAIN_S)
    in_window = clock.between(win["start"], win["end"])
    dev = devices[0]
    memory_peak = int((dev.memory_stats() or {}).get("peak_bytes_in_use", 0))
    stats = driver.stats()

    recs = win["records"]
    finished = [r for r in recs if r["finish"] is not None]
    out = dict(setup_s=setup_s, window_s=win["seconds"], records=recs,
               compiles_in_window=in_window, stats=stats,
               memory_peak_bytes=memory_peak, compile_log=clock,
               device=dict(platform=dev.platform, kind=dev.device_kind,
                           count=len(devices)))
    if tr["loop"] == "open":
        # a session that never finished counts as failed, and its
        # latency as the wait until the harness gave up on it
        lat = [(r["finish"] if r["finish"] is not None else win["gave_up"])
               - r["spec"]["due"] for r in recs]
        out.update(attempted=len(recs), failed=len(recs) - len(finished),
                   latency=lat,
                   lateness=[r["called"] - r["spec"]["due"] for r in recs])
    else:
        out.update(attempted=len(finished), failed=0, iters=win["iters"])

    # the answers: a sample drawn from the seed, fetched before the
    # program's state is freed
    sample = pick_sample(finished, tr["sample"], seed)
    answers = [(r, np.asarray(driver.status(r["rid"]).phi),
                driver.status(r["rid"]).t) for r in sample]
    del driver, submit
    gc.collect()
    out["checks"], out["gaps"] = check(inp, answers, cell["limits"],
                                       failed=out["failed"])
    out["correct"] = all(v["value"] <= v["limit"]
                         for v in out["checks"].values())
    if trace:
        from bench import trace as trace_lib
        tr_events = trace_lib.load(TRACE_DIR)
        out["trace"] = tr_events
        out["spans"] = spans
    out["inputs"] = inp
    return out


def pick_sample(finished: list, n: int, seed: int) -> list:
    """`n` finished sessions drawn from the seed, the longest of each
    combine rule among them."""
    from bench import data as data_lib
    if not finished:
        return []
    chosen = []
    for rule in sorted({r["spec"]["rule"] for r in finished}):
        of = [r for r in finished if r["spec"]["rule"] == rule]
        chosen.append(max(of, key=lambda r: (r["spec"]["budget"],
                                             -r["spec"]["index"])))
    rest = [r for r in finished if r not in chosen]
    g = data_lib.rng(seed, 5)
    take = g.permutation(len(rest))[:max(0, n - len(chosen))]
    return chosen + [rest[i] for i in sorted(take)]


def reference_phi(inp: Inputs, spec, precision="highest"):
    """The plain reference's final posterior of one session."""
    import jax.numpy as jnp

    from bench.reference import gmm_vb
    x, mask = inp.pool[spec["entry"]]
    graph = inp.weights if spec["rule"] == "dsvb" else inp.adj
    return np.asarray(gmm_vb.run(
        x, mask, inp.init_phi(spec), inp.prior,
        jnp.asarray(graph, x.dtype), jnp.asarray(spec["tau"], x.dtype),
        spec["budget"], rule=spec["rule"], K=inp.K, D=inp.D,
        node_block=inp.conf["node_block"], precision=precision))


def check(inp: Inputs, answers: list, limits: dict, *, failed: int,
          wants: list | None = None) -> tuple:
    """{name: {"value", "limit"}} of every number compared, and the gap of
    each sampled session.  `answers` are (record, phi, iterations done);
    `wants` the reference's posteriors, where already computed."""
    from bench import compare
    from bench.reference import gmm_vb

    names = gmm_vb.block_names(inp.K, inp.D)
    gaps = []
    for i, (rec, phi, t) in enumerate(answers):
        spec = rec["spec"]
        want = reference_phi(inp, spec) if wants is None else wants[i]
        gap = compare.phi_gap(phi, want, names) if t == spec["budget"] \
            else compare.NO_ANSWER
        gaps.append((spec, gap))
    checks = {}
    for rule in sorted({s["rule"] for s, _ in gaps}):
        name = f"phi_gap.{rule}"
        checks[name] = dict(value=max(g for s, g in gaps
                                      if s["rule"] == rule),
                            limit=limits[name])
    checks["sessions_short"] = dict(
        value=max(0, limits["sessions_compared"] - len(gaps)), limit=0)
    checks["sessions_lost"] = dict(value=failed, limit=0)
    return checks, gaps
