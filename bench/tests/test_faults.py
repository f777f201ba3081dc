"""The comparison that decides `correct` catches the faults a cell can
have: each test drives a whole run of a cell, shrunk to run on the CPU,
with the program broken underneath, and sees `correct` come out false.
The sound run beside them comes out true.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests/test_faults.py

The harness's look for a chip is skipped (`require_chips=0`); on the CPU
the program runs in float64 and the fused kernel in interpret mode.
"""
import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness  # noqa: E402

CELLS = ["coil20_dsvb_fleet_closed", "paper_mixed_fleet_poisson"]


def small(workload: str) -> dict:
    """The cell at a size a CPU test can hold: same code, same traffic
    shape, fewer nodes, points and sessions."""
    cell = harness.load_cell(workload)
    c, tr = cell["config"], cell["traffic"]
    if c["data"] == "coil20":
        c.update(K=3, D=4, nodes=6, points_per_node=[64], node_block=3)
        tr.update(tenants=2, max_fleet=2, first_budgets=[20, 30], pool=2,
                  sample=2, budgets={"30": 1.0})
    else:
        c.update(nodes=8, points_per_node=[12, 20])
        tr.update(max_fleet=4, pool=4, sample=4, rate_per_s=4.0,
                  budgets={"50": 0.5, "100": 0.5})
    cell["limits"]["sessions_compared"] = tr["sample"]
    return cell


def run(workload: str, seed: int = 2 ** 31 + 77) -> dict:
    return harness.run(workload, seed, 3.0, False,
                       t_start=time.perf_counter(), cell=small(workload),
                       require_chips=0)


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(workload):
    res = run(workload)
    assert res["correct"], res["checks"]
    assert not res["compiles_in_window"]


def _state_unchanged(monkeypatch):
    from repro.core import engine
    real = engine.session_step_fn

    def broken(session, **kw):
        fn = real(session, **kw)

        def step(data, phi, carry, st, t, hyper=None):
            _, carry2, st2, diag = fn(data, phi, carry, st, t, hyper)
            return phi, carry2, st2, diag
        return step
    monkeypatch.setattr(engine, "session_step_fn", broken)


def _half_the_points(monkeypatch):
    """Each node's E-step sees the first half of its points, weighted
    twice: the mean over the rest."""
    from repro.core import model
    real = model.GMMModel.local_optimum

    def broken(self, data, phi_nodes, replication):
        x, mask = data
        half = x.shape[1] // 2
        return real(self, (x[:, :half], 2.0 * mask[:, :half]), phi_nodes,
                    replication)
    monkeypatch.setattr(model.GMMModel, "local_optimum", broken)


def _answer_altered(monkeypatch):
    """One coordinate of every finished session's posterior moves by 1%
    of its block's scale as the driver hands the answer out."""
    from repro.serving import driver
    real = driver.FleetGroup.evict

    def broken(self, slot):
        rec = real(self, slot)
        phi = rec["phi"]
        rec["phi"] = phi.at[0, 0].add(0.01 * abs(phi[:, 0]).max())
        return rec
    monkeypatch.setattr(driver.FleetGroup, "evict", broken)


FAULTS = {"state_unchanged": _state_unchanged,
          "half_the_points": _half_the_points,
          "answer_altered": _answer_altered}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("workload", CELLS)
def test_fault_is_not_correct(workload, fault, monkeypatch):
    FAULTS[fault](monkeypatch)
    res = run(workload)
    assert not res["correct"], res["checks"]
    gaps = [g for _, g in res["gaps"]]
    assert gaps and np.nanmax(gaps) > 0.0


def test_traced_run_traces_the_last_part_of_the_window():
    """With `trace_seconds`, the profiler starts once that much of the
    window is left, and the run is still whole and correct."""
    from bench import trace
    cell = small("paper_mixed_fleet_poisson")
    cell["traffic"]["trace_seconds"] = 1.0
    res = harness.run("paper_mixed_fleet_poisson", 2 ** 31 + 78, 3.0, True,
                      t_start=time.perf_counter(), cell=cell,
                      require_chips=0)
    assert res["correct"], res["checks"]
    lo, hi = trace.window(res["trace"])
    assert 0.9 <= (hi - lo) / 1e9 < res["window_s"] - 1.0
