"""`bench/trace.py` on a hand-made trace whose answers are known, and on a
small trace recorded on a TPU v5e (`trace_small.json`)."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from bench import trace  # noqa: E402

MS = 1_000_000


def hand_trace():
    """A 100 ms window; on the device: a loop 8-72 ms around a kernel
    10-30 ms, an overlapping op 25-40 ms and a kernel 60-70 ms, and an op
    that starts before the window.  The host ticks 0-45 and waits 45-100."""
    dev = [("%while.5 = (s32[]) while(...)", 8 * MS, 72 * MS),
           ("op.pre", -5 * MS, 5 * MS), ("%gmm_estep_nodes.1 = f32[8] custom-call(), custom_call_target=\"tpu_custom_call\"", 10 * MS, 30 * MS),
           ("fusion.2", 25 * MS, 40 * MS), ("%gmm_estep_nodes.1 = f32[8] custom-call(), custom_call_target=\"tpu_custom_call\"", 60 * MS,
                                             70 * MS),
           ("late", 150 * MS, 160 * MS)]
    host = [("main", "bench/window", 0, 100 * MS),
            ("main", "bench/tick", 0, 45 * MS),
            ("main", "bench/sleep", 45 * MS, 100 * MS),
            ("py", "PjitFunction(slice_fn)", 42 * MS, 58 * MS)]
    # the slice program 8-72 ms, half of one -40-0 ms, and an admission copy
    modules = [("jit_slice_fn(123)", 8 * MS, 72 * MS),
               ("jit_slice_fn(123)", -40 * MS, 40 * MS),
               ("jit_scatter(9)", 80 * MS, 81 * MS)]
    return {"devices": {"/device:TPU:0": dev},
            "modules": {"/device:TPU:0": modules}, "host": host}


def test_window_busy_and_idle():
    t = hand_trace()
    assert trace.window(t) == (0, 100 * MS)
    # busy: 0-5, and 8-72 under the loop
    assert trace.union(trace.clip(t["devices"]["/device:TPU:0"], 0,
                                  100 * MS)) == [(0, 5 * MS),
                                                 (8 * MS, 72 * MS)]
    assert trace.busy_s(t) == pytest.approx(0.069)
    assert trace.idle_share(t) == pytest.approx(0.31)


def test_kernel_time_by_name():
    secs, n = trace.op_seconds(
        hand_trace(), lambda name: name.startswith("%gmm_estep_nodes."))
    assert (secs, n) == (pytest.approx(0.030), 2)


def test_module_runs_count_the_share_inside_the_window():
    runs = trace.module_runs(hand_trace(),
                             lambda n: n.startswith("jit_slice_fn("))
    assert runs == pytest.approx(1.5)
    assert trace.module_runs({**hand_trace(), "modules": {}},
                             lambda n: True) == 0.0


def _reader(name):
    sys.path.insert(0, os.path.dirname(HERE))
    import run
    return lambda ctx: run.read_metric(name, ctx)


def test_metric_readers_on_the_hand_trace():
    from bench import peaks, work
    config = dict(nodes=4, points_per_node=[8], K=3, D=2)
    traffic = dict(max_fleet=2, slice_iters=5)
    ctx = dict(trace=hand_trace(), spans=[], config=config, traffic=traffic,
               peaks=peaks.peaks("TPU v5 lite"))
    # 1.5 slices x 5 iterations x 2 slots of one session-iteration's work,
    # over the 0.1 s window at the bf16 peak
    want = (1.5 * 5 * 2 * work.step_flops(4, 8, 3, 2)
            / (0.1 * ctx["peaks"]["flops_bf16"]) * 100.0)
    assert _reader("step_mfu")(ctx) == pytest.approx(want)
    # one reader serves both cells' names
    for name in ("device_idle_share.fleet", "device_idle_share.poisson"):
        assert _reader(name)(ctx) == pytest.approx(31.0)
    no_kernel = {**hand_trace(), "devices": {"/device:TPU:0": []},
                 "modules": {}}
    assert _reader("estep_roofline")(dict(ctx, trace=no_kernel)) is None
    assert _reader("step_mfu")(dict(ctx, trace=no_kernel)) is None


def test_idle_gaps_and_labels():
    gaps = trace.idle_gaps(hand_trace())
    # 72-100 (28 ms, host sleeping), 5-8 (3 ms, ticking)
    assert [g[1] for g in gaps] == pytest.approx([0.028, 0.003])
    assert [g[0] for g in gaps] == ["bench/sleep", "bench/tick"]


def test_breakdown_top_ops():
    top = trace.breakdown(hand_trace())["device_ops"]
    assert top[0] == ["%gmm_estep_nodes.1 tpu_custom_call", pytest.approx(0.030)]
    assert [name for name, _ in top] == ["%gmm_estep_nodes.1 tpu_custom_call", "fusion.2",
                                         "op.pre"]


RECORDED = os.path.join(HERE, "trace_small.json")


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no recorded trace")
def test_recorded_trace():
    with open(RECORDED) as f:
        rec = json.load(f)
    t = {"devices": {k: [tuple(e) for e in v]
                     for k, v in rec["devices"].items()},
         "host": [tuple(e) for e in rec["host"]]}
    # expectations from a 100 ns timeline of the same events, made
    # when the trace was recorded, and the run's own count of kernel calls
    exp = rec["expect"]
    assert trace.busy_s(t) == pytest.approx(exp["busy_s"], abs=5e-4)
    assert trace.idle_share(t) == pytest.approx(exp["idle_share"], abs=2e-4)
    secs, n = trace.op_seconds(t, lambda name: name.startswith(
        exp["kernel"] + "."))
    assert n == exp["kernel_calls"]
    assert secs == pytest.approx(exp["kernel_s"], abs=1e-5)
    assert 0.0 < trace.busy_s(t) <= (trace.window(t)[1]
                                     - trace.window(t)[0]) / 1e9
