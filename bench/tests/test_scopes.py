"""`bench/scopes.py` and the readers of the program's scopes and spans
(`terms_ms_per_iter`, `vbm_ms_per_iter`, `admit_ms`, `evict_ms`) on
hand-made traces whose answers are known."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from bench import scopes  # noqa: E402

MS = 1_000_000
BODY = "jit(slice_fn)/while/body/closed_call"
TERMS = BODY + "/vmap(vb/terms)/jit(inv)/jit(solve)/lu"
VBM = BODY + "/vmap(vmap(vb/vbm))/jit(inv)/jit(solve)/lu"
PROJECT = BODY + "/vmap(vb/combine)/vb/project/vmap()/concatenate"


def hand_trace():
    """A 100 ms window.  The slice program runs -20-20 (half inside),
    20-60 and 60-100 ms: 2.5 executions.  Admissions -5-2 (starts before
    the window), 10-12 and 95-105 (cut by the window's edge); one
    eviction 20-23, nested in a tick 15-30."""
    host = [("main", "bench/window", 0, 100 * MS),
            ("main", "driver/admit", -5 * MS, 2 * MS),
            ("main", "driver/admit", 10 * MS, 12 * MS),
            ("main", "driver/admit", 95 * MS, 105 * MS),
            ("main", "driver/tick", 15 * MS, 30 * MS),
            ("main", "driver/evict", 20 * MS, 23 * MS),
            ("main", "bench/submit", 0, 12 * MS)]
    modules = [("jit_slice_fn(7)", -20 * MS, 20 * MS),
               ("jit_slice_fn(7)", 20 * MS, 60 * MS),
               ("jit_slice_fn(7)", 60 * MS, 100 * MS)]
    return {"devices": {"/device:TPU:0": []},
            "modules": {"/device:TPU:0": modules}, "host": host}


def hand_ops():
    """Scoped ops: `vb/terms` -10-10 (10 ms inside) and 30-35; `vb/vbm`
    40-52 and 50-54 (overlapping: 14 ms) and 120-130 (outside); an
    unscoped op 60-70 and a projection 70-71."""
    return {"/device:TPU:0": [(TERMS, -10 * MS, 10 * MS),
                              (TERMS, 30 * MS, 35 * MS),
                              (VBM, 40 * MS, 52 * MS),
                              (VBM, 50 * MS, 54 * MS),
                              (VBM, 120 * MS, 130 * MS),
                              (BODY + "/add", 60 * MS, 70 * MS),
                              (None, 65 * MS, 66 * MS),
                              (PROJECT, 70 * MS, 71 * MS)]}


def _reader(name, monkeypatch=None, ops=None):
    sys.path.insert(0, os.path.dirname(HERE))
    import run
    if monkeypatch is not None:
        monkeypatch.setattr(scopes, "load", lambda _dir: ops)
    return lambda ctx: run.read_metric(name, ctx)


def test_scope_of_takes_the_innermost_scope():
    assert scopes.scope_of(TERMS) == "vb/terms"
    assert scopes.scope_of(VBM) == "vb/vbm"
    assert scopes.scope_of(PROJECT) == "vb/project"
    assert scopes.scope_of(BODY + "/add") is None
    assert scopes.scope_of(None) is None
    assert scopes.scope_of("jit(f)/vb/vbe") == "vb/vbe"
    assert scopes.scope_of("jit(f)/vb/vbex/add") is None


def test_scope_seconds_clip_to_the_window():
    secs = scopes.scope_seconds(hand_ops(), 0, 100 * MS)
    assert secs["vb/terms"] == pytest.approx(0.015)
    assert secs["vb/vbm"] == pytest.approx(0.014)
    assert secs["vb/project"] == pytest.approx(0.001)
    assert secs[None] == pytest.approx(0.010)


@pytest.mark.parametrize("name,want", [("terms_ms_per_iter", 15 / 10),
                                       ("vbm_ms_per_iter", 14 / 10)])
def test_scope_readers_per_fleet_iteration(monkeypatch, name, want):
    # 2.5 executions of the slice x 4 iterations = 10 fleet iterations
    ctx = dict(trace=hand_trace(), spans=[], traffic=dict(slice_iters=4))
    assert _reader(name, monkeypatch, hand_ops())(ctx) == pytest.approx(want)


@pytest.mark.parametrize("name", ["terms_ms_per_iter", "vbm_ms_per_iter"])
def test_scope_readers_without_scopes_read_none(monkeypatch, name):
    ctx = dict(trace=hand_trace(), spans=[], traffic=dict(slice_iters=4))
    unscoped = {"/device:TPU:0": [(BODY + "/add", 0, 10 * MS),
                                  (None, 20 * MS, 30 * MS)]}
    assert _reader(name, monkeypatch, unscoped)(ctx) is None
    assert _reader(name, monkeypatch, {})(ctx) is None
    no_slice = dict(ctx, trace=dict(hand_trace(), modules={}))
    assert _reader(name, monkeypatch, hand_ops())(no_slice) is None


def test_span_readers():
    ctx = dict(trace=hand_trace(), spans=[])
    # admissions starting inside the window: 2 ms, and 10 ms whole though
    # the window's edge cuts it; the one starting before is left out
    assert _reader("admit_ms")(ctx) == pytest.approx(6.0)
    assert _reader("evict_ms")(ctx) == pytest.approx(3.0)
    empty = dict(hand_trace(), host=[("main", "bench/window", 0, 100 * MS)])
    assert _reader("admit_ms")(dict(ctx, trace=empty)) is None
    assert _reader("evict_ms")(dict(ctx, trace=empty)) is None


def test_idle_time_by_innermost_span():
    """Device busy 0-10 and 40-100: idle 10-40.  Host spans there: admit
    10-12, tick 15-30 with evict 20-23 inside; 12-15 and 30-40 no span."""
    t = hand_trace()
    t["devices"] = {"/device:TPU:0": [("%fusion.1 = f32[] fusion()", 0,
                                        10 * MS),
                                       ("%fusion.2 = f32[] fusion()",
                                        40 * MS, 100 * MS)]}
    got = scopes.idle_by_span(t)
    assert got == pytest.approx({"driver/admit": 0.002, "driver/tick": 0.012,
                                 "driver/evict": 0.003, None: 0.013})
    assert scopes.idle_gaps(t) == [["driver/tick", pytest.approx(0.030)]]
    t["host"] = [ev for ev in t["host"] if not ev[1].startswith("driver/")]
    assert scopes.idle_gaps(t) == [["bench/submit", pytest.approx(0.030)]]


# -- the HLO protos on the trace's metadata plane ---------------------------
def _varint(n):
    out = b""
    while True:
        b, n = n & 0x7F, n >> 7
        out += bytes([b | (0x80 if n else 0)])
        if not n:
            return out


def _field(num, value):
    if isinstance(value, int):
        return _varint(num << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(num << 3 | 2) + _varint(len(value)) + value


def _instruction(iid, name, op_name="", operands=()):
    out = _field(1, name) + _field(35, iid)
    if op_name:
        out += _field(7, _field(2, op_name))
    if operands:
        out += _field(36, b"".join(_varint(o) for o in operands))
    return out


def _xspace(programs):
    """An XSpace whose metadata plane holds one HloProto per program."""
    entries = b""
    for i, (prog, instrs) in enumerate(programs.items()):
        comp = _field(1, "main") + b"".join(_field(2, ins) for ins in instrs)
        module = _field(1, prog.split("(")[0]) + _field(3, comp)
        stat = _field(1, 1) + _field(6, _field(1, module))
        meta = _field(1, i) + _field(2, prog) + _field(5, stat)
        entries += _field(4, _field(1, i) + _field(2, meta))
    other = _field(1, _field(2, "/host:CPU") + _field(3, b"\x0a\x00"))
    return other + _field(1, _field(2, scopes.METADATA_PLANE) + entries)


def test_op_paths_from_the_metadata_plane():
    """A custom call without metadata takes its user's path (the LU of an
    inverse feeds the inverse's own ops), else its operand's."""
    slice_ops = [_instruction(1, "fusion.3", BODY + "/vb/combine/add"),
                 _instruction(2, "custom-call.4", "", [1]),
                 _instruction(3, "fusion.5", TERMS, [2]),
                 _instruction(4, "custom-call.6", "", [3]),
                 _instruction(5, "custom-call.7", "", [4])]
    protos = scopes.hlo_protos(_xspace({"jit_slice_fn(9)": slice_ops,
                                        "jit_scatter(2)": [
                                            _instruction(1, "fusion.3")]}))
    assert sorted(protos) == ["jit_scatter(2)", "jit_slice_fn(9)"]
    paths = scopes.op_paths(protos["jit_slice_fn(9)"])
    assert paths["custom-call.4"] == TERMS          # its user's
    assert paths["custom-call.6"] == TERMS          # its operand's
    assert paths["custom-call.7"] == TERMS          # through a chain
    assert scopes.op_paths(protos["jit_scatter(2)"]) == {}

    # device ops take the path of their instruction in the program that
    # encloses them; the same name in another program reads nothing
    ops = [("%custom-call.4 = f32[2] custom-call(...)", 5, 6),
           ("%fusion.3 = f32[2] fusion(...)", 12, 13),
           ("%fusion.3 = f32[2] fusion(...)", 30, 31)]
    modules = [("jit_slice_fn(9)", 0, 20), ("jit_scatter(2)", 25, 40)]
    got = scopes.attach(ops, modules, {"jit_slice_fn(9)": paths,
                                       "jit_scatter(2)": {}})
    assert got == [(TERMS, 5, 6), (BODY + "/vb/combine/add", 12, 13),
                   (None, 30, 31)]
