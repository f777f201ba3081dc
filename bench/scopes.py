"""The program's own names on a profiler trace: the named scopes of the
compiled VB step (`vb/terms`, `vb/vbe`, `vb/vbm`, `vb/combine`,
`vb/project`) on the device ops, and the driver's spans (`driver/...`) on
the host plane.

A TPU op event names only its HLO instruction ("%custom-call.131 = ...").
The scope path is the instruction's `op_name` metadata ("jit(slice_fn)/
while/body/.../vmap(vb/vbm)/jit(inv)/..."), which `load` reads from the
HLO protos that the trace keeps on its "/host:metadata" plane, one per
program, matched to each op through the program execution ("XLA
Modules" event) that encloses it.  Custom calls (the LU and triangular
solves) carry no `op_name`; they take their users' path, else their
operands'.

Every other function works on plain event lists, as `bench/trace.py`
does, so hand-made traces check the arithmetic.  A scoped op is `(path,
start_ns, end_ns)`, with `path` None where no path is known.  A program
without scopes or spans reads None in every metric here, not an error.
"""
from __future__ import annotations

import functools
import glob
import os
import re

from bench import trace

SCOPES = ("vb/terms", "vb/vbe", "vb/vbm", "vb/combine", "vb/project")
PROGRAM = ("driver/", "engine/")     # span names the program records
SLICE = "jit_slice_fn("             # the serving driver's compiled slice
METADATA_PLANE = "/host:metadata"

_SCOPE = re.compile("(" + "|".join(SCOPES) + r")(?=[/)]|$)")


# -- protobuf wire format: XSpace -> "/host:metadata" -> HloProto ----------
def _varint(buf, i: int) -> tuple:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message: an int for varints,
    bytes (a memoryview) for everything else."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            v, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield num, v


def _packed(buf) -> list:
    out, i = [], 0
    while i < len(buf):
        v, i = _varint(buf, i)
        out.append(v)
    return out


def _text(buf) -> str:
    return bytes(buf).decode("utf-8", "replace")


def hlo_protos(xspace: bytes) -> dict:
    """{program name ("jit_slice_fn(<id>)"): HloModuleProto bytes} from
    the XSpace's metadata plane.  XSpace.planes = 1; XPlane.name = 2,
    .event_metadata = 4 (map entry value = 2); XEventMetadata.name = 2,
    .stats = 5; XStat.bytes_value = 6 (an HloProto, whose hlo_module = 1)."""
    out = {}
    for num, plane in _fields(memoryview(xspace)):
        if num != 1:
            continue
        entries = list(_fields(plane))
        if not any(k == 2 and _text(v) == METADATA_PLANE
                   for k, v in entries):
            continue
        for k, entry in entries:
            if k != 4:
                continue
            meta = dict(_fields(entry)).get(2)
            if meta is None:
                continue
            name, proto = None, None
            for kk, v in _fields(meta):
                if kk == 2:
                    name = _text(v)
                elif kk == 5:
                    proto = dict(_fields(v)).get(6, proto)
            if name and proto is not None:
                module = dict(_fields(proto)).get(1)
                if module is not None:
                    out[name] = module
    return out


def op_paths(module) -> dict:
    """{instruction name: op_name path} of an HloModuleProto (computations
    = 3; instruction = 2; its name = 1, metadata = 7 with op_name = 2,
    id = 35, operand_ids = 36).  An instruction without a path takes the
    path of a user that has one, else of an operand."""
    name, path, operands = {}, {}, {}
    for num, comp in _fields(module):
        if num != 3:
            continue
        for k, ins in _fields(comp):
            if k != 2:
                continue
            iid, nm, op, ops = None, None, "", []
            for kk, v in _fields(ins):
                if kk == 1:
                    nm = _text(v)
                elif kk == 7:
                    op = _text(dict(_fields(v)).get(2, b""))
                elif kk == 35:
                    iid = v
                elif kk == 36:
                    ops = _packed(v) if not isinstance(v, int) else [v]
            name[iid], path[iid], operands[iid] = nm, op, ops
    users = {}
    for iid, ops in operands.items():
        for o in ops:
            users.setdefault(o, []).append(iid)
    missing = [i for i, p in path.items() if not p]
    while missing:
        left = []
        for iid in missing:
            near = users.get(iid, []) + operands.get(iid, [])
            got = next((path[j] for j in near if path.get(j)), "")
            if got:
                path[iid] = got
            else:
                left.append(iid)
        if len(left) == len(missing):
            break
        missing = left
    return {name[i]: p for i, p in path.items() if p}


def instruction(event_name: str) -> str:
    """"%fusion.41 = (...) fusion(...)" -> "fusion.41"."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def attach(ops, modules, paths: dict) -> list:
    """[(path, s, e)] of device ops `(name, s, e)`: each op's instruction
    looked up in the {instruction: path} map of the program execution
    `(program, s, e)` that encloses it; None outside any known one."""
    modules = sorted(modules, key=lambda m: m[1])
    out, j = [], 0
    for name, s, e in sorted(ops, key=lambda o: o[1]):
        while j < len(modules) and modules[j][2] <= s:
            j += 1
        prog = modules[j][0] if j < len(modules) and modules[j][1] <= s \
            else None
        out.append((paths.get(prog, {}).get(instruction(name)), s, e))
    return out


def load(trace_dir: str) -> dict:
    """{plane: [(path, start_ns, end_ns)]} of the device planes' "XLA
    Ops" lines, loops and calls (which enclose other ops) left out; {}
    when there is no trace."""
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        return {}
    newest = max(found, key=os.path.getmtime)
    return _load(newest, os.path.getmtime(newest))


@functools.lru_cache(maxsize=2)
def _load(path: str, _mtime: float) -> dict:
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        raw = f.read()
    paths = {prog: op_paths(module)
             for prog, module in hlo_protos(raw).items()}
    out = {}
    for plane in ProfileData.from_serialized_xspace(raw).planes:
        if not (plane.name.startswith("/device:") and "TPU" in plane.name):
            continue
        lines = {ln.name: ln for ln in plane.lines}
        if "XLA Ops" not in lines:
            continue
        ops = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
               for ev in lines["XLA Ops"].events
               if not ev.name.startswith(trace.CONTROL_FLOW)]
        modules = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                   for ev in (lines["XLA Modules"].events
                              if "XLA Modules" in lines else [])]
        out[plane.name] = attach(ops, modules, paths)
    return out


def scope_of(path: str | None) -> str | None:
    """The innermost `vb/...` scope in a path, else None.  Under `vmap`
    a scope reads "vmap(vb/terms)/...", so a scope name ends at "/" or
    ")"."""
    found = _SCOPE.findall(path or "")
    return found[-1] if found else None


def scope_seconds(ops: dict, lo: int, hi: int) -> dict:
    """{scope: seconds} of [lo, hi) in which some op under the scope ran,
    None for ops under no scope, averaged over the device planes."""
    total = {}
    for events in ops.values():
        by = {}
        for ev in events:
            by.setdefault(scope_of(ev[0]), []).append(ev)
        for key, evs in by.items():
            total[key] = total.get(key, 0) + trace.busy_ns(evs, lo, hi)
    n = len(ops) or 1
    return {k: v / n / 1e9 for k, v in total.items()}


def ms_per_iter(tr: dict, ops: dict, scope: str, slice_iters: int):
    """Device milliseconds under `scope` per fleet iteration inside the
    window: the scope's device time over the slice program's executions
    × `slice_iters`.  None when no op carries the scope or no slice ran."""
    lo, hi = trace.window(tr)
    secs = scope_seconds(ops, lo, hi)
    if not any(k in SCOPES for k in secs):
        return None
    runs = trace.module_runs(tr, lambda n: n.startswith(SLICE))
    if runs <= 0:
        return None
    return secs.get(scope, 0.0) * 1e3 / (runs * slice_iters)


def span_ms(tr: dict, name: str):
    """Mean duration (ms) of the host plane's `name` events that start
    inside the window; None when there is none."""
    lo, hi = trace.window(tr)
    durs = [e - s for _, n, s, e in tr["host"] if n == name and lo <= s < hi]
    return sum(durs) / len(durs) / 1e6 if durs else None


def idle_intervals(tr: dict) -> list:
    """Disjoint (start, end) stretches of the window in which the first
    device plane ran nothing."""
    lo, hi = trace.window(tr)
    planes = sorted(tr["devices"])
    if not planes:
        return [(lo, hi)]
    busy = trace.union(trace.clip(tr["devices"][planes[0]], lo, hi))
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def innermost(intervals, spans) -> dict:
    """{span name or None: ns} of the intervals' time, each instant under
    the innermost span open then (the one that started last), None where
    none is open.  `intervals` disjoint and sorted; `spans` (name, s, e)."""
    spans = sorted(spans, key=lambda sp: sp[1])
    out, active, j = {}, [], 0
    for lo, hi in intervals:
        cuts = {lo, hi}
        cuts.update(sp[2] for sp in active if lo < sp[2] < hi)
        k = j
        while k < len(spans) and spans[k][1] < hi:
            cuts.update(x for x in spans[k][1:] if lo < x < hi)
            k += 1
        cuts = sorted(cuts)
        for a, b in zip(cuts, cuts[1:]):
            while j < len(spans) and spans[j][1] <= a:
                active.append(spans[j])
                j += 1
            active = [sp for sp in active if sp[2] > a]
            name = max(active, key=lambda sp: sp[1])[0] if active else None
            out[name] = out.get(name, 0) + (b - a)
    return out


def program_spans(tr: dict) -> list:
    """(name, s, e) of the program's spans on the host plane."""
    return [(n, s, e) for _, n, s, e in tr["host"] if n.startswith(PROGRAM)]


def idle_by_span(tr: dict) -> dict:
    """{span name or None: seconds} of the device's idle time in the
    window, by the innermost program span open on the host."""
    got = innermost(idle_intervals(tr), program_spans(tr))
    return {k: v / 1e9 for k, v in got.items()}


def idle_gaps(tr: dict, n: int = 10) -> list:
    """[[label, seconds]] of the longest idle gaps, each labelled by the
    innermost program span open longest in it, else by the harness
    annotation ("bench/...") that overlaps it most."""
    spans = program_spans(tr)
    bench = [ev for ev in tr["host"] if ev[1].startswith("bench/")]
    gaps = sorted(idle_intervals(tr), key=lambda g: g[0] - g[1])[:n]
    out = []
    for s, e in gaps:
        got = {k: v for k, v in innermost([(s, e)], spans).items() if k}
        label = max(got, key=got.get) if got else trace.label(bench, s, e)
        out.append([label, (e - s) / 1e9])
    return out
