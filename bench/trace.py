"""Reduction of a JAX profiler trace to the numbers the benchmark reports.

`load` reads the newest `.xplane.pb` under a directory into plain event
lists; every other function works on those lists, so a recorded trace
(`bench/tests/trace_small.json`) checks the arithmetic without a chip.

An event is `(name, start_ns, end_ns)`.  Device events are the
operations of the device planes' "XLA Ops" line, and module events the
programs of their "XLA Modules" line (one per execution of a compiled
program, named "jit_<function>(<hash>)"); host events are those of the
host plane's threads, among them the harness's own
`jax.profiler.TraceAnnotation`s (named "bench/...").
"""
from __future__ import annotations

import glob
import os

WINDOW = "bench/window"


def load(trace_dir: str) -> dict:
    """{"devices": {plane: [event]}, "modules": {plane: [event]},
    "host": [(thread, name, s, e)]}."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    prof = ProfileData.from_file(max(paths, key=os.path.getmtime))
    devices, modules, host = {}, {}, []
    lines = {"XLA Ops": devices, "XLA Modules": modules}
    for plane in prof.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            for line in plane.lines:
                if line.name in lines:
                    lines[line.name].setdefault(plane.name, []).extend(
                        (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                        for ev in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((line.name, ev.name, ev.start_ns,
                             ev.start_ns + ev.duration_ns)
                            for ev in line.events)
    return {"devices": devices, "modules": modules, "host": host}


def window(trace: dict) -> tuple:
    """(start_ns, end_ns) of the harness's "bench/window" annotation."""
    spans = [(s, e) for _, name, s, e in trace["host"] if name == WINDOW]
    if not spans:
        raise ValueError(f"the trace holds no {WINDOW!r} annotation")
    return min(s for s, _ in spans), max(e for _, e in spans)


def clip(events, lo, hi) -> list:
    """The events' parts inside [lo, hi)."""
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if e > lo and s < hi]


def union(events) -> list:
    """Disjoint (start, end) intervals covered by the events, in order."""
    out = []
    for _, s, e in sorted(events, key=lambda ev: ev[1]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(iv) for iv in out]


def busy_ns(events, lo, hi) -> int:
    """Nanoseconds of [lo, hi) in which some operation ran."""
    return sum(e - s for s, e in union(clip(events, lo, hi)))


def busy_s(trace: dict) -> float:
    """Busy seconds inside the window, averaged over the device planes."""
    lo, hi = window(trace)
    planes = list(trace["devices"].values())
    if not planes:
        return 0.0
    return sum(busy_ns(ev, lo, hi) for ev in planes) / len(planes) / 1e9


def idle_share(trace: dict) -> float:
    """1 - busy / window, averaged over the device planes."""
    lo, hi = window(trace)
    return 1.0 - busy_s(trace) * 1e9 / (hi - lo)


def op_seconds(trace: dict, match) -> tuple:
    """(seconds, count) of the window's device events whose name
    `match(name)` accepts, summed over the device planes."""
    lo, hi = window(trace)
    total, count = 0, 0
    for events in trace["devices"].values():
        for n, s, e in clip(events, lo, hi):
            if match(n):
                total += e - s
                count += 1
    return total / 1e9, count


def module_runs(trace: dict, match) -> float:
    """Executions of the programs whose module name `match(name)` accepts,
    inside the window, averaged over the device planes; one cut by the
    window's edge counts by the share of its time inside."""
    lo, hi = window(trace)
    planes = list(trace.get("modules", {}).values())
    runs = 0.0
    for events in planes:
        for n, s, e in events:
            if match(n) and e > s and e > lo and s < hi:
                runs += (min(e, hi) - max(s, lo)) / (e - s)
    return runs / len(planes) if planes else 0.0


def short(name: str) -> str:
    """An op's HLO instruction name, with the custom-call target when it
    has one ("%custom-call.131 LuDecompositionBlock")."""
    head = name.split(" = ", 1)[0]
    if 'custom_call_target="' in name:
        head += " " + name.split('custom_call_target="', 1)[1].split('"')[0]
    return head


CONTROL_FLOW = ("%while", "%conditional", "%call")


def top_ops(trace: dict, n: int = 10) -> list:
    """[[name, seconds]] of the device operations that took most time
    (loops and calls, which enclose the operations they run, left out)."""
    lo, hi = window(trace)
    by = {}
    for events in trace["devices"].values():
        for name, s, e in clip(events, lo, hi):
            if not name.startswith(CONTROL_FLOW):
                key = short(name)
                by[key] = by.get(key, 0) + (e - s)
    top = sorted(by.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in top]


def idle_gaps(trace: dict, n: int = 10) -> list:
    """[[label, seconds]] of the longest idle gaps of the first device
    plane, each labelled by the harness annotation (else the host event)
    that overlaps it most."""
    lo, hi = window(trace)
    planes = sorted(trace["devices"])
    if not planes:
        return []
    busy = union(clip(trace["devices"][planes[0]], lo, hi))
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:n]
    return [[label(trace["host"], s, e), (e - s) / 1e9] for s, e in gaps]


def label(host, lo, hi) -> str:
    """What the host was doing in [lo, hi): the "bench/..." annotation
    with the most overlap, else the host event with the most, else
    "idle"."""
    def best(events):
        score = {}
        for _, name, s, e in events:
            ov = min(e, hi) - max(s, lo)
            if ov > 0 and name != WINDOW:
                score[name] = score.get(name, 0) + ov
        return max(score, key=score.get) if score else None

    return (best([ev for ev in host if ev[1].startswith("bench/")])
            or best(host) or "idle")


def breakdown(trace: dict) -> dict:
    return {"device_ops": top_ops(trace), "idle_gaps": idle_gaps(trace)}
