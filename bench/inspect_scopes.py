"""Print what a profiler trace says of the program's own names: the device
operations that took most time and the `vb/...` scope that owns each,
device time by scope with the share no scope covers, and the device's
idle time by the innermost program span open on the host.  For reading a
trace by hand, beside `bench/inspect_trace.py` (planes, lines, top ops).

    python3 bench/inspect_scopes.py [trace_dir]     # default .bench/trace
"""
import glob
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main():
    from bench import scopes, trace
    d = sys.argv[1] if len(sys.argv) > 1 else os.path.join(
        ROOT, ".bench", "trace")
    path = max(glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    print(path, os.path.getsize(path), "bytes")
    tr = trace.load(d)
    try:
        lo, hi = trace.window(tr)
    except ValueError:
        print("no bench/window annotation: the rest needs one")
        return
    print(f"window {(hi - lo) / 1e9:.3f} s")

    # the scope of each of the costliest ops, in each slice program
    with open(path, "rb") as f:
        protos = scopes.hlo_protos(f.read())
    slices = {p: scopes.op_paths(m) for p, m in protos.items()
              if p.startswith(scopes.SLICE)}
    print(f"\ndevice ops that took most time, and their scope in the "
          f"{len(slices)} slice program(s):")
    for name, secs in trace.top_ops(tr, 12):
        ins = scopes.instruction(name.split(" ", 1)[0])
        owners = sorted({str(scopes.scope_of(paths.get(ins)))
                         for paths in slices.values()})
        print(f"  {name:60s} {secs:10.6f} s  {', '.join(owners)}")

    ops = scopes.load(d)
    secs = scopes.scope_seconds(ops, lo, hi)
    n = len(ops) or 1
    busy = sum(trace.busy_ns(evs, lo, hi) for evs in ops.values()) / n / 1e9
    scoped = sum(trace.busy_ns([ev for ev in evs if scopes.scope_of(ev[0])],
                               lo, hi) for evs in ops.values()) / n / 1e9
    print(f"\ndevice time by scope (busy {busy:.6f} s; scopes may overlap):")
    for key in scopes.SCOPES + (None,):
        if key in secs:
            share = 100.0 * secs[key] / busy if busy else 0.0
            print(f"  {str(key):12s} {secs[key]:10.6f} s  {share:6.2f}%")
    if busy:
        print(f"  under some scope {100.0 * scoped / busy:.2f}% of busy time,"
              f" under none {100.0 * (1 - scoped / busy):.2f}%")

    idle = scopes.idle_by_span(tr)
    total = sum(idle.values())
    print(f"\ndevice idle {total:.6f} s, by the innermost program span open "
          "on the host:")
    for key, s in sorted(idle.items(), key=lambda kv: -kv[1]):
        share = 100.0 * s / total if total else 0.0
        print(f"  {str(key):16s} {s:10.6f} s  {share:6.2f}%")
    print("\nlongest idle gaps:")
    for label, s in scopes.idle_gaps(tr, 10):
        print(f"  {label:16s} {s * 1e3:10.3f} ms")


if __name__ == "__main__":
    main()
