"""Print what a profiler trace holds: its planes and lines, and the device
operations that took most time.  For reading a trace by hand.

    python3 bench/inspect_trace.py [trace_dir]     # default .bench/trace
"""
import glob
import os
import sys


def main():
    from jax.profiler import ProfileData
    d = sys.argv[1] if len(sys.argv) > 1 else os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".bench", "trace")
    path = max(glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    print(path, os.path.getsize(path), "bytes")
    prof = ProfileData.from_file(path)
    for plane in prof.planes:
        lines = list(plane.lines)
        print(f"plane {plane.name!r}: " + ", ".join(
            f"{ln.name!r} ({sum(1 for _ in ln.events)})" for ln in lines))
        if plane.name.startswith("/device:"):
            for ln in lines:
                tot = {}
                for ev in ln.events:
                    n, c = tot.get(ev.name, (0, 0))
                    tot[ev.name] = (n + ev.duration_ns, c + 1)
                top = sorted(tot.items(), key=lambda kv: -kv[1][0])[:15]
                for name, (ns, c) in top:
                    print(f"  {ln.name} | {name[:100]} | {ns / 1e6:.3f} ms"
                          f" x{c}")
                for ev in list(ln.events)[:2]:
                    print("    stats:", [(k, str(v)[:80])
                                         for k, v in ev.stats][:8])


if __name__ == "__main__":
    main()
