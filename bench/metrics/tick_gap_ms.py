"""Mean host time between driver ticks: from the end of one tick's last
`driver/sync` span to the start of the next tick's first `driver/slice`
span (evict, admit and bookkeeping while the device waits), from the
program's own telemetry spans."""


def read(ctx):
    evs = sorted((e for e in ctx["spans"] if e.get("ph") == "X"
                  and e["name"] in ("driver/slice", "driver/sync")),
                 key=lambda e: e["ts"])
    gaps, last = [], None
    for e in evs:
        if e["name"] == "driver/sync":
            last = e["ts"] + e["dur"]
        elif last is not None:
            gaps.append(e["ts"] - last)
            last = None
    return sum(gaps) / len(gaps) / 1e3 if gaps else None
