"""Mean duration of the program's `driver/evict` spans (a finished
session's state snapshot, its slot freed and the session retired) that
start inside the traced window, from the profiler trace's host plane
(`bench/scopes.py`)."""
from bench import scopes


def read(ctx):
    return scopes.span_ms(ctx["trace"], "driver/evict")
