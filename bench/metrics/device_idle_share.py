"""Device idle share of the window: 1 - (union of the device's operation
intervals) / window, from the profiler trace."""
from bench import trace


def read(ctx):
    return 100.0 * trace.idle_share(ctx["trace"])
