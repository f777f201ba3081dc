"""Device time of the ops under the compiled step's `vb/terms` scope (the
natural parameters unpacked to the E-step's per-component terms: W and
its log-determinant) per fleet iteration, from the profiler trace: their
busy time in the window over the slice program's executions ×
`slice_iters` (`bench/scopes.py`)."""
from bench import harness, scopes


def read(ctx):
    return scopes.ms_per_iter(ctx["trace"], scopes.load(harness.TRACE_DIR),
                              "vb/terms", ctx["traffic"]["slice_iters"])
