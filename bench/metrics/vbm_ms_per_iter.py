"""Device time of the ops under the compiled step's `vb/vbm` scope (the
Appendix-A hyperparameter update from the E-step's statistics, and the
pack to natural parameters) per fleet iteration, from the profiler trace
(`bench/scopes.py`)."""
from bench import harness, scopes


def read(ctx):
    return scopes.ms_per_iter(ctx["trace"], scopes.load(harness.TRACE_DIR),
                              "vb/vbm", ctx["traffic"]["slice_iters"])
