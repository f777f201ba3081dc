"""Mean duration of the program's `driver/admit` spans (a session's
record copied into its fleet slot) that start inside the traced window,
from the profiler trace's host plane, where the program's telemetry spans
land as annotations on the device ops' clock (`bench/scopes.py`)."""
from bench import scopes


def read(ctx):
    return scopes.span_ms(ctx["trace"], "driver/admit")
