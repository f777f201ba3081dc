"""Unified telemetry: metrics registry + span tracing + device taps.

One switch, three layers:

- **Metrics** (`metrics.MetricsRegistry`): labeled counters / gauges /
  histograms with JSON-lines and Prometheus-text export.  The process
  default registry lives here; instrumented layers record through the
  module-level helpers below.
- **Tracing** (`tracing.Tracer`): `span(name)` / `instant(name)` events
  with Chrome-trace export — driver ticks, submits, admissions,
  evictions, slices, compiles and checkpoint writes on one timeline.
  While enabled, every span also enters a
  `jax.profiler.TraceAnnotation` of the same name (args as stats, not
  in the name), so a profiler trace shows the program's spans on its
  host plane, on the device ops' clock.
- **Taps** (`taps`): jit-safe per-iteration series out of compiled VB
  steps.  Device-side `taps.tap(...)` insertion has its OWN switch
  (`taps.enable()`) because inserting an `io_callback` changes the
  jaxpr and forces a recompile; everything else here is host-side only
  and can never change a compiled program.

A `jax.monitoring` listener registered at import counts backend
compiles while enabled: `jax_compiles_total{fun=...}` and a
`compile/<fun>` complete event ending when the compile ended.  The
compiled VB step names its regions with `jax.named_scope` (`vb/terms`,
`vb/vbe`, `vb/vbm`, `vb/combine`, `vb/project`); scopes only change op
metadata, so they are unconditional and not part of this switch.

Disabled (the default) must be free: every helper below is a single
module-bool check before touching any registry/tracer state, so
instrumented hot paths (driver tick, submit, admit, evict, `vb_run`)
cost one branch when telemetry is off.  `tests/test_telemetry.py` pins
that the `vb_step` jaxpr and driver compile counts are byte-identical
with telemetry disabled; the benchmark's untraced runs (telemetry off)
bound what the disabled path costs end to end.

Typical use (see docs/observability.md for the catalogue)::

    from repro import telemetry

    telemetry.enable()
    ... run a driver / vb_run ...
    telemetry.export_chrome_trace("trace.json")   # chrome://tracing
    open("metrics.prom", "w").write(telemetry.to_prometheus())
    telemetry.disable(); telemetry.reset()        # tests
"""
from __future__ import annotations

from contextlib import contextmanager, nullcontext

import jax

from . import taps
from .metrics import DEFAULT_BUCKETS, MetricsRegistry
from .tracing import Tracer

__all__ = [
    "MetricsRegistry", "Tracer", "DEFAULT_BUCKETS", "taps",
    "enable", "disable", "enabled", "enabled_scope", "reset",
    "registry", "tracer",
    "inc", "set_gauge", "observe",
    "span", "instant",
    "snapshot", "to_jsonl", "to_prometheus", "export_chrome_trace",
    "warn_once",
]

_ENABLED = False
_REGISTRY = MetricsRegistry()
_TRACER = Tracer()
_NULL_CONTEXT = nullcontext()
_WARNED: set = set()


def enable() -> None:
    """Turn on host-side telemetry (metrics + spans).  Device taps have
    a separate switch — `telemetry.taps.enable()` — because they change
    jaxprs; enabling host telemetry alone never recompiles anything."""
    global _ENABLED
    _ENABLED = True


def disable() -> None:
    global _ENABLED
    _ENABLED = False


def enabled() -> bool:
    return _ENABLED


@contextmanager
def enabled_scope():
    """Enable host telemetry for a with-block (tests, benchmarks)."""
    global _ENABLED
    prev = _ENABLED
    _ENABLED = True
    try:
        yield
    finally:
        _ENABLED = prev


def reset() -> None:
    """Clear metrics, trace events, tap buffers, and warn-once state."""
    _REGISTRY.clear()
    _TRACER.clear()
    taps.clear()
    _WARNED.clear()


def registry() -> MetricsRegistry:
    return _REGISTRY


def tracer() -> Tracer:
    return _TRACER


# -- fast-path recording helpers (no-ops when disabled) -------------------
def inc(name: str, value: float = 1.0, **labels) -> None:
    if _ENABLED:
        _REGISTRY.counter(name, **labels).inc(value)


def set_gauge(name: str, value: float, **labels) -> None:
    if _ENABLED:
        _REGISTRY.gauge(name, **labels).set(value)


def observe(name: str, value: float, **labels) -> None:
    if _ENABLED:
        _REGISTRY.histogram(name, **labels).observe(value)


def span(name: str, **args):
    """Context manager: a Chrome-trace complete event and a profiler
    annotation of the same name, or a shared null context when disabled
    (one bool check, zero allocation)."""
    if _ENABLED:
        return _annotated_span(name, args)
    return _NULL_CONTEXT


@contextmanager
def _annotated_span(name: str, args: dict):
    with _TRACER.span(name, **args), \
            jax.profiler.TraceAnnotation(name, **args):
        yield


def instant(name: str, **args) -> None:
    if _ENABLED:
        _TRACER.instant(name, **args)


def warn_once(key: str, message: str, category=UserWarning,
              stacklevel: int = 2) -> bool:
    """Issue `warnings.warn(message)` only the first time `key` is seen
    this session (cleared by `reset()`).  Returns True when the warning
    fired — callers pair it with an unconditional counter so repeat
    occurrences stay countable even though they stop warning.  Active
    regardless of the enabled switch: deduplicating a warning is not
    telemetry overhead, it removes log spam."""
    if key in _WARNED:
        return False
    _WARNED.add(key)
    import warnings
    warnings.warn(message, category, stacklevel=stacklevel + 1)
    return True


# -- compile counter ------------------------------------------------------
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _on_duration(event: str, duration: float, **kw) -> None:
    """`jax.monitoring` duration listener: one backend compile (a
    persistent-cache retrieval counts too) ends now."""
    if _ENABLED and event == _COMPILE_EVENT:
        fun = kw.get("fun_name", "?")
        _REGISTRY.counter("jax_compiles_total", fun=fun).inc()
        dur = duration * 1e6
        _TRACER.complete(f"compile/{fun}", _TRACER.now_us() - dur, dur)


jax.monitoring.register_event_duration_secs_listener(_on_duration)


# -- export ---------------------------------------------------------------
def snapshot() -> list:
    return _REGISTRY.snapshot()


def to_jsonl() -> str:
    return _REGISTRY.to_jsonl()


def to_prometheus() -> str:
    return _REGISTRY.to_prometheus()


def export_chrome_trace(path: str) -> str:
    return _TRACER.export_chrome_trace(path)
