"""Mesh + shard_map entry points, and the record of which axes are manual.

This module wraps ``jax.shard_map`` and ``jax.set_mesh`` only to record
the stack of meshes entered via `use_mesh` and the axes a `shard_map`
body is manual over, so that the LM stack's sharding constraints inside
a body skip the manual axes (`dist/sharding.py`, `models/moe.py`).  The
VB engine and serving driver, which place no such constraints, call
``jax.shard_map`` directly.
"""
from __future__ import annotations

import contextlib
import threading

import jax


def shard_map(f, *, mesh, in_specs, out_specs, axis_names=None,
              check_vma=None):
    """``jax.shard_map`` that records its manual axes for the body.

    ``axis_names`` marks the manual axes (the rest stay auto/GSPMD).
    """
    names = (frozenset(axis_names) if axis_names is not None
             else frozenset(mesh.axis_names))

    def wrapped(*args):
        with manual_axes(names):
            return f(*args)

    kw = {}
    if axis_names is not None:
        kw["axis_names"] = set(axis_names)
    if check_vma is not None:
        kw["check_vma"] = check_vma
    return jax.shard_map(wrapped, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kw)


class _MeshState(threading.local):
    def __init__(self):
        self.stack = []          # meshes entered via use_mesh
        self.manual = []         # frozensets of manual axis names


_STATE = _MeshState()


@contextlib.contextmanager
def use_mesh(mesh):
    """``jax.set_mesh(mesh)`` as the ambient mesh, recorded for
    `current_mesh`."""
    _STATE.stack.append(mesh)
    try:
        with jax.set_mesh(mesh):
            yield mesh
    finally:
        _STATE.stack.pop()


@contextlib.contextmanager
def manual_axes(names):
    """Record that `names` are manual (shard_map) axes for the enclosed
    trace, so sharding constraints skip them."""
    _STATE.manual.append(frozenset(names))
    try:
        yield
    finally:
        _STATE.manual.pop()


def current_mesh():
    """The innermost mesh entered via `use_mesh`, or None."""
    return _STATE.stack[-1] if _STATE.stack else None


def current_manual_axes() -> frozenset:
    if _STATE.manual:
        return frozenset().union(*_STATE.manual)
    return frozenset()


def axis_sizes(mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def auto_axis_sizes() -> dict:
    """name -> size for ambient mesh axes NOT currently manual."""
    mesh = current_mesh()
    if mesh is None:
        return {}
    manual = current_manual_axes()
    return {a: s for a, s in axis_sizes(mesh).items() if a not in manual}
