"""jit'd public wrappers around the Pallas kernels.

This module is the one place that decides how a kernel runs: on a TPU the
calls compile to Mosaic; on any other platform the kernel body runs in
Pallas interpret mode (the CPU test suite validates the TPU kernels that
way).  The kernel modules themselves take `interpret` with no default.
"""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp

from repro import telemetry
from repro.kernels import flash_attention as _fa
from repro.kernels import gmm_estep as _ge
from repro.kernels import ssd_scan as _ss


def _default_interpret() -> bool:
    """Interpret unless the default device is a TPU.  A
    `jax.default_device(...)` scope counts: it is part of every jit's
    cache key, so the same wrapper traces once per platform."""
    dev = jax.config.jax_default_device
    if dev is None:
        return jax.default_backend() != "tpu"
    return (dev if isinstance(dev, str) else dev.platform) != "tpu"


def _instrument(name: str):
    """Kernel wall-time telemetry: a `kernel_wall_seconds{kernel=...}`
    histogram plus a `kernel/<name>` trace span per eager call.  One bool
    check when telemetry is disabled.  Calls from inside an outer trace
    (e.g. `core.backends._fused_local_vbm` jits around `gmm_estep_nodes`)
    pass straight through — timing a trace is meaningless and
    `block_until_ready` does not apply to tracers."""
    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            if not telemetry.enabled() or any(
                    isinstance(leaf, jax.core.Tracer) for leaf in
                    jax.tree_util.tree_leaves((args, kwargs))):
                return fn(*args, **kwargs)
            t0 = time.perf_counter()
            with telemetry.span(f"kernel/{name}"):
                out = fn(*args, **kwargs)
                jax.block_until_ready(out)
            telemetry.observe("kernel_wall_seconds",
                              time.perf_counter() - t0, kernel=name)
            return out
        return wrapper
    return deco


@_instrument("flash_attention")
@functools.partial(jax.jit,
                   static_argnames=("causal", "window", "block_q", "block_k"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128):
    """q (B,S,Hq,hd), k/v (B,S,Hkv,hd) GQA -> out (B,S,Hq,hd)."""
    B, S, Hq, hd = q.shape
    Hkv = k.shape[2]
    g = Hq // Hkv
    # fuse batch+heads; broadcast kv heads to q heads
    qf = jnp.moveaxis(q, 2, 1).reshape(B * Hq, S, hd)
    kf = jnp.moveaxis(jnp.repeat(k, g, axis=2), 2, 1).reshape(B * Hq, S, hd)
    vf = jnp.moveaxis(jnp.repeat(v, g, axis=2), 2, 1).reshape(B * Hq, S, hd)
    out = _fa.flash_attention(qf, kf, vf, causal=causal, window=window,
                              block_q=block_q, block_k=block_k,
                              interpret=_default_interpret())
    return jnp.moveaxis(out.reshape(B, Hq, S, hd), 1, 2)


@_instrument("ssd_scan")
@functools.partial(jax.jit, static_argnames=("chunk",))
def ssd_scan(x, dt, A, Bm, Cm, *, chunk: int = 128):
    """Mamba-2 SSD: x (B,S,H,P), dt (B,S,H), A (H,), Bm/Cm (B,S,N)."""
    return _ss.ssd_scan(x, dt, A, Bm, Cm, chunk=chunk,
                        interpret=_default_interpret())


@_instrument("gmm_estep")
@functools.partial(jax.jit, static_argnames=("block_t",))
def gmm_estep(x, mask, log_prior, Wn, b, c, *, block_t: int = 512):
    return _ge.gmm_estep(x, mask, log_prior, Wn, b, c, block_t=block_t,
                         interpret=_default_interpret())


@_instrument("gmm_estep_nodes")
@functools.partial(jax.jit, static_argnames=("block_t", "return_r"))
def gmm_estep_nodes(x, mask, log_prior, Wn, b, c, replication=1.0, *,
                    block_t: int = 512, return_r: bool = True):
    """Node-batched fused VBE step: x (N, T, D) and per-node terms; see
    gmm_estep.gmm_estep_nodes.  The engine hot path (core/backends.py)
    passes return_r=False — only the statistics leave the kernel — and the
    Appendix-A `replication` factor, applied to the statistics
    kernel-side at emit time (traced, not static)."""
    return _ge.gmm_estep_nodes(x, mask, log_prior, Wn, b, c, block_t=block_t,
                               interpret=_default_interpret(),
                               return_r=return_r, replication=replication)


@_instrument("gmm_estep_from_posterior")
@functools.partial(jax.jit, static_argnames=("block_t", "compute_dtype"))
def gmm_estep_from_posterior(x, mask, q, *, block_t: int = 512,
                             compute_dtype=None):
    """Convenience: compute the kernel's precomputed terms from a
    GMMPosterior, then run the fused kernel.  Matches
    gmm.responsibilities + gmm.sufficient_stats (replication=1).

    The per-component precompute runs INSIDE this jit in `compute_dtype`
    (default: the posterior's own dtype — the caller's precision policy
    decides; nothing is hard-cast).  `x`/`mask` stream into the kernel at
    whatever dtype they arrive in; the kernel accumulates in f32.
    """
    from repro.core import gmm
    log_prior, Wn, b, c = gmm.estep_terms(q, dtype=compute_dtype)
    return _ge.gmm_estep(x, mask, log_prior, Wn, b, c, block_t=block_t,
                         interpret=_default_interpret())
