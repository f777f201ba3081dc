"""Process-wide JAX settings that every entry point makes the same way:
the floating-point precision of a session, and the persistent
compilation cache.  Call both before the first computation.

    from repro import runtime
    runtime.use_compile_cache()
    dtype = runtime.use_platform_precision()
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

# src/repro/runtime.py -> the checkout root
_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CACHE_DIR = os.path.join(_ROOT, ".jax_cache")


def use_platform_precision():
    """Run sessions in float64 on CPU and in float32 on a TPU; returns
    that dtype.

    The TPU has no float64 LU decomposition: with x64 on, the reference
    backend's log-determinants and inverses of f64 (K, D, D) blocks do
    not compile for it (and the fused kernel cannot take f64 operands).
    So on the chip x64 stays off and every default dtype is f32, and f32
    matrix products run at full f32 precision ("highest"; the TPU's
    default is one bf16 pass, about 3 significant digits, while the
    natural parameters reach ~1e4).  On CPU the faithful layer keeps
    f64, which keeps the Eq. 46 KL metric trustworthy at counts ~1e4
    (log-dets and digammas)."""
    x64 = jax.default_backend() != "tpu"
    jax.config.update("jax_enable_x64", x64)
    if not x64:
        jax.config.update("jax_default_matmul_precision", "highest")
    return jnp.float64 if x64 else jnp.float32


def use_compile_cache() -> str:
    """Keep compiled programs across processes; returns the directory.

    JAX itself reads `JAX_COMPILATION_CACHE_DIR`: when it is set nothing
    is changed here.  Otherwise the cache lives at a fixed path inside
    the checkout (`.jax_cache/`, git-ignored) — fixed because a cache
    whose directory moves between runs never hits."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
