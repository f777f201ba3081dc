"""Blocked online-softmax (flash) attention — Pallas TPU kernel.

Target: TPU vXe MXU.  Q/K/V blocks are tiled into VMEM with hardware-aligned
(128-multiple) block shapes; the softmax running max/denominator and the
output accumulator live in VMEM scratch and persist across the sequential
kv-block grid axis.  Causal and sliding-window masking is applied per block
pair; fully-masked block pairs short-circuit (pl.when) so the sliding-window
variant does O(S * W) work, which is what makes `long_500k` tractable for
the full-attention architectures.

Layout: inputs are (BH, S, hd) — batch and heads pre-fused by ops.py (GQA kv
heads are broadcast to q heads there).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            scale: float, causal: bool, window: int, block_q: int,
            block_k: int, seq_len: int):
    qi = pl.program_id(1)          # query-block index
    kj = pl.program_id(2)          # kv-block index (sequential, innermost)
    nk = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = qi * block_q
    k_start = kj * block_k
    # block-level reachability: skip blocks that are entirely masked
    reachable = True
    if causal:
        reachable = k_start <= q_start + block_q - 1
    if window > 0:
        reachable = jnp.logical_and(
            reachable, k_start + block_k - 1 > q_start - window)

    @pl.when(reachable)
    def _compute():
        q = q_ref[0].astype(jnp.float32)                    # (bq, hd)
        k = k_ref[0].astype(jnp.float32)                    # (bk, hd)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        rows = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                  (block_q, block_k), 0)
        cols = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                  (block_q, block_k), 1)
        ok = cols < seq_len
        if causal:
            ok = jnp.logical_and(ok, cols <= rows)
        if window > 0:
            ok = jnp.logical_and(ok, cols > rows - window)
        s = jnp.where(ok, s, NEG_INF)

        m_prev = m_ref[...]                                 # (bq, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(kj == nk - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def flash_attention(q, k, v, *, interpret: bool, causal: bool = True,
                    window: int = 0, scale: float | None = None,
                    block_q: int = 128, block_k: int = 128):
    """q/k/v (BH, S, hd) -> (BH, S, hd)."""
    BH, S, hd = q.shape
    scale = float(scale if scale is not None else 1.0 / (hd ** 0.5))
    bq = min(block_q, S)
    bk = min(block_k, S)
    Sp = ((S + bq - 1) // bq) * bq
    Skp = ((S + bk - 1) // bk) * bk
    if Sp != S:
        q = jnp.pad(q, ((0, 0), (0, Sp - S), (0, 0)))
    if Skp != S:
        k = jnp.pad(k, ((0, 0), (0, Skp - S), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, Skp - S), (0, 0)))
    grid = (BH, Sp // bq, Skp // bk)
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, causal=causal, window=window,
                          block_q=bq, block_k=bk, seq_len=S),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sp, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, hd), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return out[:, :S, :]
