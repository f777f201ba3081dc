"""Mamba-2 SSD chunked scan — Pallas TPU kernel.

Per (batch, head) the sequence is processed in chunks of length L along a
sequential grid axis; the (P, N) SSM state lives in VMEM scratch and is
carried across chunk iterations.  Inside a chunk everything is
attention-shaped MXU work:

    y_intra = ((C B^T) .* decay-gates .* dt) @ x          (L,L)@(L,P)
    y_inter = (C .* exp(cum)) @ state                     (L,N)@(N,P)
    state'  = exp(cum_L) * state + (B .* dt .* decay)^T @ x

matching mamba2.ssd_chunked / ref.ssd exactly (up to fp accumulation).
Layouts chosen 2-D-friendly for the VPU: dt enters as (..., L, 1) blocks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(a_ref, x_ref, dt_ref, b_ref, c_ref, y_ref, hout_ref, state_ref,
            *, chunk: int):
    cj = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(cj == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    L = chunk
    a = a_ref[0, 0, 0]                                  # scalar decay rate A_h
    x = x_ref[0, 0, 0].astype(jnp.float32)              # (L, P)
    dt = dt_ref[0, 0, 0].astype(jnp.float32)            # (L, 1)
    Bm = b_ref[0, 0].astype(jnp.float32)                # (L, N)
    Cm = c_ref[0, 0].astype(jnp.float32)                # (L, N)

    dA = dt * a                                         # (L, 1) log-decays
    cum = jnp.cumsum(dA, axis=0)                        # (L, 1) inclusive

    # intra-chunk quadratic part
    cb = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (L, L)
    seg = cum - cum.reshape(1, L)                       # cum_l - cum_l'
    rows = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    gates = jnp.where(cols <= rows, jnp.exp(seg), 0.0)
    M = cb * gates * dt.reshape(1, L)                   # weight by dt_{l'}
    y = jax.lax.dot_general(M, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)

    # inter-chunk: contribution of the carried state
    state = state_ref[...]                              # (N, P)
    y += jax.lax.dot_general(Cm * jnp.exp(cum), state,
                             (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    y_ref[0, 0, 0] = y.astype(y_ref.dtype)

    # state update
    decay_to_end = jnp.exp(cum[L - 1:L] - cum)          # (L, 1)
    wB = Bm * (dt * decay_to_end)                       # (L, N)
    s_new = jax.lax.dot_general(wB, x, (((0,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (N, P)
    state_ref[...] = jnp.exp(cum[L - 1, 0]) * state + s_new

    @pl.when(cj == nc - 1)
    def _emit_state():
        hout_ref[0, 0] = state_ref[...]


def ssd_scan(x, dt, A, Bm, Cm, *, interpret: bool, chunk: int = 128):
    """x (B,S,H,P), dt (B,S,H), A (H,), Bm/Cm (B,S,N).

    Returns (y (B,S,H,P), final_state (B,H,P,N)) — final_state layout matches
    mamba2.ssd_chunked (transposed from the kernel-internal (N,P)).
    """
    Bb, S, H, P = x.shape
    N = Bm.shape[-1]
    L = min(chunk, S)
    assert S % L == 0, (S, L)
    nc = S // L
    # (B, H, nc, L, ...) layouts
    xr = jnp.moveaxis(x, 2, 1).reshape(Bb, H, nc, L, P)
    dtr = jnp.moveaxis(dt, 2, 1).reshape(Bb, H, nc, L, 1)
    Br = Bm.reshape(Bb, nc, L, N)
    Cr = Cm.reshape(Bb, nc, L, N)
    Ar = A.reshape(H, 1, 1).astype(jnp.float32)

    grid = (Bb, H, nc)
    y, hout = pl.pallas_call(
        functools.partial(_kernel, chunk=L),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, 1), lambda b, h, c: (h, 0, 0)),        # A
            pl.BlockSpec((1, 1, 1, L, P), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, L, 1), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, L, N), lambda b, h, c: (b, c, 0, 0)),  # B
            pl.BlockSpec((1, 1, L, N), lambda b, h, c: (b, c, 0, 0)),  # C
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, L, P), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, N, P), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bb, H, nc, L, P), x.dtype),
            jax.ShapeDtypeStruct((Bb, H, N, P), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        interpret=interpret,
    )(Ar, xr, dtr, Br, Cr)
    y = jnp.moveaxis(y.reshape(Bb, H, S, P), 1, 2)
    return y, jnp.swapaxes(hout, -1, -2)                 # (B,H,P,N)
