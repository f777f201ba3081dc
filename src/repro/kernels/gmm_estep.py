"""Fused GMM VBE step (responsibilities + sufficient statistics) — Pallas TPU.

The per-node VBE hot loop of the paper's application (Sec. IV / Appendix A)
is O(T * K * D^2): for every data point, a Mahalanobis quadratic form per
component, a row-softmax, then three accumulations (R_k, sum r x, sum r xx^T).
Done naively this makes three passes over the data in HBM.  The kernel fuses
everything into one pass: data blocks of `block_t` points stream through
VMEM, quadratic forms are (T_b, D) @ (D, D) MXU matmuls per component, and
the statistics accumulate in VMEM scratch across the sequential grid,
written out once at the end.

Inputs are the same precomputed per-component terms the oracle uses:
  log_prior (K,)  Wn (K,D,D)=nu W   b (K,D)=nu W m   c (K,)=D/beta + nu mWm

`gmm_estep_nodes` is the engine hot path: a whole sensor network at once,
x (N, T, D) with a (node, data-block) grid.  Each node has its own
per-component terms (its own current posterior), the data-block axis is the
minor (sequential) grid dimension so the VMEM accumulator carries per-node
partial statistics and is emitted once per node.  `gmm_estep` is the
single-node view (x (T, D)), a thin wrapper over the same kernel.

The engine only consumes the statistics; `return_r=False` drops the
responsibilities output entirely (no (N, T, K) write-back to HBM per
iteration — a multi-output pallas_call is opaque to XLA, so a dead output
would otherwise still be materialised).

Data may stream in a narrow dtype (bf16); quadratic forms and statistic
accumulation always run in f32 (`preferred_element_type`) — the engine's
precision-policy contract (see core/backends.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel_nodes(x_ref, mask_ref, lp_ref, wn_ref, b_ref, c_ref, rep_ref,
                  *out_refs, K: int, D: int, return_r: bool):
    """One (node, data-block) grid cell.  Every ref carries a leading
    node-block axis of 1; the accumulator is reset at the start of each
    node's (sequential, minor) data-block sweep and emitted — scaled by
    the replication factor (Appendix A) — at its end.
    out_refs = (r_ref, stats_ref, acc_ref) or (stats_ref, acc_ref).

    The per-component work runs as ROLLED `fori_loop`s over K (a dynamic
    index into the Wn ref feeds each (Tb, D) @ (D, D) MXU matmul, and each
    (D, D) second moment is added straight into its accumulator rows
    through a `pl.ds` ref slice): the trace/compile cost
    is O(1) in K, where the original unrolled per-component matmuls made
    compile time blow up past K ~ 16 (ROADMAP item; regression-tested by
    jaxpr size in tests/test_kernels.py)."""
    if return_r:
        r_ref, stats_ref, acc_ref = out_refs
    else:
        stats_ref, acc_ref = out_refs
    ti = pl.program_id(1)
    nt = pl.num_programs(1)

    @pl.when(ti == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[0].astype(jnp.float32)                     # (Tb, D)
    mask = mask_ref[0].astype(jnp.float32)               # (Tb, 1)
    lp = lp_ref[0].astype(jnp.float32)                   # (1, K)
    bmat = b_ref[0].astype(jnp.float32)                  # (K, D)
    cvec = c_ref[0].astype(jnp.float32)                  # (1, K)
    Tb = x.shape[0]
    # column selector for component k: Mosaic has no dynamic lane slices
    # or dynamic_update_slice on values, so the rolled loops read and
    # write column k of a (Tb, K) value through this mask instead
    k_iota = jax.lax.broadcasted_iota(jnp.int32, (Tb, K), 1)

    # quadratic forms: one MXU matmul per component, rolled over K
    def quad_body(k, quad):
        Wk = wn_ref[0, k].astype(jnp.float32)                # (D, D)
        xW = jax.lax.dot_general(x, Wk, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        qk = jnp.sum(xW * x, axis=1, keepdims=True)          # (Tb, 1)
        return quad + jnp.where(k_iota == k, qk, 0.0)

    quad = jax.lax.fori_loop(0, K, quad_body,
                             jnp.zeros((Tb, K), jnp.float32))
    cross = jax.lax.dot_general(x, bmat, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
    log_rho = lp - 0.5 * (quad - 2.0 * cross + cvec)

    m = jnp.max(log_rho, axis=1, keepdims=True)
    p = jnp.exp(log_rho - m)
    r = p / jnp.sum(p, axis=1, keepdims=True) * mask     # (Tb, K)
    if return_r:
        r_ref[0] = r.astype(r_ref.dtype)

    # accumulate sufficient statistics in VMEM scratch
    # acc layout: rows [0:K] = sum_x (K, D); row-blocks K + k*D : K+(k+1)*D
    # hold sum_xx_k (D, D); final row block holds R (K,) in col 0.
    sum_x = jax.lax.dot_general(r, x, (((0,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (K, D)
    acc_ref[0:K, :] += sum_x

    def xx_body(k, carry):
        rk = jnp.sum(jnp.where(k_iota == k, r, 0.0), axis=1,
                     keepdims=True)                          # (Tb, 1)
        xx = jax.lax.dot_general(x * rk, x, (((0,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[pl.ds(K + k * D, D), :] += xx
        return carry

    jax.lax.fori_loop(0, K, xx_body, 0)
    # R as a (K, 1) column: a lane sum of r^T (an r^T @ ones matmul here
    # trips the TPU compiler's transpose sharing with the matmuls above)
    acc_ref[K + K * D:K + K * D + K, 0:1] += jnp.sum(r.T, axis=1,
                                                     keepdims=True)

    @pl.when(ti == nt - 1)
    def _emit():
        # replication scaling lives kernel-side: the emitted statistics are
        # already the Appendix-A replicated R / sum_x / sum_xx
        stats_ref[0] = acc_ref[...] * rep_ref[0]


def gmm_estep_nodes(x, mask, log_prior, Wn, b, c, *, interpret: bool,
                    block_t: int = 512, return_r: bool = True,
                    replication=1.0):
    """Whole-network fused VBE step: x (N, T, D), mask (N, T), per-node
    per-component terms log_prior (N, K), Wn (N, K, D, D), b (N, K, D),
    c (N, K).  Returns (r (N, T, K), R (N, K), sum_x (N, K, D),
    sum_xx (N, K, D, D)) — `replication`-scaled stats (default 1.0 =
    unreplicated, node i matching ref.gmm_estep(x[i], ...)); the engine
    hot path passes the Appendix-A network-size factor so the scaling
    happens kernel-side at statistics-emit time instead of as a separate
    post-pass.  `replication` may be a traced scalar.  With
    `return_r=False` (the engine hot path, which only needs the
    statistics) r is None and never written to HBM.  `interpret` has no
    default: `kernels/ops.py` decides it from the platform.  Grid is
    (node, data-block) with the data axis minor, so each node's statistics
    accumulate sequentially in one VMEM scratch and are written out
    once."""
    N, T, D = x.shape
    K = log_prior.shape[-1]
    # The block size is a function of `block_t` ONLY — never of T.  Every
    # input is padded up to a multiple of the same block shape, so a
    # mask-zero-padded copy of the data sees bit-identical blocks (the
    # shared prefix) plus all-zero blocks whose statistics accumulate an
    # exact +0.0 through the sequential data-block grid.  That makes the
    # emitted statistics BIT-invariant to trailing padding — the serving
    # layer's bucketed-admission contract (serving/admission.py), mirroring
    # expfam.ordered_sum on the reference path.
    bt = max(8, block_t)
    Tp = ((T + bt - 1) // bt) * bt
    if Tp != T:
        x = jnp.pad(x, ((0, 0), (0, Tp - T), (0, 0)))
        mask = jnp.pad(mask, ((0, 0), (0, Tp - T)))
    rep = jnp.asarray(replication, jnp.float32).reshape(1)
    rows = K + K * D + K
    out_specs = [pl.BlockSpec((1, rows, D), lambda n, t: (n, 0, 0))]
    out_shape = [jax.ShapeDtypeStruct((N, rows, D), jnp.float32)]
    if return_r:
        out_specs.insert(0, pl.BlockSpec((1, bt, K), lambda n, t: (n, t, 0)))
        out_shape.insert(0, jax.ShapeDtypeStruct((N, Tp, K), jnp.float32))
    out = pl.pallas_call(
        functools.partial(_kernel_nodes, K=K, D=D, return_r=return_r),
        grid=(N, Tp // bt),
        in_specs=[
            pl.BlockSpec((1, bt, D), lambda n, t: (n, t, 0)),
            pl.BlockSpec((1, bt, 1), lambda n, t: (n, t, 0)),
            pl.BlockSpec((1, 1, K), lambda n, t: (n, 0, 0)),
            pl.BlockSpec((1, K, D, D), lambda n, t: (n, 0, 0, 0)),
            pl.BlockSpec((1, K, D), lambda n, t: (n, 0, 0)),
            pl.BlockSpec((1, 1, K), lambda n, t: (n, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((rows, D), jnp.float32)],
        interpret=interpret,
    )(x, mask[..., None], log_prior[:, None], Wn, b, c[:, None], rep)
    stats = out[-1]
    r = out[0][:, :T] if return_r else None
    sum_x = stats[:, 0:K, :]
    sum_xx = stats[:, K:K + K * D, :].reshape(N, K, D, D)
    R = stats[:, K + K * D:K + K * D + K, 0]
    return r, R, sum_x, sum_xx


def gmm_estep(x, mask, log_prior, Wn, b, c, *, interpret: bool,
              block_t: int = 512):
    """x (T, D), mask (T,).  Returns (r (T,K), R (K,), sum_x (K,D),
    sum_xx (K,D,D)) — unreplicated stats, matching ref.gmm_estep.  The
    single-node view of `gmm_estep_nodes` (one shared kernel body)."""
    r, R, sum_x, sum_xx = gmm_estep_nodes(
        x[None], mask[None], log_prior[None], Wn[None], b[None], c[None],
        block_t=block_t, interpret=interpret)
    return r[0], R[0], sum_x[0], sum_xx[0]
