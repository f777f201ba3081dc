"""The paper's technique as a data-parallel consensus layer for training.

Classical data parallelism computes the exact average of per-replica updates
every step — an all-reduce, the direct analogue of the fusion-centre VBM
solution Eq. 20 (cVB).  The paper replaces the fusion centre with one-hop
neighbour exchanges; lifted to training on a TPU mesh, the "sensor graph"
becomes the ICI/DCI ring along a mesh axis and the natural parameters become
the model parameters (Gaussian mean-field natural parameter with fixed
covariance == the weight itself; see DESIGN.md §2):

* `dp_mode="diffusion"` (dSVB, Eqs. 27a/27b): each replica takes its local
  optimiser step (the stochastic natural-gradient step — the lr schedule
  plays eta_t's Robbins-Monro role) and then combines parameters with its
  ring neighbours using nearest-neighbour weights (Eq. 47, w = 1/3 each).
* `dp_mode="admm"` (dVB-ADMM, Eqs. 38a/39/40): consensus-ADMM on the
  parameters with per-replica aggregate duals lambda_i and the kappa_t ramp.
  The primal step treats the locally-updated parameters as phi*_i; the
  projection (38b) is a no-op here because the parameter space of a weight
  is all of R^n (Omega = R^n) — noted in DESIGN.md.

Both run INSIDE a shard_map whose manual axis is the consensus axis
("data" single-pod, "pod" multi-pod); everything uses lax.ppermute — the
cheapest collective on a torus — instead of all-reduce.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.engine import (residual_balanced_rho, ring_combine,
                               ring_neighbors)

_ring_neighbors = ring_neighbors   # backward-compatible alias


def ring_size(axis: str) -> int:
    return jax.lax.axis_size(axis)


# ---------------------------------------------------------------------------
# dSVB-style diffusion (Eq. 27b with nearest-neighbour weights on a ring)
# — per-tensor form of the engine's RingDiffusion primitive
# ---------------------------------------------------------------------------
def diffusion_combine(params, axis: str, w_self: float = 1.0 / 3.0):
    def comb(p):
        out = ring_combine(p, axis, w_self, compute_dtype=jnp.float32)
        return out.astype(p.dtype)

    return jax.tree.map(comb, params)


# ---------------------------------------------------------------------------
# dVB-ADMM consensus (Eqs. 38a / 39 on a ring; deg_i = 2)
# ---------------------------------------------------------------------------
def admm_init_duals(params):
    return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)


def admm_step(params_star, params_prev, duals, axis: str, *, rho: float,
              kappa, return_residuals: bool = False):
    """One primal+dual ADMM consensus round.

    params_star: locally-optimised parameters (phi*_i of Eq. 18 — here the
    post-AdamW parameters).  params_prev: last round's consensus iterate.
    Returns (new_params, new_duals), plus the global (||r||, ||s||) RMS
    residual norms when `return_residuals` — computed from the SAME ring
    exchange the dual ascent already performs, so the observability is
    communication-free.
    """
    deg = 2.0

    def primal(p_star, p_prev, lam):
        left, right = _ring_neighbors(p_prev.astype(jnp.float32), axis)
        num = (p_star.astype(jnp.float32) - 2.0 * lam
               + rho * (deg * p_prev.astype(jnp.float32) + left + right))
        return (num / (1.0 + 2.0 * rho * deg)).astype(p_star.dtype)

    new_params = jax.tree.map(primal, params_star, params_prev, duals)

    def ring_resid(p_new):                    # Eq. 39: 2 p_i - p_{i-1} - p_{i+1}
        pf = p_new.astype(jnp.float32)
        left, right = _ring_neighbors(pf, axis)
        return deg * pf - left - right

    resid = jax.tree.map(ring_resid, new_params)
    new_duals = jax.tree.map(lambda lam, r: lam + kappa * rho / 2.0 * r,
                             duals, resid)
    if not return_residuals:
        return new_params, new_duals
    return new_params, new_duals, _rms_norms(
        jax.tree.leaves(resid),
        [rho * (pn.astype(jnp.float32) - pp.astype(jnp.float32))
         for pn, pp in zip(jax.tree.leaves(new_params),
                           jax.tree.leaves(params_prev))], axis)


# ---------------------------------------------------------------------------
# Adaptive penalty for the training-layer ADMM mode — the VB engine's
# residual-balancing rule (engine.residual_balanced_rho) on ring residuals
# ---------------------------------------------------------------------------
def _rms_norms(r_leaves, s_leaves, axis: str):
    """Global RMS norms of two residual leaf-lists (psum over `axis`)."""
    r_sq = sum(jnp.sum(r * r) for r in r_leaves)
    s_sq = sum(jnp.sum(s * s) for s in s_leaves)
    n = sum(r.size for r in r_leaves)
    r_sq = jax.lax.psum(r_sq, axis)
    s_sq = jax.lax.psum(s_sq, axis)
    n = jax.lax.psum(jnp.asarray(n, jnp.float32), axis)
    return jnp.sqrt(r_sq / n), jnp.sqrt(s_sq / n)


def admm_residual_norms(params_new, params_prev, axis: str, *, rho):
    """(||r||, ||s||) of one ADMM consensus round on the ring, as global
    RMS norms over all tensors and replicas (psum over `axis`).

    r is the Eq. 39 disagreement 2 p_i - p_{i-1} - p_{i+1}; s is Boyd's
    dual residual rho (p^t - p^{t-1}).  Feed them to `adapt_rho` between
    training steps to residual-balance `rho` exactly like the VB engine's
    `ADMMConsensus(adaptive_rho=True)` does per VB iteration.  (Inside
    `admm_step(return_residuals=True)` the same norms ride along on the
    dual update's own ring exchange — prefer that form on a hot path.)
    """
    r_leaves, s_leaves = [], []
    for p_new, p_prev in zip(jax.tree.leaves(params_new),
                             jax.tree.leaves(params_prev)):
        pf = p_new.astype(jnp.float32)
        left, right = _ring_neighbors(pf, axis)
        r_leaves.append(2.0 * pf - left - right)
        s_leaves.append(rho * (pf - p_prev.astype(jnp.float32)))
    return _rms_norms(r_leaves, s_leaves, axis)


def adapt_rho(rho, r_norm, s_norm, *, mu: float = 10.0,
              tau_incr: float = 2.0, tau_decr: float = 2.0,
              rho_min: float = 1e-3, rho_max: float = 1e3):
    """Residual-balance the training-layer ADMM penalty (Boyd Sec. 3.4.1);
    thin alias of the engine rule so both layers share one implementation."""
    return residual_balanced_rho(rho, r_norm, s_norm, mu=mu,
                                 tau_incr=tau_incr, tau_decr=tau_decr,
                                 rho_min=rho_min, rho_max=rho_max)


# ---------------------------------------------------------------------------
# Disagreement diagnostic (how far replicas are from consensus)
# ---------------------------------------------------------------------------
def consensus_residual(params, axis: str) -> jnp.ndarray:
    """mean over tensors of ||phi_i - mean_j phi_j||^2 (cheap: psum)."""
    def res(p):
        pf = p.astype(jnp.float32)
        mean = jax.lax.pmean(pf, axis)
        return jnp.mean((pf - mean) ** 2)

    leaves = jax.tree.leaves(jax.tree.map(res, params))
    return jnp.mean(jnp.stack(leaves))
