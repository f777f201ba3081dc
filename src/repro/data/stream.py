"""Streaming minibatch layer for the VB engine (Algorithm 1, stochastic form).

The paper's Algorithm 1 is a *stochastic* natural-gradient method: the
Robbins-Monro schedule eta_t (Eq. 22/29) exists precisely so each node may
estimate its local optimum phi*_i from a random subsample of its data.  The
engine's full-batch path never exercised that; this module supplies the
missing sampling layer:

* `MinibatchSpec(batch_size, seed)` — the run-level request handed to
  `engine.run_vb(..., minibatch=)`.
* `node_keys(n_nodes, seed)` — one fold-in PRNG key per GLOBAL node index,
  built host-side before any executor splits the node axis.  Because the
  key is per-node data (sharded along the node axis exactly like x), the
  single-array executor, the shard_map executor and both compute backends
  draw IDENTICAL minibatches for node i at iteration t.
* `StreamState(keys, perm, epoch)` — the carried sampler state owned by
  `engine.VBState`: the per-node fold-in keys plus the CURRENT epoch's
  permutation.  `init_state` builds it, `advance(state, base_mask, t,
  batch_size)` is the per-iteration sampler used inside
  `engine._scan_steps`: it refreshes the permutation only at epoch
  boundaries (a scalar-predicate `lax.cond`, so steady-state iterations
  pay an O(B) gather instead of the old O(T log T) per-step redraw) and
  returns gather indices plus a *scaled* mask.  Because the refreshed
  permutation is the same `fold_in(key, epoch)` draw the stateless
  sampler makes, the carried path is BIT-EXACT with it — and because
  everything is keyed on the ABSOLUTE iteration t, a run split across
  `vb_run` calls (or a checkpoint restore) replays the identical stream.
* `minibatch_select(keys, base_mask, t, batch_size)` — the stateless
  reference sampler (kept as the oracle the carried path is tested
  against).

Sampling is *random reshuffling* (epoch cycling): each epoch draws a fresh
uniform permutation of the node's sample slots and the iterations of that
epoch walk through it in `batch_size` windows (wrapping at the end, so
every slot is visited at least once per epoch — exactly once when
`batch_size` divides the capacity).  Any fixed index window of a uniform
permutation is a uniform without-replacement sample, so each iteration's
statistics are unbiased exactly as with iid sampling — but batches within
an epoch are (near-)disjoint, which cancels most of the within-epoch
noise (the classic random-reshuffling advantage over iid minibatching; on
the paper's 50-node GMM it cuts the stochastic KL gap several-fold, see
benchmarks/minibatch_bench.py).

The scaled mask carries the stochastic-VB rescaling: every selected valid
point gets the constant weight T/B (slot capacity / batch size; a slot
lands in the window with probability B/T), making the sufficient
statistics — which are linear in the mask — exactly unbiased estimators
of their full-batch values even on ragged nodes, composing with the
Appendix-A `replication` factor untouched.  Since the GMM natural
parameters are linear in the sufficient statistics, E[phi*_minibatch] =
phi*_full exactly (tests/test_streaming.py asserts this by Monte Carlo).

Full-batch degeneracy: with `batch_size` = the per-node sample capacity
there is one window per epoch, the sorted window is the identity gather,
and the T/T scale multiplies the mask by exactly 1.0 — so
`MinibatchSpec(batch_size=n_per_node)` computes the full-batch
statistics on every estimator and executor.  It is a different program
(the gather is in it), so it agrees with the full-batch run to rounding,
not bit for bit.

Variance reduction (`MinibatchSpec(control_variate="svrg")`): on top of
reshuffling, `StreamState` can carry a full-batch ANCHOR — a snapshot
iterate `anchor_phi` (N, P) and its full-batch local optimum `anchor_full`
(N, P), refreshed once per epoch inside `engine._iteration`.  The engine
then uses the SVRG-corrected estimator

    phi*_svrg(t) = phi*_B(phi_t) - phi*_B(anchor_phi) + anchor_full

where phi*_B(.) is the minibatch local optimum on iteration t's window.
Both minibatch terms share the SAME window, so the correction is a classic
control variate: E_B[phi*_B(anchor_phi)] = anchor_full exactly (statistics
are linear in the scaled mask), hence the estimator stays exactly unbiased
while the shared-window correlation cancels most of the sampling noise
(Khan's information-geometry view: the natural-gradient step is linear in
the local optimum, so variance reduction on phi* is variance reduction on
the step).  The anchors ride in the engine's carried state — checkpoint /
session-split safe — and the full-batch degeneracy above is untouched:
with batch_size >= capacity the engine never materialises the correction.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.telemetry import taps


class MinibatchSpec(NamedTuple):
    """Per-node minibatch request for a streaming `run_vb` call.

    batch_size : points visited per node per iteration (static; the E-step
        then runs on a (N, batch_size, D) gather instead of the full
        (N, Ni_max, D) array — the FLOPs saving is batch_size/Ni_max).
    seed : base seed of the deterministic per-(node, epoch) reshuffling
        stream.
    control_variate : None (plain reshuffling) or "svrg" — carry a
        full-batch anchor in `StreamState` and apply the SVRG-style
        corrected estimator (module docstring); exactly unbiased, large
        variance reduction at equal iteration count.  Inert when
        batch_size >= the per-node capacity (full batch is noise-free).
    """

    batch_size: int
    seed: int = 0
    control_variate: str | None = None


def node_keys(n_nodes: int, seed: int) -> jnp.ndarray:
    """(N, 2) uint32 per-node stream keys, derived from the GLOBAL node
    index so every executor layout sees the same per-node stream."""
    base = jax.random.PRNGKey(seed)
    return jax.vmap(lambda i: jax.random.fold_in(base, i))(
        jnp.arange(n_nodes))


class StreamState(NamedTuple):
    """Carried sampler state: one epoch permutation per node.

    keys : (N, 2) uint32 per-node fold-in keys (`node_keys`); constant.
    perm : (N, T) int32 — epoch `epoch`'s reshuffling permutation of each
        node's sample slots.
    epoch : () int32 — the epoch `perm` belongs to (refreshed by `advance`
        when the absolute iteration crosses an epoch boundary).
    anchor_phi : None, or (N, P) — the SVRG anchor iterate (the phi_nodes
        snapshot taken at the last epoch boundary).  None unless
        `MinibatchSpec(control_variate="svrg")` is active.
    anchor_full : None, or (N, P) — the full-batch local optimum
        phi*_full(anchor_phi), refreshed together with `anchor_phi`.
    """

    keys: jnp.ndarray
    perm: jnp.ndarray
    epoch: jnp.ndarray
    anchor_phi: jnp.ndarray | None = None
    anchor_full: jnp.ndarray | None = None


def _epoch_perms(keys: jnp.ndarray, epoch: jnp.ndarray,
                 capacity: int) -> jnp.ndarray:
    """(N, T) epoch permutations — the same `fold_in(key, epoch)` draw as
    the stateless `_select_one`, so carried and stateless paths agree
    bit-for-bit."""
    return jax.vmap(lambda k: jax.random.permutation(
        jax.random.fold_in(k, epoch), capacity))(keys).astype(jnp.int32)


def init_state(n_nodes: int, seed: int, capacity: int) -> StreamState:
    """Stream state at t=0: per-node keys + the epoch-0 permutations."""
    keys = node_keys(n_nodes, seed)
    epoch0 = jnp.zeros((), jnp.int32)
    return StreamState(keys, _epoch_perms(keys, epoch0, capacity), epoch0)


def advance(state: StreamState, base_mask: jnp.ndarray, t: jnp.ndarray,
            batch_size: int):
    """Carried-permutation form of `minibatch_select`.

    Returns (state', idx (N, B) int32, mb_mask (N, B) scaled mask) for the
    ABSOLUTE iteration t.  The permutation refresh happens only when t
    crosses an epoch boundary (scalar-predicate `lax.cond`: epochs are
    global because every node shares the padded capacity T), and the
    refresh draw is identical to the stateless sampler's, so the
    trajectory of (idx, mb_mask) is bit-exact with `minibatch_select` —
    including across a `vb_run` split or checkpoint restore, since epoch
    and chunk are pure functions of t.
    """
    T = base_mask.shape[1]
    batch_size = min(batch_size, T)
    n_chunks = -(-T // batch_size)                    # ceil: cover everything
    epoch = (t // n_chunks).astype(state.epoch.dtype)
    chunk = t % n_chunks
    perm = jax.lax.cond(epoch != state.epoch,
                        lambda: _epoch_perms(state.keys, epoch, T),
                        lambda: state.perm)
    if taps.enabled():
        # trace-time-gated device tap (telemetry/taps.py): epoch index per
        # iteration — rollovers show as increments in the tapped series.
        # No jaxpr change when taps are off.
        taps.tap("stream/epoch", epoch, t=t)
    pos = (chunk * batch_size + jnp.arange(batch_size)) % T
    idx = jnp.sort(jnp.take(perm, pos, axis=1), axis=1).astype(jnp.int32)
    picked = jnp.take_along_axis(base_mask, idx, axis=1)  # 0 where padding
    scale = jnp.asarray(T / batch_size, base_mask.dtype)
    return state._replace(perm=perm, epoch=epoch), idx, picked * scale


def state_specs(state: StreamState, axis: str) -> StreamState:
    """Partition specs for a carried `StreamState` under a node-sharded
    mesh: per-node leaves (keys, perm, and the SVRG anchors when present)
    shard along `axis`, the scalar epoch replicates.  Mirrors the value
    tree's None structure so the specs stay a valid shard_map prefix tree
    whether or not the run carries anchors."""
    from jax.sharding import PartitionSpec as P
    return StreamState(
        keys=P(axis), perm=P(axis), epoch=P(),
        anchor_phi=None if state.anchor_phi is None else P(axis),
        anchor_full=None if state.anchor_full is None else P(axis))


def _select_one(key: jnp.ndarray, base_mask: jnp.ndarray, t: jnp.ndarray,
                batch_size: int):
    """One node's chunk at iteration t: (idx (B,) int32, scaled mask (B,)).

    Epoch e = t // n_chunks draws permutation_e of the sample slots;
    iteration t takes window (t mod n_chunks) of it — wrapping around the
    end when batch_size does not divide the capacity, so every slot is
    visited at least once per epoch (exactly once when it divides) —
    sorted ascending (with one chunk per epoch this makes the gather the
    identity permutation: the full-batch degeneracy).

    The weight on every selected VALID point is the constant T/B
    (capacity/batch): any fixed index window of a uniform permutation is a
    uniform without-replacement draw, so each slot lands in the window
    with probability B/T and the T/B reweighting makes the statistics
    exactly unbiased — including on ragged nodes, where a window may
    contain few (or zero) valid points.  (A realized-count ratio like
    n_i/|B_i| would be biased there: it cannot compensate for the
    all-padding windows that contribute nothing.)

    Cost note: the permutation is redrawn every iteration (O(T log T) per
    node), though it only changes once per epoch — fine for sensor-sized
    buffers; a huge-buffer deployment would carry the epoch permutation in
    the scan state instead (ROADMAP follow-up).
    """
    T = base_mask.shape[0]
    n_chunks = -(-T // batch_size)                    # ceil: cover everything
    epoch = t // n_chunks
    chunk = t % n_chunks
    ke = jax.random.fold_in(key, epoch)
    perm = jax.random.permutation(ke, T)
    pos = (chunk * batch_size + jnp.arange(batch_size)) % T
    idx = jnp.sort(jnp.take(perm, pos)).astype(jnp.int32)
    picked = jnp.take(base_mask, idx)                 # 0 where padding
    scale = jnp.asarray(T / batch_size, base_mask.dtype)
    return idx, picked * scale


def minibatch_select(keys: jnp.ndarray, base_mask: jnp.ndarray,
                     t: jnp.ndarray, batch_size: int):
    """Whole-network draw at iteration t.

    keys (N, 2) from `node_keys` (or the executor's local slice of it),
    base_mask (N, T) validity mask.  Returns (idx (N, B) int32 gather
    indices into the node's sample axis, mb_mask (N, B) scaled minibatch
    mask).  Deterministic in (seed, global node index, t) only.
    """
    return jax.vmap(lambda k, m: _select_one(k, m, t, batch_size))(
        keys, base_mask)
