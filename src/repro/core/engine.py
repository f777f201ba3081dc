"""Unified conjugate-exponential VB engine: Model x Topology x Executor.

Every estimator in the paper is the same per-iteration kernel — each node
runs a VBE step + local VBM optimum to get phi*_i (Eq. 18) — followed by a
topology-specific rule for turning the stack {phi*_i} into the next iterate.
This module owns that second half ONCE; `core/algorithms.py` (GMM),
`core/linreg.py` (Normal-Gamma) and `core/distributed.py` (shard_map mesh
runners) are thin wrappers over `run_vb`.

Equation -> code map (the only implementations in the repo; the full map
with Eqs. 38-40 spelled out lives in docs/ARCHITECTURE.md):

* Eq. 20   fusion-centre average                `FusionCenter.combine`
* Eq. 22/29 Robbins-Monro step size eta_t       `eta_schedule` / `Schedule`
* Eq. 27a  natural-gradient step                `_CombineTopology.step`
* Eq. 27b  diffusion combine                    `Diffusion.combine` /
                                                `RingDiffusion.combine`
                                                (`ring_combine*` collectives)
* Eq. 38a  ADMM primal update                   `ADMMConsensus.step`
* Eq. 38b  projection onto Omega                `ADMMConsensus.step` (via
                                                `model.project_to_domain`)
* Eq. 39   ADMM dual ascent                     `ADMMConsensus.step`
* Eq. 40   kappa_t dual-step ramp               `kappa_schedule`
* Eq. 46   KL performance metric                `kl_to_reference`
* Eq. 47   nearest-neighbour weights            `network.nearest_neighbor_weights`
                                                (ring case: `RingDiffusion`)

Every graph topology runs dense ((N, N) matrix — the small-N parity
oracle) or sparse (`network.SparseGraph` edge lists via `_sparse_combine`
— O(E + N), 10k+ nodes), and two scenario topologies build on the sparse
layer: `PairwiseGossip` (asynchronous randomized link activation,
deterministic in (seed, absolute t)) and `HierarchicalFusion`
(sensor -> gateway -> region).  See docs/sparse-topologies.md.

`ADMMConsensus` additionally carries the adaptive-penalty consensus
subsystem (off by default; Algorithm 2 verbatim otherwise): residual
balancing of rho (Boyd et al., "Distributed Optimization and Statistical
Learning via ADMM", Sec. 3.4.1), per-block dual scaling over the model's
natural-parameter blocks, a residual-gated dual warmup, and dual reset on
Eq. 38b eigen-clip activation, all observable through the per-iteration
`ConsensusDiagnostics` record on `VBRun.consensus_diag`.  The convergence
story (why plain Algorithm 2 winds up on imbalanced instances and how the
subsystem fixes it) is docs/admm-convergence.md.

Executors: the default executor runs the node axis as a plain array axis
(whole runs jit + lax.scan); `MeshExecutor(mesh, axis)` runs the SAME step
function under shard_map with the node axis sharded over a mesh axis, with
each topology supplying its collective form (all_gather for arbitrary
graphs, ppermute for the ICI ring, psum-mean for the fusion centre).
Numerical equivalence of the two executors is asserted in the test-suite.

Backends: orthogonally to the executor, `run_vb(..., backend=)` selects
the COMPUTE implementation of the per-node hot path (model.local_optimum)
via core/backends.py — "reference" einsums or the "fused" Pallas kernel —
for models that support it.  Backend x executor parity is asserted in
tests/test_backends.py.

Streaming: `run_vb(..., minibatch=stream.MinibatchSpec(batch_size, seed))`
runs the stochastic form of every estimator — per-iteration reshuffled
minibatches with unbiased n_i/|B| statistics rescaling (data/stream.py),
which is what makes the Robbins-Monro `Schedule` a genuine stochastic
natural-gradient step.  Time-varying networks: `Diffusion`,
`RingDiffusion` and `ADMMConsensus` take `link_drop` / `link_mask_fn`
(see `_LinkSchedule`) to run over per-iteration failing links, with the
surviving fraction observable as `ConsensusDiagnostics.link_frac`.
Both compose with both executors and both backends
(tests/test_streaming.py).

Sessions: the engine is organised around an explicit, checkpointable
state object — `vb_init(model, data, topology, ...)` returns a `VBState`
pytree (phi, absolute iteration t, topology carry incl. ADMM duals/rho,
minibatch-sampler stream state, last diagnostics), `vb_step(state)`
advances one iteration and `vb_run(state, n_iters)` scans it.  All
per-iteration randomness is keyed on the absolute t carried in the state,
so runs split across calls (or checkpoint save/restore via
`checkpoint/ckpt.py`) are bit-exact with the unsplit run; `run_vb` is the
thin one-shot wrapper.  The serving layer (`serving/vb_service.py`)
batches many independent sessions along a leading fleet axis over
`session_step_fn`.
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro import telemetry
from repro.core import network as network_lib
from repro.data import stream
from repro.telemetry import taps


# ---------------------------------------------------------------------------
# Step-size schedules (Eqs. 29 and 40)
# ---------------------------------------------------------------------------
def eta_schedule(t: jnp.ndarray, tau: float, d0: float = 1.0) -> jnp.ndarray:
    """eta_t = 1 / (d0 + tau * t); satisfies Robbins-Monro (Eq. 22).

    >>> import jax.numpy as jnp
    >>> [round(float(eta_schedule(jnp.asarray(t), tau=0.5)), 3)
    ...  for t in (1.0, 2.0, 10.0)]
    [0.667, 0.5, 0.167]
    """
    return 1.0 / (d0 + tau * t)


def kappa_schedule(t: jnp.ndarray, xi: float = 0.05) -> jnp.ndarray:
    """kappa_t = 1 - 1/(1 + xi t)^2 ramps the ADMM dual step (Eq. 40).

    >>> import jax.numpy as jnp
    >>> kap = kappa_schedule(jnp.arange(1.0, 100.0))
    >>> bool(kap[0] < 0.15), bool(kap[-1] > 0.95)
    (True, True)
    """
    return 1.0 - 1.0 / (1.0 + xi * t) ** 2


class Schedule(NamedTuple):
    """eta_t used by the natural-gradient step (27a).

    `eta_fixed=1.0` recovers the one-shot estimators (cVB / noncoop /
    nsg-dVB), where the iterate jumps straight to (a combination of) the
    local optima; `eta_fixed=None` is the paper's Robbins-Monro schedule.

    >>> import jax.numpy as jnp
    >>> round(float(Schedule(tau=0.2).eta(jnp.asarray(0.0))), 4)  # t=1
    0.8333
    >>> float(ONE_SHOT.eta(jnp.asarray(0.0)))              # jump to phi*
    1.0
    """

    tau: float = 0.2
    d0: float = 1.0
    eta_fixed: Optional[float] = None

    def eta(self, t: jnp.ndarray, hyper=None) -> jnp.ndarray:
        """eta_t.  `hyper` is the optional per-session lifted-hyper dict
        the serving layer threads through the fleet axis (see
        `hyper_names`): entries override the static `tau` / `d0` so
        sessions differing only in schedule constants share a compiled
        fleet.  None (every solo path) reproduces the static behaviour
        exactly."""
        if self.eta_fixed is not None:
            return jnp.asarray(self.eta_fixed, t.dtype)
        tau = self.tau if not hyper or "tau" not in hyper else hyper["tau"]
        d0 = self.d0 if not hyper or "d0" not in hyper else hyper["d0"]
        return eta_schedule(t + 1.0, tau, d0)


ONE_SHOT = Schedule(eta_fixed=1.0)


# ---------------------------------------------------------------------------
# Ring collectives (Eq. 27b on the TPU ICI ring) — shared by the mesh
# executor AND the training-layer consensus optimiser (optim/consensus.py)
# ---------------------------------------------------------------------------
def _ring_perms(n: int):
    fwd = [(i, (i + 1) % n) for i in range(n)]
    bwd = [(i, (i - 1) % n) for i in range(n)]
    return fwd, bwd


def ring_neighbors(x: jnp.ndarray, axis_name: str):
    """(x_{i-1}, x_{i+1}) along the mesh-axis ring, via two ppermutes."""
    fwd, bwd = _ring_perms(jax.lax.axis_size(axis_name))
    return (jax.lax.ppermute(x, axis_name, fwd),
            jax.lax.ppermute(x, axis_name, bwd))


def ring_combine(x: jnp.ndarray, axis_name: str, w_self: float = 1.0 / 3.0,
                 compute_dtype=None) -> jnp.ndarray:
    """Eq. 27b with ring nearest-neighbour weights for ONE tensor per mesh
    slot: x_i <- w_self x_i + w_n (x_{i-1} + x_{i+1}).  With w_self = 1/3
    this is exactly Eq. 47 on a cycle graph.

    `compute_dtype` upcasts AFTER the ppermutes, so the wire traffic stays
    in the storage dtype (bf16 weights exchange bf16 bytes) while the
    weighted sum accumulates at higher precision.
    """
    left, right = ring_neighbors(x, axis_name)
    if compute_dtype is not None:
        x, left, right = (a.astype(compute_dtype) for a in (x, left, right))
    w_n = (1.0 - w_self) / 2.0
    return w_self * x + w_n * (left + right)


def ring_combine_block(varphi: jnp.ndarray, axis_name: str,
                       w_self: float = 1.0 / 3.0) -> jnp.ndarray:
    """Eq. 27b on a ring for a BLOCK of nodes per mesh slot (leading axis =
    local nodes).  Interior neighbours are a local roll; only the two
    boundary rows cross the ICI link (ppermute) — the minimal-traffic
    neighbour exchange."""
    fwd, bwd = _ring_perms(jax.lax.axis_size(axis_name))
    prev_tail = jax.lax.ppermute(varphi[-1:], axis_name, fwd)
    next_head = jax.lax.ppermute(varphi[:1], axis_name, bwd)
    shifted_right = jnp.concatenate([prev_tail, varphi[:-1]], 0)  # phi_{i-1}
    shifted_left = jnp.concatenate([varphi[1:], next_head], 0)    # phi_{i+1}
    w_n = (1.0 - w_self) / 2.0
    return w_self * varphi + w_n * (shifted_right + shifted_left)


# ---------------------------------------------------------------------------
# Residual balancing (Boyd et al. Sec. 3.4.1) — ONE rule shared by the VB
# consensus topology below and the training-layer consensus optimiser
# (optim/consensus.py)
# ---------------------------------------------------------------------------
def residual_balanced_rho(rho, r_norm, s_norm, *, mu: float = 10.0,
                          tau_incr: float = 2.0, tau_decr: float = 2.0,
                          rho_min: float = 1e-3, rho_max: float = 1e3):
    """One residual-balancing update of the ADMM penalty.

    Grow rho by `tau_incr` where the primal residual dominates
    (||r|| > mu ||s||: the iterates still disagree, press harder), shrink
    by `tau_decr` where the dual residual dominates (||s|| > mu ||r||: the
    penalty is bullying the local objectives), else leave unchanged;
    always clip to [rho_min, rho_max].  Shapes broadcast, so `rho` may be
    a scalar or a per-block vector.

    >>> import jax.numpy as jnp
    >>> float(residual_balanced_rho(jnp.asarray(1.0), 100.0, 1.0))
    2.0
    >>> float(residual_balanced_rho(jnp.asarray(1.0), 1.0, 100.0))
    0.5
    >>> float(residual_balanced_rho(jnp.asarray(1.0), 1.0, 2.0))
    1.0
    """
    grow = r_norm > mu * s_norm
    shrink = s_norm > mu * r_norm
    fac = jnp.where(grow, tau_incr, jnp.where(shrink, 1.0 / tau_decr, 1.0))
    return jnp.clip(rho * fac, rho_min, rho_max)


# ---------------------------------------------------------------------------
# Time-varying links (failing sensor links, Sec. II's unreliable networks)
# ---------------------------------------------------------------------------
class _LinkSchedule:
    """Per-iteration link-failure schedule shared by the topologies.

    Two forms, mutually exclusive:

    * `link_drop` — every undirected link independently fails with this
      probability each iteration (Bernoulli, deterministic in
      (`link_seed`, t) via `network.link_keep_matrix` /
      `network.ring_link_keep`, so both executors replay the identical
      failure pattern).
    * `link_mask_fn(t)` — an explicit keep-mask sequence: a traceable
      callable returning the iteration-t keep mask ((N, N) 0/1 symmetric
      for graph topologies, (N,) per ring edge for `RingDiffusion`).  An
      explicit adjacency sequence whose edges are a subset of the base
      graph is `lambda t: adj_seq[t]`-style.

    With neither set the topology is static and every code path is
    bit-identical to the time-invariant engine (golden-parity guarantee).
    """

    def __init__(self, link_drop: float = 0.0, link_seed: int = 0,
                 link_mask_fn: Optional[Callable] = None):
        if link_drop and link_mask_fn is not None:
            raise ValueError("pass link_drop OR link_mask_fn, not both")
        if not 0.0 <= link_drop <= 1.0:
            raise ValueError(f"link_drop must be a probability: {link_drop}")
        self.link_drop = float(link_drop)
        self.link_mask_fn = link_mask_fn
        self.time_varying = bool(link_drop) or link_mask_fn is not None
        self._link_key = (jax.random.PRNGKey(link_seed)
                          if self.time_varying and link_mask_fn is None
                          else None)

    def _require_t(self, t):
        if t is None:
            raise ValueError(
                "time-varying links need the iteration index: call "
                "combine(..., t=<iteration>) (run_vb supplies it "
                "automatically)")
        return t

    def keep_matrix(self, t, n: int, dtype) -> jnp.ndarray:
        t = self._require_t(t)
        if self.link_mask_fn is not None:
            return jnp.asarray(self.link_mask_fn(t)).astype(dtype)
        return network_lib.link_keep_matrix(self._link_key, t, n,
                                            self.link_drop, dtype)

    def keep_ring(self, t, n: int, dtype) -> jnp.ndarray:
        t = self._require_t(t)
        if self.link_mask_fn is not None:
            return jnp.asarray(self.link_mask_fn(t)).astype(dtype)
        return network_lib.ring_link_keep(self._link_key, t, n,
                                          self.link_drop, dtype)

    def keep_edges(self, t, n_undirected: int, dtype) -> jnp.ndarray:
        """Edge-list form: (E_undirected,) keep mask — one coin per
        undirected link (`network.sparse_link_keep`), so a failed link is
        failed both ways, exactly the dense contract.  A `link_mask_fn`
        must return the (E_undirected,) mask in the graph's link order."""
        t = self._require_t(t)
        if self.link_mask_fn is not None:
            return jnp.asarray(self.link_mask_fn(t)).astype(dtype)
        return network_lib.sparse_link_keep(self._link_key, t, n_undirected,
                                            self.link_drop, dtype)


def _local_rows(full: jnp.ndarray, n_local: int, axis: str) -> jnp.ndarray:
    """This shard's contiguous row block of a replicated (N, ...) array."""
    row0 = jax.lax.axis_index(axis) * n_local
    return jax.lax.dynamic_slice_in_dim(full, row0, n_local, axis=0)


def _segment_sum(x: jnp.ndarray, graph) -> jnp.ndarray:
    """sum over directed edges into each receiver — the sparse neighbour
    reduce.  Edges are receiver-sorted by `SparseGraph` construction."""
    return jax.ops.segment_sum(x, graph.receivers,
                               num_segments=graph.n_nodes,
                               indices_are_sorted=True)


def _sparse_combine(sw, varphi: jnp.ndarray,
                    keep_und: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Eq. 27b in edge-list form: phi_i <- w_self_i varphi_i
    + sum_{e: recv(e)=i} w_e varphi_send(e), via one `segment_sum` over
    the directed edges — O(E P) compute, O(N P + E) memory, never an
    (N, N) matrix.

    `keep_und` gates the undirected links of a time-varying network: the
    surviving weights renormalise per receiver (for Eq. 47 weights that
    IS Eq. 47 on the surviving graph — the dense `_effective_weights`
    semantics), and a fully isolated node (no live links AND zero
    self-weight) keeps its own iterate (`RingDiffusion._gated`
    semantics).
    """
    g = sw.graph
    w_e = sw.w_edge.astype(varphi.dtype)
    w_s = sw.w_self.astype(varphi.dtype)
    msg = varphi[g.senders]                        # (E, P)
    if keep_und is None:
        return w_s[:, None] * varphi + _segment_sum(w_e[:, None] * msg, g)
    w_e = w_e * keep_und[g.edge_id].astype(varphi.dtype)
    num = w_s[:, None] * varphi + _segment_sum(w_e[:, None] * msg, g)
    den = w_s + _segment_sum(w_e, g)
    isolated = den <= 0.0
    safe = jnp.where(isolated, jnp.ones_like(den), den)
    return jnp.where(isolated[:, None], varphi, num / safe[:, None])


# ---------------------------------------------------------------------------
# Topologies / combiners
# ---------------------------------------------------------------------------
class _CombineTopology:
    """Topologies of the form: (27a) varphi_i = phi_i + eta (phi*_i - phi_i),
    then a linear combine of {varphi_i}.  Subclasses supply `combine`.

    `step` returns (phi_next, carry_next, diag): the third slot is the
    per-iteration diagnostics pytree (None for combine topologies; only
    `ADMMConsensus` emits a `ConsensusDiagnostics`)."""

    uses_schedule = True
    emits_diagnostics = False

    def shard_inputs(self) -> dict:
        """Per-node arrays the mesh executor must shard along the node axis
        (e.g. the rows of the combination-weight matrix)."""
        return {}

    def init_carry(self, phi0: jnp.ndarray, model=None):
        return None

    def init_diag(self, model, phi0: jnp.ndarray):
        """Structure-stable t=0 value of the per-iteration diagnostics
        record (None for combine topologies: they emit none)."""
        return None

    def carry_specs(self, axis: str):
        """shard_map PartitionSpec pytree for `init_carry`'s output (leaf
        prefix: per-node arrays shard their leading node axis)."""
        from jax.sharding import PartitionSpec as P
        return P(axis)

    def combine(self, varphi, *, axis=None, local=None, t=None):
        raise NotImplementedError

    def step(self, model, phi, carry, phi_star, t, schedule: Schedule, *,
             axis=None, local=None, hyper=None):
        eta = schedule.eta(t.astype(phi.dtype), hyper)
        if schedule.eta_fixed == 1.0:
            varphi = phi_star                       # one-shot: jump to phi*
        else:
            varphi = phi + eta * (phi_star - phi)   # Eq. 27a
        return (self.combine(varphi, axis=axis, local=local, t=t),
                carry, None)


class FusionCenter(_CombineTopology):
    """Centralised reference: phi <- mean_i phi*_i exactly (Eq. 20).

    Every node ends up holding the same iterate — the fusion-centre average
    of the local optima:

    >>> import jax.numpy as jnp
    >>> varphi = jnp.asarray([[0.0, 2.0], [2.0, 4.0]])   # (N=2, P=2)
    >>> FusionCenter().combine(varphi).tolist()
    [[1.0, 3.0], [1.0, 3.0]]
    """

    def combine(self, varphi, *, axis=None, local=None, t=None):
        if axis is None:
            mean = jnp.mean(varphi, axis=0)
        else:
            mean = jax.lax.pmean(jnp.mean(varphi, axis=0), axis)
        return jnp.broadcast_to(mean, varphi.shape)


class Isolated(_CombineTopology):
    """No communication (noncoop-VB): every node keeps its own iterate.

    >>> import jax.numpy as jnp
    >>> varphi = jnp.asarray([[1.0], [2.0]])
    >>> bool(jnp.all(Isolated().combine(varphi) == varphi))
    True
    """

    def combine(self, varphi, *, axis=None, local=None, t=None):
        return varphi


class Diffusion(_CombineTopology):
    """Arbitrary-graph diffusion combine phi_i <- sum_j w_ij varphi_j
    (Eq. 27b) with a row-stochastic weight matrix (e.g. Eq. 47).

    `link_drop` / `link_mask_fn` make the network time-varying: each
    iteration the surviving off-diagonal entries are renormalised per row
    (for the Eq. 47 nearest-neighbour weights that IS Eq. 47 evaluated on
    the surviving graph — uniform over the still-reachable neighbourhood),
    so the combine stays row-stochastic over whatever links are up.

    `weights` is EITHER the dense (N, N) row-stochastic matrix (the
    paper-scale oracle) OR a `network.SparseWeights` edge-list bundle
    (`sparse_nearest_neighbor_weights` / `sparse_metropolis_weights` over
    a `SparseGraph`) — the latter runs the identical combine through
    `segment_sum` without ever materialising an N x N array, which is
    what carries the topology layer to 10k+ nodes
    (docs/sparse-topologies.md; dense/sparse parity is pinned at <= 1e-9
    in tests/test_sparse_topology.py).  In sparse mode a `link_mask_fn`
    returns the (E_undirected,) per-link keep mask instead of (N, N).

    >>> import jax.numpy as jnp
    >>> W = jnp.asarray([[0.5, 0.5], [0.5, 0.5]])        # 2-node clique
    >>> Diffusion(W).combine(jnp.asarray([[0.0], [4.0]])).tolist()
    [[2.0], [2.0]]
    >>> dead = Diffusion(W, link_mask_fn=lambda t: jnp.eye(2))  # all down
    >>> dead.combine(jnp.asarray([[0.0], [4.0]]), t=0).tolist()
    [[0.0], [4.0]]
    """

    def __init__(self, weights, *, link_drop: float = 0.0,
                 link_seed: int = 0,
                 link_mask_fn: Optional[Callable] = None):
        self.weights = weights
        self.sparse = isinstance(weights, network_lib.SparseWeights)
        self.links = _LinkSchedule(link_drop, link_seed, link_mask_fn)

    def shard_inputs(self) -> dict:
        # sparse mode: the edge arrays are not per-node rows, so they ride
        # into the shard_map body as replicated closure constants and the
        # combine slices its local rows out of the gathered result
        return {} if self.sparse else {"weights": self.weights}

    def _effective_weights(self, W_rows, t, *, axis):
        """Per-iteration weights: drop-masked, row-renormalised."""
        n = self.weights.shape[0]
        keep = self.links.keep_matrix(t, n, W_rows.dtype)
        # a node never loses itself: force the keep diagonal to 1 so a
        # zero-diagonal `link_mask_fn` (an adjacency sequence) cannot
        # delete the self-weight, and an all-links-down row renormalises
        # to the identity combine instead of zeroing phi_i
        keep = jnp.maximum(keep, jnp.eye(n, dtype=W_rows.dtype))
        if axis is not None:
            keep = _local_rows(keep, W_rows.shape[0], axis)
        W_eff = W_rows * keep
        rows = jnp.sum(W_eff, axis=1, keepdims=True)
        return W_eff / jnp.where(rows > 0, rows, jnp.ones_like(rows))

    def combine(self, varphi, *, axis=None, local=None, t=None):
        if self.sparse:
            sw = self.weights
            keep = (self.links.keep_edges(t, sw.graph.n_undirected,
                                          varphi.dtype)
                    if self.links.time_varying else None)
            if axis is None:
                return _sparse_combine(sw, varphi, keep)
            # every node must see the messages addressed to it; gather the
            # node axis, run the full edge-list combine, keep local rows
            varphi_all = jax.lax.all_gather(varphi, axis, tiled=True)
            return _local_rows(_sparse_combine(sw, varphi_all, keep),
                               varphi.shape[0], axis)
        if axis is None:
            W = self.weights
            if self.links.time_varying:
                W = self._effective_weights(W, t, axis=None)
            return W @ varphi
        # every node must see the messages addressed to it; on a mesh the
        # collective realising that for an arbitrary graph is an all_gather
        # followed by the local rows of W
        W = local["weights"]
        if self.links.time_varying:
            W = self._effective_weights(W, t, axis=axis)
        varphi_all = jax.lax.all_gather(varphi, axis, tiled=True)
        return W @ varphi_all


class RingDiffusion(_CombineTopology):
    """Diffusion on the cycle graph — the TPU-native topology where the
    communication graph IS the ICI ring along a mesh axis, so the combine
    is two ppermutes and a weighted sum (no all_gather, no all_reduce).

    With the default Eq. 47 ring weights each node keeps 1/3 and takes 1/3
    from each ring neighbour; any `w_self` splits the rest evenly:

    >>> import jax.numpy as jnp
    >>> varphi = jnp.asarray([[4.0], [8.0], [12.0]])
    >>> RingDiffusion(w_self=0.5).combine(varphi).tolist()
    [[7.0], [8.0], [9.0]]

    `graph=network.SparseGraph.ring(N)` switches the combine to the
    edge-list `segment_sum` path (same math; parity-pinned).  Because
    `SparseGraph.ring` orders link k as (k, k+1 mod N) — the coin order
    of `ring_link_keep` — the sparse path replays the IDENTICAL link
    failures for any `link_drop`/`link_seed` as the roll-based path.
    """

    def __init__(self, w_self: float = 1.0 / 3.0, *, link_drop: float = 0.0,
                 link_seed: int = 0,
                 link_mask_fn: Optional[Callable] = None,
                 graph=None):
        self.w_self = w_self
        self.links = _LinkSchedule(link_drop, link_seed, link_mask_fn)
        self.graph = graph
        if graph is not None:
            import numpy as np
            ring = network_lib.SparseGraph.ring(graph.n_nodes)
            for name in ("senders", "receivers", "edge_id"):
                if not np.array_equal(np.asarray(getattr(graph, name)),
                                      np.asarray(getattr(ring, name))):
                    raise ValueError(
                        "RingDiffusion(graph=) must be SparseGraph.ring(N) "
                        "(link k = (k, k+1 mod N) — the ring_link_keep "
                        "coin order)")

    def _sparse_weights(self, dtype):
        g = self.graph
        w_n = (1.0 - self.w_self) / 2.0
        return network_lib.SparseWeights(
            g, jnp.full((2 * g.n_undirected,), w_n, dtype),
            jnp.full((g.n_nodes,), self.w_self, dtype))

    def _gated(self, varphi, left, right, e_left, e_right):
        """Weighted combine over the surviving ring links only: dropped
        neighbours contribute nothing and the nominal weights renormalise
        over what is still connected (row-stochastic every iteration).
        A fully isolated node (both links down AND w_self == 0, so the
        renormaliser vanishes) keeps its own iterate."""
        w_n = (1.0 - self.w_self) / 2.0
        num = (self.w_self * varphi
               + w_n * (e_left[:, None] * left + e_right[:, None] * right))
        den = self.w_self + w_n * (e_left + e_right)
        isolated = den <= 0.0
        safe = jnp.where(isolated, jnp.ones_like(den), den)
        return jnp.where(isolated[:, None], varphi, num / safe[:, None])

    def combine(self, varphi, *, axis=None, local=None, t=None):
        if self.graph is not None:
            # edge-list path; a ring's (E_und,) link masks coincide with
            # the (N,) ring_link_keep masks (same ordering), so both link
            # forms drive it unchanged
            sw = self._sparse_weights(varphi.dtype)
            keep = (self.links.keep_edges(t, self.graph.n_undirected,
                                          varphi.dtype)
                    if self.links.time_varying else None)
            if axis is None:
                return _sparse_combine(sw, varphi, keep)
            varphi_all = jax.lax.all_gather(varphi, axis, tiled=True)
            return _local_rows(_sparse_combine(sw, varphi_all, keep),
                               varphi.shape[0], axis)
        if axis is not None:
            if not self.links.time_varying:
                return ring_combine_block(varphi, axis, self.w_self)
            n_local = varphi.shape[0]
            n = jax.lax.axis_size(axis) * n_local
            e = self.links.keep_ring(t, n, varphi.dtype)  # e[i]: link i,i+1
            fwd, bwd = _ring_perms(jax.lax.axis_size(axis))
            prev_tail = jax.lax.ppermute(varphi[-1:], axis, fwd)
            next_head = jax.lax.ppermute(varphi[:1], axis, bwd)
            left = jnp.concatenate([prev_tail, varphi[:-1]], 0)  # phi_{i-1}
            right = jnp.concatenate([varphi[1:], next_head], 0)  # phi_{i+1}
            e_left = _local_rows(jnp.roll(e, 1), n_local, axis)
            e_right = _local_rows(e, n_local, axis)
            return self._gated(varphi, left, right, e_left, e_right)
        if not self.links.time_varying:
            w_n = (1.0 - self.w_self) / 2.0
            return (self.w_self * varphi
                    + w_n * (jnp.roll(varphi, 1, axis=0)
                             + jnp.roll(varphi, -1, axis=0)))
        n = varphi.shape[0]
        e = self.links.keep_ring(t, n, varphi.dtype)     # e[i]: link (i,i+1)
        return self._gated(varphi,
                           jnp.roll(varphi, 1, axis=0),
                           jnp.roll(varphi, -1, axis=0),
                           jnp.roll(e, 1), e)


class PairwiseGossip(_CombineTopology):
    """Asynchronous randomized gossip (Boyd-Ghosh-Prabhakar-Shah style) on
    a `SparseGraph`: each iteration every undirected link activates
    independently with probability `p_activate` — deterministic in
    (`seed`, absolute t) via `network.sparse_link_keep`, so gossip runs
    compose with the split/resume contract exactly like `link_drop` — and
    each node averages with Eq. 47 weights over its ACTIVE neighbourhood:

        phi_i <- (varphi_i + sum_{active links (i,j)} varphi_j)
                 / (1 + |N_i^active(t)|)

    A node with no active link this iteration keeps its own iterate (the
    asynchronous-sensor semantics: nobody waits).  Two limits anchor it:
    `p_activate=1.0` is EXACTLY dense `Diffusion` with
    `nearest_neighbor_weights` on the same graph (parity-pinned), and
    p ~ 1/E activates one expected link per iteration — classic pairwise
    gossip, where the two endpoints exchange and average.

    >>> import jax.numpy as jnp
    >>> from repro.core import network
    >>> g = network.SparseGraph.ring(3)
    >>> all_on = PairwiseGossip(g, p_activate=1.0)
    >>> all_on.combine(jnp.asarray([[3.0], [6.0], [9.0]]), t=0).tolist()
    [[6.0], [6.0], [6.0]]
    """

    def __init__(self, graph, *, p_activate: float = 0.5, seed: int = 0):
        if not 0.0 < p_activate <= 1.0:
            raise ValueError(
                f"p_activate must be in (0, 1]: {p_activate}")
        if not isinstance(graph, network_lib.SparseGraph):
            raise ValueError("PairwiseGossip needs a network.SparseGraph "
                             "(use SparseGraph.from_dense for small "
                             "adjacency matrices)")
        self.graph = graph
        self.p_activate = float(p_activate)
        self.seed = int(seed)
        self._key = jax.random.PRNGKey(seed)

    def combine(self, varphi, *, axis=None, local=None, t=None):
        if t is None:
            raise ValueError(
                "PairwiseGossip draws its activation from the iteration "
                "index: call combine(..., t=<iteration>) (run_vb supplies "
                "it automatically)")
        g = self.graph
        # keep prob = 1 - drop: active with probability p_activate
        active = network_lib.sparse_link_keep(
            self._key, t, g.n_undirected, 1.0 - self.p_activate,
            varphi.dtype)
        varphi_all = (varphi if axis is None
                      else jax.lax.all_gather(varphi, axis, tiled=True))
        act_dir = active[g.edge_id]
        num = varphi_all + _segment_sum(
            act_dir[:, None] * varphi_all[g.senders], g)
        den = 1.0 + _segment_sum(act_dir, g)         # 1 + |N_i^active|
        out = num / den[:, None]
        return (out if axis is None
                else _local_rows(out, varphi.shape[0], axis))


class HierarchicalFusion(_CombineTopology):
    """Two-level sensor -> gateway -> region fusion: each gateway
    averages its sensors' iterates, each region averages its gateways'
    means, and every sensor blends its own iterate with its gateway and
    region means:

        gw_g  = mean_{i: gateway(i)=g} varphi_i
        rg_r  = mean_{g: region(g)=r} gw_g
        phi_i <- w_self varphi_i + w_gateway gw_{gateway(i)}
                 + (1 - w_self - w_gateway) rg_{region(gateway(i))}

    Row-stochastic by construction, O(N + G + R) memory via two
    `segment_sum`s — no N x N matrix, no peer-to-peer links.  Distinct
    regions are independent consensus islands (they never exchange); a
    single region with w_self = w_gateway = 0 degenerates to
    `FusionCenter` exactly (parity-pinned).  Build balanced assignments
    with `network.two_level_partition`.

    >>> import jax.numpy as jnp
    >>> from repro.core import network
    >>> gw, rg = network.two_level_partition(4, 2, 1)
    >>> h = HierarchicalFusion(gw, rg, w_self=0.0, w_gateway=0.0)
    >>> h.combine(jnp.asarray([[0.0], [2.0], [4.0], [6.0]])).tolist()
    [[3.0], [3.0], [3.0], [3.0]]
    """

    def __init__(self, gateway_of, region_of, *, w_self: float = 1.0 / 3.0,
                 w_gateway: float = 1.0 / 3.0):
        import numpy as np
        gw = np.asarray(gateway_of, np.int32)
        rg = np.asarray(region_of, np.int32)
        if gw.ndim != 1 or rg.ndim != 1:
            raise ValueError("gateway_of/region_of must be 1-D index maps")
        n_gateways = int(rg.shape[0])
        if gw.min(initial=0) < 0 or (gw.size and gw.max() >= n_gateways):
            raise ValueError("gateway_of must index into region_of")
        n_regions = int(rg.max()) + 1 if rg.size else 0
        if rg.min(initial=0) < 0:
            raise ValueError("region ids must be >= 0")
        gw_count = np.bincount(gw, minlength=n_gateways)
        rg_count = np.bincount(rg, minlength=n_regions)
        if (gw_count == 0).any() or (rg_count == 0).any():
            raise ValueError("every gateway needs >= 1 sensor and every "
                             "region >= 1 gateway")
        w_region = 1.0 - w_self - w_gateway
        if w_self < 0 or w_gateway < 0 or w_region < -1e-12:
            raise ValueError(
                f"weights must be a convex combination: w_self={w_self}, "
                f"w_gateway={w_gateway}, w_region={w_region}")
        self.gateway_of = jnp.asarray(gw)
        self.region_of = jnp.asarray(rg)
        self.n_gateways = n_gateways
        self.n_regions = n_regions
        self._gw_count = jnp.asarray(gw_count, jnp.int32)
        self._rg_count = jnp.asarray(rg_count, jnp.int32)
        self.w_self = float(w_self)
        self.w_gateway = float(w_gateway)
        self.w_region = float(max(w_region, 0.0))

    def combine(self, varphi, *, axis=None, local=None, t=None):
        dt = varphi.dtype
        full = (varphi if axis is None
                else jax.lax.all_gather(varphi, axis, tiled=True))
        gw_mean = jax.ops.segment_sum(
            full, self.gateway_of, num_segments=self.n_gateways) \
            / self._gw_count.astype(dt)[:, None]
        rg_mean = jax.ops.segment_sum(
            gw_mean, self.region_of, num_segments=self.n_regions) \
            / self._rg_count.astype(dt)[:, None]
        out = (self.w_self * full
               + self.w_gateway * gw_mean[self.gateway_of]
               + self.w_region * rg_mean[self.region_of[self.gateway_of]])
        return (out if axis is None
                else _local_rows(out, varphi.shape[0], axis))


class ConsensusDiagnostics(NamedTuple):
    """Per-iteration observability record of `ADMMConsensus` (each field
    gains a leading time axis T once stacked by the scan; see
    docs/admm-convergence.md for how to read it).

    primal_resid : ||r^t|| — RMS norm of the Eq. 39 disagreement
        sum_{j in N_i}(phi_i - phi_j), in natural-parameter space.  Per
        block (T, n_blocks) when `per_block=True`, else (T,).
    dual_resid : ||s^t|| = ||rho (phi^t - phi^{t-1})|| — Boyd's dual
        residual; same shape convention as `primal_resid`.
    rho : the penalty trajectory ((T,) scalar or (T, n_blocks)).
    kappa : the effective dual step-size ramp actually applied (0 while the
        dual warmup gate is closed; restarts after a ramp reset).
    clip_count : number of nodes whose Eq. 38b projection actually moved
        the primal iterate (eigen-clip / domain clamp activation).
    reset_count : number of nodes whose duals were reset/decayed this
        iteration (`dual_reset`); 0 when the feature is off.
    dual_on : 1.0 once the dual ascent is active (warmup gate open).
    link_frac : effective connectivity — the fraction of the nominal
        graph's (directed) adjacency entries alive this iteration;
        constant 1.0 on a static network, < 1 while links are down
        (`link_drop` / `link_mask_fn`).
    """

    primal_resid: jnp.ndarray
    dual_resid: jnp.ndarray
    rho: jnp.ndarray
    kappa: jnp.ndarray
    clip_count: jnp.ndarray
    reset_count: jnp.ndarray
    dual_on: jnp.ndarray
    link_frac: jnp.ndarray


class ADMMConsensus:
    """Consensus ADMM in natural-parameter space (Algorithm 2), plus the
    adaptive-penalty subsystem (all features off by default, which keeps
    Algorithm 2 bit-verbatim — golden-parity-tested).

    Per iteration and node i with neighbours N_i (|N_i| = d_i):

      (38a) phi_i <- [phi*_i - 2 lam_i + rho sum_{j in N_i}(phi_i + phi_j)]
                     / (1 + 2 rho d_i)
      (38b) phi_i <- Proj_Omega(phi_i)                  (if project=True)
      (39)  lam_i <- lam_i + kappa_t rho/2 sum_{j in N_i}(phi_i - phi_j)
      (40)  kappa_t = 1 - 1/(1 + xi t)^2

    Adaptive-penalty subsystem (the ROADMAP-named candidates, composable
    and individually switchable; diagnosis + recipes in
    docs/admm-convergence.md):

    * `adaptive_rho` — residual-balancing (Boyd Sec. 3.4.1) in
      natural-parameter space: every `adapt_every` iterations, grow rho by
      `tau_incr` when the primal residual dominates (||r|| > mu ||s||),
      shrink by `tau_decr` when the dual residual dominates, clipped to
      [rho_min, rho_max].  Enabling it also turns on the dual warmup and
      dual reset below (their "auto" default) — the blessed configuration
      that converges on the paper's GMM instances.
    * `dual_warmup` — residual-gated dual activation: the Eq. 39 ascent
      (and rho adaptation) stays off until the dual residual has fallen
      under `warmup_tol` x the primal residual for `warmup_window`
      consecutive iterations, i.e. until the penalty-method phase has
      equilibrated and the remaining error IS disagreement.  The Eq. 40
      ramp then counts from activation.  This is what stops the dual
      wind-up: ascending while phi*_i still moves with the E-step is what
      destabilised plain Algorithm 2.
    * `per_block` — per-block dual scaling: rho becomes one penalty per
      natural-parameter block of the model (`model.block_labels()`; for
      the GMM: alpha | nu | beta | beta*m | W^-1), each balanced
      independently, so the O(1e3) W^-1 coordinates cannot drown the O(1)
      blocks in the residual norms.
    * `dual_reset` — on Eq. 38b eigen-clip activation, multiply the
      affected node's duals by this factor (0.0 = full reset) and restart
      the kappa ramp: a projection that moved the iterate invalidates the
      geometry the duals were accumulated in.
    * `lam_max` — clip each dual coordinate to +-lam_max * |phi*_i| after
      the ascent (the PR-2 damping; superseded by the warmup gate but kept
      composable).

    Example — the convergent adaptive configuration, vs verbatim
    Algorithm 2:

    >>> import jax.numpy as jnp
    >>> adj = jnp.asarray([[0.0, 1.0], [1.0, 0.0]])      # two-node graph
    >>> plain = ADMMConsensus(adj)                       # Algorithm 2
    >>> adapt = ADMMConsensus(adj, adaptive_rho=True)    # the subsystem
    >>> (plain.emits_diagnostics, adapt.emits_diagnostics)
    (True, True)
    >>> adapt.dual_warmup, adapt.dual_reset             # "auto" resolution
    (True, 0.0)
    >>> plain.dual_warmup, plain.dual_reset
    (False, None)

    Algorithm 2 has no natural-gradient step, so `run_vb`'s `schedule` does
    not apply to this topology (run_vb rejects a non-default one).
    """

    uses_schedule = False
    emits_diagnostics = True

    def __init__(self, adj: jnp.ndarray, rho: float = 0.5, xi: float = 0.05,
                 project: bool = True, lam_max: float | None = None,
                 adaptive_rho: bool = False, mu: float = 10.0,
                 tau_incr: float = 2.0, tau_decr: float = 2.0,
                 adapt_every: int = 10, rho_min: float = 1e-3,
                 rho_max: float = 1e3, per_block: bool = False,
                 dual_warmup: bool | str = "auto", warmup_tol: float = 1e-3,
                 warmup_window: int = 10,
                 dual_reset: float | None | str = "auto",
                 clip_tol: float = 1e-9, link_drop: float = 0.0,
                 link_seed: int = 0,
                 link_mask_fn: Optional[Callable] = None):
        self.adj = adj                   # (N, N) dense or network.SparseGraph
        self.sparse = isinstance(adj, network_lib.SparseGraph)
        self.links = _LinkSchedule(link_drop, link_seed, link_mask_fn)
        self.rho = rho
        self.xi = xi
        self.project = project
        self.lam_max = lam_max
        self.adaptive_rho = adaptive_rho
        self.mu = mu
        self.tau_incr = tau_incr
        self.tau_decr = tau_decr
        self.adapt_every = adapt_every
        self.rho_min = rho_min
        self.rho_max = rho_max
        self.per_block = per_block
        self.dual_warmup = (adaptive_rho if dual_warmup == "auto"
                            else bool(dual_warmup))
        self.warmup_tol = warmup_tol
        self.warmup_window = warmup_window
        self.dual_reset = ((0.0 if adaptive_rho else None)
                           if dual_reset == "auto" else dual_reset)
        self.clip_tol = clip_tol

    @property
    def _plain(self) -> bool:
        """True = Algorithm 2 verbatim (the bit-exact golden path)."""
        return not (self.adaptive_rho or self.per_block or self.dual_warmup
                    or self.dual_reset is not None)

    def shard_inputs(self) -> dict:
        # sparse: edge arrays are not per-node rows — replicated closure
        # constants; neigh_sum gathers, reduces, and keeps local rows
        return {} if self.sparse else {"adj": self.adj}

    def init_carry(self, phi0: jnp.ndarray, model=None):
        lam0 = jnp.zeros_like(phi0)                   # duals lambda_i
        if self._plain:
            return lam0
        rho0 = self._rho0(model, phi0.dtype)
        dt = phi0.dtype
        # (duals, rho, consecutive-stable count, iters since dual
        #  activation, gate-open flag)
        return (lam0, rho0, jnp.asarray(0, jnp.int32), jnp.asarray(0.0, dt),
                jnp.asarray(not self.dual_warmup))

    def carry_specs(self, axis: str):
        from jax.sharding import PartitionSpec as P
        if self._plain:
            return P(axis)
        return (P(axis), P(), P(), P(), P())

    def _rho0(self, model, dt):
        if self.per_block:
            import numpy as np
            n_blocks = int(np.max(model.block_labels())) + 1
            return jnp.full((n_blocks,), self.rho, dt)
        return jnp.asarray(self.rho, dt)

    def init_diag(self, model, phi0: jnp.ndarray):
        """Zeroed `ConsensusDiagnostics` with the shapes `step` emits, so
        `VBState.diag` has a stable pytree structure from t=0 on."""
        dt = phi0.dtype
        rho0 = self._rho0(model, dt)
        resid_shape = rho0.shape if self.per_block else ()
        return ConsensusDiagnostics(
            primal_resid=jnp.zeros(resid_shape, dt),
            dual_resid=jnp.zeros(resid_shape, dt),
            rho=rho0,
            kappa=jnp.zeros((), dt),
            clip_count=jnp.zeros((), jnp.int32),
            reset_count=jnp.zeros((), jnp.int32),
            dual_on=jnp.zeros((), dt),
            link_frac=jnp.ones((), dt))

    # -- residual norms in natural-parameter space ------------------------
    def _block_norms(self, z, onehot, *, axis=None):
        """RMS norm of the (N, P) stack z — per block ((n_blocks,)) when
        `per_block`, else a scalar — with the node axis reduced globally
        under the mesh executor."""
        sq = jnp.sum(z * z, axis=0)                   # (P,)
        n = jnp.asarray(z.shape[0], z.dtype)
        if axis is not None:
            sq = jax.lax.psum(sq, axis)
            n = jax.lax.psum(n, axis)
        if onehot is not None:
            return jnp.sqrt((sq @ onehot) / (jnp.sum(onehot, 0) * n))
        return jnp.sqrt(jnp.sum(sq) / (n * z.shape[1]))

    def _graph_ops(self, phi, t, axis, local):
        """(deg, neigh_sum, link_frac) for this iteration's graph: the
        dense path masks + row-sums the (N, N) adjacency; the sparse path
        gates the directed edge list and reduces with `segment_sum` —
        per-iteration memory O(E + N), independent of N^2."""
        if self.sparse:
            g = self.adj
            if self.links.time_varying:
                # iteration-t links: one coin per undirected link, both
                # directions gated together (the dense keep contract)
                keep_und = self.links.keep_edges(t, g.n_undirected,
                                                 phi.dtype)
                keep_dir = keep_und[g.edge_id]
                link_frac = jnp.mean(keep_und).astype(phi.dtype)
                deg_full = _segment_sum(keep_dir, g)
            else:
                keep_dir = None
                link_frac = jnp.ones((), phi.dtype)
                deg_full = g.deg.astype(phi.dtype)
            n_local_nodes = phi.shape[0]
            deg = (deg_full if axis is None
                   else _local_rows(deg_full, n_local_nodes, axis))

            def neigh_sum(z):                        # sum_{j in N_i(t)} z_j
                z_all = (z if axis is None
                         else jax.lax.all_gather(z, axis, tiled=True))
                msg = z_all[g.senders]
                if keep_dir is not None:
                    msg = msg * keep_dir[:, None]
                s = _segment_sum(msg, g)
                return (s if axis is None
                        else _local_rows(s, n_local_nodes, axis))

            return deg, neigh_sum, link_frac

        adj_rows = self.adj if axis is None else local["adj"]
        if self.links.time_varying:
            # iteration-t adjacency: the consensus constraints (and hence
            # the 38a neighbour sums, degrees and the 39 disagreement) only
            # couple nodes whose link is up this iteration
            keep = self.links.keep_matrix(t, self.adj.shape[0], phi.dtype)
            if axis is not None:
                keep = _local_rows(keep, adj_rows.shape[0], axis)
            adj_rows = adj_rows * keep.astype(adj_rows.dtype)
            alive = jnp.sum(adj_rows)
            if axis is not None:
                alive = jax.lax.psum(alive, axis)
            link_frac = (alive / jnp.sum(self.adj)).astype(phi.dtype)
        else:
            link_frac = jnp.ones((), phi.dtype)
        deg = jnp.sum(adj_rows, axis=1)               # |N_i(t)|

        def neigh_sum(z):                             # sum_{j in N_i} z_j
            if axis is None:
                return adj_rows @ z
            return adj_rows @ jax.lax.all_gather(z, axis, tiled=True)

        return deg, neigh_sum, link_frac

    def step(self, model, phi, carry, phi_star, t, schedule: Schedule, *,
             axis=None, local=None, hyper=None):
        # `hyper` entries (serving fleet axis, see `hyper_names`) override
        # the static penalty/ramp constants; None — every solo path —
        # reproduces the static behaviour exactly.  Under adaptive_rho the
        # penalty lives in the carry (init_carry seeds it from self.rho),
        # so only xi is liftable there.
        rho = self.rho if not hyper or "rho" not in hyper else hyper["rho"]
        xi = self.xi if not hyper or "xi" not in hyper else hyper["xi"]
        deg, neigh_sum, link_frac = self._graph_ops(phi, t, axis, local)

        if self._plain:
            lam = carry
            # (38a) primal
            phi_hat = (phi_star - 2.0 * lam
                       + rho * (deg[:, None] * phi + neigh_sum(phi)))
            phi_hat = phi_hat / (1.0 + 2.0 * rho * deg)[:, None]
            if self.project:
                with jax.named_scope("vb/project"):             # (38b)
                    phi_new = jax.vmap(model.project_to_domain)(phi_hat)
            else:
                phi_new = phi_hat
            # (39) dual ascent with the kappa_t ramp (40)
            kappa = kappa_schedule(t.astype(phi.dtype) + 1.0, xi)
            resid = deg[:, None] * phi_new - neigh_sum(phi_new)
            lam_new = lam + kappa * rho / 2.0 * resid
            if self.lam_max is not None:
                bound = self.lam_max * jnp.abs(phi_star)
                lam_new = jnp.clip(lam_new, -bound, bound)
            clip_count = jnp.sum(
                jnp.max(jnp.abs(phi_new - phi_hat), axis=1) > self.clip_tol)
            if axis is not None:
                clip_count = jax.lax.psum(clip_count, axis)
            diag = ConsensusDiagnostics(
                primal_resid=self._block_norms(resid, None, axis=axis),
                dual_resid=self._block_norms(rho * (phi_new - phi),
                                             None, axis=axis),
                rho=jnp.asarray(rho, phi.dtype),
                kappa=kappa.astype(phi.dtype),
                clip_count=clip_count,
                reset_count=jnp.zeros((), jnp.int32),
                dual_on=jnp.ones((), phi.dtype),
                link_frac=link_frac)
            return phi_new, lam_new, diag
        return self._adaptive_step(model, phi, carry, phi_star, deg,
                                   neigh_sum, link_frac, xi, axis=axis)

    def _adaptive_step(self, model, phi, carry, phi_star, deg, neigh_sum,
                       link_frac, xi, *, axis=None):
        lam, rho_vec, stable, t_act, active = carry
        dt = phi.dtype
        if self.per_block:
            labels = model.block_labels()
            onehot = jax.nn.one_hot(labels, rho_vec.shape[0], dtype=dt)
            rho_coord = rho_vec[labels]               # (P,)
        else:
            onehot = None
            rho_coord = rho_vec                       # ()

        # (38a) primal, with the (possibly per-block) penalty
        phi_hat = (phi_star - 2.0 * lam
                   + rho_coord * (deg[:, None] * phi + neigh_sum(phi)))
        phi_hat = phi_hat / (1.0 + 2.0 * rho_coord * deg[:, None])
        if self.project:
            with jax.named_scope("vb/project"):             # (38b)
                phi_new = jax.vmap(model.project_to_domain)(phi_hat)
        else:
            phi_new = phi_hat
        clip_active = (jnp.max(jnp.abs(phi_new - phi_hat), axis=1)
                       > self.clip_tol)               # (N,) eigen-clip fired
        any_clip = jnp.any(clip_active)
        if axis is not None:
            any_clip = jax.lax.psum(any_clip.astype(dt), axis) > 0.0

        resid = deg[:, None] * phi_new - neigh_sum(phi_new)
        r_norm = self._block_norms(resid, onehot, axis=axis)
        s_norm = self._block_norms(rho_coord * (phi_new - phi), onehot,
                                   axis=axis)
        r_tot = jnp.sqrt(jnp.sum(r_norm ** 2))
        s_tot = jnp.sqrt(jnp.sum(s_norm ** 2))

        # -- dual warmup gate: open once s << r for warmup_window iters --
        if self.dual_warmup:
            stable = jnp.where(s_tot < self.warmup_tol * r_tot,
                               stable + 1, 0)
            active = active | (stable >= self.warmup_window)
        t_act = jnp.where(active, t_act + 1.0, 0.0)
        if self.dual_reset is not None:
            t_act = jnp.where(any_clip, 0.0, t_act)   # ramp reset on clip
        kappa = jnp.where(t_act > 0.0,
                          kappa_schedule(t_act, xi), 0.0).astype(dt)

        # (39) dual ascent
        lam_new = lam + kappa * rho_coord / 2.0 * resid
        if self.lam_max is not None:
            bound = self.lam_max * jnp.abs(phi_star)
            lam_new = jnp.clip(lam_new, -bound, bound)
        if self.dual_reset is not None:
            lam_new = jnp.where(clip_active[:, None],
                                self.dual_reset * lam_new, lam_new)
            reset_count = jnp.sum(clip_active)
        else:
            reset_count = jnp.zeros((), jnp.int32)
        if axis is not None:
            reset_count = jax.lax.psum(reset_count, axis)
        clip_count = jnp.sum(clip_active)
        if axis is not None:
            clip_count = jax.lax.psum(clip_count, axis)

        # -- residual balancing (Boyd Sec. 3.4.1), gated on dual activity --
        if self.adaptive_rho:
            balanced = residual_balanced_rho(
                rho_vec, r_norm, s_norm, mu=self.mu, tau_incr=self.tau_incr,
                tau_decr=self.tau_decr, rho_min=self.rho_min,
                rho_max=self.rho_max)
            do = active & (jnp.mod(t_act, float(self.adapt_every)) == 0.0) \
                & (t_act > 0.0)
            rho_vec = jnp.where(do, balanced, rho_vec)

        diag = ConsensusDiagnostics(
            primal_resid=r_norm, dual_resid=s_norm, rho=rho_vec,
            kappa=kappa, clip_count=clip_count, reset_count=reset_count,
            dual_on=active.astype(dt), link_frac=link_frac)
        return phi_new, (lam_new, rho_vec, stable, t_act, active), diag


# ---------------------------------------------------------------------------
# Metrics (Eq. 46) + run result
# ---------------------------------------------------------------------------
def kl_to_reference(model, phi_nodes: jnp.ndarray,
                    ref_phi: Optional[jnp.ndarray]) -> jnp.ndarray:
    """Per-node KL to the ground-truth posterior (Eq. 46).

    `ref_phi` may be (P,) or a (n_refs, P) stack — e.g. component
    permutations of a mixture reference — in which case the
    permutation-invariant min-KL is reported.
    """
    if ref_phi is None:
        return jnp.zeros(phi_nodes.shape[0], phi_nodes.dtype)
    ref = ref_phi[None] if ref_phi.ndim == 1 else ref_phi
    return jax.vmap(
        lambda p: jnp.min(jax.vmap(lambda r: model.kl(p, r))(ref)))(phi_nodes)


class VBRun(NamedTuple):
    phi: jnp.ndarray            # (N, P) final natural parameters per node
    kl_mean: jnp.ndarray        # (T,)   mean_i KL(q_i || ground truth)
    kl_std: jnp.ndarray         # (T,)
    kl_nodes: jnp.ndarray       # (T, N) per-node trajectory
    consensus_err: Any = None   # (T,)   mean_i ||phi_i - mean_j phi_j||^2
    consensus_diag: Any = None  # ConsensusDiagnostics (ADMM topologies)


class MeshExecutor(NamedTuple):
    """Run the node axis sharded over `axis` of `mesh` via shard_map."""

    mesh: Any
    axis: str = "data"


# ---------------------------------------------------------------------------
# Sessions + explicit state: the resumable half of the engine.  `run_vb`
# below is a thin (bit-exact) wrapper over vb_init -> vb_run.
# ---------------------------------------------------------------------------
class VBSession:
    """The STATIC half of a VB session: model x topology x executor x
    hyperparameters, plus the per-node data buffers.

    Everything here is configuration (or host-owned data arrays) that does
    not evolve with the iteration; the evolving arrays live in `VBState`,
    which carries a reference to its session as pytree *aux data* — so
    `jax.lax.scan` / `jax.jit` treat it as structure, and
    `checkpoint.ckpt.save` never serialises it (a checkpoint holds arrays
    only; `vb_init` rebuilds the session on restore).
    """

    __slots__ = ("model", "data", "topology", "schedule", "replication",
                 "ref_phi", "executor", "minibatch", "diagnostics",
                 "metric_nodes")

    def __init__(self, model, data, topology, schedule, replication,
                 ref_phi, executor, minibatch, diagnostics, metric_nodes):
        self.model = model
        self.data = data
        self.topology = topology
        self.schedule = schedule
        self.replication = replication
        self.ref_phi = ref_phi
        self.executor = executor
        self.minibatch = minibatch
        self.diagnostics = diagnostics
        self.metric_nodes = metric_nodes

    def with_data(self, data) -> "VBSession":
        """Same session over NEW per-node buffers — the mid-flight data
        arrival path (the streaming scenario the paper is written for).
        Every leaf must keep its shape and dtype: append new points into a
        node's padding slots via `model.append_node_data`, or replace a
        buffer outright."""
        old = jax.tree_util.tree_leaves(self.data)
        new = jax.tree_util.tree_leaves(data)
        if len(old) != len(new) or any(
                o.shape != n.shape or o.dtype != n.dtype
                for o, n in zip(old, new)):
            raise ValueError(
                "with_data: new buffers must match the session's data "
                "shapes/dtypes exactly (append into padding slots or "
                "replace same-shape buffers)")
        return VBSession(self.model, data, self.topology, self.schedule,
                         self.replication, self.ref_phi, self.executor,
                         self.minibatch, self.diagnostics, self.metric_nodes)


@jax.tree_util.register_pytree_with_keys_class
class VBState:
    """Checkpointable per-iteration state of a VB session (a pytree).

    phi : (N, P) current natural parameters per node.
    t : () int32 — ABSOLUTE iteration count.  Every per-iteration source
        of randomness (minibatch reshuffling epochs/windows, link-failure
        schedules, the eta_t/kappa_t ramps) is keyed on t, which is what
        makes a split run (`vb_run(s, a)` then `vb_run(., b)`) bit-exact
        with the unsplit `vb_run(s, a+b)`.
    carry : topology carry — ADMM duals lambda_i, and under the adaptive
        subsystem (rho, warmup-gate, ramp) state; None for combine
        topologies.
    stream : `stream.StreamState` (per-node keys + the current epoch's
        permutation) when the session streams minibatches, else None.
    diag : most recent `ConsensusDiagnostics` record (ADMM topologies;
        structure-stable from t=0 via `topology.init_diag`), else None.
    session : the static `VBSession` (pytree aux data — never serialised;
        `checkpoint.ckpt.save(path, state)` stores the arrays above and
        `ckpt.restore(path, vb_init(...))` re-attaches a fresh session).
    """

    __slots__ = ("phi", "t", "carry", "stream", "diag", "session")

    def __init__(self, phi, t, carry=None, stream=None, diag=None,
                 session=None):
        self.phi = phi
        self.t = t
        self.carry = carry
        self.stream = stream
        self.diag = diag
        self.session = session

    def tree_flatten_with_keys(self):
        from jax.tree_util import GetAttrKey
        children = tuple(
            (GetAttrKey(name), getattr(self, name))
            for name in ("phi", "t", "carry", "stream", "diag"))
        return children, self.session

    @classmethod
    def tree_unflatten(cls, session, children):
        return cls(*children, session=session)

    def replace(self, **kw) -> "VBState":
        args = {name: kw.pop(name, getattr(self, name))
                for name in ("phi", "t", "carry", "stream", "diag",
                             "session")}
        if kw:
            raise TypeError(f"unknown VBState fields: {sorted(kw)}")
        return VBState(**args)

    def with_data(self, data) -> "VBState":
        """State bound to updated per-node buffers (see
        `VBSession.with_data`)."""
        if self.session is None:
            raise ValueError("state has no session attached")
        return self.replace(session=self.session.with_data(data))

    def __repr__(self):
        n, p = self.phi.shape
        try:
            t = int(self.t)
        except (TypeError, jax.errors.TracerArrayConversionError):
            t = "<traced>"
        return (f"VBState(t={t}, nodes={n}, flat_dim={p}, "
                f"carry={'yes' if self.carry is not None else 'no'}, "
                f"stream={'yes' if self.stream is not None else 'no'})")


def vb_init(model, data, topology, *, schedule: Schedule = Schedule(),
            replication: float | None = None,
            init_phi: Optional[jnp.ndarray] = None,
            ref_phi: Optional[jnp.ndarray] = None,
            executor: Optional[MeshExecutor] = None,
            backend=None,
            minibatch: Optional[stream.MinibatchSpec] = None,
            diagnostics: bool = True,
            metric_nodes: Optional[int] = None) -> VBState:
    """Open a VB session: validate the configuration and return the t=0
    `VBState`.  Parameters are exactly `run_vb`'s (minus `n_iters`); see
    its docstring.  The returned state advances with `vb_step` /
    `vb_run`, checkpoints with `checkpoint.ckpt.save(path, state)`, and
    restores with `ckpt.restore(path, vb_init(<same config>))`.
    """
    if backend is not None:
        with_backend = getattr(model, "with_backend", None)
        if with_backend is None:
            raise ValueError(
                f"{type(model).__name__} does not support compute-backend "
                "selection (no with_backend method)")
        from repro.core import backends as backends_lib
        resolved = backends_lib.resolve(backend)
        supports = getattr(resolved, "supports", None)
        if supports is not None and not supports(model):
            # capability miss (e.g. the fused GMM kernel asked to run an
            # HMM): degrade to the model's own reference path — loudly,
            # but only once per (backend, model) pair per session: a
            # serving fleet re-opens sessions constantly and a warning
            # per vb_init is log spam.  The counter keeps every
            # occurrence observable.
            telemetry.inc("backend_fallback_total",
                          backend=resolved.name,
                          model=type(model).__name__)
            telemetry.warn_once(
                f"backend-fallback:{resolved.name}:{type(model).__name__}",
                f"backend {resolved.name!r} does not support "
                f"{type(model).__name__} (Backend.supports returned "
                "False); falling back to the reference backend",
                stacklevel=2)
            resolved = backends_lib.ReferenceBackend()
        model = with_backend(resolved)
    if not getattr(topology, "uses_schedule", True) \
            and schedule != Schedule():
        raise ValueError(
            f"{type(topology).__name__} has no natural-gradient step "
            "(Eq. 27a); it ignores `schedule` — pass the default")
    if executor is not None and metric_nodes is not None:
        raise ValueError("metric_nodes is only supported on the "
                         "single-array executor")
    n_nodes = jax.tree_util.tree_leaves(data)[0].shape[0]
    if replication is None:
        replication = float(n_nodes)
    if init_phi is None:
        init_phi = jnp.broadcast_to(model.init_phi(),
                                    (n_nodes, model.flat_dim))
    carry0 = topology.init_carry(init_phi, model)

    stream0 = None
    if minibatch is not None:
        if minibatch.batch_size < 1:
            raise ValueError(f"batch_size must be >= 1: {minibatch}")
        if getattr(model, "take_minibatch", None) is None:
            raise ValueError(
                f"{type(model).__name__} does not support streaming "
                "minibatches (no take_minibatch/data_mask methods)")
        if minibatch.control_variate not in (None, "svrg"):
            raise ValueError(
                f"unknown control_variate "
                f"{minibatch.control_variate!r}; expected None or 'svrg'")
        capacity = model.data_mask(data).shape[1]   # also validates shape
        if minibatch.batch_size > capacity:
            # covering the whole node = the full-batch path
            minibatch = minibatch._replace(batch_size=int(capacity))
        stream0 = stream.init_state(n_nodes, minibatch.seed, int(capacity))
        if minibatch.control_variate == "svrg" \
                and minibatch.batch_size < capacity:
            # SVRG anchors: snapshot iterate + its full-batch optimum,
            # refreshed at epoch boundaries inside `_iteration`.  Inert
            # (structurally absent) at full batch, where the minibatch
            # path already computes the full-batch statistics.
            stream0 = stream0._replace(
                anchor_phi=init_phi,
                anchor_full=model.local_optimum(data, init_phi,
                                                replication))

    diag0 = topology.init_diag(model, init_phi) if diagnostics else None
    session = VBSession(model, data, topology, schedule, replication,
                        ref_phi, executor, minibatch, diagnostics,
                        metric_nodes)
    return VBState(phi=init_phi, t=jnp.zeros((), jnp.int32), carry=carry0,
                   stream=stream0, diag=diag0, session=session)


def _iteration(model, data, base_mask, topology, schedule, replication,
               minibatch, phi, carry, st, t, *, axis=None, local=None,
               hyper=None):
    """ONE VB iteration — the kernel shared by `_scan_steps` (both
    executors), `vb_step`, and the serving fleet (`session_step_fn`).

    Streaming path: gather this iteration's per-node minibatch; the scaled
    mask (capacity/batch on selected points) keeps the sufficient
    statistics unbiased, so phi* becomes the stochastic estimate the
    Robbins-Monro eta_t (Eq. 22) assumes and the 27a step is a genuine
    stochastic natural-gradient step.
    """
    if minibatch is None:
        data_t, st_new = data, st
    else:
        st_new, idx, mb_mask = stream.advance(st, base_mask, t,
                                              minibatch.batch_size)
        data_t = model.take_minibatch(data, idx, mb_mask)
    if minibatch is not None and minibatch.control_variate == "svrg" \
            and st.anchor_phi is not None:
        # SVRG corrected estimator (data/stream.py module docstring):
        #   phi*_svrg = phi*_B(phi_t) - phi*_B(anchor) + phi*_full(anchor)
        # Exactly unbiased (statistics are linear in the scaled mask, so
        # E_B[phi*_B(anchor)] = phi*_full(anchor)); the anchor refreshes at
        # epoch boundaries with the CURRENT iterate, at which point the
        # two minibatch terms cancel exactly and the step is the full-batch
        # one.  Epoch parity with `advance` is automatic: both key on the
        # same absolute-t epoch arithmetic.
        def _refresh(_):
            return phi, model.local_optimum(data, phi, replication)

        def _keep(_):
            return st.anchor_phi, st.anchor_full

        anchor_phi, anchor_full = jax.lax.cond(
            st_new.epoch != st.epoch, _refresh, _keep, None)
        if taps.enabled() and axis is None:
            # 1 on the iterations that refreshed the SVRG anchor
            # (trace-time gated; see telemetry/taps.py)
            taps.tap("stream/svrg_anchor_refresh",
                     (st_new.epoch != st.epoch).astype(jnp.int32), t=t)
        st_new = st_new._replace(anchor_phi=anchor_phi,
                                 anchor_full=anchor_full)
        phi_star = (model.local_optimum(data_t, phi, replication)
                    - model.local_optimum(data_t, anchor_phi, replication)
                    + anchor_full)
    else:
        phi_star = model.local_optimum(data_t, phi, replication)
    with jax.named_scope("vb/combine"):
        phi_new, carry_new, diag = topology.step(
            model, phi, carry, phi_star, t, schedule, axis=axis,
            local=local, hyper=hyper)
    return phi_new, carry_new, st_new, diag


def session_step_fn(session: VBSession, *, axis=None, local=None):
    """One-iteration kernel over raw state pytrees, with the data buffers
    as an ARGUMENT: fn(data, phi, carry, stream, t, hyper=None) ->
    (phi', carry', stream', diag).  This is the function the serving
    layer (serving/vb_service.py) vmaps over a leading fleet axis —
    per-session data must be a mapped operand, which is why it is not
    closed over.  `hyper` is the per-session lifted-hyper dict (see
    `hyper_names`): the serving fleet maps it alongside the data so
    sessions differing only in schedule/penalty constants share one
    compiled step; None keeps the session's static values."""
    model, topology = session.model, session.topology
    schedule, replication = session.schedule, session.replication
    minibatch = session.minibatch

    def fn(data, phi, carry, st, t, hyper=None):
        base_mask = model.data_mask(data) if minibatch is not None else None
        return _iteration(model, data, base_mask, topology, schedule,
                          replication, minibatch, phi, carry, st, t,
                          axis=axis, local=local, hyper=hyper)

    return fn


def hyper_names(topology, schedule: Schedule) -> tuple:
    """Names of the hyperparameters a (topology, schedule) pair reads per
    ITERATION as plain scalars — the ones the serving layer can lift onto
    the fleet axis so sessions differing only in them share one compiled
    fleet (docs/bucketed-admission.md).

    * Robbins-Monro schedules (`eta_fixed=None` on a combine topology)
      read `tau` / `d0` in `Schedule.eta`.  A fixed eta is NOT lifted:
      `eta_fixed == 1.0` selects the one-shot jump as a static branch in
      `_CombineTopology.step`, so it must stay in the group key.
    * `ADMMConsensus` reads the penalty `rho` and ramp rate `xi` — except
      under `adaptive_rho`, where rho lives in the per-session carry
      (seeded by `init_carry`) and only `xi` is read statically.
    """
    names = []
    if getattr(topology, "uses_schedule", True) \
            and schedule.eta_fixed is None:
        names += ["tau", "d0"]
    if isinstance(topology, ADMMConsensus):
        names += ["xi"] if topology.adaptive_rho else ["rho", "xi"]
    return tuple(names)


def lifted_attr_names(topology) -> tuple:
    """Topology attributes excluded from the fleet-group signature
    because per-session values reach the step another way — via the
    lifted-hyper dict (`hyper_names`) or the carry (adaptive-rho ADMM
    seeds rho from `init_carry`).  Strictly a superset of the
    topology-owned `hyper_names` entries."""
    return ("rho", "xi") if isinstance(topology, ADMMConsensus) else ()


def session_hyper(topology, schedule: Schedule, dtype) -> dict:
    """The per-session lifted-hyper dict consumed by `session_step_fn`'s
    `hyper` argument: each `hyper_names` entry as a scalar array (the
    serving fleet stacks these along the leading fleet axis)."""
    out = {}
    for n in hyper_names(topology, schedule):
        src = schedule if n in ("tau", "d0") else topology
        out[n] = jnp.asarray(getattr(src, n), dtype)
    return out


def _scan_steps(model, data, topology, schedule, replication, ref_phi,
                n_iters, phi0, carry0, *, t0=None, stream0=None, axis=None,
                local=None, diagnostics=True, metric_nodes=None,
                minibatch=None):
    """`n_iters` iterations as one lax.scan, shared verbatim by both
    executors.  `t0` resumes from an absolute iteration count; `stream0`
    is the carried minibatch-sampler state."""
    base_mask = model.data_mask(data) if minibatch is not None else None

    def step(carry, t):
        phi, aux, st = carry
        phi_new, aux_new, st_new, diag = _iteration(
            model, data, base_mask, topology, schedule, replication,
            minibatch, phi, aux, st, t, axis=axis, local=local)
        phi_m = phi_new if metric_nodes is None else phi_new[:metric_nodes]
        kl = kl_to_reference(model, phi_m, ref_phi)
        if diagnostics:
            mean = jnp.mean(phi_new, axis=0)
            if axis is not None:
                mean = jax.lax.pmean(mean, axis)
            msd = jnp.mean((phi_new - mean) ** 2)
            if axis is not None:
                msd = jax.lax.pmean(msd, axis)
        else:
            msd = jnp.zeros((), phi_new.dtype)
            diag = None
        if taps.enabled() and axis is None:
            # opt-in device taps (telemetry/taps.py): stream the
            # per-iteration series out mid-flight via io_callback.  Trace
            # -time gated — with taps off this block leaves the jaxpr
            # byte-identical (pinned in tests/test_telemetry.py).  Not
            # supported under the mesh executor (axis is not None).
            taps.tap("vb/kl_mean", jnp.mean(kl), t=t)
            taps.tap("vb/consensus_msd", msd, t=t)
            if diag is not None and hasattr(diag, "rho"):
                taps.tap("vb/admm_rho", jnp.mean(diag.rho), t=t)
                taps.tap("vb/admm_primal_resid",
                         jnp.mean(diag.primal_resid), t=t)
                taps.tap("vb/admm_dual_resid",
                         jnp.mean(diag.dual_resid), t=t)
        return (phi_new, aux_new, st_new), (kl, msd, diag)

    ts = jnp.arange(n_iters)
    if t0 is not None:
        ts = ts + t0
    (phi, aux, st), (kls, msds, diags) = jax.lax.scan(
        step, (phi0, carry0, stream0), ts)
    return phi, aux, st, kls, msds, diags


def vb_run(state: VBState, n_iters: int) -> tuple[VBState, VBRun]:
    """Advance a session `n_iters` iterations; returns (state', VBRun).

    Scans the `vb_step` kernel from the state's absolute iteration count,
    so runs compose bit-exactly: `vb_run(s, a + b)` equals
    `vb_run(vb_run(s, a)[0], b)` on every topology, executor, backend and
    streaming configuration (tests/test_session.py) — iteration-indexed
    randomness (minibatch epochs, link-drop schedules) and the eta_t /
    kappa_t ramps are all functions of the absolute t carried in the
    state.  The `VBRun` covers the `n_iters` iterations of THIS call."""
    ses = state.session
    if ses is None:
        raise ValueError("VBState has no session attached — create states "
                         "with vb_init(...)")
    with telemetry.span("engine/vb_run", n_iters=int(n_iters)):
        return _vb_run_body(state, ses, n_iters)


def _vb_run_body(state, ses, n_iters):
    if ses.executor is None:
        phi, aux, st, kls, msds, diags = _scan_steps(
            ses.model, ses.data, ses.topology, ses.schedule,
            ses.replication, ses.ref_phi, n_iters, state.phi, state.carry,
            t0=state.t, stream0=state.stream, diagnostics=ses.diagnostics,
            metric_nodes=ses.metric_nodes, minibatch=ses.minibatch)
    else:
        phi, aux, st, kls, msds, diags = _run_vb_sharded(
            ses, n_iters, state.phi, state.carry, state.stream, state.t)
    if telemetry.enabled() and not isinstance(kls, jax.core.Tracer):
        # the diag-slot tap path (telemetry/taps.py): file the scan's own
        # per-iteration outputs as host series.  Reads arrays the run
        # materializes anyway, so this never changes a jaxpr; skipped when
        # vb_run is itself being traced (kls is a Tracer).
        import numpy as np
        ts = np.arange(int(state.t), int(state.t) + int(n_iters))
        taps.record_series("vb_run/kl_mean", jnp.mean(kls, 1), ts=ts)
        if ses.diagnostics:
            taps.record_series("vb_run/consensus_msd", msds, ts=ts)
        if diags is not None and hasattr(diags, "rho"):
            flat = lambda a: (a if a.ndim == 1
                              else a.reshape(a.shape[0], -1).mean(1))
            taps.record_series("vb_run/admm_rho", flat(diags.rho), ts=ts)
            taps.record_series("vb_run/admm_primal_resid",
                               flat(diags.primal_resid), ts=ts)
            taps.record_series("vb_run/admm_dual_resid",
                               flat(diags.dual_resid), ts=ts)
    diag_last = (jax.tree_util.tree_map(lambda a: a[-1], diags)
                 if diags is not None else None)
    state_new = VBState(
        phi=phi, t=state.t + jnp.asarray(n_iters, state.t.dtype),
        carry=aux, stream=st, diag=diag_last, session=ses)
    run = VBRun(phi=phi, kl_mean=jnp.mean(kls, 1), kl_std=jnp.std(kls, 1),
                kl_nodes=kls, consensus_err=msds if ses.diagnostics else None,
                consensus_diag=diags)
    return state_new, run


def vb_step(state: VBState) -> VBState:
    """Advance a session by ONE iteration (= `vb_run(state, 1)[0]`)."""
    state, _ = vb_run(state, 1)
    return state


def run_vb(model, data, topology, *, n_iters: int,
           schedule: Schedule = Schedule(), replication: float | None = None,
           init_phi: Optional[jnp.ndarray] = None,
           ref_phi: Optional[jnp.ndarray] = None,
           executor: Optional[MeshExecutor] = None,
           backend=None,
           minibatch: Optional[stream.MinibatchSpec] = None,
           diagnostics: bool = True,
           metric_nodes: Optional[int] = None) -> VBRun:
    """Run distributed VB: `model` on `data` over `topology`.

    Parameters
    ----------
    model : ConjugateExpModel (see core/model.py)
    data : per-node data pytree; every leaf has leading node axis N
    topology : FusionCenter | Isolated | Diffusion | RingDiffusion |
        ADMMConsensus — how {phi*_i} becomes the next iterate
    n_iters : number of VB iterations (the scan length)
    schedule : eta_t of the natural-gradient step (27a); `ONE_SHOT` for the
        jump-to-optimum estimators
    replication : likelihood replication factor (paper App. A); defaults to
        the network size N, use 1.0 for non-cooperative runs
    init_phi : (N, P) initial naturals; defaults to the prior at every node
    ref_phi : (P,) or (n_refs, P) reference for the Eq. 46 metric
    executor : None = single-array (node axis is a plain array axis, whole
        run jits); MeshExecutor(mesh, axis) = shard_map over a mesh axis
    backend : per-run compute-backend override ("reference" | "fused" | a
        `core.backends.Backend` instance) for models that support backend
        selection via `with_backend` (GMMModel).  None keeps the model's
        own backend.  Orthogonal to `executor`: the backend picks the
        kernel, the executor picks how the node axis is laid out.
    minibatch : `stream.MinibatchSpec(batch_size, seed)` switches the run
        to streaming stochastic VB — each iteration every node estimates
        phi*_i from a `batch_size` window of its per-epoch reshuffled
        local data (selected points reweighted by capacity/batch_size so
        the statistics stay unbiased, composing with `replication`).
        Deterministic per (seed, node, iteration):
        both executors and both compute backends see identical batches.
        `batch_size >= n_per_node` reproduces the full-batch run to
        rounding.  `control_variate="svrg"` re-centres every
        minibatch estimate on a full-batch anchor refreshed each epoch
        (still exactly unbiased; anchors ride the resumable stream
        state, and the full-batch degeneracy carries none).
    diagnostics : also record per-iteration consensus error
    metric_nodes : evaluate the Eq. 46 metric on only the first
        `metric_nodes` rows (kl_nodes becomes (T, metric_nodes)) — used by
        cVB, whose iterates are identical across nodes.  Single-array
        executor only.

    Returns a `VBRun` regardless of executor; the two paths are numerically
    equivalent (asserted in tests/test_engine.py).  Topologies that emit
    per-iteration diagnostics (`ADMMConsensus`) populate
    `VBRun.consensus_diag` with a `ConsensusDiagnostics` record.

    Example (Bayesian linear regression, whose local optima are a constant
    (N, P) stack, over a two-node fusion centre):

    >>> import jax.numpy as jnp
    >>> from repro.core import linreg
    >>> from repro.core.model import LinRegModel
    >>> mdl = LinRegModel(linreg.prior(2))
    >>> phi_star = jnp.stack([mdl.init_phi() + 1.0, mdl.init_phi() - 1.0])
    >>> run = run_vb(mdl, phi_star, FusionCenter(), n_iters=3,
    ...              schedule=ONE_SHOT)
    >>> run.phi.shape, run.kl_nodes.shape
    ((2, 8), (3, 2))
    >>> bool(jnp.all(run.phi[0] == run.phi[1]))          # consensus: exact
    True

    `run_vb` is a thin wrapper over the resumable session API — it is
    exactly `vb_run(vb_init(<same arguments>), n_iters)[1]`, and is
    bit-exact with the pre-session engine on every estimator, executor,
    backend and streaming configuration (the golden-parity and
    executor-equivalence suites are the oracle).  Use `vb_init` /
    `vb_step` / `vb_run` directly to pause, checkpoint, resume, or feed
    newly-arrived data mid-run; use `serving.vb_service.VBService` to
    serve fleets of sessions.
    """
    state = vb_init(model, data, topology, schedule=schedule,
                    replication=replication, init_phi=init_phi,
                    ref_phi=ref_phi, executor=executor, backend=backend,
                    minibatch=minibatch, diagnostics=diagnostics,
                    metric_nodes=metric_nodes)
    _, run = vb_run(state, n_iters)
    return run


def _run_vb_sharded(session: VBSession, n_iters, phi0, carry0, stream0, t0):
    """shard_map executor: node axis sharded over `executor.axis`.

    Returns the same (phi, carry, stream, kls, msds, diags) tuple as
    `_scan_steps` — the final carry/stream come back through the
    shard_map outputs with the state specs from
    `dist/sharding.vb_node_specs`, so `vb_run` can rebuild a complete
    `VBState` under this executor too.
    """
    mesh, axis = session.executor.mesh, session.executor.axis
    from jax.sharding import PartitionSpec
    from repro.dist import sharding

    model, data, topology = session.model, session.data, session.topology
    local_inputs = topology.shard_inputs()          # dict of (N, ...) arrays
    local_keys = tuple(sorted(local_inputs))
    has_carry = carry0 is not None
    has_stream = stream0 is not None
    diagnostics = session.diagnostics
    # diagnostics pytrees are reduced with psum/pmean inside the step, so
    # every shard returns the identical (replicated) value
    has_diag = diagnostics and getattr(topology, "emits_diagnostics", False)

    # stream state: keys/permutation (and the SVRG anchors, when carried)
    # are per-node data, the epoch counter is replicated (epoch boundaries
    # are global) — stream.state_specs mirrors the state's None structure
    stream_specs = (stream.state_specs(stream0, axis)
                    if has_stream else None)
    in_specs, out_specs = sharding.vb_node_specs(
        data, axis=axis, has_carry=has_carry, n_local=len(local_keys),
        carry_specs=topology.carry_specs(axis) if has_carry else None,
        stream_specs=stream_specs)
    if has_diag:
        out_specs = out_specs + (PartitionSpec(),)

    def run(data_l, phi_l, carry_l, stream_l, *local_vals):
        local = dict(zip(local_keys, local_vals))
        phi, aux, st, kls, msds, diags = _scan_steps(
            model, data_l, topology, session.schedule, session.replication,
            session.ref_phi, n_iters, phi_l,
            carry_l if has_carry else None, t0=t0,
            stream0=stream_l if has_stream else None,
            axis=axis, local=local, diagnostics=diagnostics,
            minibatch=session.minibatch)
        aux = aux if has_carry else jnp.zeros((), phi.dtype)
        st = st if has_stream else jnp.zeros((), phi.dtype)
        if has_diag:
            return phi, aux, st, kls, msds, diags
        return phi, aux, st, kls, msds

    fn = jax.shard_map(run, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    out = fn(data, phi0,
             carry0 if has_carry else jnp.zeros((), phi0.dtype),
             stream0 if has_stream else jnp.zeros((), phi0.dtype),
             *(local_inputs[k] for k in local_keys))
    phi, aux, st, kls, msds = out[:5]
    diags = out[5] if has_diag else None
    return (phi, aux if has_carry else None, st if has_stream else None,
            kls, msds, diags)
