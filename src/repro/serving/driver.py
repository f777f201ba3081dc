"""Continuous-batching serving driver: fixed-capacity VB fleets with
mid-flight join/leave, an arrival queue, eviction, and background
checkpoint writes — the LM-inference-server scheduling model applied to
sensor-network VB sessions.

The synchronous `VBService` loop (PR 5) serialized everything: admission
resized the fleet (recompiling the slice function), a finished session's
slot kept burning device cycles until the whole group drained, and
checkpoint I/O blocked stepping.  This module replaces that with the
continuous-batching decomposition used by LM inference engines:

* **SlotTable** — host-side allocator for a FIXED-capacity fleet.  The
  compiled slice function only ever sees one `(k, capacity)` shape, so
  sessions join and leave by `.at[slot].set(...)` writes with **zero
  recompilation** (`FleetGroup` asserts this via its `compiles` counter).
* **Active mask for free** — a free or evicted slot is written as
  `conv=True, budget=0`: the per-session budget/early-stop gate that
  `_gated_step` already applies IS the active mask, so no new in-kernel
  machinery is needed and frozen slots stay bit-for-bit inert.
* **ArrivalQueue** — thread-safe `(arrive_at, seq)` heap.  `tick()`
  admits every ready arrival at the slice boundary, dispatches one slice
  per group (JAX async dispatch), does host-side work — checkpoint
  snapshots, bookkeeping — while the device runs, then syncs the small
  per-slot flag vectors and **evicts** sessions that converged or spent
  their budget, freeing their slots for the next arrival.
* **CheckpointWriter** — a daemon thread doing device→host transfer and
  .npz compression off the scheduler thread, overlapped with the
  in-flight slice.
* **Bucketed admission** — fleet groups are keyed by the BUCKETED data
  shape: per-node buffers pad with mask-zero slots up to a capacity
  ladder rung (`admission.bucket_capacity`) and per-iteration hyper
  constants (tau/d0, rho/xi) lift to per-slot fleet arrays
  (`engine.session_hyper`), so mixed-shape mixed-hyper sessions share
  one compiled fleet — bit-equal to their solo runs via the engine's
  ordered reductions (docs/bucketed-admission.md).
* **Eviction is safe** because of the absolute-`t` resumability contract
  (engine.VBState): every per-iteration source — minibatch epochs, link
  drops, eta/kappa ramps — is a pure function of the session's own `t`,
  so a session's trajectory is independent of WHEN its slices run and a
  finished-then-extended session re-enters any free slot bit-exactly.

`VBDriver` is the scheduler; `serving/vb_service.py` keeps its public
API as a thin wrapper, and `serving/engine.py`'s LM `Engine` reuses
`SlotTable`/`ArrivalQueue`/`DriverStats` for its prefill/decode waves.
"""
from __future__ import annotations

import contextlib
import heapq
import itertools
import os
import queue as queue_lib
import threading
import time
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import telemetry
from repro.checkpoint import ckpt
from repro.core import engine
from repro.data import stream as stream_lib
from repro.serving import admission


# ---------------------------------------------------------------------------
# Generic scheduling primitives (shared with the LM serving engine)
# ---------------------------------------------------------------------------
class ArrivalQueue:
    """Thread-safe arrival queue ordered by (arrive_at, submission seq)."""

    def __init__(self):
        self._heap: list[tuple[float, int, Any]] = []
        self._lock = threading.Lock()
        self._seq = itertools.count()

    def push(self, item: Any, arrive_at: float = 0.0) -> None:
        with self._lock:
            heapq.heappush(self._heap,
                           (float(arrive_at), next(self._seq), item))

    def push_entry(self, entry: tuple[float, int, Any]) -> None:
        """Re-queue a popped entry unchanged (keeps its FIFO position)."""
        with self._lock:
            heapq.heappush(self._heap, entry)

    def pop_ready(self, now: float) -> list[tuple[float, int, Any]]:
        out = []
        with self._lock:
            while self._heap and self._heap[0][0] <= now:
                out.append(heapq.heappop(self._heap))
        return out

    def next_arrival(self) -> Optional[float]:
        with self._lock:
            return self._heap[0][0] if self._heap else None

    def __len__(self) -> int:
        with self._lock:
            return len(self._heap)


class SlotTable:
    """Fixed-capacity slot allocator: which fleet row belongs to which
    request id.  Lowest free slot first, so admission is deterministic."""

    def __init__(self, capacity: int):
        self.capacity = int(capacity)
        self._free = list(range(self.capacity - 1, -1, -1))
        self.rids: list[Optional[str]] = [None] * self.capacity

    def alloc(self, rid: str) -> Optional[int]:
        if not self._free:
            return None
        slot = self._free.pop()
        self.rids[slot] = rid
        return slot

    def free(self, slot: int) -> Optional[str]:
        rid, self.rids[slot] = self.rids[slot], None
        self._free.append(slot)
        self._free.sort(reverse=True)
        return rid

    def grow(self, new_capacity: int) -> None:
        extra = range(self.capacity, new_capacity)
        self.rids.extend([None] * (new_capacity - self.capacity))
        self._free = sorted(self._free + list(extra), reverse=True)
        self.capacity = new_capacity

    def occupied(self) -> list[tuple[int, str]]:
        return [(i, r) for i, r in enumerate(self.rids) if r is not None]

    @property
    def n_occupied(self) -> int:
        return self.capacity - len(self._free)


class BucketStats(NamedTuple):
    """Per-fleet-group (= per admission bucket) scheduler counters."""

    label: str               # "<Model>/N<nodes>/cap<rung>" or ".../exact"
    bucket_capacity: Optional[int]  # data-capacity rung (None = unbucketed)
    slots: int               # fleet slot capacity now
    admitted: int            # sessions ever admitted into this group
    active: int              # now: occupied slots that still have work
    occupancy: float         # time-averaged active/slots over stepped slices
    padding_waste: float     # 1 - occupancy: stepped-but-masked slot frac
    data_pad_frac: float     # mean fraction of mask-zero rung-padding
    #                          slots per admitted session (0 = exact fit)


class DriverStats(NamedTuple):
    """Host-side scheduler counters (cumulative unless noted)."""

    slices: int          # device slices dispatched
    compiles: int        # slice-fn traces across all groups (incl. retired)
    admitted: int        # sessions placed into a fleet slot
    evicted: int         # sessions removed at a slice boundary
    queue_depth: int     # now: sessions waiting for arrival time or a slot
    active: int          # now: occupied slots that still have work
    capacity: int        # now: total fleet slots across groups
    occupancy: float     # time-averaged active/capacity over stepped slices
    padding_waste: float  # 1 - occupancy: fraction of stepped slots masked
    checkpoints: int     # background checkpoint writes completed
    buckets: tuple = ()  # per-group BucketStats breakdown (VB driver only)
    checkpoint_errors: int = 0  # background checkpoint writes that raised


class _PendingSave:
    """Tiny future for one background checkpoint write."""

    def __init__(self):
        self._done = threading.Event()
        self.path: Optional[str] = None
        self.exc: Optional[BaseException] = None

    def wait(self, timeout: Optional[float] = None) -> str:
        self._done.wait(timeout)
        if self.exc is not None:
            raise self.exc
        return self.path


class CheckpointWriter:
    """Background checkpoint writes: the device→host transfer and .npz
    compression run on a daemon thread, overlapped with the in-flight
    device slice (the snapshot refs are captured at the slice boundary,
    so what lands on disk is always a valid resumable boundary state)."""

    def __init__(self):
        self._q: queue_lib.Queue = queue_lib.Queue()
        self._lock = threading.Lock()
        self._thread: Optional[threading.Thread] = None
        self.completed = 0
        self.errors = 0     # failed writes (counted even when nobody waits)

    def submit(self, tree: Any, path: str) -> _PendingSave:
        pending = _PendingSave()
        with self._lock:
            if self._thread is None or not self._thread.is_alive():
                self._thread = threading.Thread(target=self._worker,
                                                daemon=True)
                self._thread.start()
        self._q.put((tree, path, pending))
        return pending

    def _worker(self) -> None:
        while True:
            tree, path, pending = self._q.get()
            t0 = time.perf_counter()
            try:
                with telemetry.span("driver/checkpoint",
                                    file=os.path.basename(path)):
                    pending.path = ckpt.save(path, jax.device_get(tree))
                self.completed += 1
                telemetry.inc("driver_checkpoints_total")
                telemetry.observe("driver_checkpoint_write_seconds",
                                  time.perf_counter() - t0)
            except BaseException as e:
                # Surfaced via pending.wait() when someone holds the
                # future — but the driver's periodic autosaves never
                # wait, so the error must ALSO land somewhere visible:
                # the `errors` counter feeds DriverStats.checkpoint_errors
                # and the telemetry counter.  Swallowing keeps the
                # daemon thread (and the scheduler) alive.
                pending.exc = e
                self.errors += 1
                telemetry.inc("driver_checkpoint_errors_total")
            finally:
                pending._done.set()
                self._q.task_done()

    def flush(self) -> None:
        self._q.join()


# ---------------------------------------------------------------------------
# Pytree helpers + the gated slice kernel (moved from vb_service)
# ---------------------------------------------------------------------------
def _tree_stack(trees):
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *trees)


def _tree_index(tree, i):
    return jax.tree_util.tree_map(lambda leaf: leaf[i], tree)


def _tree_set(tree, i, value):
    return jax.tree_util.tree_map(lambda leaf, v: leaf.at[i].set(v),
                                  tree, value)


def _gated_step(step_fn, axis=None):
    """Wrap the engine's one-iteration kernel with per-session budget /
    early-stop gating: inactive sessions (converged, or budget spent)
    keep their state bit-for-bit and their absolute t frozen, so a
    session that early-stops inside a fleet ends in exactly the state a
    solo `vb_run` of the same length would have produced.  A FREE slot
    is simply a session with `conv=True, budget=0` — the same gate is
    the driver's active mask.  Under the mesh executor (`axis`) the
    early-stop delta is pmean-reduced so every shard takes the identical
    stop decision."""

    def one(data, phi, carry, st, t, conv, budget, tol, delta_prev, hyper):
        active = jnp.logical_and(~conv, t < budget)
        phi2, carry2, st2, _ = step_fn(data, phi, carry, st, t, hyper)
        msq = jnp.mean((phi2 - phi) ** 2)
        if axis is not None:
            msq = jax.lax.pmean(msq, axis)
        delta = jnp.sqrt(msq).astype(phi.dtype)
        conv2 = jnp.logical_or(conv,
                               jnp.logical_and(tol > 0.0, delta < tol))
        gate = lambda new, old: jax.tree_util.tree_map(
            lambda a, b: jnp.where(active, a, b), new, old)
        return (jnp.where(active, phi2, phi),
                gate(carry2, carry),
                gate(st2, st),
                t + active.astype(t.dtype),
                jnp.where(active, conv2, conv),
                jnp.where(active, delta, delta_prev))

    return one


def _slice_scan(one, k):
    """k gated iterations over the vmapped fleet as one lax.scan.
    `hyper` is the per-slot lifted-hyper pytree (engine.session_hyper),
    mapped alongside the data — constant within the slice."""

    def slice_fn(data, phi, carry, st, t, conv, budget, tol, delta, hyper):
        def body(c, _):
            phi, carry, st, t, conv, delta = c
            return jax.vmap(one)(data, phi, carry, st, t, conv, budget,
                                 tol, delta, hyper), None

        init = (phi, carry, st, t, conv, delta)
        (phi, carry, st, t, conv, delta), _ = jax.lax.scan(
            body, init, None, length=k)
        return phi, carry, st, t, conv, delta

    return slice_fn


# ---------------------------------------------------------------------------
# FleetGroup: one fixed-capacity fleet of same-shape sessions
# ---------------------------------------------------------------------------
class FleetGroup:
    """One fleet: same-shape sessions batched along a leading slot axis
    of FIXED capacity.  Free slots hold an inert copy of the template
    state (conv latched, zero budget), so join/leave are `.at[slot]`
    writes and the compiled slice function never retraces mid-flight.
    `max_fleet=None` falls back to power-of-two auto-growth (capacity
    doubles when full — the shape-bucketing groundwork for ROADMAP
    item 1's bucketed admission)."""

    def __init__(self, session: engine.VBSession, executor,
                 max_fleet: Optional[int] = None,
                 bucket_capacity: Optional[int] = None):
        self.session = session          # template (data ignored per-slot)
        self.executor = executor
        self.max_fleet = max_fleet
        self.bucket_capacity = bucket_capacity  # data rung; None = exact
        self.slots: Optional[SlotTable] = None
        self.data = None                # (capacity, ...) pytrees
        self.phi = self.carry = self.stream = None
        self.t = self.conv = self.budget = self.tol = self.delta = None
        self.hyper = None               # per-slot lifted-hyper pytree
        # host mirrors of the per-slot flag vectors (refreshed by
        # fetch_flags after each slice; mutated in step with control ops)
        self.host_t = self.host_conv = None
        self.host_budget = self.host_delta = None
        self._compiled = {}             # k -> compiled slice fn
        self._retired_compiles = 0
        # per-bucket accounting (read by VBDriver.stats)
        self.n_admitted = 0
        self.pad_frac_sum = 0.0         # sum over admits of padded-slot frac
        self.occ_active = 0             # sum of active counts over slices
        self.occ_slots = 0              # sum of capacities over slices

    @property
    def capacity(self) -> int:
        return 0 if self.slots is None else self.slots.capacity

    # -- allocation -------------------------------------------------------
    def _alloc(self, record: dict) -> None:
        cap = 1 if self.max_fleet is None else int(self.max_fleet)
        bcast = lambda leaf: jnp.broadcast_to(leaf[None], (cap,) + leaf.shape)
        self.data = jax.tree_util.tree_map(bcast, record["data"])
        self.phi = bcast(record["phi"])
        self.carry = jax.tree_util.tree_map(bcast, record["carry"])
        self.stream = jax.tree_util.tree_map(bcast, record["stream"])
        self.hyper = jax.tree_util.tree_map(bcast, record["hyper"])
        self.t = bcast(record["t"])
        self.conv = jnp.ones((cap,), bool)          # free slots: inert
        self.budget = jnp.zeros((cap,), record["t"].dtype)
        dt = record["phi"].dtype
        self.tol = jnp.zeros((cap,), dt)
        self.delta = jnp.zeros((cap,), dt)
        self.host_t = np.zeros((cap,), np.int64)
        self.host_conv = np.ones((cap,), bool)
        self.host_budget = np.zeros((cap,), np.int64)
        self.host_delta = np.zeros((cap,), np.float64)
        self.slots = SlotTable(cap)

    def _grow(self) -> None:
        old = self.capacity
        new = old * 2
        pad = lambda leaf: jnp.concatenate(
            [leaf, jnp.broadcast_to(leaf[:1], (new - old,) + leaf.shape[1:])])
        self.data = jax.tree_util.tree_map(pad, self.data)
        self.phi = pad(self.phi)
        self.carry = jax.tree_util.tree_map(pad, self.carry)
        self.stream = jax.tree_util.tree_map(pad, self.stream)
        self.hyper = jax.tree_util.tree_map(pad, self.hyper)
        self.t = pad(self.t)
        self.conv = jnp.concatenate(
            [self.conv, jnp.ones((new - old,), bool)])
        self.budget = jnp.concatenate(
            [self.budget, jnp.zeros((new - old,), self.budget.dtype)])
        self.tol = jnp.concatenate(
            [self.tol, jnp.zeros((new - old,), self.tol.dtype)])
        self.delta = jnp.concatenate(
            [self.delta, jnp.zeros((new - old,), self.delta.dtype)])
        self.host_t = np.concatenate(
            [self.host_t, np.zeros((new - old,), np.int64)])
        self.host_conv = np.concatenate(
            [self.host_conv, np.ones((new - old,), bool)])
        self.host_budget = np.concatenate(
            [self.host_budget, np.zeros((new - old,), np.int64)])
        self.host_delta = np.concatenate(
            [self.host_delta, np.zeros((new - old,), np.float64)])
        self.slots.grow(new)
        self._clear_compiled()          # capacity is a new shape bucket

    # -- join / leave -----------------------------------------------------
    def admit(self, rid: str, record: dict) -> Optional[int]:
        """Place one session record into a free slot; None if the fleet
        is full (fixed capacity) — the caller keeps it queued."""
        if self.slots is None:
            self._alloc(record)
        slot = self.slots.alloc(rid)
        if slot is None:
            if self.max_fleet is not None:
                return None
            self._grow()
            slot = self.slots.alloc(rid)
        with telemetry.span("driver/admit", rid=rid, slot=slot):
            self.load_state_tree(slot, record)
            self.host_t[slot] = int(record["t"])
            self.host_conv[slot] = bool(np.asarray(record["conv"]))
            self.host_budget[slot] = int(record["budget"])
            self.host_delta[slot] = float(record["delta"])
        return slot

    def evict(self, slot: int) -> dict:
        """Snapshot a slot's resumable state and mark the slot free
        (inert: conv latched, zero budget)."""
        record = self.state_tree(slot)
        self.conv = self.conv.at[slot].set(True)
        self.budget = self.budget.at[slot].set(0)
        self.host_conv[slot] = True
        self.host_budget[slot] = 0
        self.slots.free(slot)
        return record

    # -- slice execution --------------------------------------------------
    def _slice_fn(self, k: int):
        if k not in self._compiled:
            if self.executor is None:
                one = _gated_step(engine.session_step_fn(self.session))
                self._compiled[k] = jax.jit(_slice_scan(one, k))
            else:
                self._compiled[k] = self._mesh_slice_fn(k)
        return self._compiled[k]

    def _mesh_slice_fn(self, k: int):
        """MeshExecutor composition: shard_map over the NODE axis with
        the fleet vmap inside — the fleet axis is a plain leading batch
        axis on every shard, the topology collectives run over the mesh
        axis exactly as in `engine._run_vb_sharded`."""
        from jax.sharding import PartitionSpec as P

        from repro.dist import sharding

        mesh, axis = self.executor.mesh, self.executor.axis
        ses = self.session
        topology = ses.topology
        local_inputs = topology.shard_inputs()
        local_keys = tuple(sorted(local_inputs))

        # ONE partitioning rule: take the engine executor's state specs
        # (dist/sharding.vb_node_specs) and shift every state slot one
        # axis right for the leading fleet dimension; the topology's
        # shard_inputs rows are fleet-shared and keep their specs.
        has_carry = self.carry is not None
        has_stream = self.stream is not None
        base_in, _ = sharding.vb_node_specs(
            self.data, axis=axis, has_carry=has_carry,
            n_local=len(local_keys),
            carry_specs=topology.carry_specs(axis) if has_carry else None,
            stream_specs=(stream_lib.state_specs(self.stream, axis)
                          if has_stream else None))
        data_b, phi_b, carry_b, stream_b = base_in[:4]
        local_specs = base_in[4:]

        def fleet(spec):                # unbatched spec -> fleet spec
            return jax.tree_util.tree_map(
                lambda s: P(*((None,) + tuple(s))), spec,
                is_leaf=lambda s: isinstance(s, P))

        data_specs = fleet(data_b)
        phi_spec = fleet(phi_b)
        carry_spec = fleet(carry_b) if has_carry else carry_b
        stream_spec = fleet(stream_b) if has_stream else stream_b
        rep = P()                       # per-session scalars: replicated
        hyper_spec = jax.tree_util.tree_map(lambda _: rep, self.hyper)
        in_specs = (data_specs, phi_spec, carry_spec, stream_spec,
                    rep, rep, rep, rep, rep, hyper_spec) + local_specs
        out_specs = (phi_spec, carry_spec, stream_spec, rep, rep, rep)

        def run(data_l, phi_l, carry_l, st_l, t, conv, budget, tol, delta,
                hyper, *local_vals):
            local = dict(zip(local_keys, local_vals))
            one = _gated_step(
                engine.session_step_fn(ses, axis=axis, local=local),
                axis=axis)
            return _slice_scan(one, k)(data_l, phi_l, carry_l, st_l, t,
                                       conv, budget, tol, delta, hyper)

        fn = jax.shard_map(run, mesh=mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False)

        def call(data, phi, carry, st, t, conv, budget, tol, delta, hyper):
            return fn(data, phi, carry, st, t, conv, budget, tol, delta,
                      hyper, *(local_inputs[kk] for kk in local_keys))

        return call

    def step_slice(self, k: int) -> None:
        """Dispatch one k-iteration slice (async: returns immediately
        with futures; host work may overlap until fetch_flags syncs)."""
        first = k not in self._compiled
        fn = self._slice_fn(k)
        with telemetry.span("driver/slice", k=k, slots=self.capacity):
            if first:
                # the first dispatch of a (k, capacity) shape pays the
                # trace+compile; nested so timelines separate compile
                # cost from steady-state slice dispatch
                with telemetry.span("driver/compile", k=k,
                                    slots=self.capacity):
                    out = fn(self.data, self.phi, self.carry,
                             self.stream, self.t, self.conv, self.budget,
                             self.tol, self.delta, self.hyper)
            else:
                out = fn(self.data, self.phi, self.carry, self.stream,
                         self.t, self.conv, self.budget, self.tol,
                         self.delta, self.hyper)
        (self.phi, self.carry, self.stream, self.t, self.conv,
         self.delta) = out

    def fetch_flags(self) -> None:
        """Sync the small per-slot flag vectors device -> host."""
        with telemetry.span("driver/sync"):
            t, conv, delta = jax.device_get((self.t, self.conv,
                                             self.delta))
        self.host_t = np.asarray(t).astype(np.int64)
        self.host_conv = np.asarray(conv).astype(bool)
        self.host_delta = np.asarray(delta).astype(np.float64)

    # -- host-side views --------------------------------------------------
    def done_mask(self) -> np.ndarray:
        return self.host_conv | (self.host_t >= self.host_budget)

    def active_count(self) -> int:
        if self.slots is None:
            return 0
        done = self.done_mask()
        return sum(1 for i, _ in self.slots.occupied() if not done[i])

    @property
    def compiles(self) -> int:
        """Cumulative slice-fn traces, surviving cache clears.  jit
        exposes its trace count via `_cache_size`; the mesh closure
        counts as one trace per (k, capacity)."""
        live = 0
        for fn in self._compiled.values():
            cs = getattr(fn, "_cache_size", None)
            live += int(cs()) if callable(cs) else 1
        return self._retired_compiles + live

    def _clear_compiled(self) -> None:
        self._retired_compiles = self.compiles
        self._compiled.clear()

    def state_tree(self, i: int) -> dict:
        """One session's full resumable state (checkpoint payload)."""
        return dict(phi=self.phi[i], t=self.t[i],
                    carry=_tree_index(self.carry, i),
                    stream=_tree_index(self.stream, i),
                    conv=self.conv[i], budget=self.budget[i],
                    tol=self.tol[i], delta=self.delta[i],
                    data=_tree_index(self.data, i),
                    hyper=_tree_index(self.hyper, i))

    def load_state_tree(self, i: int, tree: dict) -> None:
        self.phi = self.phi.at[i].set(tree["phi"])
        self.t = self.t.at[i].set(tree["t"])
        self.carry = _tree_set(self.carry, i, tree["carry"])
        self.stream = _tree_set(self.stream, i, tree["stream"])
        self.conv = self.conv.at[i].set(tree["conv"])
        self.budget = self.budget.at[i].set(tree["budget"])
        self.tol = self.tol.at[i].set(tree["tol"])
        self.delta = self.delta.at[i].set(tree["delta"])
        self.data = _tree_set(self.data, i, tree["data"])
        self.hyper = _tree_set(self.hyper, i, tree["hyper"])


class SessionStatus(NamedTuple):
    """Host-side snapshot of one session (admitted, queued or evicted)."""

    rid: str
    t: int                  # absolute iterations actually applied
    budget: int
    converged: bool         # early-stop latch (tol reached)
    done: bool              # converged or budget exhausted
    delta: float            # last applied step's rms phi change
    phi: Any                # (N, P) current natural parameters
    queued: bool = False    # waiting for arrival time or a free slot
    evicted: bool = False   # finished and removed from its fleet slot
    latency_s: float = 0.0  # submit -> finished wall time (0 while open)


# ---------------------------------------------------------------------------
# VBDriver: the continuous-batching scheduler
# ---------------------------------------------------------------------------
class VBDriver:
    """Continuous-batching scheduler for VB sessions.

    slice_iters : device iterations per slice — the scheduling quantum.
    max_fleet : fixed slot capacity per fleet group (arrivals beyond it
        queue until an eviction frees a slot); None = power-of-two
        auto-growth, the drop-in behaviour `VBService` defaults to.
    executor : optional `engine.MeshExecutor` (node axis sharded, fleet
        vmap inside the shard_map body).
    bucket : capacity-bucketed admission.  "pow2" (default) pads each
        session's per-node data buffers up to the next power-of-two
        ladder rung (`admission.bucket_capacity`) with mask-zero slots,
        so near-same-shape sessions share one compiled fleet; a float
        (> 1) is a custom ladder growth factor (e.g. 1.25); None keeps
        the PR-6 exact-signature grouping.  Bit-safe: the engine's
        ordered reductions make padded trajectories bit-equal to
        unpadded ones (docs/bucketed-admission.md).  Minibatch sessions
        are never padded (the streaming sampler's epoch permutations are
        a function of the true capacity), nor are data pytrees the model
        cannot pad (no `pad_to_capacity`, e.g. a LinReg phi* stack).
    bucket_min : smallest ladder rung.
    ckpt_dir / ckpt_every : when set, every `ckpt_every` slices each
        occupied slot's boundary state is handed to the background
        `CheckpointWriter` as `<ckpt_dir>/<rid>.npz`.

    Sessions differing ONLY in per-iteration hyperparameters — the
    schedule's tau/d0, ADMM's rho/xi (`engine.hyper_names`) — also share
    a fleet: those constants are lifted to per-slot arrays mapped through
    the compiled step alongside the data (`engine.session_hyper`).

    Drive it synchronously (`tick()` / `drain()`) or start the
    background scheduler thread (`start()`), then `submit` / `push_data`
    / `extend_budget` from any thread; control ops apply at slice
    boundaries (the driver lock serializes them with the device loop).
    """

    def __init__(self, *, slice_iters: int = 25,
                 max_fleet: Optional[int] = None,
                 executor: Optional[engine.MeshExecutor] = None,
                 bucket: Optional[str | float] = "pow2",
                 bucket_min: int = 8,
                 ckpt_dir: Optional[str] = None, ckpt_every: int = 0):
        if slice_iters < 1:
            raise ValueError(f"slice_iters must be >= 1: {slice_iters}")
        if max_fleet is not None and max_fleet < 1:
            raise ValueError(f"max_fleet must be >= 1: {max_fleet}")
        if bucket is None or bucket == "pow2":
            self._bucket_growth = 2.0 if bucket == "pow2" else None
        else:
            self._bucket_growth = float(bucket)
            if self._bucket_growth <= 1.0:
                raise ValueError(f"bucket growth must be > 1.0: {bucket}")
        self.bucket = bucket
        self.bucket_min = int(bucket_min)
        self.slice_iters = slice_iters
        self.max_fleet = max_fleet
        self.executor = executor
        self.ckpt_dir = ckpt_dir
        self.ckpt_every = ckpt_every
        self._groups: dict[tuple, FleetGroup] = {}
        self._where: dict[str, tuple[tuple, int]] = {}  # rid -> (key, slot)
        self._queue = ArrivalQueue()
        self._queued: dict[str, dict] = {}              # rid -> entry
        self._finished: dict[str, dict] = {}            # rid -> fin record
        self._meta: dict[str, dict] = {}
        self._order: list[str] = []
        self._counter = 0
        self._clock = 0                 # slice-boundary clock (arrive_at)
        self._slices = 0
        self._n_admitted = 0
        self._n_evicted = 0
        self._occ_active = 0            # sum of active counts over slices
        self._occ_slots = 0             # sum of capacities over slices
        self._writer = CheckpointWriter()
        self._lock = threading.RLock()
        self._wake = threading.Event()
        self._stop_evt = threading.Event()
        self._thread: Optional[threading.Thread] = None

    @contextlib.contextmanager
    def _locked(self):
        """The driver lock, with the executor's mesh as the ambient mesh:
        fleet buffers that a shard_map returns over a mesh with explicit
        axes (`jax.make_mesh`'s default) can only be updated eagerly
        inside `jax.set_mesh` of that mesh."""
        with self._lock:
            if self.executor is None:
                yield
            else:
                with jax.set_mesh(self.executor.mesh):
                    yield

    # -- admission --------------------------------------------------------
    def _session_key(self, model, topology, schedule, replication,
                     minibatch, data) -> tuple:
        """Fleet-group key: structural signatures (small arrays by
        content digest), shapes of the ALREADY-BUCKETED data, and only
        the hyperparameters the compiled step actually specializes on —
        lifted ones (`engine.lifted_attr_names` / the schedule's tau+d0)
        are stripped, since per-session values flow through the fleet's
        hyper arrays (or the carry) instead of the trace."""
        topo_sig = admission.static_signature(
            topology, ignore=engine.lifted_attr_names(topology))
        # tau/d0 are dead when eta is fixed and lifted otherwise; only
        # eta_fixed itself picks a static branch (the one-shot jump)
        sched_key = ("eta_fixed", schedule.eta_fixed)
        return (admission.static_signature(model), topo_sig,
                admission.shape_signature(data), sched_key,
                replication, minibatch)

    def _bucket_plan(self, req):
        """(data on the ladder rung, (true_cap, rung)) — or
        (req.data, None) when bucketing does not apply: disabled,
        minibatch (epoch permutations are a function of the true
        capacity), or a data pytree the model cannot pad."""
        if self.bucket is None or req.minibatch is not None:
            return req.data, None
        pad = getattr(req.model, "pad_to_capacity", None)
        mask_of = getattr(req.model, "data_mask", None)
        if pad is None or mask_of is None:
            return req.data, None
        try:
            true_cap = int(mask_of(req.data).shape[1])
        except (ValueError, IndexError):    # e.g. LinReg phi* stack
            return req.data, None
        rung = admission.bucket_capacity(true_cap,
                                         growth=self._bucket_growth,
                                         min_size=self.bucket_min)
        data = pad(req.data, rung) if rung != true_cap else req.data
        return data, (true_cap, rung)

    def submit(self, req, *, arrive_at: Optional[int] = None,
               restore_from: Optional[str] = None) -> str:
        """Queue one session (any object with the `VBRequest` fields);
        returns its id.  `arrive_at` defers admission until that slice
        boundary; `restore_from` loads a `save_session` checkpoint into
        the fresh record (the request must describe the same shapes),
        resuming it bit-exactly."""
        if req.n_iters < 1:
            raise ValueError(f"n_iters must be >= 1: {req.n_iters}")
        with telemetry.span("driver/submit"):
            data, bucket = self._bucket_plan(req)
            state = engine.vb_init(
                req.model, data, req.topology, schedule=req.schedule,
                replication=req.replication, init_phi=req.init_phi,
                minibatch=req.minibatch, diagnostics=False)
            dt = state.phi.dtype
            record = dict(phi=state.phi, t=state.t, carry=state.carry,
                          stream=state.stream, conv=jnp.zeros((), bool),
                          budget=jnp.asarray(req.n_iters, state.t.dtype),
                          tol=jnp.asarray(req.tol, dt),
                          delta=jnp.zeros((), dt), data=state.session.data,
                          hyper=engine.session_hyper(req.topology,
                                                     req.schedule, dt))
            if restore_from is not None:
                record = ckpt.restore(restore_from, record)
            key = self._session_key(req.model, req.topology, req.schedule,
                                    req.replication, req.minibatch, data)
            with self._locked():
                rid = f"s{self._counter:04d}"
                self._counter += 1
                self._order.append(rid)
                at = self._clock if arrive_at is None else int(arrive_at)
                self._meta[rid] = dict(submitted=time.monotonic(),
                                       finished=None, arrive_at=at,
                                       bucket=bucket)
                entry = dict(rid=rid, key=key, session=state.session,
                             record=record, bucket=bucket)
                self._queued[rid] = entry
                self._queue.push(entry, at)
                self._try_admit()
        self._wake.set()
        return rid

    def _try_admit(self) -> None:
        """Admit every ready arrival that a fleet slot can take (lock
        held).  Fleet-full entries go back on the queue in FIFO order."""
        for at, seq, entry in self._queue.pop_ready(self._clock):
            rid, rec = entry["rid"], entry["record"]
            if bool(np.asarray(rec["conv"])) \
                    or int(rec["t"]) >= int(rec["budget"]):
                # e.g. restored from a finished checkpoint: nothing to run
                self._queued.pop(rid, None)
                self._retire(rid, dict(record=rec, key=entry["key"],
                                       session=entry["session"]))
                continue
            bucket = self._meta[rid].get("bucket")
            group = self._groups.get(entry["key"])
            if group is None:
                group = FleetGroup(entry["session"], self.executor,
                                   max_fleet=self.max_fleet,
                                   bucket_capacity=(bucket[1] if bucket
                                                    else None))
                self._groups[entry["key"]] = group
            slot = group.admit(rid, rec)
            if slot is None:
                self._queue.push_entry((at, seq, entry))
                continue
            self._queued.pop(rid, None)
            self._where[rid] = (entry["key"], slot)
            self._n_admitted += 1
            group.n_admitted += 1
            telemetry.inc("driver_admitted_total")
            if bucket is not None:
                group.pad_frac_sum += (bucket[1] - bucket[0]) / bucket[1]

    def _retire(self, rid: str, fin: dict) -> None:
        self._finished[rid] = fin
        if self._meta[rid]["finished"] is None:
            self._meta[rid]["finished"] = time.monotonic()

    # -- the scheduling loop ----------------------------------------------
    def tick(self) -> int:
        """One slice boundary: admit ready arrivals, dispatch one slice
        per fleet with active work, overlap host-side checkpoint
        snapshots with the device slice, then sync flags, evict finished
        sessions and advance the clock.  Returns #sessions still open."""
        with self._locked(), telemetry.span("driver/tick"):
            self._try_admit()
            stepped = [g for g in self._groups.values()
                       if g.active_count() > 0]
            snaps = []
            if self.ckpt_dir and self.ckpt_every and stepped \
                    and (self._slices + 1) % self.ckpt_every == 0:
                for g in stepped:       # boundary state, pre-dispatch refs
                    snaps.extend((rid, g.state_tree(slot))
                                 for slot, rid in g.slots.occupied())
            for g in stepped:
                n_act = g.active_count()
                self._occ_active += n_act
                self._occ_slots += g.capacity
                g.occ_active += n_act
                g.occ_slots += g.capacity
                g.step_slice(self.slice_iters)      # async dispatch
            if stepped:
                self._slices += 1
            for rid, tree in snaps:     # writer overlaps the device slice
                self._writer.submit(
                    tree, os.path.join(self.ckpt_dir, f"{rid}.npz"))
            for g in stepped:
                g.fetch_flags()                     # device -> host sync
            self._evict_done()
            self._clock += 1
            if telemetry.enabled():
                # fleet health gauges at every slice boundary (one bool
                # check when telemetry is off)
                occ = (self._occ_active / self._occ_slots
                       if self._occ_slots else 0.0)
                telemetry.set_gauge("driver_queue_depth",
                                    len(self._queued))
                telemetry.set_gauge("driver_active", sum(
                    g.active_count() for g in self._groups.values()))
                telemetry.set_gauge("driver_capacity", sum(
                    g.capacity for g in self._groups.values()))
                telemetry.set_gauge("driver_occupancy", occ)
                telemetry.set_gauge("driver_padding_waste",
                                    (1.0 - occ) if self._occ_slots
                                    else 0.0)
            return self._remaining_locked()

    def _evict_done(self) -> None:
        for key, group in self._groups.items():
            if group.slots is None:
                continue
            done = group.done_mask()
            for slot, rid in group.slots.occupied():
                if not done[slot]:
                    continue
                with telemetry.span("driver/evict", rid=rid, slot=slot):
                    record = group.evict(slot)
                    del self._where[rid]
                    self._n_evicted += 1
                    telemetry.inc("driver_evicted_total")
                    self._retire(rid, dict(record=record, key=key,
                                           session=group.session))

    def _remaining_locked(self) -> int:
        return (sum(g.active_count() for g in self._groups.values())
                + len(self._queued))

    def remaining(self) -> int:
        with self._locked():
            return self._remaining_locked()

    def drain(self, max_slices: Optional[int] = None,
              poll: float = 0.002) -> int:
        """Run until no session is open (or `max_slices` dispatched).
        With the background thread running this just waits; otherwise it
        pumps `tick()` inline.  Returns #sessions still open."""
        if self._thread is not None and self._thread.is_alive():
            while self.remaining() > 0:
                time.sleep(poll)
            self._writer.flush()
            return 0
        n = 0
        left = self.tick()
        while left > 0:
            n += 1
            if max_slices is not None and n >= max_slices:
                break
            left = self.tick()
        self._writer.flush()
        return left

    def start(self) -> None:
        """Start the background scheduler thread (idempotent)."""
        with self._locked():
            if self._thread is not None and self._thread.is_alive():
                return
            self._stop_evt.clear()
            self._thread = threading.Thread(target=self._loop, daemon=True)
            self._thread.start()

    def _loop(self) -> None:
        while not self._stop_evt.is_set():
            if self.tick() == 0:
                self._wake.clear()
                self._wake.wait(timeout=0.02)

    def stop(self) -> None:
        self._stop_evt.set()
        self._wake.set()
        if self._thread is not None:
            self._thread.join(timeout=10.0)
            self._thread = None

    # -- observation ------------------------------------------------------
    def status(self, rid: str) -> SessionStatus:
        with self._locked():
            meta = self._meta.get(rid)
            if meta is None:
                raise KeyError(f"unknown session {rid!r}")
            lat = ((meta["finished"] - meta["submitted"])
                   if meta["finished"] is not None else 0.0)
            if rid in self._where:
                key, i = self._where[rid]
                g = self._groups[key]
                t, budget = int(g.host_t[i]), int(g.host_budget[i])
                conv = bool(g.host_conv[i])
                return SessionStatus(
                    rid=rid, t=t, budget=budget, converged=conv,
                    done=conv or t >= budget, delta=float(g.host_delta[i]),
                    phi=g.phi[i], latency_s=lat)
            rec = (self._finished[rid]["record"] if rid in self._finished
                   else self._queued[rid]["record"])
            t, budget = int(rec["t"]), int(rec["budget"])
            conv = bool(np.asarray(rec["conv"]))
            return SessionStatus(
                rid=rid, t=t, budget=budget, converged=conv,
                done=conv or t >= budget, delta=float(rec["delta"]),
                phi=rec["phi"], queued=rid in self._queued,
                evicted=rid in self._finished, latency_s=lat)

    @property
    def sessions(self) -> list[str]:
        with self._locked():
            return list(self._order)

    def _bucket_stats(self) -> tuple:
        out = []
        for g in self._groups.values():
            data = g.data if g.data is not None else g.session.data
            n_nodes = jax.tree_util.tree_leaves(data)[0].shape[
                1 if g.data is not None else 0]
            cap = g.bucket_capacity
            label = (f"{type(g.session.model).__name__}/N{n_nodes}/"
                     + (f"cap{cap}" if cap is not None else "exact"))
            occ = g.occ_active / g.occ_slots if g.occ_slots else 0.0
            out.append(BucketStats(
                label=label, bucket_capacity=cap, slots=g.capacity,
                admitted=g.n_admitted, active=g.active_count(),
                occupancy=occ,
                padding_waste=(1.0 - occ) if g.occ_slots else 0.0,
                data_pad_frac=(g.pad_frac_sum / g.n_admitted
                               if g.n_admitted else 0.0)))
        return tuple(sorted(out, key=lambda b: b.label))

    def stats(self) -> DriverStats:
        with self._locked():
            active = sum(g.active_count() for g in self._groups.values())
            capacity = sum(g.capacity for g in self._groups.values())
            compiles = sum(g.compiles for g in self._groups.values())
            occ = (self._occ_active / self._occ_slots
                   if self._occ_slots else 0.0)
            return DriverStats(
                slices=self._slices, compiles=compiles,
                admitted=self._n_admitted, evicted=self._n_evicted,
                queue_depth=len(self._queued), active=active,
                capacity=capacity, occupancy=occ,
                padding_waste=(1.0 - occ) if self._occ_slots else 0.0,
                checkpoints=self._writer.completed,
                buckets=self._bucket_stats(),
                checkpoint_errors=self._writer.errors)

    # -- mid-flight control ops (apply at slice boundaries) ---------------
    def push_data(self, rid: str, node: int, points: Any) -> None:
        """Append freshly-arrived observations to one node's buffer
        (into padding slots — `model.append_node_data`) and un-latch the
        session's convergence flag.  An EVICTED session whose budget
        still has room goes back through the arrival queue and resumes
        in any free slot (bit-exact, absolute-t contract).

        A BUCKETED session whose buffer overflows is not an error: the
        session is evicted from its fleet, its buffers regrown to the
        next ladder rung that fits, and it re-enters the queue under the
        larger bucket's group key — same absolute-t resume contract, so
        the trajectory matches a solo run on the regrown buffers."""
        with self._locked():
            if rid in self._where:
                key, i = self._where[rid]
                g = self._groups[key]
                data_i = _tree_index(g.data, i)
                try:
                    new = g.session.model.append_node_data(data_i, node,
                                                           points)
                except ValueError:
                    if self._meta[rid].get("bucket") is None:
                        raise
                    record = g.evict(i)
                    del self._where[rid]
                    self._n_evicted += 1
                    self._retire(rid, dict(record=record, key=key,
                                           session=g.session))
                    self._rebucket(rid, node, points)
                    self._maybe_requeue(rid)
                else:
                    g.data = _tree_set(g.data, i, new)
                    g.conv = g.conv.at[i].set(False)
                    g.host_conv[i] = False
            elif rid in self._finished or rid in self._queued:
                fin = (self._finished.get(rid) or self._queued[rid])
                rec = fin["record"]
                try:
                    rec["data"] = fin["session"].model.append_node_data(
                        rec["data"], node, points)
                except ValueError:
                    if self._meta[rid].get("bucket") is None:
                        raise
                    self._rebucket(rid, node, points)
                else:
                    rec["conv"] = jnp.zeros((), bool)
                if rid in self._finished:
                    self._maybe_requeue(rid)
            else:
                raise KeyError(f"unknown session {rid!r}")
        self._wake.set()

    def _rebucket(self, rid: str, node: int, points: Any) -> None:
        """Grow an overflowing bucketed session to the next ladder rung
        that fits `points`, append them, and re-key it (lock held; the
        rid is in `_finished` or `_queued`)."""
        fin = self._finished.get(rid) or self._queued[rid]
        rec, ses = fin["record"], fin["session"]
        model = ses.model
        true_cap, rung = self._meta[rid]["bucket"]
        data = rec["data"]
        for _ in range(64):             # each rung at least doubles room
            rung = admission.bucket_capacity(
                rung + 1, growth=self._bucket_growth,
                min_size=self.bucket_min)
            grown = model.pad_to_capacity(data, rung)
            try:
                grown = model.append_node_data(grown, node, points)
                break
            except ValueError:
                continue
        else:
            raise ValueError(
                f"session {rid!r}: could not grow buffers to fit "
                "pushed points")
        rec["data"] = grown
        rec["conv"] = jnp.zeros((), bool)
        telemetry.inc("driver_rebucket_total")
        telemetry.instant("driver/rebucket", rid=rid, rung=rung)
        self._meta[rid]["bucket"] = (true_cap, rung)
        fin["session"] = engine.VBSession(
            model, grown, ses.topology, ses.schedule, ses.replication,
            ses.ref_phi, ses.executor, ses.minibatch, ses.diagnostics,
            ses.metric_nodes)
        fin["key"] = self._session_key(model, ses.topology, ses.schedule,
                                       ses.replication, ses.minibatch,
                                       grown)

    def replace_data(self, rid: str, data: Any) -> None:
        """Replace a session's data buffers wholesale (same shapes; a
        bucketed session accepts any data that pads to its rung)."""
        with self._locked():
            bucket = self._meta.get(rid, {}).get("bucket")
            if bucket is not None:
                if rid in self._where:
                    model = self._groups[self._where[rid][0]].session.model
                else:
                    fin = (self._finished.get(rid)
                           or self._queued.get(rid))
                    model = fin["session"].model if fin else None
                if model is not None:
                    data = model.pad_to_capacity(data, bucket[1])
            cur = self._current_data(rid)
            sig_new = admission.shape_signature(data)
            sig_old = admission.shape_signature(cur)
            if sig_new != sig_old:
                raise ValueError(
                    f"replace_data: shape signature mismatch "
                    f"({sig_new} != {sig_old})")
            if rid in self._where:
                key, i = self._where[rid]
                g = self._groups[key]
                g.data = _tree_set(g.data, i, data)
                g.conv = g.conv.at[i].set(False)
                g.host_conv[i] = False
            else:
                fin = (self._finished.get(rid) or self._queued[rid])
                fin["record"]["data"] = jax.tree_util.tree_map(
                    jnp.asarray, data)
                fin["record"]["conv"] = jnp.zeros((), bool)
                if rid in self._finished:
                    self._maybe_requeue(rid)
        self._wake.set()

    def _current_data(self, rid: str):
        if rid in self._where:
            key, i = self._where[rid]
            return _tree_index(self._groups[key].data, i)
        if rid in self._finished:
            return self._finished[rid]["record"]["data"]
        if rid in self._queued:
            return self._queued[rid]["record"]["data"]
        raise KeyError(f"unknown session {rid!r}")

    def extend_budget(self, rid: str, extra_iters: int) -> None:
        with self._locked():
            if rid in self._where:
                key, i = self._where[rid]
                g = self._groups[key]
                g.budget = g.budget.at[i].add(extra_iters)
                g.conv = g.conv.at[i].set(False)
                g.host_budget[i] += extra_iters
                g.host_conv[i] = False
            elif rid in self._finished or rid in self._queued:
                fin = (self._finished.get(rid) or self._queued[rid])
                rec = fin["record"]
                rec["budget"] = rec["budget"] + jnp.asarray(
                    extra_iters, rec["budget"].dtype)
                rec["conv"] = jnp.zeros((), bool)
                if rid in self._finished:
                    self._maybe_requeue(rid)
            else:
                raise KeyError(f"unknown session {rid!r}")
        self._wake.set()

    def _maybe_requeue(self, rid: str) -> None:
        """Re-queue an evicted session that has work again (new data or
        extended budget); absolute-t resumability makes re-admission
        into any free slot bit-safe."""
        fin = self._finished[rid]
        rec = fin["record"]
        if bool(np.asarray(rec["conv"])) \
                or int(rec["t"]) >= int(rec["budget"]):
            return
        del self._finished[rid]
        self._meta[rid]["finished"] = None
        telemetry.inc("driver_requeue_total")
        telemetry.instant("driver/requeue", rid=rid)
        entry = dict(rid=rid, key=fin["key"], session=fin["session"],
                     record=rec)
        self._queued[rid] = entry
        self._queue.push(entry, self._clock)
        self._try_admit()

    # -- checkpointing ----------------------------------------------------
    def save_session(self, rid: str, path: str, *, wait: bool = True) -> str:
        """Write one session's full resumable state (incl. data buffers
        and budget bookkeeping) as a `checkpoint/ckpt.py` .npz.  With
        `wait=False` the device→host transfer and compression happen on
        the background writer thread (call `flush_checkpoints` or rely
        on `drain` before reading the file)."""
        with self._locked():
            if rid in self._where:
                key, i = self._where[rid]
                tree = self._groups[key].state_tree(i)
            elif rid in self._finished:
                tree = dict(self._finished[rid]["record"])
            elif rid in self._queued:
                tree = dict(self._queued[rid]["record"])
            else:
                raise KeyError(f"unknown session {rid!r}")
        pending = self._writer.submit(tree, path)
        return pending.wait() if wait else path

    def flush_checkpoints(self) -> None:
        self._writer.flush()
