"""Request-admission helpers shared by the serving engines.

Both serving stacks admit heterogeneous requests and must turn them into
fixed-shape device batches:

* the LM `serving.engine.Engine` admits variable-length prompts and packs
  them into one right-aligned (B, L) token batch (`right_aligned_batch`),
  grouping prompts into waves by `bucket_capacity` rung when bucketing is
  enabled;
* the VB `serving.vb_service.VBService` admits sensor-network sessions:
  requests whose data pytrees agree in shape and dtype
  (`shape_signature`) share a vmapped fleet, and the BUCKET LADDER below
  (`bucket_capacity` / `bucket_for`) lets near-same-shape sessions share
  one too — each session's per-node data capacity is padded up to the
  next ladder rung with mask-zero slots (`model.pad_to_capacity`), which
  add nothing to any statistic, so the padded run agrees with the
  unpadded one to rounding (docs/bucketed-admission.md).  Everything
  here keys on the model's protocol surface only (`data_mask` /
  `pad_to_capacity`, both block-layer defaults since PR 9), so the whole
  model zoo — GMM, LinReg, HMM, PPCA (docs/model-zoo.md) — buckets
  identically with zero per-model code.

One home for those rules so the two engines cannot drift apart, plus
`data_axis_mesh` — the "1-D data mesh over whatever devices exist" both
serving smokes want (the LM smoke used to hardcode a single-device mesh).
"""
from __future__ import annotations

import hashlib

import jax
import numpy as np

from repro import telemetry

# Arrays at or under this many bytes are signed by content digest in
# `static_signature`; larger ones fall back to identity (conservative:
# splits groups, never wrongly merges them — and never pays an O(size)
# hash on a big data buffer at admission time).
DIGEST_MAX_BYTES = 1 << 16


def bucket_capacity(n: int, *, growth: float = 2.0,
                    min_size: int = 8) -> int:
    """Smallest ladder rung >= n: the bucketed capacity a session of true
    per-node data capacity `n` is padded to.  Rungs start at `min_size`
    and grow geometrically by `growth` (2.0 = power-of-two; ~1.25 gives
    the finer tensor2tensor-style boundaries ladder, at most ~25% padded
    slots per node at the cost of more distinct compiled fleets).

    >>> [bucket_capacity(n) for n in (1, 8, 9, 25, 64, 65)]
    [8, 8, 16, 32, 64, 128]
    >>> bucket_capacity(25, growth=1.25, min_size=8)   # 8,10,13,17,22,28
    28
    """
    if n < 1:
        raise ValueError(f"capacity must be >= 1: {n}")
    if growth <= 1.0:
        raise ValueError(f"growth must be > 1.0: {growth}")
    cap = int(min_size)
    while cap < n:
        # max(+1) keeps the ladder strictly increasing for tiny growth
        cap = max(cap + 1, int(-(-cap * growth // 1)))
    # bucket-decision observability: which rungs admissions land on, and
    # how many padded slots each decision costs (docs/observability.md)
    telemetry.inc("admission_bucket_total", rung=cap)
    telemetry.inc("admission_padded_slots_total", value=cap - n)
    return cap


def bucket_for(signature: tuple, *, growth: float = 2.0,
               min_size: int = 8) -> tuple:
    """Bucketed admission key: a `shape_signature` with every array
    entry's SECOND axis (the per-node sample/capacity axis of stacked
    sensor-network data) rounded up to its ladder rung.  Two sessions
    whose signatures bucket equal may share one compiled fleet once
    their data is padded to the rung (`model.pad_to_capacity`).

    >>> import jax.numpy as jnp
    >>> a = shape_signature((jnp.zeros((4, 25, 2)), jnp.zeros((4, 25))))
    >>> b = shape_signature((jnp.zeros((4, 32, 2)), jnp.zeros((4, 32))))
    >>> bucket_for(a) == bucket_for(b)
    True
    >>> bucket_for(a) == bucket_for(shape_signature(jnp.zeros((5, 25))))
    False
    """
    def one(entry):
        shape, dtype = entry
        if len(shape) >= 2:
            shape = (shape[0],
                     bucket_capacity(shape[1], growth=growth,
                                     min_size=min_size)) + shape[2:]
        return (shape, dtype)

    return (signature[0],) + tuple(one(e) for e in signature[1:])


def right_aligned_batch(seqs, length: int | None = None,
                        dtype=np.int32, pad_value: int = 0) -> np.ndarray:
    """Stack variable-length 1-D sequences into a right-aligned (B, L)
    array (left-padded with `pad_value`), the layout the LM prefill
    expects.  `length` pads to a fixed L and must cover the longest
    sequence (ValueError otherwise — truncation is the caller's policy);
    default: the longest sequence.

    >>> right_aligned_batch([[1, 2, 3], [7]]).tolist()
    [[1, 2, 3], [0, 0, 7]]
    >>> right_aligned_batch([[1, 2]], length=4).tolist()
    [[0, 0, 1, 2]]
    """
    seqs = [np.asarray(s, dtype) for s in seqs]
    longest = max((s.shape[0] for s in seqs), default=0)
    if length is None:
        length = longest
    if length < longest:
        raise ValueError(f"length {length} < longest sequence {longest}")
    out = np.full((len(seqs), length), pad_value, dtype)
    for i, s in enumerate(seqs):
        if s.shape[0]:
            out[i, length - s.shape[0]:] = s
    return out


def shape_signature(tree) -> tuple:
    """Hashable shape/dtype signature of a pytree — requests whose data
    signatures (and static hyper) agree may share one compiled batch.

    >>> import jax.numpy as jnp
    >>> a = (jnp.zeros((3, 4)), jnp.zeros((3,), jnp.int32))
    >>> b = (jnp.ones((3, 4)), jnp.ones((3,), jnp.int32))
    >>> shape_signature(a) == shape_signature(b)
    True
    >>> shape_signature(a) == shape_signature((a[0],))
    False
    """
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    return (str(treedef),) + tuple(
        (tuple(leaf.shape), str(leaf.dtype)) for leaf in leaves)


def static_signature(obj, *, ignore: tuple = ()):
    """Hashable structural signature of a model/topology configuration.

    Two separately-constructed objects of the same type whose attributes
    agree produce the same signature and therefore share a fleet group.
    Small arrays (<= DIGEST_MAX_BYTES) are signed by CONTENT — (shape,
    dtype, bytes digest) — so `Diffusion(W)` built twice over two
    equal-valued weight matrices signs equal; larger arrays fall back to
    object identity, as does anything unrecognised (conservative: splits
    groups, never wrongly merges them).

    `ignore` drops the named TOP-LEVEL attributes from the signature —
    the serving driver uses it to strip per-session hyperparameters that
    the engine lifts onto the fleet axis (engine.lifted_attr_names), so
    e.g. two `ADMMConsensus` topologies differing only in `rho` share a
    compiled fleet.
    """
    import jax.numpy as jnp

    if isinstance(obj, (int, float, bool, str, bytes, type(None))):
        return obj
    if isinstance(obj, (jnp.ndarray, np.ndarray)):
        a = np.asarray(obj)
        if a.nbytes <= DIGEST_MAX_BYTES:
            digest = hashlib.sha1(np.ascontiguousarray(a).tobytes())
            return ("arr", tuple(a.shape), str(a.dtype),
                    digest.hexdigest())
        return ("arr", id(obj))
    if isinstance(obj, tuple):           # incl. NamedTuples (Schedule etc.)
        return (type(obj).__name__,) + tuple(static_signature(v)
                                             for v in obj)
    if hasattr(obj, "__dict__") or hasattr(obj, "__slots__"):
        names = (sorted(vars(obj)) if hasattr(obj, "__dict__")
                 else sorted(n for n in obj.__slots__ if hasattr(obj, n)))
        return (type(obj).__name__,) + tuple(
            (n, static_signature(getattr(obj, n)))
            for n in names if n not in ignore)
    try:
        hash(obj)
        return obj
    except TypeError:
        return ("id", id(obj))


def data_axis_mesh(axis: str = "data"):
    """1-D mesh with `axis` spanning ALL available devices.  The serving
    smokes default to this instead of hardcoding a single-device mesh, so
    multi-device hosts (or XLA_FLAGS host-platform devices) are used."""
    return jax.make_mesh((jax.device_count(),), (axis,))
