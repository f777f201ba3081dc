"""Compute backends: WHICH implementation runs the per-iteration hot path.

The engine (core/engine.py) is written against `ConjugateExpModel`; for the
Bayesian GMM the per-node VBE step + local VBM optimum (Eqs. 17a/18,
Appendix A) dominates every paper experiment.  This module makes that
compute pluggable while everything exchanged between nodes stays in
natural-parameter space (the Khan information-geometry view: the message
phi is backend-invariant, only the arithmetic that produces phi* varies):

* `ReferenceBackend` ("reference") — the naive three-pass einsum path in
  core/gmm.py.  Ground truth; what the fused path is parity-tested against.
* `FusedBackend` ("fused") — one call goes data -> phi*:
    1. unpack phi, precompute the per-node per-component kernel terms
       (gmm.estep_terms) in `PrecisionPolicy.accum_dtype`,
    2. run the node-batched single-pass Pallas kernel
       (kernels/gmm_estep.gmm_estep_nodes): responsibilities + sufficient
       statistics in ONE sweep over the data, f32 accumulation,
    3. a fused post-stage — the Appendix-A VBM hyperparameter update
       packed straight into the message (gmm.natural_from_stats) — inside
       the same jit.
  Each node's W^{-1} is factored once per call, in step 1 (W and log|W|
  from one LU); the post-stage inverts nothing per node.
  Data may stream in a narrow dtype (`PrecisionPolicy.data_dtype=bf16`)
  while accumulation stays f32, mirroring `ring_combine`'s `compute_dtype`
  convention.

Backends are selected by name or instance via `GMMModel(..., backend=)` or
per-run via `run_vb(..., backend=)`, and compose with both executors: the
fused kernel maps over whatever slice of the node axis the executor hands
it, so under `MeshExecutor`/shard_map each shard runs the kernel on its
local nodes.  Off-TPU the kernel executes in pallas interpret mode
(numerics-identical); on a TPU the same call compiles to Mosaic
(`kernels/ops.py` decides).

Every backend is a frozen dataclass: hashable, so wrappers may pass backend
instances through `jax.jit` static arguments.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, NamedTuple, Protocol, runtime_checkable

import jax
import jax.numpy as jnp

from repro.core import expfam, gmm
from repro.core.expfam import GMMPosterior


class PrecisionPolicy(NamedTuple):
    """Dtype contract of the fused hot path.

    data_dtype : streaming dtype for x/mask entering the kernel (None =
        leave as given, but never wider than f32).  bf16 halves HBM
        traffic on TPU; the kernel upcasts blocks in VMEM.
    accum_dtype : dtype of the unpack/precompute and the VBM post-stage
        (statistics always accumulate in f32 inside the kernel).
    out_dtype : dtype of the returned phi* stack (None = match the
        incoming phi iterate, so the engine's scan carry keeps its dtype).

    Example — stream bf16, accumulate f32 (the TPU-friendly setting):

    >>> import jax.numpy as jnp
    >>> policy = PrecisionPolicy(data_dtype=jnp.bfloat16)
    >>> backend = FusedBackend(precision=policy)
    >>> backend.name, backend.precision.accum_dtype is jnp.float32
    ('fused', True)
    """

    data_dtype: Any = None
    accum_dtype: Any = jnp.float32
    out_dtype: Any = None


@runtime_checkable
class Backend(Protocol):
    """What a GMM compute backend provides to GMMModel.local_optimum.

    Backends are selected by name, instance, or per run — all equivalent:

    >>> resolve(None).name                    # default
    'reference'
    >>> resolve("fused").name                 # by name
    'fused'
    >>> resolve(ReferenceBackend()).name      # instances pass through
    'reference'

    and plug in via ``GMMModel(..., backend=)`` or
    ``engine.run_vb(..., backend=)``.
    """

    name: str

    def supports(self, model) -> bool:
        """Capability check: can this backend run `model`'s hot path?

        `engine.vb_init` consults this before binding a backend to a model
        and falls back to the reference path (with a warning) when the
        answer is no — selecting the fused kernel for a non-GMM model must
        degrade gracefully, not crash inside the kernel."""
        ...

    def local_vbm_optimum_nodes(self, x, mask, phi_nodes,
                                prior: GMMPosterior, replication,
                                K: int, D: int) -> jnp.ndarray:
        """(N, Ni, D) data + (N, P) iterates -> (N, P) local optima phi*."""
        ...


@dataclasses.dataclass(frozen=True)
class ReferenceBackend:
    """core/gmm.py as-is: three einsum passes over the data per iteration."""

    name: str = dataclasses.field(default="reference", init=False)

    def supports(self, model) -> bool:
        """The reference path IS the model's own `local_optimum` — every
        conjugate-exponential adapter supports it by construction."""
        return True

    def local_vbm_optimum_nodes(self, x, mask, phi_nodes, prior,
                                replication, K, D):
        return gmm.local_vbm_optimum_nodes(x, phi_nodes, prior, replication,
                                           K, D, mask)


@functools.partial(
    jax.jit, static_argnames=("K", "D", "block_t", "data_dtype",
                              "accum_dtype", "out_dtype"))
def _fused_local_vbm(x, mask, phi_nodes, prior, replication, *, K, D,
                     block_t, data_dtype, accum_dtype, out_dtype):
    """data -> phi* in one jitted call (kernel + fused VBM post-stage)."""
    from repro.kernels import ops

    acc = accum_dtype
    out = out_dtype if out_dtype is not None else phi_nodes.dtype

    def terms(phi):
        q, logdet_W = expfam.unpack_natural_logdet(phi.astype(acc), K, D)
        return gmm.estep_terms(q, dtype=acc, logdet_W=logdet_W)

    # the kernel computes in f32 and Mosaic cannot lower f64 operands, so
    # nothing wider than f32 enters it (the kernel upcasts narrower data)
    with jax.named_scope("vb/terms"):
        log_prior, Wn, b, c = (a.astype(jnp.float32)
                               for a in jax.vmap(terms)(phi_nodes))
    with jax.named_scope("vb/vbe"):
        if data_dtype is not None:
            x = x.astype(data_dtype)
        elif jnp.dtype(x.dtype).itemsize > 4:
            x = x.astype(jnp.float32)
        mask = mask.astype(x.dtype)
        # replication scaling happens kernel-side (at statistics-emit time)
        _, R, sum_x, sum_xx = ops.gmm_estep_nodes(
            x, mask, log_prior, Wn, b, c, replication, block_t=block_t,
            return_r=False)

    # fused post-stage: Appendix-A VBM update + pack
    prior_acc = jax.tree_util.tree_map(lambda a: a.astype(acc), prior)

    def post(R_i, sx_i, sxx_i):
        stats = gmm.SuffStats(R=R_i.astype(acc), sum_x=sx_i.astype(acc),
                              sum_xx=sxx_i.astype(acc))
        return gmm.natural_from_stats(stats, prior_acc)

    with jax.named_scope("vb/vbm"):
        return jax.vmap(post)(R, sum_x, sum_xx).astype(out)


@dataclasses.dataclass(frozen=True)
class FusedBackend:
    """Single-pass Pallas VBE kernel + jitted VBM post-stage."""

    block_t: int = 512
    precision: PrecisionPolicy = PrecisionPolicy()
    name: str = dataclasses.field(default="fused", init=False)

    def supports(self, model) -> bool:
        """The Pallas kernel implements exactly the GMM E-step; models tag
        their hot-path family via a `kernel_family` class attribute."""
        return getattr(model, "kernel_family", None) == "gmm"

    def local_vbm_optimum_nodes(self, x, mask, phi_nodes, prior,
                                replication, K, D):
        p = self.precision
        return _fused_local_vbm(
            x, mask, phi_nodes, prior, replication, K=K, D=D,
            block_t=self.block_t, data_dtype=p.data_dtype,
            accum_dtype=p.accum_dtype, out_dtype=p.out_dtype)


_BY_NAME = {"reference": ReferenceBackend, "fused": FusedBackend}


def resolve(backend: str | Backend | None) -> Backend:
    """None -> reference; a name -> default instance; instances pass through."""
    if backend is None:
        return ReferenceBackend()
    if isinstance(backend, str):
        try:
            return _BY_NAME[backend]()
        except KeyError:
            raise ValueError(
                f"unknown backend {backend!r}; expected one of "
                f"{sorted(_BY_NAME)} or a Backend instance") from None
    if not isinstance(backend, Backend):
        raise TypeError(f"not a compute backend: {backend!r}")
    return backend
