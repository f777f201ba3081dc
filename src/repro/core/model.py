"""Conjugate-exponential model adapters for the unified VB engine.

The paper's contribution 1 is that dSVB and dVB-ADMM apply to the *general
class* of conjugate-exponential models: every algorithm only ever touches a
model through (a) the flat natural-parameter vector phi exchanged between
nodes (Eq. 45), (b) the per-node local VBM optimum phi*_i (Eq. 18), (c) the
projection onto the natural-parameter domain Omega (Eq. 38b) and (d) the KL
metric d(phi, phi_hat) (Eq. 46).  `ConjugateExpModel` names exactly that
surface; `engine.run_vb` is written against it and nothing else.

Since PR 9 every adapter is a `blocks.BlockModel`: the model declares its
tuple of exponential-family blocks (core/blocks.py) and the hyper
split/join, and pack/unpack/KL/projection/block-labels plus the streaming
and serving data plumbing (pad_to_capacity / take_minibatch /
append_node_data) are protocol-level defaults derived from the block list.
An adapter only owns its `local_optimum`.  Two instances live here:

* `GMMModel`   — the paper's Bayesian Gaussian mixture (Sec. IV + App. A):
  DirichletBlock(1 row) + NormalWishartBlock, wrapping core/gmm.py.
  Mixture components carry no canonical order, so the reference for the KL
  metric may be a stack of component permutations (core/refperm.py); the
  engine takes the min.
* `LinRegModel` — Bayesian linear regression with Normal-Gamma conjugacy
  (core/linreg.py): a single NormalGammaBlock row, the classic
  diffusion-LMS WSN task.  The model has no local latent variables, so the
  VBE step is trivial and phi*_i is constant across iterations:
  `local_optimum` accepts either raw node data (X, y, mask) or a
  precomputed (N, P) phi* stack.

The model zoo (`models/hmm.py` HMMModel, `models/ppca.py` PPCAModel)
composes the same blocks into further members of the class — see
docs/model-zoo.md.
"""
from __future__ import annotations

from typing import Any, Protocol, runtime_checkable

import jax
import jax.numpy as jnp

from repro.core import backends, blocks, linreg
from repro.core.expfam import GMMPosterior, NWParams
from repro.core.linreg import NGPosterior


@runtime_checkable
class ConjugateExpModel(Protocol):
    """What the engine needs from a conjugate-exponential model.

    Any object with this surface runs under every topology and executor of
    `engine.run_vb` — that is the paper's contribution-1 generality claim
    as an API.  `blocks.BlockModel` provides default implementations of
    everything except `local_optimum` from a tuple of exponential-family
    blocks.  Example (the shipped GMM instance):

    >>> from repro.core import expfam, model
    >>> mdl = model.GMMModel(expfam.noninformative_prior(3, 2), K=3, D=2)
    >>> isinstance(mdl, model.ConjugateExpModel)
    True
    >>> mdl.flat_dim                      # P of the Eq. 45 message
    27
    >>> mdl.init_phi().shape              # the prior, packed
    (27,)
    """

    @property
    def flat_dim(self) -> int:
        """Length P of the flat natural-parameter message (Eq. 45)."""
        ...

    def pack(self, q) -> jnp.ndarray:
        """Hyperparameters -> flat natural parameters phi."""
        ...

    def unpack(self, phi: jnp.ndarray):
        """Flat natural parameters -> hyperparameter container."""
        ...

    def init_phi(self) -> jnp.ndarray:
        """Default (P,) starting point (the prior's natural parameters)."""
        ...

    def local_optimum(self, data: Any, phi_nodes: jnp.ndarray,
                      replication: float) -> jnp.ndarray:
        """Per-node VBE step + local VBM optimum phi*_i (Eqs. 17a, 18).

        `data` is the stacked per-node data pytree; `phi_nodes` is (N, P);
        `replication` is the likelihood replication factor (the network
        size N for cooperative runs, 1 for non-cooperative).  Returns the
        (N, P) stack of local optima.
        """
        ...

    def project_to_domain(self, phi: jnp.ndarray) -> jnp.ndarray:
        """Projection of one (P,) point onto the domain Omega (Eq. 38b)."""
        ...

    def kl(self, phi: jnp.ndarray, phi_ref: jnp.ndarray) -> jnp.ndarray:
        """d(phi, phi_ref) of Eq. 46: KL(Q(.|phi) || P(.|phi_ref))."""
        ...

    def block_labels(self) -> jnp.ndarray:
        """(P,) int32 block-type label per flat coordinate — the per-block
        view of phi used by the adaptive consensus layer (per-block dual
        scaling / residual norms).  Labels index the model's BLOCK_NAMES.
        """
        ...

    def data_mask(self, data: Any) -> jnp.ndarray:
        """(N, T) per-sample validity mask of the stacked node data — the
        base mask the streaming layer (data/stream.py) subsamples from."""
        ...

    def take_minibatch(self, data: Any, idx: jnp.ndarray,
                       mb_mask: jnp.ndarray) -> Any:
        """Gather the per-iteration minibatch: `idx` (N, B) indexes each
        node's sample axis, `mb_mask` (N, B) is the pre-scaled minibatch
        mask from `stream.minibatch_select` (selected-point weight T/B,
        so statistics stay unbiased).  Returns a data pytree of the same
        structure with the sample axis shrunk to B."""
        ...

    def append_node_data(self, data: Any, node: int, points: Any) -> Any:
        """Mid-flight data arrival: write `points` (the model's per-node
        observation format, leading axis = new samples) into node
        `node`'s free padding slots (mask == 0) and mark them valid.
        Returns a data pytree of IDENTICAL shapes/dtypes (buffers are
        fixed-capacity), so a live session/fleet keeps its compiled step.
        Raises ValueError when the node's buffer has no free capacity.
        Host-side (eager) — the serving layer calls it between slices."""
        ...

    def pad_to_capacity(self, data: Any, capacity: int) -> Any:
        """Grow every node's sample buffer to `capacity` slots by appending
        mask-zero padding (values zero, mask zero).  The serving layer's
        bucketed admission (serving/admission.py) pads sessions up to a
        shared ladder rung so near-same-shape sessions share one compiled
        fleet; the appended slots add nothing to any statistic, so the
        padded trajectory agrees with the unpadded one to rounding
        (docs/bucketed-admission.md), and they double as free capacity
        for `append_node_data`.
        Raises ValueError if `capacity` is below the current buffer size.
        Host-side (eager)."""
        ...


# ---------------------------------------------------------------------------
# Bayesian GMM (the paper's worked example)
# ---------------------------------------------------------------------------
class GMMModel(blocks.BlockModel):
    """Dirichlet x Normal-Wishart mixture posterior in natural-param space.

    `backend` selects the compute implementation of the per-iteration hot
    path (core/backends.py): "reference" (default; core/gmm.py einsums) or
    "fused" (node-batched single-pass Pallas kernel + jitted VBM
    post-stage), or any `backends.Backend` instance — e.g.
    `backends.FusedBackend(precision=PrecisionPolicy(data_dtype=bf16))`.
    """

    #: capability tag consumed by `backends.Backend.supports`: the fused
    #: Pallas kernel implements exactly the GMM E-step.
    kernel_family = "gmm"

    def __init__(self, prior: GMMPosterior, K: int | None = None,
                 D: int | None = None,
                 backend: str | backends.Backend | None = None):
        self.prior = prior
        self.K = K if K is not None else prior.K
        self.D = D if D is not None else prior.D
        self.backend = backends.resolve(backend)
        self.blocks = (blocks.DirichletBlock(self.K),
                       blocks.NormalWishartBlock(self.K, self.D))

    def with_backend(self, backend) -> "GMMModel":
        """Same model, different compute backend (used by run_vb(backend=))."""
        return GMMModel(self.prior, self.K, self.D, backend=backend)

    def split_hyper(self, q: GMMPosterior) -> tuple:
        return (q.alpha[None], NWParams(m=q.m, beta=q.beta, W=q.W, nu=q.nu))

    def join_hyper(self, parts: tuple) -> GMMPosterior:
        alpha, nw = parts
        return GMMPosterior(alpha=alpha[0], m=nw.m, beta=nw.beta, W=nw.W,
                            nu=nw.nu)

    def local_optimum(self, data, phi_nodes, replication):
        x, mask = data
        return self.backend.local_vbm_optimum_nodes(
            x, mask, phi_nodes, self.prior, replication, self.K, self.D)


# ---------------------------------------------------------------------------
# Bayesian linear regression (Normal-Gamma) — the generality instance
# ---------------------------------------------------------------------------
class LinRegModel(blocks.BlockModel):
    """y = w^T x + N(0, lambda^-1), lambda ~ Ga, w|lambda ~ N (conjugate)."""

    def __init__(self, prior: NGPosterior | None = None,
                 D: int | None = None):
        if prior is None and D is None:
            raise ValueError("LinRegModel needs a prior or a dimension D")
        self.prior = prior
        self.D = D if D is not None else prior.D
        self.blocks = (blocks.NormalGammaBlock(self.D),)

    @classmethod
    def from_flat_dim(cls, P: int) -> "LinRegModel":
        """Recover D from P = 2 + D + D^2 (integer root)."""
        D = int(round((-1.0 + (1.0 + 4.0 * (P - 2)) ** 0.5) / 2.0))
        if linreg.flat_dim(D) != P:
            raise ValueError(f"no integer D with flat_dim(D) == {P}")
        return cls(D=D)

    def with_backend(self, backend) -> "LinRegModel":
        """LinRegModel has no data hot loop (phi* is a one-time closed form),
        so only the reference backend applies."""
        resolved = backends.resolve(backend)
        if resolved.name != "reference":
            raise ValueError(
                f"LinRegModel has no {resolved.name!r} compute backend; "
                "its VBE step is trivial (no per-iteration data pass)")
        return self

    def split_hyper(self, q: NGPosterior) -> tuple:
        return (jax.tree_util.tree_map(lambda a: a[None], q),)

    def join_hyper(self, parts: tuple) -> NGPosterior:
        return jax.tree_util.tree_map(lambda a: a[0], parts[0])

    def local_optimum(self, data, phi_nodes, replication):
        # No local latents: phi*_i does not depend on the current iterate.
        # `data` is either a precomputed (N, P) phi* stack or raw node data.
        if hasattr(data, "ndim") and data.ndim == 2 \
                and data.shape[-1] == self.flat_dim:
            return data
        X, y, mask = data
        return jax.vmap(
            lambda Xi, yi, mi: linreg.local_optimum(
                Xi, yi, mi, self.prior, replication))(X, y, mask)

    def _raw_data(self, data):
        if hasattr(data, "ndim") and data.ndim == 2 \
                and data.shape[-1] == self.flat_dim:
            raise ValueError(
                "cannot minibatch a precomputed (N, P) phi* stack; pass "
                "raw (X, y, mask) node data to stream LinRegModel")
        return data

    def data_mask(self, data):
        return self._raw_data(data)[-1]

    def take_minibatch(self, data, idx, mb_mask):
        return super().take_minibatch(self._raw_data(data), idx, mb_mask)

    def append_node_data(self, data, node, points):
        """`points` is an (X_new (M, D), y_new (M,)) pair."""
        X, y, mask = self._raw_data(data)
        X_new, y_new = points
        X_new = jnp.asarray(X_new, X.dtype)
        y_new = jnp.asarray(y_new, y.dtype)
        if X_new.ndim == 1:
            X_new, y_new = X_new[None], jnp.atleast_1d(y_new)
        slots = self._free_slots(mask, node, X_new.shape[0])
        return (X.at[node, slots].set(X_new),
                y.at[node, slots].set(y_new),
                mask.at[node, slots].set(jnp.ones((), mask.dtype)))
