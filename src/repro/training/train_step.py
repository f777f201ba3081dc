"""Training step factory — classical and consensus (paper-technique) modes.

dp_mode:
  "allreduce" — baseline (cVB analogue): one global parameter set, batch
      sharded over data/pod axes, XLA inserts the gradient all-reduce.
  "diffusion" — dSVB analogue (Eq. 27): per-replica parameters along the
      consensus axis; local AdamW step then nearest-neighbour ring combine
      via ppermute.  No all-reduce over the consensus axis.
  "admm" — dVB-ADMM analogue (Eqs. 38a/39/40): per-replica parameters plus
      aggregate duals; primal/dual consensus round per step.

The consensus axis is "data" on the single-pod mesh and "pod" on the
multi-pod mesh (diffusion across the slow inter-pod links, exact all-reduce
inside a pod — hierarchical, the WSN-faithful deployment).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.dist import compat, sharding
from repro.models import model as model_lib
from repro.optim import adamw, consensus, schedules


class TrainState(NamedTuple):
    params: dict
    opt: adamw.AdamState
    duals: Optional[dict]     # ADMM only
    step: jnp.ndarray
    rho: Optional[jnp.ndarray] = None   # ADMM penalty as DYNAMIC state
    # (residual-balanced across steps when TrainHyper.adaptive_rho; None
    #  for non-ADMM modes)


class TrainHyper(NamedTuple):
    peak_lr: float = 3e-4
    warmup: int = 200
    total_steps: int = 10000
    weight_decay: float = 0.1
    clip_norm: float = 1.0
    # consensus knobs (paper defaults)
    w_self: float = 1.0 / 3.0   # Eq. 47 nearest-neighbour on a ring
    rho: float = 0.5            # ADMM penalty (Remark 3); initial value —
    #                             the live value is TrainState.rho
    xi: float = 0.05            # kappa ramp (Eq. 40)
    # residual balancing of rho across training steps (Boyd Sec. 3.4.1,
    # the VB engine's rule via optim.consensus.adapt_rho)
    adaptive_rho: bool = False
    rho_mu: float = 10.0        # grow when ||r|| > mu ||s||, shrink flipped


def loss_fn(cfg: ModelConfig, params, batch, *, use_kernels: bool = False):
    out = model_lib.forward(cfg, params, batch["tokens"],
                            batch.get("frontend"), use_kernels=use_kernels)
    logits = out["logits"][:, :-1, :]
    labels = batch["tokens"][:, 1:]
    mask = jnp.arange(labels.shape[1])[None, :] >= cfg.frontend_len
    mask = jnp.broadcast_to(mask, labels.shape).astype(jnp.float32)
    # Sharding-friendly CE: both terms reduce over the (model-sharded) vocab
    # axis, so XLA emits small (B,S) all-reduces instead of all-gathering
    # the full logits (take_along_axis would gather ~16 GiB for yi-6b).
    lse = jax.nn.logsumexp(logits, axis=-1)
    onehot = (labels[..., None] ==
              jnp.arange(logits.shape[-1])[None, None, :])
    ll = jnp.sum(jnp.where(onehot, logits, 0.0), axis=-1)
    ce = jnp.sum((lse - ll) * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    loss = ce + cfg.router_aux_weight * out["aux_loss"]
    return loss, {"ce": ce, "aux": out["aux_loss"]}


def init_state(cfg: ModelConfig, key, *, dp_mode: str = "allreduce",
               n_replicas: int = 1,
               hyper: "TrainHyper" = None) -> TrainState:
    """Pass the SAME `hyper` here and to `make_train_step`: the dynamic
    ADMM penalty `TrainState.rho` is seeded from `hyper.rho` (the live
    value is the state, not the hyper — residual balancing moves it when
    `hyper.adaptive_rho`)."""
    hyper = hyper if hyper is not None else TrainHyper()
    params = model_lib.init_params(cfg, key)
    if dp_mode != "allreduce":
        params = jax.tree.map(
            lambda p: jnp.broadcast_to(p[None], (n_replicas,) + p.shape),
            params)
    opt = adamw.init(params)
    duals = consensus.admm_init_duals(params) if dp_mode == "admm" else None
    rho_state = (jnp.asarray(hyper.rho, jnp.float32) if dp_mode == "admm"
                 else None)
    return TrainState(params=params, opt=opt, duals=duals,
                      step=jnp.zeros((), jnp.int32), rho=rho_state)


# ---------------------------------------------------------------------------
# Sharding trees
# ---------------------------------------------------------------------------
def state_shardings(state_like, cfg: ModelConfig, mesh: Mesh, *,
                    dp_mode: str, consensus_axis: Optional[str]):
    replica = consensus_axis if dp_mode != "allreduce" else None
    scanned = model_lib._homogeneous(cfg)
    # consensus modes: per-replica parameters shard over "model" only.
    # (fsdp inside a replica trips an XLA SPMD-partitioner CHECK on the
    # embedding gather under partial-manual shard_map; and with
    # replica=data the data axis is consumed by replication anyway.)
    fsdp = cfg.fsdp and replica is None

    no_fsdp = ("moe",) if cfg.moe_local_dispatch else ()

    def spec_params(tree):
        return sharding.param_shardings(tree, mesh, fsdp=fsdp,
                                        scanned=scanned, replica_axis=replica,
                                        no_fsdp_keys=no_fsdp)

    rep0 = NamedSharding(mesh, P())
    rep_r = NamedSharding(mesh, P(replica)) if replica else rep0
    return TrainState(
        params=spec_params(state_like.params),
        opt=adamw.AdamState(mu=spec_params(state_like.opt.mu),
                            nu=spec_params(state_like.opt.nu),
                            count=rep0),
        duals=(spec_params(state_like.duals)
               if state_like.duals is not None else None),
        step=rep0,
        rho=rep0 if state_like.rho is not None else None,
    )


def batch_sharding(mesh: Mesh):
    return NamedSharding(mesh, sharding.batch_spec(mesh))


# ---------------------------------------------------------------------------
# Step factories
# ---------------------------------------------------------------------------
def make_train_step(cfg: ModelConfig, mesh: Mesh, *, dp_mode: str = "allreduce",
                    consensus_axis: Optional[str] = None,
                    hyper: TrainHyper = TrainHyper(),
                    use_kernels: bool = False):
    """Returns a (state, batch) -> (state, metrics) function (not yet jitted;
    launch/dryrun wraps it with jit + shardings)."""
    if dp_mode == "allreduce":
        return _allreduce_step(cfg, hyper, use_kernels)
    assert consensus_axis is not None
    return _consensus_step(cfg, mesh, dp_mode, consensus_axis, hyper,
                           use_kernels)


def _local_update(cfg, hyper, use_kernels, params, opt, batch, step):
    lr = schedules.cosine_warmup(step, peak_lr=hyper.peak_lr,
                                 warmup=hyper.warmup,
                                 total=hyper.total_steps)
    (loss, aux), grads = jax.value_and_grad(
        functools.partial(loss_fn, cfg, use_kernels=use_kernels),
        has_aux=True)(params, batch)
    grads, gnorm = adamw.clip_by_global_norm(grads, hyper.clip_norm)
    new_params, new_opt = adamw.update(
        grads, opt, params, lr=lr, weight_decay=hyper.weight_decay)
    metrics = {"loss": loss, "ce": aux["ce"], "grad_norm": gnorm, "lr": lr}
    return new_params, new_opt, metrics


def _allreduce_step(cfg, hyper, use_kernels):
    def step_fn(state: TrainState, batch):
        new_params, new_opt, metrics = _local_update(
            cfg, hyper, use_kernels, state.params, state.opt, batch,
            state.step)
        return TrainState(new_params, new_opt, None, state.step + 1), metrics

    return step_fn


def _consensus_step(cfg, mesh: Mesh, dp_mode: str, axis: str, hyper,
                    use_kernels):
    is_admm = dp_mode == "admm"

    def inner(params, opt, duals, step, rho, batch):
        # strip the per-replica leading axis (size 1 in this shard)
        params_l = jax.tree.map(lambda p: p[0], params)
        opt_l = adamw.AdamState(mu=jax.tree.map(lambda p: p[0], opt.mu),
                                nu=jax.tree.map(lambda p: p[0], opt.nu),
                                count=opt.count)
        # local stochastic step on local data (no consensus-axis psum!)
        p_star, new_opt, metrics = _local_update(
            cfg, hyper, use_kernels, params_l, opt_l, batch, step)
        if dp_mode == "diffusion":
            p_new = consensus.diffusion_combine(p_star, axis, hyper.w_self)
            d_new = None
            rho_new = rho
            r_norm = s_norm = jnp.zeros((), jnp.float32)
        else:
            kap = schedules.kappa(step.astype(jnp.float32) + 1.0, hyper.xi)
            duals_l = jax.tree.map(lambda p: p[0], duals)
            # residual norms ride along on the dual update's own ring
            # exchange — the same primal/dual residuals the VB engine
            # records in ConsensusDiagnostics; with `adaptive_rho` they
            # residual-balance the DYNAMIC TrainState.rho between steps
            # (the engine's Boyd Sec. 3.4.1 rule via consensus.adapt_rho)
            p_new, d_new, (r_norm, s_norm) = consensus.admm_step(
                p_star, params_l, duals_l, axis, rho=rho, kappa=kap,
                return_residuals=True)
            d_new = jax.tree.map(lambda p: p[None], d_new)
            if hyper.adaptive_rho:
                rho_new = consensus.adapt_rho(rho, r_norm, s_norm,
                                              mu=hyper.rho_mu)
            else:
                rho_new = rho
        metrics = {k: jax.lax.pmean(v, axis) for k, v in metrics.items()}
        metrics["consensus_residual"] = consensus.consensus_residual(
            p_new, axis)
        metrics["admm_primal_resid"] = r_norm
        metrics["admm_dual_resid"] = s_norm
        metrics["admm_rho"] = (rho_new if is_admm
                               else jnp.zeros((), jnp.float32))
        p_new = jax.tree.map(lambda p: p[None], p_new)
        new_opt = adamw.AdamState(
            mu=jax.tree.map(lambda p: p[None], new_opt.mu),
            nu=jax.tree.map(lambda p: p[None], new_opt.nu),
            count=new_opt.count)
        return p_new, new_opt, d_new, rho_new, metrics

    def step_fn(state: TrainState, batch):
        lead = P(axis)
        rep = P()

        def leaf_specs(tree, spec):
            return jax.tree.map(lambda _: spec, tree)

        rho_in = (state.rho if state.rho is not None
                  else jnp.zeros((), jnp.float32))
        in_specs = (
            leaf_specs(state.params, lead),
            adamw.AdamState(mu=leaf_specs(state.opt.mu, lead),
                            nu=leaf_specs(state.opt.nu, lead), count=rep),
            (leaf_specs(state.duals, lead)
             if state.duals is not None else None),
            rep,
            rep,
            leaf_specs(batch, lead),
        )
        out_specs = (in_specs[0], in_specs[1], in_specs[2], rep,
                     leaf_specs({"loss": 0, "ce": 0, "grad_norm": 0, "lr": 0,
                                 "consensus_residual": 0,
                                 "admm_primal_resid": 0,
                                 "admm_dual_resid": 0,
                                 "admm_rho": 0}, rep))
        # partial-manual: the "model" axis stays auto (GSPMD)
        fn = compat.shard_map(inner, mesh=mesh, axis_names={axis},
                              in_specs=in_specs, out_specs=out_specs,
                              check_vma=False)
        p, o, d, rho_new, metrics = fn(state.params, state.opt, state.duals,
                                       state.step, rho_in, batch)
        return TrainState(p, o, d, state.step + 1,
                          rho_new if state.rho is not None else None), \
            metrics

    return step_fn
