"""Mixture-of-Experts FFN with sort-free capacity dispatch.

TPU-native dispatch: instead of the (T, E, C) one-hot einsum (quadratic
FLOPs in tokens) or a ragged all_to_all, tokens are placed into a static
(E * C, d) buffer via scatter and read back via gather — zero matmul FLOPs
for routing, static shapes, drop-on-overflow semantics (capacity_factor).
Expert FFNs are batched einsums over the leading expert axis, so the d_ff
dimension shards over the mesh "model" axis for every assigned config
(including E values like 40 that don't divide the axis).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers


def moe_params(key, cfg: ModelConfig, dtype):
    kr, k1, k2, k3 = jax.random.split(key, 4)
    E, d, f = cfg.n_experts, cfg.d_model, cfg.d_ff
    return {
        "router": layers.dense_init(kr, (d, E), 0, jnp.float32),
        "wi": layers.dense_init(k1, (E, d, f), 1, dtype),
        "wg": layers.dense_init(k2, (E, d, f), 1, dtype),
        "wo": layers.dense_init(k3, (E, f, d), 1, dtype),
    }


def moe_block(x: jnp.ndarray, p, cfg: ModelConfig):
    """x (B, S, d) -> (out (B, S, d), aux_loss scalar).

    With cfg.moe_local_dispatch and an ambient mesh, routing + the capacity
    scatter/gather run per data shard under shard_map (per-shard capacity,
    zero cross-shard dispatch traffic); expert FFN weights stay
    model-sharded via the auto axes.
    """
    from repro.dist import compat
    mesh = compat.current_mesh()
    if cfg.moe_local_dispatch and mesh is not None:
        import functools
        from jax.sharding import PartitionSpec as P
        sizes = compat.auto_axis_sizes()
        axes = tuple(a for a in ("pod", "data")
                     if sizes.get(a, 1) > 1
                     and x.shape[0] % sizes[a] == 0)
        # local dispatch leaves the expert weights on auto (GSPMD) axes:
        # a partial-manual shard_map
        if axes:
            fn = compat.shard_map(
                functools.partial(_moe_dispatch, cfg=cfg,
                                  axis_names=axes),
                mesh=mesh, axis_names=set(axes),
                in_specs=(P(axes), P()), out_specs=(P(axes), P()),
                check_vma=False)
            return fn(x, p)
    return _moe_dispatch(x, p, cfg=cfg, axis_names=())


def _moe_dispatch(x, p, *, cfg: ModelConfig, axis_names=()):
    B, S, d = x.shape
    T = B * S
    E, k = cfg.n_experts, cfg.experts_per_token
    cap = max(1, int(T * k / E * cfg.capacity_factor))
    xt = x.reshape(T, d)

    logits = (xt.astype(jnp.float32) @ p["router"])              # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, k)              # (T, k)
    gate_vals = gate_vals / jnp.sum(gate_vals, -1, keepdims=True)

    # position of each (token, slot) within its expert's capacity buffer.
    # Two-level blocked cumsum: a single (T*k, E) cumsum is costed (and on
    # some backends executed) as an O(n^2) reduce-window; block-local scans
    # + a tiny scan over block totals is O(n * blk) with identical results
    # (§Perf: granite-moe train_4k Tc dropped ~50x with this).
    flat_e = expert_idx.reshape(T * k)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)          # (T*k, E)
    blk = 1024
    n = T * k
    nb = (n + blk - 1) // blk
    pad = nb * blk - n
    oh = jnp.pad(onehot, ((0, pad), (0, 0))).reshape(nb, blk, E)
    local = jnp.cumsum(oh, axis=1)                               # in-block
    block_tot = local[:, -1, :]                                  # (nb, E)
    offsets = jnp.cumsum(block_tot, axis=0) - block_tot          # exclusive
    pos = (local - oh + offsets[:, None, :]).reshape(nb * blk, E)[:n]
    pos = jnp.take_along_axis(pos, flat_e[:, None], axis=1)[:, 0]
    keep = pos < cap
    dest = jnp.where(keep, flat_e * cap + pos, E * cap)          # drop slot

    # scatter tokens into the (E*C, d) buffer (duplicated per chosen expert)
    src = jnp.repeat(xt, k, axis=0)                              # (T*k, d)
    buf = jnp.zeros((E * cap + 1, d), x.dtype).at[dest].set(src)
    xe = buf[: E * cap].reshape(E, cap, d)

    # expert FFN (SwiGLU), batched over experts; f shards over "model"
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, p["wg"])) * \
        jnp.einsum("ecd,edf->ecf", xe, p["wi"])
    ye = jnp.einsum("ecf,efd->ecd", h, p["wo"])                  # (E, C, d)

    # gather back and mix with gate values
    ybuf = jnp.concatenate(
        [ye.reshape(E * cap, d), jnp.zeros((1, d), ye.dtype)], 0)
    yslots = ybuf[dest].reshape(T, k, d)
    gates = (gate_vals * keep.reshape(T, k)).astype(x.dtype)
    out = jnp.einsum("tkd,tk->td", yslots, gates).reshape(B, S, d)

    # load-balancing auxiliary loss (Switch-style)
    frac_tokens = jnp.mean(
        jax.nn.one_hot(expert_idx, E, dtype=jnp.float32), axis=(0, 1))
    mean_probs = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(frac_tokens * mean_probs)
    for a in axis_names:                       # local-dispatch mode
        aux = jax.lax.pmean(aux, a)
    return out, aux
