"""Mesh-parallel faithfulness: sharded runners == single-array runners.

These need >1 device, so they run in a subprocess with host-platform
devices (conftest.run_subprocess) — the main pytest process keeps 1 device.
"""
import pytest


CODE_FAITHFUL = r"""
from repro import runtime
runtime.use_platform_precision()
import jax
from repro.core import expfam
import jax.numpy as jnp
from repro.core import algorithms, distributed, network
from repro.data import synthetic

data = synthetic.paper_synthetic(n_nodes=8, n_per_node=40, seed=1)
K, D = 3, 2
prior = expfam.noninformative_prior(K, D, beta0=0.1, w0_scale=10.0)
adj, _ = network.random_geometric_graph(8, seed=3)
W = network.nearest_neighbor_weights(adj)
mesh = jax.make_mesh((4,), ("data",))

phi = distributed.run_dsvb_sharded(mesh, data.x, data.mask, W, prior,
                                   n_iters=40, K=K, D=D)
ref = algorithms.run_dsvb(data.x, data.mask, W, prior, n_iters=40, K=K, D=D)
err = float(jnp.max(jnp.abs(phi - ref.phi)))
assert err < 1e-8, f"dsvb sharded err {err}"

phi = distributed.run_admm_sharded(mesh, data.x, data.mask, adj, prior,
                                   n_iters=40, K=K, D=D)
ref = algorithms.run_dvb_admm(data.x, data.mask, adj, prior, n_iters=40,
                              K=K, D=D)
err = float(jnp.max(jnp.abs(phi - ref.phi)))
assert err < 1e-8, f"admm sharded err {err}"

phi = distributed.run_dsvb_ring_sharded(mesh, data.x, data.mask, prior,
                                        n_iters=40, K=K, D=D)
Wr = network.nearest_neighbor_weights(network.ring_graph(8))
ref = algorithms.run_dsvb(data.x, data.mask, Wr, prior, n_iters=40, K=K, D=D)
err = float(jnp.max(jnp.abs(phi - ref.phi)))
assert err < 1e-8, f"ring sharded err {err}"
print("OK")
"""


def test_sharded_runners_match_dense(subproc):
    out = subproc(CODE_FAITHFUL, n_devices=4)
    assert "OK" in out


CODE_CONSENSUS = r"""
import jax, jax.numpy as jnp, numpy as np, functools
from jax.sharding import PartitionSpec as P
from repro.dist import compat
from repro.optim import consensus
from repro.core import network

mesh = jax.make_mesh((8,), ("data",))
n = 8
params = {"w": jnp.arange(8.0 * 3).reshape(8, 3),
          "b": jnp.linspace(0, 1, 8)[:, None] * jnp.ones((8, 2))}

@functools.partial(compat.shard_map, mesh=mesh, in_specs=P("data"),
                   out_specs=P("data"))
def combine(p):
    local = jax.tree.map(lambda a: a[0], p)
    out = consensus.diffusion_combine(local, "data")
    return jax.tree.map(lambda a: a[None], out)

got = combine(params)
W = np.asarray(network.nearest_neighbor_weights(network.ring_graph(8)))
for k in params:
    want = W @ np.asarray(params[k])
    np.testing.assert_allclose(np.asarray(got[k]), want, atol=1e-6)

# ADMM duals: lambda stays antisymmetric-aggregated => sum_i lambda_i == 0
@functools.partial(compat.shard_map, mesh=mesh,
                   in_specs=(P("data"), P("data")),
                   out_specs=(P("data"), P("data")))
def admm(p_star, p_prev):
    ps = jax.tree.map(lambda a: a[0], p_star)
    pp = jax.tree.map(lambda a: a[0], p_prev)
    duals = consensus.admm_init_duals(ps)
    pn, dn = consensus.admm_step(ps, pp, duals, "data", rho=0.5, kappa=1.0)
    return (jax.tree.map(lambda a: a[None], pn),
            jax.tree.map(lambda a: a[None], dn))

pn, dn = admm(params, params)
for k in params:
    s = np.asarray(dn[k]).sum(0)
    np.testing.assert_allclose(s, 0.0, atol=1e-5)
print("OK")
"""


def test_consensus_optim_ring_math(subproc):
    out = subproc(CODE_CONSENSUS, n_devices=8)
    assert "OK" in out


CODE_TRAIN_MODES = r"""
import jax, jax.numpy as jnp
from repro.configs.base import ModelConfig
from repro.dist import compat
from repro.training import train_step as ts

cfg = ModelConfig(name="tiny", arch_type="dense", n_layers=2, d_model=64,
                  n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=128,
                  param_dtype="float32", compute_dtype="float32")
mesh = jax.make_mesh((4, 2), ("data", "model"))
key = jax.random.PRNGKey(0)
batch = {"tokens": jax.random.randint(key, (8, 32), 0, 128)}
losses = {}
with compat.use_mesh(mesh):
    for mode in ["allreduce", "diffusion", "admm"]:
        axis = "data" if mode != "allreduce" else None
        state = ts.init_state(cfg, key, dp_mode=mode, n_replicas=4)
        shd = ts.state_shardings(state, cfg, mesh, dp_mode=mode,
                                 consensus_axis=axis)
        state = jax.device_put(state, shd)
        b = jax.device_put(batch, ts.batch_sharding(mesh))
        fn = jax.jit(ts.make_train_step(cfg, mesh, dp_mode=mode,
                                        consensus_axis=axis))
        for _ in range(3):
            state, m = fn(state, b)
        losses[mode] = float(m["loss"])
        if mode != "allreduce":
            assert float(m["consensus_residual"]) < 1e-6  # identical replicas
# same data, same init => initial dynamics nearly identical across modes
assert abs(losses["allreduce"] - losses["diffusion"]) < 0.05
assert abs(losses["allreduce"] - losses["admm"]) < 0.05
print("OK", losses)
"""


def test_train_modes_on_mesh(subproc):
    out = subproc(CODE_TRAIN_MODES, n_devices=8)
    assert "OK" in out


CODE_ADAPTIVE_RHO = r"""
import jax, jax.numpy as jnp
from repro.configs.base import ModelConfig
from repro.dist import compat
from repro.training import train_step as ts

cfg = ModelConfig(name="tiny", arch_type="dense", n_layers=2, d_model=64,
                  n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=128,
                  param_dtype="float32", compute_dtype="float32")
mesh = jax.make_mesh((4,), ("data",))
key = jax.random.PRNGKey(0)
batch = {"tokens": jax.random.randint(key, (8, 32), 0, 128)}
# rho_mu < 1 makes the balancing rule fire on ANY nonzero residual
# imbalance, so the adaptation is observable within a few steps
hyper = ts.TrainHyper(adaptive_rho=True, rho_mu=0.5, rho=0.5)
with compat.use_mesh(mesh):
    state = ts.init_state(cfg, key, dp_mode="admm", n_replicas=4,
                          hyper=hyper)
    assert state.rho is not None and float(state.rho) == 0.5
    shd = ts.state_shardings(state, cfg, mesh, dp_mode="admm",
                             consensus_axis="data")
    state = jax.device_put(state, shd)
    b = jax.device_put(batch, ts.batch_sharding(mesh))
    fn = jax.jit(ts.make_train_step(cfg, mesh, dp_mode="admm",
                                    consensus_axis="data", hyper=hyper))
    rhos = [float(state.rho)]
    for _ in range(4):
        state, m = fn(state, b)
        rhos.append(float(state.rho))
        assert float(m["admm_rho"]) == rhos[-1]
# rho is DYNAMIC state: the balancing rule moved it across steps
assert any(r != rhos[0] for r in rhos[1:]), rhos

# without adaptive_rho the dynamic rho must stay put
with compat.use_mesh(mesh):
    hyper2 = ts.TrainHyper(rho=0.7)
    state = ts.init_state(cfg, key, dp_mode="admm", n_replicas=4,
                          hyper=hyper2)
    shd = ts.state_shardings(state, cfg, mesh, dp_mode="admm",
                             consensus_axis="data")
    state = jax.device_put(state, shd)
    b = jax.device_put(batch, ts.batch_sharding(mesh))
    fn = jax.jit(ts.make_train_step(cfg, mesh, dp_mode="admm",
                                    consensus_axis="data", hyper=hyper2))
    rho0 = float(state.rho)
    for _ in range(3):
        state, m = fn(state, b)
    assert float(state.rho) == rho0, (float(state.rho), rho0)
# non-ADMM modes carry no rho state
state = ts.init_state(cfg, key, dp_mode="diffusion", n_replicas=4)
assert state.rho is None
print("OK", rhos)
"""


def test_admm_adaptive_rho_is_dynamic_state(subproc):
    out = subproc(CODE_ADAPTIVE_RHO, n_devices=4)
    assert "OK" in out


CODE_SHARDING_RULES = r"""
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.dist import sharding

mesh = jax.make_mesh((4, 2), ("data", "model"))
# model axis picks a divisible dim; fsdp picks another
s = sharding.spec_for((64, 32), mesh, fsdp=True)
assert "model" in s and "data" in s, s
# indivisible dims replicate
s = sharding.spec_for((7, 5), mesh, fsdp=True)
assert s == P(None, None), s
# scan axis never sharded
s = sharding.spec_for((10, 64, 32), mesh, fsdp=False, n_scan_axes=1)
assert s[0] is None, s
# replica axis leads
s = sharding.spec_for((4, 64, 32), mesh, fsdp=False, replica_axis="data")
assert s[0] == "data", s
print("OK")
"""


def test_sharding_rules(subproc):
    out = subproc(CODE_SHARDING_RULES, n_devices=8)
    assert "OK" in out
