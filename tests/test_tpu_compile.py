"""Compiles for a described TPU v5e, with no chip attached.

The TPU compiler is installed with jaxlib, and compiles for a topology
that is only described.  These tests compile the main path the way the
chip would: the fused VBE kernel as Mosaic at the paper's real-data widths
(no interpreter), and one slice of the engine on each backend in the chip
precision (x64 off, see `repro.runtime.use_platform_precision`).  Nothing
runs, so they check that the chip's compiler accepts the programs, not
their numbers.

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import runtime
from repro.core import engine, expfam, network
from repro.core import model as model_lib
from repro.data import synthetic
from repro.kernels import gmm_estep as ge
from repro.kernels import ops


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _chip_precision_no_cache(monkeypatch):
    """The precision `runtime.use_platform_precision` picks on a TPU, and
    no persistent compilation cache: a compile for a described device can
    be written to the cache but not read back without the chip."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    saved = {k: getattr(jax.config, k) for k in (
        "jax_enable_x64", "jax_default_matmul_precision",
        "jax_enable_compilation_cache")}
    with monkeypatch.context() as m:
        m.setattr(jax, "default_backend", lambda: "tpu")
        assert runtime.use_platform_precision() == jnp.float32
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    for k, v in saved.items():
        jax.config.update(k, v)
    cc.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("N,T,D,K,dtype", [
    (50, 100, 2, 3, jnp.float32),        # the paper's synthetic instance
    (50, 4096, 34, 20, jnp.float32),     # ionosphere width
    (50, 4096, 52, 20, jnp.float32),     # COIL-20 width
    (50, 4096, 52, 20, jnp.bfloat16),    # bf16 data streaming
])
def test_gmm_estep_kernel_compiles_for_v5e(one_chip, N, T, D, K, dtype):
    args = (_sds((N, T, D), dtype, one_chip), _sds((N, T), dtype, one_chip),
            _sds((N, K), jnp.float32, one_chip),
            _sds((N, K, D, D), jnp.float32, one_chip),
            _sds((N, K, D), jnp.float32, one_chip),
            _sds((N, K), jnp.float32, one_chip))

    def stats(*a):
        return ge.gmm_estep_nodes(*a, interpret=False, return_r=False,
                                  replication=float(N))[1:]

    compiled = jax.jit(stats).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # the kernel's matmuls carry the chip precision (full f32 products)
    assert "Precision.HIGHEST" in str(jax.make_jaxpr(stats)(*args))


def _paper_state(backend):
    K, D, N = 3, 2, 50
    data = synthetic.paper_synthetic(n_nodes=N, n_per_node=100, seed=0)
    adj, _ = network.random_geometric_graph(N, seed=0)
    prior = expfam.noninformative_prior(K, D, beta0=0.1, w0_scale=10.0)
    mdl = model_lib.GMMModel(prior, K, D)
    return engine.vb_init(mdl, (data.x, data.mask),
                          engine.ADMMConsensus(adj, adaptive_rho=True),
                          backend=backend)


def _compile_slice(state, sharding, n_iters=4):
    """Compile `vb_run` for `n_iters` iterations with the state on the
    described chip (the session's data is closed over)."""
    args = jax.tree_util.tree_map(
        lambda a: _sds(a.shape, a.dtype, sharding), state)
    return jax.jit(lambda s: engine.vb_run(s, n_iters)[0]).lower(
        args).compile()


def test_reference_slice_compiles_in_chip_precision(one_chip):
    state = _paper_state("reference")
    assert state.phi.dtype == jnp.float32
    text = _compile_slice(state, one_chip).as_text()
    assert "f64" not in text


def test_fused_slice_compiles_with_mosaic_kernel(one_chip, monkeypatch):
    # jax.default_backend() is the CPU here, so kernels/ops.py would pick
    # interpret mode; steer it to the chip's choice for this compile
    monkeypatch.setattr(ops, "_default_interpret", lambda: False)
    jax.clear_caches()
    try:
        text = _compile_slice(_paper_state("fused"), one_chip).as_text()
    finally:
        jax.clear_caches()
    assert "tpu_custom_call" in text
    assert "f64" not in text
