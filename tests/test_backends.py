"""Engine-level parity of the compute backends (core/backends.py).

`run_vb(..., backend="fused")` — the node-batched single-pass Pallas VBE
kernel + jitted VBM post-stage — must reproduce the reference einsum path
(core/gmm.py) across every topology, masked (ragged Ni) node data, both
executors, and the bf16-storage/f32-accum precision policy.  Everything
here runs in f32: that is the precision the fused kernel owns (the
acceptance bar is KL-trajectory agreement at rtol <= 1e-4 in f32).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import backends, engine, expfam, gmm, network, refperm
from repro.core import model as model_lib
from repro.data import synthetic
from repro.kernels import ops, ref

K, D, N_NODES, N_ITERS = 3, 2, 8, 25


@pytest.fixture(scope="module")
def setup():
    # ragged Ni: unequal per-node sample sizes -> zero-padded rows + mask
    data = synthetic.paper_synthetic(n_nodes=N_NODES, n_per_node=30, seed=9,
                                     unequal_sizes=True, imbalanced=False,
                                     dtype=np.float32)
    assert float(jnp.min(jnp.sum(data.mask, 1))) \
        < float(jnp.max(jnp.sum(data.mask, 1)))          # genuinely ragged
    prior = expfam.noninformative_prior(K, D, beta0=0.1, w0_scale=10.0,
                                        dtype=jnp.float32)
    adj, _ = network.random_geometric_graph(N_NODES, seed=4)
    adj = adj.astype(jnp.float32)
    W = network.nearest_neighbor_weights(adj).astype(jnp.float32)
    x_all, labels = data.flat
    ref_q = gmm.ground_truth_posterior(x_all, labels, prior, K)
    ref_phis = refperm.permuted_refs(ref_q)
    mdl = model_lib.GMMModel(prior, K, D)
    return data, prior, adj, W, ref_phis, mdl


def _topologies(adj, W):
    """The five estimators of the paper as (name, topology, run_vb kwargs)."""
    return [
        ("cvb", engine.FusionCenter(), dict(schedule=engine.ONE_SHOT)),
        ("noncoop", engine.Isolated(),
         dict(schedule=engine.ONE_SHOT, replication=1.0)),
        ("nsg_dvb", engine.Diffusion(W), dict(schedule=engine.ONE_SHOT)),
        ("dsvb", engine.Diffusion(W), dict(schedule=engine.Schedule())),
        ("dvb_admm", engine.ADMMConsensus(adj), {}),
    ]


@pytest.mark.parametrize("est", ["cvb", "noncoop", "nsg_dvb", "dsvb",
                                 "dvb_admm"])
def test_fused_matches_reference_all_estimators(setup, est):
    """KL trajectories + final phi: fused == reference, rtol 1e-4 in f32."""
    data, prior, adj, W, ref_phis, mdl = setup
    name, topo, kw = next(t for t in _topologies(adj, W) if t[0] == est)
    a = engine.run_vb(mdl, (data.x, data.mask), topo, n_iters=N_ITERS,
                      ref_phi=ref_phis, backend="reference", **kw)
    b = engine.run_vb(mdl, (data.x, data.mask), topo, n_iters=N_ITERS,
                      ref_phi=ref_phis, backend="fused", **kw)
    np.testing.assert_allclose(np.asarray(b.kl_mean), np.asarray(a.kl_mean),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(b.kl_nodes), np.asarray(a.kl_nodes),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(b.phi), np.asarray(a.phi),
                               rtol=2e-4, atol=2e-3)


def test_node_batched_kernel_matches_oracle():
    """gmm_estep_nodes == vmapped naive oracle on ragged masked data."""
    rng = np.random.default_rng(0)
    N, T, Kk, Dd = 5, 137, 4, 3
    x = jnp.asarray(rng.normal(size=(N, T, Dd)) * 2, jnp.float32)
    mask = jnp.asarray(rng.random((N, T)) > 0.2, jnp.float32)
    lp = jnp.asarray(rng.normal(size=(N, Kk)), jnp.float32)
    A = rng.normal(size=(N, Kk, Dd, Dd)) * 0.3
    Wn = jnp.asarray(np.einsum("nkij,nklj->nkil", A, A) + np.eye(Dd),
                     jnp.float32)
    b = jnp.asarray(rng.normal(size=(N, Kk, Dd)), jnp.float32)
    c = jnp.asarray(rng.uniform(1, 3, (N, Kk)), jnp.float32)
    r, R, sx, sxx = ops.gmm_estep_nodes(x, mask, lp, Wn, b, c, block_t=32)
    rr, RR, sxr, sxxr = ref.gmm_estep_nodes(x, mask, lp, Wn, b, c)
    np.testing.assert_allclose(r, rr, atol=2e-5)
    np.testing.assert_allclose(R, RR, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(sx, sxr, rtol=1e-4, atol=5e-4)
    np.testing.assert_allclose(sxx, sxxr, rtol=1e-3, atol=5e-3)


def _factorisation_shapes(jaxpr):
    """Operand shapes of every `lu` / `cholesky` in a jaxpr, sub-jaxprs
    (jit, scan, cond, pallas_call bodies) included."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in ("lu", "cholesky"):
            out.append(tuple(eqn.invars[0].aval.shape))
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else [v]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    out += _factorisation_shapes(sub)
    return out


@pytest.mark.parametrize("backend", ["reference", "fused"])
def test_local_optimum_factors_each_node_once(backend):
    """One local optimum factors each node's W^-1 once: unpack's
    factorisation gives W and log|W|, and the VBM post-stage packs the W^-1 it builds.  The
    only other factorisation is the prior's W0^-1, one (K, D, D) LU that
    every node shares."""
    Kk, Dd, n = 3, 4, 2
    prior = expfam.noninformative_prior(Kk, Dd, dtype=jnp.float32)
    phi = jnp.broadcast_to(expfam.pack_natural(prior),
                           (n, expfam.flat_dim(Kk, Dd)))
    x = jnp.ones((n, 16, Dd), jnp.float32)
    mask = jnp.ones((n, 16), jnp.float32)
    be = backends.resolve(backend)
    jaxpr = jax.make_jaxpr(
        lambda x, mask, phi: be.local_vbm_optimum_nodes(
            x, mask, phi, prior, 2.0, Kk, Dd))(x, mask, phi)
    shapes = _factorisation_shapes(jaxpr.jaxpr)
    assert [s for s in shapes if s[0] == n] == [(n, Kk, Dd, Dd)], shapes
    assert [s for s in shapes if s[0] != n] == [(Kk, Dd, Dd)], shapes


def test_bf16_storage_f32_accum(setup):
    """PrecisionPolicy(data_dtype=bf16): wire/stream dtype narrows, the
    f32-accumulated result stays within bf16-commensurate tolerance."""
    data, prior, adj, W, ref_phis, mdl = setup
    bf16 = backends.FusedBackend(
        precision=backends.PrecisionPolicy(data_dtype=jnp.bfloat16))
    a = engine.run_vb(mdl, (data.x, data.mask), engine.Diffusion(W),
                      n_iters=N_ITERS, ref_phi=ref_phis)
    b = engine.run_vb(mdl, (data.x, data.mask), engine.Diffusion(W),
                      n_iters=N_ITERS, ref_phi=ref_phis, backend=bf16)
    rel = np.max(np.abs(np.asarray(b.phi) - np.asarray(a.phi))
                 / (np.abs(np.asarray(a.phi)) + 1.0))
    assert rel < 3e-2, rel
    np.testing.assert_allclose(np.asarray(b.kl_mean), np.asarray(a.kl_mean),
                               rtol=5e-2, atol=5e-2)


def test_backend_selection_api(setup):
    """Resolution rules: names, instances, model- vs run-level, errors."""
    data, prior, adj, W, ref_phis, mdl = setup
    assert backends.resolve(None).name == "reference"
    assert backends.resolve("fused").name == "fused"
    fb = backends.FusedBackend(block_t=128)
    assert backends.resolve(fb) is fb
    with pytest.raises(ValueError, match="unknown backend"):
        backends.resolve("mosaic")
    # model-level selection == run-level override
    mdl_f = model_lib.GMMModel(prior, K, D, backend="fused")
    a = engine.run_vb(mdl_f, (data.x, data.mask), engine.Diffusion(W),
                      n_iters=5)
    b = engine.run_vb(mdl, (data.x, data.mask), engine.Diffusion(W),
                      n_iters=5, backend="fused")
    np.testing.assert_allclose(np.asarray(a.phi), np.asarray(b.phi))
    # LinRegModel: reference passes through, fused refuses
    lr = model_lib.LinRegModel(D=3)
    assert lr.with_backend("reference") is lr
    with pytest.raises(ValueError, match="no 'fused' compute backend"):
        lr.with_backend("fused")


def test_wrapper_backend_passthrough(setup):
    """algorithms.run_* accept backend= (static under their jit)."""
    from repro.core import algorithms
    data, prior, adj, W, ref_phis, mdl = setup
    a = algorithms.run_dsvb(data.x, data.mask, W, prior, n_iters=10,
                            K=K, D=D)
    b = algorithms.run_dsvb(data.x, data.mask, W, prior, n_iters=10,
                            K=K, D=D, backend="fused")
    np.testing.assert_allclose(np.asarray(b.phi), np.asarray(a.phi),
                               rtol=2e-4, atol=2e-3)


# ---------------------------------------------------------------------------
# Mesh executor x fused backend (subprocess: forced multi-device host)
# ---------------------------------------------------------------------------
CODE_MESH_FUSED = r"""
import jax
import jax.numpy as jnp
import numpy as np
from repro.core import backends, engine, expfam, network
from repro.core import model as model_lib
from repro.data import synthetic

K, D = 3, 2
data = synthetic.paper_synthetic(n_nodes=8, n_per_node=30, seed=9,
                                 unequal_sizes=True, imbalanced=False,
                                 dtype=np.float32)
prior = expfam.noninformative_prior(K, D, beta0=0.1, w0_scale=10.0,
                                    dtype=jnp.float32)
adj, _ = network.random_geometric_graph(8, seed=5)
adj = adj.astype(jnp.float32)
W = network.nearest_neighbor_weights(adj).astype(jnp.float32)
mesh = jax.make_mesh((4,), ("data",))
mexec = engine.MeshExecutor(mesh, "data")
mdl = model_lib.GMMModel(prior, K, D)

for name, topo, kw in [
    ("dsvb", engine.Diffusion(W), dict(schedule=engine.Schedule())),
    ("ring", engine.RingDiffusion(), dict(schedule=engine.Schedule())),
    ("admm", engine.ADMMConsensus(adj), {}),
    ("cvb", engine.FusionCenter(), dict(schedule=engine.ONE_SHOT)),
]:
    single = engine.run_vb(mdl, (data.x, data.mask), topo, n_iters=15,
                           backend="fused", **kw)
    sharded = engine.run_vb(mdl, (data.x, data.mask), topo, n_iters=15,
                            backend="fused", executor=mexec, **kw)
    reference = engine.run_vb(mdl, (data.x, data.mask), topo, n_iters=15,
                              backend="reference", executor=mexec, **kw)
    err = float(jnp.max(jnp.abs(single.phi - sharded.phi)
                        / (jnp.abs(single.phi) + 1.0)))
    assert err < 1e-5, f"{name} fused mesh-vs-single rel err {err}"
    err = float(jnp.max(jnp.abs(reference.phi - sharded.phi)
                        / (jnp.abs(reference.phi) + 1.0)))
    assert err < 1e-4, f"{name} mesh fused-vs-reference rel err {err}"
print("OK")
"""


def test_mesh_executor_fused_backend(subproc):
    out = subproc(CODE_MESH_FUSED, n_devices=4)
    assert "OK" in out
