"""Per-kernel shape/dtype sweeps asserting allclose against ref.py oracles
(interpret mode executes the TPU kernel bodies in python on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# flash_attention
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("B,S,Hq,Hkv,hd", [
    (2, 64, 4, 2, 32),
    (1, 128, 2, 1, 64),
    (2, 96, 4, 4, 16),      # S not a multiple of block -> padding path
    (1, 256, 8, 2, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("window", [0, 32])
def test_flash_attention_sweep(B, S, Hq, Hkv, hd, dtype, window):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, Hq, hd), dtype)
    k = jax.random.normal(ks[1], (B, S, Hkv, hd), dtype)
    v = jax.random.normal(ks[2], (B, S, Hkv, hd), dtype)
    out = ops.flash_attention(q, k, v, window=window)
    g = Hq // Hkv
    qr = jnp.moveaxis(q, 2, 1)
    kr = jnp.moveaxis(jnp.repeat(k, g, 2), 2, 1)
    vr = jnp.moveaxis(jnp.repeat(v, g, 2), 2, 1)
    want = jnp.moveaxis(ref.attention(qr, kr, vr, window=window), 1, 2)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=tol)


def test_flash_attention_causality():
    """Future tokens must not influence output (hard property)."""
    ks = jax.random.split(KEY, 3)
    B, S, H, hd = 1, 64, 2, 32
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, H, hd))
    v = jax.random.normal(ks[2], (B, S, H, hd))
    out1 = ops.flash_attention(q, k, v)
    k2 = k.at[:, S // 2:].set(99.0)
    v2 = v.at[:, S // 2:].set(-99.0)
    out2 = ops.flash_attention(q, k2, v2)
    np.testing.assert_allclose(out1[:, :S // 2], out2[:, :S // 2], atol=1e-5)


# ---------------------------------------------------------------------------
# ssd_scan
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("B,S,H,P,N,chunk", [
    (2, 64, 4, 16, 8, 16),
    (1, 128, 2, 32, 16, 32),
    (2, 64, 2, 8, 4, 64),    # single chunk
    (1, 96, 3, 16, 8, 32),   # 3 chunks
])
def test_ssd_scan_sweep(B, S, H, P, N, chunk):
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.5)
    Bm = jax.random.normal(ks[3], (B, S, N)) * 0.3
    Cm = jax.random.normal(ks[4], (B, S, N)) * 0.3
    y, h = ops.ssd_scan(x, dt, A, Bm, Cm, chunk=chunk)
    yr, hr = ref.ssd(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(y, yr, atol=5e-5)
    np.testing.assert_allclose(h, hr, atol=5e-5)


def test_ssd_matches_model_chunked():
    """Kernel == the model's pure-jnp chunked path (mamba2.ssd_chunked)."""
    from repro.models.mamba2 import ssd_chunked
    ks = jax.random.split(KEY, 5)
    B, S, H, P, N = 2, 128, 4, 16, 8
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.5)
    Bm = jax.random.normal(ks[3], (B, S, N)) * 0.3
    Cm = jax.random.normal(ks[4], (B, S, N)) * 0.3
    y1, h1 = ops.ssd_scan(x, dt, A, Bm, Cm, chunk=32)
    y2, h2 = ssd_chunked(x, dt, A, Bm, Cm, 32)
    np.testing.assert_allclose(y1, y2, atol=1e-5)
    np.testing.assert_allclose(h1, h2, atol=1e-5)


# ---------------------------------------------------------------------------
# gmm_estep
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("T,K,D,block", [
    (100, 3, 2, 32),
    (257, 4, 5, 64),        # padding path
    (64, 2, 8, 64),
    (500, 6, 3, 128),
])
def test_gmm_estep_sweep(T, K, D, block):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(T, D)) * 2, jnp.float32)
    mask = jnp.asarray((rng.random(T) > 0.1), jnp.float32)
    log_prior = jnp.asarray(rng.normal(size=K), jnp.float32)
    A = rng.normal(size=(K, D, D)) * 0.3
    Wn = jnp.asarray(np.einsum("kij,klj->kil", A, A) + np.eye(D),
                     jnp.float32)
    b = jnp.asarray(rng.normal(size=(K, D)), jnp.float32)
    c = jnp.asarray(rng.uniform(1, 3, K), jnp.float32)
    r, R, sx, sxx = ops.gmm_estep(x, mask, log_prior, Wn, b, c,
                                  block_t=block)
    rr, RR, sxr, sxxr = ref.gmm_estep(x, mask, log_prior, Wn, b, c)
    np.testing.assert_allclose(r, rr, atol=2e-5)
    np.testing.assert_allclose(R, RR, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(sx, sxr, rtol=1e-4, atol=5e-4)
    np.testing.assert_allclose(sxx, sxxr, rtol=1e-3, atol=5e-3)


def test_gmm_estep_matches_core_vbe():
    """Fused kernel == repro.core.gmm VBE path on a real posterior."""
    from repro.core import expfam, gmm
    rng = np.random.default_rng(1)
    K, D = 3, 4
    q = expfam.noninformative_prior(K, D, dtype=jnp.float32)
    q = q._replace(m=jnp.asarray(rng.normal(size=(K, D)), jnp.float32),
                   nu=jnp.asarray([6.0, 7.0, 8.0], jnp.float32))
    x = jnp.asarray(rng.normal(size=(200, D)) * 2, jnp.float32)
    mask = jnp.ones((200,), jnp.float32)
    r, R, sx, sxx = ops.gmm_estep_from_posterior(x, mask, q)
    r2 = gmm.responsibilities(x, q, mask)
    st = gmm.sufficient_stats(x, r2, 1.0)
    np.testing.assert_allclose(r, r2, atol=3e-5)
    np.testing.assert_allclose(R, st.R, rtol=1e-4)
    np.testing.assert_allclose(sxx, st.sum_xx, rtol=1e-3, atol=1e-3)


def _gmm_node_args(N, T, K, D, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(N, T, D)) * 2, jnp.float32)
    mask = jnp.asarray(rng.random((N, T)) > 0.2, jnp.float32)
    lp = jnp.asarray(rng.normal(size=(N, K)), jnp.float32)
    A = rng.normal(size=(N, K, D, D)) * 0.3
    Wn = jnp.asarray(np.einsum("nkij,nklj->nkil", A, A) + np.eye(D),
                     jnp.float32)
    b = jnp.asarray(rng.normal(size=(N, K, D)), jnp.float32)
    c = jnp.asarray(rng.uniform(1, 3, (N, K)), jnp.float32)
    return x, mask, lp, Wn, b, c


def test_gmm_estep_nodes_large_k_parity():
    """K=32 (the ROADMAP large-K case): the rolled-loop kernel must match
    the oracle just like the small-K sweeps."""
    args = _gmm_node_args(N=3, T=96, K=32, D=3)
    r, R, sx, sxx = ops.gmm_estep_nodes(*args, block_t=32)
    rr, RR, sxr, sxxr = ref.gmm_estep_nodes(*args)
    np.testing.assert_allclose(r, rr, atol=3e-5)
    np.testing.assert_allclose(R, RR, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(sx, sxr, rtol=1e-4, atol=5e-4)
    np.testing.assert_allclose(sxx, sxxr, rtol=1e-3, atol=5e-3)


def test_gmm_estep_kernel_replication_scaling():
    """Kernel-side replication: stats scale by the factor, r does not."""
    args = _gmm_node_args(N=2, T=50, K=3, D=2)
    from repro.kernels import gmm_estep as ge
    r1, R1, sx1, sxx1 = ge.gmm_estep_nodes(*args, replication=1.0,
                                           interpret=True)
    r8, R8, sx8, sxx8 = ge.gmm_estep_nodes(*args, replication=8.0,
                                           interpret=True)
    np.testing.assert_array_equal(np.asarray(r1), np.asarray(r8))
    np.testing.assert_allclose(np.asarray(R8), 8.0 * np.asarray(R1),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(sxx8), 8.0 * np.asarray(sxx1),
                               rtol=1e-6)


def _count_eqns(jaxpr) -> int:
    n = 0
    for eqn in jaxpr.eqns:
        n += 1
        for v in eqn.params.values():
            if hasattr(v, "jaxpr"):          # ClosedJaxpr
                n += _count_eqns(v.jaxpr)
            elif hasattr(v, "eqns"):         # Jaxpr
                n += _count_eqns(v)
    return n


def test_gmm_estep_trace_size_constant_in_k():
    """Compile-time regression (ROADMAP: unrolled per-component matmuls
    blew up compile time past K~16): the kernel's program must be the SAME
    SIZE at K=32 as at K=4 — the per-component work is a rolled fori_loop,
    so trace/lowering cost is O(1) in K."""
    from repro.kernels import gmm_estep as ge

    def size_at(K):
        args = _gmm_node_args(N=2, T=64, K=K, D=3)
        jaxpr = jax.make_jaxpr(
            lambda *a: ge.gmm_estep_nodes(*a, block_t=32, interpret=True,
                                          return_r=False))(*args)
        return _count_eqns(jaxpr.jaxpr)

    small, large = size_at(4), size_at(32)
    assert large == small, (small, large)
