"""Inputs of a run: sensor data and each session's starting point, made
from `--seed`, and the deployment's network graph, made from its own seed.

Everything here belongs to the benchmark, not to the program: the
generators follow the paper's descriptions (Hua & Li, arXiv:2011.13600,
Sec. V-A and the COIL-20 experiment) and make their arrays on the device,
one jitted call per shape, in the dtype the sessions run in.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp

from bench.reference import gmm_vb as ref

# Sec. V-A ground truth: three 2-D Gaussian components
PAPER_MU = np.array([[1.5, 3.5], [4.0, 4.0], [6.5, 4.5]])
PAPER_SIGMA = np.array([[[0.6, 0.4], [0.4, 0.6]],
                        [[0.6, -0.4], [-0.4, 0.6]],
                        [[0.6, 0.4], [0.4, 0.6]]])


def key(seed: int, *salt: int):
    """A JAX key from a seed of any size (seeds may exceed 32 bits)."""
    words = np.random.SeedSequence([int(seed), *salt]).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


def rng(seed: int, *salt: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), *salt])


@functools.partial(jax.jit, static_argnames=("count", "n_nodes", "n_points",
                                             "free_slots", "dtype"))
def paper_pool(k, *, count, n_nodes, n_points, free_slots, dtype):
    """`count` Sec. V-A datasets: (count, N, n_points, 2) points and
    masks.  Nodes in the first 30% draw 80% from component 1, the next
    40% draw 90% from component 2, the rest 60% from component 3 (the
    imbalanced allocation).  The last `free_slots` slots of every node are
    left free (mask 0), as a serving request leaves room for new data."""
    a, b = round(0.3 * n_nodes), round(0.7 * n_nodes)
    node = np.arange(n_nodes)
    probs = np.where((node < a)[:, None], [0.8, 0.1, 0.1],
                     np.where((node < b)[:, None], [0.05, 0.9, 0.05],
                              [0.2, 0.2, 0.6]))
    k_lab, k_z = jax.random.split(k)
    shape = (count, n_nodes, n_points)
    lab = jax.random.categorical(k_lab, jnp.log(jnp.asarray(probs))[None, :,
                                                                    None, :],
                                 shape=shape)
    chol = jnp.asarray(np.linalg.cholesky(PAPER_SIGMA))
    z = jax.random.normal(k_z, shape + (2,))
    x = (jnp.asarray(PAPER_MU)[lab]
         + jnp.einsum("...ij,...j->...i", chol[lab], z))
    mask = jnp.broadcast_to(
        (jnp.arange(n_points) < n_points - free_slots).astype(dtype), shape)
    return x.astype(dtype), mask


@functools.partial(jax.jit, static_argnames=("count", "n_nodes", "n_points",
                                             "K", "D", "dtype"))
def coil20_pool(k, *, count, n_nodes, n_points, K, D, dtype):
    """`count` datasets shaped like COIL-20 after PCA: K classes in D
    dims, each an elongated low-rank cluster (a turntable rotation sweep:
    centre ~ N(0, 2.2^2), a rank-4 basis ~ N(0, 0.9^2), noise 0.25),
    with equal class counts shuffled and dealt evenly to the nodes.
    Returns points, masks and the (count, K, D) class centres."""
    def one(k):
        kc, kb, kp, kt, kn = jax.random.split(k, 5)
        centre = 2.2 * jax.random.normal(kc, (K, D))
        basis = 0.9 * jax.random.normal(kb, (K, D, 4))
        total = n_nodes * n_points
        lab = jax.random.permutation(kp, jnp.arange(total) % K)
        t = jax.random.normal(kt, (total, 4))
        x = (centre[lab] + jnp.einsum("ndr,nr->nd", basis[lab], t)
             + 0.25 * jax.random.normal(kn, (total, D)))
        return x.reshape(n_nodes, n_points, D).astype(dtype), centre

    x, centres = jax.vmap(one)(jax.random.split(k, count))
    return x, jnp.ones(x.shape[:3], dtype), centres.astype(dtype)


@functools.partial(jax.jit, static_argnames=("K", "D"))
def centred_init(k, centres, prior, noise, *, K, D):
    """The prior with mean k at class centre k plus N(0, noise^2) (a warm
    start, one component to a class), packed as the Eq. 45 message."""
    m = centres + noise * jax.random.normal(k, (K, D), centres.dtype)
    return ref.pack(prior["alpha"], m, prior["beta"], prior["W"],
                    prior["nu"])


@functools.partial(jax.jit, static_argnames=("K", "D"))
def perturbed_init(k, x, prior, *, K, D):
    """The prior with its K means drawn uniformly over the data's range
    (the paper's random restarts), packed as the Eq. 45 message."""
    flat = x.reshape(-1, D)
    lo, hi = jnp.min(flat, 0), jnp.max(flat, 0)
    m = lo + (hi - lo) * jax.random.uniform(k, (K, D), x.dtype)
    return ref.pack(prior["alpha"], prior["m"] + (m - prior["m"]),
                    prior["beta"], prior["W"], prior["nu"])


def noninformative_prior(K: int, D: int, *, beta0: float, w0_scale: float,
                         dtype) -> dict:
    """The paper's broad conjugate prior: alpha0 = 1, m0 = 0, beta0,
    W0 = w0_scale * I, nu0 = D."""
    return dict(alpha=jnp.ones((K,), dtype),
                m=jnp.zeros((K, D), dtype),
                beta=jnp.full((K,), beta0, dtype),
                W=jnp.broadcast_to(w0_scale * jnp.eye(D, dtype=dtype),
                                   (K, D, D)),
                nu=jnp.full((K,), float(D), dtype))


def geometric_graph(n_nodes: int, seed: int, *, side: float,
                    radius: float) -> np.ndarray:
    """A connected random geometric graph (the paper's 50 nodes in a
    3.5 x 3.5 square, radius 0.8): the (N, N) 0/1 adjacency."""
    g = rng(seed, 1)
    for _ in range(1000):
        pos = g.uniform(0.0, side, size=(n_nodes, 2))
        d2 = np.sum((pos[:, None] - pos[None]) ** 2, axis=-1)
        adj = (d2 <= radius * radius).astype(np.float64)
        np.fill_diagonal(adj, 0.0)
        seen, stack = {0}, [0]
        while stack:
            for j in np.nonzero(adj[stack.pop()])[0]:
                if j not in seen:
                    seen.add(j)
                    stack.append(j)
        if len(seen) == n_nodes:
            return adj
    raise RuntimeError(f"no connected geometric graph for N={n_nodes}")


def nearest_neighbor_weights(adj: np.ndarray) -> np.ndarray:
    """Eq. 47: w_ij = 1 / (|N_i| + 1) over N_i and i itself."""
    a = adj + np.eye(adj.shape[0])
    return a / a.sum(axis=1, keepdims=True)
