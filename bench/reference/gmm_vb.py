"""Plain reference of a served VB session: a Bayesian Gaussian mixture over
a sensor network (Hua & Li, arXiv:2011.13600).

Written from the paper and imports nothing of the program.  Every node
runs the VBE step and its local VBM optimum (Eqs. 17a and 18; Bishop
10.46-10.63 with the Appendix-A replication of every count by the number
of nodes).  The VBE step's quadratic form is expanded,
x' (nu W) x - 2 x' (nu W m) + D/beta + nu m' W m, as the fused kernel
computes it: large terms cancel there, which is where a lower matmul
precision loses most.  The nodes then combine by one of two rules:

* dSVB (Algorithm 1): the natural-gradient step (Eq. 27a) with
  eta_t = 1 / (d0 + tau (t + 1)), then the diffusion combine (Eq. 27b).
* dVB-ADMM (Algorithm 2, Eqs. 38-40) with the adaptive-penalty rules a
  session states: residual balancing of rho every 10 active iterations
  (mu = 10, factors 2), a dual warm-up gate (the dual step stays off until
  ||s|| < 1e-3 ||r|| for 10 iterations running), and a dual reset (to 0)
  with a restart of the kappa ramp on any node whose projection (38b)
  moved it.

The message exchanged is the flat natural-parameter vector of Eq. 45:
[alpha - 1 (K) | per component k: n1, n4, n3 (D), vec(n2) (D*D)] with
n1 = (nu - D)/2, n4 = -beta/2, n3 = beta m, n2 = -W^-1/2 - beta m m^T/2.

Each function computes in the dtype of its inputs; `precision` names the
matmul precision of a whole run ("highest" is f32 in full; "high" is the
three-pass bfloat16 product, the control of a float32 configuration).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.scipy.special import digamma

MIN_ALPHA = 1e-3
MIN_BETA = 1e-6
MIN_EIG = 1e-8


# ---------------------------------------------------------------------------
# Eq. 45: hyperparameters <-> the flat message
# ---------------------------------------------------------------------------
def flat_dim(K: int, D: int) -> int:
    return K + K * (2 + D + D * D)


def block_names(K: int, D: int) -> list:
    """The block each coordinate of the flat message belongs to."""
    return ["alpha"] * K + (["nu", "beta"] + ["mean"] * D
                            + ["winv"] * (D * D)) * K


def pack(alpha, m, beta, W, nu):
    K, D = m.shape
    W_inv = jnp.linalg.inv(W)
    n2 = -0.5 * W_inv - 0.5 * beta[:, None, None] * m[:, :, None] * m[:, None]
    comp = jnp.concatenate([((nu - D) / 2.0)[:, None], (-beta / 2.0)[:, None],
                            beta[:, None] * m, n2.reshape(K, D * D)], axis=1)
    return jnp.concatenate([alpha - 1.0, comp.reshape(-1)])


def _blocks(phi, K, D):
    comp = phi[K:].reshape(K, 2 + D + D * D)
    return (phi[:K], comp[:, 0], comp[:, 1], comp[:, 2:2 + D],
            comp[:, 2 + D:].reshape(K, D, D))


def unpack(phi, K, D):
    a, n1, n4, n3, n2 = _blocks(phi, K, D)
    beta = -2.0 * n4
    m = n3 / beta[:, None]
    W_inv = -2.0 * n2 - beta[:, None, None] * m[:, :, None] * m[:, None]
    return a + 1.0, m, beta, jnp.linalg.inv(W_inv), 2.0 * n1 + D


# ---------------------------------------------------------------------------
# Eqs. 17a, 18: one node's VBE step and local VBM optimum
# ---------------------------------------------------------------------------
def local_optimum(x, mask, phi, prior, replication, K, D):
    """x (T, D), mask (T,), phi (P,) -> phi* (P,)."""
    alpha, m, beta, W, nu = unpack(phi, K, D)
    e_logpi = digamma(alpha) - digamma(jnp.sum(alpha))
    j = jnp.arange(1, D + 1, dtype=x.dtype)
    e_logdet = (jnp.sum(digamma((nu[:, None] + 1.0 - j) / 2.0), axis=1)
                + D * jnp.log(2.0) + jnp.linalg.slogdet(W)[1])
    # E[(x - mu)' L (x - mu)] = x' (nu W) x - 2 x' (nu W m) + c
    Wn = nu[:, None, None] * W
    b = jnp.einsum("kde,ke->kd", Wn, m)
    c = D / beta + jnp.einsum("kd,kd->k", m, b)
    quad = jnp.einsum("td,kde,te->tk", x, Wn, x)
    log_rho = (e_logpi + 0.5 * e_logdet - 0.5 * D * jnp.log(2.0 * jnp.pi)
               - 0.5 * (quad - 2.0 * x @ b.T + c))
    r = jax.nn.softmax(log_rho, axis=1) * mask[:, None]         # (T, K)

    R = replication * jnp.sum(r, axis=0)
    sum_x = replication * jnp.einsum("tk,td->kd", r, x)
    sum_xx = replication * jnp.einsum("tk,td,te->kde", r, x, x)
    xbar = sum_x / (R[:, None] + 1e-12)
    S_R = sum_xx - R[:, None, None] * xbar[:, :, None] * xbar[:, None]
    b0, m0 = prior["beta"], prior["m"]
    d = xbar - m0
    W_inv = (jnp.linalg.inv(prior["W"]) + S_R
             + (b0 * R / (b0 + R))[:, None, None] * d[:, :, None] * d[:, None])
    W_inv = 0.5 * (W_inv + jnp.swapaxes(W_inv, 1, 2))
    beta_n = b0 + R
    m_n = (b0[:, None] * m0 + sum_x) / beta_n[:, None]
    return pack(prior["alpha"] + R, m_n, beta_n, jnp.linalg.inv(W_inv),
                prior["nu"] + R)


def local_optima(x, mask, phi, prior, K, D, node_block):
    """All nodes, `node_block` at a time so that one block's (T, K, D)
    temporaries are what the device holds."""
    rep = jnp.asarray(x.shape[0], x.dtype)
    return jax.lax.map(
        lambda a: local_optimum(a[0], a[1], a[2], prior, rep, K, D),
        (x, mask, phi), batch_size=node_block)


# ---------------------------------------------------------------------------
# Eq. 38b: projection onto the domain, component by component
# ---------------------------------------------------------------------------
def project(phi, K, D):
    """alpha >= MIN_ALPHA, beta >= MIN_BETA, nu > D - 1 and W^-1 positive
    definite (eigenvalues floored at max(MIN_EIG, 1e-10 * the largest));
    a component already inside comes back unchanged."""
    a, n1, n4_in, n3, n2 = _blocks(phi, K, D)
    a = jnp.where(a + 1.0 < MIN_ALPHA, MIN_ALPHA - 1.0, a)
    n4 = jnp.minimum(n4_in, -MIN_BETA / 2.0)
    beta = -2.0 * n4
    m = n3 / beta[:, None]
    nu_min = (D - 1.0) + 1e-3
    n1 = jnp.where(2.0 * n1 + D < nu_min, (nu_min - D) / 2.0, n1)
    mmT = m[:, :, None] * m[:, None]
    W_inv = -2.0 * n2 - beta[:, None, None] * mmT
    W_inv = 0.5 * (W_inv + jnp.swapaxes(W_inv, 1, 2))
    ev, V = jnp.linalg.eigh(W_inv)
    floor = jnp.maximum(MIN_EIG, 1e-10 * jnp.max(jnp.abs(ev), 1,
                                                  keepdims=True))
    clip = jnp.any(ev < floor, axis=1) | (n4 != n4_in)
    W_inv = jnp.einsum("kij,kj,klj->kil", V, jnp.maximum(ev, floor), V)
    n2 = jnp.where(clip[:, None, None], -0.5 * W_inv - 0.5 * beta[:, None,
                                                                  None] * mmT,
                   n2)
    comp = jnp.concatenate([n1[:, None], n4[:, None], n3,
                            n2.reshape(K, D * D)], axis=1)
    return jnp.concatenate([a, comp.reshape(-1)])


# ---------------------------------------------------------------------------
# The two combine rules
# ---------------------------------------------------------------------------
def dsvb_step(phi, phi_star, t, W, tau, d0=1.0):
    eta = 1.0 / (d0 + tau * (t + 1.0))
    return W @ (phi + eta * (phi_star - phi))


ADMM = dict(rho=0.5, xi=0.05, mu=10.0, factor=2.0, adapt_every=10,
            rho_min=1e-3, rho_max=1e3, warmup_tol=1e-3, warmup_window=10,
            clip_tol=1e-9)


def admm_init(phi0):
    dt = phi0.dtype
    return dict(lam=jnp.zeros_like(phi0), rho=jnp.asarray(ADMM["rho"], dt),
                stable=jnp.asarray(0, jnp.int32), t_act=jnp.asarray(0.0, dt),
                active=jnp.asarray(False))


def admm_step(phi, phi_star, st, adj, K, D):
    c = ADMM
    deg = jnp.sum(adj, axis=1)[:, None]
    lam, rho = st["lam"], st["rho"]
    phi_hat = ((phi_star - 2.0 * lam + rho * (deg * phi + adj @ phi))
               / (1.0 + 2.0 * rho * deg))                               # 38a
    phi_new = jax.vmap(lambda p: project(p, K, D))(phi_hat)             # 38b
    moved = jnp.max(jnp.abs(phi_new - phi_hat), axis=1) > c["clip_tol"]
    resid = deg * phi_new - adj @ phi_new
    r = jnp.sqrt(jnp.mean(resid ** 2))
    s = jnp.sqrt(jnp.mean((rho * (phi_new - phi)) ** 2))
    stable = jnp.where(s < c["warmup_tol"] * r, st["stable"] + 1, 0)
    active = st["active"] | (stable >= c["warmup_window"])
    t_act = jnp.where(active, st["t_act"] + 1.0, 0.0)
    t_act = jnp.where(jnp.any(moved), 0.0, t_act)
    kappa = jnp.where(t_act > 0.0,
                      1.0 - 1.0 / (1.0 + c["xi"] * t_act) ** 2, 0.0)     # 40
    lam = lam + kappa * rho / 2.0 * resid                               # 39
    lam = jnp.where(moved[:, None], 0.0, lam)
    fac = jnp.where(r > c["mu"] * s, c["factor"],
                    jnp.where(s > c["mu"] * r, 1.0 / c["factor"], 1.0))
    due = active & (jnp.mod(t_act, float(c["adapt_every"])) == 0.0) \
        & (t_act > 0.0)
    rho = jnp.where(due, jnp.clip(rho * fac, c["rho_min"], c["rho_max"]),
                    rho)
    return phi_new, dict(lam=lam, rho=rho.astype(phi.dtype), stable=stable,
                         t_act=t_act.astype(phi.dtype), active=active)


# ---------------------------------------------------------------------------
# A whole session
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("rule", "K", "D", "node_block",
                                             "precision"))
def run(x, mask, phi0, prior, graph, tau, n_iters, *, rule, K, D,
        node_block, precision="highest"):
    """`n_iters` iterations of one session from phi0 (N, P).  `graph` is
    the diffusion weights (rule "dsvb") or the 0/1 adjacency ("admm")."""
    with jax.default_matmul_precision(precision):
        def body(t, carry):
            phi, st = carry
            phi_star = local_optima(x, mask, phi, prior, K, D, node_block)
            if rule == "dsvb":
                return dsvb_step(phi, phi_star, t.astype(phi.dtype), graph,
                                 tau), st
            return admm_step(phi, phi_star, st, graph, K, D)

        st0 = admm_init(phi0) if rule == "admm" else {}
        phi, _ = jax.lax.fori_loop(0, n_iters, body, (phi0, st0))
        return phi
