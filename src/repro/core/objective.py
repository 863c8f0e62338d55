"""CONCORD / PseudoNet objective, gradient, and proximal operator.

The PseudoNet criterion (paper eq. (1), internally-consistent scaling):

    F(Omega) = g(Omega) + h(Omega)
    g(Omega) = -sum_i log(omega_ii) + 1/2 tr(Omega S Omega) + lam2/2 ||Omega||_F^2
    h(Omega) = lam1 * ||Omega_X||_1           (off-diagonal l1)

    grad g(Omega) = -Omega_D^{-1} + 1/2 (W + W^T) + lam2 * Omega,   W = Omega S

which matches the gradient stated in Algorithm 2 of the paper (the paper's
line-7 objective display carries stray factors of 2 that are inconsistent
with its own gradient; we keep gradient == d/dOmega objective).

Everything here is pure jnp on a single logical array; the distributed
drivers in core/cov.py and core/obs.py reproduce these formulas on shards.

Two evaluation modes mirror the paper's variants:
  * "cov": W = Omega @ S with S = X^T X / n precomputed.
  * "obs": Y = Omega @ X^T / sqrt-free (we keep 1/n folded), Z = Y @ X, and
           tr(Omega S Omega) = ||Y||_F^2 * n ... see ObsState docs.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from . import matops


def soft_threshold(z: jax.Array, alpha) -> jax.Array:
    """Elementwise soft-thresholding S_alpha(z) (paper eq. (2))."""
    return jnp.sign(z) * jnp.maximum(jnp.abs(z) - alpha, 0.0)


def prox_l1_offdiag(z: jax.Array, alpha) -> jax.Array:
    """Prox of alpha*||Z_X||_1: soft-threshold off-diagonal, keep diagonal."""
    st = soft_threshold(z, alpha)
    return st + (z - st) * jnp.eye(z.shape[-1], dtype=z.dtype)


def offdiag_l1(omega: jax.Array) -> jax.Array:
    p = omega.shape[-1]
    mask = 1.0 - jnp.eye(p, dtype=omega.dtype)
    return jnp.sum(jnp.abs(omega) * mask)


def smooth_objective_cov(omega: jax.Array, w: jax.Array, lam2) -> jax.Array:
    """g(Omega) given W = Omega @ S.

    tr(Omega S Omega) = tr(W Omega) = sum_ij W_ij Omega_ij for symmetric Omega.
    """
    diag = jnp.diagonal(omega, axis1=-2, axis2=-1)
    logdet_term = -jnp.sum(jnp.log(diag))
    quad = 0.5 * jnp.sum(w * omega)
    ridge = 0.5 * lam2 * jnp.sum(omega * omega)
    return logdet_term + quad + ridge


def smooth_objective_obs(omega: jax.Array, y: jax.Array, n: int, lam2) -> jax.Array:
    """g(Omega) given Y = Omega @ X^T (unnormalized).

    tr(Omega S Omega) = (1/n)||Omega X^T||_F^2 = ||Y||_F^2 / n.
    """
    diag = jnp.diagonal(omega, axis1=-2, axis2=-1)
    logdet_term = -jnp.sum(jnp.log(diag))
    quad = 0.5 * jnp.sum(y * y) / n
    ridge = 0.5 * lam2 * jnp.sum(omega * omega)
    return logdet_term + quad + ridge


def gradient_from_w(omega: jax.Array, w: jax.Array, lam2) -> jax.Array:
    """grad g = -Omega_D^{-1} + (W + W^T)/2 + lam2 * Omega."""
    p = omega.shape[-1]
    inv_diag = 1.0 / jnp.diagonal(omega, axis1=-2, axis2=-1)
    return (
        -jnp.eye(p, dtype=omega.dtype) * inv_diag
        + 0.5 * (w + jnp.swapaxes(w, -1, -2))
        + lam2 * omega
    )


def full_objective_cov(omega, s, lam1, lam2):
    w = matops.dot(omega, s)
    return smooth_objective_cov(omega, w, lam2) + lam1 * offdiag_l1(omega)


def full_objective_obs(omega, x, lam1, lam2):
    n = x.shape[0]
    y = matops.dot(omega, x.T)
    return smooth_objective_obs(omega, y, n, lam2) + lam1 * offdiag_l1(omega)


class ProxState(NamedTuple):
    """Carry for the proximal-gradient loop."""
    omega: jax.Array       # current iterate, (p, p)
    w: jax.Array           # W = Omega @ S  (cov) or Z = Y @ X / n (obs)
    g_val: jax.Array       # g(omega)
    step: jax.Array        # iteration counter
    tau: jax.Array         # last accepted step size
    delta: jax.Array       # ||omega_{k+1} - omega_k||_F / max(1, ||omega_k||_F)
    ls_iters: jax.Array    # cumulative line-search iterations (for cost model `t`)


def sufficient_decrease(g_new, g_old, omega_new, omega_old, grad, tau):
    """Backtracking acceptance (Algorithms 2/3 line 12).

    g(O+) <= g(O) + tr((O+ - O)^T G) + ||O+ - O||_F^2 / (2 tau)
    """
    diff = omega_new - omega_old
    rhs = g_old + jnp.sum(diff * grad) + jnp.sum(diff * diff) / (2.0 * tau)
    return g_new <= rhs
