"""Seeded data for the chain-graph deployments, made on the device.

The ground truth is the tridiagonal precision matrix Omega0 of the paper's
chain graph (unit diagonal, ``weight`` on the first off-diagonals).  Its
Cholesky factor L (Omega0 = L L^T) is lower bidiagonal, so the Gaussian
samples X = Z L^{-1}, whose rows have covariance Omega0^{-1}, solve the
two-term recurrence X[:, j] = (Z[:, j] - L[j+1, j] X[:, j+1]) / L[j, j]
from the last column back.  That is O(n p) work, done here as one
associative scan over the columns in a single jitted call.

A run's data set is one base draw, fixed by the traffic mix's
``data_seed``, with every column's sign flipped or kept by the run's
``--seed``.  Flipping the signs of variables maps the estimation problem
onto an equivalent one: every iterate, product and sum of the solver has
the same magnitudes with the signs permuted, so each seed poses different
inputs and the same work (the trial count of the base draw), and the
timing's spread is the system's own, not the data's.
"""
from __future__ import annotations

import functools

import numpy as np


def chain_omega(p: int, weight: float) -> np.ndarray:
    """The dense (p, p) chain precision matrix (for tests and checks)."""
    om = np.eye(p)
    i = np.arange(p - 1)
    om[i, i + 1] = om[i + 1, i] = weight
    return om


def chain_cholesky(p: int, weight: float) -> tuple[np.ndarray, np.ndarray]:
    """Diagonal d[j] = L[j, j] and subdiagonal s[j] = L[j+1, j] (length
    p - 1) of the Cholesky factor of the chain precision, in float64."""
    d = np.empty(p)
    s = np.empty(p - 1)
    d[0] = 1.0
    for j in range(p - 1):
        s[j] = weight / d[j]
        d[j + 1] = np.sqrt(1.0 - s[j] * s[j])
    return d, s


def chain_samples(z, weight: float):
    """X = Z L^{-1} for normal draws ``z`` of shape (n, p): the samples
    whose covariance is the inverse of the chain precision."""
    import jax.numpy as jnp
    from jax import lax

    p = z.shape[1]
    d, s = chain_cholesky(p, weight)
    # X[:, j] = a[j] X[:, j+1] + b[:, j], with a[p-1] = 0
    a = jnp.asarray(np.append(-s / d[:-1], 0.0), z.dtype)
    b = z / jnp.asarray(d, z.dtype)

    def combine(right, left):
        # in a reverse scan the first argument is the later (right) block
        a_r, b_r = right
        a_l, b_l = left
        return a_l * a_r, b_l + a_l * b_r

    a_full = jnp.broadcast_to(a, z.shape)
    _, x = lax.associative_scan(combine, (a_full, b), reverse=True, axis=1)
    return x


def seed_key(seed: int):
    """A PRNG key from any non-negative seed up to 64 bits."""
    import jax

    seed = int(seed)
    if seed < 0 or seed >= 2 ** 64:
        raise ValueError(f"seed must lie in [0, 2**64), got {seed}")
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


@functools.lru_cache(maxsize=None)
def _generator(n: int, p: int, weight: float):
    import jax
    import jax.numpy as jnp

    def gen(base_key, sign_key):
        z = jax.random.normal(base_key, (n, p), jnp.float32)
        signs = jax.random.rademacher(sign_key, (p,), jnp.float32)
        return chain_samples(z, weight) * signs

    return jax.jit(gen)


def make_x(data_seed: int, seed: int, n: int, p: int, weight: float):
    """A run's (n, p) float32 samples, generated on the default device:
    the base draw of ``data_seed`` with column signs drawn from ``seed``."""
    return _generator(n, p, float(weight))(seed_key(data_seed),
                                           seed_key(seed))
