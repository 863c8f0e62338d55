"""Plain reference for the CONCORD estimator: its first-order optimality
(KKT) residual in float64, from the estimate and the data alone.

The estimate Omega minimises

    g(Omega) + lam1 * sum_{i != j} |Omega_ij|,
    g(Omega) = -sum_i log Omega_ii + tr(Omega S Omega) / 2 + lam2 ||Omega||_F^2 / 2

with S = X^T X / n, so grad g = -diag(1 / Omega_ii) + (W + W^T) / 2 + lam2 Omega
with W = Omega S.  Omega is optimal exactly when it is a fixed point of the
unit-step proximal-gradient map, Omega = prox(Omega - grad g), where the
prox soft-thresholds the off-diagonal entries by lam1 and leaves the
diagonal.  The residual is the gap of that fixed point:

    kkt_rel = ||Omega - prox(Omega - grad g)||_F / max(1, ||Omega||_F)
    kkt_max = max_ij |Omega - prox(Omega - grad g)|_ij

It also evaluates the objective itself in float64, from the same W, so
that the objective the program reports for its estimate can be compared.

Everything is numpy and scipy in float64 on the host, in blocks of rows so
that the (p, p) temporaries of a large p never coexist.  W = Omega S is
formed from the sparse estimate: through an explicit S when p is small
enough to hold it, otherwise as (Omega X^T) X / n one block of rows at a
time.
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import scipy.sparse as sp

#: the largest p for which S (p x p float64) is formed explicitly
EXPLICIT_S_MAX_P = 16384
#: rows per block of the residual pass
BLOCK_ROWS = 1024
#: threads over the blocks
WORKERS = min(16, os.cpu_count() or 1)


def _row_blocks(p: int):
    for r0 in range(0, p, BLOCK_ROWS):
        yield r0, min(p, r0 + BLOCK_ROWS)


class GradientOperator:
    """W = Omega S for one data matrix, by rows and by columns."""

    def __init__(self, x: np.ndarray):
        self.x = np.asarray(x, np.float64)
        self.n, self.p = self.x.shape
        self.s = None
        if self.p <= EXPLICIT_S_MAX_P:
            self.s = self.x.T @ self.x
            self.s /= self.n

    def bind(self, omega: sp.csr_matrix) -> "BoundW":
        return BoundW(self, omega)


class BoundW:
    """W = Omega S for one estimate: ``rows(r0, r1)`` gives W[r0:r1, :]
    and ``cols_t(r0, r1)`` gives W[:, r0:r1]^T, both as new C-ordered
    arrays."""

    def __init__(self, op: GradientOperator, omega: sp.csr_matrix):
        self.op = op
        if op.s is not None:
            self.w = np.asarray(omega @ op.s)
            self.wt = np.ascontiguousarray(self.w.T)
        else:
            self.w = None
            y = np.asarray(omega @ op.x.T)          # (p, n)
            self.y = y
            self.yt = np.ascontiguousarray(y.T)     # (n, p)

    def rows(self, r0, r1):
        if self.w is not None:
            return self.w[r0:r1].copy()
        return self.y[r0:r1] @ self.op.x / self.op.n

    def cols_t(self, r0, r1):
        if self.w is not None:
            return self.wt[r0:r1]
        return self.op.x[:, r0:r1].T @ self.yt / self.op.n


#: what an estimate that is not finite or not positive on the diagonal reads
INVALID = {"kkt_rel": float("inf"), "kkt_max": float("inf"),
           "objective": float("nan")}


def _sparse_rows(om32, r0, r1):
    """(all finite, float64 CSR) of rows r0:r1 of a dense estimate."""
    rows = om32[r0:r1]
    return (bool(np.all(np.isfinite(rows))),
            sp.csr_matrix(rows, dtype=np.float64))


def _block_gap(w: BoundW, csr, r0, r1, lam1, lam2):
    """(sum of squares, max) of the fixed-point gap over rows r0:r1, and
    the rows' share of sum_ij W_ij Omega_ij."""
    g = w.rows(r0, r1)
    blk = csr[r0:r1].tocoo()
    ri, cj, v = blk.row, blk.col, blk.data
    quad = float(g[ri, cj] @ v)
    g += w.cols_t(r0, r1)
    g *= 0.5
    gnz = g[ri, cj] + lam2 * v
    on_diag = cj == ri + r0
    gnz[on_diag] -= 1.0 / v[on_diag]
    z = v - gnz
    prox = np.where(on_diag, z, np.sign(z) * np.maximum(np.abs(z) - lam1, 0.0))
    gap_nz = v - prox
    np.abs(g, out=g)
    g -= lam1
    np.maximum(g, 0.0, out=g)
    g[ri, cj] = 0.0
    flat = g.ravel()
    mx = float(flat.max())
    if gap_nz.size:
        mx = max(mx, float(np.abs(gap_nz).max()))
    return float(flat @ flat) + float(gap_nz @ gap_nz), mx, quad


def kkt_residual(omega, op: GradientOperator, lam1: float,
                 lam2: float) -> dict:
    """The f64 KKT residuals of estimate ``omega`` (a (p, p) array) for the
    data behind ``op``, and its objective.  Returns ``{"kkt_rel": ...,
    "kkt_max": ..., "objective": ...}``, the objective being
    g(Omega) + lam1 * sum_{i != j} |Omega_ij| in float64.

    An entry where Omega is zero (never the diagonal of a valid estimate)
    has the gap max(|G_ij| - lam1, 0) with G = (W + W^T) / 2, so each block
    of rows takes a few dense passes, and the nonzero entries of Omega are
    then computed exactly from the sparse structure.  Blocks run on a few
    threads (numpy and BLAS release the interpreter lock)."""
    om32 = np.asarray(omega)
    p = om32.shape[0]
    if om32.shape != (p, p) or p != op.p:
        raise ValueError(f"estimate of shape {om32.shape} for p={op.p}")
    with ThreadPoolExecutor(max_workers=WORKERS) as pool:
        blocks = list(pool.map(lambda b: _sparse_rows(om32, *b),
                               _row_blocks(p)))
        if not all(finite for finite, _ in blocks):
            return dict(INVALID)
        csr = sp.vstack([blk for _, blk in blocks], format="csr")
        diag = csr.diagonal()
        if np.any(diag <= 0):
            return dict(INVALID)
        w = op.bind(csr)
        parts = list(pool.map(
            lambda b: _block_gap(w, csr, b[0], b[1], lam1, lam2),
            _row_blocks(p)))
    sq = sum(part[0] for part in parts)
    mx = max(part[1] for part in parts)
    om_sq = float(csr.data @ csr.data)
    offdiag_l1 = float(np.abs(csr.data).sum() - np.abs(diag).sum())
    objective = (-float(np.log(diag).sum()) + 0.5 * sum(part[2] for part in parts)
                 + 0.5 * lam2 * om_sq + lam1 * offdiag_l1)
    return {"kkt_rel": float(np.sqrt(sq) / max(1.0, np.sqrt(om_sq))),
            "kkt_max": mx, "objective": objective}
