"""Sparsity-aware matmul dispatch for the Ω-side products (the matops layer).

HP-CONCORD's dominant cost is the ΩŜ / ΩXᵀ product, and the iterate Ω
becomes extremely sparse as the solve proceeds — the regime the paper's
1.28M-dimension runs live in.  This module turns that emergent sparsity
into skipped work:

  * ``block_mask(a, bs)``       — block-occupancy mask of a matrix (one bit
                                  per bs x bs tile).  The solver harvests it
                                  for free from the prox step (the fused
                                  Pallas prox kernel emits per-tile nnz
                                  counts; the jnp path computes it in one
                                  cheap pass).
  * ``masked_matmul(...)``      — the block-gather product: gather only the
                                  occupied tiles of A (up to a static
                                  capacity), batched-matmul them against the
                                  matching row-blocks of B, scatter-add by
                                  block row.  Work is proportional to the
                                  capacity, not p^2.  This is the jittable
                                  fallback of the Pallas block-CSR kernel
                                  (``kernels.blocksparse_matmul``), which
                                  needs host-side CSR construction.
  * ``matmul(a, b, mask, policy)`` — the dispatch: a ``lax.cond``/``switch``
                                  on the *observed* block density routes to
                                  the dense path above the crossover
                                  threshold and to the block-gather path
                                  (with the smallest capacity tier that
                                  provably covers the occupied blocks)
                                  below it.  Both branches are exact: the
                                  sparse branch only ever runs when its
                                  capacity bounds the occupied-block count.

``MatmulPolicy`` is a hashable NamedTuple so it can ride through ``jax.jit``
static arguments (``solve_reference(sparse_matmul=...)``) and shard_map'd
distributed drivers alike.  The crossover threshold for ``mode="auto"`` is
produced by ``core.costmodel.crossover_density`` (calibrated by
``benchmarks/sparse_crossover.py``).
"""
from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

#: capacity ladder, as fractions of the policy threshold.  The dispatch
#: picks the smallest rung whose capacity covers the observed occupied
#: blocks, so late (very sparse) iterations do proportionally less work
#: instead of always paying for the full threshold capacity.
TIER_FRACTIONS = (0.125, 0.25, 0.5, 1.0)

#: dtype of every block-occupancy mask.  Fixed and compact on purpose: the
#: distributed drivers ppermute the mask around the 1.5D ring alongside the
#: Ω operand, so an operand-dtype mask would move 4-8 bytes per block where
#: one is enough (an f64 solve used to ship 8-byte masks).  Consumers only
#: ever test ``mask > 0``.
MASK_DTYPE = jnp.int8

#: dtype of block-density STATISTICS (the scalar the dispatch and the
#: solver report as ``block_density``).  f32 by policy: it is a diagnostic
#: ratio in [0, 1] compared against a crossover threshold, never part of
#: the f64 iterate arithmetic, and the distributed drivers psum it.
DENSITY_DTYPE = jnp.float32

#: precision of every product the solver forms (Ω-side, Gram, gradient).
#: Stated here rather than left to the platform: a TPU's default for f32
#: operands feeds bf16 to the matrix unit, which moves the f32 solve off
#: the f64 reference's optimum.  HIGHEST keeps f32-accurate products; the
#: CPU backend and f64 operands compute the same either way.
PRECISION = lax.Precision.HIGHEST


def dot(a, b):
    """``a @ b`` at the solver's stated :data:`PRECISION`."""
    return jnp.matmul(a, b, precision=PRECISION)


class MatmulPolicy(NamedTuple):
    """Static (hashable) routing policy for Ω-side products.

    mode        "off" — always dense; "on" — block-sparse below
                ``threshold``; "auto" — same mechanics, but the threshold
                came from the cost model's dense↔block-sparse crossover.
    block_size  tile edge of the occupancy mask (MXU-aligned, 128, on TPU;
                anything that divides the operand on CPU tests).
    threshold   block-density crossover: observed density above it takes
                the dense path.
    """
    mode: str = "off"
    block_size: int = 128
    threshold: float = 0.25

    @property
    def enabled(self) -> bool:
        return self.mode != "off"


DENSE = MatmulPolicy()


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


def _pad2(a, rows: int, cols: int):
    r, c = a.shape
    if r == rows and c == cols:
        return a
    return jnp.pad(a, ((0, rows - r), (0, cols - c)))


def block_mask(a, block_size: int):
    """Block-occupancy mask: out[i, j] = 1 iff tile (i, j) has any nonzero.

    Shape is (cdiv(r, bs), cdiv(c, bs)), dtype ``MASK_DTYPE`` (compact and
    independent of the operand dtype — the distributed drivers rotate this
    around the ring); partial edge tiles are zero-padded (padding never
    flips a tile on).  Semantically identical to the per-tile nnz counts
    the fused prox kernel emits (``kernels.softthresh``).
    """
    r, c = a.shape
    bs = block_size
    nbr, nbc = _cdiv(r, bs), _cdiv(c, bs)
    ap = _pad2(a, nbr * bs, nbc * bs)
    tiles = jnp.abs(ap).reshape(nbr, bs, nbc, bs)
    return (tiles.max(axis=(1, 3)) > 0).astype(MASK_DTYPE)


def block_density(mask):
    """Fraction of occupied blocks (``DENSITY_DTYPE`` scalar)."""
    return jnp.mean((mask > 0).astype(DENSITY_DTYPE))


def capacity_tiers(total_blocks: int, threshold: float) -> list[int]:
    """Ascending block capacities the dispatch may gather (deduplicated,
    all < total_blocks — a capacity of the full grid saves nothing)."""
    caps = sorted({
        max(1, math.ceil(threshold * total_blocks * f))
        for f in TIER_FRACTIONS
    })
    return [c for c in caps if c < total_blocks]


def masked_matmul(a, b, mask, *, block_size: int, capacity: int):
    """Block-gather product: C = A @ B using only occupied bs x bs tiles
    of A (up to ``capacity`` of them, occupied-first).

    Correct whenever the occupied-block count is <= capacity: unoccupied
    tiles of A are exactly zero by construction of the mask, and gathered
    padding picks are zero-masked, so the result equals the dense product
    up to float summation order.  Cost: O(capacity * bs^2 * m) flops plus
    the gathers — i.e. proportional to nnz(Ω) instead of p^2.
    """
    p, k = a.shape
    kb, m = b.shape
    bs = block_size
    nbr, nbc = mask.shape
    ap = _pad2(a, nbr * bs, nbc * bs)
    bp = _pad2(b, nbc * bs, m)
    occupied = mask.reshape(-1) > 0
    order = jnp.argsort(~occupied)            # occupied block ids first
    idx = order[:capacity]
    r_idx = idx // nbc
    c_idx = idx % nbc
    a4 = ap.reshape(nbr, bs, nbc, bs)
    vals = a4[r_idx, :, c_idx, :]             # (capacity, bs, bs) gather
    vals = vals * occupied[idx][:, None, None].astype(vals.dtype)
    b3 = bp.reshape(nbc, bs, m)
    prods = jnp.einsum("nij,njm->nim", vals, b3[c_idx], precision=PRECISION)
    out = jax.ops.segment_sum(prods, r_idx, num_segments=nbr)
    return out.reshape(nbr * bs, m)[:p]


def matmul(a, b, *, mask=None, policy: MatmulPolicy | None = None):
    """The Ω-side product dispatch.

    Dense :func:`dot` when the policy is off (or no mask is available);
    otherwise a ``lax.switch`` on the observed block density of ``mask``:
    density above ``policy.threshold`` falls back to the dense path, below
    it the block-gather path runs with the smallest capacity tier that
    covers the occupied blocks.  Exact either way (see ``masked_matmul``).
    """
    if policy is None or not policy.enabled or mask is None:
        return dot(a, b)
    bs = policy.block_size
    nbr, nbc = _cdiv(a.shape[0], bs), _cdiv(a.shape[1], bs)
    if mask.shape != (nbr, nbc):
        raise ValueError(
            f"mask shape {mask.shape} does not tile operand {a.shape} at "
            f"block_size={bs} (want {(nbr, nbc)})")
    total = nbr * nbc
    caps = capacity_tiers(total, policy.threshold)
    if not caps:
        return dot(a, b)

    # Rung selection compares INTEGER occupied-block counts against the
    # integer capacities (a float density ratio loses ulps past 2^24
    # blocks and could under-select a rung, silently dropping occupied
    # blocks): rung i is the first with caps[i] >= occupied; past the
    # last rung (occupied > ceil(threshold * total)) -> dense.
    occupied = jnp.sum((mask > 0).astype(jnp.int32))
    bounds = jnp.asarray(caps, jnp.int32)
    ix = jnp.searchsorted(bounds, occupied, side="left")
    ix = jnp.minimum(ix, len(caps))

    def _make_sparse(cap):
        def _sparse(a_, b_, m_):
            return masked_matmul(a_, b_, m_, block_size=bs, capacity=cap)
        return _sparse

    branches = [_make_sparse(c) for c in caps]
    branches.append(lambda a_, b_, m_: dot(a_, b_))
    return lax.switch(ix, branches, a, b, mask)


def panel_gram(x, *, panel: int = 512):
    """Blocked XᵀX: the (p, p) Gram of an (n, p) row-block, accumulated by
    column panels so each product is a bounded (panel, n) @ (n, p) slab —
    the data-side sibling of the Ω-product dispatch above, and the unit of
    work the streaming Gram accumulator (``data.gram``) folds per chunk.

    Every panel routes through :func:`matmul` (the dense path of the
    dispatch; the X operand carries no exploitable block sparsity).  The
    f64 contract of the accumulator is preserved: a float64 numpy input
    stays float64 even with jax x64 disabled — the panels then run
    host-side in numpy, because ``jnp.asarray`` would silently downcast
    to f32 and break the streamed-vs-dense 1e-10 agreement.
    """
    if panel < 1:
        raise ValueError(f"panel must be >= 1, got {panel}")
    if x.ndim != 2:
        raise ValueError(f"x must be 2-D (n, p), got shape {x.shape}")
    p = x.shape[1]
    host_f64 = (not isinstance(x, jax.Array)
                and np.asarray(x).dtype == np.float64
                and not jax.config.jax_enable_x64)
    if host_f64:
        xh = np.asarray(x)
        out = np.empty((p, p), np.float64)
        for lo in range(0, p, panel):
            out[lo:lo + panel] = xh[:, lo:lo + panel].T @ xh
        return out
    xj = jnp.asarray(x)
    blocks = [matmul(xj[:, lo:lo + panel].T, xj)
              for lo in range(0, p, panel)]
    return blocks[0] if len(blocks) == 1 else jnp.concatenate(blocks, axis=0)
