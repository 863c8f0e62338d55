"""Fused proximal-update Pallas TPU kernel.

One VMEM pass computes  out = S_alpha(z) offdiag + z diag  AND the
objective reduction pieces the line search needs (log-det over the
diagonal, off-diagonal l1, Frobenius sum-of-squares, diagonal min for the
positivity guard).  The paper's CPU code makes 3+ passes over the p^2
iterate for these elementwise steps; on TPU the whole state is streamed
HBM->VMEM once per line-search trial.

The kernel has an optional WEIGHT operand lane for the composable penalty
API (``core.penalty``): with ``weights`` the per-entry threshold becomes
``alpha * w_ij`` (``w_ij = inf`` forces an exact zero, the structural-
exclusion convention), streamed through VMEM alongside the iterate.
Without it the scalar-broadcast fast path is byte-for-byte the original
kernel — no extra HBM traffic, bit-identical output.

Tiles are (block_m, block_n) VMEM blocks; the per-tile partial stats land
in one (8, 128) block per tile of a (grid_m * 8, grid_n * 128) output —
the smallest block the TPU lowering accepts (sublane and lane aligned).
Every row of a tile's block carries the same stats and only lanes 0..4
carry data; the wrapper reads row 0 of each block and reduces.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK = (256, 256)
# lane-aligned stats vector;
#   [0]=logdet [1]=l1 [2]=sumsq [3]=min_diag [4]=tile nnz count
# lane 4 is the free block-occupancy harvest: with block == the matops
# block size, stats[..., 4] > 0 IS the block-sparse dispatch mask.
# (lane 1 stays the UNWEIGHTED |out| sum in the weighted kernel.)
STATS_LANES = 128
#: sublane rows of a tile's stats block (the TPU's (8, 128) minimum tile)
STATS_SUBLANES = 8

#: floor dtype of the per-tile stats output: at least f32 (counts and
#: reductions would drift in bf16), widened to the operand dtype so an
#: f64 interpret-mode solve keeps f64 line-search stats end to end.
STATS_MIN_DTYPE = jnp.float32


def check_native_blocks(kernel: str, specs, shapes) -> None:
    """Refuse, before dispatch, a layout the TPU lowering would reject.

    Mosaic needs the last two dimensions of every VMEM block to be
    multiples of (8, 128), or equal to the array's own dimensions.
    ``specs`` pairs with ``shapes`` (array shapes); specs without a block
    shape (SMEM tables) are skipped."""
    for spec, shape in zip(specs, shapes):
        block = getattr(spec, "block_shape", None)
        if block is None:
            continue
        for b, a, q in zip(tuple(block)[-2:], tuple(shape)[-2:], (8, 128)):
            if b % q and b != a:
                raise ValueError(
                    f"{kernel}: block {tuple(block)} of a {tuple(shape)} "
                    f"operand cannot lower for the TPU (the last two block "
                    f"dims must be multiples of (8, 128) or span the "
                    f"array); pick a 128-multiple block")


def tile_stats(stats):
    """(grid_m * 8, grid_n * 128) stats output -> (grid_m, grid_n, 128):
    row 0 of each tile's (8, 128) block."""
    gm, gn = stats.shape[0] // STATS_SUBLANES, stats.shape[1] // STATS_LANES
    return stats.reshape(gm, STATS_SUBLANES, gn, STATS_LANES)[:, 0]


def _write_stats(out, m, valid, stats_ref):
    is_diag = m > 0
    logdet = jnp.sum(jnp.where(is_diag, jnp.log(jnp.maximum(out, 1e-30)), 0.0))
    l1 = jnp.sum(jnp.where(is_diag, 0.0, jnp.abs(out)))
    sumsq = jnp.sum(out * out)
    min_diag = jnp.min(jnp.where(is_diag, out, jnp.inf))
    nnz = jnp.sum(((out != 0.0) & valid).astype(stats_ref.dtype))
    lane = jax.lax.broadcasted_iota(
        jnp.int32, (STATS_SUBLANES, STATS_LANES), 1)
    stats = jnp.where(lane == 0, logdet, 0.0)
    stats = jnp.where(lane == 1, l1, stats)
    stats = jnp.where(lane == 2, sumsq, stats)
    stats = jnp.where(lane == 3, min_diag, stats)
    stats = jnp.where(lane == 4, nnz, stats)
    stats_ref[...] = stats.astype(stats_ref.dtype)


def _tile_valid(shape, nrows, ncols):
    # mask out-of-bounds lanes of edge tiles (padding must not reach the
    # reductions)
    bm, bn = shape
    grow = pl.program_id(0) * bm + jax.lax.broadcasted_iota(
        jnp.int32, (bm, bn), 0)
    gcol = pl.program_id(1) * bn + jax.lax.broadcasted_iota(
        jnp.int32, (bm, bn), 1)
    return (grow < nrows) & (gcol < ncols)


def _kernel(alpha_ref, z_ref, mask_ref, out_ref, stats_ref, *, nrows, ncols):
    valid = _tile_valid(z_ref.shape, nrows, ncols)
    z = jnp.where(valid, z_ref[...], 0.0)
    m = jnp.where(valid, mask_ref[...], 0.0)
    alpha = alpha_ref[0]
    st = jnp.sign(z) * jnp.maximum(jnp.abs(z) - alpha, 0.0)
    out = st * (1.0 - m) + z * m
    out_ref[...] = out
    _write_stats(out, m, valid, stats_ref)


def _kernel_weighted(alpha_ref, z_ref, mask_ref, w_ref, out_ref, stats_ref,
                     *, nrows, ncols):
    valid = _tile_valid(z_ref.shape, nrows, ncols)
    z = jnp.where(valid, z_ref[...], 0.0)
    m = jnp.where(valid, mask_ref[...], 0.0)
    w = jnp.where(valid, w_ref[...], 0.0)
    alpha = alpha_ref[0]
    # inf weights must force exact zeros even at alpha == 0 (inf*0 = nan)
    thr = jnp.where(jnp.isinf(w), jnp.inf, alpha * w)
    st = jnp.sign(z) * jnp.maximum(jnp.abs(z) - thr, 0.0)
    out = st * (1.0 - m) + z * m
    out_ref[...] = out
    _write_stats(out, m, valid, stats_ref)


def kernel_layout(m: int, n: int, *, weighted: bool = False,
                  block=DEFAULT_BLOCK) -> dict:
    """Grid + BlockSpec geometry of the fused-prox ``pallas_call``.

    The single source the wrapper below AND the CA4xx kernel verifier
    (``repro.analysis.pallaspass``, via ``kernels.manifest``) share: the
    verifier enumerates ``grid`` and evaluates every index map returned
    here, so a layout edit is checked exactly as it ships.  ``in_specs``
    lists the SMEM alpha table first, matching the operand order of the
    call; ``out_shapes`` are the logical (unpadded) output array shapes.
    The stats output holds one ``(STATS_SUBLANES, STATS_LANES)`` block per
    tile (see :func:`tile_stats`).
    """
    bm = min(block[0], m)
    bn = min(block[1], n)
    gm, gn = pl.cdiv(m, bm), pl.cdiv(n, bn)
    tile = pl.BlockSpec((bm, bn), lambda i, j: (i, j))
    in_specs = [pl.BlockSpec(memory_space=pltpu.SMEM), tile, tile]
    if weighted:
        in_specs.append(tile)
    return {
        "grid": (gm, gn),
        "in_specs": in_specs,
        "out_specs": [
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((STATS_SUBLANES, STATS_LANES), lambda i, j: (i, j)),
        ],
        "out_shapes": ((m, n), (gm * STATS_SUBLANES, gn * STATS_LANES)),
    }


@partial(jax.jit, static_argnames=("block", "interpret"))
def fused_prox_stats(z: jax.Array, diag_mask: jax.Array, alpha,
                     *, interpret: bool, weights=None,
                     block=DEFAULT_BLOCK):
    """Returns (out, logdet, l1_offdiag, sumsq, min_diag, block_nnz).

    ``block_nnz`` is the (grid_m, grid_n) per-tile nonzero count of the
    prox output — with ``block`` set to the matops block size it is the
    block-occupancy mask the sparse matmul dispatch consumes, harvested
    in the same HBM pass as the prox itself.

    ``weights`` (optional, (m, n)) switches the threshold to
    ``alpha * weights`` elementwise (the weighted-l1/adaptive-lasso lane);
    ``None`` keeps the scalar-broadcast fast path."""
    m, n = z.shape
    lay = kernel_layout(m, n, weighted=weights is not None, block=block)
    alpha_arr = jnp.asarray(alpha, z.dtype).reshape(1)
    stats_dtype = jnp.promote_types(z.dtype, STATS_MIN_DTYPE)
    out_shape = [
        jax.ShapeDtypeStruct(lay["out_shapes"][0], z.dtype),
        jax.ShapeDtypeStruct(lay["out_shapes"][1], stats_dtype),
    ]
    if not interpret:
        check_native_blocks(
            "fused_prox_stats", lay["in_specs"] + lay["out_specs"],
            [(1,)] + [(m, n)] * (len(lay["in_specs"]) - 1)
            + list(lay["out_shapes"]))
    kw = dict(grid=lay["grid"], in_specs=lay["in_specs"],
              out_specs=lay["out_specs"], out_shape=out_shape,
              interpret=interpret)
    if weights is None:
        out, stats = pl.pallas_call(
            partial(_kernel, nrows=m, ncols=n), **kw,
        )(alpha_arr, z, diag_mask)
    else:
        w = jnp.asarray(weights, z.dtype)
        if w.shape != z.shape:
            raise ValueError(
                f"weights shape {w.shape} must match the iterate shape "
                f"{z.shape}")
        out, stats = pl.pallas_call(
            partial(_kernel_weighted, nrows=m, ncols=n), **kw,
        )(alpha_arr, z, diag_mask, w)
    stats = tile_stats(stats)
    logdet = jnp.sum(stats[..., 0])
    l1 = jnp.sum(stats[..., 1])
    sumsq = jnp.sum(stats[..., 2])
    min_diag = jnp.min(stats[..., 3])
    block_nnz = stats[..., 4]
    return out, logdet, l1, sumsq, min_diag, block_nnz


@partial(jax.jit, static_argnames=("block", "interpret"))
def fused_prox(z: jax.Array, diag_mask: jax.Array, alpha,
               *, interpret: bool, weights=None, block=DEFAULT_BLOCK):
    """Prox only (no stats) — the distributed drivers' inner step."""
    return fused_prox_stats(z, diag_mask, alpha, weights=weights,
                            block=block, interpret=interpret)[0]
