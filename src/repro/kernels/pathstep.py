"""Fused path-step Pallas TPU megakernel for the batched compact engine.

One flat step of the batched path engine (``core.batch``) is: form the
CONCORD smooth gradient from the cached product W = Omega S, take the
prox candidate at the lane's trial step size, and reduce the acceptance
dot products ``<diff, grad>`` / ``<diff, diff>`` plus the penalty-side
objective partials.  Done as jnp ops that is five-plus HBM passes over
every lane's p^2 state per trial; this kernel streams each tile of the
lane-stacked state through VMEM ONCE and emits the candidate plus all
per-tile reduction partials in the same pass.  Only the candidate's new
aux product (a matmul) and the smooth objective assembled from these
partials stay outside.

Layout: the C lanes are stacked tall — Omega and W arrive as
``(C * p, p)`` — and the grid is ``(C * p/bs, p/bs)`` square tiles with
``bs`` a divisor of p.  For the TPU lowering ``bs`` must also be a
multiple of 128 (a tile edge that is not would be refused by Mosaic on
the stacked operand), so a p with no such divisor raises ``ValueError``
before dispatch; interpret mode takes any divisor.

The transposed-W term of the gradient needs tile ``(j, i mod p/bs)`` of
the SAME lane, fetched by a second BlockSpec on W
whose index map swaps the within-lane block coordinates (no transposed
copy of W is ever materialized).  Per-lane scalars ride in an SMEM
``(C, 3)`` table ``[tau, alpha = tau * lam1, lam2]`` indexed by the
lane id ``i // (p/bs)``.

Per-tile stats land in one (8, 128) block per tile, as in ``softthresh``
(lanes 0..4 = dot_dg, dot_dd, sumsq, l1_offdiag, nnz); the wrapper
sum-reduces them per lane, and the nnz lane is the occupancy harvest.  The
elementwise candidate is bit-identical to the jitted ``ref.py`` oracle
(eager oracle dispatch fuses multiply-adds differently and can differ by
one ulp); the stats differ from a flat ``jnp.sum`` only by tile-order
association (the oracle equality test uses f64 and a tight allclose).

SCAD/MCP penalties are not representable as one scalar threshold per
lane, so the engine only routes soft-threshold-family penalties here
(``PenaltySpec.pallas_ok``) and falls back to the jnp trial otherwise.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .softthresh import (
    STATS_LANES,
    STATS_MIN_DTYPE,
    STATS_SUBLANES,
    check_native_blocks,
    tile_stats,
)

#: preferred square tile edge; the actual edge is the largest divisor of
#: p not exceeding it (natively: the largest 128-multiple divisor)
DEFAULT_BLOCK = 256

#: lane width a native tile edge must be a multiple of
LANE = 128

#: stats lanes: [0]=<diff,grad> [1]=<diff,diff> [2]=||cand||_F^2
#: [3]=off-diagonal l1 of cand [4]=tile nnz of cand
N_STATS = 5


def _block_edge(p: int, block: int, native: bool = False) -> int:
    if native:
        for bs in range(max(block, LANE) // LANE * LANE, 0, -LANE):
            if p % bs == 0:
                return bs
        raise ValueError(
            f"fused_path_step: p={p} has no tile edge that is a multiple "
            f"of {LANE}, so the kernel cannot lower for the TPU; run the "
            f"batched path with use_pallas=False or pad p to a multiple "
            f"of {LANE}")
    bs = min(block, p)
    while p % bs:
        bs -= 1
    # no useful divisor (p prime, or coprime with everything <= block):
    # run the whole matrix as one tile rather than 1 x 1 confetti
    return p if bs == 1 and p > 1 else bs


def _tile_step(scal_ref, om, w, wt, wts, c, diag):
    """Shared per-tile math of both kernel bodies: gradient tile, prox
    candidate at the lane's tau, and the five reduction partials."""
    tau = scal_ref[c, 0]
    alpha = scal_ref[c, 1]
    lam2 = scal_ref[c, 2]
    grad = 0.5 * (w + wt) + lam2 * om
    grad = jnp.where(diag, grad - 1.0 / om, grad)
    z = om - tau * grad
    if wts is None:
        thr = alpha
    else:
        # inf weights force exact zeros even at alpha == 0 (inf*0 = nan)
        thr = jnp.where(jnp.isinf(wts), jnp.inf, alpha * wts)
    soft = jnp.sign(z) * jnp.maximum(jnp.abs(z) - thr, 0.0)
    cand = jnp.where(diag, z, soft)
    diff = cand - om
    return cand, (jnp.sum(diff * grad), jnp.sum(diff * diff),
                  jnp.sum(cand * cand),
                  jnp.sum(jnp.where(diag, 0.0, jnp.abs(cand))),
                  jnp.sum((cand != 0.0)))


def _write_stats(parts, stats_ref):
    shape = (STATS_SUBLANES, STATS_LANES)
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    stats = jnp.zeros(shape, stats_ref.dtype)
    for k, v in enumerate(parts):
        stats = jnp.where(lane == k, v.astype(stats_ref.dtype), stats)
    stats_ref[...] = stats


def _diag_tile(bs: int, gpm: int):
    """Within-tile diagonal mask: tile (i, j) holds diagonal entries iff
    its within-lane block row ``i mod gpm`` equals its block column."""
    i = pl.program_id(0)
    j = pl.program_id(1)
    on_diag_block = (i % gpm) == j
    r = jax.lax.broadcasted_iota(jnp.int32, (bs, bs), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (bs, bs), 1)
    return (r == c) & on_diag_block


def _kernel(scal_ref, om_ref, w_ref, wt_ref, out_ref, stats_ref, *,
            bs: int, gpm: int):
    c = pl.program_id(0) // gpm
    diag = _diag_tile(bs, gpm)
    cand, parts = _tile_step(scal_ref, om_ref[...], w_ref[...],
                             wt_ref[...].T, None, c, diag)
    out_ref[...] = cand
    _write_stats(parts, stats_ref)


def _kernel_weighted(scal_ref, om_ref, w_ref, wt_ref, wts_ref, out_ref,
                     stats_ref, *, bs: int, gpm: int):
    c = pl.program_id(0) // gpm
    diag = _diag_tile(bs, gpm)
    cand, parts = _tile_step(scal_ref, om_ref[...], w_ref[...],
                             wt_ref[...].T, wts_ref[...], c, diag)
    out_ref[...] = cand
    _write_stats(parts, stats_ref)


def kernel_layout(c_lanes: int, p: int, *, weighted: bool = False,
                  block: int = DEFAULT_BLOCK, native: bool = False) -> dict:
    """Grid + BlockSpec geometry of the path-step ``pallas_call``.

    Shared by the wrapper below and the CA4xx kernel verifier (via
    ``kernels.manifest``).  ``bs`` is the resolved tile edge (``native``
    picks a 128-multiple for the TPU lowering; otherwise the prime-p
    full-tile fallback of :func:`_block_edge` applies) and ``gpm`` the
    per-lane block count; the SMEM scalar table rides first in
    ``in_specs``, matching the call's operand order.
    """
    bs = _block_edge(p, block, native)
    gpm = p // bs
    gm, gn = c_lanes * gpm, gpm
    tile = pl.BlockSpec((bs, bs), lambda i, j: (i, j))
    # the transposed-W operand: within lane i // gpm, swap block coords
    tile_t = pl.BlockSpec(
        (bs, bs), lambda i, j: ((i // gpm) * gpm + j, i % gpm))
    in_specs = [pl.BlockSpec(memory_space=pltpu.SMEM), tile, tile, tile_t]
    if weighted:
        in_specs.append(tile)
    return {
        "grid": (gm, gn),
        "in_specs": in_specs,
        "out_specs": [
            pl.BlockSpec((bs, bs), lambda i, j: (i, j)),
            pl.BlockSpec((STATS_SUBLANES, STATS_LANES), lambda i, j: (i, j)),
        ],
        "out_shapes": ((c_lanes * p, p),
                       (gm * STATS_SUBLANES, gn * STATS_LANES)),
        "bs": bs,
        "gpm": gpm,
    }


@partial(jax.jit, static_argnames=("block", "interpret"))
def fused_path_step(omega: jax.Array, w: jax.Array, tau, lam1, lam2,
                    *, interpret: bool, weights=None,
                    block: int = DEFAULT_BLOCK):
    """One fused flat step for C stacked lanes.

    ``omega``/``w`` are (C, p, p) iterates and their cached aux products
    W = Omega S; ``tau``/``lam1``/``lam2`` are (C,) per-lane scalars.
    ``weights`` (optional (C, p, p)) switches the prox to the weighted-l1
    threshold.  Returns ``(cand, stats)`` with ``cand`` (C, p, p) the prox
    candidates and ``stats`` (C, 5) the per-lane reductions
    ``[<diff,grad>, <diff,diff>, ||cand||_F^2, l1_offdiag, nnz]``.
    """
    c_lanes, p, _ = omega.shape
    dtype = omega.dtype
    lay = kernel_layout(c_lanes, p, weighted=weights is not None,
                        block=block, native=not interpret)
    bs, gpm = lay["bs"], lay["gpm"]
    gm, gn = lay["grid"]
    scal = jnp.stack([
        jnp.broadcast_to(jnp.asarray(tau, dtype), (c_lanes,)),
        jnp.broadcast_to(jnp.asarray(tau * lam1, dtype), (c_lanes,)),
        jnp.broadcast_to(jnp.asarray(lam2, dtype), (c_lanes,)),
    ], axis=1)
    om2 = omega.reshape(c_lanes * p, p)
    w2 = w.reshape(c_lanes * p, p)
    stats_dtype = jnp.promote_types(dtype, STATS_MIN_DTYPE)
    out_shape = [
        jax.ShapeDtypeStruct(lay["out_shapes"][0], dtype),
        jax.ShapeDtypeStruct(lay["out_shapes"][1], stats_dtype),
    ]
    if not interpret:
        check_native_blocks(
            "fused_path_step", lay["in_specs"] + lay["out_specs"],
            [(c_lanes, 3)] + [(c_lanes * p, p)] * (len(lay["in_specs"]) - 1)
            + list(lay["out_shapes"]))
    kw = dict(grid=lay["grid"], in_specs=lay["in_specs"],
              out_specs=lay["out_specs"], out_shape=out_shape,
              interpret=interpret)
    if weights is None:
        cand, stats = pl.pallas_call(
            partial(_kernel, bs=bs, gpm=gpm), **kw)(scal, om2, w2, w2)
    else:
        wts = jnp.asarray(weights, dtype)
        if wts.shape != omega.shape:
            raise ValueError(f"weights shape {wts.shape} must match the "
                             f"lane-stacked iterate shape {omega.shape}")
        cand, stats = pl.pallas_call(
            partial(_kernel_weighted, bs=bs, gpm=gpm),
            **kw)(scal, om2, w2, w2, wts.reshape(c_lanes * p, p))
    per_lane = tile_stats(stats).reshape(
        c_lanes, gpm, gn, STATS_LANES).sum(axis=(1, 2))
    return cand.reshape(c_lanes, p, p), per_lane[:, :N_STATS]
