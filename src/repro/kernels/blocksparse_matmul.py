"""Block-sparse x dense Pallas TPU matmul — the TPU adaptation of the
paper's sparse-dense multiply (W = Omega S, Y = Omega X^T).

The CPU code calls MKL CSR x dense; TPU has no scalar-gather sparse units,
so sparsity is expressed at MXU granularity: Omega is stored as block-CSR
with 128-aligned tiles and the kernel simply SKIPS absent tiles.  The cost
model's d (nnz per row) becomes block density, and the flop saving is
(1 - block_density) of the dense product, realized on the systolic array
with zero gather overhead.

Layout: values (nb, bs, bs) with COO-expanded, row-major-sorted
(row_idx, col_idx) int32 vectors (every block-row holds >= 1 entry — the
builder inserts a zero block for empty rows so output initialization
always fires).  Grid is (col_tiles, nb): for a fixed output column tile we
sweep the nonzero blocks in CSR order, so all contributions to one output
tile are consecutive grid steps and accumulate in VMEM; the output block
switches exactly when row_idx changes.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(row_ref, col_ref, v_ref, b_ref, o_ref):
    i = pl.program_id(1)                      # nnz-block index (fast dim)

    @pl.when((i == 0) | (row_ref[i] != row_ref[jnp.maximum(i - 1, 0)]))
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    v = v_ref[0]                              # (bs, bs)
    b = b_ref[...]                            # (bs, bn)
    o_ref[...] += jnp.dot(v, b, preferred_element_type=o_ref.dtype)


def kernel_layout(nb: int, bs: int, p: int, m: int,
                  *, block_n: int = 256) -> dict:
    """Grid + BlockSpec geometry of the block-sparse ``pallas_call``.

    Shared by the wrapper below and the CA4xx kernel verifier (via
    ``kernels.manifest``).  ``in_specs`` covers the two non-prefetch
    operands (values, b); the ``row``/``col`` scalar-prefetch vectors are
    appended to every index-map call, which is how the out-spec scatters
    on ``row[i]`` — the aliasing hazard CA401 enumerates concretely.
    """
    bn = min(block_n, m)
    nt = pl.cdiv(m, bn)
    return {
        "grid": (nt, nb),
        "num_scalar_prefetch": 2,
        "in_specs": [
            pl.BlockSpec((1, bs, bs), lambda j, i, row, col: (i, 0, 0)),
            pl.BlockSpec((bs, bn), lambda j, i, row, col: (col[i], j)),
        ],
        "out_specs": pl.BlockSpec(
            (bs, bn), lambda j, i, row, col: (row[i], j)),
        "out_shapes": ((p, m),),
    }


def _validate_row_runs(row_idx) -> None:
    """The CA401 aliasing contract, enforced at trace time on concrete
    ids: each block-row id must appear as ONE contiguous run.  The kernel
    re-zeroes its output tile whenever ``row_idx`` changes, so a row id
    that returns after an interruption would silently clobber the partial
    sums already flushed for that row.  Abstract ids (inside an outer
    jit/vmap) skip the check — the static verifier covers the manifest
    configs there."""
    if isinstance(row_idx, jax.core.Tracer):
        return
    rows = np.asarray(row_idx)
    if rows.size <= 1:
        return
    change = np.flatnonzero(np.diff(rows) != 0)
    run_starts = rows[np.concatenate(([0], change + 1))]
    uniq, counts = np.unique(run_starts, return_counts=True)
    dupes = uniq[counts > 1]
    if dupes.size:
        raise ValueError(
            f"blocksparse_matmul row_idx revisits block-row(s) "
            f"{dupes.tolist()} non-contiguously: all entries of a "
            f"block-row must form one contiguous run (CSR row-major "
            f"order, see dense_to_block_csr), otherwise the kernel's "
            f"output tile for that row is re-zeroed on the second visit "
            f"and the first visit's accumulation is silently lost")


@partial(jax.jit, static_argnames=("block_n", "interpret"))
def _blocksparse_matmul(values: jax.Array, row_idx: jax.Array,
                        col_idx: jax.Array, b: jax.Array,
                        *, interpret: bool, block_n: int = 256):
    nb, bs, _ = values.shape
    p, m = b.shape
    lay = kernel_layout(nb, bs, p, m, block_n=block_n)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=lay["num_scalar_prefetch"],
        grid=lay["grid"],
        in_specs=lay["in_specs"],
        out_specs=lay["out_specs"],
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(lay["out_shapes"][0], b.dtype),
        interpret=interpret,
    )(row_idx, col_idx, values, b)


def blocksparse_matmul(values: jax.Array, row_idx: jax.Array,
                       col_idx: jax.Array, b: jax.Array,
                       *, interpret: bool, block_n: int = 256):
    """C = A @ B with A in block-CSR ((nb, bs, bs) + sorted row/col ids).

    b: (p, m). Returns (p, m). Requires every block-row represented at
    least once AND each row id's entries contiguous (CSR row-major order;
    see dense_to_block_csr in ref.py) — concrete ``row_idx`` violating
    the contiguity contract raises ValueError at trace time.
    """
    _validate_row_runs(row_idx)
    return _blocksparse_matmul(values, row_idx, col_idx, b,
                               block_n=block_n, interpret=interpret)
