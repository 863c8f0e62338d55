"""1.5D communication-avoiding matmuls + transposes (paper Algorithm 4, S.2).

Two flavors of the rotation:

  * gather-flavor — the rotating operand R contributes different OUTPUT
    blocks each round (the contraction is fully local).  Used for
    S = X^T X (Cov), W = Omega S (Cov), Z = Y X (Obs).  After
    n_R/c_F rounds each team allgathers its panel (Alg. 4 line 8).

  * reduce-flavor — the rotating operand R contributes different slices of
    the CONTRACTION dim; partial products accumulate into a stationary
    output, finished with a psum over the team layer (Alg. 4 line 8).
    Used for Y = Omega X^T (Obs).

The ring shift is one lax.ppermute per round (TPU: one ICI neighbor hop);
the shift and the local dot both read the same buffer, so they have no data
dependence and XLA's latency-hiding scheduler overlaps them (the paper's
overlap of MPI_Isend with dgemm).

All functions with the ``_local`` suffix run INSIDE shard_map (shards in,
shards out, collectives inline) so the distributed CONCORD loop can call
them from within one big shard_map'd while_loop.  The module-level
functions are standalone shard_map wrappers used by tests and benchmarks.

Replication-aware transposes implement Lemma 3.2: with replication c, the
all-to-all neighborhood shrinks from P to P/c^2 (each replica layer
exchanges only a 1/c slice, finished by an allgather over the layer).
"""
from __future__ import annotations

from functools import partial

import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..core import matops
from . import compat
from .grid import AXES, Grid1p5D

# Layout shorthands (see grid.py):
#   X-like     : global (r, p) col-blocked  -> spec P(None, ("i","j")) or
#                global (p, r) row-blocked  -> spec P(("i","j"), None)
#   Omega-like : global (p, r) row-blocked  -> spec P(("i","k"), None)
SPEC_XCOL = P(None, ("i", "j"))
SPEC_XROW = P(("i", "j"), None)
SPEC_OM = P(("i", "k"), None)


def _team_x():
    return lax.axis_index("i") * compat.axis_size("j") + lax.axis_index("j")


def _team_om():
    return lax.axis_index("i") * compat.axis_size("k") + lax.axis_index("k")


def _ring_pos_om(grid: Grid1p5D):
    return _team_om() * grid.c_omega + lax.axis_index("j")


# ---------------------------------------------------------------------------
# gather-flavor rotation (runs inside shard_map)
# ---------------------------------------------------------------------------

def rot_gather_local(r_blk, f_loc, grid: Grid1p5D, *, n_r: int,
                     canonical: str, ring: str, r_mask=None,
                     policy: matops.MatmulPolicy | None = None):
    """Rotate R around `ring`, multiplying with the fixed local block.

    ring="x":      tile = r_visit @ f_loc   (R row-block x fixed col-block)
                   team layer = "k", c_F = c_x
    ring="omega":  tile = f_loc @ r_visit   (fixed row-block x R col-block)
                   team layer = "j", c_F = c_omega

    With ``r_mask`` (the rotating operand's block-occupancy mask, ring="x"
    only — i.e. R is the Ω iterate), the mask travels around the ring with
    R and every local tile product routes through the block-sparse
    dispatch of ``core.matops``, skipping absent blocks past the policy's
    density crossover.

    Returns the stacked tile sequence (n_r, *tile.shape) reordered so index
    b holds the tile of R block b (the caller reshapes into a panel).
    """
    c_f = grid.c_x if ring == "x" else grid.c_omega
    layer_axis = "k" if ring == "x" else "j"
    if c_f < n_r and n_r % c_f:
        raise ValueError(f"need c_F | n_R (or c_F >= n_R): c_F={c_f}, n_R={n_r}")
    if r_mask is not None and ring != "x":
        raise ValueError("masked rotation is defined for ring='x' (the "
                         "rotating operand is the Omega iterate)")
    rounds = max(1, n_r // c_f)
    stagger = grid.stagger_perm(canonical, ring, n_r)
    shift = grid.shift_perm(ring, c_f)

    cur0 = lax.ppermute(r_blk, AXES, stagger)
    msk0 = None if r_mask is None else lax.ppermute(r_mask, AXES, stagger)

    def body(carry, _):
        cur, msk = carry
        nxt = lax.ppermute(cur, AXES, shift)
        nmsk = None if msk is None else lax.ppermute(msk, AXES, shift)
        if ring == "x":
            tile = matops.matmul(cur, f_loc, mask=msk, policy=policy)
        else:
            tile = matops.dot(f_loc, cur)
        return (nxt, nmsk), tile

    (_, _), tiles = lax.scan(body, (cur0, msk0), None,
                             length=rounds)            # (rounds, br, bc)
    g = lax.all_gather(tiles, layer_axis)                 # (c_f, rounds, ...)
    seq = jnp.swapaxes(g, 0, 1).reshape((rounds * c_f,) + tiles.shape[1:])
    team = _team_x() if ring == "x" else _team_om()
    # sequence position m holds the tile of block (team*c_f + m) mod n_r;
    # when c_f > n_r team members hold duplicates — the mod-take dedupes.
    idx = jnp.mod(jnp.arange(n_r) - team * c_f, n_r)
    return jnp.take(seq, idx, axis=0)


def xtx_local(x_loc, grid: Grid1p5D, *, scale=1.0):
    """S = scale * X^T X from the local X col-block (n, blk_x).  Cov line 2."""
    xt_loc = x_loc.T  # canonical X-like row-block of X^T
    seq = rot_gather_local(xt_loc, x_loc, grid, n_r=grid.n_x,
                           canonical="xlike", ring="x")
    blk = x_loc.shape[1]
    return seq.reshape(grid.n_x * blk, blk) * scale     # S col-panel (p, blk_x)


def omega_s_local(omega_rows, s_panel, grid: Grid1p5D, *, canonical="omegalike"):
    """W = Omega @ S.  omega_rows: R row-block; s_panel: fixed (p, blk_x).

    canonical="omegalike" for the standalone op (Omega in its canonical
    layout, n_om blocks); the Cov driver stores Omega X-like-transposed
    (c_omega == c_x) and passes canonical="xlike"."""
    n_r = grid.n_om if canonical == "omegalike" else grid.n_x
    seq = rot_gather_local(omega_rows, s_panel, grid, n_r=n_r,
                           canonical=canonical, ring="x")
    blk_r, blk_c = omega_rows.shape[0], s_panel.shape[1]
    return seq.reshape(n_r * blk_r, blk_c)              # W col-panel (p, blk_x)


def y_x_local(y_rows, x_loc, grid: Grid1p5D, *, scale=1.0):
    """Z = scale * Y @ X.  y_rows: fixed Omega-like (blk_om, n);
    x_loc: rotating X col-block (n, blk_x).  Obs line 4."""
    seq = rot_gather_local(x_loc, y_rows, grid, n_r=grid.n_x,
                           canonical="xlike", ring="omega")
    # seq: (n_x, blk_om, blk_x) with block v at index v -> concat on cols
    blk_om = y_rows.shape[0]
    z = jnp.transpose(seq, (1, 0, 2)).reshape(blk_om, -1)
    return z * scale                                    # Z row-block (blk_om, p)


# ---------------------------------------------------------------------------
# reduce-flavor rotation (runs inside shard_map)
# ---------------------------------------------------------------------------

def omega_xt_local(omega_rows, xt_loc, grid: Grid1p5D, *, scale=1.0,
                   omega_mask=None,
                   policy: matops.MatmulPolicy | None = None):
    """Y = scale * Omega @ X^T.  omega_rows: fixed Omega-like (blk_om, p);
    xt_loc: rotating X^T row-block (blk_x, n).  Obs lines 2/10.

    With ``omega_mask`` (the fixed operand's (blk_om/bs, p/bs) occupancy),
    each round gates the contracted Omega column-slice with the matching
    mask column-slice through the ``core.matops`` dispatch (requires the
    policy block size to divide blk_x)."""
    if omega_mask is not None and policy is None:
        raise ValueError("omega_mask requires a matops policy (they are "
                         "only meaningful together)")
    n_x, c_om = grid.n_x, grid.c_omega
    blk_om, p = omega_rows.shape
    blk_x, n = xt_loc.shape
    mcols_blk = None if omega_mask is None else blk_x // policy.block_size
    rounds = n_x // c_om
    stagger = grid.stagger_perm("xlike", "omega", n_x)
    shift = grid.shift_perm("omega", c_om)

    cur0 = lax.ppermute(xt_loc, AXES, stagger)
    v0 = jnp.mod(_ring_pos_om(grid), n_x).astype(jnp.int32)

    def body(carry, _):
        cur, acc, v = carry
        nxt = lax.ppermute(cur, AXES, shift)
        cols = lax.dynamic_slice(omega_rows, (jnp.int32(0), v * blk_x),
                                 (blk_om, blk_x))
        if omega_mask is None:
            acc = acc + matops.dot(cols, cur)
        else:
            mcols = lax.dynamic_slice(
                omega_mask, (jnp.int32(0), v * mcols_blk),
                (omega_mask.shape[0], mcols_blk))
            acc = acc + matops.matmul(cols, cur, mask=mcols, policy=policy)
        v = jnp.mod(v + c_om, n_x)
        return (nxt, acc, v), None

    acc0 = jnp.zeros((blk_om, n), dtype=jnp.result_type(omega_rows, xt_loc))
    (_, acc, _), _ = lax.scan(body, (cur0, acc0, v0), None, length=rounds)
    y = lax.psum(acc, "j")                              # finish team reduce
    return y * scale                                    # Y row-block (blk_om, n)


# ---------------------------------------------------------------------------
# replication-aware distributed transposes (Lemma 3.2)
# ---------------------------------------------------------------------------

def transpose_xlike_local(w_panel, grid: Grid1p5D):
    """(p, blk_x) col-panel of W  ->  (p, blk_x) col-panel of W^T.

    Each replica layer k exchanges only its 1/c_x row-slice (Lemma 3.2),
    finished by an allgather over "k"."""
    n_x, c_x = grid.n_x, grid.c_x
    p, blk = w_panel.shape
    sub = blk // c_x
    k = lax.axis_index("k")
    w3 = w_panel.reshape(n_x, blk, blk)
    mine = lax.dynamic_slice_in_dim(w3, k * sub, sub, axis=1)   # (n_x, sub, blk)
    rcv = lax.all_to_all(mine, ("i", "j"), split_axis=0, concat_axis=0,
                         tiled=True)                            # (n_x, sub, blk)
    rows = jnp.transpose(rcv, (1, 0, 2)).reshape(sub, p)        # W[t-rows k-slice, :]
    cols_t = rows.T                                             # (p, sub)
    g = lax.all_gather(cols_t, "k")                             # (c_x, p, sub)
    return jnp.transpose(g, (1, 0, 2)).reshape(p, blk)


def transpose_omegalike_local(z_rows, grid: Grid1p5D):
    """(blk_om, p) row-block of Z  ->  (blk_om, p) row-block of Z^T."""
    n_om, c_om = grid.n_om, grid.c_omega
    blk, p = z_rows.shape
    sub = blk // c_om
    j = lax.axis_index("j")
    z3 = z_rows.reshape(blk, n_om, blk)
    mine = lax.dynamic_slice_in_dim(z3, j * sub, sub, axis=0)   # (sub, n_om, blk)
    rcv = lax.all_to_all(mine, ("i", "k"), split_axis=1, concat_axis=1,
                         tiled=True)                            # (sub, n_om, blk)
    part = jnp.transpose(rcv, (2, 1, 0))                        # (blk, n_om, sub)
    g = lax.all_gather(part, "j")                               # (c_om, blk, n_om, sub)
    return jnp.transpose(g, (1, 2, 0, 3)).reshape(blk, p)


# ---------------------------------------------------------------------------
# standalone wrappers (own shard_map; used by tests, benchmarks, lm-head)
# ---------------------------------------------------------------------------

def _smap(grid, mesh, fn, in_specs, out_specs):
    from .compat import shard_map
    return shard_map(fn, mesh=mesh, in_specs=in_specs,
                     out_specs=out_specs, check_vma=False)


def xtx(x, grid: Grid1p5D, mesh, *, scale=1.0):
    """S = scale * X^T X.  x: (n, p) -> S: (p, p) X-like col-blocked."""
    fn = partial(xtx_local, grid=grid, scale=scale)
    return _smap(grid, mesh, fn, (SPEC_XCOL,), SPEC_XCOL)(x)


def omega_s(omega, s, grid: Grid1p5D, mesh):
    """W = Omega @ S.  omega: (p, p) Omega-like; s: (p, p) X-like col."""
    fn = partial(omega_s_local, grid=grid, canonical="omegalike")
    return _smap(grid, mesh, fn, (SPEC_OM, SPEC_XCOL), SPEC_XCOL)(omega, s)


def omega_xt(omega, x, grid: Grid1p5D, mesh, *, scale=1.0):
    """Y = scale * Omega @ X^T.  omega: (p, p) Omega-like; x: (n, p)."""
    def fn(om_loc, x_loc):
        return omega_xt_local(om_loc, x_loc.T, grid, scale=scale)
    return _smap(grid, mesh, fn, (SPEC_OM, SPEC_XCOL), SPEC_OM)(omega, x)


def y_x(y, x, grid: Grid1p5D, mesh, *, scale=1.0):
    """Z = scale * Y @ X.  y: (p, n) Omega-like rows; x: (n, p)."""
    fn = partial(y_x_local, grid=grid, scale=scale)
    return _smap(grid, mesh, fn, (SPEC_OM, SPEC_XCOL), SPEC_OM)(y, x)


def transpose_xlike(w, grid: Grid1p5D, mesh):
    fn = partial(transpose_xlike_local, grid=grid)
    return _smap(grid, mesh, fn, (SPEC_XCOL,), SPEC_XCOL)(w)


def transpose_omegalike(z, grid: Grid1p5D, mesh):
    fn = partial(transpose_omegalike_local, grid=grid)
    return _smap(grid, mesh, fn, (SPEC_OM,), SPEC_OM)(z)


# ---------------------------------------------------------------------------
# declared collective schedules + analysis manifest (repro.analysis)
# ---------------------------------------------------------------------------
# Every ring product above DECLARES its schedule: which axes it may bind,
# which collective kinds it may post, how many rotation rounds its ring
# scan runs, what may travel the wire, and — exactly — how many bytes one
# invocation moves (core.costmodel.comm_volume, the analytic side of the
# paper's W term).  The comm engine (rules CA301-CA306) verifies the
# declarations against the schedule it extracts from the traced jaxpr, so
# a refactor that adds a collective, drops a round, or widens the wire
# dtype fails `python -m repro.analysis` before it ever runs distributed.

def _contract(entry, flavor, *, kinds, masked=False, block_size=None,
              canonical=None):
    from ..core.costmodel import comm_volume
    from .contract import CommContract

    def vol(**kw):
        return comm_volume(flavor=flavor, masked=masked,
                           block_size=block_size, canonical=canonical, **kw)

    return CommContract(
        entry=entry, axes=AXES, kinds=kinds,
        rounds=lambda **kw: vol(**kw).rounds,
        wire=("operand", "mask") if masked else ("operand",),
        volume=lambda **kw: vol(**kw).total,
        volume_class=("ring+allgather" if flavor != "omega_xt"
                      else "ring+psum") + (" masked" if masked else ""))


COMM_CONTRACT = {
    "xtx_local": _contract(
        "comm.matmul1p5d.xtx_local", "xtx",
        kinds=("ppermute", "all_gather")),
    "omega_s_local": _contract(
        "comm.matmul1p5d.omega_s_local", "omega_s",
        kinds=("ppermute", "all_gather")),
    "y_x_local": _contract(
        "comm.matmul1p5d.y_x_local", "y_x",
        kinds=("ppermute", "all_gather")),
    "omega_xt_local": _contract(
        "comm.matmul1p5d.omega_xt_local", "omega_xt",
        kinds=("ppermute", "psum")),
}

#: the representative multi-device schedule every entry traces: P=8 with
#: both replication factors ON (c_x = c_omega = 2) so staggers, shifts
#: and team finishes all actually move bytes; p % P == 0 keeps every
#: layout constraint (grid.pad_p)
_TRACE_GRID = dict(n_devices=8, c_x=2, c_omega=2)
_TRACE_P, _TRACE_N = 32, 12


def _trace_setup():
    grid = Grid1p5D(**_TRACE_GRID)
    env = (("i", grid.n_i), ("j", grid.c_omega), ("k", grid.c_x))
    params = dict(p=_TRACE_P, n=_TRACE_N, dtype="float64", **_TRACE_GRID)
    return grid, env, params


def _entry_xtx():
    grid, env, _ = _trace_setup()
    x_loc = jnp.linspace(-1.0, 1.0, _TRACE_N * (_TRACE_P // grid.n_x),
                         dtype=jnp.float64).reshape(_TRACE_N, -1)
    return {"fn": lambda x: xtx_local(x, grid), "args": (x_loc,),
            "axis_env": env}


def _entry_omega_s():
    grid, env, _ = _trace_setup()
    blk_om, blk_x = _TRACE_P // grid.n_om, _TRACE_P // grid.n_x
    om = jnp.linspace(0.0, 1.0, blk_om * _TRACE_P,
                      dtype=jnp.float64).reshape(blk_om, _TRACE_P)
    s = jnp.linspace(0.0, 1.0, _TRACE_P * blk_x,
                     dtype=jnp.float64).reshape(_TRACE_P, blk_x)
    return {"fn": lambda a, b: omega_s_local(a, b, grid,
                                             canonical="omegalike"),
            "args": (om, s), "axis_env": env}


def _entry_y_x():
    grid, env, _ = _trace_setup()
    blk_om, blk_x = _TRACE_P // grid.n_om, _TRACE_P // grid.n_x
    y = jnp.ones((blk_om, _TRACE_N), jnp.float64)
    x_loc = jnp.ones((_TRACE_N, blk_x), jnp.float64)
    return {"fn": lambda a, b: y_x_local(a, b, grid), "args": (y, x_loc),
            "axis_env": env}


def _entry_omega_xt():
    grid, env, _ = _trace_setup()
    blk_om, blk_x = _TRACE_P // grid.n_om, _TRACE_P // grid.n_x
    om = jnp.ones((blk_om, _TRACE_P), jnp.float64)
    xt = jnp.ones((blk_x, _TRACE_N), jnp.float64)
    return {"fn": lambda a, b: omega_xt_local(a, b, grid), "args": (om, xt),
            "axis_env": env}


def _comm(fn_name):
    _, _, params = _trace_setup()
    return {"contract": COMM_CONTRACT[fn_name], "params": params}


_PATH = "src/repro/comm/matmul1p5d.py"
ANALYSIS_ENTRIES = [
    {"name": "comm.matmul1p5d.xtx_ring", "path": _PATH,
     "axis_names": AXES, "build": _entry_xtx,
     "comm": lambda: _comm("xtx_local")},
    {"name": "comm.matmul1p5d.omega_s_ring", "path": _PATH,
     "axis_names": AXES, "build": _entry_omega_s,
     "comm": lambda: _comm("omega_s_local")},
    {"name": "comm.matmul1p5d.y_x_ring", "path": _PATH,
     "axis_names": AXES, "build": _entry_y_x,
     "comm": lambda: _comm("y_x_local")},
    {"name": "comm.matmul1p5d.omega_xt_ring", "path": _PATH,
     "axis_names": AXES, "build": _entry_omega_xt,
     "comm": lambda: _comm("omega_xt_local")},
]
