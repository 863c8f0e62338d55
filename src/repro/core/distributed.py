"""Distributed HP-CONCORD drivers (paper Algorithms 2 and 3).

Both variants run the *entire* proximal-gradient solve (outer loop + line
search) inside one ``shard_map`` over the 1.5D grid mesh, so the whole fit
lowers to a single XLA program with the communication-avoiding collectives
(ring ppermutes, team allgathers/psums, replication-aware transposes)
inlined.  The control flow is the generic ``prox_gradient`` loop from
``core.prox``; only the ``VariantOps`` bundle differs:

  Cov  (Algorithm 2) — per-device state is an X-like column panel.
    aux_of  : W = Omega @ S          1.5D gather-rotation of Omega
                                     (stored as the local transpose of the
                                     column panel — valid because the
                                     iterates are symmetric; this is the
                                     paper's Figure-1 "local transpose")
    grad_of : W^T via the replication-aware distributed transpose
    S = X^T X / n is computed ONCE up front by rotating X^T (line 2).

  Obs  (Algorithm 3) — per-device state is an Omega-like row block.
    aux_of  : Y = Omega @ X^T        1.5D reduce-rotation of X^T
    grad_of : Z = Y @ X / n          1.5D gather-rotation of X,
              Z^T via the distributed transpose
    S is never formed.

Padding.  The layouts need p divisible by P.  We pad to p' = pad_p(p) and
*freeze* the padded coordinates: the padded diagonal starts at 1 and its
gradient is masked to zero, off-block entries are zero and stay zero
because the padded block of S (resp. the padded columns of X) is zero, so
the real (p x p) block of every iterate is EXACTLY the unpadded iterate.
The ridge term subtracts the constant contributed by the frozen diagonal
so reported objectives match the reference solver.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..comm import matmul1p5d as mm
from ..comm import sparse1p5d as sp
from ..comm.compat import axis_size, shard_map
from ..comm.grid import Grid1p5D
from . import matops
from .costmodel import Machine, ProblemShape, tune
from .penalty import PenaltySpec, normalize_penalty
from .prox import ProxResult, VariantOps, guard_nonpos_diag, prox_gradient

SPEC_XCOL = mm.SPEC_XCOL
SPEC_OM = mm.SPEC_OM


class FitResult(NamedTuple):
    omega: jax.Array
    iters: jax.Array
    ls_total: jax.Array
    converged: jax.Array    # genuine delta < tol exit (never set on a stall)
    g_final: jax.Array
    variant: str
    grid: Grid1p5D
    block_density: jax.Array | float = 1.0
    stalled: jax.Array | bool = False   # line search exhausted without accept


def _shard_policy(policy: matops.MatmulPolicy | None,
                  shard_shape: tuple[int, int],
                  also_divide: tuple[int, ...] = ()
                  ) -> matops.MatmulPolicy | None:
    """The policy actually usable on a per-device Ω shard: the mask is
    rotated/sliced at block granularity inside the ring loops, so the block
    grid must tile the shard (and any ``also_divide`` slice widths) exactly;
    otherwise fall back to dense."""
    if policy is None or not policy.enabled:
        return None
    bs = policy.block_size
    if any(d % bs for d in tuple(shard_shape) + tuple(also_divide)):
        warnings.warn(
            f"sparse_matmul block_size={bs} does not tile the local Omega "
            f"shard {shard_shape} (slice widths {also_divide}); falling back "
            f"to the dense path (pick a block size dividing p_pad/n_blocks)",
            stacklevel=3)
        return None
    return policy


# ---------------------------------------------------------------------------
# local-layout helpers (run inside shard_map)
# ---------------------------------------------------------------------------

def _block_x():
    """X-like block index t = i*c_omega + j of this device."""
    return lax.axis_index("i") * axis_size("j") + lax.axis_index("j")


def _eye_panel_x(p_pad: int, blk: int, dtype):
    """Local X-like column panel of the identity: ones at (t*blk + r, r)."""
    t = _block_x()
    rows = jnp.arange(p_pad)[:, None]
    cols = jnp.arange(blk)[None, :]
    return (rows == t * blk + cols).astype(dtype)


def _eye_rows_om(p_pad: int, blk: int, dtype):
    """Local Omega-like row block of the identity: ones at (r, u*blk + r)."""
    u = _block_om()
    rows = jnp.arange(blk)[:, None]
    cols = jnp.arange(p_pad)[None, :]
    return (cols == u * blk + rows).astype(dtype)


def _block_om():
    """Omega-like block index u = i*c_x + k of this device."""
    return lax.axis_index("i") * axis_size("k") + lax.axis_index("k")


def _diag_mask_panel_x(p_pad: int, blk: int, p_real: int, dtype):
    """(diag mask, frozen-padded-diag mask) for an X-like column panel."""
    t = _block_x()
    rows = jnp.arange(p_pad)[:, None]
    cols = jnp.arange(blk)[None, :]
    gcol = t * blk + cols
    on_diag = (rows == gcol).astype(dtype)
    padded = (rows == gcol) & (gcol >= p_real)
    return on_diag, padded.astype(dtype)


def _diag_mask_rows_om(p_pad: int, blk: int, p_real: int, dtype):
    u = _block_om()
    rows = jnp.arange(blk)[:, None]
    cols = jnp.arange(p_pad)[None, :]
    grow = u * blk + rows
    on_diag = (cols == grow).astype(dtype)
    padded = (cols == grow) & (grow >= p_real)
    return on_diag, padded.astype(dtype)


def _local_diag_panel_x(panel, blk):
    """Extract this panel's diagonal entries: panel[t*blk + r, r]."""
    t = _block_x()
    r = jnp.arange(blk)
    rows3 = lax.dynamic_slice_in_dim(panel, t * blk, blk, axis=0)
    return rows3[r, r]


def _local_diag_rows_om(rows_blk, blk):
    """Extract diagonal entries of an Omega-like row block: rows[r, u*blk+r]."""
    u = _block_om()
    r = jnp.arange(blk)
    cols3 = lax.dynamic_slice_in_dim(rows_blk, u * blk, blk, axis=1)
    return cols3[r, r]


def _psum_x(v):
    """Global sum of a per-X-block quantity (blocks indexed by (i, j))."""
    return lax.psum(v, ("i", "j"))


def _psum_om(v):
    return lax.psum(v, ("i", "k"))


def _pmin_x(v):
    return lax.pmin(v, ("i", "j"))


def _pmin_om(v):
    return lax.pmin(v, ("i", "k"))


def _dist_sparse_ops(policy: matops.MatmulPolicy, use_pallas: bool, dtype,
                     diag_mask_of, psum, prox):
    """(prox_stats, mask_of, density_of) shared by the Cov and Obs drivers —
    only the diag-mask layout and the psum axes differ between variants."""
    bs = policy.block_size

    def prox_stats(z, pen, tau, data):
        if use_pallas and pen.pallas_ok:
            # occupancy harvested for free from the fused kernel's nnz lane
            from ..kernels import ops as kops
            out, _, _, _, _, bnnz = kops.fused_prox_stats(
                z, diag_mask_of(), tau * pen.lam1, weights=pen.weights,
                block=(bs, bs))
            return out, (bnnz > 0).astype(matops.MASK_DTYPE)
        out = prox(z, pen, tau, data)
        return out, matops.block_mask(out, bs)

    def mask_of(omega_loc, data):
        return matops.block_mask(omega_loc, bs)

    def density_of(mask):
        # numerator and denominator both count each Omega block once per
        # partitioning team, so replication layers cancel in the ratio
        nnz = psum(jnp.sum((mask > 0).astype(matops.DENSITY_DTYPE)))
        total = psum(jnp.asarray(mask.size, matops.DENSITY_DTYPE))
        return nnz / total

    return prox_stats, mask_of, density_of


# ---------------------------------------------------------------------------
# Cov variant (Algorithm 2)
# ---------------------------------------------------------------------------

def _cov_local_ops(grid: Grid1p5D, p_pad: int, p_real: int, lam2, dtype,
                   use_pallas: bool = False,
                   sparse_matmul: matops.MatmulPolicy | None = None
                   ) -> VariantOps:
    blk = p_pad // grid.n_x
    n_pad_diag = p_pad - p_real
    policy = _shard_policy(sparse_matmul, (p_pad, blk))

    def aux_of(omega_panel, data, mask=None):
        # Figure 1: local transpose converts the column panel to the row
        # block the rotation consumes (iterates are symmetric).
        omega_rows = omega_panel.T
        if mask is None:
            return mm.omega_s_local(omega_rows, data["s"], grid,
                                    canonical="xlike")
        return sp.omega_s_local_sparse(omega_rows, mask.T, data["s"], grid,
                                       canonical="xlike", policy=policy)

    def g_of(omega_panel, w_panel, data):
        diag = _local_diag_panel_x(omega_panel, blk)
        logdet = -_psum_x(jnp.sum(jnp.log(jnp.maximum(diag, 1e-30))))
        quad = 0.5 * _psum_x(jnp.sum(w_panel * omega_panel))
        ridge = 0.5 * lam2 * (
            _psum_x(jnp.sum(omega_panel * omega_panel)) - n_pad_diag)
        g = logdet + quad + ridge
        return guard_nonpos_diag(g, _pmin_x(jnp.min(diag)))

    def grad_of(omega_panel, w_panel, data):
        wt_panel = mm.transpose_xlike_local(w_panel, grid)
        diag = _local_diag_panel_x(omega_panel, blk)
        diag_mask, pad_mask = _diag_mask_panel_x(p_pad, blk, p_real, dtype)
        t = _block_x()
        inv = jnp.zeros((p_pad, blk), dtype)
        inv = lax.dynamic_update_slice_in_dim(
            inv, jnp.diag(1.0 / diag), t * blk, axis=0)
        grad = -inv + 0.5 * (w_panel + wt_panel) + lam2 * omega_panel
        return grad * (1.0 - pad_mask)            # freeze padded diagonal

    def dot(a, b):
        return _psum_x(jnp.sum(a * b))

    def prox(z, pen, tau, data):
        diag_mask, _ = _diag_mask_panel_x(p_pad, blk, p_real, dtype)
        if use_pallas and pen.pallas_ok:
            from ..kernels import ops as kops
            return kops.fused_prox(z, diag_mask, tau * pen.lam1,
                                   weights=pen.weights)
        return pen.prox(z, tau, diag_mask)

    if policy is None:
        return VariantOps(aux_of, g_of, grad_of, dot, prox)
    return VariantOps(aux_of, g_of, grad_of, dot, prox, *_dist_sparse_ops(
        policy, use_pallas, dtype,
        lambda: _diag_mask_panel_x(p_pad, blk, p_real, dtype)[0],
        _psum_x, prox))


# ---------------------------------------------------------------------------
# Obs variant (Algorithm 3)
# ---------------------------------------------------------------------------

def _obs_local_ops(grid: Grid1p5D, p_pad: int, p_real: int, n: int, lam2,
                   dtype, use_pallas: bool = False,
                   sparse_matmul: matops.MatmulPolicy | None = None
                   ) -> VariantOps:
    blk = p_pad // grid.n_om
    n_pad_diag = p_pad - p_real
    # the reduce-flavor rotation slices Omega at blk_x granularity, so the
    # mask slice must land on block boundaries too
    policy = _shard_policy(sparse_matmul, (blk, p_pad),
                           also_divide=(p_pad // grid.n_x,))

    def aux_of(omega_rows, data, mask=None):
        xt_loc = data["x"].T                      # local transpose
        if mask is None:
            return mm.omega_xt_local(omega_rows, xt_loc, grid)  # Y, unnorm.
        return sp.omega_xt_local_sparse(omega_rows, mask, xt_loc, grid,
                                        policy=policy)

    def g_of(omega_rows, y_rows, data):
        diag = _local_diag_rows_om(omega_rows, blk)
        logdet = -_psum_om(jnp.sum(jnp.log(jnp.maximum(diag, 1e-30))))
        quad = 0.5 * _psum_om(jnp.sum(y_rows * y_rows)) / n
        ridge = 0.5 * lam2 * (
            _psum_om(jnp.sum(omega_rows * omega_rows)) - n_pad_diag)
        g = logdet + quad + ridge
        return guard_nonpos_diag(g, _pmin_om(jnp.min(diag)))

    def grad_of(omega_rows, y_rows, data):
        z = mm.y_x_local(y_rows, data["x"], grid, scale=1.0 / n)
        zt = mm.transpose_omegalike_local(z, grid)
        diag = _local_diag_rows_om(omega_rows, blk)
        diag_mask, pad_mask = _diag_mask_rows_om(p_pad, blk, p_real, dtype)
        u = _block_om()
        inv = jnp.zeros((blk, p_pad), dtype)
        inv = lax.dynamic_update_slice_in_dim(
            inv, jnp.diag(1.0 / diag), u * blk, axis=1)
        grad = -inv + 0.5 * (z + zt) + lam2 * omega_rows
        return grad * (1.0 - pad_mask)

    def dot(a, b):
        return _psum_om(jnp.sum(a * b))

    def prox(z, pen, tau, data):
        diag_mask, _ = _diag_mask_rows_om(p_pad, blk, p_real, dtype)
        if use_pallas and pen.pallas_ok:
            from ..kernels import ops as kops
            return kops.fused_prox(z, diag_mask, tau * pen.lam1,
                                   weights=pen.weights)
        return pen.prox(z, tau, diag_mask)

    if policy is None:
        return VariantOps(aux_of, g_of, grad_of, dot, prox)
    return VariantOps(aux_of, g_of, grad_of, dot, prox, *_dist_sparse_ops(
        policy, use_pallas, dtype,
        lambda: _diag_mask_rows_om(p_pad, blk, p_real, dtype)[0],
        _psum_om, prox))


# ---------------------------------------------------------------------------
# shard_map drivers
# ---------------------------------------------------------------------------

def _scalar_specs():
    return ProxResult(omega=None, iters=P(), ls_total=P(), converged=P(),
                      g_final=P(), delta_final=P(), stalled=P(),
                      block_density=P())


def _pad_omega0(omega0, p: int, p_pad: int, dtype):
    """Pad a warm-start iterate with the frozen identity diagonal so the
    padded block behaves exactly like a cold start there.  (Cold starts
    never call this: the identity is built per-shard inside shard_map.)"""
    omega0 = jnp.asarray(omega0, dtype)
    if p_pad != p:
        omega0 = jnp.pad(omega0, ((0, p_pad - p), (0, p_pad - p)))
        pad_idx = jnp.arange(p, p_pad)
        omega0 = omega0.at[pad_idx, pad_idx].set(1.0)
    return omega0


def _pad_spec_weights(spec: PenaltySpec, p: int, p_pad: int,
                      dtype) -> PenaltySpec:
    """Cast the weight matrix to the solve dtype and zero-pad it to the
    grid-padded dimension (padded off-diagonal entries stay exactly zero
    whatever their weight, so the pad value is inert)."""
    if spec.weights is None:
        return spec
    w = jnp.asarray(spec.weights, dtype)
    if w.shape != (p, p):
        raise ValueError(
            f"penalty weights shape {w.shape} must match the problem "
            f"dimension ({p}, {p})")
    if p_pad != p:
        w = jnp.pad(w, ((0, p_pad - p), (0, p_pad - p)))
    return dataclasses.replace(spec, weights=w)


def _spec_partition(spec: PenaltySpec, mat_spec):
    """shard_map in_specs tree for a penalty spec: the (p_pad, p_pad)
    weight matrix shards with the Omega layout, scalars replicate."""
    return jax.tree.map(
        lambda leaf: mat_spec if getattr(leaf, "ndim", 0) == 2 else P(),
        spec)


#: dispatch-observer hook (``repro.obs.commwatch``): when set, the driver
#: announces every jit dispatch (before the call) and its result.  The
#: observer may re-trace the closure (``jax.make_jaxpr``) but must not
#: compile or execute anything — the solve itself is untouched.
_DISPATCH_OBSERVER = None


def set_dispatch_observer(observer):
    """Install ``observer`` (or None) on the driver dispatch hook; returns
    the previous observer so callers can restore it."""
    global _DISPATCH_OBSERVER
    prev = _DISPATCH_OBSERVER
    _DISPATCH_OBSERVER = observer
    return prev


def _dispatch(variant, fn, args, grid, meta):
    """Run one driver jit dispatch through the observer hook (no-op when
    no observer is installed)."""
    obs = _DISPATCH_OBSERVER
    token = None
    if obs is not None:
        token = obs.on_dispatch(variant, fn, args, grid, meta)
    res = jax.jit(fn)(*args)
    if obs is not None:
        obs.on_result(token, res)
    return res


def fit_cov(
    s: jax.Array,
    lam1: float | None = None,
    lam2: float = 0.0,
    *,
    grid: Grid1p5D,
    mesh=None,
    tol: float = 1e-5,
    max_iters: int = 500,
    max_ls: int = 30,
    warm_start_tau: bool = False,
    use_pallas: bool = False,
    omega0: jax.Array | None = None,
    penalty: PenaltySpec | str | None = None,
    sparse_matmul: matops.MatmulPolicy | None = None,
) -> FitResult:
    """Distributed Cov solve (Algorithm 2). ``s`` is the (p, p) sample cov.
    ``omega0`` optionally warm-starts the iterates (e.g. along a lam1 path).
    ``penalty`` swaps the prox operator (``core.penalty``): scalar penalty
    parameters travel replicated through the shard_map, a weighted-l1
    weight matrix is sharded with the Omega panel layout.  Legacy
    ``lam1``/``lam2`` floats build the equivalent l1 spec.
    ``sparse_matmul`` routes the W = Omega S rotation through the
    block-sparse local products of ``comm.sparse1p5d``."""
    if grid.c_x != grid.c_omega:
        raise ValueError("Cov keeps Omega in the X-like layout: c_x == c_omega")
    mesh = mesh or grid.make_mesh()
    p = s.shape[0]
    p_pad = grid.pad_p(p)
    dtype = s.dtype
    spec = _pad_spec_weights(normalize_penalty(penalty, lam1, lam2),
                             p, p_pad, dtype)
    if p_pad != p:
        s = jnp.pad(s, ((0, p_pad - p), (0, p_pad - p)))
    blk = p_pad // grid.n_x
    ops = _cov_local_ops(grid, p_pad, p, jnp.asarray(spec.lam2, dtype),
                         dtype, use_pallas, sparse_matmul)
    spec_parts = _spec_partition(spec, SPEC_XCOL)

    def solve_local(om0_panel, s_panel, pen):
        return prox_gradient(
            om0_panel, {"s": s_panel}, ops, penalty=pen, tol=tol,
            max_iters=max_iters, max_ls=max_ls, warm_start_tau=warm_start_tau)

    specs = _scalar_specs()._replace(omega=SPEC_XCOL)
    if omega0 is None:
        # cold start: build the identity panel per shard (never materialize
        # the full p_pad^2 identity on one device)
        def local(s_panel, pen):
            return solve_local(_eye_panel_x(p_pad, blk, dtype), s_panel, pen)

        fn = shard_map(local, mesh=mesh, in_specs=(SPEC_XCOL, spec_parts),
                       out_specs=ProxResult(*specs), check_vma=False)
        args = (s, spec)
    else:
        def local(s_panel, pen, om0_panel):
            return solve_local(om0_panel, s_panel, pen)

        fn = shard_map(local, mesh=mesh,
                       in_specs=(SPEC_XCOL, spec_parts, SPEC_XCOL),
                       out_specs=ProxResult(*specs), check_vma=False)
        args = (s, spec, _pad_omega0(omega0, p, p_pad, dtype))
    res = _dispatch("cov", fn, args, grid,
                    {"p": p, "p_pad": p_pad, "n": None,
                     "dtype": jnp.dtype(dtype).name,
                     "sparse": ops.prox_stats is not None})
    return FitResult(res.omega[:p, :p], res.iters, res.ls_total,
                     res.converged, res.g_final, "cov", grid,
                     res.block_density, res.stalled)


def fit_obs(
    x: jax.Array,
    lam1: float | None = None,
    lam2: float = 0.0,
    *,
    grid: Grid1p5D,
    mesh=None,
    tol: float = 1e-5,
    max_iters: int = 500,
    max_ls: int = 30,
    warm_start_tau: bool = False,
    use_pallas: bool = False,
    omega0: jax.Array | None = None,
    penalty: PenaltySpec | str | None = None,
    sparse_matmul: matops.MatmulPolicy | None = None,
) -> FitResult:
    """Distributed Obs solve (Algorithm 3). ``x`` is the (n, p) data matrix.
    ``omega0`` optionally warm-starts the iterates (e.g. along a lam1 path).
    ``penalty`` swaps the prox operator (``core.penalty``); a weighted-l1
    weight matrix is sharded with the Omega row-block layout.  Legacy
    ``lam1``/``lam2`` floats build the equivalent l1 spec.
    ``sparse_matmul`` routes the Y = Omega X^T rotation through the
    block-sparse local products of ``comm.sparse1p5d``."""
    mesh = mesh or grid.make_mesh()
    n, p = x.shape
    p_pad = grid.pad_p(p)
    dtype = x.dtype
    spec = _pad_spec_weights(normalize_penalty(penalty, lam1, lam2),
                             p, p_pad, dtype)
    if p_pad != p:
        x = jnp.pad(x, ((0, 0), (0, p_pad - p)))
    blk = p_pad // grid.n_om
    ops = _obs_local_ops(grid, p_pad, p, n, jnp.asarray(spec.lam2, dtype),
                         dtype, use_pallas, sparse_matmul)
    spec_parts = _spec_partition(spec, SPEC_OM)

    def solve_local(om0_rows, x_loc, pen):
        return prox_gradient(
            om0_rows, {"x": x_loc}, ops, penalty=pen, tol=tol,
            max_iters=max_iters, max_ls=max_ls, warm_start_tau=warm_start_tau)

    specs = _scalar_specs()._replace(omega=SPEC_OM)
    if omega0 is None:
        def local(x_loc, pen):
            return solve_local(_eye_rows_om(p_pad, blk, dtype), x_loc, pen)

        fn = shard_map(local, mesh=mesh, in_specs=(SPEC_XCOL, spec_parts),
                       out_specs=ProxResult(*specs), check_vma=False)
        args = (x, spec)
    else:
        def local(x_loc, pen, om0_rows):
            return solve_local(om0_rows, x_loc, pen)

        fn = shard_map(local, mesh=mesh,
                       in_specs=(SPEC_XCOL, spec_parts, SPEC_OM),
                       out_specs=ProxResult(*specs), check_vma=False)
        args = (x, spec, _pad_omega0(omega0, p, p_pad, dtype))
    res = _dispatch("obs", fn, args, grid,
                    {"p": p, "p_pad": p_pad, "n": n,
                     "dtype": jnp.dtype(dtype).name,
                     "sparse": ops.prox_stats is not None})
    return FitResult(res.omega[:p, :p], res.iters, res.ls_total,
                     res.converged, res.g_final, "obs", grid,
                     res.block_density, res.stalled)


# ---------------------------------------------------------------------------
# High-level estimator — the paper's cost-model-driven front door
# ---------------------------------------------------------------------------

def estimate_density(p: int, n: int, lam1: float) -> float:
    """Crude prior for d (avg nnz/row of the iterates) used by the tuner
    before any fit exists: heavier penalty -> sparser iterates."""
    return float(min(p, max(2.0, 0.05 * p / max(lam1, 1e-2))))


def fit(
    x: jax.Array | None = None,
    s: jax.Array | None = None,
    *,
    lam1: float,
    lam2: float = 0.0,
    variant: str = "auto",
    n_devices: int | None = None,
    c_x: int | None = None,
    c_omega: int | None = None,
    machine: Machine | None = None,
    n_samples: int | None = None,
    **kw,
) -> FitResult:
    """Deprecated shim — use :mod:`repro.estimator` (``ConcordEstimator`` or
    ``repro.estimator.fit``), which adds backend selection, warm starts and
    rich fit reports on top of the same cost-model dispatch.

    Pass ``x`` (n, p) to allow either variant, or only ``s`` (p, p) to force
    Cov. ``c_x``/``c_omega`` pin the replication factors (e.g. for the
    Figure-3 sweep); otherwise the tuner picks them.
    """
    warnings.warn(
        "distributed.fit is deprecated; use repro.estimator.ConcordEstimator "
        "or repro.estimator.fit", DeprecationWarning, stacklevel=2)
    if x is None and s is None:
        raise ValueError("pass x or s")
    P_ = n_devices or len(jax.devices())
    p = (x if x is not None else s).shape[-1]
    n = x.shape[0] if x is not None else (n_samples or p)
    m = machine or Machine()
    shape = ProblemShape(p=p, n=n, d=estimate_density(p, n, lam1))

    pinned_cx, pinned_co = c_x is not None, c_omega is not None
    user_pinned = pinned_cx or pinned_co
    if variant == "auto":
        variants = ("cov", "obs") if x is not None else ("cov",)
        best = tune(shape, P_, m, variants)
        variant = best.variant
        c_x = c_x if c_x is not None else best.c_x
        c_omega = c_omega if c_omega is not None else best.c_omega
    c_x = c_x or 1
    c_omega = c_omega or 1
    if variant == "cov":
        if pinned_co and c_omega != c_x:
            # same error as estimator.backends._check_grid — a pinned
            # c_omega must not be silently coerced to c_x
            raise ValueError(
                f"Cov keeps Omega in the X-like layout, so c_x must equal "
                f"c_omega (got c_x={c_x}, c_omega={c_omega})")
        c_omega = c_x  # Cov keeps Omega X-like
        if c_x * c_omega > P_ or P_ % (c_x * c_omega):
            if user_pinned:
                # Same error as estimator.backends._check_grid: never
                # silently rewrite a USER-pinned replication layout (the
                # old behaviour reset it to 1x1 behind the caller's back).
                raise ValueError(
                    f"replication c_x*c_omega={c_x * c_omega} must divide "
                    f"n_devices={P_} (got c_x={c_x}, c_omega={c_omega})")
            # tuner-derived factors may become infeasible after the Cov
            # c_omega = c_x coercion; repairing the tuner's own choice is
            # not a user-visible rewrite
            c_x = c_omega = 1
        grid = Grid1p5D(P_, c_x, c_omega)
        s_mat = s if s is not None else (x.T @ x) / n
        return fit_cov(s_mat, lam1, lam2, grid=grid, **kw)
    grid = Grid1p5D(P_, c_x, c_omega)
    if x is None:
        raise ValueError("Obs variant requires the data matrix x")
    return fit_obs(x, lam1, lam2, grid=grid, **kw)


def fit_path(
    x: jax.Array,
    lam1_grid,
    lam2: float = 0.0,
    *,
    variant: str = "obs",
    grid: Grid1p5D | None = None,
    **kw,
) -> list[FitResult]:
    """Deprecated shim — use ``repro.estimator.ConcordEstimator.fit_path``,
    which warm-starts consecutive path points and reuses the compiled solve.

    Fit a path of estimates over a lam1 grid (the paper's Section-5
    tuning-parameter sweep). Runs coarse-to-fine so sparser fits come first."""
    warnings.warn(
        "distributed.fit_path is deprecated; use "
        "repro.estimator.ConcordEstimator.fit_path", DeprecationWarning,
        stacklevel=2)
    P_ = len(jax.devices())
    grid = grid or Grid1p5D(P_, 1, 1)
    out = []
    for lam1 in sorted(lam1_grid, reverse=True):
        fn = fit_obs if variant == "obs" else fit_cov
        data = x if variant == "obs" else (x.T @ x) / x.shape[0]
        out.append(fn(data, lam1, lam2, grid=grid, **kw))
    return out


# ---------------------------------------------------------------------------
# analysis manifest (repro.analysis.jaxprpass)
# ---------------------------------------------------------------------------

def _analysis_fit_cov():
    grid = Grid1p5D(1, 1, 1)
    mesh = grid.make_mesh()
    p = 8
    s = jnp.eye(p, dtype=jnp.float64) + 0.05 * jnp.ones((p, p), jnp.float64)

    def run(s_):
        res = fit_cov(s_, 0.2, grid=grid, mesh=mesh, tol=1e-3, max_iters=4,
                      max_ls=4)
        return res.omega, res.iters, res.converged, res.block_density

    return {"fn": run, "args": (s,), "axis_sizes": dict(_AXIS_SIZES_1DEV)}


def _analysis_fit_obs():
    grid = Grid1p5D(1, 1, 1)
    mesh = grid.make_mesh()
    n, p = 12, 8
    x = jnp.linspace(-1.0, 1.0, n * p, dtype=jnp.float64).reshape(n, p)

    def run(x_):
        res = fit_obs(x_, 0.2, grid=grid, mesh=mesh, tol=1e-3, max_iters=4,
                      max_ls=4)
        return res.omega, res.iters, res.converged, res.block_density

    return {"fn": run, "args": (x,), "axis_sizes": dict(_AXIS_SIZES_1DEV)}


def _driver_contract():
    """Declared schedule of the end-to-end drivers (comm engine CA305/
    CA306 structure checks; no volume contract — the outer while_loop's
    trip count is dynamic, so bytes/invocation is not a static quantity
    here; the per-product volumes are pinned by the ``comm.matmul1p5d``
    and ``comm.sparse1p5d`` entries instead)."""
    from ..comm.contract import CommContract
    return CommContract(
        entry="core.distributed.fit",
        axes=("i", "j", "k"),
        kinds=("ppermute", "psum", "pmin", "pmax", "all_gather",
               "all_to_all"),
        # the iterate/objective arithmetic is f64 by contract; the ring
        # also rotates int8 occupancy masks and reduces f32 density
        # diagnostics and i32/bool loop control
        wire=("operand", "mask", "float32", "int32", "int64", "bool"),
        volume_class="shard_map driver (dynamic trip count)")


COMM_CONTRACT = {
    "fit_cov": _driver_contract(),
    "fit_obs": _driver_contract(),
}

#: both 1.5D shard_map drivers, traced end to end on a 1-device
#: (1, 1, 1) mesh: the jaxpr still contains every psum/axis binding of
#: the distributed iteration, so the dtype and axis contracts are
#: checked without multi-device hardware (axis extents are all 1 there,
#: hence no volume contract on these entries — see _driver_contract)
_AXIS_SIZES_1DEV = {"i": 1, "j": 1, "k": 1}
ANALYSIS_ENTRIES = [
    {"name": "core.distributed.fit_cov",
     "path": "src/repro/core/distributed.py",
     "axis_names": ("i", "j", "k"), "build": _analysis_fit_cov,
     "comm": lambda: {"contract": COMM_CONTRACT["fit_cov"], "params": {}}},
    {"name": "core.distributed.fit_obs",
     "path": "src/repro/core/distributed.py",
     "axis_names": ("i", "j", "k"), "build": _analysis_fit_obs,
     "comm": lambda: {"contract": COMM_CONTRACT["fit_obs"], "params": {}}},
]
