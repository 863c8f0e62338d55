"""Backend registry for the ``repro.estimator`` facade.

A *backend* is a callable

    backend(problem, penalty, config, omega0=None) -> FitReport

registered under a name, where ``penalty`` is a
:class:`repro.core.penalty.PenaltySpec` (a bare float is also accepted
and treated as the lam1 of an l1 penalty).  Three ship by default:

  ``reference``    single-device jitted solve (``core.prox``); warm starts
                   and lam1/lam2 are traced so a regularization path reuses
                   one compiled program.
  ``distributed``  the 1.5D shard_map drivers (``core.distributed``);
                   replication factors come from the config or the tuner.
  ``auto``         consults ``core.costmodel.tune`` (paper Lemmas 3.1-3.5)
                   for variant + replication, then dispatches to
                   ``reference`` on one device or ``distributed`` otherwise.

``register_backend`` lets downstream code plug in new engines (e.g. a GPU
Pallas solver) without touching the estimator.
"""
from __future__ import annotations

import time
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..comm.grid import Grid1p5D
from ..core import distributed as dist
from ..core import matops, prox
from ..core.penalty import PenaltySpec, as_penalty, penalty_value_np
from ..core.costmodel import (
    Machine,
    ProblemShape,
    crossover_density,
    enumerate_configs,
    tune,
)
from ..obs.trace import get_tracer
from .config import SolverConfig
from .report import FitReport

#: |entry| below this counts as a structural zero when observing iterate
#: density (matches the soft-threshold exact zeros; guards fp noise).
NNZ_TOL = 1e-8

#: default block-density threshold for sparse_matmul="on"
DEFAULT_SPARSE_THRESHOLD = 0.25


#: relative asymmetry above this rejects an input "covariance" — genuine
#: sample covariances are symmetric to machine precision; anything worse
#: is a transposed/buggy input, not rounding.
SYMMETRY_RTOL = 1e-6


def _require_finite(name: str, arr) -> None:
    if not bool(np.all(np.isfinite(np.asarray(arr)))):
        raise ValueError(
            f"{name} contains NaN/Inf; refusing to fit (a non-finite input "
            f"silently produces a garbage estimate — clean or impute the "
            f"data first)")


def _require_symmetric(s) -> None:
    sh = np.asarray(s)
    scale = float(np.max(np.abs(sh))) if sh.size else 0.0
    asym = float(np.max(np.abs(sh - sh.T))) if sh.size else 0.0
    if asym > SYMMETRY_RTOL * max(scale, 1.0):
        raise ValueError(
            f"s must be symmetric: max |s - s^T| = {asym:.3e} at scale "
            f"{scale:.3e} — pass a genuine Gram/covariance (see "
            f"data.compute_gram for streamed construction)")


class Problem(NamedTuple):
    """Input data for one estimation problem (either x or s, maybe both)."""
    x: jax.Array | None         # (n, p) observations
    s: jax.Array | None         # (p, p) sample covariance
    n: int                      # sample count (for s-only problems: given)
    p: int

    @staticmethod
    def from_data(x=None, s=None, n_samples: int | None = None) -> "Problem":
        if x is None and s is None:
            raise ValueError("pass x (n, p) or s (p, p)")
        if n_samples is not None and (not isinstance(n_samples, (int,
                np.integer)) or n_samples < 1):
            raise ValueError(f"n_samples must be a positive int, got "
                             f"{n_samples!r}")
        tracer = get_tracer()
        if x is not None:
            x = jnp.asarray(x)
            if x.ndim != 2:
                raise ValueError(f"x must be 2-D (n, p), got shape {x.shape}")
            with tracer.span("validate", cat="facade", level="trace"):
                _require_finite("x", x)
        if s is not None:
            s = jnp.asarray(s)
            if s.ndim != 2 or s.shape[0] != s.shape[1]:
                raise ValueError(f"s must be square (p, p), got {s.shape}")
            with tracer.span("validate", cat="facade", level="trace"):
                _require_finite("s", s)
                _require_symmetric(s)
        if x is not None and s is not None and x.shape[1] != s.shape[0]:
            raise ValueError(
                f"x has p={x.shape[1]} columns but s is {s.shape}")
        p = (x if x is not None else s).shape[-1]
        n = x.shape[0] if x is not None else (n_samples or p)
        return Problem(x=x, s=s, n=int(n), p=int(p))

    def cov(self) -> jax.Array:
        """The (p, p) sample covariance, formed on demand."""
        if self.s is not None:
            return self.s
        return matops.dot(self.x.T, self.x) / self.n


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

BackendFn = Callable[..., FitReport]

_REGISTRY: dict[str, BackendFn] = {}


def register_backend(name: str, fn: BackendFn, *,
                     overwrite: bool = False) -> None:
    if not overwrite and name in _REGISTRY:
        raise ValueError(f"backend {name!r} already registered")
    _REGISTRY[name] = fn


def get_backend(name: str) -> BackendFn:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown backend {name!r}; available: {available_backends()}"
        ) from None


def available_backends() -> list[str]:
    return sorted(_REGISTRY)


# ---------------------------------------------------------------------------
# shared helpers
# ---------------------------------------------------------------------------

def _cast(arr, config: SolverConfig):
    if config.dtype is None:
        return arr
    return jnp.asarray(arr, jnp.dtype(config.dtype))


def _variant_candidates(problem: Problem, config: SolverConfig) -> tuple:
    variants = ("cov", "obs") if problem.x is not None else ("cov",)
    if config.variant != "auto":
        variants = (config.variant,)
    return variants


def observed_nnz_per_row(omega) -> float:
    """Average nonzeros per row of an iterate (the cost model's ``d``)."""
    om = np.asarray(omega)
    return max(1.0, float(np.count_nonzero(np.abs(om) > NNZ_TOL))
               / om.shape[0])


def _problem_shape(problem: Problem, lam1: float,
                   omega0=None) -> ProblemShape:
    """Cost-model shape for the solve.  With a warm start available (e.g.
    the previous lambda step on a path), its OBSERVED density replaces the
    static ``estimate_density`` prior — the tuner then sees the sparsity
    the iterates actually have."""
    if omega0 is not None:
        d = observed_nnz_per_row(omega0)
    else:
        d = dist.estimate_density(problem.p, problem.n, lam1)
    return ProblemShape(p=problem.p, n=problem.n, d=d)


def _matmul_policy(config: SolverConfig, p: int,
                   m: int) -> matops.MatmulPolicy | None:
    """Resolve the config's sparse_matmul knobs into a static routing
    policy for an Ω-side product with ``m`` output columns.  ``"auto"``
    takes its threshold from the cost model's dense↔block-sparse crossover
    (never routing sparse above the modeled break-even density)."""
    mode = config.sparse_matmul
    if mode == "off":
        return None
    if mode == "on":
        thr = (config.sparse_threshold if config.sparse_threshold is not None
               else DEFAULT_SPARSE_THRESHOLD)
    else:  # auto
        thr = crossover_density(p, m, config.sparse_block)
        if config.sparse_threshold is not None:
            thr = min(thr, config.sparse_threshold)
    if thr <= 0.0:
        return None
    return matops.MatmulPolicy(mode, config.sparse_block, float(thr))


def _check_grid(variant: str, c_x: int, c_omega: int,
                n_devices: int) -> tuple[str, int, int]:
    if variant == "cov" and c_x != c_omega:
        raise ValueError(
            f"Cov keeps Omega in the X-like layout, so c_x must equal "
            f"c_omega (got c_x={c_x}, c_omega={c_omega})")
    if c_x * c_omega > n_devices or n_devices % (c_x * c_omega):
        raise ValueError(
            f"replication c_x*c_omega={c_x * c_omega} must divide "
            f"n_devices={n_devices} (got c_x={c_x}, c_omega={c_omega})")
    return variant, c_x, c_omega


def _resolve_variant_only(problem: Problem, lam1: float,
                          config: SolverConfig, omega0=None) -> str:
    """Variant for the single-device reference engine (replication moot)."""
    if config.variant != "auto":
        return config.variant
    best = tune(_problem_shape(problem, lam1, omega0), 1, Machine(),
                _variant_candidates(problem, config))
    return best.variant


def _resolve_variant(problem: Problem, lam1: float, config: SolverConfig,
                     n_devices: int, omega0=None) -> tuple[str, int, int]:
    """Pin down (variant, c_x, c_omega) for a distributed solve.

    User-pinned values are validated (raising on an infeasible grid, never
    silently overridden); anything left open is chosen by the cost model,
    enumerating only combinations consistent with the pins and with the
    layout constraints (Cov needs c_x == c_omega; the product must divide
    the device count)."""
    if config.variant != "auto" and config.c_x and config.c_omega:
        return _check_grid(config.variant, config.c_x, config.c_omega,
                           n_devices)
    variants = _variant_candidates(problem, config)
    if n_devices == 1:
        if config.variant != "auto":
            return _check_grid(config.variant, config.c_x or 1,
                               config.c_omega or 1, n_devices)
        best = tune(_problem_shape(problem, lam1, omega0), 1, Machine(),
                    variants)
        return _check_grid(best.variant, config.c_x or 1,
                           config.c_omega or 1, n_devices)
    cands = [
        cb for cb in enumerate_configs(_problem_shape(problem, lam1, omega0),
                                       n_devices, Machine(), variants)
        if (config.c_x is None or cb.c_x == config.c_x)
        and (config.c_omega is None or cb.c_omega == config.c_omega)
        and n_devices % (cb.c_x * cb.c_omega) == 0
        and (cb.variant != "cov" or cb.c_x == cb.c_omega)
    ]
    if not cands:
        raise ValueError(
            f"no feasible (variant, c_x, c_omega) for n_devices={n_devices} "
            f"with variant={config.variant!r} c_x={config.c_x} "
            f"c_omega={config.c_omega}")
    best = min(cands, key=lambda cb: cb.total)
    return _check_grid(best.variant, best.c_x, best.c_omega, n_devices)


def _offdiag_l1(omega) -> float:
    om = np.asarray(omega)
    return float(np.sum(np.abs(om)) - np.sum(np.abs(np.diag(om))))


def _solve_with_obs(config: SolverConfig, backend: str, variant: str,
                    solve, *, p: int, n: int, n_devices: int = 1):
    """Run ``solve()`` (returning a result with ``.omega``) under the
    configured observability level.

    The solve runs inside the ``fit.<backend>`` span, split into the
    dispatch fence (trace + compile + enqueue) and the
    ``block_until_ready`` execution drain; at every level these reach a
    running profiler.  Above ``obs="off"`` the spans also land in the
    tracer's ring and the host-boundary telemetry dict lands on the
    report.  Nothing here is visible to jax tracing, so compiled programs
    and numerics are identical at every level."""
    tracer = get_tracer()
    with tracer.scoped(config.obs):
        t0 = time.perf_counter()
        with tracer.span(f"fit.{backend}", variant=variant, p=p, n=n,
                         n_devices=n_devices) as span:
            with tracer.span("dispatch", level="trace", variant=variant):
                res = solve()
            t1 = time.perf_counter()
            with tracer.span("execute", level="trace", variant=variant):
                jax.block_until_ready(res.omega)
        wall = time.perf_counter() - t0
    if config.obs == "off":
        return res, wall, None
    iters, ls_total = int(res.iters), int(res.ls_total)
    span.note(iters=iters, ls_total=ls_total, converged=bool(res.converged))
    telemetry = {
        "obs": config.obs,
        "dispatch_s": t1 - t0,
        "execute_s": wall - (t1 - t0),
        "ls_per_iter": ls_total / max(iters, 1),
    }
    return res, wall, telemetry


def _as_spec(penalty) -> PenaltySpec:
    """Backend-entry normalization: spec passes through, a bare number is
    the lam1 of an l1 penalty (plugin-backend ergonomics)."""
    return as_penalty(penalty)


def _report(res, *, lam1, lam2, wall, backend, variant, config=None,
            c_x=1, c_omega=1, n_devices=1, penalty=None,
            telemetry=None) -> FitReport:
    tracer = get_tracer()
    with tracer.span("report", cat="facade"):
        g = float(res.g_final)
        config = config or SolverConfig()
        if penalty is None:
            penalty = PenaltySpec("l1", lam1, lam2)
        # Always compute the final estimate's occupancy post hoc: the
        # solver's in-loop telemetry (res.block_density) reads 1.0 both for
        # genuinely dense iterates AND whenever the policy was dropped
        # downstream (e.g. a block size that does not tile the distributed
        # shard), so it cannot back the report's density column on its
        # own.  One nonzero scan feeds both the nnz/row and the
        # block-occupancy columns.
        with tracer.span("report.copy", cat="facade", level="trace"):
            om = np.asarray(res.omega)
        with tracer.span("report.scan", cat="facade", level="trace"):
            nz = np.abs(om) > NNZ_TOL
            nnz_per_row = max(1.0, float(nz.sum()) / om.shape[0])
            bs = config.sparse_block
            edges = np.arange(0, om.shape[0], bs)
            occ = np.add.reduceat(np.add.reduceat(nz, edges, axis=0),
                                  edges, axis=1) > 0
            block_density = float(occ.mean())
        with tracer.span("report.penalty", cat="facade", level="trace"):
            objective = g + penalty_value_np(penalty, res.omega)
        return FitReport(
            omega=res.omega,
            lam1=float(lam1), lam2=float(lam2),
            iters=int(res.iters), ls_total=int(res.ls_total),
            converged=bool(res.converged),
            stalled=bool(res.stalled),
            objective=objective,
            objective_smooth=g,
            penalty=penalty.label(),
            wall_time_s=float(wall),
            backend=backend, variant=variant,
            c_x=int(c_x), c_omega=int(c_omega), n_devices=int(n_devices),
            nnz_per_row=nnz_per_row,
            block_density=block_density,
            sparse_matmul=config.sparse_matmul,
            telemetry=telemetry,
        )


# ---------------------------------------------------------------------------
# built-in backends
# ---------------------------------------------------------------------------

def reference_backend(problem: Problem, penalty, config: SolverConfig,
                      omega0=None) -> FitReport:
    """Single-device jitted solve; the workhorse of warm-started paths."""
    spec = _as_spec(penalty)
    lam1 = float(np.asarray(spec.lam1))
    variant = _resolve_variant_only(problem, lam1, config, omega0)
    if variant == "cov":
        data = _cast(problem.cov(), config)
    else:
        if problem.x is None:
            raise ValueError("Obs variant requires the data matrix x")
        data = _cast(problem.x, config)
    if omega0 is not None:
        omega0 = jnp.asarray(omega0, data.dtype)
    policy = _matmul_policy(
        config, problem.p, problem.p if variant == "cov" else problem.n)

    def solve():
        return prox.solve_reference(
            data, penalty=spec, omega0=omega0, variant=variant,
            tol=config.tol, max_iters=config.max_iters,
            max_ls=config.max_ls, warm_start_tau=config.warm_start_tau,
            sparse_matmul=policy, use_pallas=config.use_pallas)

    res, wall, telemetry = _solve_with_obs(
        config, "reference", variant, solve, p=problem.p, n=problem.n)
    return _report(res, lam1=lam1, lam2=float(np.asarray(spec.lam2)),
                   wall=wall, backend="reference", variant=variant,
                   config=config, penalty=spec, telemetry=telemetry)


def distributed_backend(problem: Problem, penalty, config: SolverConfig,
                        omega0=None) -> FitReport:
    """1.5D shard_map solve over all (or ``config.n_devices``) devices."""
    spec = _as_spec(penalty)
    lam1 = float(np.asarray(spec.lam1))
    n_dev = config.n_devices or len(jax.devices())
    variant, c_x, c_omega = _resolve_variant(problem, lam1, config, n_dev,
                                             omega0)
    grid = Grid1p5D(n_dev, c_x, c_omega)
    policy = _matmul_policy(
        config, problem.p, problem.p if variant == "cov" else problem.n)
    if variant != "cov" and problem.x is None:
        raise ValueError("Obs variant requires the data matrix x")

    def solve():
        if variant == "cov":
            return dist.fit_cov(
                _cast(problem.cov(), config), penalty=spec, grid=grid,
                tol=config.tol, max_iters=config.max_iters,
                max_ls=config.max_ls, warm_start_tau=config.warm_start_tau,
                use_pallas=config.use_pallas, omega0=omega0,
                sparse_matmul=policy)
        return dist.fit_obs(
            _cast(problem.x, config), penalty=spec, grid=grid,
            tol=config.tol, max_iters=config.max_iters,
            max_ls=config.max_ls, warm_start_tau=config.warm_start_tau,
            use_pallas=config.use_pallas, omega0=omega0,
            sparse_matmul=policy)

    # obs="trace" arms the comm reconciliation watcher around the dense
    # dispatch (the sparse policy's mask traffic has no analytic twin yet)
    watch = None
    if config.obs == "trace" and policy is None:
        from ..obs.commwatch import CommWatch
        watch = CommWatch().install()
    try:
        res, wall, telemetry = _solve_with_obs(
            config, "distributed", variant, solve, p=problem.p,
            n=problem.n, n_devices=n_dev)
    finally:
        if watch is not None:
            watch.uninstall()
    if watch is not None and telemetry is not None:
        recon = watch.reconcile()
        telemetry["comm_reconcile"] = [r.to_json() for r in recon]
        telemetry["comm_reconcile_ok"] = all(r.ok for r in recon)
    return _report(res, lam1=lam1, lam2=float(np.asarray(spec.lam2)),
                   wall=wall, backend="distributed", variant=res.variant,
                   config=config, c_x=grid.c_x, c_omega=grid.c_omega,
                   n_devices=n_dev, penalty=spec, telemetry=telemetry)


def auto_backend(problem: Problem, penalty, config: SolverConfig,
                 omega0=None) -> FitReport:
    """Cost-model-driven dispatch (the paper's decision procedure): resolve
    variant + replication via ``costmodel.tune``, then run on the reference
    engine (one device) or the distributed engine (several)."""
    spec = _as_spec(penalty)
    n_dev = config.n_devices or len(jax.devices())
    variant, c_x, c_omega = _resolve_variant(
        problem, float(np.asarray(spec.lam1)), config, n_dev, omega0)
    pinned = config.replace(variant=variant, c_x=c_x, c_omega=c_omega)
    if n_dev == 1:
        return reference_backend(problem, spec, pinned, omega0)
    return distributed_backend(problem, spec, pinned, omega0)


register_backend("reference", reference_backend)
register_backend("distributed", distributed_backend)
register_backend("auto", auto_backend)
