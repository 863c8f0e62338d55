"""Batched estimator surface: ``fit_batch`` + the batched lam1-path engine.

Thin facade over :mod:`repro.core.batch` (the vmap'd multi-problem solve
engine).  Two entry points:

  * ``fit_batch`` — solve B stacked independent problems (multi-subject /
    multi-tenant workloads, server micro-batches) as ONE compiled program;
    returns a :class:`BatchReport` aggregating per-problem
    :class:`FitReport`s.  ``penalty`` accepts a
    :class:`~repro.core.penalty.PenaltySpec` whose numeric leaves may be
    (B,)-batched so different lanes run different penalty parameters.
  * ``batched_path_reports`` — the engine behind
    ``ConcordEstimator.fit_path(mode="batched")``: a whole lam1 grid
    against shared data as one program.

The engine runs the single-device reference loop (dense products); the
distributed 1.5D drivers remain per-problem backends.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from ..core import batch as core_batch
from ..core import matops
from ..core.penalty import PenaltySpec, normalize_penalty
from ..core.prox import ProxResult
from .backends import Problem, _cast, _report
from .config import SolverConfig
from .report import BatchReport, FitReport


def _check_engine(config: SolverConfig) -> None:
    if config.backend == "distributed":
        raise ValueError(
            "the batched engine runs the single-device reference loop; "
            "use backend='reference' or 'auto' (distributed solves stay "
            "per-problem)")


def _resolve_batch_variant(config: SolverConfig, have_s: bool) -> str:
    """The batched engine's variant="auto" rule (both entry points): Cov
    when a covariance is already available (paths form S once and its
    products are p x p), Obs for raw stacked datasets (no per-problem
    covariance pass needed)."""
    if config.variant != "auto":
        return config.variant
    return "cov" if have_s else "obs"


def _resolve_batch_gemm(config: SolverConfig, variant: str, dtype) -> str:
    """``batch_gemm="auto"``: the host BLAS stepper exactly where it is
    legal and measured faster — CPU backend, Cov variant, compact
    schedule, megakernel off, f64 compute (where its agreement with the
    XLA route is validated) — else plain XLA."""
    if config.batch_gemm != "auto":
        return config.batch_gemm
    if (variant == "cov" and config.batch_schedule == "compact"
            and not config.use_pallas and jnp.dtype(dtype) == jnp.float64
            and jax.default_backend() == "cpu"):
        return "host"
    return "xla"


def _slice_result(res: ProxResult, i: int) -> ProxResult:
    """Per-problem view of a batched ProxResult (leading (B,) axis)."""
    return ProxResult(*(f[i] for f in res))


def batch_reports(res: ProxResult, lam1s, lam2s, wall: float, *,
                  variant: str, config: SolverConfig,
                  backend: str = "batched",
                  penalty: PenaltySpec | None = None) -> list[FitReport]:
    """Split one batched ProxResult into per-problem FitReports.

    ``penalty`` is the (possibly lane-batched) spec the batch ran with;
    each report gets its own lane (``PenaltySpec.lane``) so objectives
    and labels reflect per-lane penalty parameters.  The batch ran as one
    compiled program, so per-problem wall time is not physical — each
    report carries its 1/B share (sums reproduce the measured total)."""
    b = len(lam1s)
    # the engine always runs dense products (the block-sparse lax.switch
    # would execute every branch under vmap) — report the routing mode
    # that actually ran, whatever the config asked for
    config = config.replace(sparse_matmul="off")
    lanes = [None] * b
    if penalty is not None:
        lanes = [penalty.lane(i, b).with_lam1(float(lam1s[i]))
                 for i in range(b)]
    return [
        _report(_slice_result(res, i), lam1=float(lam1s[i]),
                lam2=float(lam2s[i]), wall=wall / b, backend=backend,
                variant=variant, config=config, penalty=lanes[i])
        for i in range(b)
    ]


def fit_batch(x=None, *, s=None, lam1=None, lam2=0.0, penalty=None,
              omega0=None, config: SolverConfig | None = None,
              **knobs) -> BatchReport:
    """Solve B stacked problems as one compiled batched program.

    ``x``: (B, n, p) stacked observation matrices, or ``s``: (B, p, p)
    stacked sample covariances — one shape for the whole batch (bucket
    requests by shape before calling).  ``lam1``/``lam2`` are scalars
    (shared) or length-B sequences (per-problem); ``penalty`` instead
    passes a full :class:`PenaltySpec` (or string form), any of whose
    numeric leaves may carry a leading (B,) lane axis for per-lane
    penalty parameters in the one compiled program.  ``omega0`` is None,
    one shared (p, p) warm start, or stacked (B, p, p).  Extra keyword
    args are ``SolverConfig`` fields.  Returns a :class:`BatchReport`.
    """
    cfg = (config or SolverConfig()).replace(**knobs) if knobs else \
        (config or SolverConfig())
    _check_engine(cfg)
    if (x is None) == (s is None):
        raise ValueError("pass exactly one of x (B, n, p) or s (B, p, p)")
    data = jnp.asarray(x if x is not None else s)
    if data.ndim != 3:
        raise ValueError(f"batched data must be 3-D stacked problems, got "
                         f"shape {data.shape}")
    if s is not None and data.shape[-1] != data.shape[-2]:
        raise ValueError(f"s must stack square matrices, got {data.shape}")
    variant = _resolve_batch_variant(cfg, have_s=s is not None)
    if variant == "obs" and x is None:
        raise ValueError("Obs variant requires the stacked data matrices x")
    if variant == "cov" and x is not None:
        # form the per-problem covariances in one batched einsum
        n = data.shape[1]
        data = jnp.einsum("bni,bnj->bij", data, data,
                          precision=matops.PRECISION) / n
    data = _cast(data, cfg)
    b = data.shape[0]
    if penalty is not None:
        spec = normalize_penalty(penalty, lam1, lam2)
        # exact user-passed penalties for the reports (compute-dtype casts
        # only feed the solver)
        lam1s = np.broadcast_to(np.asarray(spec.lam1, np.float64), (b,))
        lam2s = np.broadcast_to(np.asarray(spec.lam2, np.float64), (b,))
        t0 = time.perf_counter()
        res, stats = core_batch.solve_batch(
            data, penalty=spec, omega0=omega0, variant=variant,
            tol=cfg.tol, max_iters=cfg.max_iters, max_ls=cfg.max_ls,
            warm_start_tau=cfg.warm_start_tau,
            tau_schedule=cfg.tau_schedule, schedule=cfg.batch_schedule,
            chunk=cfg.batch_chunk, max_lanes=cfg.batch_max_lanes,
            gemm=_resolve_batch_gemm(cfg, variant, data.dtype),
            return_stats=True)
    else:
        if lam1 is None:
            raise TypeError("pass lam1 (or penalty=)")
        spec = None
        lam1s = np.broadcast_to(np.asarray(lam1, np.float64), (b,))
        lam2s = np.broadcast_to(np.asarray(lam2, np.float64), (b,))
        t0 = time.perf_counter()
        res, stats = core_batch.solve_batch(
            data, jnp.asarray(lam1s, data.dtype),
            jnp.asarray(lam2s, data.dtype),
            omega0=omega0, variant=variant,
            tol=cfg.tol, max_iters=cfg.max_iters, max_ls=cfg.max_ls,
            warm_start_tau=cfg.warm_start_tau,
            tau_schedule=cfg.tau_schedule, schedule=cfg.batch_schedule,
            chunk=cfg.batch_chunk, max_lanes=cfg.batch_max_lanes,
            gemm=_resolve_batch_gemm(cfg, variant, data.dtype),
            return_stats=True)
    jax.block_until_ready(res.omega)
    wall = time.perf_counter() - t0
    reports = batch_reports(res, lam1s, lam2s, wall, variant=variant,
                            config=cfg, penalty=spec)
    return BatchReport(reports=tuple(reports), wall_time_s=wall,
                       stats=stats)


def batched_path_reports(problem: Problem, grid: list[float],
                         config: SolverConfig, *,
                         penalty: PenaltySpec | None = None,
                         lam2: float = 0.0,
                         omega0=None):
    """Run a whole lam1 grid against shared data as one compiled program.

    ``penalty`` (optional) is the spec template whose lam1 the grid
    replaces — SCAD/MCP/weighted paths lower to the same single program.
    The engine knobs (``batch_schedule``/``batch_chunk``/
    ``batch_max_lanes``/``batch_gemm``/``batch_warm_start``/
    ``tau_schedule``/``use_pallas``) come from the config.  Returns
    (per-point reports in ``grid`` order, total wall seconds, the
    engine's :class:`~repro.core.batch.BatchRunStats`).  Engine behind
    ``ConcordEstimator.fit_path(mode="batched")``."""
    _check_engine(config)
    variant = _resolve_batch_variant(config, have_s=problem.s is not None)
    if variant == "cov":
        data = _cast(problem.cov(), config)
    else:
        if problem.x is None:
            raise ValueError("Obs variant requires the data matrix x")
        data = _cast(problem.x, config)
    if omega0 is not None:
        omega0 = jnp.asarray(omega0, data.dtype)
    lam1s = jnp.asarray(grid, data.dtype)
    if penalty is not None:
        lam2 = float(np.asarray(penalty.lam2))
    t0 = time.perf_counter()
    res, stats = core_batch.solve_path_batched(
        data, lam1s, lam2, penalty=penalty, omega0=omega0, variant=variant,
        tol=config.tol, max_iters=config.max_iters, max_ls=config.max_ls,
        warm_start_tau=config.warm_start_tau,
        tau_schedule=config.tau_schedule, schedule=config.batch_schedule,
        chunk=config.batch_chunk, max_lanes=config.batch_max_lanes,
        use_pallas=config.use_pallas,
        gemm=_resolve_batch_gemm(config, variant, data.dtype),
        warm_start=config.batch_warm_start, return_stats=True)
    jax.block_until_ready(res.omega)
    wall = time.perf_counter() - t0
    lam2s = [lam2] * len(grid)
    spec_b = penalty.with_lam1(np.asarray(grid, np.float64)) \
        if penalty is not None else None
    return batch_reports(res, grid, lam2s, wall, variant=variant,
                         config=config, penalty=spec_b), wall, stats
