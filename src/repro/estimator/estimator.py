"""``ConcordEstimator`` — the sklearn-style front door to every solver.

One object, five entry points:

    est = ConcordEstimator(lam1=0.15, lam2=0.05)
    est.fit(X)                      # (n, p) observations — or ANY chunk
                                    # stream (generator, shard paths, ...)
    est.fit_cov(S, n_samples=n)     # (p, p) sample covariance
    est.fit_gram(gram_result)       # streamed Gram from repro.data
    path = est.fit_path(X, lam1_grid=[...])        # warm-started lam1 path
    best = path.best_bic()                         # model selection

The penalty is pluggable (``repro.core.penalty``): pass
``penalty="scad:3.7"`` / ``"mcp"`` / ``"elastic_net"`` (strength from
``lam1``/``lam2``), or a full :class:`PenaltySpec` (e.g.
``PenaltySpec.weighted_l1(lam1, W)`` for adaptive lasso and structural
0/inf edge constraints).  The bare ``lam1=``/``lam2=`` kwargs are the
DEPRECATED legacy form: they keep working and construct the equivalent
l1 spec (bit-identical solve), but new code should hand the estimator a
spec — see the README migration table.

All solver knobs live in a frozen ``SolverConfig``; the backend registry
(``"reference"`` / ``"distributed"`` / ``"auto"``) decides what actually
runs.  ``fit_path`` runs the grid descending with warm starts: each point
starts from the previous solution (and, on the reference backend, reuses
the same compiled program, since every penalty parameter and omega0 are
traced arguments).  ``fit_path(adaptive=True)`` runs the two-stage
adaptive lasso: an l1 stage-1 path, weights ``1/(|omega_hat| + eps)``
from its BIC-best point, then a weighted stage-2 path over the same grid.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Iterable

import numpy as np

from ..core.penalty import PenaltySpec, adaptive_weights, as_penalty
from ..obs.trace import get_tracer
from .backends import Problem, get_backend
from .config import SolverConfig
from .report import FitReport, PathResult, pseudo_bic


def _validate_grid(lam1_grid) -> list[float]:
    try:
        grid = [float(v) for v in lam1_grid]
    except TypeError:
        raise ValueError(f"lam1_grid must be an iterable of floats, got "
                         f"{lam1_grid!r}") from None
    if not grid:
        raise ValueError("lam1_grid must be non-empty")
    for v in grid:
        if not math.isfinite(v) or v <= 0:
            raise ValueError(f"lam1_grid values must be finite and > 0, "
                             f"got {v}")
    return grid


def _observed(method):
    """Run a public entry point with the tracer scoped to the config's
    ``obs`` level, so every span of the call follows it."""
    @functools.wraps(method)
    def wrapper(self, *args, **kwargs):
        with get_tracer().scoped(self.config.obs):
            return method(self, *args, **kwargs)
    return wrapper


class ConcordEstimator:
    """Sparse inverse covariance estimation via CONCORD/HP-CONCORD.

    Parameters mirror sklearn's covariance estimators: the penalty is a
    constructor argument, solver mechanics live in ``config``.  After
    ``fit``/``fit_cov`` the instance exposes ``omega_`` (the estimate),
    ``report_`` (a :class:`FitReport`) and ``n_iter_``.

    ``penalty`` accepts a :class:`PenaltySpec`, a string form ("l1",
    "elastic_net", "scad:3.7", "mcp:2.5" — strength from ``lam1``/
    ``lam2``), or None (then ``config.penalty`` applies, default "l1").
    The scalar ``lam1``/``lam2`` kwargs are the deprecated legacy surface
    and are shimmed into the equivalent spec.
    """

    def __init__(self, lam1: float | None = None, lam2: float | None = None,
                 penalty: PenaltySpec | str | None = None,
                 config: SolverConfig | None = None):
        self.config = config or SolverConfig()
        if not isinstance(self.config, SolverConfig):
            raise TypeError(f"config must be a SolverConfig, got "
                            f"{type(self.config).__name__}")
        if isinstance(penalty, PenaltySpec):
            if lam1 is not None or lam2 is not None:
                raise ValueError(
                    "a PenaltySpec already carries lam1/lam2; pass either "
                    "the spec or the scalar kwargs, not both")
            spec = penalty
        else:
            # the estimator keeps its historical lam1 default of 0.1; the
            # lower solver layers require an explicit strength
            spec = as_penalty(penalty if penalty is not None
                              else self.config.penalty,
                              lam1=0.1 if lam1 is None else lam1,
                              lam2=lam2)
        self.penalty: PenaltySpec = spec
        self._lam1 = float(np.asarray(spec.lam1))
        self._lam2 = float(np.asarray(spec.lam2))
        self.omega_ = None
        self.report_: FitReport | None = None
        self.n_iter_: int | None = None

    # -- legacy scalar surface (deprecated, kept working) ---------------
    # ``est.lam1 = v`` mutation predates the penalty spec; the setters
    # rebuild the spec so old code that retunes the strength in place
    # keeps solving with the new value.

    @property
    def lam1(self) -> float:
        return self._lam1

    @lam1.setter
    def lam1(self, value) -> None:
        self._lam1 = float(value)
        self.penalty = self.penalty.with_lam1(self._lam1)

    @property
    def lam2(self) -> float:
        return self._lam2

    @lam2.setter
    def lam2(self, value) -> None:
        self._lam2 = float(value)
        self.penalty = dataclasses.replace(self.penalty, lam2=self._lam2)

    # -- single fits ----------------------------------------------------

    def _solve(self, problem: Problem, spec: PenaltySpec,
               omega0=None) -> FitReport:
        backend = get_backend(self.config.backend)
        return backend(problem, spec, self.config, omega0)

    def _finish(self, report: FitReport) -> "ConcordEstimator":
        self.report_ = report
        self.omega_ = report.omega
        self.n_iter_ = report.iters
        return self

    @_observed
    def fit(self, x, *, omega0=None, transform: str | None = None,
            chunk_rows: int | None = None) -> "ConcordEstimator":
        """Fit from observations (either variant works).

        ``x`` may be an in-memory (n, p) matrix, OR any chunk stream the
        data subsystem understands — a generator/iterator of row-blocks,
        a ``ChunkSource``, shard file paths, or a zero-arg factory (see
        ``repro.data.shards``).  Streams (and arrays with ``transform``
        set) are reduced to their f64 Gram by ``data.compute_gram``
        without ever materializing X, then solved through the Cov
        variant — the out-of-core front door."""
        from ..data.shards import is_streaming_input
        if is_streaming_input(x) or transform is not None:
            from ..data.gram import compute_gram
            gram = compute_gram(x, transform=transform or "none",
                                chunk_rows=chunk_rows)
            return self.fit_gram(gram, omega0=omega0)
        problem = Problem.from_data(x=x)
        return self._finish(self._solve(problem, self.penalty, omega0))

    @_observed
    def fit_cov(self, s, *, n_samples: int | None = None,
                omega0=None) -> "ConcordEstimator":
        """Fit from a (p, p) sample covariance (forces the Cov variant)."""
        problem = Problem.from_data(s=s, n_samples=n_samples)
        return self._finish(self._solve(problem, self.penalty, omega0))

    @_observed
    def fit_gram(self, gram, *, omega0=None) -> "ConcordEstimator":
        """Fit from a streamed Gram (``data.compute_gram`` /
        ``distributed_gram`` / the ``launch.gram prep`` artifact).

        Accepts a :class:`repro.data.GramResult` or anything exposing
        ``.s`` (the (p, p) Gram) and ``.n`` (rows streamed); the sample
        count rides along so BIC model selection downstream stays
        meaningful.  Validation (symmetry, finiteness) applies as in
        ``fit_cov``."""
        s = getattr(gram, "s", None)
        n = getattr(gram, "n", None)
        if s is None or n is None:
            raise TypeError(
                f"fit_gram wants a GramResult-like object with .s and .n "
                f"(got {type(gram).__name__}); for a plain covariance "
                f"array use fit_cov(s, n_samples=...)")
        problem = Problem.from_data(s=s, n_samples=int(n))
        return self._finish(self._solve(problem, self.penalty, omega0))

    # -- regularization path --------------------------------------------

    def _resolve_path_mode(self, mode: str, grid: list[float]) -> str:
        """``fit_path(mode="auto")``: consult the cost model's
        batched-vs-sequential predictor with the engine knobs this config
        would actually run (tau schedule, chunk, gemm route, pilot warm
        start)."""
        if mode != "auto":
            return mode
        import jax

        from ..core.costmodel import choose_path_mode
        from ..core.prox import resolve_tau_schedule
        gemm = self.config.batch_gemm
        if gemm == "auto":
            # mirror the batch layer's resolution; the predictor only
            # needs the step-cost class, not the exact dtype gate
            gemm = "host" if jax.default_backend() == "cpu" else "xla"
        return choose_path_mode(
            grid,
            tau_schedule=resolve_tau_schedule(
                self.config.tau_schedule, self.config.warm_start_tau),
            chunk=self.config.batch_chunk,
            max_iters=self.config.max_iters,
            gemm=gemm, warm_start=self.config.batch_warm_start)

    def _run_path(self, problem: Problem, grid: list[float],
                  spec: PenaltySpec, mode: str, warm_start: bool,
                  score_bic: bool, s_mat):
        with get_tracer().span("fit_path", points=len(grid),
                               mode=mode) as span:
            stats = None
            if mode == "batched":
                from .batch import batched_path_reports
                reports, _, stats = batched_path_reports(
                    problem, grid, self.config, penalty=spec)
            else:
                reports = []
                omega0 = None
                for lam1 in grid:
                    rep = self._solve(problem, spec.with_lam1(lam1),
                                      omega0 if warm_start else None)
                    reports.append(rep)
                    omega0 = rep.omega
            if score_bic:
                reports = [
                    dataclasses.replace(
                        rep, bic=pseudo_bic(rep.omega, s_mat, problem.n))
                    for rep in reports
                ]
            span.note(total_iters=sum(r.iters for r in reports))
        return reports, stats

    @_observed
    def fit_path(self, x=None, lam1_grid: Iterable[float] = (), *,
                 s=None, n_samples: int | None = None,
                 warm_start: bool = True,
                 score_bic: bool = True,
                 mode: str = "sequential",
                 adaptive: bool = False,
                 adaptive_eps: float = 1e-3) -> PathResult:
        """Fit a descending lam1 path.

        ``mode="sequential"`` (default) solves the grid point by point;
        each point starts from the previous solution (``warm_start``),
        which typically converges in a fraction of the cold-start
        iterations — the paper's Section-5 model-selection sweep as a
        single call.  ``warm_start=False`` runs every point cold (for
        benchmarking).

        ``mode="batched"`` lowers the ENTIRE grid to one compiled
        multi-problem program (``core.batch``): every point solves
        concurrently against the shared data, finished points freeze while
        stragglers keep iterating.  Warm starts do not apply (points run
        concurrently, cold); the engine is the single-device reference
        loop.  Per-point estimates match the sequential reference path
        (1e-5 agreement is asserted in float64 by the test suite).

        The path runs the estimator's penalty at every grid point
        (``spec.with_lam1`` — one compiled program on the reference
        backend, since penalty parameters are traced).

        ``adaptive=True`` runs the TWO-STAGE adaptive lasso instead:
        stage 1 is a plain l1 path over the grid, then each grid point is
        refit with ``weighted_l1`` weights ``1/(|omega_hat| +
        adaptive_eps)`` built from stage 1's estimate AT THE SAME lam1
        (the pointwise two-stage refit — a single dense anchor would pin
        the whole stage-2 path to the anchor's sparsity).  In batched
        mode the per-point weight matrices ride as one (B, p, p) lane-
        batched spec leaf through the single compiled program.  Returns
        the stage-2 path with ``adaptive=True`` and ``stage1`` attached.

        ``mode="auto"`` consults the cost model
        (``core.costmodel.choose_path_mode``): batched when the compact
        engine's predicted speedup over a sequential sweep of this grid
        clears the threshold, sequential otherwise.

        With ``score_bic`` each report carries a pseudo-likelihood BIC so
        ``PathResult.best_bic()`` picks a model in one line.
        """
        if mode not in ("sequential", "batched", "auto"):
            raise ValueError(f"mode must be 'sequential', 'batched' or "
                             f"'auto', got {mode!r}")
        grid = _validate_grid(lam1_grid)
        mode = self._resolve_path_mode(mode, grid)
        if score_bic and x is None and n_samples is None:
            raise ValueError(
                "BIC scoring needs the sample count: pass n_samples "
                "alongside s, or score_bic=False")
        problem = Problem.from_data(x=x, s=s, n_samples=n_samples)
        # form the covariance once for the whole path (cov-variant backends
        # and BIC scoring would otherwise recompute X^T X / n per point)
        if problem.s is None and (score_bic or self.config.variant != "obs"):
            problem = problem._replace(s=problem.cov())
        s_mat = problem.s if score_bic else None
        grid = sorted(grid, reverse=True)
        warm = warm_start and mode == "sequential"
        spec1 = self.penalty
        if adaptive and spec1.kind != "l1":
            # stage 1 of the adaptive refit is always a plain l1 path
            spec1 = PenaltySpec("l1", self.lam1, self.lam2)
        reports, bstats = self._run_path(problem, grid, spec1, mode,
                                         warm_start, score_bic, s_mat)
        stage1 = PathResult(reports=tuple(reports), warm_start=warm,
                            mode=mode, batch_stats=bstats)
        if not adaptive:
            self._finish(reports[-1])
            return stage1
        weights = [adaptive_weights(rep.omega, eps=adaptive_eps)
                   for rep in stage1.reports]
        bstats2 = None
        if mode == "batched":
            from .batch import batched_path_reports
            # per-point weight matrices = one (B, p, p) lane-batched leaf
            spec2 = PenaltySpec("weighted_l1", grid[0], self.lam2,
                                weights=np.stack(weights))
            reports2, _, bstats2 = batched_path_reports(
                problem, grid, self.config, penalty=spec2)
        else:
            reports2 = []
            omega0 = None
            for lam1, w in zip(grid, weights):
                spec2 = PenaltySpec("weighted_l1", lam1, self.lam2,
                                    weights=w)
                rep = self._solve(problem, spec2,
                                  omega0 if warm_start else None)
                reports2.append(rep)
                omega0 = rep.omega
        if score_bic:
            reports2 = [
                dataclasses.replace(
                    rep, bic=pseudo_bic(rep.omega, s_mat, problem.n))
                for rep in reports2
            ]
        result = PathResult(reports=tuple(reports2), warm_start=warm,
                            mode=mode, adaptive=True, stage1=stage1,
                            batch_stats=bstats2)
        self._finish(reports2[-1])
        return result

    # -- batched multi-problem solves -----------------------------------

    @_observed
    def fit_batch(self, x=None, *, s=None, lam1=None, lam2=None,
                  penalty=None, omega0=None):
        """Solve stacked (B, ...) problems as one compiled batched program.

        ``x``: (B, n, p) stacked observation matrices or ``s``: (B, p, p)
        stacked covariances.  The batch runs the estimator's penalty
        FAMILY; ``lam1``/``lam2`` override only the strengths (scalars or
        length-B sequences — a SCAD estimator with ``lam1=[...]`` stays
        SCAD per lane).  ``penalty`` replaces the spec outright: a string
        form (strength from lam1/lam2, defaulting to the estimator's) or
        a full :class:`PenaltySpec` whose numeric leaves may carry a
        leading (B,) lane axis (per-lane penalty parameters in one
        compiled program).  Returns a
        :class:`repro.estimator.report.BatchReport`; the last problem's
        report also lands on ``report_``/``omega_`` (sklearn convention,
        mirroring ``fit_path``)."""
        from .batch import fit_batch as _fit_batch
        if penalty is None:
            spec = self.penalty
            if lam1 is not None:
                spec = spec.with_lam1(np.asarray(lam1, np.float64))
            if lam2 is not None:
                spec = dataclasses.replace(
                    spec, lam2=np.asarray(lam2, np.float64))
        elif isinstance(penalty, str):
            spec = as_penalty(penalty,
                              lam1=self.lam1 if lam1 is None else lam1,
                              lam2=self.lam2 if lam2 is None else lam2)
        else:
            if lam1 is not None or lam2 is not None:
                raise ValueError(
                    "a PenaltySpec already carries lam1/lam2; pass either "
                    "the spec or the scalar overrides, not both")
            spec = as_penalty(penalty)
        result = _fit_batch(x, s=s, penalty=spec, omega0=omega0,
                            config=self.config)
        self._finish(result.reports[-1])
        return result


# ---------------------------------------------------------------------------
# functional facade
# ---------------------------------------------------------------------------

def fit(x=None, *, s=None, lam1: float | None = None, lam2: float = 0.0,
        penalty: PenaltySpec | str | None = None,
        n_samples: int | None = None, transform: str | None = None,
        chunk_rows: int | None = None,
        config: SolverConfig | None = None, **knobs) -> FitReport:
    """One-call fit through the facade.  ``x`` may be a matrix or a chunk
    stream (``transform``/``chunk_rows`` ride through to the streaming
    Gram pipeline).  ``penalty`` swaps the penalty family (spec or string
    form).  Extra keyword args are SolverConfig fields (e.g.
    ``backend="distributed"``, ``tol=1e-6``)."""
    cfg = (config or SolverConfig()).replace(**knobs) if knobs else \
        (config or SolverConfig())
    if isinstance(penalty, PenaltySpec):
        est = ConcordEstimator(penalty=penalty, config=cfg)
    else:
        est = ConcordEstimator(lam1=lam1, lam2=lam2, penalty=penalty,
                               config=cfg)
    if x is not None:
        est.fit(x, transform=transform, chunk_rows=chunk_rows)
    else:
        est.fit_cov(s, n_samples=n_samples)
    return est.report_


def fit_path(x=None, lam1_grid: Iterable[float] = (), *, s=None,
             lam2: float = 0.0,
             penalty: PenaltySpec | str | None = None,
             n_samples: int | None = None,
             warm_start: bool = True, score_bic: bool = True,
             mode: str = "sequential", adaptive: bool = False,
             config: SolverConfig | None = None, **knobs) -> PathResult:
    """One-call regularization path through the facade (sequential
    warm-started, ``mode="batched"`` for one compiled program, or
    ``adaptive=True`` for the two-stage adaptive lasso)."""
    cfg = (config or SolverConfig()).replace(**knobs) if knobs else \
        (config or SolverConfig())
    if isinstance(penalty, PenaltySpec):
        est = ConcordEstimator(penalty=penalty, config=cfg)
    else:
        est = ConcordEstimator(lam1=1.0, lam2=lam2, penalty=penalty,
                               config=cfg)
    return est.fit_path(x, lam1_grid, s=s, n_samples=n_samples,
                        warm_start=warm_start, score_bic=score_bic,
                        mode=mode, adaptive=adaptive)
