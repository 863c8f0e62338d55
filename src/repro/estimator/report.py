"""Rich fit results for the ``repro.estimator`` facade.

``FitReport`` is the per-solve record (estimate + solver telemetry + the
backend/grid the dispatcher actually chose); ``PathResult`` aggregates the
reports of a regularization path (warm-started sequential or batched) and
adds model selection; ``BatchReport`` aggregates the per-problem reports
of one batched multi-problem solve (``fit_batch``).

Convergence semantics: ``converged`` is True only on a genuine
``delta < tol`` exit.  ``stalled`` is True when the line search exhausted
``max_ls`` trials without accepting a step (the iterate stopped moving at
machine precision — the solver used to misreport this as convergence).
The two flags are mutually exclusive; both False means the iteration cap
hit first.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field

import numpy as np


@dataclass(frozen=True)
class FitReport:
    """Everything a caller may want to know about one solve."""
    omega: object               # (p, p) estimate (jax or numpy array)
    lam1: float
    lam2: float
    iters: int                  # outer proximal-gradient iterations
    ls_total: int               # total line-search trials
    converged: bool
    objective: float            # full objective g + lam1*||offdiag||_1
    objective_smooth: float     # smooth part g (logdet + quad + ridge)
    wall_time_s: float
    backend: str                # backend that actually ran ("reference"/...)
    variant: str                # "cov" or "obs" as resolved
    c_x: int = 1
    c_omega: int = 1
    n_devices: int = 1
    bic: float | None = None    # filled in by fit_path for model selection
    nnz_per_row: float | None = None    # observed nnz/row of the estimate
    block_density: float | None = None  # occupied-block fraction at
                                        # sparse_block granularity
    sparse_matmul: str = "off"          # Ω-product routing mode that ran
    stalled: bool = False       # line search exhausted max_ls with no accept
                                # (mutually exclusive with converged)
    penalty: str = "l1"         # penalty label ("l1", "scad:3.7",
                                # "weighted_l1", ...); objective includes
                                # this penalty's nonsmooth value
    telemetry: dict | None = None   # obs!="off" only: host-boundary solve
                                    # telemetry (dispatch vs execute wall
                                    # split, mean ls trials per
                                    # iteration); None when obs="off"

    def summary(self) -> str:
        dens = ""
        if self.block_density is not None:
            dens = (f" density={self.block_density:.3f}"
                    f"[{self.sparse_matmul}]")
        if self.nnz_per_row is not None:
            dens += f" nnz/row={self.nnz_per_row:.1f}"
        stall = " STALLED" if self.stalled else ""
        pen = f" pen={self.penalty}" if self.penalty != "l1" else ""
        return (f"[{self.backend}/{self.variant} c_x={self.c_x} "
                f"c_omega={self.c_omega}] lam1={self.lam1:g}{pen} "
                f"iters={self.iters} ls={self.ls_total} "
                f"converged={self.converged}{stall} obj={self.objective:.4f}"
                f"{dens} t={self.wall_time_s:.3f}s")


def pseudo_bic(omega, s, n: int, *, tol: float = 1e-8) -> float:
    """BIC under the CONCORD pseudo-likelihood: ``2n * g0 + log(n) * |E|``
    with g0 the unpenalized smooth objective and |E| the edge count.  Used
    by ``fit_path`` for one-call model selection (lam1 sweep -> best BIC)."""
    om = np.asarray(omega, dtype=np.float64)
    sm = np.asarray(s, dtype=np.float64)
    diag = np.diag(om)
    if np.any(diag <= 0):
        return float("inf")
    g0 = -np.sum(np.log(diag)) + 0.5 * np.sum((om @ sm) * om)
    p = om.shape[0]
    edges = (np.count_nonzero(np.abs(om) > tol) - p) / 2.0
    return float(2.0 * n * g0 + math.log(max(n, 2)) * edges)


@dataclass(frozen=True)
class PathResult:
    """Result of a regularization path (descending lam1).

    ``mode`` records how the grid ran: ``"sequential"`` (one solve per
    point, optionally warm-started) or ``"batched"`` (the whole grid as
    one compiled multi-problem program, ``core.batch``).

    ``fit_path(adaptive=True)`` returns the STAGE-2 weighted path with
    ``adaptive=True`` and the stage-1 l1 path attached as ``stage1``.

    ``batch_stats`` (batched mode only) is the engine's
    :class:`~repro.core.batch.BatchRunStats` — segment count, wave sizes
    and the active-lane occupancy timeline of the compact schedule."""
    reports: tuple[FitReport, ...] = field(default_factory=tuple)
    warm_start: bool = True
    mode: str = "sequential"
    adaptive: bool = False
    stage1: "PathResult | None" = None
    batch_stats: object | None = None

    def __post_init__(self):
        object.__setattr__(self, "reports", tuple(self.reports))

    @property
    def lam1_grid(self) -> tuple[float, ...]:
        return tuple(r.lam1 for r in self.reports)

    @property
    def omegas(self) -> list:
        return [r.omega for r in self.reports]

    @property
    def total_iters(self) -> int:
        return int(sum(r.iters for r in self.reports))

    @property
    def total_ls(self) -> int:
        return int(sum(r.ls_total for r in self.reports))

    @property
    def wall_time_s(self) -> float:
        return float(sum(r.wall_time_s for r in self.reports))

    @property
    def telemetry(self) -> dict:
        """Convergence telemetry as a structured time series along the
        path: one numpy array per field, indexed by grid point (the
        host-boundary view — per-iteration state never leaves the
        compiled solver loop)."""
        reps = self.reports
        return {
            "lam1": np.array([r.lam1 for r in reps]),
            "objective": np.array([r.objective for r in reps]),
            "objective_smooth": np.array([r.objective_smooth for r in reps]),
            "iters": np.array([r.iters for r in reps]),
            "ls_total": np.array([r.ls_total for r in reps]),
            "converged": np.array([r.converged for r in reps]),
            "nnz_per_row": np.array([
                np.nan if r.nnz_per_row is None else r.nnz_per_row
                for r in reps]),
            "block_density": np.array([
                np.nan if r.block_density is None else r.block_density
                for r in reps]),
            "wall_time_s": np.array([r.wall_time_s for r in reps]),
        }

    def best_bic(self) -> FitReport:
        """Report with the lowest pseudo-likelihood BIC along the path."""
        scored = [r for r in self.reports if r.bic is not None]
        if not scored:
            raise ValueError("no BIC scores on this path (fit without data?)")
        return min(scored, key=lambda r: r.bic)

    def __len__(self) -> int:
        return len(self.reports)

    def __iter__(self):
        return iter(self.reports)

    def __getitem__(self, i):
        return self.reports[i]

    def summary(self) -> str:
        lines = [r.summary() for r in self.reports]
        how = ("batched" if self.mode == "batched"
               else ("warm" if self.warm_start else "cold") + " starts")
        if self.adaptive:
            how += ", adaptive stage 2"
        lines.append(f"path total: {self.total_iters} outer iters, "
                     f"{self.total_ls} ls trials, {self.wall_time_s:.3f}s "
                     f"({how})")
        if self.batch_stats is not None:
            lines.append(self.batch_stats.summary())
        return "\n".join(lines)


@dataclass(frozen=True)
class BatchReport:
    """Result of one batched multi-problem solve (``fit_batch``).

    ``reports`` holds one :class:`FitReport` per stacked problem, in input
    order.  The whole batch ran as ONE compiled program, so only the
    aggregate wall time is physical; each report carries its 1/B share.
    ``stats`` is the engine's :class:`~repro.core.batch.BatchRunStats`
    (schedule, segments, occupancy timeline).
    """
    reports: tuple[FitReport, ...] = field(default_factory=tuple)
    wall_time_s: float = 0.0    # end-to-end time of the one batched solve
    stats: object | None = None

    def __post_init__(self):
        object.__setattr__(self, "reports", tuple(self.reports))

    @property
    def n_problems(self) -> int:
        return len(self.reports)

    @property
    def omegas(self) -> list:
        return [r.omega for r in self.reports]

    @property
    def total_iters(self) -> int:
        return int(sum(r.iters for r in self.reports))

    @property
    def all_converged(self) -> bool:
        return all(r.converged for r in self.reports)

    @property
    def any_stalled(self) -> bool:
        return any(r.stalled for r in self.reports)

    def __len__(self) -> int:
        return len(self.reports)

    def __iter__(self):
        return iter(self.reports)

    def __getitem__(self, i):
        return self.reports[i]

    def summary(self) -> str:
        lines = [r.summary() for r in self.reports]
        lines.append(
            f"batch total: {self.n_problems} problems, {self.total_iters} "
            f"outer iters, {self.wall_time_s:.3f}s as one compiled solve "
            f"(converged {sum(r.converged for r in self.reports)}"
            f"/{self.n_problems}"
            + (f", stalled {sum(r.stalled for r in self.reports)}"
               if self.any_stalled else "") + ")")
        if self.stats is not None:
            lines.append(self.stats.summary())
        return "\n".join(lines)
