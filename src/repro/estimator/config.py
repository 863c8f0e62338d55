"""Unified solver configuration for the ``repro.estimator`` facade.

``SolverConfig`` collects every solver knob that used to be scattered
across ``fit_reference`` keyword args, ``distributed.fit`` keyword args and
``launch/solve.py`` argparse flags into one frozen, validated dataclass.
It is hashable, so backends can use it (or fields of it) as part of a jit
static key, and ``dataclasses.replace`` gives cheap derived configs.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass

from ..core.penalty import parse_penalty
from ..core.prox import TAU_SCHEDULES

VARIANTS = ("auto", "cov", "obs")

SPARSE_MATMUL_MODES = ("off", "on", "auto")

_DTYPES = ("float32", "float64", "bfloat16")

BATCH_SCHEDULES = ("compact", "monolithic")

#: "auto" resolves per fit: the host BLAS stepper on CPU Cov compact
#: batches (where it beats one-core XLA), plain XLA everywhere else
BATCH_GEMMS = ("auto", "xla", "host")

BATCH_WARM_STARTS = (None, "pilot")

#: runtime observability levels (``repro.obs``): "off" records nothing in
#: the tracer's ring (spans still reach a running profiler); "summary"
#: records coarse per-solve spans; "trace" adds fine spans (dispatch/
#: execute split, report steps, segments and their boundaries, chunks)
#: and arms the comm reconciliation watcher on distributed solves
OBS_MODES = ("off", "summary", "trace")


@dataclass(frozen=True)
class SolverConfig:
    """Every knob of a CONCORD/HP-CONCORD solve, in one place.

    backend        which engine runs the solve: ``"reference"`` (single
                   device), ``"distributed"`` (1.5D shard_map drivers) or
                   ``"auto"`` (consults the paper's cost model, picks the
                   engine, variant and replication factors).  Backends are
                   looked up in the registry (``repro.estimator.backends``)
                   at fit time, so plugins may register new names.
    variant        ``"cov"`` (Algorithm 2, forms S), ``"obs"`` (Algorithm 3,
                   S never formed) or ``"auto"`` (cost-model crossover).
    c_x/c_omega    1.5D replication factors; ``None`` lets the tuner pick.
    n_devices      device count for the distributed grid; ``None`` = all.
    tol            relative-change convergence tolerance.
    max_iters      outer proximal-gradient iteration cap.
    max_ls         per-iteration line-search trial cap.
    warm_start_tau warm-start the line-search step size between outer
                   iterations (beyond-paper knob; saves 20-40% trials).
    dtype          compute dtype name (``None`` keeps the input dtype).
    use_pallas     use the fused Pallas prox kernel in solves (also makes
                   the block-occupancy harvest free, see sparse_matmul).
    sparse_matmul  Ω-side product routing (the matops layer):
                   ``"off"`` — always dense; ``"on"`` — block-sparse
                   below ``sparse_threshold``; ``"auto"`` — threshold from
                   the cost model's dense↔block-sparse crossover
                   (``core.costmodel.crossover_density``).
    sparse_block   occupancy-mask tile edge (128 = MXU-aligned on TPU; on
                   small/distributed problems it must divide the per-shard
                   Omega block or the solve falls back to dense).
    sparse_threshold
                   block-density crossover for ``"on"`` (default 0.25 when
                   None); for ``"auto"`` it caps the model's threshold.
    tau_schedule   per-iteration line-search start rule
                   (``core.prox.TAU_SCHEDULES``): ``None`` defers to
                   ``warm_start_tau`` (its legacy boolean form),
                   ``"restart"``/``"warm"``/``"greedy"`` force one.
    batch_schedule compact (segmented lane compaction, default) or
                   monolithic (one vmapped while_loop) batched engine.
    batch_chunk    flat steps per compact segment (compaction cadence).
    batch_max_lanes
                   wave-size cap for the compact engine (``None`` = one
                   wave; small caps help cache-limited hosts).
    batch_gemm     aux-product route of the compact engine: ``"xla"``,
                   ``"host"`` (host BLAS stepper; CPU + Cov only) or
                   ``"auto"`` (host exactly when that combination holds).
    batch_warm_start
                   ``"pilot"`` solves the median-difficulty lane first and
                   warm-starts the rest from it (path mode); ``None`` runs
                   all lanes cold.
    obs            runtime observability (``repro.obs``): ``"off"``
                   (default — nothing recorded in the tracer's ring; the
                   spans are profiler annotations only), ``"summary"``
                   (coarse per-solve spans and report telemetry; <2% wall
                   overhead on the CPU, gated by
                   ``benchmarks/obs_overhead.py``) or ``"trace"`` (adds
                   dispatch-vs-execute, report-step, per-segment/boundary
                   and chunk spans, and measured-vs-static comm
                   reconciliation on distributed solves).  Purely
                   host-side: never part of any jit static key, never
                   traced — identical compiled programs and bit-exact
                   results at every level.
    penalty        penalty family as a string form parsed by
                   ``core.penalty.parse_penalty``: ``"l1"`` (default),
                   ``"elastic_net"``, ``"scad"``/``"scad:3.7"``,
                   ``"mcp"``/``"mcp:2.5"``.  Strength comes from the
                   estimator's ``lam1``/``lam2``; penalties needing
                   matrix parameters (``weighted_l1``) are passed as a
                   ``PenaltySpec`` on the estimator instead.  The config
                   stays a hashable string so it can key jit statics.
    """
    backend: str = "auto"
    variant: str = "auto"
    c_x: int | None = None
    c_omega: int | None = None
    n_devices: int | None = None
    tol: float = 1e-5
    max_iters: int = 500
    max_ls: int = 30
    warm_start_tau: bool = False
    dtype: str | None = None
    use_pallas: bool = False
    sparse_matmul: str = "off"
    sparse_block: int = 128
    sparse_threshold: float | None = None
    penalty: str = "l1"
    tau_schedule: str | None = None
    batch_schedule: str = "compact"
    batch_chunk: int = 32
    batch_max_lanes: int | None = None
    batch_gemm: str = "auto"
    batch_warm_start: str | None = None
    obs: str = "off"

    def __post_init__(self):
        if not isinstance(self.backend, str) or not self.backend:
            raise ValueError(f"backend must be a non-empty string, got "
                             f"{self.backend!r}")
        if self.variant not in VARIANTS:
            raise ValueError(f"variant must be one of {VARIANTS}, got "
                             f"{self.variant!r}")
        for name in ("c_x", "c_omega"):
            v = getattr(self, name)
            if v is not None and (not isinstance(v, int) or v < 1):
                raise ValueError(f"{name} must be a positive int or None, "
                                 f"got {v!r}")
        if self.n_devices is not None and self.n_devices < 1:
            raise ValueError(f"n_devices must be >= 1, got {self.n_devices}")
        if not (self.tol > 0.0):
            raise ValueError(f"tol must be > 0, got {self.tol}")
        if self.max_iters < 1:
            raise ValueError(f"max_iters must be >= 1, got {self.max_iters}")
        if self.max_ls < 1:
            raise ValueError(f"max_ls must be >= 1, got {self.max_ls}")
        if self.dtype is not None and self.dtype not in _DTYPES:
            raise ValueError(f"dtype must be one of {_DTYPES} or None, got "
                             f"{self.dtype!r}")
        if self.sparse_matmul not in SPARSE_MATMUL_MODES:
            raise ValueError(f"sparse_matmul must be one of "
                             f"{SPARSE_MATMUL_MODES}, got "
                             f"{self.sparse_matmul!r}")
        if not isinstance(self.sparse_block, int) or self.sparse_block < 1:
            raise ValueError(f"sparse_block must be a positive int, got "
                             f"{self.sparse_block!r}")
        if self.sparse_threshold is not None and not (
                0.0 < self.sparse_threshold <= 1.0):
            raise ValueError(f"sparse_threshold must be in (0, 1] or None, "
                             f"got {self.sparse_threshold!r}")
        if self.tau_schedule is not None and \
                self.tau_schedule not in TAU_SCHEDULES:
            raise ValueError(f"tau_schedule must be one of {TAU_SCHEDULES} "
                             f"or None, got {self.tau_schedule!r}")
        if self.batch_schedule not in BATCH_SCHEDULES:
            raise ValueError(f"batch_schedule must be one of "
                             f"{BATCH_SCHEDULES}, got "
                             f"{self.batch_schedule!r}")
        if not isinstance(self.batch_chunk, int) or self.batch_chunk < 1:
            raise ValueError(f"batch_chunk must be a positive int, got "
                             f"{self.batch_chunk!r}")
        if self.batch_max_lanes is not None and (
                not isinstance(self.batch_max_lanes, int)
                or self.batch_max_lanes < 1):
            raise ValueError(f"batch_max_lanes must be a positive int or "
                             f"None, got {self.batch_max_lanes!r}")
        if self.batch_gemm not in BATCH_GEMMS:
            raise ValueError(f"batch_gemm must be one of {BATCH_GEMMS}, "
                             f"got {self.batch_gemm!r}")
        if self.batch_warm_start not in BATCH_WARM_STARTS:
            raise ValueError(f"batch_warm_start must be one of "
                             f"{BATCH_WARM_STARTS}, got "
                             f"{self.batch_warm_start!r}")
        if self.obs not in OBS_MODES:
            raise ValueError(f"obs must be one of {OBS_MODES}, got "
                             f"{self.obs!r}")
        if not isinstance(self.penalty, str):
            raise ValueError(
                f"config.penalty must be a penalty string form (got "
                f"{type(self.penalty).__name__}); pass PenaltySpec objects "
                f"to the estimator, not the config")
        parse_penalty(self.penalty)     # raises ValueError on bad forms

    def replace(self, **changes) -> "SolverConfig":
        """Functional update (frozen dataclass)."""
        return dataclasses.replace(self, **changes)
