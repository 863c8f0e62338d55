"""Batched multi-problem solve engine: one XLA program, many solves.

The paper's end-to-end workflow is never one solve — Section 5 sweeps a
tuning-parameter grid, and the BIGQUIC/pseudolikelihood lines of work all
select lambda by fitting whole regularization paths.  Running that grid as
a Python loop of sequential solves leaves the hardware idle between path
points.  This module lowers an entire grid to compiled batched programs:

  * ``solve_path_batched`` — a lam1 VECTOR against shared data (the
    regularization path / model-selection sweep).  The data matrix is
    closed over (broadcast, one copy in memory); only the penalty and the
    iterates carry a batch axis.
  * ``solve_batch`` — stacked ``(B, ...)`` datasets (multi-subject /
    multi-tenant workloads), each with its own penalty if desired.

Two execution schedules share the same per-lane math:

``schedule="compact"`` (default) — the segmented compaction engine.  The
solve is flattened into FLAT STEPS (one line-search trial per lane per
step, replaying the sequential trial sequence exactly: per-lane step
sizes, per-lane backtracking, per-lane convergence).  Steps run in
fixed-size jitted chunks (``_path_chunk``); at every chunk boundary the
host gathers the still-live lanes to the front, pads to the nearest
capacity tier ({1, 2, 3} x powers of two, so the whole run compiles a
handful of programs total) and launches the next chunk — per-chunk flops
scale with ACTIVE lanes, not B.  Lanes are scheduled in difficulty order
(``core.costmodel.predict_path_iters``) so same-segment lanes converge
together, and finished lanes are harvested at the boundary they complete
in.  Gathers are pure row moves and every trial is the factored
``core.prox.ls_trial``, so per-lane iterates, iteration counts and
line-search totals are BIT-EXACTLY those of B sequential solves (the
compaction test asserts array equality in f64).

``schedule="monolithic"`` — the original single-program engine: ``vmap``
of the generic ``prox_gradient`` loop, one carry-masked ``while_loop``
where converged lanes freeze bit-exactly but still burn flops.  Kept as
the zero-host-sync fallback (one dispatch for the whole grid) and as the
reference the compaction engine is asserted against.

Penalties are :class:`repro.core.penalty.PenaltySpec` pytrees whose
numeric leaves are traced, so EVERY penalty parameter — not just lam1 —
may differ per lane inside one compiled program: a spec leaf with a
leading (B,) axis (e.g. per-lane SCAD shapes, per-lane lam1) is vmapped,
shared leaves (e.g. one weight matrix) broadcast without copies
(``PenaltySpec.batch_axes``).  The legacy ``lam1``/``lam2`` arguments
build the equivalent l1 spec, bit-identically.

``tau_schedule`` selects the line-search step-size schedule
(:data:`repro.core.prox.TAU_SCHEDULES`): "restart" is the paper's and is
bit-exact against default sequential solves; "greedy" cuts total trials
~40% at identical outer iterations (assert bit-exactness against a
sequential solve run with the SAME schedule).

``use_pallas`` routes the compact engine's flat step through the fused
path-step megakernel (``kernels.pathstep``): gradient + prox + acceptance
dot products + occupancy in one pass over the tiles (Cov variant,
soft-threshold penalty family; others fall back to the jnp path).  The
kernel's tile-order reductions are not bit-identical to ``jnp.sum``, so
this trades exact reproducibility for fused dispatch — leave it off when
asserting bit-exactness.

Wall-clock: the compact engine's cost is ``sum over flat steps of the
padded capacity`` times the per-lane trial cost, so a path whose lanes
finish at different times no longer pays B times its slowest lane —
see ``benchmarks/path_batch.py`` for the measured occupancy timeline and
the speedup gate.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp

from ..obs.trace import get_tracer
from . import costmodel, matops
from .penalty import PenaltySpec, normalize_penalty
from .prox import (
    ProxResult,
    cov_ops,
    ls_trial,
    obs_ops,
    prox_gradient,
    resolve_tau_schedule,
    tau_first,
    tau_start,
)

_SOLVER_STATICS = ("variant", "tol", "max_iters", "max_ls", "warm_start_tau",
                   "tau_schedule")


#: execution schedules of the batched engine
BATCH_SCHEDULES = ("compact", "monolithic")

#: flat steps per compiled chunk: boundaries are where the host repacks
#: live lanes, so smaller chunks compact sooner but sync more often
DEFAULT_CHUNK = 32


def _variant_ops(variant: str):
    if variant == "cov":
        return cov_ops()
    if variant == "obs":
        return obs_ops()
    raise ValueError(f"unknown variant {variant!r}")


def _data_of(arr, lam2, variant: str):
    key = "s" if variant == "cov" else "x"
    return {key: arr, "lam2": jnp.asarray(lam2, arr.dtype)}


def _resolve_spec(penalty, lam1, lam2) -> tuple[PenaltySpec, object]:
    """(spec, ridge) from either a penalty spec/string or legacy floats.
    The smooth ridge is returned separately (it feeds the per-lane data
    dict exactly like the pre-spec plumbing)."""
    spec = normalize_penalty(penalty, lam1, lam2)
    return spec, spec.lam2


def _omega0_axis(omega0, p, dtype):
    if omega0 is None:
        return jnp.eye(p, dtype=dtype), None
    omega0 = jnp.asarray(omega0, dtype)
    return omega0, (0 if omega0.ndim == 3 else None)


# ---------------------------------------------------------------------------
# monolithic schedule: one vmapped while_loop for the whole grid
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=_SOLVER_STATICS)
def _solve_path_batched(
    s_or_x: jax.Array,
    penalty: PenaltySpec,
    ridge,
    omega0,
    *,
    variant: str,
    tol: float,
    max_iters: int,
    max_ls: int,
    warm_start_tau: bool,
    tau_schedule: str | None = None,
) -> ProxResult:
    ops = _variant_ops(variant)
    data = _data_of(s_or_x, ridge, variant)
    omega0, om_axis = _omega0_axis(omega0, s_or_x.shape[-1], s_or_x.dtype)
    b = penalty.lam1.shape[0]
    pleaves, ptree = jax.tree_util.tree_flatten(penalty)

    def one(om0, *pl):
        pen = jax.tree_util.tree_unflatten(ptree, pl)
        return prox_gradient(
            om0, data, ops, penalty=pen, tol=tol, max_iters=max_iters,
            max_ls=max_ls, warm_start_tau=warm_start_tau,
            tau_schedule=tau_schedule)

    return jax.vmap(one, in_axes=(om_axis, *penalty.batch_axes(b)))(
        omega0, *pleaves)


@partial(jax.jit, static_argnames=_SOLVER_STATICS)
def _solve_batch(
    s_or_x: jax.Array,
    penalty: PenaltySpec,
    ridge: jax.Array,
    omega0,
    *,
    variant: str,
    tol: float,
    max_iters: int,
    max_ls: int,
    warm_start_tau: bool,
    tau_schedule: str | None = None,
) -> ProxResult:
    b = s_or_x.shape[0]
    omega0, om_axis = _omega0_axis(omega0, s_or_x.shape[-1], s_or_x.dtype)
    pleaves, ptree = jax.tree_util.tree_flatten(penalty)

    def one(om0, arr, l2, *pl):
        pen = jax.tree_util.tree_unflatten(ptree, pl)
        return prox_gradient(
            om0, _data_of(arr, l2, variant), _variant_ops(variant),
            penalty=pen, tol=tol, max_iters=max_iters, max_ls=max_ls,
            warm_start_tau=warm_start_tau, tau_schedule=tau_schedule)

    return jax.vmap(one, in_axes=(om_axis, 0, 0, *penalty.batch_axes(b)))(
        omega0, s_or_x, ridge, *pleaves)


# ---------------------------------------------------------------------------
# compact schedule: segmented compaction over flat line-search steps
# ---------------------------------------------------------------------------

class _Lanes(NamedTuple):
    """Per-lane flat-step state (leading axis = padded capacity C)."""
    omega: jax.Array       # (C, p, p) current iterate
    aux: jax.Array         # (C, p, p) W = Omega S  /  (C, p, n) Y = Omega X^T
    g_val: jax.Array       # (C,) smooth objective at omega
    tau_try: jax.Array     # (C,) step size of the NEXT line-search trial
    delta: jax.Array       # (C,) last relative change (inf before 1st step)
    step: jax.Array        # (C,) int32 outer iterations completed
    trials: jax.Array      # (C,) int32 trials in the CURRENT outer iteration
    ls_total: jax.Array    # (C,) int32 cumulative trials
    stalled: jax.Array     # (C,) bool line search exhausted without accept
    done: jax.Array        # (C,) bool frozen (converged/stalled/capped/pad)


class BatchRunStats(NamedTuple):
    """Compaction telemetry of one batched solve (host-side ints)."""
    schedule: str          # "compact" or "monolithic"
    n_lanes: int           # B, the number of real problems
    chunk: int             # flat steps per compiled chunk
    segments: int          # chunk programs launched
    waves: int             # max_lanes waves the grid was split into
    occupancy: tuple       # live real lanes at each executed flat step
    capacities: tuple      # padded capacity at each executed flat step
    order: tuple           # lane processing order (difficulty sort)
    gemm: str = "xla"      # flat-step gemm backend (BATCH_GEMMS)
    pilot_lane: int = -1   # warm-start pilot lane index (-1 = none)
    kernel: str | None = None   # "pathstep" when the fused megakernel ran
                                # the flat steps (None: the jnp trial)

    @property
    def lane_steps(self) -> int:
        """Useful per-lane trials executed (sum of the occupancy line)."""
        return int(sum(self.occupancy))

    @property
    def padded_lane_steps(self) -> int:
        """Lane-trials actually paid for, padding included — the compact
        engine's wall-clock is proportional to this."""
        return int(sum(self.capacities))

    @property
    def mean_occupancy(self) -> float:
        """Fraction of paid lane-steps doing useful work (1.0 = no
        padding waste; the monolithic engine's analogue is
        lane_steps / (B * max lane steps))."""
        paid = self.padded_lane_steps
        return self.lane_steps / paid if paid else 1.0

    def summary(self) -> str:
        pilot = (f", pilot lane {self.pilot_lane}"
                 if self.pilot_lane >= 0 else "")
        return (f"[{self.schedule}/{self.gemm}] {self.n_lanes} lanes, "
                f"{self.segments} segments x {self.chunk} steps "
                f"({self.waves} wave{'s' if self.waves != 1 else ''}{pilot}), "
                f"occupancy {self.mean_occupancy:.0%} "
                f"({self.lane_steps}/{self.padded_lane_steps} lane-steps)")


def capacity_ladder(n_max: int) -> list:
    """Padded-capacity tiers {1, 2, 3} x powers of two up to ``n_max`` —
    the same geometric family as the matops gather tiers, bounding the
    number of compiled chunk programs at ~2 log2(B)."""
    tiers = set()
    k = 1
    while k <= n_max:
        tiers.add(k)
        if 3 * k // 2 <= n_max and (3 * k) % 2 == 0:
            tiers.add(3 * k // 2)
        k *= 2
    tiers.update({1, 2, 3} & set(range(1, n_max + 1)))
    return sorted(tiers)


def _capacity(n_live: int, b: int) -> int:
    """Smallest ladder tier >= n_live, never exceeding the grid size."""
    cap = 1
    while cap < n_live:
        cap = 3 * cap // 2 if cap % 2 == 0 and 3 * cap // 2 >= n_live \
            else cap * 2
    return min(cap, b) if cap >= n_live else b


_CHUNK_STATICS = ("variant", "tol", "max_iters", "max_ls", "tau_schedule",
                  "chunk", "stacked", "tau_init", "use_pallas")

#: gemm backends of the compact engine's flat step.  "xla" keeps the whole
#: chunk one compiled program (the default, bit-compatible with the
#: sequential reference).  "host" steps the chunk from the host and routes
#: the Omega @ S product through the platform BLAS (np.matmul): on the
#: benchmark CPU that product is ~1.5-2x faster than XLA's f64 GEMM, which
#: dominates the per-trial cost at p >= 512.  Host-BLAS results are not
#: bit-identical to XLA-GEMM results (different accumulation order), but
#: the engine stays bit-exact AGAINST ITSELF across batch sizes, waves and
#: compaction (np.matmul is bit-stable across leading batch dims), which
#: the consistency tests assert.
BATCH_GEMMS = ("xla", "host")


def _apply_trial(lanes: _Lanes, trial, *, tol: float, max_iters: int,
                 max_ls: int, tau_schedule: str, tau_init: float) -> _Lanes:
    """Advance every live lane by ONE line-search trial.

    ``trial`` is ``(cand, aux_c, g_c, dot_dd, ok, nrm2)`` — the per-lane
    candidate, its aux product and smooth objective, the squared step
    norm, the sufficient-decrease acceptance and ``<omega, omega>`` of
    the pre-trial iterate.  Accept updates the iterate and starts the
    next outer iteration at the schedule's tau, reject halves tau, and
    exhausting ``max_ls`` stalls the lane — exactly the sequential
    backtracking semantics of ``prox_gradient``, shared verbatim by the
    jitted chunk program and the host-stepped gemm="host" executor."""
    cand, aux_c, g_c, dot_dd, ok, nrm2 = trial
    dtype = lanes.omega.dtype
    live = ~lanes.done

    trials_new = lanes.trials + 1
    accept = live & ok
    exhaust = live & ~ok & (trials_new >= max_ls)
    reject = live & ~ok & (trials_new < max_ls)
    fin = accept | exhaust

    delta_acc = jnp.sqrt(dot_dd) / jnp.maximum(1.0, jnp.sqrt(nrm2))
    step_new = lanes.step + 1
    done_acc = (step_new >= max_iters) | (delta_acc < tol)
    tau_next = tau_start(tau_schedule, step_new, lanes.tau_try,
                         tau_init, dtype)

    def sel(mask, a, b):
        return jnp.where(mask.reshape(mask.shape + (1,) *
                                      (a.ndim - 1)), a, b)

    return _Lanes(
        omega=sel(accept, cand, lanes.omega),
        aux=sel(accept, aux_c, lanes.aux),
        g_val=jnp.where(accept, g_c, lanes.g_val),
        tau_try=jnp.where(
            accept, tau_next,
            jnp.where(reject, lanes.tau_try * 0.5, lanes.tau_try)),
        delta=jnp.where(accept, delta_acc,
                        jnp.where(exhaust, jnp.asarray(0.0, dtype),
                                  lanes.delta)),
        step=jnp.where(fin, step_new, lanes.step),
        trials=jnp.where(fin, 0,
                         jnp.where(reject, trials_new, lanes.trials)),
        ls_total=jnp.where(fin, lanes.ls_total + trials_new,
                           lanes.ls_total),
        stalled=lanes.stalled | exhaust,
        done=lanes.done | (accept & done_acc) | exhaust,
    )


@partial(jax.jit, static_argnames=("variant", "stacked", "tau_schedule",
                                   "tau_init"))
def _init_lanes(arr, ridge, omega0, *, variant: str, stacked: bool,
                tau_schedule: str, tau_init: float) -> _Lanes:
    """Flat-step state at the identity of the outer loop: aux and g at the
    warm start, first-trial tau from the schedule, counters zeroed."""
    ops = _variant_ops(variant)
    dtype = omega0.dtype
    c = omega0.shape[0]

    def one(om0, arr_i, l2):
        data = _data_of(arr_i, l2, variant)
        aux0 = ops.aux_of(om0, data)
        return aux0, ops.g_of(om0, aux0, data)

    aux0, g0 = jax.vmap(one, in_axes=(0, 0 if stacked else None, 0))(
        omega0, arr, ridge)
    return _Lanes(
        omega=omega0,
        aux=aux0,
        g_val=g0,
        tau_try=jnp.full((c,), tau_first(tau_schedule, tau_init), dtype),
        delta=jnp.full((c,), jnp.inf, dtype),
        step=jnp.zeros((c,), jnp.int32),
        trials=jnp.zeros((c,), jnp.int32),
        ls_total=jnp.zeros((c,), jnp.int32),
        stalled=jnp.zeros((c,), bool),
        done=jnp.zeros((c,), bool),
    )


def _uses_megakernel(use_pallas: bool, variant: str, penalty: PenaltySpec,
                     stacked: bool) -> bool:
    """Whether the flat step runs the fused path-step kernel: Cov, shared
    data and a soft-threshold-family penalty (see ``kernels.pathstep``)."""
    return use_pallas and variant == "cov" and penalty.pallas_ok \
        and not stacked


@partial(jax.jit, static_argnames=_CHUNK_STATICS)
def _path_chunk(arr, ridge, lanes: _Lanes, penalty: PenaltySpec, *,
                variant: str, tol: float, max_iters: int, max_ls: int,
                tau_schedule: str, chunk: int, stacked: bool,
                tau_init: float, use_pallas: bool):
    """Run up to ``chunk`` flat steps (one line-search trial per live lane
    per step), exiting early once every lane is done.

    One flat step replays exactly one trial of the sequential backtracking
    loop: accept updates the iterate and starts the next outer iteration
    at the schedule's tau, reject halves tau, exhausting ``max_ls``
    stalls the lane — so per-lane trajectories, iteration counts and
    trial counts are bit-identical to ``prox_gradient``'s.  Done lanes
    (and capacity padding) are select-frozen; only the CHUNK BOUNDARY
    repacks them away, so varying live-lane counts reuse this one
    program per (capacity, statics) key.

    Returns ``(lanes, occ)`` where ``occ[t]`` is the live-lane count at
    executed step t (0 on steps skipped by the early exit).
    """
    ops = _variant_ops(variant)
    c = lanes.g_val.shape[0]
    dtype = lanes.omega.dtype
    pleaves, ptree = jax.tree_util.tree_flatten(penalty)
    pallas = _uses_megakernel(use_pallas, variant, penalty, stacked)

    def trials_jnp(lanes):
        def one(om, aux, gv, tau, arr_i, l2, *pl):
            pen = jax.tree_util.tree_unflatten(ptree, pl)
            data = _data_of(arr_i, l2, variant)
            grad = ops.grad_of(om, aux, data)
            cand, aux_c, g_c, dot_dd, ok = ls_trial(
                ops, data, pen, om, grad, gv, tau)
            return cand, aux_c, g_c, dot_dd, ok, ops.dot(om, om)

        return jax.vmap(one, in_axes=(0, 0, 0, 0, 0 if stacked else None,
                                      0, *penalty.batch_axes(c)))(
            lanes.omega, lanes.aux, lanes.g_val, lanes.tau_try,
            arr, ridge, *pleaves)

    def trials_pallas(lanes):
        # fused megakernel: gradient tile + prox + acceptance dot products
        # + occupancy in one pass; only the p x p aux product and the
        # smooth objective stay in XLA (they need a matmul).
        from ..kernels import ops as kops
        tau = lanes.tau_try
        lam1 = jnp.broadcast_to(jnp.asarray(penalty.lam1, dtype), (c,))
        lam2 = jnp.broadcast_to(jnp.asarray(ridge, dtype), (c,))
        weights = penalty.weights
        if weights is not None and weights.ndim == 2:
            weights = jnp.broadcast_to(weights[None], (c,) + weights.shape)
        cand, stats = kops.fused_path_step(
            lanes.omega, lanes.aux, tau, lam1, lam2, weights=weights)
        dot_dg, dot_dd = stats[:, 0], stats[:, 1]
        aux_c = matops.dot(cand, arr)
        g_c = jax.vmap(
            lambda om, aux, l2: ops.g_of(om, aux, {"lam2": l2}))(
            cand, aux_c, jnp.asarray(lam2, dtype))
        ok = g_c <= lanes.g_val + dot_dg + dot_dd / (2.0 * tau)
        nrm2 = jnp.sum(lanes.omega * lanes.omega, axis=(1, 2))
        return cand, aux_c, g_c, dot_dd, ok, nrm2

    def body(state):
        t, lanes, occ = state
        occ = occ.at[t].set(jnp.sum(~lanes.done, dtype=jnp.int32))
        trial = trials_pallas(lanes) if pallas else trials_jnp(lanes)
        new = _apply_trial(lanes, trial, tol=tol, max_iters=max_iters,
                           max_ls=max_ls, tau_schedule=tau_schedule,
                           tau_init=tau_init)
        return t + 1, new, occ

    def cond(state):
        t, lanes, _ = state
        return (t < chunk) & jnp.any(~lanes.done)

    occ0 = jnp.zeros((chunk,), jnp.int32)
    _, lanes, occ = jax.lax.while_loop(
        cond, body, (jnp.asarray(0, jnp.int32), lanes, occ0))
    return lanes, occ


# ---------------------------------------------------------------------------
# gemm="host" executor: host-stepped chunks around the platform BLAS
# ---------------------------------------------------------------------------

def _to_host(x) -> np.ndarray:
    """Zero-copy view of a CPU jax array when possible, else a copy."""
    try:
        return np.from_dlpack(x)
    except (TypeError, RuntimeError, BufferError):
        return np.asarray(x)


@partial(jax.jit, static_argnames=("variant", "stacked"))
def _host_propose(arr, ridge, lanes: _Lanes, penalty: PenaltySpec, *,
                  variant: str, stacked: bool):
    """First half of a flat step: per-lane gradient and prox candidate at
    the lane's trial tau.  The aux product of the candidate is NOT taken
    here — the host executor runs it through np.matmul between this
    program and :func:`_host_update`."""
    ops = _variant_ops(variant)
    c = lanes.g_val.shape[0]
    pleaves, ptree = jax.tree_util.tree_flatten(penalty)

    def one(om, aux, tau, arr_i, l2, *pl):
        pen = jax.tree_util.tree_unflatten(ptree, pl)
        data = _data_of(arr_i, l2, variant)
        grad = ops.grad_of(om, aux, data)
        z = om - tau * grad
        return ops.prox(z, pen, tau, data), grad

    return jax.vmap(one, in_axes=(0, 0, 0, 0 if stacked else None, 0,
                                  *penalty.batch_axes(c)))(
        lanes.omega, lanes.aux, lanes.tau_try, arr, ridge, *pleaves)


@partial(jax.jit, static_argnames=("tol", "max_iters", "max_ls",
                                   "tau_schedule", "tau_init"))
def _host_update(ridge, lanes: _Lanes, cand, grad, aux_c, *, tol: float,
                 max_iters: int, max_ls: int, tau_schedule: str,
                 tau_init: float) -> _Lanes:
    """Second half of a flat step: smooth objective and acceptance dots of
    the host-multiplied candidate, then the shared trial-update selects.
    Cov variant only (its ``g_of``/``grad_of`` read just ``lam2`` from the
    data dict, so the data matrix never enters this program)."""
    ops = _variant_ops("cov")

    def one(om, gv, tau, cand_i, grad_i, aux_ci, l2):
        data = {"lam2": l2}
        g_c = ops.g_of(cand_i, aux_ci, data)
        diff = cand_i - om
        dot_dd = ops.dot(diff, diff)
        rhs = gv + ops.dot(diff, grad_i) + dot_dd / (2.0 * tau)
        return g_c, dot_dd, g_c <= rhs, ops.dot(om, om)

    g_c, dot_dd, ok, nrm2 = jax.vmap(one)(
        lanes.omega, lanes.g_val, lanes.tau_try, cand, grad, aux_c, ridge)
    return _apply_trial(lanes, (cand, aux_c, g_c, dot_dd, ok, nrm2),
                        tol=tol, max_iters=max_iters, max_ls=max_ls,
                        tau_schedule=tau_schedule, tau_init=tau_init)


@partial(jax.jit, static_argnames=("variant",))
def _init_g(omega0, aux0, ridge, *, variant: str):
    """Per-lane smooth objective at the warm start (aux supplied by the
    caller, so the host executor can feed a host-BLAS product)."""
    ops = _variant_ops(variant)
    return jax.vmap(lambda om, aux, l2: ops.g_of(om, aux, {"lam2": l2}))(
        omega0, aux0, ridge)


def _init_lanes_host(arr_np: np.ndarray, ridge, omega0, *,
                     tau_schedule: str, tau_init: float) -> _Lanes:
    """Host-gemm twin of :func:`_init_lanes` (cov variant): the warm-start
    aux product runs through np.matmul like every subsequent trial's."""
    dtype = omega0.dtype
    c = omega0.shape[0]
    aux0 = jnp.asarray(np.matmul(_to_host(omega0), arr_np))
    g0 = _init_g(omega0, aux0, ridge, variant="cov")
    return _Lanes(
        omega=omega0,
        aux=aux0,
        g_val=g0,
        tau_try=jnp.full((c,), tau_first(tau_schedule, tau_init), dtype),
        delta=jnp.full((c,), jnp.inf, dtype),
        step=jnp.zeros((c,), jnp.int32),
        trials=jnp.zeros((c,), jnp.int32),
        ls_total=jnp.zeros((c,), jnp.int32),
        stalled=jnp.zeros((c,), bool),
        done=jnp.zeros((c,), bool),
    )


def _host_chunk(arr, arr_np, ridge, lanes: _Lanes, penalty: PenaltySpec, *,
                variant: str, tol: float, max_iters: int, max_ls: int,
                tau_schedule: str, chunk: int, stacked: bool,
                tau_init: float, use_pallas: bool):
    """Host-stepped twin of :func:`_path_chunk`: identical flat-step
    semantics and occupancy accounting, but each step is two small jitted
    programs around a host np.matmul for the candidate's aux product.
    The host loop syncs per step anyway to drive BLAS, so the early exit
    reads the done mask directly."""
    del use_pallas  # the megakernel only applies to the jitted executor
    occ = np.zeros((chunk,), np.int32)
    for t in range(chunk):
        done_np = _to_host(lanes.done)
        # the host executor syncs per step BY DESIGN (it drives BLAS);
        # this pull is that sync, not an accidental one
        n_live = int(done_np.size - np.count_nonzero(done_np))  # ca: allow=CA106
        if n_live == 0:
            break
        occ[t] = n_live
        cand, grad = _host_propose(arr, ridge, lanes, penalty,
                                   variant=variant, stacked=stacked)
        aux_c = jnp.asarray(np.matmul(_to_host(cand), arr_np))
        lanes = _host_update(ridge, lanes, cand, grad, aux_c, tol=tol,
                             max_iters=max_iters, max_ls=max_ls,
                             tau_schedule=tau_schedule, tau_init=tau_init)
    return lanes, jnp.asarray(occ)


def _broadcast_spec(spec: PenaltySpec, b: int) -> PenaltySpec:
    """Every leaf broadcast to a lane-leading shape (lazily — no copies
    until the per-wave gather materializes a tier), so chunk-boundary
    gathers treat all penalty parameters uniformly."""
    leaves, tree = jax.tree_util.tree_flatten(spec)
    out = []
    for leaf, nd in zip(leaves, spec._expected_ndims()):
        arr = jnp.asarray(leaf)
        if arr.ndim == nd:
            arr = jnp.broadcast_to(arr, (b,) + arr.shape)
        elif arr.ndim != nd + 1 or arr.shape[0] != b:
            raise ValueError(
                f"penalty leaf of base ndim {nd} has shape {arr.shape}; "
                f"expected that or a (B={b},)-leading batch of it")
        out.append(arr)
    return jax.tree_util.tree_unflatten(tree, out)


def _take_lanes(tree, idx):
    idx = jnp.asarray(idx, jnp.int32)
    return jax.tree.map(lambda x: jnp.take(x, idx, axis=0), tree)


def _difficulty_order(spec: PenaltySpec, b: int, max_iters: int,
                      sort_lanes: bool) -> np.ndarray:
    """Processing order: hardest (most predicted iterations) first, so the
    easy tail of a wave drains together and compaction shrinks capacity
    early.  Stable-sorts on the cost model's per-lam1 prediction; without
    per-lane lam1 (or with sorting disabled) keeps input order."""
    if sort_lanes:
        lam1 = np.asarray(spec.lam1, np.float64)
        if lam1.shape == (b,) and np.all(np.isfinite(lam1)) \
                and np.all(lam1 > 0):
            pred = costmodel.predict_path_iters(lam1, max_iters=max_iters)
            return np.argsort(-pred, kind="stable").astype(np.int64)
    return np.arange(b, dtype=np.int64)


def _solve_compact(arr, spec, ridge, omega0, *, variant, tol, max_iters,
                   max_ls, tau_schedule, chunk, max_lanes, sort_lanes,
                   stacked, use_pallas, gemm="xla", warm_start=None):
    """Host driver of the compact schedule: difficulty-sorted waves, a
    gather/pad/launch loop per wave, per-boundary harvesting of finished
    lanes, and scatter back to input order.

    ``warm_start="pilot"`` prepends a one-lane wave solving the
    median-difficulty lane; every later lane warm-starts from its
    solution (each lane still bit-exactly matches a sequential solve run
    from the same omega0 — the pilot's from the user start, the rest from
    the pilot's omega).  ``gemm`` picks the flat-step executor (see
    :data:`BATCH_GEMMS`)."""
    arr = jnp.asarray(arr)
    dtype = arr.dtype
    p = arr.shape[-1]
    b = spec.lam1.shape[0]
    if chunk < 1:
        raise ValueError(f"chunk must be >= 1, got {chunk}")
    if gemm not in BATCH_GEMMS:
        raise ValueError(f"gemm must be one of {BATCH_GEMMS}, got {gemm!r}")
    if gemm == "host" and variant != "cov":
        raise ValueError("gemm='host' supports variant='cov' only")
    if gemm == "host" and use_pallas:
        raise ValueError("gemm='host' and use_pallas are mutually "
                         "exclusive (the megakernel lives in the jitted "
                         "executor)")
    if warm_start not in (None, "pilot"):
        raise ValueError(f"warm_start must be None or 'pilot', "
                         f"got {warm_start!r}")
    if warm_start == "pilot" and omega0 is not None:
        raise ValueError("warm_start='pilot' picks its own warm starts; "
                         "pass either it or omega0, not both")
    spec_b = _broadcast_spec(spec, b)
    ridge_b = jnp.broadcast_to(jnp.asarray(ridge, dtype), (b,))
    if omega0 is None:
        om_b = jnp.broadcast_to(jnp.eye(p, dtype=dtype)[None], (b, p, p))
    else:
        omega0 = jnp.asarray(omega0, dtype)
        om_b = jnp.broadcast_to(
            omega0[None] if omega0.ndim == 2 else omega0, (b, p, p))

    order = _difficulty_order(spec, b, max_iters, sort_lanes)
    wave_size = b if max_lanes is None else max(1, int(max_lanes))
    pilot_lane = -1
    if warm_start == "pilot" and b > 1:
        pilot_lane = int(order[len(order) // 2])
        rest = order[order != pilot_lane]
        waves = [np.asarray([pilot_lane], np.int64)]
        waves += [rest[i:i + wave_size] for i in range(0, b - 1, wave_size)]
    else:
        waves = [order[i:i + wave_size] for i in range(0, b, wave_size)]
    arr_np = _to_host(arr) if gemm == "host" else None

    statics = dict(variant=variant, tol=tol, max_iters=max_iters,
                   max_ls=max_ls, tau_schedule=tau_schedule, chunk=chunk,
                   stacked=stacked, tau_init=1.0, use_pallas=use_pallas)
    tracer = get_tracer()
    results: list = [None] * b
    occupancy: list = []
    capacities: list = []
    segments = 0

    def harvest(state, cur_ids):
        done = np.asarray(state.done)
        delta = np.asarray(state.delta)
        stall = np.asarray(state.stalled)
        for slot in np.flatnonzero(done & (cur_ids >= 0)):
            lane = int(cur_ids[slot])
            results[lane] = {
                "omega": np.asarray(state.omega[slot]),
                "iters": int(state.step[slot]),
                "ls_total": int(state.ls_total[slot]),
                "g_final": np.asarray(state.g_val[slot]),
                "delta_final": delta[slot],
                "stalled": bool(stall[slot]),
                "converged": bool(delta[slot] < tol) and not bool(
                    stall[slot]),
            }
        return done

    for wave_idx, wave in enumerate(waves):
        ids = np.asarray(wave, np.int64)
        cap = _capacity(len(ids), b)
        tracer.event("batch.wave", cat="batch", level="trace",
                     wave=wave_idx, lanes=len(ids))
        pad_idx = np.concatenate(
            [ids, np.full(cap - len(ids), ids[-1], np.int64)])
        real = jnp.asarray(np.arange(cap) < len(ids))
        arr_w = _take_lanes(arr, pad_idx) if stacked else arr
        ridge_w = _take_lanes(ridge_b, pad_idx)
        spec_w = _take_lanes(spec_b, pad_idx)
        om_w = _take_lanes(om_b, pad_idx)
        if gemm == "host":
            arr_np_w = _to_host(arr_w) if stacked else arr_np
            state = _init_lanes_host(arr_np_w, ridge_w, om_w,
                                     tau_schedule=tau_schedule,
                                     tau_init=1.0)
        else:
            arr_np_w = None
            state = _init_lanes(arr_w, ridge_w, om_w, variant=variant,
                                stacked=stacked, tau_schedule=tau_schedule,
                                tau_init=1.0)
        state = state._replace(done=state.done | ~real
                               | (max_iters <= 0))
        cur_ids = pad_idx.copy()
        cur_ids[len(ids):] = -1

        while True:
            n_real = int(np.sum(cur_ids >= 0))  # ca: allow=CA106 (np host array)
            with tracer.span("batch.segment", cat="batch", level="trace",
                             segment=segments, wave=wave_idx, lanes=n_real,
                             cap=cap):
                if gemm == "host":
                    state, occ = _host_chunk(arr_w, arr_np_w, ridge_w, state,
                                             spec_w, **statics)
                else:
                    state, occ = _path_chunk(arr_w, ridge_w, state, spec_w,
                                             **statics)
            segments += 1
            # the chunk boundary: wait for the device, read the chunk's
            # outputs back, repack the live lanes
            with tracer.span("batch.wait", cat="batch", level="trace"):
                jax.block_until_ready((state, occ))
            with tracer.span("batch.harvest", cat="batch", level="trace"):
                occ_np = np.asarray(occ)
                executed = occ_np[occ_np > 0]
                # a chunk's recorded count includes duplicated pad lanes;
                # clip to the real-lane count for an honest occupancy line
                occupancy.extend(int(min(v, n_real)) for v in executed)
                capacities.extend([cap] * len(executed))
                done = harvest(state, cur_ids)
                live = np.flatnonzero(~done)
            if live.size == 0:
                break
            with tracer.span("batch.repack", cat="batch", level="trace"):
                new_cap = _capacity(live.size, b)
                slots = np.concatenate(
                    [live, np.full(new_cap - live.size, live[-1], np.int64)])
                state = _take_lanes(state, slots)
                real = jnp.asarray(np.arange(new_cap) < live.size)
                state = state._replace(done=state.done | ~real)
                if stacked:
                    arr_w = _take_lanes(arr_w, slots)
                    if gemm == "host":
                        arr_np_w = _to_host(arr_w)
                ridge_w = _take_lanes(ridge_w, slots)
                spec_w = _take_lanes(spec_w, slots)
                cur_ids = cur_ids[slots]
                cur_ids[live.size:] = -1
                cap = new_cap

        if pilot_lane >= 0 and wave is waves[0]:
            om_b = jnp.broadcast_to(
                jnp.asarray(results[pilot_lane]["omega"], dtype)[None],
                (b, p, p))

    res = ProxResult(
        omega=jnp.asarray(np.stack([r["omega"] for r in results])),
        iters=jnp.asarray([r["iters"] for r in results], jnp.int32),
        ls_total=jnp.asarray([r["ls_total"] for r in results], jnp.int32),
        converged=jnp.asarray([r["converged"] for r in results], bool),
        g_final=jnp.asarray(np.stack([r["g_final"] for r in results])),
        delta_final=jnp.asarray(
            np.stack([r["delta_final"] for r in results])),
        stalled=jnp.asarray([r["stalled"] for r in results], bool),
        block_density=jnp.ones((b,), matops.DENSITY_DTYPE),
    )
    stats = BatchRunStats(
        schedule="compact", n_lanes=b, chunk=chunk, segments=segments,
        waves=len(waves), occupancy=tuple(occupancy),
        capacities=tuple(capacities), order=tuple(int(i) for i in order),
        gemm=gemm, pilot_lane=pilot_lane,
        kernel="pathstep" if _uses_megakernel(use_pallas, variant, spec,
                                              stacked) else None)
    return res, stats


def _monolithic_stats(b: int) -> BatchRunStats:
    return BatchRunStats(schedule="monolithic", n_lanes=b, chunk=0,
                         segments=1, waves=1, occupancy=(),
                         capacities=(), order=tuple(range(b)))


def _check_schedule(schedule: str) -> None:
    if schedule not in BATCH_SCHEDULES:
        raise ValueError(f"schedule must be one of {BATCH_SCHEDULES}, "
                         f"got {schedule!r}")


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

def solve_path_batched(
    s_or_x: jax.Array,
    lam1_grid: jax.Array,
    lam2: float = 0.0,
    *,
    penalty: PenaltySpec | str | None = None,
    omega0: jax.Array | None = None,
    variant: str = "cov",
    tol: float = 1e-5,
    max_iters: int = 500,
    max_ls: int = 30,
    warm_start_tau: bool = False,
    tau_schedule: str | None = None,
    schedule: str = "compact",
    chunk: int = DEFAULT_CHUNK,
    max_lanes: int | None = None,
    sort_lanes: bool = True,
    use_pallas: bool = False,
    gemm: str = "xla",
    warm_start: str | None = None,
    return_stats: bool = False,
):
    """Solve a whole lam1 grid against SHARED data with batched programs.

    ``s_or_x`` is the (p, p) sample covariance (variant="cov") or the
    (n, p) observations (variant="obs"), broadcast across the batch (one
    copy); ``lam1_grid`` is the (B,) penalty vector.  ``penalty`` swaps
    the penalty family for the whole grid (its lam1 is replaced by the
    grid; other parameters — SCAD shape, a weight matrix — are shared
    across lanes).  ``omega0`` may be None (identity start for every
    point), a single (p, p) warm start shared by all points, or a stacked
    (B, p, p) per-point start.  Returns a :class:`ProxResult` whose every
    field carries a leading (B,) axis — per-lane values bit-exactly equal
    to B sequential solves — or ``(result, BatchRunStats)`` with
    ``return_stats``.

    ``schedule="compact"`` (default) runs the segmented compaction engine
    (chunked flat steps, live lanes repacked at boundaries so flops track
    active lanes); ``"monolithic"`` is the original one-dispatch vmapped
    while_loop.  ``chunk``/``max_lanes``/``sort_lanes`` tune the compact
    engine (steps per segment, wave size, difficulty-sorted scheduling);
    ``tau_schedule`` selects the per-lane line-search schedule
    (:data:`~repro.core.prox.TAU_SCHEDULES`); ``use_pallas`` routes the
    flat step through the fused path-step megakernel (Cov +
    soft-threshold penalties; not bit-exact, see the module docstring).

    ``gemm="host"`` steps chunks from the host and runs the candidate's
    aux product through the platform BLAS (:data:`BATCH_GEMMS` — Cov
    variant, compact schedule); ``warm_start="pilot"`` solves the
    median-difficulty lane first and warm-starts the rest from it.  Both
    preserve "each lane equals a sequential solve from the same omega0
    with the same gemm"; neither is bit-compatible with the defaults.
    """
    lam1_grid = jnp.asarray(lam1_grid)
    if lam1_grid.ndim != 1:
        raise ValueError(f"lam1_grid must be 1-D, got shape {lam1_grid.shape}")
    if penalty is None:
        spec, ridge = PenaltySpec("l1", lam1_grid), lam2
    else:
        # the grid IS the strength here, so a string form needs only its
        # kind/shape — feed a placeholder lam1 that the grid replaces
        base, ridge = _resolve_spec(
            penalty, 0.0 if isinstance(penalty, str) else None, lam2)
        spec = base.with_lam1(lam1_grid)
    _check_schedule(schedule)
    if schedule == "monolithic":
        if gemm != "xla" or warm_start is not None:
            raise ValueError("gemm/warm_start are compact-schedule knobs; "
                             "schedule='monolithic' supports neither")
        res = _solve_path_batched(
            s_or_x, spec, ridge, omega0, variant=variant, tol=tol,
            max_iters=max_iters, max_ls=max_ls,
            warm_start_tau=warm_start_tau, tau_schedule=tau_schedule)
        return (res, _monolithic_stats(lam1_grid.shape[0])) \
            if return_stats else res
    res, stats = _solve_compact(
        s_or_x, spec, ridge, omega0, variant=variant, tol=tol,
        max_iters=max_iters, max_ls=max_ls,
        tau_schedule=resolve_tau_schedule(tau_schedule, warm_start_tau),
        chunk=chunk, max_lanes=max_lanes, sort_lanes=sort_lanes,
        stacked=False, use_pallas=use_pallas, gemm=gemm,
        warm_start=warm_start)
    return (res, stats) if return_stats else res


def solve_batch(
    s_or_x: jax.Array,
    lam1: jax.Array | None = None,
    lam2: jax.Array = 0.0,
    *,
    penalty: PenaltySpec | str | None = None,
    omega0: jax.Array | None = None,
    variant: str = "cov",
    tol: float = 1e-5,
    max_iters: int = 500,
    max_ls: int = 30,
    warm_start_tau: bool = False,
    tau_schedule: str | None = None,
    schedule: str = "compact",
    chunk: int = DEFAULT_CHUNK,
    max_lanes: int | None = None,
    sort_lanes: bool = True,
    gemm: str = "xla",
    return_stats: bool = False,
):
    """Solve B stacked independent problems with batched programs.

    ``s_or_x`` is (B, p, p) stacked covariances (variant="cov") or
    (B, n, p) stacked observation matrices (variant="obs") — every problem
    shares one shape, the server-side bucketing invariant.  ``lam1`` and
    ``lam2`` are scalars (shared) or (B,) vectors (per-problem);
    equivalently ``penalty`` carries the whole spec, and ANY of its
    numeric leaves may be (B,)-batched for per-lane penalty parameters
    (e.g. per-lane SCAD shapes) inside the single compiled program.
    ``omega0`` is None, one shared (p, p) start, or stacked (B, p, p).
    Returns a :class:`ProxResult` with a leading (B,) axis on every field
    (or ``(result, BatchRunStats)`` with ``return_stats``); the
    ``schedule``/``chunk``/``max_lanes``/``sort_lanes``/``tau_schedule``
    knobs are as in :func:`solve_path_batched`.
    """
    s_or_x = jnp.asarray(s_or_x)
    if s_or_x.ndim != 3:
        raise ValueError(
            f"solve_batch expects stacked (B, n|p, p) data, got shape "
            f"{s_or_x.shape}")
    b = s_or_x.shape[0]
    spec, ridge = _resolve_spec(penalty, lam1, lam2)
    lam1_b = jnp.broadcast_to(jnp.asarray(spec.lam1, s_or_x.dtype), (b,))
    spec = spec.with_lam1(lam1_b)
    ridge_b = jnp.broadcast_to(jnp.asarray(ridge, s_or_x.dtype), (b,))
    _check_schedule(schedule)
    if schedule == "monolithic":
        if gemm != "xla":
            raise ValueError("gemm is a compact-schedule knob; "
                             "schedule='monolithic' is always XLA")
        res = _solve_batch(
            s_or_x, spec, ridge_b, omega0, variant=variant, tol=tol,
            max_iters=max_iters, max_ls=max_ls,
            warm_start_tau=warm_start_tau, tau_schedule=tau_schedule)
        return (res, _monolithic_stats(b)) if return_stats else res
    res, stats = _solve_compact(
        s_or_x, spec, ridge_b, omega0, variant=variant, tol=tol,
        max_iters=max_iters, max_ls=max_ls,
        tau_schedule=resolve_tau_schedule(tau_schedule, warm_start_tau),
        chunk=chunk, max_lanes=max_lanes, sort_lanes=sort_lanes,
        stacked=True, use_pallas=False, gemm=gemm)
    return (res, stats) if return_stats else res


# ---------------------------------------------------------------------------
# analysis manifest (repro.analysis.jaxprpass)
# ---------------------------------------------------------------------------

def _analysis_cov(p):
    return jnp.eye(p, dtype=jnp.float64) + 0.05 * jnp.ones((p, p),
                                                           jnp.float64)


def _analysis_path():
    p, b = 6, 3
    spec = PenaltySpec("l1", jnp.linspace(0.1, 0.3, b, dtype=jnp.float64),
                       jnp.asarray(0.0, jnp.float64))
    fn = partial(_solve_path_batched, variant="cov", tol=1e-3, max_iters=5,
                 max_ls=5, warm_start_tau=False)
    return {"fn": fn,
            "args": (_analysis_cov(p), spec, jnp.asarray(0.0, jnp.float64),
                     None)}


def _analysis_path_reuse():
    s = _analysis_cov(6)

    def run(lo):
        grid = jnp.linspace(lo, lo + 0.2, 3, dtype=jnp.float64)
        res = solve_path_batched(s, grid, tol=1e-3, max_iters=4, max_ls=4,
                                 schedule="monolithic")
        return res.omega.block_until_ready()

    return {"watched": {"core.batch._solve_path_batched":
                        _solve_path_batched},
            "calls": [partial(run, 0.10), partial(run, 0.15),
                      partial(run, 0.20)]}


def _analysis_batch():
    p, b = 6, 2
    s = jnp.stack([_analysis_cov(p)] * b)
    spec = PenaltySpec("l1", jnp.linspace(0.1, 0.2, b, dtype=jnp.float64),
                       jnp.asarray(0.0, jnp.float64))
    ridge = jnp.zeros((b,), jnp.float64)
    fn = partial(_solve_batch, variant="cov", tol=1e-3, max_iters=5,
                 max_ls=5, warm_start_tau=False)
    return {"fn": fn, "args": (s, spec, ridge, None)}


def _analysis_batch_reuse():
    s = jnp.stack([_analysis_cov(6)] * 2)

    def run(lam1):
        res = solve_batch(s, jnp.asarray([lam1, lam1 + 0.05], jnp.float64),
                          tol=1e-3, max_iters=4, max_ls=4,
                          schedule="monolithic")
        return res.omega.block_until_ready()

    return {"watched": {"core.batch._solve_batch": _solve_batch},
            "calls": [partial(run, 0.10), partial(run, 0.16),
                      partial(run, 0.22)]}


def _chunk_statics():
    return dict(variant="cov", tol=1e-3, max_iters=4, max_ls=4,
                tau_schedule="greedy", chunk=3, stacked=False,
                tau_init=1.0, use_pallas=False)


def _analysis_chunk():
    p, c = 6, 3
    s = _analysis_cov(p)
    spec = PenaltySpec("l1", jnp.linspace(0.1, 0.3, c, dtype=jnp.float64),
                       jnp.zeros((c,), jnp.float64))
    ridge = jnp.zeros((c,), jnp.float64)
    om0 = jnp.broadcast_to(jnp.eye(p, dtype=jnp.float64)[None], (c, p, p))
    lanes = _init_lanes(s, ridge, om0, variant="cov", stacked=False,
                        tau_schedule="greedy", tau_init=1.0)
    fn = partial(_path_chunk, **_chunk_statics())
    return {"fn": fn, "args": (s, ridge, lanes, spec)}


def _analysis_chunk_reuse():
    p, c = 6, 4
    s = _analysis_cov(p)
    spec = PenaltySpec("l1", jnp.full((c,), 0.2, jnp.float64),
                       jnp.zeros((c,), jnp.float64))
    ridge = jnp.zeros((c,), jnp.float64)
    om0 = jnp.broadcast_to(jnp.eye(p, dtype=jnp.float64)[None], (c, p, p))
    statics = dict(_chunk_statics(), tau_schedule="restart")

    def run(n_live):
        lanes = _init_lanes(s, ridge, om0, variant="cov", stacked=False,
                            tau_schedule="restart", tau_init=1.0)
        lanes = lanes._replace(done=jnp.arange(c) >= n_live)
        out, _ = _path_chunk(s, ridge, lanes, spec, **statics)
        return out.omega.block_until_ready()

    # the compaction contract: 4, then 2, then 1 live lanes at one
    # capacity tier must all hit the SAME compiled chunk program
    return {"watched": {"core.batch._path_chunk": _path_chunk},
            "calls": [partial(run, 4), partial(run, 2), partial(run, 1)]}


#: the batched lambda-path and multi-problem engines: one compiled
#: program per (shape, penalty kind, statics) key is THE contract here,
#: and for the compact engine one chunk program per capacity tier
#: regardless of how many lanes are live inside it
ANALYSIS_ENTRIES = [
    {"name": "core.batch.solve_path_batched",
     "path": "src/repro/core/batch.py", "axis_names": (),
     "build": _analysis_path, "reuse": _analysis_path_reuse},
    {"name": "core.batch.solve_batch", "path": "src/repro/core/batch.py",
     "axis_names": (), "build": _analysis_batch,
     "reuse": _analysis_batch_reuse},
    {"name": "core.batch.path_chunk", "path": "src/repro/core/batch.py",
     "axis_names": (), "build": _analysis_chunk,
     "reuse": _analysis_chunk_reuse},
]
