"""Proximal gradient driver for CONCORD/PseudoNet (paper Algorithms 1-3).

The loop is generic over a ``VariantOps`` bundle so that the single-device
reference (this file), the distributed Cov driver and the distributed Obs
driver (core/distributed.py) all share identical control flow:

    aux_of(omega, data[, mask]) -> aux     # the per-line-search product
                                           #   cov: W = Omega @ S
                                           #   obs: Y = Omega @ X^T
    g_of(omega, aux, data)     -> scalar   # smooth objective from aux
                                           #   (returns +inf when diag <= 0)
    grad_of(omega, aux, data)  -> grad     # once per outer iteration
                                           #   cov: uses W and the distributed
                                           #        transpose W^T
                                           #   obs: forms Z = Y @ X / n, Z^T
    dot(a, b)                  -> scalar   # global <A, B> (psum'd on shards)
    prox(z, penalty, tau, data) -> array   # prox of tau*penalty, diag exempt

The penalty is a :class:`repro.core.penalty.PenaltySpec`: a pytree whose
kind (l1 / weighted_l1 / scad / mcp / ...) is static and whose numeric
parameters are traced leaves, so a warm-started lambda path or a batched
grid with per-lane penalty parameters reuses ONE compiled program.  The
legacy ``lam1=`` float keyword still works everywhere and constructs the
equivalent l1 spec (bit-identical solve).

Three optional ops switch on the sparsity-aware matmul path (core.matops):

    prox_stats(z, penalty, tau, data) -> (array, mask)
                                           # prox + the harvested
                                           # block-occupancy mask of the
                                           # new iterate (free with the
                                           # fused Pallas prox kernel)
    mask_of(omega, data)       -> mask     # occupancy of a warm start
    density_of(mask)           -> scalar   # GLOBAL block density (psum'd
                                           # on shards)

When ``prox_stats`` is set, the loop threads the mask of the current
iterate through the carry and hands it to ``aux_of`` so every Ω-side
product can route through the block-sparse kernels once the observed
density crosses the policy threshold.

The distributed drivers run this exact function INSIDE shard_map: `omega`
and `aux` are then per-device shards and the ops close over collectives.
Control flow is fully jax.lax (while_loop both levels) so a whole solve
lowers as one XLA program with the 1.5D collectives inlined.

The loop is also ``jax.vmap``-able over a stacked problem axis (the batched
multi-problem engine in ``core.batch``): under vmap a ``while_loop`` keeps
running until EVERY lane's condition is false and the body executes for all
lanes each round, so both loop bodies freeze their already-finished lanes
(accepted line searches, converged/stalled outer iterations) by selecting
the old carry — finished problems hold their state bit-exactly while
stragglers keep iterating.
"""
from __future__ import annotations

import warnings
from functools import partial
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from . import matops
from .objective import (
    gradient_from_w,
    smooth_objective_cov,
    smooth_objective_obs,
)
from .penalty import PenaltySpec, normalize_penalty


class VariantOps(NamedTuple):
    aux_of: Callable
    g_of: Callable
    grad_of: Callable
    dot: Callable
    prox: Callable
    prox_stats: Callable | None = None    # enables the block-sparse path
    mask_of: Callable | None = None
    density_of: Callable | None = None


class ProxResult(NamedTuple):
    omega: jax.Array
    iters: jax.Array        # outer proximal-gradient iterations taken (s)
    ls_total: jax.Array     # total line-search trials (s*t)
    converged: jax.Array    # genuine delta < tol exit (never set on a stall)
    g_final: jax.Array
    delta_final: jax.Array
    stalled: jax.Array = False      # line search exhausted max_ls without
                                    # accepting a step; iterate unchanged
    block_density: jax.Array = 1.0  # observed final block density (1.0 dense)


class _Carry(NamedTuple):
    omega: jax.Array
    aux: jax.Array
    mask: jax.Array | None
    g_val: jax.Array
    step: jax.Array
    ls_total: jax.Array
    delta: jax.Array
    tau_prev: jax.Array
    stalled: jax.Array


class _LsCarry(NamedTuple):
    tau: jax.Array
    omega_new: jax.Array
    aux_new: jax.Array
    mask_new: jax.Array | None
    g_new: jax.Array
    accepted: jax.Array
    trials: jax.Array


def guard_nonpos_diag(g, min_diag):
    """+inf objective if any diagonal entry is non-positive (log barrier)."""
    bad = (min_diag <= 0.0) | jnp.isnan(g)
    return jnp.where(bad, jnp.inf, g)


# ---------------------------------------------------------------------------
# line-search step-size schedules (shared with the batched flat-step engine)
# ---------------------------------------------------------------------------

#: step-size schedules for the backtracking line search:
#:   "restart"  tau restarts at tau_init every outer iteration (the paper)
#:   "warm"     first trial is min(2 * last accepted tau, tau_init)
#:              (the legacy warm_start_tau=True behaviour, bit-identical)
#:   "greedy"   first-ever trial starts at tau_init/4 and later iterations
#:              grow the accepted tau by 1.3x (capped at tau_init).  On the
#:              benchmark path shapes this cuts total trials ~40% below
#:              "restart" while taking the IDENTICAL outer-iteration count
#:              (the accepted steps coincide; only rejected probes differ).
TAU_SCHEDULES = ("restart", "warm", "greedy")

#: "greedy" constants, measured on the BENCH_path_batch shapes: growing a
#: just-accepted tau by 1.3 (not 2.0) re-rejects far less often, and a
#: conservative first-ever trial skips the cold-start rejection cascade.
GREEDY_TAU_GROWTH = 1.3
GREEDY_TAU_FIRST = 0.25


def resolve_tau_schedule(tau_schedule: str | None,
                         warm_start_tau: bool) -> str:
    """Canonical schedule name; ``None`` keeps the legacy bool semantics
    (``warm_start_tau=True`` is exactly the "warm" schedule)."""
    if tau_schedule is None:
        return "warm" if warm_start_tau else "restart"
    if tau_schedule not in TAU_SCHEDULES:
        raise ValueError(f"tau_schedule must be one of {TAU_SCHEDULES} or "
                         f"None, got {tau_schedule!r}")
    return tau_schedule


def tau_first(schedule: str, tau_init):
    """First-ever trial step size (outer step 0) under a schedule."""
    return GREEDY_TAU_FIRST * tau_init if schedule == "greedy" else tau_init


def tau_start(schedule: str, step, tau_prev, tau_init, dtype):
    """First-trial step size of an outer iteration: ``step`` is the outer
    iteration counter and ``tau_prev`` the tau the previous line search
    ended at (its accepted step).  Shared verbatim by the sequential loop
    and the batched flat-step engine so their trial sequences — and hence
    iterates — stay bit-identical."""
    if schedule == "restart":
        return jnp.asarray(tau_init, dtype)
    growth = 2.0 if schedule == "warm" else GREEDY_TAU_GROWTH
    return jnp.where(
        step > 0,
        jnp.minimum(growth * tau_prev, tau_init),
        jnp.asarray(tau_first(schedule, tau_init), dtype),
    )


def ls_trial(ops: VariantOps, data, penalty, omega, grad, g_val, tau):
    """One backtracking trial at step size ``tau`` (dense product path).

    Returns ``(cand, aux_c, g_c, dot_dd, ok)``: the prox candidate, its
    aux product and smooth objective, the squared step norm
    ``<cand - omega, cand - omega>`` (reused by the relative-change test),
    and the sufficient-decrease acceptance.  This is the exact trial math
    of :func:`prox_gradient`'s inner loop, factored out so the batched
    flat-step engine (``core.batch``) replays bit-identical trials."""
    z = omega - tau * grad
    cand = ops.prox(z, penalty, tau, data)
    aux_c = ops.aux_of(cand, data)
    g_c = ops.g_of(cand, aux_c, data)
    diff = cand - omega
    dot_dd = ops.dot(diff, diff)
    rhs = g_val + ops.dot(diff, grad) + dot_dd / (2.0 * tau)
    return cand, aux_c, g_c, dot_dd, g_c <= rhs


def prox_gradient(
    omega0: jax.Array,
    data,
    ops: VariantOps,
    *,
    penalty: PenaltySpec | None = None,
    lam1: float | None = None,
    tol: float = 1e-5,
    max_iters: int = 500,
    max_ls: int = 30,
    tau_init: float = 1.0,
    warm_start_tau: bool = False,
    tau_schedule: str | None = None,
) -> ProxResult:
    """Run the CONCORD/PseudoNet proximal gradient method.

    The penalty enters only through ``ops.prox``/``ops.prox_stats``;
    pass a :class:`PenaltySpec` (its parameters stay traced), or the
    legacy ``lam1=`` float which builds the equivalent l1 spec.

    warm_start_tau=False reproduces the paper exactly (tau restarts at
    tau_init every outer iteration); True starts from 2x the previously
    accepted step, which typically saves 20-40% of line-search trials
    (beyond-paper knob, still provably convergent by the same argument).
    ``tau_schedule`` names a schedule from :data:`TAU_SCHEDULES` explicitly
    and overrides the bool ("greedy" saves the most trials); ``None``
    keeps the legacy ``warm_start_tau`` semantics bit-exactly.
    """
    if penalty is None:
        if lam1 is None:
            raise TypeError("prox_gradient needs penalty= (or the legacy "
                            "lam1= float)")
        # raw constructor on purpose: lam1 may be a tracer (vmapped lanes)
        penalty = PenaltySpec("l1", lam1)
    elif lam1 is not None:
        raise ValueError("pass either penalty= or lam1=, not both")
    schedule = resolve_tau_schedule(tau_schedule, warm_start_tau)
    dtype = jnp.result_type(omega0)
    sparse = ops.prox_stats is not None
    if sparse:
        mask0 = ops.mask_of(omega0, data)
        aux0 = ops.aux_of(omega0, data, mask0)
    else:
        mask0 = None
        aux0 = ops.aux_of(omega0, data)
    g0 = ops.g_of(omega0, aux0, data)

    def ls_cond(ls: _LsCarry):
        return (~ls.accepted) & (ls.trials < max_ls)

    def outer_body(carry: _Carry) -> _Carry:
        grad = ops.grad_of(carry.omega, carry.aux, data)

        tau0 = tau_start(schedule, carry.step, carry.tau_prev, tau_init,
                         dtype)

        def ls_try(tau):
            if sparse:
                z = carry.omega - tau * grad
                cand, mask_c = ops.prox_stats(z, penalty, tau, data)
                aux_c = ops.aux_of(cand, data, mask_c)
                g_c = ops.g_of(cand, aux_c, data)
                diff = cand - carry.omega
                rhs = (
                    carry.g_val
                    + ops.dot(diff, grad)
                    + ops.dot(diff, diff) / (2.0 * tau)
                )
                return cand, aux_c, mask_c, g_c, g_c <= rhs
            cand, aux_c, g_c, _, ok = ls_trial(
                ops, data, penalty, carry.omega, grad, carry.g_val, tau)
            return cand, aux_c, None, g_c, ok

        def ls_body(ls: _LsCarry) -> _LsCarry:
            tau = ls.tau * 0.5
            cand, aux_c, mask_c, g_c, ok = ls_try(tau)
            nxt = _LsCarry(tau, cand, aux_c, mask_c, g_c, ok, ls.trials + 1)
            # Freeze lanes that already accepted: under vmap the loop keeps
            # running while ANY lane still searches, and the body executes
            # for all of them.
            return jax.tree.map(
                lambda n, o: jnp.where(ls.accepted, o, n), nxt, ls)

        cand0, aux_c0, mask_c0, g_c0, ok0 = ls_try(tau0)
        ls = jax.lax.while_loop(
            ls_cond,
            ls_body,
            _LsCarry(tau0, cand0, aux_c0, mask_c0, g_c0, ok0,
                     jnp.asarray(1, jnp.int32)),
        )

        diff = ls.omega_new - carry.omega
        delta = jnp.sqrt(ops.dot(diff, diff)) / jnp.maximum(
            1.0, jnp.sqrt(ops.dot(carry.omega, carry.omega))
        )
        # If the line search exhausted max_ls without acceptance, keep the
        # old iterate and STALL: delta is zeroed so the outer loop exits,
        # and the stalled flag records that this was not a genuine
        # delta < tol convergence (the old behaviour reported
        # converged=True here, which lied).
        omega_next = jnp.where(ls.accepted, ls.omega_new, carry.omega)
        aux_next = jax.tree.map(
            lambda a, b: jnp.where(ls.accepted, a, b), ls.aux_new, carry.aux
        )
        mask_next = jax.tree.map(
            lambda a, b: jnp.where(ls.accepted, a, b), ls.mask_new, carry.mask
        )
        g_next = jnp.where(ls.accepted, ls.g_new, carry.g_val)
        delta = jnp.where(ls.accepted, delta, jnp.asarray(0.0, dtype))
        nxt = _Carry(
            omega=omega_next,
            aux=aux_next,
            mask=mask_next,
            g_val=g_next,
            step=carry.step + 1,
            ls_total=carry.ls_total + ls.trials,
            delta=delta,
            tau_prev=ls.tau,
            stalled=~ls.accepted,
        )
        # Freeze finished lanes (converged, stalled or iteration-capped):
        # under vmap the outer while_loop runs until every lane is done and
        # the body executes for all of them, so a finished problem must
        # hold its carry bit-exactly while stragglers keep iterating.
        active = outer_cond(carry)
        return jax.tree.map(lambda n, o: jnp.where(active, n, o), nxt, carry)

    def outer_cond(carry: _Carry):
        return (carry.step < max_iters) & (carry.delta >= tol)

    init = _Carry(
        omega=omega0,
        aux=aux0,
        mask=mask0,
        g_val=g0,
        step=jnp.asarray(0, jnp.int32),
        ls_total=jnp.asarray(0, jnp.int32),
        delta=jnp.asarray(jnp.inf, dtype),
        tau_prev=jnp.asarray(tau_init, dtype),
        stalled=jnp.asarray(False),
    )
    final = jax.lax.while_loop(outer_cond, outer_body, init)
    if sparse:
        density_of = ops.density_of or matops.block_density
        density = density_of(final.mask)
    else:
        density = jnp.asarray(1.0, matops.DENSITY_DTYPE)
    return ProxResult(
        omega=final.omega,
        iters=final.step,
        ls_total=final.ls_total,
        converged=(final.delta < tol) & ~final.stalled,
        g_final=final.g_val,
        delta_final=final.delta,
        stalled=final.stalled,
        block_density=density,
    )


# ---------------------------------------------------------------------------
# Single-device reference variants (the oracles for the distributed drivers).
# ---------------------------------------------------------------------------

def _ref_dot(a, b):
    return jnp.sum(a * b)


def _ref_prox(z, pen, tau, data):
    return pen.prox(z, tau)


def _ref_prox_op(use_pallas: bool) -> Callable:
    """The prox of the single-device variants: with ``use_pallas`` the
    fused Pallas kernel for the soft-threshold penalty family (as in the
    distributed drivers), the jnp prox otherwise."""
    if not use_pallas:
        return _ref_prox

    def prox(z, pen, tau, data):
        if not pen.pallas_ok:
            return pen.prox(z, tau)
        from ..kernels import ops as kops
        eye = jnp.eye(z.shape[-1], dtype=z.dtype)
        return kops.fused_prox(z, eye, tau * pen.lam1, weights=pen.weights)

    return prox


def _ref_sparse_ops(policy: matops.MatmulPolicy, use_pallas: bool):
    """(prox_stats, mask_of, density_of) for the single-device variants.

    With ``use_pallas`` the occupancy mask is harvested for free from the
    fused prox kernel's per-tile nnz stats lane (soft-threshold penalty
    family only; SCAD/MCP fall back to the jnp prox + one mask pass); the
    jnp path computes the same mask in one extra cheap pass (it is the
    kernel's oracle)."""
    bs = policy.block_size

    def prox_stats(z, pen, tau, data):
        if use_pallas and pen.pallas_ok:
            from ..kernels import ops as kops
            eye = jnp.eye(z.shape[-1], dtype=z.dtype)
            out, _, _, _, _, bnnz = kops.fused_prox_stats(
                z, eye, tau * pen.lam1, weights=pen.weights, block=(bs, bs))
            return out, (bnnz > 0).astype(matops.MASK_DTYPE)
        out = pen.prox(z, tau)
        return out, matops.block_mask(out, bs)

    def mask_of(omega, data):
        return matops.block_mask(omega, bs)

    def density_of(mask):
        return matops.block_density(mask)

    return prox_stats, mask_of, density_of


def cov_ops(sparse_matmul: matops.MatmulPolicy | None = None,
            use_pallas: bool = False) -> VariantOps:
    """Reference Cov variant: data = {'s': S, 'lam2': lam2}.

    ``sparse_matmul`` routes W = Omega @ S through the matops block-sparse
    dispatch, with the occupancy mask maintained by the prox step."""
    policy = sparse_matmul

    def aux_of(omega, data, mask=None):
        return matops.matmul(omega, data["s"], mask=mask, policy=policy)

    def g_of(omega, w, data):
        g = smooth_objective_cov(omega, w, data["lam2"])
        return guard_nonpos_diag(g, jnp.min(jnp.diagonal(omega)))

    def grad_of(omega, w, data):
        return gradient_from_w(omega, w, data["lam2"])

    prox = _ref_prox_op(use_pallas)
    if policy is None or not policy.enabled:
        return VariantOps(aux_of, g_of, grad_of, _ref_dot, prox)
    return VariantOps(aux_of, g_of, grad_of, _ref_dot, prox,
                      *_ref_sparse_ops(policy, use_pallas))


def obs_ops(sparse_matmul: matops.MatmulPolicy | None = None,
            use_pallas: bool = False) -> VariantOps:
    """Reference Obs variant: data = {'x': X, 'lam2': lam2}; S never formed.

    ``sparse_matmul`` routes Y = Omega @ X^T through the matops dispatch."""
    policy = sparse_matmul

    def aux_of(omega, data, mask=None):
        return matops.matmul(omega, data["x"].T, mask=mask,
                             policy=policy)     # Y, unnormalized

    def g_of(omega, y, data):
        g = smooth_objective_obs(omega, y, data["x"].shape[0], data["lam2"])
        return guard_nonpos_diag(g, jnp.min(jnp.diagonal(omega)))

    def grad_of(omega, y, data):
        x = data["x"]
        z = matops.dot(y, x) / x.shape[0]     # Z = Omega S
        return gradient_from_w(omega, z, data["lam2"])

    prox = _ref_prox_op(use_pallas)
    if policy is None or not policy.enabled:
        return VariantOps(aux_of, g_of, grad_of, _ref_dot, prox)
    return VariantOps(aux_of, g_of, grad_of, _ref_dot, prox,
                      *_ref_sparse_ops(policy, use_pallas))


@partial(jax.jit, static_argnames=("variant", "tol", "max_iters", "max_ls",
                                   "warm_start_tau", "tau_schedule",
                                   "sparse_matmul", "use_pallas"))
def _solve_reference(
    s_or_x: jax.Array,
    penalty: PenaltySpec,
    omega0: jax.Array | None,
    *,
    variant: str,
    tol: float,
    max_iters: int,
    max_ls: int,
    warm_start_tau: bool,
    sparse_matmul: matops.MatmulPolicy | None,
    use_pallas: bool,
    tau_schedule: str | None = None,
) -> ProxResult:
    """Jitted engine behind :func:`solve_reference`.  The penalty spec's
    numeric leaves (lam1, lam2, shape, weights) and ``omega0`` are traced,
    so a regularization path over same-shape problems reuses one compiled
    program per (shape, penalty kind, statics) key."""
    if variant == "cov":
        data = {"s": s_or_x, "lam2": jnp.asarray(penalty.lam2, s_or_x.dtype)}
        ops = cov_ops(sparse_matmul, use_pallas)
    elif variant == "obs":
        data = {"x": s_or_x, "lam2": jnp.asarray(penalty.lam2, s_or_x.dtype)}
        ops = obs_ops(sparse_matmul, use_pallas)
    else:
        raise ValueError(f"unknown variant {variant!r}")
    p = s_or_x.shape[-1]
    if omega0 is None:
        omega0 = jnp.eye(p, dtype=s_or_x.dtype)
    return prox_gradient(
        omega0, data, ops, penalty=penalty, tol=tol,
        max_iters=max_iters, max_ls=max_ls, warm_start_tau=warm_start_tau,
        tau_schedule=tau_schedule,
    )


def solve_reference(
    s_or_x: jax.Array,
    lam1: float | None = None,
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
    sparse_matmul: matops.MatmulPolicy | None = None,
    use_pallas: bool = False,
) -> ProxResult:
    """Single-device CONCORD/PseudoNet solve. variant='cov' expects S, 'obs'
    expects X. ``omega0`` warm-starts the iterates (defaults to the identity).

    The penalty comes either from ``penalty=`` (a
    :class:`~repro.core.penalty.PenaltySpec` or string form, which also
    carries the smooth ridge in its ``lam2`` field) or from the legacy
    ``lam1``/``lam2`` floats (the equivalent l1 spec, bit-identical solve).
    All penalty parameters and ``omega0`` are traced, so a regularization
    path over same-shape problems reuses one compiled program per
    (shape, penalty kind, statics) key.

    ``sparse_matmul`` (a hashable :class:`repro.core.matops.MatmulPolicy`)
    routes the Ω-side product through the block-sparse dispatch once the
    observed block density of the iterate drops below the policy threshold.
    ``use_pallas`` runs the prox through the fused Pallas kernel
    (soft-threshold penalty family), which with ``sparse_matmul`` also
    harvests the occupancy mask instead of a separate jnp pass.
    """
    spec = normalize_penalty(penalty, lam1, lam2)
    if spec.weights is not None:
        p = s_or_x.shape[-1]
        wshape = getattr(spec.weights, "shape", None)
        if wshape != (p, p):
            raise ValueError(
                f"penalty weights shape {wshape} must match the problem "
                f"dimension ({p}, {p})")
    return _solve_reference(
        s_or_x, spec, omega0, variant=variant, tol=tol,
        max_iters=max_iters, max_ls=max_ls, warm_start_tau=warm_start_tau,
        tau_schedule=tau_schedule, sparse_matmul=sparse_matmul,
        use_pallas=use_pallas,
    )


def fit_reference(
    s_or_x: jax.Array,
    lam1: float,
    lam2: float = 0.0,
    *,
    variant: str = "cov",
    tol: float = 1e-5,
    max_iters: int = 500,
    max_ls: int = 30,
    warm_start_tau: bool = False,
) -> ProxResult:
    """Deprecated shim — use :mod:`repro.estimator` (``ConcordEstimator`` with
    ``backend='reference'``) or :func:`solve_reference` directly."""
    warnings.warn(
        "fit_reference is deprecated; use repro.estimator.ConcordEstimator "
        "(backend='reference') or repro.core.prox.solve_reference",
        DeprecationWarning, stacklevel=2)
    return solve_reference(
        s_or_x, lam1, lam2, variant=variant, tol=tol,
        max_iters=max_iters, max_ls=max_ls, warm_start_tau=warm_start_tau,
    )


# ---------------------------------------------------------------------------
# analysis manifest (repro.analysis.jaxprpass)
# ---------------------------------------------------------------------------

def _analysis_solve():
    p = 8
    s = jnp.eye(p, dtype=jnp.float64) + 0.05 * jnp.ones((p, p), jnp.float64)
    spec = PenaltySpec("l1", jnp.asarray(0.1, jnp.float64),
                       jnp.asarray(0.0, jnp.float64))
    fn = partial(_solve_reference, variant="cov", tol=1e-4, max_iters=8,
                 max_ls=8, warm_start_tau=False, sparse_matmul=None,
                 use_pallas=False)
    return {"fn": fn, "args": (s, spec, None)}


def _analysis_solve_reuse():
    p = 6
    s = jnp.eye(p, dtype=jnp.float64) + 0.04 * jnp.ones((p, p), jnp.float64)

    def run(lam1):
        res = solve_reference(s, lam1, tol=1e-3, max_iters=5, max_ls=5)
        return res.omega.block_until_ready()

    # three path points, one shape: the compiled cache must hold after
    # the warmup call (lam1 is a traced leaf of the penalty spec)
    return {"watched": {"core.prox._solve_reference": _solve_reference},
            "calls": [partial(run, 0.10), partial(run, 0.18),
                      partial(run, 0.26)]}


#: the sequential reference solve — the oracle every other layer matches
ANALYSIS_ENTRIES = [
    {"name": "core.prox.solve_reference", "path": "src/repro/core/prox.py",
     "axis_names": (), "build": _analysis_solve,
     "reuse": _analysis_solve_reuse},
]
