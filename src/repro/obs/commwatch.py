"""Static-vs-measured communication reconciliation (the CA303 closure).

The static analysis suite (``repro.analysis.commpass``) proves each ring
product's bytes-on-wire per *invocation*; what it cannot know statically
is how many times the solver's dynamic ``while_loop``s invoke each ring.
This module closes that loop:

**Measured side.**  A :class:`CommWatch` installed on
``core.distributed``'s dispatch hook sees every ``fit_cov``/``fit_obs``
jit dispatch.  It re-traces the exact shard_map closure being dispatched
with ``jax.make_jaxpr`` (tracing only — no compile, so zero extra
compiled programs) and walks the jaxpr into collective events carrying
their while-nesting depth and static scan multiplicity.  After the solve
returns, the solve's OWN observed trip counts (``iters``, ``ls_total``
— device-computed by the solver itself) expand each event into an exact
execution count:

    depth 0 (outside both loops)  x 1
    depth 1 (outer prox loop)     x iters
    depth 2 (line-search loop)    x (ls_total - iters)

(The first line-search trial of every outer iteration runs in the outer
body; the inner loop only runs the backtracking re-trials, hence the
``ls_total - iters`` residual.)  Bytes use the same
``core.costmodel.collective_wire_bytes`` conventions as CA303.

**Predicted side.**  An independent analytic table built from
``core.costmodel.comm_volume`` (paper Algorithm 4 ring/finish volumes)
plus the closed-form per-phase collective counts of the
``core.prox.prox_gradient`` control flow.

:func:`CommWatch.reconcile` demands EXACT equality (integer counts,
``Fraction`` bytes) per (primitive, axes) — a single extra collective or
one widened payload anywhere in the stack is a reportable finding.

Scope: the dense product path.  The block-sparse policy adds mask ring
traffic and density reductions whose analytic volume lives in
``comm.sparse1p5d``'s contracts; reconciling those is out of scope here
and :func:`predict_schedule` refuses rather than guessing.

The module also implements the ``comm/compat.py`` wrapper watcher: every
collective *posted through the compat layer* (trace-time) is counted
with its per-call payload bytes per (prim, axis) — see
:class:`CommWatch.posted`.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from fractions import Fraction

from ..analysis.commpass import EVENT_PRIMS, _payload
from ..analysis.jaxprpass import _axis_names_of, _sub_jaxprs
from ..core.costmodel import DTYPE_BYTES, collective_wire_bytes, comm_volume

#: vma-variant primitive names fold onto their canonical collective so
#: the measured and predicted tables key identically on every jax version
NORMALIZE_PRIM = {"psum_invariant": "psum", "all_gather_invariant": "all_gather"}


class ReconcileError(RuntimeError):
    """A schedule this reconciler cannot expand or predict exactly."""


@dataclass(frozen=True)
class WalkedEvent:
    """One collective eqn of a dispatched program, pre-expansion."""
    prim: str              # normalized primitive name
    axes: tuple            # mesh axes bound, in eqn order
    extent: int            # product of bound axis sizes
    payload_bytes: int
    moves: bool            # ppermute tables that are the identity ship 0
    depth: int             # while-loop nesting depth at the eqn
    static_times: int      # product of enclosing scan lengths
    in_cond: bool          # inside a while cond_jaxpr (not expandable)


def walk_collectives(jaxpr, axis_sizes: dict, *, _depth: int = 0,
                     _times: int = 1, _in_cond: bool = False,
                     _out: list | None = None) -> list:
    """Walk a (Closed)Jaxpr into :class:`WalkedEvent` records.

    Unlike ``analysis.commpass.extract_schedule`` (which poisons repeat
    counts at the first ``while``), this walker keeps the *static*
    multiplicity per while-depth so the runtime trip counts can expand it
    exactly."""
    out = _out if _out is not None else []
    inner = getattr(jaxpr, "jaxpr", jaxpr)
    for eqn in inner.eqns:
        name = eqn.primitive.name
        if name == "scan":
            length = eqn.params.get("length") or 0
            walk_collectives(eqn.params["jaxpr"], axis_sizes, _depth=_depth,
                             _times=_times * length, _in_cond=_in_cond,
                             _out=out)
        elif name == "while":
            if _times != 1:
                raise ReconcileError(
                    "while_loop nested inside a scan: the depth-based "
                    "expansion cannot attribute trip counts here")
            walk_collectives(eqn.params["cond_jaxpr"], axis_sizes,
                             _depth=_depth + 1, _times=_times,
                             _in_cond=True, _out=out)
            walk_collectives(eqn.params["body_jaxpr"], axis_sizes,
                             _depth=_depth + 1, _times=_times,
                             _in_cond=_in_cond, _out=out)
        elif name == "cond":
            # CA301 guarantees every branch posts the identical collective
            # sequence, so one representative branch is the schedule
            walk_collectives(eqn.params["branches"][0], axis_sizes,
                             _depth=_depth, _times=_times,
                             _in_cond=_in_cond, _out=out)
        elif name in EVENT_PRIMS:
            axes = tuple(_axis_names_of(eqn))
            extent = 1
            for a in axes:
                size = axis_sizes.get(a)
                if size is None:
                    raise ReconcileError(f"collective binds axis {a!r} with "
                                         f"unknown extent")
                extent *= size
            _, _, nbytes = _payload(eqn)
            perm = eqn.params.get("perm")
            out.append(WalkedEvent(
                prim=NORMALIZE_PRIM.get(name, name), axes=axes,
                extent=extent, payload_bytes=nbytes,
                moves=(perm is None or any(s != d for s, d in perm)),
                depth=_depth, static_times=_times, in_cond=_in_cond))
        else:
            for sub in _sub_jaxprs(eqn.params):
                walk_collectives(sub, axis_sizes, _depth=_depth,
                                 _times=_times, _in_cond=_in_cond, _out=out)
    return out


def expand_counts(events: list, iters: int, ls_total: int) -> dict:
    """Expand walked events with observed trip counts into the measured
    table ``{(prim, axes): {"count": int, "bytes": Fraction}}``."""
    mult = {0: 1, 1: iters, 2: ls_total - iters}
    table: dict = {}
    for e in events:
        if e.in_cond:
            raise ReconcileError(
                f"collective {e.prim} inside a while cond_jaxpr: cond "
                f"fires trips+1 times, which the result scalars do not "
                f"record")
        if e.depth not in mult:
            raise ReconcileError(
                f"collective {e.prim} at while depth {e.depth}: only the "
                f"prox outer/line-search nesting (depth <= 2) is "
                f"expandable")
        count = e.static_times * mult[e.depth]
        one = collective_wire_bytes(e.prim, e.payload_bytes, e.extent,
                                    moves=e.moves)
        row = table.setdefault((e.prim, e.axes),
                               {"count": 0, "bytes": Fraction(0)})
        row["count"] += count
        row["bytes"] += count * one
    return table


# ---------------------------------------------------------------------------
# analytic prediction (costmodel volumes x prox_gradient phase counts)
# ---------------------------------------------------------------------------

def predict_schedule(variant: str, *, p_pad: int, n: int | None, grid,
                     iters: int, ls_total: int,
                     dtype: str = "float64") -> dict:
    """The analytic twin of :func:`expand_counts` for one dense
    ``fit_cov``/``fit_obs`` solve: per-(prim, axes) execution counts and
    exact ``Fraction`` bytes-on-wire built from ``comm_volume`` (ring
    products) and the closed-form collective census of the
    ``prox_gradient`` phases:

      aux+objective runs ``1 + ls_total`` times (cold start + every
      line-search trial), the gradient runs ``iters`` times, each trial
      posts two global dots, and each outer iteration posts the two
      relative-change dots.
    """
    w = DTYPE_BYTES[dtype]
    P, cx, co = grid.n_devices, grid.c_x, grid.c_omega
    n_x, n_om, n_i = grid.n_x, grid.n_om, grid.n_i
    blk_x, blk_om = p_pad // n_x, p_pad // n_om
    aux_calls = 1 + ls_total
    table: dict = {}

    def add(prim, axes, count, nbytes):
        row = table.setdefault((prim, tuple(axes)),
                               {"count": 0, "bytes": Fraction(0)})
        row["count"] += count
        row["bytes"] += Fraction(nbytes)

    def wire(prim, payload_elems, extent):
        return collective_wire_bytes(prim, payload_elems * w, extent)

    ring_axes = ("i", "j", "k")
    if variant == "cov":
        # aux_of: W = Omega S, gather ring (Omega stored X-like)
        vol = comm_volume(p_pad, p_pad, P, cx, co, flavor="omega_s",
                          dtype=dtype, canonical="xlike")
        add("ppermute", ring_axes, aux_calls * (1 + vol.rounds),
            aux_calls * vol.ring_bytes)
        add("all_gather", ("k",), aux_calls, aux_calls * vol.finish_bytes)
        # grad_of: replication-aware transpose of W (Lemma 3.2)
        sub = blk_x // cx
        add("all_to_all", ("i", "j"), iters,
            iters * wire("all_to_all", n_x * sub * blk_x, n_x))
        add("all_gather", ("k",), iters,
            iters * wire("all_gather", p_pad * sub, cx))
        scalar_axes, scalar_extent = ("i", "j"), n_i * co
    elif variant == "obs":
        if n is None:
            raise ReconcileError("obs prediction needs the sample count n")
        # aux_of: Y = Omega X^T, reduce ring
        vol = comm_volume(p_pad, n, P, cx, co, flavor="omega_xt",
                          dtype=dtype)
        add("ppermute", ring_axes, aux_calls * (1 + vol.rounds),
            aux_calls * vol.ring_bytes)
        add("psum", ("j",), aux_calls, aux_calls * vol.finish_bytes)
        # grad_of: Z = Y X gather ring + transpose of Z
        voly = comm_volume(p_pad, n, P, cx, co, flavor="y_x", dtype=dtype)
        add("ppermute", ring_axes, iters * (1 + voly.rounds),
            iters * voly.ring_bytes)
        add("all_gather", ("j",), iters, iters * voly.finish_bytes)
        sub = blk_om // co
        add("all_to_all", ("i", "k"), iters,
            iters * wire("all_to_all", sub * n_om * blk_om, n_om))
        add("all_gather", ("j",), iters,
            iters * wire("all_gather", blk_om * n_om * sub, co))
        scalar_axes, scalar_extent = ("i", "k"), n_i * cx
    else:
        raise ReconcileError(f"unknown variant {variant!r}")

    # scalar collectives of the objective/line-search phases: 3 psums +
    # 1 pmin guard per objective, 2 dot-psums per trial, 2 per iteration
    n_psum = 3 * aux_calls + 2 * ls_total + 2 * iters
    add("psum", scalar_axes, n_psum, n_psum * wire("psum", 1, scalar_extent))
    add("pmin", scalar_axes, aux_calls,
        aux_calls * wire("pmin", 1, scalar_extent))
    return table


# ---------------------------------------------------------------------------
# reconciliation report
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ReconcileRow:
    prim: str
    axes: tuple
    measured_count: int
    predicted_count: int
    measured_bytes: Fraction
    predicted_bytes: Fraction

    @property
    def match(self) -> bool:
        return (self.measured_count == self.predicted_count
                and self.measured_bytes == self.predicted_bytes)

    def to_json(self) -> dict:
        return {"prim": self.prim, "axes": list(self.axes),
                "measured_count": self.measured_count,
                "predicted_count": self.predicted_count,
                "measured_bytes": str(self.measured_bytes),
                "predicted_bytes": str(self.predicted_bytes),
                "match": self.match}


@dataclass(frozen=True)
class ReconcileReport:
    variant: str
    p: int
    p_pad: int
    n: int | None
    n_devices: int
    c_x: int
    c_omega: int
    iters: int
    ls_total: int
    rows: tuple

    @property
    def ok(self) -> bool:
        return all(r.match for r in self.rows)

    @property
    def measured_total(self) -> Fraction:
        return sum((r.measured_bytes for r in self.rows), Fraction(0))

    @property
    def predicted_total(self) -> Fraction:
        return sum((r.predicted_bytes for r in self.rows), Fraction(0))

    def to_json(self) -> dict:
        return {"variant": self.variant, "p": self.p, "p_pad": self.p_pad,
                "n": self.n, "n_devices": self.n_devices, "c_x": self.c_x,
                "c_omega": self.c_omega, "iters": self.iters,
                "ls_total": self.ls_total, "ok": self.ok,
                "measured_bytes_total": str(self.measured_total),
                "predicted_bytes_total": str(self.predicted_total),
                "rows": [r.to_json() for r in self.rows]}

    def render(self) -> str:
        hdr = (f"{self.variant}: p={self.p} (pad {self.p_pad}) "
               f"P={self.n_devices} c_x={self.c_x} c_omega={self.c_omega} "
               f"iters={self.iters} ls_total={self.ls_total}")
        lines = [hdr, f"{'prim':<12} {'axes':<12} {'measured':>22} "
                      f"{'predicted':>22}  match"]
        for r in self.rows:
            m = f"{r.measured_count}x / {_fmt_bytes(r.measured_bytes)}"
            p_ = f"{r.predicted_count}x / {_fmt_bytes(r.predicted_bytes)}"
            lines.append(f"{r.prim:<12} {','.join(r.axes):<12} {m:>22} "
                         f"{p_:>22}  {'OK' if r.match else 'MISMATCH'}")
        lines.append(f"total measured {_fmt_bytes(self.measured_total)} vs "
                     f"predicted {_fmt_bytes(self.predicted_total)} -> "
                     f"{'EXACT MATCH' if self.ok else 'DIVERGENCE'}")
        return "\n".join(lines)


def _fmt_bytes(b: Fraction) -> str:
    f = float(b)
    return f"{f:.0f}B" if f == int(f) else f"{f:.1f}B"


def _table_to_rows(measured: dict, predicted: dict) -> tuple:
    rows = []
    for key in sorted(set(measured) | set(predicted)):
        m = measured.get(key, {"count": 0, "bytes": Fraction(0)})
        p = predicted.get(key, {"count": 0, "bytes": Fraction(0)})
        rows.append(ReconcileRow(
            prim=key[0], axes=key[1],
            measured_count=m["count"], predicted_count=p["count"],
            measured_bytes=m["bytes"], predicted_bytes=p["bytes"]))
    return tuple(rows)


# ---------------------------------------------------------------------------
# the dispatch observer
# ---------------------------------------------------------------------------

@dataclass
class DispatchRecord:
    """One observed driver dispatch, filled in across the hook protocol."""
    variant: str
    grid: object
    meta: dict
    events: list
    result: object = None


class CommWatch:
    """Observer over the distributed drivers and the compat wrappers.

    Usage::

        with CommWatch() as watch:
            res = dist.fit_cov(s, lam1, grid=grid)
        report = watch.reconcile()[0]
        assert report.ok

    ``install``/``uninstall`` (or the context manager) register this
    object on ``core.distributed.set_dispatch_observer`` and
    ``comm.compat.set_collective_watcher``.
    """

    def __init__(self):
        self.records: list = []
        #: collectives posted through comm/compat.py wrappers:
        #: {(prim, axis): {"calls": int, "bytes": int}}
        self.posted: dict = {}
        self._prev_dispatch = None
        self._prev_wrapper = None
        self._installed = False

    # -- lifecycle -------------------------------------------------------
    def install(self) -> "CommWatch":
        from ..comm import compat
        from ..core import distributed
        if self._installed:
            return self
        self._prev_dispatch = distributed.set_dispatch_observer(self)
        self._prev_wrapper = compat.set_collective_watcher(self)
        self._installed = True
        return self

    def uninstall(self) -> None:
        from ..comm import compat
        from ..core import distributed
        if not self._installed:
            return
        distributed.set_dispatch_observer(self._prev_dispatch)
        compat.set_collective_watcher(self._prev_wrapper)
        self._installed = False

    def __enter__(self) -> "CommWatch":
        return self.install()

    def __exit__(self, *exc):
        self.uninstall()
        return False

    # -- core.distributed dispatch-observer protocol ---------------------
    def on_dispatch(self, variant: str, fn, args, grid, meta: dict):
        """Called right before the driver's jit call.
        ``make_jaxpr`` only traces — no compile, no numeric effect."""
        import jax

        axis_sizes = {"i": grid.n_i, "j": grid.c_omega, "k": grid.c_x}
        jaxpr = jax.make_jaxpr(fn)(*args)
        rec = DispatchRecord(variant=variant, grid=grid, meta=dict(meta),
                             events=walk_collectives(jaxpr, axis_sizes))
        self.records.append(rec)
        return rec

    def on_result(self, token: DispatchRecord, result) -> None:
        token.result = result

    # -- comm.compat wrapper-watcher protocol ----------------------------
    def on_collective(self, prim: str, axis_name, operand) -> None:
        """Count a collective posted through a compat wrapper (trace-time
        semantics: a cached program re-executes without re-posting)."""
        axes = tuple(axis_name) if isinstance(axis_name, (tuple, list)) \
            else (axis_name,)
        shape = getattr(operand, "shape", ())
        dtype = getattr(operand, "dtype", None)
        nbytes = math.prod(shape) * getattr(dtype, "itemsize", 8)
        row = self.posted.setdefault((prim, axes), {"calls": 0, "bytes": 0})
        row["calls"] += 1
        row["bytes"] += nbytes

    # -- reconciliation --------------------------------------------------
    def reconcile(self) -> list:
        """One :class:`ReconcileReport` per observed dispatch.  Pulls the
        solve's observed ``iters``/``ls_total`` (the only device sync this
        subsystem ever does, after the solve is already finished)."""
        reports = []
        for rec in self.records:
            if rec.result is None:
                raise ReconcileError(
                    f"{rec.variant} dispatch was observed but its result "
                    f"never arrived (solve still running or crashed)")
            if rec.meta.get("sparse"):
                raise ReconcileError(
                    "block-sparse solves add mask ring traffic the dense "
                    "predictor does not model; reconcile dense solves")
            iters = int(rec.result.iters)
            ls_total = int(rec.result.ls_total)
            measured = expand_counts(rec.events, iters, ls_total)
            predicted = predict_schedule(
                rec.variant, p_pad=rec.meta["p_pad"], n=rec.meta.get("n"),
                grid=rec.grid, iters=iters, ls_total=ls_total,
                dtype=rec.meta.get("dtype", "float64"))
            reports.append(ReconcileReport(
                variant=rec.variant, p=rec.meta.get("p", rec.meta["p_pad"]),
                p_pad=rec.meta["p_pad"], n=rec.meta.get("n"),
                n_devices=rec.grid.n_devices, c_x=rec.grid.c_x,
                c_omega=rec.grid.c_omega, iters=iters, ls_total=ls_total,
                rows=_table_to_rows(measured, predicted)))
        return reports

    def clear(self) -> None:
        self.records.clear()
        self.posted.clear()


# ---------------------------------------------------------------------------
# analysis manifest (repro.analysis.jaxprpass — CA202 reuse recipe)
# ---------------------------------------------------------------------------

def _analysis_obs_build():
    """Trace the reference solve step with the span tracer armed at
    ``trace`` (via ctx): instrumentation is host-side only, so the traced
    program — and with it the CA201/CA203 contracts — must be exactly the
    one core.prox exports untraced."""
    from functools import partial

    import jax.numpy as jnp

    from ..core.prox import PenaltySpec, _solve_reference
    from .trace import get_tracer

    p = 8
    s = jnp.eye(p, dtype=jnp.float64) + 0.05 * jnp.ones((p, p), jnp.float64)
    spec = PenaltySpec("l1", jnp.asarray(0.1, jnp.float64),
                       jnp.asarray(0.0, jnp.float64))
    fn = partial(_solve_reference, variant="cov", tol=1e-4, max_iters=8,
                 max_ls=8, warm_start_tau=False, sparse_matmul=None,
                 use_pallas=False)
    return {"fn": fn, "args": (s, spec, None),
            "ctx": lambda: get_tracer().scoped("trace")}


def _analysis_obs_reuse():
    """CA202: solving at ``obs="trace"`` must add ZERO compiled programs —
    the tracer wraps dispatch at host boundaries and the comm watcher only
    re-traces (``make_jaxpr``), so the reference engine's compiled cache
    must hold across traced path points exactly as it does untraced."""
    import numpy as np

    from ..core.prox import _solve_reference
    from ..estimator import ConcordEstimator, SolverConfig

    rng = np.random.default_rng(0)
    x = rng.standard_normal((20, 8))
    config = SolverConfig(backend="reference", variant="cov", tol=1e-3,
                          max_iters=5, max_ls=5, obs="trace")

    def run(lam1):
        ConcordEstimator(lam1=lam1, config=config).fit(x)

    from functools import partial
    return {"watched": {"core.prox._solve_reference": _solve_reference},
            "calls": [partial(run, 0.20), partial(run, 0.26),
                      partial(run, 0.32)]}


#: the comm engine (CA3xx) skips — this host-side module declares no
#: COMM_CONTRACT of its own; the CA202 recipe and the armed-tracer trace
#: (identical program to core.prox's) are the contracts here
ANALYSIS_ENTRIES = [
    {"name": "obs.commwatch.traced_solve_reuse",
     "path": "src/repro/obs/commwatch.py",
     "axis_names": (),
     "build": _analysis_obs_build,
     "reuse": _analysis_obs_reuse,
     "skip": ("CA300", "CA301", "CA302", "CA303",
              "CA304", "CA305", "CA306")},
]
