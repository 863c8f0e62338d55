"""comm engine: SPMD collective-schedule rules (CA301-CA306).

The jaxpr engine checks what a traced program COMPUTES; this engine
checks what it COMMUNICATES.  Every manifest entry is traced (ring
entries under ``axis_env``, so multi-device schedules trace on a
1-device container) and its jaxpr is walked into a **collective
schedule**: the ordered ppermute/psum/all_gather/... events with their
axis names, permutation tables, payload shapes/dtypes and control-flow
context — a ppermute inside a ``lax.scan`` of length R is one event
fired R times, a ``lax.cond`` records per-branch sub-schedules, a
``lax.while_loop`` poisons byte accounting (trip count is dynamic) but
still surfaces its events for the structural rules.

On that schedule:

  * CA301 — branches of one cond/switch post different collective
    sequences (the static signature of an SPMD deadlock);
  * CA302 — a ppermute table is not a bijection in range of the bound
    axis extent (and, under a contract, must cover the full ring);
  * CA303 — total bytes-on-wire derived from the schedule must EQUAL
    (as exact Fractions) the analytic ``core.costmodel`` volume the
    module's ``COMM_CONTRACT`` declares;
  * CA304 — collectives that move bytes for nothing (psum of an
    already-psummed value, composable back-to-back ppermutes);
  * CA305 — schedule disagrees with the declared contract (undeclared
    axis, undeclared collective kind, ring scan length != declared
    rounds);
  * CA306 — a payload dtype the contract does not allow on the wire.

Entry schema extensions over :mod:`repro.analysis.jaxprpass` (all
optional, so existing entries are valid comm entries with structural
checks only)::

    {
      ...,                           # name/path/axis_names/build as before
      "build": lambda: {
          ...,                       # fn/args/kwargs/ctx as before
          "axis_env": (("i", 2), ("j", 2), ("k", 2)),  # trace SPMD axes
          "axis_sizes": {"i": 1},    # extents when tracing through a mesh
      },
      "comm": lambda: {              # bind a declared COMM_CONTRACT
          "contract": CommContract(...),
          "params": {...},           # kwargs for the contract's callables
      },
      "skip": ("CA201",),            # per-entry rule opt-outs (a declared
    }                                # narrowing lives NEXT to its contract)

Byte conventions are ``core.costmodel.collective_wire_bytes``'s — the
single shared definition both sides of the CA303 equality use.
"""
from __future__ import annotations

import math
import traceback
from contextlib import nullcontext
from dataclasses import dataclass, field
from fractions import Fraction

from ..core.costmodel import collective_wire_bytes
from .findings import Finding
from .jaxprpass import _axis_names_of, _eqn_snippet, _sub_jaxprs
from .rules import Profile

#: payload-bearing collectives (axis_index & friends carry no wire bytes)
EVENT_PRIMS = frozenset({
    "psum", "pmin", "pmax", "psum_invariant", "ppermute", "pbroadcast",
    "all_gather", "all_gather_invariant", "all_to_all", "reduce_scatter",
    "psum_scatter",
})

_REDUCE_PRIMS = frozenset({"psum", "pmin", "pmax", "psum_invariant"})


@dataclass(frozen=True)
class CollectiveEvent:
    """One collective eqn in program order, with its repeat count."""
    prim: str
    axes: tuple            # mesh axis names the eqn binds
    extent: int | None     # product of bound axis sizes (None = unknown)
    shape: tuple           # invars[0] payload shape
    dtypes: tuple          # payload dtype of every array operand
    payload_bytes: int
    perm: tuple | None     # ppermute table
    times: int | None      # product of enclosing scan lengths (None: while)
    context: str           # control-flow path, e.g. "scan[2]"
    snippet: str

    @property
    def moves(self) -> bool:
        if self.perm is None:
            return True
        return any(s != d for s, d in self.perm)

    def wire_bytes(self) -> Fraction | None:
        """Critical-path bytes over all firings (None = indeterminate)."""
        if self.times is None or self.extent is None:
            return None
        one = collective_wire_bytes(
            self.prim, self.payload_bytes, self.extent, moves=self.moves)
        return self.times * one

    def signature(self) -> tuple:
        """What must agree across SPMD branches (CA301): everything a
        peer device matches on, which is NOT the permutation values."""
        return (self.prim, self.axes, self.shape, self.dtypes, self.times)

    def to_json(self) -> dict:
        wb = self.wire_bytes()
        return {
            "prim": self.prim, "axes": list(self.axes),
            "extent": self.extent, "shape": list(self.shape),
            "dtypes": list(self.dtypes), "times": self.times,
            "context": self.context, "perm": (
                None if self.perm is None else [list(p) for p in self.perm]),
            "bytes_on_wire": None if wb is None else str(wb),
        }


@dataclass
class Schedule:
    """The extracted collective schedule of one traced entry."""
    events: list = field(default_factory=list)
    #: (length, ppermute_inside, context, snippet) per lax.scan
    scans: list = field(default_factory=list)
    #: (branch_jaxprs, context, snippet) per lax.cond/switch
    conds: list = field(default_factory=list)
    #: True if a while_loop made repeat counts dynamic
    indeterminate: bool = False

    def total_bytes(self) -> Fraction | None:
        total = Fraction(0)
        for e in self.events:
            wb = e.wire_bytes()
            if wb is None:
                return None
            total += wb
        return total

    def to_json(self) -> dict:
        tb = self.total_bytes()
        return {"events": [e.to_json() for e in self.events],
                "static_bytes": None if tb is None else str(tb),
                "indeterminate": self.indeterminate}


def _payload(eqn):
    """(shape, dtypes, bytes) over the eqn's array operands."""
    shapes, dtypes, nbytes = [], [], 0
    for v in eqn.invars:
        aval = getattr(v, "aval", None)
        shape = getattr(aval, "shape", None)
        dtype = getattr(aval, "dtype", None)
        if shape is None or dtype is None:
            continue
        shapes.append(tuple(shape))
        dtypes.append(str(dtype))
        nbytes += math.prod(shape) * dtype.itemsize
    return (shapes[0] if shapes else ()), tuple(dtypes), nbytes


def _mul(a, b):
    return None if (a is None or b is None) else a * b


def _ctx(context: str, frame: str) -> str:
    return f"{context}/{frame}" if context else frame


def extract_schedule(jaxpr, axis_sizes: dict, *, _times: int | None = 1,
                     _context: str = "", _out: Schedule | None = None
                     ) -> Schedule:
    """Walk a (Closed)Jaxpr into program-order collective events.

    ``axis_sizes`` maps mesh axis name -> extent (from the entry's
    ``axis_env``/``axis_sizes``); an event binding an unlisted axis gets
    ``extent=None`` and poisons byte accounting but not the structural
    rules.
    """
    out = _out if _out is not None else Schedule()
    inner = getattr(jaxpr, "jaxpr", jaxpr)
    for eqn in inner.eqns:
        name = eqn.primitive.name
        if name == "scan":
            length = eqn.params.get("length")
            before = len(out.events)
            extract_schedule(eqn.params["jaxpr"], axis_sizes,
                             _times=_mul(_times, length),
                             _context=_ctx(_context, f"scan[{length}]"),
                             _out=out)
            has_pp = any(e.prim == "ppermute"
                         for e in out.events[before:])
            out.scans.append((length, has_pp, _context, _eqn_snippet(eqn)))
        elif name == "while":
            out.indeterminate = True
            for key in ("cond_jaxpr", "body_jaxpr"):
                extract_schedule(eqn.params[key], axis_sizes, _times=None,
                                 _context=_ctx(_context, "while[?]"),
                                 _out=out)
        elif name == "cond":
            branches = tuple(eqn.params["branches"])
            out.conds.append((branches, _context, _eqn_snippet(eqn)))
            # devices agreeing on the predicate run the SAME branch, so
            # the schedule follows one representative; CA301 fires if
            # the branches could disagree about what that schedule is
            extract_schedule(branches[0], axis_sizes, _times=_times,
                             _context=_ctx(_context, "cond"), _out=out)
        elif name in EVENT_PRIMS:
            axes = tuple(_axis_names_of(eqn))
            extent = 1
            for a in axes:
                size = axis_sizes.get(a)
                extent = _mul(extent, size)
            shape, dtypes, nbytes = _payload(eqn)
            perm = eqn.params.get("perm")
            out.events.append(CollectiveEvent(
                prim=name, axes=axes, extent=extent, shape=shape,
                dtypes=dtypes, payload_bytes=nbytes,
                perm=None if perm is None else tuple(map(tuple, perm)),
                times=_times, context=_context, snippet=_eqn_snippet(eqn)))
        else:
            for sub in _sub_jaxprs(eqn.params):
                extract_schedule(sub, axis_sizes, _times=_times,
                                 _context=_context, _out=out)
    return out


# -- per-entry checks -------------------------------------------------------

def _finding(rule, entry, message, snippet) -> Finding:
    return Finding(rule=rule, path=entry["path"], line=0,
                   context=entry["name"], message=message, snippet=snippet)


def check_branch_schedules(entry, schedule, axis_sizes) -> list:
    """CA301: every branch of a cond/switch must post the same ordered
    collective signature — devices disagreeing on the predicate would
    otherwise wait on collectives their peers never post."""
    out = []
    for branches, context, snippet in schedule.conds:
        sigs = []
        for br in branches:
            sub = extract_schedule(br, axis_sizes)
            sigs.append(tuple(e.signature() for e in sub.events))
        if not any(sigs):
            continue                    # no collectives anywhere: safe
        if len(set(sigs)) > 1:
            desc = " vs ".join(
                "[" + ", ".join(f"{s[0]}{list(s[1])}" for s in sig) + "]"
                for sig in sigs)
            out.append(_finding(
                "CA301", entry,
                f"cond/switch branches post divergent collective "
                f"schedules ({desc}){' at ' + context if context else ''}: "
                f"devices taking different branches deadlock — hoist the "
                f"collectives out of the branch or make every branch post "
                f"the identical sequence", snippet))
    return out


def check_ppermute_tables(entry, schedule, contract) -> list:
    """CA302: permutation tables must be in-range bijections (and cover
    the full ring when a COMM_CONTRACT declares the schedule)."""
    out = []
    for e in schedule.events:
        if e.prim != "ppermute" or e.perm is None:
            continue
        srcs = [s for s, _ in e.perm]
        dsts = [d for _, d in e.perm]
        problems = []
        if len(set(srcs)) != len(srcs):
            problems.append("duplicate source ranks")
        if len(set(dsts)) != len(dsts):
            problems.append("duplicate destination ranks")
        if e.extent is not None:
            bad = [r for r in srcs + dsts if not 0 <= r < e.extent]
            if bad:
                problems.append(
                    f"ranks {sorted(set(bad))} out of range for axis "
                    f"extent {e.extent}")
            if (not problems and contract is not None
                    and len(e.perm) != e.extent):
                problems.append(
                    f"covers {len(e.perm)}/{e.extent} ranks (a declared "
                    f"ring schedule must keep every device in the "
                    f"rotation)")
        if problems:
            out.append(_finding(
                "CA302", entry,
                f"ppermute over {list(e.axes)}"
                f"{' at ' + e.context if e.context else ''} is not a "
                f"valid ring permutation: {'; '.join(problems)} — data "
                f"on the missing lanes is silently dropped/zeroed",
                e.snippet))
    return out


def check_volume(entry, schedule, contract, params) -> list:
    """CA303: schedule bytes must EQUAL the contract's analytic bytes."""
    expected = contract.expected_volume(params)
    if expected is None:
        return []
    expected = Fraction(expected)
    static = schedule.total_bytes()
    if static is None:
        return [_finding(
            "CA303", entry,
            f"COMM_CONTRACT declares an exact volume "
            f"({expected} bytes/invocation"
            f"{', ' + contract.volume_class if contract.volume_class else ''}"
            f") but the traced schedule's byte count is indeterminate "
            f"(dynamic trip count or unbound axis extent) — a volume "
            f"contract requires a statically accountable schedule",
            "indeterminate schedule")]
    if static != expected:
        return [_finding(
            "CA303", entry,
            f"traced schedule moves {static} bytes/invocation but the "
            f"COMM_CONTRACT"
            f"{' (' + contract.volume_class + ')' if contract.volume_class else ''}"
            f" declares {expected} (analytic core.costmodel volume at "
            f"{params}) — an extra collective, a missing round, or a "
            f"widened wire dtype crept into the schedule",
            f"static={static} expected={expected}")]
    return []


def check_redundant(entry, jaxpr) -> list:
    """CA304: per-body dataflow — psum of an already-psummed value over a
    subset of the same axes, or ppermute-of-ppermute whose intermediate
    has no other consumer (one composed table does the same work in one
    hop)."""
    out = []
    for body in _all_bodies(jaxpr):
        produced = {}                   # var id -> (prim, axes, eqn)
        uses: dict[int, int] = {}
        for eqn in body.eqns:
            for v in eqn.invars:
                if hasattr(v, "aval") and not hasattr(v, "val"):
                    uses[id(v)] = uses.get(id(v), 0) + 1
        outvars = {id(v) for v in body.outvars if hasattr(v, "aval")}
        for eqn in body.eqns:
            name = eqn.primitive.name
            if name not in EVENT_PRIMS:
                continue
            axes = frozenset(_axis_names_of(eqn))
            for v in eqn.invars:
                src = produced.get(id(v))
                if src is None:
                    continue
                src_prim, src_axes, src_eqn = src
                if (name in _REDUCE_PRIMS and src_prim in _REDUCE_PRIMS
                        and axes <= src_axes):
                    out.append(_finding(
                        "CA304", entry,
                        f"{name} over {sorted(axes)} of a value already "
                        f"reduced by {src_prim} over {sorted(src_axes)}: "
                        f"the operand is replicated on those axes, so "
                        f"this collective moves bytes to multiply by the "
                        f"axis size (almost certainly a double-reduce "
                        f"bug)", _eqn_snippet(eqn)))
                elif (name == "ppermute" and src_prim == "ppermute"
                        and axes == src_axes and uses.get(id(v), 0) == 1
                        and id(v) not in outvars):
                    out.append(_finding(
                        "CA304", entry,
                        f"back-to-back ppermutes over {sorted(axes)} "
                        f"whose intermediate has no other consumer: "
                        f"compose the permutation tables into one hop "
                        f"(half the wire bytes, half the launches)",
                        _eqn_snippet(eqn)))
            for v in eqn.outvars:
                produced[id(v)] = (name, axes, eqn)
    return out


def _all_bodies(jaxpr):
    """Yield every Jaxpr body (top level + nested) exactly once."""
    inner = getattr(jaxpr, "jaxpr", jaxpr)
    yield inner
    for eqn in inner.eqns:
        for sub in _sub_jaxprs(eqn.params):
            yield from _all_bodies(sub)


def check_contract_schedule(entry, schedule, contract, params) -> list:
    """CA305: axes, kinds, and ring-scan rounds vs the declaration."""
    out = []
    allowed_axes = set(contract.axes if contract.axes is not None
                       else entry.get("axis_names") or ())
    kinds = None if contract.kinds is None else set(contract.kinds)
    seen = set()
    for e in schedule.events:
        undeclared = [a for a in e.axes if a not in allowed_axes]
        if undeclared and ("axes", e.prim, tuple(undeclared)) not in seen:
            seen.add(("axes", e.prim, tuple(undeclared)))
            out.append(_finding(
                "CA305", entry,
                f"{e.prim} binds axis(es) {undeclared} but the "
                f"COMM_CONTRACT declares {sorted(allowed_axes)} — the "
                f"schedule touches a ring the contract does not cover",
                e.snippet))
        if kinds is not None and e.prim not in kinds and \
                ("kind", e.prim) not in seen:
            seen.add(("kind", e.prim))
            out.append(_finding(
                "CA305", entry,
                f"schedule posts `{e.prim}` but the COMM_CONTRACT only "
                f"declares {sorted(kinds)} — an undeclared collective "
                f"kind changes the communication pattern", e.snippet))
    rounds = contract.expected_rounds(params)
    if rounds is not None:
        for length, has_pp, context, snippet in schedule.scans:
            if has_pp and length != rounds:
                out.append(_finding(
                    "CA305", entry,
                    f"ring scan runs {length} round(s)"
                    f"{' at ' + context if context else ''} but the "
                    f"COMM_CONTRACT declares {rounds} — the rotation "
                    f"visits the wrong number of blocks", snippet))
    return out


def check_wire_dtypes(entry, schedule, contract, operand_dtypes) -> list:
    """CA306: every payload dtype must be on the contract's wire list
    ("operand" = the entry's own operand dtypes, "mask" = the int8
    occupancy-mask dtype)."""
    if contract.wire is None:
        return []
    allowed = set()
    for t in contract.wire:
        if t == "operand":
            allowed.update(operand_dtypes)
        elif t == "mask":
            from ..core.matops import MASK_DTYPE
            allowed.add(str(MASK_DTYPE.dtype) if hasattr(MASK_DTYPE, "dtype")
                        else str(MASK_DTYPE.__name__))
        else:
            allowed.add(t)
    out = []
    seen = set()
    for e in schedule.events:
        for dt in e.dtypes:
            if dt in allowed or (e.prim, dt) in seen:
                continue
            seen.add((e.prim, dt))
            out.append(_finding(
                "CA306", entry,
                f"{e.prim}"
                f"{' at ' + e.context if e.context else ''} ships "
                f"{dt} but the COMM_CONTRACT wire policy allows only "
                f"{sorted(allowed)} — the declared bytes-on-wire budget "
                f"silently multiplies", e.snippet))
    return out


# -- driver -----------------------------------------------------------------

def _error_finding(entry, stage, exc) -> Finding:
    tb = traceback.format_exception_only(type(exc), exc)[-1].strip()
    return Finding(
        rule="CA300", path=entry["path"], line=0, context=entry["name"],
        message=f"comm entry failed during {stage}: {tb} — a broken entry "
                f"means the collective-schedule checks did not run",
        snippet=stage)


def run_entry(entry: dict, profile: Profile):
    """Trace + check one manifest entry.  Returns (findings, record);
    record is the JSON-able schedule trace (None when tracing failed).
    Never raises: failures surface as CA300."""
    import jax

    skip = set(entry.get("skip") or ())
    active = {r for r in profile.rules if r.startswith("CA3")} - skip
    if not active:
        return [], None
    try:
        with jax.enable_x64(True):
            spec = entry["build"]()
            ctx = spec.get("ctx") or nullcontext
            fn, args = spec["fn"], tuple(spec.get("args", ()))
            kwargs = dict(spec.get("kwargs", {}))
            axis_env = spec.get("axis_env")
            mk = {} if axis_env is None else {"axis_env": list(axis_env)}
            with ctx():
                jaxpr = jax.make_jaxpr(
                    lambda *a: fn(*a, **kwargs), **mk)(*args)
    except Exception as e:              # noqa: BLE001 - report, don't die
        return [_error_finding(entry, "trace", e)], None

    axis_sizes = dict(axis_env or ())
    axis_sizes.update(spec.get("axis_sizes") or {})
    schedule = extract_schedule(jaxpr, axis_sizes)

    comm = entry.get("comm")
    comm = comm() if callable(comm) else comm
    contract = None if comm is None else comm["contract"]
    params = {} if comm is None else dict(comm.get("params") or {})
    operand_dtypes = {str(getattr(v.aval, "dtype", ""))
                      for v in getattr(jaxpr, "jaxpr", jaxpr).invars}

    findings = []
    try:
        if "CA301" in active:
            findings += check_branch_schedules(entry, schedule, axis_sizes)
        if "CA302" in active:
            findings += check_ppermute_tables(entry, schedule, contract)
        if "CA304" in active:
            findings += check_redundant(entry, jaxpr)
        if contract is not None:
            if "CA303" in active:
                findings += check_volume(entry, schedule, contract, params)
            if "CA305" in active:
                findings += check_contract_schedule(
                    entry, schedule, contract, params)
            if "CA306" in active:
                findings += check_wire_dtypes(
                    entry, schedule, contract, operand_dtypes)
    except Exception as e:              # noqa: BLE001
        return findings + [_error_finding(entry, "check", e)], None

    record = {"entry": entry["name"], "path": entry["path"],
              **schedule.to_json()}
    if contract is not None:
        expected = contract.expected_volume(params)
        record["contract"] = {
            "volume_class": contract.volume_class,
            "rounds": contract.expected_rounds(params),
            "expected_bytes": None if expected is None else
            str(Fraction(expected)),
            "params": {k: str(v) for k, v in params.items()},
        }
    return findings, record


def run_entries(entries, profile: Profile):
    """Returns (findings, schedule_records) over the whole manifest."""
    findings, records = [], []
    for entry in entries:
        f, rec = run_entry(entry, profile)
        findings.extend(f)
        if rec is not None:
            records.append(rec)
    return findings, records
