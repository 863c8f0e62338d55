"""jaxpr engine: semantic JAX-contract rules (CA2xx).

Where the AST engine reads source, this engine runs the tracer: every
solver layer exports an ``ANALYSIS_ENTRIES`` manifest (collected by
:mod:`repro.analysis.manifest`) describing its real entry points at
representative shapes.  Each entry is traced with ``jax.make_jaxpr``
under ``enable_x64`` and its (nested) jaxprs are walked for

  * CA201 — ``convert_element_type`` narrowing float64 to a smaller
    float: the f64 Gram/solve contract may never silently downcast;
  * CA203 — collective primitives (psum & friends) naming a mesh axis the
    entry did not declare;

and, for entries that ship a ``reuse`` recipe, the compiled-program
caches are watched across repeat invocations at unchanged shapes/statics
(CA202 — generalizing the penalty tests' ``_cache_size`` assertion).

Entry schema (each item of a module's ``ANALYSIS_ENTRIES`` list)::

    {
      "name": "core.prox.solve_reference",   # finding context
      "path": "src/repro/core/prox.py",      # finding location
      "axis_names": ("i", "j", "k"),          # mesh axes psum may bind
      "build": lambda: {                      # called under enable_x64
          "fn": callable,                     # what to make_jaxpr
          "args": tuple, "kwargs": dict,      # representative operands
          "ctx": optional () -> contextmanager,   # e.g. use_mesh(mesh)
      },
      "reuse": optional lambda: {             # CA202, executed (not traced)
          "watched": {"label": jitted_fn},    # caches to snapshot
          "calls": [thunk, ...],              # calls[0] warms, rest must
      },                                      # not grow any cache
      "skip": ("CA201", ...),                 # optional per-entry opt-outs
    }                                         # (a declared narrowing lives
                                              # next to its contract)

The build spec may also carry ``"axis_env"`` (a tuple of (axis, size)
pairs passed to ``make_jaxpr``) so SPMD ring functions trace their
multi-device schedules without devices, and ``"axis_sizes"`` /
``"comm"`` consumed by the comm engine (see
:mod:`repro.analysis.commpass`).

``build``/``reuse`` are zero-arg thunks so importing a layer module never
builds arrays or touches the backend.
"""
from __future__ import annotations

import traceback
from contextlib import nullcontext

from .findings import Finding
from .recompile import RecompileGuard
from .rules import Profile

NARROW_FLOATS = ("float32", "float16", "bfloat16")

#: primitives whose params can bind mesh axis names
COLLECTIVE_PRIM_NAMES = frozenset({
    "psum", "pmin", "pmax", "ppermute", "pbroadcast", "all_gather",
    "all_to_all", "reduce_scatter", "psum_scatter", "axis_index",
    "psum_invariant", "all_gather_invariant",
})

_AXIS_PARAM_KEYS = ("axes", "axis_name", "axis_index_groups_axis")


def iter_eqns(jaxpr):
    """Yield every eqn in a (Closed)Jaxpr, descending into sub-jaxprs held
    in eqn params (pjit/while/cond/scan/shard_map/custom_* bodies)."""
    inner = getattr(jaxpr, "jaxpr", jaxpr)     # ClosedJaxpr -> Jaxpr
    for eqn in inner.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn.params):
            yield from iter_eqns(sub)


def _sub_jaxprs(params: dict):
    for value in params.values():
        yield from _jaxprs_in(value)


def _jaxprs_in(value):
    if hasattr(value, "eqns") or hasattr(value, "jaxpr"):
        yield value
    elif isinstance(value, (tuple, list)):
        for v in value:
            yield from _jaxprs_in(v)


def _axis_names_of(eqn) -> list:
    names = []
    for key in _AXIS_PARAM_KEYS:
        v = eqn.params.get(key)
        if v is None:
            continue
        for name in v if isinstance(v, (tuple, list)) else (v,):
            if isinstance(name, str):
                names.append(name)
    return names


def _eqn_snippet(eqn) -> str:
    text = " ".join(str(eqn).split())
    return text if len(text) <= 160 else text[:157] + "..."


# -- per-entry checks -------------------------------------------------------

def check_downcasts(entry: dict, jaxpr) -> list:
    """CA201: f64 -> narrow-float convert_element_type anywhere in the
    traced program."""
    out = []
    for eqn in iter_eqns(jaxpr):
        if eqn.primitive.name != "convert_element_type":
            continue
        src = getattr(eqn.invars[0].aval, "dtype", None)
        dst = eqn.params.get("new_dtype")
        if src is None or dst is None:
            continue
        if str(src) == "float64" and str(dst) in NARROW_FLOATS:
            out.append(Finding(
                rule="CA201", path=entry["path"], line=0,
                context=entry["name"], snippet=_eqn_snippet(eqn),
                message=f"float64 value narrowed to {dst} inside traced "
                        f"entry '{entry['name']}': the f64 contract must "
                        f"not silently downcast (derive the dtype from "
                        f"the operand or name a *_DTYPE policy)"))
    return out


def check_collective_axes(entry: dict, jaxpr) -> list:
    """CA203: collective primitive binds an axis the entry didn't declare."""
    declared = set(entry.get("axis_names") or ())
    out = []
    seen = set()
    for eqn in iter_eqns(jaxpr):
        if eqn.primitive.name not in COLLECTIVE_PRIM_NAMES:
            continue
        for name in _axis_names_of(eqn):
            if name in declared or (eqn.primitive.name, name) in seen:
                continue
            seen.add((eqn.primitive.name, name))
            out.append(Finding(
                rule="CA203", path=entry["path"], line=0,
                context=entry["name"], snippet=_eqn_snippet(eqn),
                message=f"`{eqn.primitive.name}` binds mesh axis "
                        f"{name!r} but entry '{entry['name']}' declares "
                        f"axes {sorted(declared) or '()'} — the axis "
                        f"would be unbound (or bound to the wrong mesh) "
                        f"at run time"))
    return out


def check_reuse(entry: dict) -> list:
    """CA202: repeat invocations at unchanged shapes/statics must not grow
    any watched compiled-program cache after the warmup call."""
    recipe = entry["reuse"]()
    watched, calls = recipe["watched"], recipe["calls"]
    if not calls:
        return []
    calls[0]()                                  # warmup: may compile
    guard = RecompileGuard(watched)
    with guard:
        for call in calls[1:]:
            call()
    out = []
    for label, delta in guard.grew().items():
        out.append(Finding(
            rule="CA202", path=entry["path"], line=0,
            context=entry["name"], snippet=label,
            message=f"'{label}' compiled {delta} new program(s) when "
                    f"'{entry['name']}' was re-invoked with new parameter "
                    f"values at unchanged shapes/statics — a lambda path "
                    f"would recompile per point (keep penalty params and "
                    f"warm starts traced, not static)"))
    return out


# -- driver -----------------------------------------------------------------

def _error_finding(entry: dict, stage: str, exc: BaseException) -> Finding:
    tb = traceback.format_exception_only(type(exc), exc)[-1].strip()
    return Finding(
        rule="CA200", path=entry["path"], line=0, context=entry["name"],
        message=f"manifest entry failed during {stage}: {tb} — a broken "
                f"entry point means the contract checks did not run",
        snippet=stage)


def run_entry(entry: dict, profile: Profile) -> list:
    """Trace + check one manifest entry.  Never raises: failures surface
    as CA200 findings so one broken entry can't mask the rest."""
    import jax

    findings = []
    skip = set(entry.get("skip") or ())
    active = ({"CA201", "CA202", "CA203"} & profile.rules) - skip
    if {"CA201", "CA203"} & active:
        try:
            with jax.enable_x64(True):
                spec = entry["build"]()
                ctx = spec.get("ctx") or nullcontext
                fn, args = spec["fn"], tuple(spec.get("args", ()))
                kwargs = dict(spec.get("kwargs", {}))
                # ring entries trace their SPMD schedules without devices
                # by binding the mesh axes via make_jaxpr's axis_env
                axis_env = spec.get("axis_env")
                mk = {} if axis_env is None else {"axis_env": list(axis_env)}
                with ctx():
                    jaxpr = jax.make_jaxpr(
                        lambda *a: fn(*a, **kwargs), **mk)(*args)
        except Exception as e:           # noqa: BLE001 - report, don't die
            return [_error_finding(entry, "trace", e)]
        if "CA201" in active:
            findings.extend(check_downcasts(entry, jaxpr))
        if "CA203" in active:
            findings.extend(check_collective_axes(entry, jaxpr))
    if "CA202" in active and entry.get("reuse") is not None:
        try:
            with jax.enable_x64(True):
                findings.extend(check_reuse(entry))
        except Exception as e:           # noqa: BLE001
            findings.append(_error_finding(entry, "reuse", e))
    return findings


def run_entries(entries, profile: Profile) -> list:
    findings = []
    for entry in entries:
        findings.extend(run_entry(entry, profile))
    return findings
