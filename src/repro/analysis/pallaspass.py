"""pallas engine: Pallas kernel grid/BlockSpec rules (CA4xx).

The AST/jaxpr/comm engines stop at the ``pallas_call`` boundary: a write
race in a scatter-style output index map, a coverage gap leaving stale
output tiles, or an out-of-bounds block id are all invisible to them.
This engine closes that gap CONCRETELY: every
``kernels.manifest.KERNEL_ENTRIES`` configuration's grid is enumerated
(grids are small — thousands of points) and every BlockSpec index map is
evaluated at every grid point, with the scalar-prefetch vectors bound
exactly as ``PrefetchScalarGridSpec`` binds them.

On that enumeration:

  * CA401 — two grid points write the same output block along grid dims
    the kernel does not declare as sequential accumulation, or a
    declared accumulation revisits a block non-consecutively (TPU grids
    execute sequentially, last dim fastest, and an output block is
    flushed when its index changes — a non-contiguous revisit clobbers);
  * CA402 — the written blocks fail to tile the output array;
  * CA403 — a block index leaves [0, cdiv(dim, block)) for any operand;
  * CA404 — ``make_jaxpr`` of the kernel function (f64-contract entries
    only) shows a float64 value narrowing inside the traced body;
  * CA405 — a ``pallas_call``-bearing kernel module registers no entry,
    or an entry names a missing ``ref.py`` oracle / unknown tolerance
    class;
  * CA406 — index-map arity vs grid (+ prefetch) rank, block rank vs
    operand rank, block dims vs operand dims, SMEM scalar-table rows vs
    the grid's lane demand.

Like the other engines it never raises: a broken entry surfaces as
CA400 so it cannot mask the rest.  ``run_entries`` returns
``(findings, records)`` with JSON-able per-entry grid records for the
CI artifact, mirroring the comm engine.
"""
from __future__ import annotations

import ast
import inspect
import itertools
import traceback
from pathlib import Path

from .findings import Finding
from .jaxprpass import NARROW_FLOATS, _eqn_snippet, iter_eqns
from .rules import Profile

#: grid-size ceiling per configuration — a registry mistake (e.g. a
#: full-size production shape) must fail loudly, not hang the gate
MAX_GRID_POINTS = 1_000_000


def _finding(rule: str, entry: dict, message: str, *,
             snippet: str = "") -> Finding:
    return Finding(rule=rule, path=entry["path"], line=0,
                   context=entry["name"], message=message, snippet=snippet)


def _error_finding(entry: dict, stage: str, exc: BaseException) -> Finding:
    tb = traceback.format_exception_only(type(exc), exc)[-1].strip()
    return Finding(
        rule="CA400", path=entry["path"], line=0, context=entry["name"],
        message=f"kernel entry failed during {stage}: {tb} — a broken "
                f"entry means the grid/BlockSpec checks did not run",
        snippet=stage)


# -- geometry helpers -------------------------------------------------------

def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


def _grid_points(grid) -> list:
    return list(itertools.product(*(range(int(g)) for g in grid)))


def _map_arity(index_map) -> int:
    """Non-default positional parameter count of an index map (bound
    closure constants like flash attention's ``g=group`` don't count)."""
    params = inspect.signature(index_map).parameters.values()
    return sum(1 for p in params
               if p.kind in (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD)
               and p.default is p.empty)


def _eval_map(spec, point, prefetch) -> tuple:
    idx = spec.index_map(*point, *prefetch)
    if not isinstance(idx, tuple):
        idx = (idx,)
    return tuple(int(v) for v in idx)


def _nblocks(arg) -> tuple:
    return tuple(_cdiv(dim, bs)
                 for dim, bs in zip(arg.shape, arg.spec.block_shape))


def _block_args(layout, role: str):
    """(position, BlockArg) pairs of one side, SMEM scalar specs
    (block_shape None) excluded — they have no index map."""
    args = layout.inputs if role == "in" else layout.outputs
    return [(k, a) for k, a in enumerate(args)
            if a.spec.block_shape is not None]


# -- per-config checks ------------------------------------------------------

def check_spec_shapes(entry: dict, label: str, layout) -> list:
    """CA406: grid/BlockSpec/SMEM scalar-table consistency."""
    out = []
    where = f"config '{label}'"
    if any(int(g) < 1 for g in layout.grid):
        out.append(_finding(
            "CA406", entry,
            f"{where}: grid {tuple(layout.grid)} has a non-positive "
            f"dimension — the kernel body would never run",
            snippet=f"grid={tuple(layout.grid)}"))
        return out
    want_arity = len(layout.grid) + len(layout.prefetch)
    for role in ("in", "out"):
        for k, arg in _block_args(layout, role):
            bs = arg.spec.block_shape
            tag = f"{where}: {role}[{k}] '{arg.name}'"
            if len(bs) != len(arg.shape):
                out.append(_finding(
                    "CA406", entry,
                    f"{tag}: block shape {tuple(bs)} has rank {len(bs)} "
                    f"but the operand is rank {len(arg.shape)} "
                    f"{tuple(arg.shape)}",
                    snippet=f"{arg.name}: block={tuple(bs)}"))
                continue
            if any(int(b) < 1 for b in bs) or any(
                    int(b) > int(d) for b, d in zip(bs, arg.shape)):
                out.append(_finding(
                    "CA406", entry,
                    f"{tag}: block shape {tuple(bs)} does not fit the "
                    f"operand shape {tuple(arg.shape)} (every block dim "
                    f"must be in [1, dim])",
                    snippet=f"{arg.name}: block={tuple(bs)}"))
            arity = _map_arity(arg.spec.index_map)
            if arity != want_arity:
                out.append(_finding(
                    "CA406", entry,
                    f"{tag}: index map takes {arity} grid argument(s) "
                    f"but the grid rank plus scalar-prefetch count is "
                    f"{want_arity} — the map would be called with the "
                    f"wrong arity",
                    snippet=f"{arg.name}: arity {arity} != {want_arity}"))
    for k, rows in layout.scalar_rows.items():
        arg = layout.inputs[k]
        have = int(arg.shape[0]) if arg.shape else 0
        if have < rows:
            out.append(_finding(
                "CA406", entry,
                f"{where}: SMEM scalar table in[{k}] '{arg.name}' holds "
                f"{have} row(s) but the grid's lane indexing reads up to "
                f"row {rows - 1} — the kernel body would read past the "
                f"table",
                snippet=f"{arg.name}: rows {have} < {rows}"))
    return out


def check_bounds(entry: dict, label: str, layout, points) -> list:
    """CA403: every evaluated block index inside the padded bounds."""
    out = []
    for role in ("in", "out"):
        for k, arg in _block_args(layout, role):
            nb = _nblocks(arg)
            flagged = set()
            for point in points:
                idx = _eval_map(arg.spec, point, layout.prefetch)
                if len(idx) != len(nb):
                    if ("rank", k, role) not in flagged:
                        flagged.add(("rank", k, role))
                        out.append(_finding(
                            "CA406", entry,
                            f"config '{label}': {role}[{k}] "
                            f"'{arg.name}' index map returns "
                            f"{len(idx)} coordinate(s) for a rank-"
                            f"{len(nb)} block grid at grid point "
                            f"{point}",
                            snippet=f"{arg.name}: {idx}"))
                    continue
                for d, (i, n) in enumerate(zip(idx, nb)):
                    if 0 <= i < n or (d, k, role) in flagged:
                        continue
                    flagged.add((d, k, role))
                    out.append(_finding(
                        "CA403", entry,
                        f"config '{label}': {role}[{k}] '{arg.name}' "
                        f"block index {i} along dim {d} is outside "
                        f"[0, {n}) at grid point {point} (operand "
                        f"{tuple(arg.shape)}, block "
                        f"{tuple(arg.spec.block_shape)}) — the kernel "
                        f"would address past the padded operand",
                        snippet=f"{arg.name}[{d}]: {i} not in [0, {n})"))
    return out


def check_races(entry: dict, label: str, layout, points) -> list:
    """CA401: overlapping output writes along undeclared dims, and
    non-contiguous revisits of a declared sequential accumulation."""
    out = []
    for k, arg in _block_args(layout, "out"):
        nb = _nblocks(arg)
        # points are enumerated in execution order (row-major, last grid
        # dim fastest — TPU semantics), so `lin` is the grid step index
        writes: dict = {}
        for lin, point in enumerate(points):
            idx = _eval_map(arg.spec, point, layout.prefetch)
            if len(idx) != len(nb) or not all(
                    0 <= i < n for i, n in zip(idx, nb)):
                continue        # CA403/CA406 territory
            writes.setdefault(idx, []).append((lin, point))
        declared = layout.sequential.get(k, frozenset())
        seen_race = False
        seen_revisit = False
        for blk, hits in sorted(writes.items()):
            if len(hits) < 2:
                continue
            pts = [p for _, p in hits]
            varying = {d for d in range(len(layout.grid))
                       if len({p[d] for p in pts}) > 1}
            undeclared = varying - set(declared)
            if undeclared and not seen_race:
                seen_race = True
                (l0, p0), (l1, p1) = hits[0], hits[1]
                out.append(_finding(
                    "CA401", entry,
                    f"config '{label}': out[{k}] '{arg.name}' block "
                    f"{blk} is written by {len(hits)} grid points (e.g. "
                    f"{p0} and {p1}) that differ along grid dim(s) "
                    f"{sorted(undeclared)} which the kernel does NOT "
                    f"declare as sequential accumulation — overlapping "
                    f"output writes race (scatter indices must be "
                    f"unique, or the dim declared sequential)",
                    snippet=f"{arg.name}{blk}: points {p0} vs {p1}"))
            elif not undeclared and not seen_revisit:
                lins = [ln for ln, _ in hits]
                if max(lins) - min(lins) != len(lins) - 1:
                    seen_revisit = True
                    out.append(_finding(
                        "CA401", entry,
                        f"config '{label}': out[{k}] '{arg.name}' block "
                        f"{blk} is revisited NON-consecutively along its "
                        f"declared sequential dim(s) "
                        f"{sorted(declared)} (grid steps {sorted(lins)}) "
                        f"— the output block is flushed when its index "
                        f"changes, so the later visit clobbers the "
                        f"earlier partial sums (duplicate scatter ids "
                        f"must form one contiguous run)",
                        snippet=f"{arg.name}{blk}: steps {sorted(lins)}"))
    return out


def check_coverage(entry: dict, label: str, layout, points) -> list:
    """CA402: the written blocks must tile every output array."""
    out = []
    for k, arg in _block_args(layout, "out"):
        nb = _nblocks(arg)
        written = set()
        for point in points:
            idx = _eval_map(arg.spec, point, layout.prefetch)
            if len(idx) == len(nb) and all(
                    0 <= i < n for i, n in zip(idx, nb)):
                written.add(idx)
        expected = set(itertools.product(*(range(n) for n in nb)))
        missing = sorted(expected - written)
        if missing:
            shown = ", ".join(map(str, missing[:4]))
            if len(missing) > 4:
                shown += ", ..."
            out.append(_finding(
                "CA402", entry,
                f"config '{label}': out[{k}] '{arg.name}' — "
                f"{len(missing)} of {len(expected)} output blocks are "
                f"never written ({shown}): unwritten blocks ship stale "
                f"memory",
                snippet=f"{arg.name}: missing {shown}"))
    return out


# -- whole-entry checks -----------------------------------------------------

def check_accumulator(entry: dict) -> list:
    """CA404: trace the kernel function at f64 and walk its (nested)
    jaxprs — the interpret-mode pallas_call body traces as jax ops — for
    float64 values narrowing to f32/f16/bf16."""
    import jax

    with jax.enable_x64(True):
        spec = entry["trace"]()
        fn, args = spec["fn"], tuple(spec.get("args", ()))
        kwargs = dict(spec.get("kwargs", {}))
        jaxpr = jax.make_jaxpr(lambda *a: fn(*a, **kwargs))(*args)
    out = []
    seen = set()
    for eqn in iter_eqns(jaxpr):
        prim = eqn.primitive.name
        if prim == "convert_element_type":
            src = getattr(eqn.invars[0].aval, "dtype", None)
            dst = eqn.params.get("new_dtype")
            if src is None or dst is None:
                continue
            if str(src) == "float64" and str(dst) in NARROW_FLOATS:
                key = ("convert", str(dst))
                if key in seen:
                    continue
                seen.add(key)
                out.append(_finding(
                    "CA404", entry,
                    f"float64 value narrowed to {dst} inside the traced "
                    f"kernel body of '{entry['name']}': the f64 "
                    f"iteration contract must hold inside the kernel "
                    f"(accumulate at the operand dtype, or exempt the "
                    f"kernel from the f64 contract explicitly)",
                    snippet=_eqn_snippet(eqn)))
        elif prim == "dot_general":
            pref = eqn.params.get("preferred_element_type")
            srcs = {str(getattr(v.aval, "dtype", "")) for v in eqn.invars}
            if pref is not None and srcs == {"float64"} and \
                    str(pref) in NARROW_FLOATS:
                key = ("dot", str(pref))
                if key in seen:
                    continue
                seen.add(key)
                out.append(_finding(
                    "CA404", entry,
                    f"dot_general over float64 operands accumulates at "
                    f"preferred_element_type={pref} inside "
                    f"'{entry['name']}': a narrow MXU accumulator "
                    f"breaks the f64 contract",
                    snippet=_eqn_snippet(eqn)))
    return out


def check_oracle(entry: dict) -> list:
    """CA405 (per-entry half): the declared oracle twin must exist on
    kernels.ref and the tolerance class must be a known one."""
    from ..kernels import ref
    from ..kernels.manifest import TOLERANCE_CLASSES

    out = []
    oracle = entry.get("oracle")
    if not oracle or not hasattr(ref, oracle):
        out.append(_finding(
            "CA405", entry,
            f"entry '{entry['name']}' declares oracle {oracle!r} but "
            f"kernels.ref has no such function — every kernel needs a "
            f"pure-jnp twin to be differentially testable",
            snippet=f"oracle={oracle!r}"))
    tol = entry.get("tolerance")
    if tol not in TOLERANCE_CLASSES:
        out.append(_finding(
            "CA405", entry,
            f"entry '{entry['name']}' declares tolerance class {tol!r}; "
            f"it must be one of {TOLERANCE_CLASSES} so the sanitizer "
            f"knows whether to compare bit-exactly or within rtol/atol",
            snippet=f"tolerance={tol!r}"))
    return out


def _module_has_pallas_call(path: Path) -> bool:
    try:
        tree = ast.parse(path.read_text(encoding="utf-8"))
    except (OSError, SyntaxError):
        return False            # unreadable/broken source is CA100's job
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        fn = node.func
        if (isinstance(fn, ast.Attribute) and fn.attr == "pallas_call") \
                or (isinstance(fn, ast.Name) and fn.id == "pallas_call"):
            return True
    return False


def check_module_coverage(entries) -> list:
    """CA405 (registry half): every kernels/*.py module that issues a
    ``pallas_call`` must be covered by at least one registry entry."""
    from .. import kernels as kpkg

    covered = {e.get("path") for e in entries}
    out = []
    kdir = Path(kpkg.__file__).resolve().parent
    for f in sorted(kdir.glob("*.py")):
        rel = f"src/repro/kernels/{f.name}"
        if rel in covered or not _module_has_pallas_call(f):
            continue
        out.append(Finding(
            rule="CA405", path=rel, line=0, context="kernels.manifest",
            message=f"{rel} issues pallas_call but registers no "
                    f"KERNEL_ENTRIES entry: the kernel ships with no "
                    f"oracle twin, no declared tolerance class and no "
                    f"grid/BlockSpec verification",
            snippet=f.name))
    return out


# -- driver -----------------------------------------------------------------

def run_entry(entry: dict, profile: Profile):
    """Check one registry entry.  Returns (findings, record); record is
    the JSON-able grid summary (None when nothing ran).  Never raises:
    failures surface as CA400 findings."""
    findings = []
    skip = set(entry.get("skip") or ())
    active = ({"CA401", "CA402", "CA403", "CA404", "CA405", "CA406"}
              & profile.rules) - skip
    if "CA405" in active:
        try:
            findings.extend(check_oracle(entry))
        except Exception as e:      # noqa: BLE001 - report, don't die
            findings.append(_error_finding(entry, "oracle", e))
    if "CA404" in active and entry.get("f64_contract") \
            and entry.get("trace") is not None:
        try:
            findings.extend(check_accumulator(entry))
        except Exception as e:      # noqa: BLE001
            findings.append(_error_finding(entry, "trace", e))

    cfg_records = []
    for cfg in entry.get("configs", ()):
        label = cfg.get("label", "?")
        try:
            layout = entry["layout"](cfg)
            npoints = 1
            for g in layout.grid:
                npoints *= int(g)
            if npoints > MAX_GRID_POINTS:
                raise ValueError(
                    f"grid {tuple(layout.grid)} has {npoints} points "
                    f"(> {MAX_GRID_POINTS}): register a reduced shape")
            points = _grid_points(layout.grid)
        except Exception as e:      # noqa: BLE001
            findings.append(_error_finding(entry, f"layout[{label}]", e))
            continue
        try:
            if "CA406" in active:
                findings.extend(check_spec_shapes(entry, label, layout))
            if "CA403" in active:
                findings.extend(check_bounds(entry, label, layout, points))
            if "CA401" in active:
                findings.extend(check_races(entry, label, layout, points))
            if "CA402" in active:
                findings.extend(
                    check_coverage(entry, label, layout, points))
        except Exception as e:      # noqa: BLE001
            findings.append(_error_finding(entry, f"checks[{label}]", e))
            continue
        cfg_records.append({
            "config": label,
            "grid": [int(g) for g in layout.grid],
            "points": len(points),
            "sequential": {str(k): sorted(v) for k, v in
                           layout.sequential.items()},
        })
    record = None
    if cfg_records or active:
        record = {"entry": entry["name"], "path": entry["path"],
                  "oracle": entry.get("oracle"),
                  "tolerance": entry.get("tolerance"),
                  "configs": cfg_records}
    return findings, record


def run_entries(entries, profile: Profile, *, all_entries=None):
    """Check a registry subset.  ``all_entries`` (default: ``entries``)
    is the full registry the CA405 module-coverage check runs against —
    under ``--changed`` scoping the per-entry checks shrink but coverage
    stays whole-program.  Returns (findings, grid_records)."""
    findings, records = [], []
    for entry in entries:
        f, rec = run_entry(entry, profile)
        findings.extend(f)
        if rec is not None:
            records.append(rec)
    if "CA405" in profile.rules:
        findings.extend(check_module_coverage(
            entries if all_entries is None else all_entries))
    return findings, records
