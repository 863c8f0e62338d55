"""Differential kernel sanitizer: interpret-mode fuzz vs ref.py oracles.

The static CA4xx pass proves the GEOMETRY of every registered kernel;
this harness proves the ARITHMETIC: each ``KERNEL_ENTRIES`` entry's fuzz
builder runs the kernel in interpret mode (kernel body executed as jax
ops on CPU) against its jitted pure-jnp oracle at every manifest
configuration — edge tiles, the prime-p full-tile fallback, inf-guarded
weight lanes — and the declared tolerance class is ENFORCED:

  * ``bit-exact`` outputs are compared with
    ``np.testing.assert_array_equal`` — one flipped ulp fails;
  * ``fp-tolerant`` outputs use ``np.allclose`` at the entry's
    rtol/atol.

Seeding is deterministic per (seed, entry, config) via
``np.random.SeedSequence`` over stable CRC32 digests (no PYTHONHASHSEED
dependence), so CI failures replay locally with the same arrays.
Exposed as ``repro-analyze --fuzz-kernels`` and as the pytest module
``tests/test_kernel_sanitizer.py``.
"""
from __future__ import annotations

import traceback
import zlib
from dataclasses import asdict, dataclass

import numpy as np


@dataclass(frozen=True)
class FuzzResult:
    """One compared output of one (entry, config) fuzz case."""
    entry: str
    config: str
    output: str
    tolerance: str
    ok: bool
    max_abs_diff: float = 0.0
    detail: str = ""

    def to_json(self) -> dict:
        return asdict(self)

    def render(self) -> str:
        status = "ok" if self.ok else "FAIL"
        out = (f"{status}: {self.entry} [{self.config}] {self.output} "
               f"({self.tolerance}, max|diff|={self.max_abs_diff:.3e})")
        if self.detail:
            out += f" — {self.detail}"
        return out


def case_rng(seed: int, entry_name: str, label: str):
    """Deterministic per-case generator, stable across processes."""
    return np.random.default_rng(np.random.SeedSequence([
        seed, zlib.crc32(entry_name.encode()), zlib.crc32(label.encode())]))


def _compare(entry: dict, label: str, name: str, got, want,
             tol_class: str) -> FuzzResult:
    from ..kernels.manifest import TOLERANCE_CLASSES

    g, w = np.asarray(got), np.asarray(want)
    base = dict(entry=entry["name"], config=label, output=name,
                tolerance=tol_class)
    if tol_class not in TOLERANCE_CLASSES:
        return FuzzResult(ok=False, detail=f"unknown tolerance class "
                          f"{tol_class!r} (CA405 contract)", **base)
    if g.shape != w.shape or g.dtype != w.dtype:
        return FuzzResult(
            ok=False, detail=f"shape/dtype mismatch: kernel "
            f"{g.shape}/{g.dtype} vs oracle {w.shape}/{w.dtype}", **base)
    finite = np.isfinite(g) & np.isfinite(w)
    mad = float(np.max(np.abs(g[finite] - w[finite]))) \
        if finite.any() else 0.0
    if tol_class == "bit-exact":
        try:
            np.testing.assert_array_equal(g, w)
            return FuzzResult(ok=True, max_abs_diff=mad, **base)
        except AssertionError:
            n_bad = int(np.sum(~((g == w) | (np.isnan(g) & np.isnan(w)))))
            return FuzzResult(
                ok=False, max_abs_diff=mad,
                detail=f"{n_bad} element(s) differ from the oracle but "
                       f"the entry declares bit-exact", **base)
    ok = bool(np.allclose(g, w, rtol=entry.get("rtol", 1e-12),
                          atol=entry.get("atol", 1e-12)))
    detail = "" if ok else (
        f"outside rtol={entry.get('rtol')}/atol={entry.get('atol')}")
    return FuzzResult(ok=ok, max_abs_diff=mad, detail=detail, **base)


def run_case(entry: dict, cfg: dict, *, seed: int = 0) -> list:
    """Fuzz one (entry, config) pair under enable_x64.  Returns a list
    of :class:`FuzzResult` (one per compared output).  Never raises: a
    crashed builder surfaces as a single failed result."""
    import jax

    label = cfg.get("label", "?")
    rng = case_rng(seed, entry["name"], label)
    try:
        with jax.enable_x64(True):
            cases = entry["fuzz"](cfg, rng)
            results = [_compare(entry, label, name, got, want, tol)
                       for name, got, want, tol in cases]
    except Exception as e:          # noqa: BLE001 - report, don't die
        tb = traceback.format_exception_only(type(e), e)[-1].strip()
        return [FuzzResult(entry=entry["name"], config=label,
                           output="<error>", tolerance="-", ok=False,
                           detail=f"fuzz builder raised: {tb}")]
    if not results:
        return [FuzzResult(entry=entry["name"], config=label,
                           output="<empty>", tolerance="-", ok=False,
                           detail="fuzz builder compared no outputs")]
    return results


def fuzz_entries(entries, *, seed: int = 0) -> list:
    """Run every configuration of every entry.  Returns all results
    (use :func:`failures` to gate)."""
    results = []
    for entry in entries:
        for cfg in entry.get("configs", ()):
            results.extend(run_case(entry, cfg, seed=seed))
    return results


def failures(results) -> list:
    return [r for r in results if not r.ok]


def report(results, *, seed: int) -> dict:
    """The JSON artifact block CI uploads under ``kernel_fuzz``."""
    bad = failures(results)
    return {
        "seed": seed,
        "cases": [r.to_json() for r in results],
        "counts": {"cases": len(results), "failures": len(bad)},
    }
