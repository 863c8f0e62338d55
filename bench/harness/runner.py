"""One run of one cell: set-up, the measured window, the check.

Set-up generates the cell's data set from the seed on the device, reads
the compile cache and makes the warm-up call.  The window then calls the
public entry point closed-loop, one caller, each call starting when the
previous one has returned, until ``seconds`` have passed.  After the
window the device's peak memory is read, the program's arrays are freed,
and a sample of the window's answers, drawn from the seed, is compared
with the configuration's plain reference.
"""
from __future__ import annotations

import gc
import shutil
import sys
import time
from dataclasses import dataclass, field

import numpy as np

from . import data as bench_data
from . import xtrace
from .spec import ROOT, Cell, reader

CACHE_DIR = ROOT / ".bench_cache" / "jax"
TRACE_DIR = ROOT / ".bench_cache" / "trace"

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
#: host events of the benchmark itself, which never name an idle gap: its
#: annotations and the Python tracer's frames of its own files
HARNESS_FRAMES = ("bench.", "$runner.py", "$program.py", "$run.py")


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclass
class CallRecord:
    wall_s: float           # host clock around the public call
    solve_s: float          # sum of the reports' own solve seconds
    trials: int             # sum of the reports' line-search trials
    segments: int | None    # chunk programs of a batched path


@dataclass
class RunRecord:
    """What the metric readers of ``bench/metrics`` read."""
    cell: str
    setup_s: float
    window_s: float = 0.0
    calls: list = field(default_factory=list)
    trace: xtrace.TraceSummary | None = None


class CompileClock:
    """Counts the backend compiles JAX reports."""

    def __init__(self):
        import jax
        self.backend_compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == BACKEND_COMPILE:
            self.backend_compiles += 1


def require_chips(chips: int):
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX's first device is {devices[0].platform!r}; this "
                     f"benchmark runs only on a TPU")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX shows "
                     f"{len(devices)}")
    return devices


def enable_cache():
    import jax
    CACHE_DIR.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class Reservoir:
    """A uniform sample of ``k`` calls of the window, drawn from the seed."""

    def __init__(self, k: int, seed: int):
        self.k = k
        self.rng = np.random.default_rng(seed)
        self.kept = []          # (call index, reports)
        self.seen = 0

    def offer(self, index, reports):
        self.seen += 1
        if len(self.kept) < self.k:
            self.kept.append((index, reports))
            return
        j = int(self.rng.integers(self.seen))
        if j < self.k:
            self.kept[j] = (index, reports)


def peak_bytes(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, *, program_factory=None, on_chip=True):
    """Run ``cell``; returns the result dict of the output line.

    ``program_factory`` stands in for ``Program`` (the tests use it to
    plant faults); ``on_chip=False`` skips the look for a TPU and the
    compile cache (the tests run on the CPU)."""
    import jax

    name = cell.name
    cfg, traffic = cell.config, cell.traffic
    devices = require_chips(cell.chips) if on_chip else jax.devices()
    if on_chip:
        enable_cache()
    clock = CompileClock()

    from .program import Program
    program = (program_factory or Program)(cfg, traffic)
    x = bench_data.make_x(traffic["data_seed"], seed, cfg["n"], cfg["p"],
                          cfg["graph"]["weight"])
    x.block_until_ready()
    for _ in range(int(traffic["warmup_calls"])):
        program.call(x)
    gc.collect()
    record = RunRecord(cell=name, setup_s=time.perf_counter() - t_start)
    log(f"bench: {name} set-up {record.setup_s:.3f} s "
        f"(backend compiles {clock.backend_compiles})")

    trace_dir = None
    if trace:
        trace_dir = TRACE_DIR / name
        shutil.rmtree(trace_dir, ignore_errors=True)
        trace_dir.mkdir(parents=True)
        jax.profiler.start_trace(str(trace_dir))

    sample = Reservoir(int(traffic["sample_checked"]), seed)
    compiles0 = clock.backend_compiles
    with jax.profiler.TraceAnnotation("bench.window"):
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            t_call = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.call"):
                reports, stats = program.call(x)
            wall = time.perf_counter() - t_call
            record.calls.append(CallRecord(
                wall_s=wall,
                solve_s=sum(r.wall_time_s for r in reports),
                trials=sum(int(r.ls_total) for r in reports),
                segments=None if stats is None else int(stats.segments)))
            sample.offer(len(record.calls) - 1, reports)
            del reports, stats
        record.window_s = time.perf_counter() - t0
    window_compiles = clock.backend_compiles - compiles0

    if trace:
        jax.profiler.stop_trace()
        profile = xtrace.load(str(trace_dir))
        bounds = _window_bounds(profile)
        record.trace = xtrace.reduce(profile, window=bounds,
                                     skip_host=HARNESS_FRAMES)
        del profile
        shutil.rmtree(trace_dir, ignore_errors=True)

    used = devices[:cell.chips]
    if record.trace is not None:
        ids = {d.id for d in used}
        record.trace.devices = {i: s for i, s in record.trace.devices.items()
                                if i in ids}
    mem_peak = peak_bytes(used) if on_chip else 0

    # free the program's state before the reference runs
    kept = [(i, [(float(r.lam1), np.asarray(r.omega), float(r.objective))
                 for r in reps])
            for i, reps in sample.kept]
    sample.kept.clear()
    x_host = np.asarray(x)
    del x, program
    gc.collect()

    log(f"bench: {name} {len(record.calls)} calls in {record.window_s:.3f} s,"
        f" backend compiles in the window {window_compiles}")
    check = compare(cell, kept, x_host)
    metrics = {}
    entries = cell.per_layer() if trace else cell.end_to_end()
    for m in entries:
        value = reader(m["name"])(record)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind,
              "count": len(devices),
              "memory_peak_bytes": mem_peak}
    result = {"correct": check["correct"],
              "attempted": len(record.calls),
              "failed": check["failed"],
              "metrics": metrics, "device": device}
    if record.trace is not None:
        device["busy_s"] = record.trace.mean("busy_ns") / 1e9
        device["window_s"] = record.window_s
        result["breakdown"] = {"device_ops": record.trace.top_ops,
                               "idle_gaps": record.trace.idle_gaps}
    result["check"] = check["numbers"]
    return result


def _window_bounds(profile):
    for s, e, name in xtrace.host_events(profile):
        if name == "bench.window":
            return (s, e)
    return None


def compare(cell: Cell, kept, x_host) -> dict:
    """Compare the sampled answers with the plain reference; every number
    is the worst over the sampled calls and their estimates.  ``kept``
    holds, per sampled call, its index and (lam1, estimate, reported
    objective) of each of its estimates; ``x_host`` is the run's data."""
    ref = cell.reference()
    lam2 = float(cell.config["lam2"])
    expected = _expected_lam1(cell)
    worst = {}
    failed = 0
    op = ref.GradientOperator(x_host)
    for _, points in kept:
        values = {"missing_points": float(_missing(expected, points))}
        for lam1, omega, objective in points:
            res = ref.kkt_residual(omega, op, lam1, lam2)
            res["obj_gap"] = _rel_gap(objective, res.pop("objective"))
            for k, v in res.items():
                values[k] = max(values.get(k, 0.0), v)
        if not _within(values, cell.limits):
            failed += 1
        for k, v in values.items():
            worst[k] = max(worst.get(k, 0.0), v)
    numbers = {k: {"value": worst[k], "limit": cell.limits[k]["limit"]}
               for k in cell.limits if k in worst}
    correct = bool(kept) and failed == 0 and len(numbers) == len(cell.limits)
    for k, v in numbers.items():
        log(f"check {k} = {v['value']!r} (limit {v['limit']!r})")
    return {"correct": correct, "failed": failed, "numbers": numbers,
            "values": worst}


def _rel_gap(reported: float, reference: float) -> float:
    """|reported - reference| / |reference|; infinite where either is not
    a finite number."""
    if not (np.isfinite(reported) and np.isfinite(reference)) or reference == 0:
        return float("inf")
    return abs(reported - reference) / abs(reference)


def _expected_lam1(cell: Cell) -> list:
    if cell.traffic["entry"] == "fit_path":
        return sorted((float(v) for v in cell.config["lam1_grid"]),
                      reverse=True)
    return [float(cell.config["lam1"])]


def _missing(expected, points) -> int:
    got = sorted((point[0] for point in points), reverse=True)
    if len(got) != len(expected):
        return max(len(expected), abs(len(got) - len(expected)))
    return sum(1 for a, b in zip(expected, got)
               if abs(a - b) > 1e-9 * max(1.0, abs(a)))


def _within(values: dict, limits: dict) -> bool:
    return all(k in values and values[k] <= lim["limit"]
               for k, lim in limits.items())
