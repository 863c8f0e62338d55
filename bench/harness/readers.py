"""Readers of a ``RunRecord``, shared by the files of ``bench/metrics``.

Each returns a number, or None where the run holds nothing to read (the
metric is then left out of the result line)."""
from __future__ import annotations


def seconds_per_call(r):
    """Window seconds over calls completed (closed loop: the mean call)."""
    return r.window_s / len(r.calls) if r.calls else None


def setup_seconds(r):
    return r.setup_s


def host_seconds(r):
    """Host clock around the public call minus the solve seconds the
    program reports for it, per call."""
    if not r.calls:
        return None
    return sum(c.wall_s - c.solve_s for c in r.calls) / len(r.calls)


def trials(r):
    """Line-search trials per call (summed over a path's points)."""
    if not r.calls:
        return None
    return sum(c.trials for c in r.calls) / len(r.calls)


def segments(r):
    """Chunk programs a batched path launched, per call."""
    vals = [c.segments for c in r.calls if c.segments is not None]
    return sum(vals) / len(vals) if vals else None


def _per_call_device(r, attr):
    if r.trace is None or not r.calls or not r.trace.devices:
        return None
    ns = r.trace.mean(attr)
    return ns / 1e9 / len(r.calls) if ns > 0 else None


def matmul_seconds(r):
    """Device seconds in matrix-product ops per call (mean over chips)."""
    return _per_call_device(r, "matmul_ns")


def idle_share(r):
    """Percent of the traced window in which no op ran (mean over chips)."""
    if r.trace is None or not r.trace.devices or r.window_s <= 0:
        return None
    busy = r.trace.mean("busy_ns") / 1e9
    if busy <= 0:
        return None
    return 100.0 * (1.0 - busy / r.window_s)
