"""Host-side span/event tracer on the profiler's timeline.

Spans mark host-boundary work (input validation, a solve dispatch, the
per-estimate report, a compact-schedule segment and its chunk boundary, a
Gram chunk update, a serve request); instant events mark points in time.
Every span enters a ``jax.profiler.TraceAnnotation`` named
``concord.<span name>``, at every ``obs`` level, so a profiler trace
(``jax.profiler.trace(dir)``) holds the program's host steps on the same
clock as the device ops.  With no profiler running an annotation costs
about a microsecond.  Nothing here is visible to jax tracing, so no level
can change a compiled program or its numerics.

The ring buffer records a span only when the tracer's mode enables its
level; it keeps ``time.perf_counter`` durations for
``FitReport.telemetry`` and the JSONL export.  Two levels nest:

  * ``"summary"`` — one span per coarse unit of work (solve, path
    point, report, request).  The overhead benchmark
    (``benchmarks/obs_overhead.py``, an 8-point p=512 f64 path on the
    CPU) holds it under 2% of wall time.
  * ``"trace"``  — adds fine-grained spans (dispatch vs execute,
    validation, the report's copy/scan/penalty steps, per-segment chunk
    launches and their boundaries, per-chunk Gram updates).

``mode="off"`` (the default) records nothing in the ring.

Export: :meth:`Tracer.export_jsonl` (one JSON object per line);
:func:`load_jsonl` reads it back for the ``repro-obs`` CLI.
"""
from __future__ import annotations

import json
import threading
import time
from collections import deque
from contextlib import contextmanager
from dataclasses import dataclass, field

from jax.profiler import TraceAnnotation

MODES = ("off", "summary", "trace")
_LEVEL_RANK = {"off": 0, "summary": 1, "trace": 2}

#: profiler-annotation prefix: keeps the program's spans apart from the
#: Python tracer's frames and other annotations on the host plane
ANNOTATION_PREFIX = "concord."

#: ring-buffer capacity: old spans fall off rather than growing without
#: bound in an always-on service
RING_CAPACITY = 4096


@dataclass
class Span:
    """One recorded span (``phase="span"``) or instant event
    (``phase="instant"``).  Times are ``time.perf_counter`` seconds."""
    name: str
    cat: str = "solver"
    t_start: float = 0.0
    duration: float = 0.0
    level: str = "summary"
    phase: str = "span"
    args: dict = field(default_factory=dict)

    def note(self, **attrs) -> "Span":
        """Attach attributes discovered while the span is open (iteration
        counts, convergence flags, ...)."""
        self.args.update(attrs)
        return self

    def to_json(self) -> dict:
        return {
            "name": self.name, "cat": self.cat, "ph": self.phase,
            "t_start": self.t_start, "duration": self.duration,
            "level": self.level, "args": dict(self.args),
        }


class _SpanScope:
    """One span: a profiler annotation always, and a ring record on exit
    when the tracer's mode enables the span's level (``span`` is then
    the record, else None)."""
    __slots__ = ("_tracer", "_span", "_annotation")

    def __init__(self, tracer: "Tracer", name: str, span: Span | None):
        self._tracer = tracer
        self._span = span
        self._annotation = TraceAnnotation(ANNOTATION_PREFIX + name)

    def __enter__(self):
        self._annotation.__enter__()
        if self._span is None:
            return self
        self._span.t_start = time.perf_counter()
        return self._span

    def __exit__(self, *exc):
        if self._span is not None:
            self._span.duration = time.perf_counter() - self._span.t_start
            self._tracer._record(self._span)
        self._annotation.__exit__(*exc)
        return False

    def note(self, **attrs):
        if self._span is not None:
            self._span.note(**attrs)
        return self


class Tracer:
    """Mode-gated span recorder over a bounded ring buffer."""

    def __init__(self, mode: str = "off", capacity: int = RING_CAPACITY):
        self._mode = "off"
        self.set_mode(mode)
        self._events: deque = deque(maxlen=capacity)
        self._lock = threading.Lock()

    # -- mode ------------------------------------------------------------
    @property
    def mode(self) -> str:
        return self._mode

    def set_mode(self, mode: str) -> None:
        if mode not in MODES:
            raise ValueError(f"obs mode must be one of {MODES}, got {mode!r}")
        self._mode = mode

    def enabled(self, level: str = "summary") -> bool:
        return _LEVEL_RANK[self._mode] >= _LEVEL_RANK.get(level, 99)

    @contextmanager
    def scoped(self, mode: str):
        """Temporarily run the tracer at ``mode`` (how the facade applies
        ``SolverConfig.obs`` for the duration of one call)."""
        prev = self._mode
        self.set_mode(mode)
        try:
            yield self
        finally:
            self._mode = prev

    # -- recording -------------------------------------------------------
    def span(self, name: str, *, cat: str = "solver",
             level: str = "summary", **attrs):
        """``with tracer.span("fit", p=64) as s: ... s.note(iters=12)``

        Enters the profiler annotation ``concord.<name>`` at every mode;
        records into the ring only when the mode enables ``level``."""
        span = (Span(name=name, cat=cat, level=level, args=dict(attrs))
                if self.enabled(level) else None)
        return _SpanScope(self, name, span)

    def event(self, name: str, *, cat: str = "solver",
              level: str = "summary", **attrs) -> None:
        if not self.enabled(level):
            return
        self._record(Span(name=name, cat=cat, t_start=time.perf_counter(),
                          duration=0.0, level=level, phase="instant",
                          args=dict(attrs)))

    def _record(self, span: Span) -> None:
        with self._lock:
            self._events.append(span)

    # -- inspection ------------------------------------------------------
    def snapshot(self) -> tuple:
        """Point-in-time copy of the ring (oldest first)."""
        with self._lock:
            return tuple(self._events)

    def clear(self) -> None:
        with self._lock:
            self._events.clear()

    def __len__(self) -> int:
        return len(self._events)

    # -- export ----------------------------------------------------------
    def export_jsonl(self, path) -> int:
        spans = self.snapshot()
        with open(path, "w", encoding="utf-8") as f:
            for s in spans:
                f.write(json.dumps(s.to_json()) + "\n")
        return len(spans)


def _span_from_json(d: dict) -> Span:
    return Span(name=d["name"], cat=d.get("cat", "solver"),
                t_start=d["t_start"], duration=d["duration"],
                level=d.get("level", "summary"),
                phase=d.get("ph", "span"), args=dict(d.get("args", ())))


def load_jsonl(path) -> list:
    with open(path, encoding="utf-8") as f:
        return [_span_from_json(json.loads(line))
                for line in f if line.strip()]


# ---------------------------------------------------------------------------
# process-global tracer
# ---------------------------------------------------------------------------

_TRACER: Tracer | None = None


def get_tracer() -> Tracer:
    global _TRACER
    if _TRACER is None:
        _TRACER = Tracer()
    return _TRACER
