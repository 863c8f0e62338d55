"""Reduction of a JAX profiler trace (``.xplane.pb``) to device metrics.

A TPU trace holds one plane per chip, ``/device:TPU:<i>``, whose ``XLA Ops``
line has one event per executed HLO instruction, named by the
instruction's text (``%multiply_reduce_fusion.18 = (...) fusion(...),
kind=kOutput, ...``), on the same nanosecond clock as the host planes.  A
``while`` op's event spans the events of its body.  From those events this
module computes, per chip:

- busy time: the union of the op intervals (overlapping ops count once);
- matrix-product time: dots, convolutions and the output fusions
  (``kind=kOutput``) that XLA builds around them;
- collective time: all-reduce, all-gather, collective-permute,
  reduce-scatter, all-to-all, send and recv, with their start and done
  halves;

and for the whole trace the ops that took most device time (loops left
out, their bodies counted) and the longest idle gaps, each gap named by
the longest host event that overlaps it.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"

#: the op of an HLO instruction as the TPU trace names it:
#: "%name = <type> op(operands), ..." -- the first lower-case word that
#: opens a parenthesis and is not an operand (``%``) or a layout (``T(``)
_OP = re.compile(r"(?<![%\w.-])([a-z][a-z0-9_-]*)\(")
_FUSION_KIND = re.compile(r"\bkind=(k\w+)")
_COLLECTIVES = ("all-reduce", "all-gather", "collective-permute",
                "reduce-scatter", "all-to-all", "send", "recv")
#: ops whose interval holds other ops' intervals (a loop, a branch)
_CONTAINERS = ("while", "conditional", "call")


@dataclass
class Op:
    start_ns: float
    end_ns: float
    name: str

    @property
    def kind(self) -> str:
        """``fusion:kOutput``-style for fusions, else the HLO op name."""
        m = _OP.search(self.name)
        op = m.group(1) if m else self.name
        if op == "fusion":
            k = _FUSION_KIND.search(self.name)
            return f"fusion:{k.group(1)}" if k else op
        return op


def is_container(op: Op) -> bool:
    return op.kind in _CONTAINERS


def is_collective(op: Op) -> bool:
    kind = op.kind
    return any(kind == c or kind.startswith(c + "-") for c in _COLLECTIVES)


def is_matmul(op: Op) -> bool:
    """A dot or convolution, or a fusion rooted at one (XLA's output
    fusions, ``kind=kOutput``, are the fusions around a dot)."""
    return op.kind in ("dot", "convolution", "fusion:kOutput")


@dataclass
class DeviceSummary:
    busy_ns: float = 0.0
    matmul_ns: float = 0.0
    collective_ns: float = 0.0
    n_ops: int = 0


@dataclass
class TraceSummary:
    devices: dict = field(default_factory=dict)     # id -> DeviceSummary
    top_ops: list = field(default_factory=list)     # [(name, seconds)]
    idle_gaps: list = field(default_factory=list)   # [(host label, seconds)]

    def mean(self, attr: str) -> float:
        vals = [getattr(d, attr) for d in self.devices.values()]
        return sum(vals) / len(vals) if vals else 0.0


def load(trace_dir: str):
    """The newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return ProfileData.from_file(max(paths, key=os.path.getmtime))


def device_ops(profile) -> dict:
    """{device id: [Op, ...]} from the ``XLA Ops`` line of each TPU plane."""
    out = {}
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        ops = [Op(float(ev.start_ns), float(ev.start_ns) + float(ev.duration_ns),
                  ev.name)
               for line in plane.lines if line.name == OPS_LINE
               for ev in line.events]
        out[int(m.group(1))] = sorted(ops, key=lambda o: o.start_ns)
    return out


def host_events(profile) -> list:
    """(start_ns, end_ns, name) of every host event with a duration."""
    out = []
    for plane in profile.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.duration_ns > 0:
                    out.append((float(ev.start_ns),
                                float(ev.start_ns) + float(ev.duration_ns),
                                ev.name))
    return out


def union(intervals) -> list:
    """Merged, sorted (start, end) intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged


def summarize_device(ops) -> DeviceSummary:
    busy = union((o.start_ns, o.end_ns) for o in ops)
    return DeviceSummary(
        busy_ns=sum(e - s for s, e in busy),
        matmul_ns=sum(o.end_ns - o.start_ns for o in ops if is_matmul(o)),
        collective_ns=sum(o.end_ns - o.start_ns for o in ops
                          if is_collective(o)),
        n_ops=len(ops))


def label(op: Op) -> str:
    """A short name for the breakdown: the instruction and its kind."""
    head = op.name.split(" = ", 1)[0].lstrip("%")
    return f"{head} ({op.kind})"


def _gap_label(start, end, hosts, skip) -> str:
    """The host event that covers most of the gap; of those that cover it
    equally, the shortest, which is the innermost frame."""
    best, key = "no host event", (0.0, 0.0)
    for hs, he, name in hosts:
        if name.startswith(skip):
            continue
        ov = min(end, he) - max(start, hs)
        if ov > 0 and (ov, -(he - hs)) > key:
            best, key = name, (ov, -(he - hs))
    return best


def reduce(profile, *, window=None, skip_host=(), top=10) -> TraceSummary:
    """Per-device busy, matrix-product and collective time, plus the top
    ops and longest idle gaps.  ``window`` (start_ns, end_ns) clips the
    idle gaps to the measured window; ``skip_host`` names host events
    (the benchmark's own) that never label a gap."""
    per_dev = device_ops(profile)
    out = TraceSummary()
    totals = {}
    for dev, ops in per_dev.items():
        out.devices[dev] = summarize_device(ops)
        for o in ops:
            if not is_container(o):
                key = label(o)
                totals[key] = totals.get(key, 0.0) + (o.end_ns - o.start_ns)
    n_dev = max(1, len(per_dev))
    out.top_ops = [[name, ns / n_dev / 1e9] for name, ns in
                   sorted(totals.items(), key=lambda kv: -kv[1])[:top]]
    if per_dev:
        dev0 = min(per_dev)
        busy = union((o.start_ns, o.end_ns) for o in per_dev[dev0])
        lo, hi = window if window else (busy[0][0], busy[-1][1])
        edges = [[lo, lo]] + busy + [[hi, hi]]
        gaps = []
        for (_, e0), (s1, _) in zip(edges, edges[1:]):
            s, e = max(e0, lo), min(s1, hi)
            if e > s:
                gaps.append((e - s, s, e))
        gaps.sort(reverse=True)
        hosts = host_events(profile)
        out.idle_gaps = [[_gap_label(s, e, hosts, tuple(skip_host)), g / 1e9]
                         for g, s, e in gaps[:top]]
    return out
