"""Finding a cell's files by name.

``BENCHMARK.json`` names each cell's configuration and traffic mix.  The
configuration is the JSON file the benchmark lists for it, the traffic mix
is ``bench/traffic/<traffic>.json``, the correctness limits of a cell are
``bench/limits/<cell>.json``, a configuration's plain reference is
``bench/references/<reference>.py``, and every metric, end-to-end or per
layer, is read by ``bench/metrics/<metric>.py``.  Adding a cell, a mix or
a metric therefore adds files and entries and edits none.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def _by_name(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One workload of ``BENCHMARK.json`` with everything it names."""

    def __init__(self, name: str, bench: dict | None = None):
        self.bench = bench or benchmark()
        self.workload = _by_name(self.bench["workloads"], name, "workload")
        self.name = name
        self.chips = int(self.workload["chips"])
        cfg_entry = _by_name(self.bench["configs"], self.workload["config"],
                             "config")
        self.config = load_json(ROOT / cfg_entry["file"])
        self.traffic = load_json(
            BENCH / "traffic" / f"{self.workload['traffic']}.json")
        self.limits = load_json(BENCH / "limits" / f"{name}.json")

    def reference(self):
        ref = self.config["reference"]
        return load_module(BENCH / "references" / f"{ref}.py",
                           f"bench_reference_{ref}")

    def _lists(self, metric) -> bool:
        return self.name in metric.get("workloads", [self.name])

    def end_to_end(self) -> list:
        """The end-to-end metrics this cell reports."""
        return [m for m in self.bench["end_to_end"] if self._lists(m)]

    def per_layer(self) -> list:
        """The per-layer metrics this cell reports: those that list it, and
        those without a list whose end-to-end metric it reports."""
        e2e = {m["name"] for m in self.end_to_end()}
        out = []
        for m in self.bench["per_layer"]:
            if "workloads" in m:
                if self.name in m["workloads"]:
                    out.append(m)
            elif m["moves"] in e2e:
                out.append(m)
        return out


def reader(metric_name: str):
    """The ``read(record)`` function of ``bench/metrics/<metric>.py``."""
    mod = load_module(BENCH / "metrics" / f"{metric_name}.py",
                      "bench_metric_" + metric_name.replace(".", "_"))
    return mod.read
