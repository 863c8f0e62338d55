"""The system under test, driven only through its public entry points.

A configuration file gives the estimator's penalty and its ``SolverConfig``
fields; a traffic mix gives the entry point (``fit`` or ``fit_path``) and
its arguments.  ``Program.call`` makes one call as a user would, waits for
every estimate it returns, and hands back the reports.
"""
from __future__ import annotations

import sys

from .spec import ROOT


def _import_estimator():
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    from repro.estimator import ConcordEstimator, SolverConfig
    return ConcordEstimator, SolverConfig


class Program:
    def __init__(self, config: dict, traffic: dict):
        est_cls, cfg_cls = _import_estimator()
        self._est_cls = est_cls
        self.solver = cfg_cls(**config["solver"])
        self.lam1 = float(config["lam1"])
        self.lam2 = float(config["lam2"])
        self.entry = traffic["entry"]
        if self.entry == "fit_path":
            self.grid = [float(v) for v in config["lam1_grid"]]
            self.mode = traffic["mode"]
            self.score_bic = bool(traffic["score_bic"])
        elif self.entry != "fit":
            raise ValueError(f"unknown entry point {self.entry!r}")

    def estimator(self):
        return self._est_cls(penalty="l1", lam1=self.lam1, lam2=self.lam2,
                             config=self.solver)

    def call(self, x):
        """One public call on data ``x``; returns (reports, batch stats)
        once every estimate is on the device."""
        import jax

        est = self.estimator()
        if self.entry == "fit":
            est.fit(x)
            reports, stats = [est.report_], None
        else:
            path = est.fit_path(x, lam1_grid=self.grid, mode=self.mode,
                                score_bic=self.score_bic)
            reports, stats = list(path.reports), path.batch_stats
        jax.block_until_ready([r.omega for r in reports])
        return reports, stats
