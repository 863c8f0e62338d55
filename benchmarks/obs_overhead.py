"""Observability overhead gate (BENCH_obs_overhead.json).

Runs the SAME p=512 float64 lam1 path twice on the reference backend —
once at ``obs="off"`` and once at ``obs="summary"`` — and gates the
relative wall-time overhead of the instrumented run below 2%.  The obs
mode is host-side only (the tracer's ring and the report telemetry; the
profiler annotations run at every mode, "off" included); it is not
a static argument of any jitted program, so both runs reuse the same
compiled solver and the only cost the gate can see is the tracer's own
bookkeeping.  The two paths must also be BIT-EXACT: instrumentation
observes a solve, it never changes one.

Runs are interleaved off/summary per repeat and the gate compares the
best-of-N wall per mode (min filters scheduler noise — the same policy
as the path-batch benchmark), so slow host drift cannot land on one
side of the gate.

Emits results/BENCH_obs_overhead.csv and results/BENCH_obs_overhead.json
(top-level ``overhead_pct`` / ``gate_pct`` / ``passed`` — the CI obs job
uploads the JSON and fails the build when ``passed`` is false).

  PYTHONPATH=src python -m benchmarks.obs_overhead [--quick]

Default: 8-point path at p=512 (the acceptance-criteria shape);
``--quick`` shrinks to p=128 for smoke runs.
"""
from __future__ import annotations

import argparse
import time

import numpy as np

from .common import emit, write_bench

#: maximum tolerated wall overhead of obs="summary" vs obs="off" at the
#: acceptance shape (p=512: each path point solves for hundreds of ms,
#: so the tracer's fixed ~0.2ms/point bookkeeping is well under 2%)
GATE_PCT = 2.0

#: smoke-run gate (--quick, p=128): the same fixed per-point cost
#: against millisecond solves — a sanity bound, not the acceptance gate
GATE_QUICK_PCT = 25.0


def run(p: int = 512, n: int = 1024, points: int = 8, tol: float = 1e-6,
        max_iters: int = 400, repeats: int = 5,
        gate_pct: float = GATE_PCT):
    import jax
    jax.config.update("jax_enable_x64", True)

    from repro.core import graphs
    from repro.estimator import ConcordEstimator, SolverConfig

    prob = graphs.make_problem("chain", p, n, seed=0)
    grid = np.geomspace(0.4, 0.05, points)

    def run_path(obs: str):
        config = SolverConfig(backend="reference", variant="cov",
                              tol=tol, max_iters=max_iters, obs=obs)
        est = ConcordEstimator(penalty="l1", config=config)
        # cold points (no warm start): each solve runs its full cold
        # iteration count, so the timed region is seconds of solver work
        # against which the tracer's fixed per-point cost is measured —
        # a warm-started path is so fast that host noise swamps the gate
        res = est.fit_path(s=prob.s, lam1_grid=grid, n_samples=n,
                           warm_start=False, score_bic=False)
        jax.block_until_ready(res.reports[-1].omega)
        return res

    # warmup: compile the shared programs outside the timed region
    run_path("off")
    run_path("summary")

    walls = {"off": [], "summary": []}
    paths = {}
    for _ in range(repeats):
        for obs in ("off", "summary"):
            t0 = time.perf_counter()
            paths[obs] = run_path(obs)
            walls[obs].append(time.perf_counter() - t0)

    # instrumented solves are bit-exact vs the uninstrumented path
    for i in range(points):
        np.testing.assert_array_equal(
            np.asarray(paths["summary"].reports[i].omega),
            np.asarray(paths["off"].reports[i].omega),
            err_msg=f"obs='summary' changed the solve at path point {i}")

    t_off = float(min(walls["off"]))
    t_summary = float(min(walls["summary"]))
    overhead_pct = 100.0 * (t_summary - t_off) / t_off
    passed = overhead_pct < gate_pct

    rows = [{"obs": obs, "repeat": i, "wall_s": round(w, 4)}
            for obs in ("off", "summary")
            for i, w in enumerate(walls[obs])]
    emit("BENCH_obs_overhead", rows)

    summary = {
        "p": p, "n": n, "points": points, "dtype": "float64",
        "tol": tol, "max_iters": max_iters, "repeats": repeats,
        "backend": "reference",
        "wall_off_s": round(t_off, 4),
        "wall_summary_s": round(t_summary, 4),
        "wall_off_all_s": [round(w, 4) for w in walls["off"]],
        "wall_summary_all_s": [round(w, 4) for w in walls["summary"]],
        "overhead_pct": round(overhead_pct, 3),
        "gate_pct": gate_pct,
        "bitexact": True,
        "passed": passed,
    }
    path = write_bench("BENCH_obs_overhead", summary)
    print(f"# {points}-point f64 path at p={p}: obs=off {t_off:.2f}s, "
          f"obs=summary {t_summary:.2f}s -> overhead "
          f"{overhead_pct:+.2f}% (gate <{gate_pct:g}%) "
          f"{'OK' if passed else 'FAIL'} -> {path}")
    assert passed, (
        f"obs='summary' overhead {overhead_pct:.2f}% exceeds the "
        f"{gate_pct:g}% gate")
    return summary


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small shape for smoke runs (p=128, n=320)")
    ap.add_argument("--p", type=int, default=None)
    ap.add_argument("--n", type=int, default=None)
    ap.add_argument("--points", type=int, default=8)
    ap.add_argument("--repeats", type=int, default=5)
    args = ap.parse_args(argv)
    p = args.p or (128 if args.quick else 512)
    n = args.n or (320 if args.quick else 1024)
    gate = GATE_QUICK_PCT if args.quick else GATE_PCT
    return run(p=p, n=n, points=args.points, repeats=args.repeats,
               gate_pct=gate)


if __name__ == "__main__":
    main()
