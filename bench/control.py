"""Readings of a cell's compared numbers, for setting its limits.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 [--draws 0,1,2] --precision highest|default

For each base draw and seed, in one process, this generates the cell's
data, makes the cell's public call as the window does (the first call
compiles, later ones reuse the program), and compares the answer with the
plain reference exactly as a run does.  ``--draws`` replaces the traffic
mix's ``data_seed`` by independent base draws.  ``--precision highest`` is
the program as it ships and gives the lower readings; ``default`` (one
bf16 pass with float32 accumulation) is the control: the program's own
precision switch, ``repro.core.matops.PRECISION``, set below the float32
products the configuration states.  One JSON line per reading.  Needs
the chip.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np  # noqa: E402

from harness import data as bench_data  # noqa: E402
from harness import runner  # noqa: E402
from harness.program import Program  # noqa: E402
from harness.spec import Cell  # noqa: E402

PRECISIONS = ("highest", "default")


def set_precision(name: str) -> None:
    from jax import lax

    from repro.core import matops
    matops.PRECISION = {"highest": lax.Precision.HIGHEST,
                        "default": lax.Precision.DEFAULT}[name]


def readings(cell: Cell, seeds, precision: str, draws=None, *,
             on_chip=True):
    """Yield one dict of compared numbers per base draw and seed."""
    if on_chip:
        runner.require_chips(cell.chips)
        runner.enable_cache()
    cfg = cell.config
    program = Program(cfg, cell.traffic)
    set_precision(precision)
    pairs = [(d, s) for d in (draws or [cell.traffic["data_seed"]])
             for s in seeds]
    for draw, seed in pairs:
        t0 = time.perf_counter()
        x = bench_data.make_x(draw, seed, cfg["n"], cfg["p"],
                              cfg["graph"]["weight"])
        reports, _ = program.call(x)
        call_s = time.perf_counter() - t0
        kept = [(0, [(float(r.lam1), np.asarray(r.omega), float(r.objective))
                     for r in reports])]
        iters = [int(r.iters) for r in reports]
        trials = [int(r.ls_total) for r in reports]
        del reports
        x_host = np.asarray(x)
        del x
        gc.collect()
        t1 = time.perf_counter()
        check = runner.compare(cell, kept, x_host)
        yield {"workload": cell.name, "precision": precision, "draw": draw,
               "seed": seed,
               "correct": check["correct"],
               "numbers": check["values"],
               "iters": iters, "trials": trials, "call_s": call_s,
               "reference_s": time.perf_counter() - t1}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--draws", default=None,
                    help="comma-separated base draws (default: the mix's)")
    ap.add_argument("--precision", choices=PRECISIONS, default="highest")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    draws = args.draws and [int(d) for d in args.draws.split(",")]
    try:
        for line in readings(Cell(args.workload), seeds, args.precision,
                             draws):
            print(json.dumps(line), flush=True)
    except runner.NoChip as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
