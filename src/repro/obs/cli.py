"""``repro-obs`` — inspect, diff and gate observability artifacts.

Subcommands:

  * ``print <trace>``       pretty-print a JSONL span export
  * ``diff <a> <b>``        per-span-name count/duration deltas between
                            two span exports (regression triage)
  * ``reconcile``           run a distributed solve on N forced host
                            devices with the comm watcher armed and
                            check measured == static comm bytes
                            per (prim, axes); exit 1 on ANY divergence
                            (the CI gate), optionally exporting the
                            spans (JSONL) and the reconciliation JSON
                            as artifacts.

For a timeline of the spans beside the device ops, capture a profiler
trace (``jax.profiler.trace(dir)``) and open it in Perfetto or
TensorBoard: every span is there as ``concord.<name>``.

``reconcile`` must own the process: it sets
``XLA_FLAGS=--xla_force_host_platform_device_count`` before jax loads,
so run it as its own invocation (as CI does), not after something else
imported jax.
"""
from __future__ import annotations

import argparse
import json
import sys
from collections import defaultdict

from .trace import load_jsonl


def _by_name(spans) -> dict:
    agg: dict = defaultdict(lambda: {"count": 0, "duration": 0.0})
    for s in spans:
        agg[s.name]["count"] += 1
        agg[s.name]["duration"] += s.duration
    return dict(agg)


def cmd_print(args) -> int:
    spans = load_jsonl(args.trace)
    print(f"{args.trace}: {len(spans)} events")
    for s in sorted(spans, key=lambda s: s.t_start):
        extras = " ".join(f"{k}={v}" for k, v in sorted(s.args.items()))
        kind = "span " if s.phase == "span" else "event"
        print(f"  {s.t_start:12.6f}s {kind} {s.cat}/{s.name:<24} "
              f"{s.duration * 1e3:9.3f}ms  {extras}")
    agg = _by_name(spans)
    print("by name:")
    for name, row in sorted(agg.items(),
                            key=lambda kv: -kv[1]["duration"]):
        print(f"  {name:<28} x{row['count']:<5} "
              f"{row['duration'] * 1e3:10.3f}ms total")
    return 0


def cmd_diff(args) -> int:
    a, b = _by_name(load_jsonl(args.a)), _by_name(load_jsonl(args.b))
    print(f"{'span':<28} {'count A->B':>14} {'duration A->B (ms)':>26}")
    for name in sorted(set(a) | set(b)):
        ra = a.get(name, {"count": 0, "duration": 0.0})
        rb = b.get(name, {"count": 0, "duration": 0.0})
        print(f"{name:<28} {ra['count']:>6} -> {rb['count']:<5} "
              f"{ra['duration'] * 1e3:>11.3f} -> {rb['duration'] * 1e3:.3f}")
    return 0


def cmd_reconcile(args) -> int:
    import os
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={args.devices} "
        + os.environ.get("XLA_FLAGS", ""))
    import numpy as np

    import jax
    # virtual CPU devices only: the host-platform device count above does
    # not apply to an accelerator, which this check must never claim
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_enable_x64", True)

    from ..comm.grid import Grid1p5D
    from ..core import distributed as dist
    from .commwatch import CommWatch
    from .trace import get_tracer

    rng = np.random.default_rng(args.seed)
    x = rng.standard_normal((args.n, args.p))
    s = (x.T @ x) / args.n
    grid = Grid1p5D(args.devices, args.c_x, args.c_omega)
    tracer = get_tracer()
    tracer.set_mode("trace")
    reports = []
    for variant in args.variants.split(","):
        with CommWatch() as watch:
            with tracer.span(f"reconcile.{variant}", p=args.p,
                             n_devices=args.devices):
                if variant == "cov":
                    res = dist.fit_cov(s, args.lam1, grid=grid,
                                       max_iters=args.max_iters)
                else:
                    res = dist.fit_obs(x, args.lam1, grid=grid,
                                       max_iters=args.max_iters)
                jax.block_until_ready(res.omega)
        reports.extend(watch.reconcile())
    for rep in reports:
        print(rep.render())
        print()
    if args.trace_out:
        tracer.export_jsonl(args.trace_out)
        print(f"trace -> {args.trace_out}")
    if args.json_out:
        with open(args.json_out, "w", encoding="utf-8") as f:
            json.dump([r.to_json() for r in reports], f, indent=2)
        print(f"reconciliation -> {args.json_out}")
    if not all(r.ok for r in reports):
        print("FAIL: measured collective schedule diverges from the "
              "static comm_volume prediction", file=sys.stderr)
        return 1
    print("OK: measured == static for every (prim, axes)")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="repro-obs", description=__doc__)
    sub = ap.add_subparsers(dest="cmd", required=True)

    p = sub.add_parser("print", help="pretty-print a JSONL span export")
    p.add_argument("trace")
    p.set_defaults(fn=cmd_print)

    p = sub.add_parser("diff", help="diff two span exports by span name")
    p.add_argument("a")
    p.add_argument("b")
    p.set_defaults(fn=cmd_diff)

    p = sub.add_parser(
        "reconcile",
        help="distributed solve with the comm watcher armed; exit 1 on "
             "measured != static bytes (sets XLA_FLAGS, run standalone)")
    p.add_argument("--devices", type=int, default=4)
    p.add_argument("--c-x", type=int, default=1)
    p.add_argument("--c-omega", type=int, default=1)
    p.add_argument("--p", type=int, default=32)
    p.add_argument("--n", type=int, default=48)
    p.add_argument("--lam1", type=float, default=0.3)
    p.add_argument("--max-iters", type=int, default=6)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--variants", default="cov,obs",
                   help="comma list of cov/obs")
    p.add_argument("--trace-out", default=None,
                   help="write the recorded spans here (JSONL)")
    p.add_argument("--json-out", default=None,
                   help="write the reconciliation rows here")
    p.set_defaults(fn=cmd_reconcile)

    args = ap.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
