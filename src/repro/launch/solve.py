"""HP-CONCORD launcher: distributed sparse inverse covariance estimation
(the paper's own workload), through the ``repro.estimator`` facade.

  PYTHONPATH=src python -m repro.launch.solve --graph chain --p 200 \
      --n 400 --lam1 0.15 --backend auto

The cost model (paper Lemmas 3.1-3.5) picks the backend's Cov/Obs variant
and the (c_X, c_Omega) replication factors unless pinned.  ``--path`` runs
a lam1 path (the Section-5 model-selection sweep) and reports the BIC-best
point; ``--path-mode batched`` lowers the whole grid to one compiled
multi-problem program instead of sequential warm-started solves.
``--penalty scad:3.7`` (or ``mcp``, ``elastic_net``) swaps the prox
operator through the composable penalty API (``core.penalty``), and
``--path --adaptive`` runs the two-stage adaptive-lasso refit.

``--from-gram DIR`` solves straight from a ``launch.gram prep`` artifact
(S.npy + metadata) — the raw observations never enter this process:

  PYTHONPATH=src python -m repro.launch.solve --from-gram results/gram_sf \
      --lam1 0.15
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from ..core import distributed, graphs
from ..core.costmodel import Machine, ProblemShape, tune
from ..estimator import ConcordEstimator, SolverConfig


def _solve_from_gram(args):
    """Solve from a prepped Gram artifact: the raw data never loads."""
    from .gram import load_gram

    gram = load_gram(args.from_gram)
    config = SolverConfig(
        backend=args.backend, variant="cov",
        c_x=args.cx, c_omega=args.comega,
        tol=args.tol, max_iters=args.max_iters,
        sparse_matmul=args.sparse_matmul, sparse_block=args.sparse_block,
        sparse_threshold=args.sparse_threshold, penalty=args.penalty)
    est = ConcordEstimator(lam1=args.lam1, lam2=args.lam2, config=config)
    print(f"[gram] {gram.transform} Gram: n={gram.n} p={gram.p} "
          f"({gram.n_chunks} chunks, source dtype {gram.source_dtype})")
    if args.path:
        grid = [float(v) for v in args.path.split(",")]
        path = est.fit_path(s=jnp.asarray(gram.s), n_samples=gram.n,
                            lam1_grid=grid, mode=args.path_mode,
                            adaptive=args.adaptive)
        print(path.summary())
        chosen = path.best_bic()
        print(f"BIC-best lam1={chosen.lam1:g} (bic={chosen.bic:.1f})")
        rep = chosen
    else:
        rep = est.fit_gram(gram).report_
    print(rep.summary())
    est_omega = np.asarray(rep.omega)
    print(f"avg degree {graphs.avg_degree(est_omega):.2f}")
    return rep


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--graph", default="chain", choices=["chain", "random"])
    ap.add_argument("--p", type=int, default=200)
    ap.add_argument("--n", type=int, default=400)
    ap.add_argument("--lam1", type=float, default=0.15)
    ap.add_argument("--lam2", type=float, default=0.05)
    ap.add_argument("--penalty", default="l1", metavar="KIND",
                    help="penalty family (core.penalty string form): l1, "
                         "elastic_net, scad[:A], mcp[:GAMMA]; strength "
                         "comes from --lam1/--lam2")
    ap.add_argument("--adaptive", action="store_true",
                    help="two-stage adaptive-lasso refit of --path: "
                         "stage-1 l1 path, then each grid point refit "
                         "with weights 1/(|omega|+eps) built from its "
                         "own stage-1 estimate (pointwise)")
    ap.add_argument("--backend", default="auto",
                    choices=["auto", "reference", "distributed"])
    ap.add_argument("--variant", default="auto",
                    choices=["auto", "cov", "obs"])
    ap.add_argument("--cx", type=int, default=None)
    ap.add_argument("--comega", type=int, default=None)
    ap.add_argument("--tol", type=float, default=1e-5)
    ap.add_argument("--max-iters", type=int, default=300)
    ap.add_argument("--sparse-matmul", default="off",
                    choices=["off", "on", "auto"],
                    help="route Ω-side products through the block-sparse "
                         "matops layer once the observed iterate block "
                         "density crosses the threshold ('auto' takes the "
                         "threshold from the cost model crossover)")
    ap.add_argument("--sparse-block", type=int, default=128,
                    help="occupancy-mask tile edge for --sparse-matmul")
    ap.add_argument("--sparse-threshold", type=float, default=None,
                    help="block-density crossover override in (0, 1]")
    ap.add_argument("--path", default=None, metavar="LAM1S",
                    help="comma-separated lam1 grid: run a "
                         "regularization path instead of a single fit")
    ap.add_argument("--path-mode", default="sequential",
                    choices=["sequential", "batched"],
                    help="sequential: one warm-started solve per path "
                         "point; batched: the whole grid as ONE compiled "
                         "multi-problem program (core.batch)")
    ap.add_argument("--from-gram", default=None, metavar="DIR",
                    help="solve from a launch.gram prep artifact "
                         "(S.npy + gram_meta.json) instead of "
                         "synthesizing a problem")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if args.adaptive and not args.path:
        ap.error("--adaptive needs --path (it refits a lam1 grid)")

    from .cache import enable_compile_cache
    enable_compile_cache()

    if args.from_gram:
        return _solve_from_gram(args)

    prob = graphs.make_problem(args.graph, args.p, args.n, seed=args.seed)
    P = len(jax.devices())
    shape = ProblemShape(p=args.p, n=args.n,
                         d=distributed.estimate_density(
                             args.p, args.n, args.lam1))
    best = tune(shape, P, Machine())
    print(f"[costmodel] P={P}: best variant={best.variant} "
          f"c_x={best.c_x} c_omega={best.c_omega} "
          f"T_model={best.total:.3e}s "
          f"(compute {best.t_compute:.2e} / latency {best.t_latency:.2e} "
          f"/ bandwidth {best.t_bandwidth:.2e})")

    config = SolverConfig(
        backend=args.backend, variant=args.variant,
        c_x=args.cx, c_omega=args.comega,
        tol=args.tol, max_iters=args.max_iters,
        sparse_matmul=args.sparse_matmul, sparse_block=args.sparse_block,
        sparse_threshold=args.sparse_threshold, penalty=args.penalty)
    est = ConcordEstimator(lam1=args.lam1, lam2=args.lam2, config=config)
    x = jnp.asarray(prob.x)

    if args.path:
        grid = [float(v) for v in args.path.split(",")]
        path = est.fit_path(x, lam1_grid=grid, mode=args.path_mode,
                            adaptive=args.adaptive)
        print(path.summary())
        chosen = path.best_bic()
        print(f"BIC-best lam1={chosen.lam1:g} (bic={chosen.bic:.1f})")
        rep = chosen
    else:
        rep = est.fit(x).report_

    est_omega = np.asarray(rep.omega)
    ppv, fdr = graphs.ppv_fdr(est_omega, prob.omega0)
    print(rep.summary())
    print(f"PPV {ppv:.3f}  FDR {fdr:.3f}  "
          f"avg degree {graphs.avg_degree(est_omega):.2f}")
    return rep


if __name__ == "__main__":
    main()
