#!/usr/bin/env python3
"""Bring-up smoke run of the HP-CONCORD estimator on a TPU.

Drives the estimator facade (``repro.estimator.ConcordEstimator``) at
chip-scale widths and checks what comes out:

    python3 chip_smoke.py              # one chip: the five phases below
    python3 chip_smoke.py --chips 4    # the 1.5D distributed backend on the
                                       # four chips of one host, and only it

One chip (``SolverConfig(n_devices=1)``, so a host that shows four chips
still runs the one-chip engine):

  cov      Cov fit, p=8192, n=16384, lam1=0.15, lam2=0.05, default knobs
           (the pure-XLA route); its f64 optimality (KKT) residual is
           recomputed on the host from (Omega, S) and bounded
  obs      Obs fit, p=8192, n=256 (the paper's n << p regime), same check
  kernel   the Cov fit again with sparse_matmul="on", use_pallas=True: the
           lowered solve must hold the fused prox kernel (a
           tpu_custom_call) and its estimate must agree with the cov phase
  batched  fit_path(mode="batched", use_pallas=True) at p=4096 over a
           4-point lam1 grid (the pathstep megakernel), against the
           sequential warm-started fit_path on the same grid
  f64_cov, f64_obs
           both variants at p=1024: f32 on the chip against an f64 solve of
           the same estimator on the host's CPU device, in this process

Four chips (``--chips 4``): Cov with c_x = c_omega = 1 (a 4-round ring)
and Obs with c_omega = 2, both at p=8192, each against the one-chip
reference solve of the same problem.  They fit once: every distributed
fit compiles anew, so a second call would time a second compile.

Every phase prints one JSON line: compile seconds (XLA backend compiles
during the first call), the second call's solve seconds (ended by
``block_until_ready``), the solver counters, the objective and the
device's ``peak_bytes_in_use`` (a process-wide high-water mark).  The data
is generated from ``--seed`` by ``graphs.make_problem("chain", ...)``.
A failed check raises and nothing catches it.  Without a TPU the script
exits non-zero before any phase.  The last line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

# the f64 reference solves run on the host's CPU device in this process
if os.environ.get("JAX_PLATFORMS") and \
        "cpu" not in os.environ["JAX_PLATFORMS"].split(","):
    os.environ["JAX_PLATFORMS"] += ",cpu"

import numpy as np  # noqa: E402

from repro.core import graphs  # noqa: E402
from repro.estimator import ConcordEstimator, SolverConfig  # noqa: E402
from repro.launch.cache import enable_compile_cache  # noqa: E402

LAM1, LAM2 = 0.15, 0.05
P, N_COV, N_OBS = 8192, 16384, 256
P_PATH, N_PATH = 4096, 8192
PATH_GRID = (0.3, 0.25, 0.2, 0.15)
P_REF, N_REF_COV, N_REF_OBS = 1024, 2048, 256

#: f32 on the chip against the f64 reference, and engine against engine
OBJ_RTOL = 1e-4
#: |entry| an edge must reach in one estimate to count against an exact
#: zero in the other (smaller entries sit at the f32 rounding level)
EDGE_TOL = 1e-3
#: relative f64 KKT residual ||Omega - prox(Omega - grad)|| / ||Omega||.
#: f32 solves stop at 1e-5..4e-4 on these problems; at p=1024 a v5e's
#: default f32 matmul precision (bf16 operands) leaves 6.7e-3 (Cov) and
#: 1.9e-2 (Obs), which is why ``matops.PRECISION`` is stated
KKT_MAX = 2e-3

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class CompileClock:
    """Sums the XLA backend compile seconds JAX reports."""

    def __init__(self, jax):
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == BACKEND_COMPILE:
            self.total += duration


def require_tpu(jax):
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX's first device is "
                 f"{dev.platform!r}); this smoke run needs a TPU")
    return dev


def emit(line: dict) -> None:
    print(json.dumps(line), flush=True)


def peak_bytes(dev) -> int:
    return int(dev.memory_stats()["peak_bytes_in_use"])


def config(**kw) -> SolverConfig:
    return SolverConfig(n_devices=1, **kw)


def estimator(cfg: SolverConfig) -> ConcordEstimator:
    return ConcordEstimator(lam1=LAM1, lam2=LAM2, config=cfg)


def twice(clock, run):
    """First call (compiles) and second call of ``run``; returns the
    second result and the first call's compile and wall seconds."""
    c0, t0 = clock.total, time.perf_counter()
    run()
    first_s = time.perf_counter() - t0
    return run(), clock.total - c0, first_s


def fit_line(phase, rep, compile_s, first_s, dev, **extra) -> dict:
    line = {"phase": phase, "variant": rep.variant, "backend": rep.backend,
            "p": int(rep.omega.shape[0]), "compile_s": compile_s,
            "first_call_s": first_s, "solve_s": rep.wall_time_s,
            "iters": rep.iters, "ls_total": rep.ls_total,
            "converged": rep.converged, "stalled": rep.stalled,
            "objective": rep.objective,
            "peak_bytes_in_use": peak_bytes(dev)}
    line.update(extra)
    return line


# -- checks ------------------------------------------------------------------

def kkt_residual(omega, s) -> float:
    """f64 optimality residual of a CONCORD estimate, from (Omega, S) on the
    host: the unit-step prox-gradient fixed-point gap, relative to Omega."""
    om = np.asarray(omega, np.float64)
    s = np.asarray(s, np.float64)
    w = om @ s
    g = 0.5 * (w + w.T) + LAM2 * om
    diag = np.diag_indices_from(g)
    g[diag] -= 1.0 / om[diag]
    z = om - g
    prox = np.sign(z) * np.maximum(np.abs(z) - LAM1, 0.0)
    prox[diag] = z[diag]
    return float(np.linalg.norm(om - prox) / max(1.0, np.linalg.norm(om)))


def edge_mismatches(a, b) -> int:
    """Off-diagonal entries that are an edge (|w| > EDGE_TOL) in one
    estimate and exactly zero in the other."""
    a = np.abs(np.asarray(a, np.float64))
    b = np.abs(np.asarray(b, np.float64))
    iu = np.triu_indices_from(a, 1)
    a, b = a[iu], b[iu]
    return int(np.sum(((a > EDGE_TOL) & (b == 0)) | ((b > EDGE_TOL) & (a == 0))))


def rel_gap(obj, ref) -> float:
    return abs(obj - ref) / abs(ref)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(f"chip_smoke check failed: {what}")


def check_agree(name, rep, ref) -> dict:
    """Objective gap and edge agreement of two estimates of one problem."""
    gap = rel_gap(rep.objective, ref.objective)
    mism = edge_mismatches(rep.omega, ref.omega)
    check(gap <= OBJ_RTOL, f"{name}: relative objective gap {gap} > "
          f"{OBJ_RTOL}")
    check(mism == 0, f"{name}: {mism} edges differ")
    return {"obj_gap": gap, "edge_mismatches": mism}


def check_solved(name, rep) -> None:
    check(rep.converged and not rep.stalled,
          f"{name}: converged={rep.converged} stalled={rep.stalled}")


# -- one chip ----------------------------------------------------------------

def phase_fit(name, clock, dev, x, s, cfg) -> object:
    rep, compile_s, first_s = twice(
        clock, lambda: estimator(cfg).fit(x).report_)
    check_solved(name, rep)
    kkt = kkt_residual(rep.omega, s)
    check(kkt <= KKT_MAX, f"{name}: f64 KKT residual {kkt} > {KKT_MAX}")
    emit(fit_line(name, rep, compile_s, first_s, dev, n=int(x.shape[0]),
                  kkt_f64=kkt))
    return rep


def lowered_solve_text(x, cfg) -> str:
    """StableHLO of the reference solve the facade runs for ``cfg`` on
    data ``x`` (lowered only: the same program the fit compiled)."""
    import jax.numpy as jnp

    from repro.core import matops, prox
    from repro.core.penalty import as_penalty
    from repro.estimator.backends import _matmul_policy
    xj = jnp.asarray(x)
    s = matops.dot(xj.T, xj) / x.shape[0]
    p = s.shape[0]
    return prox._solve_reference.lower(
        s, as_penalty("l1", lam1=LAM1, lam2=LAM2), None, variant="cov",
        tol=cfg.tol, max_iters=cfg.max_iters, max_ls=cfg.max_ls,
        warm_start_tau=cfg.warm_start_tau, tau_schedule=cfg.tau_schedule,
        sparse_matmul=_matmul_policy(cfg, p, p),
        use_pallas=cfg.use_pallas).as_text()


def phase_kernel(clock, dev, x, cov_rep) -> None:
    from repro.kernels import ops
    check(ops.interpret_default() is False,
          "kernels would run interpreted on the TPU")
    cfg = config(variant="cov", sparse_matmul="on", use_pallas=True)
    rep, compile_s, first_s = twice(
        clock, lambda: estimator(cfg).fit(x).report_)
    check_solved("kernel", rep)
    native = "tpu_custom_call" in lowered_solve_text(x, cfg)
    check(native, "kernel: the solve holds no tpu_custom_call")
    agree = check_agree("kernel vs cov", rep, cov_rep)
    emit(fit_line("kernel", rep, compile_s, first_s, dev, n=int(x.shape[0]),
                  tpu_custom_call=native,
                  block_density=rep.block_density, **agree))


def path_line(name, path, compile_s, first_s, dev, **extra) -> dict:
    reps = path.reports
    line = {"phase": name, "mode": path.mode, "p": int(reps[0].omega.shape[0]),
            "lam1": [r.lam1 for r in reps], "compile_s": compile_s,
            "first_call_s": first_s,
            "solve_s": sum(r.wall_time_s for r in reps),
            "iters": [r.iters for r in reps],
            "ls_total": [r.ls_total for r in reps],
            "converged": [r.converged for r in reps],
            "stalled": [r.stalled for r in reps],
            "objective": [r.objective for r in reps],
            "peak_bytes_in_use": peak_bytes(dev)}
    line.update(extra)
    return line


def phase_batched(clock, dev, x) -> None:
    def path(mode, **kw):
        return estimator(config(variant="cov", **kw)).fit_path(
            x, lam1_grid=PATH_GRID, mode=mode, score_bic=False)

    bat, compile_b, first_b = twice(
        clock, lambda: path("batched", use_pallas=True))
    seq, compile_s, first_s = twice(clock, lambda: path("sequential"))
    kernel = bat.batch_stats.kernel
    check(kernel == "pathstep",
          f"batched: flat steps ran {kernel!r}, not the pathstep kernel")
    for name, pth in (("batched", bat), ("sequential", seq)):
        for r in pth.reports:
            check_solved(f"{name} lam1={r.lam1}", r)
    gaps, mism = [], []
    for rb, rs in zip(bat.reports, seq.reports):
        agree = check_agree(f"batched vs sequential lam1={rb.lam1}", rb, rs)
        gaps.append(agree["obj_gap"])
        mism.append(agree["edge_mismatches"])
    emit(path_line("batched", bat, compile_b, first_b, dev, kernel=kernel,
                   obj_gap_vs_sequential=gaps, edge_mismatches=mism))
    emit(path_line("sequential", seq, compile_s, first_s, dev))


def phase_f64(jax, clock, dev, variant, n, seed) -> None:
    prob = graphs.make_problem("chain", P_REF, n, seed=seed)
    rep, compile_s, first_s = twice(
        clock, lambda: estimator(config(variant=variant)).fit(prob.x).report_)
    check_solved(f"f64_{variant} chip", rep)
    cpu = jax.devices("cpu")[0]
    with jax.enable_x64(True), jax.default_device(cpu):
        ref = estimator(config(variant=variant, dtype="float64")).fit(
            prob.x.astype(np.float64)).report_
    check_solved(f"f64_{variant} reference", ref)
    check(np.asarray(ref.omega).dtype == np.float64,
          "the reference solve did not run in f64")
    agree = check_agree(f"f64_{variant}: chip f32 vs CPU f64", rep, ref)
    x64 = prob.x.astype(np.float64)
    kkt = kkt_residual(rep.omega, x64.T @ x64 / n)
    check(kkt <= KKT_MAX, f"f64_{variant}: KKT residual {kkt} > {KKT_MAX}")
    emit(fit_line(f"f64_{variant}", rep, compile_s, first_s, dev, n=n,
                  ref_objective=ref.objective, ref_iters=ref.iters,
                  kkt_f64=kkt, **agree))


def one_chip(jax, clock, dev, seed) -> None:
    prob = graphs.make_problem("chain", P, N_COV, seed=seed)
    cov = phase_fit("cov", clock, dev, prob.x, prob.s, config(variant="cov"))
    phase_kernel(clock, dev, prob.x, cov)
    del prob
    prob = graphs.make_problem("chain", P, N_OBS, seed=seed)
    phase_fit("obs", clock, dev, prob.x, prob.s, config(variant="obs"))
    prob = graphs.make_problem("chain", P_PATH, N_PATH, seed=seed)
    phase_batched(clock, dev, prob.x)
    del prob
    phase_f64(jax, clock, dev, "cov", N_REF_COV, seed)
    phase_f64(jax, clock, dev, "obs", N_REF_OBS, seed)


# -- four chips --------------------------------------------------------------

def ring_device_ids(grid, variant) -> list:
    """Device ids of the solve's mesh in the order its ring visits them
    (x-major for Cov, omega-major for Obs; see ``comm.grid``)."""
    flat = grid.make_mesh().devices.reshape(-1)
    order = range(grid.n_devices) if variant == "cov" else \
        (grid.omajor_to_flat(f) for f in range(grid.n_devices))
    return [int(flat[f].id) for f in order]


def four_chips(jax, clock, seed) -> None:
    from repro.comm.grid import Grid1p5D
    devices = jax.devices()
    check(len(devices) == 4, f"--chips 4 needs 4 devices, JAX shows "
          f"{len(devices)}")
    for variant, n, c_x, c_omega in (("cov", N_COV, 1, 1),
                                     ("obs", N_OBS, 1, 2)):
        prob = graphs.make_problem("chain", P, n, seed=seed)
        cfg = SolverConfig(backend="distributed", variant=variant, c_x=c_x,
                           c_omega=c_omega, n_devices=4)
        c0, t0 = clock.total, time.perf_counter()
        rep = estimator(cfg).fit(prob.x).report_
        first_s, compile_s = time.perf_counter() - t0, clock.total - c0
        check_solved(f"4chip_{variant}", rep)
        spans = sorted(int(d.id) for d in rep.omega.sharding.device_set)
        check(len(spans) == 4,
              f"4chip_{variant}: the estimate lives on devices {spans}")
        grid = Grid1p5D(4, rep.c_x, rep.c_omega)
        ring = ring_device_ids(grid, variant)
        check(sorted(ring) == sorted(int(d.id) for d in devices),
              f"4chip_{variant}: ring {ring} does not span the host")
        ref = estimator(config(variant=variant, backend="reference")).fit(
            prob.x).report_
        check_solved(f"4chip_{variant} reference", ref)
        agree = check_agree(f"4chip_{variant} vs one chip", rep, ref)
        emit({"phase": f"4chip_{variant}", "p": P, "n": n,
              "grid": {"n_devices": rep.n_devices, "c_x": rep.c_x,
                       "c_omega": rep.c_omega, "rounds": grid.rounds},
              "ring_device_ids": ring, "omega_devices": spans,
              "compile_s": compile_s, "first_call_s": first_s,
              "solve_s": rep.wall_time_s, "iters": rep.iters,
              "ls_total": rep.ls_total, "converged": rep.converged,
              "stalled": rep.stalled, "objective": rep.objective,
              "ref_objective": ref.objective, "ref_iters": ref.iters,
              "peak_bytes_in_use": {int(d.id): peak_bytes(d)
                                    for d in devices}, **agree})
        del prob


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the 1.5D distributed phase on the "
                         "four chips of one host")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    dev = require_tpu(jax)
    enable_compile_cache()
    clock = CompileClock(jax)
    if args.chips == 4:
        four_chips(jax, clock, args.seed)
    else:
        one_chip(jax, clock, dev, args.seed)
    emit({"ok": True, "device": {"platform": dev.platform,
                                 "kind": dev.device_kind,
                                 "count": len(jax.devices())}})
    return 0


if __name__ == "__main__":
    sys.exit(main())
