"""Paper Figure 3: the (c_X, c_Omega) replication heatmap.

Two layers of evidence:
  * measured — the distributed Obs solver on 16 virtual CPU devices across
    every feasible (c_X, c_Omega) pair (subprocess so the device count
    does not leak into other benchmarks; pinned to the CPU platform so it
    never contends for an accelerator the parent may hold);
  * modeled — Lemma 3.4/3.5 communication volumes at the paper's scale
    (512 processes, p=40k, n=100), reproducing the 5x-speedup structure.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

from repro.core.costmodel import EDISON, ProblemShape, obs_costs

from .common import emit

_CHILD = r"""
import json
import numpy as np, jax, jax.numpy as jnp
from repro.core import graphs
from repro.estimator import ConcordEstimator, SolverConfig
prob = graphs.make_problem("chain", p=64, n=32, seed=0)
x = jnp.asarray(prob.x)
out = []
P = 16
c = 1
cands = []
while c <= P:
    cands.append(c); c *= 2
for cx in cands:
    for co in cands:
        if cx * co > P or P % (cx * co):
            continue
        est = ConcordEstimator(
            lam1=0.2, lam2=0.05,
            config=SolverConfig(backend="distributed", variant="obs",
                                c_x=cx, c_omega=co, tol=1e-5, max_iters=60))
        est.fit(x)                       # warm-up (compile)
        rep = est.fit(x).report_         # measure
        out.append({"c_x": cx, "c_omega": co,
                    "t_s": round(rep.wall_time_s, 4),
                    "iters": rep.iters})
print("JSON" + json.dumps(out))
"""


def run():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "src")] +
        env.get("PYTHONPATH", "").split(os.pathsep))
    proc = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                          capture_output=True, text=True, timeout=560)
    rows = []
    for line in proc.stdout.splitlines():
        if line.startswith("JSON"):
            rows = json.loads(line[4:])
    if proc.returncode != 0 or not rows:
        raise RuntimeError(
            f"fig3 child failed (exit {proc.returncode}):\n"
            f"{proc.stderr[-2000:]}")
    emit("fig3_replication_measured", rows)

    # modeled heatmap at paper scale (512 procs, p=40k, n=100)
    shape = ProblemShape(p=40000, n=100, d=4.0, s=30, t=10.0)
    mrows = []
    P = 512
    c = 1
    cands = []
    while c <= P:
        cands.append(c)
        c *= 2
    for cx in cands:
        for co in cands:
            if cx * co > P:
                continue
            cb = obs_costs(shape, P, cx, co, EDISON)
            mrows.append({"c_x": cx, "c_omega": co,
                          "model_t_s": round(cb.total, 3),
                          "words": int(cb.words)})
    best = min(mrows, key=lambda r: r["model_t_s"])
    base = [r for r in mrows if r["c_x"] == 1 and r["c_omega"] == 1][0]
    print(f"# modeled replication speedup at paper scale: "
          f"{base['model_t_s'] / best['model_t_s']:.1f}x "
          f"(best c_x={best['c_x']}, c_omega={best['c_omega']})")
    emit("fig3_replication_model", mrows)
    return rows
