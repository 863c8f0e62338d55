"""Paper Figure 4: scaling vs problem size and node count (+ BigQUIC-class
baseline comparison).

  * measured — strong scaling of the distributed Obs/Cov solvers across
    virtual-device counts (subprocess per device count);
  * baseline — our in-repo Gaussian-likelihood proximal baseline (glasso
    objective; BigQUIC stand-in) timed on the same problems;
  * modeled — cost-model projection to 256/1024 nodes at p up to 1.28M
    (the paper's headline 17-minute configuration).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

from repro.core.costmodel import EDISON, ProblemShape, tune

from .common import emit, timeit

_CHILD = r"""
import json, time
import numpy as np, jax, jax.numpy as jnp
from repro.core import graphs
from repro.core.distributed import fit_obs
from repro.comm.grid import Grid1p5D
P = %d
prob = graphs.make_problem("chain", p=96, n=48, seed=0)
g = Grid1p5D(P, 1, min(2, P))
r = fit_obs(jnp.asarray(prob.x), 0.2, 0.05, grid=g, tol=1e-5, max_iters=40)
jax.block_until_ready(r.omega)
t0 = time.perf_counter()
r = fit_obs(jnp.asarray(prob.x), 0.2, 0.05, grid=g, tol=1e-5, max_iters=40)
jax.block_until_ready(r.omega)
print("JSON" + json.dumps({"P": P, "t_s": round(time.perf_counter()-t0, 4),
                           "iters": int(r.iters)}))
"""


def _glasso_baseline(p=96, n=48):
    """BigQUIC-class baseline: l1-penalized GAUSSIAN likelihood by
    proximal gradient (same outer loop class, the paper's comparison
    target family)."""
    import jax
    import jax.numpy as jnp
    from repro.core import graphs
    from repro.core.objective import prox_l1_offdiag

    prob = graphs.make_problem("chain", p=p, n=n, seed=0)
    s = jnp.asarray(prob.s) + 0.1 * jnp.eye(p)

    @jax.jit
    def fit():
        def body(carry, _):
            omega, tau = carry
            grad = s - jnp.linalg.inv(omega)
            cand = prox_l1_offdiag(omega - tau * grad, tau * 0.2)
            # crude PD safeguard
            ok = jnp.all(jnp.linalg.eigvalsh(cand) > 1e-4)
            omega = jnp.where(ok, cand, omega)
            return (omega, tau), None
        init = (jnp.eye(p), jnp.asarray(0.1))
        (omega, _), _ = jax.lax.scan(body, init, None, length=40)
        return omega

    return timeit(fit, repeats=2)


def run():
    rows = []
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    for P in [1, 2, 4, 8, 16]:
        env = dict(os.environ)
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={P}"
        env["JAX_PLATFORMS"] = "cpu"      # virtual CPU devices, never a chip
        env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
        proc = subprocess.run([sys.executable, "-c", _CHILD % P], env=env,
                              capture_output=True, text=True, timeout=560)
        for line in proc.stdout.splitlines():
            if line.startswith("JSON"):
                rows.append(json.loads(line[4:]))
    emit("fig4_scaling_measured", rows)

    t_base, _ = _glasso_baseline()
    print(f"# glasso-class baseline (p=96): {t_base:.3f}s vs "
          f"hp-concord 1-dev {rows[0]['t_s'] if rows else '?'}s")

    # modeled projection at paper scale
    mrows = []
    for p, nodes in [(40000, 1), (40000, 16), (80000, 1024),
                     (320000, 256), (1280000, 1024)]:
        P = nodes * 2  # paper: 2 MPI ranks/node
        shape = ProblemShape(p=p, n=100, d=4.0, s=40, t=10.0)
        try:
            best = tune(shape, P, EDISON, variants=("obs",))
            mrows.append({"p": p, "nodes": nodes,
                          "model_t_s": round(best.total, 1),
                          "c_x": best.c_x, "c_omega": best.c_omega})
        except ValueError as e:
            mrows.append({"p": p, "nodes": nodes, "model_t_s": -1,
                          "c_x": 0, "c_omega": 0})
    emit("fig4_scaling_model", mrows)
    return rows
