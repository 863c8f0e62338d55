"""Each fault a cell can have, planted under a whole run of the harness on
the CPU at a small size, turns ``correct`` false; the sound program at the
same size stays correct.

    JAX_PLATFORMS=cpu python -m pytest -q tests/bench/test_faults.py

The faults: a solver whose step returns its state unchanged; half of the
data's rows left out, the covariance taken over the rest; an answer
altered where the facade produces it; and, for a fit on the distributed
backend (the 1.5D drivers, which no cell runs yet), every exchange
between chips in the 1.5D products left out (in a child process with
four virtual CPU devices).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import pytest

import bench_paths  # noqa: F401  (puts bench/ and src/ on the path)
from harness import runner, spec
from harness.program import Program

#: a limit for sound runs at these sizes (readings 1e-4..2e-3 on the CPU)
TEST_KKT_LIMIT = 5e-3
SIZES = {"cov_fit": (48, 96), "obs_fit": (48, 24), "cov_path": (48, 96)}
ONE_CHIP = ["cov_fit", "obs_fit", "cov_path"]


def _cell(name):
    cell = spec.Cell(name)
    p, n = SIZES[name]
    cell.config = dict(cell.config, p=p, n=n)
    cell.limits = dict(cell.limits, kkt_rel={"limit": TEST_KKT_LIMIT})
    return cell


def _run(cell, factory=Program, seed=5):
    return runner.run_cell(cell, seed, 0.3, False, time.perf_counter(),
                           program_factory=factory, on_chip=False)


class HalfBatch(Program):
    """Fits the first half of the rows only."""

    def call(self, x):
        return super().call(x[: x.shape[0] // 2])


class AlteredAnswer(Program):
    """Moves one off-diagonal pair of every returned estimate by 0.1."""

    def call(self, x):
        import dataclasses
        reports, stats = super().call(x)
        out = []
        for r in reports:
            om = r.omega.at[1, 2].add(0.1).at[2, 1].add(0.1)
            out.append(dataclasses.replace(r, omega=om))
        return out, stats


@pytest.fixture
def frozen_solver(monkeypatch):
    """The proximal-gradient step returns its state unchanged."""
    import jax

    from repro.core import batch, prox

    def frozen(omega0, data, ops, **_):
        z = jax.numpy.zeros((), omega0.dtype)
        return prox.ProxResult(omega0, z.astype("int32"), z.astype("int32"),
                               True, z, z, False, 1.0)

    monkeypatch.setattr(prox, "prox_gradient", frozen)
    monkeypatch.setattr(batch, "ls_trial", lambda ops, data, pen, omega, grad,
                        g_val, tau: (omega, None, None, g_val, True),
                        raising=False)
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.mark.parametrize("name", ONE_CHIP)
def test_sound_program_is_correct(name):
    res = _run(_cell(name))
    assert res["correct"], res["check"]


@pytest.mark.parametrize("name", ONE_CHIP)
def test_half_the_rows_left_out_is_not_correct(name):
    res = _run(_cell(name), HalfBatch)
    assert not res["correct"], res["check"]
    assert res["failed"] >= 1


@pytest.mark.parametrize("name", ONE_CHIP)
def test_an_altered_answer_is_not_correct(name):
    res = _run(_cell(name), AlteredAnswer)
    assert not res["correct"], res["check"]


@pytest.mark.parametrize("name", ["cov_fit", "obs_fit"])
def test_a_step_that_returns_its_state_is_not_correct(name, frozen_solver):
    res = _run(_cell(name))
    assert not res["correct"], res["check"]


_CHILD = r"""
import json, sys, time
sys.path[:0] = [{bench!r}, {src!r}]
from harness import runner, spec
from harness.program import Program
cell = spec.Cell("obs_fit")
cell.config = dict(cell.config, p={p}, n={n},
                   solver=dict(backend="distributed", variant="obs",
                               n_devices=4))
cell.limits = dict(cell.limits, kkt_rel={{"limit": {limit}}})
if {broken}:
    import jax
    from repro.comm import matmul1p5d as mm

    class NoExchange:
        # jax.lax with every exchange of the 1.5D products left out: a
        # chip keeps its own block where it would send or receive one

        def __getattr__(self, name):
            return getattr(jax.lax, name)

        @staticmethod
        def ppermute(x, axis_name, perm):
            return x

        @staticmethod
        def psum(x, axis_name):
            return x

        @staticmethod
        def all_gather(x, axis_name, **_):
            size = jax.lax.psum(1, axis_name)
            return jax.numpy.broadcast_to(x[None], (size,) + x.shape)

    mm.lax = NoExchange()
res = runner.run_cell(cell, 5, 0.3, False, time.perf_counter(),
                      on_chip=False)
print(json.dumps(res))
"""


@pytest.mark.parametrize("broken", [False, True])
def test_the_ring_exchange_left_out_is_not_correct(broken):
    p, n = SIZES["obs_fit"]
    code = _CHILD.format(bench=str(spec.BENCH), src=str(spec.ROOT / "src"),
                         p=p, n=n, limit=TEST_KKT_LIMIT, broken=broken)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    res = json.loads(proc.stdout.strip().splitlines()[-1])
    assert res["correct"] is (not broken), res["check"]
