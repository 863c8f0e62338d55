"""The control, kept at a size a CPU test run holds: the program with its
products in bfloat16 (one bf16 pass with float32 accumulation, the TPU's
``lax.Precision.DEFAULT``) in place of the float32 products the
configurations state must fail the comparison that the program passes.

The CPU computes every f32 product exactly whatever precision is asked
for, so the test puts the one-pass product itself in the place of
``repro.core.matops.dot``: each operand rounded to bfloat16, the product
accumulated in float32, as the TPU's matrix unit does.  On the chip,
``bench/control.py --precision default`` switches the program's own
``matops.PRECISION`` at each cell's own size instead.

    JAX_PLATFORMS=cpu python -m pytest -q tests/bench/test_control.py
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import pytest

import bench_paths  # noqa: F401  (puts bench/ and src/ on the path)
from harness import runner, spec

#: at p=512 and the configuration's n=100 the program's f64 KKT residual
#: read 2.2e-4..7.9e-4 on the CPU and the one-pass control's 7.2e-3..1.7e-2
#: (six base draws)
P = 512
LIMITS = {"kkt_rel": {"limit": 2e-3}}


def _one_pass(dot):
    def dot_bf16(a, b):
        return dot(a.astype(jnp.bfloat16).astype(jnp.float32),
                   b.astype(jnp.bfloat16).astype(jnp.float32))

    return dot_bf16


@pytest.fixture
def bf16_products(monkeypatch):
    from repro.core import matops
    monkeypatch.setattr(matops, "dot", _one_pass(matops.dot))
    jax.clear_caches()
    yield
    jax.clear_caches()


def _run(seed):
    cell = spec.Cell("cov_fit")
    cell.config = dict(cell.config, p=P)
    cell.limits = LIMITS
    return runner.run_cell(cell, seed, 0.1, False, time.perf_counter(),
                           on_chip=False)


@pytest.mark.parametrize("seed", [1, 2])
def test_program_is_correct(seed):
    res = _run(seed)
    assert res["correct"], res["check"]


@pytest.mark.parametrize("seed", [1, 2])
def test_control_in_bf16_is_not_correct(seed, bf16_products):
    res = _run(seed)
    assert not res["correct"], res["check"]
