"""Compile rehearsals for a TPU v5e that is described, not attached.

The kernels of the solve path, and the whole reference solve with them,
are lowered with ``interpret=False`` and compiled by the TPU compiler for
one chip of a ``v5e:2x2`` topology at the widths ``chip_smoke.py`` runs.
Interpret mode accepts layouts the chip's compiler refuses (block shapes
that are not (8, 128)-aligned); these tests catch that without a chip.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import prox
from repro.core.penalty import as_penalty
from repro.estimator import SolverConfig
from repro.estimator.backends import _matmul_policy
from repro.kernels import ops, pathstep, softthresh


@pytest.fixture(scope="module")
def topo():
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip cannot be read back from the cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    from jax.experimental import topologies
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:      # noqa: BLE001 - any failure means no topology
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def chip(topo):
    one = SingleDeviceSharding(topo.devices[0])

    def shape(*dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one)

    return shape


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("weighted", [False, True],
                         ids=["scalar", "weighted"])
def test_fused_prox_stats_compiles_for_v5e(chip, weighted):
    p = 8192
    args = [chip(p, p), chip(p, p), chip()]
    if weighted:
        args.append(chip(p, p))

    def run(z, mask, alpha, w=None):
        return softthresh.fused_prox_stats(z, mask, alpha, weights=w,
                                           block=(128, 128), interpret=False)

    assert "tpu_custom_call" in _compiled_text(run, *args)


def test_fused_path_step_compiles_for_v5e(chip):
    c, p = 4, 4096

    def run(om, w, tau, lam):
        return pathstep.fused_path_step(om, w, tau, lam, lam, interpret=False)

    text = _compiled_text(run, chip(c, p, p), chip(c, p, p), chip(c),
                          chip(c))
    assert "tpu_custom_call" in text


def test_reference_solve_with_kernels_compiles_for_v5e(chip):
    """The whole jitted solve as the estimator runs it on a chip with
    sparse_matmul="on" and use_pallas=True: the fused prox kernel is in
    the compiled program."""
    p = 8192
    cfg = SolverConfig(sparse_matmul="on", use_pallas=True)
    # the solve asks the running backend (here the CPU) whether to
    # interpret; steer it to the chip's answer
    ops.set_interpret(False)
    compiled = prox._solve_reference.lower(
        chip(p, p), as_penalty("l1", lam1=0.15, lam2=0.05), None,
        variant="cov", tol=1e-5, max_iters=500, max_ls=30,
        warm_start_tau=False, tau_schedule=None,
        sparse_matmul=_matmul_policy(cfg, p, p), use_pallas=True).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_unaligned_p_prox_uses_edge_tiles_for_v5e(chip):
    """p=1000 is not a multiple of 128: the prox kernel keeps 128-tiles
    and masks the partial edge tiles, so it still lowers natively."""
    p = 1000

    def run(z, mask, alpha):
        return softthresh.fused_prox_stats(z, mask, alpha, block=(128, 128),
                                           interpret=False)

    assert "tpu_custom_call" in _compiled_text(run, chip(p, p), chip(p, p),
                                               chip())


def test_unaligned_p_path_step_refuses_before_dispatch(chip):
    """The path-step kernel needs square tiles that divide p; with no
    128-multiple divisor it raises ValueError before any lowering."""
    c, p = 2, 1000

    def run(om, w, tau, lam):
        return pathstep.fused_path_step(om, w, tau, lam, lam, interpret=False)

    with pytest.raises(ValueError, match="multiple of 128"):
        jax.jit(run).lower(chip(c, p, p), chip(c, p, p), chip(c), chip(c))
