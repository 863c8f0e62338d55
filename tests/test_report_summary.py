"""The report's device summary of an estimate (``omega_summary``) against
the host formulas it replaced: nnz/row and block density bit-identical,
the l1 objective to summation order, the other penalties still on the
host; every backend that reports through it; and a report that fetches
once and leaves Ω on the device."""
import contextlib
import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import run_with_devices

from repro.core.penalty import PenaltySpec, penalty_value_np
from repro.core.prox import ProxResult
from repro.estimator.backends import (NNZ_TOL, _report, observed_nnz_per_row,
                                      omega_summary)
from repro.estimator.config import SolverConfig


def host_report_numbers(omega, block, spec, g):
    """The plain host reference: the numpy formulas the report used to run
    on a host copy of Ω (nnz/row, block density, objective)."""
    om = np.asarray(omega)
    nz = np.abs(om) > NNZ_TOL
    nnz_per_row = max(1.0, float(nz.sum()) / om.shape[0])
    edges = np.arange(0, om.shape[0], block)
    occ = np.add.reduceat(np.add.reduceat(nz, edges, axis=0),
                          edges, axis=1) > 0
    return nnz_per_row, float(occ.mean()), g + penalty_value_np(spec, om)


@contextlib.contextmanager
def _x64_for(dtype):
    prev = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", np.dtype(dtype) == np.float64)
    try:
        yield
    finally:
        jax.config.update("jax_enable_x64", prev)


def _planted_omega(p, dtype, seed=0):
    """Symmetric sparse estimate with a positive diagonal, empty
    off-diagonal quadrants (so some tiles are unoccupied), and entries
    planted exactly at the threshold and one ulp either side of it."""
    rng = np.random.default_rng(seed)
    a = rng.standard_normal((p, p)).astype(dtype)
    a[rng.random((p, p)) < 0.95] = 0
    om = np.triu(a, 1)
    om = om + om.T
    om[:p // 2, p // 2:] = om[p // 2:, :p // 2] = 0
    np.fill_diagonal(om, 1.0 + rng.random(p))
    at = dtype(NNZ_TOL)
    om[0, 3] = om[3, 0] = at                                  # not counted
    om[1, 4] = om[4, 1] = -np.nextafter(at, dtype(1))         # counted
    om[p - 1, p - 3] = om[p - 3, p - 1] = np.nextafter(at, dtype(0))
    return om


def _result(omega, g):
    return ProxResult(omega=jnp.asarray(omega), iters=jnp.int32(7),
                      ls_total=jnp.int32(19), converged=jnp.bool_(True),
                      g_final=jnp.asarray(g, omega.dtype),
                      delta_final=jnp.asarray(0.0, omega.dtype),
                      stalled=jnp.bool_(False))


SHAPES = [(256, 128), (300, 128), (300, 64)]    # p % block == 0 and != 0
DTYPES = [np.float32, np.float64]


@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "f64"])
@pytest.mark.parametrize("p, block", SHAPES)
def test_omega_summary_matches_the_host_formulas(p, block, dtype):
    om = _planted_omega(p, dtype)
    with _x64_for(dtype):
        s = jax.device_get(omega_summary(jnp.asarray(om), block=block,
                                         tol=NNZ_TOL))
        d = observed_nnz_per_row(om)
    nz = np.abs(om) > NNZ_TOL
    edges = np.arange(0, p, block)
    occ = np.add.reduceat(np.add.reduceat(nz, edges, axis=0), edges,
                          axis=1) > 0
    assert s.nnz_rows.dtype == np.int32 and s.occupied.dtype == np.int32
    np.testing.assert_array_equal(s.nnz_rows, nz.sum(axis=1))
    assert int(s.occupied) == int(occ.sum()) < occ.size
    assert d == max(1.0, float(nz.sum()) / p)
    assert s.offdiag_l1_rows.dtype == dtype
    off = np.abs(om.astype(np.float64))
    np.fill_diagonal(off, 0.0)
    np.testing.assert_allclose(s.offdiag_l1_rows, off.sum(axis=1),
                               rtol=1e-6)


@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "f64"])
@pytest.mark.parametrize("p, block", SHAPES[:2])
@pytest.mark.parametrize("kind", ["l1", "elastic_net", "weighted_l1"])
def test_report_matches_the_host_report(kind, p, block, dtype):
    om = _planted_omega(p, dtype)
    w = np.abs(_planted_omega(p, np.float64, seed=1)) + 0.5
    np.fill_diagonal(w, 0.0)
    spec = {"l1": PenaltySpec.l1(0.3, 0.05),
            "elastic_net": PenaltySpec.elastic_net(0.3, 0.05),
            "weighted_l1": PenaltySpec.weighted_l1(0.3, w, 0.05)}[kind]
    g = 123.456
    with _x64_for(dtype):
        res = _result(om, g)
        rep = _report(res, lam1=0.3, lam2=0.05, wall=0.0,
                      backend="reference", variant="cov",
                      config=SolverConfig(sparse_block=block), penalty=spec)
    nnz_per_row, block_density, objective = host_report_numbers(
        om, block, spec, float(np.asarray(res.g_final)))
    assert rep.omega is res.omega
    assert (rep.nnz_per_row, rep.block_density) == (nnz_per_row,
                                                    block_density)
    if kind == "weighted_l1":           # the host fallback, unchanged
        assert rep.objective == objective
    else:
        assert rep.objective == pytest.approx(objective, rel=1e-6)
    assert (rep.iters, rep.ls_total, rep.converged, rep.stalled) == \
        (7, 19, True, False)


def _check_reports(reports, block, spec_of):
    for rep in reports:
        nnz_per_row, block_density, objective = host_report_numbers(
            rep.omega, block, spec_of(rep), rep.objective_smooth)
        assert (rep.nnz_per_row, rep.block_density) == (nnz_per_row,
                                                        block_density)
        assert rep.objective == pytest.approx(objective, rel=1e-6)


@pytest.mark.parametrize("engine", ["reference", "batched_path"])
def test_engines_report_the_host_numbers(engine):
    from repro.core import graphs
    from repro.estimator import ConcordEstimator

    block = 8                           # 30 % 8 != 0: padded edge tiles
    prob = graphs.make_problem("chain", 30, 80, seed=0)
    cfg = SolverConfig(backend="reference", variant="cov", tol=1e-5,
                       max_iters=100, sparse_block=block)
    est = ConcordEstimator(lam1=0.4, lam2=0.05, config=cfg)
    if engine == "reference":
        reports = [est.fit_cov(prob.s, n_samples=80).report_]
    else:
        reports = est.fit_path(s=prob.s, lam1_grid=[0.4, 0.2, 0.05],
                               n_samples=80, score_bic=False,
                               mode="batched").reports
    assert any(0 < r.block_density < 1 for r in reports)
    _check_reports(reports, block,
                   lambda r: PenaltySpec.l1(r.lam1, r.lam2))


def test_distributed_reports_the_host_numbers():
    """A sharded estimate from the distributed backend on four virtual devices
    reports the host numbers of its gathered copy."""
    out = run_with_devices("""
import numpy as np
import pytest
from repro.core import graphs
from repro.core.penalty import PenaltySpec, penalty_value_np
from repro.estimator import ConcordEstimator, SolverConfig
from repro.estimator.backends import NNZ_TOL
""" + inspect.getsource(host_report_numbers)
                           + inspect.getsource(_check_reports) + """
prob = graphs.make_problem("chain", 32, 64, seed=0)
for variant, c in (("cov", 2), ("obs", 1)):
    cfg = SolverConfig(backend="distributed", n_devices=4, variant=variant,
                       c_x=c, c_omega=c, tol=1e-5, max_iters=100,
                       sparse_block=8)
    rep = ConcordEstimator(lam1=0.2, lam2=0.05, config=cfg).fit(
        prob.x).report_
    assert rep.backend == "distributed" and rep.n_devices == 4
    _check_reports([rep], 8, lambda r: PenaltySpec.l1(r.lam1, r.lam2))
    print(variant, "ok")
""", n_devices=4)
    assert "cov ok" in out and "obs ok" in out


def test_report_fetches_once_and_leaves_omega_on_the_device():
    """Under the device-to-host transfer guard, the report reads the host
    only inside its one explicit fetch, and never a (p, p) array.  The
    CPU backend does not enforce the guard, so every host read of a
    ``jax.Array`` is recorded as well: with Ω sharded over four virtual
    devices, as the distributed backend hands it over, each one (``np.asarray``
    included) goes through ``ArrayImpl._value``."""
    out = run_with_devices("""
import jax
import jax.numpy as jnp
import numpy as np
from jax._src.array import ArrayImpl
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.comm.compat import make_mesh
from repro.core.penalty import PenaltySpec
from repro.core.prox import ProxResult
from repro.estimator.backends import NNZ_TOL, _report
from repro.estimator.config import SolverConfig
""" + inspect.getsource(_planted_omega) + inspect.getsource(_result) + """
p = 256
res = _result(_planted_omega(p, np.float32), 50.0)
rows = NamedSharding(make_mesh((4,), ("r",)), P("r", None))
res = res._replace(omega=jax.device_put(res.omega, rows))
kwargs = dict(lam1=0.3, lam2=0.05, wall=0.0, backend="distributed",
              variant="cov", config=SolverConfig(sparse_block=128),
              penalty=PenaltySpec.l1(0.3, 0.05))
_report(res, **kwargs)                  # compile the summary first

reads, fetches, explicit = [], [], [False]
value, device_get = ArrayImpl._value, jax.device_get

def recording_value(self):
    reads.append((tuple(self.shape), explicit[0]))
    return value.fget(self)

def recording_device_get(x):
    fetches.append(x)
    explicit[0] = True
    try:
        return device_get(x)
    finally:
        explicit[0] = False

ArrayImpl._value = property(recording_value)
jax.device_get = recording_device_get
with jax.transfer_guard_device_to_host("disallow"):
    rep = _report(res, **kwargs)
assert len(fetches) == 1
assert reads and all(inside for _, inside in reads), reads
assert (p, p) not in {shape for shape, _ in reads}, reads
assert rep.omega is res.omega
np.asarray(res.omega)                   # the probe does see a host copy
assert reads[-1] == ((p, p), False), reads
print("ok")
""", n_devices=4)
    assert out.strip().endswith("ok")
