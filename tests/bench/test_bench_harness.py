"""CPU tests of the benchmark's own machinery: the data generator, the
trace reduction, the plain reference, and that every cell of
``BENCHMARK.json`` resolves to its files and keeps to the naming rules.

    JAX_PLATFORMS=cpu python -m pytest -q tests/bench
"""
from __future__ import annotations

import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

import bench_paths  # noqa: F401  (puts bench/ and src/ on the path)
from harness import data, spec, xtrace

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCH = spec.benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]


# -- data ---------------------------------------------------------------------

@pytest.mark.parametrize("p", [2, 17, 300])
def test_chain_omega_is_the_programs(p):
    from repro.core import graphs
    np.testing.assert_array_equal(data.chain_omega(p, 0.4),
                                  graphs.chain_omega(p, dtype=np.float64))


@pytest.mark.parametrize("p,n,seed", [(17, 9, 0), (300, 50, 7)])
def test_chain_samples_match_the_dense_solve(p, n, seed):
    """Same normal draws as graphs.sample_gaussian, same X to f32 rounding."""
    import jax.numpy as jnp

    from repro.core import graphs
    want = graphs.sample_gaussian(graphs.chain_omega(p, dtype=np.float64), n,
                                  seed=seed)
    z = np.random.default_rng(seed).standard_normal((n, p))
    got = np.asarray(data.chain_samples(jnp.asarray(z, jnp.float32), 0.4))
    assert got.dtype == np.float32
    np.testing.assert_allclose(got, want, rtol=0, atol=4e-6 * np.abs(want).max())


def test_chain_cholesky_factors_omega():
    p = 40
    d, s = data.chain_cholesky(p, 0.4)
    low = np.diag(d) + np.diag(s, -1)
    np.testing.assert_allclose(low @ low.T, data.chain_omega(p, 0.4),
                               atol=1e-14)


def test_same_seed_same_data_for_large_seeds():
    big = 2 ** 33 + 5
    a = np.asarray(data.make_x(0, big, 8, 32, 0.4))
    b = np.asarray(data.make_x(0, big, 8, 32, 0.4))
    c = np.asarray(data.make_x(0, big + 2 ** 32, 8, 32, 0.4))
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)
    # a seed only flips whole columns of the base draw
    np.testing.assert_array_equal(np.abs(a), np.abs(c))
    flips = np.sign(a[0]) * np.sign(c[0])
    np.testing.assert_array_equal(a * flips, c)


def test_seeds_pose_the_same_work():
    """Two seeds give the same solver work and estimates equal up to the
    sign flips of their variables."""
    from repro.estimator import ConcordEstimator, SolverConfig
    cfg = SolverConfig(backend="reference", variant="cov")
    reps = []
    for seed in (1, 2 ** 40 + 3):
        x = data.make_x(0, seed, 96, 48, 0.4)
        est = ConcordEstimator(penalty="l1", lam1=0.15, lam2=0.05, config=cfg)
        reps.append((np.asarray(x), est.fit(x).report_))
    (xa, ra), (xb, rb) = reps
    d = np.sign(xa[0]) * np.sign(xb[0])
    assert (ra.iters, ra.ls_total) == (rb.iters, rb.ls_total)
    np.testing.assert_array_equal(np.asarray(ra.omega) * np.outer(d, d),
                                  np.asarray(rb.omega))


# -- trace reduction --------------------------------------------------------

def _xspace(events, host=()):
    """A text-proto XSpace: ``events`` are (device, HLO text, start_ns,
    dur_ns) on the XLA Ops line; ``host`` (name, start_ns, dur_ns)."""
    names = sorted({e[1] for e in events} | {h[0] for h in host})
    ids = {n: i + 1 for i, n in enumerate(names)}
    meta = "".join(f'  event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}\n'
                   for n, i in ids.items())
    planes = []
    for dev in sorted({e[0] for e in events}):
        evs = "".join(
            f"    events {{ metadata_id: {ids[n]} offset_ps: {s * 1000} "
            f"duration_ps: {d * 1000} }}\n"
            for dv, n, s, d in events if dv == dev)
        planes.append(f'planes {{\n  id: {dev + 2}\n  name: "/device:TPU:{dev}"\n'
                      f'  lines {{\n    id: 1\n    name: "XLA Ops"\n'
                      f"    timestamp_ns: 0\n{evs}  }}\n{meta}}}\n")
    hevs = "".join(f"    events {{ metadata_id: {ids[n]} offset_ps: {s * 1000} "
                   f"duration_ps: {d * 1000} }}\n" for n, s, d in host)
    planes.append(f'planes {{\n  id: 1\n  name: "/host:CPU"\n  lines {{\n    id: 1\n'
                  f'    name: "python"\n    timestamp_ns: 0\n{hevs}  }}\n{meta}}}\n')
    from jax.profiler import ProfileData
    return ProfileData.from_text_proto("".join(planes))


def test_trace_reduction_counts_overlap_once_and_finds_the_gap():
    # device 0: a matmul fusion [0, 100) overlapping a loop fusion [50, 150),
    # a gap [150, 400) while the host runs "report", then an all-reduce
    # [400, 450); device 1: one op [0, 300)
    mm = "%fusion.1 = f32[8,8]{1,0} fusion(f32[8,8]{1,0:T(8,128)} %a), kind=kOutput"
    loop = "%fusion.2 = f32[8,8]{1,0} fusion(f32[8,8]{1,0} %b), kind=kLoop"
    ar = "%all-reduce.3 = f32[8]{0} all-reduce(f32[8]{0} %c), replica_groups={}"
    prof = _xspace(
        [(0, mm, 0, 100), (0, loop, 50, 100), (0, ar, 400, 50),
         (1, mm, 0, 300)],
        host=[("bench.window", 0, 500), ("$backends.py:1 fit", 150, 260),
              ("$backends.py:2 _report", 150, 250), ("$numpy:3 f", 170, 10)])
    red = xtrace.reduce(prof, window=(0, 500), skip_host=("bench.",))
    d0, d1 = red.devices[0], red.devices[1]
    assert d0.busy_ns == 200          # 150 of fusions counted once + 50
    assert d0.matmul_ns == 100
    assert d0.collective_ns == 50
    assert d1.busy_ns == 300 and d1.collective_ns == 0
    assert red.mean("busy_ns") == 250
    assert red.mean("collective_ns") == 25
    assert red.idle_gaps[0][0] == "$backends.py:2 _report"
    assert red.idle_gaps[0][1] == pytest.approx(250e-9)
    assert red.idle_gaps[1][1] == pytest.approx(50e-9)   # [450, 500)
    top = dict(red.top_ops)
    assert top["fusion.1 (fusion:kOutput)"] == pytest.approx(200e-9)  # (100+300)/2


def test_trace_reduction_of_an_empty_trace_reads_nothing():
    from harness import readers
    from harness.runner import CallRecord, RunRecord
    prof = _xspace([], host=[("bench.window", 0, 10)])
    red = xtrace.reduce(prof)
    rec = RunRecord(cell="c", setup_s=1.0, window_s=1.0,
                    calls=[CallRecord(1.0, 0.5, 3, None)], trace=red)
    assert readers.idle_share(rec) is None
    assert readers.matmul_seconds(rec) is None
    assert readers.segments(rec) is None


# -- the plain reference ------------------------------------------------------

def _dense_kkt(omega, x, lam1, lam2):
    om = np.asarray(omega, np.float64)
    x = np.asarray(x, np.float64)
    s = x.T @ x / x.shape[0]
    w = om @ s
    g = 0.5 * (w + w.T) + lam2 * om
    i = np.diag_indices_from(g)
    g[i] -= 1.0 / om[i]
    z = om - g
    prox = np.sign(z) * np.maximum(np.abs(z) - lam1, 0.0)
    prox[i] = z[i]
    gap = om - prox
    return np.linalg.norm(gap) / max(1.0, np.linalg.norm(om)), np.abs(gap).max()


@pytest.mark.parametrize("explicit_s", [True, False])
def test_reference_matches_the_dense_formula(monkeypatch, explicit_s):
    ref = spec.load_module(spec.BENCH / "references" / "concord.py", "ref_t")
    monkeypatch.setattr(ref, "EXPLICIT_S_MAX_P", 10 ** 6 if explicit_s else 1)
    monkeypatch.setattr(ref, "BLOCK_ROWS", 7)
    rng = np.random.default_rng(0)
    p, n = 30, 20
    x = rng.standard_normal((n, p)).astype(np.float32)
    om = data.chain_omega(p, 0.3).astype(np.float32)
    om[3, 9] = om[9, 3] = 0.05
    got = ref.kkt_residual(om, ref.GradientOperator(x), 0.1, 0.05)
    rel, mx = _dense_kkt(om, x, 0.1, 0.05)
    assert got["kkt_rel"] == pytest.approx(rel, rel=1e-12)
    assert got["kkt_max"] == pytest.approx(mx, rel=1e-12)


def test_reference_refuses_a_nonpositive_diagonal():
    ref = spec.load_module(spec.BENCH / "references" / "concord.py", "ref_t2")
    om = np.eye(4, dtype=np.float32)
    om[2, 2] = 0.0
    x = np.ones((3, 4), np.float32)
    assert ref.kkt_residual(om, ref.GradientOperator(x), 0.1, 0.0)[
        "kkt_rel"] == float("inf")


# -- the benchmark's files ----------------------------------------------------

@pytest.mark.parametrize("name", CELLS)
def test_every_cell_resolves_to_its_files(name):
    cell = spec.Cell(name, BENCH)
    assert cell.config["name"] == cell.workload["config"]
    assert {"p", "n", "lam1", "lam2", "solver", "graph", "reference",
            "reduced", "assumed", "source"} <= set(cell.config)
    assert cell.traffic["entry"] in ("fit", "fit_path")
    assert callable(cell.reference().kkt_residual)
    assert cell.limits and all("limit" in v for v in cell.limits.values())
    e2e = cell.end_to_end()
    assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2
    assert cell.per_layer()
    for m in e2e + cell.per_layer():
        assert callable(spec.reader(m["name"]))


def test_config_files_list_the_keys_benchmark_json_reduces():
    for c in BENCH["configs"]:
        cfg = spec.load_json(spec.ROOT / c["file"])
        assert set(c["reduced"]) == set(cfg["reduced"]), c["name"]
        for key in c["reduced"]:
            assert key in cfg, (c["name"], key)


def test_names_and_units_keep_to_the_rules():
    names = []
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in BENCH[section]:
            assert NAME.match(e["name"]), e["name"]
            names.append(e["name"])
            if "unit" in e:
                assert UNIT.match(e["unit"]), e["unit"]
                assert e["better"] in ("lower", "higher")
                assert e["source"] in ("device_trace", "program_span",
                                       "program_counter", "host_clock")
    for w in BENCH["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
    for c in BENCH["configs"]:
        assert all(NAME.match(k) for k in c["reduced"])
    metric_names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(set(metric_names)) == len(metric_names)
    assert len({w["name"] for w in BENCH["workloads"]}) == len(BENCH["workloads"])


def test_benchmark_json_has_exactly_the_contract_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= 1
    assert os.path.getsize(spec.ROOT / "BENCHMARK.json") <= 64 * 1024


def test_run_exits_nonzero_and_prints_no_result_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "1", "--seconds", "1", "--trace", "0"],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "TPU" in proc.stderr


@pytest.mark.parametrize("name", CELLS)
def test_result_line_keys(name):
    """A whole run on the CPU at a tiny size: the result line carries the
    contract's keys and the cell's end-to-end metrics, the compared
    numbers come last, and JSON holds it."""
    import time

    from harness import runner
    cell = spec.Cell(name, BENCH)
    cell.config = dict(cell.config, p=32, n=64)
    res = runner.run_cell(cell, 3, 0.2, False, time.perf_counter(),
                          on_chip=False)
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "check"
    assert set(res["metrics"]) == {m["name"] for m in cell.end_to_end()}
    assert res["attempted"] >= 1
    json.dumps(res)
