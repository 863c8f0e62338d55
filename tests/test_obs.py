"""Observability subsystem (``repro.obs``): tracer/metrics units, the
JSONL round-trip, the spans on the profiler's host plane at every obs
level, estimator integration (bit-exactness at every obs level, zero
extra compiles, an empty ring at ``obs="off"``), measured-vs-static comm
reconciliation on 1 and 4 devices, and the serve drain's
queue-wait/solve-wall latency split."""
import glob
import os
import subprocess
import sys

import numpy as np
import pytest

from conftest import SRC, run_with_devices

from repro.obs.metrics import Histogram, MetricsRegistry
from repro.obs.trace import ANNOTATION_PREFIX, MODES, Tracer, load_jsonl


@pytest.fixture(autouse=True)
def _reset_obs_globals():
    """The tracer and registry are process-global singletons; leave them
    off and empty so no test observes another's spans or counters."""
    yield
    tr = sys.modules.get("repro.obs.trace")
    if tr is not None and tr._TRACER is not None:
        tr._TRACER.set_mode("off")
        tr._TRACER.clear()
    mt = sys.modules.get("repro.obs.metrics")
    if mt is not None and mt._REGISTRY is not None:
        mt._REGISTRY.clear()


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------

def test_tracer_off_is_inert():
    t = Tracer()
    with t.span("solve", p=8) as s:
        s.note(iters=3)
    t.event("tick")
    assert len(t) == 0 and t.snapshot() == ()


def test_tracer_records_spans_events_and_notes():
    t = Tracer(mode="summary")
    with t.span("solve", cat="solver", p=8) as s:
        s.note(iters=3)
        t.event("checkpoint", step=1)
    spans = t.snapshot()
    assert [s.name for s in spans] == ["checkpoint", "solve"]
    ev, sp = spans
    assert ev.phase == "instant" and ev.duration == 0.0
    assert sp.phase == "span" and sp.duration >= 0.0
    assert sp.args == {"p": 8, "iters": 3} and ev.args == {"step": 1}


def test_tracer_summary_filters_trace_level_spans():
    t = Tracer(mode="summary")
    with t.span("outer"):
        with t.span("inner", level="trace"):
            pass
    assert [s.name for s in t.snapshot()] == ["outer"]
    t.clear()
    t.set_mode("trace")
    with t.span("outer"):
        with t.span("inner", level="trace"):
            pass
    assert sorted(s.name for s in t.snapshot()) == ["inner", "outer"]


def test_tracer_ring_capacity_bounds_memory():
    t = Tracer(mode="trace", capacity=4)
    for i in range(10):
        t.event("e", i=i)
    spans = t.snapshot()
    assert len(spans) == 4
    assert [s.args["i"] for s in spans] == [6, 7, 8, 9]


def test_tracer_scoped_restores_mode():
    t = Tracer(mode="off")
    with t.scoped("trace"):
        assert t.mode == "trace"
        t.event("inside")
    assert t.mode == "off" and len(t) == 1


def test_jsonl_roundtrip(tmp_path):
    t = Tracer(mode="trace")
    with t.span("solve", cat="solver", p=16) as s:
        s.note(converged=True)
    t.event("mark", cat="batch", level="trace", wave=2)
    path = tmp_path / "trace.jsonl"
    assert t.export_jsonl(path) == 2
    back = load_jsonl(path)
    for orig, rt in zip(t.snapshot(), back):
        assert orig.to_json() == rt.to_json()


# ---------------------------------------------------------------------------
# the profiler's timeline
# ---------------------------------------------------------------------------

def _host_spans(trace_dir) -> list:
    """(name, start_ns, end_ns) of the program's spans on the host plane
    of the profiler trace written under ``trace_dir``, in start order."""
    from jax.profiler import ProfileData

    path, = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(ANNOTATION_PREFIX):
                    out.append((ev.name[len(ANNOTATION_PREFIX):],
                                ev.start_ns, ev.start_ns + ev.duration_ns))
    return sorted(out, key=lambda e: e[1])


def _inside(inner, outer) -> bool:
    return outer[1] <= inner[1] and inner[2] <= outer[2]


@pytest.mark.parametrize("mode", MODES)
def test_spans_reach_the_profiler_at_every_mode(tmp_path, mode):
    """Every span is a ``concord.``-prefixed profiler annotation whatever
    the mode; the ring keeps only the levels the mode enables."""
    import jax

    t = Tracer(mode=mode)
    with jax.profiler.trace(str(tmp_path)):
        with t.span("outer"):
            with t.span("inner", level="trace") as s:
                s.note(k=1)
    spans = _host_spans(tmp_path)
    assert [n for n, _, _ in spans] == ["outer", "inner"]
    assert _inside(spans[1], spans[0])
    ring = {"off": [], "summary": ["outer"], "trace": ["inner", "outer"]}
    assert [s.name for s in t.snapshot()] == ring[mode]


def test_fit_puts_facade_steps_on_the_profiler_at_obs_off(tmp_path):
    """A default (``obs="off"``) fit from X shows the finite check, the
    dispatch/execute split and the report's device summary inside the
    whole-report span, no host penalty step for the l1 penalty, and
    records nothing in the ring."""
    import jax

    from repro.core import graphs
    from repro.estimator import ConcordEstimator, SolverConfig
    from repro.obs.trace import get_tracer

    prob = graphs.make_problem("chain", 24, 64, seed=0)
    est = ConcordEstimator(lam1=0.2, lam2=0.05, config=SolverConfig(
        backend="reference", variant="obs", tol=1e-5, max_iters=60))
    est.fit(prob.x)                     # compile outside the trace
    get_tracer().clear()
    with jax.profiler.trace(str(tmp_path)):
        est.fit(prob.x)
    spans = _host_spans(tmp_path)
    names = [n for n, _, _ in spans]
    for name in ("validate", "fit.reference", "dispatch", "execute",
                 "report", "report.summary"):
        assert names.count(name) == 1, (name, names)
    assert "report.penalty" not in names
    by_name = {sp[0]: sp for sp in spans}
    assert _inside(by_name["report.summary"], by_name["report"])
    assert _inside(by_name["dispatch"], by_name["fit.reference"])
    assert by_name["validate"][2] <= by_name["fit.reference"][1]
    assert by_name["fit.reference"][2] <= by_name["report"][1]
    assert len(get_tracer()) == 0


def test_batched_path_puts_each_chunk_boundary_on_the_profiler(tmp_path):
    """The compact engine's boundaries: one wait and one harvest after
    every segment, a repack wherever lanes stay live, and one report per
    grid point, all at ``obs="off"``."""
    import jax

    from repro.core import graphs
    from repro.estimator import ConcordEstimator, SolverConfig

    prob = graphs.make_problem("chain", 20, 48, seed=0)
    cfg = SolverConfig(backend="reference", variant="cov", tol=1e-4,
                       max_iters=60, batch_chunk=4)
    est = ConcordEstimator(penalty="l1", config=cfg)
    grid = [0.3, 0.2, 0.1]

    def path():
        return est.fit_path(s=prob.s, lam1_grid=grid, n_samples=48,
                            score_bic=False, mode="batched")

    path()                              # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        stats = path().batch_stats
    spans = _host_spans(tmp_path)
    names = [n for n, _, _ in spans]
    assert stats.segments > stats.waves
    for name in ("batch.segment", "batch.wait", "batch.harvest"):
        assert names.count(name) == stats.segments, name
    assert names.count("batch.repack") == stats.segments - stats.waves
    assert names.count("report") == len(grid)
    path_span, = (sp for sp in spans if sp[0] == "fit_path")
    assert all(_inside(sp, path_span) for sp in spans
               if sp[0].startswith(("batch.", "report")))
    waits = [sp for sp in spans if sp[0] == "batch.wait"]
    harvests = [sp for sp in spans if sp[0] == "batch.harvest"]
    assert all(w[2] <= h[1] for w, h in zip(waits, harvests))


def test_gram_chunks_reach_the_profiler(tmp_path):
    import jax

    from repro.data.gram import compute_gram

    rng = np.random.default_rng(0)
    chunks = [rng.standard_normal((16, 8)) for _ in range(3)]
    with jax.profiler.trace(str(tmp_path)):
        gram = compute_gram(iter(chunks))
    names = [n for n, _, _ in _host_spans(tmp_path)]
    assert names.count("gram.chunk") == gram.n_chunks == 3


@pytest.mark.parametrize("obs, expected", [
    ("summary", {"fit.reference", "report"}),
    ("trace", {"fit.reference", "dispatch", "execute", "validate",
               "report", "report.summary"}),
])
def test_obs_level_selects_the_ring_spans_of_a_fit(obs, expected):
    from repro.obs.trace import get_tracer

    tracer = get_tracer()
    tracer.clear()
    _fit(obs)
    assert {s.name for s in tracer.snapshot()} == expected
    assert all(s.duration >= 0 for s in tracer.snapshot())


@pytest.mark.parametrize("penalty, host_penalty", [
    ("l1", False), ("elastic_net", False), ("scad", True), ("mcp", True),
])
def test_report_penalty_span_only_for_host_penalties(tmp_path, penalty,
                                                     host_penalty):
    """The l1 kinds take their penalty from the device summary; only the
    other kinds run the host penalty, each inside the report."""
    import jax

    from repro.core import graphs
    from repro.estimator import ConcordEstimator, SolverConfig

    prob = graphs.make_problem("chain", 24, 64, seed=0)
    est = ConcordEstimator(penalty=penalty, lam1=0.2, lam2=0.05,
                           config=SolverConfig(backend="reference",
                                               variant="cov", tol=1e-5,
                                               max_iters=60))
    est.fit_cov(prob.s, n_samples=64)   # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        est.fit_cov(prob.s, n_samples=64)
    spans = _host_spans(tmp_path)
    names = [n for n, _, _ in spans]
    assert names.count("report.summary") == 1
    assert names.count("report.penalty") == int(host_penalty)
    report, = (sp for sp in spans if sp[0] == "report")
    assert all(_inside(sp, report) for sp in spans
               if sp[0].startswith("report."))


def test_reconcile_writes_its_spans_as_jsonl(tmp_path):
    out = tmp_path / "reconcile.jsonl"
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-m", "repro.obs.cli", "reconcile", "--devices",
         "2", "--p", "16", "--n", "24", "--max-iters", "3",
         "--variants", "cov", "--trace-out", str(out)],
        env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr[-4000:]
    names = {s.name for s in load_jsonl(out)}
    assert "reconcile.cov" in names


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

def test_counter_monotone_and_gauge():
    reg = MetricsRegistry()
    c = reg.counter("reqs", variant="cov")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)
    g = reg.gauge("depth")
    g.set(7)
    assert g.value == 7.0
    # get-or-create: same labels return the same object
    assert reg.counter("reqs", variant="cov") is c
    assert len(reg) == 2


def test_registry_type_clash_raises():
    reg = MetricsRegistry()
    reg.counter("x")
    with pytest.raises(TypeError):
        reg.gauge("x")


def test_histogram_quantiles_match_numpy_within_bucket_width():
    rng = np.random.default_rng(0)
    samples = rng.lognormal(mean=-6.0, sigma=1.5, size=5000)
    h = Histogram("lat")
    for v in samples:
        h.observe(v)
    assert h.total == len(samples)
    for q in (0.5, 0.95, 0.99):
        ref = float(np.quantile(samples, q))
        got = h.quantile(q)
        # interpolated inside an exponential bucket: within one bucket's
        # relative width of the exact sample quantile
        assert ref / h.growth <= got <= ref * h.growth, (q, got, ref)
    # extremes follow the same contract (a lone sample in the edge
    # bucket reads as the bucket midpoint, not the exact min/max)
    assert h.min <= h.quantile(0.0) <= h.min * h.growth
    assert h.max / h.growth <= h.quantile(1.0) <= h.max


def test_histogram_single_sample_and_empty():
    h = Histogram("lat")
    assert np.isnan(h.quantile(0.5))
    h.observe(0.125)
    for q in (0.0, 0.5, 1.0):
        assert h.quantile(q) == pytest.approx(0.125)


def test_prometheus_text_exposition():
    reg = MetricsRegistry()
    reg.counter("repro_solves_total", variant="cov").inc(3)
    reg.gauge("repro_queue_depth").set(2)
    hist = reg.histogram("repro_solve_wall_seconds", variant="cov")
    for v in (0.01, 0.02, 0.04):
        hist.observe(v)
    text = reg.to_prometheus()
    assert "# TYPE repro_solves_total counter" in text
    assert 'repro_solves_total{variant="cov"} 3' in text
    assert "# TYPE repro_queue_depth gauge" in text
    assert "# TYPE repro_solve_wall_seconds summary" in text
    assert 'quantile="0.5"' in text
    assert 'repro_solve_wall_seconds_count{variant="cov"} 3' in text
    snap = reg.snapshot()
    assert snap['repro_solves_total{variant="cov"}'] == 3
    assert snap['repro_solve_wall_seconds{variant="cov"}']["count"] == 3


# ---------------------------------------------------------------------------
# estimator integration
# ---------------------------------------------------------------------------

def _fit(obs, **cfg_overrides):
    from repro.core import graphs
    from repro.estimator import ConcordEstimator, SolverConfig

    prob = graphs.make_problem("chain", 24, 64, seed=0)
    cfg = dict(backend="reference", variant="cov", tol=1e-5, max_iters=60,
               obs=obs)
    cfg.update(cfg_overrides)
    est = ConcordEstimator(lam1=0.2, lam2=0.05, config=SolverConfig(**cfg))
    est.fit_cov(prob.s, n_samples=64)
    return est.report_


def test_obs_levels_are_bit_exact_and_carry_telemetry():
    base = _fit("off")
    assert base.telemetry is None
    for obs in ("summary", "trace"):
        rep = _fit(obs)
        np.testing.assert_array_equal(
            np.asarray(rep.omega), np.asarray(base.omega),
            err_msg=f"obs={obs!r} changed the estimate")
        assert rep.iters == base.iters and rep.ls_total == base.ls_total
        tele = rep.telemetry
        assert tele["obs"] == obs
        assert tele["dispatch_s"] >= 0 and tele["execute_s"] >= 0
        assert "_pending_cost" not in tele


def test_obs_config_validation():
    from repro.estimator import SolverConfig
    with pytest.raises(ValueError, match="obs"):
        SolverConfig(obs="verbose")


def test_obs_off_records_nothing_and_changes_no_program(recompile_guard):
    """``obs="off"`` records nothing in the ring, even with the process
    tracer left at "trace" by someone else, and runs the same compiled
    programs to the same estimates as an instrumented call."""
    from repro.core import batch, graphs, prox
    from repro.estimator import ConcordEstimator, SolverConfig
    from repro.obs.trace import get_tracer

    prob = graphs.make_problem("chain", 20, 48, seed=0)

    def path(obs):
        cfg = SolverConfig(backend="reference", variant="cov", tol=1e-4,
                           max_iters=60, batch_chunk=4, obs=obs)
        return ConcordEstimator(penalty="l1", config=cfg).fit_path(
            s=prob.s, lam1_grid=[0.3, 0.1], n_samples=48,
            score_bic=False, mode="batched")

    fit_traced, path_traced = _fit("trace"), path("trace")
    tracer = get_tracer()
    tracer.set_mode("trace")
    tracer.clear()
    with recompile_guard(solve=prox._solve_reference,
                         chunk=batch._path_chunk):
        fit_off, path_off = _fit("off"), path("off")
    assert len(tracer) == 0
    assert fit_off.telemetry is None
    np.testing.assert_array_equal(np.asarray(fit_off.omega),
                                  np.asarray(fit_traced.omega))
    for a, b in zip(path_off.reports, path_traced.reports):
        np.testing.assert_array_equal(np.asarray(a.omega),
                                      np.asarray(b.omega))


def test_obs_trace_adds_zero_compiles(recompile_guard):
    from repro.core import prox

    from repro.obs.trace import get_tracer

    base = _fit("trace")      # compile once
    _fit("off")
    tracer = get_tracer()
    with recompile_guard(solve=prox._solve_reference):
        _fit("trace")
        _fit("summary")
        n_recorded = len(tracer)
        off = _fit("off")
    assert len(tracer) == n_recorded > 0
    np.testing.assert_array_equal(np.asarray(off.omega),
                                  np.asarray(base.omega))


def test_fit_path_telemetry_and_span():
    from repro.core import graphs
    from repro.estimator import ConcordEstimator, SolverConfig
    from repro.obs.trace import get_tracer

    tracer = get_tracer()
    tracer.clear()
    prob = graphs.make_problem("chain", 20, 48, seed=0)
    cfg = SolverConfig(backend="reference", variant="cov", tol=1e-4,
                       max_iters=60, obs="summary")
    est = ConcordEstimator(penalty="l1", config=cfg)
    path = est.fit_path(s=prob.s, lam1_grid=[0.3, 0.2, 0.1],
                        n_samples=48, score_bic=False)
    tele = path.telemetry
    assert set(tele) >= {"lam1", "iters", "ls_total", "converged",
                         "objective", "wall_time_s"}
    assert all(len(v) == 3 for v in tele.values())
    assert np.all(tele["iters"] >= 1)
    names = [s.name for s in tracer.snapshot()]
    assert "fit_path" in names and "fit.reference" in names


# ---------------------------------------------------------------------------
# comm reconciliation: measured == static, exactly
# ---------------------------------------------------------------------------

def test_commwatch_reconciles_single_device_exactly():
    import jax
    import jax.numpy as jnp

    from repro.comm.grid import Grid1p5D
    from repro.core import distributed as dist
    from repro.obs.commwatch import CommWatch

    rng = np.random.default_rng(0)
    x = rng.standard_normal((40, 24))
    s = jnp.asarray(x.T @ x / 40)
    with CommWatch() as watch:
        res = dist.fit_cov(s, 0.3, grid=Grid1p5D(1, 1, 1), max_iters=5)
        jax.block_until_ready(res.omega)
    reports = watch.reconcile()
    assert reports, "no dispatches reconciled"
    for rep in reports:
        assert rep.ok, rep.render()
        assert rep.rows
        for r in rep.rows:
            assert r.measured_count == r.predicted_count > 0


@pytest.mark.slow
def test_reconcile_4dev_measured_equals_static_cov_and_obs():
    """THE acceptance assertion: on 4 devices, a traced solve's measured
    per-(prim, axes) collective invocation counts AND payload bytes equal
    the CA303 static comm_volume prediction exactly, for both the cov
    and obs variants, including a replicated (c_omega=2) grid."""
    run_with_devices("""
import numpy as np, jax, jax.numpy as jnp
from repro.comm.grid import Grid1p5D
from repro.core import distributed as dist
from repro.obs.commwatch import CommWatch

rng = np.random.default_rng(0)
x = rng.standard_normal((48, 32))
s = jnp.asarray(x.T @ x / 48)
for variant, cx, co in [("cov", 1, 1), ("cov", 2, 2),
                        ("obs", 1, 1), ("obs", 1, 2)]:
    g = Grid1p5D(4, cx, co)
    with CommWatch() as watch:
        if variant == "cov":
            res = dist.fit_cov(s, 0.3, grid=g, max_iters=6)
        else:
            res = dist.fit_obs(jnp.asarray(x), 0.3, grid=g, max_iters=6)
        jax.block_until_ready(res.omega)
    reports = watch.reconcile()
    assert reports, (variant, cx, co)
    for rep in reports:
        assert rep.ok, (variant, cx, co, rep.render())
        for r in rep.rows:
            assert r.measured_count == r.predicted_count > 0
        assert rep.measured_total == rep.predicted_total > 0
        print(variant, cx, co, "OK", int(rep.measured_total), "bytes")
print("OK")
""", n_devices=4)


@pytest.mark.slow
def test_estimator_trace_mode_reconciles_on_4_devices():
    """End-to-end through the estimator facade: ``obs="trace"`` on the
    distributed backend lands the reconciliation on the report's
    telemetry, every row exact."""
    run_with_devices("""
import numpy as np
from repro.core import graphs
from repro.estimator import ConcordEstimator, SolverConfig

prob = graphs.make_problem("chain", 24, 56, seed=0)
cfg = SolverConfig(backend="distributed", variant="cov", tol=1e-4,
                   max_iters=8, obs="trace")
est = ConcordEstimator(lam1=0.25, config=cfg)
est.fit_cov(prob.s, n_samples=56)
from fractions import Fraction
tele = est.report_.telemetry
assert tele is not None and tele["comm_reconcile_ok"] is True
reps = tele["comm_reconcile"]
assert reps and all(r["ok"] for r in reps)
assert all(Fraction(r["measured_bytes_total"]) > 0 for r in reps)
assert all(row["match"] for r in reps for row in r["rows"])
print("OK")
""", n_devices=4)


# ---------------------------------------------------------------------------
# serve drain latency split
# ---------------------------------------------------------------------------

def test_serve_obs_latency_split():
    import argparse

    from repro.launch.serve import serve_concord
    from repro.obs.metrics import get_registry

    get_registry().clear()
    stats = serve_concord(argparse.Namespace(
        requests=4, batch=2, p=16, n=40, lam2=0.05, tol=1e-4,
        max_iters=40, seed=0, obs="summary"))
    for arr in (stats.queue_wait_s, stats.solve_wall_s, stats.latency_s):
        assert arr is not None and arr.shape == (4,)
        assert np.all(arr >= 0)
    np.testing.assert_allclose(stats.latency_s,
                               stats.queue_wait_s + stats.solve_wall_s)
    # groups launch one after another (reordered by predicted length),
    # so at least one request waited behind another group's solve
    assert stats.queue_wait_s.max() > 0
    snap = get_registry().snapshot()
    assert snap["repro_serve_latency_seconds"]["count"] == 4
    assert snap["repro_serve_queue_wait_seconds"]["count"] == 4
    assert snap["repro_serve_solve_wall_seconds"]["count"] == 4
