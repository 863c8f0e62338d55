"""End-to-end behaviour of the paper's system: full estimation pipeline
with cost-model-driven configuration, and the Section-5 clustering
pipeline on a synthetic 'cortex'."""
import numpy as np
import jax.numpy as jnp

from repro.core import clustering, distributed, graphs
from repro.core.costmodel import Machine, ProblemShape, tune
from repro.core.prox import fit_reference


def test_end_to_end_estimation_pipeline():
    """data -> cost model -> solver -> support metrics, single device."""
    prob = graphs.make_problem("chain", p=60, n=240, seed=11)
    shape = ProblemShape(p=60, n=240, d=3.0)
    best = tune(shape, 1, Machine())
    assert best.variant in ("cov", "obs")
    res = distributed.fit(x=jnp.asarray(prob.x), lam1=0.22, lam2=0.02,
                          tol=1e-6, max_iters=300)
    ppv, fdr = graphs.ppv_fdr(np.asarray(res.omega), prob.omega0)
    assert bool(res.converged)
    assert ppv > 0.8, ppv


def test_clustering_pipeline_beats_marginal_baseline():
    """Partial-correlation clusters >= marginal-correlation clusters on
    a region-structured problem (the Section 5 claim, miniaturized)."""
    side, region, n = 8, 4, 500
    p = side * side
    omega = np.eye(p, dtype=np.float32)
    nbrs = clustering.grid_neighbors(side, side)
    labels = np.zeros(p, dtype=np.int64)
    for idx in range(p):
        r, c = divmod(idx, side)
        labels[idx] = (r // region) * (side // region) + (c // region)
    for i in range(p):
        for j in nbrs[i]:
            if j > i and labels[i] == labels[j]:
                omega[i, j] = omega[j, i] = -0.28
    d = np.abs(omega).sum(1) - 1.0
    omega[np.diag_indices(p)] = d + 1.0
    x = graphs.sample_gaussian(omega, n, seed=3)
    s = jnp.asarray((x.T @ x) / n)

    r = fit_reference(s, 0.18, 0.05, tol=1e-5, max_iters=250)
    sup = graphs.support(np.asarray(r.omega), tol=1e-4)
    sup = sup | sup.T
    deg = clustering.degrees_from_support(sup)
    best = 0.0, 1
    for eps in (0.0, 0.5, 1.0):
        ph = clustering.persistence_watershed(deg.astype(float), nbrs,
                                              eps=eps)
        score = clustering.modified_jaccard(ph, labels)
        if score > best[0]:
            best = score, len(np.unique(ph))
    assert 0.0 < best[0] <= 1.0
    assert best[1] >= 2


def test_compile_cache_keeps_a_set_directory(monkeypatch, tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set the entry points change nothing:
    JAX reads the variable itself."""
    import jax

    from repro.launch.cache import enable_compile_cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_fixed_checkout_dir(monkeypatch):
    """Without the variable the cache goes to one fixed, git-ignored
    directory of the checkout (the same path on every run)."""
    import os

    import jax

    from repro.launch import cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        assert cache.enable_compile_cache() == cache.DEFAULT_DIR
        assert jax.config.jax_compilation_cache_dir == cache.DEFAULT_DIR
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert cache.DEFAULT_DIR == os.path.join(repo, ".jax_cache")
    with open(os.path.join(repo, ".gitignore"), encoding="utf-8") as f:
        assert ".jax_cache/" in f.read().split()
