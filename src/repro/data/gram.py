"""Out-of-core Gram accumulation: row-blocks in, a (p, p) f64 Gram out.

HP-CONCORD only ever needs the sufficient statistic S = XᵀX/n (of
suitably transformed data), so tera-scale n never has to sit in memory:

    acc = GramAccumulator(transform="standardize")
    for chunk in source:            # (m_i, p) row-blocks, any dtype
        acc.update(chunk)
    result = acc.finalize()         # GramResult: S, n, stream stats
    ConcordEstimator(...).fit_gram(result)

Mechanics:

  * every panel product runs BLOCKED through the matops dispatch
    (``core.matops.panel_gram``) and accumulates in float64 regardless of
    the chunk dtype — a bf16/f32 shard stream still yields an f64 Gram;
  * column mean/variance stream alongside in ONE pass (Welford, with the
    Chan merge for chunk-at-a-time and ``merge()``), so ``center`` and
    ``standardize`` are applied *algebraically* at finalize — no second
    sweep ever happens for moment transforms;
  * the ``rank`` (nonparanormal) transform is order-based and uses the
    bounded two-pass mode (:func:`rank_gram`): ceil(p/panel) sweeps of a
    re-iterable source with O(n·panel) resident memory, a (n·p·8)-byte
    on-disk scratch memmap, then one streaming Gram pass over the scratch;
  * :func:`distributed_gram` is the multi-host twin: each host reduces its
    own shards to a partial (ΣXᵀX, Σx, Σx², n) image, and ONE ``psum``
    through ``comm/compat.py`` combines them — communication is O(p²)
    once, independent of n (the Arroyo-Hou reduce-to-sufficient-statistics
    pattern).
"""
from __future__ import annotations

import os
import tempfile
from typing import NamedTuple, Sequence

import numpy as np

from ..core.matops import panel_gram
from ..obs.trace import get_tracer
from .shards import ChunkSource, as_source
from .transforms import StreamStats, Transform, get_transform

__all__ = [
    "GramAccumulator", "GramResult", "compute_gram", "distributed_gram",
    "rank_gram",
]

#: default column-panel edge for the blocked XᵀX products
DEFAULT_PANEL = 512

#: default resident-memory budget of the rank transform's column sweeps
RANK_BUDGET_BYTES = 256 * 1024 * 1024


class GramResult(NamedTuple):
    """A finalized streaming Gram: the solver-ready sufficient statistic
    plus the stream statistics it was derived from."""
    s: np.ndarray           # (p, p) float64 Gram of the TRANSFORMED data
    n: int                  # rows streamed
    p: int
    transform: str          # transform name that produced s
    mean: np.ndarray        # (p,) f64 column means of the RAW stream
    var: np.ndarray         # (p,) f64 population variances of the raw stream
    n_chunks: int           # chunks consumed
    source_dtype: str       # dtype of the incoming chunks

    def to_meta(self) -> dict:
        """JSON-able metadata (everything but the arrays) for sidecar
        files written by ``launch/gram.py prep``."""
        return {
            "n": int(self.n), "p": int(self.p),
            "transform": self.transform,
            "n_chunks": int(self.n_chunks),
            "source_dtype": self.source_dtype,
            "gram_dtype": "float64",
            "mean_absmax": float(np.max(np.abs(self.mean))) if self.p else 0.0,
            "diag_mean": float(np.mean(np.diag(self.s))) if self.p else 0.0,
        }


class GramAccumulator:
    """Chunked one-pass Gram accumulator (moment transforms).

    ``update(chunk)`` streams an (m, p) row-block; ``finalize()`` returns
    the :class:`GramResult` under ``transform``.  State is O(p²) float64:
    the raw second-moment sum, running mean and M2 (Welford).  Order of
    chunks changes the result only at the usual f64 summation-order level
    (well inside the 1e-10 agreement the tests pin).

    The ``rank`` transform cannot accumulate one-pass (scores depend on
    global order statistics) — construct via :func:`compute_gram` /
    :func:`rank_gram` instead; passing it here raises.
    """

    def __init__(self, p: int | None = None, *,
                 transform: str | Transform = "none",
                 panel: int = DEFAULT_PANEL):
        self.transform = get_transform(transform)
        if self.transform.two_pass:
            raise ValueError(
                f"transform {self.transform.name!r} needs the two-pass "
                f"mode: use compute_gram(..., transform="
                f"{self.transform.name!r}) or rank_gram")
        if panel < 1:
            raise ValueError(f"panel must be >= 1, got {panel}")
        self.panel = int(panel)
        self.p = int(p) if p is not None else None
        self.n = 0
        self.n_chunks = 0
        self.source_dtype: str | None = None
        self._xx = self._mean = self._m2 = None
        if self.p is not None:
            self._alloc(self.p)

    def _alloc(self, p: int) -> None:
        self.p = p
        self._xx = np.zeros((p, p), np.float64)
        self._mean = np.zeros(p, np.float64)
        self._m2 = np.zeros(p, np.float64)

    def update(self, chunk) -> "GramAccumulator":
        """Fold one (m, p) row-block into the stream moments."""
        arr = np.asarray(chunk)
        if arr.ndim != 2:
            raise ValueError(f"chunk must be 2-D (rows, p), got {arr.shape}")
        if arr.shape[0] == 0:
            return self
        if self._xx is None:
            self._alloc(arr.shape[1])
        elif arr.shape[1] != self.p:
            raise ValueError(
                f"chunk has {arr.shape[1]} columns, accumulator is p={self.p}")
        if not np.all(np.isfinite(arr)):
            raise ValueError(
                f"chunk {self.n_chunks} contains non-finite values; refusing "
                f"to fold NaN/Inf into the Gram")
        self.source_dtype = self.source_dtype or arr.dtype.name
        with get_tracer().span("gram.chunk", cat="data", level="trace",
                               chunk=self.n_chunks, rows=int(arr.shape[0]),
                               p=int(arr.shape[1])):
            a64 = np.ascontiguousarray(arr, np.float64)
            m = a64.shape[0]
            # blocked panel products through the matops dispatch, f64 always
            self._xx += np.asarray(panel_gram(a64, panel=self.panel))
            # Welford/Chan chunk merge of mean and M2
            cmean = a64.mean(axis=0)
            centered = a64 - cmean      # one chunk-sized temporary, reused
            cm2 = np.einsum("ij,ij->j", centered, centered)
            tot = self.n + m
            delta = cmean - self._mean
            self._mean += delta * (m / tot)
            self._m2 += cm2 + delta * delta * (self.n * m / tot)
            self.n = tot
            self.n_chunks += 1
        return self

    def merge(self, other: "GramAccumulator") -> "GramAccumulator":
        """Fold another accumulator's state in (pairwise Chan merge) —
        the host-side reduction used by :func:`distributed_gram`."""
        if other.n == 0:
            return self
        if self._xx is None:
            self._alloc(other.p)
        elif other.p != self.p:
            raise ValueError(f"cannot merge p={other.p} into p={self.p}")
        tot = self.n + other.n
        delta = other._mean - self._mean
        self._xx += other._xx
        self._mean += delta * (other.n / tot)
        self._m2 += other._m2 + delta * delta * (self.n * other.n / tot)
        self.n = tot
        self.n_chunks += other.n_chunks
        self.source_dtype = self.source_dtype or other.source_dtype
        return self

    def stats(self) -> StreamStats:
        if self.n == 0:
            raise ValueError("no rows accumulated")
        return StreamStats(n=self.n, mean=self._mean.copy(),
                           var=self._m2 / self.n, xx=self._xx)

    def finalize(self) -> GramResult:
        """Apply the transform algebraically and return the Gram."""
        st = self.stats()
        s = np.asarray(self.transform.finalize_gram(st), np.float64)
        s = 0.5 * (s + s.T)     # exact-symmetry insurance (BLAS panel
        #                         order could differ across the diagonal)
        return GramResult(
            s=s, n=st.n, p=self.p, transform=self.transform.name,
            mean=st.mean, var=st.var, n_chunks=self.n_chunks,
            source_dtype=self.source_dtype or "float64")


# ---------------------------------------------------------------------------
# two-pass rank / nonparanormal mode
# ---------------------------------------------------------------------------

def _count_rows(source: ChunkSource) -> int:
    if source.n_rows is not None:
        return int(source.n_rows)
    return sum(int(np.asarray(c).shape[0]) for c in source.chunks())


def rank_gram(data, *, panel: int = DEFAULT_PANEL,
              budget_bytes: int = RANK_BUDGET_BYTES,
              scratch_dir: str | None = None,
              chunk_rows: int | None = None) -> GramResult:
    """Bounded two-pass nonparanormal Gram (the ``rank`` transform).

    Memory contract (documented in the README): with w = the column-panel
    width fitted to ``budget_bytes`` (resident buffer is n·w f64 values),

      * pass 1: ceil(p / w) sweeps of the (re-iterable) source; sweep j
        loads only columns [jw, (j+1)w), rank-transforms each column, and
        writes the scores into an on-disk float64 scratch memmap — peak
        resident memory O(n·w), scratch disk n·p·8 bytes;
      * pass 2: one streaming :class:`GramAccumulator` pass over the
        scratch rows (O(p²) state), after which the scratch is deleted.

    One-shot iterators are rejected up front (``reiterable`` is required).
    """
    source = as_source(data, chunk_rows=chunk_rows)
    source.require_reiterable("the rank (nonparanormal) transform")
    from .transforms import rank_transform_column
    n = _count_rows(source)
    if n == 0:
        raise ValueError("empty source")
    first = next(iter(source.chunks()))
    p = np.asarray(first).shape[1]
    w = max(1, min(p, int(budget_bytes // max(n * 8, 1))))
    fd, scratch_path = tempfile.mkstemp(suffix=".rank.f64",
                                        dir=scratch_dir)
    os.close(fd)
    try:
        z = np.memmap(scratch_path, dtype=np.float64, mode="w+",
                      shape=(n, p))
        for lo in range(0, p, w):
            hi = min(lo + w, p)
            buf = np.empty((n, hi - lo), np.float64)
            row = 0
            for chunk in source.chunks():
                arr = np.asarray(chunk)
                if not np.all(np.isfinite(arr[:, lo:hi])):
                    raise ValueError(
                        "non-finite values in stream; refusing to rank")
                buf[row:row + arr.shape[0]] = arr[:, lo:hi]
                row += arr.shape[0]
            if row != n:
                raise ValueError(
                    f"re-iteration returned {row} rows, first sweep saw {n} "
                    f"(source is not stable across sweeps)")
            for j in range(hi - lo):
                buf[:, j] = rank_transform_column(buf[:, j])
            z[:, lo:hi] = buf
        z.flush()
        acc = GramAccumulator(p, transform="none")
        rows = chunk_rows or max(1, int(budget_bytes // max(p * 8, 1)))
        for lo in range(0, n, rows):
            acc.update(z[lo:lo + rows])
        res = acc.finalize()
    finally:
        try:
            del z
        except NameError:
            pass
        os.unlink(scratch_path)
    return res._replace(transform="rank",
                        source_dtype=np.asarray(first).dtype.name)


# ---------------------------------------------------------------------------
# front door + distributed twin
# ---------------------------------------------------------------------------

def compute_gram(data, *, transform: str | Transform = "none",
                 chunk_rows: int | None = None,
                 panel: int = DEFAULT_PANEL, **rank_kw) -> GramResult:
    """Stream any chunk-like input (array, iterator, shard paths, factory —
    see ``shards.as_source``) into a :class:`GramResult` under
    ``transform``.  Dispatches to the one-pass accumulator for moment
    transforms and to :func:`rank_gram` for order-based ones."""
    tf = get_transform(transform)
    if tf.two_pass:
        return rank_gram(data, panel=panel, chunk_rows=chunk_rows, **rank_kw)
    source = as_source(data, chunk_rows=chunk_rows)
    acc = GramAccumulator(source.p, transform=tf, panel=panel)
    for chunk in source.chunks():
        acc.update(chunk)
    return acc.finalize()


def _psum_moments(xx_l, s1_l, s2_l, n_l):
    """All-reduce the per-host raw-moment images over the 1-axis mesh.

    The one communication step of the streaming pipeline — routed through
    ``comm/compat.py`` like every collective outside the 1.5D layer."""
    from ..comm.compat import psum
    return (psum(xx_l, "hosts"), psum(s1_l, "hosts"),
            psum(s2_l, "hosts"), psum(n_l, "hosts"))


def distributed_gram(per_host_data: Sequence, *,
                     transform: str | Transform = "none",
                     chunk_rows: int | None = None,
                     panel: int = DEFAULT_PANEL) -> GramResult:
    """Multi-host streaming Gram: one chunk source per device, reduced
    with ONE ``psum`` through the ``comm/compat.py`` shims.

    Each host folds its own shards into a partial accumulator (no
    communication), the partial raw-moment images (ΣXᵀX, Σx, Σ(x-μ)²+nμ²,
    n) are stacked over a 1-axis mesh, and a single f32/f64 psum yields
    the global moments — total traffic O(p²) per host, independent of n.
    The rank transform is order-based across ALL hosts' rows and cannot be
    reduced this way; it raises.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from ..comm.compat import make_mesh, shard_map, use_mesh

    tf = get_transform(transform)
    if tf.two_pass:
        raise ValueError(
            f"transform {tf.name!r} is order-based across all hosts' rows "
            f"and cannot be psum-reduced; rank-transform the consolidated "
            f"stream via rank_gram instead")
    if not per_host_data:
        raise ValueError("no per-host sources")
    accs = [GramAccumulator(transform=tf, panel=panel) for _ in per_host_data]
    for acc, data in zip(accs, per_host_data):
        source = as_source(data, chunk_rows=chunk_rows)
        for chunk in source.chunks():
            acc.update(chunk)
    ps = {a.p for a in accs if a.p is not None}
    if len(ps) != 1:
        raise ValueError(f"hosts saw inconsistent column counts {ps}")
    p = ps.pop()

    n_dev = len(per_host_data)
    devices = jax.devices()
    if n_dev > len(devices):
        raise ValueError(
            f"{n_dev} per-host sources but only {len(devices)} devices")
    if not jax.config.jax_enable_x64:
        # the wire format must be f64 to preserve the accumulator's f64
        # contract (the paper's runs are double precision); without x64
        # the psum would silently truncate, so reduce host-side instead
        merged = accs[0]
        for a in accs[1:]:
            merged.merge(a)
        return merged.finalize()
    # raw-moment images: Welford state -> psum-able sums (exact in f64;
    # the one lossy step is this final merge, same as any tree reduction)
    xx = np.stack([a._xx for a in accs])
    s1 = np.stack([a._mean * a.n for a in accs])
    s2 = np.stack([a._m2 + a._mean ** 2 * a.n for a in accs])
    cnt = np.asarray([[float(a.n)] for a in accs])
    mesh = make_mesh((n_dev,), ("hosts",), devices=devices[:n_dev])

    with use_mesh(mesh):
        fn = shard_map(_psum_moments, mesh=mesh,
                       in_specs=(P("hosts"), P("hosts"), P("hosts"),
                                 P("hosts")),
                       out_specs=(P(), P(), P(), P()))
        g_xx, g_s1, g_s2, g_n = fn(
            jnp.asarray(xx, jnp.float64), jnp.asarray(s1, jnp.float64),
            jnp.asarray(s2, jnp.float64), jnp.asarray(cnt, jnp.float64))
    n = int(round(float(np.asarray(g_n)[0])))
    mean = np.asarray(g_s1, np.float64)[0] / n
    var = np.asarray(g_s2, np.float64)[0] / n - mean ** 2
    st = StreamStats(n=n, mean=mean, var=np.maximum(var, 0.0),
                     xx=np.asarray(g_xx, np.float64)[0])
    s = np.asarray(tf.finalize_gram(st), np.float64)
    s = 0.5 * (s + s.T)
    return GramResult(
        s=s, n=n, p=p, transform=tf.name, mean=st.mean, var=st.var,
        n_chunks=sum(a.n_chunks for a in accs),
        source_dtype=accs[0].source_dtype or "float64")


# ---------------------------------------------------------------------------
# analysis manifest (repro.analysis.jaxprpass)
# ---------------------------------------------------------------------------

def _analysis_panel_gram():
    import jax.numpy as jnp
    x = jnp.linspace(0.0, 1.0, 48, dtype=jnp.float64).reshape(6, 8)
    return {"fn": lambda xx: panel_gram(xx, panel=4), "args": (x,)}


def _analysis_distributed_reduce():
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from ..comm.compat import make_mesh, shard_map, use_mesh
    mesh = make_mesh((1,), ("hosts",), devices=jax.devices()[:1])
    fn = shard_map(_psum_moments, mesh=mesh, in_specs=(P("hosts"),) * 4,
                   out_specs=(P(),) * 4)
    p = 4
    return {
        "fn": fn,
        "args": (jnp.zeros((1, p, p), jnp.float64),
                 jnp.zeros((1, p), jnp.float64),
                 jnp.zeros((1, p), jnp.float64),
                 jnp.zeros((1, 1), jnp.float64)),
        "ctx": lambda: use_mesh(mesh),
    }


#: the f64 compute core of every streamed Gram, and the one-psum reduce
ANALYSIS_ENTRIES = [
    {"name": "data.gram.panel_gram", "path": "src/repro/core/matops.py",
     "axis_names": (), "build": _analysis_panel_gram},
    {"name": "data.gram.distributed_reduce",
     "path": "src/repro/data/gram.py", "axis_names": ("hosts",),
     "build": _analysis_distributed_reduce},
]
