"""Pallas kernel sweeps vs the pure-jnp oracles (interpret=True on CPU)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


@pytest.mark.parametrize("shape", [(64, 64), (256, 300), (100, 50),
                                   (513, 257), (128, 1024)])
@pytest.mark.parametrize("dtype", [jnp.float32])
@pytest.mark.parametrize("alpha", [0.0, 0.3, 2.0])
def test_fused_prox_sweep(shape, dtype, alpha, rng):
    z = rng.standard_normal(shape).astype(dtype)
    p = min(shape)
    mask = np.zeros(shape, np.float32)
    mask[np.arange(p), np.arange(p)] = 1
    z[np.arange(p), np.arange(p)] = \
        np.abs(z[np.arange(p), np.arange(p)]) + 0.1
    out, ld, l1, ss, md, bnnz = ops.fused_prox_stats(
        jnp.asarray(z), jnp.asarray(mask), alpha)
    ro, rld, rl1, rss, rmd, rbnnz = ref.fused_prox_stats(
        jnp.asarray(z), jnp.asarray(mask), alpha)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ro), rtol=1e-6)
    np.testing.assert_allclose(float(ld), float(rld), rtol=1e-4)
    np.testing.assert_allclose(float(l1), float(rl1), rtol=1e-4)
    np.testing.assert_allclose(float(ss), float(rss), rtol=1e-4)
    np.testing.assert_allclose(float(md), float(rmd), rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(bnnz), np.asarray(rbnnz))


@pytest.mark.parametrize("shape,block", [((128, 96), (32, 32)),
                                         ((100, 70), (32, 32)),
                                         ((64, 64), (16, 32))])
def test_fused_prox_block_nnz_is_exact_occupancy(shape, block, rng):
    """The kernel's nnz stats lane IS the block-occupancy mask: it must
    match the jnp.nonzero-derived per-tile counts of the prox output."""
    z = rng.standard_normal(shape).astype(np.float32)
    p = min(shape)
    mask = np.zeros(shape, np.float32)
    mask[np.arange(p), np.arange(p)] = 1
    out, *_, bnnz = ops.fused_prox_stats(jnp.asarray(z), jnp.asarray(mask),
                                         0.8, block=block)
    out_np = np.asarray(out)
    bm = min(block[0], shape[0])
    bn = min(block[1], shape[1])
    gm, gn = -(-shape[0] // bm), -(-shape[1] // bn)
    expect = np.zeros((gm, gn))
    for i, j in zip(*np.nonzero(out_np)):
        expect[i // bm, j // bn] += 1
    np.testing.assert_array_equal(np.asarray(bnnz), expect)


@pytest.mark.parametrize("p,m,bs,density", [
    (96, 64, 16, 0.4), (128, 128, 32, 0.1), (64, 256, 16, 1.0),
    (64, 32, 16, 0.0),  # fully empty -> builder inserts zero blocks
])
def test_blocksparse_sweep(p, m, bs, density, rng):
    a = rng.standard_normal((p, p)).astype(np.float32)
    keep = rng.random((p // bs, p // bs)) < density
    for r in range(p // bs):
        for c in range(p // bs):
            if not keep[r, c]:
                a[r * bs:(r + 1) * bs, c * bs:(c + 1) * bs] = 0
    vals, rows, cols = ref.dense_to_block_csr(a, bs)
    b = rng.standard_normal((p, m)).astype(np.float32)
    out = ops.blocksparse_matmul(jnp.asarray(vals), jnp.asarray(rows),
                                 jnp.asarray(cols), jnp.asarray(b))
    np.testing.assert_allclose(np.asarray(out), a @ b, rtol=1e-4, atol=1e-4)


def test_blocksparse_dense_roundtrip(rng):
    a = rng.standard_normal((64, 64)).astype(np.float32)
    vals, rows, cols = ref.dense_to_block_csr(a, 16)
    back = ref.block_csr_to_dense(jnp.asarray(vals), jnp.asarray(rows),
                                  jnp.asarray(cols), 64)
    np.testing.assert_allclose(np.asarray(back), a, rtol=1e-6)


def test_blocksparse_rejects_non_contiguous_row_revisit():
    """row_idx [0, 1, 0] revisits block-row 0 after writing block-row 1:
    the kernel's sequential accumulation would flush and then clobber
    block-row 0, so the wrapper must refuse at trace time (the CA401
    revisit hazard, caught before any wrong numbers ship)."""
    vals = jnp.ones((3, 4, 4), jnp.float32)
    rows = jnp.asarray([0, 1, 0], jnp.int32)
    cols = jnp.asarray([0, 1, 1], jnp.int32)
    b = jnp.ones((8, 8), jnp.float32)
    with pytest.raises(ValueError, match="non-contiguously"):
        ops.blocksparse_matmul(vals, rows, cols, b)


def test_blocksparse_contiguous_duplicate_rows_accumulate(rng):
    """Duplicate row ids in one contiguous CSR run are the accumulation
    path, not a hazard: the result must match the dense product."""
    bs, p, m = 4, 8, 8
    a = rng.standard_normal((p, p)).astype(np.float32)
    a[bs:, :bs] = 0.0          # block (1, 0) empty -> rows [0, 0, 1]
    vals, rows, cols = ref.dense_to_block_csr(a, bs)
    np.testing.assert_array_equal(np.asarray(rows), [0, 0, 1])
    b = rng.standard_normal((p, m)).astype(np.float32)
    out = ops.blocksparse_matmul(jnp.asarray(vals), jnp.asarray(rows),
                                 jnp.asarray(cols), jnp.asarray(b))
    np.testing.assert_allclose(np.asarray(out), a @ b, rtol=1e-4,
                               atol=1e-4)


def test_blocksparse_validation_skips_traced_row_idx(rng):
    """Under jit the row table is a tracer: the host-side contiguity
    check must stand aside (the static CA401 pass owns that case) and
    tracing must succeed."""
    import jax

    bs, p, m = 4, 8, 8
    a = rng.standard_normal((p, p)).astype(np.float32)
    vals, rows, cols = ref.dense_to_block_csr(a, bs)
    b = rng.standard_normal((p, m)).astype(np.float32)

    @jax.jit
    def run(v, r, c, bb):
        return ops.blocksparse_matmul(v, r, c, bb, interpret=True)

    out = run(jnp.asarray(vals), jnp.asarray(rows), jnp.asarray(cols),
              jnp.asarray(b))
    np.testing.assert_allclose(np.asarray(out), a @ b, rtol=1e-4,
                               atol=1e-4)


def test_interpret_override_cannot_leak_part1():
    """Pins the module-global interpret override; the autouse conftest
    guard must restore it before part2 (file order is run order)."""
    ops.set_interpret(True)
    assert ops.interpret_default() is True


def test_interpret_override_cannot_leak_part2():
    assert ops._INTERPRET_OVERRIDE is None      # part1's pin was undone
    ops.set_interpret(False)
    ops.reset_interpret()
    assert ops._INTERPRET_OVERRIDE is None
    with pytest.raises(TypeError):
        ops.set_interpret("yes")


FLASH_CASES = [
    # B, Hq, Hkv, Lq, Lkv, D, causal, window, softcap
    (2, 4, 2, 128, 128, 64, True, None, None),
    (1, 4, 4, 256, 256, 32, True, 64, None),
    (1, 2, 1, 128, 128, 64, True, None, 30.0),
    (1, 2, 2, 64, 192, 32, True, None, None),    # Lq < Lkv
    (2, 2, 2, 128, 128, 64, False, None, None),
    (1, 2, 2, 160, 160, 32, True, None, None),   # edge tiles
    (1, 8, 2, 128, 128, 32, True, 32, 10.0),     # everything at once
]


@pytest.mark.parametrize("case", FLASH_CASES)
def test_flash_attention_sweep(case, rng):
    B, Hq, Hkv, Lq, Lkv, D, causal, window, cap = case
    q = rng.standard_normal((B, Hq, Lq, D)).astype(np.float32)
    k = rng.standard_normal((B, Hkv, Lkv, D)).astype(np.float32)
    v = rng.standard_normal((B, Hkv, Lkv, D)).astype(np.float32)
    o = ops.flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                            causal=causal, window=window, softcap=cap,
                            block_q=64, block_k=64)
    r = ref.attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                      causal=causal, window=window, softcap=cap)
    np.testing.assert_allclose(np.asarray(o), np.asarray(r),
                               rtol=2e-3, atol=2e-3)


def test_flash_attention_bf16(rng):
    B, H, L, D = 1, 2, 128, 64
    q = (rng.standard_normal((B, H, L, D)) * 0.5).astype(np.float32)
    k = (rng.standard_normal((B, H, L, D)) * 0.5).astype(np.float32)
    v = (rng.standard_normal((B, H, L, D)) * 0.5).astype(np.float32)
    qb, kb, vb = (jnp.asarray(a, jnp.bfloat16) for a in (q, k, v))
    o = ops.flash_attention(qb, kb, vb, block_q=64, block_k=64)
    r = ref.attention(qb, kb, vb)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(r, np.float32),
                               rtol=5e-2, atol=5e-2)


def test_mea_attention_matches_flash_oracle(rng):
    """The XLA-native chunked attention (models/layers.py) and the Pallas
    kernel agree with the same oracle."""
    from repro.models.layers import mea_attention
    B, H, L, D = 1, 2, 128, 32
    q = jnp.asarray(rng.standard_normal((B, H, L, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, H, L, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, H, L, D)), jnp.float32)
    pos = jnp.arange(L)
    o1 = mea_attention(q, k, v, pos, pos, jnp.asarray(0, jnp.int32),
                       True, D ** -0.5, None, 32)
    o2 = ops.flash_attention(q, k, v, block_q=64, block_k=64)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("fn", [
    "softthresh.fused_prox_stats", "softthresh.fused_prox",
    "pathstep.fused_path_step", "blocksparse_matmul.blocksparse_matmul",
    "flash_attention.flash_attention"])
def test_kernel_modules_leave_interpret_to_ops(fn):
    """No kernel wrapper defaults to interpret mode: ``kernels.ops`` alone
    decides it from the running backend, so nothing reaches the chip
    interpreted by omission."""
    import importlib
    import inspect
    mod, name = fn.split(".")
    f = getattr(importlib.import_module(f"repro.kernels.{mod}"), name)
    param = inspect.signature(f).parameters["interpret"]
    assert param.default is inspect.Parameter.empty


def test_native_prox_refuses_unaligned_block_before_dispatch():
    """A 64-wide tile of a 256-wide operand cannot lower for the TPU: the
    wrapper says so with a ValueError instead of a compiler error (or a
    silent reroute)."""
    z = jnp.ones((256, 256), jnp.float32)
    with pytest.raises(ValueError, match=r"\(8, 128\)"):
        ops.fused_prox_stats(z, z, 0.1, block=(64, 64), interpret=False)
