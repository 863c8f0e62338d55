"""Flash-attention Pallas TPU kernel (online-softmax, VMEM-tiled).

The LM-zoo hot-spot.  Supports every attention flavor the assigned
architectures need in one kernel:

  * GQA              — kv-head picked by q-head // group in the index map,
                       so no repeat/materialization of K/V.
  * causal masking   — kv tiles entirely in the future are skipped
                       (@pl.when on the tile, not just masked).
  * sliding window   — danube / mixtral / gemma2-local; tiles entirely
                       OUTSIDE the window are skipped, giving the
                       O(L * window) flop count instead of O(L^2).
  * logit softcap    — gemma2's cap * tanh(logits / cap).

Online softmax state (running max m, denominator l, accumulator acc) lives
in VMEM scratch across the kv-tile grid dimension (the innermost one), as
in the canonical TPU flash attention.  Block sizes are MXU/lane aligned
(q, kv tiles multiples of 128 when the problem allows).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
            *, scale, causal, window, softcap, block_q, block_k,
            q_offset, kv_len):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # tile-level skip: lowest q position this tile can see / highest needed
    q_lo = iq * block_q + q_offset          # global position of first query
    k_lo = ik * block_k
    run = jnp.asarray(True)
    if causal:
        run &= k_lo <= q_lo + block_q - 1   # some key not in the future
    if window is not None:
        run &= k_lo + block_k - 1 > q_lo - window  # some key inside window

    @pl.when(run)
    def _body():
        # zero edge-tile padding (interpret mode pads with NaN; 0 * NaN = NaN
        # would otherwise leak through p @ v)
        kvalid = (k_lo + jax.lax.broadcasted_iota(
            jnp.int32, (block_k, 1), 0)) < kv_len
        q = q_ref[0, 0].astype(jnp.float32)  # (bq, d)
        k = jnp.where(kvalid, k_ref[0, 0], 0.0).astype(jnp.float32)
        v = jnp.where(kvalid, v_ref[0, 0], 0.0).astype(jnp.float32)
        q = jnp.where(jnp.isnan(q), 0.0, q)  # padded query rows (discarded)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        qpos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = kpos < kv_len                 # kv padding
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                  # (bq, 128) broadcast lanes
        m_cur = jnp.max(s, axis=1)[:, None]
        m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_cur, m_prev.shape))
        p = jnp.exp(s - m_new[:, :1])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.broadcast_to(
            jnp.sum(p, axis=1)[:, None], m_prev.shape)
        acc_ref[...] = acc_ref[...] * alpha[:, :1] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finish():
        l = l_ref[..., :1]
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def kernel_layout(B: int, Hq: int, Hkv: int, Lq: int, Lkv: int, D: int,
                  *, block_q: int = DEFAULT_BLOCK_Q,
                  block_k: int = DEFAULT_BLOCK_K) -> dict:
    """Grid + BlockSpec geometry of the flash-attention ``pallas_call``.

    Shared by the wrapper below and the CA4xx kernel verifier (via
    ``kernels.manifest``).  The out spec ignores the kv grid dim (dim 3,
    the innermost one): the kernel revisits its output block across kv
    tiles with VMEM scratch accumulators, declared to the verifier as a
    sequential-accumulation dim.  The kv index maps carry ``group`` as a
    bound default arg, so their non-default arity stays the grid rank.
    """
    group = Hq // Hkv
    bq = min(block_q, Lq)
    bk = min(block_k, Lkv)
    gq, gk = pl.cdiv(Lq, bq), pl.cdiv(Lkv, bk)
    return {
        "grid": (B, Hq, gq, gk),
        "in_specs": [
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda b, h, i, j, g=group: (b, h // g, j, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda b, h, i, j, g=group: (b, h // g, j, 0)),
        ],
        "out_specs": pl.BlockSpec(
            (1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
        "out_shapes": ((B, Hq, Lq, D),),
        "bq": bq,
        "bk": bk,
    }


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "softcap", "scale",
                     "block_q", "block_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int | None = None,
                    softcap: float | None = None, scale: float | None = None,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    interpret: bool):
    """q: (B, Hq, Lq, D); k, v: (B, Hkv, Lkv, D). Returns (B, Hq, Lq, D).

    Lq may be shorter than Lkv (chunked prefill / decode): query position i
    is aligned so the LAST query attends to the LAST key.
    """
    B, Hq, Lq, D = q.shape
    Hkv, Lkv = k.shape[1], k.shape[2]
    lay = kernel_layout(B, Hq, Hkv, Lq, Lkv, D,
                        block_q=block_q, block_k=block_k)
    bq, bk = lay["bq"], lay["bk"]
    scale = scale if scale is not None else float(D) ** -0.5
    q_offset = Lkv - Lq

    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, window=window, softcap=softcap,
        block_q=bq, block_k=bk, q_offset=q_offset, kv_len=Lkv)

    out = pl.pallas_call(
        kernel,
        grid=lay["grid"],
        in_specs=lay["in_specs"],
        out_specs=lay["out_specs"],
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return out
