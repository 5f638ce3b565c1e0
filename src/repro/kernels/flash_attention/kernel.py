"""Pallas kernel: blocked causal flash attention (GQA + sliding window).

Grid: ``(B, H, n_q, n_kv)`` — the kv axis is innermost and sequential on
TPU, so VMEM scratch (running max ``m``, normalizer ``l`` and the f32
output accumulator) carries across kv steps and is re-initialized at
``ik == 0``.  Block shapes:

    q:   (1, 1, bq, D)   index (b, h, iq, 0)
    k/v: (1, 1, bk, D)   index (b, h // group, ik, 0)   <- GQA head map
    out: (1, 1, bq, D)   index (b, h, iq, 0)            (ignores ik)

Causality and the sliding window are applied as in-block masks against the
absolute positions; blocks entirely above the diagonal or entirely outside
the window skip their matmuls via ``pl.when`` (the dominant saving for the
32k/500k decode shapes).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, bq: int, bk: int, n_kv: int, window: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = iq * bq
    k_start = ik * bk
    # block-level reachability: any (qpos >= kpos) and window overlap
    reachable = k_start <= q_start + bq - 1
    if window > 0:
        reachable &= (k_start + bk - 1) > (q_start - window)

    @pl.when(reachable)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)            # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)            # (bk, D)
        v = v_ref[0, 0].astype(jnp.float32)            # (bk, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (bq, bk)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = kpos <= qpos
        if window > 0:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG)

        m_prev = m_ref[...]                            # (bq, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ik == n_kv - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_q", "block_k", "window",
                                             "interpret"))
def flash_attention_kernel(q, k, v, *, block_q: int = 128,
                           block_k: int = 128, window: int = 0,
                           interpret: bool = True):
    """q: (B, H, L, D); k, v: (B, K, L, D); L divisible by both blocks."""
    B, H, L, D = q.shape
    K = k.shape[1]
    assert L % block_q == 0 and L % block_k == 0, (L, block_q, block_k)
    group = H // K
    n_q, n_kv = L // block_q, L // block_k
    scale = 1.0 / math.sqrt(D)

    kernel = functools.partial(_flash_kernel, scale=scale, bq=block_q,
                               bk=block_k, n_kv=n_kv, window=window)
    return pl.pallas_call(
        kernel,
        grid=(B, H, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D),
                         lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, iq, ik: (b, h // group, ik, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, iq, ik: (b, h // group, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D),
                               lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, L, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention_kernel",
    )(q, k, v)
