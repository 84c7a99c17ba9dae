"""Pallas TPU kernel: FlashAttention-2-style fused attention with GQA.

Grid (N_q_heads_flat, Sq/bq, Skv/bk) — kv innermost. Per (head, q-block):
running max / sum / accumulator live in VMEM scratch across kv steps; the
output tile is written once on the last kv step (classic online softmax).
GQA is handled by the index map: q-head n reads kv-head n // group.

Tiling: bq x d and bk x d tiles in VMEM; the bq x bk score tile never leaves
VMEM — the O(S^2) matrix never touches HBM, which is the entire point.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            block_q: int, block_k: int, causal: bool, q_offset: int,
            scale: float, n_kv_steps: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0]                                  # (bq, d)
    k = k_ref[0]                                  # (bk, d)
    v = v_ref[0]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if causal:
        q_pos = q_offset + qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        s = jnp.where(q_pos >= k_pos, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)                        # (bq, bk) fp32
    corr = jnp.exp(m_prev - m_new)                # (bq, 1)
    l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(ki == n_kv_steps - 1)
    def _finalize():
        o_ref[0] = (acc_scr[...] /
                    jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("group", "causal", "q_offset",
                                             "block_q", "block_k", "interpret"))
def flash_attention_kernel(q, k, v, *, group: int = 1, causal: bool = True,
                           q_offset: int = 0, block_q: int = 128,
                           block_k: int = 128, interpret: bool = False):
    """q: (N, Sq, d) with N = B*H_q; k/v: (N // group, Skv, d)."""
    n, sq, d = q.shape
    skv = k.shape[1]
    if sq % block_q or skv % block_k:
        raise ValueError(f"Sq={sq} % {block_q} or Skv={skv} % {block_k} != 0")
    n_kv = skv // block_k
    grid = (n, sq // block_q, n_kv)
    kernel = functools.partial(
        _kernel, block_q=block_q, block_k=block_k, causal=causal,
        q_offset=q_offset, scale=d ** -0.5, n_kv_steps=n_kv)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda h, qi, ki: (h, qi, 0)),
            pl.BlockSpec((1, block_k, d), lambda h, qi, ki: (h // group, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda h, qi, ki: (h // group, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda h, qi, ki: (h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((n, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
