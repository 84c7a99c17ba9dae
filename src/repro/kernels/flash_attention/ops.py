"""Jit'd wrapper: model-layout (B, S, H, dh) GQA attention on the Pallas
flash kernel (compiled unless interpret=True)."""

from __future__ import annotations

from repro.kernels.flash_attention.flash_attention import flash_attention_kernel


def flash_attention_pallas(q, k, v, *, causal: bool = True, q_offset: int = 0,
                           block_q: int = 128, block_k: int = 128,
                           interpret: bool = False):
    """Drop-in for models.attention.flash_attention (same layout/semantics)."""
    b, sq, h, dh = q.shape
    kv = k.shape[2]
    g = h // kv
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, sq, dh)
    kf = k.transpose(0, 2, 1, 3).reshape(b * kv, k.shape[1], dh)
    vf = v.transpose(0, 2, 1, 3).reshape(b * kv, v.shape[1], dh)
    o = flash_attention_kernel(qf, kf, vf, group=g, causal=causal,
                               q_offset=q_offset, block_q=block_q,
                               block_k=block_k, interpret=interpret)
    return o.reshape(b, h, sq, dh).transpose(0, 2, 1, 3)
