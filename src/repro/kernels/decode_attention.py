"""Pallas TPU batched GQA decode-attention kernel.

One query token per sequence against a (padded) KV cache. Grid:
``(batch·kv_heads, num_kv_blocks)``; each step loads one kv block and the
G query heads that share it (the whole GQA group rides one MXU pass —
scores are a (G × block_k) matmul). Per-sequence valid lengths mask padded
cache slots. Running max/sum/acc in VMEM scratch, as in the prefill
kernel; the workload is memory-bound (cache streaming), so block_k is
large (512) to maximize the HBM burst size.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                   *, scale: float, block_k: int):
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[pl.program_id(0)]
    k_start = ki * block_k
    # skip blocks entirely past the valid cache region
    @pl.when(k_start < length)
    def _compute():
        q = q_ref[0].astype(jnp.float32)  # (G, d)
        k = k_ref[0].astype(jnp.float32)  # (bk, d)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (G, bk)
        g = s.shape[0]
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (g, block_k), 1)
        s = jnp.where(kpos < length, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == pl.num_programs(1) - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, ...] = (acc_ref[...] / l).astype(o_ref.dtype)


def decode_attention_bhgd(q, k, v, lengths, *, block_k: int = 512,
                          interpret: bool):
    """Decode attention over pre-flattened kv-heads.

    q: (BHkv, G, D) — one token's query heads grouped by kv head;
    k, v: (BHkv, S, D) padded caches; lengths: (BHkv,) valid entries.
    Returns (BHkv, G, D).
    """
    bh, g, d = q.shape
    s = k.shape[1]
    block_k = min(block_k, max(s, 8))
    nk = math.ceil(s / block_k)
    pad = nk * block_k - s
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0)))
    kernel = functools.partial(_decode_kernel, scale=1.0 / math.sqrt(d),
                               block_k=block_k)
    return pl.pallas_call(
        kernel,
        grid=(bh, nk),
        in_specs=[
            # The whole (BHkv,) lengths vector sits in SMEM: Mosaic refuses a
            # rank-1 block narrower than 128 that is not the whole array.
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, g, d), lambda b, ki: (b, 0, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, ki: (b, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, ki: (b, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, g, d), lambda b, ki: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, g, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(lengths.astype(jnp.int32), q, k, v)
