"""Pallas TPU flash-attention (prefill) kernel.

Grid: ``(batch·q_heads, num_q_blocks, num_kv_blocks)`` — the kv axis is the
innermost (sequential) dimension; running max / sum / accumulator live in
VMEM scratch and persist across kv steps (the standard TPU flash pattern).
GQA is handled in the k/v index maps (q-head → kv-head is a static integer
division), so no head replication is materialized.

Block sizes default to 128×128 (MXU-aligned); the f32 working set per step
is q(bq·d) + k,v(2·bk·d) + scores(bq·bk) + acc(bq·d) ≈ 260 KB for d=128 —
comfortably inside the ~16 MB VMEM budget, leaving room for double
buffering of the k/v streams.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, kv_len: int,
                  block_q: int, block_k: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # causal: skip kv blocks strictly above the diagonal band
    q_start = qi * block_q
    k_start = ki * block_k
    should_run = jnp.logical_or(
        jnp.logical_not(causal), k_start <= q_start + block_q - 1)

    @pl.when(should_run)
    def _compute():
        q = q_ref[0].astype(jnp.float32)  # (bq, d)
        k = k_ref[0].astype(jnp.float32)  # (bk, d)
        v = v_ref[0].astype(jnp.float32)  # (bk, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (bq, bk)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = kpos < kv_len  # padded keys
        if causal:
            qpos = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            mask = jnp.logical_and(mask, kpos <= qpos)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]  # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)  # (bq, bk)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == pl.num_programs(2) - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, ...] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention_bhsd(q, k, v, *, causal: bool = True, block_q: int = 128,
                         block_k: int = 128, interpret: bool):
    """Flash attention over pre-flattened heads.

    q: (BHq, Sq, D); k, v: (BHkv, Sk, D) with BHq = BHkv · G.
    Sequences are padded to block multiples; padded keys are masked via
    ``kv_len`` baked into the kernel.
    """
    bhq, sq, d = q.shape
    bhkv, sk, _ = k.shape
    if bhq % bhkv:
        raise ValueError(f"q heads {bhq} not a multiple of kv heads {bhkv}")
    g = bhq // bhkv
    block_q = min(block_q, max(sq, 8))
    block_k = min(block_k, max(sk, 8))
    nq = math.ceil(sq / block_q)
    nk = math.ceil(sk / block_k)
    q_pad = nq * block_q - sq
    k_pad = nk * block_k - sk
    if q_pad:
        q = jnp.pad(q, ((0, 0), (0, q_pad), (0, 0)))
    if k_pad:
        k = jnp.pad(k, ((0, 0), (0, k_pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, k_pad), (0, 0)))

    kernel = functools.partial(
        _flash_kernel, scale=1.0 / math.sqrt(d), causal=causal, kv_len=sk,
        block_q=block_q, block_k=block_k)
    out = pl.pallas_call(
        kernel,
        grid=(bhq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, qi, ki: (bh // g, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, qi, ki: (bh // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((bhq, nq * block_q, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
    return out[:, :sq]
