"""Pallas TPU kernel for the parallel mLSTM form (xLSTM's hot path).

Attention-shaped with an additive decay bias instead of softmax:

    b_ij = F_i − F_j + log i_j      (causal; F = cumsum log-sigmoid forget)
    m_i  = max_j b_ij
    num_i = Σ_j (q_i·k_j/√d) exp(b_ij − m_i) v_j
    den_i = Σ_j (q_i·k_j/√d) exp(b_ij − m_i)
    y_i  = num_i / max(|den_i|, exp(−m_i))

Same grid/scratch pattern as the flash kernel (the kv axis is sequential;
running (m, num, den) in VMEM): rescaling by exp(m_prev − m_new) is valid
because it multiplies both the signed numerator and denominator terms by
the same positive factor. Oracle: ``repro.models.xlstm._mlstm_parallel``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _mlstm_kernel(q_ref, k_ref, v_ref, fcum_ref, logi_ref, o_ref,
                  m_ref, num_ref, den_ref, *, scale: float, seq_len: int,
                  block_q: int, block_k: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        num_ref[...] = jnp.zeros_like(num_ref)
        den_ref[...] = jnp.zeros_like(den_ref)

    q_start = qi * block_q
    k_start = ki * block_k

    @pl.when(k_start <= q_start + block_q - 1)  # causal block skip
    def _compute():
        q = q_ref[0].astype(jnp.float32)          # (bq, d)
        k = k_ref[0].astype(jnp.float32)          # (bk, d)
        v = v_ref[0].astype(jnp.float32)
        fq = fcum_ref[0].astype(jnp.float32)      # (bq, 1) — F_i
        fk_li = logi_ref[0].astype(jnp.float32)   # (bk, 1) — log i_j − F_j

        bmat = fq + fk_li.T                       # (bq, bk): F_i − F_j + log i_j
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                  (block_q, block_k), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                  (block_q, block_k), 1)
        mask = (kpos <= qpos) & (kpos < seq_len)
        bmat = jnp.where(mask, bmat, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(bmat, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        scores = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        w = scores * jnp.exp(bmat - m_new)
        w = jnp.where(mask, w, 0.0)
        num_ref[...] = num_ref[...] * alpha + jax.lax.dot_general(
            w, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        den_ref[...] = den_ref[...] * alpha + jnp.sum(w, axis=1, keepdims=True)
        m_ref[...] = m_new

    @pl.when(ki == pl.num_programs(2) - 1)
    def _finalize():
        m = m_ref[...]
        den = jnp.maximum(jnp.abs(den_ref[...]), jnp.exp(-m))
        o_ref[0, ...] = (num_ref[...] / den).astype(o_ref.dtype)


def mlstm_attention_bhsd(q, k, v, log_i, log_f, *, block_q: int = 128,
                         block_k: int = 128, interpret: bool):
    """Parallel mLSTM over pre-flattened heads.

    q/k/v: (BH, S, D); log_i, log_f: (BH, S) (input-gate log and
    log-sigmoid forget). Returns y: (BH, S, D).
    """
    bh, s, d = q.shape
    block_q = min(block_q, max(s, 8))
    block_k = min(block_k, max(s, 8))
    nq = math.ceil(s / block_q)
    nk = math.ceil(s / block_k)
    q_pad = nq * block_q - s
    k_pad = nk * block_k - s
    fcum = jnp.cumsum(log_f.astype(jnp.float32), axis=1)  # (BH, S)
    fk_li = (log_i.astype(jnp.float32) - fcum)            # log i_j − F_j
    if q_pad:
        q = jnp.pad(q, ((0, 0), (0, q_pad), (0, 0)))
        fcum_q = jnp.pad(fcum, ((0, 0), (0, q_pad)))
    else:
        fcum_q = fcum
    if k_pad:
        k = jnp.pad(k, ((0, 0), (0, k_pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, k_pad), (0, 0)))
        fk_li = jnp.pad(fk_li, ((0, 0), (0, k_pad)))

    kernel = functools.partial(
        _mlstm_kernel, scale=1.0 / math.sqrt(d), seq_len=s,
        block_q=block_q, block_k=block_k)
    out = pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_k, 1), lambda b, qi, ki: (b, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, nq * block_q, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, fcum_q[..., None], fk_li[..., None])
    return out[:, :s]
