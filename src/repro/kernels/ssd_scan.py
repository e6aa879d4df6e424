"""Pallas TPU Mamba-2 / SSD chunked-scan kernel.

Grid: ``(batch·heads, num_chunks)`` with the chunk axis sequential; the
(P×N) recurrent state lives in VMEM scratch and is carried across chunks.
Each chunk step is four MXU matmuls (CBᵀ, diag-term, state injection,
state-to-output) over a (Q × {P,N}) working set — the chunk length Q is
the Pallas block size (default 128, MXU-aligned).

B and C are shared across heads (ngroups = 1), expressed in the index maps
(bh → batch is a static division), so no replication is materialized.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, h_ref, *,
                chunk: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0].astype(jnp.float32)      # (Q, P)
    dt = dt_ref[0].astype(jnp.float32)    # (Q,)
    a = a_ref[0].astype(jnp.float32)      # scalar decay rate (negative)
    b = b_ref[0].astype(jnp.float32)      # (Q, N)
    c = c_ref[0].astype(jnp.float32)      # (Q, N)

    da = dt * a                            # (Q,) log-decay per step
    da_cum = jnp.cumsum(da)                # within-chunk cumulative
    da_total = da_cum[-1]

    # intra-chunk (quadratic) term: y[q] += Σ_k CBᵀ[q,k]·exp(Σ_{k<j≤q}da)·dt[k]·x[k]
    seg = da_cum[:, None] - da_cum[None, :]          # (Q, Q)
    qpos = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    kpos = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    l_mat = jnp.where(kpos <= qpos, jnp.exp(seg), 0.0)
    cb = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (Q, Q)
    w = cb * l_mat * dt[None, :]
    y = jax.lax.dot_general(w, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (Q, P)

    # inter-chunk: contribution of the carried state
    decay_from_start = jnp.exp(da_cum)  # (Q,)
    h_prev = h_ref[...]  # (P, N)
    y_off = jax.lax.dot_general(
        c * decay_from_start[:, None], h_prev,
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)  # (Q, P)
    y_ref[0, ...] = (y + y_off).astype(y_ref.dtype)

    # state update: h = h·exp(Σda) + Σ_k x[k] ⊗ (b[k]·decay_to_end[k]·dt[k])
    decay_to_end = jnp.exp(da_total - da_cum)  # (Q,)
    bw = b * (decay_to_end * dt)[:, None]  # (Q, N)
    inject = jax.lax.dot_general(x, bw, (((0,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # (P, N)
    h_ref[...] = h_prev * jnp.exp(da_total) + inject


def ssd_scan_bhsd(x, dt, a, b, c, *, chunk: int = 128, interpret: bool):
    """SSD scan over pre-flattened heads.

    x: (BH, S, P); dt: (BH, S) (positive, already softplus'd);
    a: (BH,) negative decay rates; b, c: (B, S, N) shared across heads.
    Returns y: (BH, S, P). Sequences are padded to chunk multiples with
    dt = 0 (identity decay, zero injection) so padding is exact.
    """
    bh, s, p = x.shape
    bsz, _, n = b.shape
    if bh % bsz:
        raise ValueError(f"BH={bh} not a multiple of B={bsz}")
    h_per_b = bh // bsz
    chunk = min(chunk, max(s, 8))
    nc = math.ceil(s / chunk)
    pad = nc * chunk - s
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad)))
        b = jnp.pad(b, ((0, 0), (0, pad), (0, 0)))
        c = jnp.pad(c, ((0, 0), (0, pad), (0, 0)))

    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    out = pl.pallas_call(
        kernel,
        grid=(bh, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, p), lambda bh_, ci: (bh_, ci, 0)),
            pl.BlockSpec((1, chunk), lambda bh_, ci: (bh_, ci)),
            pl.BlockSpec((1,), lambda bh_, ci: (bh_,), memory_space=pltpu.SMEM),
            pl.BlockSpec((1, chunk, n), lambda bh_, ci: (bh_ // h_per_b, ci, 0)),
            pl.BlockSpec((1, chunk, n), lambda bh_, ci: (bh_ // h_per_b, ci, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, p), lambda bh_, ci: (bh_, ci, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, nc * chunk, p), x.dtype),
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(x, dt, a.astype(jnp.float32), b, c)
    return out[:, :s]
