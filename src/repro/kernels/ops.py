"""Jit'd public wrappers around the Pallas kernels.

These accept model-layout tensors — q (B, S, Hq, D), caches
(B, S, Hkv, D), SSD inputs (B, S, H, P) — and handle GQA head-flattening,
padding, and dtype plumbing. Kernels lower through Mosaic for the TPU
unless the caller passes ``interpret=True`` (Pallas interpret mode, which
is how CPU tests run them). There is no automatic fallback: on a backend
that is not a TPU, a call without ``interpret=True`` fails to lower.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import decode_attention as _dec
from repro.kernels import flash_attention as _fa
from repro.kernels import ssd_scan as _ssd


def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 128,
                    block_k: int = 128, interpret: bool = False):
    """q: (B, S, Hq, D); k, v: (B, S, Hkv, D) → (B, S, Hq, D)."""
    b, s, hq, d = q.shape
    hkv = k.shape[2]
    qf = q.transpose(0, 2, 1, 3).reshape(b * hq, s, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * hkv, s, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * hkv, s, d)
    out = _fa.flash_attention_bhsd(
        qf, kf, vf, causal=causal, block_q=block_q, block_k=block_k,
        interpret=interpret)
    return out.reshape(b, hq, s, d).transpose(0, 2, 1, 3)


def decode_attention(q, k_cache, v_cache, cache_len, *, block_k: int = 512,
                     interpret: bool = False):
    """q: (B, 1, Hq, D); caches: (B, S, Hkv, D); cache_len: scalar or (B,).

    Returns (B, 1, Hq, D) — drop-in for the jnp decode path.
    """
    b, _, hq, d = q.shape
    s, hkv = k_cache.shape[1], k_cache.shape[2]
    g = hq // hkv
    qf = q.reshape(b, hkv, g, d).reshape(b * hkv, g, d)
    kf = k_cache.transpose(0, 2, 1, 3).reshape(b * hkv, s, d)
    vf = v_cache.transpose(0, 2, 1, 3).reshape(b * hkv, s, d)
    lengths = jnp.broadcast_to(jnp.asarray(cache_len, jnp.int32).reshape(-1),
                               (b,))
    lengths = jnp.repeat(lengths, hkv) if lengths.shape[0] == b else lengths
    out = _dec.decode_attention_bhgd(
        qf, kf, vf, lengths, block_k=block_k,
        interpret=interpret)
    return out.reshape(b, hkv, g, d).reshape(b, 1, hq, d)


def mlstm_attention(q, k, v, log_i, log_f, *, block_q: int = 128,
                    block_k: int = 128, interpret: bool = False):
    """Parallel mLSTM in model layout.

    q/k/v: (B, S, H, D); log_i, log_f: (B, S, H) → y: (B, S, H, D).
    Drop-in for ``repro.models.xlstm._mlstm_parallel`` (its oracle).
    """
    from repro.kernels import mlstm_attention as _ml

    b, s, h, d = q.shape
    def flat(x4):
        return x4.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    li = log_i.transpose(0, 2, 1).reshape(b * h, s)
    lf = log_f.transpose(0, 2, 1).reshape(b * h, s)
    out = _ml.mlstm_attention_bhsd(
        flat(q), flat(k), flat(v), li, lf, block_q=block_q, block_k=block_k,
        interpret=interpret)
    return out.reshape(b, h, s, d).transpose(0, 2, 1, 3)


def ssd_scan(x, dt, a, b, c, *, chunk: int = 128,
             interpret: bool = False):
    """SSD scan in model layout.

    x: (B, S, H, P); dt: (B, S, H); a: (H,); b, c: (B, S, N) →
    y: (B, S, H, P). Drop-in for ``repro.models.ssm.ssd_chunked`` (which is
    its oracle) minus the final-state output.
    """
    bsz, s, h, p = x.shape
    xf = x.transpose(0, 2, 1, 3).reshape(bsz * h, s, p)
    dtf = dt.transpose(0, 2, 1).reshape(bsz * h, s)
    af = jnp.tile(a.reshape(1, h), (bsz, 1)).reshape(bsz * h)
    out = _ssd.ssd_scan_bhsd(xf, dtf, af, b, c, chunk=chunk,
                             interpret=interpret)
    return out.reshape(bsz, h, s, p).transpose(0, 2, 1, 3)
