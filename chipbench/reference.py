"""Plain float32 reference of the decoders the benchmark serves.

It imports nothing of the program and takes nothing the program made: it
builds the weights itself from the same seed, by the recipe below, and
runs the published architecture in ``jax.numpy`` at float32 under
``default_matmul_precision("highest")``, with no cache, batching or
kernels. The architecture is the module ``arch/<model_type>.py`` (see
``arch/__init__.py``): one layer's weights and forward, the final norm,
and whether the head is tied. This file holds what every architecture
shares: the draws of the weight recipe, the float8 rounding, both norms,
rotary embeddings, causal grouped-query attention, SwiGLU, and the pass
over embedding, layers and head.

The weight recipe, which the program's ``Model.init`` follows too: from
``PRNGKey(s)``, split (embedding, layers, head); split the layers' key
into one per layer, from which the architecture's module draws that
layer. A projection of fan-in ``n`` is ``normal / sqrt(n)``, the
embedding ``normal * 0.02``, the untied head ``normal / sqrt(hidden)``;
every value is rounded to the stored type (bfloat16). Norm scales are
one; every bias is zero.

The weights are made layer by layer, and the reference runs layer by
layer over all rows, so that it fits in a chip's memory beside nothing
else. ``forward`` can also run the control: the same computation with
every matrix product's operands rounded to float8 (e4m3, one scale per
weight matrix and one per activation row), the precision step below the
configuration's bfloat16.
"""
from __future__ import annotations

import functools
import json
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

import arch

F8 = jnp.float8_e4m3fn
F8_MAX = float(jnp.finfo(F8).max)
STORED = {"bfloat16": jnp.bfloat16, "float16": jnp.float16,
          "float32": jnp.float32}


# ------------------------------------------------------------------ weights
def normal(key, shape, scale, stored):
    """``normal * scale`` rounded to the stored type, held in float32."""
    w = jax.random.normal(key, shape) * scale
    return w.astype(stored).astype(jnp.float32)


def _keys(cfg: dict, key_seed: int):
    k_emb, k_layers, k_head = jax.random.split(jax.random.PRNGKey(key_seed), 3)
    return k_emb, jax.random.split(k_layers, cfg["num_hidden_layers"]), k_head


# ------------------------------------------------------------------ compute
def f8(x, axis):
    """Round ``x`` to float8 e4m3 with one scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, F8_MAX / amax, 1.0)
    return (x * scale).astype(F8).astype(jnp.float32) / scale


def matmul(x, w, control: bool):
    """``x @ w``; with ``control``, of float8-rounded operands."""
    if control:
        x, w = f8(x, -1), f8(w, None)
    return x @ w


def rms_norm(x, scale, eps: float):
    var = jnp.mean(jnp.square(x), -1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def layer_norm(x, scale, bias, eps: float):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias


def swiglu(h):
    """SiLU of the first half times the second."""
    gate, up = jnp.split(h, 2, axis=-1)
    return gate * jax.nn.sigmoid(gate) * up


def rope(x, theta: float):
    """x: (rows, seq, heads, hd); positions 0..seq-1, halves rotated."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv[None]
    c, s = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def causal_attention(q, k, v):
    """Causal grouped-query attention. q: (rows, seq, hq, hd); k, v:
    (rows, seq, hkv, hd) → (rows, seq, hq * hd)."""
    r, s, hq, hd = q.shape
    hkv = k.shape[2]
    q = q.reshape(r, s, hkv, hq // hkv, hd)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", q, k) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    att = jnp.einsum("bhgqk,bkhd->bqhgd", jax.nn.softmax(scores, -1), v)
    return att.reshape(r, s, hq * hd)


def _kinds(mod, cfg: dict):
    kind = getattr(mod, "layer_kind", None)
    return [kind(cfg, i) if kind else None
            for i in range(cfg["num_hidden_layers"])]


@functools.lru_cache(maxsize=None)
def _programs(cfg_key: str, control: bool):
    """The jitted pieces of a forward pass, made once per configuration
    (the layer's two per kind of layer)."""
    cfg = json.loads(cfg_key)
    mod = arch.load(cfg)
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    stored = STORED[cfg["torch_dtype"]]
    kinds = set(_kinds(mod, cfg))
    return {
        "table": jax.jit(lambda k: normal(k, (v, d), 0.02, stored)),
        "head": jax.jit(lambda k: normal(k, (d, v), 1.0 / math.sqrt(d), stored)),
        "layer_weights": {
            kind: jax.jit(functools.partial(mod.layer_weights, cfg, kind=kind))
            for kind in kinds},
        "layer": {
            kind: jax.jit(functools.partial(mod.layer, cfg, control=control,
                                            kind=kind))
            for kind in kinds},
        "logits": jax.jit(lambda x, w: matmul(mod.final_norm(cfg, x), w,
                                              control)),
    }


def forward(cfg: dict, key_seed: int, tokens: np.ndarray, first: int,
            control: bool = False, rows_per_block: Optional[int] = None,
            ) -> np.ndarray:
    """Logits (rows, seq - first, vocab), float32, of positions
    ``first..seq-1`` of ``tokens`` (rows, seq).

    With ``control`` every matrix product runs on float8-rounded operands.
    ``rows_per_block`` bounds the rows one attention call holds.
    """
    mod = arch.load(cfg)
    rows = tokens.shape[0]
    block = rows_per_block or rows
    run = _programs(json.dumps(cfg, sort_keys=True), control)
    k_emb, layer_keys, k_head = _keys(cfg, key_seed)
    with jax.default_matmul_precision("highest"):
        table = run["table"](k_emb)
        x = table[jnp.asarray(tokens)]
        if control:
            x = f8(x, -1)
        head = table.T if mod.tied(cfg) else None
        del table
        for lk, kind in zip(layer_keys, _kinds(mod, cfg)):
            w = run["layer_weights"][kind](lk)
            layer = run["layer"][kind]
            x = jnp.concatenate([layer(w, x[i:i + block])
                                 for i in range(0, rows, block)])
            del w
        if head is None:
            head = run["head"](k_head)
        x = x[:, first:]
        out = [np.asarray(run["logits"](x[i:i + 1], head)) for i in range(rows)]
    return np.concatenate(out)
