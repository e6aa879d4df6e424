"""Plain float32 reference of the dense decoder the benchmark serves.

It imports nothing of the program and takes nothing the program made: it
builds the weights itself from the same seed, by the recipe below, and
runs the published architecture in ``jax.numpy`` at float32 under
``default_matmul_precision("highest")``, with no cache, batching or
kernels. Two variants, chosen by the configuration's ``model_type``:

* ``qwen2``: RMSNorm, SwiGLU, biases on q/k/v only, tied output head.
* ``starcoder2``: LayerNorm with bias, tanh GELU (``gelu_pytorch_tanh``),
  a bias on every projection, untied output head.

Both use rotary embeddings over the first and second half of each head
(``rotate_half``), grouped-query attention and a causal mask.

The weight recipe, which the program's ``Model.init`` follows too: from
``PRNGKey(s)``, split (embedding, layers, head); split the layers' key
into one per layer; each layer splits (attention, mlp, unused), attention
splits (q, k, v, o) and the mlp (in, out). A projection of fan-in ``n``
is ``normal / sqrt(n)``, the embedding ``normal * 0.02``, the untied head
``normal / sqrt(hidden)``; every value is rounded to the stored type
(bfloat16). Norm scales are one; every bias is zero.

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
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

F8 = jnp.float8_e4m3fn
F8_MAX = float(jnp.finfo(F8).max)
STORED = {"bfloat16": jnp.bfloat16, "float16": jnp.float16,
          "float32": jnp.float32}


def _arch(cfg: dict) -> dict:
    kind = cfg["model_type"]
    if kind == "qwen2":
        return {"layer_norm": False, "eps": cfg["rms_norm_eps"], "gated": True,
                "qkv_bias": True, "proj_bias": False,
                "tied": cfg["tie_word_embeddings"]}
    if kind == "starcoder2":
        return {"layer_norm": True, "eps": cfg["norm_epsilon"], "gated": False,
                "qkv_bias": cfg["use_bias"], "proj_bias": cfg["use_bias"],
                "tied": cfg["tie_word_embeddings"]}
    raise ValueError(f"no reference for model_type {kind!r}")


def _dims(cfg: dict):
    d = cfg["hidden_size"]
    hq = cfg["num_attention_heads"]
    hkv = cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // hq
    return d, hq, hkv, hd


# ------------------------------------------------------------------ weights
def _normal(key, shape, scale, stored):
    w = jax.random.normal(key, shape) * scale
    return w.astype(stored).astype(jnp.float32)


def _keys(cfg: dict, key_seed: int):
    k_emb, k_layers, k_head = jax.random.split(jax.random.PRNGKey(key_seed), 3)
    return k_emb, jax.random.split(k_layers, cfg["num_hidden_layers"]), k_head


def _layer_weights(cfg: dict, key) -> Dict[str, jax.Array]:
    d, hq, hkv, hd = _dims(cfg)
    f = cfg["intermediate_size"]
    a = _arch(cfg)
    stored = STORED[cfg["torch_dtype"]]
    ka, km, _ = jax.random.split(key, 3)
    kq, kk, kv, ko = jax.random.split(ka, 4)
    k1, k2 = jax.random.split(km)
    wi_out = 2 * f if a["gated"] else f
    w = {
        "wq": _normal(kq, (d, hq * hd), 1.0 / math.sqrt(d), stored),
        "wk": _normal(kk, (d, hkv * hd), 1.0 / math.sqrt(d), stored),
        "wv": _normal(kv, (d, hkv * hd), 1.0 / math.sqrt(d), stored),
        "wo": _normal(ko, (hq * hd, d), 1.0 / math.sqrt(hq * hd), stored),
        "wi": _normal(k1, (d, wi_out), 1.0 / math.sqrt(d), stored),
        "wd": _normal(k2, (f, d), 1.0 / math.sqrt(f), stored),
        "bq": jnp.zeros(hq * hd), "bk": jnp.zeros(hkv * hd),
        "bv": jnp.zeros(hkv * hd), "bo": jnp.zeros(d),
        "bi": jnp.zeros(wi_out), "bd": jnp.zeros(d),
        "n1": jnp.ones(d), "n1b": jnp.zeros(d),
        "n2": jnp.ones(d), "n2b": jnp.zeros(d),
    }
    if not a["qkv_bias"]:
        for k in ("bq", "bk", "bv"):
            del w[k]
    if not a["proj_bias"]:
        for k in ("bo", "bi", "bd"):
            del w[k]
    return w


# ------------------------------------------------------------------ compute
def _f8(x, axis):
    """Round ``x`` to float8 e4m3 with one scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, F8_MAX / amax, 1.0)
    return (x * scale).astype(F8).astype(jnp.float32) / scale


def _matmul(x, w, control: bool):
    if control:
        x, w = _f8(x, -1), _f8(w, None)
    return x @ w


def _norm(a: dict, x, scale, bias):
    if a["layer_norm"]:
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + a["eps"]) * scale + bias
    var = jnp.mean(jnp.square(x), -1, keepdims=True)
    return x * jax.lax.rsqrt(var + a["eps"]) * scale


def _act(a: dict, h):
    if a["gated"]:
        gate, up = jnp.split(h, 2, axis=-1)
        return gate * jax.nn.sigmoid(gate) * up
    c = math.sqrt(2.0 / math.pi)
    return 0.5 * h * (1.0 + jnp.tanh(c * (h + 0.044715 * h ** 3)))


def _rope(x, theta: float):
    """x: (rows, seq, heads, hd); positions 0..seq-1, halves rotated."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv[None]
    c, s = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _layer(cfg: dict, w, x, control: bool):
    """One decoder layer over x: (rows, seq, hidden)."""
    d, hq, hkv, hd = _dims(cfg)
    a = _arch(cfg)
    r, s, _ = x.shape
    h = _norm(a, x, w["n1"], w["n1b"])
    q = _matmul(h, w["wq"], control) + w.get("bq", 0.0)
    k = _matmul(h, w["wk"], control) + w.get("bk", 0.0)
    v = _matmul(h, w["wv"], control) + w.get("bv", 0.0)
    q = _rope(q.reshape(r, s, hq, hd), cfg["rope_theta"])
    k = _rope(k.reshape(r, s, hkv, hd), cfg["rope_theta"])
    v = v.reshape(r, s, hkv, hd)
    g = hq // hkv
    q = q.reshape(r, s, hkv, g, hd)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", q, k) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    att = jnp.einsum("bhgqk,bkhd->bqhgd", jax.nn.softmax(scores, -1), v)
    att = att.reshape(r, s, hq * hd)
    x = x + _matmul(att, w["wo"], control) + w.get("bo", 0.0)
    h = _norm(a, x, w["n2"], w["n2b"])
    h = _act(a, _matmul(h, w["wi"], control) + w.get("bi", 0.0))
    return x + _matmul(h, w["wd"], control) + w.get("bd", 0.0)


@functools.lru_cache(maxsize=None)
def _programs(cfg_key: str, control: bool):
    """The jitted pieces of a forward pass, made once per configuration."""
    cfg = json.loads(cfg_key)
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    stored = STORED[cfg["torch_dtype"]]
    return {
        "table": jax.jit(lambda k: _normal(k, (v, d), 0.02, stored)),
        "head": jax.jit(lambda k: _normal(k, (d, v), 1.0 / math.sqrt(d), stored)),
        "layer_weights": jax.jit(lambda k: _layer_weights(cfg, k)),
        "layer": jax.jit(lambda w, x: _layer(cfg, w, x, control)),
        "logits": jax.jit(lambda x, w: _matmul(
            _norm(_arch(cfg), x, 1.0, 0.0), w, control)),
    }


def forward(cfg: dict, key_seed: int, tokens: np.ndarray, first: int,
            control: bool = False, rows_per_block: Optional[int] = None,
            ) -> np.ndarray:
    """Logits (rows, seq - first, vocab), float32, of positions
    ``first..seq-1`` of ``tokens`` (rows, seq).

    With ``control`` every matrix product runs on float8-rounded operands.
    ``rows_per_block`` bounds the rows one attention call holds.
    """
    a = _arch(cfg)
    rows = tokens.shape[0]
    block = rows_per_block or rows
    run = _programs(json.dumps(cfg, sort_keys=True), control)
    k_emb, layer_keys, k_head = _keys(cfg, key_seed)
    with jax.default_matmul_precision("highest"):
        table = run["table"](k_emb)
        x = table[jnp.asarray(tokens)]
        if control:
            x = _f8(x, -1)
        head = table.T if a["tied"] else None
        del table
        for lk in layer_keys:
            w = run["layer_weights"](lk)
            x = jnp.concatenate([run["layer"](w, x[i:i + block])
                                 for i in range(0, rows, block)])
            del w
        if head is None:
            head = run["head"](k_head)
        x = x[:, first:]
        out = [np.asarray(run["logits"](x[i:i + 1], head)) for i in range(rows)]
    return np.concatenate(out)
