"""starcoder2: LayerNorm with bias, grouped-query attention, rotary
embeddings, tanh GELU (``gelu_pytorch_tanh``), a bias on every projection
where ``use_bias`` is set, untied output head (Starcoder2ForCausalLM).

One layer's weight recipe: its key splits (attention, mlp, unused);
attention splits (q, k, v, o) and the mlp (in, out).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

import arch
import reference as ref
import work


def program(cfg: dict):
    from repro.configs.base import ModelConfig

    d, hq, hkv, hd = arch.dims(cfg)
    return ModelConfig(
        name=cfg["name"], family="dense", num_layers=cfg["num_hidden_layers"],
        d_model=d, num_heads=hq, num_kv_heads=hkv, head_dim=hd,
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        tie_embeddings=cfg["tie_word_embeddings"],
        rope_theta=float(cfg["rope_theta"]),
        max_seq_len=cfg["max_position_embeddings"],
        param_dtype=cfg["torch_dtype"], compute_dtype=cfg["torch_dtype"],
        activation="gelu", norm="layernorm", qkv_bias=cfg["use_bias"],
        mlp_bias=cfg["use_bias"])


# ---------------------------------------------------------------- reference
def tied(cfg: dict) -> bool:
    return cfg["tie_word_embeddings"]


def layer_weights(cfg: dict, key, kind=None):
    d, hq, hkv, hd = arch.dims(cfg)
    f = cfg["intermediate_size"]
    stored = ref.STORED[cfg["torch_dtype"]]
    ka, km, _ = jax.random.split(key, 3)
    kq, kk, kv, ko = jax.random.split(ka, 4)
    k1, k2 = jax.random.split(km)
    w = {
        "wq": ref.normal(kq, (d, hq * hd), 1.0 / math.sqrt(d), stored),
        "wk": ref.normal(kk, (d, hkv * hd), 1.0 / math.sqrt(d), stored),
        "wv": ref.normal(kv, (d, hkv * hd), 1.0 / math.sqrt(d), stored),
        "wo": ref.normal(ko, (hq * hd, d), 1.0 / math.sqrt(hq * hd), stored),
        "wi": ref.normal(k1, (d, f), 1.0 / math.sqrt(d), stored),
        "wd": ref.normal(k2, (f, d), 1.0 / math.sqrt(f), stored),
        "n1": jnp.ones(d), "n1b": jnp.zeros(d),
        "n2": jnp.ones(d), "n2b": jnp.zeros(d),
    }
    if cfg["use_bias"]:
        w.update(bq=jnp.zeros(hq * hd), bk=jnp.zeros(hkv * hd),
                 bv=jnp.zeros(hkv * hd), bo=jnp.zeros(d), bi=jnp.zeros(f),
                 bd=jnp.zeros(d))
    return w


def _gelu_tanh(h):
    c = math.sqrt(2.0 / math.pi)
    return 0.5 * h * (1.0 + jnp.tanh(c * (h + 0.044715 * h ** 3)))


def layer(cfg: dict, w, x, control: bool, kind=None):
    """One decoder layer over x: (rows, seq, hidden)."""
    d, hq, hkv, hd = arch.dims(cfg)
    eps = cfg["norm_epsilon"]
    r, s, _ = x.shape
    h = ref.layer_norm(x, w["n1"], w["n1b"], eps)
    q = ref.matmul(h, w["wq"], control) + w.get("bq", 0.0)
    k = ref.matmul(h, w["wk"], control) + w.get("bk", 0.0)
    v = ref.matmul(h, w["wv"], control) + w.get("bv", 0.0)
    q = ref.rope(q.reshape(r, s, hq, hd), cfg["rope_theta"])
    k = ref.rope(k.reshape(r, s, hkv, hd), cfg["rope_theta"])
    att = ref.causal_attention(q, k, v.reshape(r, s, hkv, hd))
    x = x + ref.matmul(att, w["wo"], control) + w.get("bo", 0.0)
    h = ref.layer_norm(x, w["n2"], w["n2b"], eps)
    h = _gelu_tanh(ref.matmul(h, w["wi"], control) + w.get("bi", 0.0))
    return x + ref.matmul(h, w["wd"], control) + w.get("bd", 0.0)


def final_norm(cfg: dict, x):
    return ref.layer_norm(x, 1.0, 0.0, cfg["norm_epsilon"])


def widest(cfg: dict, seq: int) -> int:
    # the feed-forward's hidden activation and its GELU, held at once
    return max(cfg["num_attention_heads"] * seq, 2 * cfg["intermediate_size"])


# ---------------------------------------------------------------- work
def layer_params(cfg: dict) -> int:
    """q, k, v and o, then the GELU feed-forward's up and down (no norms)."""
    d, hq, hkv, hd = arch.dims(cfg)
    return d * hd * (hq + 2 * hkv) + hq * hd * d + 2 * d * cfg["intermediate_size"]


def kv_bytes_per_position(cfg: dict) -> float:
    _, _, hkv, hd = arch.dims(cfg)
    return cfg["num_hidden_layers"] * 2 * hkv * hd * work.DTYPE_BYTES[cfg["torch_dtype"]]
