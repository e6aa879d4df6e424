"""qwen2: RMSNorm, grouped-query attention with biases on q, k and v only,
rotary embeddings, SwiGLU, tied output head (Qwen2ForCausalLM).

One layer's weight recipe: its key splits (attention, mlp, unused);
attention splits (q, k, v, o) and the mlp (in, out). The mlp's input
projection holds gate and up side by side.
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
        activation="silu", norm="rmsnorm", qkv_bias=True, mlp_bias=False)


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
    return {
        "wq": ref.normal(kq, (d, hq * hd), 1.0 / math.sqrt(d), stored),
        "wk": ref.normal(kk, (d, hkv * hd), 1.0 / math.sqrt(d), stored),
        "wv": ref.normal(kv, (d, hkv * hd), 1.0 / math.sqrt(d), stored),
        "wo": ref.normal(ko, (hq * hd, d), 1.0 / math.sqrt(hq * hd), stored),
        "wi": ref.normal(k1, (d, 2 * f), 1.0 / math.sqrt(d), stored),
        "wd": ref.normal(k2, (f, d), 1.0 / math.sqrt(f), stored),
        "bq": jnp.zeros(hq * hd), "bk": jnp.zeros(hkv * hd),
        "bv": jnp.zeros(hkv * hd), "n1": jnp.ones(d), "n2": jnp.ones(d),
    }


def layer(cfg: dict, w, x, control: bool, kind=None):
    """One decoder layer over x: (rows, seq, hidden)."""
    d, hq, hkv, hd = arch.dims(cfg)
    eps = cfg["rms_norm_eps"]
    r, s, _ = x.shape
    h = ref.rms_norm(x, w["n1"], eps)
    q = ref.matmul(h, w["wq"], control) + w["bq"]
    k = ref.matmul(h, w["wk"], control) + w["bk"]
    v = ref.matmul(h, w["wv"], control) + w["bv"]
    q = ref.rope(q.reshape(r, s, hq, hd), cfg["rope_theta"])
    k = ref.rope(k.reshape(r, s, hkv, hd), cfg["rope_theta"])
    att = ref.causal_attention(q, k, v.reshape(r, s, hkv, hd))
    x = x + ref.matmul(att, w["wo"], control)
    h = ref.swiglu(ref.matmul(ref.rms_norm(x, w["n2"], eps), w["wi"], control))
    return x + ref.matmul(h, w["wd"], control)


def final_norm(cfg: dict, x):
    return ref.rms_norm(x, 1.0, cfg["rms_norm_eps"])


def widest(cfg: dict, seq: int) -> int:
    return max(cfg["num_attention_heads"] * seq, 2 * cfg["intermediate_size"])


# ---------------------------------------------------------------- work
def layer_params(cfg: dict) -> int:
    """q, k, v and o, then SwiGLU's gate, up and down (no norms)."""
    d, hq, hkv, hd = arch.dims(cfg)
    return d * hd * (hq + 2 * hkv) + hq * hd * d + 3 * d * cfg["intermediate_size"]


def kv_bytes_per_position(cfg: dict) -> float:
    _, _, hkv, hd = arch.dims(cfg)
    return cfg["num_hidden_layers"] * 2 * hkv * hd * work.DTYPE_BYTES[cfg["torch_dtype"]]
