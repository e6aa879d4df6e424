"""Operations and bytes each engine program needs, from a configuration's
shapes alone.

What a layer holds is the architecture's own count (``arch/<model_type>.py``:
the weights a token multiplies, the cache one position holds, and, where
its reads depend on the rows, its own ``prefill`` and ``decode``); this
file holds the arithmetic every architecture shares, and the counts of a
dense decoder, which read every layer's weights once per step.

Counted from the published shapes and the cache length in use, never from
a compiler's cost analysis, so a roofline share reads the same work
whatever implements it. Matrix products count 2 operations per
multiply-add. Attention counts the causal pairs it needs, not a padded
square. Bytes are the least a program must move between memory and the
chip: every weight it reads once, in its stored type, the keys and values it reads and
writes, and nothing for activations that a fused program keeps on chip.
"""
from __future__ import annotations

import dataclasses
from typing import Dict

import arch


@dataclasses.dataclass(frozen=True)
class Work:
    flops: float
    bytes: float

    def __add__(self, other: "Work") -> "Work":
        return Work(self.flops + other.flops, self.bytes + other.bytes)

    def least_seconds(self, peaks: Dict[str, float]) -> float:
        """Least time at the chip's peaks: the larger of the two bounds."""
        return max(self.flops / peaks["flops_per_s"],
                   self.bytes / peaks["bytes_per_s"])


#: Bytes of one stored weight, by the configuration's ``torch_dtype``.
DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def layer_params(cfg: dict) -> int:
    """Weights of one decoder layer that a token multiplies (no norms), as
    the architecture's module counts them."""
    return arch.load(cfg).layer_params(cfg)


def kv_bytes_per_position(cfg: dict) -> float:
    """Bytes of the cache one position holds over all layers, as the
    architecture's module counts them."""
    return arch.load(cfg).kv_bytes_per_position(cfg)


def weight_bytes(cfg: dict) -> float:
    """Bytes of every weight a forward pass reads: the layers, and the
    output head (the embedding table when it is tied; a lookup of the
    table reads only its rows, so an untied table is not counted)."""
    mod = arch.load(cfg)
    if hasattr(mod, "weight_bytes"):
        return mod.weight_bytes(cfg)
    width = DTYPE_BYTES[cfg["torch_dtype"]]
    head = cfg["vocab_size"] * cfg["hidden_size"]
    return width * (cfg["num_hidden_layers"] * mod.layer_params(cfg) + head)


def _attention_flops(cfg: dict, queries: int, first_key_len: int) -> float:
    """Score and value products of ``queries`` consecutive positions, the
    first of which sees ``first_key_len`` keys (itself included)."""
    _, hq, _, hd = arch.dims(cfg)
    pairs = queries * first_key_len + queries * (queries - 1) / 2
    return cfg["num_hidden_layers"] * 4.0 * hq * hd * pairs


def prefill(cfg: dict, rows: int, prompt_len: int) -> Work:
    """The prefill program: ``rows`` prompts of ``prompt_len`` tokens,
    keys and values written for every position, logits for the last."""
    mod = arch.load(cfg)
    if hasattr(mod, "prefill"):
        return mod.prefill(cfg, rows, prompt_len)
    tokens = rows * prompt_len
    flops = (2.0 * cfg["num_hidden_layers"] * layer_params(cfg) * tokens
             + rows * _attention_flops(cfg, prompt_len, 1)
             + 2.0 * rows * cfg["hidden_size"] * cfg["vocab_size"])
    nbytes = weight_bytes(cfg) + tokens * kv_bytes_per_position(cfg)
    return Work(flops, nbytes)


def decode(cfg: dict, rows: int, prompt_len: int, steps: int) -> Work:
    """The fused decode program: ``steps`` greedy steps after a prompt of
    ``prompt_len``; step ``i`` attends to ``prompt_len + i + 1`` positions
    and reads the weights, the head and that much cache once."""
    mod = arch.load(cfg)
    if hasattr(mod, "decode"):
        return mod.decode(cfg, rows, prompt_len, steps)
    per_token = (2.0 * cfg["num_hidden_layers"] * layer_params(cfg)
                 + 2.0 * cfg["hidden_size"] * cfg["vocab_size"])
    flops = rows * (steps * per_token
                    + _attention_flops(cfg, steps, prompt_len + 1))
    positions = steps * (prompt_len + 1) + steps * (steps - 1) / 2
    nbytes = (steps * weight_bytes(cfg)
              + rows * (positions + steps) * kv_bytes_per_position(cfg))
    return Work(flops, nbytes)


def request(cfg: dict, prompt_len: int, gen_len: int) -> Work:
    """One request served alone: its prefill and its ``gen_len - 1``
    decode steps (the first token comes from the prefill's logits)."""
    return prefill(cfg, 1, prompt_len) + decode(cfg, 1, prompt_len, gen_len - 1)
