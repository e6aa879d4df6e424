"""Architectures the harness knows, one module per ``model_type``.

``load(cfg)`` finds ``arch/<model_type>.py`` by file, as ``run.reader``
finds ``metrics/<name>.py``, so a configuration of a new architecture
comes with new files alone. A module holds everything the harness knows
of its architecture; ``reference.py`` and ``work.py`` keep what every
architecture shares, and the module imports them.

A module gives:

* ``program(cfg)``: the program's ``ModelConfig`` for the configuration.
* For ``reference.py``: ``tied(cfg)``, whether the output head is the
  embedding table; ``layer_weights(cfg, key, kind)``, one decoder layer's
  weights by the recipe the program's ``Model.init`` follows;
  ``layer(cfg, w, x, control, kind)``, that layer's float32 forward over
  ``x`` (rows, seq, hidden), every matrix product through
  ``reference.matmul`` so that ``control`` rounds its operands to float8;
  ``final_norm(cfg, x)``, the norm before the output head. Where the
  layers differ (a leading dense layer before expert layers, say),
  ``layer_kind(cfg, index)`` names each layer's kind, and the two layer
  functions get it as ``kind``; without it every layer's kind is None.
* For ``check.py``: ``widest(cfg, seq)``, the float32 values one position
  of one row holds at once at the widest point of a layer (attention
  scores over ``seq`` keys, or the feed-forward's hidden activation).
* For ``work.py``: ``layer_params(cfg)``, the weights of one decoder layer
  that a token multiplies; ``kv_bytes_per_position(cfg)``, the cache one
  position holds over all layers. Optionally ``weight_bytes(cfg)``,
  ``prefill(cfg, rows, prompt_len)`` and ``decode(cfg, rows, prompt_len,
  steps)``, each a ``work.Work`` where it is given, for an architecture
  whose reads are not every layer's weights once per step (sparse
  experts, whose reads depend on the experts the rows touch); without
  them ``work.py``'s dense counts apply.
"""
from __future__ import annotations

import functools
import importlib.util
import re
from pathlib import Path

#: Where the modules are looked for.
DIR = Path(__file__).resolve().parent


def load(cfg: dict):
    """The module of the configuration's ``model_type``. Stops, naming the
    file it looked for, where there is none."""
    kind = cfg["model_type"]
    path = DIR / f"{kind}.py"
    if not re.fullmatch(r"[A-Za-z0-9_]+", kind) or not path.is_file():
        raise SystemExit(f"no architecture module for model_type {kind!r}: "
                         f"looked for {path}")
    return _module(path)


def dims(cfg: dict):
    """(hidden, query heads, key/value heads, head size) of a
    configuration: ``head_dim`` where it is given, else hidden over heads."""
    d = cfg["hidden_size"]
    hq = cfg["num_attention_heads"]
    return d, hq, cfg["num_key_value_heads"], cfg.get("head_dim") or d // hq


@functools.lru_cache(maxsize=None)
def _module(path: Path):
    spec = importlib.util.spec_from_file_location(
        f"chipbench_arch_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
