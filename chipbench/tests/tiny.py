"""A tiny cell of the qwen2 architecture for tests: the real served path
and reference at widths a CPU test (or a trace small enough to commit)
can hold."""
from __future__ import annotations

import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
ROOT = BENCH.parent

CELL = "tiny.chat"


def make_root(root: Path, replicas: int = 1, rate: float = 20.0,
              max_gap: float = 0.016) -> Path:
    """A checkout-shaped directory whose BENCHMARK.json holds one tiny cell
    reporting every metric of the real benchmark. The default ``max_gap``
    lies between the tiny program's largest reading on the CPU over 12
    seeds (0.0080) and the float8 control's smallest (0.0278)."""
    (root / "chipbench" / "traffic").mkdir(parents=True, exist_ok=True)
    (root / "chipbench" / "configs").mkdir(parents=True, exist_ok=True)
    with open(BENCH / "configs" / "qwen2-0.5b.json") as f:
        cfg = json.load(f)
    cfg.update(name="tiny", hidden_size=64, intermediate_size=128,
               num_hidden_layers=2, num_attention_heads=4,
               num_key_value_heads=2, vocab_size=512,
               engine={"batch_buckets": [1, 4]},
               check={"tokens": 64, "max_gap": max_gap})
    with open(root / "chipbench" / "configs" / "tiny.json", "w") as f:
        json.dump(cfg, f)
    mix = dict(prompt_len=16, gen_len=8, slo_ms=1500, shape_seed=3, rate=rate,
               replicas=replicas)
    with open(root / "chipbench" / "traffic" / "tiny.json", "w") as f:
        json.dump(mix, f)
    with open(ROOT / "BENCHMARK.json") as f:
        bench = json.load(f)
    bench["configs"] = [{"name": "tiny", "source": "tests",
                         "file": "chipbench/configs/tiny.json", "reduced": [],
                         "why": "tests"}]
    bench["workloads"] = [{"name": CELL, "config": "tiny", "traffic": "tiny",
                           "chips": 1, "why": "tests"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        m.pop("workloads", None)
    with open(root / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    return root
