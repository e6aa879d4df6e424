"""Figures 3 & 4: relative response time and relative time-per-inference
vs batch size, per workload, with the linear baseline.

Two data sources:
  * the calibrated Table-2 latency models (what the Table-3 simulations
    use) — deterministic means;
  * a REAL measurement: the JAX :class:`InferenceEngine` running a reduced
    qwen2 config on this host across batch buckets (the engine-measured
    curve is the serving-stack ground truth for batching sub-linearity).
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from repro.serverless.latency import PAPER_WORKLOADS

from benchmarks.common import write_csv

BATCH_SIZES = (1, 2, 4, 8, 12, 16, 20, 24, 32)


def model_curves() -> List[Dict]:
    rows = []
    for name, model in PAPER_WORKLOADS.items():
        base = model.mean(1)
        for bs in BATCH_SIZES:
            rt = model.mean(bs)
            rows.append({
                "workload": name,
                "batch_size": bs,
                "rt_ms": rt * 1000,
                "relative_rt": rt / base,            # Fig. 3
                "relative_per_inference": (rt / bs) / base,  # Fig. 4
                "linear_baseline_rt": float(bs),
                "linear_baseline_per_inference": 1.0,
            })
    return rows


def engine_curve(gen_len: int = 4, repeats: int = 3) -> List[Dict]:
    import jax

    from repro.configs import get_config
    from repro.serving.engine import EngineConfig, InferenceEngine

    cfg = get_config("qwen2-0.5b").reduced()
    ecfg = EngineConfig(batch_buckets=(1, 2, 4, 8, 16, 32),
                        prompt_buckets=(16,), max_len=32, gen_len=gen_len)
    eng = InferenceEngine(cfg, ecfg, rng=jax.random.PRNGKey(0))
    eng.warmup(plen=16)
    rng = np.random.default_rng(0)
    rows = []
    base = None
    for bs in ecfg.batch_buckets:
        times = []
        for _ in range(repeats):
            prompts = rng.integers(0, cfg.vocab_size, (bs, 16)).astype(np.int32)
            _, t = eng.generate(prompts, gen_len=gen_len)
            times.append(t["latency_s"])
        rt = float(np.median(times))
        base = rt if base is None else base
        rows.append({
            "workload": "jax-engine-qwen2-smoke",
            "batch_size": bs,
            "rt_ms": rt * 1000,
            "relative_rt": rt / base,
            "relative_per_inference": (rt / bs) / base,
            "linear_baseline_rt": float(bs),
            "linear_baseline_per_inference": 1.0,
        })
    return rows


def run(quick: bool = False) -> List[Dict]:
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    rows = model_curves()
    rows += engine_curve(repeats=1 if quick else 3)
    write_csv("fig3_fig4_batch_scaling.csv", rows)
    return rows


if __name__ == "__main__":
    for r in run():
        print(r)
