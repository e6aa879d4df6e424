"""Data-plane microbenchmark: fused decode loop + bucket-aware packing.

Two halves, matching the two layers of the fast data plane:

* **decode** — tokens/s of the fused ``lax.scan`` decode loop vs the
  per-token reference loop on the same tiny model and SAME parameters
  (bit-identity is asserted before any timing). The gap is pure
  Python→XLA dispatch overhead: the per-token loop pays one device
  round-trip per generated token, the fused loop pays one per batch.
  ``per_token_dispatch_us`` is that overhead, measured as the per-step
  time difference between the two loops.
* **packing** — a ``bench_live_parity``-style run of the live runtime
  (FakeClock + :class:`SyntheticTarget` with engine-shaped
  ``batch_buckets``) at equal SLA, with and without bucket-aware packing
  (``pack=True``): the policy's full-trigger rounds its batch target up
  to the next bucket edge and dispatches exactly at it, so "full"
  batches execute with zero padding. Cost at equal SLA = dispatched
  upstream batches + padding waste (bucket slots burned on padding are
  paid compute on a fixed-shape engine).

Decode-half acceptance: fused ≥ 3x tokens/s on the decode-dominated
config (gen_len ≥ 32, small bucket) with bit-identical outputs — the
harness headline is the best bucket's speedup, gated to 0 if ANY bucket
diverges from the reference loop. Packing-half acceptance: mean padding
waste strictly drops at equal SLA.
"""
from __future__ import annotations

import statistics
import time
from typing import Dict, List

import numpy as np

from benchmarks.common import write_csv

#: Engine-shaped bucket grid shared by both packing runs.
PACK_BUCKETS = (1, 2, 4, 8)


def _tiny_model_cfg():
    """1-layer model small enough that decode is dispatch-dominated —
    the regime the fused loop targets (any real model is *more* work per
    dispatch, so the fused win only grows with model size)."""
    from repro.configs.base import ModelConfig

    return ModelConfig(
        name="bench-engine-tiny", family="dense",
        num_layers=1, d_model=16, num_heads=1, num_kv_heads=1,
        head_dim=16, d_ff=32, vocab_size=64, max_seq_len=256,
        param_dtype="float32", compute_dtype="float32",
        remat=False, scan_layers=False,
    )


def _time_generate(engine, prompts, gen_len: int, budget_s: float) -> float:
    """Median wall seconds per generate() call over a time-budgeted loop
    (median, not mean: one scheduler hiccup must not skew a µs-scale
    dispatch-overhead measurement)."""
    engine.generate(prompts, gen_len=gen_len)  # ensure compiled
    samples: List[float] = []
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < budget_s or len(samples) < 5:
        t1 = time.perf_counter()
        engine.generate(prompts, gen_len=gen_len)
        samples.append(time.perf_counter() - t1)
    return statistics.median(samples)


def decode_rows(quick: bool) -> List[Dict]:
    import jax

    from repro.serving.engine import EngineConfig, InferenceEngine

    cfg = _tiny_model_cfg()
    gen_len = 64 if quick else 128
    plen = 8
    buckets = (1,) if quick else (1, 2, 4)
    budget = 0.5 if quick else 2.0
    max_len = plen + gen_len + 8

    # One set of parameters shared by every engine variant: the fused vs
    # per-token comparison is loop structure only.
    template = InferenceEngine(
        cfg, EngineConfig(batch_buckets=(max(buckets),), prompt_buckets=(plen,),
                          max_len=max_len, gen_len=gen_len),
        rng=jax.random.PRNGKey(0))
    params = template.params

    rows: List[Dict] = []
    rng = np.random.default_rng(0)
    for bucket in buckets:
        ecfg = dict(batch_buckets=(bucket,), prompt_buckets=(plen,),
                    max_len=max_len, gen_len=gen_len)
        fused = InferenceEngine(cfg, EngineConfig(**ecfg), params=params)
        unfused = InferenceEngine(
            cfg, EngineConfig(fused_decode=False, cache_pool=False, **ecfg),
            params=params)

        # Bit-identity gate: same params, same prompts, token-for-token
        # equal across several draws before any timing is trusted.
        identical = True
        for _ in range(3):
            prompts = rng.integers(0, cfg.vocab_size, (bucket, plen),
                                   dtype=np.int64).astype(np.int32)
            a, _ = fused.generate(prompts, gen_len=gen_len)
            b, _ = unfused.generate(prompts, gen_len=gen_len)
            identical = identical and bool(np.array_equal(a, b))

        prompts = rng.integers(0, cfg.vocab_size, (bucket, plen),
                               dtype=np.int64).astype(np.int32)
        fused_s = _time_generate(fused, prompts, gen_len, budget)
        unfused_s = _time_generate(unfused, prompts, gen_len, budget)
        speedup = unfused_s / fused_s
        # Per generated token (beyond the first, which both paths produce
        # from prefill logits), the per-token loop pays one extra
        # Python→XLA dispatch; the fused loop amortizes all of them.
        dispatch_us = (unfused_s - fused_s) / (gen_len - 1) * 1e6
        rows.append({
            "kind": "decode",
            "bucket": bucket,
            "gen_len": gen_len,
            "bit_identical": identical,
            "fused_tok_per_s": round(bucket * gen_len / fused_s, 1),
            "unfused_tok_per_s": round(bucket * gen_len / unfused_s, 1),
            "fused_ms_per_batch": round(fused_s * 1e3, 3),
            "unfused_ms_per_batch": round(unfused_s * 1e3, 3),
            "speedup": round(speedup, 2),
            "per_token_dispatch_us": round(dispatch_us, 1),
            "fused_compiles": fused.compile_count,
            "unfused_compiles": unfused.compile_count,
            "fused_cache_allocs": fused.cache_allocs,
            "unfused_cache_allocs": unfused.cache_allocs,
        })
    return rows


def packing_rows(quick: bool) -> List[Dict]:
    from repro.core import SLAConfig, ms
    from repro.runtime import FakeClock, SyntheticTarget, run_replay
    from repro.serverless.latency import get_workload
    from repro.simulation.arrivals import (PoissonProcess, Schedule,
                                           sample_schedule)

    duration = 120.0 if quick else 600.0
    wl = get_workload("pytorch-fashion-mnist")
    sla = SLAConfig(slo_target=ms(500))
    times = sample_schedule(PoissonProcess(rate=30.0, duration=duration),
                            7, duration)

    rows: List[Dict] = []
    for packed in (False, True):
        clk = FakeClock()
        target = SyntheticTarget(wl, clk,
                                 rng=np.random.default_rng(11),
                                 batch_buckets=PACK_BUCKETS)
        kwargs = {} if packed else {"bucketing": PACK_BUCKETS}
        res = run_replay(
            policy="mlproxy", sla=sla, workload=wl,
            arrivals=Schedule(times), duration=duration, seed=7,
            target=target, clock=clk, policy_kwargs=kwargs, pack=packed,
        )
        s = res.summary
        rows.append({
            "kind": "packing",
            "packed": packed,
            "requests": int(len(times)),
            "completed": s["completed"],
            "violation_pct": round(s["violation_pct"], 3),
            "padding_waste_pct": round(s["padding_waste"] * 100, 3),
            "dispatched_batches": s["dispatched_batches"],
            "avg_batch_size": round(s["avg_batch_size"], 3),
            "upstream_batches": target.batches,
        })
    return rows


def run(quick: bool = False) -> List[Dict]:
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    rows = decode_rows(quick)
    rows += packing_rows(quick)
    write_csv("engine.csv", rows)
    return rows


if __name__ == "__main__":
    for r in run(quick=True):
        print(r)
