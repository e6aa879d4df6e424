"""Serving driver: MLProxy fronting the JAX engine (+ replica pool).

The hybrid loop: simulated arrivals drive the proxy in virtual time; every
dispatched batch executes a real bucketed prefill+decode on this host and
the measured wall time is the upstream latency the Smart Monitor learns
from. ``--snapshot`` persists the control-plane state so a restarted proxy
resumes with learned latency statistics (fault tolerance of the paper's
component itself).

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b \
        --rate 40 --duration 60 [--snapshot /tmp/proxy_state.json]
"""
from __future__ import annotations

import argparse
import json
import os

import jax

from repro.configs import ARCH_IDS, get_config
from repro.core import OptimizerConfig, SLAConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.serverless.platform import PlatformConfig
from repro.serving.batcher import EngineBackedLatency
from repro.serving.engine import EngineConfig, InferenceEngine
from repro.simulation.arrivals import PoissonProcess
from repro.simulation.simulator import Simulator


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--arch", default="qwen2-0.5b", choices=list(ARCH_IDS))
    p.add_argument("--rate", type=float, default=40.0)
    p.add_argument("--duration", type=float, default=60.0)
    p.add_argument("--slo-ms", type=float, default=2000.0)
    p.add_argument("--gen-len", type=int, default=4)
    p.add_argument("--full-size", action="store_true",
                   help="full config (needs accelerators); default reduced")
    p.add_argument("--snapshot", default=None,
                   help="path to persist/restore proxy control-plane state")
    args = p.parse_args()

    enable_compile_cache()
    cfg = get_config(args.arch)
    if not args.full_size:
        cfg = cfg.reduced()
    ecfg = EngineConfig(batch_buckets=(1, 2, 4, 8, 16, 32),
                        prompt_buckets=(16,), max_len=16 + args.gen_len + 8,
                        gen_len=args.gen_len)
    engine = InferenceEngine(cfg, ecfg, rng=jax.random.PRNGKey(0))
    print(f"[serve] compiling buckets for {cfg.name} ...")
    engine.warmup(plen=16)
    print(f"[serve] {engine.compile_count} programs cached")

    sla = SLAConfig(slo_target=args.slo_ms / 1000.0)
    sim = Simulator(
        policy="mlproxy", sla=sla,
        workload=EngineBackedLatency(engine, prompt_len=16,
                                     gen_len=args.gen_len),
        arrivals=PoissonProcess(rate=args.rate, duration=args.duration),
        platform_config=PlatformConfig(initial_scale=1, cold_start=0.5),
        duration=args.duration, seed=0,
        policy_kwargs={"bucketing": "pow2",
                       "optimizer": OptimizerConfig(update_interval=5.0,
                                                    initial_max_bs=2)},
    )
    if args.snapshot and os.path.exists(args.snapshot):
        with open(args.snapshot) as f:
            sim.policy.restore(json.load(f))
        print(f"[serve] restored proxy state (Max_BS={sim.policy.max_bs})")

    res = sim.run()
    s = res.summary
    print(f"[serve] {s['completed']:.0f} requests, "
          f"{engine.stats['batches']:.0f} JAX batches, "
          f"avg batch {s['avg_batch_size']:.2f}, P95 {s['p95']*1000:.0f} ms, "
          f"violations {s['violation_pct']:.2f}%")
    if args.snapshot:
        state = sim.policy.snapshot()
        with open(args.snapshot, "w") as f:
            json.dump(state, f, default=lambda o: getattr(o, "__dict__", str(o)))
        print(f"[serve] proxy state saved → {args.snapshot}")


if __name__ == "__main__":
    main()
