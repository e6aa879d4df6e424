"""Find a cell's knee once, on the chip: serve its mix at several fixed
rates through one warm pool and report, per rate, what decides the knee.

    python3 chipbench/sweep.py --workload <cell> --rates 30,45,60 --seconds 20

The knee is the highest rate at which at least 95% of the requests sent
meet the mix's SLO with no growing backlog (the drain after the window
stays short and the queue at its end small). Also prints the batch-1
latency at the mix's shapes, from which the SLO is set: 5 times it,
rounded up to 50 ms. The rate and SLO found are written into the mix's
file by hand; no run reads this script's output.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time

import numpy as np

import run as harness  # sets up the import paths

import readings
import serve


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", required=True)
    p.add_argument("--seconds", type=float, default=20.0)
    p.add_argument("--seed", type=int, default=7)
    args = p.parse_args(argv)

    import jax

    from repro.launch.compile_cache import enable_compile_cache

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"no TPU: JAX found {devices[0].platform}", file=sys.stderr)
        return 2
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    _, cell, config, traffic = harness.cell_files(harness.ROOT, args.workload)
    t0 = time.perf_counter()
    pool, warm, _, _, counter = harness.build(config, traffic, devices,
                                              args.seed)
    plen = traffic["prompt_len"]
    b1 = warm[(1, plen)]
    print(f"set-up {time.perf_counter() - t0:.6f} s; warm-up seconds per "
          f"bucket {json.dumps({str(k[0]): v for k, v in warm.items()})}")
    print(f"batch-1 latency {1e3 * b1:.6f} ms -> SLO "
          f"{50 * math.ceil(5 * b1 * 1e3 / 50)} ms (the mix states "
          f"{traffic['slo_ms']} ms)", flush=True)
    rows = []
    for rate in [float(r) for r in args.rates.split(",")]:
        mix = dict(traffic, rate=rate)
        w = serve.run_window(pool, mix, args.seconds, args.seed,
                             vocab=config["vocab_size"], warm=warm,
                             counter=counter)
        lat = readings.latencies_s(w)
        queued = int(np.count_nonzero(~(w.dispatched <= w.seconds)))
        row = {
            "rate": rate, "sent": len(w.due),
            "attain_pct": 100 * float(np.mean(lat <= mix["slo_ms"] / 1e3)),
            "p50_ms": 1e3 * readings.rank(lat, 50),
            "p95_ms": 1e3 * readings.rank(lat, 95),
            "late_p95_ms": 1e3 * readings.rank(w.sent - w.due, 95),
            "drain_s": w.ended - w.seconds,
            "queued_at_end": queued,
            "mean_batch": float(np.mean([n for _, n, _ in w.batches])),
            "tokens_per_s": float(np.count_nonzero(w.done <= w.seconds))
            * mix["gen_len"] / w.seconds,
            "compiles": w.compiles,
        }
        rows.append(row)
        print(json.dumps(row), flush=True)
    dev = devices[0]
    print(json.dumps({"workload": args.workload, "device": dev.device_kind,
                      "replicas": len(pool.replicas), "rows": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
