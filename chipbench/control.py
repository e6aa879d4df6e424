"""Read the readings that a cell's ``max_gap`` limit is set from, on the chip.

    python3 chipbench/control.py --workload <cell> --seeds 1,2,3 --control 3 --seconds 10

Runs the cell once per seed in this one process, each with its own
weights and prompts, and reads the widest gap of the served tokens (the
program's reading). The first ``--control`` seeds are control runs: the
reference at float8 stands in the program's place, its tokens read at the
same positions of the same prompts and served tokens, and its gap goes
through the harness's own comparison, so such a run has to report
``correct`` false. The benchmark's own runs never run the control. Prints
one JSON line per seed and a summary: the largest program reading, the
smallest control reading, and whether every control run came out not
correct.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import run as harness


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--control", type=int, default=3)
    p.add_argument("--seconds", type=float, default=10.0)
    args = p.parse_args(argv)

    import jax

    from repro.launch.compile_cache import enable_compile_cache

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"no TPU: JAX found {devices[0].platform}", file=sys.stderr)
        return 2
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    program, control, control_correct = [], [], []
    for k, seed in enumerate(int(s) for s in args.seeds.split(",")):
        is_control = k < args.control
        res = harness.run_cell(args.workload, seed, args.seconds, False,
                               devices, time.perf_counter(),
                               control=is_control)
        gap = res["compared"]["max_gap"]
        program.append(gap["program"] if is_control else gap["value"])
        if is_control:
            control.append(gap["value"])
            control_correct.append(res["correct"])
        print(json.dumps({"seed": seed, "control_run": is_control,
                          "correct": res["correct"], "gap": program[-1],
                          "control_gap": gap["value"] if is_control else None,
                          "limit": gap["limit"],
                          "unserved": res["compared"]["unserved"]["value"],
                          "setup_s": res["metrics"]["setup_s"]["value"]}),
              flush=True)
    print(json.dumps({"workload": args.workload, "seeds": len(program),
                      "program_max": max(program),
                      "control_min": min(control) if control else None,
                      "control_runs_correct": control_correct,
                      "program": program, "control": control}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
