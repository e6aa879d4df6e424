"""Run one benchmark cell once on the chip.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``; its
configuration is the file the entry of ``configs`` names, its traffic
mix ``chipbench/traffic/<traffic>.json``, and each metric the reader
``chipbench/metrics/<metric>.py``. A run makes the weights on the chip
from the seed, warms the cell's shapes only, serves ``--seconds`` of the
mix through the program's proxy (``serve.py``), drains, and checks a
sample of what it served against the float32 reference (``check.py``).

Each run writes its requests, engine calls and batches to
``chipbench/out/runs/<cell>.<seed>.<trace>.json``. With ``--trace 0`` the
last line of standard output reports the cell's end-to-end metrics; with
``--trace 1`` the window's last seconds are traced
(``chipbench/out/trace/<cell>/``; the profiler stops after the drain, and
the trace is reduced in this process, which holds the chip) and it
reports the per-layer metrics. The numbers
compared for ``correct`` come last, on standard error and in the line.
Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result.
"""
from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import List, Optional  # noqa: E402

import numpy as np  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))

import check  # noqa: E402
import schedule  # noqa: E402
import serve  # noqa: E402
import trace_reduce  # noqa: E402

#: Seconds at the end of the window that a ``--trace 1`` run traces: tens
#: of engine calls in every cell.
TRACE_S = 25.0
#: Host span marking the traced part of the window in the trace.
WINDOW_SPAN = "chipbench.window"


@dataclasses.dataclass
class Run:
    """What a metric reader reads (``metrics/<name>.py``: ``read(run)``)."""

    cell: dict
    config: dict
    traffic: dict
    window: serve.Window
    setup_s: float
    trace: Optional[dict]
    peaks: Optional[dict]


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def cell_files(root: Path, name: str):
    """(benchmark, cell, configuration, traffic mix) of the named cell."""
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; have {sorted(cells)}")
    cell = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = load_json(root / entry["file"])
    traffic = load_json(root / "chipbench" / "traffic" / f"{cell['traffic']}.json")
    return bench, cell, config, traffic


def cell_metrics(bench: dict, cell: str, trace: bool) -> List[dict]:
    """The end-to-end metrics the cell reports, or with ``trace`` its
    per-layer metrics."""
    e2e = [m for m in bench["end_to_end"] if cell in m.get("workloads", [cell])]
    if not trace:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in moved)]


def reader(name: str):
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def model_config(config: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.configs.base import ModelConfig

    kind = config["model_type"]
    if kind == "qwen2":
        arch = dict(activation="silu", norm="rmsnorm", qkv_bias=True,
                    mlp_bias=False)
    elif kind == "starcoder2":
        arch = dict(activation="gelu", norm="layernorm",
                    qkv_bias=config["use_bias"], mlp_bias=config["use_bias"])
    else:
        raise SystemExit(f"no program configuration for {kind!r}")
    heads = config["num_attention_heads"]
    return ModelConfig(
        name=config["name"], family="dense",
        num_layers=config["num_hidden_layers"],
        d_model=config["hidden_size"], num_heads=heads,
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config.get("head_dim") or config["hidden_size"] // heads,
        d_ff=config["intermediate_size"], vocab_size=config["vocab_size"],
        tie_embeddings=config["tie_word_embeddings"],
        rope_theta=float(config["rope_theta"]),
        max_seq_len=config["max_position_embeddings"],
        param_dtype=config["torch_dtype"], compute_dtype=config["torch_dtype"],
        **arch)


def trace_task(out: Path, seconds: float):
    """Coroutine function that starts the profiler ``TRACE_S`` seconds
    before the window closes and marks the traced window with the host
    span ``chipbench.window``; the caller stops the profiler after the
    drain, so that writing the trace stalls nothing that is measured."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0

    async def traced(clock) -> None:
        loop = asyncio.get_running_loop()
        await clock.sleep(max(0.0, seconds - TRACE_S - clock.now()))
        await loop.run_in_executor(None, lambda: jax.profiler.start_trace(
            str(out), profiler_options=opts))
        with jax.profiler.TraceAnnotation(WINDOW_SPAN):
            await clock.sleep(max(0.0, seconds - clock.now()))

    return traced


def build(config: dict, traffic: dict, devices, seed: int):
    """The cell's replica pool, its weights made from the seed, warmed at
    the cell's shapes only → (pool, warm-up seconds per (bucket, prompt
    bucket), weights' key seed, devices used, compile counter)."""
    import jax

    from repro.serving.engine import EngineConfig, ReplicaPool

    replicas = traffic.get("replicas", 1)
    plen, gen = traffic["prompt_len"], traffic["gen_len"]
    ecfg = EngineConfig(batch_buckets=tuple(config["engine"]["batch_buckets"]),
                        prompt_buckets=(plen,), max_len=plen + gen, gen_len=gen)
    key_seed = schedule.model_key_seed(seed)
    counter = serve.CompileCounter()
    used = list(devices[:replicas])
    t0 = time.perf_counter()
    pool = ReplicaPool(model_config(config), ecfg, n_replicas=replicas,
                       rng=jax.random.PRNGKey(key_seed), devices=used)
    jax.block_until_ready([e.params for e in pool.replicas])
    t1 = time.perf_counter()
    warm = pool.warmup()
    print(f"weights {t1 - t0:.6f} s, warm-up {time.perf_counter() - t1:.6f} s "
          f"({counter.n} XLA compiles or cache loads)", flush=True)
    return pool, warm, key_seed, used, counter


def run_cell(name: str, seed: int, seconds: float, trace: bool, devices,
             t0: float, root: Path = ROOT, out: Optional[Path] = None,
             control: bool = False) -> dict:
    """One run of a cell on ``devices``; returns the result line's object.
    With ``control`` the float8 control stands in the program's place in
    the comparison: ``max_gap`` is the control's gap, and ``correct``
    reads it against the limit (the program's own gap is kept beside it,
    as ``"program"``)."""
    import jax

    bench, cell, config, traffic = cell_files(root, name)
    if len(devices) < cell["chips"]:
        raise SystemExit(f"{name} needs {cell['chips']} chips, "
                         f"found {len(devices)}")
    t_build = time.perf_counter()
    pool, warm, key_seed, used, counter = build(config, traffic, devices, seed)
    setup_s = time.perf_counter() - t0
    print(f"set-up {setup_s:.6f} s, of which {t_build - t0:.6f} s before the "
          f"build (imports, JAX and the TPU runtime)", flush=True)

    out = out or (root / "chipbench" / "out")
    trace_dir = out / "trace" / name
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
    window = serve.run_window(
        pool, traffic, seconds, seed, vocab=config["vocab_size"], warm=warm,
        counter=counter,
        trace=trace_task(trace_dir, seconds) if trace else None)
    if trace:
        jax.profiler.stop_trace()
    if window.compiles:
        print(f"compiles inside the window: {window.compiles}", flush=True)
    write_record(out / "runs" / f"{name}.{seed}.{int(trace)}.json", window)
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in used)
    reduced = None
    if trace:
        paths = glob.glob(str(trace_dir / "**" / "*.xplane.pb"), recursive=True)
        meta = {c.index: (used[c.replica].id, c.bucket) for c in window.calls}
        reduced = trace_reduce.reduce(paths[0], serve.SPAN, WINDOW_SPAN, meta,
                                      [d.id for d in used]) if paths else None
    del pool
    gc.collect()

    compared = compare(config, traffic, window, seed, key_seed, control)
    run = Run(cell, config, traffic, window, setup_s, reduced,
              peaks(used[0]) if trace else None)
    values = {}
    for m in cell_metrics(bench, name, trace):
        v = reader(m["name"])(run)
        if v is not None and math.isfinite(v):
            values[m["name"]] = {"value": v, "unit": m["unit"]}
    attempted = len(window.due)
    served = int(np.count_nonzero(window.completed))
    print(summary_line(run), flush=True)
    dev = used[0]
    result = {
        "correct": check.verdict(compared),
        "attempted": attempted,
        "failed": attempted - served,
        "metrics": values,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices()), "memory_peak_bytes": peak},
    }
    if reduced is not None:
        result["device"]["busy_s"] = reduced["busy_s"]
        result["device"]["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["compared"] = {c["name"]: {k: v for k, v in c.items() if k != "name"}
                          for c in compared}
    return result


def write_record(path: Path, w: serve.Window) -> None:
    """The window's requests, engine calls and batches, on its clock, for a
    look at a run after the fact (null: never sent, dispatched or done)."""
    def seconds(a):
        return [None if math.isnan(x) else x for x in a.tolist()]

    path.parent.mkdir(parents=True, exist_ok=True)
    with open(path, "w") as f:
        json.dump({"seconds": w.seconds, "ended": w.ended, "max_bs": w.max_bs,
                   "due": seconds(w.due), "sent": seconds(w.sent),
                   "dispatched": seconds(w.dispatched), "done": seconds(w.done),
                   "calls": [[c.start, c.end, c.rows, c.bucket, c.replica]
                             for c in w.calls],
                   "batches": [list(b) for b in w.batches]}, f)


def compare(config: dict, traffic: dict, window: serve.Window, seed: int,
            key_seed: int, control: bool = False) -> List[dict]:
    """The numbers that decide ``correct``, each with its limit. With
    ``control``, ``max_gap`` is the float8 control's reading."""
    gen = traffic["gen_len"]
    attempted = len(window.due)
    served = [i for i in range(attempted) if window.outputs[i] is not None]
    malformed = sum(
        1 for i in served
        if window.outputs[i].shape != (gen,)
        or not np.issubdtype(window.outputs[i].dtype, np.integer)
        or window.outputs[i].min() < 0
        or window.outputs[i].max() >= config["vocab_size"])
    out = [{"name": "unserved", "value": attempted - len(served), "limit": 0},
           {"name": "malformed", "value": malformed, "limit": 0}]
    if served and not malformed:
        groups = [window.group(i) for i in range(attempted)]
        idx = check.sample(groups, math.ceil(config["check"]["tokens"] / gen),
                           seed)
        res = check.widest_gap(config, key_seed, window.prompts[idx],
                               np.stack([window.outputs[i] for i in idx]),
                               control=control)
        print(f"checked {len(idx)} requests, {res['tokens']} served tokens, "
              f"{len({groups[i] for i in idx})} (bucket, replica) groups",
              flush=True)
        gap = {"name": "max_gap", "value": res["gap"],
               "limit": config["check"]["max_gap"]}
        if control:
            print(f"control in the program's place: program's gap "
                  f"{res['gap']}, control's {res['control_gap']}", flush=True)
            gap.update(value=res["control_gap"], program=res["gap"])
        out.append(gap)
    return out


def peaks(device) -> dict:
    table = load_json(HERE / "peaks.json")
    if device.device_kind not in table["chips"]:
        raise SystemExit(f"no peaks for device kind {device.device_kind!r}")
    return table["chips"][device.device_kind]


def summary_line(run: Run) -> str:
    """Tails, attainment and batching of the run, whatever it reports."""
    import readings

    w = run.window
    lat = readings.latencies_s(w)
    slo = run.traffic["slo_ms"] / 1e3
    sizes = [n for _, n, _ in w.batches]
    return (f"served {int(np.count_nonzero(w.completed))}/{len(w.due)}, "
            f"p50 {1e3 * readings.rank(lat, 50):.6f} ms, "
            f"p95 {1e3 * readings.rank(lat, 95):.6f} ms, "
            f"within {run.traffic['slo_ms']} ms {100 * np.mean(lat <= slo):.6f}%, "
            f"{len(sizes)} batches of {np.mean(sizes) if sizes else 0:.6f} "
            f"requests, {len(w.calls)} engine calls, max_bs {w.max_bs}, "
            f"drain ended {w.ended:.6f} s")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)

    import jax

    from repro.launch.compile_cache import enable_compile_cache

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"no TPU: JAX found {devices[0].platform}", file=sys.stderr)
        return 2
    enable_compile_cache()
    # every program of a run, the small ones too, is found again next run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace), devices, PROCESS_START)
    for name, c in result["compared"].items():
        print(f"{name} {c['value']} limit {c['limit']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
