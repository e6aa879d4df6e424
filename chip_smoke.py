"""Chip smoke test: full-width qwen2-0.5b served through the live proxy on a TPU.

Drives the served path once through the entry points a user calls: the
load generator, ``AsyncProxyServer`` (MLProxy policy), ``EngineTarget``,
``ReplicaPool`` and ``InferenceEngine`` prefill plus fused decode. The model
is ``configs/qwen2_0_5b.py`` unreduced (24 layers, d_model 896, vocab
151936, bf16) with random weights from ``Model.init`` and ``--seed``.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # four one-chip replicas against one

With one chip the script
  1. serves a seeded Poisson replay (~64 requests at 8 req/s, 128-token
     prompts, 16 generated tokens) through a one-replica pool and checks
     that every request was served, that nothing compiled inside the
     window, and that every output is 16 in-vocab token ids;
  2. checks the engine's prefill logits against ``Model.forward`` on the
     same params and tokens;
  3. runs prefill plus fused decode with ``use_pallas=True``, checks that
     both programs hold Mosaic kernels (``tpu_custom_call``) and that their
     logits agree with the jnp path, and checks the decode kernel with a
     different cache length in every row against ``kernels/ref.py``.
With ``--chips 4`` it runs only the replay through one replica and then
through four (one per chip, ``EngineTarget`` concurrency 4), and checks
that each replica's arrays sit on its own chip and that the tokens equal
the one-replica run's.

Every line but the last names the device it was measured on. The last line
is ``{"ok": true, "device": {...}}``; any failed check raises, so the
script exits non-zero without it. With no TPU it exits non-zero at once.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

ARCH = "qwen2-0.5b"
PROMPT_LEN = 128
GEN_LEN = 16
BATCH_BUCKETS = (1, 2, 4, 8)
MAX_LEN = 160
RATE = 8.0          # req/s
DURATION = 8.0      # s of arrivals: ~64 requests
SLO_S = 10.0        # loose: the check is that everything is served
#: Logits of two bf16 paths on the same params and tokens (engine prefill
#: vs Model.forward; Pallas vs jnp attention): the largest difference may be
#: at most this share of the largest reference logit. Each of the 24 layers
#: rounds the residual stream to bf16 (relative 2**-8); independent
#: roundings grow as sqrt(24) * 2**-8, about 2%. The largest logits of the
#: random-weight model are about 3, where 2.5% is about 5 bf16 ulps (2**-6).
LOGIT_RTOL = 0.025
#: Where the greedy tokens of the two paths first differ, the jnp logits of
#: the two choices lie at most this many bf16 ulps apart.
NEAR_TIE_ULPS = 4
#: Pallas decode kernel vs ``kernels/ref.py`` in bf16: |d| <= tol * (1 +
#: |ref|), the bound ``tests/test_kernels.py`` holds bf16 kernels to.
KERNEL_TOL = 2e-2
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def require_tpu():
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU; JAX found {devices[0].platform}")
    return devices


class CompileCounter:
    """Counts XLA backend compiles (persistent-cache loads included)."""

    def __init__(self) -> None:
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event == COMPILE_EVENT:
            self.n += 1


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


def replay(pool, prompts: np.ndarray, seed: int, concurrency=None):
    """One seeded Poisson replay through the live runtime → ReplayResult."""
    from repro.core import SLAConfig
    from repro.runtime.clock import WallClock
    from repro.runtime.loadgen import run_replay
    from repro.runtime.targets import EngineTarget
    from repro.serving.batcher import ReplicaPoolTarget
    from repro.simulation.arrivals import PoissonProcess

    clock = WallClock()
    target = EngineTarget(
        ReplicaPoolTarget(pool, prompt_len=PROMPT_LEN, gen_len=GEN_LEN),
        max_concurrent=concurrency, clock=clock)
    rows = iter(prompts)
    return run_replay(
        policy="mlproxy", sla=SLAConfig(slo_target=SLO_S),
        arrivals=PoissonProcess(rate=RATE, duration=DURATION),
        duration=DURATION, target=target, clock=clock, seed=seed,
        payload_fn=lambda: next(rows))


def check_served(res, vocab: int, tag: str) -> np.ndarray:
    """Every request served, none lost; returns the (n, GEN_LEN) outputs."""
    s = res.summary
    counts = {k: int(s[k]) for k in
              ("submitted", "completed", "failed", "timed_out", "shed", "rejected")}
    print(f"[{tag}] {counts}")
    check(counts["submitted"] > 0 and counts["submitted"] == counts["completed"],
          f"{tag}: not every request completed: {counts}")
    check(all(counts[k] == 0 for k in ("failed", "timed_out", "shed", "rejected")),
          f"{tag}: requests lost: {counts}")
    outs = [np.asarray(t.request.payload) for t in res.tickets]
    for out in outs:
        check(out.shape == (GEN_LEN,) and np.issubdtype(out.dtype, np.integer),
              f"{tag}: output {out.dtype} {out.shape}, want int ({GEN_LEN},)")
        check(bool(np.all((out >= 0) & (out < vocab))), f"{tag}: token out of vocab")
    print(f"[{tag}] p50 {s['p50'] * 1e3:.6f} ms  p95 {s['p95'] * 1e3:.6f} ms  "
          f"{s['throughput'] * GEN_LEN:.6f} tokens/s  "
          f"avg batch {s['avg_batch_size']:.6f}  batches {int(s['dispatched_batches'])}")
    return np.stack(outs)


def served_window(pool, prompts, seed, counter, tag, concurrency=None):
    """Replay with the compile counters read around the window."""
    programs = sum(e.compile_count for e in pool.replicas)
    compiles = counter.n
    res = replay(pool, prompts, seed, concurrency)
    new_programs = sum(e.compile_count for e in pool.replicas) - programs
    new_compiles = counter.n - compiles
    print(f"[{tag}] compiles inside the window: engine {new_programs}, "
          f"XLA {new_compiles}")
    check(new_programs == 0 and new_compiles == 0,
          f"{tag}: compiled inside the served window")
    return res


def warm(pool, counter, tag) -> None:
    compiles = counter.n
    t0 = time.perf_counter()
    pool.warmup()
    dt = time.perf_counter() - t0
    print(f"[{tag}] set-up compile+warmup {dt:.6f} s, engine programs "
          f"{[e.compile_count for e in pool.replicas]}, XLA compiles "
          f"{counter.n - compiles}")


def reference_phase(eng, prompts, tag) -> None:
    """Engine prefill logits vs Model.forward on the same params/tokens."""
    import jax
    import jax.numpy as jnp

    x = prompts[:2]
    got = eng.prefill_logits(x)
    want = np.asarray(jax.jit(eng.model.forward)(eng.params, jnp.asarray(x)))[:, -1]
    check(bool(np.all(np.isfinite(got))), f"{tag}: non-finite logits")
    err = float(np.max(np.abs(got - want)))
    scale = float(np.max(np.abs(want)))
    print(f"[{tag}] prefill vs Model.forward: max|d| {err:.6f}, max|ref| "
          f"{scale:.6f}, tol {LOGIT_RTOL * scale:.6f}, argmax equal "
          f"{np.array_equal(got.argmax(-1), want.argmax(-1))}")
    check(err <= LOGIT_RTOL * scale, f"{tag}: logits differ from Model.forward")


def teacher_forced_logits(model, params, x, tokens) -> np.ndarray:
    """(GEN_LEN, b, vocab) logits of ``model`` decoding the given tokens.

    Step t's logits predict ``tokens[:, t]`` from the prompt and
    ``tokens[:, :t]``, so two models fed the same tokens are compared on the
    same inputs at every step, whatever each would have chosen greedily.
    """
    import jax

    logits, cache = jax.jit(model.prefill)(
        params, x, model.init_cache(x.shape[0], MAX_LEN))
    out = [logits[:, -1]]
    step = jax.jit(model.decode_step)
    for t in range(1, GEN_LEN):
        logits, cache = step(params, tokens[:, t - 1:t], cache)
        out.append(logits[:, -1])
    return np.stack(jax.device_get(out)).astype(np.float32)


def bf16_ulp(x: float) -> float:
    """Spacing of bfloat16 numbers (8 significant bits) at magnitude ``x``."""
    return float(2.0 ** (np.floor(np.log2(abs(x))) - 7))


def pallas_engine(eng):
    """An engine on ``eng``'s params whose attention runs the Pallas kernels."""
    from repro.serving.engine import InferenceEngine

    cfg = dataclasses.replace(eng.cfg, use_pallas=True)
    return InferenceEngine(cfg, eng.ecfg, params=eng.params, device=eng.device)


def mosaic_check(eng_pl, tag) -> None:
    """The served programs hold Mosaic kernels, not interpreted code."""
    import jax
    import jax.numpy as jnp

    b = BATCH_BUCKETS[-1]
    cache = jax.eval_shape(lambda: eng_pl.model.init_cache(b, MAX_LEN))
    tokens = {"prefill": jax.ShapeDtypeStruct((b, PROMPT_LEN), jnp.int32),
              "fused decode": jax.ShapeDtypeStruct((b, 1), jnp.int32)}
    for name, fn in eng_pl.programs(b, PROMPT_LEN).items():
        hlo = fn.lower(eng_pl.params, tokens[name], cache).compile().as_text()
        n = hlo.count("tpu_custom_call")
        print(f"[{tag}] pallas {name}: {n} tpu_custom_call in compiled HLO")
        check(n > 0, f"{tag}: pallas {name} has no Mosaic kernel")


def decode_kernel_check(cfg, seed: int, tag) -> None:
    """The decode kernel at the model's head widths, each row with its own
    cache length, against the plain-jnp reference of ``kernels/ref.py``.

    The served model keeps one cache length for the whole batch, so this
    direct call is what runs the kernel's per-row lengths.
    """
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops, ref

    b = BATCH_BUCKETS[-1]
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(keys[0], (b, 1, cfg.num_heads, cfg.head_dim))
    kc, vc = (jax.random.normal(k, (b, MAX_LEN, cfg.num_kv_heads, cfg.head_dim))
              for k in keys[1:])
    q, kc, vc = (x.astype(cfg.compute_dtype) for x in (q, kc, vc))
    lengths = jnp.asarray(np.linspace(MAX_LEN, 1, b).round(), jnp.int32)
    got = jax.jit(ops.decode_attention)(q, kc, vc, lengths)
    want = ref.decode_attention(q, kc, vc, lengths)
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    err = np.max(np.abs(got - want) - KERNEL_TOL * np.abs(want), axis=(1, 2, 3))
    print(f"[{tag}] pallas decode kernel, lengths {np.asarray(lengths).tolist()}:"
          f" max(|d| - {KERNEL_TOL}|ref|) per row {np.array2string(err, precision=6)},"
          f" tol {KERNEL_TOL}")
    check(bool(np.all(err <= KERNEL_TOL)), f"{tag}: decode kernel differs from ref")


def pallas_logits_check(eng, eng_pl, x, tag) -> None:
    """Prefill + fused decode through the Pallas kernels against the jnp
    path: logits at every step, and greedy tokens up to bf16 near-ties."""
    b = x.shape[0]
    want_tok, _ = eng.generate(x)
    eng_pl.generate(x)
    got_tok, timing = eng_pl.generate(x)
    print(f"[{tag}] pallas generate {timing['latency_s'] * 1e3:.6f} ms "
          f"(bucket {timing['bucket']}), tokens equal to jnp "
          f"{float(np.mean(got_tok == want_tok)):.6f}")
    # Logits of both paths on the jnp path's tokens: prefill (flash kernel)
    # and every decode step (decode kernel) within the bf16 tolerance.
    want = teacher_forced_logits(eng.model, eng.params, x, want_tok)
    got = teacher_forced_logits(eng_pl.model, eng.params, x, want_tok)
    tol = LOGIT_RTOL * float(np.max(np.abs(want)))
    err = np.max(np.abs(got - want), axis=(1, 2))
    print(f"[{tag}] pallas vs jnp logits per step: max|d| "
          f"{np.array2string(err, precision=6)}, tol {tol:.6f}")
    check(bool(np.all(err <= tol)), f"{tag}: pallas logits differ from jnp")
    # Greedy tokens match up to each row's first difference, and there the
    # jnp logits of the two choices lie within a few bf16 ulps: a rounding
    # flip, not a wrong kernel.
    for r in range(b):
        diff = np.flatnonzero(got_tok[r] != want_tok[r])
        if diff.size:
            t = diff[0]
            top = want[t, r, want_tok[r, t]]
            gap = top - want[t, r, got_tok[r, t]]
            limit = NEAR_TIE_ULPS * bf16_ulp(top)
            print(f"[{tag}] row {r}: first token difference at step {t}, "
                  f"jnp logit gap {gap:.6f}, limit {limit:.6f}")
            check(gap <= limit, f"{tag}: row {r} step {t} is not a near-tie")


def on_device(eng, device) -> bool:
    leaves = eng.device_arrays()
    return bool(leaves) and all(x.devices() == {device} for x in leaves)


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="4: compare four one-chip replicas with one replica")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args()

    devices = require_tpu()
    import jax

    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache
    from repro.serving.engine import EngineConfig, ReplicaPool

    dev = devices[0]
    tag = f"{dev.platform} {dev.device_kind} x{len(devices)}"
    print(f"[{tag}] compile cache: {enable_compile_cache()}")
    if args.chips == 4:
        check(len(devices) >= 4, f"--chips 4 needs 4 devices, found {len(devices)}")
        devices = devices[:4]
    counter = CompileCounter()
    cfg = get_config(ARCH)
    ecfg = EngineConfig(batch_buckets=BATCH_BUCKETS, prompt_buckets=(PROMPT_LEN,),
                        max_len=MAX_LEN, gen_len=GEN_LEN)
    prompts = np.random.default_rng(args.seed).integers(
        0, cfg.vocab_size, (4 * int(RATE * DURATION), PROMPT_LEN), dtype=np.int32)

    t0 = time.perf_counter()
    pool = ReplicaPool(cfg, ecfg, n_replicas=1,
                       rng=jax.random.PRNGKey(args.seed),
                       devices=devices if args.chips == 4 else None)
    n_params = sum(x.size for x in jax.tree.leaves(pool.replicas[0].params))
    print(f"[{tag}] {ARCH}: {n_params} params, init {time.perf_counter() - t0:.6f} s")
    warm(pool, counter, f"{tag} 1 replica")
    one = check_served(served_window(pool, prompts, args.seed, counter,
                                     f"{tag} 1 replica"),
                       cfg.vocab_size, f"{tag} 1 replica")

    if args.chips == 4:
        pool.scale_to(4)
        warm(pool, counter, f"{tag} 4 replicas")
        res = served_window(pool, prompts, args.seed, counter,
                            f"{tag} 4 replicas", concurrency=4)
        four = check_served(res, cfg.vocab_size, f"{tag} 4 replicas")
        for i, eng in enumerate(pool.replicas):
            print(f"[{tag} 4 replicas] replica {i} on {eng.device}: "
                  f"{int(eng.stats['batches'])} batches, "
                  f"{int(eng.stats['requests'])} requests")
            check(on_device(eng, devices[i]), f"replica {i} not on {devices[i]}")
            check(eng.stats["batches"] > 0, f"replica {i} served nothing")
        check(one.shape == four.shape, f"request counts differ: {one.shape} {four.shape}")
        print(f"[{tag}] 4-replica tokens equal 1-replica tokens: "
              f"{float(np.mean(one == four)):.6f}")
        check(np.array_equal(one, four), "4-replica tokens differ from 1 replica")
    else:
        eng = pool.replicas[0]
        reference_phase(eng, prompts, tag)
        eng_pl = pallas_engine(eng)
        mosaic_check(eng_pl, tag)
        decode_kernel_check(cfg, args.seed, tag)
        pallas_logits_check(eng, eng_pl, prompts[:BATCH_BUCKETS[-1]], tag)

    for i, d in enumerate(devices):
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            print(f"[{d.platform} {d.device_kind} #{i}] peak bytes in use "
                  f"{stats['peak_bytes_in_use']}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
