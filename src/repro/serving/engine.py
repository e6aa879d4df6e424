"""JAX inference engine — the data plane MLProxy fronts on TPU.

Fixed-shape compiled programs make batch-size *bucketing* mandatory on
XLA backends: the engine compiles ``prefill``/decode once per
(batch-bucket, prompt-bucket) and pads incoming batches up to the bucket.
This is the TPU-native adaptation of the paper (DESIGN.md §2): the proxy's
monitor keys its latency windows by the padded bucket size, which is the
size whose latency the next dispatch decision must predict.

Hot-path layout (the fast data plane):

* **Fused decode** (``EngineConfig.fused_decode``, default on): the whole
  greedy decode loop is one compiled ``lax.scan`` program per
  (batch bucket, step count) — one device dispatch per batch instead of
  ``gen_len`` Python→XLA round-trips. Token outputs are bit-identical to
  the per-token path (greedy argmax over the same logits); set
  ``fused_decode=False`` to get the per-token reference loop.
* **Gen-length bucketing** (``EngineConfig.gen_buckets``): requested
  generation lengths round up to the next configured step bucket, so the
  fused program compiles once per bucket instead of once per distinct
  ``gen_len``. Extra steps are computed and sliced off; outputs for the
  requested length are unchanged (greedy decoding is prefix-stable).
* **Persistent KV-cache pool** (``EngineConfig.cache_pool``, default on):
  ``generate`` checks its cache out of a per-bucket pool and returns it
  afterwards instead of allocating + zero-filling per call. Reuse without
  zero-fill is sound because ``prefill`` overwrites rows ``[0:plen]`` for
  every row of the bucket and resets ``cache["len"]``, and decode
  attention masks positions ``>= cache_len`` — stale rows from a previous
  batch are never attended. Donated cache arguments let XLA recycle the
  buffers in place.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models.model import Model


def next_bucket(n: int, buckets: Sequence[int], clamp: bool = False) -> int:
    """Smallest bucket holding ``n`` requests.

    ``clamp=True`` returns the largest bucket for oversized ``n`` instead
    of raising — for *estimation* paths (monitor latency queries, mean
    lookups) that must stay total even when a policy's cap exceeds the
    engine's compiled buckets. Execution paths keep the strict default and
    chunk oversized batches instead (see ``serving/batcher.py``).
    """
    for b in buckets:
        if n <= b:
            return b
    if clamp:
        return buckets[-1]
    raise ValueError(f"batch {n} exceeds largest bucket {buckets[-1]}")


#: The sanctioned wall clock for real-measurement code (this module and
#: its adapters). Everything that *models* time must take an injected
#: Clock instead — see ``runtime/clock.py`` and the reprolint
#: ``wallclock`` rule. Measurement modules importing this alias keep the
#: repo's wall-clock references in one greppable seam.
wall_clock = time.monotonic


@dataclasses.dataclass
class EngineConfig:
    batch_buckets: Tuple[int, ...] = (1, 2, 4, 8, 16, 32)
    prompt_buckets: Tuple[int, ...] = (16, 32, 64, 128)
    max_len: int = 160  # prompt bucket + generation budget
    gen_len: int = 8
    greedy: bool = True
    #: Compile the decode loop as one lax.scan program per (batch bucket,
    #: step bucket) instead of dispatching per token. Off = the per-token
    #: reference loop (bit-identical outputs, ~gen_len× more dispatches).
    fused_decode: bool = True
    #: Step buckets for the fused loop: a requested gen_len rounds up to
    #: the next bucket (extra tokens are computed then sliced off), so the
    #: compile cache stays bounded under varying gen_len. None = compile
    #: per distinct requested length.
    gen_buckets: Optional[Tuple[int, ...]] = None
    #: Reuse KV caches across batches via a per-bucket pool instead of
    #: allocating + zero-filling per generate() call.
    cache_pool: bool = True


class ReplicaUnavailable(RuntimeError):
    """A replica cannot serve; :meth:`ReplicaPool.generate` fails over.

    Only this error (and :meth:`ReplicaPool.fail`) takes a replica out of
    rotation. Any other error, a device error (``jax.errors.JaxRuntimeError``)
    included, propagates to the caller with its cause.
    """


class InferenceEngine:
    """Single-replica engine: bucketed compile cache + prefill/decode loop.

    ``device`` pins the replica: its params, KV pool and inputs are put on
    that device, so compiled programs run there. None = JAX's default
    device.
    """

    def __init__(self, cfg: ModelConfig, engine_cfg: EngineConfig,
                 params: Optional[Any] = None, rng: Optional[jax.Array] = None,
                 device: Optional[jax.Device] = None):
        self.cfg = cfg
        self.ecfg = engine_cfg
        self.model = Model(cfg)
        self.device = device
        if params is None:
            rng = rng if rng is not None else jax.random.PRNGKey(0)
            if device is not None:
                rng = jax.device_put(rng, device)  # init runs on ``device``
            # one compiled program instead of an eager op per weight
            params = jax.jit(self.model.init)(rng)
        if device is not None:
            params = jax.device_put(params, device)
        self.params = params
        self._prefill_cache: Dict[Tuple[int, int], Any] = {}
        self._decode_cache: Dict[int, Any] = {}
        self._fused_cache: Dict[Tuple[int, int], Any] = {}
        self._kv_pool: Dict[int, Any] = {}
        self.compile_count = 0
        #: KV-cache allocations (pool misses); with the pool on, this
        #: saturates at one per bucket instead of growing per batch.
        self.cache_allocs = 0
        self.stats: Dict[str, float] = {"batches": 0, "requests": 0, "tokens": 0}
        self._in_warmup = False

    # ------------------------------------------------------------- compiled
    def _prefill_fn(self, bucket: int, plen: int):
        key = (bucket, plen)
        fn = self._prefill_cache.get(key)
        if fn is None:
            model = self.model

            def run(params, tokens, cache):
                return model.prefill(params, tokens, cache)

            # The input cache's contents are dead (prefill overwrites the
            # prompt rows and resets the length): donate so XLA writes the
            # new cache into the pooled buffers instead of copying.
            fn = jax.jit(run, donate_argnames=("cache",))
            self._prefill_cache[key] = fn
            self.compile_count += 1
        return fn

    def _decode_fn(self, bucket: int):
        fn = self._decode_cache.get(bucket)
        if fn is None:
            model = self.model

            def run(params, tokens, cache):
                logits, cache = model.decode_step(params, tokens, cache)
                nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
                return nxt[:, None], cache

            fn = jax.jit(run, donate_argnames=("cache",))
            self._decode_cache[bucket] = fn
            self.compile_count += 1
        return fn

    def _fused_fn(self, bucket: int, steps: int):
        """One compiled program running ``steps - 1`` greedy decode steps."""
        key = (bucket, steps)
        fn = self._fused_cache.get(key)
        if fn is None:
            model = self.model

            def run(params, first, cache):
                def body(carry, _):
                    tok, c = carry
                    logits, c = model.decode_step(params, tok, c)
                    nxt = jnp.argmax(logits[:, -1], axis=-1)
                    nxt = nxt.astype(jnp.int32)[:, None]
                    return (nxt, c), nxt

                (_, cache), toks = jax.lax.scan(
                    body, (first, cache), None, length=steps - 1)
                # (steps-1, bucket, 1) → (bucket, steps-1)
                return jnp.swapaxes(toks[..., 0], 0, 1), cache

            fn = jax.jit(run, donate_argnames=("cache",))
            self._fused_cache[key] = fn
            self.compile_count += 1
        return fn

    # ------------------------------------------------------------ kv cache
    def _checkout_cache(self, bucket: int):
        cache = self._kv_pool.pop(bucket, None) if self.ecfg.cache_pool else None
        if cache is None:
            cache = self.model.init_cache(bucket, self.ecfg.max_len)
            if self.device is not None:
                cache = jax.device_put(cache, self.device)
            self.cache_allocs += 1
        return cache

    def _return_cache(self, bucket: int, cache) -> None:
        if self.ecfg.cache_pool:
            self._kv_pool[bucket] = cache

    def _gen_steps(self, gen_len: int, plen: int) -> int:
        """Total generated tokens the compiled loop produces for ``gen_len``.

        Rounds up to ``gen_buckets`` (bounded compile cache), clamped so
        decode never writes past ``max_len``, and never below the
        requested length.
        """
        steps = gen_len
        if self.ecfg.gen_buckets:
            steps = next_bucket(gen_len, self.ecfg.gen_buckets, clamp=True)
        return max(gen_len, min(steps, self.ecfg.max_len - plen + 1))

    def warmup(self, plen: Optional[int] = None) -> Dict[Tuple[int, int], float]:
        """Precompile the configured buckets (what a replica does at startup).

        Warms every (batch bucket, prompt bucket) pair — or just the pairs
        for one prompt bucket when ``plen`` is given — at the default
        ``gen_len``, priming the prefill/decode compile caches and the KV
        pool. Returns post-compile wall seconds per ``(bucket, plen)``
        pair (each pair is run twice; the first run pays compilation and
        is discarded), the seed material for
        :class:`~repro.serving.batcher.EngineBackedLatency` estimates.

        Warmup traffic is synthetic: serving ``stats`` are not touched.
        """
        plens = ([next_bucket(plen, self.ecfg.prompt_buckets, clamp=True)]
                 if plen is not None else list(self.ecfg.prompt_buckets))
        timings: Dict[Tuple[int, int], float] = {}
        self._in_warmup = True
        try:
            for b in self.ecfg.batch_buckets:
                for p in plens:
                    prompts = np.zeros((b, p), np.int32)
                    self.generate(prompts)  # cold: compiles
                    _, timing = self.generate(prompts)
                    timings[(b, p)] = timing["latency_s"]
        finally:
            self._in_warmup = False
        return timings

    def _prefill(self, prompts: np.ndarray):
        """Left-pad ``prompts`` into their buckets and run the compiled
        prefill on a pooled cache → (last-position logits, cache, bucket,
        prompt bucket)."""
        n, plen_raw = prompts.shape
        bucket = next_bucket(n, self.ecfg.batch_buckets)
        plen = next_bucket(plen_raw, self.ecfg.prompt_buckets)
        padded = np.zeros((bucket, plen), np.int32)
        padded[:n, plen - plen_raw:] = prompts  # left-pad into the bucket
        tokens = jax.device_put(padded, self.device)
        cache = self._checkout_cache(bucket)
        logits, cache = self._prefill_fn(bucket, plen)(self.params, tokens, cache)
        return logits, cache, bucket, plen

    # ------------------------------------------------------------------ api
    def programs(self, bucket: int, plen: int) -> Dict[str, Any]:
        """The jitted programs :meth:`generate` runs for one batch bucket and
        prompt bucket, by name, for lowering and inspection.

        ``"prefill"`` takes (params, tokens (bucket, plen), cache); the
        decode program, ``"fused decode"`` or (with ``fused_decode`` off)
        ``"decode step"``, takes (params, tokens (bucket, 1), cache).
        """
        out = {"prefill": self._prefill_fn(bucket, plen)}
        if self.ecfg.fused_decode:
            steps = self._gen_steps(self.ecfg.gen_len, plen)
            out["fused decode"] = self._fused_fn(bucket, steps)
        else:
            out["decode step"] = self._decode_fn(bucket)
        return out

    def device_arrays(self) -> List[jax.Array]:
        """Every array the replica holds: its params and pooled KV caches."""
        return jax.tree.leaves(self.params) + jax.tree.leaves(self._kv_pool)

    def prefill_logits(self, prompts: np.ndarray) -> np.ndarray:
        """Last-position logits (n, vocab) of the compiled prefill program.

        The same program and padding :meth:`generate` uses, exposed so a
        reference forward pass can be compared with what the engine serves.
        """
        logits, cache, bucket, _ = self._prefill(prompts)
        self._return_cache(bucket, cache)
        return np.asarray(jax.device_get(logits))[:prompts.shape[0], -1]

    def generate(self, prompts: np.ndarray, gen_len: Optional[int] = None,
                 ) -> Tuple[np.ndarray, Dict[str, float]]:
        """Greedy-decode ``gen_len`` tokens for a batch of prompts.

        prompts: (n, plen) int32, n ≤ largest bucket. Returns (tokens
        (n, gen_len), timing dict with wall seconds + bucket metadata).
        """
        gen_len = gen_len if gen_len is not None else self.ecfg.gen_len
        n = prompts.shape[0]
        t0 = time.perf_counter()
        logits, cache, bucket, plen = self._prefill(prompts)
        first = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
        if self.ecfg.fused_decode and gen_len > 1:
            steps = self._gen_steps(gen_len, plen)
            rest, cache = self._fused_fn(bucket, steps)(self.params, first, cache)
            result = jnp.concatenate([first, rest[:, :gen_len - 1]], axis=1)
        else:
            out = [first]
            decode = self._decode_fn(bucket)
            cur = first
            for _ in range(gen_len - 1):
                cur, cache = decode(self.params, cur, cache)
                out.append(cur)
            result = jnp.concatenate(out, axis=1)
        result = jax.device_get(result)[:n]
        self._return_cache(bucket, cache)
        dt = time.perf_counter() - t0
        if not self._in_warmup:
            self.stats["batches"] += 1
            self.stats["requests"] += n
            self.stats["tokens"] += n * gen_len
        return result, {
            "latency_s": dt, "bucket": bucket, "prompt_bucket": plen,
            "padding_waste": (bucket - n) / bucket,
        }


class ReplicaPool:
    """Elastic pool of engine replicas with failover (fault-tolerance shim).

    Replicas share weights (one copy in memory on this host) but have
    independent compile caches and health state, mirroring how a Knative
    deployment schedules independent model servers. ``fail(i)`` marks a
    replica down (its in-flight work is retried elsewhere); ``scale_to``
    adds/removes replicas.

    ``devices`` spreads replicas over accelerators, one per device: replica
    ``i`` holds its KV pool and a copy of the weights on ``devices[i]``
    (replica 0 shares the template's, which are made on ``devices[0]``).
    None keeps every replica on the default device, sharing the template's
    weights.

    Dispatch is **parallel across replicas**: each replica is guarded by
    its own lock (a replica's compile caches and KV pool are not
    thread-safe), and ``generate`` prefers an *idle* healthy replica over
    strict rotation, so concurrent callers overlap on different replicas
    instead of serializing behind one. Synchronization with the device
    happens only at the result boundary (``device_get`` inside the
    replica), so one caller's host-side padding of the next batch overlaps
    another replica's device compute.
    """

    def __init__(self, cfg: ModelConfig, engine_cfg: EngineConfig,
                 n_replicas: int = 1, rng: Optional[jax.Array] = None,
                 devices: Optional[Sequence[jax.Device]] = None):
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        self.devices = list(devices) if devices else None
        self._template = InferenceEngine(
            cfg, engine_cfg, rng=rng,
            device=self.devices[0] if self.devices else None)
        self.cfg = cfg
        self.engine_cfg = engine_cfg
        self.replicas: List[Optional[InferenceEngine]] = []
        self.healthy: List[bool] = []
        self._locks: List[threading.Lock] = []
        self._rr = 0
        self.retries = 0
        self.scale_to(n_replicas)

    def scale_to(self, n: int) -> None:
        """Grow or shrink the pool to exactly ``n`` replicas.

        Shrinking removes the tail replicas outright (freeing their compile
        caches) instead of merely marking them unhealthy — otherwise a later
        scale-up appends fresh replicas while the dead ones keep consuming
        round-robin slots and ``n_healthy`` drifts from the pool size.
        """
        if n < 0:
            raise ValueError(f"replica count must be >= 0, got {n}")
        if self.devices and n > len(self.devices):
            raise ValueError(f"{n} replicas for {len(self.devices)} devices")
        if n < len(self.replicas):
            del self.replicas[n:]
            del self.healthy[n:]
            del self._locks[n:]
            self._rr = self._rr % len(self.replicas) if self.replicas else 0
        while len(self.replicas) < n:
            device = self.devices[len(self.replicas)] if self.devices else None
            eng = InferenceEngine(self.cfg, self.engine_cfg,
                                  params=self._template.params, device=device)
            self.replicas.append(eng)
            self.healthy.append(True)
            self._locks.append(threading.Lock())

    @property
    def n_healthy(self) -> int:
        return sum(self.healthy)

    def fail(self, index: int) -> None:
        self.healthy[index] = False

    def recover(self, index: int) -> None:
        self.healthy[index] = True

    def warmup(self, plen: Optional[int] = None) -> Dict[Tuple[int, int], float]:
        """Warm every replica; returns the first replica's timings.

        The first replica warms alone, so its timings are not skewed by
        others sharing its device. The rest then warm in parallel threads:
        each compiles its own programs, and XLA compiles without the GIL.
        """
        if not self.replicas:
            return {}
        timings = self.replicas[0].warmup(plen)
        rest = self.replicas[1:]
        if rest:
            with ThreadPoolExecutor(max_workers=len(rest)) as ex:
                list(ex.map(lambda eng: eng.warmup(plen), rest))
        return timings

    def _acquire_replica(self) -> Tuple[Optional[int], Optional[threading.Lock]]:
        """Pick a healthy replica and acquire its lock.

        One non-blocking sweep in round-robin order first — an idle
        replica wins immediately, which is what lets concurrent
        dispatches overlap — then a blocking acquire on the
        round-robin-next healthy replica when all are busy. Returns
        (None, None) when no replica is healthy.
        """
        n = len(self.replicas)
        start = self._rr
        for off in range(1, n + 1):
            idx = (start + off) % n
            if not self.healthy[idx]:
                continue
            if self._locks[idx].acquire(blocking=False):
                self._rr = idx
                return idx, self._locks[idx]
        for off in range(1, n + 1):
            idx = (start + off) % n
            if self.healthy[idx]:
                self._rr = idx
                self._locks[idx].acquire()
                return idx, self._locks[idx]
        return None, None

    def generate(self, prompts: np.ndarray, gen_len: Optional[int] = None):
        """Idle-preferring round-robin dispatch with failover (at-least-once).

        A replica that raises :class:`ReplicaUnavailable` is marked down and
        the batch retried on another; every other error propagates.
        """
        last: Optional[ReplicaUnavailable] = None
        attempts = 0
        while self.replicas and attempts <= len(self.replicas):
            idx, lock = self._acquire_replica()
            if idx is None:
                attempts += 1
                continue
            try:
                out, timing = self.replicas[idx].generate(prompts, gen_len)
                timing["replica"] = idx
                return out, timing
            except ReplicaUnavailable as e:
                last = e
                self.fail(idx)
                self.retries += 1
                attempts += 1
            finally:
                lock.release()
        raise ReplicaUnavailable("no healthy replicas") from last
