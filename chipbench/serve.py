"""One measured window through the program's served path.

``run_window`` sends a traffic mix's schedule, open loop, through the
path a user of the proxy drives: ``LoadGenerator`` → ``AsyncProxyServer``
(MLProxy policy, ``BatchQueue``) → ``EngineTarget`` →
``ReplicaPoolTarget`` → ``ReplicaPool`` → ``InferenceEngine`` prefill
plus fused decode. Arrivals stop at ``seconds``; the server then drains.
What comes back is plain data on the window's clock (zero at the start
of the schedule): when each request was due, sent, dispatched and
completed, what it was served, and every engine call.

The only objects of the benchmark's own on that path are ``TimedPool``, a
thin wrapper that the pool target calls in the pool's place: it times and
records each ``generate`` call and marks it in a profiler trace as the
host span ``chipbench.generate.<call index>``; and the load generator's
due times, which the harness keeps because the program's ``Request``
records when a request was sent, not when it was due.
"""
from __future__ import annotations

import asyncio
import dataclasses
import itertools
import threading
from typing import Callable, Dict, List, Optional

import numpy as np

import schedule

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
SPAN = "chipbench.generate."
#: Longest wait for the batches in flight when the window closes; what is
#: still running then is cancelled and counts as never served.
DRAIN_S = 240.0


class CompileCounter:
    """Counts XLA backend compiles, persistent-cache loads included."""

    def __init__(self) -> None:
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event == COMPILE_EVENT:
            self.n += 1


@dataclasses.dataclass(frozen=True)
class Call:
    """One ``ReplicaPool.generate`` call, on the window's clock."""

    index: int
    start: float
    end: float
    rows: int
    bucket: int
    prompt_len: int
    replica: int


class TimedPool:
    """The replica pool as ``ReplicaPoolTarget`` and ``EngineTarget`` see
    it, with every ``generate`` call timed and recorded."""

    def __init__(self, pool, clock) -> None:
        self.pool = pool
        self.engine_cfg = pool.engine_cfg
        self.replicas = pool.replicas
        self.clock = clock
        self.calls: List[Call] = []
        #: prompt bytes → index of the call that served that row
        self.row_call: Dict[bytes, int] = {}
        self._lock = threading.Lock()
        self._index = itertools.count()

    def generate(self, prompts: np.ndarray, gen_len: Optional[int] = None):
        import jax

        with self._lock:
            index = next(self._index)
        with jax.profiler.TraceAnnotation(f"{SPAN}{index}"):
            start = self.clock.now()
            out, timing = self.pool.generate(prompts, gen_len)
            end = self.clock.now()
        call = Call(index, start, end, prompts.shape[0], timing["bucket"],
                    timing["prompt_bucket"], timing["replica"])
        with self._lock:
            self.calls.append(call)
            for row in prompts:
                self.row_call[row.tobytes()] = index
        return out, timing


@dataclasses.dataclass
class Window:
    """What one window did, as plain arrays on the window's clock."""

    seconds: float
    ended: float              # when the drain after the window ended
    due: np.ndarray
    sent: np.ndarray          # NaN where never submitted
    dispatched: np.ndarray    # NaN where never dispatched
    done: np.ndarray          # NaN where not completed
    prompts: np.ndarray
    outputs: List[Optional[np.ndarray]]
    call_of: List[Optional[int]]   # the engine call that served request i
    calls: List[Call]         # by index: ``calls[i].index == i``
    batches: List[tuple]      # (dispatch time, requests, cause)
    max_bs: float
    compiles: int
    summary: dict

    @property
    def completed(self) -> np.ndarray:
        return ~np.isnan(self.done)

    def group(self, i: int):
        """(batch bucket, replica) that served request ``i``, or None."""
        c = self.call_of[i]
        return None if c is None else (self.calls[c].bucket,
                                       self.calls[c].replica)


def run_window(pool, traffic: dict, seconds: float, seed: int, *,
               vocab: int, warm: Optional[Dict] = None,
               counter: Optional[CompileCounter] = None,
               trace: Optional[Callable] = None) -> Window:
    """Serve ``seconds`` of the mix's schedule through ``pool`` and drain.

    MLProxy starts as a running endpoint would be: its largest batch at the
    pool's largest bucket, with ``pack`` on, and its monitor holding, for
    each (batch bucket, prompt bucket) in ``warm``, the seconds the warm-up
    call took, recorded as measured batches before the first arrival. (A
    fresh AIMD starts at one request and adds one per 30 s; an empty
    monitor makes the first batch wait the whole SLO.)
    ``trace(clock)``, where given, is a coroutine function run beside the
    load generator (it starts the profiler and marks the traced window).
    """
    from repro.core.config import OptimizerConfig, ProxyConfig, SLAConfig
    from repro.runtime.clock import WallClock, run
    from repro.runtime.loadgen import LoadGenerator
    from repro.runtime.server import AsyncProxyServer, RuntimeConfig
    from repro.runtime.targets import EngineTarget
    from repro.serving.batcher import ReplicaPoolTarget
    from repro.simulation.arrivals import Schedule

    due = schedule.arrival_times(traffic, seconds, seed)
    prompts = schedule.prompts(len(due), traffic["prompt_len"], vocab, seed)
    rows = iter(prompts)
    clock = WallClock()
    timed = TimedPool(pool, clock)
    target = EngineTarget(
        ReplicaPoolTarget(timed, prompt_len=traffic["prompt_len"],
                          gen_len=traffic["gen_len"]),
        max_concurrent=len(pool.replicas), clock=clock)
    server = AsyncProxyServer(clock=clock, config=RuntimeConfig())
    pc = ProxyConfig(
        sla=SLAConfig(slo_target=traffic["slo_ms"] / 1e3),
        optimizer=OptimizerConfig(
            initial_max_bs=max(pool.engine_cfg.batch_buckets)))
    server.add_endpoint("ep", sla=pc.sla, target=target, policy="mlproxy",
                        policy_kwargs={"proxy_config": pc}, pack=True)
    policy = server.frontend.endpoint("ep").policy
    for (bucket, _), dt in (warm or {}).items():
        for _ in range(policy.monitor.config.min_samples):
            policy.monitor.record_upstream(bucket, dt, 0.0)
    gen = LoadGenerator(server, Schedule(due), duration=seconds,
                        endpoint="ep", payload_fn=lambda: next(rows))
    programs = sum(e.compile_count for e in pool.replicas)
    compiles = counter.n if counter else 0

    async def main() -> None:
        await server.start()
        tracing = (asyncio.ensure_future(trace(clock))
                   if trace is not None else None)
        await gen.run()
        if tracing is not None:
            await tracing
        await server.drain(timeout=DRAIN_S)

    run(clock, main())
    ended = clock.now()
    new = sum(e.compile_count for e in pool.replicas) - programs
    if counter:
        new += counter.n - compiles
    n = len(due)
    sent = np.full(n, np.nan)
    dispatched = np.full(n, np.nan)
    done = np.full(n, np.nan)
    outputs: List[Optional[np.ndarray]] = [None] * n
    call_of: List[Optional[int]] = [None] * n
    for i, ticket in enumerate(gen.tickets):
        req = ticket.request
        sent[i] = req.arrival_time
        if req.dispatch_time is not None:
            dispatched[i] = req.dispatch_time
        served = (req.completion_time is not None and ticket.error is None
                  and not ticket.timed_out and not ticket.rejected
                  and not ticket.shed)
        if served:
            done[i] = req.completion_time
            outputs[i] = np.asarray(req.payload)
            call_of[i] = timed.row_call.get(prompts[i].tobytes())
    return Window(
        seconds=seconds, ended=ended, due=due, sent=sent, dispatched=dispatched, done=done,
        prompts=prompts, outputs=outputs, call_of=call_of,
        calls=sorted(timed.calls, key=lambda c: c.index),
        batches=[(t, size, cause)
                 for t, _, size, _, cause in server.dispatch_log],
        max_bs=float(policy.max_bs), compiles=new,
        summary=server.summary())
