"""Unit tests for Algorithm 1 (queue scheduler) and Algorithm 2 (AIMD)."""
import collections
import random

import pytest

from repro.core import (
    AIMDBatchOptimizer,
    MLProxy,
    MonitorConfig,
    OptimizerConfig,
    ProxyConfig,
    Request,
    SLAConfig,
    SmartMonitor,
)
from repro.core.policies import make_policy
from repro.core.scheduler import QueueScheduler


def make_sched(slo=1.0, max_bs=4, est=None, **monitor_kw):
    """Scheduler with a monitor pre-seeded so RT95[bs] == est(bs)."""
    sla = SLAConfig(slo_target=slo)
    cfg = ProxyConfig(sla=sla, monitor=MonitorConfig(min_samples=1, **monitor_kw))
    mon = SmartMonitor(cfg.monitor, sla)
    if est is not None:
        for bs in range(1, 64):
            for _ in range(3):
                mon.record_upstream(bs, est(bs), now=0.0)
    out = []
    sched = QueueScheduler(cfg, mon, dispatch_fn=out.append, max_bs_fn=lambda: max_bs)
    return sched, mon, out


def test_dispatch_on_max_batch_size():
    sched, _, out = make_sched(max_bs=3, est=lambda bs: 0.1)
    for i in range(3):
        sched.on_arrival(Request(arrival_time=float(i) * 0.01), now=float(i) * 0.01)
    assert len(out) == 1
    assert out[0].size == 3
    assert out[0].cause == "full"
    assert sched.queue_len == 0


def test_timeout_computation_matches_equation():
    # RT95[bs] = 0.1 + 0.05*bs ; SLO = 1.0
    sched, _, out = make_sched(slo=1.0, max_bs=16, est=lambda bs: 0.1 + 0.05 * bs)
    sched.on_arrival(Request(arrival_time=10.0), now=10.0)
    # N_q = 1 → probe bs=2 → est = 0.2 ; DTO = 0.8 ; FRT = 0 → deadline 10.8
    assert sched.next_deadline == pytest.approx(10.8)
    sched.on_arrival(Request(arrival_time=10.3), now=10.3)
    # N_q = 2 → probe bs=3 → est = 0.25 ; DTO = 0.75 ; FRT = 0.3 → 10.3+0.45
    assert sched.next_deadline == pytest.approx(10.75)
    assert not out


def test_negative_timeout_dispatches_immediately():
    sched, _, out = make_sched(slo=0.2, max_bs=16, est=lambda bs: 0.5)
    sched.on_arrival(Request(arrival_time=0.0), now=0.0)
    assert len(out) == 1 and out[0].cause == "timeout"


def test_timer_fires_dispatch():
    sched, _, out = make_sched(slo=1.0, max_bs=16, est=lambda bs: 0.1)
    sched.on_arrival(Request(arrival_time=0.0), now=0.0)
    deadline = sched.next_deadline
    sched.on_timer(deadline - 0.01)  # early → no-op
    assert not out
    sched.on_timer(deadline)
    assert len(out) == 1 and out[0].cause == "timeout"
    assert sched.next_deadline is None


def test_frt_uses_oldest_request():
    sched, _, _ = make_sched(slo=1.0, max_bs=100, est=lambda bs: 0.0)
    sched.on_arrival(Request(arrival_time=0.0), now=0.0)
    for t in (0.2, 0.4, 0.6):
        sched.on_arrival(Request(arrival_time=t), now=t)
    # DTO = 1.0 - 0 = 1.0, FRT = 0.6 → deadline = 0.6 + (1.0 - 0.6) = 1.0
    assert sched.next_deadline == pytest.approx(1.0)


def test_flush():
    sched, _, out = make_sched(max_bs=10, est=lambda bs: 0.0)
    sched.on_arrival(Request(arrival_time=0.0), now=0.0)
    sched.flush(now=0.5)
    assert len(out) == 1 and out[0].cause == "flush"


def test_bucketing_pads_to_pow2():
    sla = SLAConfig(slo_target=1.0)
    cfg = ProxyConfig(sla=sla, monitor=MonitorConfig(min_samples=1), bucketing="pow2")
    mon = SmartMonitor(cfg.monitor, sla)
    out = []
    sched = QueueScheduler(cfg, mon, dispatch_fn=out.append, max_bs_fn=lambda: 5)
    for i in range(5):
        sched.on_arrival(Request(arrival_time=0.0), now=0.0)
    assert out[0].size == 5 and out[0].bucket_size == 8


# ----------------------------------------------------------------- Algorithm 2

def make_opt(slo=1.0, **kw):
    sla = SLAConfig(slo_target=slo)
    mon = SmartMonitor(MonitorConfig(), sla)
    opt = AIMDBatchOptimizer(OptimizerConfig(**kw), sla, mon)
    return opt, mon


def test_aimd_additive_increase():
    opt, mon = make_opt()
    mon.record_e2e(0.1, now=0.0)  # well under SLO
    opt.update(now=30.0)
    assert opt.max_bs == 2
    opt.update(now=60.0)
    assert opt.max_bs == 3


def test_aimd_multiplicative_decrease_on_latency():
    opt, mon = make_opt()
    for _ in range(10):
        opt.update(now=0.0)  # no data → increase
    assert opt.max_bs == 11
    mon.record_e2e(0.9, now=300.0)  # > 0.8 * SLO → violation
    opt.update(now=300.0)
    assert opt.max_bs_raw == pytest.approx(11.0 * 0.8)


def test_aimd_decrease_on_timeout_ratio():
    opt, mon = make_opt(to_thresh=0.5)
    for _ in range(4):
        opt.update(now=0.0)
    start = opt.max_bs_raw
    mon.record_dispatch(2, "timeout")
    mon.record_dispatch(2, "timeout")
    mon.record_dispatch(2, "full")
    opt.update(now=100.0)
    assert opt.max_bs_raw == pytest.approx(start * 0.8)
    # interval counters reset after update
    assert mon.timeout_ratio() == 0.0


def test_aimd_respects_interval():
    opt, mon = make_opt(update_interval=30.0)
    assert not opt.maybe_update(now=0.0)  # anchors
    assert not opt.maybe_update(now=10.0)
    assert opt.maybe_update(now=31.0)
    assert not opt.maybe_update(now=40.0)


def test_aimd_floor_at_one():
    opt, mon = make_opt()
    mon.record_e2e(10.0, now=0.0)
    opt._last_update = 0.0
    for t in range(1, 50):
        mon.record_e2e(10.0, now=30.0 * t)
        opt.update(now=30.0 * t)
    assert opt.max_bs == 1


# ----------------------------------------------------------------- MLProxy


def test_proxy_end_to_end_flow():
    sla = SLAConfig(slo_target=1.0)
    cfg = ProxyConfig(
        sla=sla,
        monitor=MonitorConfig(min_samples=1),
        optimizer=OptimizerConfig(initial_max_bs=8),
    )
    batches = []
    proxy = MLProxy(cfg, dispatch_fn=batches.append)
    # seed latency knowledge: upstream takes 0.1 s for any size
    for bs in range(1, 8):
        proxy.monitor.record_upstream(bs, 0.1, now=0.0)
    t = 0.0
    proxy.on_request(Request(arrival_time=t), now=t)
    assert proxy.scheduler.next_deadline == pytest.approx(0.9)
    proxy.on_timer(0.9)
    assert len(batches) == 1
    proxy.on_response(batches[0], upstream_latency=0.1, now=1.0)
    stats = proxy.stats(now=1.0)
    assert stats["dispatched_requests"] == 1
    assert stats["violation_rate"] == 0.0


def test_proxy_snapshot_restore_resumes_warm():
    sla = SLAConfig(slo_target=1.0)
    cfg = ProxyConfig(sla=sla, monitor=MonitorConfig(min_samples=1))
    batches = []
    proxy = MLProxy(cfg, dispatch_fn=batches.append)
    for bs in range(1, 8):
        proxy.monitor.record_upstream(bs, 0.25, now=0.0)
    for _ in range(5):
        proxy.optimizer.update(now=0.0)
    state = proxy.snapshot()

    proxy2 = MLProxy(cfg, dispatch_fn=batches.append)
    proxy2.restore(state)
    assert proxy2.max_bs == proxy.max_bs
    assert proxy2.monitor.upstream_percentile(4, 0.0) == pytest.approx(0.25)


# ------------------------------------------- pricing on a bucketed endpoint

BUCKETS = (1, 2, 4, 8, 16, 32)
# Warm wall time (s) of one engine call per batch bucket: qwen2-0.5b,
# 512-token prompts + 64 generated, on one TPU v5e (PERF.md §6).
QWEN2_BUCKET_S = {1: 0.11198, 2: 0.13563, 4: 0.14622, 8: 0.17173,
                  16: 0.24895, 32: 0.39211}


def bucketed_sched(bucketing=BUCKETS, max_bs=32, slo=0.6):
    """Scheduler whose monitor holds one window per engine bucket only,
    seeded as a warm endpoint is (min_samples samples each)."""
    sla = SLAConfig(slo_target=slo)
    cfg = ProxyConfig(sla=sla, bucketing=bucketing)
    mon = SmartMonitor(cfg.monitor, sla)
    for bs, dt in QWEN2_BUCKET_S.items():
        for _ in range(cfg.monitor.min_samples):
            mon.record_upstream(bs, dt, now=0.0)
    out = []
    sched = QueueScheduler(cfg, mon, dispatch_fn=out.append,
                           max_bs_fn=lambda: max_bs)
    return sched, mon, out


@pytest.mark.parametrize("case", ["13_queued", "16_queued_edge", "unbucketed"])
def test_timeout_prices_probe_at_its_bucket(case):
    if case == "13_queued":
        # N_q + 1 = 14 runs as bucket 16: DTO = SLO − RT95[16].
        sched, mon, out = bucketed_sched()
        for _ in range(13):
            sched.on_arrival(Request(arrival_time=0.0), now=0.0)
        assert not out
        assert sched.next_deadline == pytest.approx(0.6 - QWEN2_BUCKET_S[16])
        assert mon.lifetime_estimate_fallbacks == 0
    elif case == "16_queued_edge":
        # The 17th request would spill the batch into bucket 32: once the
        # oldest is older than SLO − RT95[32], the 16 go at once.
        sched, mon, out = bucketed_sched()
        frt = 0.6 - QWEN2_BUCKET_S[32] + 0.01
        sched.on_arrival(Request(arrival_time=0.0), now=0.0)
        for _ in range(15):
            sched.on_arrival(Request(arrival_time=frt), now=frt)
        assert len(out) == 1 and out[0].size == 16
        assert out[0].cause == "timeout" and out[0].bucket_size == 16
        assert mon.lifetime_estimate_fallbacks == 0
    else:
        # No bucketing: the raw N_q + 1 = 14 has no window of its own and
        # is priced by the regression over the populated sizes, as before.
        sched, mon, out = bucketed_sched(bucketing=None)
        for _ in range(13):
            sched.on_arrival(Request(arrival_time=0.0), now=0.0)
        # probes 2..14: all but 2, 4 and 8 fell back
        assert mon.lifetime_estimate_fallbacks == 10
        reg = mon._regression_estimate(14, 0.0)
        assert reg != pytest.approx(QWEN2_BUCKET_S[16])
        assert sched.next_deadline == pytest.approx(0.6 - reg)


def replay_attainment(seed, rate=32.0, seconds=51.0, slo=0.6, hop=0.004,
                      jitter=0.01):
    """Share of Poisson arrivals served within ``slo`` by MLProxy in front
    of a one-at-a-time engine that runs each batch at its padded bucket's
    time, on a simulated clock. MLProxy starts warm: ``Max_BS`` at the top
    bucket with packing, and one window per bucket."""
    rng = random.Random(seed)
    sla = SLAConfig(slo_target=slo)
    cfg = ProxyConfig(sla=sla, pack_buckets=BUCKETS,
                      optimizer=OptimizerConfig(initial_max_bs=BUCKETS[-1]))
    done = collections.deque()  # (completion time, batch), in order

    def dispatch(batch):
        start = batch.dispatch_time + hop
        if done:
            start = max(start, done[-1][0])
        service = QWEN2_BUCKET_S[batch.effective_size]
        done.append((start + service * (1.0 + jitter * rng.uniform(-1, 1)),
                     batch))

    policy = make_policy("mlproxy", sla, dispatch, proxy_config=cfg)
    for bs, dt in QWEN2_BUCKET_S.items():
        for _ in range(cfg.monitor.min_samples):
            policy.monitor.record_upstream(bs, dt, 0.0)
    arrivals, t = [], rng.expovariate(rate)
    while t < seconds:
        arrivals.append(Request(arrival_time=t))
        t += rng.expovariate(rate)
    i = 0
    while i < len(arrivals) or done or policy.queue_len:
        cands = [policy.next_event_time(t)]
        if i < len(arrivals):
            cands.append(arrivals[i].arrival_time)
        if done:
            cands.append(done[0][0])
        t = min(c for c in cands if c is not None)
        while done and done[0][0] <= t:
            end, batch = done.popleft()
            policy.on_response(batch, end - batch.dispatch_time, end)
        if i < len(arrivals) and arrivals[i].arrival_time <= t:
            policy.on_request(arrivals[i], t)
            i += 1
        policy.on_timer(t)
    assert all(r.completion_time is not None for r in arrivals)
    within = sum(r.e2e_latency <= slo for r in arrivals)
    return within / len(arrivals), policy.monitor.lifetime_estimate_fallbacks


@pytest.mark.parametrize("seed", [3000000011, 3000000012, 3000000013])
def test_replay_bucketed_endpoint_meets_its_slo(seed):
    # qwen2-0.5b's chat cell on a simulated clock: 32 req/s, SLO 600 ms.
    # Priced at the raw N_q + 1, 83–89% land within the SLO.
    attain, fallbacks = replay_attainment(seed)
    assert attain >= 0.97
    assert fallbacks == 0
