"""Unit tests for the Smart Monitor (windows, percentiles, fallbacks)."""
import math
import random

import pytest

from repro.core import MonitorConfig, SLAConfig, SmartMonitor
from repro.core.monitor import LatencyWindow, P2Quantile, _theil_sen_fit

SLA = SLAConfig(slo_target=0.5)


def test_latency_window_percentile_nearest_rank():
    w = LatencyWindow(maxlen=100, horizon=1e9)
    for i, v in enumerate([10.0, 20.0, 30.0, 40.0]):
        w.add(float(i), v)
    assert w.percentile(50) == 20.0
    assert w.percentile(95) == 40.0
    assert w.percentile(100) == 40.0


def test_latency_window_horizon_eviction():
    w = LatencyWindow(maxlen=100, horizon=10.0)
    w.add(0.0, 1.0)
    w.add(5.0, 2.0)
    w.add(20.0, 3.0)
    assert w.values(now=21.0) == [2.0, 3.0][1:] or w.values(now=21.0) == [3.0]
    # at t=21, cutoff=11: sample at t=5 evicted too
    assert w.values(now=21.0) == [3.0]


def test_latency_window_maxlen():
    w = LatencyWindow(maxlen=8, horizon=1e9)
    for i in range(100):
        w.add(float(i), float(i))
    assert len(w) == 8
    assert w.values() == [float(i) for i in range(92, 100)]


def test_p2_quantile_converges_to_empirical():
    rng = random.Random(0)
    est = P2Quantile(0.95)
    xs = [rng.expovariate(1.0) for _ in range(5000)]
    for x in xs:
        est.add(x)
    emp = sorted(xs)[int(0.95 * len(xs))]
    assert est.value() == pytest.approx(emp, rel=0.15)


def test_p2_quantile_few_samples():
    est = P2Quantile(0.95)
    for x in [1.0, 2.0, 3.0]:
        est.add(x)
    assert est.value() == 3.0


def test_theil_sen_fit_recovers_line():
    pts = [(1.0, 0.1 + 0.02 * 1), (2.0, 0.1 + 0.02 * 2), (4.0, 0.1 + 0.02 * 4),
           (8.0, 0.1 + 0.02 * 8)]
    a, b = _theil_sen_fit(pts)
    assert a == pytest.approx(0.1, abs=1e-9)
    assert b == pytest.approx(0.02, abs=1e-9)


def test_monitor_exact_window_path():
    mon = SmartMonitor(MonitorConfig(min_samples=3), SLA)
    for i in range(10):
        mon.record_upstream(4, 0.1 + 0.001 * i, now=float(i))
    est = mon.upstream_percentile(4, now=10.0)
    assert 0.1 <= est <= 0.11


def test_monitor_regression_fallback_for_unseen_size():
    mon = SmartMonitor(MonitorConfig(min_samples=1), SLA)
    # populate sizes 1 and 2 with a linear curve lat = 0.05 + 0.01*bs
    for bs in (1, 2, 4):
        for i in range(5):
            mon.record_upstream(bs, 0.05 + 0.01 * bs, now=float(i))
    est8 = mon.upstream_percentile(8, now=10.0)
    assert est8 == pytest.approx(0.05 + 0.01 * 8, rel=0.05)


def test_monitor_extrapolation_floor():
    # A downhill fit (big batches measured cheaper, e.g. during a cold-start
    # storm at bs=8) extrapolated far past the data must floor at half the
    # cheapest observed percentile — never go to zero or negative.
    mon = SmartMonitor(MonitorConfig(min_samples=1), SLA)
    for i in range(5):
        mon.record_upstream(8, 1.0, now=float(i))
        mon.record_upstream(16, 0.5, now=float(i))
    # fit: slope -0.0625, intercept 1.5 → raw estimate at bs=40 is -1.0
    est = mon.upstream_percentile(40, now=10.0)
    assert est == pytest.approx(0.5 * 0.5)  # 0.5 × min observed percentile
    # interpolation between the observed sizes is untouched by the floor
    assert mon.upstream_percentile(12, now=10.0) == pytest.approx(0.75)


def test_monitor_retry_accounting():
    mon = SmartMonitor(MonitorConfig(), SLA)
    mon.record_upstream(2, 0.1, now=0.0)                 # clean
    mon.record_upstream(2, 0.3, now=1.0, attempts=3)     # crash-retried
    assert mon.lifetime_upstream_batches == 2
    assert mon.lifetime_upstream_attempts == 4
    assert mon.lifetime_retried_batches == 1
    assert mon.retry_rate() == pytest.approx(0.5)
    state = mon.snapshot()
    mon2 = SmartMonitor(MonitorConfig(), SLA)
    mon2.restore(state)
    assert mon2.retry_rate() == pytest.approx(0.5)


def test_monitor_optimistic_default_before_any_data():
    mon = SmartMonitor(MonitorConfig(optimistic_default=0.0), SLA)
    assert mon.upstream_percentile(5, now=0.0) == 0.0


def test_monitor_timeout_ratio_and_reset():
    mon = SmartMonitor(MonitorConfig(), SLA)
    mon.record_dispatch(2, "timeout")
    mon.record_dispatch(4, "full")
    mon.record_dispatch(4, "full")
    assert mon.timeout_ratio() == pytest.approx(1 / 3)
    mon.reset_interval()
    assert mon.timeout_ratio() == 0.0


def test_monitor_violation_accounting():
    mon = SmartMonitor(MonitorConfig(), SLA)
    mon.record_e2e(0.4, now=0.0)   # ok
    mon.record_e2e(0.6, now=0.0)   # violation (slo=0.5)
    assert mon.violation_rate() == pytest.approx(0.5)


def test_monitor_snapshot_restore_roundtrip():
    mon = SmartMonitor(MonitorConfig(estimator="p2"), SLA)
    for i in range(20):
        mon.record_upstream(2, 0.1 + 0.01 * (i % 5), now=float(i))
        mon.record_e2e(0.2, now=float(i))
    mon.record_dispatch(2, "timeout")
    state = mon.snapshot()
    mon2 = SmartMonitor(MonitorConfig(estimator="p2"), SLA)
    mon2.restore(state)
    assert mon2.upstream_percentile(2, now=20.0) == mon.upstream_percentile(2, now=20.0)
    assert mon2.timeout_ratio() == mon.timeout_ratio()
    assert mon2.violation_rate() == mon.violation_rate()


def test_p2_estimator_backend():
    mon = SmartMonitor(MonitorConfig(estimator="p2", min_samples=5), SLA)
    rng = random.Random(1)
    xs = [0.1 + 0.02 * rng.random() for _ in range(500)]
    for i, x in enumerate(xs):
        mon.record_upstream(3, x, now=float(i))
    emp = sorted(xs)[int(0.95 * len(xs))]
    assert mon.upstream_percentile(3, now=600.0) == pytest.approx(emp, rel=0.1)


# --------------------------------------------------- sorted-cache coherence
def _reference_percentile(pairs, q, now, horizon, outlier_mult=0.0):
    """The pre-cache implementation: evict by horizon, full sort per call."""
    vals = sorted(v for (t, v) in pairs if t >= now - horizon)
    if not vals:
        return None
    if outlier_mult > 0 and len(vals) >= 4:
        med = vals[len(vals) // 2]
        kept = [v for v in vals if v <= outlier_mult * med]
        if kept:
            vals = kept
    rank = min(len(vals) - 1, max(0, math.ceil(q / 100.0 * len(vals)) - 1))
    return vals[rank]


def test_latency_window_cache_matches_reference_under_churn():
    # interleaved add / horizon-evict / maxlen-evict / winsorized queries
    # must agree with a from-scratch sort every time
    rng = random.Random(7)
    w = LatencyWindow(maxlen=16, horizon=5.0)
    pairs = []
    t = 0.0
    for _ in range(3000):
        t += rng.random() * 0.8
        v = rng.random() * (10.0 if rng.random() < 0.1 else 1.0)
        w.add(t, v)
        pairs.append((t, v))
        pairs = pairs[-16:]  # mirror maxlen
        q = rng.choice([50.0, 90.0, 95.0, 99.0])
        mult = rng.choice([0.0, 3.0, 5.0])
        assert w.percentile(q, now=t, outlier_mult=mult) == \
            _reference_percentile(pairs, q, t, 5.0, mult)


def test_latency_window_cache_survives_maxlen_eviction():
    w = LatencyWindow(maxlen=4, horizon=1e9)
    for i in range(4):
        w.add(float(i), float(i))
    assert w.percentile(100) == 3.0  # builds the cache
    w.add(4.0, 10.0)  # deque evicts value 0.0; cache must drop it too
    assert w.percentile(1) == 1.0
    assert w.percentile(100) == 10.0
    assert sorted(w.values()) == [1.0, 2.0, 3.0, 10.0]


def test_latency_window_count_evicts_like_values():
    w = LatencyWindow(maxlen=100, horizon=10.0)
    w.add(0.0, 1.0)
    w.add(5.0, 2.0)
    w.add(20.0, 3.0)
    assert w.count(21.0) == 1
    assert len(w) == 1


def test_monitor_counts_estimate_fallbacks():
    mon = SmartMonitor(MonitorConfig(min_samples=3), SLA)
    # before any sample: the optimistic default is a fallback
    mon.upstream_percentile(8, now=0.0)
    assert mon.lifetime_estimate_fallbacks == 1
    for bs in (8, 16):
        for _ in range(3):
            mon.record_upstream(bs, 0.01 * bs, now=0.0)
    # a size with a window of its own: answered from it, not counted
    assert mon.upstream_percentile(16, now=1.0) == pytest.approx(0.16)
    assert mon.lifetime_estimate_fallbacks == 1
    # a size with no window: the regression answers, and it counts
    assert mon.upstream_percentile(12, now=1.0) == pytest.approx(0.12)
    assert mon.lifetime_estimate_fallbacks == 2
    # a window below min_samples is no window either
    mon.record_upstream(4, 0.04, now=1.0)
    mon.upstream_percentile(4, now=1.0)
    assert mon.lifetime_estimate_fallbacks == 3
    # bound into an aggregator's registry under the endpoint's prefix
    from repro.obs.metrics import MetricsRegistry
    reg = MetricsRegistry()
    mon.register_metrics(reg, prefix="endpoint.ep")
    assert reg.value("endpoint.ep.lifetime_estimate_fallbacks") == 3
