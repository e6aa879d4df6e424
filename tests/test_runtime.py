"""Tests for the live async runtime (repro.runtime).

Covers the ISSUE-4 acceptance points: FakeClock determinism (same seed +
trace → identical dispatch decisions), admission control / backpressure,
graceful drain with the runtime conservation invariant (submitted ==
completed + rejected, zero lost), all five policies running unmodified,
sim↔live parity on a shared schedule, and the calibration bridge
round-trip (measure → fit → simulate within 10%).
"""
import asyncio
import math
import time

import numpy as np
import pytest

from repro.core import SLAConfig, ms
from repro.core.config import OptimizerConfig, ProxyConfig
from repro.runtime import (AsyncProxyServer, Calibration, DispatchTarget,
                           FakeClock, LoadGenerator, RuntimeConfig,
                           SyntheticTarget, WallClock, clamp_policy_kwargs,
                           run, run_replay)
from repro.serverless.latency import AffineLatency, MeasuredLatency, get_workload
from repro.serverless.platform import PlatformConfig
from repro.simulation.arrivals import (MMPP2, PoissonProcess, Schedule,
                                       sample_schedule)
from repro.simulation.simulator import run_simulation

SLA = SLAConfig(slo_target=ms(500))
WL = get_workload("pytorch-fashion-mnist")

ALL_POLICIES = ("passthrough", "static", "clipper", "oracle", "mlproxy")


def policy_kwargs(policy):
    if policy == "static":
        return {"batch_size": 8, "timeout": 0.2}
    if policy == "oracle":
        return {"latency_model": lambda bs: WL.percentile(bs, 95)}
    return {}


# --------------------------------------------------------------- FakeClock
class TestFakeClock:
    def test_sleep_orders_virtual_time(self):
        clock = FakeClock()
        log = []

        async def sleeper(tag, dt):
            await clock.sleep(dt)
            log.append((tag, clock.now()))

        async def main():
            await asyncio.gather(sleeper("b", 2.0), sleeper("a", 1.0),
                                 sleeper("c", 3.0))

        run(clock, main())
        assert log == [("a", 1.0), ("b", 2.0), ("c", 3.0)]
        assert clock.now() == 3.0

    def test_wait_timeout_and_event(self):
        clock = FakeClock()
        results = {}

        async def main():
            ev = asyncio.Event()

            async def setter():
                await clock.sleep(0.5)
                ev.set()

            task = asyncio.ensure_future(setter())
            results["timeout"] = await clock.wait(asyncio.Event(), 0.2)
            results["event"] = await clock.wait(ev, 10.0)
            await task

        run(clock, main())
        assert results == {"timeout": False, "event": True}
        assert clock.now() < 1.0  # event win did not burn the 10s timeout

    def test_deadlock_detection(self):
        clock = FakeClock()

        async def main():
            await asyncio.Event().wait()  # never set, no timers pending

        with pytest.raises(RuntimeError, match="deadlock"):
            run(clock, main())


# ----------------------------------------------------------- determinism
class TestDeterminism:
    def test_same_seed_identical_dispatch_decisions(self):
        """Two runs of seed+trace produce the same decision log, twice."""
        kw = dict(
            policy="mlproxy", sla=SLA, workload=WL,
            arrivals=MMPP2(rate_lo=10.0, rate_hi=80.0, mean_lo=20.0,
                           mean_hi=5.0, duration=90.0),
            duration=90.0, seed=42,
        )
        a = run_replay(**kw)
        b = run_replay(**kw)
        assert a.dispatch_log == b.dispatch_log
        assert len(a.dispatch_log) > 10
        np.testing.assert_array_equal(a.e2e_latencies, b.e2e_latencies)
        assert a.summary["p95"] == b.summary["p95"]

    def test_different_seed_differs(self):
        kw = dict(policy="mlproxy", sla=SLA, workload=WL,
                  arrivals=PoissonProcess(rate=30.0, duration=60.0),
                  duration=60.0)
        a = run_replay(seed=0, **kw)
        b = run_replay(seed=1, **kw)
        assert a.dispatch_log != b.dispatch_log


# ----------------------------------------------- admission / backpressure
class TestAdmissionControl:
    def test_max_outstanding_rejects_and_conserves(self):
        """A slow upstream + tight outstanding cap sheds load, loses none."""
        slow = AffineLatency(a=2.0, c=0.0, noise_cv=0.0)
        res = run_replay(
            policy="passthrough", sla=SLA, workload=slow,
            arrivals=PoissonProcess(rate=50.0, duration=20.0), duration=20.0,
            seed=3, config=RuntimeConfig(max_outstanding=10),
            target_concurrency=2,
        )
        c = res.conservation
        assert c["rejected"] > 0
        assert c["lost"] == 0
        assert c["submitted"] == c["completed"] + c["rejected"]

    def test_max_queue_caps_policy_queue(self):
        clock = FakeClock()
        server = AsyncProxyServer(
            clock=clock, config=RuntimeConfig(max_queue=4))
        # static policy that never dispatches before its long timeout:
        # submissions beyond the queue cap must be rejected at the door
        server.add_endpoint(
            "ep", sla=SLA,
            target=SyntheticTarget(WL, clock, rng=np.random.default_rng(0)),
            policy="static", policy_kwargs={"batch_size": 100, "timeout": 60.0},
        )

        async def main():
            await server.start()
            tickets = [server.submit(endpoint="ep") for _ in range(10)]
            rejected = sum(t.rejected for t in tickets)
            await server.drain()
            return rejected

        rejected = run(clock, main())
        assert rejected == 6  # 4 admitted into the queue, rest shed
        assert server.conservation()["lost"] == 0

    def test_no_admission_after_drain(self):
        clock = FakeClock()
        server = AsyncProxyServer(clock=clock)
        server.add_endpoint(
            "ep", sla=SLA,
            target=SyntheticTarget(WL, clock, rng=np.random.default_rng(0)),
            policy="passthrough",
        )

        async def main():
            await server.start()
            server.submit(endpoint="ep")
            await server.drain()
            late = server.submit(endpoint="ep")
            assert late.rejected
            return server.conservation()

        c = run(clock, main())
        assert c["submitted"] == 2
        assert c["completed"] == 1
        assert c["rejected"] == 1


# ------------------------------------------------------------------ drain
class TestDrain:
    @pytest.mark.parametrize("policy", ALL_POLICIES)
    def test_drain_conservation_all_policies(self, policy):
        """No request lost on shutdown for any policy, queued or in-flight.

        ``run_replay`` drains internally and ``drain()`` asserts the
        conservation invariant; this re-checks the ledger explicitly.
        """
        res = run_replay(
            policy=policy, sla=SLA, workload=WL,
            arrivals=PoissonProcess(rate=40.0, duration=30.0), duration=30.0,
            seed=5, policy_kwargs=policy_kwargs(policy),
        )
        c = res.conservation
        assert c["lost"] == 0
        assert c["outstanding"] == 0
        assert c["queued"] == 0
        assert c["submitted"] == c["completed"] + c["rejected"]
        assert res.summary["completed"] > 0

    def test_drain_flushes_queued_requests(self):
        """Requests still queued at drain are flush-dispatched, not dropped."""
        clock = FakeClock()
        server = AsyncProxyServer(clock=clock)
        server.add_endpoint(
            "ep", sla=SLA,
            target=SyntheticTarget(WL, clock, rng=np.random.default_rng(0)),
            policy="static", policy_kwargs={"batch_size": 64, "timeout": 300.0},
        )

        async def main():
            await server.start()
            tickets = [server.submit(endpoint="ep") for _ in range(7)]
            await server.drain()
            return tickets

        tickets = run(clock, main())
        assert all(t.future.done() and not t.rejected for t in tickets)
        assert server.completed == 7
        assert [e[4] for e in server.dispatch_log] == ["flush"]

    @pytest.mark.parametrize("timeout", [None, 1.0],
                             ids=["drain()", "drain(timeout=1.0)"])
    def test_drain_right_after_last_batch_returns(self, timeout):
        """Drain entered in the loop turn that finished the last batch.

        The finished task is still in the server's task set until the
        loop runs its done-callback; drain must give the loop that turn
        (an eager ``gather`` of done tasks spun here on Python 3.12+).
        """
        clock = WallClock()
        server = AsyncProxyServer(clock=clock)

        class Instant(DispatchTarget):
            async def __call__(self, batch, deadline=None):
                return None

        server.add_endpoint("ep", sla=SLA, target=Instant(),
                            policy="passthrough")

        async def main():
            await server.start()
            ticket = server.submit(endpoint="ep")
            await ticket.future
            start = time.monotonic()
            await server.drain(timeout=timeout)
            return time.monotonic() - start

        assert run(clock, main()) < 1.0
        c = server.assert_conserved(require_drained=True)
        assert c["completed"] == 1 and c["failed"] == 0


# --------------------------------------------------------------- targets
class TestTargets:
    def test_synthetic_concurrency_queueing_shows_in_latency(self):
        """With one upstream slot, queueing inflates measured latency."""
        det = AffineLatency(a=0.1, c=0.0, noise_cv=0.0)
        free = run_replay(policy="passthrough", sla=SLA, workload=det,
                          arrivals=PoissonProcess(rate=30.0, duration=10.0),
                          duration=10.0, seed=2)
        queued = run_replay(policy="passthrough", sla=SLA, workload=det,
                            arrivals=PoissonProcess(rate=30.0, duration=10.0),
                            duration=10.0, seed=2, target_concurrency=1)
        assert free.summary["p95"] == pytest.approx(0.1, rel=1e-6)
        assert queued.summary["p95"] > free.summary["p95"] * 2
        assert queued.conservation["lost"] == 0

    def test_wall_clock_short_run(self):
        """A real wall-clock run (no FakeClock) completes and conserves."""
        res = run_replay(
            policy="mlproxy", sla=SLAConfig(slo_target=ms(300)),
            workload=get_workload("sklearn-iris"),
            arrivals=PoissonProcess(rate=60.0, duration=1.0), duration=1.0,
            seed=0, clock=WallClock(),
        )
        assert res.summary["completed"] > 20
        assert res.conservation["lost"] == 0


# ------------------------------------------------------ config-time clamp
class TestPolicyCapClamp:
    def test_mlproxy_cap_clamped_to_bucket(self):
        kw = clamp_policy_kwargs("mlproxy", {}, 32)
        assert kw["optimizer"].max_bs_cap == 32

    def test_mlproxy_proxy_config_clamped(self):
        pc = ProxyConfig(sla=SLA, optimizer=OptimizerConfig(max_bs_cap=256))
        kw = clamp_policy_kwargs("mlproxy", {"proxy_config": pc}, 16)
        assert kw["proxy_config"].optimizer.max_bs_cap == 16

    def test_under_cap_untouched(self):
        opt = OptimizerConfig(max_bs_cap=8)
        kw = clamp_policy_kwargs("mlproxy", {"optimizer": opt}, 32)
        assert kw["optimizer"] is opt

    def test_static_clamped_and_error_mode(self):
        assert clamp_policy_kwargs(
            "static", {"batch_size": 100, "timeout": 0.1}, 32
        )["batch_size"] == 32
        with pytest.raises(ValueError, match="exceeds the largest"):
            clamp_policy_kwargs("static", {"batch_size": 100, "timeout": 0.1},
                                32, mode="error")

    def test_unset_clipper_oracle_cap_never_raises(self):
        """Regression: the caller never set max_cap, so neither mode may
        raise — the policy's implicit default is not a caller choice."""
        for policy in ("clipper", "oracle"):
            kw = clamp_policy_kwargs(policy, {}, 64, mode="error")
            assert kw.get("max_cap") == 64  # default 256 lowered silently
            kw = clamp_policy_kwargs(policy, {}, 64, mode="clamp")
            assert kw.get("max_cap") == 64

    def test_unset_cap_not_injected_when_default_fits(self):
        """Regression: clamping can never *raise* an unset cap — when the
        engine bucket exceeds the policy default, nothing is injected."""
        for policy in ("clipper", "oracle"):
            assert "max_cap" not in clamp_policy_kwargs(policy, {}, 512)

    def test_provided_clipper_cap_still_clamps_and_errors(self):
        assert clamp_policy_kwargs("clipper", {"max_cap": 128}, 32)[
            "max_cap"] == 32
        with pytest.raises(ValueError, match="exceeds the largest"):
            clamp_policy_kwargs("clipper", {"max_cap": 128}, 32, mode="error")

    def test_server_applies_clamp_from_target(self):
        clock = FakeClock()
        server = AsyncProxyServer(clock=clock)
        target = SyntheticTarget(WL, clock, rng=np.random.default_rng(0))
        target.max_batch = 16
        server.add_endpoint("ep", sla=SLA, target=target, policy="mlproxy")
        pol = server.frontend.endpoint("ep").policy
        assert pol.config.optimizer.max_bs_cap == 16


# ------------------------------------------------------------ sim ↔ live
class TestParity:
    def test_mlproxy_parity_on_shared_schedule(self):
        """Same schedule, transparent platform vs synthetic target:
        RT95 / violations / batching within the documented tolerance."""
        duration = 120.0
        times = sample_schedule(PoissonProcess(rate=30.0, duration=duration),
                                7, duration)
        transparent = PlatformConfig(
            container_concurrency=10**6, cold_start=0.0, min_scale=1,
            max_scale=1, initial_scale=1, ps_slowdown=0.0,
            scale_to_zero_grace=1e12,
        )
        sim = run_simulation(policy="mlproxy", sla=SLA, workload=WL,
                             arrivals=Schedule(times),
                             platform_config=transparent,
                             duration=duration, seed=7)
        live = run_replay(policy="mlproxy", sla=SLA, workload=WL,
                          arrivals=Schedule(times), duration=duration, seed=7)
        assert live.summary["completed"] == sim.summary["completed"] == len(times)
        assert live.summary["p95"] == pytest.approx(sim.summary["p95"], rel=0.10)
        assert abs(live.summary["violation_pct"]
                   - sim.summary["violation_pct"]) < 2.0
        assert live.summary["dispatched_batches"] == pytest.approx(
            sim.policy_stats["dispatched_batches"], rel=0.10)

    def test_schedule_replays_identically_in_both_worlds(self):
        """The Schedule process hands both drivers the same instants."""
        times = sample_schedule(PoissonProcess(rate=20.0, duration=30.0),
                                0, 30.0)
        sched = Schedule(times)
        rng = np.random.default_rng(0)
        swept = []
        t = 0.0
        while t < 30.0:
            swept.extend(sched.next_arrivals(t, rng, 7.0).tolist())
            t += 7.0
        np.testing.assert_allclose(swept, times)


# ------------------------------------------------------------ calibration
class TestCalibration:
    def _samples(self, model, buckets=(1, 2, 4, 8), n=200, seed=0):
        rng = np.random.default_rng(seed)
        return {b: [model.sample(b, rng) for _ in range(n)] for b in buckets}

    def test_affine_fit_recovers_noiseless_curve(self):
        truth = AffineLatency(a=0.05, c=0.01, noise_cv=0.0)
        fit = AffineLatency.fit([(b, truth.mean(b)) for b in (1, 2, 4, 8, 16)])
        assert fit.a == pytest.approx(0.05, rel=1e-6)
        assert fit.c == pytest.approx(0.01, rel=1e-6)

    def test_measured_from_samples_and_noise_estimate(self):
        truth = AffineLatency(a=0.05, c=0.01, noise_cv=0.2)
        m = MeasuredLatency.from_samples(self._samples(truth))
        for b in (1, 2, 4, 8):
            assert m.mean(b) == pytest.approx(truth.mean(b), rel=0.05)
        assert m.noise_cv == pytest.approx(0.2, rel=0.3)

    def test_roundtrip_within_10pct(self):
        """Acceptance: measure → fit → simulate reproduces measured means
        within 10% across buckets."""
        truth = get_workload("tfserving-mobilenet")
        calib = Calibration.from_samples(self._samples(truth), source="test")
        errors = calib.verify_roundtrip(rtol=0.10)
        assert set(errors) == {1, 2, 4, 8}

    def test_json_roundtrip(self, tmp_path):
        truth = AffineLatency(a=0.1, c=0.005, noise_cv=0.1)
        calib = Calibration.from_samples(self._samples(truth), source="t")
        path = str(tmp_path / "calib.json")
        calib.save(path)
        loaded = Calibration.load(path)
        assert loaded == calib
        assert loaded.measured_model().mean(4) == pytest.approx(
            calib.measured_model().mean(4))

    def test_live_run_measures_buckets(self):
        """bucket_samples from a live run fit into a usable calibration."""
        res = run_replay(
            policy="mlproxy", sla=SLAConfig(slo_target=ms(1000)),
            workload=get_workload("tfserving-mobilenet"),
            arrivals=PoissonProcess(rate=40.0, duration=60.0), duration=60.0,
            seed=7, policy_kwargs={"bucketing": "pow2"},
        )
        calib = Calibration.from_samples(res.bucket_samples, source="live")
        assert calib.buckets and all(s.n > 0 for s in calib.buckets)
        model = calib.measured_model()
        assert math.isfinite(model.mean(1)) and model.mean(1) > 0


# -------------------------------------------------------------- loadgen
class TestLoadGenerator:
    def test_arrivals_land_on_schedule(self):
        clock = FakeClock()
        server = AsyncProxyServer(clock=clock)
        server.add_endpoint(
            "ep", sla=SLA,
            target=SyntheticTarget(WL, clock, rng=np.random.default_rng(0)),
            policy="passthrough",
        )
        times = np.array([0.5, 1.0, 2.25])
        gen = LoadGenerator(server, Schedule(times), duration=10.0,
                            endpoint="ep")

        async def main():
            await server.start()
            tickets = await gen.run()
            await server.drain()
            return tickets

        tickets = run(clock, main())
        arrivals = [t.request.arrival_time for t in tickets]
        np.testing.assert_allclose(arrivals, times)
