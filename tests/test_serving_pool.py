"""ReplicaPool bookkeeping tests (engine stubbed — no JAX compilation).

Regression coverage for the scale-down/re-grow bug: scaling down used to
mark tail replicas unhealthy without removing them, so a later scale-up
appended fresh replicas while the dead ones kept consuming round-robin
slots and ``n_healthy`` drifted from the pool size.
"""
import numpy as np
import pytest

import repro.serving.engine as engine_mod
from repro.serving.engine import ReplicaPool


class _StubEngine:
    """Stands in for InferenceEngine: records calls, no JAX."""

    def __init__(self, cfg, engine_cfg, params=None, rng=None, device=None):
        self.cfg = cfg
        self.engine_cfg = engine_cfg
        self.params = params if params is not None else object()
        self.calls = 0
        self.fail = False

    def generate(self, prompts, gen_len=None):
        if self.fail:
            raise engine_mod.ReplicaUnavailable("injected replica failure")
        self.calls += 1
        return prompts[:, :1], {"latency_s": 0.001, "bucket": len(prompts)}


@pytest.fixture
def pool(monkeypatch):
    monkeypatch.setattr(engine_mod, "InferenceEngine", _StubEngine)
    # jax.random.PRNGKey(0) default arg is evaluated at call time inside
    # __init__ only when rng is None; pass a dummy to stay JAX-free.
    return ReplicaPool(cfg=None, engine_cfg=None, n_replicas=4, rng=np.zeros(2))


def test_scale_down_removes_replicas(pool):
    pool.scale_to(2)
    assert len(pool.replicas) == 2
    assert len(pool.healthy) == 2
    assert pool.n_healthy == 2


def test_scale_down_then_up_regression(pool):
    """The seed bug: shrink left dead replicas in round-robin rotation."""
    pool.scale_to(2)
    pool.scale_to(4)
    assert len(pool.replicas) == 4
    assert pool.n_healthy == 4  # used to drift: dead slots never revived
    # every replica actually serves traffic again
    for _ in range(8):
        _, timing = pool.generate(np.zeros((1, 4), np.int32))
        assert 0 <= timing["replica"] < 4
    assert all(r.calls >= 1 for r in pool.replicas)


def test_scale_down_resets_round_robin_cursor(pool):
    pool._rr = 3
    pool.scale_to(1)
    _, timing = pool.generate(np.zeros((1, 4), np.int32))
    assert timing["replica"] == 0


def test_scale_to_zero_then_up(pool):
    pool.scale_to(0)
    assert pool.replicas == [] and pool.n_healthy == 0
    with pytest.raises(RuntimeError, match="no healthy replicas"):
        pool.generate(np.zeros((1, 4), np.int32))
    pool.scale_to(3)
    assert pool.n_healthy == 3


def test_scale_to_negative_rejected(pool):
    with pytest.raises(ValueError):
        pool.scale_to(-1)


def test_pool_target_keeps_trailing_prompt_context(pool):
    """Over-long payloads must keep the LAST prompt_len tokens: with
    left-padding the engine continues from the trailing context."""
    from repro.core.request import Batch, Request as Req
    from repro.serving.batcher import ReplicaPoolTarget

    target = ReplicaPoolTarget(pool, prompt_len=4)
    long_payload = np.arange(10, dtype=np.int32)  # tokens 0..9
    batch = Batch(requests=[Req(arrival_time=0.0, payload=long_payload)],
                  dispatch_time=0.0, cause="full")
    prompts = target._prompts(batch)
    assert prompts.tolist() == [[6, 7, 8, 9]]  # tail, not head
    short = Batch(requests=[Req(arrival_time=0.0,
                                payload=np.array([5, 6], np.int32))],
                  dispatch_time=0.0, cause="full")
    assert target._prompts(short).tolist() == [[0, 0, 5, 6]]  # left-padded


def test_failover_skips_failed_replica(pool):
    pool.replicas[1].fail = True
    seen = set()
    for _ in range(8):
        _, timing = pool.generate(np.zeros((1, 4), np.int32))
        seen.add(timing["replica"])
    assert 1 not in seen
    assert pool.n_healthy == 3
    assert pool.retries >= 1


def test_device_error_propagates_without_failover(pool):
    """A device error (OOM, failed compile) reaches the caller with its
    message: it neither marks replicas down nor turns into the pool's own
    "no healthy replicas"."""
    import jax

    def device_error(prompts, gen_len=None):
        raise jax.errors.JaxRuntimeError("RESOURCE_EXHAUSTED: out of HBM")

    for eng in pool.replicas:
        eng.generate = device_error
    with pytest.raises(jax.errors.JaxRuntimeError,
                       match="RESOURCE_EXHAUSTED") as err:
        pool.generate(np.zeros((1, 4), np.int32))
    assert "no healthy replicas" not in str(err.value)
    assert pool.n_healthy == 4 and pool.retries == 0
