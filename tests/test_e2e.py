"""End-to-end behaviour tests: paper-claim validation at test scale,
control-plane fault tolerance, elastic restore, engine-in-the-loop serving."""
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core import (
    MLProxy,
    MonitorConfig,
    OptimizerConfig,
    ProxyConfig,
    Request,
    SLAConfig,
    ms,
)
from repro.serverless.latency import get_workload
from repro.serverless.platform import PlatformConfig
from repro.simulation.arrivals import TraceModulatedPoisson
from repro.simulation.simulator import run_simulation
from repro.simulation.traces import synthetic_trace


def _sim(policy, *, seed=0, duration=900.0, rate=30.0, slo=500.0,
         workload="pytorch-fashion-mnist", trace="wc", platform=None,
         policy_kwargs=None):
    tr = synthetic_trace(trace, duration=duration, seed=seed).scaled(rate)
    return run_simulation(
        policy=policy, sla=SLAConfig(slo_target=ms(slo)),
        workload=get_workload(workload),
        arrivals=TraceModulatedPoisson(tr),
        platform_config=platform or PlatformConfig(initial_scale=1),
        duration=duration, warmup=duration / 5, seed=seed,
        policy_kwargs=policy_kwargs or {},
    ).summary


def test_paper_claim_cost_and_slo_reduction():
    """Paper Table 3 directionally: containers ↓ sharply with violations
    held low and avg batch in the paper's band (T4-like diurnal trace,
    capacity-capped cluster as in the paper's 27-vCPU deployment)."""
    pc = PlatformConfig(initial_scale=1, max_scale=27, cold_start=10.0)
    base = _sim("passthrough", rate=60.0, slo=1000.0, trace="t4", platform=pc)
    prox = _sim("mlproxy", rate=60.0, slo=1000.0, trace="t4", platform=pc)
    reduction = 1 - prox["avg_containers"] / base["avg_containers"]
    assert reduction > 0.5, (base, prox)
    assert prox["violation_pct"] < max(2 * base["violation_pct"], 1.0)
    assert 2.0 < prox["avg_batch_size"] < 20.0


def test_proxy_crash_restart_mid_run():
    """Control-plane fault tolerance: snapshot mid-run, restore into a new
    proxy, behaviour (Max_BS, latency knowledge) carries over."""
    sla = SLAConfig(slo_target=0.5)
    cfg = ProxyConfig(sla=sla, monitor=MonitorConfig(min_samples=1),
                      optimizer=OptimizerConfig(update_interval=5.0))
    sink = []
    proxy = MLProxy(cfg, dispatch_fn=sink.append)
    t = 0.0
    for i in range(200):
        t += 0.02
        proxy.on_request(Request(arrival_time=t), now=t)
        proxy.on_timer(t)
        while sink:
            b = sink.pop()
            proxy.on_response(b, 0.05 + 0.001 * b.size, now=t + 0.06)
    snap = proxy.snapshot()
    learned_bs = proxy.max_bs
    est = proxy.monitor.upstream_percentile(2, now=t)

    proxy2 = MLProxy(cfg, dispatch_fn=sink.append)
    proxy2.restore(snap)
    assert proxy2.max_bs == learned_bs
    assert proxy2.monitor.upstream_percentile(2, now=t) == est
    # and it keeps operating
    proxy2.on_request(Request(arrival_time=t + 1), now=t + 1)
    assert proxy2.scheduler.queue_len >= 0


def test_platform_fault_injection_does_not_lose_requests():
    pc = PlatformConfig(initial_scale=2, failure_prob_per_batch=0.01,
                        straggler_prob=0.02, straggler_mult=4.0,
                        hedge_factor=3.0)
    s = _sim("mlproxy", platform=pc, duration=600.0)
    # all requests that arrived post-warmup completed (at-least-once)
    assert s["completed"] > 0
    assert s["failed_attempts"] >= 0
    assert s["violation_pct"] < 25.0


def test_elastic_checkpoint_restore_other_mesh(tmp_path):
    """Train on the default device, restore onto a 2x2 mesh (subprocess
    with 4 virtual devices)."""
    code = f"""
import dataclasses, jax, jax.numpy as jnp
from repro.configs import get_config
from repro.models.model import Model
from repro.distributed import checkpoint as ckpt
from repro.distributed.elastic import restore_elastic
cfg = dataclasses.replace(get_config("qwen2-0.5b").reduced(), num_layers=2)
model = Model(cfg)
params = model.init(jax.random.PRNGKey(0))
d = {str(tmp_path)!r}
ckpt.save_checkpoint(d, 7, params, metadata={{"arch": cfg.name}})
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 2), ("data", "model"))
step, restored, meta = restore_elastic(d, params, mesh, cfg)
assert step == 7 and meta["arch"] == cfg.name
tok = jnp.zeros((2, 8), jnp.int32)
with jax.set_mesh(mesh):
    logits = jax.jit(model.forward)(restored, tok)
ref = model.forward(params, tok)
import numpy as np
np.testing.assert_allclose(np.asarray(logits), np.asarray(ref), atol=2e-4)
print("ELASTIC-OK")
"""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=90)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "ELASTIC-OK" in out.stdout


def test_engine_in_the_loop_serving():
    """MLProxy driving the real JAX engine (hybrid sim): batches grow."""
    import jax

    from repro.configs import get_config
    from repro.serving.batcher import EngineBackedLatency
    from repro.serving.engine import EngineConfig, InferenceEngine
    from repro.simulation.arrivals import PoissonProcess

    cfg = get_config("qwen2-0.5b").reduced()
    ecfg = EngineConfig(batch_buckets=(1, 2, 4, 8), prompt_buckets=(16,),
                        max_len=24, gen_len=2)
    eng = InferenceEngine(cfg, ecfg, rng=jax.random.PRNGKey(0))
    lat = EngineBackedLatency(eng, prompt_len=8, gen_len=2)
    res = run_simulation(
        policy="mlproxy", sla=SLAConfig(slo_target=2.0), workload=lat,
        arrivals=PoissonProcess(rate=20.0, duration=25.0),
        platform_config=PlatformConfig(initial_scale=1, cold_start=0.2),
        duration=25.0, seed=0,
        policy_kwargs={"bucketing": "pow2",
                       "optimizer": OptimizerConfig(update_interval=4.0,
                                                    initial_max_bs=2)},
    )
    s = res.summary
    assert s["completed"] > 100
    # real wall-clock engine latencies vary run to run; the claim under
    # test is that batches FORM (>1), not a specific operating point
    assert s["avg_batch_size"] > 1.2
    assert eng.stats["batches"] > 0


def test_replica_pool_elastic_scaling_under_failures():
    import jax

    from repro.configs import get_config
    from repro.serving.engine import EngineConfig, ReplicaPool

    cfg = get_config("qwen2-0.5b").reduced()
    ecfg = EngineConfig(batch_buckets=(1, 2), prompt_buckets=(8,),
                        max_len=16, gen_len=2)
    pool = ReplicaPool(cfg, ecfg, n_replicas=3, rng=jax.random.PRNGKey(0))
    prompts = np.zeros((2, 8), np.int32)
    pool.fail(0)
    pool.fail(2)
    out, timing = pool.generate(prompts)  # only replica 1 healthy
    assert timing["replica"] == 1
    pool.scale_to(4)
    assert pool.n_healthy >= 2
    out2, _ = pool.generate(prompts)
    np.testing.assert_array_equal(out, out2)  # same weights → same greedy
