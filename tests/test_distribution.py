"""Distribution tests: sharding specs, small-mesh compilation, shard_map MoE
equivalence, collective parser, roofline math. Runs on 4 virtual host
devices (set before jax initializes — safe because this module is the only
one spawning its own subprocess-scoped device count)."""
import json
import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_py(code: str) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=90)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_param_specs_basic():
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.distributed.sharding import param_spec
    from repro.launch.mesh import make_mesh

    # use a tiny mesh only for axis names; divisibility math is pure
    import jax
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    cfg = get_config("yi-34b")
    # attention projection: features over model, d_model over data
    sp = param_spec("layers/attn/wq", (60, 7168, 7168), mesh, cfg)
    assert sp[2] == "model" if mesh.shape["model"] > 1 else True
    # 1-D leaves replicated
    sp = param_spec("layers/attn_norm/scale", (60, 7168), mesh, cfg)
    assert all(s is None for s in sp)


def test_param_specs_on_real_mesh():
    code = """
import jax
from repro.configs import get_config
from repro.distributed.sharding import param_spec
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 2), ("data", "model"))
cfg = get_config("yi-34b")
assert param_spec("layers/attn/wq", (60, 7168, 7168), mesh, cfg) == P(None, "data", "model")
assert param_spec("layers/attn/wo", (60, 7168, 7168), mesh, cfg) == P(None, "model", "data")
assert param_spec("embed", (64000, 7168), mesh, cfg) == P("model", "data")
assert param_spec("lm_head", (7168, 64000), mesh, cfg) == P("data", "model")
cfg_moe = get_config("kimi-k2-1t-a32b")
sp = param_spec("layers/moe/wi", (61, 384, 7168, 2, 2048), mesh, cfg_moe)
assert sp[1] == "model" and sp[4] == "data", sp
sp = param_spec("layers/moe/wo", (61, 384, 2048, 7168), mesh, cfg_moe)
assert sp[1] == "model" and sp[2] == "data", sp
print("OK")
"""
    assert "OK" in run_py(code)


def test_small_mesh_train_compiles_and_runs():
    """Real (not abstract) train step on a 2x2 mesh with full sharding."""
    code = """
import dataclasses, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_config
from repro.models.model import Model
from repro.distributed import sharding as shd
from repro.optim import adamw
cfg = dataclasses.replace(get_config("qwen2-0.5b").reduced(), num_layers=2)
model = Model(cfg)
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 2), ("data", "model"))
params = model.init(jax.random.PRNGKey(0))
psh = shd.shard_params(params, mesh, cfg)
params = jax.device_put(params, psh)
opt_cfg = adamw.AdamWConfig()
opt = adamw.init_state(opt_cfg, params)
tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, cfg.vocab_size, dtype=jnp.int32)
def step(p, o, batch):
    loss, g = jax.value_and_grad(model.loss)(p, batch)
    p, o, m = adamw.apply_updates(opt_cfg, p, g, o)
    return p, o, loss
with jax.set_mesh(mesh):
    p2, o2, loss = jax.jit(step)(params, opt, {"tokens": tokens, "labels": tokens})
assert jnp.isfinite(loss), loss
print("loss", float(loss))
"""
    out = run_py(code)
    assert "loss" in out


def test_shard_map_moe_matches_global_on_mesh():
    """Both shard_map plans (token-route for small T, weight-gather for
    large T) must match the no-mesh oracle exactly (no capacity drops)."""
    code = """
import jax, jax.numpy as jnp, numpy as np
from repro.models.moe import init_moe, moe_ffn, _moe_global
key = jax.random.PRNGKey(0)
D,E,F = 32, 8, 64
p = init_moe(key, D, E, F, "silu", jnp.float32)
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 2), ("data", "model"))
for (B, S, tag) in [(4, 8, "token-route"), (8, 32, "weight-gather")]:
    x = jax.random.normal(jax.random.fold_in(key, B), (B, S, D))
    y_ref, _ = _moe_global(p, x, top_k=2, capacity_factor=8.0)
    with jax.set_mesh(mesh):
        y_sm, _ = jax.jit(lambda p, x: moe_ffn(p, x, top_k=2, capacity_factor=8.0))(p, x)
    err = float(jnp.max(jnp.abs(y_ref - y_sm)))
    assert err < 1e-5, (tag, err)
    print("OK", tag, err)
"""
    out = run_py(code)
    assert out.count("OK") == 2


def test_replica_pool_one_replica_per_device():
    """Four replicas on four devices: each replica's params and KV pool
    live on its own device, and every replica serves the same tokens as a
    one-replica pool."""
    code = """
import jax, numpy as np
from repro.configs import get_config
from repro.serving.engine import EngineConfig, ReplicaPool
cfg = get_config("qwen2-0.5b").reduced()
ecfg = EngineConfig(batch_buckets=(2,), prompt_buckets=(8,), max_len=16, gen_len=3)
devs = jax.devices()
prompts = np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 8)).astype(np.int32)
want, _ = ReplicaPool(cfg, ecfg, n_replicas=1).generate(prompts)
pool = ReplicaPool(cfg, ecfg, n_replicas=4, devices=devs)
served = set()
for _ in range(4):
    out, timing = pool.generate(prompts)
    np.testing.assert_array_equal(out, want)
    served.add(timing["replica"])
assert served == {0, 1, 2, 3}, served
for i, eng in enumerate(pool.replicas):
    leaves = eng.device_arrays()
    assert leaves and all(x.devices() == {devs[i]} for x in leaves), i
try:
    pool.scale_to(5)
except ValueError:
    pass
else:
    raise AssertionError("a fifth replica was given a device")
print("PLACED-OK")
"""
    assert "PLACED-OK" in run_py(code)


def test_multipod_mesh_axes():
    code = """
from repro.launch.mesh import make_production_mesh
import numpy as np
m = make_production_mesh(multi_pod=False)
assert m.axis_names == ("data", "model") and m.devices.shape == (16, 16)
print("OK-single")
"""
    env_code = code  # needs 256 devices
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=256"
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", env_code], capture_output=True,
                         text=True, env=env, timeout=90)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK-single" in out.stdout


def test_collective_bytes_parser():
    from repro.launch.dryrun import collective_bytes

    hlo = """
  %ag = bf16[128,256]{1,0} all-gather(%x), replica_groups={}
  %ar.1 = f32[64]{0} all-reduce(%y), to_apply=%add
  %cp = (s32[8]{0}, s32[8]{0}) collective-permute(%a, %b), channel_id=3
  %nothing = f32[10]{0} add(%p, %q)
"""
    out = collective_bytes(hlo)
    assert out["counts"] == {"all-gather": 1, "all-reduce": 1,
                             "collective-permute": 1}
    assert out["bytes_by_op"]["all-gather"] == 128 * 256 * 2
    assert out["bytes_by_op"]["all-reduce"] == 64 * 4
    assert out["bytes_by_op"]["collective-permute"] == 2 * 8 * 4


def test_roofline_extrapolation_math():
    from repro.roofline.analysis import _extrapolate, RooflineRow

    pts = [{"depth": 2, "v": 10.0}, {"depth": 4, "v": 16.0}]
    assert _extrapolate(pts, 10, lambda p: p["v"]) == pytest.approx(34.0)
    row = RooflineRow(arch="a", shape="s", mesh="m", status="ok",
                      t_compute=1.0, t_memory=2.0, t_collective=0.5)
    assert row.dominant() == "memory"


def test_roofline_on_artifacts_if_present():
    from repro.roofline.analysis import ARTIFACT_DIR, roofline_table

    if not os.path.isdir(ARTIFACT_DIR) or not os.listdir(ARTIFACT_DIR):
        pytest.skip("no dry-run artifacts yet")
    rows = roofline_table("pod1")
    assert rows
    for r in rows:
        if r.status == "ok":
            assert r.t_compute >= 0 and r.t_memory >= 0
            assert r.bottleneck in ("compute", "memory", "collective")
