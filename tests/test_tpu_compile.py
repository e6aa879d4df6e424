"""Compile the served path for a described TPU v5e, with no chip attached.

The TPU compiler is installed wherever JAX's TPU support is, and compiles
for a topology it is only told about. These tests compile the main-path
Pallas kernels through Mosaic (``interpret=False``) and the full-width
qwen2-0.5b serving programs for one v5e chip, so a kernel the chip would
refuse, or a program that would not fit its memory, fails here. Nothing
runs, so nothing here is a result or a time.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import signal

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops
from repro.models.model import Model
from repro.serving.engine import EngineConfig, InferenceEngine

#: HBM of one TPU v5e chip (Google Cloud documentation, "TPU v5e").
V5E_HBM_BYTES = 16 * 10**9
# qwen2-0.5b serving widths: 14 query heads, 2 kv heads, head dim 64.
B, HQ, HKV, D = 8, 14, 2, 64
PROMPT, MAX_LEN = 128, 160


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep it out of the cache.
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    # Loading the TPU library installs a handler that prints a stack trace
    # into the test log when the worker is terminated; keep SIGTERM's own.
    sigterm = signal.getsignal(signal.SIGTERM)
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        finally:
            signal.signal(signal.SIGTERM, sigterm)
        yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_enabled)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def test_flash_attention_compiles_for_v5e(one_chip):
    q = _spec((B, PROMPT, HQ, D), jnp.bfloat16, one_chip)
    kv = _spec((B, PROMPT, HKV, D), jnp.bfloat16, one_chip)
    compiled = _compile(
        lambda q, k, v: ops.flash_attention(q, k, v, interpret=False), q, kv, kv)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("lengths_shape", [(), (B,)], ids=["scalar", "per_row"])
def test_decode_attention_compiles_for_v5e(one_chip, lengths_shape):
    q = _spec((B, 1, HQ, D), jnp.bfloat16, one_chip)
    kv = _spec((B, MAX_LEN, HKV, D), jnp.bfloat16, one_chip)
    lengths = _spec(lengths_shape, jnp.int32, one_chip)
    compiled = _compile(
        lambda q, k, v, n: ops.decode_attention(q, k, v, n, interpret=False),
        q, kv, kv, lengths)
    assert "tpu_custom_call" in compiled.as_text()


def test_full_width_serving_programs_fit_v5e(one_chip):
    """The engine's own prefill and decode-step programs, full qwen2-0.5b."""
    cfg = get_config("qwen2-0.5b")
    ecfg = EngineConfig(batch_buckets=(B,), prompt_buckets=(PROMPT,),
                        max_len=MAX_LEN, gen_len=16, fused_decode=False)
    params = jax.tree.map(lambda s: _spec(s.shape, s.dtype, one_chip),
                          Model(cfg).init_abstract())
    eng = InferenceEngine(cfg, ecfg, params=params)
    cache = jax.tree.map(
        lambda s: _spec(s.shape, s.dtype, one_chip),
        jax.eval_shape(lambda: eng.model.init_cache(B, MAX_LEN)))
    programs = eng.programs(B, PROMPT)
    assert set(programs) == {"prefill", "decode step"}
    tokens = {"prefill": _spec((B, PROMPT), jnp.int32, one_chip),
              "decode step": _spec((B, 1), jnp.int32, one_chip)}
    for name, fn in programs.items():
        mem = fn.lower(params, tokens[name], cache).compile().memory_analysis()
        used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
        assert 0 < used < V5E_HBM_BYTES, (name, used)
