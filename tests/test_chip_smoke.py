"""The Pallas checks of ``chip_smoke.py``, run on the CPU in interpret mode.

On the chip the checks compare Mosaic kernels with the jnp path and with
``kernels/ref.py``. Here the same check functions run on a reduced bf16
model with the kernels interpreted: they must pass on the kernels as they
are, and fail when a fault is planted in the kernel's inputs or output, so
their tolerances are shown to catch a wrong kernel.
"""
import dataclasses
import functools
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels import ops
from repro.serving.engine import EngineConfig, InferenceEngine

ROOT = os.path.join(os.path.dirname(__file__), "..")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def engines(smoke):
    cfg = dataclasses.replace(
        get_config(smoke.ARCH).reduced(), max_seq_len=256,
        param_dtype="bfloat16", compute_dtype="bfloat16")
    ecfg = EngineConfig(batch_buckets=(2,), prompt_buckets=(smoke.PROMPT_LEN,),
                        max_len=smoke.MAX_LEN, gen_len=smoke.GEN_LEN)
    eng = InferenceEngine(cfg, ecfg, rng=jax.random.PRNGKey(0))
    x = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (2, smoke.PROMPT_LEN), dtype=np.int32)
    return eng, smoke.pallas_engine(eng), x


@pytest.fixture
def interpreted(monkeypatch):
    """Interpret the kernels; returns the decode wrapper for planting faults."""
    monkeypatch.setattr(ops, "flash_attention",
                        functools.partial(ops.flash_attention, interpret=True))
    decode = functools.partial(ops.decode_attention, interpret=True)
    monkeypatch.setattr(ops, "decode_attention", decode)
    return lambda fault: monkeypatch.setattr(
        ops, "decode_attention", functools.partial(fault, decode))


def _first_row_length(decode, q, k, v, lengths, **kw):
    """Every row reads row 0's cache length."""
    lengths = jnp.broadcast_to(jnp.asarray(lengths).reshape(-1), (q.shape[0],))
    return decode(q, k, v, jnp.full_like(lengths, lengths[0]), **kw)


def _one_short(decode, q, k, v, lengths, **kw):
    """Each row misses its newest cache entry."""
    return decode(q, k, v, jnp.asarray(lengths) - 1, **kw)


def _zeroed(decode, q, k, v, lengths, **kw):
    return jnp.zeros_like(decode(q, k, v, lengths, **kw))


def test_decode_kernel_check_passes(smoke, engines, interpreted):
    smoke.decode_kernel_check(engines[0].cfg, 0, "cpu")


@pytest.mark.parametrize("fault", [_first_row_length, _one_short, _zeroed])
def test_decode_kernel_check_catches_fault(smoke, engines, interpreted, fault):
    interpreted(fault)
    with pytest.raises(AssertionError, match="decode kernel differs"):
        smoke.decode_kernel_check(engines[0].cfg, 0, "cpu")


def test_pallas_logits_check_passes(smoke, engines, interpreted):
    smoke.pallas_logits_check(*engines, "cpu")


def test_pallas_logits_check_catches_zeroed_attention(smoke, engines,
                                                      interpreted):
    eng, _, x = engines
    interpreted(_zeroed)
    with pytest.raises(AssertionError, match="pallas logits differ"):
        smoke.pallas_logits_check(eng, smoke.pallas_engine(eng), x, "cpu")


def test_bf16_ulp(smoke):
    assert smoke.bf16_ulp(2.67) == 2.0 ** -6
    assert smoke.bf16_ulp(-1.0) == 2.0 ** -7
