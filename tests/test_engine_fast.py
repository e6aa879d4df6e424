"""Fast data-plane tests: fused decode, KV-cache pool, warmup, seeding.

All on a 1-layer tiny model so compiles are cheap; the fused loop's
contract — bit-identical tokens to the per-token reference loop — is the
load-bearing invariant here, everything else builds on it.
"""
import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.configs.base import ModelConfig
from repro.serving.batcher import EngineBackedLatency
from repro.serving.engine import EngineConfig, InferenceEngine

TINY = ModelConfig(
    name="tiny-fast", family="dense", num_layers=1, d_model=16,
    num_heads=1, num_kv_heads=1, head_dim=16, d_ff=32, vocab_size=64,
    max_seq_len=64, param_dtype="float32", compute_dtype="float32",
    remat=False, scan_layers=False)

BUCKETS = (1, 2, 4)
PLENS = (4, 8)


def _ecfg(**kw):
    base = dict(batch_buckets=BUCKETS, prompt_buckets=PLENS,
                max_len=24, gen_len=8)
    base.update(kw)
    return EngineConfig(**base)


@pytest.fixture(scope="module")
def shared_params():
    return InferenceEngine(TINY, _ecfg(), rng=jax.random.PRNGKey(0)).params


@pytest.fixture(scope="module")
def reference_engine(shared_params):
    """Per-token loop, no pool: the ground truth the fast path must match."""
    return InferenceEngine(TINY, _ecfg(fused_decode=False, cache_pool=False),
                           params=shared_params)


# ------------------------------------------------------------- fused decode
@pytest.mark.parametrize("n", [1, 2, 3, 4])
def test_fused_bit_identical_per_bucket(shared_params, reference_engine, n):
    fused = InferenceEngine(TINY, _ecfg(), params=shared_params)
    prompts = np.random.default_rng(n).integers(
        0, TINY.vocab_size, (n, 5)).astype(np.int32)
    a, ta = fused.generate(prompts, gen_len=8)
    b, tb = reference_engine.generate(prompts, gen_len=8)
    assert a.shape == b.shape == (n, 8)
    assert np.array_equal(a, b)
    assert ta["bucket"] == tb["bucket"]


def test_fused_single_token_and_repeat_calls(shared_params, reference_engine):
    fused = InferenceEngine(TINY, _ecfg(), params=shared_params)
    prompts = np.ones((2, 4), np.int32)
    out, _ = fused.generate(prompts, gen_len=1)  # gen_len=1: prefill only
    ref, _ = reference_engine.generate(prompts, gen_len=1)
    assert np.array_equal(out, ref)
    # repeat calls through the pooled cache keep matching the reference
    for seed in range(3):
        p = np.random.default_rng(seed).integers(
            0, TINY.vocab_size, (2, 4)).astype(np.int32)
        a, _ = fused.generate(p, gen_len=6)
        b, _ = reference_engine.generate(p, gen_len=6)
        assert np.array_equal(a, b)


def test_gen_bucket_rounding_is_prefix_stable(shared_params, reference_engine):
    """gen_buckets rounds the compiled step count up; the sliced output
    must equal the exact-length reference (greedy decoding is
    prefix-stable), and intermediate lengths must not add compiles."""
    eng = InferenceEngine(TINY, _ecfg(gen_buckets=(4, 8)),
                          params=shared_params)
    prompts = np.random.default_rng(7).integers(
        0, TINY.vocab_size, (2, 4)).astype(np.int32)
    out5, _ = eng.generate(prompts, gen_len=5)  # compiles (2, 8)
    before = eng.compile_count
    for gl in (6, 7, 8):
        out, _ = eng.generate(prompts, gen_len=gl)
        ref, _ = reference_engine.generate(prompts, gen_len=gl)
        assert np.array_equal(out, ref)
    assert eng.compile_count == before  # all lengths share the 8-step scan
    ref5, _ = reference_engine.generate(prompts, gen_len=5)
    assert out5.shape == (2, 5)
    assert np.array_equal(out5, ref5)


# ------------------------------------------------------------ kv-cache pool
def test_cache_pool_allocs_saturate_per_bucket(shared_params):
    eng = InferenceEngine(TINY, _ecfg(), params=shared_params)
    for _ in range(4):
        eng.generate(np.ones((4, 4), np.int32), gen_len=4)
    assert eng.cache_allocs == 1  # one alloc for bucket 4, then reuse
    eng.generate(np.ones((2, 4), np.int32), gen_len=4)
    eng.generate(np.ones((1, 4), np.int32), gen_len=4)
    assert eng.cache_allocs == 3  # one per touched bucket
    for _ in range(5):
        eng.generate(np.ones((3, 4), np.int32), gen_len=4)  # bucket 4 again
    assert eng.cache_allocs == 3


def test_cache_pool_disabled_allocates_per_call(shared_params):
    eng = InferenceEngine(TINY, _ecfg(cache_pool=False), params=shared_params)
    for _ in range(3):
        eng.generate(np.ones((4, 4), np.int32), gen_len=4)
    assert eng.cache_allocs == 3


def test_no_stale_row_leakage_across_batches(shared_params):
    """A reused cache still holds the previous batch's KV rows; prefill +
    the attention length mask must make them unreachable. A padded batch
    (n=3 in bucket 4) after a full batch is the sharpest case: row 3's
    stale history must not change row 0–2's tokens."""
    pooled = InferenceEngine(TINY, _ecfg(), params=shared_params)
    fresh = InferenceEngine(TINY, _ecfg(cache_pool=False),
                            params=shared_params)
    rng = np.random.default_rng(3)
    # poison the bucket-4 cache with a distinctive full batch
    poison = rng.integers(32, 64, (4, 8)).astype(np.int32)
    pooled.generate(poison, gen_len=8)
    # then a shorter, partially-filled batch through the SAME pooled cache
    probe = rng.integers(0, 32, (3, 4)).astype(np.int32)
    got, _ = pooled.generate(probe, gen_len=8)
    want, _ = fresh.generate(probe, gen_len=8)
    assert np.array_equal(got, want)


# ------------------------------------------------------------------- warmup
def test_warmup_covers_all_pairs_without_stats_pollution(shared_params):
    eng = InferenceEngine(TINY, _ecfg(), params=shared_params)
    timings = eng.warmup()
    assert set(timings) == {(b, p) for b in BUCKETS for p in PLENS}
    assert all(dt > 0 for dt in timings.values())
    # warmup traffic is synthetic: serving stats must stay untouched
    assert eng.stats == {"batches": 0, "requests": 0, "tokens": 0}
    # every serving-path shape is now compiled: no compile on first real call
    before = eng.compile_count
    for b in BUCKETS:
        for p in PLENS:
            eng.generate(np.ones((b, p), np.int32))
    assert eng.compile_count == before
    assert eng.stats["batches"] == len(BUCKETS) * len(PLENS)


def test_warmup_single_prompt_bucket(shared_params):
    eng = InferenceEngine(TINY, _ecfg(), params=shared_params)
    timings = eng.warmup(plen=3)  # rounds up to prompt bucket 4
    assert set(timings) == {(b, 4) for b in BUCKETS}


# -------------------------------------------------------- latency seeding
def test_engine_backed_latency_seeds_from_warmup(shared_params):
    eng = InferenceEngine(TINY, _ecfg(), params=shared_params)
    lat = EngineBackedLatency(eng, prompt_len=4, warmup=True)
    # seeded: no cold-0.0 window for any compiled bucket, and the
    # oversized probe scales off the largest seeded bucket instead of
    # promising a free batch
    for b in BUCKETS:
        assert lat.mean(b) > 0.0
    assert lat.mean(8) >= lat.mean(4)


def test_engine_backed_latency_seed_prefers_nearest_prompt_bucket():
    class _StubEngine:
        class ecfg:
            batch_buckets = (1, 2)
        cfg = None

    lat = EngineBackedLatency.__new__(EngineBackedLatency)
    lat.engine = _StubEngine()
    lat.prompt_len = 8
    lat._ema = {}
    lat.seed({(1, 4): 0.5, (1, 8): 0.1, (2, 8): 0.2})
    assert lat._ema == {1: 0.1, 2: 0.2}


# --------------------------------------------------------- public accessors
@pytest.mark.parametrize("fused", [True, False])
def test_programs_are_the_ones_generate_runs(shared_params, fused):
    """``programs`` hands out the cached programs ``generate`` compiled."""
    eng = InferenceEngine(TINY, _ecfg(fused_decode=fused), params=shared_params)
    eng.generate(np.ones((2, 4), np.int32))
    before = eng.compile_count
    progs = eng.programs(2, 4)
    assert eng.compile_count == before
    decode = "fused decode" if fused else "decode step"
    assert list(progs) == ["prefill", decode]
    assert progs["prefill"] is eng._prefill_fn(2, 4)


def test_prefill_logits_match_model_forward(shared_params):
    eng = InferenceEngine(TINY, _ecfg(), params=shared_params)
    x = np.random.default_rng(5).integers(
        0, TINY.vocab_size, (2, 8)).astype(np.int32)
    got = eng.prefill_logits(x)
    want = np.asarray(eng.model.forward(shared_params, jax.numpy.asarray(x)))
    np.testing.assert_allclose(got, want[:, -1], rtol=1e-5, atol=1e-5)


def test_device_arrays_hold_params_and_pooled_caches(shared_params):
    eng = InferenceEngine(TINY, _ecfg(), params=shared_params)
    n_params = len(jax.tree.leaves(shared_params))
    assert len(eng.device_arrays()) == n_params
    eng.generate(np.ones((2, 4), np.int32))
    eng.generate(np.ones((4, 4), np.int32))
    n_cache = len(jax.tree.leaves(eng.model.init_cache(1, 8)))
    assert len(eng.device_arrays()) == n_params + 2 * n_cache
