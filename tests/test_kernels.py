"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


def _rand(key, shape, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    return x.astype(dtype)


def _tol(dtype):
    return {"float32": 2e-5, "bfloat16": 2e-2}[jnp.dtype(dtype).name]


# ------------------------------------------------------------ flash attention
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,s,hq,hkv,d", [
    (1, 128, 4, 4, 32),    # MHA, exact block fit
    (2, 200, 8, 2, 64),    # GQA 4:1, ragged block
    (1, 64, 6, 3, 16),     # GQA 2:1, small
    (2, 257, 4, 1, 32),    # MQA, off-by-one length
])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_matches_oracle(b, s, hq, hkv, d, dtype, causal):
    key = jax.random.PRNGKey(b * 1000 + s)
    q = _rand(key, (b, s, hq, d), dtype)
    k = _rand(jax.random.fold_in(key, 1), (b, s, hkv, d), dtype)
    v = _rand(jax.random.fold_in(key, 2), (b, s, hkv, d), dtype)
    out = ops.flash_attention(q, k, v, causal=causal, block_q=64, block_k=64,
                              interpret=True)
    want = ref.flash_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        atol=_tol(dtype), rtol=_tol(dtype))


def test_flash_attention_block_size_invariance():
    key = jax.random.PRNGKey(0)
    q = _rand(key, (1, 160, 4, 32), jnp.float32)
    k = _rand(jax.random.fold_in(key, 1), (1, 160, 2, 32), jnp.float32)
    v = _rand(jax.random.fold_in(key, 2), (1, 160, 2, 32), jnp.float32)
    a = ops.flash_attention(q, k, v, block_q=32, block_k=32, interpret=True)
    b = ops.flash_attention(q, k, v, block_q=128, block_k=64, interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_flash_attention_two_oracles_agree():
    key = jax.random.PRNGKey(3)
    q = _rand(key, (2, 96, 4, 16), jnp.float32)
    k = _rand(jax.random.fold_in(key, 1), (2, 96, 2, 16), jnp.float32)
    v = _rand(jax.random.fold_in(key, 2), (2, 96, 2, 16), jnp.float32)
    a = ref.flash_attention(q, k, v)
    b = ref.flash_attention_chunked(q, k, v, q_chunk=32)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


# ------------------------------------------------------------ decode attention
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,s,hq,hkv,d,valid", [
    (2, 128, 8, 2, 64, 128),   # full cache
    (2, 128, 8, 2, 64, 77),    # partial cache
    (1, 640, 4, 4, 32, 501),   # multi-block, MHA
    (4, 96, 4, 1, 16, 33),     # MQA
    # per-row lengths: each (row, kv head) reads its own SMEM entry
    (2, 128, 8, 2, 64, (128, 5)),
    (4, 96, 4, 1, 16, (33, 1, 96, 60)),
    (3, 160, 14, 2, 64, (160, 129, 1)),  # qwen2-0.5b head widths
])
def test_decode_attention_matches_oracle(b, s, hq, hkv, d, valid, dtype):
    key = jax.random.PRNGKey(s + int(np.sum(valid)))
    q = _rand(key, (b, 1, hq, d), dtype)
    kc = _rand(jax.random.fold_in(key, 1), (b, s, hkv, d), dtype)
    vc = _rand(jax.random.fold_in(key, 2), (b, s, hkv, d), dtype)
    out = ops.decode_attention(q, kc, vc, jnp.asarray(valid), block_k=128,
                               interpret=True)
    want = ref.decode_attention(q, kc, vc, jnp.asarray(valid))
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        atol=_tol(dtype), rtol=_tol(dtype))


def test_decode_attention_ignores_padding_content():
    """Anything beyond cache_len must not affect the output."""
    key = jax.random.PRNGKey(0)
    b, s, hq, hkv, d, valid = 2, 64, 4, 2, 32, 40
    q = _rand(key, (b, 1, hq, d), jnp.float32)
    kc = _rand(jax.random.fold_in(key, 1), (b, s, hkv, d), jnp.float32)
    vc = _rand(jax.random.fold_in(key, 2), (b, s, hkv, d), jnp.float32)
    out1 = ops.decode_attention(q, kc, vc, jnp.asarray(valid), interpret=True)
    kc2 = kc.at[:, valid:].set(999.0)
    vc2 = vc.at[:, valid:].set(-999.0)
    out2 = ops.decode_attention(q, kc2, vc2, jnp.asarray(valid), interpret=True)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), atol=1e-6)


# -------------------------------------------------------------------- SSD scan
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,s,h,p,n,chunk", [
    (1, 64, 2, 16, 16, 16),
    (2, 100, 4, 8, 32, 32),   # ragged chunks
    (1, 33, 1, 32, 8, 16),    # off-by-one
])
def test_ssd_scan_matches_oracle(b, s, h, p, n, chunk, dtype):
    key = jax.random.PRNGKey(s * 10 + h)
    x = _rand(key, (b, s, h, p), dtype)
    dt = jax.nn.softplus(jax.random.normal(
        jax.random.fold_in(key, 1), (b, s, h))).astype(jnp.float32)
    a = -jnp.exp(jax.random.normal(jax.random.fold_in(key, 2), (h,)) * 0.3)
    bb = _rand(jax.random.fold_in(key, 3), (b, s, n), dtype)
    cc = _rand(jax.random.fold_in(key, 4), (b, s, n), dtype)
    out = ops.ssd_scan(x, dt, a, bb, cc, chunk=chunk, interpret=True)
    want = ref.ssd_scan(x, dt, a, bb, cc, chunk=chunk)
    tol = _tol(dtype) * 4  # long products of decays amplify rounding
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        atol=tol, rtol=tol)


def test_ssd_scan_matches_sequential_oracle():
    key = jax.random.PRNGKey(9)
    b, s, h, p, n = 1, 48, 2, 8, 16
    x = _rand(key, (b, s, h, p), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(key, 1), (b, s, h)))
    a = -jnp.exp(jax.random.normal(jax.random.fold_in(key, 2), (h,)) * 0.3)
    bb = _rand(jax.random.fold_in(key, 3), (b, s, n), jnp.float32)
    cc = _rand(jax.random.fold_in(key, 4), (b, s, n), jnp.float32)
    out = ops.ssd_scan(x, dt, a, bb, cc, chunk=16, interpret=True)
    want = ref.ssd_scan_sequential(x, dt, a, bb, cc)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=1e-4, rtol=1e-4)


def test_ssd_scan_chunk_invariance():
    key = jax.random.PRNGKey(4)
    b, s, h, p, n = 2, 64, 2, 8, 8
    x = _rand(key, (b, s, h, p), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(key, 1), (b, s, h)))
    a = -jnp.exp(jnp.zeros((h,)))
    bb = _rand(jax.random.fold_in(key, 3), (b, s, n), jnp.float32)
    cc = _rand(jax.random.fold_in(key, 4), (b, s, n), jnp.float32)
    o1 = ops.ssd_scan(x, dt, a, bb, cc, chunk=8, interpret=True)
    o2 = ops.ssd_scan(x, dt, a, bb, cc, chunk=32, interpret=True)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-4)


# -------------------------------------------------------------------- mLSTM
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,s,h,d,bq,bk", [
    (2, 128, 2, 32, 64, 64),   # exact block fit
    (1, 200, 4, 16, 64, 32),   # ragged blocks
    (2, 65, 3, 64, 128, 128),  # single padded block
])
def test_mlstm_attention_matches_oracle(b, s, h, d, bq, bk, dtype):
    key = jax.random.PRNGKey(s + d)
    q = _rand(key, (b, s, h, d), dtype)
    k = _rand(jax.random.fold_in(key, 1), (b, s, h, d), dtype)
    v = _rand(jax.random.fold_in(key, 2), (b, s, h, d), dtype)
    log_i = (jax.random.normal(jax.random.fold_in(key, 3), (b, s, h)) * 0.5
             ).astype(jnp.float32)
    log_f = jax.nn.log_sigmoid(
        jax.random.normal(jax.random.fold_in(key, 4), (b, s, h)) + 2.0)
    out = ops.mlstm_attention(q, k, v, log_i, log_f, block_q=bq, block_k=bk,
                              interpret=True)
    want = ref.mlstm_attention(q, k, v, log_i, log_f)
    tol = _tol(dtype) * 2  # signed-denominator normalizer amplifies rounding
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        atol=tol, rtol=tol)


def test_mlstm_attention_block_invariance():
    key = jax.random.PRNGKey(11)
    b, s, h, d = 1, 160, 2, 32
    q = _rand(key, (b, s, h, d), jnp.float32)
    k = _rand(jax.random.fold_in(key, 1), (b, s, h, d), jnp.float32)
    v = _rand(jax.random.fold_in(key, 2), (b, s, h, d), jnp.float32)
    log_i = jnp.zeros((b, s, h))
    log_f = jax.nn.log_sigmoid(jnp.full((b, s, h), 2.0))
    a = ops.mlstm_attention(q, k, v, log_i, log_f, block_q=32, block_k=32,
                            interpret=True)
    c = ops.mlstm_attention(q, k, v, log_i, log_f, block_q=128, block_k=64,
                            interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(c), atol=2e-5)


# ------------------------------------------------- model integration (pallas)
def test_model_with_pallas_attention_matches_jnp(monkeypatch):
    import dataclasses
    import functools

    from repro.configs import get_config
    from repro.models.model import Model

    # The model looks the kernels up at call time: run them interpreted.
    for name in ("flash_attention", "decode_attention"):
        monkeypatch.setattr(
            ops, name, functools.partial(getattr(ops, name), interpret=True))
    cfg = get_config("yi-34b").reduced()
    cfg_pl = dataclasses.replace(cfg, use_pallas=True)
    model, model_pl = Model(cfg), Model(cfg_pl)
    params = model.init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 24), 0,
                                cfg.vocab_size, dtype=jnp.int32)
    a = model.forward(params, tokens)
    b = model_pl.forward(params, tokens)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-3)
