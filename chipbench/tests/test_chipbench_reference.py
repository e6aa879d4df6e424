"""The reference builds the program's weights from the seed by its own
recipe and computes the published architecture: at float32 it agrees with
the program's forward pass, for both variants; the float8 control does
not."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
sys.path.insert(0, str(HERE.parents[1] / "src"))
import check  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402

TINY = dict(hidden_size=64, intermediate_size=96, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=2, vocab_size=300,
            torch_dtype="float32")


def tiny(name):
    with open(HERE.parent / "configs" / f"{name}.json") as f:
        cfg = json.load(f)
    cfg.update(TINY)
    return cfg


@pytest.mark.parametrize("name,control", [("qwen2-0.5b", True),
                                          ("starcoder2-15b-l10", False)])
def test_reference_equals_program_at_float32(name, control):
    import jax
    import jax.numpy as jnp

    from repro.models.model import Model

    cfg = tiny(name)
    model = Model(run.model_config(cfg))
    params = jax.jit(model.init)(jax.random.PRNGKey(1234))
    tokens = np.random.default_rng(0).integers(0, 300, (3, 12)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(model.forward(params, jnp.asarray(tokens)))[:, 5:]
    got = reference.forward(cfg, 1234, tokens, 5)
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)
    if control:
        low = reference.forward(cfg, 1234, tokens, 5, control=True)
        assert np.max(np.abs(low - got)) > 10 * np.max(np.abs(got - want))


def test_rows_per_block_fits_the_budget():
    cfg = tiny("starcoder2-15b-l10")
    cfg.update(num_attention_heads=48, intermediate_size=24576)
    rows = check.rows_per_block(cfg, 1055, budget_bytes=1.5e9)
    assert rows >= 1
    assert rows * 4 * 1055 * 48 * 1055 <= 1.5e9
