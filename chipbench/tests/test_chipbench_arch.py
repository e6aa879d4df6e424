"""Architectures are modules of the harness, found by ``model_type``: a new
one comes from new files alone, a missing one stops with the file's name,
and the two the benchmark serves read what they read before they moved
into ``arch/``."""
import dataclasses
import json
import shutil
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
sys.path.insert(0, str(HERE.parents[1] / "src"))
import arch  # noqa: E402
import check  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402
import work  # noqa: E402

TINY = dict(hidden_size=64, intermediate_size=96, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=2, vocab_size=300)
TOKENS = np.random.default_rng(0).integers(0, 300, (3, 12)).astype(np.int32)


def config(name, **changes):
    with open(HERE.parent / "configs" / f"{name}.json") as f:
        cfg = json.load(f)
    cfg.update(changes)
    return cfg


def _mix(name):
    mix = "chat.knee80" if name == "qwen2-0.5b" else "code.knee80"
    with open(HERE.parent / "traffic" / f"{mix}.json") as f:
        return json.load(f)


#: A made-up architecture: qwen2's module under another name, whose first
#: layer is of a kind of its own, and which counts its own decode.
MADE_UP = """

def layer_kind(cfg, index):
    return "first" if index == 0 else "rest"


def decode(cfg, rows, prompt_len, steps):
    return work.Work(float(rows), float(steps))
"""


@pytest.fixture
def made_up(tmp_path, monkeypatch):
    """An ``arch/`` holding the modules there are and one new file."""
    shutil.copytree(arch.DIR, tmp_path / "arch")
    src = (arch.DIR / "qwen2.py").read_text()
    (tmp_path / "arch" / "made_up.py").write_text(src + MADE_UP)
    monkeypatch.setattr(arch, "DIR", tmp_path / "arch")
    return tmp_path / "arch"


def test_new_architecture_is_found_from_new_files_alone(made_up):
    qwen = config("qwen2-0.5b", **TINY)
    new = dict(qwen, model_type="made_up")
    assert arch.load(new).__file__ == str(made_up / "made_up.py")
    assert (dataclasses.asdict(run.model_config(new))
            == dataclasses.asdict(run.model_config(qwen)))
    got = reference.forward(new, 1234, TOKENS, 5)
    np.testing.assert_array_equal(got, reference.forward(qwen, 1234, TOKENS, 5))
    programs = reference._programs(json.dumps(new, sort_keys=True), False)
    assert set(programs["layer"]) == {"first", "rest"}
    assert work.prefill(new, 4, 16) == work.prefill(qwen, 4, 16)
    assert work.decode(new, 4, 16, 7) == work.Work(4.0, 7.0)
    assert check.rows_per_block(new, 27) == check.rows_per_block(qwen, 27)


@pytest.mark.parametrize("entry", ["model_config", "forward", "prefill",
                                   "rows_per_block"])
def test_unknown_model_type_stops_naming_the_file(entry):
    cfg = config("qwen2-0.5b", model_type="no_such_arch", **TINY)
    call = {"model_config": lambda: run.model_config(cfg),
            "forward": lambda: reference.forward(cfg, 1, TOKENS, 5),
            "prefill": lambda: work.prefill(cfg, 1, 16),
            "rows_per_block": lambda: check.rows_per_block(cfg, 16)}[entry]
    with pytest.raises(SystemExit, match=r"arch/no_such_arch\.py"):
        call()


# Read from the harness before the architectures moved into ``arch/``:
# the float64 sum and sum of squares of the logits of TOKENS' positions
# 5..11 at key seed 1234, and four logits of row 1's last position.
PINNED_LOGITS = {
    ("qwen2-0.5b", "float32", False): (56.15294236800764, 163.66080944193627, 0.13271360099315643, 0.062212683260440826, -0.1555086076259613, 0.12067652493715286),
    ("qwen2-0.5b", "bfloat16", False): (56.1309413808176, 163.67633667747955, 0.1328229010105133, 0.062412362545728683, -0.15558616816997528, 0.12055924534797668),
    ("qwen2-0.5b", "float32", True): (53.59094648389873, 163.55182844634888, 0.1285959780216217, 0.07084427028894424, -0.1750081479549408, 0.10773255676031113),
    ("qwen2-0.5b", "bfloat16", True): (55.84522937562997, 164.47128976868188, 0.12138606607913971, 0.08611871302127838, -0.18907468020915985, 0.10340660065412521),
    ("starcoder2-15b-l10", "float32", False): (-5.708747889628285, 6402.501916253301, 0.5068824291229248, 0.2056918889284134, 0.210004061460495, -0.33611172437667847),
    ("starcoder2-15b-l10", "bfloat16", False): (-4.703627526410855, 6403.982732307247, 0.5076220631599426, 0.20120808482170105, 0.21669399738311768, -0.33508431911468506),
    ("starcoder2-15b-l10", "float32", True): (6.765421068932483, 6418.38829148551, 0.40574392676353455, 0.21371769905090332, 0.3050481975078583, -0.2715758979320526),
    ("starcoder2-15b-l10", "bfloat16", True): (7.534064004092215, 6409.359986059261, 0.39543479681015015, 0.33522912859916687, 0.3275872468948364, -0.2900051176548004),
}


@pytest.mark.parametrize("name,dtype,control", sorted(PINNED_LOGITS))
def test_reference_logits_as_before_the_move(name, dtype, control):
    cfg = config(name, torch_dtype=dtype, **TINY)
    got = reference.forward(cfg, 1234, TOKENS, 5, control=control,
                            rows_per_block=2).astype(np.float64)
    want = PINNED_LOGITS[(name, dtype, control)]
    np.testing.assert_allclose([got.sum(), np.square(got).sum()], want[:2],
                               rtol=1e-6, atol=1e-4)
    np.testing.assert_allclose(got[1, -1, :4], want[2:], rtol=1e-6, atol=1e-6)


# Read from the harness before the move, at the cells' own shapes: per
# batch bucket (bucket, prefill flops, prefill bytes, decode flops, decode
# bytes), then one request's (flops, bytes), and the reference's rows per
# block at the longest sequence checked.
PINNED_WORK = {
    "qwen2-0.5b": (
        [(1, 377982976000.0, 994213888, 65187053568.0, 62661021696.0),
         (2, 755965952000.0, 1000505344, 130374107136.0, 63082930176.0),
         (4, 1511931904000.0, 1013088256, 260748214272.0, 63926747136.0),
         (8, 3023863808000.0, 1038254080, 521496428544.0, 65614381056.0),
         (16, 6047727616000.0, 1088585728, 1042992857088.0, 68989648896.0),
         (32, 12095455232000.0, 1189249024, 2085985714176.0, 75740184576.0)],
        (443170029568.0, 63655235584.0), 67),
    "starcoder2-15b-l10": (
        [(1, 7989368979456.0, 8300527616, 264589541376.0, 257327149056.0),
         (2, 15978737958912.0, 8321499136, 529179082752.0, 257988059136.0),
         (4, 31957475917824.0, 8363442176, 1058358165504.0, 259309879296.0),
         (8, 63914951835648.0, 8447328256, 2116716331008.0, 261953519616.0),
         (16, 127829903671296.0, 8615100416, 4233432662016.0, 267240800256.0)],
        (8253958520832.0, 265627676672.0), 7),
}


@pytest.mark.parametrize("name", sorted(PINNED_WORK))
def test_work_as_before_the_move(name):
    cfg = config(name)
    mix = _mix(name)
    plen, gen = mix["prompt_len"], mix["gen_len"]
    buckets, req, block = PINNED_WORK[name]
    assert [b for b, *_ in buckets] == cfg["engine"]["batch_buckets"]
    for b, pf, pb, df, db in buckets:
        assert work.prefill(cfg, b, plen) == work.Work(pf, pb)
        assert work.decode(cfg, b, plen, gen - 1) == work.Work(df, db)
    assert work.request(cfg, plen, gen) == work.Work(*req)
    assert check.rows_per_block(cfg, plen + gen - 1) == block
