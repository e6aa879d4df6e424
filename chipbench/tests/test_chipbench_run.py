"""A tiny cell through the harness on the CPU: the real served path, the
reference comparison, faults planted underneath it, and a cell added by
new files alone."""
import json
import shutil
import sys
import time
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent))
import check  # noqa: E402
import run  # noqa: E402
import serve  # noqa: E402
import tiny  # noqa: E402

SEED = 2**31 + 7
E2E = {"p50_ms", "p95_ms", "slo_attain", "setup_s"}


@pytest.fixture(scope="module", autouse=True)
def compile_cache(tmp_path_factory):
    """Compile each of the tiny cell's programs once for the whole file:
    every pool here compiles the same programs again."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    jax.config.update(keys[0], str(tmp_path_factory.mktemp("jax_cache")))
    jax.config.update(keys[1], 0.0)
    compilation_cache.reset_cache()
    yield
    for k, v in saved.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("checkout"), replicas=2)


def test_run_through_the_served_path_is_correct(root, tmp_path):
    import jax

    res = run.run_cell(tiny.CELL, SEED, 1.5, False, jax.devices() * 2,
                       time.perf_counter(), root=root, out=tmp_path)
    assert res["correct"], res["compared"]
    assert res["failed"] == 0 and res["attempted"] > 10
    assert set(res["metrics"]) == E2E
    assert res["device"]["platform"] == "cpu"
    assert list(res)[-1] == "compared"
    assert set(res["compared"]) == {"unserved", "malformed", "max_gap"}
    with open(tmp_path / "runs" / f"{tiny.CELL}.{SEED}.0.json") as f:
        record = json.load(f)
    assert len(record["done"]) == res["attempted"]
    assert sum(n for _, n, _ in record["batches"]) == res["attempted"]


def test_control_run_is_not_correct(root, tmp_path):
    """The float8 control in the program's place goes through the same
    comparison and comes out not correct."""
    import jax

    res = run.run_cell(tiny.CELL, SEED, 1.5, False, jax.devices() * 2,
                       time.perf_counter(), root=root, out=tmp_path,
                       control=True)
    gap = res["compared"]["max_gap"]
    assert not res["correct"], gap
    assert gap["value"] > gap["limit"] >= gap["program"], gap


@pytest.fixture(scope="module")
def pool(root):
    """The tiny cell's warm two-replica pool, shared by the fault runs."""
    import jax

    _, _, config, traffic = run.cell_files(root, tiny.CELL)
    built = run.build(config, traffic, jax.devices() * 2, SEED)
    return config, traffic, built


def _altered(out):
    out = np.array(out)
    out[:, 3] = (out[:, 3] + 1) % 512
    return out


FAULTS = {
    # a token altered in every row where the engine produces it
    "token": ("repro.serving.engine.InferenceEngine.generate",
              lambda real: lambda self, p, g=None: (
                  lambda o, t: (_altered(o), t))(*real(self, p, g))),
    # each row's answer handed to the next request of its batch
    "rows_swapped": ("repro.serving.engine.InferenceEngine.generate",
                     lambda real: lambda self, p, g=None: (
                         lambda o, t: (np.roll(o, 1, axis=0), t))(
                             *real(self, p, g))),
    # one replica of two serving altered tokens
    "one_replica": ("repro.serving.engine.ReplicaPool.generate",
                    lambda real: lambda self, p, g=None: (
                        lambda o, t: (_altered(o) if t["replica"] == 1 else o,
                                      t))(*real(self, p, g))),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_fault_is_not_correct(pool, monkeypatch, fault):
    import importlib

    config, traffic, (engines, warm, key_seed, _, counter) = pool
    target, wrap = FAULTS[fault]
    mod_name, cls_name, meth = target.rsplit(".", 2)
    cls = getattr(importlib.import_module(mod_name), cls_name)
    monkeypatch.setattr(cls, meth, wrap(getattr(cls, meth)))
    window = serve.run_window(engines, traffic, 1.5, SEED,
                              vocab=config["vocab_size"], warm=warm,
                              counter=counter)
    assert len({window.group(i)[1] for i in range(len(window.due))}) == 2
    numbers = run.compare(config, traffic, window, SEED, key_seed)
    compared = {c["name"]: c for c in numbers}
    assert compared["unserved"]["value"] == 0
    gap = compared["max_gap"]
    assert gap["value"] > gap["limit"], gap
    assert not check.verdict(numbers)


def test_new_mix_and_cell_are_found_from_new_files_alone(root, tmp_path):
    shutil.copytree(root, tmp_path / "c", dirs_exist_ok=True)
    new = tmp_path / "c"
    with open(new / "chipbench" / "traffic" / "tiny.json") as f:
        mix = json.load(f)
    mix["rate"] = 3.0
    with open(new / "chipbench" / "traffic" / "tiny.slow.json", "w") as f:
        json.dump(mix, f)
    with open(new / "BENCHMARK.json") as f:
        bench = json.load(f)
    bench["workloads"].append({"name": "tiny.slow", "config": "tiny",
                               "traffic": "tiny.slow", "chips": 1,
                               "why": "tests"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] == "p95_ms":
            m["workloads"] = ["tiny.slow"]
    with open(new / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    bench, cell, config, traffic = run.cell_files(new, "tiny.slow")
    assert traffic["rate"] == 3.0 and config["name"] == "tiny"
    e2e = {m["name"] for m in run.cell_metrics(bench, "tiny.slow", False)}
    assert "p95_ms" in e2e
    assert "p95_ms" not in {m["name"] for m in run.cell_metrics(bench, tiny.CELL, False)}
    per_layer = {m["name"] for m in run.cell_metrics(bench, "tiny.slow", True)}
    assert "queue_wait_ms.knee" in per_layer
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(run.reader(m["name"]))


def test_command_without_a_tpu_exits_non_zero(capsys):
    code = run.main(["--workload", "qwen2-0.5b.chat.knee80", "--seed", "1",
                     "--seconds", "1", "--trace", "0"])
    assert code != 0
    assert capsys.readouterr().out == ""
