"""The trace reduction, on a trace of the tiny cell recorded on a TPU v5e
by ``record_trace.py`` and committed in ``data/``."""
import json
import sys
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent))
import record_trace  # noqa: E402
import run  # noqa: E402
import serve  # noqa: E402
import trace_reduce  # noqa: E402

DATA = HERE / "data"


def recorded():
    with open(DATA / "tiny_calls.json") as f:
        rec = json.load(f)
    meta = {int(i): tuple(v) for i, v in rec["calls"].items()}
    return meta, rec["result"]


def test_reduction_of_a_recorded_trace():
    meta, result = recorded()
    red = trace_reduce.reduce(str(DATA / "tiny.xplane.pb"), serve.SPAN,
                              run.WINDOW_SPAN, meta,
                              sorted({d for d, _ in meta.values()}))
    # what the run that recorded it reported, from the same trace
    assert red["busy_s"] == result["device"]["busy_s"]
    assert red["window_s"] == result["device"]["window_s"]
    assert 0 < red["busy_s"] < red["window_s"]
    assert abs(red["window_s"] - record_trace.TRACE_S) < 0.1
    whole = [r for r in red["calls"].values()
             if r["prefill"] > 0 and r["fused_decode"] > 0]
    assert whole, red["calls"]
    # every engine call runs one prefill and then one longer fused decode
    for r in whole:
        assert r["fused_decode"] > r["prefill"]
    names = [name for name, _ in red["device_ops"]]
    assert any(n.startswith("prefill b=") for n in names), names
    assert any(n.startswith("fused_decode b=") for n in names), names
    assert all(label in ("inside_generate", "between_calls")
               for label, _ in red["idle_gaps"])


def test_a_trace_of_other_chips_reduces_to_nothing():
    meta, result = recorded()
    assert trace_reduce.reduce(str(DATA / "tiny.xplane.pb"), serve.SPAN,
                               run.WINDOW_SPAN, meta, [97]) is None


def test_merge_and_owner():
    s, e = trace_reduce._merge(np.array([0.0, 1, 5, 6, 2]),
                               np.array([2.0, 3, 7, 6.5, 2.5]))
    np.testing.assert_array_equal(s, [0, 5])
    np.testing.assert_array_equal(e, [3, 7])
    spans = {0: (0.0, 100e6), 1: (101e6, 200e6)}
    assert trace_reduce._owner(spans, 100.2e6) == 0
    assert trace_reduce._owner(spans, 100.8e6) == 1
    assert trace_reduce._owner(spans, 150e6) == 1
    assert trace_reduce._owner(spans, 300e6) is None
