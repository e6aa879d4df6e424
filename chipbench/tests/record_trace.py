"""Record the trace that ``test_chipbench_trace.py`` reduces: the tiny cell
served on the chip with ``--trace 1``.

    python3 chipbench/tests/record_trace.py    # on a TPU

Writes the ``.xplane.pb``, and beside it the run's engine calls, whose
(device, bucket) the reduction needs, with the run's result, to
``chipbench/out/trace_fixture/``; copy both into ``tests/data/``.
"""
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import run  # noqa: E402
import serve  # noqa: E402
import tiny  # noqa: E402

#: Seconds traced at the end of the recorded window: short, so that the
#: committed trace stays small.
TRACE_S = 3.0


def main() -> None:
    import jax

    devices = jax.devices()
    assert devices[0].platform == "tpu", devices
    run.TRACE_S = TRACE_S
    dest = tiny.BENCH / "out" / "trace_fixture"
    dest.mkdir(parents=True, exist_ok=True)
    with tempfile.TemporaryDirectory() as tmp:
        root = tiny.make_root(Path(tmp), rate=4.0)
        captured = {}
        real = serve.run_window

        def keep(*a, **k):
            captured["window"] = w = real(*a, **k)
            return w

        serve.run_window = keep
        res = run.run_cell(tiny.CELL, 11, 6.0, True, devices,
                           time.perf_counter(), root=root, out=Path(tmp) / "out")
        xplane = next((Path(tmp) / "out").rglob("*.xplane.pb"))
        shutil.copy(xplane, dest / "tiny.xplane.pb")
    w = captured["window"]
    calls = {c.index: [devices[c.replica].id, c.bucket] for c in w.calls}
    with open(dest / "tiny_calls.json", "w") as f:
        json.dump({"calls": calls, "result": res}, f, indent=1)
    print(json.dumps(res))


if __name__ == "__main__":
    main()
