"""The benchmark's copy of the arrival arithmetic, and what a seed changes."""
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
sys.path.insert(0, str(HERE.parents[1] / "src"))
import schedule  # noqa: E402


@pytest.mark.parametrize("rate,duration,seed", [(50.0, 30.0, 7), (8.0, 100.0, 2**31 + 5)])
def test_copy_equals_sample_schedule(rate, duration, seed):
    from repro.simulation.arrivals import PoissonProcess, sample_schedule

    want = sample_schedule(PoissonProcess(rate=rate, duration=duration),
                           np.random.default_rng(seed), duration)
    got = schedule.poisson_times(rate, duration, np.random.default_rng(seed))
    np.testing.assert_array_equal(got, want)


def test_seeds_permute_the_same_gaps():
    mix = {"rate": 40.0, "shape_seed": 3}
    a = schedule.arrival_times(mix, 20.0, 1)
    b = schedule.arrival_times(mix, 20.0, 2**31 + 9)
    assert len(a) == len(b) > 0
    assert not np.array_equal(a, b)
    np.testing.assert_allclose(np.sort(np.diff(a, prepend=0.0)),
                               np.sort(np.diff(b, prepend=0.0)))
    assert a[-1] < 20.0 and b[-1] < 20.0
    np.testing.assert_array_equal(a, schedule.arrival_times(mix, 20.0, 1))


def test_prompts_and_weights_key_from_large_seeds():
    p = schedule.prompts(5, 16, 100, 2**31 + 3)
    assert p.shape == (5, 16) and p.min() >= 0 and p.max() < 100
    np.testing.assert_array_equal(p, schedule.prompts(5, 16, 100, 2**31 + 3))
    keys = {schedule.model_key_seed(s) for s in (0, 1, 2**31, 2**31 + 1, 2**33)}
    assert len(keys) == 5 and all(0 <= k < 2**32 for k in keys)
