"""Arrival schedules and prompts for a traffic mix, made from ``--seed``.

The Poisson arithmetic is a copy of ``_poisson_window`` and
``sample_schedule`` in ``src/repro/simulation/arrivals.py``: it belongs to
the benchmark, so that a change to the program cannot move the yardstick.
``tests/test_chipbench_schedule.py`` holds the copy equal to the original.

Runs with different seeds serve the same work. The gaps between arrivals
are one Poisson sample, drawn from the mix's ``shape_seed``; ``--seed``
only permutes them, and draws the prompts. So every seed sends the same
number of requests at the same mean rate, in another order.
"""
from __future__ import annotations

import numpy as np

_EMPTY = np.empty(0, dtype=np.float64)


def poisson_window(start: float, end: float, rate: float,
                   rng: np.random.Generator) -> np.ndarray:
    """All homogeneous-Poisson arrivals in ``(start, end]`` at ``rate``."""
    if rate <= 0 or end <= start:
        return _EMPTY
    chunks = []
    t = start
    while True:
        n = max(16, int(rate * (end - t) * 1.2) + 8)
        times = t + np.cumsum(rng.exponential(1.0 / rate, n))
        last = float(times[-1])
        if last > end:
            chunks.append(times[: int(np.searchsorted(times, end, side="right"))])
            break
        chunks.append(times)
        if last == end:
            break
        t = last
    return chunks[0] if len(chunks) == 1 else np.concatenate(chunks)


def poisson_times(rate: float, duration: float, rng,
                  horizon: float = 64.0) -> np.ndarray:
    """Every Poisson arrival over ``[0, duration)``, swept in windows of
    ``horizon`` seconds as ``sample_schedule`` sweeps them."""
    if not isinstance(rng, np.random.Generator):
        rng = np.random.default_rng(rng)
    chunks = []
    t = 0.0
    while t < duration:
        h = min(horizon, duration - t)
        block = poisson_window(t, min(t + h, duration), rate, rng)
        if len(block):
            chunks.append(block)
        t += h
    if not chunks:
        return _EMPTY.copy()
    out = chunks[0] if len(chunks) == 1 else np.concatenate(chunks)
    return out[out < duration]


def arrival_times(traffic: dict, seconds: float, seed: int) -> np.ndarray:
    """Due times in ``[0, seconds)`` of one run: the mix's Poisson gaps,
    drawn once from its ``shape_seed`` and permuted by ``seed``."""
    base = poisson_times(traffic["rate"], seconds, traffic["shape_seed"])
    gaps = np.diff(base, prepend=0.0)
    order = np.random.default_rng([seed, 1]).permutation(len(gaps))
    return np.cumsum(gaps[order])


def prompts(n: int, prompt_len: int, vocab: int, seed: int) -> np.ndarray:
    """``n`` prompts of ``prompt_len`` token ids in ``[0, vocab)``."""
    return np.random.default_rng([seed, 2]).integers(
        0, vocab, (n, prompt_len), dtype=np.int32)


def model_key_seed(seed: int) -> int:
    """A 32-bit seed for the weights' ``jax.random.PRNGKey``: ``--seed`` may
    not fit in 32 bits, and ``PRNGKey`` would silently cut it."""
    return int(np.random.SeedSequence([seed, 3]).generate_state(1)[0])
