"""Make the repo root importable in tests (experiments/, benchmarks/), and
give every test a time limit so that a hang fails one test, not the run."""
import contextlib
import os
import signal
import sys
import threading

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

# About five times the slowest test, 23 s under 6 xdist workers on the CPU.
TEST_TIME_LIMIT_S = 120.0


class TimeLimitExceeded(BaseException):
    """Raised in a test that outran its limit. A ``BaseException``, so that
    an ``except Exception`` in the code under test cannot swallow it."""


@contextlib.contextmanager
def time_limit(seconds: float):
    """Raise :class:`TimeLimitExceeded` wherever the main thread is after
    ``seconds``. Does nothing without ``SIGALRM`` or off the main thread."""
    if (not hasattr(signal, "SIGALRM")
            or threading.current_thread() is not threading.main_thread()):
        yield
        return

    def expire(signum, frame):
        raise TimeLimitExceeded(f"test ran past its {seconds} s time limit")

    handler = signal.signal(signal.SIGALRM, expire)
    signal.setitimer(signal.ITIMER_REAL, seconds)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, handler)


@pytest.fixture(autouse=True)
def _test_time_limit():
    with time_limit(TEST_TIME_LIMIT_S):
        yield
