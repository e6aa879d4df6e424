"""The per-test time limit of the root ``conftest.py``."""
import asyncio
import signal

import pytest

from conftest import TimeLimitExceeded, time_limit

needs_alarm = pytest.mark.skipif(not hasattr(signal, "SIGALRM"),
                                 reason="no SIGALRM on this platform")


def spin_loop():
    while True:
        pass


def spin_coroutine():
    async def spin():
        while True:
            await asyncio.gather()  # completes at once, never suspends

    asyncio.run(spin())


@needs_alarm
@pytest.mark.parametrize("spin", [spin_loop, spin_coroutine],
                         ids=["loop", "coroutine"])
def test_spin_is_interrupted_with_the_limit_message(spin):
    with pytest.raises(TimeLimitExceeded, match=r"past its 0\.2 s time limit"):
        with time_limit(0.2):
            spin()

