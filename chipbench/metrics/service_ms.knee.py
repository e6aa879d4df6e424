"""Median wall time (ms) of one ``ReplicaPool.generate`` call: one batch
through prefill and fused decode, host work included."""
import numpy as np


def read(run):
    calls = run.window.calls
    return 1e3 * float(np.median([c.end - c.start for c in calls])) if calls else None
