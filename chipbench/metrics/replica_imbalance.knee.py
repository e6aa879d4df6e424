"""Requests served by the busiest replica over the mean served per replica
of the pool (1.0: even), counted from the replica each engine call ran on
(``timing["replica"]`` of ``ReplicaPool.generate``) over every call of the
run, the drain's included."""
import numpy as np


def read(run):
    calls = run.window.calls
    if not calls:
        return None
    served = np.zeros(run.traffic.get("replicas", 1))
    for c in calls:
        served[c.replica] += c.rows
    return float(served.max() / served.mean())
