"""Share (%) of the requests due in the window that completed within the
mix's SLO, counted from the due time."""
import numpy as np

import readings


def read(run):
    lat = readings.latencies_s(run.window)
    return 100.0 * float(np.mean(lat <= run.traffic["slo_ms"] / 1e3))
