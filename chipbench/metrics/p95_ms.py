"""95th percentile latency (ms) of the requests due in the window, from due
time to completion; the drain after the window is included."""
import readings


def read(run):
    return 1e3 * readings.rank(readings.latencies_s(run.window), 95)
