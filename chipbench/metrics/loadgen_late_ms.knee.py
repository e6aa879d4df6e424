"""95th percentile (ms) of how late the load generator submitted each
request after it was due."""
import readings


def read(run):
    w = run.window
    return 1e3 * readings.rank(w.sent - w.due, 95)
