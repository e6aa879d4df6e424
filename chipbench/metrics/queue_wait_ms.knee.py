"""95th percentile (ms) of the wait from a request's due time to the proxy
dispatching its batch (``Request.dispatch_time``)."""
import numpy as np

import readings


def read(run):
    w = run.window
    wait = (w.dispatched - w.due)[~np.isnan(w.dispatched)]
    return 1e3 * readings.rank(wait, 95) if len(wait) else None
