"""Mean requests per batch the proxy dispatched inside the window."""
import numpy as np


def read(run):
    sizes = [n for t, n, _ in run.window.batches if t <= run.window.seconds]
    return float(np.mean(sizes)) if sizes else None
