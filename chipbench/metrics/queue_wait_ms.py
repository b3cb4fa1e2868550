"""Mean time an answered request waited in the runtime's queue before its
batch was dispatched (``ServedResult.queue_ms``, the runtime's own host
clock), in ms."""
import numpy as np


def read(run):
    q = np.asarray(run["queue_ms"], float)
    q = q[~np.isnan(q)]
    return float(q.mean()) if q.size else None
