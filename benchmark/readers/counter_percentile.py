"""A percentile ``q`` of a list the cell's driver kept, times ``scale``."""
import numpy as np


def read(run, name, q, scale=1.0):
    values = run.counters.get(name)
    if not values:
        return None
    return scale * float(np.percentile(np.asarray(values, np.float64), q))
