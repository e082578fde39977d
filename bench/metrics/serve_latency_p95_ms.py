"""95th percentile, over every sample consumed in the window, of the time
from when the sample was due to the return of the tick that consumed it."""

import numpy as np


def read(run):
    lat = run.records.get("latencies_s")
    if lat is None or lat.size == 0:
        return None
    return float(np.percentile(lat, 95)) * 1e3
