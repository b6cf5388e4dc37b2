"""Latency percentiles over every request due in the window, from its
due time; a request never answered, or answered with an error, ranks
above every answered one."""
import math

import numpy as np


def percentile_ms(rec, q: float):
    lat = rec.get("latency_s")
    if lat is None or len(lat) == 0:
        return None
    lat = np.sort(np.asarray(lat, np.float64))
    value = lat[max(int(math.ceil(q * lat.size)) - 1, 0)]
    return float(value) * 1e3 if np.isfinite(value) else None
