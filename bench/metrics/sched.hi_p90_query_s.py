"""90th percentile of submit-to-result latency of the priority-10
queries: the tail the fair-share gate protects (host clock)."""

import numpy as np


def read(run):
    lat = [q.latency for q in run.queries if q.priority >= 10]
    return float(np.percentile(lat, 90)) if lat else None
