"""90th percentile of submit-to-result latency over every query of the
window (host clock: the harness stamps the submission and the moment the
scheduler hands the finished query's state back)."""

import numpy as np


def read(run):
    lat = [q.latency for q in run.queries]
    return float(np.percentile(lat, 90)) if lat else None
