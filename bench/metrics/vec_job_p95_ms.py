"""95th percentile of job latency over every job of the window."""
import numpy as np


def read(facts):
    lat = facts.get("job_latency_s")
    if not lat:
        return None
    return 1e3 * float(np.percentile(lat, 95))
