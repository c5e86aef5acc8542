"""The 90th percentile of the changesets' latencies, from the file written
to the updated assessment (host clock; linear interpolation between the
two nearest ranks)."""
import numpy as np


def read(run):
    lat = [s["latency_s"] for s in run.steps]
    if not lat:
        return None
    return float(np.percentile(lat, 90))
