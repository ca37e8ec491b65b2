"""p50_ms: median latency of every request sent in the window, from its
scheduled arrival to its answer (host clock). A failed request counts as
infinitely late. The percentile is a rank of the
recorded latencies (no interpolation, which an infinite one would turn
into nan)."""
import numpy as np


def read(run):
    lat = run.latencies_ms()
    if not len(lat):
        return None
    return float(np.percentile(lat, 50, method="inverted_cdf"))
