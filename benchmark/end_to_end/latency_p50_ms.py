"""Median over the full window rows closed by a tuple due inside the
window: delivery stamp - creation time of the closing tuple (the
reference names it; the schedule gives its creation time)."""
import numpy as np


def read(trace, stats, window, q=50):
    lat = window["latencies_ms"]
    if lat is None or not len(lat):
        return None
    return float(np.percentile(lat, q))
