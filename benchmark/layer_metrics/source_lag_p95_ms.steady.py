"""source + native parse: how late the source pulled each chunk — pull
time minus the time its last tuple was created — 95th percentile over the
window (in a traced run: over the window before the trace began)."""
import numpy as np


def read(trace, stats, window):
    lags = window["lags"]
    lags = lags[lags[:, 1] < window["quiet_until"]]
    if not len(lags):
        return None
    return float(np.percentile(lags[:, 1] - lags[:, 0], 95)) * 1e3
