"""device: 1 - union of the busy intervals / traced span, mean over the
chips (the per-chip values are on an earlier line of the run)."""


def read(trace, stats, window):
    if trace is None or not trace["devices"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
