"""Pallas kernels: device time in Mosaic custom calls / device busy time
in the traced span (0 where no kernel gate holds: a mesh, YSB)."""


def read(trace, stats, window):
    if trace is None or trace["busy_s"] <= 0:
        return None
    return 100.0 * trace["mosaic_s"] / trace["busy_s"]
