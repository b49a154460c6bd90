"""staging: mean wall time between the batches the program staged over
the window, by its own count (``Staging.Wire`` of ``g.stats()``: batches
shipped encoded plus batches shipped raw): the fill wait a closing tuple
pays.  Full batches and the half-filled ones that the program's
punctuation flushes both count, so it moves with the batch size, the
punctuation interval and how the program cuts the source's chunks."""


def read(trace, stats, window):
    staged = stats["wire_batches"] + stats["wire_raw_batches"]
    if staged <= 0:
        return None
    return stats["t"] / staged * 1e3
