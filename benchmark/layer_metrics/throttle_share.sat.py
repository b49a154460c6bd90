"""driver sweep: sweeps in which the source was held back by the
in-transit cap / sweeps of the benchmark's loop, over the window."""


def read(trace, stats, window):
    if stats["sweeps"] <= 0:
        return None
    return 100.0 * stats["throttle_events"] / stats["sweeps"]
