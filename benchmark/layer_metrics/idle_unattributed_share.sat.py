"""device: idle time of chip 0 in the traced span that none of the
program's ``wf.*`` spans and none of the benchmark's three covers / all its
idle time: the part of the chip's wait that still has no name (the
benchmark's own loop around ``g.step()``, the profiler)."""
from benchmark import program_spans as ps


def read(trace, stats, window):
    sp = ps.load(window)
    if sp is None or sp["idle_s"] <= 0:
        return None
    return 100.0 * sp["unattributed_s"] / sp["idle_s"]
