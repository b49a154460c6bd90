"""fused operator program: device time under the phase ``wf.order`` (a
count window in event-time order bringing the rows that waited and the
batch's into (released or waiting, key, event time, tie) order: one sort,
the lane numbers riding it), ms per staging batch pulled in the traced
span.  A program without the phase gives nothing to read."""
from benchmark import device_phases as dp


def read(trace, stats, window):
    return dp.dev_ms_per_batch(window, ("wf.order",))
