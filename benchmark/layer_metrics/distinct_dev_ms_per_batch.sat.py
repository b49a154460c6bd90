"""fused operator program: device time under the phase ``wf.agg.distinct``
(a rolling aggregate's sets: the words of the bit tables read a lane, a
run's bits OR-ed down it, the new members counted, the changed words
written in place), ms per staging batch pulled in the traced span.  A
program without the phase gives nothing to read."""
from benchmark import device_phases as dp


def read(trace, stats, window):
    return dp.dev_ms_per_batch(window, ("wf.agg.distinct",))
