"""fused operator program: device time under the phase ``wf.join.carry``
(what an interval join keeps for the next step: the open build rows
gathered into the carry, the counters), ms per staging batch pulled in
the traced span."""
from benchmark import device_phases as dp


def read(trace, stats, window):
    return dp.dev_ms_per_batch(window, ("wf.join.carry",))
