"""fused operator program: device time under the phase ``wf.join.close``
(an interval join picking the build rows that close, ordering them to the
front and gathering the output batch; what does not fit is held back), ms
per staging batch pulled in the traced span."""
from benchmark import device_phases as dp


def read(trace, stats, window):
    return dp.dev_ms_per_batch(window, ("wf.join.close",))
