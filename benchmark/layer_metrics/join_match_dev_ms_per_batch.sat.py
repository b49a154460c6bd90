"""fused operator program: device time under the phases ``wf.join.sort``
(an interval join bringing both sides, the carried build rows included,
into (key, event time) order) and ``wf.join.match`` (cutting the ordered
lanes into runs, the interval and predicate tests, the segmented fold of
the matched probes), ms per staging batch pulled in the traced span."""
from benchmark import device_phases as dp


def read(trace, stats, window):
    return dp.dev_ms_per_batch(window, ("wf.join.sort", "wf.join.match"))
