"""fused operator program: device time under the phase ``wf.group`` (bringing
a batch into key order: the grouping permutation by counting sort, sort or
the Pallas kernel, and the gathers that move the lanes by it), ms per
staging batch pulled in the traced span."""
from benchmark import device_phases as dp


def read(trace, stats, window):
    return dp.dev_ms_per_batch(window, ("wf.group",), dp.first_stage)
