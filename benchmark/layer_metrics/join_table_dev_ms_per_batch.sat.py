"""fused operator program: device time under the phase ``wf.join.table``
(the pair form's retained build side: a batch's build rows written into
the keyed table, the lookups of the probes whose build row is not before
them in their batch, the validity test that evicts), ms per staging batch
pulled in the traced span."""
from benchmark import device_phases as dp


def read(trace, stats, window):
    return dp.dev_ms_per_batch(window, ("wf.join.table",))
