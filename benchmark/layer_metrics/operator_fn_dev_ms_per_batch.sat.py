"""fused operator program: device time under the phase ``wf.fn`` (the user's
functions over a batch: map and filter bodies, key extractors, window
lifts; YSB's join gather, the mesh cell's Map + Filter program), ms per
staging batch pulled in the traced span."""
from benchmark import device_phases as dp


def read(trace, stats, window):
    return dp.dev_ms_per_batch(window, ("wf.fn",))
