"""fused operator program: device time under the phase ``wf.place`` (a window
step folding its batch into pane cells and merging them into the state:
the contraction, the scatters, the ``lax.cond`` / ``lax.switch`` around
them) in the first window stage's program, ms per staging batch pulled in
the traced span.  The later stages' whole programs are
``stage2_dev_ms_per_batch.sat``."""
from benchmark import device_phases as dp


def read(trace, stats, window):
    return dp.dev_ms_per_batch(window, ("wf.place",), dp.first_stage)
