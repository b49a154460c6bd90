"""fused operator program: device time under the phase ``wf.fire`` (the
sliding fold over the panes, picking and compacting the fired rows, the
hand-over, the end-of-stream flush) in the first window stage's program,
ms per staging batch pulled in the traced span: amortized, a time window
fires once in many batches.  The later stages' whole programs are
``stage2_dev_ms_per_batch.sat``."""
from benchmark import device_phases as dp


def read(trace, stats, window):
    return dp.dev_ms_per_batch(window, ("wf.fire",), dp.first_stage)
