"""fused operator program: device time under the phase ``wf.ring`` (what a
window step does to its whole state whether it fires or not: rolling the
pane ring, the eviction mask, the 64-bit split / combine of the cells it
touches) in the first window stage's program, ms per staging batch pulled
in the traced span."""
from benchmark import device_phases as dp


def read(trace, stats, window):
    return dp.dev_ms_per_batch(window, ("wf.ring",), dp.first_stage)
