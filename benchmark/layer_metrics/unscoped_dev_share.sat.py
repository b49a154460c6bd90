"""device: device time of the ``XLA Ops`` events under no ``wf.<phase>`` of
the program (container operations left out) / device busy time of the
traced span, %: how much of the chip's work the program's own names do not
reach.  A program without scopes gives nothing to read."""
from benchmark import device_phases as dp


def read(trace, stats, window):
    return dp.unscoped_share(window)
