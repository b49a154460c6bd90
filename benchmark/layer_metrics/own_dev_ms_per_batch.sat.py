"""mesh collectives (ICI): device time under the phase ``wf.mesh.own`` (a key
shard counting the lanes it owns and sorting them to the front of the
batch), mean over the chips, ms per staging batch pulled in the traced
span."""
from benchmark import device_phases as dp


def read(trace, stats, window):
    return dp.dev_ms_per_batch(window, ("wf.mesh.own",))
