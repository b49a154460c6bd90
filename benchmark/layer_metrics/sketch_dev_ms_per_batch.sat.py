"""mesh collectives (ICI): device time under the phase ``wf.shard.sketch``
(the shard plane's key sketch in the program ahead of a keyed consumer:
four count-min rows and the shard counts, ``shard_ledger.device_hist32``),
mean over the chips, ms per staging batch pulled in the traced span."""
from benchmark import device_phases as dp


def read(trace, stats, window):
    return dp.dev_ms_per_batch(window, ("wf.shard.sketch",))
