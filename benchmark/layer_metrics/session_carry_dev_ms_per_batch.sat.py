"""fused operator program: device time under the phase ``wf.session.carry``
(a session step carrying each key's runs from the sorted lanes into the
key domain, a 32-bit index scatter and gathers over the keys, and merging
them with the open sessions), ms per staging batch pulled in the traced
span."""
from benchmark import device_phases as dp


def read(trace, stats, window):
    return dp.dev_ms_per_batch(window, ("wf.session.carry",))
