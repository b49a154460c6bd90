"""unpack program (+ wire decode): device time under the phase ``wf.unpack``
(``staging.unpack``: re-typing the packed buffer into lanes, the wire
decode where an edge encodes), ms per staging batch (262144 tuples) pulled
in the traced span.  Read from the program's own scopes on the ``XLA Ops``
line (``benchmark/device_phases.py``); a program without scopes gives
nothing to read."""
from benchmark import device_phases as dp


def read(trace, stats, window):
    return dp.dev_ms_per_batch(window, ("wf.unpack",))
