"""driver sweep: the driver thread's blocked time on the chip, wherever it
landed: the self time of every span the program names as a wait
(``recorder.WAITS``: ``wf.wait.*``, ``wf.pool.wait``, ``wf.megastep.drain``),
per staging batch (262144 tuples) pulled in the traced span.  The wait moves
from one of those spans to another from run to run (which bound the driver
meets first); their sum does not.  None on a program that does not name its
waits."""
from benchmark import wait_spans as ws


def read(trace, stats, window):
    return ws.ms_per_batch(window, ws.wait_seconds)
