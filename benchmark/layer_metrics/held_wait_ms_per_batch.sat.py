"""fused operator program: the driver thread blocked in ``wf.wait.held``, the
read of the counts the step BEFORE left on the device (the one shell of the
session window, both joins, the ordered count window and the rolling
aggregate: ``session_tpu._RowsBoundedByDataTPU._step``), per staging batch
(262144 tuples) pulled in the traced span.  It is the wait that
``step_dispatch_host_ms_per_batch`` held until PR 51: it falls when the CHIP
gets faster, and reads 0 where no such operator runs (a window's reads at
the end of the stream go by ``wf.wait.flush`` and count in
``chip_wait_ms_per_batch`` alone).  None on a program that does not name its
waits."""
from benchmark import program_spans as ps
from benchmark import wait_spans as ws


def read(trace, stats, window):
    if ws.load(window) is None:
        return None
    return ps.host_ms_per_batch(window, (ws.HELD,))
