"""egress / sink: the driver thread blocked in ``wf.wait.d2h``, the wait for an
output batch's bytes on the host (the step that fills it, the pack program,
the rest of the copy started at receipt; where the ``wf.sink.d2h`` around it
says ``waited=0`` the step had run, and it is the copy on the wire), per
staging batch (262144 tuples) pulled in the traced span.  What is left in
``sink_host_ms_per_batch`` since PR 51 is the re-typing and the user's sink
function.  None on a program that does not name its waits."""
from benchmark import program_spans as ps
from benchmark import wait_spans as ws


def read(trace, stats, window):
    if ws.load(window) is None:
        return None
    return ps.host_ms_per_batch(window, (ws.D2H,))
