"""driver sweep: the driver thread's own work: its self time under every span
it holds (the program's ``wf.*`` and the benchmark's ``source.pull`` /
``sink.callback``) but the waits for the chip (``chip_wait_ms_per_batch``)
and ``generator.idle``, per staging batch (262144 tuples) pulled in the
traced span.  The serial host time a batch that the chip's ``step_dev`` +
``unpack_dev`` and the wall are held against: the larger of the two paces
the cell.  It takes the device path to be the driver thread's (default
``Config()``, as every cell runs: ``wait_spans``'s docstring).  None on a
program that does not name its waits."""
from benchmark import wait_spans as ws


def read(trace, stats, window):
    return ws.ms_per_batch(window, ws.work_seconds)
