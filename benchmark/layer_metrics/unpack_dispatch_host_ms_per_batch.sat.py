"""unpack program: host time inside ``wf.dispatch`` of ``op=staging.unpack``
and the ``wf.compile`` of it (a compile inside the window shows here by
its seconds, where ``compiles_in_window`` shows it by count), per
staging batch (262144 tuples) pulled in the traced span."""
from benchmark import program_spans as ps

UNPACK = "staging.unpack"


def read(trace, stats, window):
    return ps.host_ms_per_batch(window, ("wf.dispatch", "wf.compile"),
                                op=lambda o: o == UNPACK)
