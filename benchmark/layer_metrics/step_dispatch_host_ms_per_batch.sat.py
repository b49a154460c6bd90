"""fused operator program: host time inside every other ``wf.dispatch`` (the
operator steps, the megastep scan), their ``wf.compile`` and the megastep's
blocking ``wf.megastep.drain``, per
staging batch (262144 tuples) pulled in the traced span."""
from benchmark import program_spans as ps

UNPACK = "staging.unpack"


def read(trace, stats, window):
    return ps.host_ms_per_batch(
        window, ("wf.dispatch", "wf.compile", "wf.megastep.drain"),
        op=lambda o: o != UNPACK)
