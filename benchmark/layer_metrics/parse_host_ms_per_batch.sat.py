"""source + native parse: host time inside ``wf.parse`` (the native parse of a
chunk and the column shaping up to ``emit_columns``), per
staging batch (262144 tuples) pulled in the traced span."""
from benchmark import program_spans as ps


def read(trace, stats, window):
    return ps.host_ms_per_batch(window, ("wf.parse",))
