"""staging: host time inside ``wf.wire.encode`` (tail zeroing of the packed
buffer and the wire plane's lane codecs), per
staging batch (262144 tuples) pulled in the traced span."""
from benchmark import program_spans as ps


def read(trace, stats, window):
    return ps.host_ms_per_batch(window, ("wf.wire.encode",))
