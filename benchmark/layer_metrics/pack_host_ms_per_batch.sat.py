"""staging: host time inside ``wf.pack`` itself (rows written into the pooled
staging buffer; the encode, H2D and unpack dispatch nested in it are other
metrics'), per
staging batch (262144 tuples) pulled in the traced span."""
from benchmark import program_spans as ps


def read(trace, stats, window):
    return ps.host_ms_per_batch(window, ("wf.pack",))
