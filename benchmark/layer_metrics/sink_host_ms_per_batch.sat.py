"""egress / sink: host time blocked in ``wf.sink.d2h`` (the columnar egress
of the sink's deferred queue) and inside ``wf.sink.deliver`` (the user's
sink function; the benchmark's ``sink.callback`` is within it), per
staging batch (262144 tuples) pulled in the traced span."""
from benchmark import program_spans as ps


def read(trace, stats, window):
    return ps.host_ms_per_batch(window, ("wf.sink.d2h", "wf.sink.deliver"))
