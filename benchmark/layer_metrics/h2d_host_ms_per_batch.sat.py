"""staging: host time blocked in ``wf.h2d`` (``jnp.asarray`` / ``device_put`` of
the staged buffer) and in ``wf.pool.wait`` (a recycled buffer whose gate the
device had not passed).  The host's blocked time, not the wire's, per
staging batch (262144 tuples) pulled in the traced span."""
from benchmark import program_spans as ps


def read(trace, stats, window):
    return ps.host_ms_per_batch(window, ("wf.h2d", "wf.pool.wait"))
