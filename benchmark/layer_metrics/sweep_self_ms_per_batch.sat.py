"""driver sweep: what is left to the scheduler itself, the self time of
``wf.sweep``, ``wf.source.tick`` (with the user's chunk iterator: the
benchmark's ``source.pull`` and ``generator.idle`` are within it) and
``wf.drain`` (collectors, watermarks, emitters between the dispatches), per
staging batch (262144 tuples) pulled in the traced span."""
from benchmark import program_spans as ps


def read(trace, stats, window):
    return ps.host_ms_per_batch(window, ("wf.sweep", "wf.source.tick",
                                         "wf.drain"))
