"""fused operator program: device time under the phases ``wf.agg.sort``
(a batch's lanes sorted by the word of each set table, the plain leaves
riding), ``wf.agg.fold`` (the plain leaves folded a key, the new members
summed a key, the touched groups' state read, folded and written) and
``wf.agg.rows`` (one upsert row a touched group, compacted to the front
of the output batch) of a rolling aggregate, ms per staging batch pulled
in the traced span.  A program without the phases gives nothing to
read."""
from benchmark import device_phases as dp


def read(trace, stats, window):
    return dp.dev_ms_per_batch(
        window, ("wf.agg.sort", "wf.agg.fold", "wf.agg.rows"))
