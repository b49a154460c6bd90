"""egress / sink: rows delivered to the sink's function / lanes the egress
copies held (``rows`` of ``wf.sink.deliver`` over ``lanes`` of
``wf.sink.d2h``), in the traced span: how much of the device-to-host copy
is result rows and how much the padding of output batches sized for the
worst case.  A program whose ``wf.sink.d2h`` does not say its lanes gives
nothing to read."""
from benchmark.harness import load_module


def read(trace, stats, window):
    t = load_module("layer_metrics", "sink_rows_per_batch.sat") \
        .totals_of(window)
    if t is None or not t["lanes"]:
        return None
    return 100.0 * t["rows"] / t["lanes"]
