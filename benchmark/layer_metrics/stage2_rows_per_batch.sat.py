"""fused operator program: rows the SECOND device stage of a batch was
handed by the first (a count window in event-time order behind an interval
join), per staging batch (262144 tuples) pulled in the traced span:
``rows_in`` on the ``wf.dispatch`` spans that say ``stage`` 2 or more (the
stage reads the count a step late, when it costs no wait: the span of
step ``n`` carries the rows of step ``n - 1``).  The mechanism engaged:
a reading near 0 beside a first stage that closes thousands of rows a
batch means the second stage did not see them.  A program whose spans
carry no ``stage`` (any commit before it existed) gives nothing to read."""
from benchmark.harness import load_module


def later_stage_rows(spans):
    """``(rows, dispatches)`` over the spans of a stage past the first
    that say ``rows_in``; None where none does."""
    said = [int(st["rows_in"]) for st in spans
            if int(st.get("stage", 1)) > 1 and "rows_in" in st]
    return (sum(said), len(said)) if said else None


def read(trace, stats, window):
    spans = load_module("layer_metrics", "window_out_lanes_per_batch.sat") \
        .dispatch_spans(window)
    if spans is None:
        return None
    got = later_stage_rows(spans)
    if got is None:
        return None
    batches = load_module("layer_metrics", "step_dev_ms_per_batch.sat") \
        .traced_batches(window)
    return got[0] / batches if batches > 0 else None
