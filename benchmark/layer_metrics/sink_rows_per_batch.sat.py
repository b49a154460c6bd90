"""egress / sink: result rows delivered to the sink's function per staging
batch (262144 tuples) pulled in the traced span: ``rows`` of the
``wf.sink.deliver`` spans.  A window operator whose rows follow the data
(a session closes when its bidder falls silent) feeds the sink a
data-dependent share of the key space every batch; a time window feeds it
once a slide."""
from benchmark import trace_reduce
from benchmark.harness import load_module

DELIVER, D2H = "wf.sink.deliver", "wf.sink.d2h"


def sink_events(path):
    """``(name, stats)`` of every sink span on the host plane of the
    trace file, in file order."""
    from jax.profiler import ProfileData
    return [(e.name, dict(e.stats))
            for plane in ProfileData.from_file(path).planes
            if plane.name == trace_reduce.HOST_PLANE
            for line in plane.lines for e in line.events
            if e.name in (DELIVER, D2H)]


def sink_totals(events):
    """``{"rows", "lanes", "deliveries", "copies"}`` summed over the
    sink's spans: rows handed to the sink's function, lanes the egress
    copies held (None where the program's ``wf.sink.d2h`` does not say)."""
    rows = sum(int(st.get("rows", 0)) for n, st in events if n == DELIVER)
    said = [int(st["lanes"]) for n, st in events
            if n == D2H and "lanes" in st]
    return {"rows": rows, "lanes": sum(said) if said else None,
            "deliveries": sum(n == DELIVER for n, _ in events),
            "copies": sum(n == D2H for n, _ in events)}


def totals_of(window):
    trace_dir = window.get("trace_dir")
    if not trace_dir or window.get("trace0") is None:
        return None
    path = trace_reduce.find_xplane(trace_dir)
    return None if path is None else sink_totals(sink_events(path))


def read(trace, stats, window):
    t = totals_of(window)
    if t is None or not t["deliveries"]:
        return None
    batches = load_module("layer_metrics", "step_dev_ms_per_batch.sat") \
        .traced_batches(window)
    return t["rows"] / batches if batches > 0 else None
