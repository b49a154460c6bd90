"""fused operator program: lanes of the batch the FIRST window stage hands
downstream, per staged batch: ``out_cap`` on the ``wf.dispatch`` spans of
the traced span (a window step notes the capacity of the batch it hands on
where that differs from the one it was given), of the operator that the
first such span names.  A step's output is sized by what it can fire; a
program whose spans carry no ``out_cap`` (any commit before it existed)
gives nothing to read."""
from benchmark import program_spans as ps
from benchmark import trace_reduce

SPAN = "wf.dispatch"


def dispatch_spans(window):
    """The stats of each ``wf.dispatch`` event of the run's trace, in
    time order; None in an untraced run or without a trace file."""
    trace_dir = window.get("trace_dir")
    if not trace_dir or window.get("trace0") is None:
        return None
    path = trace_reduce.find_xplane(trace_dir)
    if path is None:
        return None
    events = [(start, stats) for line in ps.read_trace(path)["lines"]
              for name, start, _end, stats in line if name == SPAN]
    return [st for _, st in sorted(events, key=lambda t: t[0])]


def first_stage_lanes(spans):
    """Mean ``out_cap`` over the dispatches of the first operator that
    notes one (the first window stage: its dispatch comes first in every
    sweep); None when no span notes any."""
    noted = [st for st in spans if "out_cap" in st]
    if not noted:
        return None
    caps = [int(st["out_cap"]) for st in noted
            if st.get("op") == noted[0].get("op")]
    return sum(caps) / len(caps)


def read(trace, stats, window):
    spans = dispatch_spans(window)
    return None if spans is None else first_stage_lanes(spans)
