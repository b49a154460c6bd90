"""staging: bytes the host shipped / bytes of the batches counted once,
over the ``wf.h2d`` spans of the traced span that carry both (``bytes`` and
``logical``: the unpacked staging of a mesh edge; the packed one-chip
transfer carries ``bytes`` alone and gives nothing to read).  4.0 while
every one of four chips is shipped the whole batch, 1.0 for an ingest that
hands a chip only its keys."""
from benchmark import trace_reduce

SPAN = "wf.h2d"


def h2d_spans(window):
    """The stats of each ``wf.h2d`` event of the run's trace; None in an
    untraced run or without a trace file."""
    trace_dir = window.get("trace_dir")
    if not trace_dir or window.get("trace0") is None:
        return None
    path = trace_reduce.find_xplane(trace_dir)
    if path is None:
        return None
    from jax.profiler import ProfileData
    return [dict(e.stats)
            for plane in ProfileData.from_file(path).planes
            if plane.name == trace_reduce.HOST_PLANE
            for line in plane.lines for e in line.events if e.name == SPAN]


def replication(spans):
    """None when no span says what its batch holds counted once."""
    both = [st for st in spans if "logical" in st and "bytes" in st]
    logical = sum(int(st["logical"]) for st in both)
    return sum(int(st["bytes"]) for st in both) / logical \
        if logical > 0 else None


def read(trace, stats, window):
    spans = h2d_spans(window)
    return None if spans is None else replication(spans)
