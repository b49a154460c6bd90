"""staging: tuples staged / capacity staged over the ``wf.h2d`` spans of the
traced span that carry ``n`` and ``cap`` (the unpacked staging of a mesh
edge; ``batch_fill_share.sat`` reads the packed edge's ``wf.wire.encode``,
which a mesh edge does not open).  100 % when every batch ships full; what
the punctuation flushes short lowers it, and each such batch is shipped to
every chip and stepped at full capacity."""
from benchmark.harness import load_module


def fill(spans):
    both = [st for st in spans if "cap" in st and "n" in st]
    cap = sum(int(st["cap"]) for st in both)
    return 100.0 * sum(int(st["n"]) for st in both) / cap if cap > 0 else None


def read(trace, stats, window):
    spans = load_module("layer_metrics",
                        "ingest_replication.sat").h2d_spans(window)
    return None if spans is None else fill(spans)
