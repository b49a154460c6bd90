"""staging: tuples staged / capacity staged over the ``wf.wire.encode`` spans
of the traced span (``n`` and ``cap``): 100 % when every batch ships full;
what the punctuation flushes short lowers it, and each such batch is
unpacked, stepped and shipped at full capacity."""
from benchmark import program_spans as ps


def read(trace, stats, window):
    sp = ps.load(window)
    if sp is None or not sp["fill"][1]:
        return None
    return 100.0 * sp["fill"][0] / sp["fill"][1]
