"""staging: share of the window's packed staged batches that shipped
wire-encoded (``Staging.Wire`` of ``g.stats()``: ``batches`` over
``batches + raw_batches``), in %.  Each staging edge times its link and
its codec and encodes only where the link is the slower, so this is how
often the codec engaged.  ``better`` says ``lower`` for the machine the
benchmark runs on: its chip is host-attached, every encoded batch there
is host time the link did not need, and 0 is the reading of an edge that
decided raw.  A deployment behind a slow link reads it the other way: 100
there means the codec is shrinking a transfer that bounds the rate.  A
program that stages nothing packed (a mesh) gives nothing to read."""


def read(trace, stats, window):
    staged = stats.get("wire_batches", 0) + stats.get("wire_raw_batches", 0)
    if staged <= 0:
        return None
    return 100.0 * stats["wire_batches"] / staged
