"""staging: share of the window's packed staged batches that shipped
wire-encoded, in %: ``wire_encoded_share.sat`` on the paced cell, where
an encoded batch is host time on the path of the closing tuple.  Read
the same way: ``lower`` is better on the benchmark's host-attached chip,
the other way behind a slow link."""


def read(trace, stats, window):
    staged = stats.get("wire_batches", 0) + stats.get("wire_raw_batches", 0)
    if staged <= 0:
        return None
    return 100.0 * stats["wire_batches"] / staged
