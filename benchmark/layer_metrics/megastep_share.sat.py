"""megastep: staged batches that ran inside a K-batch scan / all staged
batches of the megastep edges, over the window.  A graph with no eligible
edge (a mesh) has nothing to read."""


def read(trace, stats, window):
    total = stats["megastep_scanned"] + stats["megastep_per_batch"]
    if total <= 0:
        return None
    return 100.0 * stats["megastep_scanned"] / total
