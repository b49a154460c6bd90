"""staging: bytes moved host to device per tuple over the window (the
wire plane's compression shows here; on a mesh, what the sharded ingest
replicates)."""


def read(trace, stats, window):
    if stats["pulled"] <= 0:
        return None
    return stats["h2d_bytes"] / stats["pulled"]
