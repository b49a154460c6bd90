"""egress / sink: bytes moved device to host per tuple over the window."""


def read(trace, stats, window):
    if stats["pulled"] <= 0:
        return None
    return stats["d2h_bytes"] / stats["pulled"]
