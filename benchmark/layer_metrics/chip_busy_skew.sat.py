"""device: (busiest chip's busy time - least busy chip's) / the mean busy
time over the chips, in the traced span.  Uniform keys over key shards
of equal width hold it near 0; a hot key, or a mapping that gives one
chip more of the work, does not.  A trace of one chip has nothing to
compare."""


def read(trace, stats, window):
    if trace is None or len(trace["devices"]) < 2 or trace["busy_s"] <= 0:
        return None
    busy = [d["busy_s"] for d in trace["devices"].values()]
    return 100.0 * (max(busy) - min(busy)) / (sum(busy) / len(busy))
