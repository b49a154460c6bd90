"""Process start -> the first measured tuple is due: import, native
build, compile cache, ring, graph build, warm-up (compilation included)."""


def read(trace, stats, window):
    return window["setup_s"]
