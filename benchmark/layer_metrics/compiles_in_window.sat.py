"""unpack program (and every other): programs the jit registry saw
compile inside the window.  Should read 0."""


def read(trace, stats, window):
    return stats["compiles"]
