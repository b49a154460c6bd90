"""device: idle time of chip 0 in the traced span whose innermost covering
span is one of the program's waits for the chip (``recorder.WAITS``) / all
its idle time: the host stands blocked and the chip computes nothing, so
that part of the idle is the LINK (a copy on the wire, a read's round trip),
not the host's work.  None on a program that does not name its waits."""
from benchmark import wait_spans as ws


def read(trace, stats, window):
    return ws.idle_under_wait_share(window)
