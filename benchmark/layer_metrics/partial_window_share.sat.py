"""fused operator program: windows cut at a key's START (fewer rows than
the window holds: ``CB_partial_windows``) / windows fired
(``CB_windows_fired``), %, from the counters of the count window in
event-time order, over the whole run (the configuration's check reads
them once the stream has ended and keeps them: ``LAST_COUNTERS`` of its
module).  On a replayed segment a seller comes back pass after pass, so
the share falls as the run grows; the reference counts the same share
(``expected(...).partial``).  A configuration that keeps no such
counters, or a program without them, gives nothing to read."""
from benchmark.harness import load_module


def share(counters):
    if not counters:
        return None
    fired = counters.get("CB_windows_fired")
    part = counters.get("CB_partial_windows")
    if not fired or part is None:
        return None
    return 100.0 * part / fired


def read(trace, stats, window):
    try:
        mod = load_module("configs", window["config"]["name"])
    except (OSError, KeyError):
        return None
    return share(getattr(mod, "LAST_COUNTERS", None))
