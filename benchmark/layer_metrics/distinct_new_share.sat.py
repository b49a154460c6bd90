"""fused operator program: members a rolling aggregate's sets did not
hold yet (``Agg_members_new``) / members it tested (``Agg_members_
tested``), %, from the aggregate's counters over the whole run (the
configuration's check reads them once the stream has ended and keeps
them: ``LAST_COUNTERS`` of its module).  On a replayed segment every
(group, member) pair comes back pass after pass: the first pass sets the
bits, every later one must find them set, so the share falls towards 0
as the run grows, and a program that counted a member again reads high.
A configuration that keeps no such counters, or a program without them,
gives nothing to read."""
from benchmark.harness import load_module


def share(counters):
    if not counters:
        return None
    tested = counters.get("Agg_members_tested")
    new = counters.get("Agg_members_new")
    if not tested or new is None:
        return None
    return 100.0 * new / tested


def read(trace, stats, window):
    try:
        mod = load_module("configs", window["config"]["name"])
    except (OSError, KeyError):
        return None
    return share(getattr(mod, "LAST_COUNTERS", None))
