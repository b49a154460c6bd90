"""fused operator program: device time inside ``sort`` operations / device
busy time in the traced span: what a step that orders its batch (session
windows sort by key, then event time, every batch) pays for the ordering.
The ``XLA Ops`` line names an event by its HLO instruction; a sort is one
whose opcode is ``sort``.  A trace without such an operation gives nothing
to read."""


def sort_seconds(trace):
    return sum(s for name, s in trace.get("ops", {}).items()
               if name.split(" ")[-1] == "sort")


def read(trace, stats, window):
    if trace is None or trace.get("busy_s", 0) <= 0:
        return None
    inside = sort_seconds(trace)
    return 100.0 * inside / trace["busy_s"] if inside > 0 else None
