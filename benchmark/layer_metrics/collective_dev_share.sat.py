"""mesh collectives (ICI): device time inside collective operations
(all-gather, all-reduce, all-to-all, reduce-scatter, collective-permute
and the start / done halves of their asynchronous forms, by the opcode
on the ``XLA Ops`` line) / device busy time in the traced span, both means
over the chips.  Measured, where ``monitoring/shard_ledger.py`` prices ICI
from a modeled bandwidth.  0 where the mapping leaves the interconnect
nothing to carry (a key-sharded step fed a replicated batch); a trace of
one chip has no mesh and gives nothing to read."""

COLLECTIVES = ("all-gather", "all-reduce", "all-to-all", "reduce-scatter",
               "collective-permute")


def is_collective(op_name: str) -> bool:
    """``all-gather-start.3 all-gather-start`` -> True: ``trace_reduce``
    names an operation ``<instruction> <opcode>``."""
    opcode = op_name.rsplit(" ", 1)[-1]
    for half in ("-start", "-done"):
        if opcode.endswith(half):
            opcode = opcode[:-len(half)]
    return opcode in COLLECTIVES


def read(trace, stats, window):
    if trace is None or len(trace["devices"]) < 2 or trace["busy_s"] <= 0:
        return None
    inside = sum(s for n, s in trace["ops"].items() if is_collective(n))
    return 100.0 * inside / trace["busy_s"]
