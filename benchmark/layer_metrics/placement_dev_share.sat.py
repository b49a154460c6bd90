"""fused operator program: device time inside scatter operations / device
busy time in the traced span: what a window step that places its batch by
scatter (``TB_placement: scatter`` in ``g.stats()["Operators"]``; a grid
past ``ffat_kernels.DENSE_PLACE_MAX_CELLS``) pays for the placement, and 0
for one that places by contraction.

XLA:TPU runs a scatter as a ``fusion`` of ``kind=kCustom`` and the ``XLA
Ops`` line names an event by its whole HLO instruction, so a scatter is
told from the gathers (the other custom fusions) by its shapes: it takes
an index lane AND an update lane of one length, and its result (the table
written) is not of that length; a gather takes one index lane and its
result follows it.  An instruction that says ``scatter`` by opcode counts
too.  A scatter whose updates are a constant folded into the fusion reads
as a gather and is left out, and the re-layout loops behind a scatter's
result are not counted either: the share is a floor.  One chip only (a
mesh cell has ``collective_dev_share.sat``)."""
import re

from benchmark import trace_reduce

SHAPE = re.compile(r"\b(pred|[suf]\d+|bf16)\[([\d,]*)\]")


def is_scatter(event_name: str) -> bool:
    """``%fusion.259 = (u32[43253826], u32[43253826]) fusion(u32[43253826]
    %broadcast, ..., s32[262144] %idx, u32[262144] %v), kind=kCustom`` ->
    True; ``%fusion.64 = u32[1179648] fusion(u32[1179648] %table,
    s32[1179648] %idx), kind=kCustom`` (a gather) -> False."""
    head, eq, rest = event_name.partition(" = ")
    if not eq:
        return False
    result, call, tail = rest.partition(" fusion(")
    if not call:
        return trace_reduce.short_op(event_name).split(" ")[-1] == "scatter"
    if "kind=kCustom" not in tail:
        return False
    operands = SHAPE.findall(tail.partition("), kind=")[0])
    out_dims = {dims for _, dims in SHAPE.findall(result)}
    lanes = {dims for dt, dims in operands
             if dt == "s32" and dims and dims not in out_dims}
    return any(sum(d == dims for _, d in operands) >= 2 for dims in lanes)


def scatter_seconds(path: str):
    """Device seconds inside scatters, mean over the chips of the trace;
    None when the trace holds no ``XLA Ops`` line."""
    from jax.profiler import ProfileData
    per_chip = []
    for plane in ProfileData.from_file(path).planes:
        if not trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            if line.name == trace_reduce.OPS_LINE:
                per_chip.append(sum(e.duration_ns for e in line.events
                                    if is_scatter(e.name)) / 1e9)
    return sum(per_chip) / len(per_chip) if per_chip else None


def read(trace, stats, window):
    if trace is None or len(trace["devices"]) != 1 or trace["busy_s"] <= 0:
        return None
    path = window.get("trace_dir") and trace_reduce.find_xplane(
        window["trace_dir"])
    if not path:
        return None
    inside = scatter_seconds(path)
    return None if inside is None else 100.0 * inside / trace["busy_s"]
