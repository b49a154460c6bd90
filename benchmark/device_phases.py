"""From the program's own names on the device's ``XLA Ops`` line to device
time by phase of operator.

The program opens a ``jax.named_scope`` around the code that writes each
phase of a device program (``recorder.phase`` / ``recorder.operator_scope``
in ``windflow_tpu/monitoring/recorder.py``; the table of names is
``recorder.PHASES`` and ``docs/OBSERVABILITY.md`` "Device phases"), so the
HLO ``op_name`` of every operation reads ``.../wf.op.<operator>/wf.<phase>/
<primitive>``, and a profiler capture shows it as the stat ``tf_op`` of the
event's METADATA record on the chip's ``XLA Ops`` line, next to XLA's own
``bytes_accessed``.  ``jax.profiler.ProfileData`` does not show metadata
stats, so this module walks the ``.xplane.pb`` protobuf itself (a varint
walker over the few fields it needs; no import beyond the standard library
and numpy).  One pass gives, per chip and as a mean over the chips:

* device seconds by ``(module, operator, phase)``: the XLA module the event
  ran in (``jit_step``, ``jit_unpack_fn``; by time, from the ``XLA Modules``
  line), the ``wf.op.<operator>`` component of its path (``None`` outside
  any) and the INNERMOST ``wf.<phase>`` component;
* the rest, ``unscoped``: events whose path holds no phase, by instruction;
* ``bytes_accessed`` summed along the same rows (XLA's MODELED bytes: over
  a measured time they can read above the HBM peak, so they are printed,
  never a metric).

Container operations (``while``, ``conditional``, ``call``:
``trace_reduce.CONTAINERS``) hold other events of the same line and are
left out, as ``trace_reduce`` leaves them out, so the rows add up to the
line's leaf time.

**Attribution rule.**  XLA fuses across scope borders, and a fusion carries
the ``op_name`` of its ROOT instruction: a fusion's whole time goes to the
phase that wrote its root.  XLA:TPU's own passes make instructions WITHOUT
any ``op_name``: the custom fusion a scatter is expanded into, the chunks
of a large gather, the 64-bit rewrite's split / combine calls, copies and
re-layouts; on a step that scatters into wide 64-bit state they are a
third of the chip's time (``nexmark_q5.saturated``: 33 %).  The trace
carries every program's HLO (plane ``/host:metadata``), so such an
instruction is charged to its nearest neighbour in the dataflow that has a
phase: among its users first, then its operands, then the instruction
that calls its computation (:func:`infer_scopes`).  What stays
``unscoped`` has no named neighbour at all; ``unnamed_s`` says how much
time was attributed by a neighbour's name.

A trace of a program without scopes (any commit before they existed, or an
executable served from a compilation cache filled by one: the cache keys a
program with its metadata stripped) gives ``None`` for every phase metric
and nothing raises.

    python3 benchmark/device_phases.py <trace_dir or .xplane.pb> [N]

prints the table: ms per run of the module, share of busy, GB/s of modeled
bytes against ``peaks.json``'s HBM peak, the unscoped rest with its largest
instructions and, with ``N``, the ``N`` largest instructions of every row
(``~``: one that took a neighbour's phase).
"""

from __future__ import annotations

import json
import os
import re
import sys
from typing import Dict, Iterator, List, Optional, Tuple

import numpy as np

if __package__ in (None, ""):         # run as a script
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from benchmark import harness, trace_reduce  # noqa: E402

#: ``wf.op.<operator>`` and ``wf.<phase>`` components of an ``op_name``
OPERATOR = re.compile(r"(?:^|/)wf\.op\.([^/:]+)")
PHASE = re.compile(r"(?:^|/)(wf\.(?!op\.)[A-Za-z0-9_.]+)(?=[/:]|$)")
UNSCOPED = "unscoped"
#: the plane that holds each program's ``HloProto``, keyed by program id
HLO_PLANE = "/host:metadata"
MASK64 = (1 << 64) - 1
TOP_UNSCOPED = 5

Row = Tuple[Optional[str], Optional[str], str]   # module, operator, phase


# ---------------------------------------------------------------------------
# the protobuf, as far as it is needed (tsl/profiler/protobuf/xplane.proto)
# ---------------------------------------------------------------------------

def _fields(buf: bytes, pos: int, end: int) -> Iterator[tuple]:
    """``(field number, wire type, value)`` of one message: an int for a
    varint or a fixed field, ``(start, end)`` for a length-delimited one."""
    while pos < end:
        key = shift = 0
        while True:
            b = buf[pos]
            pos += 1
            key |= (b & 0x7F) << shift
            if b < 0x80:
                break
            shift += 7
        wire = key & 7
        if wire == 0:
            val = shift = 0
            while True:
                b = buf[pos]
                pos += 1
                val |= (b & 0x7F) << shift
                if b < 0x80:
                    break
                shift += 7
            yield key >> 3, 0, val
        elif wire == 2:
            n = shift = 0
            while True:
                b = buf[pos]
                pos += 1
                n |= (b & 0x7F) << shift
                if b < 0x80:
                    break
                shift += 7
            yield key >> 3, 2, (pos, pos + n)
            pos += n
        elif wire == 1:
            yield key >> 3, 1, int.from_bytes(buf[pos:pos + 8], "little")
            pos += 8
        elif wire == 5:
            yield key >> 3, 5, int.from_bytes(buf[pos:pos + 4], "little")
            pos += 4
        else:
            raise ValueError(f"wire type {wire} at byte {pos}")


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _text(buf: bytes, span) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _stat(buf: bytes, span) -> Tuple[int, object]:
    """One ``XStat``: ``(metadata id, value)``; a ``ref_value`` comes back
    as ``("ref", id)`` for the plane's stat-metadata names to resolve."""
    mid, val = 0, None
    for num, wire, v in _fields(buf, *span):
        if num == 1:
            mid = v
        elif num in (3, 4):                 # uint64 / int64
            val = _signed(v) if num == 4 else v
        elif num in (5, 6):                 # str / bytes
            val = _text(buf, v) if num == 5 else None
        elif num == 7:
            val = ("ref", v)
        elif num == 2:                      # double: nothing here needs it
            val = None
    return mid, val


def _map_entry(buf: bytes, span) -> Tuple[int, Optional[tuple]]:
    key, value = 0, None
    for num, _, v in _fields(buf, *span):
        if num == 1:
            key = v
        elif num == 2:
            value = v
    return key, value


def _line(buf: bytes, span) -> Tuple[str, int, List[tuple]]:
    """One ``XLine``: its name, ``timestamp_ns`` and the spans of its
    events (parsed only for the lines that are read)."""
    name, t0, events = "", 0, []
    for num, _, v in _fields(buf, *span):
        if num == 2:
            name = _text(buf, v)
        elif num == 3:
            t0 = _signed(v)
        elif num == 4:
            events.append(v)
    return name, t0, events


def _events(buf: bytes, t0_ns: int, spans: List[tuple]) -> np.ndarray:
    """``[metadata id, start ns, end ns]`` a row."""
    out = np.empty((len(spans), 3), np.float64)
    for i, span in enumerate(spans):
        mid = off = dur = 0
        for num, _, v in _fields(buf, *span):
            if num == 1:
                mid = v
            elif num == 2:
                off = v
            elif num == 3:
                dur = v
        start = t0_ns + off / 1e3
        out[i] = (mid, start, start + dur / 1e3)
    return out


def scope_of(op_name: Optional[str]) -> Tuple[Optional[str], Optional[str]]:
    """``jit(step)/wf.op.win/wf.place/scatter-add:`` -> ``("win",
    "wf.place")``: the operator and the innermost phase of a path."""
    if not op_name:
        return None, None
    op = OPERATOR.search(op_name)
    ph = PHASE.findall(op_name)
    return (op.group(1) if op else None), (ph[-1] if ph else None)


def _varints(buf: bytes, wire: int, v) -> List[int]:
    """A repeated int64 field, packed or not."""
    if wire == 0:
        return [v]
    out, (pos, end) = [], v
    while pos < end:
        val = shift = 0
        while True:
            b = buf[pos]
            pos += 1
            val |= (b & 0x7F) << shift
            if b < 0x80:
                break
            shift += 7
        out.append(val)
    return out


def _hlo_instructions(buf: bytes, span) -> List[tuple]:
    """The instructions of one ``HloProto`` (xla/service/hlo.proto), as
    :func:`infer_scopes` takes them: ``(name, op_name, operand names,
    computation, names of the computations it calls)``."""
    raw, comp_name = [], {}
    for num, _, module in _fields(buf, *span):
        if num != 1:                        # HloProto.hlo_module
            continue
        for mnum, _, comp in _fields(buf, *module):
            if mnum != 3:                   # HloModuleProto.computations
                continue
            cname, cid, members = "", 0, []
            for cnum, _, v in _fields(buf, *comp):
                if cnum == 1:
                    cname = _text(buf, v)
                elif cnum == 5:
                    cid = v
                elif cnum == 2:             # HloComputationProto.instructions
                    members.append(v)
            comp_name[cid] = cname
            for ispan in members:
                name, path, iid, operands, calls = "", None, 0, [], []
                for inum, wire, v in _fields(buf, *ispan):
                    if inum == 1:
                        name = _text(buf, v)
                    elif inum == 7:         # OpMetadata.op_name
                        for onum, _, ov in _fields(buf, *v):
                            if onum == 2:
                                path = _text(buf, ov)
                    elif inum == 35:
                        iid = v
                    elif inum == 36:
                        operands += _varints(buf, wire, v)
                    elif inum == 38:
                        calls += _varints(buf, wire, v)
                raw.append((name, path, iid, operands, cname, calls))
    by_id = {iid: name for name, _, iid, _, _, _ in raw}
    return [(name, path, [by_id[o] for o in operands if o in by_id], cname,
             [comp_name[c] for c in calls if c in comp_name])
            for name, path, _, operands, cname, calls in raw]


def infer_scopes(instructions: List[tuple]) -> Dict[str, tuple]:
    """``{instruction: (operator, phase)}`` for the instructions of one
    program whose own ``op_name`` holds no phase and whose place in the
    dataflow gives them one.  XLA:TPU's own passes (the expansion of a
    scatter or a large gather, the 64-bit rewrite's split / combine, the
    copies and re-layouts between fusions) create instructions WITHOUT
    metadata; such glue exists for the sake of what consumes it, so it is
    charged to the nearest instruction with a phase among its users,
    followed through further unnamed ones (breadth first, in program
    order); where its result only leaves the computation, to the nearest
    among its operands; where neither has one, to the instruction that
    calls its computation (a ``while`` body, a ``conditional`` branch)."""
    own = {n: scope_of(path) for n, path, _, _, _ in instructions}
    operands = {n: ops for n, _, ops, _, _ in instructions}
    users: Dict[str, List[str]] = {}
    caller: Dict[str, str] = {}
    where = {}
    for n, _, ops, comp, calls in instructions:
        where[n] = comp
        for o in ops:
            users.setdefault(o, []).append(n)
        for c in calls:
            caller.setdefault(c, n)

    def nearest(start: str, edges) -> Optional[tuple]:
        seen, frontier = {start}, [start]
        while frontier:
            nxt = []
            for x in frontier:
                for y in edges.get(x, ()):
                    if y in seen or where.get(y) != where[start]:
                        continue
                    seen.add(y)
                    if own[y][1] is not None:
                        return own[y]
                    nxt.append(y)
            frontier = nxt
        return None

    out: Dict[str, tuple] = {}

    def scope(n: str, depth: int = 0) -> Optional[tuple]:
        if own[n][1] is not None:
            return own[n]
        if n not in out:
            found = nearest(n, users) or nearest(n, operands)
            if found is None and depth < 8 and where[n] in caller:
                found = scope(caller[where[n]], depth + 1)
            out[n] = found
        return out[n]

    for n in own:
        scope(n)
    return {n: sc for n, sc in out.items() if sc is not None}


def read_xplane(path: str) -> List[dict]:
    """The device planes of a trace, ``[{"chip", "modules": [(name, start
    ns, end ns)], "ops": array [metadata id, start ns, end ns], "meta": {id:
    (event name, tf_op or None, bytes_accessed, program id)}, "inferred":
    {program id: {instruction: (operator, phase)}}}]``: the last from the
    programs' HLO, which the trace carries on its ``/host:metadata`` plane
    (:func:`infer_scopes`)."""
    with open(path, "rb") as f:
        buf = f.read()
    planes = []
    inferred: Dict[int, dict] = {}
    for num, _, pspan in _fields(buf, 0, len(buf)):
        if num != 1:
            continue
        name, lines, emeta, smeta = "", [], [], {}
        for pnum, _, v in _fields(buf, *pspan):
            if pnum == 2:
                name = _text(buf, v)
            elif pnum == 3:
                lines.append(v)
            elif pnum == 4:
                emeta.append(v)
            elif pnum == 5:
                key, span = _map_entry(buf, v)
                for snum, _, sv in _fields(buf, *span):
                    if snum == 2:
                        smeta[key] = _text(buf, sv)
        if name == HLO_PLANE:
            for mspan in emeta:
                key, span = _map_entry(buf, mspan)
                for mnum, _, v in _fields(buf, *(span or (0, 0))):
                    if mnum != 5:
                        continue
                    for snum, _, sv in _fields(buf, *v):
                        if snum == 6:       # the one stat: "Hlo Proto"
                            inferred[key & MASK64] = infer_scopes(
                                _hlo_instructions(buf, sv))
        dev = trace_reduce.DEVICE_PLANE.match(name)
        if not dev:
            continue
        ops = mods = np.empty((0, 3))
        for lspan in lines:
            lname, t0, spans = _line(buf, lspan)
            if lname == trace_reduce.OPS_LINE:
                ops = _events(buf, t0, spans)
            elif lname == trace_reduce.MODULES_LINE:
                mods = _events(buf, t0, spans)
        used = set(ops[:, 0].astype(np.int64).tolist()
                   + mods[:, 0].astype(np.int64).tolist())
        meta: Dict[int, tuple] = {}
        tf_op = next((k for k, n in smeta.items() if n == "tf_op"), None)
        nbytes = next((k for k, n in smeta.items()
                       if n == "bytes_accessed"), None)
        program = next((k for k, n in smeta.items()
                        if n == "program_id"), None)
        for mspan in emeta:
            key, span = _map_entry(buf, mspan)
            if key not in used or span is None:
                continue
            ename, path_, moved, pid = "", None, 0, None
            for mnum, _, v in _fields(buf, *span):
                if mnum == 2:
                    ename = _text(buf, v)
                elif mnum == 5:
                    sid, val = _stat(buf, v)
                    if isinstance(val, tuple):
                        val = smeta.get(val[1])
                    if sid == tf_op:
                        path_ = val
                    elif sid == nbytes and isinstance(val, int):
                        moved = val
                    elif sid == program and isinstance(val, int):
                        pid = val & MASK64
            meta[key] = (ename, path_, moved, pid)
        planes.append({
            "chip": int(dev.group(1)), "ops": ops, "meta": meta,
            "inferred": inferred,
            "modules": [(trace_reduce.module_name(meta[int(m)][0]), s, e)
                        for m, s, e in mods if int(m) in meta]})
    return planes


# ---------------------------------------------------------------------------
# the reduction
# ---------------------------------------------------------------------------

def reduce_planes(planes: List[dict]) -> Optional[dict]:
    """Seconds and modeled bytes by row, means over the chips.  ``planes``
    as :func:`read_xplane` gives them (the tests hand-make them).  None
    without an ``XLA Ops`` line."""
    planes = [p for p in planes if len(p["ops"])]
    if not planes:
        return None
    n = len(planes)
    rows: Dict[Row, List[float]] = {}
    parts: Dict[Row, Dict[str, float]] = {}
    runs: Dict[Row, set] = {}
    busy_s = leaf_s = unnamed_s = 0.0
    for p in planes:
        mods = sorted(p["modules"], key=lambda m: m[1])
        starts = np.array([m[1] for m in mods], np.float64)
        iv = np.array([(s, e) for _, s, e in mods], np.float64)
        busy_s += trace_reduce.union_seconds(iv)[0] / n
        scopes: Dict[int, tuple] = {}
        for mid, (ename, path, moved, *pid) in p["meta"].items():
            short = trace_reduce.short_op(ename)
            op, ph = scope_of(path)
            named = ph is not None
            if not named:
                # an instruction XLA made without a name: its neighbours'
                op, ph = p.get("inferred", {}).get(
                    pid[0] if pid else None, {}).get(
                    short.split(" ")[0], (op, None))
            scopes[mid] = (short.split(" ")[-1] in trace_reduce.CONTAINERS,
                           short, op, ph, moved, named)
        # the module event each operation started in
        inside = np.searchsorted(starts, p["ops"][:, 1], side="right") - 1
        for (mid, s, e), i in zip(p["ops"], inside.tolist()):
            container, short, op, ph, moved, named = scopes.get(
                int(mid), (False, "?", None, None, 0, False))
            if container:
                continue
            if not named:
                unnamed_s += (e - s) / 1e9 / n
            module = mods[i][0] if i >= 0 and s < mods[i][2] else None
            key = (module, op, ph or UNSCOPED)
            row = rows.setdefault(key, [0.0, 0.0])
            row[0] += (e - s) / 1e9 / n
            row[1] += moved / n
            leaf_s += (e - s) / 1e9 / n
            # by instruction, a "~" before one that took a neighbour's name
            short = short if named or ph is None else "~" + short
            part = parts.setdefault(key, {})
            part[short] = part.get(short, 0.0) + (e - s) / 1e9 / n
            # programs of several operators share a module's name
            # (jit_step): a row's runs are the module events it ran in
            runs.setdefault(key, set()).add((p["chip"], i))
    rest: Dict[str, float] = {}
    for (_, _, ph), part in parts.items():
        if ph == UNSCOPED:
            for short, secs in part.items():
                rest[short] = rest.get(short, 0.0) + secs
    return {"chips": n, "busy_s": busy_s, "leaf_s": leaf_s, "rows": rows,
            "unscoped_ops": rest, "unnamed_s": unnamed_s, "parts": parts,
            "runs": {k: len(v) / n for k, v in runs.items()}}


def seconds(red: dict, phases, module=None) -> float:
    """Device seconds of the phases named, in the modules ``module``
    accepts (a predicate on the module's name; every module without)."""
    return sum(s for (m, _, ph), (s, _) in red["rows"].items()
               if ph in phases and (module is None or module(m or "")))


def first_stage(module: str) -> bool:
    """Not the program of a window stage fed by another window's rows
    (``jit_step_w2``, ...: ``stage2_dev_ms_per_batch.sat`` reads those)."""
    later = harness.load_module(
        "layer_metrics", "stage2_dev_ms_per_batch.sat").LATER_STAGES
    return not later.search(module)


def has_phases(red: Optional[dict]) -> bool:
    return red is not None and any(ph != UNSCOPED
                                   for _, _, ph in red["rows"])


_loaded: Dict[str, Optional[dict]] = {}


def load(window: dict) -> Optional[dict]:
    """The reduction of the run's trace, read once; None in an untraced
    run, without a trace file, and for a program without scopes."""
    trace_dir = window.get("trace_dir")
    if not trace_dir or window.get("trace0") is None:
        return None
    if trace_dir not in _loaded:
        path = trace_reduce.find_xplane(trace_dir)
        red = None if path is None else reduce_planes(read_xplane(path))
        _loaded[trace_dir] = red if has_phases(red) else None
    return _loaded[trace_dir]


def dev_ms_per_batch(window: dict, phases, module=None) -> Optional[float]:
    """Device time inside the phases named, per staging batch (262144
    tuples) pulled in the traced span: ``step_dev_ms_per_batch.sat``'s
    denominator.  None where the trace holds none of them."""
    red = load(window)
    if red is None:
        return None
    batches = harness.load_module(
        "layer_metrics", "step_dev_ms_per_batch.sat").traced_batches(window)
    secs = seconds(red, phases, module)
    if batches <= 0 or secs <= 0:
        return None
    return secs / batches * 1e3


def unscoped_share(window: dict) -> Optional[float]:
    """Device time of the ``XLA Ops`` events under no phase / busy time of
    the traced span (``XLA Modules``), %."""
    red = load(window)
    if red is None or red["busy_s"] <= 0:
        return None
    return 100.0 * seconds(red, (UNSCOPED,)) / red["busy_s"]


# ---------------------------------------------------------------------------
# the table, by hand
# ---------------------------------------------------------------------------

def tables(red: dict, hbm_bytes_per_s: float, detail: int = 0) -> str:
    busy = red["busy_s"]
    rest_s = seconds(red, (UNSCOPED,))
    out = [f"{red['chips']} chip(s); busy {busy:.6f} s (XLA Modules), leaf "
           f"operations {red['leaf_s']:.6f} s = "
           f"{100 * red['leaf_s'] / busy if busy else 0:.2f} % of busy "
           "(containers left out)",
           f"a phase of their own: {red['leaf_s'] - red['unnamed_s']:.6f} "
           f"s; a neighbour's (instructions XLA made without a name): "
           f"{max(0.0, red['unnamed_s'] - rest_s):.6f} s; unscoped: "
           f"{rest_s:.6f} s",
           "GB/s: XLA's MODELED bytes_accessed over measured time (it can "
           f"pass the HBM peak, {hbm_bytes_per_s / 1e9:.0f} GB/s)", "",
           f"{'module':18} {'operator':14} {'phase':18} {'s':>10} "
           f"{'ms/run':>9} {'% busy':>7} {'GB/s':>8} {'% peak':>7}"]
    for (mod, op, ph), (s, moved) in sorted(red["rows"].items(),
                                            key=lambda kv: -kv[1][0]):
        runs = red["runs"][(mod, op, ph)]
        rate = moved / s if s > 0 else 0.0
        out.append(
            f"{mod or '-':18} {op or '-':14} {ph:18} {s:10.6f} "
            f"{1e3 * s / runs if runs else 0.0:9.4f} "
            f"{100 * s / busy if busy else 0:7.2f} {rate / 1e9:8.1f} "
            f"{100 * rate / hbm_bytes_per_s:7.1f}")
        for name, part in sorted(red["parts"][(mod, op, ph)].items(),
                                 key=lambda kv: -kv[1])[:detail]:
            out.append(f"{'':52} {part:10.6f} "
                       f"{1e3 * part / runs if runs else 0.0:9.4f}  {name}")
    rest = sorted(red["unscoped_ops"].items(), key=lambda kv: -kv[1])
    out += ["", f"unscoped: {sum(s for _, s in rest):.6f} s in "
            f"{len(rest)} instructions; the largest:"]
    out += [f"  {s:10.6f} s  {name}" for name, s in rest[:TOP_UNSCOPED]]
    return "\n".join(out)


if __name__ == "__main__":
    arg = sys.argv[1]
    found = trace_reduce.find_xplane(arg) if os.path.isdir(arg) else arg
    if found is None:
        sys.exit(f"no .xplane.pb under {arg}")
    reduction = reduce_planes(read_xplane(found))
    if reduction is None:
        sys.exit("no XLA Ops line in this trace")
    if not has_phases(reduction):
        print("no wf.<phase> in this trace: the program that ran has no "
              "scopes (or its executables came from an older cache)")
    with open(os.path.join(harness.BENCH, "peaks.json")) as f:
        peak = next(iter(json.load(f).values()))["hbm_bytes_per_s"]
    print(tables(reduction, peak,
                 int(sys.argv[2]) if len(sys.argv) > 2 else 0))
