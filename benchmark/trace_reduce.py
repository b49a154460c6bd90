"""From a profiler trace (``*.xplane.pb``) to what the per-layer metrics
read: busy intervals per device, device time per XLA module and per
operation, Mosaic custom-call time, and the idle gaps labelled by what
the host was doing.

Read with ``jax.profiler.ProfileData`` and nothing else.  On a TPU the
trace has one plane per chip, ``/device:TPU:<n>``, whose line ``XLA
Modules`` holds one event per executed program and ``XLA Ops`` one per
operation inside it (a ``while`` and the operations of its body both);
the benchmark's own ``TraceAnnotation`` spans are on the ``python`` line
of ``/host:CPU``.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Tuple

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
MODULES_LINE, OPS_LINE = "XLA Modules", "XLA Ops"
#: the benchmark's host spans, innermost first: a gap is labelled by the
#: first of these that covers its midpoint, and ``step.other`` (the rest
#: of ``g.step()``: parse, pack, H2D, dispatch, drain) if none does
HOST_SPANS = ("sink.callback", "source.pull", "generator.idle")
TOP = 10


def find_xplane(trace_dir: str) -> Optional[str]:
    """The newest ``.xplane.pb`` under ``trace_dir``."""
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def module_name(event_name: str) -> str:
    """``jit_step(123456789)`` -> ``jit_step``: the program's name without
    the fingerprint the profiler appends."""
    return re.sub(r"\(\d+\)$", "", event_name)


def union_seconds(intervals: np.ndarray) -> Tuple[float, np.ndarray]:
    """Length of the union of ``[start, end)`` rows (ns) in seconds, and
    the merged intervals."""
    if not len(intervals):
        return 0.0, intervals.reshape(0, 2)
    iv = intervals[np.argsort(intervals[:, 0])]
    ends = np.maximum.accumulate(iv[:, 1])
    new = np.r_[True, iv[1:, 0] > ends[:-1]]
    starts = iv[new, 0]
    stops = ends[np.r_[np.flatnonzero(new)[1:] - 1, len(iv) - 1]]
    return float((stops - starts).sum()) / 1e9, np.c_[starts, stops]


def _events(line) -> List[Tuple[str, float, float]]:
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for e in line.events]


def short_op(event_name: str) -> str:
    """The ``XLA Ops`` line names an event by its whole HLO instruction
    (``%fusion.612 = u32[...]{...} fusion(...), kind=kCustom, ...``):
    keep the instruction's name and its opcode, ``fusion.612 fusion``."""
    name, eq, rest = event_name.partition(" = ")
    if not eq:
        return event_name[:80]
    if rest.startswith("("):              # a tuple type: skip to its end
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                rest = rest[i + 1:]
                break
    else:
        rest = rest.partition(" ")[2]
    return f"{name.lstrip('%')} {rest.strip().partition('(')[0]}"


def is_mosaic(event_name: str) -> bool:
    """A Pallas kernel runs as a Mosaic custom call."""
    return 'custom_call_target="tpu_custom_call"' in event_name


#: operations that only hold other operations of the same line
CONTAINERS = ("while", "conditional", "call")


def reduce_planes(planes: list) -> dict:
    """``planes``: ``[(plane_name, [(line_name, [(event_name, start_ns,
    end_ns, stats_dict)])])]``.  Returns the reduction (see module
    docstring); device seconds are means over the chips in the trace."""
    devices: Dict[int, dict] = {}
    host: Dict[str, List[Tuple[float, float]]] = {s: [] for s in HOST_SPANS}
    t_lo, t_hi = np.inf, -np.inf
    for pname, lines in planes:
        dev = DEVICE_PLANE.match(pname)
        for lname, events in lines:
            if dev and lname in (MODULES_LINE, OPS_LINE) and events:
                d = devices.setdefault(int(dev.group(1)),
                                       {"modules": {}, "ops": {},
                                        "mosaic_s": 0.0, "busy": None})
                iv = np.array([(s, e) for _, s, e, _ in events], np.float64)
                t_lo, t_hi = min(t_lo, iv[:, 0].min()), max(t_hi,
                                                            iv[:, 1].max())
                if lname == MODULES_LINE:
                    d["busy"] = iv
                    for n, s, e, _ in events:
                        n = module_name(n)
                        d["modules"][n] = d["modules"].get(n, 0.0) \
                            + (e - s) / 1e9
                else:
                    for n, s, e, _ in events:
                        if is_mosaic(n):
                            d["mosaic_s"] += (e - s) / 1e9
                        n = short_op(n)
                        if n.split(" ")[-1] not in CONTAINERS:
                            d["ops"][n] = d["ops"].get(n, 0.0) \
                                + (e - s) / 1e9
            elif pname == HOST_PLANE:
                for n, s, e, _ in events:
                    if n in host:
                        host[n].append((s, e))
    if not devices:
        return {"devices": {}, "window_s": 0.0, "busy_s": 0.0}
    for n, iv in host.items():
        for s, e in iv:
            t_lo, t_hi = min(t_lo, s), max(t_hi, e)
    window_s = (t_hi - t_lo) / 1e9
    n_dev = len(devices)
    per_dev, gaps = {}, []
    for i, d in sorted(devices.items()):
        busy_s, merged = union_seconds(d["busy"] if d["busy"] is not None
                                       else np.empty((0, 2)))
        per_dev[i] = {"busy_s": busy_s,
                      "idle_share": 1.0 - busy_s / window_s}
        if i == min(devices):
            edges = np.r_[t_lo, merged.ravel(), t_hi].reshape(-1, 2)
            gaps = [(a, b) for a, b in edges if b > a]

    def mean_over_devices(key):
        out: Dict[str, float] = {}
        for d in devices.values():
            for n, s in d[key].items():
                out[n] = out.get(n, 0.0) + s / n_dev
        return out

    spans = {n: np.array(iv, np.float64).reshape(-1, 2)
             for n, iv in host.items()}

    def label(a: float, b: float) -> str:
        mid = (a + b) / 2
        for n in HOST_SPANS:
            iv = spans[n]
            if len(iv) and np.any((iv[:, 0] <= mid) & (mid < iv[:, 1])):
                return n
        return "step.other"

    by_label: Dict[str, float] = {}
    for a, b in gaps:
        n = label(a, b)
        by_label[n] = by_label.get(n, 0.0) + (b - a) / 1e9
    modules, ops = mean_over_devices("modules"), mean_over_devices("ops")
    top = lambda d: [[n, s] for n, s in sorted(  # noqa: E731
        d.items(), key=lambda kv: -kv[1])[:TOP]]
    return {
        "window_s": window_s,
        "busy_s": float(np.mean([p["busy_s"] for p in per_dev.values()])),
        "devices": per_dev, "modules": modules, "ops": ops,
        "mosaic_s": float(np.mean([d["mosaic_s"]
                                   for d in devices.values()])),
        "breakdown": {"device_ops": top(ops), "idle_gaps": top(by_label)},
    }


def read_planes(path: str) -> list:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        if not (DEVICE_PLANE.match(plane.name) or plane.name == HOST_PLANE):
            continue
        lines = []
        for line in plane.lines:
            if DEVICE_PLANE.match(plane.name) \
                    and line.name not in (MODULES_LINE, OPS_LINE):
                continue
            keep = None if DEVICE_PLANE.match(plane.name) else HOST_SPANS
            events = []
            for e in line.events:
                if keep is not None and e.name not in keep:
                    continue
                events.append((e.name, float(e.start_ns),
                               float(e.start_ns + e.duration_ns), {}))
            if events:
                lines.append((line.name, events))
        planes.append((plane.name, lines))
    return planes


def reduce_trace(trace_dir: str) -> Optional[dict]:
    path = find_xplane(trace_dir)
    return None if path is None else reduce_planes(read_planes(path))


def describe(path: str, top: int = 12) -> str:
    """A trace by hand: every plane and line, its event count, busiest
    names and one event's stats."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"plane {plane.name!r}")
        for line in plane.lines:
            ev = list(line.events)
            tot: Dict[str, float] = {}
            for e in ev:
                tot[e.name] = tot.get(e.name, 0.0) + e.duration_ns / 1e6
            out.append(f"  line {line.name!r}: {len(ev)} events")
            for n, ms in sorted(tot.items(), key=lambda kv: -kv[1])[:top]:
                out.append(f"    {ms:10.3f} ms  {n}")
            if ev:
                out.append(f"    stats of {ev[0].name!r}: "
                           f"{dict(ev[0].stats)}")
    return "\n".join(out)


if __name__ == "__main__":
    import sys
    print(describe(find_xplane(sys.argv[1]) or sys.argv[1]))
