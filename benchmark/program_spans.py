"""From the program's own spans in a profiler trace to what the host did
while the chip waited.

The program brackets the host's work at every layer boundary of a sweep
with ``wf.*`` spans (``windflow_tpu/monitoring/recorder.py``; the table is
in ``docs/OBSERVABILITY.md``).  Under ``--trace 1`` they land on the
``/host:CPU`` plane of the same ``.xplane.pb`` as the chips' ``XLA
Modules`` lines, on one clock, one line per host thread, with their counts
(``op``, ``batch``, ``n``, ``cap``, ...) as event stats.  This module reads
that file once and gives the per-layer readers:

* self time per span: its duration minus the part its child spans cover,
  nesting rebuilt per line from the times alone;
* the idle gaps of chip 0 (complement of the union of its ``XLA Modules``
  events over the traced span, the definition ``device_idle.*`` uses),
  each gap shared out BY OVERLAP among the innermost spans that cover it:
  the driver thread's, except where that is ``wf.sweep`` itself and a pool
  thread's span covers the instant; what no span covers is
  ``unattributed`` (the benchmark's own loop around ``g.step()``);
* the fill of the staged batches, from ``n`` and ``cap`` of
  ``wf.wire.encode``.

A program without these spans (any commit before they existed) gives
``None`` everywhere and nothing raises.

    python3 benchmark/program_spans.py <trace_dir or .xplane.pb>

prints the self-time and idle-gap tables of one trace.
"""

from __future__ import annotations

import os
import sys
from typing import Dict, List, Optional, Tuple

import numpy as np

if __package__ in (None, ""):         # run as a script
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from benchmark import harness, trace_reduce  # noqa: E402

PREFIX = "wf."
ROOT_SPAN = "wf.sweep"
UNATTRIBUTED = "unattributed"
#: spans whose ``op`` tells programs or operators apart
BY_OP = ("wf.dispatch", "wf.compile", "wf.drain")
#: the only spans whose stats are read (a paced cell's trace holds 10^5
#: sweeps and ticks: their stats stay in the file)
WITH_STATS = BY_OP + ("wf.wire.encode",)
#: the generator's span over a stretch in which no chunk was due stays open
#: across sweeps; the sweeps' and ticks' own time under it is the host
#: spinning on an empty source, so it is the generator's, not the
#: scheduler's
WAITING = "generator.idle"
SPINS = ("wf.sweep", "wf.source.tick")

Event = Tuple[str, float, float, dict]     # name, start_ns, end_ns, stats
Segment = Tuple[float, float, tuple]       # start_ns, end_ns, (name, op)


def key_of(name: str, stats: dict) -> tuple:
    return (name, stats.get("op") if name in BY_OP else None)


def show(key: tuple) -> str:
    return key[0] if key[1] is None else f"{key[0]} op={key[1]}"


def read_trace(path: str) -> dict:
    """One pass over the file: every host event named ``wf.*`` and the
    benchmark's own three per thread line, the busy intervals of chip 0,
    and the traced span as ``trace_reduce`` bounds it (device events of
    every chip and the benchmark's spans)."""
    from jax.profiler import ProfileData
    lines: List[List[Event]] = []
    busy: Dict[int, list] = {}
    t_lo, t_hi = np.inf, -np.inf
    for plane in ProfileData.from_file(path).planes:
        dev = trace_reduce.DEVICE_PLANE.match(plane.name)
        if not dev and plane.name != trace_reduce.HOST_PLANE:
            continue
        for line in plane.lines:
            if dev:
                if line.name not in (trace_reduce.MODULES_LINE,
                                     trace_reduce.OPS_LINE):
                    continue
                for e in line.events:
                    s, t = float(e.start_ns), float(e.start_ns
                                                    + e.duration_ns)
                    t_lo, t_hi = min(t_lo, s), max(t_hi, t)
                    if line.name == trace_reduce.MODULES_LINE:
                        busy.setdefault(int(dev.group(1)), []).append((s, t))
                continue
            kept: List[Event] = []
            for e in line.events:
                own = e.name in trace_reduce.HOST_SPANS
                if not own and not e.name.startswith(PREFIX):
                    continue
                s, t = float(e.start_ns), float(e.start_ns + e.duration_ns)
                if own:
                    t_lo, t_hi = min(t_lo, s), max(t_hi, t)
                kept.append((e.name, s, t, dict(e.stats)
                             if e.name in WITH_STATS else {}))
            if kept:
                lines.append(kept)
    chip0 = np.array(busy[min(busy)], np.float64) if busy \
        else np.empty((0, 2))
    return {"lines": lines, "busy": chip0, "t_lo": t_lo, "t_hi": t_hi}


def leaf_segments(events: List[Event]) -> List[Segment]:
    """One thread's spans cut into the stretches in which one span is the
    innermost open: the span's self time is the sum of its stretches.  A
    child is a span that starts inside another; one that starts or ends
    with its parent to the nanosecond still is the inner one, and one
    that ends after its parent (clock jitter, or ``generator.idle``, which
    the generator holds open from one sweep into a later one) keeps the
    spans that start under it as its children.  While ``generator.idle``
    is open, the own time of the sweeps and ticks under it is its."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2], i))
    segs: List[Segment] = []
    stack: List[Tuple[float, tuple]] = []      # (end, key), innermost last
    cursor = 0.0
    waiting_until = -np.inf                    # end of the open WAITING span

    def cut(upto: float) -> None:
        nonlocal cursor
        key = stack[-1][1]
        if key[0] in SPINS and cursor < min(upto, waiting_until):
            segs.append((cursor, min(upto, waiting_until), (WAITING, None)))
            cursor = segs[-1][1]
        if upto > cursor:
            segs.append((cursor, upto, key))
            cursor = upto

    def close_until(t: float) -> None:
        while stack and stack[-1][0] <= t:
            cut(stack[-1][0])
            stack.pop()

    for i in order:
        name, s, t, stats = events[i]
        close_until(s)
        if stack:
            cut(s)
        cursor = max(cursor, s)
        stack.append((t, key_of(name, stats)))
        if name == WAITING:
            waiting_until = max(waiting_until, t)
    close_until(np.inf)
    return segs


def _share_out(rest: List[Tuple[float, float]], segs: List[Segment],
               into: Dict[tuple, float], skip=None):
    """Give each ``rest`` interval's overlap with ``segs`` (sorted,
    disjoint) to the segment's key; return what no segment covered.
    Segments whose key ``skip`` names cover nothing."""
    left: List[Tuple[float, float]] = []
    j = 0
    for a, b in rest:
        while j < len(segs) and segs[j][1] <= a:
            j += 1
        k, at = j, a
        while k < len(segs) and segs[k][0] < b:
            s, e, key = segs[k]
            k += 1
            if skip is not None and key == skip:
                continue
            s, e = max(s, at), min(e, b)
            if e <= s:
                continue
            if s > at:
                left.append((at, s))
            into[key] = into.get(key, 0.0) + (e - s)
            at = e
        if b > at:
            left.append((at, b))
    return left


def analyse(tr: dict) -> Optional[dict]:
    """The tables (seconds).  None when the trace holds no ``wf.sweep``:
    the program that ran had no spans."""
    per_line = [leaf_segments(ev) for ev in tr["lines"]]
    root = (ROOT_SPAN, None)
    in_sweep = [sum(t - s for n, s, t, _ in ev if n == ROOT_SPAN)
                for ev in tr["lines"]]
    if not any(in_sweep):
        return None
    driver = int(np.argmax(in_sweep))      # the thread that runs g.step()

    self_s: Dict[tuple, float] = {}
    total_s: Dict[tuple, float] = {}
    count: Dict[tuple, int] = {}
    fill_n = fill_cap = 0
    for ev, segs in zip(tr["lines"], per_line):
        for name, s, t, stats in ev:
            key = key_of(name, stats)
            total_s[key] = total_s.get(key, 0.0) + (t - s) / 1e9
            count[key] = count.get(key, 0) + 1
            if name == "wf.wire.encode" and "cap" in stats:
                fill_n += int(stats["n"])
                fill_cap += int(stats["cap"])
        for s, e, key in segs:
            self_s[key] = self_s.get(key, 0.0) + (e - s) / 1e9

    span_s = (tr["t_hi"] - tr["t_lo"]) / 1e9
    _, merged = trace_reduce.union_seconds(tr["busy"])
    edges = np.r_[tr["t_lo"], merged.ravel(), tr["t_hi"]].reshape(-1, 2)
    rest = [(a, b) for a, b in edges if b > a]
    idle_s = sum(b - a for a, b in rest) / 1e9
    gaps: Dict[tuple, float] = {}
    # the driver's innermost span; where that is the sweep itself (it
    # waits for the pool), a pool thread's; then the sweep; then nothing
    rest = _share_out(rest, per_line[driver], gaps, skip=root)
    for i, segs in enumerate(per_line):
        if i != driver:
            rest = _share_out(rest, segs, gaps)
    rest = _share_out(rest, per_line[driver], gaps)
    gaps_s = {k: v / 1e9 for k, v in gaps.items()}
    unattributed_s = sum(b - a for a, b in rest) / 1e9

    cover_s, _ = trace_reduce.union_seconds(np.array(
        [(s, t) for n, s, t, _ in tr["lines"][driver] if n == ROOT_SPAN],
        np.float64))
    return {"span_s": span_s, "idle_s": idle_s, "self_s": self_s,
            "total_s": total_s, "count": count, "gaps_s": gaps_s,
            "unattributed_s": unattributed_s,
            "sweep_cover": cover_s / span_s if span_s > 0 else 0.0,
            "driver_self_s": sum((e - s) for s, e, _ in per_line[driver])
            / 1e9,
            "fill": (fill_n, fill_cap)}


_loaded: Dict[str, Optional[dict]] = {}


def load(window: dict) -> Optional[dict]:
    """The analysis of the run's trace, read once; None in an untraced
    run, without a trace file, or for a program without spans."""
    trace_dir = window.get("trace_dir")
    if not trace_dir or window.get("trace0") is None:
        return None
    if trace_dir not in _loaded:
        path = trace_reduce.find_xplane(trace_dir)
        _loaded[trace_dir] = None if path is None \
            else analyse(read_trace(path))
    return _loaded[trace_dir]


def host_ms_per_batch(window: dict, names, op=None) -> Optional[float]:
    """Self time of the spans named, per batch pulled in the traced span.
    ``op``: a predicate on the span's ``op`` stat, for the names that
    carry one."""
    sp = load(window)
    if sp is None:
        return None
    # staging batches' worth of tuples pulled in the traced span, as the
    # device's milliseconds per batch count them
    batches = harness.load_module(
        "layer_metrics", "step_dev_ms_per_batch.sat").traced_batches(window)
    if batches <= 0:
        return None
    secs = sum(s for (name, o), s in sp["self_s"].items()
               if name in names and (op is None or o is None or op(o)))
    return secs / batches * 1e3


def tables(sp: dict) -> str:
    out = [f"traced span {sp['span_s']:.4f} s, wf.sweep covers "
           f"{100 * sp['sweep_cover']:.2f} %, driver-thread self times "
           f"sum to {sp['driver_self_s']:.4f} s",
           "", f"{'span':44} {'count':>7} {'total s':>10} {'self s':>10}"]
    for key, s in sorted(sp["self_s"].items(), key=lambda kv: -kv[1]):
        out.append(f"{show(key):44} {sp['count'].get(key, 0):7d} "
                   f"{sp['total_s'].get(key, 0.0):10.4f} {s:10.4f}")
    out += ["", f"idle gaps of chip 0: {sp['idle_s']:.4f} s",
            f"{'innermost span over the gap':44} {'s':>10} {'share %':>8}"]
    rows = sorted(sp["gaps_s"].items(), key=lambda kv: -kv[1])
    rows = [(show(k), v) for k, v in rows] \
        + [(UNATTRIBUTED, sp["unattributed_s"])]
    for label, s in rows:
        share = 100 * s / sp["idle_s"] if sp["idle_s"] > 0 else 0.0
        out.append(f"{label:44} {s:10.4f} {share:8.2f}")
    n, cap = sp["fill"]
    if cap:
        out += ["", f"batch fill: {n} / {cap} = {100 * n / cap:.2f} %"]
    return "\n".join(out)


if __name__ == "__main__":
    arg = sys.argv[1]
    found = trace_reduce.find_xplane(arg) if os.path.isdir(arg) else arg
    if found is None:
        sys.exit(f"no .xplane.pb under {arg}")
    analysis = analyse(read_trace(found))
    print("no wf.sweep in this trace: the program that ran has no spans"
          if analysis is None else tables(analysis))
