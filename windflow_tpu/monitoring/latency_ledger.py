"""Latency ledger: per-batch critical-path decomposition + SLO verdicts.

The flight recorder (monitoring/recorder.py) already stamps every sampled
batch's journey — ``staged``/``emitted`` at birth, ``dispatched`` at the
async enqueue, ``device_done`` on the sampled sync, ``collected`` at each
inbox pull, ``sunk`` at the sink — but nothing decomposes those stamps:
``stats()["Latency"]`` reports the staged→sunk total and per-operator
service times, so "p99 is 2 s" never says WHERE the 2 s went.  This
module harvests the existing span rings at monitor/stats cadence — **zero new
hot-path work** — and lands every completed trace in five per-operator
segment histograms:

==========================  =============================================
segment                     meaning
==========================  =============================================
``staged_to_emitted``       ingest / staging-queue wait
``emitted_to_dispatched``   group-formation wait — under the megastep
                            executor this IS the K-wait
``dispatched_to_device_done``  device compute (sampled-sync traces only)
``device_done_to_collected``   D2H drain + downstream inbox wait
``collected_to_sunk``       sink-side processing
==========================  =============================================

Decomposition is a running-max boundary walk over the trace's events
(latest occurrence of each stage), so the five segments **telescope**:
their sum equals the trace's first→last event span exactly — the
segment-sum honesty tests/test_latency_plane.py pins at K=1/4/8.  A
``device_done`` stamp marked ``shared_k = K`` (a megastep group drains
once for K logical batches) keeps its full wall value in the histogram —
each batch really waited that long — but the per-operator
``device_busy_usec`` aggregate credits it at 1/K so group compute is
never double-counted.

On top sits the declarative SLO: when ``Config.latency_slo_ms`` is set,
the ledger evaluates the p99 of a rolling window of recent e2e spans at
watchdog cadence; over budget enters a latched ``SLO_VIOLATED`` verdict
attributed to the dominant (operator, segment) pair of the same window
("p99 budget 250 ms, e2e 309 ms, 61% in emitted→dispatched on op
`window` — megastep K-wait"), cleared only after ``clear_after``
consecutive in-budget evaluations.  The health plane surfaces the
verdict (monitoring/health.py), OpenMetrics exports ``wf_slo_*`` /
``wf_latency_segment_*`` families, the postmortem bundle gains
``latency.json`` (tools/wf_doctor.py renders it).

Off (``Config.latency_ledger = False`` or no flight recorder) the plane
is never built: every call site keeps one ``is not None`` check
(micro-asserted by tests/test_latency_plane.py, same stance as the
other planes).
"""

from __future__ import annotations

from collections import deque
from typing import Dict, List, Optional

import numpy as np

from windflow_tpu.analysis.hotpath import hot_path
from windflow_tpu.basic import current_time_usecs
from windflow_tpu.monitoring.recorder import (COLLECTED, DEVICE_DONE,
                                              DISPATCHED, EMITTED,
                                              LatencyHistogram, SUNK)

#: the five critical-path segments, in pipeline order; index i's segment
#: ends at the boundary stage ``_SEG_STAGE[i]``
SEGMENTS = (
    "staged_to_emitted",
    "emitted_to_dispatched",
    "dispatched_to_device_done",
    "device_done_to_collected",
    "collected_to_sunk",
)

_SEG_STAGE = (EMITTED, DISPATCHED, DEVICE_DONE, COLLECTED, SUNK)

#: human form for verdict messages ("61% in emitted→dispatched ...")
SEGMENT_ARROWS = {
    "staged_to_emitted": "staged→emitted",
    "emitted_to_dispatched": "emitted→dispatched",
    "dispatched_to_device_done": "dispatched→device_done",
    "device_done_to_collected": "device_done→collected",
    "collected_to_sunk": "collected→sunk",
}


def _p99(values: List[float]) -> float:
    if not values:
        return 0.0
    s = sorted(values)
    return s[min(len(s) - 1, int(0.99 * (len(s) - 1) + 0.999))]


class _OpLatency:
    """Per-operator accumulation: one histogram per segment, wall totals,
    the shared_k-deflated device-busy credit, and fire freshness."""

    __slots__ = ("segments", "total_usec", "device_busy_usec",
                 "shared_k_traces", "freshness")

    def __init__(self) -> None:
        self.segments: Dict[str, LatencyHistogram] = {}
        self.total_usec = 0.0
        self.device_busy_usec = 0.0
        self.shared_k_traces = 0
        self.freshness: Optional[LatencyHistogram] = None

    def add_segment(self, seg: str, dt: float, shared: int) -> None:
        h = self.segments.get(seg)
        if h is None:
            h = self.segments[seg] = LatencyHistogram()
        h.add(dt)
        self.total_usec += dt
        if seg == "dispatched_to_device_done":
            if shared > 1:
                self.device_busy_usec += dt / shared
                self.shared_k_traces += 1
            else:
                self.device_busy_usec += dt

    def dominant_segment(self) -> Optional[str]:
        best, best_sum = None, 0.0
        for seg, h in self.segments.items():
            if h.total > best_sum:
                best, best_sum = seg, h.total
        return best


class LatencyLedger:
    """Graph-scoped latency plane.  Built by ``PipeGraph._build`` when
    ``Config.latency_ledger`` AND the flight recorder are on; harvests the
    recorder's rings incrementally (per-ring cursors) at monitor/stats
    cadence and never touches the hot path."""

    #: bound on traces held open awaiting their ``sunk`` event; beyond it
    #: the oldest are dropped (counted, not silently)
    MAX_OPEN = 2048
    #: recently-finalized trace ids remembered so a late event (second
    #: sink of a multicast, ring stragglers) cannot re-open a trace
    DONE_RECENT = 4096

    def __init__(self, recorder, slo_ms: float = 0.0, window: int = 512,
                 clear_after: int = 3, min_samples: int = 8) -> None:
        self.recorder = recorder
        self.slo_usec = float(slo_ms) * 1000.0
        self.clear_after = max(1, int(clear_after))
        self.min_samples = max(1, int(min_samples))
        self._cursors: Dict[int, int] = {}      # id(ring) -> consumed n
        self._open: Dict[int, list] = {}        # trace -> [(op, st, t, sh)]
        self._done_recent = deque(maxlen=self.DONE_RECENT)
        self._done_set = set()
        # rolling evaluation window: (e2e_usec, [(op, seg, dt), ...])
        self._recent = deque(maxlen=max(16, int(window)))
        self.per_op: Dict[str, _OpLatency] = {}
        self.e2e = LatencyHistogram()
        self.segment_totals = {seg: 0.0 for seg in SEGMENTS}
        self.traces_decomposed = 0
        self.traces_dropped = 0
        self.events_lost = 0
        # megastep plane (set by PipeGraph._build after plane attach):
        # source of the per-edge K and freshness floor
        self.megastep_plane = None
        # SLO verdict state machine (enter / latch / clear)
        self.slo_active = False
        self.slo_entered = 0
        self.slo_cleared = 0
        self._ok_ticks = 0
        self._recent_p99_usec = 0.0
        self.verdict: Optional[dict] = None
        self.last_verdict: Optional[dict] = None

    # -- harvest (cadence only; reads the rings the hot path writes) --------
    @hot_path
    def harvest(self) -> None:
        """Consume new ring events since the last harvest, then finalize
        every trace whose ``sunk`` arrived.  All rings are drained before
        any finalization so a trace's upstream events (written earlier in
        wall time) are in hand when its sink event is."""
        sunk_now = []
        for ring in self.recorder.rings:
            n_now = ring.n        # snapshot: writers may advance under us
            key = id(ring)
            n0 = self._cursors.get(key, 0)
            if n_now - n0 > ring.size:
                # the ring wrapped past unconsumed events: count the loss
                # (spans missing their middle still telescope — the
                # boundary walk skips absent stages)
                self.events_lost += (n_now - n0) - ring.size
                n0 = n_now - ring.size
            for j in range(n0, n_now):
                i = j % ring.size
                trace = int(ring.trace[i])
                stage = int(ring.stage[i])
                if trace in self._done_set:
                    continue
                ev = self._open.get(trace)
                if ev is None:
                    ev = self._open[trace] = []
                ev.append((ring.op_name, stage, int(ring.t[i]),
                           int(ring.shared_k[i])))
                if stage == SUNK:
                    sunk_now.append(trace)
            self._cursors[key] = n_now
        for trace in sunk_now:
            ev = self._open.pop(trace, None)
            if ev is not None:
                self._finalize(ev)
                self._remember_done(trace)
        if len(self._open) > self.MAX_OPEN:
            # oldest-first (dict insertion order), no snapshot list of
            # every open trace just to drop a few
            drop = len(self._open) - self.MAX_OPEN
            for _ in range(drop):
                trace = next(iter(self._open))
                del self._open[trace]
                self._remember_done(trace)
            self.traces_dropped += drop

    @hot_path
    def _remember_done(self, trace: int) -> None:
        if len(self._done_recent) == self._done_recent.maxlen:
            self._done_set.discard(self._done_recent[0])
        self._done_recent.append(trace)
        self._done_set.add(trace)

    @hot_path
    def _finalize(self, events: list) -> None:
        """Running-max boundary walk: for each stage in pipeline order
        take its LATEST occurrence (the sink-side ``collected`` of a
        multi-hop trace, the last hop's ``dispatched``); the segment is
        the boundary delta, attributed to the operator that recorded the
        boundary event.  Segments telescope to last−first event time by
        construction — the sum-honesty property the tests pin."""
        events.sort(key=lambda e: e[2])
        t0 = events[0][2]
        prev = t0
        segs = []
        for si, stage in enumerate(_SEG_STAGE):
            best = None
            for e in events:
                if e[1] == stage and (best is None or e[2] >= best[2]):
                    best = e
            if best is None:
                continue        # stage absent (e.g. unsampled device sync)
            b = best[2] if best[2] > prev else prev
            segs.append((best[0], SEGMENTS[si], float(b - prev), best[3]))
            prev = b
        e2e = float(prev - t0)
        for op_name, seg, dt, shared in segs:
            track = self.per_op.get(op_name)
            if track is None:
                track = self.per_op[op_name] = _OpLatency()
            track.add_segment(seg, dt, shared)
            self.segment_totals[seg] += dt
        self.e2e.add(e2e)
        self.traces_decomposed += 1
        brief = []
        for op_name, seg, dt, _shared in segs:
            brief.append((op_name, seg, dt))
        self._recent.append((e2e, brief))

    # -- freshness gauges (called from sampled-sync sites only) -------------
    def note_window_fire(self, op_name: str, ts, valid,
                         now_usec: Optional[int] = None) -> None:
        """Fire-time minus window-close event time over the fired records
        of one sampled (already-synced) window batch.  ``ts``/``valid``
        may be device or host arrays — callers only reach here from sites
        that already paid the sync (1 in sample_every * device_sync_every
        batches), so the ``np.asarray`` is not a new blocking sync."""
        v = np.asarray(valid)
        if not v.any():
            return
        close = int(np.asarray(ts)[v].max())
        if close <= 0:
            return
        if now_usec is None:
            now_usec = current_time_usecs()
        track = self.per_op.get(op_name)
        if track is None:
            track = self.per_op[op_name] = _OpLatency()
        if track.freshness is None:
            track.freshness = LatencyHistogram()
        track.freshness.add(max(0.0, float(now_usec - close)))

    # -- SLO evaluation (watchdog cadence) ----------------------------------
    def tick(self) -> None:
        """One cadence step: harvest, then evaluate the SLO against the
        rolling window.  Enter is immediate, the verdict latches, and
        clear needs ``clear_after`` consecutive in-budget evaluations —
        the same hysteresis stance as the health stall latch."""
        self.harvest()
        if self.slo_usec <= 0:
            return
        e2es = [e for e, _segs in self._recent]
        if len(e2es) < self.min_samples:
            return
        p99 = _p99(e2es)
        self._recent_p99_usec = p99
        if p99 > self.slo_usec:
            if not self.slo_active:
                self.slo_active = True
                self.slo_entered += 1
            self._ok_ticks = 0
            self.verdict = self._build_verdict(p99)
            self.last_verdict = self.verdict
        elif self.slo_active:
            self._ok_ticks += 1
            if self._ok_ticks >= self.clear_after:
                self.slo_active = False
                self.slo_cleared += 1
                self.verdict = None

    def _build_verdict(self, p99_usec: float) -> dict:
        """Attribute the violation to the dominant (operator, segment)
        pair of the SAME rolling window the p99 came from."""
        sums: Dict[tuple, float] = {}
        total = 0.0
        for _e2e, segs in self._recent:
            for op_name, seg, dt in segs:
                sums[(op_name, seg)] = sums.get((op_name, seg), 0.0) + dt
                total += dt
        dom_op, dom_seg, share = None, None, 0.0
        if sums:
            (dom_op, dom_seg), dom_sum = max(sums.items(),
                                             key=lambda kv: kv[1])
            share = dom_sum / total if total else 0.0
        p99_ms = round(p99_usec / 1000.0, 3)
        budget_ms = round(self.slo_usec / 1000.0, 3)
        arrow = SEGMENT_ARROWS.get(dom_seg, dom_seg or "?")
        msg = (f"p99 budget {budget_ms:g} ms, e2e {p99_ms:g} ms, "
               f"{share:.0%} in {arrow} on op `{dom_op}`")
        if dom_seg == "emitted_to_dispatched" and self._megastep_k(dom_op):
            msg += " — megastep K-wait"
        return {
            "state": "SLO_VIOLATED",
            "p99_ms": p99_ms,
            "budget_ms": budget_ms,
            "dominant_op": dom_op,
            "dominant_segment": dom_seg,
            "share": round(share, 4),
            "message": msg,
        }

    def _megastep_k(self, op_name: Optional[str]) -> int:
        plane = self.megastep_plane
        if plane is None or op_name is None:
            return 0
        for edge in plane.edges:
            if edge.op.name == op_name:
                return edge.k
        return 0

    def _megastep_floor(self, op_name: str) -> Optional[float]:
        plane = self.megastep_plane
        if plane is None:
            return None
        for edge in plane.edges:
            if edge.op.name == op_name:
                return edge.freshness_floor_usec()
        return None

    # -- export --------------------------------------------------------------
    def section(self) -> dict:
        """The ``stats()["Latency_plane"]`` payload — also the postmortem
        ``latency.json`` body."""
        graph_total = sum(self.segment_totals.values()) or 0.0
        per_op = {}
        for op_name, track in sorted(self.per_op.items()):
            entry = {
                "segments_usec": {seg: h.quantiles()
                                  for seg, h in sorted(
                                      track.segments.items())},
                "total_usec": round(track.total_usec, 3),
                "budget_share": round(track.total_usec / graph_total, 4)
                if graph_total else 0.0,
                "dominant_segment": track.dominant_segment(),
                "device_busy_usec": round(track.device_busy_usec, 3),
                "shared_k_traces": track.shared_k_traces,
            }
            if track.freshness is not None:
                entry["freshness_usec"] = track.freshness.quantiles()
            k = self._megastep_k(op_name)
            if k:
                entry["megastep_k"] = k
                entry["freshness_floor_usec"] = self._megastep_floor(
                    op_name)
            per_op[op_name] = entry
        return {
            "enabled": True,
            "slo_ms": round(self.slo_usec / 1000.0, 3),
            "traces_decomposed": self.traces_decomposed,
            "traces_open": len(self._open),
            "traces_dropped": self.traces_dropped,
            "events_lost": self.events_lost,
            "e2e_usec": self.e2e.quantiles(),
            "segments_total_usec": {s: round(v, 3) for s, v
                                    in self.segment_totals.items()},
            "per_op": per_op,
            "slo": {
                "active": self.slo_active,
                "entered": self.slo_entered,
                "cleared": self.slo_cleared,
                "recent_p99_ms": round(self._recent_p99_usec / 1000.0, 3),
                "budget_ms": round(self.slo_usec / 1000.0, 3),
                "window": len(self._recent),
                "verdict": self.verdict,
                "last_verdict": self.last_verdict,
            },
        }
