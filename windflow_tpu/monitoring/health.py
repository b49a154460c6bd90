"""Health plane: per-operator watchdog, stall attribution, postmortems.

The monitoring layers so far *report* — counters (stats.py), spans
(recorder.py), compiles (jit_registry.py) — but never *judge*: a stalled
shard or a backpressured operator surfaced only as a bare
``"PipeGraph stalled ... (routing bug?)"`` and a dashboard that kept
showing the app alive.  This module closes that gap (the DrJAX stance:
silent degradation on a large mesh must be a first-class, machine-readable
signal):

* **State machine.**  :class:`HealthPlane` derives one of
  ``OK / BACKPRESSURED / STALLED / FAILED`` per operator from the gauges
  the monitor cadence already samples — queue-depth, watermark-frontier
  advancement, per-op input progress, and recompile storms from the
  compile watcher.  Evaluation runs at *cadence* (the 1 Hz monitoring
  thread, ``stats()`` reads, the stall path) — never on the per-batch hot
  path; with ``Config.health_watchdog`` off, ``PipeGraph`` binds no plane
  at all and every call site degenerates to one ``is not None`` check.

* **Stall attribution.**  On a stall (the driver loop made no progress,
  or the watchdog saw an operator's frontier frozen past the grace
  period), :meth:`HealthPlane.diagnose_stall` walks the operator list in
  reverse topological order and names the first operator still holding
  pending input whose progress counters stopped — the root cause whose
  refusal to drain explains every upstream symptom.  The diagnosis (per-op
  queue depth, frontier, last-advance age) is embedded in the raised
  ``WindFlowError`` instead of "routing bug?".

* **Verdict timeline.**  State *changes* append to a bounded deque, so a
  postmortem shows when each operator degraded, not just the final frame.

Thresholds live in ``Config`` (``WF_TPU_HEALTH_*`` env knobs,
docs/OBSERVABILITY.md "Health plane").  The plane never imports jax at
module scope; the black-box bundle it feeds (``PipeGraph.dump_postmortem``)
is rendered offline by ``tools/wf_doctor.py`` with no jax either.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Dict, Optional

from windflow_tpu.basic import current_time_usecs

#: operator health states, worst last (graph verdict = max by this order).
#: SLO_VIOLATED sits between OK and BACKPRESSURED: the pipeline is
#: draining fine, it is just slower than the declared latency budget
#: (Config.latency_slo_ms; monitoring/latency_ledger.py) — with no SLO
#: configured the state is unreachable and every transition matches the
#: pre-SLO plane verbatim.
OK = "OK"
#: the roofline plane's advisory verdict (monitoring/calibration.py
#: RooflineLedger): the dominant hop's achieved throughput collapsed
#: against its own trailing baseline for ENTER_AFTER consecutive ticks.
#: The lowest non-OK notch — purely advisory (nothing is failing, the
#: pipeline just got slower than itself), so every harder state
#: outranks it; with the plane off the state is unreachable and every
#: transition matches the pre-roofline plane verbatim.
ROOFLINE_DEGRADED = "ROOFLINE_DEGRADED"
SLO_VIOLATED = "SLO_VIOLATED"
#: the tenant plane's budget verdict (monitoring/tenant_ledger.py):
#: the tenant this operator belongs to holds more resident device state
#: than Config.hbm_budget_bytes for ENTER_AFTER consecutive ticks.  One
#: notch above SLO_VIOLATED (memory overage starves co-resident tenants;
#: a slow pipeline only starves itself) and below BACKPRESSURED — with
#: no budget declared the state is unreachable and every transition
#: matches the pre-tenant plane verbatim.
OVER_BUDGET = "OVER_BUDGET"
BACKPRESSURED = "BACKPRESSURED"
STALLED = "STALLED"
FAILED = "FAILED"
STATES = (OK, ROOFLINE_DEGRADED, SLO_VIOLATED, OVER_BUDGET, BACKPRESSURED,
          STALLED, FAILED)
_SEVERITY = {s: i for i, s in enumerate(STATES)}

#: postmortem bundle schema tag (tools/wf_doctor.py validates against it)
POSTMORTEM_SCHEMA = "wf-postmortem/1"

#: state-change timeline entries retained for the postmortem (the
#: reshard executor's timeline keeps as many)
HISTORY = 256


class _OpTrack:
    """Watchdog memory for one operator: the previous sample's counters
    and the timestamps the state machine derives ages from."""

    __slots__ = ("name", "state", "since_usec", "last_advance_usec",
                 "last_inputs", "last_frontier", "queue_depth", "frontier",
                 "compile_storm", "failure", "stall_latched", "hot_shard",
                 "slo", "over_budget", "roofline")

    def __init__(self, name: str, now: int) -> None:
        self.name = name
        self.state = OK
        self.since_usec = now          # when the current state was entered
        self.last_advance_usec = now   # inputs/frontier last moved
        self.last_inputs = -1
        self.last_frontier: Optional[int] = None
        self.queue_depth = 0
        self.frontier: Optional[int] = None
        self.compile_storm = False
        self.failure: Optional[str] = None
        #: set by diagnose_stall's attribution: STALLED stays latched
        #: until the operator makes progress again (a later cadence
        #: sample inside the grace window must not flip a confirmed
        #: root cause back to OK)
        self.stall_latched = False
        #: shard-plane attribution (monitoring/shard_ledger.py): the
        #: specific replica holding the backlog when the operator is
        #: degraded and runs at parallelism > 1 — a BACKPRESSURED/
        #: STALLED verdict names the hot SHARD, not just the operator
        self.hot_shard: Optional[dict] = None
        #: latency-ledger attribution when this operator dominates an
        #: active SLO violation (monitoring/latency_ledger.py verdict)
        self.slo: Optional[dict] = None
        #: tenant-ledger attribution when this operator is the heaviest
        #: op of a tenant in active budget overage
        #: (monitoring/tenant_ledger.py verdict)
        self.over_budget: Optional[dict] = None
        #: roofline-ledger attribution when this operator is the
        #: dominant hop of an active throughput-collapse verdict
        #: (monitoring/calibration.RooflineLedger)
        self.roofline: Optional[dict] = None

    def verdict(self, now: int) -> dict:
        v = {
            "state": self.state,
            "since_usec": self.since_usec,
            "queue_depth": self.queue_depth,
            "watermark_frontier_usec": self.frontier,
            "last_advance_age_usec": max(0, now - self.last_advance_usec),
            "compile_storm": self.compile_storm,
            "failure": self.failure,
        }
        if self.hot_shard is not None:
            v["hot_shard"] = self.hot_shard
        if self.slo is not None:
            v["slo"] = self.slo
        if self.over_budget is not None:
            v["over_budget"] = self.over_budget
        if self.roofline is not None:
            v["roofline"] = self.roofline
        return v


class HealthPlane:
    """Graph-scoped watchdog.  Built by ``PipeGraph._build`` when
    ``Config.health_watchdog`` is on; every entry point is cadence-rate
    (1 Hz monitor thread, ``stats()``, the stall/crash paths) and takes
    the plane's own lock — nothing here runs per batch."""

    def __init__(self, graph) -> None:
        self.graph = graph
        cfg = graph.config
        self.stall_grace_usec = max(0, int(cfg.health_stall_grace_usec))
        self.backpressure_depth = int(cfg.health_backpressure_depth) \
            or max(1, cfg.max_inbox_messages // 2)
        self.recompile_storm = max(1, int(cfg.health_recompile_storm))
        now = current_time_usecs()
        self._tracks: Dict[str, _OpTrack] = {
            op.name: _OpTrack(op.name, now) for op in graph._operators}
        #: state-change timeline: {"t_usec", "changes": {op: state}}
        self.timeline: deque = deque(maxlen=HISTORY)
        self.stall_events = 0
        self.last_stall: Optional[dict] = None
        self.samples_taken = 0
        self.sample_usec_total = 0.0   # watchdog self-cost
        self._stall_bundle_written = False   # cadence auto-bundle: once
        #: thread id of a bundle write in progress (set by the graph's
        #: bundle writer around its locked write): an auto-bundle fired
        #: from the re-entrant stats sample on the SAME thread would
        #: deadlock the non-reentrant postmortem lock — another thread's
        #: auto-bundle just serializes behind the lock and must proceed
        self._bundle_thread = None
        self._lock = threading.Lock()
        #: latency ledger (monitoring/latency_ledger.py), bound by
        #: PipeGraph._build when Config.latency_ledger is on; its active
        #: SLO verdict turns the dominant operator's OK into
        #: SLO_VIOLATED (None = one attribute check per sample)
        self.latency = None
        #: tenant handle (monitoring/tenant_ledger.GraphTenantHandle),
        #: bound by PipeGraph._build when Config.tenant_ledger is on;
        #: its active OVER_BUDGET verdict turns the heaviest operator's
        #: OK into OVER_BUDGET (None = one attribute check per sample —
        #: the kill-switch contract, micro-asserted by
        #: tests/test_tenant_plane.py)
        self.tenant = None
        #: roofline ledger (monitoring/calibration.RooflineLedger),
        #: bound by PipeGraph._build when Config.roofline_plane is on;
        #: its active collapse verdict turns the dominant hop's OK into
        #: the advisory ROOFLINE_DEGRADED (None = one attribute check
        #: per sample, micro-asserted by tests/test_calibration.py)
        self.roofline = None
        #: the jit registry is process-global and never resets: baseline
        #: its per-op recompile counts now so a storm verdict reflects
        #: THIS graph's run, not a prior graph sharing operator names
        self._recompile_base = self._recompile_counts()

    # -- sampling (the watchdog tick) ---------------------------------------
    def sample(self, now: Optional[int] = None) -> dict:
        """One watchdog evaluation over the live graph.  Returns the
        per-operator verdict map.  Reads of replica counters are lock-free
        (same telemetry stance as ``PipeGraph._backpressured``); the
        plane's own bookkeeping is serialized — the monitor thread and a
        ``stats()`` caller may tick concurrently."""
        t0 = time.perf_counter()
        now = now if now is not None else current_time_usecs()
        storms = self._compile_storms()
        # snapshot the ledger's SLO verdict once, outside the lock — the
        # ledger ticks on the same monitor thread, so this is a plain
        # read of its latest published verdict, not a re-evaluation
        lat = self.latency
        slo_v = lat.verdict if lat is not None and lat.slo_active else None
        # same stance for the tenant ledger's budget verdict: the ledger
        # ticks at the same cadence, this is a read of its latest
        # published verdict (None unless THIS graph holds the tenant's
        # heaviest op — only that graph paints the verdict)
        ten = self.tenant
        ob_v = ten.health_verdict() if ten is not None else None
        # and the roofline ledger's collapse verdict — same plain-read
        # stance (the ledger ticks on the same monitor thread)
        rfl = self.roofline
        rf_v = rfl.health_verdict() if rfl is not None else None
        with self._lock:
            changes = {}
            for op in self.graph._operators:
                track = self._tracks.get(op.name)
                if track is None:   # operator added post-build: track late
                    track = self._tracks[op.name] = _OpTrack(op.name, now)
                state = self._evaluate_op(op, track, now,
                                          storms.get(op.name, False),
                                          slo_v, ob_v, rf_v)
                if state != track.state:
                    track.state = state
                    track.since_usec = now
                    changes[op.name] = state
            if changes:
                self.timeline.append({"t_usec": now, "changes": changes})
            verdicts = {name: t.verdict(now)
                        for name, t in self._tracks.items()}
            self.samples_taken += 1
            self.sample_usec_total += (time.perf_counter() - t0) * 1e6
            newly_stalled = [op for op, s in changes.items()
                             if s == STALLED]
            write_bundle = False
            if newly_stalled:
                # watchdog-confirmed stall (cadence detection — streaming
                # deployments driving step() never reach wait_end's hard
                # stall); count the event, auto-bundle once per graph
                # (wait_end's hard-stall path dumps its own fresher frame
                # regardless — bundle writes are serialized by the
                # graph's postmortem lock)
                self.stall_events += 1
                if not self._stall_bundle_written \
                        and self._bundle_thread != threading.get_ident() \
                        and self.graph.config.health_postmortem_on_crash:
                    self._stall_bundle_written = True
                    write_bundle = True
        if write_bundle:
            # outside the lock: dump_postmortem re-enters section()/sample()
            self.graph._safe_postmortem(
                "watchdog: stalled operator(s) " + ", ".join(newly_stalled))
        return verdicts

    def _evaluate_op(self, op, track: _OpTrack, now: int,
                     storm: bool, slo_v: Optional[dict] = None,
                     ob_v: Optional[dict] = None,
                     rf_v: Optional[dict] = None) -> str:
        # the queue-depth/min-frontier walk is the graph's (shared with
        # gauges(): the watchdog must judge exactly what the lag gauge
        # reports, or the two drift)
        depth, frontier = self.graph.op_frontier_and_depth(op)
        inputs = 0
        alive = False
        for rep in op.replicas:
            inputs += rep.stats.inputs_received
            if not rep.done:
                alive = True
        advanced = inputs != track.last_inputs \
            or (frontier is not None and frontier != track.last_frontier)
        if advanced:
            track.last_advance_usec = now
        track.last_inputs = inputs
        track.last_frontier = frontier
        track.queue_depth = depth
        track.frontier = frontier
        track.compile_storm = storm
        track.slo = None   # re-attached below only while the violation holds
        track.over_budget = None   # ditto for the budget verdict
        track.roofline = None      # ditto for the roofline collapse
        # hot-shard attribution: the replica holding the deepest backlog
        # (ties broken by the most-lagged frontier) — per-replica reads
        # only, so it works with the shard ledger off too; the ledger's
        # hot-KEY table joins in at diagnose_stall
        track.hot_shard = None
        if len(op.replicas) > 1 and depth > 0:
            from windflow_tpu.batch import WM_MAX, WM_NONE
            worst, w_depth, w_front = None, -1, None
            for rep in op.replicas:
                d = len(rep.inbox)
                wm = rep.current_wm
                f = wm if (wm != WM_NONE and wm < WM_MAX) else None
                if d > w_depth or (d == w_depth and f is not None
                                   and (w_front is None or f < w_front)):
                    worst, w_depth, w_front = rep.index, d, f
            if worst is not None and w_depth > 0:
                track.hot_shard = {
                    "shard": worst,
                    "queue_depth": w_depth,
                    "watermark_frontier_usec": w_front,
                }
        if advanced:
            track.stall_latched = False
        if track.failure is not None:
            return FAILED
        if not alive:
            # terminated cleanly — but a still-latched SLO verdict keeps
            # naming the run's latency story for post-run stats() and
            # postmortem readers (the ledger stops ticking with the
            # graph, so the latch is the final word)
            state = OK
            if rf_v is not None and rf_v.get("dominant_op") == op.name:
                # advisory and lowest-severity: attached first so a
                # latched SLO/budget verdict takes the state slot
                track.roofline = rf_v
                state = ROOFLINE_DEGRADED
            if slo_v is not None and slo_v.get("dominant_op") == op.name:
                track.slo = slo_v
                state = SLO_VIOLATED
            if ob_v is not None and ob_v.get("heaviest_op") == op.name:
                # resident state outlives the run — a latched budget
                # verdict is post-run truth, same as the SLO latch
                track.over_budget = ob_v
                state = OVER_BUDGET
            return state
        if track.stall_latched:
            return STALLED
        if depth > 0 and not advanced \
                and now - track.last_advance_usec >= self.stall_grace_usec:
            # latch here too: a grace-window detection IS a confirmed
            # stall — diagnose_stall reads the latch to avoid counting
            # the same stall a second time at wait_end
            track.stall_latched = True
            return STALLED
        if depth >= self.backpressure_depth or storm:
            return BACKPRESSURED
        # SLO check LAST: a violation only upgrades an otherwise-OK
        # operator (FAILED/STALLED/BACKPRESSURED already name a harder
        # problem and the latency verdict rides along in track.slo
        # regardless via the ledger section) — and only the verdict's
        # dominant operator carries the state, so one slow op does not
        # paint the whole graph red
        state = OK
        # roofline check FIRST among the verdict upgrades: advisory and
        # lowest-severity, so an SLO/budget verdict on the same operator
        # overwrites the state slot (the attribution stays in
        # track.roofline regardless), and only the collapse verdict's
        # dominant hop carries the state
        if rf_v is not None and rf_v.get("dominant_op") == op.name:
            track.roofline = rf_v
            state = ROOFLINE_DEGRADED
        if slo_v is not None and slo_v.get("dominant_op") == op.name:
            track.slo = slo_v
            state = SLO_VIOLATED
        # budget check after SLO: both verdicts attach to their tracks,
        # and when one operator carries both, OVER_BUDGET (the more
        # severe state) wins the state slot — the co-resident tenants
        # it starves are a harder problem than its own latency
        if ob_v is not None and ob_v.get("heaviest_op") == op.name:
            track.over_budget = ob_v
            state = OVER_BUDGET
        return state

    def _recompile_counts(self) -> dict:
        """Summed compile-watcher recompiles per operator.  A registry
        entry maps by exact name or a "."-suffixed variant (wf_jit sites
        key "{op}.mesh"/"{op}.dense"/…) — a bare prefix would let
        operator 'agg' absorb 'agg2's recompiles.  Guarded: the watchdog
        must never die on a telemetry probe."""
        try:
            from windflow_tpu.monitoring.jit_registry import default_registry
            snap = default_registry().snapshot()
        except Exception:  # lint: broad-except-ok (the registry imports
            # jax; on an exotic/dead backend the storm signal degrades to
            # "none", the rest of the verdict still computes)
            return {}
        counts = {}
        for op in self.graph._operators:
            counts[op.name] = sum(
                entry.get("recompiles", 0)
                for name, entry in snap.items()
                if name == op.name or name.startswith(op.name + "."))
        return counts

    def _compile_storms(self) -> dict:
        """Per-operator recompilation-storm flags: recompiles accumulated
        SINCE this plane's construction (the process-global registry never
        resets — raw totals would leak a prior graph's storm into a fresh
        graph sharing operator names)."""
        counts = self._recompile_counts()
        return {name: True for name, n in counts.items()
                if n - self._recompile_base.get(name, 0)
                >= self.recompile_storm}

    # -- failure / stall notifications --------------------------------------
    def note_failure(self, exc: BaseException) -> Optional[str]:
        """Crash-path attribution: walk the traceback for the innermost
        replica frame and mark its operator FAILED.  Returns the operator
        name (None when no replica frame exists — e.g. a failure in the
        driver loop itself)."""
        op_name = None
        tb = getattr(exc, "__traceback__", None)
        while tb is not None:
            me = tb.tb_frame.f_locals.get("self")
            op = getattr(getattr(me, "op", None), "name", None)
            if op is not None and hasattr(me, "inbox"):
                op_name = op               # keep the innermost replica
            tb = tb.tb_next
        now = current_time_usecs()
        with self._lock:
            target = self._tracks.get(op_name) if op_name else None
            if target is not None:
                target.failure = f"{type(exc).__name__}: {exc}"[:300]
                if target.state != FAILED:
                    target.state = FAILED
                    target.since_usec = now
                    self.timeline.append({"t_usec": now,
                                          "changes": {op_name: FAILED}})
        return op_name

    def diagnose_stall(self) -> dict:
        """Attribution for a confirmed stall: sample once more, then walk
        the operator list in REVERSE topological order and name the first
        operator still holding pending input — the deepest consumer that
        stopped draining, whose refusal explains every upstream backlog.
        Records the stall event and returns the diagnosis dict (also kept
        as ``last_stall`` for the postmortem)."""
        now = current_time_usecs()
        verdicts = self.sample(now)
        root = None
        already_counted = False
        with self._lock:
            for op in reversed(self.graph._operators):
                track = self._tracks[op.name]
                live = any(not r.done for r in op.replicas)
                if live and track.queue_depth > 0:
                    # a cadence tick may have latched (and counted) this
                    # stall already — confirm, don't double-count
                    already_counted = track.stall_latched
                    if track.state != STALLED:
                        track.since_usec = now
                    track.state = STALLED
                    track.stall_latched = True
                    root = op.name
                    break
            if root is not None and not already_counted:
                verdicts[root] = self._tracks[root].verdict(now)
                self.timeline.append({"t_usec": now,
                                      "changes": {root: STALLED}})
            if not already_counted:
                self.stall_events += 1
            diag = {
                "t_usec": now,
                "root_cause": root,
                "verdicts": verdicts,
            }
            self.last_stall = diag
        if root is not None:
            # shard-plane join: the root operator's per-shard load and
            # hot-key table, so the diagnosis names the hot SHARD (and
            # the key pinning it) rather than just the operator
            led = getattr(self.graph, "_shard", None)
            if led is not None:
                try:
                    diag["shard"] = led.op_summary(root)
                except Exception:  # lint: broad-except-ok (same stance
                    # as every other health read: a ledger bug must not
                    # replace the stall diagnosis)
                    pass
        return diag

    @staticmethod
    def format_diagnosis(diag: dict) -> str:
        """The human half of a stall diagnosis — the text embedded in the
        raised ``WindFlowError`` so a stall is debuggable from the
        exception alone."""
        root = diag.get("root_cause")
        verdicts = diag.get("verdicts") or {}
        if root:
            v = verdicts.get(root, {})
            head = (f"root cause '{root}': stopped draining with "
                    f"{v.get('queue_depth', '?')} message(s) pending "
                    f"(frontier={v.get('watermark_frontier_usec')}, "
                    f"last advance "
                    f"{(v.get('last_advance_age_usec') or 0) / 1e6:.3f}s "
                    "ago)")
            hs = v.get("hot_shard")
            if hs:
                head += (f"; hot shard {hs.get('shard')} holds "
                         f"{hs.get('queue_depth')} of them")
            sh = diag.get("shard") or {}
            hot = (sh.get("hot_keys") or [{}])[0]
            if hot.get("key") is not None:
                head += (f" — key {hot['key']} alone carries "
                         f"{100 * (hot.get('share') or 0):.0f}% of the "
                         f"stream (shard ledger, {sh.get('basis')})")
        else:
            head = ("no operator holds pending input — sources idle but "
                    "the graph never terminated (source starvation or a "
                    "lost EOS)")
        per_op = "; ".join(
            f"{name}={v.get('state')}"
            f"(queue={v.get('queue_depth')}, "
            f"age={(v.get('last_advance_age_usec') or 0) / 1e6:.1f}s)"
            for name, v in verdicts.items())
        return f"{head}. Per-operator: {per_op}"

    # -- reporting -----------------------------------------------------------
    def section(self, sample_first: bool = True) -> dict:
        """The ``stats()["Health"]`` payload (one fresh watchdog tick by
        default — ``stats()`` reads are cadence-rate by contract)."""
        now = current_time_usecs()
        if sample_first:
            self.sample(now)
        with self._lock:
            return {
                "enabled": True,
                "graph_state": max(
                    (t.state for t in self._tracks.values()),
                    key=_SEVERITY.__getitem__) if self._tracks else OK,
                "verdicts": {name: t.verdict(now)
                             for name, t in self._tracks.items()},
                "stall_events": self.stall_events,
                "last_stall": self.last_stall,
                "samples_taken": self.samples_taken,
                "watchdog_usec_total": round(self.sample_usec_total, 1),
                "thresholds": {
                    "stall_grace_usec": self.stall_grace_usec,
                    "backpressure_depth": self.backpressure_depth,
                    "recompile_storm": self.recompile_storm,
                },
                "timeline": list(self.timeline),
            }
