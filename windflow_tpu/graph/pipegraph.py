"""PipeGraph: application container, wiring, and the host driver loop.

Re-design of the reference ``PipeGraph`` (``/root/reference/wf/pipegraph.hpp``).
``run()`` in the reference spawns one OS thread per replica/collector through
FastFlow (``pipegraph.hpp:614-697``); here it wires replica inboxes, emitters
and collectors, then drives everything from a **single cooperative dispatch
loop**.  On TPU the host's only job is to keep compiled programs and transfers
enqueued — JAX dispatch is asynchronous, so while the device crunches batch N
the loop is already staging N+1; thread-per-replica would add contention, not
parallelism (SURVEY.md §7 design stance; and see parallel/mesh.py for how
replication maps to chips instead).

Host-heavy pipelines are the exception: window engines, FlatMaps, sink
serializers all share the driver thread, capping a CPU-operator pipeline at
one core where the reference scales thread-per-replica
(``basic_operator.hpp:54``).  ``Config.host_worker_threads > 0`` restores
that capability with a worker pool: each sweep, host replicas with pending
input drain concurrently (one task per replica, so per-replica processing
stays serial and keyed routing still pins a key to one replica); sources and
TPU replicas stay on the driver thread.  GIL-releasing host work (numpy,
native calls) then scales across cores; ``tests/test_host_pool.py``
pins pooled results equal to single-thread ones, and that one slow
replica does not starve its siblings.

End of run mirrors ``PipeGraph::wait_end`` (``pipegraph.hpp:703-768``): EOS
punctuations cascade, window state flushes, and per-operator stats JSON is
dumped when tracing is enabled.
"""

from __future__ import annotations

import dataclasses
import json
import os
import threading
import time
from collections import deque
from typing import List, Optional

from windflow_tpu.basic import (Config, ExecutionMode, RoutingMode,
                                TimePolicy, WindFlowError,
                                current_time_usecs, default_config)
from windflow_tpu.graph.multipipe import MultiPipe
from windflow_tpu.monitoring import recorder as flightrec
from windflow_tpu.ops.base import Operator
from windflow_tpu.ops.sink import Sink
from windflow_tpu.ops.source import Source, SourceReplica
from windflow_tpu.parallel.collectors import create_collector
from windflow_tpu.parallel.emitters import SplittingEmitter, create_emitter


def _staging_pool_stats() -> dict:
    """Hit/miss counters of the process-wide staging-buffer recycling pool
    (windflow_tpu/staging), surfaced through the monitoring stats dump."""
    from windflow_tpu import staging
    return staging.default_pool().stats()


def _section_error(e: Exception) -> dict:
    """What a plane's section reads as when its read raised."""
    return {"enabled": True, "error": f"{type(e).__name__}: {e}"[:200]}


def _calibration_summary() -> dict:
    """Provenance frame of every modeled constant (monitoring/
    calibration.py), for dump_trace metadata and the postmortem's
    calibration.json — guarded like every other telemetry read."""
    try:
        from windflow_tpu.monitoring import calibration
        return calibration.provenance_summary()
    except Exception as e:  # lint: broad-except-ok (a provenance read
        # must never take a trace dump or postmortem down)
        return {"error": f"{type(e).__name__}: {e}"[:200]}


def _rss_kb() -> float:
    """Resident set size in KiB (reference ``get_MemUsage``,
    ``monitoring.hpp:52-70``)."""
    try:
        with open("/proc/self/statm") as f:
            resident_pages = int(f.read().split()[1])
        return resident_pages * (os.sysconf("SC_PAGE_SIZE") / 1024.0)
    except (OSError, ValueError, IndexError):
        return 0.0


class PipeGraph:
    def __init__(self, name: str = "app",
                 mode: ExecutionMode = ExecutionMode.DEFAULT,
                 time_policy: TimePolicy = TimePolicy.INGRESS,
                 config: Optional[Config] = None) -> None:
        self.name = name
        self.mode = mode
        self.time_policy = time_policy
        self.config = config or dataclasses.replace(default_config)
        self.pipes: List[MultiPipe] = []
        self._splits: List[MultiPipe] = []
        self._merges: List[MultiPipe] = []
        self._started = False
        self._collectors = []
        self._all_replicas = []
        self._source_replicas: List[SourceReplica] = []
        self._operators: List[Operator] = []
        self._monitor = None
        # backpressure telemetry (high-water marks + throttle count)
        self._throttle_events = 0
        self._max_inbox_seen = 0
        self._max_inflight_device_seen = 0
        # staging-plane lookahead telemetry (Config.stage_prefetch_depth)
        self._prefetch_ticks = 0
        # flight recorder (monitoring/recorder.py): built in _build when
        # Config.flight_recorder is on; None means every hook is inert
        self._recorder = None
        # health plane (monitoring/health.py): watchdog built in _build
        # when Config.health_watchdog is on; None means every call site
        # is one flag check (the documented off-path)
        self._health = None
        # sweep ledger (monitoring/sweep_ledger.py): per-hop dispatch/HBM
        # attribution built in _build when Config.sweep_ledger is on;
        # None leaves one `is not None` check at each read site (stats,
        # trace metadata, postmortem) — nothing on the per-batch path
        self._ledger = None
        # shard plane (monitoring/shard_ledger.py): per-shard load/ICI
        # attribution + key-skew sketches on the keyed edges, built in
        # _build when Config.shard_ledger is on; None leaves one
        # `is not None` check at each read site and attaches no sketch
        # anywhere (the per-batch paths then carry one check each)
        self._shard = None
        # whole-chain fusion (windflow_tpu/fusion): the executable fused
        # segments installed by _build when Config.whole_chain_fusion is
        # on — each routes a whole operator chain as ONE jitted dispatch
        # per batch.  Read by the wiring redirect below, the sweep
        # ledger's fusion section, and stats attribution; empty means
        # every hop dispatches its own program (the pre-fusion sweep).
        self._fused_segments = []
        # durability plane (windflow_tpu/durability): epoch checkpoints +
        # restore, built in _build when Config.durability names a
        # directory; None leaves one `is None` check per sweep (the
        # documented off-path, micro-asserted like health/ledger)
        self._durability = None
        # reshard executor (windflow_tpu/serving): applies the shard
        # plane's move_keys/split_hot_key plans live, built in _build
        # when Config.reshard_executor is on (default OFF: unlike the
        # observe-only planes, this one mutates routing); None leaves
        # one `is not None` check per sweep + one per source tick chunk
        self._reshard = None
        # megastep plane (windflow_tpu/megastep.py): K batch sweeps per
        # compiled program on the eligible staged edges, built in _build
        # when Config.megastep_sweeps resolves to K>1; None/inactive
        # leaves the per-batch cadence verbatim (one check per finalize
        # on the staging emitters, nothing anywhere else)
        self._megastep_plane = None
        # latency ledger (monitoring/latency_ledger.py): per-batch
        # critical-path decomposition + SLO verdicts, built in _build
        # when Config.latency_ledger AND the flight recorder are on;
        # None leaves one `is not None` check at each cadence/read site
        # and binds nothing to any replica (micro-asserted)
        self._latency = None
        # tenant plane (monitoring/tenant_ledger.py): this graph's handle
        # into the PROCESS-level tenant ledger — per-tenant HBM/dispatch/
        # byte attribution + budget verdicts across every co-resident
        # PipeGraph, built in _build when Config.tenant_ledger is on;
        # None leaves one `is not None` check at each cadence/read site
        # and registers nothing anywhere (micro-asserted)
        self._tenant = None
        # roofline plane (monitoring/calibration.RooflineLedger): the
        # live achieved-vs-roofline gauge + the advisory
        # ROOFLINE_DEGRADED verdict, built in _build when
        # Config.roofline_plane is on; None leaves one `is not None`
        # check at each cadence/read site and reads no counter anywhere
        # (micro-asserted by tests/test_calibration.py)
        self._roofline = None
        # checkpoint blobs stashed by restore() for the plane to apply
        # after _build (operator state) and before the first source tick
        self._pending_restore = None
        # last postmortem bundle written (crash path or dump_postmortem);
        # the lock serializes writers — the monitor thread's watchdog
        # auto-bundle and the driver's stall/crash path may race into
        # the same directory
        self._postmortem_dir = None
        self._postmortem_lock = threading.Lock()
        # rolling-throughput gauge samples: (wall_s, tuples_sunk_total),
        # appended by sample_gauges() (the monitoring thread calls it once
        # per second; stats() also samples so headless runs get gauges)
        self._thr_samples = deque(maxlen=64)
        # host worker pool (Config.host_worker_threads): replicas drained
        # off the driver thread, and the driver-thread remainder
        self._pool = None
        self._pool_replicas = []
        self._main_replicas = []
        #: columnar sink replicas, polled once a sweep for batches the
        #: device has finished (ops/sink.py SinkReplica.deliver)
        self._columnar_sinks = []
        # pre-flight analysis (windflow_tpu/analysis): last check()'s
        # diagnostics + wall cost, surfaced through stats()
        self._preflight_diags = None
        self._preflight_ms = None
        # wfverify (analysis/tracecheck.py): the object-level verifier's
        # last report (diagnostics folded into _preflight_diags; the
        # report keeps the suppressed findings and per-callable counts)
        self._tracecheck_report = None
        # wfir (analysis/ir_audit.py): the IR auditor's last report —
        # WF9xx findings over the lowered StableHLO of this graph's
        # programs (check() stores it; stats()/postmortem re-audit live)
        self._ir_audit_report = None

    # -- construction --------------------------------------------------------
    def add_source(self, source: Source) -> MultiPipe:
        if self._started:
            raise WindFlowError("cannot add sources to a running PipeGraph")
        mp = MultiPipe(self, source)
        self.pipes.append(mp)
        return mp

    def _register_split(self, mp: MultiPipe) -> None:
        self._splits.append(mp)

    def _register_merge(self, mp: MultiPipe) -> None:
        self._merges.append(mp)
        self.pipes.append(mp)

    # -- wiring --------------------------------------------------------------
    def _all_pipes(self):
        """Every MultiPipe in the graph, including transitive split branches
        (the single traversal used by both replica construction and edge
        wiring, so the two can never diverge)."""
        out = []

        def collect(mp: MultiPipe):
            out.append(mp)
            for child in mp.split_children:
                collect(child)

        for mp in self.pipes:
            collect(mp)
        return out

    def _check_fixed_capacity_ops(self):
        """Fixed-capacity device operators (``Operator.fixed_capacity_label``
        is set: FfatWindowsTPU pane state, StatefulMap/FilterTPU slot
        tables, dense-key ReduceTPU cross-chip tables — each compiles a
        state layout tied to ONE batch capacity) fed by several upstream
        paths — a merge relayed through capacity-preserving TPU stages —
        must see ONE capacity; surface the mismatch at build time with the
        offending sizes instead of a mid-run step error.  (Backstop for
        ``Config.preflight="off"`` runs: the walk itself lives in
        analysis/preflight.py, where :meth:`check` reports it as WF403.)"""
        from windflow_tpu.analysis.preflight import capacity_conflicts
        for op, label, caps in capacity_conflicts(self):
            raise WindFlowError(
                f"'{op.name}' ({label}) compiles for one "
                f"fixed batch capacity but its upstream paths "
                f"deliver {sorted(caps)}; give the merged branches "
                "equal withOutputBatchSize")

    def _edges(self):
        """Yield (src_op, dst_op_or_split, routing) for every graph edge, in
        topological order of the MultiPipe DAG."""
        edges = []
        for mp in self._all_pipes():
            ops = mp.operators
            for a, b in zip(ops, ops[1:]):
                edges.append(("op", a, b))
            if mp.split_children:
                edges.append(("split", mp))
        for merged in self._merges:
            for parent in merged.merge_parents:
                src = parent.operators[-1] if parent.operators else None
                if src is None:
                    raise WindFlowError("cannot merge an empty MultiPipe")
                edges.append(("op", src, merged.operators[0]))
        return edges

    def _topo_operators(self):
        """Every distinct operator in _build's enumeration order — the
        ordinal space checkpoint manifests pin, factored out so restore
        can validate a composed-but-unbuilt graph against a manifest
        (durability/checkpoint.topology_signature) without the two
        traversals ever diverging."""
        seen, out = set(), []
        for mp in self._all_pipes():
            for op in mp.operators:
                if id(op) not in seen:
                    seen.add(id(op))
                    out.append(op)
        return out

    def _build(self) -> None:
        # 1. instantiate replicas
        for op in self._topo_operators():
            op.ordinal = len(self._operators)  # stable topo index
            self._operators.append(op)
            op.mesh = self.config.mesh
            op.config = self.config
            op.build_replicas(self.mode, self.time_policy)
        for op in self._operators:
            self._all_replicas.extend(op.replicas)
            if isinstance(op, Source):
                self._source_replicas.extend(op.replicas)
        for rep in self._all_replicas:
            rep.config = self.config
        if self.config.preflight == "off":
            # preflight reported capacity conflicts already (WF403: raised
            # under "error", warned under "warn" — the promised bypass);
            # only an "off" run needs the original hard build-time check
            self._check_fixed_capacity_ops()

        # 1a. key-aligned mesh ingest (ROADMAP item 4b): stamp eligible
        # host-fed key-sharded FFAT consumers BEFORE wiring — the
        # emitter dispatch (create_emitter) and the op's sharded step
        # factory both read the stamp (parallel/mesh.mark_aligned_ingest)
        if self.config.mesh is not None \
                and self.config.key_aligned_ingest:
            from windflow_tpu.parallel.mesh import mark_aligned_ingest
            mark_aligned_ingest(self)

        # 1b. whole-chain fusion (windflow_tpu/fusion): executable fused
        # segments lower into ONE program per batch — installed BEFORE
        # wiring so the redirect below can route each segment as one hop.
        # Preflight already ran (start() order), so the chains were
        # type-checked as their constituent specs.  Skipped on a mesh:
        # the sharded program factories compose differently.
        from windflow_tpu.fusion import executor as _fusion
        if self.config.whole_chain_fusion \
                and self.config.mesh is None:
            self._fused_segments = _fusion.apply_fusion(self)
        fused_host = {}         # id(segment head/member) -> host op
        fused_edge_skip = set()  # interior (src, dst) id pairs
        for seg in self._fused_segments:
            members = seg["members"]
            for m in members[:-1]:
                fused_host[id(m)] = members[-1]
            for fa, fb in zip(members, members[1:]):
                fused_edge_skip.add((id(fa), id(fb)))

        # 2. wire edges: emitters on sources of the edge, collectors +
        #    channels on destinations.  ``route_op`` carries the edge's
        #    routing contract; ``dst_op`` owns the consuming replicas —
        #    they differ exactly when a fused segment's head hands its
        #    edge to the segment host.
        def wire_edge(src_op: Operator, route_op: Operator,
                      dst_op: Operator):
            emitters = []
            for src_rep in src_op.replicas:
                dests = [(dst_rep, dst_rep.add_channel())
                         for dst_rep in dst_op.replicas]
                em = create_emitter(
                    route_op.routing, dests, src_op.output_batch_size,
                    src_is_tpu=src_op.is_tpu, dst_is_tpu=dst_op.is_tpu,
                    key_extractor=route_op.key_extractor,
                    mesh=self.config.mesh)
                emitters.append(em)
            return emitters

        # downstream-keyby key forwarding (fusion satellite): a chain op
        # feeding exactly one KEYBY device consumer extracts that
        # consumer's keys INSIDE its own program and ships them on the
        # batch's keys lane, so the consumer (or its keyby emitter)
        # never re-extracts — collected while wiring, applied after
        fanout = {}
        key_forward = {}
        for edge in self._edges():
            if edge[0] == "op":
                fanout[id(edge[1])] = fanout.get(id(edge[1]), 0) + 1
            else:
                src = edge[1].operators[-1]
                fanout[id(src)] = fanout.get(id(src), 0) \
                    + len(edge[1].split_children)

        def note_key_forward(a, route_op):
            # skipped when the CONSUMER is a fused-segment head too: the
            # segment host re-extracts in-program (its prelude forces
            # keys=None), so a forwarded lane would be computed per
            # batch and provably discarded
            if route_op.routing == RoutingMode.KEYBY \
                    and route_op.is_tpu \
                    and route_op.key_extractor is not None \
                    and fanout.get(id(a)) == 1 \
                    and id(a) not in fused_host \
                    and id(route_op) not in fused_host:
                key_forward[id(a)] = (a, route_op.key_extractor)

        for edge in self._edges():
            if edge[0] == "op":
                _, a, b = edge
                if (id(a), id(b)) in fused_edge_skip:
                    continue    # interior to a fused segment: no hop
                tgt = fused_host.get(id(b), b)
                note_key_forward(a, b)
                for rep, em in zip(a.replicas, wire_edge(a, b, tgt)):
                    rep.emitter = em
            else:  # split point
                _, mp = edge
                src_op = mp.operators[-1]
                branch_heads = [child.operators[0]
                                for child in mp.split_children]
                per_src_branch_emitters = [
                    wire_edge(src_op, head,
                              fused_host.get(id(head), head))
                    for head in branch_heads]
                # transpose: one SplittingEmitter per source replica
                for i, rep in enumerate(src_op.replicas):
                    branches = [per_src_branch_emitters[b_idx][i]
                                for b_idx in range(len(branch_heads))]
                    rep.emitter = SplittingEmitter(mp.split_fn, branches)

        # 2b. apply the collected key forwards + safe input donation on
        # chain programs (see ops/chained.py; fusion hosts donate through
        # their own program build).  Donation is independent of the
        # fusion flag: the chained-pair step's donation misses exist on
        # un-fused sweeps too (sweep-ledger tripwire).
        from windflow_tpu.ops.chained import ChainedTPU
        upstreams = _fusion._upstream_edges(self)
        from windflow_tpu.windows.ffat_tpu import number_window_stages
        number_window_stages(self._operators, upstreams)
        for a, kx in key_forward.values():
            if a._fusion_exec is not None:
                a._fusion_exec.set_downstream_key_extractor(kx)
            elif isinstance(a, ChainedTPU):
                a.set_downstream_key_extractor(kx)
        for op in self._operators:
            if isinstance(op, ChainedTPU) and id(op) not in fused_host \
                    and op._fusion_exec is None \
                    and _fusion.input_donation_safe(op, upstreams):
                op.enable_input_donation()

        # 2c. fused-segment members are inert: their replicas receive no
        # channels (interior edges skipped above) and never terminate
        # through the EOS cascade — mark them done so is_done() and the
        # watchdog read them as cleanly terminated; their stats are
        # attributed from the fused hop at read time (stats()).
        for seg in self._fused_segments:
            for m in seg["members"][:-1]:
                for rep in m.replicas:
                    rep.done = True
                    rep.stats.is_terminated = True

        # 3. collectors: one per replica with input channels
        for rep in self._all_replicas:
            if rep.num_channels > 0:
                rep.collector = create_collector(self.mode, rep.num_channels)
                self._collectors.append(rep.collector)

        # 3b. observability: the flight recorder's per-replica rings and
        # the emitters' stats/ring/flight binding (monitoring/recorder.py).
        # Transfer byte counters are bound even with the recorder off —
        # they are plain integer adds, and the H2D/D2H totals must be real
        # on every run (stats_record.hpp:152-160 parity).
        cfg = self.config
        if cfg.flight_recorder and cfg.trace_sample_every > 0:
            from windflow_tpu.monitoring.recorder import FlightRecorder
            self._recorder = FlightRecorder(
                sample_every=cfg.trace_sample_every,
                device_sync_every=cfg.trace_device_sync_every,
                expected_rings=len(self._all_replicas))
            for rep in self._all_replicas:
                rep.ring = self._recorder.ring_for(rep.op.name, rep.index)
        for rep in self._all_replicas:
            if rep.emitter is not None:
                rep.emitter.bind_observability(rep.stats, rep.ring,
                                               self._recorder)

        # 3c. health plane (monitoring/health.py): per-operator watchdog
        # evaluated at monitor cadence — built here so the operator list
        # is final; off leaves _health None (one flag check per call site)
        if cfg.health_watchdog:
            from windflow_tpu.monitoring.health import HealthPlane
            self._health = HealthPlane(self)

        # 3d'. durability plane (windflow_tpu/durability): built after
        # replicas exist so it can switch Kafka sink replicas to fenced
        # exactly-once buffering; checkpoints run at sweep cadence from
        # step(), restore state is applied by start() before the first
        # source tick
        if cfg.durability:
            from windflow_tpu.durability.checkpoint import DurabilityPlane
            self._durability = DurabilityPlane(self)

        # 3d. sweep ledger (monitoring/sweep_ledger.py): built AFTER the
        # operator list is final and BEFORE any batch runs, so its
        # registry baseline excludes every earlier graph's dispatches in
        # this process while capturing all of this one's
        if cfg.sweep_ledger:
            from windflow_tpu.monitoring.sweep_ledger import SweepLedger
            self._ledger = SweepLedger(self)

        # 3e. shard plane (monitoring/shard_ledger.py): built AFTER
        # wiring and fusion (it attaches key-skew sketches to the keyed
        # emitters and folds the in-program updates into the keyby
        # split / fused-chain programs, all of which must exist and
        # none of which may have compiled yet)
        if cfg.shard_ledger:
            from windflow_tpu.monitoring.shard_ledger import ShardLedger
            self._shard = ShardLedger(self)

        # 3f. key compaction (parallel/compaction.py): attach remap
        # tables to qualifying keyed consumers and wire the feeding
        # emitters for host admission / placement override — AFTER
        # fusion (preludes installed, fused hosts known) and the shard
        # plane (sketches exist to seed from), before anything compiles.
        # Off attaches nothing: every step keeps one `is not None` check.
        if cfg.key_compaction:
            from windflow_tpu.parallel.compaction import attach_compaction
            attach_compaction(self)

        # 3f'. wire plane (windflow_tpu/wire.py): enable columnar wire
        # compression on the staging emitters whose feeding edge has a
        # declared/inferred record spec — AFTER wiring (the emitters
        # exist) and before anything stages.  Spec-less edges stay raw
        # passthrough (preflight named them as WF606); off/auto-on-CPU
        # attaches no encoder anywhere.
        from windflow_tpu.wire import attach_wire, wire_enabled
        if wire_enabled(cfg):
            attach_wire(self)

        # 3f''. megastep plane (windflow_tpu/megastep.py): hook the
        # eligible staged edges so K consecutive batch sweeps fold into
        # ONE lax.scan dispatch — built AFTER fusion (the tail may be a
        # fused segment host) and the wire plane (the scan body inlines
        # the same wire decode the per-batch unpack runs), before
        # anything stages.  The durability epoch cadence converts here
        # from logical sweeps to K-granular driver sweeps (whole
        # megasteps), so every commit's quiesce lands between megasteps
        # and each epoch covers the stream extent it covered per-batch.
        from windflow_tpu.megastep import (attach_plane,
                                           round_epoch_to_megastep)
        self._megastep_plane = attach_plane(cfg, self._source_replicas)
        round_epoch_to_megastep(cfg, self._megastep_plane)

        # 3f'''. latency ledger (monitoring/latency_ledger.py): per-batch
        # critical-path decomposition of the recorder's span lane + the
        # SLO verdict state machine — built AFTER the recorder (it
        # harvests the rings at cadence) and the megastep plane (the
        # per-edge K and freshness floor feed the verdict/advisor).
        # Window replicas get the ledger bound for the fire-freshness
        # gauge at their existing sampled-sync site; everything else
        # keeps `latency = None` (one check, micro-asserted).
        if cfg.latency_ledger \
                and self._recorder is not None:
            from windflow_tpu.monitoring.latency_ledger import LatencyLedger
            self._latency = LatencyLedger(
                self._recorder,
                slo_ms=cfg.latency_slo_ms or 0.0)
            self._latency.megastep_plane = self._megastep_plane
            for op in self._operators:
                if op.reports_fire_freshness:
                    for rep in op.replicas:
                        rep.latency = self._latency
            if self._health is not None:
                self._health.latency = self._latency

        # 3f''''. tenant plane (monitoring/tenant_ledger.py): register
        # this graph with the PROCESS-level tenant ledger — built AFTER
        # every other plane (attribution baselines must see the final
        # operator/wrapper set, and the ledger reads the shard/latency
        # planes at collect cadence).  Config.tenant defaults to the app
        # name; Config.hbm_budget_bytes > 0 arms the budget state
        # machine whose latched OVER_BUDGET verdict the health plane
        # paints on the tenant's heaviest op.
        if cfg.tenant_ledger:
            from windflow_tpu.monitoring.tenant_ledger import default_ledger
            tenant = cfg.tenant or self.name
            self._tenant = default_ledger().register(
                self, tenant, cfg.hbm_budget_bytes)
            if self._health is not None:
                self._health.tenant = self._tenant

        # 3f'''''. calibration store + roofline plane (monitoring/
        # calibration.py): Config.calibration installs the probe-measured
        # constants process-wide (the shard ICI model, the tenant
        # ledger and the roofline ceiling all read
        # through calibration.constant — their provenance tags flip
        # `modeled` → `calibrated(<age>)`), and the RooflineLedger turns
        # the replicas' existing throughput counters into the live
        # achieved-vs-roofline gauge at monitor cadence.  Built after
        # the sweep/tenant planes (the bytes join reads the sweep
        # section) and before the reshard executor.
        from windflow_tpu.monitoring import calibration as _calib
        if cfg.calibration and not _calib.killed():
            try:
                _calib.set_default_store(_calib.load(cfg.calibration))
            except Exception as e:  # lint: broad-except-ok (a corrupt
                # store must degrade the process to its modeled
                # defaults with a warning, never fail graph build)
                import warnings as _w
                _w.warn(f"Config.calibration={cfg.calibration!r} failed "
                        f"to load ({e}) — running uncalibrated",
                        RuntimeWarning)
        if cfg.roofline_plane:
            self._roofline = _calib.RooflineLedger(self)
            if self._health is not None:
                self._health.roofline = self._roofline

        # 3g. reshard executor (windflow_tpu/serving): built LAST — it
        # discovers the keyed emitters the wiring installed, reads the
        # health plane and shard ledger at tick cadence, and mutates
        # routing only through the quiesce barrier.  Mesh graphs are
        # not executor targets (their reshard mechanism is the rescale
        # restore, docs/DURABILITY.md); replica-sharded keyed operators
        # are.
        if cfg.reshard_executor \
                and self.config.mesh is None:
            from windflow_tpu.serving import ReshardExecutor
            self._reshard = ReshardExecutor(self)

        # sanity: every non-sink replica must have an emitter (fused
        # members are inert by design — the segment host emits for them)
        for op in self._operators:
            if op._fused_into is not None:
                continue
            for rep in op.replicas:
                if rep.emitter is None and not op.is_terminal:
                    raise WindFlowError(
                        f"operator '{op.name}' has no downstream consumer — "
                        "every MultiPipe must end in a Sink")

        # 4. host worker pool partition: host (non-source, pool-safe)
        #    replicas drain concurrently; sources tick on the driver thread
        #    and TPU replicas stay there too (stateful device operators
        #    share state across replicas, serialized by construction —
        #    the role of the reference's spinlock, map_gpu.hpp:114-115)
        if self.config.host_worker_threads > 0:
            from concurrent.futures import ThreadPoolExecutor
            self._pool = ThreadPoolExecutor(
                max_workers=self.config.host_worker_threads,
                thread_name_prefix=f"wf-{self.name}")
            for op in self._operators:
                pooled = (not op.is_tpu and op.host_pool_safe
                          and not isinstance(op, Source))
                (self._pool_replicas if pooled
                 else self._main_replicas).extend(op.replicas)
        else:
            self._main_replicas = self._all_replicas
        self._columnar_sinks = [
            rep for op in self._operators
            if isinstance(op, Sink) and op.columnar
            for rep in op.replicas]

    # -- execution -----------------------------------------------------------
    def run(self) -> "PipeGraph":
        """Build, then drive the whole graph to completion — the
        reference's ``run()`` (``start()`` + ``wait_end()``,
        ``pipegraph.hpp:614-697``); both halves are also public so the
        reference idiom ``g.start(); ...; g.wait_end()`` transliterates."""
        self.start()
        return self.wait_end()

    def wait_end(self) -> "PipeGraph":
        """Drive a started graph to completion (reference
        ``PipeGraph::wait_end``, ``pipegraph.hpp:703-768``); a streaming
        deployment would call :meth:`step` from its own loop instead."""
        if not self._started:
            raise WindFlowError("wait_end before start")
        try:
            while not self.is_done():
                if not self.step():
                    raise self._stall_error()
        except BaseException as exc:
            # Crash path: salvage the telemetry FIRST (health attribution
            # + postmortem bundle — the rings/histograms/jit tables are
            # most valuable exactly now), then release threads.  Do NOT
            # dump stats: a stats dump touching a dead backend would raise
            # inside the handler and mask the root-cause operator error;
            # the postmortem writer guards every section individually.
            try:
                if self._health is not None:
                    # the synthetic stall error has no replica frame in
                    # its traceback, so attribution is a no-op for it; a
                    # genuine replica-raised WindFlowError attributes
                    # like any crash
                    self._health.note_failure(exc)
                self._write_crash_postmortem(exc)
            except BaseException:  # lint: broad-except-ok (salvage must
                # never mask the root-cause error re-raised below — a
                # second Ctrl-C here aborts the salvage, not the teardown)
                pass
            finally:
                self._finalize(dump=False, aborted=True)
            raise
        self._finalize()
        return self

    def _stall_error(self) -> WindFlowError:
        """Build the stall error with the health plane's root-cause
        diagnosis (per-op queue depth, frontier, last-advance age) —
        "routing bug?" told the user nothing.  Also writes the postmortem
        bundle (watchdog-confirmed stall) so the message can point at it."""
        head = ("PipeGraph stalled: no replica made progress but the "
                "graph has not terminated. ")
        if self._health is None:
            return WindFlowError(
                head + "Health watchdog is off (Config.health_watchdog / "
                "WF_TPU_HEALTH=0) — no diagnosis available; re-run with "
                "it on for root-cause attribution")
        try:
            diag = self._health.diagnose_stall()
            msg = head + self._health.format_diagnosis(diag)
        except Exception as e:  # lint: broad-except-ok (same stance as
            # every other health read: a watchdog bug must not replace
            # the stall error — an undiagnosed stall beats a KeyError)
            msg = head + (f"(health diagnosis failed: "
                          f"{type(e).__name__}: {e}"[:200] + ")")
        err = WindFlowError(msg)
        if self.config.health_postmortem_on_crash:
            # always dump a fresh frame here — a watchdog bundle written
            # minutes ago (possibly for a recovered transient stall) is
            # staler than the diagnosis just taken; the write is
            # serialized by the postmortem lock
            bundle = self._safe_postmortem("stall")
            if bundle:
                # mark THE exception as already bundled: the crash-path
                # handler keys off this, not graph state, so neither a
                # manual snapshot nor an old watchdog bundle can suppress
                # a genuine crash bundle later
                err._wf_postmortem_bundle = bundle
                err.args = (msg + f". Postmortem bundle: {bundle}",)
        return err

    def _write_crash_postmortem(self, exc: BaseException) -> None:
        """Best-effort bundle on abnormal termination.  Skipped only when
        THIS exception is the stall error whose bundle _stall_error just
        wrote — any other failure captures crash-time telemetry no matter
        what was bundled before."""
        if self.config.health_postmortem_on_crash \
                and getattr(exc, "_wf_postmortem_bundle", None) is None:
            self._safe_postmortem(f"crash: {type(exc).__name__}: "
                                  f"{exc}"[:300])

    def _safe_postmortem(self, reason: str) -> Optional[str]:
        try:
            return self.dump_postmortem(reason=reason)
        except Exception:  # lint: broad-except-ok (the postmortem writer
            # runs inside crash handlers; any failure here must never mask
            # the root-cause operator error being propagated)
            return None

    # -- static analysis (windflow_tpu/analysis) -----------------------------
    def check(self) -> list:
        """Pre-flight static analysis of the composed graph: abstract
        evaluation of every operator chain (``jax.eval_shape`` on the user
        kernels — zero device work), window-spec consistency, keyby/mesh
        shard-divisibility, and watermark-mode compatibility across
        merge/split points.  Returns the FULL list of
        :class:`~windflow_tpu.analysis.Diagnostic` findings (never just
        the first); ``start()`` runs it automatically under
        ``Config.preflight`` and ``tools/wf_check.py`` wraps it as a CLI."""
        from windflow_tpu.analysis.preflight import check_graph
        t0 = time.perf_counter()
        diags = check_graph(self)
        self._preflight_ms = round((time.perf_counter() - t0) * 1e3, 3)
        self._preflight_diags = diags
        return diags

    def _run_preflight(self) -> None:
        mode = self.config.preflight
        if mode not in ("error", "warn", "off"):
            raise WindFlowError(
                f"Config.preflight must be 'error', 'warn' or 'off', "
                f"got {mode!r}")
        if mode == "off":
            return
        import warnings
        from windflow_tpu.analysis.diagnostics import (PreflightError,
                                                       PreflightWarning)
        diags = self.check()
        errors = [d for d in diags if d.severity == "error"]
        for d in diags:
            if d.severity != "error" or mode == "warn":
                warnings.warn(str(d), PreflightWarning, stacklevel=3)
        if errors and mode == "error":
            raise PreflightError(errors)

    def start(self) -> None:
        if self._started:
            raise WindFlowError("PipeGraph already started")
        self._run_preflight()
        self._started = True
        self._build()
        if self._durability is not None and self._pending_restore is not None:
            # restore(): apply the checkpointed operator/replica state
            # now — replicas and fusion preludes exist, no source has
            # ticked, the monitor has not sampled
            pending, self._pending_restore = self._pending_restore, None
            self._durability.apply_restore(pending)
        try:
            if self.config.tracing_enabled:
                # reference: tracing spawns a MonitoringThread at run()
                # (pipegraph.hpp:676-678)
                from windflow_tpu.monitoring.monitor import MonitoringThread
                self._monitor = MonitoringThread(self)
                self._monitor.start()
            for sr in self._source_replicas:
                sr.start()
        except BaseException:
            # _build() created the (non-daemon) worker pool; a failing
            # monitor/source start must not leak its threads.  Streaming
            # deployments that drive step() directly instead of wait_end()
            # carry the same duty: call _finalize(dump=False) when
            # abandoning a started graph on error.
            self._finalize(dump=False)
            raise

    def step(self) -> bool:
        """One scheduler sweep: pull a chunk from each live source (unless
        backpressured), then drain every replica in topological order.
        Returns True on any progress."""
        rec = self._recorder
        if rec is None:
            return self._sweep()
        # the root of this thread's layer spans (monitoring/recorder.py):
        # every site below finds the recorder through it
        with rec.span("wf.sweep", sweep=next(rec.sweeps)):
            return self._sweep()

    def _tick(self, sr) -> bool:
        with flightrec.span("wf.source.tick"):
            return sr.tick(self._tick_chunk(sr))

    def _drain(self, rep, limit: int) -> bool:
        """``rep.drain`` under a ``wf.drain`` span: below ``wf.sweep`` on
        the driver thread, a root of its own on a pool thread."""
        rec = self._recorder
        if rec is None:
            return rep.drain(limit)
        with rec.span("wf.drain", op=rep.op.name):
            return rep.drain(limit)

    def _sweep(self) -> bool:
        progress = False
        throttled = self._backpressured()
        if throttled:
            # Source ticks are deferred this sweep: downstream inboxes are at
            # the in-transit cap (reference: allocateBatch_GPU_t blocks on
            # FullGPUMemoryException, recycling_gpu.hpp:88-126).  Draining
            # below continues, so the graph keeps moving.
            self._throttle_events += 1
        for sr in self._source_replicas:
            if not sr.exhausted and not throttled:
                if self._tick(sr):
                    progress = True
                # Cadence punctuation keeps watermarks advancing on idle
                # streams.  Skipped while throttled: a punctuation flushes
                # the emitter's open batch first (the watermark must never
                # overtake buffered data), which would ship a data batch
                # into inboxes already at the cap.  Under backpressure data
                # is in flight anyway, so watermarks advance with it.
                sr.maybe_punctuate()
        limit = self.config.sweep_drain_limit
        if self._pool is not None:
            # one task per replica-with-work: per-replica processing stays
            # serial (single consumer per inbox), cross-replica it runs on
            # the pool; the sweep barrier below keeps the topological
            # drain of the driver-thread replicas race-free
            futures = [self._pool.submit(self._drain, rep, limit)
                       for rep in self._pool_replicas if rep.inbox]
        for rep in self._main_replicas:
            if rep.inbox and self._drain(rep, limit):
                progress = True
        if self._pool is not None:
            for f in futures:
                if f.result():
                    progress = True
        for rep in self._columnar_sinks:
            # a columnar sink delivers a batch when the device reports its
            # step done, which no inbox announces: ask (after the pool
            # barrier, so a pooled sink is not drained beside this).  A
            # sink that holds nothing costs this one attribute check
            # (micro-asserted); one whose oldest batch is still running
            # opens no span.
            if rep._pending and rep.oldest_ready() \
                    and self._drain(rep, limit):
                progress = True
        # Staging-plane prefetch (Config.stage_prefetch_depth): the drain
        # above only DISPATCHED device work (JAX dispatch is async), so the
        # host is idle while the chip crunches — use it to pack batch N+1
        # into the recycled staging buffers now (windflow_tpu/staging),
        # the driver-loop form of the reference's 2-deep pinned double
        # buffering.  Each pass re-checks the in-transit caps, so
        # lookahead never overruns backpressure; punctuation cadence stays
        # with the main tick pass.
        for _ in range(max(0, self.config.stage_prefetch_depth)):
            if self._backpressured():
                break
            ticked = False
            for sr in self._source_replicas:
                if not sr.exhausted and self._tick(sr):
                    ticked = True
            if not ticked:
                break
            progress = True
            self._prefetch_ticks += 1
        if not progress:
            # Sources were deferred but nothing drained (e.g. limit=0 edge
            # cases): force one tick so the graph cannot deadlock on its own
            # throttle.
            for sr in self._source_replicas:
                if not sr.exhausted and self._tick(sr):
                    progress = True
        if self._durability is not None:
            # epoch cadence (windflow_tpu/durability): counts sweeps and,
            # every Config.durability_epoch_sweeps-th, quiesces to the
            # aligned barrier and commits a checkpoint epoch.  Off-path
            # cost is exactly this one check (micro-asserted).  Under an
            # active megastep plane one driver sweep covers K logical
            # batch sweeps and this call site sits BETWEEN driver
            # sweeps, so every quiesce already lands between megasteps;
            # round_epoch_to_megastep converted the configured cadence
            # to driver sweeps at build.
            self._durability.on_sweep()
        if self._reshard is not None:
            # executor cadence (windflow_tpu/serving): one counter
            # compare per sweep; every Config.reshard_check_sweeps-th
            # it reads health + the shard plan and applies what fires.
            self._reshard.on_sweep()
        return progress

    def _tick_chunk(self, sr) -> int:
        chunk = self.config.source_tick_chunk \
            or sr.op.output_batch_size or 256
        plane = self._megastep_plane
        if plane is not None and plane.active \
                and getattr(sr.emitter, "_megastep", None) is not None:
            # K-granular pacing: pull K batches' worth per tick so the
            # staging emitter fills a whole megastep group each sweep
            # instead of parking K-1 sweeps' batches in the queue
            chunk *= plane.k
        if self._reshard is not None:
            # admission control (docs/OBSERVABILITY.md "Reshard
            # executor"): when no plan can help a degraded operator,
            # the source intake throttles instead of growing inboxes
            chunk = self._reshard.admit_chunk(chunk)
        return chunk

    def _backpressured(self) -> bool:
        """True when any replica inbox is at the in-transit cap.  Also folds
        the high-water marks reported by :meth:`stats`.

        The ``inflight_device``/``inbox`` reads are deliberately lock-free:
        pool threads mutate them under the replica's inflight lock, but
        CPython guarantees tear-free reads, so throttling sees an at most
        one-sweep-stale value — the cap is a soft bound, not an invariant,
        and taking K locks per sweep would serialize the pool on its
        hottest path."""
        cfg = self.config
        hit = False
        for rep in self._all_replicas:
            depth = len(rep.inbox)
            if depth > self._max_inbox_seen:
                self._max_inbox_seen = depth
            if rep.inflight_device > self._max_inflight_device_seen:
                self._max_inflight_device_seen = rep.inflight_device
            if rep.inflight_device >= cfg.max_inflight_batches \
                    or depth >= cfg.max_inbox_messages:
                hit = True
        return hit

    def is_done(self) -> bool:
        return all(r.done for r in self._all_replicas)

    def restore(self, checkpoint_dir: Optional[str] = None) -> "PipeGraph":
        """Rebuild this composed-but-unstarted graph at the last complete
        checkpoint epoch (windflow_tpu/durability, docs/DURABILITY.md):
        validates the manifest's topology signature against the graph
        (WF602 named diff on mismatch), restores every operator's state
        — FFAT pane rings, stateful slot tables, reduce states — plus
        per-replica watermark frontiers, seeks Kafka sources back to the
        checkpointed offsets, and re-fences exactly-once sinks so the
        replay neither loses nor duplicates a record.  Returns the graph
        STARTED; drive it with :meth:`wait_end` (or :meth:`step`)."""
        from windflow_tpu.durability.checkpoint import restore_graph
        return restore_graph(self, checkpoint_dir)

    def _finalize(self, dump: bool = True, aborted: bool = False) -> None:
        if self._tenant is not None:
            # freeze this graph's attribution in the process tenant
            # ledger before teardown, so the tenant roll-up keeps its
            # history after the replicas are gone (guarded: shutdown
            # telemetry must never block shutdown)
            try:
                self._tenant.freeze()
            except Exception:  # lint: broad-except-ok (see above)
                pass
        if self._durability is not None:
            # flush + close the checkpoint store (counters stay readable:
            # stats() reads the cached section fields, not the KV)
            self._durability.close()
        if self._pool is not None:
            self._pool.shutdown(wait=True)
            self._pool = None
        if self._monitor is not None:
            # abnormal termination still ships a final report + END_APP
            # best-effort (the dashboard used to show crashed apps live
            # forever); the monitor marks the report Aborted
            self._monitor.stop(aborted=aborted)
            self._monitor = None
        if dump and self.config.tracing_enabled:
            self.dump_stats()

    # -- introspection (reference pipegraph.hpp:721-789) ---------------------
    def get_num_dropped_tuples(self) -> int:
        return sum(c.num_dropped for c in self._collectors) \
            + sum(op.num_dropped_tuples() for op in self._operators)

    def to_dot(self) -> str:
        """Graphviz DOT diagram of the graph (reference
        ``pipegraph.hpp:560-576``)."""
        from windflow_tpu.monitoring.diagram import to_dot
        return to_dot(self)

    def getNumDroppedTuples(self) -> int:
        """Reference-spelled alias of :meth:`get_num_dropped_tuples`
        (``pipegraph.hpp:786-789``)."""
        return self.get_num_dropped_tuples()

    # -- observability: gauges, latency, span traces -------------------------
    def sample_gauges(self) -> None:
        """Append one rolling-throughput sample.  The monitoring thread
        calls this once per second; ``stats()`` also samples so headless
        runs (no dashboard) still get the rolling gauges."""
        total = sum(r.stats.inputs_received for op in self._operators
                    if op.is_terminal for r in op.replicas)
        self._thr_samples.append((time.monotonic(), total))

    def health_tick(self) -> None:
        """One watchdog evaluation (monitoring/health.py).  The monitoring
        thread calls this on its cadence — and, like ``sample_gauges``,
        headless runs get the same tick from every ``stats()`` read.  With
        ``Config.health_watchdog`` off this is the whole cost: one check."""
        if self._latency is not None:
            # harvest + SLO evaluation BEFORE the watchdog samples, so
            # the health verdicts read this tick's decomposition (with
            # the ledger off this is the whole cost: one check)
            try:
                self._latency.tick()
            except Exception:  # lint: broad-except-ok (a telemetry
                # harvest must never take the watchdog down; the
                # Latency_plane section surfaces the error on read)
                pass
        if self._tenant is not None:
            # budget state machine tick BEFORE the watchdog samples, so
            # the health verdicts read this tick's OVER_BUDGET latch
            # (with the ledger off this is the whole cost: one check)
            try:
                self._tenant.tick()
            except Exception:  # lint: broad-except-ok (a telemetry
                # collect must never take the watchdog down; the Tenant
                # section surfaces the error on read)
                pass
        if self._roofline is not None:
            # roofline rate tick BEFORE the watchdog samples, so the
            # health verdicts read this tick's collapse latch (with the
            # plane off this is the whole cost: one check)
            try:
                self._roofline.tick()
            except Exception:  # lint: broad-except-ok (a telemetry
                # rate read must never take the watchdog down; the
                # Roofline section surfaces the error on read)
                pass
        if self._health is not None:
            self._health.sample()

    def _plane_section(self, plane, before: Optional[str] = None) -> dict:
        """The ``section()`` of one plane object (health, latency, tenant,
        roofline, durability, reshard, sweep, shard), guarded: a telemetry
        read must never take the pipeline or a stats dump down.  With the
        plane off this is the whole cost: one check.  ``before`` names
        what the plane runs first so that a headless ``stats()`` call
        sees current numbers without a monitor thread (the latency
        ledger's ``harvest``, the roofline's ``tick``)."""
        if plane is None:
            return {"enabled": False}
        try:
            if before is not None:
                getattr(plane, before)()
            return plane.section()
        except Exception as e:  # lint: broad-except-ok (the planes walk
            # registry snapshots, device sketch states and abstract specs
            # at stats cadence — telemetry degrades, the report still
            # ships)
            return _section_error(e)

    def _ir_audit_section(self) -> dict:
        """wfir (analysis/ir_audit.py): WF9xx findings over the lowered
        StableHLO of this graph's compiled programs.  Re-audits the
        compile watcher's program store at read cadence (cold path, no
        compiles); guarded like ``_plane_section``.  With
        ``Config.ir_audit`` off (or ``WF_TPU_IR_AUDIT=0``) this is the
        whole cost: one check."""
        try:
            from windflow_tpu.analysis import ir_audit
            if not ir_audit.enabled(self.config):
                return {"enabled": False}
            report = ir_audit.audit_graph(self, dry_lower=False)
            self._ir_audit_report = report
            out = {"enabled": True}
            out.update(report.to_json())
            return out
        except Exception as e:  # lint: broad-except-ok (the auditor
            # parses backend-emitted IR text at stats cadence —
            # telemetry degrades, the report still ships)
            return _section_error(e)

    def _rolling_rate(self, window_s: float) -> float:
        """Sunk-tuples/sec over (at least) the trailing ``window_s``: the
        delta between the newest sample and the youngest sample that is at
        least ``window_s`` old (the whole retained window when none is)."""
        if len(self._thr_samples) < 2:
            return 0.0
        now_t, now_v = self._thr_samples[-1]
        base = None
        for t, v in self._thr_samples:
            if now_t - t >= window_s:
                base = (t, v)      # samples are time-ordered: keep the
            else:                  # youngest one old enough
                break
        if base is None:
            base = self._thr_samples[0]
        dt = now_t - base[0]
        return (now_v - base[1]) / dt if dt > 0 else 0.0

    def op_frontier_and_depth(self, op) -> tuple:
        """``(summed inbox depth, watermark frontier)`` for one operator.
        Frontier = MIN over replicas (watermark semantics): the lag gauge
        and the health watchdog must surface a stalled replica, not hide
        it behind its most-advanced sibling.  Shared by :meth:`gauges`
        and the health plane's stall detection so the two can never
        drift."""
        from windflow_tpu.batch import WM_MAX, WM_NONE
        depth = 0
        fronts = []
        for rep in op.replicas:
            depth += len(rep.inbox)
            wm = rep.current_wm
            if wm != WM_NONE and wm < WM_MAX:
                fronts.append(wm)
        return depth, (min(fronts) if fronts else None)

    def gauges(self) -> dict:
        """Point-in-time gauges (sampled by the monitoring thread into the
        NEW_REPORT payload): per-operator watermark lag (wall clock minus
        frontier — meaningful under INGRESS/wall-based EVENT time) and
        inbox queue depth, staging-pool occupancy, rolling throughput."""
        from windflow_tpu import staging
        now = current_time_usecs()
        per_op = {}
        for op in self._operators:
            depth, front = self.op_frontier_and_depth(op)
            per_op[op.name] = {
                "queue_depth": depth,
                "watermark_frontier_usec": front,
                "watermark_lag_usec":
                    max(0, now - front) if front is not None else None,
            }
        pool = staging.default_pool()
        return {
            "sampled_at_usec": now,
            "operators": per_op,
            "staging_pool_held_bytes": pool.stats()["held_bytes"],
            "throughput_1s_tps": round(self._rolling_rate(1.0), 1),
            "throughput_10s_tps": round(self._rolling_rate(10.0), 1),
        }

    def _latency_section(self) -> dict:
        """Per-operator service-span and end-to-end staged→sunk latency
        distributions (p50/p95/p99), merged across replicas from the
        log-bucketed histograms (monitoring/recorder.py)."""
        from windflow_tpu.monitoring.recorder import LatencyHistogram
        per_op = {}
        e2e = LatencyHistogram()
        for op in self._operators:
            h = LatencyHistogram()
            for rep in op.replicas:
                h.merge(rep.stats.service_hist)
                e2e.merge(rep.stats.e2e_hist)   # nonzero only at sinks
            per_op[op.name] = h.quantiles()
        return {"service_usec_per_operator": per_op,
                "end_to_end_usec": e2e.quantiles()}

    def profile(self, duration_ms: float = 1000.0,
                log_dir: Optional[str] = None) -> str:
        """Profiler bridge: capture a ``jax.profiler`` device trace while
        driving the started graph for ``duration_ms`` (or until it
        finishes).  The capture lands in ``log_dir`` /
        ``Config.profiler_dir`` (default ``{log_dir}/{name}_xprof``) as a
        TensorBoard/Perfetto ``plugins/profile`` directory.  With the
        flight recorder on, the layer spans (``wf.sweep``, ``wf.parse``,
        ``wf.dispatch`` with ``op=`` and ``batch=``, ...:
        monitoring/recorder.py) are inside that capture, on the host
        plane and on the device lines' clock.  Returns the capture
        directory."""
        if not self._started:
            raise WindFlowError("profile() needs a started graph — call "
                                "start() first (run() profiles nothing: "
                                "it returns only when the graph is done)")
        import jax.profiler
        d = log_dir or self.config.profiler_dir \
            or os.path.join(self.config.log_dir, f"{self.name}_xprof")
        os.makedirs(d, exist_ok=True)
        jax.profiler.start_trace(d)
        try:
            deadline = time.monotonic() + duration_ms / 1e3
            while time.monotonic() < deadline and not self.is_done():
                if not self.step():
                    break
        finally:
            jax.profiler.stop_trace()
        return d

    def dump_trace(self, path: Optional[str] = None) -> str:
        """Write the flight recorder's span events as Chrome-trace JSON
        (``{name}_trace.json`` under ``Config.log_dir``), loadable in
        ``chrome://tracing`` / Perfetto (``otherData`` carries the ledger
        sections and the per-layer span table); the raw events ride along as
        ``{name}_events.json`` for offline re-export through
        ``tools/trace_export.py``.  Returns the trace path."""
        if self._recorder is None:
            raise WindFlowError(
                "flight recorder is off (Config.flight_recorder) or the "
                "graph has not been built — nothing to dump")
        from windflow_tpu.monitoring.recorder import write_chrome_trace
        d = self.config.log_dir
        os.makedirs(d, exist_ok=True)
        path = path or os.path.join(d, f"{self.name}_trace.json")
        events = self._recorder.events()
        write_chrome_trace(events, path, metadata={
            # what the host did at each layer boundary over the run
            # (count, total and self time per span name)
            "layers": self._recorder.layers(),
            # sweep-ledger cross-reference: per-hop dispatch counts and
            # attributed HBM bytes for the spans in this trace
            "sweep": self._plane_section(self._ledger),
            # shard-plane cross-reference: per-shard load + hot keys for
            # the operators whose spans this trace carries
            "shard": self._plane_section(self._shard),
            # tenant-plane cross-reference: which tenant this graph's
            # spans bill to, and the process tenant roll-up at dump time
            "tenant": self._plane_section(self._tenant),
            # calibration cross-reference: where every modeled constant
            # behind the trace's derived numbers currently comes from
            # (measured/modeled/calibrated provenance + store age)
            "calibration": _calibration_summary(),
        })
        root, ext = os.path.splitext(path)
        base = root[:-len("_trace")] if root.endswith("_trace") else root
        with open(f"{base}_events{ext or '.json'}", "w") as f:
            json.dump(events, f)
        return path

    def stats(self) -> dict:
        """Stats report; schema follows the reference's dashboard JSON
        (``pipegraph.hpp:468-526``).  The fixed reference fields describe the
        FastFlow runtime; here they describe the host driver equivalents."""
        self.sample_gauges()
        if self._fused_segments:
            # per-op stats for fused members are attributed from the
            # fused hop at read cadence (never on the batch path)
            from windflow_tpu.fusion import attribute_member_stats
            attribute_member_stats(self)
        return {
            "PipeGraph_name": self.name,
            "Mode": self.mode.value,
            # in-transit batch throttling (see _backpressured): source ticks
            # are deferred while any inbox is at the cap
            "Backpressure": f"ON (max_inflight_batches="
                            f"{self.config.max_inflight_batches}, "
                            f"max_inbox_messages="
                            f"{self.config.max_inbox_messages})",
            "Backpressure_throttle_events": self._throttle_events,
            "Max_inbox_depth_seen": self._max_inbox_seen,
            "Max_inflight_device_batches_seen":
                self._max_inflight_device_seen,
            "Non_blocking": "ON",     # async XLA dispatch
            "Thread_pinning": "OFF",  # driver loop + pool, no pinning
            "Host_worker_threads": self.config.host_worker_threads,
            # staging plane (windflow_tpu/staging): host-buffer recycling
            # pool counters + lookahead tick count
            "Staging_pool": _staging_pool_stats(),
            # wire plane (windflow_tpu/wire.py): per-lane codec table +
            # wire-vs-logical byte counters of this graph's staging
            # emitters (docs/OBSERVABILITY.md "Wire plane")
            "Staging": {"Wire": self._wire_section(),
                        **self._staged_counts()},
            "Stage_prefetch_depth": self.config.stage_prefetch_depth,
            "Stage_prefetch_ticks": self._prefetch_ticks,
            "Dropped_tuples": self.get_num_dropped_tuples(),
            "Operator_number": len(self._operators),
            "Thread_number": 1 + self.config.host_worker_threads
                               + (1 if self._monitor is not None else 0),
            "rss_size_kb": _rss_kb(),
            # graph-level transfer totals (reference per-replica H2D/D2H
            # counters, stats_record.hpp:152-160, summed here).
            # Bytes_H2D_total is the WIRE total (bytes actually moved);
            # the logical total is what the decoded lanes occupy — the
            # two diverge exactly by the wire plane's compression, and
            # equating them would let compression silently inflate every
            # bytes-derived ratio (wire-round honesty fix)
            "Bytes_H2D_total": sum(r.stats.h2d_bytes
                                   for r in self._all_replicas),
            "Bytes_H2D_logical_total": sum(r.stats.h2d_logical_bytes
                                           for r in self._all_replicas),
            "Bytes_D2H_total": sum(r.stats.d2h_bytes
                                   for r in self._all_replicas),
            # flight-recorder layer (monitoring/recorder.py): latency
            # distributions + point-in-time gauges, shipped to the
            # dashboard in every NEW_REPORT
            "Flight_recorder": (self._recorder.summary()
                                if self._recorder is not None
                                else {"enabled": False}),
            # host time per layer span (count, total_ns, self_ns by span
            # name; wait_ns on wf.sweep: blocked on the chip): what a
            # profiler capture shows per event, summed over the run;
            # empty with the recorder off
            "Layers": (self._recorder.layers()
                       if self._recorder is not None else {}),
            # pre-flight analysis (windflow_tpu/analysis): check() cost +
            # finding counts, so preflight stays visible in every dump
            "Preflight": {
                "mode": self.config.preflight,
                "check_ms": self._preflight_ms,
                "diagnostics": (None if self._preflight_diags is None
                                else [str(d) for d in
                                      self._preflight_diags]),
            },
            "Latency": self._latency_section(),
            # latency ledger (monitoring/latency_ledger.py): per-batch
            # critical-path segment decomposition, window freshness,
            # and the SLO verdict
            "Latency_plane": self._plane_section(self._latency, "harvest"),
            # tenant plane (monitoring/tenant_ledger.py): per-tenant
            # HBM/ICI/dispatch attribution + budget verdicts across
            # every PipeGraph in the process — what the tenant advisor
            # (analysis/tenancy.py, tools/wf_tenant.py) plans against
            "Tenant": self._plane_section(self._tenant),
            # roofline plane (monitoring/calibration.RooflineLedger):
            # per-hop achieved tup/s vs the calibrated bandwidth
            # ceiling, with measured/modeled/calibrated provenance on
            # every column and the latched ROOFLINE_DEGRADED verdict —
            # docs/OBSERVABILITY.md "Calibration plane"
            "Roofline": self._plane_section(self._roofline, "tick"),
            "Gauges": self.gauges(),
            # health plane (monitoring/health.py): per-operator watchdog
            # verdicts, stall counters + attribution, verdict timeline
            "Health": self._plane_section(self._health),
            # device plane (monitoring/device_metrics.py): compile-watcher
            # per-op table, HBM/live-buffer gauges, staging-attributed
            # device bytes — the ``"Device"`` half of the telemetry story
            "Device": self._device_section(),
            # sweep ledger (monitoring/sweep_ledger.py): per-hop jitted
            # dispatches + XLA-cost HBM bytes per staged batch, donation
            # misses, hop-boundary residency — the attribution layer the
            # fusion advisor (tools/wf_advisor.py) plans against
            "Sweep": self._plane_section(self._ledger),
            # shard plane (monitoring/shard_ledger.py): per-shard queue/
            # lag/latency/HBM attribution, key-skew sketches on keyed
            # edges, mesh ICI model — the measurement layer the reshard
            # advisor (tools/wf_shard.py) plans against
            "Shard": self._plane_section(self._shard),
            # wfir (analysis/ir_audit.py): WF9xx audit of the lowered
            # StableHLO of this graph's compiled programs — collectives,
            # callbacks, donation aliasing, Pallas lowering proven on
            # the IR the chip actually runs (docs/ANALYSIS.md "wfir")
            "IR_audit": self._ir_audit_section(),
            # megastep plane (windflow_tpu/megastep.py): resolved K and
            # per-edge megastep/fallback counters — docs/OBSERVABILITY.md
            # "Megastep in the ledger"
            "Megastep": (self._megastep_plane.summary()
                         if self._megastep_plane is not None
                         else {"k": 1, "edges": []}),
            # durability plane (windflow_tpu/durability): epochs
            # committed, checkpoint/restore wall cost + bytes, sink
            # fence dedupe hits — docs/DURABILITY.md
            "Durability": self._plane_section(self._durability),
            # reshard executor (windflow_tpu/serving): plans applied,
            # keys moved, quiesce/recovery wall cost, admission factor,
            # action timeline — docs/OBSERVABILITY.md
            "Reshard": self._plane_section(self._reshard),
            "Operators": [op.dump_stats() for op in self._operators],
        }

    def _staged_counts(self) -> dict:
        """What the staging edges shipped, counted where each batch is
        cut: ``tuples / capacity`` is the fill share, ``partial_batches``
        the ones a punctuation, a lane change or the end of stream
        flushed short.  ``parsed_in_place_tuples`` are the rows a source
        wrote into the staging buffer itself (the one-pass frame parse),
        counted where they are written: against ``tuples`` it says how
        often that route engaged."""
        from windflow_tpu.wire import iter_stage_emitters
        ems = [em for _src, _route, em in iter_stage_emitters(self)]
        return {"batches": sum(e.staged_batches for e in ems),
                "partial_batches": sum(e.partial_batches for e in ems),
                "tuples": sum(e.staged_tuples for e in ems),
                "parsed_in_place_tuples": sum(e.parsed_in_place_tuples
                                              for e in ems),
                "capacity": sum(e.staged_batches * e._local_cap
                                for e in ems)}

    def _wire_section(self) -> dict:
        """Guarded like every other plane section; with
        ``Config.wire_compression`` off the emitters carry no encoders
        and the section reports enabled=False with zero counters."""
        try:
            from windflow_tpu.wire import wire_section
            return wire_section(self)
        except Exception as e:  # lint: broad-except-ok (a telemetry
            # read must never take the pipeline or a stats dump down —
            # same stance as every other plane section)
            return {"enabled": None, "error": f"{type(e).__name__}: "
                                              f"{e}"[:200]}

    def _device_section(self) -> dict:
        """Guarded: a metrics read must never take the pipeline down
        (same stance as the monitoring thread's quiet switch-off)."""
        from windflow_tpu.monitoring import device_metrics
        try:
            return device_metrics.device_section(self)
        except Exception as e:  # lint: broad-except-ok (backend probes —
            # memory_stats/live_arrays — may fail arbitrarily on exotic
            # runtimes; telemetry degrades, the report still ships)
            return {"error": f"{type(e).__name__}: {e}"[:200]}

    def dump_stats(self, log_dir: Optional[str] = None) -> str:
        d = log_dir or self.config.log_dir
        os.makedirs(d, exist_ok=True)
        path = os.path.join(d, f"{self.name}_stats.json")
        with open(path, "w") as f:
            json.dump(self.stats(), f, indent=2)
        return path

    def dump_postmortem(self, dir: Optional[str] = None,
                        reason: str = "manual") -> str:
        """Black-box postmortem bundle: flight-recorder rings, the last
        ``stats()``, health verdict timeline + stall attribution, jit and
        device tables, the sweep ledger's per-hop dispatch/HBM
        attribution, preflight findings — written as one directory of
        JSON files that ``tools/wf_doctor.py`` renders and validates with
        no jax installed.  Every section is individually guarded (section
        failures land in the manifest's ``errors`` map, they never abort
        the bundle): the crash path calls this exactly when parts of the
        telemetry may be broken.  Returns the bundle directory."""
        with self._postmortem_lock:
            return self._dump_postmortem_locked(dir, reason)

    def _dump_postmortem_locked(self, dir: Optional[str],
                                reason: str) -> str:
        # suppress the watchdog auto-bundle on THIS thread for the
        # duration of the write: the stats section below re-enters
        # HealthPlane.sample(), and an auto-bundle fired from there
        # would re-enter this non-reentrant lock and deadlock inside a
        # crash handler.  Thread-scoped suppression only — a manual
        # snapshot must not consume the once-per-graph auto-bundle, and
        # another thread's concurrent auto-bundle just serializes behind
        # the lock.
        if self._health is not None:
            self._health._bundle_thread = threading.get_ident()
        try:
            return self._dump_postmortem_impl(dir, reason)
        finally:
            if self._health is not None:
                self._health._bundle_thread = None

    def _dump_postmortem_impl(self, dir: Optional[str],
                              reason: str) -> str:
        d = dir or self.config.health_postmortem_dir \
            or os.path.join(self.config.log_dir, f"{self.name}_postmortem")
        os.makedirs(d, exist_ok=True)
        files: List[str] = []
        errors: dict = {}

        def write(name: str, build, *args) -> None:
            try:
                obj = build(*args)
                with open(os.path.join(d, name), "w") as f:
                    json.dump(obj, f, indent=1, default=str)
                files.append(name)
            except Exception as e:  # lint: broad-except-ok (postmortem
                # sections must degrade independently — a dead backend
                # breaking stats() must not lose the rings or verdicts)
                errors[name] = f"{type(e).__name__}: {e}"[:300]

        write("stats.json", self.stats)
        write("events.json",
              lambda: self._recorder.events()
              if self._recorder is not None else [])
        write("health.json",
              lambda: self._health.section(sample_first=False)
              if self._health is not None else {"enabled": False})
        write("device.json", self._device_section)

        def jit_tables():
            from windflow_tpu.monitoring.jit_registry import \
                default_registry
            reg = default_registry()
            return {"jit": reg.snapshot(), "totals": reg.totals()}
        write("jit.json", jit_tables)
        write("sweep.json", self._plane_section, self._ledger)
        write("shard.json", self._plane_section, self._shard)
        write("ir_audit.json", self._ir_audit_section)
        write("latency.json", self._plane_section, self._latency,
              "harvest")
        write("tenant.json", self._plane_section, self._tenant)
        write("roofline.json", self._plane_section, self._roofline,
              "tick")
        write("calibration.json", _calibration_summary)
        write("durability.json", self._plane_section, self._durability)
        write("reshard.json", self._plane_section, self._reshard)
        write("preflight.json", lambda: {
            "mode": self.config.preflight,
            "check_ms": self._preflight_ms,
            "diagnostics": (None if self._preflight_diags is None
                            else [str(dg) for dg in self._preflight_diags]),
        })
        from windflow_tpu.monitoring.health import POSTMORTEM_SCHEMA
        manifest = {
            "schema": POSTMORTEM_SCHEMA,
            "app": self.name,
            "reason": reason,
            "written_at_usec": current_time_usecs(),
            "files": files,
            "errors": errors,
        }
        with open(os.path.join(d, "manifest.json"), "w") as f:
            json.dump(manifest, f, indent=1)
        self._postmortem_dir = d
        return d
