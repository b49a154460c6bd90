"""Operator and replica base classes.

Re-design of the reference's ``Basic_Operator`` / ``Basic_Replica``
(``/root/reference/wf/basic_operator.hpp:54-235,246-381``).  The structural
difference is the execution vehicle: a reference replica is a FastFlow node
with its own OS thread (``svc()`` called by the runtime); here a replica is a
plain object whose ``drain()`` is called by the host driver's cooperative
scheduler (graph/pipegraph.py).  On TPU the heavy lifting happens inside
compiled XLA programs, so dedicating host threads per replica buys nothing —
one dispatch loop keeps the chip fed (SURVEY.md §7 design stance).

End-of-stream follows the reference protocol (``eosnotify`` cascade,
``basic_operator.hpp:180-189``): an EOS punctuation per input channel; when
all channels have delivered EOS, the replica flushes operator state, flushes
its emitter, forwards EOS, and terminates.
"""

from __future__ import annotations

import copy
import threading
from collections import deque
from typing import Any, Callable, List, Optional

from windflow_tpu.analysis import debug_concurrency as _dbg
from windflow_tpu.basic import (ExecutionMode, RoutingMode, TimePolicy,
                                WindFlowError, current_time_usecs,
                                default_config)
from windflow_tpu.batch import DeviceBatch, HostBatch, Punctuation, WM_MAX, WM_NONE
from windflow_tpu.context import RuntimeContext
from windflow_tpu.monitoring import recorder as flightrec
from windflow_tpu.monitoring.stats import StatsRecord
from windflow_tpu.parallel.collectors import Collector
from windflow_tpu.parallel.emitters import Emitter


class Replica:
    """One logical replica of an operator (reference ``Basic_Replica``)."""

    #: replicas whose user function may mutate its input copy shared
    #: (multicast) tuples before processing (reference ``copyOnWrite``,
    #: ``map.hpp:57-215``)
    copy_on_shared = False

    #: lock discipline declaration enforced by tools/wf_lint.py (WF721):
    #: the in-transit device-batch counter mutates only under its lock
    #: (deliberately lock-free READS live in PipeGraph._backpressured —
    #: the discipline covers this class's own accesses)
    __lock_guards__ = {"_inflight_lock": ("inflight_device",)}

    def __init__(self, op: "Operator", index: int) -> None:
        self.op = op
        self.index = index
        self.context = RuntimeContext(op.parallelism, index, op.name)
        self.inbox: deque = deque()
        #: outstanding device batches in this inbox — the per-operator
        #: in-transit count the host driver throttles against (reference
        #: ``inTransit_counter``, ``recycling_gpu.hpp:88-126``).  Guarded
        #: by a lock: with the host worker pool several producer replicas
        #: may stage batches into this inbox concurrently (deque appends
        #: are atomic; the int += is not).
        self.inflight_device = 0
        self._inflight_lock = threading.Lock()
        self.collector: Optional[Collector] = None  # wired by the graph
        self.emitter: Optional[Emitter] = None      # wired by the graph
        self.config = default_config                # PipeGraph overrides
        self.num_channels = 0
        self._eos_channels = set()
        self.done = False
        self.current_wm = WM_NONE
        self._hooked_wm = WM_NONE   # last watermark passed to on_watermark
        self.stats = StatsRecord(operator_name=op.name, replica_index=index,
                                 is_tpu=op.is_tpu)
        #: flight-recorder span ring (monitoring/recorder.py), bound by
        #: PipeGraph._build when Config.flight_recorder is on; None leaves
        #: a single `is not None` check as the hot path's whole cost
        self.ring = None
        self._traced_seen = 0   # traced batches seen (device_done cadence)
        #: latency ledger (monitoring/latency_ledger.py), bound by
        #: PipeGraph._build on WINDOW replicas only when
        #: Config.latency_ledger is on; None leaves one `is not None`
        #: check at the sampled-sync site as the whole cost
        self.latency = None
        self.mode = ExecutionMode.DEFAULT
        self.time_policy = TimePolicy.INGRESS
        #: origin id of the input currently being processed (HostBatch.ids);
        #: one-to-one/one-to-many relays pass it to their emits so
        #: DETERMINISTIC ordering can break timestamp ties
        #: config-independently (reference Single_t id)
        self.cur_tid = None

    # -- wiring -------------------------------------------------------------
    def add_channel(self) -> int:
        ch = self.num_channels
        self.num_channels += 1
        return ch

    # -- runtime ------------------------------------------------------------
    def receive(self, channel: int, msg) -> None:
        self.inbox.append((channel, msg))
        if isinstance(msg, DeviceBatch):
            with self._inflight_lock:
                self.inflight_device += 1

    def drain(self, limit: int = 0) -> bool:
        """Process pending inbox messages (at most ``limit`` when > 0; the
        driver bounds per-sweep work so sibling replicas interleave fairly,
        approximating the reference's thread-parallel arrival order).
        Returns True if any progress was made."""
        if _dbg.ENABLED:
            # single-consumer contract: the driver/pool schedules at most
            # one drain per replica at a time (the sweep barrier); a
            # second thread draining concurrently is a scheduler race
            with _dbg.entry_guard(self, "Replica.drain"):
                return self._drain_impl(limit)
        return self._drain_impl(limit)

    def _drain_impl(self, limit: int) -> bool:
        progressed = False
        n = 0
        while self.inbox:
            if limit and n >= limit:
                break
            n += 1
            channel, msg = self.inbox.popleft()
            if isinstance(msg, DeviceBatch):
                with self._inflight_lock:
                    self.inflight_device -= 1
            progressed = True
            if isinstance(msg, Punctuation) and msg.is_eos:
                self._handle_channel_eos(channel)
                continue
            for ready in self.collector.on_message(channel, msg):
                self._dispatch(ready)
        return progressed

    def _handle_channel_eos(self, channel: int) -> None:
        if channel in self._eos_channels:
            return
        self._eos_channels.add(channel)
        for ready in self.collector.on_channel_eos(channel):
            self._dispatch(ready)
        if len(self._eos_channels) == self.num_channels:
            self._terminate()

    def _terminate(self) -> None:
        if self.done:
            return
        self.on_eos()
        if self.emitter is not None:
            self.emitter.flush(self.current_wm)
            self.emitter.propagate_punctuation(WM_MAX)
        cf = self.op.closing_func
        if cf is not None:
            # per-replica shutdown callback (reference closing_func run in
            # svc_end with the replica's RuntimeContext, map.hpp:79-81);
            # adapt() swallows the context for non-riched closers
            from windflow_tpu.meta import adapt
            adapt(cf, 0)(self.context)
        self.done = True
        self.stats.is_terminated = True

    def _dispatch(self, msg) -> None:
        if _dbg.ENABLED:
            # the stats sample bracket (start_sample enters a debug guard,
            # end_sample exits it) spans this whole method; an operator
            # raising mid-processing must not leave a stale guard entry
            # that would false-positive a later, unrelated access
            try:
                return self._dispatch_impl(msg)
            finally:
                _dbg.exit_(self.stats)
        return self._dispatch_impl(msg)

    def _dispatch_impl(self, msg) -> None:
        if isinstance(msg, Punctuation):
            self._advance_wm(msg.watermark)
            self._maybe_hook_wm()
            if self.emitter is not None:
                self.emitter.propagate_punctuation(self.current_wm)
            return
        # flight recorder (monitoring/recorder.py): span events for the
        # 1-in-N traced batch; untraced batches cost one attribute check
        tr = msg.trace if self.ring is not None else None
        if tr is not None:
            self.ring.record(tr[0], flightrec.COLLECTED,
                             current_time_usecs())
        self.stats.start_sample()
        if isinstance(msg, DeviceBatch):
            self._advance_wm(msg.watermark)
            self.stats.inputs_received += msg.known_size or 0
            self.process_device_batch(msg)
        else:
            assert isinstance(msg, HostBatch)
            self._advance_wm(msg.watermark)
            self.stats.inputs_received += len(msg)
            # Copy-on-write: a multicast batch is shared by sibling replicas;
            # an in-place-capable operator must mutate a private copy
            # (reference ``copyOnWrite``, ``map.hpp:57-215``).
            cow = msg.shared and self.copy_on_shared
            for item, ts, tid in zip(msg.items, msg.tss,
                                     msg.ids_or_nones()):
                if cow:
                    item = copy.deepcopy(item)
                self.cur_tid = tid
                self.context._set_context(ts, msg.watermark)
                self.process_single(item, ts, msg.watermark)
            self.cur_tid = None
        self._maybe_hook_wm()
        self.stats.end_sample()
        if tr is not None and self.op.is_terminal:
            # staged→sunk span closes at sink RECEIPT (a columnar sink
            # delivers when the device reports the batch done, up to
            # ``defer`` batches later; that is in the benchmark's delivery
            # latency, not in this histogram)
            now = current_time_usecs()
            self.ring.record(tr[0], flightrec.SUNK, now)
            self.stats.e2e_hist.add(now - tr[1])

    def _maybe_hook_wm(self) -> None:
        # only invoke the (potentially O(open windows)) hook on a real advance
        if self.current_wm != self._hooked_wm:
            self._hooked_wm = self.current_wm
            self.on_watermark(self.current_wm)

    def _advance_wm(self, wm: int) -> None:
        if wm != WM_NONE and wm > self.current_wm:
            self.current_wm = wm

    # -- operator logic (overridden by concrete replicas) --------------------
    def process_single(self, item: Any, ts: int, wm: int) -> None:
        raise WindFlowError(
            f"operator '{self.op.name}' cannot consume host tuples")

    def process_device_batch(self, batch: DeviceBatch) -> None:
        raise WindFlowError(
            f"operator '{self.op.name}' cannot consume device batches; "
            "insert a host stage or mark the upstream edge for staging")

    def on_eos(self) -> None:
        """Flush hook: window firing, sink finalization, etc."""

    def on_watermark(self, wm: int) -> None:
        """Watermark-advance hook (fires time windows past the frontier)."""


class Operator:
    """Descriptor for one operator in the graph (reference
    ``Basic_Operator``): name, parallelism, input routing mode, output batch
    size, and whether its compute runs on TPU."""

    #: subclasses set this to their replica class
    replica_class = Replica
    #: terminal operators (sinks) have no emitter / downstream consumer
    is_terminal = False
    #: stable topological index assigned by PipeGraph._build; origin-id
    #: prefix for source stamping
    ordinal = 0
    #: per-replica shutdown callback, set by withClosingFunction (reference
    #: ``closing_func``: every operator builder accepts one); invoked at
    #: replica termination with the replica's RuntimeContext (arity 1) or
    #: no arguments (arity 0)
    closing_func = None
    #: host operators whose replicas may be drained concurrently by the
    #: host worker pool (Config.host_worker_threads); operators with
    #: cross-replica shared mutable state (e.g. a shared persistent DB
    #: handle) clear this to stay on the driver thread
    host_pool_safe = True
    #: non-None for device operators whose compiled state layout is tied to
    #: ONE batch capacity (FfatWindowsTPU pane state, stateful slot tables,
    #: dense-key mesh reduce tables): PipeGraph rejects merged upstream
    #: paths delivering unequal capacities at BUILD time (parity:
    #: ``multipipe.hpp:441-444`` rejects bad GPU predecessors at build).
    #: The value is the label used in the error message.
    fixed_capacity_label = None
    #: True on device operators whose output batch is sized by what a
    #: step can fire or close rather than by its input: their
    #: ``wf.dispatch`` span says ``out_cap`` even where the two agree
    notes_out_cap = False
    #: lanes the compiled step of ONE key shard runs over, where a mesh
    #: step compacts the batch to the lanes a shard owns (set when the
    #: step is built): ``step_cap`` on the ``wf.dispatch`` span
    step_cap = None
    #: on a windowing device operator (the count / time window, the
    #: count window in event-time order, the session window, the interval
    #: join), which window stage of its pipeline it is: 1 for the first,
    #: n + 1 where it is fed, through whatever operators, by a stage-n
    #: one's rows (set by the graph build, ``windows/ffat_tpu.
    #: number_window_stages``); None on every other operator.  A stage
    #: past the first says so on its ``wf.dispatch`` span (``stage``,
    #: and ``_stage_notes``)
    window_stage = None
    #: ``"arrival"`` / ``"event_time"`` on a count window: the order in
    #: which it counts a key's rows.  ``rows_follow_data``: True on a
    #: device operator whose rows close where the DATA says and leave a
    #: step compacted in the order of its own sort (by key), some held
    #: back a step: not the order of their timestamps.  Preflight WF609
    #: names a count window in arrival order fed by one
    count_order = None
    rows_follow_data = False
    #: whole-chain fusion (windflow_tpu/fusion): non-None on the MEMBER
    #: operators of a fused segment — the name of the fused hop their
    #: execution folded into.  Member replicas are inert (wired with no
    #: channels, marked done at build); stats are attributed from the
    #: fused hop (fusion/executor.attribute_member_stats).
    _fused_into = None
    #: fused-segment HOST hooks: the stateless members' combined record
    #: transform, inlined at program-build time by stateful tails
    #: (ffat_tpu._build_step, ReduceTPU._get_step/_get_dense_step,
    #: tpu_stateful._get_step); the fused program's registry name; and
    #: whether the graph proved the input batch buffers unshared so the
    #: program may take them with donate_argnums
    #: (fusion/executor.input_donation_safe).
    _fused_prelude = None
    _fused_name = None
    _fused_donate_inputs = False
    #: all-stateless fused segments have no tail program to extend: the
    #: host op carries a FusedStatelessExec instead, dispatched through
    #: _TPUReplica._op_step (one attribute check per batch).
    _fusion_exec = None
    #: what it is to a fused chain (analysis/fusion.py links, fusion/
    #: executor.py trims): ``"member"`` is a stateless record transform
    #: the chain inlines (its specs: ``ops/chained._tpu_specs``);
    #: ``"tail"`` can be a chain's LAST operator and never an inner one:
    #: its output is a different stream (window results, reduced
    #: batches), so fusing PAST it changes the program contract, not
    #: just its launch count.  None takes no part in a chain at all.
    chain_role = None
    #: True on device operators whose replicas fire windows: the graph
    #: binds the latency ledger to them for the fire-freshness gauge at
    #: their sampled-sync site (``_TPUReplica``); the rest keep
    #: ``latency = None`` (one check)
    reports_fire_freshness = False
    #: the ``kind`` of the blob ``snapshot_state`` writes (checkpoints on
    #: disk carry the string), stated by the class that implements
    #: ``snapshot_state`` or by the subclasses that share one
    #: implementation.  None, or a ``snapshot_state`` overridden BELOW
    #: the class that states the kind: a blob nobody can re-bucket —
    #: preflight says WF604 on a mesh, a rescaling restore refuses with
    #: WF605 (durability/rebucket.py, which holds a rule for each kind
    #: whose state has a shard shape)
    snapshot_kind = None
    #: True where the checkpointed state has no shard shape to change
    #: (one shared table, or one replica on one chip): a restore onto
    #: another shape takes the blob as it is, and the kind needs no rule
    snapshot_shapeless = False
    #: device-side key compaction (parallel/compaction.py): non-None on
    #: keyed consumers the graph build attached a KeyCompactor to —
    #: their step resolves arbitrary int32 keys to dense slots through
    #: the device-resident remap table.  None (Config.key_compaction
    #: off, or a non-qualifying consumer) leaves exactly one
    #: `is not None` check on the step path.
    _compactor = None

    def __init__(self, name: str, parallelism: int,
                 routing: RoutingMode = RoutingMode.FORWARD,
                 output_batch_size: int = 0,
                 is_tpu: bool = False,
                 key_extractor: Optional[Callable] = None) -> None:
        if parallelism < 1:
            raise WindFlowError(
                f"operator '{name}' must have parallelism >= 1")
        self.name = name
        self.parallelism = parallelism
        self.routing = routing
        self.output_batch_size = output_batch_size
        self.is_tpu = is_tpu
        self.key_extractor = key_extractor
        self.replicas: List[Replica] = []
        #: jax Mesh for multi-chip execution; set by PipeGraph._build from
        #: Config.mesh.  Mesh-aware operators compile sharded programs when
        #: this is not None (parallel/mesh.py).
        self.mesh = None

    @property
    def is_keyed(self) -> bool:
        return self.routing == RoutingMode.KEYBY

    def build_replicas(self, mode: ExecutionMode,
                       time_policy: TimePolicy) -> List[Replica]:
        if self.is_tpu and mode != ExecutionMode.DEFAULT:
            # Parity: reference builders reject GPU operators outside DEFAULT
            # mode (SURVEY.md §2.5 structural invariants).
            raise WindFlowError(
                f"TPU operator '{self.name}' requires DEFAULT execution mode")
        self.replicas = [self.replica_class(self, i)
                        for i in range(self.parallelism)]
        for r in self.replicas:
            r.mode = mode
            r.time_policy = time_policy
        return self.replicas

    #: True on operators holding cross-batch state the durability plane
    #: cannot snapshot yet (host window engines, persistent-DB suites):
    #: a checkpoint of a graph containing one restores everything else
    #: and the pre-flight checker surfaces the gap as WF603
    checkpoint_opaque = False

    def snapshot_state(self) -> Optional[dict]:
        """Durable-state hook (windflow_tpu/durability): one picklable
        blob capturing ALL cross-batch state this operator owns (its
        replicas' included), taken at the quiesced checkpoint barrier.
        ``None`` means stateless — nothing written, nothing restored.
        Device arrays must come back as host numpy (the plane's only
        device sync, at checkpoint cadence)."""
        return None

    def restore_state(self, blob: dict) -> None:
        """Inverse of :meth:`snapshot_state`, applied to a freshly built
        (never-stepped) operator before the first source tick."""
        raise WindFlowError(
            f"operator '{self.name}' ({type(self).__name__}) cannot "
            "restore checkpoint state it never snapshots")

    def key_space(self) -> Optional[int]:
        """Declared dense key-space bound of a keyed operator (the
        ``withMaxKeys`` / dense ``withNumKeySlots`` contract), or None
        for arbitrary/interned key spaces.  The shard ledger
        (monitoring/shard_ledger.py) keys off this: a bounded space gets
        an EXACT per-key histogram (and, on a mesh, per-key-shard load
        from the ranges each chip owns); unbounded spaces fall back to
        the count-min sketch."""
        return None

    def _stage_notes(self) -> dict:
        """What a window stage past the first adds to its ``wf.dispatch``
        span beside ``stage`` (``rows_in``, where it knows)."""
        return {}

    def inlines_prelude(self) -> bool:
        """True where a fused chain's stateless members can ride INSIDE
        this operator's step program (``_fused_prelude``, built by
        fusion/executor.py): the chain is then one dispatch a batch.
        False keeps the operator's own program and dispatch."""
        return False

    def megastep_tail(self):
        """``(kind, None)`` where this operator's step can be the body
        of a megastep scan (``kind`` names the scan-body adapter in
        megastep.py that knows its carry layout), else ``(None,
        reason)``.  ``megastep.tail_kind`` asks after the conditions
        that are about the graph; the reason feeds preflight's WF608."""
        return None, f"unsupported tail operator {type(self).__name__}"

    def num_dropped_tuples(self) -> int:
        """Tuples this operator dropped beyond collector-level drops (e.g.
        out-of-range keys on the mesh reduce, late tuples on TB windows);
        folded into PipeGraph.get_num_dropped_tuples."""
        return 0

    def dump_stats(self) -> dict:
        st = {
            "Operator_name": self.name,
            "Operator_type": type(self).__name__,
            "Parallelism": self.parallelism,
            "Replicas": [r.stats.to_json() for r in self.replicas],
        }
        if self._fused_into is not None:
            # whole-chain fusion: this operator's execution folded into
            # one fused program (the replica counters above are
            # attributed from that hop, not dispatched here)
            st["Fused_into"] = self._fused_into
        return st
