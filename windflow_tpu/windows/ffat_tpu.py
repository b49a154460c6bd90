"""FfatWindowsTPU: incremental sliding-window aggregation on TPU.

Device equivalent of the reference's ``Ffat_Windows_GPU``
(``/root/reference/wf/ffat_replica_gpu.hpp:424``, ``flatfat_gpu.hpp:143``),
re-designed for XLA rather than translated from CUDA:

* The reference lifts tuples into pane aggregates with per-key kernels
  (``ffat_replica_gpu.hpp:92-216`` lift, ``Aggregate_Panes_Kernel``); here the
  whole batch is sorted by key once and panes are built with a segmented
  ``associative_scan`` — the XLA expression of the same reduction.
* The reference maintains a per-key FlatFAT tree on device and computes
  ``numWinsPerBatch`` window results per launch (``flatfat_gpu.hpp:60-139``).
  Here per-key state is **dense over a static key space** [0, max_keys): a
  carry ring of the trailing R-1 pane aggregates per key plus the current
  partial pane.  Window results gather their R panes and reduce them with a
  log-depth scan, for every key and every fired window in one fused program —
  the "batch many windows per launch" trick (``builders_gpu.hpp:576``
  ``withNumWinPerBatch``) taken to its TPU conclusion: *all* windows a batch
  completes, across *all* keys, in one launch.
* Count-based windows of length W sliding by S decompose into panes of
  P = gcd(W, S) (same decomposition as the reference's pane logic): R = W/P
  panes per window, fired every D = S/P panes.

Invariants/contract:
* key extractor is JAX-traceable and returns ints in [0, max_keys);
  out-of-range keys are dropped (masked), as are invalid lanes.
* ``lift`` maps a record pytree to an aggregate pytree; ``comb`` is an
  associative combiner of aggregates.  No identity element is required.
* One step processes one fixed-capacity batch; all shapes are static, so the
  program compiles exactly once per batch capacity.
"""

from __future__ import annotations

import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from windflow_tpu.basic import RoutingMode, WindFlowError, WinType
from windflow_tpu.batch import WM_NONE, DeviceBatch
from windflow_tpu.monitoring import recorder as flightrec
from windflow_tpu.monitoring.jit_registry import wf_jit
from windflow_tpu.ops.base import Operator
from windflow_tpu.ops.tpu import _TPUReplica
from windflow_tpu.windows.engine import WindowSpec
from windflow_tpu.windows.ffat_kernels import (agg_spec_for,
                                               make_ffat_flush,
                                               make_ffat_state,
                                               make_ffat_step,
                                               make_ffat_tb_state,
                                               make_ffat_tb_step,
                                               resolve_monoid,
                                               tb_placement)


def number_window_stages(operators, upstreams: dict) -> None:
    """Set ``window_stage`` on every windowing device operator of a graph
    (those whose ``Operator.window_stage`` is not None: the count / time
    window, the count window in event-time order, the session window,
    the interval join) from its edges (``upstreams``: ``id(op) -> [(upstream op, _)]``, as
    ``fusion.executor._upstream_edges`` gives them): one more than the
    deepest windowing operator anywhere upstream of it."""
    depth: dict = {}

    def above(op) -> int:
        """Windowing operators on the deepest path into ``op``."""
        if id(op) not in depth:
            depth[id(op)] = 0           # a cycle cannot be built; be safe
            depth[id(op)] = max(
                (above(up) + (up.window_stage is not None)
                 for up, _ in upstreams.get(id(op), ())), default=0)
        return depth[id(op)]

    for op in operators:
        if op.window_stage is not None:
            op.window_stage = above(op) + 1


class FfatTPUReplica(_TPUReplica):
    def _op_step(self, batch):
        return self.op._step(batch, self.index)

    def on_eos(self):
        if self.op.is_tb and self.op._per_replica_state:
            # Keyed TB state is PER REPLICA (each replica owns its key
            # partition's pane ring and clock — independent partitions'
            # watermark frontiers must never advance each other's rings),
            # so every replica flushes its own state at its own EOS.
            outs = self.op._flush_tb(self.index)
        elif self.op.is_tb:
            # FORWARD-routed TB: batches round-robin over replicas into ONE
            # shared state (no key partition exists to split it by), so the
            # last replica to terminate flushes it once.
            self.op._eos_replicas += 1
            if self.op._eos_replicas < self.op.parallelism:
                return
            outs = self.op._flush_tb(0)
        else:
            # CB state is operator-level (per-key clock lanes make the one
            # dense table safe under key partitioning); only the LAST
            # replica to terminate may flush it — earlier-terminating
            # siblings' peers might still hold queued data batches whose
            # tuples belong in the open windows.
            self.op._eos_replicas += 1
            if self.op._eos_replicas < self.op.parallelism:
                return
            outs = self.op._flush()
        for out in outs:
            self.stats.device_programs_launched += 1
            # flush outputs carry size=None; .size counts the fired mask
            # (one device sync each — EOS only, never the hot path)
            self.stats.outputs_sent += out.size
            self.emitter.emit_device_batch(out)


class FfatWindowsTPU(Operator):
    """Count-based windows use the rank/pane decomposition
    (``make_ffat_step``); time-based windows use quantum panes — pane =
    ``ts // gcd(win, slide)`` — over a rolling per-key pane ring with
    watermark-driven firing (``make_ffat_tb_step``; reference TB lift
    kernels, ``ffat_replica_gpu.hpp:92-216``)."""

    replica_class = FfatTPUReplica
    fixed_capacity_label = "FfatWindowsTPU"
    chain_role = "tail"
    reports_fire_freshness = True
    #: its re-bucket rule: ``durability/rebucket._rebucket_ffat``
    snapshot_kind = "ffat_tpu"

    #: 1 for the first window operator of a pipeline, n + 1 for one fed
    #: (through whatever operators) by a stage-n window's rows; set by
    #: the graph build (:func:`number_window_stages`)
    window_stage = 1

    #: compacted key space (parallel/compaction.py): True when the graph
    #: build attached a KeyCompactor — ``max_keys`` then bounds the SLOT
    #: space, not the user's (arbitrary int32) key space
    _compact_keys = False

    def __init__(self, lift: Callable, comb: Callable, spec: WindowSpec, *,
                 max_keys: Optional[int], name: str = "ffat_windows_tpu",
                 parallelism: int = 1,
                 key_extractor: Optional[Callable] = None,
                 pane_capacity: Optional[int] = None,
                 overflow_policy: str = "drop",
                 sum_like: bool = False,
                 monoid: Optional[str] = None) -> None:
        routing = (RoutingMode.KEYBY if key_extractor is not None
                   else RoutingMode.FORWARD)
        super().__init__(name, parallelism, routing=routing, is_tpu=True,
                         key_extractor=key_extractor)
        self.lift = lift
        self.comb = comb
        self.spec = spec
        #: None = compacted key space (withCompactedKeys): the graph
        #: build assigns the slot bound via enable_compaction; running
        #: without it (kill switch / no graph) fails at the first batch
        #: with a clear message (see _ensure)
        self.max_keys = max_keys
        if max_keys is None and key_extractor is None:
            raise WindFlowError(
                f"FfatWindowsTPU '{name}': a compacted key space "
                "(withCompactedKeys) requires withKeyBy — non-keyed "
                "windows use withMaxKeys(1)")
        self._cstats = None
        self.P = math.gcd(spec.win_len, spec.slide)
        self.R = spec.win_len // self.P
        self.D = spec.slide // self.P
        self.is_tb = spec.win_type == WinType.TB
        if not self.is_tb:
            self.count_order = "arrival"
        # TB pane ring contract: the ring must cover the window span, plus
        # the time spread of any single batch (including idle gaps *inside*
        # a batch — gaps between batches cost nothing, pre-gap windows fire
        # before the ring rolls), plus the lateness allowance in panes
        # (lateness holds windows open, so their panes stay pinned in the
        # ring).  Exceeding it is overload: panes are evicted and counted
        # (n_evicted).  When not set via withPaneCapacity, the ring is
        # auto-sized at the first batch to one batch's worth of panes
        # (capped at 8192) — keyed partitioning concentrates one key's
        # tuples, so a partition batch of C tuples can span C panes.
        self.NP = pane_capacity
        if self.is_tb and pane_capacity is not None                 and pane_capacity < 2 * self.R:
            # >= 2R also guarantees the step's two pre-place fire passes
            # reach every window over in-ring data (ffat_kernels docstring)
            raise WindFlowError(
                "pane_capacity must be at least 2*win/gcd panes")
        if self.is_tb and key_extractor is None and parallelism > 1:
            # FORWARD round-robin at parallelism > 1 would interleave
            # batches into the shared ring in replica-drain order, not
            # arrival order — a later-frontier batch on one replica could
            # fire windows before an earlier batch on a sibling is placed.
            # Keyed routing (withKeyBy) is the scaling path, exactly as the
            # reference scales windows by key partitioning.
            raise WindFlowError(
                "non-keyed time-based FfatWindowsTPU requires "
                "parallelism == 1; use withKeyBy to scale")
        if overflow_policy not in ("drop", "count", "error"):
            raise WindFlowError(
                f"unknown overflow policy '{overflow_policy}' "
                "(drop | count | error)")
        #: TB ring-overflow policy: "drop" (default) suppresses windows
        #: that lost data panes and counts them; "count" fires them over
        #: the surviving panes only (wrong aggregates, n_evicted counts);
        #: "error" raises at the next host checkpoint.  The reference never
        #: fires a wrong window (its FlatFAT grows instead).
        self.overflow_policy = overflow_policy
        #: declared leafwise-monoid combiner ("sum" | "max" | "min";
        #: withSumCombiner == monoid "sum", withMonoidCombiner for the
        #: rest): CB drops the fold's flag lane and skips the grouping
        #: permutation (scatter-combine pane cells); TB skips grouping
        #: entirely — pane placement is timestamp arithmetic, lifts
        #: scatter-combine into the ring.  The declaration must match the
        #: combiner exactly (declaring "sum" for a max combiner silently
        #: computes sums).
        try:
            self.monoid = resolve_monoid(sum_like, monoid)
        except ValueError as e:
            raise WindFlowError(str(e)) from None
        self._overflow_steps = 0
        self._auto_np = False          # NP chosen by the span estimator
        self._np_ceil = None
        self._evicted_seen = 0         # n_evicted at the last regrow check
        self._pending_evct = None      # lazy counter read (one cadence old)
        self._evicted_base = 0         # evictions excused as regrow pains
        self._error_armed = False      # error policy live (post-transient)
        self._clean_checks = 0
        self._dirty_checks = 0
        # data-ts extrema observed while the multi-channel watermark fold
        # is still unresolved (frontier == WM_NONE): nothing fires in that
        # phase, so every placed pane stays live and the ring must cover
        # exactly this spread (see _regrow_for_span)
        self._unres_lo = None
        self._unres_hi = None
        # True once a step ran with a RESOLVED frontier: before that
        # nothing has fired, so the ring may be REBASED down to re-cover
        # panes the capacity roll slid past while a sibling channel was
        # still unheard (see _rebase_ring); after it, panes below the
        # fired frontier are closed and only upward growth is safe
        self._fold_stepped = False
        # Device state, created on first batch.  CB: one shared table (key
        # 0) — per-key clock lanes make it partition-safe.  TB: one state
        # PER REPLICA index — the ring clocks are shared across a state's
        # keys, so each key partition needs its own.
        self._states = {}
        self._jit_step = None
        self._jit_flush = None
        self._capacity = None
        self._payload_zero = None   # all-invalid batch for TB EOS flush
        self._flushed = False
        self._eos_replicas = 0

    def enable_compaction(self, comp) -> None:
        """Attach a pinned KeyCompactor (graph build): arbitrary int32
        keys map to stable dense slots through the device-resident remap
        table, and ``max_keys`` becomes the SLOT bound — the pane rings
        stay dense over [0, slots) exactly as under withMaxKeys.
        Unmapped keys (host admission never saw them: device-born
        streams before a reseed catches up) are masked invalid and
        counted, the operator's existing out-of-range contract."""
        self._compactor = comp
        self._compact_keys = True
        self.max_keys = comp.slots
        comp.register_device_stats(lambda: self._cstats)

    @property
    def program_name(self) -> str:
        """Name of the step's function, so of its XLA module
        (``jit_step``; ``jit_step_w2`` for a second window stage): every
        operator's program is ``jit_step`` in a device trace, and two
        window stages of one graph have to be told apart there."""
        return "step" if self.window_stage <= 1 \
            else f"step_w{self.window_stage}"

    # -- state layout --------------------------------------------------------
    def _init_state(self, agg_spec):
        if self.mesh is not None:
            from windflow_tpu.parallel.mesh import (
                make_sharded_ffat_state, make_sharded_ffat_tb_state)
            if self.is_tb:
                return make_sharded_ffat_tb_state(
                    agg_spec, self.max_keys, self.NP, self.mesh)
            return make_sharded_ffat_state(agg_spec, self.max_keys, self.R,
                                           self.mesh)
        if self.is_tb:
            return make_ffat_tb_state(agg_spec, self.max_keys, self.NP)
        return make_ffat_state(agg_spec, self.max_keys, self.R)

    # -- per-batch program ---------------------------------------------------
    def _ingest(self) -> str:
        """Staged-batch layout the mesh step consumes (mesh.py
        ``_ffat_shard_layout``)."""
        return getattr(self, "_ingest_mode", None) \
            or ("flat" if jax.process_count() > 1 else "data")

    def _tb_plan(self) -> Optional[dict]:
        """The static placement plan of the built time-based step
        (``ffat_kernels.tb_placement``, on the sizes the kernel itself
        sees: a key shard's on a mesh); None where there is none —
        count-based, an undeclared combiner, or no batch has sized the
        state yet."""
        if not self.is_tb or self.monoid is None or not self._states \
                or self._capacity is None:
            return None
        K, B = self.max_keys, self._capacity
        if self.mesh is not None:
            from windflow_tpu.parallel.mesh import _ffat_shard_layout
            K, *_, B = _ffat_shard_layout(self.mesh, B, K, self._ingest())
        cells = jax.tree.leaves(next(iter(self._states.values()))["cells"])
        return tb_placement(
            self.monoid, [jax.ShapeDtypeStruct(c.shape[2:], c.dtype)
                          for c in cells], K, self.NP, B)

    def _placement_args(self) -> dict:
        """``placement=`` of this step's ``wf.compile`` span."""
        plan = self._tb_plan()
        return {"placement": plan["placement"]} if plan else {}

    def _with_placement(self, step):
        """``step``, its ``wf.compile`` span saying which placement the
        program holds (kept a call INSIDE ``_build_step``: tracecheck
        reads the donation of ``_jit_step`` off that method's source)."""
        step.compile_args = self._placement_args
        return step

    def _build_step(self, capacity: int):
        if self.mesh is not None:
            # Multi-chip: key-sharded state, data-sharded batches riding an
            # all_gather over ICI (parallel/mesh.py make_sharded_ffat_step).
            # Config.mesh is how the graph API reaches the sharded kernels.
            from windflow_tpu.parallel.mesh import (ffat_owned_lanes,
                                                    make_sharded_ffat_step,
                                                    make_sharded_ffat_tb_step)
            # multi-process graphs stage batches fully sharded over
            # (data, key) — the only layout each process can assemble from
            # the lanes IT ingested — so the step gathers over both axes
            # (mesh.py _ffat_shard_layout "flat").  "aligned" is set by
            # the graph build (Config.key_aligned_ingest) when every
            # feeding edge is a host staging edge routed through the
            # key-aligned emitter: the host pre-places each tuple on its
            # key-owner column, so the step skips the all_gather that
            # dominates the modeled ICI bytes (parallel/emitters.
            # AlignedMeshStageEmitter; docs/OBSERVABILITY.md wire plane).
            ingest = self._ingest()
            if self.is_tb:
                return self._with_placement(make_sharded_ffat_tb_step(
                    self.mesh, capacity, self.max_keys, self.P, self.R,
                    self.D, self.NP, self.lift, self.comb,
                    self.key_extractor,
                    drop_tainted=self.overflow_policy == "drop",
                    ingest=ingest,
                    monoid=self.monoid, op_name=f"{self.name}.mesh",
                    owner=self.name))
            # lanes a key shard's step runs over (its share of the batch:
            # mesh.py ffat_owned_lanes); `step_cap` on its wf.dispatch
            self.step_cap = ffat_owned_lanes(self.mesh, capacity)
            return make_sharded_ffat_step(
                self.mesh, capacity, self.max_keys, self.P, self.R, self.D,
                self.lift, self.comb, self.key_extractor,
                monoid=self.monoid,
                ingest=ingest, op_name=f"{self.name}.mesh", owner=self.name)
        # Pallas kernel selection (windflow_tpu/kernels): resolved once
        # per program build against Config.pallas_kernels + the runtime
        # backend; the kernels trace into this same wf_jit program, so
        # fused preludes, regrow rebuilds, and restore all keep them.
        # Mesh programs above stay on the lax path (kernels inside
        # shard_map are a future round).
        pallas = self._pallas_mode()
        comp = self._compactor
        if comp is None:
            lift, key_fn = self.lift, self.key_extractor
        else:
            # compacted key space: the kernel sees {"rec": record,
            # "slot": dense id} lanes — the slot lane is resolved by the
            # remap lookup in the wrapper below, inside this SAME program
            user_lift = self.lift
            lift = lambda r: user_lift(r["rec"])  # noqa: E731
            key_fn = lambda r: r["slot"]          # noqa: E731
        if self.is_tb:
            step = make_ffat_tb_step(capacity, self.max_keys, self.P,
                                     self.R, self.D, self.NP,
                                     lift, self.comb,
                                     key_fn,
                                     drop_tainted=self.overflow_policy
                                     == "drop",
                                     monoid=self.monoid, pallas=pallas)
        else:
            step = make_ffat_step(capacity, self.max_keys, self.P, self.R,
                                  self.D, lift, self.comb,
                                  key_fn,
                                  monoid=self.monoid,
                                  pallas=pallas)
        if comp is not None:
            from windflow_tpu.parallel import compaction
            kernel = step
            user_key = self.key_extractor

            def step(state, payload, ts, valid, *rest):
                # remap operands ride as (table_keys, table_slots, cstats)
                # appended after the kernel's own args; cstats is the
                # donated hit/miss/candidate state (zero extra dispatches)
                *kargs, tk, tsl, cst = rest
                with flightrec.phase("wf.fn"):
                    raw = jax.vmap(user_key)(payload).astype(jnp.int32)
                slots, hit = compaction.lookup_slots(tk, tsl, raw, valid)
                cst = compaction.cstats_update(cst, raw, hit,
                                               valid & ~hit)
                outs = kernel(state, {"rec": payload, "slot": slots}, ts,
                              valid & hit, *kargs)
                out = dict(outs[1])
                out["key"] = compaction.slots_to_user_keys(
                    out["key"], tk, tsl)
                outs = (outs[0], out) + tuple(outs[2:])
                return (*outs, cst)
        # this operator's part of the program under its own name (device
        # phases, monitoring/recorder.py); a fused prelude's members open
        # theirs, so the prelude runs outside it
        step = flightrec.operator_scope(self.name)(step)
        prelude = self._fused_prelude
        if prelude is not None:
            # Whole-chain fusion (windflow_tpu/fusion): the fused
            # segment's stateless members run INSIDE this program, so
            # the map/filter hop boundaries the sweep ledger priced
            # never materialize in HBM and the chain pays this single
            # dispatch.  Ring regrowth rebuilds the step through this
            # same path, so a regrown program keeps its prelude.
            inner = step

            def step(state, payload, ts, valid, *rest):
                payload, valid = prelude(payload, valid)
                return inner(state, payload, ts, valid, *rest)
        # State-only donation, fused or not: the ring is the program's
        # one input whose buffers an output aliases (window results have
        # their own shapes — batch-lane donation would elide nothing and
        # XLA warns about unusable donations).  Compacted steps also
        # donate the cstats operand (the sketch pattern).
        donate = (0,)
        if comp is not None:
            donate = (0, 7 if self.is_tb else 6)
        # the program's name in a device trace (jit_<name>): a window
        # stage fed by another window's rows says which stage it is
        step.__name__ = self.program_name
        return self._with_placement(
            wf_jit(step, op_name=self._fused_name or self.name,
                   donate_argnums=donate))

    def _pallas_mode(self):
        """Resolved Pallas gate for this operator's compiled programs
        (windflow_tpu/kernels; None = lax path)."""
        from windflow_tpu.kernels import resolve_pallas_for
        return resolve_pallas_for(self)

    # -- operator plumbing ---------------------------------------------------
    @property
    def _per_replica_state(self) -> bool:
        # TB ring clocks are shared across a state's keys, so KEYBY
        # partitions (disjoint keys, independent watermark frontiers) need
        # one state per replica; FORWARD round-robin feeds every replica
        # the same keys and must share one state.
        return self.is_tb and self.routing == RoutingMode.KEYBY             and self.parallelism > 1

    def _sidx(self, ridx: int) -> int:
        return ridx if self._per_replica_state else 0

    def _run_step(self, sidx: int, payload, ts, valid, *kargs):
        """Dispatch the compiled step, appending the compaction operands
        (remap tables + donated cstats) when a compactor is attached;
        updates the state (and cstats) and returns the kernel's
        remaining outputs.  The un-compacted path pays one check."""
        comp = self._compactor
        if comp is None:
            outs = self._jit_step(self._states[sidx], payload, ts, valid,
                                  *kargs)
            self._states[sidx] = outs[0]
            return outs[1:]
        if not comp.active:
            # unlike the stateful plane there is NO lossless fallback
            # for a compacted window (max_keys bounds the SLOT space):
            # running on would silently mask every not-yet-admitted
            # key's records forever, so fail loudly instead
            raise WindFlowError(
                f"FfatWindowsTPU '{self.name}': the compacted key space "
                "lost its host admission path (the key extractor failed "
                "on the staging probe, or admission errored) — declare "
                "withMaxKeys or make the extractor batch-applicable")
        from windflow_tpu.parallel import compaction
        comp.on_batch()
        if self._cstats is None:
            self._cstats = compaction.cstats_init()
        tk, tsl = comp.tables()
        outs = self._jit_step(self._states[sidx], payload, ts, valid,
                              *kargs, tk, tsl, self._cstats)
        self._states[sidx] = outs[0]
        self._cstats = outs[-1]
        return outs[1:-1]

    def _ensure(self, batch: DeviceBatch, sidx: int):
        if self._capacity is None:
            if self.max_keys is None:
                raise WindFlowError(
                    f"FfatWindowsTPU '{self.name}': compacted key space "
                    "(withCompactedKeys) needs Config.key_compaction on "
                    "and a graph build to assign slots; declare "
                    "withMaxKeys to run without compaction")
            self._capacity = batch.capacity
            cap_by_mem = max(64, (1 << 23) // max(1, self.max_keys))
            # ceiling: purely the MEMORY bound on the dense [max_keys,
            # NP] state (plus the NP-proportional window-output grid).
            # It deliberately does NOT clamp to the single-batch span
            # (one batch of C tuples spans <= C panes, but the ring must
            # hold UNFIRED panes across MANY batches when the min-folded
            # watermark lags the frontier — a batch-capacity ceiling made
            # the ring ungrowable exactly when multi-channel lag needed
            # it, found by the r5 5000-tuple fuzz soak).  The lateness
            # allowance is ADDED — lateness pins panes in the ring by
            # contract, so clamping it away would make the grown ring
            # permanently too small for high-lateness specs
            lat_panes = (self.spec.lateness // self.P + 1) if self.is_tb \
                else 0
            self._np_ceil = max(2 * self.R, self.R + 64,
                                self.R + lat_panes
                                + min(8192, cap_by_mem) + 2)
            if self.NP is None and self.is_tb:
                # Auto-size from the FIRST batch's observed time spread
                # (one host sync, once): 8x margin over its pane span plus
                # the lateness allowance, floored at 2R / R+64 and capped
                # at the ceiling.  A first batch unrepresentative of the
                # steady state cannot silently lose windows: ring overflow
                # is detected on a cadence and the ring REGROWS toward the
                # ceiling (see _maybe_regrow — the device form of the host
                # FlatFAT's growth, ffat_op.py).
                tmin = int(jnp.min(jnp.where(batch.valid, batch.ts,
                                             jnp.int64(1) << 62)))
                tmax = int(jnp.max(jnp.where(batch.valid, batch.ts,
                                             -(jnp.int64(1) << 62))))
                span = (tmax - tmin) // self.P + 1 if tmax >= tmin else 1
                lat_panes = self.spec.lateness // self.P + 1
                est = 8 * span + lat_panes + self.R + 2
                self.NP = max(2 * self.R, self.R + 64,
                              min(est, self._np_ceil))
                self._auto_np = True
            elif self.NP is None:
                self.NP = self._np_ceil
            self._jit_step = self._build_step(batch.capacity)
            if self.is_tb:
                self._payload_zero = jax.tree.map(jnp.zeros_like,
                                                  batch.payload)
        elif batch.capacity != self._capacity:
            raise WindFlowError(
                "FfatWindowsTPU requires a fixed upstream batch capacity "
                f"({self._capacity}), got {batch.capacity}")
        if sidx not in self._states:
            payload = batch.payload
            if self._fused_prelude is not None:
                # fused chain: the lift sees the chain's OUTPUT records —
                # size the aggregate state from the post-prelude spec
                # (abstract eval, zero device work)
                from windflow_tpu.fusion.executor import prelude_out_spec
                payload = prelude_out_spec(self._fused_prelude,
                                           batch.payload, batch.valid)
            self._states[sidx] = self._init_state(
                agg_spec_for(self.lift, payload))

    def _wm_pane(self, wm: int) -> int:
        """Lateness-adjusted watermark in pane units (the host-side firing
        frontier the device program compares window ends against)."""
        if wm == WM_NONE:
            return -(1 << 60)
        return (wm - self.spec.lateness) // self.P

    def _step(self, batch: DeviceBatch, ridx: int = 0) -> DeviceBatch:
        sidx = self._sidx(ridx)
        self._ensure(batch, sidx)
        if self.is_tb:
            if self._auto_np:
                # no NP < ceiling gate: at the ceiling growth no-ops in
                # _grow_ring, but extrema tracking and the pre-fold
                # _rebase_ring (a pure position shift, no growth) must
                # still run or a lagging channel's below-base panes are
                # unrecoverable on ceiling-size rings
                self._regrow_for_span(batch)
            if batch.frontier != WM_NONE:
                # this step fires: pre-fold rebasing closes (see
                # _rebase_ring) — read BEFORE the flag below by
                # _regrow_for_span, so the first resolved batch itself
                # still rebases ahead of its own placement
                self._fold_stepped = True
            # Fire on the batch's staging-time frontier, not the min-folded
            # propagated stamp: the step places every tuple of the batch
            # before firing, so the newest frontier is safe here and saves
            # one batch of firing lag (batch.py DeviceBatch.frontier).
            out, fired, out_ts, _ = self._run_step(
                sidx, batch.payload, batch.ts, batch.valid,
                jnp.int64(self._wm_pane(batch.frontier)))
            # periodic host checkpoint (one sync every 32 steps, and at
            # EOS): an auto-sized ring REGROWS on overflow before the
            # error policy would fail loudly
            self._overflow_steps += 1
            if self._overflow_steps % 32 == 0:
                if self._auto_np:
                    self._maybe_regrow()
                if self.overflow_policy == "error":
                    self._check_overflow()
        else:
            out, fired, out_ts = self._run_step(
                sidx, batch.payload, batch.ts, batch.valid)
        # fired-window results inherit the input batch's flight-recorder
        # trace: the staged→sunk span then covers the whole window path
        return DeviceBatch(out, out_ts, fired,
                           watermark=batch.watermark, size=None,
                           trace=batch.trace)

    def _flush(self) -> list:
        """EOS flush of the CB shared state: fire remaining partial windows
        (reference EOS flush of open windows).  Called once, by the last
        replica to terminate."""
        if not self._states or self._flushed:
            return []
        self._flushed = True
        if self._jit_flush is None:
            self._jit_flush = self._build_flush()
        if self._compactor is not None:
            out, fired, ts = self._jit_flush(self._states[0],
                                             *self._compactor.tables())
        else:
            out, fired, ts = self._jit_flush(self._states[0])
        return [DeviceBatch(out, ts, fired, watermark=0, size=None)]

    def _flush_tb(self, ridx: int) -> list:
        """EOS flush of one TB state: iterate the normal step with an empty
        batch and an infinite watermark — each pass fires the windows whose
        ends the ring roll has brought into range, until the window
        frontier stops advancing.  Keyed TB flushes per replica; FORWARD TB
        flushes the shared state once (guarded by the caller)."""
        import numpy as np
        sidx = self._sidx(ridx)
        if sidx not in self._states:
            return []
        if self.overflow_policy == "error":
            self._check_overflow()
        cap = self._capacity
        ts0 = jnp.zeros(cap, jnp.int64)
        invalid = jnp.zeros(cap, bool)
        outs = []
        while True:
            out, fired, out_ts, n_adv = self._run_step(
                sidx, self._payload_zero, ts0, invalid,
                jnp.int64(1 << 60))
            with flightrec.wait("flush"):
                any_fired = bool(np.asarray(fired).any())
                n_adv = int(n_adv)
            if any_fired:
                outs.append(DeviceBatch(out, out_ts, fired, watermark=0,
                                        size=None))
            # loop on ADVANCE, not emission: windows beyond an empty gap
            # in the pane sequence would stall behind a no-emission pass
            if n_adv == 0:
                break
        return outs

    def _maybe_regrow(self):
        """Self-healing for the span-estimated ring: if panes were evicted
        since the last check, double the ring (up to the tuple-count
        ceiling), padding the live state with invalid columns — the device
        form of the host FlatFAT's growth-on-span (ffat_op.py).  Already-
        evicted panes are gone (their windows were suppressed and counted
        by the overflow policy); growth stops further loss.

        The eviction counter is read one checkpoint LATE: each call
        enqueues the (lazy, un-awaited) device sum and inspects the one
        enqueued 32 steps ago — by then dispatch has executed it, so the
        healthy path never blocks on a device sync."""
        if self.NP >= self._np_ceil or not self._states:
            return
        prev = self._pending_evct
        self._pending_evct = sum(
            jnp.sum(st["n_evicted"]) for st in self._states.values())
        if prev is None:
            return
        ev = int(prev)
        if ev <= self._evicted_seen:
            return
        self._evicted_seen = ev
        # x4 per event: the lazy read grows at most once per two
        # checkpoints, so convergence to the ceiling must be steep
        self._grow_ring(min(self._np_ceil, max(self.NP * 4, self.NP + 64)))

    def _grow_ring(self, new_np: int) -> None:
        """Pad every live ring to ``new_np`` panes (invalid columns) and
        rebuild the step program — shared by the eviction-cadence regrow
        above and the preemptive span regrow below."""
        pad = new_np - self.NP
        if pad <= 0:
            return

        def grow(st):
            out = dict(st)
            out["cells"] = jax.tree.map(
                lambda a: jnp.pad(
                    a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2)),
                st["cells"])
            out["cell_valid"] = jnp.pad(st["cell_valid"],
                                        ((0, 0), (0, pad)))
            if self.mesh is not None:
                from windflow_tpu.parallel.mesh import state_sharding
                sh = state_sharding(self.mesh)
                for k in ("cells", "cell_valid"):
                    out[k] = jax.tree.map(
                        lambda a: jax.device_put(a, sh), out[k])
            return out

        self._states = {k: grow(st) for k, st in self._states.items()}
        self.NP = new_np
        self._pending_evct = None
        self._jit_step = self._build_step(self._capacity)
        if self.NP >= self._np_ceil:
            # ceiling reached: evictions up to here were the estimator's
            # growing pains, not the stream violating a user-sized ring —
            # the 'error' policy only counts evictions past this point
            self._evicted_base = self._tb_counter("n_evicted")

    def _rebase_ring(self, lo_pane: int, hi_pane: int) -> None:
        """Move the ring window DOWN to ``lo_pane`` so panes the capacity
        roll slid past while the watermark fold was unresolved become
        placeable again (a lagging sibling channel's first data lives
        BELOW everything placed so far; growth alone pads the ring's top
        and cannot help).  Safe exactly while nothing has fired
        (``_fold_stepped`` False): the slid-past columns are empty — the
        roll found nothing to evict — and ``win_next``/``max_seen``/
        ``horizon`` are absolute pane stamps unaffected by where the ring
        window sits.  Shifting wraps top columns to the bottom; they are
        invalid by the ``hi_pane < new_base + NP`` clamp, and invalid
        cells' values are masked at merge (kernels).  Costs one host read
        of ``base`` per state — growth cadence only, never steady-state."""
        if self._fold_stepped:
            return
        for sidx, st in self._states.items():
            # rare host sync (see docstring); on a mesh "base" is a
            # [key-shards] lane whose per-shard clocks advance in
            # lockstep from the same gathered batches — read shard 0,
            # the elementwise shift below keeps every shard consistent
            base = int(np.asarray(st["base"]).reshape(-1)[0])
            new_base = max(lo_pane, hi_pane - self.NP + 1)
            delta = base - new_base
            if delta <= 0:
                continue
            out = dict(st)
            out["cells"] = jax.tree.map(
                lambda a: jnp.roll(a, delta, axis=1), st["cells"])
            out["cell_valid"] = jnp.roll(st["cell_valid"], delta, axis=1)
            out["base"] = st["base"] - delta
            if self.mesh is not None:
                from windflow_tpu.parallel.mesh import state_sharding
                sh = state_sharding(self.mesh)
                for k in ("cells", "cell_valid"):
                    out[k] = jax.tree.map(
                        lambda a: jax.device_put(a, sh), out[k])
            self._states[sidx] = out

    def _regrow_for_span(self, batch) -> None:
        """PREEMPTIVE ring growth from the host-known watermark lag (r5;
        found by the 5000-tuple fuzz soak: two seeds evicted a handful of
        panes — and suppressed their windows — under configurations whose
        multi-replica host stages let the min-folded watermark lag the
        staging frontier further than the first-batch span estimate).

        By the watermark contract, no future tuple is older than the
        propagated watermark, so the ring only ever needs the panes in
        ``(wm_adj, ts_max]`` plus ``R-1`` of window history — ``ts_max``
        is the batch's max DATA timestamp (attached host-side at staging
        and carried through mask-only stages), which can run arbitrarily
        far ahead of any watermark when a sibling channel lags.  Both
        stamps are host metadata — the bound costs ZERO device syncs —
        and growing to it BEFORE the step means the capacity roll never
        evicts non-late data; the eviction-cadence regrow remains as the
        backstop for device-born batches (no ``ts_max``) and streams
        whose true span exceeds the memory ceiling.

        The ring must also cover the BATCH'S OWN pane spread even when
        every pane is fireable: one step's fire passes advance at most
        ``3 * (NP // D + 2)`` windows, so a batch spanning far more
        panes than the ring holds would force the capacity roll to evict
        panes the passes could not fire in time — the
        ``ts_max - ts_min`` spread bound (the operator's documented ring
        contract, previously estimated from the FIRST batch only) now
        updates from every staged batch.

        While the multi-channel watermark fold is unresolved
        (``frontier == WM_NONE``) NOTHING fires, so every placed pane
        stays live and the ring must cover exactly the OBSERVED data
        spread — it grows (geometrically) to that, not to the memory
        ceiling (ADVICE r5: the former eager ceiling commit permanently
        charged tiny-span streams a ceiling-size ring plus a step
        recompile before their first resolved frontier).  The extrema
        seen during the unresolved phase keep bounding ``hi`` after the
        fold resolves, until the watermark passes them — the pre-fold
        panes are still unfired and must not be rolled out.

        Multi-host meshes skip the span regrow entirely: each process
        observes different local extrema, and divergent per-process
        growth decisions would desynchronize the sharded ring shapes
        (ADVICE r5 medium; staging also stops attaching process-local
        extrema, batch.py _stage_soa).  The eviction-cadence regrow is
        SPMD-consistent and remains the growth path there."""
        if batch.ts_max is None:
            return
        if jax.process_count() > 1:
            return
        wm = batch.frontier             # newest safe stamp: firing uses it
        if wm == WM_NONE:
            lo = batch.ts_min if batch.ts_min is not None else batch.ts_max
            prev_lo = self._unres_lo
            if self._unres_lo is None or lo < self._unres_lo:
                self._unres_lo = lo
            if self._unres_hi is None or batch.ts_max > self._unres_hi:
                self._unres_hi = batch.ts_max
            needed = int(self._unres_hi - self._unres_lo) // self.P \
                + self.R + 2
            if needed > self.NP:
                self._grow_ring(min(self._np_ceil,
                                    max(needed, self.NP * 2)))
            if prev_lo is not None and lo < prev_lo:
                # a lagging channel opened panes BELOW everything placed:
                # leading batches may already have rolled base past them
                self._rebase_ring(self._unres_lo // self.P,
                                  self._unres_hi // self.P)
            return
        lo = self._wm_pane(wm)          # oldest pane still open for data
        hi = batch.ts_max // self.P     # newest pane this batch touches
        if self._unres_hi is not None:
            if lo > self._unres_hi // self.P:
                # watermark passed the pre-fold data: stop tracking it
                self._unres_lo = self._unres_hi = None
            else:
                hi = max(hi, self._unres_hi // self.P)
        rebase_lo = None
        if not self._fold_stepped:
            # FIRST resolved batch (nothing fired yet): its own rows and
            # the pre-fold extrema may all reach below the rolled base —
            # the ring must re-cover down to the oldest of them before
            # this step places (the step fires AFTER placement, so panes
            # under the watermark still emit their windows normally)
            cand = [lo]
            if batch.ts_min is not None:
                cand.append(batch.ts_min // self.P)
            if self._unres_lo is not None:
                cand.append(self._unres_lo // self.P)
            rebase_lo = min(cand)
        needed = int(hi - lo) + self.R + 2
        if batch.ts_min is not None:
            spread = (batch.ts_max - batch.ts_min) // self.P + 1
            needed = max(needed, int(spread) + self.R + 2)
        if rebase_lo is not None:
            needed = max(needed, int(hi - rebase_lo) + self.R + 2)
        if needed > self.NP:
            # at least double: each growth recompiles the step, so
            # convergence under a widening lag must be geometric
            self._grow_ring(min(self._np_ceil,
                                max(needed, self.NP * 2)))
        if rebase_lo is not None:
            self._rebase_ring(rebase_lo, hi)

    # -- durable state (windflow_tpu/durability) -----------------------------
    def snapshot_state(self):
        """All cross-batch state: the dense pane rings/tables per state
        index (device -> host numpy), the compiled-capacity/ring-size
        pair the step program is rebuilt from, and the regrow/overflow
        estimator bookkeeping — so a restored ring neither re-learns its
        span nor re-arms a stale error grace.  Fused chains need nothing
        extra here: the tail operator owns the merged state, and restore
        rebuilds the step through ``_build_step``, which re-inlines the
        fused prelude."""
        if not self._states:
            return None     # never stepped: nothing to restore
        return {
            "kind": self.snapshot_kind,
            "states": {k: jax.tree.map(np.asarray, st)
                       for k, st in self._states.items()},
            "capacity": self._capacity,
            "NP": self.NP,
            "auto_np": self._auto_np,
            "np_ceil": self._np_ceil,
            "overflow_steps": self._overflow_steps,
            "evicted_seen": self._evicted_seen,
            "evicted_base": self._evicted_base,
            "error_armed": self._error_armed,
            "clean_checks": self._clean_checks,
            "dirty_checks": self._dirty_checks,
            "unres_lo": self._unres_lo,
            "unres_hi": self._unres_hi,
            "fold_stepped": self._fold_stepped,
            "flushed": self._flushed,
            "eos_replicas": self._eos_replicas,
            "payload_zero": (jax.tree.map(np.asarray, self._payload_zero)
                            if self._payload_zero is not None else None),
            # compacted key space: the remap table is the key→pane-ring
            # half of per-key state — snapshot it so a restored ring's
            # rows keep meaning the same user keys
            "compactor": (self._compactor.snapshot()
                          if self._compactor is not None else None),
        }

    def restore_state(self, blob):
        self.NP = blob["NP"]
        self._auto_np = blob["auto_np"]
        self._np_ceil = blob["np_ceil"]
        self._overflow_steps = blob["overflow_steps"]
        self._evicted_seen = blob["evicted_seen"]
        self._evicted_base = blob["evicted_base"]
        self._error_armed = blob["error_armed"]
        self._clean_checks = blob["clean_checks"]
        self._dirty_checks = blob["dirty_checks"]
        self._unres_lo = blob["unres_lo"]
        self._unres_hi = blob["unres_hi"]
        self._fold_stepped = blob["fold_stepped"]
        self._flushed = blob["flushed"]
        self._eos_replicas = blob["eos_replicas"]
        self._pending_evct = None   # lazy device read: re-primed on step
        if self.mesh is not None:
            # multi-chip restore: re-place the host blobs in the
            # key-sharded layout the sharded step consumes (axis 0 of
            # every leaf is the key/shard dimension: cells, horizon,
            # and the per-key-shard TB scalar lanes alike).  The blob
            # was re-bucketed for THIS mesh shape by the durability
            # plane (durability/rebucket.py) before reaching here.
            from windflow_tpu.parallel.mesh import state_sharding
            sh = state_sharding(self.mesh)
            place = lambda a: jax.device_put(jnp.asarray(a), sh)
        else:
            place = jnp.asarray
        self._states = {k: jax.tree.map(place, st)
                        for k, st in blob["states"].items()}
        for st in self._states.values():
            # a checkpoint from before the step counted its wide
            # placements, or the steps that advanced its ring
            if "n_late" in st:
                for name in ("n_wide", "n_ring_advances"):
                    st.setdefault(name, jnp.zeros_like(st["n_late"]))
            elif self.mesh is not None:
                # ... or, count-based on a mesh, its many-round steps
                from windflow_tpu.parallel.mesh import (CB_WIDE_STEPS,
                                                        KEY_AXIS)
                st.setdefault(CB_WIDE_STEPS, place(jnp.zeros(
                    (self.mesh.shape[KEY_AXIS],), jnp.int64)))
        if blob["payload_zero"] is not None:
            self._payload_zero = jax.tree.map(jnp.asarray,
                                              blob["payload_zero"])
        if blob.get("compactor") is not None \
                and self._compactor is not None:
            self._compactor.restore(blob["compactor"])
        self._capacity = blob["capacity"]
        self._jit_step = self._build_step(self._capacity)

    def _check_overflow(self):
        # operator-wide: counters and the excused-eviction base
        # are summed over every replica state
        if self._auto_np and self.NP < self._np_ceil:
            return   # still growing: regrow, don't error, on overflow
        with flightrec.wait("evicted"):
            ev = self._tb_counter("n_evicted")
        if self._auto_np and not self._error_armed:
            # the undersized phase leaves a window-firing backlog whose
            # drain still evicts briefly after growth; arm the error only
            # after TWO consecutive clean checkpoints (the grow checkpoint
            # itself is trivially clean — its base was just snapshotted).
            # The grace is BOUNDED: persistent overflow at the ceiling is
            # the stream violating the ring contract, and re-basing
            # forever would silently defeat the 'error' policy.
            if ev > self._evicted_base:
                self._dirty_checks += 1
                if self._dirty_checks <= 4:
                    self._evicted_base = ev
                    self._clean_checks = 0
                    return
                self._error_armed = True
            else:
                self._clean_checks += 1
                if self._clean_checks < 2:
                    return
                self._error_armed = True
        if ev > self._evicted_base:
            raise WindFlowError(
                f"{self.name}: TB pane ring overflow (pane_capacity="
                f"{self.NP} < window span + batch time spread + lateness "
                "panes); increase withPaneCapacity or choose overflow "
                "policy 'drop'/'count'")

    def _tb_counter(self, name: str) -> int:
        # one device sync at read time, never on the step path; summed over
        # replica states (and over key-shard lanes on a mesh)
        return sum(int(jnp.sum(st[name])) for st in self._states.values())

    def inlines_prelude(self) -> bool:
        # compacted key spaces (withCompactedKeys, max_keys None) stay
        # un-fused: their remap admits keys at the HOST staging boundary
        # (parallel/compaction.py), and a prelude would move key
        # extraction behind the chain where no host admission path can
        # see it — a pinned table that never fills
        return self.max_keys is not None

    def megastep_tail(self):
        if self.parallelism != 1:
            return None, "parallel window state (per-replica rings)"
        return ("ffat_tb" if self.is_tb else "ffat_cb"), None

    def key_space(self):
        # keys-lane plumbing for the shard ledger: the dense pane state
        # bounds the key space exactly where the compiled step does.
        # Compacted key spaces are unbounded to ROUTING (the sketch sees
        # raw keys; only the state is slot-dense), so they report None.
        if self._compact_keys:
            return None
        return self.max_keys if self.key_extractor is not None else None

    def num_dropped_tuples(self) -> int:
        if self.is_tb and self._states:
            return self._tb_counter("n_late")
        return 0

    def dump_stats(self) -> dict:
        n_late = None
        if self.is_tb and self._states:
            n_late = self._tb_counter("n_late")
            if self.replicas:
                self.replicas[0].stats.inputs_ignored = n_late
        st = super().dump_stats()
        if self._compactor is not None:
            st["Key_compaction"] = self._compactor.summary()
        if n_late is not None:
            st["Late_tuples_dropped"] = n_late
            st["Pane_cells_evicted"] = self._tb_counter("n_evicted")
            st["Windows_dropped_on_overflow"] = \
                self._tb_counter("n_win_dropped")
            # steps in which the pane ring advanced (windows fired and
            # freed panes, or the capacity roll made room): the others
            # make no pass over it
            st["TB_ring_advances"] = self._tb_counter("n_ring_advances")
        plan = self._tb_plan()
        if plan is not None:
            # static per built step: "dense" = the batch is placed by one
            # one-hot contraction and no scatter is left in the placement
            st["TB_placement"] = plan["placement"]
            st["TB_placement_limbs"] = sum(plan["limbs"])
            # steps whose batch spanned more than NARROW_PLACE_PANES
            # panes and scattered into the whole ring (0 on "dense")
            st["TB_wide_placements"] = self._tb_counter("n_wide")
        if self.step_cap is not None and self._states:
            # count-based on a mesh: the lanes a key shard's step is built
            # at (static), and the steps that took more than one round
            # because a shard owned more (parallel/mesh.py)
            from windflow_tpu.parallel.mesh import CB_WIDE_STEPS
            st["CB_step_lanes"] = self.step_cap
            st["CB_wide_steps"] = self._tb_counter(CB_WIDE_STEPS)
        return st

    def _build_flush(self):
        if self.mesh is not None:
            from windflow_tpu.parallel.mesh import make_sharded_ffat_flush
            return make_sharded_ffat_flush(self.mesh, self.max_keys,
                                           self.P, self.R, self.D,
                                           self.comb,
                                           op_name=f"{self.name}.flush",
                                           owner=self.name)
        flush = make_ffat_flush(self.max_keys, self.P, self.R,
                                self.D, self.comb)
        if self._compactor is not None:
            # compacted key space: partial-window records fired at EOS
            # carry SLOT ids too — map them back through the same
            # inverse table as the step's fired records
            inner = flush

            def flush(state, tk, tsl):
                from windflow_tpu.parallel import compaction
                out, fired, ts = inner(state)
                out = dict(out)
                out["key"] = compaction.slots_to_user_keys(
                    out["key"], tk, tsl)
                return out, fired, ts
        return wf_jit(flightrec.operator_scope(self.name)(flush),
                      op_name=f"{self.name}.flush")
