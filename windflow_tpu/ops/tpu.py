"""TPU operators: the device compute path.

These replace the reference's CUDA operator set (``/root/reference/wf/map_gpu.hpp``,
``filter_gpu.hpp``, ``reduce_gpu.hpp``) with XLA programs:

* ``Map_GPU``'s grid-stride elementwise kernel (``map_gpu.hpp:60-76``) becomes
  ``jax.vmap`` of the user's per-item function over the batch — XLA tiles it
  onto the VPU/MXU and fuses adjacent elementwise work.
* ``Filter_GPU``'s predicate + compaction (``filter_gpu.hpp``) becomes a
  validity-mask update: compaction is deferred (mask-aware consumers) because
  XLA prefers static shapes; the mask costs one fused elementwise op instead
  of a gather.
* ``Reduce_GPU``'s ``sort_by_key`` + ``reduce_by_key`` pipeline
  (``reduce_gpu.hpp:227-283``) becomes an XLA sort + segmented
  ``associative_scan`` — the same algorithm Thrust runs, expressed so the
  compiler can fuse the user combiner into the scan.

Structural invariants kept from the reference (SURVEY.md §2.5): TPU operators
consume batches only, require an upstream output batch size > 0, and run in
DEFAULT execution mode only.

User functions must be JAX-traceable, operating on one record (a pytree of
scalars) with ``jnp`` ops.  They are traced once per batch shape: the staging
emitter pads every batch to a fixed capacity precisely so each operator
compiles a single program.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from windflow_tpu.basic import RoutingMode, WindFlowError, current_time_usecs
from windflow_tpu.batch import DeviceBatch
from windflow_tpu.monitoring import recorder as flightrec
from windflow_tpu.monitoring.jit_registry import wf_jit
from windflow_tpu.ops.base import Operator, Replica


def _payload_nbytes(tree) -> int:
    return sum(getattr(l, "nbytes", 0) for l in jax.tree.leaves(tree))


class _TPUReplica(Replica):
    """Shared device-batch plumbing for TPU operator replicas."""

    def _op_step(self, batch: DeviceBatch):
        """Hook for replicas whose operator step needs the replica index
        (per-replica state); default ops take the batch alone.  A fused
        all-stateless segment installs its chain program here
        (windflow_tpu/fusion FusedStatelessExec) — the unfused path pays
        exactly this one attribute check."""
        fx = self.op._fusion_exec
        if fx is not None:
            return fx.step(batch)
        return self.op._step(batch)

    def process_device_batch(self, batch: DeviceBatch) -> None:
        # the stable name of this dispatch in a profiler capture is the
        # host span's op= (the XLA module is jit_step for every operator)
        counts = {"op": self.op.name, "batch": batch.seq}
        if self.op.mesh is not None:
            # one dispatch drives a program on every chip of the mesh
            counts["mesh"] = self.op.mesh.size
        with flightrec.span("wf.dispatch", **counts) as sp:
            out = self._op_step(batch)
            if out is not None and (out.capacity != batch.capacity
                                    or self.op.notes_out_cap):
                # a window step hands on a batch sized by what it can
                # fire, not by what it was given
                sp.note(out_cap=out.capacity)
            if self.op.step_cap is not None:
                sp.note(step_cap=self.op.step_cap)
            if (self.op.window_stage or 1) > 1:
                # the second device stage of a batch: two programs a
                # batch, told apart here and by the program's name
                sp.note(stage=self.op.window_stage,
                        **self.op._stage_notes())
        self.stats.device_programs_launched += 1
        if self.ring is not None and batch.trace is not None:
            # `dispatched` stamps the ASYNC enqueue (the host is already
            # free); the device-side completion is only observable through
            # a real sync, so `device_done` blocks on the output for every
            # M-th traced batch (Config.trace_device_sync_every) — 1 in
            # (sample_every * M) batches pays the sync.
            self.ring.record(batch.trace[0], flightrec.DISPATCHED,
                             current_time_usecs())
            self._traced_seen += 1
            sync_every = self.config.trace_device_sync_every
            if out is not None and sync_every \
                    and self._traced_seen % sync_every == 0:
                with flightrec.wait("sync", batch=batch.seq):
                    jax.block_until_ready(out.valid)
                now = current_time_usecs()
                self.ring.record(batch.trace[0], flightrec.DEVICE_DONE,
                                 now)
                if self.latency is not None:
                    # window-freshness gauge (latency ledger): fire time
                    # minus window-close event time over the fired
                    # records of this already-synced batch — bound only
                    # on window replicas, and only the 1-in-
                    # (sample * sync) sampled batch reaches here
                    self.latency.note_window_fire(self.op.name, out.ts,
                                                  out.valid, now)
        if out is not None:
            # operator steps build fresh DeviceBatches; the trace lane
            # and the batch number are host metadata, relayed here so one
            # hook covers every device operator (map/filter/reduce/
            # stateful/windows)
            if out.trace is None:
                out.trace = batch.trace
            out.seq = batch.seq
            self.stats.outputs_sent += out.known_size or 0
            self.emitter.emit_device_batch(out)


class MapTPUReplica(_TPUReplica):
    pass


class MapTPU(Operator):
    """Stateless elementwise transform on device (reference stateless
    ``Map_GPU``, ``map_gpu.hpp:60-76,104-433``).

    ``fn`` maps one record pytree to one record pytree.  With
    ``batch_fn=True``, ``fn`` instead receives the whole SoA payload (leading
    dim = capacity) and the validity mask — the escape hatch for
    batch-granular math (the reference has no equivalent; CUDA kernels are
    always per-item)."""

    replica_class = MapTPUReplica
    chain_role = "member"

    def __init__(self, fn: Callable, name: str = "map_tpu",
                 parallelism: int = 1, batch_fn: bool = False,
                 routing: RoutingMode = RoutingMode.FORWARD,
                 key_extractor=None) -> None:
        super().__init__(name, parallelism, routing=routing, is_tpu=True,
                         key_extractor=key_extractor)
        self.fn = fn
        self.batch_fn = batch_fn

        def step(payload, valid):
            with flightrec.operator_scope(name), flightrec.phase("wf.fn"):
                if self.batch_fn:
                    return self.fn(payload, valid)
                return jax.vmap(self.fn)(payload)

        self._jit_step = wf_jit(step, op_name=name)

    def _step(self, batch: DeviceBatch) -> DeviceBatch:
        out_payload = self._jit_step(batch.payload, batch.valid)
        # keys lane deliberately not forwarded: it is edge-scoped metadata
        # (valid only for the extractor of the edge that attached it), and a
        # map may rewrite the key field anyway.
        return DeviceBatch(out_payload, batch.ts, batch.valid,
                           watermark=batch.watermark, size=batch._size,
                           frontier=batch.frontier, ts_max=batch.ts_max,
                           ts_min=batch.ts_min)


class FilterTPUReplica(_TPUReplica):
    pass


class FilterTPU(Operator):
    """Device predicate filter (reference ``Filter_GPU``): survivors are
    expressed as a validity-mask intersection rather than a compaction —
    downstream operators and the TPU→host boundary are mask-aware, so the
    copy the reference pays on the GPU is avoided entirely."""

    replica_class = FilterTPUReplica
    chain_role = "member"

    def __init__(self, fn: Callable, name: str = "filter_tpu",
                 parallelism: int = 1,
                 routing: RoutingMode = RoutingMode.FORWARD,
                 key_extractor=None) -> None:
        super().__init__(name, parallelism, routing=routing, is_tpu=True,
                         key_extractor=key_extractor)
        self.fn = fn

        def step(payload, valid):
            with flightrec.operator_scope(name), flightrec.phase("wf.fn"):
                return valid & jax.vmap(self.fn)(payload)

        self._jit_step = wf_jit(step, op_name=name)

    def _step(self, batch: DeviceBatch) -> DeviceBatch:
        new_valid = self._jit_step(batch.payload, batch.valid)
        return DeviceBatch(batch.payload, batch.ts, new_valid,
                           watermark=batch.watermark, frontier=batch.frontier,
                           size=None,  # survivor count unknown until observed
                           ts_max=batch.ts_max, ts_min=batch.ts_min)


def _segmented_reduce(keys, payload, ts, valid, comb, capacity):
    """Sorted segmented reduce: the XLA expression of the reference's
    ``Extract_Keys_Kernel`` → ``thrust::sort_by_key`` → ``thrust::reduce_by_key``
    pipeline (``reduce_gpu.hpp:227-258``).

    Invalid lanes get a sentinel sort key so they sort behind every real
    segment; the sort lane is int64 so the sentinel lies OUTSIDE the int32
    key space (an actual key of INT32_MAX must not be mistaken for padding
    and dropped).  Returns (distinct_keys, combined_payload, seg_ts,
    out_valid) with the distinct-key results left-compacted to the front of
    the batch."""
    sentinel = jnp.int64(1) << 32
    skeys = jnp.where(valid, keys.astype(jnp.int64), sentinel)
    order = jnp.argsort(skeys)
    skeys = skeys[order]
    spayload = jax.tree.map(lambda a: a[order], payload)
    sts = ts[order]

    starts = jnp.concatenate([jnp.array([True]), skeys[1:] != skeys[:-1]])

    def op(a, b):
        # Segmented-scan monoid: if b opens a new segment, the running value
        # resets to b; otherwise it folds comb(a, b).
        fa, pa, ta = a
        fb, pb, tb = b
        combined = comb(pa, pb)
        p = jax.tree.map(
            lambda c, vb: jnp.where(_bshape(fb, c), vb, c), combined, pb)
        t = jnp.where(fb, tb, jnp.maximum(ta, tb))
        return (fa | fb, p, t)

    _, scanned_payload, scanned_ts = jax.lax.associative_scan(
        op, (starts, spayload, sts))

    # segment ends = positions where the next key differs
    ends = jnp.concatenate([skeys[:-1] != skeys[1:], jnp.array([True])])
    ends = ends & (skeys != sentinel)
    # compact segment results to the front
    dest = jnp.cumsum(ends) - 1
    n_out = ends.sum()
    scatter_idx = jnp.where(ends, dest, capacity - 1)

    def compact(a):
        out = jnp.zeros((capacity,) + a.shape[1:], a.dtype)
        out = out.at[scatter_idx].set(
            jnp.where(_bshape(ends, a), a, jnp.zeros_like(a)))
        return out

    out_payload = jax.tree.map(compact, scanned_payload)
    out_keys = compact(skeys)
    out_ts = compact(scanned_ts)
    out_valid = jnp.arange(capacity) < n_out
    return out_keys, out_payload, out_ts, out_valid


def _bshape(mask, ref):
    """Broadcast a [B] bool mask against a [B, ...] leaf."""
    return mask.reshape(mask.shape + (1,) * (ref.ndim - 1))


class ReduceTPUReplica(_TPUReplica):
    pass


class ReduceTPU(Operator):
    """Per-batch associative reduce on device (reference ``Reduce_GPU``,
    ``reduce_gpu.hpp:107-315``): keyed batches shrink to one combined record
    per distinct key; non-keyed batches to a single record.  ``comb`` must be
    associative (the reference requires the same for Thrust).  Cross-batch
    rolling aggregation is the job of windows, exactly as in the reference
    where ``Reduce_GPU`` is also per-batch.

    The key extractor of a keyed TPU operator must be JAX-traceable and
    return an integer: keys are extracted *inside* the compiled program
    (reference: ``Extract_Keys_Kernel`` runs on device too,
    ``reduce_gpu.hpp:227``), so the extraction fuses with the sort/scan and
    works identically whether the batch arrived from a host staging edge or a
    TPU→TPU edge."""

    replica_class = ReduceTPUReplica
    chain_role = "tail"
    snapshot_kind = "reduce_tpu"
    #: drop counters + remap: shard-shape independent
    snapshot_shapeless = True

    def __init__(self, comb: Callable[[Any, Any], Any],
                 name: str = "reduce_tpu", parallelism: int = 1,
                 key_extractor=None, max_keys: Optional[int] = None,
                 sum_like: bool = False,
                 monoid: Optional[str] = None) -> None:
        routing = RoutingMode.KEYBY if key_extractor is not None \
            else RoutingMode.FORWARD
        super().__init__(name, parallelism, routing=routing, is_tpu=True,
                         key_extractor=key_extractor)
        self.comb = comb
        # Bound of the dense key space [0, max_keys) for the dense
        # tables: required on the mesh (cross-chip partials), optional on
        # a single chip where an UNDECLARED reduce sorts arbitrary int32
        # keys.  A declared monoid ("sum" | "max" | "min"; legacy
        # sum_like=True means "sum") lets the cross-chip combine ride one
        # reduce collective (psum/pmax/pmin) instead of all_gather +
        # fold, and — together with max_keys — replaces the single-chip
        # sort/scan with one scatter-combine pass (_get_dense_step).
        self.max_keys = max_keys
        from windflow_tpu.windows.ffat_kernels import resolve_monoid
        try:
            self.monoid = resolve_monoid(sum_like, monoid)
        except ValueError as e:
            raise WindFlowError(str(e)) from None
        self._jit_steps = {}
        # dense-key variant (withMaxKeys): the cross-chip partial tables
        # are compiled for one batch capacity — build-time capacity check
        if max_keys is not None:
            self.fixed_capacity_label = "ReduceTPU[withMaxKeys]"
        # device scalar accumulating dense-table key drops — mesh path
        # and the single-chip declared-monoid path alike (tuples whose
        # key falls outside [0, max_keys) cannot live in the dense
        # tables); read lazily at stats time, never on the step path
        self._mesh_dropped = None
        # one-time drop warning for the single-chip dense path (ADVICE
        # r5): adding withMaxKeys + withMonoidCombiner for speed silently
        # switches semantics from the sorted path (keeps arbitrary int32
        # keys) to the dense-table contract (out-of-range keys dropped) —
        # surface the first observed drop loudly.  The cadence check reads
        # a device scalar enqueued 64 steps earlier (same lazy-read trick
        # as the FFAT regrow checkpoint), so the hot path never syncs.
        # RETIRED under key compaction (PR 11): the compacted step routes
        # out-of-range keys to the overflow/sorted lane instead of
        # dropping them, so this path only exists for the
        # WF_TPU_KEY_COMPACTION=0 kill switch.
        self._drop_warned = False
        self._drop_steps = 0
        self._pending_drop = None
        # device-side key compaction (parallel/compaction.py): the
        # accumulated hit/miss/candidate state threaded through the
        # compacted step as one donated operand; _compactor itself is
        # attached by the graph build (None = one check per batch)
        self._cstats = None

    def enable_compaction(self, comp) -> None:
        """Attach a KeyCompactor (graph build, Config.key_compaction):
        declared-monoid reduces over UNDECLARED int32 key spaces run the
        dense scatter-combine path through the remap table, with the
        cold tail on the sorted lane of the same program; declared
        ``withMaxKeys`` reduces reroute out-of-range keys to that lane
        instead of dropping them."""
        self._compactor = comp
        comp.register_device_stats(lambda: self._cstats)

    def _get_step(self, capacity: int, probe_batch=None):
        step = self._jit_steps.get(capacity)
        if step is None:
            comb = self.comb
            key_fn = self.key_extractor
            prelude = self._fused_prelude

            def step(keys, payload, ts, valid):
                if prelude is not None:
                    # whole-chain fusion (windflow_tpu/fusion): the
                    # stateless members run inside this same program.
                    # Any edge-attached keys describe the PRE-chain
                    # records — extraction must rerun on the chain's
                    # output, below, in-program.
                    payload, valid = prelude(payload, valid)
                    keys = None
                return reduce(keys, payload, ts, valid)

            @flightrec.operator_scope(self.name)
            def reduce(keys, payload, ts, valid):
                if keys is None:
                    if key_fn is not None:
                        with flightrec.phase("wf.fn"):
                            keys = jax.vmap(key_fn)(payload) \
                                .astype(jnp.int32)
                    else:
                        # Non-keyed: one global segment (thrust::reduce path).
                        keys = jnp.zeros(capacity, dtype=jnp.int32)
                with flightrec.phase("wf.reduce"):
                    return _segmented_reduce(keys, payload, ts, valid, comb,
                                             capacity)

            # staged-fed fused chain: the sorted reduce's outputs are
            # capacity-shaped like its inputs, so donating the (provably
            # unshared — fusion/executor.input_donation_safe) batch
            # lanes lets XLA write them in place — provided the prelude
            # preserves each lane's spec (donation_aliases_cleanly on
            # the first batch's shapes); the dense path's [K] tables
            # alias nothing and stay non-donated
            donate = ()
            if self._fused_donate_inputs and probe_batch is not None:
                from windflow_tpu.fusion.executor import \
                    donation_aliases_cleanly
                if donation_aliases_cleanly(
                        lambda p, t, v: step(None, p, t, v),
                        probe_batch.payload, probe_batch.ts,
                        probe_batch.valid):
                    donate = (1, 2, 3)
            step = wf_jit(step, op_name=self._fused_name or self.name,
                          donate_argnums=donate)
            self._jit_steps[capacity] = step
        return step

    def _get_dense_step(self, capacity: int):
        """Single-chip declared-monoid fast path (requires ``withMaxKeys``
        + ``withMonoidCombiner``): ONE scatter-combine pass builds the
        dense ``[K]`` distinct-key table — no sort, no segmented scan —
        exactly the per-chip half of the mesh path
        (parallel/mesh._dense_keyed_partial) without the collective.  The
        reference pays ``thrust::sort_by_key`` + ``reduce_by_key`` for
        every combiner (``reduce_gpu.hpp:227-258``); a declared monoid
        makes the grouping unnecessary.  Out-of-range keys cannot live in
        the dense table: they are dropped and counted, the same
        ``withMaxKeys`` key-space contract the mesh path enforces
        (single-chip UNDECLARED reduces still sort arbitrary int32
        keys)."""
        step = self._jit_steps.get(("dense", capacity))
        if step is None:
            from windflow_tpu.kernels import resolve_pallas_for
            from windflow_tpu.windows.ffat_kernels import (_monoid_identity,
                                                           _monoid_scatter)
            # non-keyed: one global segment, K=1 (the mesh contract,
            # _get_sharded_step) — not a max_keys-lane batch with one row
            K = self.max_keys if self.key_extractor is not None else 1
            monoid = self.monoid
            key_fn = self.key_extractor
            prelude = self._fused_prelude
            # Pallas segmented reduce (windflow_tpu/kernels): the dense
            # slot tables build in one tiled masked-fold kernel traced
            # into this same program; leaves outside the kernel's
            # shape/dtype gates keep the lax scatter (per-leaf routing
            # — values identical either way)
            pallas = resolve_pallas_for(self)

            def step(keys, payload, ts, valid):
                if prelude is not None:
                    # fused chain: see _get_step — the prelude runs here
                    # and keys re-extract from its output
                    payload, valid = prelude(payload, valid)
                    keys = None
                return reduce(keys, payload, ts, valid)

            @flightrec.operator_scope(self.name)
            def reduce(keys, payload, ts, valid):
                if keys is None:
                    with flightrec.phase("wf.fn"):
                        keys = jax.vmap(key_fn)(payload).astype(jnp.int32) \
                            if key_fn is not None \
                            else jnp.zeros(capacity, jnp.int32)
                with flightrec.phase("wf.reduce"):
                    return tables(keys, payload, ts, valid)

            def tables(keys, payload, ts, valid):
                in_range = (keys >= 0) & (keys < K)
                ok = valid & in_range
                n_drop = jnp.sum(valid & ~in_range, dtype=jnp.int64)
                row = jnp.where(ok, keys, K)

                def scat(leaf):
                    ident = _monoid_identity(monoid, leaf.dtype)
                    buf = jnp.full((K + 1,) + leaf.shape[1:], ident,
                                   leaf.dtype)
                    return _monoid_scatter(buf.at[row], monoid)(
                        jnp.where(_bshape(ok, leaf), leaf, ident))[:K]

                def lax_ts():
                    return jnp.full(K + 1, -1, jnp.int64).at[row].max(
                        jnp.where(ok, ts, jnp.int64(-1)))[:K]

                routed = None
                if pallas is not None:
                    from windflow_tpu import kernels as pk
                    routed = pk.routed_monoid_tables(
                        row, payload, monoid, K, pallas.interpret,
                        lax_leaf=scat, ts=ts, ts_init=-1,
                        lax_ts=lax_ts, want_count=True)
                if routed is not None:
                    table, ts_t, cnt = routed
                    has = cnt > 0
                else:
                    table = jax.tree.map(scat, payload)
                    ts_t = lax_ts()
                    has = jnp.zeros(K + 1, bool).at[row].set(True)[:K]
                return table, ts_t, has, n_drop

            step = wf_jit(step,
                          op_name=f"{self._fused_name or self.name}.dense")
            self._jit_steps[("dense", capacity)] = step
        return step

    def _get_compacted_step(self, capacity: int):
        """Compacted keyed reduce (parallel/compaction.py): remapped hot
        keys scatter-combine into the dense slot table, the cold tail
        runs the sorted lane, and the rank-merged output is bit-identical
        to the sorted path's — one program, zero extra dispatches.  Also
        the declared-``withMaxKeys`` variant (``bounded``): the identity
        remap plus the overflow lane that retires the PR 1 silent-drop
        path."""
        step = self._jit_steps.get(("compact", capacity))
        if step is None:
            from windflow_tpu.kernels import resolve_pallas_for
            from windflow_tpu.parallel import compaction
            bounded = self.max_keys is not None
            step = compaction.make_compacted_reduce(
                capacity,
                self.max_keys if bounded else self._compactor.slots,
                self.monoid, self.comb, self.key_extractor,
                self._fused_prelude, bounded,
                pallas=resolve_pallas_for(self), owner=self.name)
            # the donated operand is the cstats state (last arg); the
            # remap tables are read-only operands shared across steps
            donate = (4,) if bounded else (6,)
            step = wf_jit(step,
                          op_name=f"{self._fused_name or self.name}"
                                  ".compact",
                          donate_argnums=donate)
            self._jit_steps[("compact", capacity)] = step
        return step

    def _get_sharded_step(self, capacity: int):
        step = self._jit_steps.get(("mesh", capacity))
        if step is None:
            from windflow_tpu.parallel.mesh import (
                make_sharded_reduce_arbitrary, make_sharded_reduce_step)
            K = self.max_keys if self.key_extractor is not None else 1
            if K is None:
                # Arbitrary int32 keys: hash-shard lanes to their owner
                # chip with one all_to_all, then per-chip sort/reduce — no
                # dense table bound, nothing dropped (reference
                # reduce_gpu.hpp:227-258 arbitrary-key path).  withMaxKeys
                # remains the faster dense/psum variant for bounded keys.
                step = make_sharded_reduce_arbitrary(
                    self.mesh, capacity, self.comb, self.key_extractor,
                    op_name=f"{self.name}.mesh", owner=self.name,
                    # key compaction (parallel/compaction.py): the remap
                    # overrides the owner hash per slot — hot keys
                    # balanced over chips; built before the first batch,
                    # so the cache key needs no variant tag
                    remap=self._compactor is not None)
            else:
                # key-aligned ingest (mesh.mark_aligned_ingest): host
                # pre-placed lanes let each key shard build only its
                # own table rows — the cross-chip table collective
                # disappears (parallel/mesh.py)
                step = make_sharded_reduce_step(
                    self.mesh, capacity, K, self.comb, self.key_extractor,
                    monoid=self.monoid,
                    ingest=getattr(self, "_ingest_mode", None) or "data",
                    op_name=f"{self.name}.mesh", owner=self.name)
            self._jit_steps[("mesh", capacity)] = step
        return step

    def key_space(self) -> Optional[int]:
        # keys-lane plumbing for the shard ledger: the dense-table
        # contract bounds the key space exactly where routing/state do
        return self.max_keys if self.key_extractor is not None else None

    def inlines_prelude(self) -> bool:
        # compacted tails too: their cold tail is the in-program sorted
        # lane, so a slow-to-seed table costs speed, never records
        return True

    def megastep_tail(self):
        if self.monoid is not None and self.max_keys is not None:
            return "reduce_dense", None
        return "reduce_sorted", None

    def num_dropped_tuples(self) -> int:
        if self._mesh_dropped is None:
            return 0
        return int(self._mesh_dropped)  # one device sync, diagnostics only

    # -- durable state (windflow_tpu/durability) -----------------------------
    # ReduceTPU's dense tables are rebuilt per batch (per-batch reduce
    # semantics — cross-batch aggregation is the windows' job), so the
    # only state worth a checkpoint is the accumulated drop counter the
    # stats layer reports.
    def snapshot_state(self):
        blob = {"kind": self.snapshot_kind}
        if self._mesh_dropped is not None:
            blob["dropped"] = int(self._mesh_dropped)
        if self._compactor is not None:
            # the remap table is operator state: a replay must rebuild
            # the same key→slot assignment so hit/miss partitioning (and
            # with it every device counter) evolves identically
            blob["compactor"] = self._compactor.snapshot()
        return blob if len(blob) > 1 else None

    def restore_state(self, blob):
        if "dropped" in blob:
            self._mesh_dropped = jnp.asarray(blob["dropped"], jnp.int64)
        if blob.get("compactor") is not None \
                and self._compactor is not None:
            self._compactor.restore(blob["compactor"])

    def _maybe_warn_drops(self, n_drop: int) -> None:
        """One-time RuntimeWarning the first time the single-chip dense
        path (withMaxKeys + withMonoidCombiner) is SEEN dropping
        out-of-range keys; also noted in dump_stats, mirroring how the
        other silent-drop paths surface through the stats layer."""
        if self._drop_warned or n_drop <= 0 or self.mesh is not None:
            return
        self._drop_warned = True
        import warnings
        warnings.warn(
            f"ReduceTPU '{self.name}': withMaxKeys({self.max_keys}) + "
            "withMonoidCombiner uses the dense-table contract — "
            f"{n_drop} tuple(s) with out-of-range keys (outside "
            f"[0, {self.max_keys})) were dropped and counted in "
            "Out_of_range_keys_dropped; the undeclared sorted path keeps "
            "arbitrary int32 keys", RuntimeWarning, stacklevel=3)

    def dump_stats(self) -> dict:
        st = super().dump_stats()
        comp = self._compactor
        if comp is not None:
            summary = comp.summary()
            st["Key_compaction"] = summary
            if comp.bounded and summary["overflow_tuples"]:
                # compaction absorbed the PR 1 dense-path key drop: keys
                # outside [0, max_keys) were REROUTED to the sorted
                # overflow lane (kept, not dropped) and counted here
                st["Out_of_range_keys_rerouted"] = \
                    summary["overflow_tuples"]
        if self._mesh_dropped is not None:
            dropped = self.num_dropped_tuples()
            st["Out_of_range_keys_dropped"] = dropped
            self._maybe_warn_drops(dropped)
            if self._drop_warned:
                st["Out_of_range_keys_note"] = (
                    "dense-table contract (withMaxKeys + "
                    "withMonoidCombiner): keys outside [0, max_keys) are "
                    "dropped; the undeclared sorted path keeps arbitrary "
                    "int32 keys")
        return st

    def _check_comb_contract(self, payload) -> None:
        """The combiner must return the full record structure — one that
        drops, renames, or restructures fields (e.g. forgets a carried
        'ts' column) cannot fold records associatively.  Checked here, at
        the first batch, so every execution path (single-chip sort/scan,
        mesh dense tables, mesh arbitrary-key all_to_all) gets the clear
        message instead of an opaque pytree mismatch from inside a scan."""
        one = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype), payload)
        out_struct = jax.eval_shape(self.comb, one, one)
        if jax.tree.structure(out_struct) != jax.tree.structure(one):
            if isinstance(one, dict) and isinstance(out_struct, dict) \
                    and sorted(one.keys()) != sorted(out_struct.keys()):
                want, got = sorted(one.keys()), sorted(out_struct.keys())
            else:  # same field names but nested shape differs: treedefs
                want = jax.tree.structure(one)
                got = jax.tree.structure(out_struct)
            raise WindFlowError(
                "ReduceTPU combiner must return the same record structure "
                f"as its inputs (records have {want}, combiner returned "
                f"{got}); carry every field through the combine")
        # Same structure is not enough: a leaf whose shape or dtype drifts
        # (a combiner summing over an axis, or promoting f32→f64) fails
        # later inside the scan with the same opaque mismatch.
        # tree_util spelling: jax.tree.flatten_with_path only exists on
        # jax >= 0.5 and this must run on the 0.4.x floor too
        in_leaves, _ = jax.tree_util.tree_flatten_with_path(one)
        out_leaves = jax.tree.leaves(out_struct)
        for (path, a), b in zip(in_leaves, out_leaves):
            if a.shape != b.shape or a.dtype != b.dtype:
                raise WindFlowError(
                    "ReduceTPU combiner must preserve each field's shape "
                    f"and dtype: field {jax.tree_util.keystr(path) or '.'} "
                    f"is {a.shape}/{a.dtype} in the records but the "
                    f"combiner returned {b.shape}/{b.dtype}")

    def _step(self, batch: DeviceBatch) -> DeviceBatch:
        if not self._jit_steps:
            payload = batch.payload
            if self._fused_prelude is not None:
                # fused chain: the combiner folds the chain's OUTPUT
                # records — contract-check against the post-prelude spec
                # (abstract eval, zero device work)
                from windflow_tpu.fusion.executor import prelude_out_spec
                payload = prelude_out_spec(self._fused_prelude,
                                           batch.payload, batch.valid)
            self._check_comb_contract(payload)
        comp = self._compactor
        if self.mesh is not None:
            # Sharded variant: dense per-chip partials combined over ICI;
            # output is a capacity-max_keys batch of distinct-key records.
            step = self._get_sharded_step(batch.capacity)
            if comp is not None and self.max_keys is None:
                # arbitrary-key mesh reduce with a remap: the owner hash
                # is overridden per slot (hot keys balanced over chips)
                comp.on_batch()
                tk, tsl = comp.tables()
                table, ts_out, has, n_drop = step(
                    batch.payload, batch.ts, batch.valid, tk, tsl)
            else:
                table, ts_out, has, n_drop = step(
                    batch.payload, batch.ts, batch.valid)
            self._mesh_dropped = n_drop if self._mesh_dropped is None \
                else self._mesh_dropped + n_drop
            return DeviceBatch(table, ts_out, has,
                               watermark=batch.watermark, size=None,
                               frontier=batch.frontier)
        if comp is not None and self.monoid is not None \
                and self.key_extractor is not None:
            # compacted path (parallel/compaction.py): dense slots for
            # the remapped hot keys + the sorted lane for the cold tail,
            # in ONE program whose output matches the sorted path
            # record-for-record
            from windflow_tpu.parallel import compaction
            comp.on_batch()
            if self._cstats is None:
                self._cstats = compaction.cstats_init()
            step = self._get_compacted_step(batch.capacity)
            if comp.bounded:
                out_payload, out_ts, out_valid, self._cstats = step(
                    batch.keys, batch.payload, batch.ts, batch.valid,
                    self._cstats)
            else:
                tk, tsl = comp.tables()
                out_payload, out_ts, out_valid, self._cstats = step(
                    batch.keys, batch.payload, batch.ts, batch.valid,
                    tk, tsl, self._cstats)
            return DeviceBatch(out_payload, out_ts, out_valid,
                               watermark=batch.watermark, size=None,
                               frontier=batch.frontier)
        if self.monoid is not None and self.max_keys is not None:
            # declared-monoid dense table: same output contract as the
            # mesh branch (capacity-max_keys batch of distinct-key
            # records in ascending key order — the order the sorted path
            # also emits)
            table, ts_out, has, n_drop = self._get_dense_step(
                batch.capacity)(batch.keys, batch.payload,
                                batch.ts, batch.valid)
            self._mesh_dropped = n_drop if self._mesh_dropped is None \
                else self._mesh_dropped + n_drop
            # lazy drop check on a 64-step cadence: inspects the counter
            # enqueued one cadence AGO (long executed — no sync stall)
            self._drop_steps += 1
            if not self._drop_warned and self._drop_steps % 64 == 0:
                prev = self._pending_drop
                self._pending_drop = self._mesh_dropped
                if prev is not None:
                    self._maybe_warn_drops(int(prev))
            return DeviceBatch(table, ts_out, has,
                               watermark=batch.watermark, size=None,
                               frontier=batch.frontier)
        out_keys, out_payload, out_ts, out_valid = \
            self._get_step(batch.capacity, batch)(batch.keys,
                                                  batch.payload,
                                                  batch.ts, batch.valid)
        return DeviceBatch(out_payload, out_ts, out_valid,
                           watermark=batch.watermark, size=None,
                           frontier=batch.frontier)
