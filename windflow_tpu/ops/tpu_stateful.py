"""Stateful keyed TPU operators: per-key mutable state on device.

Re-design of the reference's stateful GPU paths:

* ``Map_GPU`` stateful kernel — one CUDA worker per distinct key walks the
  batch's per-key index chain applying ``fn(tuple, state)`` in arrival order
  (``map_gpu.hpp:78-102``); state lives in a shared
  ``tbb::concurrent_unordered_map<key, wrapper_state_t>`` guarded by a
  spinlock that serializes stateful kernels across replicas
  (``map_gpu.hpp:114-115,278-295``).
* ``Filter_GPU`` stateful kernel — same walk, predicate + state update
  (``filter_gpu.hpp:119``).

TPU mapping (SURVEY.md §7 "hard parts": dense key-slot tables, host-managed
key→slot assignment):

1. **Key→slot interning on host.**  The state table is a dense pytree of
   ``[num_key_slots, ...]`` device arrays.  Per batch, the distinct keys are
   pulled to host (a tiny D2H — the reference does exactly this with
   ``dist_keys_cpu``, ``keyby_emitter_gpu.hpp:519-583``) and interned into
   dense slot ids by a Python dict, replacing the reference's device-pointer
   hash map with index arithmetic XLA can compile.
2. **Rank-wavefront in-order apply.**  The reference's "one worker per key
   walks its chain" becomes: stable-sort lanes by slot, compute each lane's
   *rank* (occurrence index within its key), then loop rank = 0..max_rank.
   Each wavefront step applies ``vmap(fn)`` to every lane at that rank —
   lanes at the same rank hold **distinct keys by construction**, so the
   state gather/scatter is conflict-free and fully parallel.  The loop depth
   is the max per-key multiplicity in the batch (typically ≪ capacity), the
   TPU analogue of the CUDA chain-walk's depth.
3. **Shared state, serialized.**  The table lives on the *operator*, not the
   replica; the host driver dispatches batches one at a time, so cross-replica
   state access is serialized by construction — the role of the reference's
   spinlock.

Stateful function signatures (the in-place C++ references become returns):

* map: ``fn(record, state) -> (new_record, new_state)``
* filter: ``fn(record, state) -> (keep_bool, new_state)``
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from windflow_tpu.basic import RoutingMode, WindFlowError
from windflow_tpu.batch import DeviceBatch
from windflow_tpu.monitoring import recorder as flightrec
from windflow_tpu.monitoring.jit_registry import wf_jit
from windflow_tpu.ops.base import Operator
from windflow_tpu.ops.tpu import _TPUReplica, _bshape
from windflow_tpu.parallel.emitters import KeyInterner
from windflow_tpu.utils.dtypes import cast_state_update as _cast_update
from windflow_tpu.windows.grouping import auto_order, invert_perm

_KEY_SENTINEL = np.int32(2**31 - 1)


def _broadcast_state(proto, num_slots: int):
    """Materialize the [S, ...] state table from one per-key prototype."""
    def rep(x):
        a = jnp.asarray(x)
        return jnp.repeat(a[None], num_slots, axis=0)
    return jax.tree.map(rep, proto)


def _wavefront_body(fn: Callable, capacity: int,
                    num_slots: int, is_filter: bool):
    """Per-batch program body: rank-wavefront stateful apply over resolved
    dense slot ids (``slots``; lanes with slot >= num_slots are ignored)."""

    def body_fn(state, payload, valid, slots):
        # Stable sort by slot: arrival order is preserved within each key —
        # the ordering guarantee of the reference's per-key chain walk.
        sort_key = jnp.where(valid & (slots < num_slots), slots,
                             jnp.int32(num_slots))
        order = auto_order(sort_key, num_slots + 1)
        s_slots = sort_key[order]
        s_valid = valid[order]
        s_payload = jax.tree.map(lambda a: a[order], payload)

        # rank[i] = occurrence index of lane i within its key segment
        idx = jnp.arange(capacity, dtype=jnp.int32)
        starts = jnp.concatenate(
            [jnp.ones(1, bool), s_slots[1:] != s_slots[:-1]])
        seg_start = jax.lax.associative_scan(
            jnp.maximum, jnp.where(starts, idx, jnp.int32(0)))
        rank = idx - seg_start
        max_rank = jnp.max(jnp.where(s_valid, rank, jnp.int32(0)))

        gather_slots = jnp.clip(s_slots, 0, num_slots - 1)

        # Each lane is applied exactly once (at its own rank), so fn always
        # reads the ORIGINAL sorted payload; results accumulate into a
        # separate output carry — whose pytree structure may differ from the
        # input's (a stateful map may add/drop record fields, unlike the
        # reference's in-place C++ tuples).
        if is_filter:
            out0 = jnp.ones(capacity, bool)
        else:
            res_shape, _ = jax.eval_shape(
                jax.vmap(fn), s_payload,
                jax.tree.map(lambda a: a[gather_slots], state))
            out0 = jax.tree.map(
                lambda sd: jnp.zeros(sd.shape, sd.dtype), res_shape)

        def body(carry):
            r, st, out = carry
            mask = (rank == r) & s_valid
            cur = jax.tree.map(lambda a: a[gather_slots], st)
            res, new_st = jax.vmap(fn)(s_payload, cur)
            if is_filter:
                out = jnp.where(mask, res, out)
            else:
                out = jax.tree.map(
                    lambda o, v: jnp.where(_bshape(mask, o), v, o), out, res)
            # Conflict-free scatter: within one rank all slots are distinct.
            # Masked-out lanes scatter to index num_slots → dropped (XLA
            # drops out-of-bounds scatter updates under jit).
            scat = jnp.where(mask, s_slots, jnp.int32(num_slots))
            st = jax.tree.map(
                lambda a, u: a.at[scat].set(_cast_update(u, a.dtype),
                                            mode="drop"),
                st, new_st)
            return r + 1, st, out

        _, state, s_out = jax.lax.while_loop(
            lambda c: c[0] <= max_rank, body, (jnp.int32(0), state, out0))

        inv = invert_perm(order)
        if is_filter:
            new_valid = valid & s_out[inv]
            return state, payload, new_valid
        out_payload = jax.tree.map(lambda a: a[inv], s_out)
        return state, out_payload, valid

    return body_fn


def _assoc_body(lift: Callable, comb: Callable, project: Callable,
                capacity: int, num_slots: int, is_filter: bool):
    """Log-depth alternative to the wavefront for *associative* state
    updates (``state' = comb(state, lift(record))``): a segmented inclusive
    scan folds each key's contributions in arrival order, so a single-hot-key
    batch costs the same as a uniform one — the wavefront's depth equals the
    max per-key multiplicity, which degrades to ``capacity`` sequential
    sweeps under skew (reference has no analogue: its per-key CUDA chain
    walk is inherently sequential, ``map_gpu.hpp:78-102``).

    ``project(record, state_incl)`` sees the state *including* the record's
    own contribution (rolling-reduce semantics, like the reference's CPU
    ``Reduce`` emitting the updated state per input, ``reduce.hpp:58-176``);
    for filters it returns the keep bool."""

    def body_fn(state, payload, valid, slots):
        sort_key = jnp.where(valid & (slots < num_slots), slots,
                             jnp.int32(num_slots))
        order = auto_order(sort_key, num_slots + 1)
        s_slots = sort_key[order]
        s_valid = valid[order]
        s_payload = jax.tree.map(lambda a: a[order], payload)

        lifts = jax.vmap(lift)(s_payload)
        starts = jnp.concatenate(
            [jnp.ones(1, bool), s_slots[1:] != s_slots[:-1]])

        # segmented inclusive scan of contributions (invalid lanes are all
        # in the trailing sentinel segment, so no flags needed)
        def op(a, b):
            sa, va = a
            sb, vb = b
            combined = comb(va, vb)
            v = jax.tree.map(
                lambda c, x: jnp.where(_bshape(sb, c), x, c), combined, vb)
            return sa | sb, v

        _, prefix = jax.lax.associative_scan(op, (starts, lifts))

        gather_slots = jnp.clip(s_slots, 0, num_slots - 1)
        init = jax.tree.map(lambda a: a[gather_slots], state)
        state_incl = comb(init, prefix)

        s_out = jax.vmap(project)(s_payload, state_incl)

        # persist each segment's final state (segment-end lanes of real
        # slots; the sentinel segment is dropped by the OOB scatter)
        ends = jnp.concatenate([s_slots[:-1] != s_slots[1:],
                                jnp.ones(1, bool)])
        scat = jnp.where(ends & (s_slots < num_slots), s_slots,
                         jnp.int32(num_slots))
        state = jax.tree.map(
            lambda a, u: a.at[scat].set(_cast_update(u, a.dtype),
                                        mode="drop"),
            state, state_incl)

        inv = invert_perm(order)
        if is_filter:
            return state, payload, valid & s_out[inv]
        out_payload = jax.tree.map(lambda a: a[inv], s_out)
        return state, out_payload, valid

    return body_fn


class _StatefulTPUBase(Operator):
    """Shared machinery: state table + interner on the operator (shared by
    all replicas — reference shares one tbb map across replicas too)."""

    _is_filter = False
    chain_role = "tail"
    #: its re-bucket rule: ``durability/rebucket._rebucket_stateful``
    snapshot_kind = "stateful_tpu"

    @property
    def fixed_capacity_label(self):
        # slot-table programs (and their intern padding) are compiled for
        # one batch capacity; mixed capacities would silently retrace per
        # batch or fail inside the scan — reject the merge at build
        return type(self).__name__

    def __init__(self, fn: Callable, initial_state: Any, name: str,
                 parallelism: int, key_extractor: Callable,
                 num_key_slots: int = 4096, dense_keys: bool = False,
                 assoc: Optional[tuple] = None) -> None:
        if key_extractor is None:
            raise WindFlowError(
                f"stateful TPU operator '{name}' requires a key extractor "
                "(reference: stateful Map_GPU/Filter_GPU are keyed-only)")
        super().__init__(name, parallelism, routing=RoutingMode.KEYBY,
                         is_tpu=True, key_extractor=key_extractor)
        self.fn = fn
        self.num_key_slots = num_key_slots
        #: dense_keys: the extractor already returns slot ids in
        #: [0, num_key_slots) — skip host interning entirely, so the step is
        #: one fully-async device program with no per-batch D2H sync
        #: (out-of-range keys are masked invalid, like FfatWindowsTPU)
        self.dense_keys = dense_keys
        #: assoc: (lift, comb, project) declares the state update
        #: associative — the log-depth segmented-scan body replaces the
        #: wavefront (skew-proof); ``fn`` is ignored when set
        self.assoc = assoc
        self._state = _broadcast_state(initial_state, num_key_slots)
        self._interner = KeyInterner()
        self._extract = None
        self._steps = {}   # per-capacity program cache
        # device-side key compaction (parallel/compaction.py): when the
        # graph attaches a compactor (host-staged edges only), slots
        # resolve IN-PROGRAM through the remap table and the per-batch
        # D2H intern sync disappears; _cstats is the donated hit/miss
        # state threaded through that program
        self._cstats = None

    def enable_compaction(self, comp) -> None:
        """Attach a pinned KeyCompactor (graph build): the device-resident
        interner.  Keys are admitted host-side at the staging boundary
        (every key has a slot before its batch ships), the step resolves
        slots with one in-program searchsorted, and the table raises on
        overflow exactly like ``withNumKeySlots`` interning."""
        self._compactor = comp
        comp.register_device_stats(lambda: self._cstats)

    def _adopt_compactor_mapping(self) -> None:
        """Fallback after compactor deactivation (a speculative host
        observation failed): fold the remap's key→slot dict into the
        interner — slots were assigned contiguously in admission order,
        so the intern path continues indexing the same state rows."""
        comp, self._compactor = self._compactor, None
        self._interner._ids.update(comp.export_mapping())

    # -- host-managed key→slot assignment -----------------------------------
    def _intern(self, uniq: np.ndarray) -> np.ndarray:
        interner = self._interner
        slots = np.empty(len(uniq), np.int32)
        for i, k in enumerate(uniq):
            slots[i] = interner.intern(int(k))
        if len(interner) > self.num_key_slots:
            raise WindFlowError(
                f"operator '{self.name}': distinct keys exceed "
                f"num_key_slots={self.num_key_slots}; raise it via "
                "withNumKeySlots")
        return slots

    def _body(self, capacity: int):
        return self._body_factory()(capacity, self.num_key_slots)

    def _body_factory(self):
        """(capacity, num_slots) -> per-batch body; the mesh layer calls it
        with the per-shard slot count."""
        if self.assoc is not None:
            lift, comb, project = self.assoc
            return lambda cap, S: _assoc_body(lift, comb, project, cap, S,
                                              self._is_filter)
        return lambda cap, S: _wavefront_body(self.fn, cap, S,
                                              self._is_filter)

    def _get_sharded_step(self, capacity: int):
        step = self._steps.get(("mesh", capacity))
        if step is None:
            from windflow_tpu.parallel.mesh import (make_sharded_stateful_step,
                                                    state_sharding)
            step = make_sharded_stateful_step(
                self.mesh, capacity, self.num_key_slots,
                self._body_factory(), self.key_extractor, self.dense_keys,
                self._is_filter,
                # key-aligned ingest (mesh.mark_aligned_ingest): lanes
                # arrive pre-placed on their slot-owner's column — no
                # data-axis all_gather, no psum lane merge
                ingest=getattr(self, "_ingest_mode", None) or "data",
                op_name=f"{self.name}.mesh", owner=self.name)
            # shard the state table along the key axis on first use
            self._state = jax.device_put(self._state,
                                         state_sharding(self.mesh))
            self._steps[("mesh", capacity)] = step
        return step

    def _get_step(self, capacity: int):
        step = self._steps.get(capacity)
        if step is None:
            body = self._body(capacity)
            key_fn = self.key_extractor
            S = self.num_key_slots
            prelude = self._fused_prelude
            if prelude is not None and not self.dense_keys:
                # the fusion planner only selects dense-key tails
                # (``inlines_prelude``)
                raise WindFlowError(
                    f"stateful operator '{self.name}': whole-chain "
                    "fusion requires withDenseKeys")
            if self.dense_keys:
                # slot = key, resolved inside the one compiled program: the
                # whole step is async device work, no host round-trip
                def step(state, payload, valid, keys):
                    if prelude is not None:
                        # fused chain: the stateless members run inside
                        # this program; edge-attached keys describe the
                        # PRE-chain records — re-extract from its output
                        payload, valid = prelude(payload, valid)
                        keys = None
                    return on_slots(state, payload, valid, keys)

                @flightrec.operator_scope(self.name)
                def on_slots(state, payload, valid, keys):
                    if keys is None:
                        with flightrec.phase("wf.fn"):
                            keys = jax.vmap(key_fn)(payload) \
                                .astype(jnp.int32)
                    with flightrec.phase("wf.state"):
                        ok = valid & (keys >= 0) & (keys < S)
                        return body(state, payload, ok, keys)
            else:
                @flightrec.operator_scope(self.name)
                @flightrec.phase("wf.state")
                def step(state, payload, valid, keys, uniq_keys, uniq_slots):
                    pos = jnp.clip(jnp.searchsorted(uniq_keys, keys),
                                   0, capacity - 1)
                    return body(state, payload, valid, uniq_slots[pos])
            step = wf_jit(step, op_name=self._fused_name or self.name,
                          donate_argnums=(0,))
            self._steps[capacity] = step
        return step

    def _get_compact_step(self, capacity: int):
        """Compacted slot resolution (parallel/compaction.py): the remap
        tables ride the program as read-only operands and the whole step
        stays one fully-async dispatch — no per-batch intern sync.  Miss
        lanes (possible only for keys the host admission never saw) are
        masked invalid and counted, the dense-key out-of-range
        contract."""
        step = self._steps.get(("compact", capacity))
        if step is None:
            from windflow_tpu.parallel import compaction
            body = self._body(capacity)
            key_fn = self.key_extractor

            @flightrec.operator_scope(self.name)
            def step(state, payload, valid, keys, tk, tsl, cst):
                if keys is None:
                    with flightrec.phase("wf.fn"):
                        keys = jax.vmap(key_fn)(payload).astype(jnp.int32)
                with flightrec.phase("wf.state"):
                    slots, hit = compaction.lookup_slots(tk, tsl, keys,
                                                         valid)
                    cst = compaction.cstats_update(cst, keys, hit,
                                                   valid & ~hit)
                    st, out, ov = body(state, payload, hit, slots)
                return st, out, ov, cst

            step = wf_jit(step, op_name=self._fused_name or self.name,
                          donate_argnums=(0, 6))
            self._steps[("compact", capacity)] = step
        return step

    def inlines_prelude(self) -> bool:
        # host-interning tables are excluded: their key intern reads
        # distinct keys back to host BEFORE the step, which would need
        # the prelude's output mid-chain — a second dispatch, defeating
        # fusion
        return bool(self.dense_keys)

    def megastep_tail(self):
        if not self.dense_keys:
            return None, ("host-interning stateful (per-batch D2H "
                          "intern sync; declare withDenseKeys)")
        return "stateful", None

    def key_space(self):
        # keys-lane plumbing for the shard ledger: dense extractors are
        # bounded by the slot table; interned key spaces are unbounded
        # (the intern map assigns slots in arrival order, so slot ids
        # say nothing about the user's key distribution)
        return self.num_key_slots if self.dense_keys else None

    # -- durable state (windflow_tpu/durability) -----------------------------
    def snapshot_state(self):
        """The dense ``[num_key_slots, ...]`` state table plus the host
        key→slot intern map (the two halves of per-key device state: the
        values AND where each key lives).  The table exists from
        construction, so this snapshots even before the first batch —
        restore then simply re-seeds the same initial table."""
        return {
            "kind": self.snapshot_kind,
            "state": jax.tree.map(np.asarray, self._state),
            "interner": dict(self._interner._ids),
            # compacted runs: the remap IS the key→slot half of per-key
            # state — restored so replays index the same table rows
            "compactor": (self._compactor.snapshot()
                          if self._compactor is not None else None),
        }

    def restore_state(self, blob):
        if self.mesh is not None:
            # multi-chip restore: the slot table lives key-sharded (slot
            # ranges per chip) — re-place the host blob in that layout;
            # the table's logical content is shard-shape independent, so
            # a rescale restore needs nothing but this placement
            from windflow_tpu.parallel.mesh import state_sharding
            sh = state_sharding(self.mesh)
            self._state = jax.tree.map(
                lambda a: jax.device_put(jnp.asarray(a), sh),
                blob["state"])
        else:
            self._state = jax.tree.map(jnp.asarray, blob["state"])
        self._interner._ids = dict(blob["interner"])
        cblob = blob.get("compactor")
        if cblob is not None and self._compactor is not None:
            self._compactor.restore(cblob)
        elif cblob is not None:
            # checkpoint taken under key compaction, restored with the
            # plane OFF: the remap's key→slot dict is the key half of
            # per-key state — fold it into the host interner so the
            # restored table rows keep meaning the same keys (slots
            # were assigned contiguously, the intern contract)
            self._interner._ids.update(
                {int(k): int(v) for k, v in cblob["key_slot"].items()})
        elif self._compactor is not None and self._interner._ids:
            # checkpoint taken WITHOUT compaction, restored with the
            # plane ON: the restored interner owns the state rows — a
            # fresh remap would assign CONFLICTING slots, so the
            # operator keeps the host-interning path
            self._compactor.deactivate()
            self._compactor = None

    def _stateful_step(self, batch: DeviceBatch):
        cap = batch.capacity
        if self.mesh is not None:
            return self._sharded_stateful_step(batch)
        if self.dense_keys:
            # no interning: dispatch stays fully asynchronous
            return self._get_step(cap)(self._state, batch.payload,
                                       batch.valid, batch.keys)
        comp = self._compactor
        if comp is not None:
            if not comp.active:
                # a speculative host observation path died: fall back to
                # interning, keeping the slots already assigned
                self._adopt_compactor_mapping()
            else:
                from windflow_tpu.parallel import compaction
                comp.on_batch()
                if self._cstats is None:
                    self._cstats = compaction.cstats_init()
                tk, tsl = comp.tables()
                st, out, ov, self._cstats = self._get_compact_step(cap)(
                    self._state, batch.payload, batch.valid, batch.keys,
                    tk, tsl, self._cstats)
                return st, out, ov
        keys_dev, uniq_keys_dev, uniq_slots_dev = self._intern_batch(batch)
        return self._get_step(cap)(self._state, batch.payload, batch.valid,
                                   keys_dev, uniq_keys_dev, uniq_slots_dev)

    def _intern_batch(self, batch: DeviceBatch):
        """Shared intern/pad block for the single-chip and mesh paths: keys
        are extracted once (reusing a keyby edge's attached key lane); the
        device array feeds the step and its host copy drives interning
        (tiny D2H — parity with the reference's dist_keys_cpu copy at the
        keyby boundary)."""
        cap = batch.capacity
        if self._extract is None:
            key_fn = self.key_extractor

            def extract(payload):
                return jax.vmap(key_fn)(payload).astype(jnp.int32)

            self._extract = wf_jit(extract,
                                   op_name=f"{self.name}.key_extract")
        keys_dev = batch.keys if batch.keys is not None \
            else self._extract(batch.payload)
        with flightrec.wait("keys", batch=batch.seq):
            keys_np = np.asarray(keys_dev)
            valid_np = np.asarray(batch.valid)
        uniq = np.unique(keys_np[valid_np])
        uniq_slots = self._intern(uniq)
        pad = cap - len(uniq)
        uniq_keys_dev = jnp.asarray(
            np.concatenate([uniq.astype(np.int32),
                            np.full(pad, _KEY_SENTINEL, np.int32)]))
        uniq_slots_dev = jnp.asarray(
            np.concatenate([uniq_slots,
                            np.full(pad, self.num_key_slots, np.int32)]))
        return keys_dev, uniq_keys_dev, uniq_slots_dev

    def dump_stats(self) -> dict:
        st = super().dump_stats()
        if self._compactor is not None:
            st["Key_compaction"] = self._compactor.summary()
        return st

    def _sharded_stateful_step(self, batch: DeviceBatch):
        """Mesh path: key-sharded state table, data-sharded batch, one
        psum lane merge (parallel/mesh.py make_sharded_stateful_step)."""
        cap = batch.capacity
        step = self._get_sharded_step(cap)
        if self.dense_keys:
            dummy = self._steps.get(("mesh_dummy", cap))
            if dummy is None:
                dummy = jnp.zeros(cap, jnp.int32)
                self._steps[("mesh_dummy", cap)] = dummy
            return step(self._state, batch.payload, batch.valid, dummy,
                        dummy)
        _, uniq_keys_dev, uniq_slots_dev = self._intern_batch(batch)
        return step(self._state, batch.payload, batch.valid, uniq_keys_dev,
                    uniq_slots_dev)


class StatefulMapTPUReplica(_TPUReplica):
    pass


class StatefulMapTPU(_StatefulTPUBase):
    """Keyed stateful map on device (reference stateful ``Map_GPU``,
    ``map_gpu.hpp:78-102,104-433``): ``fn(record, state) -> (record, state)``
    applied to each key's tuples in arrival order."""

    replica_class = StatefulMapTPUReplica
    _is_filter = False

    def __init__(self, fn, initial_state, name: str = "map_tpu",
                 parallelism: int = 1, key_extractor=None,
                 num_key_slots: int = 4096, dense_keys: bool = False,
                 assoc=None) -> None:
        super().__init__(fn, initial_state, name, parallelism, key_extractor,
                         num_key_slots, dense_keys=dense_keys, assoc=assoc)

    def _step(self, batch: DeviceBatch) -> DeviceBatch:
        self._state, out_payload, valid = self._stateful_step(batch)
        # fused chains may filter inside the program: the input count no
        # longer bounds the survivors, so the size is observed lazily
        size = None if self._fused_prelude is not None else batch._size
        return DeviceBatch(out_payload, batch.ts, valid,
                           watermark=batch.watermark, size=size,
                           frontier=batch.frontier)


class StatefulFilterTPUReplica(_TPUReplica):
    pass


class StatefulFilterTPU(_StatefulTPUBase):
    """Keyed stateful filter on device (reference stateful ``Filter_GPU``,
    ``filter_gpu.hpp:119``): ``fn(record, state) -> (keep, state)``; dropped
    tuples leave the validity mask, state updates still apply in order."""

    replica_class = StatefulFilterTPUReplica
    _is_filter = True

    def __init__(self, fn, initial_state, name: str = "filter_tpu",
                 parallelism: int = 1, key_extractor=None,
                 num_key_slots: int = 4096, dense_keys: bool = False,
                 assoc=None) -> None:
        super().__init__(fn, initial_state, name, parallelism, key_extractor,
                         num_key_slots, dense_keys=dense_keys, assoc=assoc)

    def _step(self, batch: DeviceBatch) -> DeviceBatch:
        self._state, out_payload, valid = self._stateful_step(batch)
        return DeviceBatch(out_payload, batch.ts, valid,
                           watermark=batch.watermark, size=None,
                           frontier=batch.frontier)
