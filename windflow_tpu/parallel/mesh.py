"""Multi-chip execution: device meshes, key-sharded window state, and
collective keyed reduction over ICI.

This is the slot the reference fills with thread replication + emitter routing
(SURVEY.md §2.6 item 10: "GPU offload batching … This is the slot where the
TPU backend goes").  Where WindFlow scales an operator by cloning replicas
onto OS threads and hashing keys across lock-free queues
(``keyby_emitter.hpp:216``), the TPU design scales by **sharding over a
device mesh**:

* mesh axes ``("data", "key")`` — ``data`` shards the *tuples* of each staged
  batch (the analogue of replicating stateless operators), ``key`` shards the
  *keyed state space* (the analogue of KEYBY partitioning of stateful
  operators).
* stateless Map/Filter steps run on data-sharded batches with zero
  communication.
* keyed windows (:func:`make_sharded_ffat_step`) keep their dense per-key
  state sharded along ``key``; each key-shard sees the full batch via an
  ``all_gather`` over ``data`` (tuples ride ICI once) and updates only the
  keys it owns; a count window's shard first compacts the batch to the
  lanes it owns and steps over those alone.
* keyed reduction (:func:`make_sharded_keyed_reduce`) computes per-chip
  dense partial tables and combines them across the mesh with ``psum``
  (sum-like combiners) or a gather+fold (arbitrary associative combiners) —
  the ICI expression of the reference's ``thrust::reduce_by_key`` +
  inter-replica merge.

All collectives are XLA collectives over the mesh (``psum``/``all_gather``);
on real hardware they ride ICI, multi-host meshes extend over DCN with the
same program (the driver validates this path on a virtual CPU mesh).
"""

from __future__ import annotations

import math
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from windflow_tpu.basic import WindFlowError
from windflow_tpu.batch import DeviceBatch, HostBatch, host_to_device
from windflow_tpu.monitoring.jit_registry import wf_jit
from windflow_tpu.monitoring.recorder import operator_scope, phase
from windflow_tpu.windows.ffat_kernels import (TB_SCALARS, _b,
                                           _masked_reduce_last,
                                           _monoid_identity, _seg_scan,
                                           make_ffat_flush,
                                           make_ffat_state, make_ffat_step,
                                           make_ffat_tb_state,
                                           make_ffat_tb_step,
                                           monoid_collective,
                                           resolve_monoid)
from windflow_tpu.windows.grouping import auto_order

DATA_AXIS = "data"
KEY_AXIS = "key"


def make_mesh(n_devices: Optional[int] = None, data: int = 1,
              devices: Optional[Sequence] = None) -> Mesh:
    """Create a ``(data, key)`` mesh over the first ``n_devices`` devices.

    ``data`` fixes the data-parallel extent; the key axis takes the rest.
    With ``data=1`` the mesh degenerates to pure key sharding (the keyed
    Reduce/FFAT scaling configuration from BASELINE.json)."""
    devs = list(devices) if devices is not None else list(jax.devices())
    if n_devices is not None:
        if len(devs) < n_devices:
            raise WindFlowError(
                f"requested {n_devices} devices, only {len(devs)} visible")
        devs = devs[:n_devices]
    n = len(devs)
    if n % data != 0:
        raise WindFlowError(f"{n} devices not divisible by data={data}")
    arr = np.array(devs).reshape(data, n // data)
    return Mesh(arr, (DATA_AXIS, KEY_AXIS))


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding for staged batch lanes: tuples split along ``data``,
    replicated along ``key``."""
    return NamedSharding(mesh, P(DATA_AXIS))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def state_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding for dense per-key state tables: split along ``key``."""
    return NamedSharding(mesh, P(KEY_AXIS))


def stage_batch(hb: HostBatch, capacity: int, mesh: Mesh) -> DeviceBatch:
    """Host→mesh staging: pad to ``capacity`` and lay tuples out data-sharded
    (the multi-chip form of the reference's pinned-staging H2D path)."""
    return host_to_device(hb, capacity=capacity,
                          device=batch_sharding(mesh))


def _aligned_slot_bound(op) -> Optional[int]:
    """The dense slot space an aligned emitter would place by, or None
    when this operator kind/configuration cannot take aligned ingest:

    * key-sharded ``FfatWindowsTPU`` with a declared dense key space
      (the PR 13 original);
    * declared-``withMaxKeys`` ``ReduceTPU`` — the sharded dense
      reduce (ROADMAP item-4 leftover: pre-placed lanes let each key
      shard build ONLY its own partial rows, so the cross-chip table
      collective — psum for monoids, all_gather+fold for generic
      combiners — disappears entirely);
    * ``withDenseKeys`` stateful Map/Filter — pre-placed lanes are
      exactly the lanes whose slots the shard owns, so the data-axis
      all_gather AND the psum lane merge both vanish.

    Compacted key spaces stay unaligned (admission runs at the keyed
    staging boundary of a replica-sharded consumer).  Not
    ``op.key_space()``: sessions and the pair join declare one and have
    no key-sharded step to align (they refuse a mesh only at build)."""
    from windflow_tpu.ops.tpu import ReduceTPU
    from windflow_tpu.ops.tpu_stateful import _StatefulTPUBase
    from windflow_tpu.windows.ffat_tpu import FfatWindowsTPU
    if op.key_extractor is None:
        return None
    if isinstance(op, FfatWindowsTPU):
        if op.max_keys is None or getattr(op, "_compact_keys", False):
            return None
        return op.max_keys
    if isinstance(op, ReduceTPU):
        return op.max_keys      # None (arbitrary/compacted) = unaligned
    if isinstance(op, _StatefulTPUBase):
        return op.num_key_slots if op.dense_keys else None
    return None


def mark_aligned_ingest(graph) -> None:
    """Mark the mesh consumers eligible for KEY-ALIGNED ingest (ROADMAP
    item 4b; ``Config.key_aligned_ingest`` / ``WF_TPU_KEY_ALIGNED=0``
    kill switch): a key-sharded consumer with a declared dense key/slot
    space (:func:`_aligned_slot_bound` — FFAT windows, dense
    ``ReduceTPU``, dense-key stateful Map/Filter), fed EXCLUSIVELY by
    host staging edges under KEYBY routing, is stamped
    ``_ingest_mode="aligned"`` — the graph wiring then installs
    :class:`~windflow_tpu.parallel.emitters.AlignedMeshStageEmitter` on
    those edges and the consumer's sharded step compiles its
    no-all_gather variant (``_ffat_shard_layout`` ``"aligned"`` /
    ``make_sharded_reduce_step`` / ``make_sharded_stateful_step``
    ``ingest="aligned"``).  Device-fed consumers keep the data-sharded
    ingest (a TPU→TPU edge has no host boundary to align at), as do
    compacted key spaces (their admission runs at the keyed staging
    boundary of a REPLICA-sharded consumer) and multi-process graphs
    (each process stages only its local lanes).

    Called by ``PipeGraph._build`` after replica construction, before
    edge wiring — the emitter dispatch reads the stamp."""
    cfg = graph.config
    mesh = cfg.mesh
    if mesh is None or jax.process_count() > 1:
        return
    from windflow_tpu.basic import RoutingMode
    kk = mesh.shape[KEY_AXIS]
    dd = mesh.shape[DATA_AXIS]
    ups = {}
    for edge in graph._edges():
        if edge[0] == "op":
            _, a, b = edge
            ups.setdefault(id(b), []).append(a)
        else:
            _, mp = edge
            src = mp.operators[-1]
            for child in mp.split_children:
                if child.operators:
                    ups.setdefault(id(child.operators[0]),
                                   []).append(src)
    for op in graph._topo_operators():
        if not getattr(op, "is_tpu", False):
            continue
        bound = _aligned_slot_bound(op)
        if bound is None or op.routing != RoutingMode.KEYBY \
                or op.parallelism != 1:
            continue
        if bound % kk:
            continue        # WF402 territory: the mesh pass reports it
        feeds = ups.get(id(op), [])
        if not feeds or any(u.is_tpu for u in feeds):
            continue        # device-fed: no host boundary to align at
        if any((u.output_batch_size or 0) % (kk * dd)
               for u in feeds):
            continue        # indivisible staging capacity: keep default
        op._ingest_mode = "aligned"


# ---------------------------------------------------------------------------
# Keyed reduce over the mesh (reference Reduce_GPU + cross-replica merge;
# BASELINE.json: "keyby-sharded Reduce … linear scaling to 8 chips").
# ---------------------------------------------------------------------------

@phase("wf.reduce")
def _dense_keyed_partial(keys, vals, valid, comb, K):
    """Per-chip dense partial table: sort by key, segmented scan, scatter the
    segment tails into rows of a ``[K, ...]`` table.  The XLA/ICI-friendly
    replacement for ``thrust::sort_by_key`` + ``reduce_by_key``
    (``reduce_gpu.hpp:227-258``) producing a *dense* table so cross-chip
    combination is a collective, not a re-shuffle."""
    sk = jnp.where(valid & (keys >= 0) & (keys < K), keys, K)
    order = auto_order(sk, K + 1)   # O(n) dense grouping (grouping.py)
    sk_s = sk[order]
    sv = jax.tree.map(lambda a: a[order], vals)
    starts = jnp.concatenate([jnp.array([True]), sk_s[1:] != sk_s[:-1]])
    scanned = _seg_scan(comb, starts, sv)
    ends = jnp.concatenate([sk_s[:-1] != sk_s[1:], jnp.array([True])])
    row = jnp.where(ends & (sk_s < K), sk_s, K)

    def scat(leaf):
        buf = jnp.zeros((K + 1,) + leaf.shape[1:], leaf.dtype)
        return buf.at[row].set(leaf, mode="drop")[:K]

    table = jax.tree.map(scat, scanned)
    has = jnp.zeros(K + 1, bool).at[row].set(True)[:K]
    return table, has


def make_sharded_reduce_step(mesh: Mesh, capacity: int, K: int,
                             comb: Callable, key_fn: Optional[Callable],
                             use_psum: bool = False,
                             monoid: Optional[str] = None,
                             ingest: str = "data",
                             op_name: str = "mesh.reduce_step",
                             owner: Optional[str] = None):
    """Sharded ReduceTPU step with the operator's batch contract: returns
    ``fn(payload, ts, valid) -> (table, ts_out, has, n_dropped)`` where
    ``table`` is the dense ``[K]`` combined-record table, ``ts_out`` the
    per-key max input timestamp, ``has`` the occupancy mask — i.e. a
    DeviceBatch of capacity ``K`` whose valid lanes are the distinct keys —
    and ``n_dropped`` the count of valid tuples whose key fell outside
    ``[0, K)`` (the dense tables cannot hold them; the count surfaces in
    stats rather than vanishing silently).  This is what ``ReduceTPU``
    compiles when the graph runs on a mesh (Config.mesh): per-chip dense
    partials over the flattened ``(data, key)`` axes combined with a
    single reduce collective — ``psum``/``pmax``/``pmin`` for declared
    monoid combiners (``monoid``; legacy ``use_psum=True`` means
    ``"sum"``) — or all_gather + log-fold for arbitrary combiners
    (reference: Reduce_GPU per replica + cross-replica merge,
    ``reduce_gpu.hpp:227-283``).

    Non-keyed reduces pass ``key_fn=None`` with ``K == 1`` (the
    ``thrust::reduce`` global path).

    ``ingest="aligned"`` (key-aligned mesh ingest, ROADMAP item-4
    leftover): the host pre-placed every tuple on its key-owner's
    ``(data, key)`` column (AlignedMeshStageEmitter, dense-range owner
    ``key // K_local``), so each key shard builds ONLY its own
    ``K_local`` partial rows from its own ``capacity/kk`` lanes and
    the cross-chip table combine — ``psum``/``pmax``/``pmin`` of
    ``[K, ...]`` tables for declared monoids, ``all_gather`` + log-fold
    for generic combiners — disappears ENTIRELY; only the within-column
    data-axis gather remains (identity at ``data=1``), and the output
    tables return key-sharded instead of replicated (same global
    ``[K]`` contract)."""
    monoid = resolve_monoid(use_psum, monoid)
    n_total = math.prod(mesh.devices.shape)
    if capacity % n_total:
        raise WindFlowError(
            f"capacity {capacity} not divisible by {n_total} devices")
    axes = (DATA_AXIS, KEY_AXIS)
    if ingest not in ("data", "aligned"):
        raise WindFlowError(f"unknown reduce ingest layout '{ingest}'")
    if ingest == "aligned":
        kk = mesh.shape[KEY_AXIS]
        dd = mesh.shape[DATA_AXIS]
        if K % kk:
            raise WindFlowError(
                f"max_keys {K} not divisible by key axis {kk}")
        K_local = K // kk

        @operator_scope(owner or op_name)
        def local_aligned(payload, ts, valid):
            with phase("wf.fn"):
                keys = jax.vmap(key_fn)(payload).astype(jnp.int32)
            base = (jax.lax.axis_index(KEY_AXIS)
                    * K_local).astype(jnp.int32)
            lk = keys - base
            in_range = (keys >= 0) & (keys < K) \
                & (lk >= 0) & (lk < K_local)
            # out-of-range keys clip onto an edge column host-side and
            # mask out here — counted exactly like the unaligned drop
            with phase("wf.mesh.exchange"):
                n_drop = jax.lax.psum(
                    jnp.sum(valid & ~in_range, dtype=jnp.int64), axes)
            ok = valid & in_range
            if dd > 1:
                # within-column hop only (1/kk of the all_gather bytes):
                # every data row of a key column folds the same lanes
                ag = lambda a: jax.lax.all_gather(a, DATA_AXIS, axis=0,
                                                  tiled=True)
                with phase("wf.mesh.exchange"):
                    payload = jax.tree.map(ag, payload)
                    lk, ts, ok = ag(lk), ag(ts), ag(ok)
            vals = (payload, ts)
            comb2 = lambda a, b: (comb(a[0], b[0]),
                                  jnp.maximum(a[1], b[1]))
            (table, ts_t), has = _dense_keyed_partial(
                lk, vals, ok, comb2, K_local)
            # each shard's rows are FINAL — no cross-chip combine; rows
            # a shard never saw stay invalid exactly as the collective
            # path leaves them identity-filled/unfolded
            ts_out = jnp.where(has, ts_t, jnp.int64(-1))
            return table, ts_out, has, n_drop

        bspec = P((DATA_AXIS, KEY_AXIS))
        fn = shard_map(local_aligned, mesh=mesh,
                       in_specs=(bspec, bspec, bspec),
                       out_specs=(P(KEY_AXIS), P(KEY_AXIS),
                                  P(KEY_AXIS), P()),
                       check_vma=False)
        return wf_jit(fn, op_name=op_name)

    @operator_scope(owner or op_name)
    def local(payload, ts, valid):
        if key_fn is not None:
            with phase("wf.fn"):
                keys = jax.vmap(key_fn)(payload).astype(jnp.int32)
        else:
            keys = jnp.zeros(ts.shape[0], jnp.int32)
        n_drop = jnp.sum(valid & ((keys < 0) | (keys >= K)),
                         dtype=jnp.int64)
        with phase("wf.mesh.exchange"):
            n_drop = jax.lax.psum(n_drop, axes)
        # fold ts with the payload so the segment tails carry max-ts too
        vals = (payload, ts)
        comb2 = lambda a, b: (comb(a[0], b[0]), jnp.maximum(a[1], b[1]))
        (table, ts_t), has = _dense_keyed_partial(keys, vals, valid, comb2, K)
        if monoid is not None:
            coll = monoid_collective(monoid)
            with phase("wf.mesh.exchange"):
                z = jax.tree.map(
                    lambda a: jnp.where(_b(has, a), a,
                                        _monoid_identity(monoid, a.dtype)),
                    table)
                out = jax.tree.map(lambda a: coll(a, axes), z)
                ts_out = jax.lax.pmax(
                    jnp.where(has, ts_t, jnp.int64(-1)), axes)
                any_has = jax.lax.psum(has.astype(jnp.int32), axes) > 0
            return out, ts_out, any_has, n_drop
        with phase("wf.mesh.exchange"):
            g_t = jax.tree.map(lambda a: jax.lax.all_gather(a, axes),
                               (table, ts_t))
            g_h = jax.lax.all_gather(has, axes)
        with phase("wf.reduce"):
            anyf, (folded, ts_f) = _masked_reduce_last(comb2, g_h, g_t,
                                                       axis=0)
        return folded, ts_f, anyf, n_drop

    fn = shard_map(local, mesh=mesh,
                       in_specs=(P(axes), P(axes), P(axes)),
                       out_specs=(P(), P(), P(), P()), check_vma=False)
    return wf_jit(fn, op_name=op_name)


def make_sharded_reduce_arbitrary(mesh: Mesh, capacity: int, comb: Callable,
                                  key_fn: Callable,
                                  op_name: str = "mesh.reduce_arbitrary",
                                  remap: bool = False,
                                  owner: Optional[str] = None):
    """Keyed reduce over the mesh for an ARBITRARY int32 key space — no
    ``withMaxKeys`` bound and no dropped keys (VERDICT r2 item 5).

    Keys are hash-sharded: each chip buckets its local lanes by owner chip
    (``key mod n`` on the uint32 reinterpretation), one ``all_to_all`` over
    ICI routes every lane to its owner, and each chip then runs the plain
    sort + segmented reduce over the keys it owns (the distributed form of
    the reference's arbitrary-key ``thrust::sort_by_key`` +
    ``reduce_by_key``, ``reduce_gpu.hpp:227-258``, with the shuffle the
    reference does between replicas done as one collective).

    Returns ``fn(payload, ts, valid) -> (payload, ts, valid, n_dropped)``;
    each chip's distinct-key rows are left-compacted into its ``[capacity]``
    block of the concatenated output (worst case one chip owns every key,
    so the per-chip block cannot shrink below ``capacity``); ``n_dropped``
    is always 0 — nothing is out of range by construction.

    ``remap=True`` is the key-compaction variant (parallel/compaction.py):
    the signature grows two REPLICATED read-only operands
    ``(table_keys, table_slots)`` and slotted (hot) keys route to owner
    ``slot % n`` instead of the uint32 hash — the remap balances hot
    keys over chips deterministically while the cold tail keeps the
    hash.  The per-chip sort/segment path itself is unchanged, so the
    output contract is identical."""
    axes = (DATA_AXIS, KEY_AXIS)
    n = math.prod(mesh.devices.shape)
    if capacity % n:
        raise WindFlowError(
            f"capacity {capacity} not divisible by {n} devices")
    local_cap = capacity // n

    @operator_scope(owner or op_name)
    def local(payload, ts, valid, *tables):
        from windflow_tpu.ops.tpu import _segmented_reduce
        with phase("wf.fn"):
            keys = jax.vmap(key_fn)(payload).astype(jnp.int32)
        bp, bt, bmask = buckets(keys, payload, ts, valid, tables)
        # one collective: bucket row i of every chip lands on chip i
        a2a = lambda x: jax.lax.all_to_all(x, axes, split_axis=0,
                                           concat_axis=0, tiled=True)
        flat = lambda a: a.reshape((capacity,) + a.shape[2:])
        with phase("wf.mesh.exchange"):
            rp = jax.tree.map(a2a, bp)
            rt, rm = a2a(bt), a2a(bmask)
            rp = jax.tree.map(flat, rp)
            rt, rm = flat(rt), flat(rm)
        with phase("wf.fn"):
            rkeys = jax.vmap(key_fn)(rp).astype(jnp.int32)
        with phase("wf.reduce"):
            _, out_payload, out_ts, out_valid = _segmented_reduce(
                rkeys, rp, rt, rm, comb, capacity)
        return out_payload, out_ts, out_valid, jnp.zeros((), jnp.int64)

    @phase("wf.mesh.own")
    def buckets(keys, payload, ts, valid, tables):
        """The local lanes bucketed by the chip that owns their key."""
        own = (keys.astype(jnp.uint32) % n).astype(jnp.int32)
        if tables:
            from windflow_tpu.parallel.compaction import lookup_slots
            tk, tsl = tables
            slot, hit = lookup_slots(tk, tsl, keys, valid)
            own = jnp.where(hit, slot % jnp.int32(n), own)
        owner = jnp.where(valid, own, jnp.int32(n))
        # group local lanes by owner: rank within the owner run indexes the
        # outgoing bucket row (a run can never exceed local_cap lanes)
        order = auto_order(owner, n + 1)
        so = owner[order]
        sp = jax.tree.map(lambda a: a[order], payload)
        st, sv = ts[order], valid[order]
        pos = jnp.arange(local_cap)
        starts = jnp.concatenate([jnp.array([True]), so[1:] != so[:-1]])
        seg_start = jax.lax.associative_scan(
            jnp.maximum, jnp.where(starts, pos, 0))
        rank = (pos - seg_start).astype(jnp.int32)
        row = jnp.where(sv & (so < n), so, n)

        def scat(leaf):
            buf = jnp.zeros((n + 1, local_cap) + leaf.shape[1:], leaf.dtype)
            return buf.at[row, rank].set(leaf)[:n]
        bp = jax.tree.map(scat, sp)
        bt = scat(st)
        bmask = jnp.zeros((n + 1, local_cap), bool) \
            .at[row, rank].set(sv & (so < n))[:n]
        return bp, bt, bmask

    in_specs = (P(axes), P(axes), P(axes))
    if remap:
        # remap tables are replicated: every chip owns the same table
        in_specs = in_specs + (P(), P())
    fn = shard_map(local, mesh=mesh, in_specs=in_specs,
                       out_specs=(P(axes), P(axes), P(axes), P()),
                       check_vma=False)
    return wf_jit(fn, op_name=op_name)


def make_sharded_keyed_reduce(mesh: Mesh, capacity: int, K: int,
                              comb: Callable, key_fn: Callable,
                              use_psum: bool = False,
                              monoid: Optional[str] = None,
                              op_name: str = "mesh.keyed_reduce"):
    """Compile a keyed reduce over the whole mesh; thin wrapper over
    :func:`make_sharded_reduce_step` (one implementation of the collective
    combine) that drops the timestamp/drop-count outputs.  Returns
    ``fn(payload, valid) -> (table, has)`` with both outputs replicated on
    every chip."""
    step = make_sharded_reduce_step(mesh, capacity, K, comb, key_fn,
                                    use_psum=use_psum, monoid=monoid)

    def fn(payload, valid):
        ts = jnp.zeros(valid.shape[0], jnp.int64)
        table, _, has, _ = step(payload, ts, valid)
        return table, has

    return wf_jit(fn, op_name=op_name)


# ---------------------------------------------------------------------------
# Key-sharded FFAT windows (reference Ffat_Windows_GPU replicas each owning a
# key subset; here shards of one dense state table own key ranges).
# ---------------------------------------------------------------------------

def _ffat_shard_layout(mesh: Mesh, capacity: int, K: int,
                       ingest: str = "data"):
    """Shared guards + layout for key-sharded FFAT variants: returns
    ``(K_local, key_base_fn, gather, batch_spec, step_cap)`` where
    ``step_cap`` is the lane count each key shard's local step actually
    sees after ``gather``.

    ``ingest`` picks the staged-batch layout the step consumes:

    * ``"data"`` (single-host default): lanes split along ``data``,
      replicated along ``key`` — ``gather`` is one all_gather over the
      data axis, entirely within a host's ICI domain (identity on a
      1-wide data axis).
    * ``"flat"`` (multi-host graphs): lanes fully sharded over
      ``(data, key)`` — the only layout a process can assemble from the
      lanes IT ingested (batch.py ``_stage_soa``) — and ``gather``
      reconstructs the logical lane order with an all_gather over
      ``key`` then ``data`` (data-major block order = the logical
      P((data, key)) order).  The key-axis hop crosses DCN.
    * ``"aligned"`` (key-aligned ingest, ROADMAP item 4b): lanes fully
      sharded over ``(data, key)`` with the HOST having already placed
      every tuple in its key-owner's column
      (parallel/emitters.AlignedMeshStageEmitter — the same
      ``key // K_local`` ownership ``key_base_fn`` rebases by).  The
      gather collapses to the within-column data-axis hop — identity on
      a 1-wide data axis — killing the all_gather that dominates the
      modeled ICI bytes/tuple: each key shard
      processes only its own ``capacity/kk`` lanes."""
    kk = mesh.shape[KEY_AXIS]
    dd = mesh.shape[DATA_AXIS]
    if K % kk:
        raise WindFlowError(f"max_keys {K} not divisible by key axis {kk}")
    if capacity % dd:
        raise WindFlowError(
            f"capacity {capacity} not divisible by data axis {dd}")
    if ingest not in ("data", "flat", "aligned"):
        raise WindFlowError(f"unknown ffat ingest layout '{ingest}'")
    K_local = K // kk
    key_base_fn = lambda: jax.lax.axis_index(KEY_AXIS) * K_local

    if ingest in ("flat", "aligned"):
        if capacity % (dd * kk):
            raise WindFlowError(
                f"capacity {capacity} not divisible by the mesh's "
                f"{dd * kk} devices")

    if ingest == "flat":
        @phase("wf.mesh.exchange")
        def gather(payload, ts, valid):
            def ag(a):
                a = jax.lax.all_gather(a, KEY_AXIS, axis=0, tiled=True)
                if dd > 1:
                    a = jax.lax.all_gather(a, DATA_AXIS, axis=0,
                                           tiled=True)
                return a
            return jax.tree.map(ag, payload), ag(ts), ag(valid)

        return (K_local, key_base_fn, gather, P((DATA_AXIS, KEY_AXIS)),
                capacity)

    if ingest == "aligned":
        @phase("wf.mesh.exchange")
        def gather(payload, ts, valid):
            if dd == 1:
                return payload, ts, valid
            # within-column hop only: each key shard re-assembles its
            # OWN column's rows (d-major block order = the aligned
            # emitter's row order); no key-axis traffic at all
            ag = lambda a: jax.lax.all_gather(a, DATA_AXIS, axis=0,
                                              tiled=True)
            return jax.tree.map(ag, payload), ag(ts), ag(valid)

        return (K_local, key_base_fn, gather, P((DATA_AXIS, KEY_AXIS)),
                capacity // kk)

    @phase("wf.mesh.exchange")
    def gather(payload, ts, valid):
        if dd == 1:
            return payload, ts, valid
        ag = lambda a: jax.lax.all_gather(a, DATA_AXIS, axis=0, tiled=True)
        return jax.tree.map(ag, payload), ag(ts), ag(valid)

    return K_local, key_base_fn, gather, P(DATA_AXIS), capacity


#: state lane of the key-sharded count-window step, one per key shard:
#: the steps in which the shard owned more lanes than its share of the
#: batch and took more than one round over them (``CB_wide_steps``)
CB_WIDE_STEPS = "n_wide"


def ffat_owned_lanes(mesh: Mesh, capacity: int) -> int:
    """Lanes a key shard's count-window step is built at: its even share
    of the batch, ``capacity // kk`` (``CB_step_lanes``) — what the
    ``"aligned"`` layout is handed by the host, and what the other
    layouts compact their owned lanes to inside the step."""
    return max(1, capacity // mesh.shape[KEY_AXIS])


def _owned_to_front(ok, tree):
    """Move the ``ok`` lanes of every leaf of ``tree`` to the front, in
    arrival order (the lanes behind them are the ones not owned).  One
    stable sort on the one-bit flag with the scalar leaves riding it as
    operands; a leaf with trailing dimensions follows by gather.  On a
    v5e the sort of 262144 lanes with two leaves riding is 0.40 ms, where
    a running count + a 32-bit scatter of lane indices + a gather a leaf
    to a quarter of the lanes is 2.4."""
    leaves, treedef = jax.tree.flatten(tree)
    rides = [a.ndim == 1 for a in leaves]
    iota = [] if all(rides) else [jnp.arange(ok.shape[0], dtype=jnp.int32)]
    done = jax.lax.sort(
        [(~ok).astype(jnp.int32)]
        + [a for a, r in zip(leaves, rides) if r] + iota,
        num_keys=1, is_stable=True)
    riders = iter(done[1:])
    moved = [next(riders) if r else None for r in rides]
    order = next(riders, None)      # the iota, where some leaf needs it
    return jax.tree.unflatten(
        treedef, [a[order] if m is None else m
                  for a, m in zip(leaves, moved)])


def _make_key_shard_ffat_step(step_cap: int, lanes: int, K_local: int,
                              Pn: int, R: int, D: int, lift: Callable,
                              comb: Callable, key_fn: Optional[Callable],
                              key_base_fn: Callable, **kw):
    """The count-window step of ONE key shard over a gathered
    ``step_cap``-lane batch (un-jitted; traced inside ``shard_map``), with
    the shard's lane of ``CB_WIDE_STEPS`` in its state: see
    :func:`make_sharded_ffat_step`."""
    kw = dict(kw, key_base_fn=key_base_fn)
    step_whole = make_ffat_step(step_cap, K_local, Pn, R, D, lift, comb,
                                key_fn, **kw)
    if lanes >= step_cap:
        def step(state, payload, ts, valid):
            state = dict(state)
            n_wide = state.pop(CB_WIDE_STEPS)
            new_state, *rest = step_whole(state, payload, ts, valid)
            new_state[CB_WIDE_STEPS] = n_wide
            return (new_state, *rest)
        return step
    # the owned lanes arrive as (key, lifted value): what the step reads
    # of a record, so nothing else of it is moved
    step_owned = make_ffat_step(lanes, K_local, Pn, R, D,
                                lambda r: r["lift"], comb,
                                lambda r: r["key"], **kw)
    n_rounds = -(-step_cap // lanes)

    def step(state, payload, ts, valid):
        state = dict(state)
        n_wide = state.pop(CB_WIDE_STEPS)
        with phase("wf.fn"):
            keys = jax.vmap(key_fn)(payload).astype(jnp.int32) \
                if key_fn is not None else jnp.zeros(step_cap, jnp.int32)
        lkeys = keys - jnp.int32(key_base_fn())
        ok = valid & (lkeys >= 0) & (lkeys < K_local)
        with phase("wf.mesh.own"):
            n_own = jnp.sum(ok, dtype=jnp.int32)
        with phase("wf.fn"):
            lifted = jax.vmap(lift)(payload)
        with phase("wf.mesh.own"):
            rec = _owned_to_front(ok, {"key": keys, "lift": lifted})
            # whole rounds to slice: the last one may reach past the batch
            rec = jax.tree.map(lambda a: jnp.pad(
                a, [(0, n_rounds * lanes - step_cap)]
                + [(0, 0)] * (a.ndim - 1)), rec)
        lane = jnp.arange(lanes, dtype=jnp.int32)
        no_ts = jnp.zeros(lanes, ts.dtype)

        def one_round(r, state):
            at = lambda a: jax.lax.dynamic_slice_in_dim(a, r * lanes, lanes)
            return step_owned(state, jax.tree.map(at, rec), no_ts,
                              r * lanes + lane < n_own)[:3]

        # the hand-on batch has the whole-batch step's shape; a round's
        # rows are a prefix of its own output and land behind the rounds'
        # before it (room for one more round's output: an update that
        # does not fit is moved, not cut)
        like = jax.eval_shape(step_whole, state, payload, ts, valid)
        first = jax.eval_shape(one_round, 0, state)
        rows = jax.tree.map(
            lambda s, n: jnp.zeros((s.shape[0] + n.shape[0],) + s.shape[1:],
                                   s.dtype), like[1], first[1])
        # the state leaves the step in the step's own types (a carried
        # aggregate may come in wider than the lifted values)
        state = jax.tree.map(lambda a, s: a.astype(s.dtype), state, first[0])

        def body(c):
            r, state, rows, n_rows = c
            state, out, fired = one_round(r, state)
            rows = jax.tree.map(
                lambda b, o: jax.lax.dynamic_update_slice_in_dim(
                    b, o, n_rows, 0), rows, out)
            return r + 1, state, rows, n_rows + jnp.sum(fired,
                                                        dtype=jnp.int32)

        # one round where the owned lanes fit a share of the batch (and
        # where there are none: the step still runs, and fires nothing)
        todo = jnp.maximum(1, -(-n_own // lanes))
        _, new_state, rows, n_rows = jax.lax.while_loop(
            lambda c: c[0] < todo, body,
            (jnp.int32(0), state, rows, jnp.int32(0)))
        n_out = like[2].shape[0]
        out = jax.tree.map(lambda b: b[:n_out], rows)
        fired = jnp.arange(n_out, dtype=jnp.int32) < n_rows
        # hand on the WHOLE batch's newest timestamp, as the whole-batch
        # step does: not the owned lanes'
        out_ts = jnp.where(fired, jnp.max(jnp.where(valid, ts, 0)), 0)
        new_state[CB_WIDE_STEPS] = n_wide + jnp.where(todo > 1, 1, 0)
        return new_state, out, fired, out_ts

    return step


def make_sharded_ffat_step(mesh: Mesh, capacity: int, K: int, Pn: int, R: int,
                           D: int, lift: Callable, comb: Callable,
                           key_fn: Optional[Callable],
                           sum_like: bool = False,
                           grouping: str = "rank_scatter",
                           ingest: str = "data",
                           monoid: Optional[str] = None,
                           op_name: str = "mesh.ffat_step",
                           owner: Optional[str] = None):
    """Compile one FFAT window step sharded over the mesh (``owner``:
    the operator's name, its ``wf.op.<name>`` scope in a device trace;
    the program's name where none is given).

    State tables are split along ``key`` (chip *i* owns keys
    ``[i*K/kk, (i+1)*K/kk)``); the staged batch arrives data-sharded and is
    ``all_gather``-ed across ``data`` inside the program so every key shard
    sees every tuple exactly once over ICI.  Fired-window outputs come back
    key-sharded, one row block per chip.

    **Owned-lane compaction.**  Every key shard is handed the whole batch
    (``"data"``, ``"flat"``) and owns about a ``kk``-th of its lanes, so
    it first sorts the lanes it owns, in arrival order, to the front and
    runs the step built at :func:`ffat_owned_lanes` lanes over them — the
    program the ``"aligned"`` layout builds, whose lane work and
    ``[K_local, capacity // (kk P) + 2]`` pane grid are a ``kk``-th of
    the whole batch's.  One round where the owned lanes fit a share of
    the batch; a shard that owns more (skewed keys) takes a round more
    for every further ``capacity // kk`` lanes in the same
    ``lax.while_loop``, decided from the batch itself, and counts the
    step in its lane of the state's ``CB_WIDE_STEPS``.  The rounds' rows
    land one behind the other in ONE output batch of the whole-batch
    step's shape (a key's windows in order; rows of different keys in
    round order), and the hand-on timestamp is the whole batch's.  The
    ``"aligned"`` layout's lanes are already the owned ones: it keeps its
    program."""
    K_local, key_base_fn, gather, bspec, step_cap = _ffat_shard_layout(
        mesh, capacity, K, ingest)
    shard_step = _make_key_shard_ffat_step(
        step_cap, ffat_owned_lanes(mesh, capacity), K_local, Pn, R, D, lift,
        comb, key_fn, key_base_fn, sum_like=sum_like, grouping=grouping,
        monoid=monoid)

    # the benchmark finds this program in a device trace by the name of
    # the wrapped function, the XLA module ``jit_local``
    @operator_scope(owner or op_name)
    def local(state, payload, ts, valid):
        return shard_step(state, *gather(payload, ts, valid))

    fn = shard_map(
        local, mesh=mesh,
        in_specs=(P(KEY_AXIS), bspec, bspec, bspec),
        out_specs=(P(KEY_AXIS), P(KEY_AXIS), P(KEY_AXIS), P(KEY_AXIS)),
        check_vma=False)
    return wf_jit(fn, op_name=op_name, donate_argnums=(0,))


def make_sharded_ffat_flush(mesh: Mesh, K: int, Pn: int, R: int, D: int,
                            comb: Callable,
                            op_name: str = "mesh.ffat_flush",
                            owner: Optional[str] = None):
    """EOS flush of the key-sharded CB state as an explicit shard_map:
    each key shard flushes its own rows (keys rebased by the shard's
    base) and the outputs stay key-sharded — so each host's sink reads
    exactly its own keys' partial windows (a plain jit lets XLA pick the
    output layout, which scrambled per-process reads)."""
    kk = mesh.shape[KEY_AXIS]
    if K % kk:
        raise WindFlowError(f"max_keys {K} not divisible by key axis {kk}")
    K_local = K // kk
    key_base_fn = lambda: jax.lax.axis_index(KEY_AXIS) * K_local
    flush_local = make_ffat_flush(K_local, Pn, R, D, comb,
                                  key_base_fn=key_base_fn)
    fn = shard_map(
        operator_scope(owner or op_name)(flush_local), mesh=mesh,
        in_specs=(P(KEY_AXIS),),
        out_specs=(P(KEY_AXIS), P(KEY_AXIS), P(KEY_AXIS)),
        check_vma=False)
    return wf_jit(fn, op_name=op_name)


def make_sharded_ffat_state(agg_spec, K: int, R: int, mesh: Mesh):
    """Allocate the dense FFAT state pre-sharded along ``key``."""
    state = make_ffat_state(agg_spec, K, R)
    state[CB_WIDE_STEPS] = jnp.zeros((mesh.shape[KEY_AXIS],), jnp.int64)
    sh = state_sharding(mesh)
    return jax.tree.map(lambda a: jax.device_put(a, sh), state)


def make_sharded_stateful_step(mesh: Mesh, capacity: int, S: int,
                               body_factory: Callable,
                               key_fn: Callable, dense: bool,
                               is_filter: bool, ingest: str = "data",
                               op_name: str = "mesh.stateful_step",
                               owner: Optional[str] = None):
    """Key-sharded stateful Map/Filter step (reference stateful ``Map_GPU``
    whose keyed state is one shared table, ``map_gpu.hpp:114-115``; here the
    dense ``[num_key_slots, ...]`` table is split along ``key`` so each chip
    owns a slot range).

    Layout mirrors the FFAT sharding: the data-sharded batch is
    ``all_gather``-ed across ``data`` so every key shard sees every lane;
    each shard runs the per-key in-order body over the lanes whose slot it
    owns (non-owned lanes contribute the body's neutral output), and lane
    results merge across key shards with one ``psum`` — each lane has
    exactly one owner, so the sum selects its real result.  Outputs return
    data-sharded, matching the batch layout downstream stages expect.

    ``ingest="aligned"`` (key-aligned mesh ingest; dense slot spaces
    only — AlignedMeshStageEmitter places by the same ``slot //
    S_local`` dense-range owner): each key shard's lanes are exactly
    the lanes whose slots it owns, so BOTH collectives of the default
    layout vanish — no data-axis all_gather to see foreign lanes, no
    psum lane merge to reconcile owners (every lane has its owner's
    verdict in place).  Outputs stay in the aligned ``(data, key)``
    layout; the only residual hop is the within-column data gather at
    ``data > 1``.  Per-key arrival order is preserved (the emitter
    appends each column in arrival order), so state evolution is
    record-identical to the unaligned layout per key."""
    kk = mesh.shape[KEY_AXIS]
    dd = mesh.shape[DATA_AXIS]
    if S % kk:
        raise WindFlowError(
            f"num_key_slots {S} not divisible by key axis {kk}")
    if capacity % dd:
        raise WindFlowError(
            f"capacity {capacity} not divisible by data axis {dd}")
    S_local = S // kk
    blk = capacity // dd
    if ingest not in ("data", "aligned"):
        raise WindFlowError(
            f"unknown stateful ingest layout '{ingest}'")
    if ingest == "aligned":
        if not dense:
            raise WindFlowError(
                "key-aligned stateful ingest requires withDenseKeys")
        if capacity % (dd * kk):
            raise WindFlowError(
                f"capacity {capacity} not divisible by the mesh's "
                f"{dd * kk} devices (key-aligned ingest)")
        col_cap = capacity // kk        # lanes one key column holds
        blk_col = capacity // (dd * kk)  # one device's block of them
        body_a = body_factory(col_cap, S_local)

        @operator_scope(owner or op_name)
        def local_aligned(state, payload, valid, _uk, _us):
            if dd > 1:
                ag = lambda a: jax.lax.all_gather(a, DATA_AXIS, axis=0,
                                                  tiled=True)
                with phase("wf.mesh.exchange"):
                    payload = jax.tree.map(ag, payload)
                    valid = ag(valid)
            with phase("wf.fn"):
                keys = jax.vmap(key_fn)(payload).astype(jnp.int32)
            base = (jax.lax.axis_index(KEY_AXIS)
                    * S_local).astype(jnp.int32)
            lslot = keys - base
            owned = valid & (keys >= 0) & (keys < S) \
                & (lslot >= 0) & (lslot < S_local)
            lslot = jnp.where(owned, lslot, jnp.int32(S_local))
            with phase("wf.state"):
                new_state, out_payload, out_valid = body_a(
                    state, payload, owned, lslot)
            d = jax.lax.axis_index(DATA_AXIS) * blk_col
            sl = lambda a: jax.lax.dynamic_slice_in_dim(a, d, blk_col,
                                                        axis=0)
            owned_b = sl(owned)
            if is_filter:
                # the owner's verdict is in place — un-owned (foreign /
                # out-of-range) lanes drop, the single-chip contract
                return (new_state, jax.tree.map(sl, payload),
                        sl(out_valid) & owned_b)
            return (new_state, jax.tree.map(sl, out_payload), owned_b)

        bspec = P((DATA_AXIS, KEY_AXIS))
        fn = shard_map(
            local_aligned, mesh=mesh,
            in_specs=(P(KEY_AXIS), bspec, bspec, P(), P()),
            out_specs=(P(KEY_AXIS), bspec, bspec),
            check_vma=False)
        return wf_jit(fn, op_name=op_name, donate_argnums=(0,))
    body = body_factory(capacity, S_local)

    @phase("wf.mesh.exchange")
    def merge_lanes(leaf, owned):
        # zero out non-owned lanes, sum across key shards (bool via int32)
        if leaf.dtype == jnp.bool_:
            z = jnp.where(_b(owned, leaf), leaf, False)
            return jax.lax.psum(z.astype(jnp.int32), KEY_AXIS) > 0
        z = jnp.where(_b(owned, leaf), leaf, jnp.zeros_like(leaf))
        return jax.lax.psum(z, KEY_AXIS)

    @operator_scope(owner or op_name)
    def local(state, payload, valid, uniq_keys, uniq_slots):
        if dd > 1:
            ag = lambda a: jax.lax.all_gather(a, DATA_AXIS, axis=0,
                                              tiled=True)
            with phase("wf.mesh.exchange"):
                payload = jax.tree.map(ag, payload)
                valid = ag(valid)
        with phase("wf.fn"):
            keys = jax.vmap(key_fn)(payload).astype(jnp.int32)
        if dense:
            slots = keys
            ok = valid & (keys >= 0) & (keys < S)
        else:
            with phase("wf.state"):
                pos = jnp.clip(jnp.searchsorted(uniq_keys, keys),
                               0, capacity - 1)
                slots = uniq_slots[pos]
            ok = valid & (slots < S)
        base = (jax.lax.axis_index(KEY_AXIS) * S_local).astype(jnp.int32)
        lslot = slots - base
        owned = ok & (lslot >= 0) & (lslot < S_local)
        lslot = jnp.where(owned, lslot, jnp.int32(S_local))
        with phase("wf.state"):
            new_state, out_payload, out_valid = body(state, payload, owned,
                                                     lslot)
        # back to the data-sharded layout FIRST: psum over KEY_AXIS and the
        # per-data-row block slice commute, and slicing first divides the
        # collective volume by the data-axis extent
        d = jax.lax.axis_index(DATA_AXIS) * blk
        sl = lambda a: jax.lax.dynamic_slice_in_dim(a, d, blk, axis=0)
        owned_b, valid_b = sl(owned), sl(valid)
        # a lane is real only if SOME shard owns its slot — out-of-range
        # keys have no owner and must drop, exactly as on a single chip
        with phase("wf.mesh.exchange"):
            owned_any = jax.lax.psum(owned_b.astype(jnp.int32),
                                     KEY_AXIS) > 0
        if is_filter:
            # non-owner shards keep their lanes; the owner's verdict is the
            # only veto (out_valid from the body is owned & keep)
            keep = sl(out_valid) | ~owned_b
            with phase("wf.mesh.exchange"):
                vetoed = jax.lax.psum((~keep).astype(jnp.int32),
                                      KEY_AXIS) > 0
            return (new_state, jax.tree.map(sl, payload),
                    valid_b & owned_any & ~vetoed)
        merged_payload = jax.tree.map(
            lambda l: merge_lanes(sl(l), owned_b), out_payload)
        return new_state, merged_payload, valid_b & owned_any

    fn = shard_map(
        local, mesh=mesh,
        in_specs=(P(KEY_AXIS), P(DATA_AXIS), P(DATA_AXIS), P(), P()),
        out_specs=(P(KEY_AXIS), P(DATA_AXIS), P(DATA_AXIS)),
        check_vma=False)
    return wf_jit(fn, op_name=op_name, donate_argnums=(0,))


# Time-based FFAT on the mesh.  The single-chip TB state keeps scalar pane
# clocks shared by all keys (ffat_kernels.make_ffat_tb_state); sharded along
# ``key`` each shard's ring evolves independently — its capacity roll depends
# on the panes of the keys it owns — so the scalars (``TB_SCALARS``) become
# one lane per key shard, sharded the same way as the ``[K, NP]`` cells.


def make_sharded_ffat_tb_state(agg_spec, K: int, NP: int, mesh: Mesh):
    """Allocate the TB pane-ring state pre-sharded along ``key``: cells split
    by key rows, one scalar-clock lane per key shard."""
    kk = mesh.shape[KEY_AXIS]
    state = make_ffat_tb_state(agg_spec, K, NP)
    for name in TB_SCALARS:
        state[name] = jnp.broadcast_to(state[name], (kk,))
    sh = state_sharding(mesh)
    return jax.tree.map(lambda a: jax.device_put(a, sh), state)


def make_sharded_ffat_tb_step(mesh: Mesh, capacity: int, K: int, P_usec: int,
                              R: int, D: int, NP: int, lift: Callable,
                              comb: Callable, key_fn: Optional[Callable],
                              drop_tainted: bool = False,
                              grouping: str = "rank_scatter",
                              ingest: str = "data",
                              sum_like: bool = False,
                              monoid: Optional[str] = None,
                              op_name: str = "mesh.ffat_tb_step",
                              owner: Optional[str] = None):
    """Compile one time-based FFAT step sharded over the mesh.

    Same layout as the CB variant (:func:`make_sharded_ffat_step`): state
    split along ``key`` — chip *i* owns keys ``[i*K/kk, (i+1)*K/kk)`` and its
    own pane-ring clock — the data-sharded batch ``all_gather``-ed across
    ``data`` so every key shard sees every tuple once over ICI, and the
    watermark pane frontier passed replicated (it is host metadata, identical
    on every chip).  Reference: ``Ffat_Windows_GPU`` TB replicas each owning
    a key subset with quantum panes, ``ffat_replica_gpu.hpp:92-216,438-514``."""
    K_local, key_base_fn, gather, bspec, step_cap = _ffat_shard_layout(
        mesh, capacity, K, ingest)
    step_local = make_ffat_tb_step(step_cap, K_local, P_usec, R, D, NP,
                                   lift, comb, key_fn,
                                   key_base_fn=key_base_fn,
                                   drop_tainted=drop_tainted,
                                   grouping=grouping, sum_like=sum_like,
                                   monoid=monoid)

    @operator_scope(owner or op_name)
    def local(state, payload, ts, valid, wm_pane):
        payload, ts, valid = gather(payload, ts, valid)
        sstate = {k: (v[0] if k in TB_SCALARS else v)
                  for k, v in state.items()}
        new_state, out, fired, out_ts, n_adv = step_local(
            sstate, payload, ts, valid, wm_pane)
        new_state = {k: (v[None] if k in TB_SCALARS else v)
                     for k, v in new_state.items()}
        # Total window advance across key shards (drivers loop flushes on
        # it).  Along ``data`` the value is already replicated — every data
        # row of a key shard saw the same gathered batch — so summing over
        # KEY_AXIS alone keeps it both exact and mesh-replicated.
        with phase("wf.mesh.exchange"):
            n_adv = jax.lax.psum(n_adv, KEY_AXIS)
        return new_state, out, fired, out_ts, n_adv

    sspec = {k: P(KEY_AXIS) for k in
             ("cells", "cell_valid", "horizon") + TB_SCALARS}
    fn = shard_map(
        local, mesh=mesh,
        in_specs=(sspec, bspec, bspec, bspec, P()),
        out_specs=(sspec, P(KEY_AXIS), P(KEY_AXIS), P(KEY_AXIS), P()),
        check_vma=False)
    return wf_jit(fn, op_name=op_name, donate_argnums=(0,))
