"""Emitters: the routing plane between operator stages.

Re-design of the reference emitter family (``/root/reference/wf/basic_emitter.hpp``,
``forward_emitter.hpp``, ``keyby_emitter.hpp``, ``broadcast_emitter.hpp``, and the
``*_emitter_gpu.hpp`` device variants):

* The reference emitter pushes pointers into lock-free thread queues
  (``ff_send_out_to``).  Here an emitter appends messages to destination
  replica inboxes; the host driver (graph/pipegraph.py) drains them.  Because
  JAX arrays are immutable, broadcast needs no reference-counted multicast
  (reference ``delete_counter``, ``single_t.hpp:54``) — sharing a DeviceBatch
  handle is free.

* The CPU→GPU staging emitters (``forward_emitter_gpu.hpp:254-300`` pinned
  double-buffering) become :class:`DeviceStageEmitter`: host records are
  accumulated and staged to TPU HBM as one SoA batch.  JAX dispatch is
  asynchronous, so consecutive staged batches overlap transfer/compute without
  explicit double buffering.

* The GPU→GPU keyby emitter's sort/unique machinery
  (``keyby_emitter_gpu.hpp:519-583``) is *not* reproduced at the emitter: keys
  ride the batch as a dense-id lane and key grouping happens inside the
  consuming operator with XLA sort/segment ops — the compiler fuses it with
  the operator body, which a standalone emitter kernel would prevent.
"""

from __future__ import annotations

import math
from typing import Any, Callable, Optional, Sequence, Tuple

import jax
import numpy as np

from windflow_tpu import staging
from windflow_tpu.analysis.hotpath import hot_path
from windflow_tpu.basic import RoutingMode, WindFlowError, int32_key
from windflow_tpu.batch import (DeviceBatch, HostBatch, Punctuation, WM_NONE,
                                columns_to_device, host_to_device,
                                stage_packed, staged_nbytes, transfer_nbytes)
from windflow_tpu.monitoring import recorder as flightrec


_M64 = (1 << 64) - 1


def splitmix64_int(k: int) -> int:
    """Pure-Python splitmix64, bit-identical to the native ``wf_hash64`` /
    ``native.hash64`` (keyed routing placement must agree across the
    per-tuple, columnar-native, and on-device paths)."""
    x = (k + 0x9E3779B97F4A7C15) & _M64
    x = ((x ^ (x >> 30)) * 0xBF58476D1CE4E5B9) & _M64
    x = ((x ^ (x >> 27)) * 0x94D049BB133111EB) & _M64
    return x ^ (x >> 31)


def _splitmix64_dev(k32):
    """splitmix64 as jnp ops over an int32 key lane (sign-extended to the
    same int64 the host paths hash) — keeps device-side keyby placement
    bit-identical to the host staging emitter's."""
    import jax.numpy as jnp
    x = k32.astype(jnp.int64).astype(jnp.uint64) \
        + jnp.uint64(0x9E3779B97F4A7C15)
    x = (x ^ (x >> jnp.uint64(30))) * jnp.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> jnp.uint64(27))) * jnp.uint64(0x94D049BB133111EB)
    return x ^ (x >> jnp.uint64(31))


# canonical definition lives in basic.py (pure-Python layers like the Kafka
# client need it without pulling in this module's numpy/jax imports);
# re-exported here because keyby placement is this layer's concern
from windflow_tpu.basic import stable_hash  # noqa: F401,E402


class KeyInterner:
    """Host-side mapping from arbitrary user keys to dense int slots.

    The TPU answer to per-key device state without pointer-chasing hash maps
    (SURVEY.md §7 "hard parts"): the host assigns each distinct key a dense id
    at the staging boundary; device state lives in dense ``[num_slots, ...]``
    tables indexed by that id.  Parity: the reference copies distinct keys to
    host at the keyby boundary anyway (``dist_keys_cpu``,
    ``keyby_emitter_gpu.hpp:519-583``)."""

    def __init__(self) -> None:
        self._ids = {}

    def intern(self, key: Any) -> int:
        i = self._ids.get(key)
        if i is None:
            i = len(self._ids)
            self._ids[key] = i
        return i

    def __len__(self) -> int:
        return len(self._ids)

    def keys_by_slot(self) -> list:
        out = [None] * len(self._ids)
        for k, i in self._ids.items():
            out[i] = k
        return out


class Emitter:
    """Base emitter: owns destination inboxes and per-destination channel ids
    (reference ``Basic_Emitter``, ``basic_emitter.hpp:62-121``)."""

    #: whether this emitter implements the host-tuple emit() interface;
    #: device-only emitters (pass-through, device keyby) override to False
    #: so callers can detect an impossible host fallback up front
    can_emit_host_items = True

    def __init__(self, dests: Sequence[Tuple[Any, int]],
                 output_batch_size: int) -> None:
        # dests: list of (replica, channel_id on that replica).
        self.dests = list(dests)
        self.output_batch_size = output_batch_size
        # observability plumbing, bound by PipeGraph._build through the
        # OWNING replica (the replica whose output this emitter routes):
        # `stats` is that replica's StatsRecord (transfer byte counters —
        # the reference credits H2D/D2H to the transferring replica,
        # stats_record.hpp:152-160), `ring` its flight-recorder span ring,
        # `flight` the graph's FlightRecorder (trace-id assignment at
        # batch-birth sites).  All None when observability is off.
        self.stats = None
        self.ring = None
        self.flight = None

    def bind_observability(self, stats, ring, flight) -> None:
        """Attach the owning replica's stats/ring and the graph recorder;
        compound emitters (keyed staging, device→host, splitting) override
        to propagate the binding to their inner emitters."""
        self.stats = stats
        self.ring = ring
        self.flight = flight

    def _new_seq(self) -> int:
        """Sequence number of a batch BORN at this emitter (the
        ``batch=`` of its layer spans); 0 when the recorder is off."""
        return 0 if self.flight is None else self.flight.next_batch()

    def _trace_of(self, seq: int, stage: int = flightrec.EMITTED):
        """Trace lane of batch ``seq``: the 1-in-N sampling decision plus
        the birth span event; None (and no work beyond one check) when
        the recorder is off or the batch is not sampled."""
        if self.flight is None:
            return None
        tr = self.flight.trace_of(seq)
        if tr is not None and self.ring is not None:
            self.ring.record(tr[0], stage, tr[1])
        return tr

    def _new_trace(self, stage: int = flightrec.EMITTED):
        """Trace lane of a new batch that carries no sequence number
        (host batches)."""
        return self._trace_of(self._new_seq(), stage)

    # -- host-tuple interface ----------------------------------------------
    def emit(self, item: Any, ts: int, wm: int,
             shared: bool = False, tid=None) -> None:
        """``shared=True`` marks an item whose object is (or may be) also
        delivered elsewhere (split multicast); it taints the open batch so
        in-place consumers copy before mutating rather than paying an eager
        deepcopy per branch.  ``tid`` is the optional origin id relayed for
        DETERMINISTIC tie-breaking (HostBatch.ids)."""
        raise NotImplementedError

    # -- device-batch interface --------------------------------------------
    def emit_device_batch(self, batch: DeviceBatch) -> None:
        raise NotImplementedError

    # -- whole-host-batch interface (TPU→host boundary) ---------------------
    def emit_host_batch(self, hb: HostBatch) -> None:
        """Route a whole HostBatch (from a device transfer) downstream.
        Forward/broadcast emitters route at batch granularity — the
        reference GPU→CPU path also re-ships whole CPU batches
        (``keyby_emitter_gpu.hpp:594-638``); the default falls back to
        per-tuple emit for routings that need tuple granularity (keyby)."""
        for item, ts, tid in zip(hb.items, hb.tss, hb.ids_or_nones()):
            self.emit(item, ts, hb.watermark, hb.shared, tid=tid)

    # -- columnar interface (bulk sources, windflow_tpu/io) -----------------
    def emit_columns(self, cols, tss, wm: int, row_wms=None) -> None:
        """Emit a block of tuples given as SoA numpy columns.  ``wm`` is the
        frontier after the block's LAST row; ``row_wms`` (optional int64
        [n]) is the frontier after EACH row — sources that know it (e.g. a
        cumulative max of event timestamps) let the staging emitter stamp
        batches that split the block exactly instead of conservatively.
        The default explodes to per-tuple records (host destinations care
        about items, not layout); the device staging emitter overrides this
        with a zero-per-tuple path."""
        names = list(cols)
        arrs = [cols[n] for n in names]
        for i in range(len(tss)):
            item = {n: a[i].item() for n, a in zip(names, arrs)}
            self.emit(item, int(tss[i]),
                      int(row_wms[i]) if row_wms is not None else wm)

    # -- in-place interface (a producer that writes packed words itself) ----
    def packed_destination(self, names, dtypes):
        """Where a producer of columns ``names`` (a tuple) of ``dtypes``
        (numpy dtype names, in the same order) writes its next rows in
        place: ``(builder, lane_off)``, the open
        ``staging.PackedBatchBuilder`` and the int64 word offsets of the
        columns' lanes in ``names`` order followed by the ts lane's.  The
        producer writes at most ``builder.room`` rows from row
        ``builder.n`` on and reports them with :meth:`commit_packed`.
        ``None``: this edge has no such destination and takes columns
        (:meth:`emit_columns`), as every edge but the packed one-chip
        staging edge does."""
        return None

    def propagate_punctuation(self, wm: int) -> None:
        """Flush open batches, then multicast a watermark punctuation
        (reference ``forward_emitter.hpp:226-262``)."""
        self.flush(wm)
        for replica, ch in self.dests:
            replica.receive(ch, Punctuation(wm))

    def flush(self, wm: int) -> None:
        """Send any partially-filled batches downstream (EOS / cadence)."""

    # -- helpers ------------------------------------------------------------
    def _send(self, dest_idx: int, msg) -> None:
        replica, ch = self.dests[dest_idx]
        replica.receive(ch, msg)


def _concat(arrs):
    return arrs[0] if len(arrs) == 1 else np.concatenate(arrs)


def _log_fold(comb, rec: dict, m: int) -> dict:
    """Fold ``m`` records held as a dict of ``[m]`` numpy columns into
    one scalar record through an ASSOCIATIVE combiner, by repeated
    halving — the combiner runs log2(m) times over vectorized halves
    instead of m-1 times over scalars.  Only the grouping changes
    (associativity; float sums carry the same rounding tolerance as the
    dense reduce path)."""
    while m > 1:
        h = m // 2
        a = {k: v[:h] for k, v in rec.items()}
        b = {k: v[h:2 * h] for k, v in rec.items()}
        c = comb(a, b)
        if m - 2 * h:
            rec = {k: np.concatenate([np.atleast_1d(np.asarray(c[k])),
                                      np.asarray(v[2 * h:])])
                   for k, v in rec.items()}
        else:
            rec = {k: np.atleast_1d(np.asarray(c[k])) for k in rec}
        m = h + (m - 2 * h)
    return {k: v[0] for k, v in rec.items()}


# transfer byte accounting: the packed staging path counts its buffer's
# exact nbytes; every other path uses the shared whole-batch definition
_db_nbytes = transfer_nbytes


class _OpenBatch:
    """Accumulates tuples for one destination.

    The watermark folds the MINIMUM frontier, as the reference does
    (``Batch_CPU_t::addTuple``, ``batch_cpu_t.hpp:51-205``): a downstream
    host operator may unpack the batch and re-emit singles each carrying the
    batch stamp, and a max-fold would let the first single's watermark fire
    windows ahead of its batch-siblings still in flight on the same channel,
    silently dropping them as late.  The tighter newest frontier travels
    separately as ``DeviceBatch.frontier`` (see batch.py), valid only for
    the consuming operator's own place-then-fire step."""

    __slots__ = ("items", "tss", "wm", "shared", "tids", "any_tid")

    def __init__(self):
        self.items: list = []
        self.tss: list = []
        self.wm: int = WM_NONE
        self.shared: bool = False
        self.tids: list = []
        self.any_tid: bool = False

    @hot_path
    def add(self, item, ts, wm, shared=False, tid=None):
        self.items.append(item)
        self.tss.append(ts)
        self.tids.append(tid)
        self.any_tid |= tid is not None
        self.shared |= shared
        if wm != WM_NONE:
            self.wm = wm if self.wm == WM_NONE else min(self.wm, wm)

    def ids_or_none(self):
        return self.tids if self.any_tid else None


class ForwardEmitter(Emitter):
    """FORWARD / REBALANCING routing of host tuples: round-robin over
    destinations, accumulating per-destination batches of ``output_batch_size``
    (reference ``forward_emitter.hpp:49-285``)."""

    def __init__(self, dests, output_batch_size):
        super().__init__(dests, output_batch_size)
        self._open = [_OpenBatch() for _ in dests]
        self._next = 0

    @hot_path
    def emit(self, item, ts, wm, shared=False, tid=None):
        d = self._next
        self._next = (self._next + 1) % len(self.dests)
        ob = self._open[d]
        ob.add(item, ts, wm, shared, tid)
        if len(ob.items) >= max(1, self.output_batch_size):
            self._flush_dest(d)

    def _flush_dest(self, d):
        ob = self._open[d]
        if ob.items:
            self._send(d, HostBatch(ob.items, ob.tss, ob.wm,
                                    shared=ob.shared,
                                    ids=ob.ids_or_none(),
                                    trace=self._new_trace()))
            self._open[d] = _OpenBatch()

    def emit_host_batch(self, hb):
        # batch-granular round-robin; flush the destination's open batch
        # first so per-destination arrival order is preserved
        d = self._next
        self._next = (self._next + 1) % len(self.dests)
        self._flush_dest(d)
        self._send(d, hb)

    def flush(self, wm):
        for d in range(len(self.dests)):
            self._flush_dest(d)


class KeyByEmitter(Emitter):
    """KEYBY routing: ``hash(key) % num_dests`` per tuple with per-destination
    open batches (reference ``keyby_emitter.hpp:216-257``)."""

    def __init__(self, dests, output_batch_size,
                 key_extractor: Callable[[Any], Any]):
        super().__init__(dests, output_batch_size)
        self.key_extractor = key_extractor
        self._open = [_OpenBatch() for _ in dests]
        #: shard-plane sketch (monitoring/shard_ledger.py), attached by
        #: the ledger at graph build; None leaves one check per FLUSH —
        #: the per-tuple emit path carries no sketch work at all (the
        #: flush path samples one key per shipped batch instead)
        self._sketch = None
        #: reshard-executor key→shard override (windflow_tpu/serving):
        #: moved keys route to their assigned shard BEFORE the hash —
        #: the advisor's move_keys contract.  None leaves one check per
        #: tuple (a plain attribute read, no allocation)
        self._override = None

    def set_override(self, override) -> None:
        """Install/replace the key→destination override map (reshard
        executor moves; restore re-installs checkpointed maps)."""
        self._override = dict(override) if override else None

    @hot_path
    def emit(self, item, ts, wm, shared=False, tid=None):
        key = self.key_extractor(item)
        d = None
        if self._override is not None:
            d = self._override.get(key)
        if d is None:
            d = stable_hash(key) % len(self.dests)
        ob = self._open[d]
        ob.add(item, ts, wm, shared, tid)
        if len(ob.items) >= max(1, self.output_batch_size):
            self._flush_dest(d)

    def _flush_dest(self, d):
        ob = self._open[d]
        if ob.items:
            if self._sketch is not None:
                try:
                    key = self.key_extractor(ob.items[0])
                except Exception:  # lint: broad-except-ok (telemetry
                    # sampling of an arbitrary user key — a throwing
                    # extractor degrades the sketch, never routing)
                    key = None
                # exactly ONE note_flush per shipped batch (note_flush
                # itself never raises), so loads stay single-counted
                self._sketch.note_flush(d, len(ob.items), key)
            self._send(d, HostBatch(ob.items, ob.tss, ob.wm,
                                    shared=ob.shared,
                                    ids=ob.ids_or_none(),
                                    trace=self._new_trace()))
            self._open[d] = _OpenBatch()

    def flush(self, wm):
        for d in range(len(self.dests)):
            self._flush_dest(d)


class BroadcastEmitter(Emitter):
    """BROADCAST routing: every destination sees every tuple (reference
    ``broadcast_emitter.hpp``).  Batches are built once and the same immutable
    HostBatch object is delivered to all inboxes."""

    def __init__(self, dests, output_batch_size):
        super().__init__(dests, output_batch_size)
        self._ob = _OpenBatch()

    def emit(self, item, ts, wm, shared=False, tid=None):
        self._ob.add(item, ts, wm, shared, tid)
        if len(self._ob.items) >= max(1, self.output_batch_size):
            self.flush(wm)

    def flush(self, wm):
        if self._ob.items:
            # one immutable batch object multicast by handle; `shared` makes
            # in-place consumers copy before mutating (reference pairs the
            # delete_counter multicast with Map's copyOnWrite,
            # single_t.hpp:54, map.hpp:57-215)
            b = HostBatch(self._ob.items, self._ob.tss, self._ob.wm,
                          shared=len(self.dests) > 1 or self._ob.shared,
                          ids=self._ob.ids_or_none(),
                          trace=self._new_trace())
            for d in range(len(self.dests)):
                self._send(d, b)
            self._ob = _OpenBatch()

    def emit_host_batch(self, hb):
        self.flush(hb.watermark)
        if len(self.dests) > 1:
            hb = HostBatch(hb.items, hb.tss, hb.watermark, shared=True,
                           ids=hb.ids)
        for d in range(len(self.dests)):
            self._send(d, hb)


class _StagedPacket:
    """One finalized packed batch, pre-``stage_packed``: everything the
    per-batch ship stamps, captured at finalize time so the megastep
    plane (windflow_tpu/megastep.py) can queue K of them and either
    fold them into one scan dispatch or replay the verbatim per-batch
    ship (``_ship_packed``) in FIFO order.  ``nbytes`` is the WIRE
    buffer's size at finalize (the H2D ledger credit); ``wm_pane`` is
    filled in by the megastep edge for time-based window tails."""

    __slots__ = ("buf", "fmt", "wm", "frontier", "ts_min", "ts_max",
                 "n", "seq", "trace", "nbytes", "logical_nbytes", "pool",
                 "treedef", "dtypes", "capacity", "wm_pane")

    def __init__(self, buf, fmt, wm, frontier, ts_min, ts_max, n,
                 seq, trace, logical_nbytes, pool, treedef, dtypes,
                 capacity):
        self.buf = buf
        self.fmt = fmt
        self.wm = wm
        self.frontier = frontier
        self.ts_min = ts_min
        self.ts_max = ts_max
        self.n = n
        self.seq = seq
        self.trace = trace
        self.nbytes = buf.nbytes
        self.logical_nbytes = logical_nbytes
        self.pool = pool
        self.treedef = treedef
        self.dtypes = dtypes
        self.capacity = capacity
        self.wm_pane = None


class DeviceStageEmitter(Emitter):
    """Host→TPU boundary (reference CPU→GPU ``Forward_Emitter_GPU`` /
    ``KeyBy_Emitter_GPU`` staging paths): accumulates host records, stages one
    SoA DeviceBatch of fixed capacity ``output_batch_size``, and round-robins
    destination replicas.

    Keyed destinations need no work here: keyed TPU operators extract their
    key lane from the payload inside their own compiled program (see
    ``ops/tpu.py``), identically for staged and device-resident batches.  The
    fixed capacity keeps every staged batch the same shape, so the
    destination's compiled program never re-traces.
    """

    def __init__(self, dests, output_batch_size, mesh=None):
        if output_batch_size <= 0:
            # Parity: a device operator must be preceded by batching output
            # (reference multipipe.hpp:441-444).
            raise WindFlowError(
                "a TPU operator requires the upstream operator to set an "
                "output batch size > 0")
        super().__init__(dests, output_batch_size)
        self._ob = _OpenBatch()
        self._next = 0
        # Newest watermark seen by this emitter (monotone): staged batches
        # carry it as DeviceBatch.frontier so the consuming device operator
        # can fire time windows without the min-fold's one-batch lag — see
        # _OpenBatch and DeviceBatch.frontier for why the propagated
        # watermark stays min-folded.
        self._frontier = WM_NONE
        # Columnar accumulation: list of (cols dict, tss, per-row-wm)
        # chunks + row count.  A chunk-level watermark is only valid after
        # the chunk's LAST row — stamping a head batch of a split chunk
        # with it would let downstream time windows fire ahead of the
        # chunk's still-buffered tail rows and drop them as late.  So each
        # chunk is kept with a per-row frontier lane (given by the source,
        # or synthesized as last-row-only), and a staged batch is stamped
        # with the running max at ITS last row.
        self._col_chunks = []
        self._col_rows = 0
        # Streaming packed staging (windflow_tpu/staging): single-chip
        # packable columns bypass the chunk-accumulate/concatenate path
        # entirely — rows are written straight into a pooled staging
        # buffer at their final packed offsets, and a full buffer ships
        # as ONE fused host→device transfer.  State of the open builder
        # (the pool is looked up per batch, not captured: swapping the
        # process-wide pool via staging.set_default_pool must redirect
        # live emitters, or stats()["Staging_pool"] reports counters the
        # staging path no longer touches):
        self._builder = None
        self._b_dtypes = None
        self._b_treedef = None
        self._b_wm = WM_NONE            # running row-frontier max
        self._b_ts_min = None           # data-ts extrema of the OPEN batch
        self._b_ts_max = None
        # what this edge staged, counted where each batch is cut
        # (stats()["Staging"]): a batch shipped short of its capacity is
        # one the punctuation, a lane change or the end of stream flushed
        self.staged_batches = 0
        self.partial_batches = 0
        self.staged_tuples = 0
        # rows a producer wrote into the open builder itself
        # (packed_destination / commit_packed), counted where they are
        # written; every other row came through emit_columns or emit
        self.parsed_in_place_tuples = 0
        # (names, dtypes) of an in-place producer's columns -> (treedef,
        # dtypes in lane order, lane_off); None for lanes that cannot ride
        # the packed buffer
        self._in_place_layouts = {}
        # shard-plane key probe (monitoring/shard_ledger.HostKeyProbe):
        # attached by the ledger when this non-keyed staging edge feeds
        # a keyed device consumer whose key extraction runs in-program
        # (mesh FFAT / dense reduce / stateful) — the probe applies that
        # extractor host-side at batch granularity; None leaves one
        # check per columnar chunk / per shipped record batch
        self._shard_probe = None
        # wire plane (windflow_tpu/wire.py): enabled by wire.attach_wire
        # at graph build when the feeding edge has a declared/inferred
        # record spec — finished packed buffers are re-encoded lane by
        # lane (delta/dict/const/bit-pack) into a pooled wire buffer and
        # the inverse decode rides the SAME unpack dispatch on device.
        # Off/downgraded leaves exactly one flag check per finalize.
        self._wire_on = False
        self._wire_reseed = 64
        self._wire_measured = False
        self._wire_encoders = {}
        # megastep plane (windflow_tpu/megastep.py): attached by
        # PipeGraph._build when this edge feeds an eligible device tail
        # and Config.megastep_sweeps resolves to K>1 — finalized packed
        # batches are OFFERED to the edge, which folds K of them into
        # one lax.scan dispatch.  None (the K=1 kill switch and every
        # ineligible edge) leaves exactly one check per finalize and
        # the verbatim per-batch ship below.
        self._megastep = None
        # Multi-chip: lay staged batch lanes out data-sharded over the mesh
        # so downstream sharded programs consume them without a reshard
        # (parallel/mesh.py batch_sharding).
        self._stage_target = None
        #: lanes THIS process contributes per staged batch: equals the
        #: batch capacity single-process; on a multi-host mesh each of the
        #: P processes stages capacity/P local lanes and the global batch
        #: is assembled shard-locally (batch.py _stage_soa; SURVEY §5.8)
        self._local_cap = output_batch_size
        if mesh is not None:
            from windflow_tpu.parallel.mesh import batch_sharding
            if output_batch_size % math.prod(mesh.devices.shape):
                raise WindFlowError(
                    f"output batch size {output_batch_size} not divisible "
                    f"by the mesh's {math.prod(mesh.devices.shape)} devices")
            self._stage_target = batch_sharding(mesh)
            if jax.process_count() > 1:
                # fully-sharded staging: each process's lanes land at its
                # own (data, key) blocks (batch.py _stage_soa); consumers
                # gather over both axes (mesh.py ingest="flat")
                from jax.sharding import (NamedSharding,
                                          PartitionSpec as _P)

                from windflow_tpu.parallel.mesh import DATA_AXIS, KEY_AXIS
                self._stage_target = NamedSharding(
                    mesh, _P((DATA_AXIS, KEY_AXIS)))
                self._local_cap = output_batch_size // jax.process_count()

    def _advance_frontier(self, wm):
        if wm != WM_NONE and wm > self._frontier:
            self._frontier = wm

    def _count_staged(self, n: int) -> None:
        self.staged_batches += 1
        self.partial_batches += n < self._local_cap
        self.staged_tuples += n

    def _local_share(self, nbytes: int) -> int:
        """This PROCESS's share of a staged batch's bytes: on a
        multi-host mesh each host packs and ships only its local chips'
        shard (batch.py ``_stage_soa``), so crediting the GLOBAL batch
        size on every host would multiply the H2D ledger by the process
        count (the per-host attribution the sweep ledger's wire
        subsection surfaces)."""
        if self._stage_target is not None and jax.process_count() > 1:
            return nbytes // jax.process_count()
        return nbytes

    def _count_h2d(self, db: DeviceBatch) -> None:
        """Credit one unpacked staged batch: what the host shipped (on a
        mesh every shard of every lane, a replicated lane once per chip:
        the ``bytes`` of its ``wf.h2d`` span) and the batch counted once."""
        if self.stats is not None:
            self.stats.h2d_bytes += staged_nbytes(db)
            self.stats.h2d_logical_bytes += \
                self._local_share(_db_nbytes(db))

    def enable_wire(self, reseed_every: int = 64,
                    measured: bool = False) -> None:
        """Attach the wire plane to this emitter's packed staging
        (called by ``wire.attach_wire`` at graph build — only for edges
        whose record spec is declared/inferred, the WF606 contract).
        ``measured`` (``Config.wire_compression`` "auto") lets each
        encoder decide from its own link and codec times whether the
        edge encodes at all; otherwise the codec is forced.  Mesh-
        sharded targets ignore the flag: their transfers are assembled
        per shard, not packed."""
        self._wire_on = self._stage_target is None
        self._wire_reseed = max(1, reseed_every)
        self._wire_measured = measured

    def _wire_encoder(self, dtypes, capacity: int, pool):
        key = (dtypes, capacity)
        enc = self._wire_encoders.get(key)
        if enc is None:
            from windflow_tpu.wire import WireEncoder
            enc = WireEncoder(dtypes, capacity,
                              reseed_every=self._wire_reseed,
                              link_rate=pool.link_rate
                              if self._wire_measured else None)
            self._wire_encoders[key] = enc
        return enc

    def emit(self, item, ts, wm, shared=False, tid=None):
        # `shared` is irrelevant here: staging materializes new device
        # arrays from the record's values, never aliasing the host object;
        # `tid` is dropped — device edges are DEFAULT-mode only.
        self._advance_frontier(wm)
        self._ob.add(item, ts, wm)
        if len(self._ob.items) >= self._local_cap:
            # capacity flush: INTERNAL, so a megastep edge keeps
            # accumulating record-path batches (flush() below is the
            # external entry point that drains the megastep queue)
            self._flush_impl(wm)

    def emit_columns(self, cols, tss, wm, row_wms=None):
        """Columnar fast path.  Single-chip packable columns take the
        STREAMING packed route: rows are written directly into a pooled
        staging buffer at their final packed offsets
        (staging.PackedBatchBuilder) and a full buffer ships as ONE fused
        host→device transfer — no chunk concatenate, no per-batch numpy
        allocation, no per-lane device_put (the reference's recycled
        pinned staging, ``forward_emitter_gpu.hpp:254-300`` +
        ``recycling.hpp``).  Mesh-sharded targets and non-packable lanes
        fall back to the chunk-accumulate path below."""
        if self._shard_probe is not None:
            self._shard_probe.columns(cols, len(tss))
        if self._stage_target is None and not self._col_chunks:
            leaves, treedef = jax.tree.flatten(
                {nm: np.asarray(a) for nm, a in cols.items()})
            if all(l.ndim == 1 and staging.packable_dtype(l.dtype)
                   for l in leaves):
                with flightrec.span("wf.pack", n=len(tss)):
                    self._emit_columns_packed(leaves, treedef, tss, wm,
                                              row_wms)
                return
        if self._builder is not None:
            # falling back mid-stream: ship the open packed rows first so
            # per-destination arrival order is preserved
            self._finalize_builder()
        # the pack of a mesh (or unpackable-lane) edge: the chunk joins
        # the open batch, and a batch that fills is assembled and
        # shipped (its wf.h2d nests here and is not this span's time)
        with flightrec.span("wf.pack", n=len(tss)):
            self._emit_columns_chunked(cols, tss, wm, row_wms)

    def _emit_columns_packed(self, leaves, treedef, tss, wm, row_wms):
        """Streaming packed staging (see emit_columns).  Watermark lane
        contract matches the chunked path: a staged batch is stamped with
        the running row-frontier max at ITS last row; a chunk-level ``wm``
        is applied only once the chunk's last row is packed."""
        tss = np.ascontiguousarray(tss, np.int64)
        dtypes = tuple(str(l.dtype) for l in leaves)
        m = len(tss)
        pos = 0
        while pos < m:
            b = self._builder_for(treedef, dtypes)
            take = min(b.room, m - pos)
            sl = slice(pos, pos + take)
            tsl = tss[sl]
            b.append([l[sl] for l in leaves], tsl)
            pos += take
            if row_wms is not None:
                w = int(np.max(row_wms[sl]))
            else:
                # a chunk-level wm is valid only after the chunk's LAST row
                w = wm if pos == m else WM_NONE
            self._note_packed_rows(int(tsl.min()), int(tsl.max()), w)

    def _builder_for(self, treedef, dtypes):
        """The open packed builder for lanes of this structure; a change
        of structure mid-stream ships the open rows first."""
        if self._builder is not None and (treedef != self._b_treedef
                                          or dtypes != self._b_dtypes):
            self._finalize_builder()
        if self._builder is None:
            self._b_treedef = treedef
            self._b_dtypes = dtypes
            self._builder = staging.PackedBatchBuilder(
                dtypes, self.output_batch_size)
            self._b_ts_min = None
            self._b_ts_max = None
        return self._builder

    def _note_packed_rows(self, ts_lo: int, ts_hi: int, w: int) -> None:
        """Rows joined the open builder: fold their data-ts extrema and
        the row frontier after the last of them (``w``) into the open
        batch's stamps, and ship the batch once it is full."""
        if self._b_ts_min is None or ts_lo < self._b_ts_min:
            self._b_ts_min = ts_lo
        if self._b_ts_max is None or ts_hi > self._b_ts_max:
            self._b_ts_max = ts_hi
        if w != WM_NONE and w > self._b_wm:
            self._b_wm = w
        if self._builder.room == 0:
            self._finalize_builder()

    def packed_destination(self, names, dtypes):
        """The in-place route (see :meth:`Emitter.packed_destination`),
        offered exactly where ``emit_columns`` takes the streaming packed
        route: one chip, no chunk-accumulated rows open, packable
        lanes."""
        if self._stage_target is not None or self._col_chunks:
            return None
        try:
            lay = self._in_place_layouts[names, dtypes]
        except KeyError:
            lay = None
            if all(staging.packable_dtype(d) for d in dtypes):
                # the lane order emit_columns gives the same columns
                order, treedef = jax.tree.flatten(
                    {nm: i for i, nm in enumerate(names)})
                lane_dtypes = tuple(dtypes[i] for i in order)
                offs = staging.PackedBatchBuilder.lane_layout(
                    lane_dtypes, self.output_batch_size)
                lay = (treedef, lane_dtypes, np.array(
                    [offs[order.index(i)] for i in range(len(names))]
                    + [offs[-1]], np.int64))
            self._in_place_layouts[names, dtypes] = lay
        if lay is None:
            return None
        treedef, lane_dtypes, lane_off = lay
        return self._builder_for(treedef, lane_dtypes), lane_off

    def commit_packed(self, m: int, ts_lo: int, ts_hi: int, w: int) -> None:
        """``m`` rows were written in place into the builder
        :meth:`packed_destination` handed out; ``ts_lo`` / ``ts_hi`` are
        their data-ts extrema and ``w`` the row frontier after the last
        of them — the batch bookkeeping ``_emit_columns_packed`` reads
        off its columns, given by the writer instead.  A shard-plane key
        probe reads the rows back from the staging buffer
        (``rows_view``: views, an int64 lane's two planes combined)."""
        if self._shard_probe is not None:
            b = self._builder
            self._shard_probe.columns(jax.tree.unflatten(
                self._b_treedef, b.rows_view(b.n, m)), m)
        with flightrec.span("wf.pack", n=m):
            self._builder.advance(m)
            self.parsed_in_place_tuples += m
            self._note_packed_rows(ts_lo, ts_hi, w)

    def _finalize_builder(self, fallback_wm: int = WM_NONE) -> None:
        """Ship the open packed batch (padding derived on device from the
        fill count; the pooled buffer is recycled gated on the unpack —
        batch.stage_packed)."""
        b, self._builder = self._builder, None
        if b is None:
            return
        if b.n == 0:
            b.abandon()
            return
        wm = self._b_wm if self._b_wm != WM_NONE else fallback_wm
        self._advance_frontier(wm)
        self._count_staged(b.n)
        seq = self._new_seq()
        with flightrec.span("wf.wire.encode", batch=seq, n=b.n,
                            cap=b.capacity) as sp:
            buf = b.finish()
            logical_nbytes = buf.nbytes
            fmt = None
            if self._wire_on:
                # wire plane (windflow_tpu/wire.py): lane-wise re-encode
                # of the finished logical buffer; a batch compression
                # cannot shrink ships the logical buffer unchanged (fmt
                # None), and so does every batch of an edge that
                # measured its link faster than its codec
                enc = self._wire_encoder(self._b_dtypes, b.capacity,
                                         b.pool)
                if enc.ships_raw:
                    enc.stats.note_raw(logical_nbytes)
                else:
                    buf, fmt = enc.encode(buf, pool=b.pool)
            sp.note(bytes=buf.nbytes, logical=logical_nbytes)
        if self.stats is not None:
            # the packed path's H2D transfer is exactly this buffer;
            # the logical counter keeps compression from silently
            # inflating bytes-derived ratios (wire-round honesty fix)
            self.stats.h2d_bytes += buf.nbytes
            self.stats.h2d_logical_bytes += logical_nbytes
        pkt = _StagedPacket(buf, fmt, wm, self._frontier,
                            self._b_ts_min, self._b_ts_max, b.n, seq,
                            self._trace_of(seq, flightrec.STAGED),
                            logical_nbytes, b.pool, self._b_treedef,
                            self._b_dtypes, b.capacity)
        ms = self._megastep
        if ms is not None and ms.offer(pkt):
            return
        self._ship_packed(pkt)

    def _ship_packed(self, pkt: "_StagedPacket") -> None:
        """The verbatim per-batch ship of one finalized packed batch —
        the K=1 path, the megastep warm-up/fallback path, and
        ``MegastepEdge.drain_remainder``'s partial-group path.  Stamps
        come from the PACKET (captured at finalize), not the emitter:
        a queued batch shipped later must not borrow a frontier that
        advanced past it."""
        db = stage_packed(pkt.buf, pkt.treedef, pkt.dtypes,
                          pkt.capacity, pkt.n, watermark=pkt.wm,
                          device=None, frontier=pkt.frontier,
                          ts_max=pkt.ts_max, ts_min=pkt.ts_min,
                          pool=pkt.pool, trace=pkt.trace, seq=pkt.seq,
                          wire=pkt.fmt,
                          logical_nbytes=pkt.logical_nbytes)
        d = self._next
        self._next = (self._next + 1) % len(self.dests)
        self._send(d, db)

    def _emit_columns_chunked(self, cols, tss, wm, row_wms=None):
        """Chunk-accumulate staging (mesh-sharded targets, non-packable
        lanes): stage full batches with one concatenate + one transfer.
        See the ``_col_chunks`` note for the watermark lane."""
        if row_wms is None:
            # chunk-level wm: valid only after the last row
            row_wms = np.full(len(tss), WM_NONE, np.int64)
            if len(tss) and wm != WM_NONE:
                row_wms[-1] = wm
        self._col_chunks.append((cols, tss, row_wms))
        self._col_rows += len(tss)
        cap = self._local_cap
        if self._col_rows < cap:
            return
        names = list(self._col_chunks[0][0])
        cat = {n: _concat([c[0][n] for c in self._col_chunks])
               for n in names}
        tcat = _concat([c[1] for c in self._col_chunks])
        wcat = np.maximum.accumulate(
            _concat([c[2] for c in self._col_chunks]))
        total = len(tcat)
        for lo in range(0, total - total % cap, cap):
            hi = lo + cap
            bwm = int(wcat[hi - 1])
            self._advance_frontier(bwm)
            self._stage_columns(
                {n: a[lo:lo + cap] for n, a in cat.items()},
                tcat[lo:lo + cap], bwm)
        rem = total % cap
        self._col_chunks = [] if rem == 0 else [
            ({n: a[total - rem:] for n, a in cat.items()},
             tcat[total - rem:], wcat[total - rem:])]
        self._col_rows = rem

    def _stage_columns(self, cols, tss, wm):
        self._count_staged(len(tss))
        seq = self._new_seq()
        db = columns_to_device(cols, tss, self.output_batch_size,
                               watermark=wm, device=self._stage_target,
                               frontier=self._frontier,
                               trace=self._trace_of(seq, flightrec.STAGED),
                               seq=seq)
        self._count_h2d(db)
        d = self._next
        self._next = (self._next + 1) % len(self.dests)
        self._send(d, db)

    def flush(self, wm):
        """EXTERNAL flush (EOS, punctuation cadence, durability
        quiesce): ship everything open, then drain any megastep queue
        per-batch — a checkpoint or a propagated watermark must never
        overtake packed batches parked for a future megastep."""
        self._flush_impl(wm)
        ms = self._megastep
        if ms is not None:
            ms.external_drain()

    def _flush_impl(self, wm):
        if self._builder is not None:
            self._finalize_builder(fallback_wm=wm)
        if self._col_chunks:
            with flightrec.span("wf.pack", n=self._col_rows):
                names = list(self._col_chunks[0][0])
                cat = {n: _concat([c[0][n] for c in self._col_chunks])
                       for n in names}
                tcat = _concat([c[1] for c in self._col_chunks])
                # everything buffered is fully staged by this batch, so
                # the newest row frontier applies
                w = int(max(int(c[2].max()) for c in self._col_chunks))
                self._col_chunks = []
                self._col_rows = 0
                self._advance_frontier(w)
                self._stage_columns(cat, tcat, w if w != WM_NONE else wm)
        self._advance_frontier(wm)
        if not self._ob.items:
            return
        if self._shard_probe is not None:
            self._shard_probe.items(self._ob.items)
        if self._wire_on:
            # record-path wire route: stack the open batch to SoA and
            # ship through the packed/wire pipeline.  Stamping is kept
            # EXACTLY the record path's (the open batch's min-folded
            # watermark, nothing newer), so wire on/off runs stay
            # record-for-record identical.
            from windflow_tpu.batch import _stack_records
            leaves = treedef = None
            try:
                soa = _stack_records(self._ob.items)
                leaves, treedef = jax.tree.flatten(soa)
                ok = all(getattr(l, "ndim", 0) == 1
                         and staging.packable_dtype(l.dtype)
                         for l in leaves)
            except Exception:  # lint: broad-except-ok (arbitrary user
                # records may not stack to SoA columns — ANY failure
                # means "take the uncompressed record path below")
                ok = False
            if ok:
                ob, self._ob = self._ob, _OpenBatch()
                tss = np.ascontiguousarray(ob.tss, np.int64)
                # stamp THIS batch with the open batch's min-folded wm
                # (exact record-path parity), then restore the running
                # row-frontier max: on a mixed record+columnar emitter
                # a later columnar batch must never stamp LOWER than
                # the wire-off run would (the frontier only rises)
                prev_wm = self._b_wm
                self._b_wm = ob.wm
                self._emit_columns_packed(leaves, treedef, tss,
                                          WM_NONE, None)
                self._b_wm = ob.wm
                self._finalize_builder()
                self._b_wm = max(prev_wm, ob.wm)
                return
        hb = HostBatch(self._ob.items, self._ob.tss, self._ob.wm)
        self._count_staged(len(hb))
        seq = self._new_seq()
        db = host_to_device(hb, capacity=self.output_batch_size,
                            device=self._stage_target,
                            frontier=self._frontier,
                            trace=self._trace_of(seq, flightrec.STAGED),
                            seq=seq)
        self._count_h2d(db)
        d = self._next
        self._next = (self._next + 1) % len(self.dests)
        self._send(d, db)
        self._ob = _OpenBatch()


class KeyedDeviceStageEmitter(Emitter):
    """Host→TPU boundary with KEYBY routing (reference CPU→GPU
    ``KeyBy_Emitter_GPU``, ``keyby_emitter_gpu.hpp:400-476``): tuples are
    partitioned by ``splitmix64(key) % num_dests`` into per-destination
    staged batches, so every key's tuples flow through exactly one replica
    in arrival order — the invariant that makes shared per-key device state
    (ops/tpu_stateful.py) correct at parallelism > 1, exactly as the
    reference's keyby routing does for its stateful GPU operators
    (``std::hash % num_dests``, ``keyby_emitter.hpp:216``).  Hashing (the
    native ``wf_keyby_partition``) rather than a plain modulo keeps
    structured key sets (all-even ids, strided ids) from landing on one
    replica."""

    def __init__(self, dests, output_batch_size, key_extractor, mesh=None):
        super().__init__(dests, output_batch_size)
        self.key_extractor = key_extractor
        # one single-destination staging emitter per partition
        self._inner = [DeviceStageEmitter([d], output_batch_size, mesh=mesh)
                       for d in dests]
        #: shard-plane sketch (monitoring/shard_ledger.py), attached by
        #: the ledger at graph build; None leaves one check per tuple /
        #: per columnar chunk.  The per-tuple path buffers truncated
        #: keys (plain list appends) and bulk-updates every 256 tuples.
        self._sketch = None
        self._sk_buf = []
        #: key compactor (parallel/compaction.py), attached by the graph
        #: build when the consumer compacts: every key column admits at
        #: this boundary (host-fed consumers see a miss-free remap), and
        #: evictable compactors with placement_override route slotted
        #: keys by ``slot % n`` instead of the splitmix hash — hot keys
        #: balanced deterministically over the replicas.  None leaves
        #: one check per emit path.
        self._compactor = None
        #: reshard-executor key→shard override (windflow_tpu/serving):
        #: moved k32 keys route to their assigned shard BEFORE both the
        #: compaction placement and the splitmix hash
        self._override = None
        #: split_hot_key pre-aggregation (the executor's partial-combine
        #: tier): tuples of the named hot keys fold through the
        #: consumer's associative combiner AT THIS BOUNDARY and ship as
        #: one partial record per flush — the hot key's downstream load
        #: drops by the fold factor while the final per-key aggregate
        #: is unchanged (associativity; per-batch partials coarsen,
        #: the documented split semantic).  None leaves one check per
        #: emit path.
        self._preagg = None         # {"keys": set, "comb": fn}
        self._preagg_acc = {}       # k32 -> [record, max_ts, n]
        self.preagg_folds = 0       # tuples absorbed into partials

    def set_override(self, override) -> None:
        """Install/replace the key→destination override map, keyed by
        the int32-truncated key the device state collapses to."""
        if not override:
            self._override = None
            return
        self._override = {self._key32(k): d for k, d in override.items()}

    def set_preagg(self, keys, comb) -> None:
        """Enable the pre-aggregating partial combine for ``keys``
        (split_hot_key executor action); ``comb`` is the consumer's
        associative record combiner.  ``None``/empty disables."""
        self._flush_preagg(WM_NONE)
        if not keys or comb is None:
            self._preagg = None
            return
        self._preagg = {"keys": {self._key32(k) for k in keys},
                        "comb": comb}

    def _fold_into(self, k32, item, ts):
        acc = self._preagg_acc.get(k32)
        if acc is None:
            self._preagg_acc[k32] = [item, ts, 1]
            return
        acc[0] = self._preagg["comb"](acc[0], item)
        acc[1] = max(acc[1], ts)
        acc[2] += 1
        self.preagg_folds += 1

    def _flush_preagg(self, wm) -> None:
        if not self._preagg_acc:
            return
        acc, self._preagg_acc = self._preagg_acc, {}
        for k32, (item, ts, _n) in acc.items():
            self._route_one(k32, item, ts, wm)

    def bind_observability(self, stats, ring, flight):
        super().bind_observability(stats, ring, flight)
        for e in self._inner:
            e.bind_observability(stats, ring, flight)

    @staticmethod
    def _key32(k) -> int:
        """Truncate a numeric key to the int32 key space the device operator
        interns (its extractor output is cast to int32 on device) — routing
        must collapse exactly the keys the state table collapses, or one
        logical key would straddle replicas.  Canonical rule:
        ``basic.int32_key`` (shared with compaction admission, the
        reshard executor's state moves, and rescale re-bucketing)."""
        return int32_key(k)

    def emit(self, item, ts, wm, shared=False, tid=None):
        # scalar splitmix64 (bit-identical to the native/columnar path) —
        # pure int ops, no per-tuple FFI or array allocation
        k32 = self._key32(self.key_extractor(item))
        pa = self._preagg
        if pa is not None and k32 in pa["keys"]:
            self._fold_into(k32, item, ts)
            return
        self._route_one(k32, item, ts, wm)

    def _route_one(self, k32, item, ts, wm):
        comp = self._compactor
        d = None
        if comp is not None:
            try:
                comp.observe_one(k32)
                if comp.placement_override:
                    d = comp.place_one(k32, len(self.dests))
            except Exception:  # lint: broad-except-ok (admission is
                # telemetry-adjacent host work: a compactor failure
                # deactivates the plane, it must never take routing
                # down — the HostKeyProbe stance)
                comp.deactivate()
                self._compactor = None
        if self._override is not None:
            # executor move wins over every derived placement: the key
            # was moved deliberately, and state moved with it
            o = self._override.get(k32)
            if o is not None:
                d = o
        if d is None:
            d = splitmix64_int(k32) % len(self.dests)
        self._inner[d].emit(item, ts, wm)
        if self._sketch is not None:
            self._sk_buf.append(k32)
            if len(self._sk_buf) >= 256:
                self._drain_sketch_buf()

    def _drain_sketch_buf(self):
        buf, self._sk_buf = self._sk_buf, []
        try:
            # placement counts derive inside update_host from the same
            # splitmix hash this emit path routed with
            self._sketch.update_host(np.asarray(buf, np.int64))
        except Exception:  # lint: broad-except-ok (telemetry on the
            # staging path: a sketch failure disables the sketch, it
            # must never take routing down — the HostKeyProbe stance)
            self._sketch = None

    def emit_columns(self, cols, tss, wm, row_wms=None):
        from windflow_tpu import native
        n = len(self.dests)
        keys = None
        try:
            # Vectorized: per-record key fns are elementwise field math, so
            # they usually apply directly to the SoA columns.
            k = np.asarray(self.key_extractor(cols))
            if k.shape == (len(tss),):
                # int64→int32: the device's int32 truncation first, so
                # routing collapses exactly the keys the state collapses
                keys = k.astype(np.int64).astype(np.int32).astype(np.int64)
        except Exception:   # lint: broad-except-ok (speculative
            # vectorization probe of an arbitrary user extractor — ANY
            # failure means "not elementwise", handled by the per-row
            # fallback below)
            pass
        if keys is None:
            # Non-elementwise or scalar-returning extractor: per-row path.
            keys = np.array(
                [self._key32(self.key_extractor(
                    {k: v[i].item() for k, v in cols.items()}))
                 for i in range(len(tss))], np.int64)
        pa = self._preagg
        if pa is not None:
            hot = np.isin(keys, np.fromiter(pa["keys"], np.int64,
                                            len(pa["keys"])))
            if hot.any():
                self._fold_columns(pa, cols, tss, keys, hot)
                keep = ~hot
                if not keep.any():
                    return
                cols = {k: np.asarray(v)[keep] for k, v in cols.items()}
                tss = tss[keep]
                keys = keys[keep]
                if row_wms is not None:
                    row_wms = row_wms[keep]
        comp = self._compactor
        if comp is not None:
            try:
                # admission BEFORE the batch ships: host-fed compacted
                # consumers never see a remap miss
                comp.observe(keys)
            except Exception:  # lint: broad-except-ok (admission must
                # never take routing down — the HostKeyProbe stance)
                comp.deactivate()
                comp = self._compactor = None
        if comp is not None and comp.placement_override:
            # remap placement: slotted (hot) keys go to slot % n — the
            # same destinations the scalar emit path picks
            dest = comp.place_np(keys, n)
            counts = np.bincount(dest, minlength=n)
        else:
            # native C hash+count partition (wf_host.cpp
            # wf_keyby_partition)
            dest, counts = native.keyby_partition(keys, n)
        if self._override is not None:
            # executor moves re-place their keys over the derived
            # placement (a handful of entries: the advisor's move list)
            dest = np.asarray(dest).copy()
            for k, d_ov in self._override.items():
                dest[keys == k] = d_ov
            counts = np.bincount(dest, minlength=n)
        if self._sketch is not None:
            try:
                # the key column + per-destination counts already exist
                # here: the shard-plane update is bincount passes over
                # them
                self._sketch.update_host(keys, counts=counts)
            except Exception:  # lint: broad-except-ok (telemetry on the
                # staging path: a sketch failure disables the sketch,
                # never routing — the HostKeyProbe stance)
                self._sketch = None
        for d in range(n):
            if counts[d]:
                idx = np.nonzero(dest == d)[0]
                # the row frontier is global (covers rows of every
                # partition up to that point), so slicing it per partition
                # keeps each channel's stamps valid
                self._inner[d].emit_columns(
                    {k: v[idx] for k, v in cols.items()}, tss[idx], wm,
                    row_wms[idx] if row_wms is not None else None)

    def _fold_columns(self, pa, cols, tss, keys, hot) -> None:
        """Columnar half of the pre-aggregating partial combine: the hot
        rows of each hot key log-fold through the consumer's combiner
        (vectorized numpy halving — log2(n) combiner calls, associative
        regrouping only, the dense-path contract) into the running
        partial."""
        comb = pa["comb"]
        arrs = {n: np.asarray(v) for n, v in cols.items()}
        for k in np.unique(keys[hot]):
            idx = np.nonzero(keys == k)[0]
            rec = {n: v[idx] for n, v in arrs.items()}
            folded = _log_fold(comb, rec, len(idx))
            self.preagg_folds += len(idx) - 1
            self._fold_into(int(k), folded, int(tss[idx].max()))

    def emit_device_batch(self, batch):
        raise WindFlowError(
            "keyed staging emitter received a device batch; TPU→TPU keyed "
            "edges use DeviceKeyByEmitter")

    def flush(self, wm):
        self._flush_preagg(wm)
        if self._sketch is not None and self._sk_buf:
            self._drain_sketch_buf()
        for e in self._inner:
            e.flush(wm)

    def propagate_punctuation(self, wm):
        self._flush_preagg(wm)
        for e in self._inner:
            e.propagate_punctuation(wm)


class AlignedMeshStageEmitter(Emitter):
    """Host→mesh staging with KEY-ALIGNED placement (ROADMAP item 4b):
    each record is staged directly into the block of the ``(data,
    key)``-sharded batch owned by the key shard that owns its key, so
    the consumer's sharded program skips the data-axis ``all_gather``
    the ICI model names dominant (~232 modeled B/tuple vs ~17 B
    payload on the sharded FFAT graphs) — the consuming FFAT step compiles its
    ``ingest="aligned"`` variant (parallel/mesh.py) whose gather is the
    identity on a 1-wide data axis and a kk-times-smaller within-column
    gather otherwise.

    Placement is the STRUCTURAL dense-range owner ``key // K_local`` —
    exactly the ownership ``mesh._ffat_shard_layout``'s ``key_base_fn``
    rebases by, so a tuple can never land on a shard that would drop
    it.  Reshard-executor key moves deliberately do NOT apply here
    (``set_override`` refuses loudly): the consumer's ownership is
    compiled into the sharded program, so an emitter-side move would
    stage a key onto a column whose shard masks it out-of-range and
    silently drops it — a mesh graph's reshard mechanism is the
    rescale-on-restore path (docs/DURABILITY.md), matching the PR-12
    executor limits.  Batches
    assemble per-column with per-block prefix validity computed on host
    (alignment breaks the single-fill-count derivation), and a shipped
    batch's watermark is capped at the minimum data timestamp of any
    row still buffered — a skew-retained row must never become late
    against its own channel's stamp.  Skewed streams reduce batch
    occupancy (a hot column fills while cold columns idle); that cost
    is visible in ``stats()`` occupancy and is the reshard advisor's
    problem, not a correctness risk."""

    def __init__(self, dests, output_batch_size, key_extractor, mesh,
                 max_keys: int):
        super().__init__(dests, output_batch_size)
        from jax.sharding import NamedSharding, PartitionSpec as _P

        from windflow_tpu.parallel.mesh import DATA_AXIS, KEY_AXIS
        kk = mesh.shape[KEY_AXIS]
        dd = mesh.shape[DATA_AXIS]
        if output_batch_size % (kk * dd):
            raise WindFlowError(
                f"output batch size {output_batch_size} not divisible by "
                f"the mesh's {kk * dd} devices (key-aligned ingest)")
        if max_keys % kk:
            raise WindFlowError(
                f"max_keys {max_keys} not divisible by the key axis {kk}")
        if jax.process_count() > 1:
            raise WindFlowError(
                "key-aligned ingest is single-process (multi-host meshes "
                "stage fully-sharded local lanes)")
        self.key_extractor = key_extractor
        self._kk, self._dd = kk, dd
        self._K_local = max_keys // kk
        self._col_cap = output_batch_size // kk
        self._blk = output_batch_size // (kk * dd)
        self._sharding = NamedSharding(mesh, _P((DATA_AXIS, KEY_AXIS)))
        # per-key-shard-column buffers: columnar chunks + record items
        self._chunks = [[] for _ in range(kk)]   # [(cols dict, tss)]
        self._items = [_OpenBatch() for _ in range(kk)]
        self._rows = [0] * kk
        self._wm = WM_NONE              # running max of received stamps
        #: shard-plane key probe (monitoring/shard_ledger.HostKeyProbe):
        #: keys are host-visible at this boundary, so the ledger probes
        #: them here; None leaves one check per chunk / materialize
        self._shard_probe = None
        self.batches_shipped = 0
        self.rows_shipped = 0

    def set_override(self, override) -> None:
        """Refused: the aligned consumer's key ownership is COMPILED
        into its sharded program (``key // K_local``), so an
        emitter-side move would stage the key onto a column whose shard
        masks it out-of-range — a silent drop, never a move.  Mesh
        reshard routes through rescale-on-restore (docs/DURABILITY.md);
        raising here keeps that boundary loud if a future executor ever
        discovers this emitter."""
        if override:
            raise WindFlowError(
                "key-aligned mesh ingest cannot apply executor key "
                "moves: ownership is compiled into the sharded step "
                "(reshard a mesh graph via rescale-on-restore, "
                "docs/DURABILITY.md)")

    # -- placement -----------------------------------------------------------
    def _owner_np(self, k32: np.ndarray) -> np.ndarray:
        return np.clip(k32 // self._K_local, 0,
                       self._kk - 1).astype(np.int64)

    def _note_wm(self, wm) -> None:
        if wm != WM_NONE and wm > self._wm:
            self._wm = wm

    # -- ingest --------------------------------------------------------------
    def emit(self, item, ts, wm, shared=False, tid=None):
        self._note_wm(wm)
        k32 = int32_key(self.key_extractor(item))
        c = min(max(k32 // self._K_local, 0), self._kk - 1)
        self._items[c].add(item, ts, wm)
        self._rows[c] += 1
        if self._rows[c] >= self._col_cap:
            self._ship_one()

    def emit_columns(self, cols, tss, wm, row_wms=None):
        self._note_wm(int(np.max(row_wms)) if row_wms is not None
                      and len(row_wms) else wm)
        if self._shard_probe is not None:
            self._shard_probe.columns(cols, len(tss))
        keys = None
        try:
            k = np.asarray(self.key_extractor(cols))
            if k.shape == (len(tss),):
                keys = k.astype(np.int64).astype(np.int32) \
                    .astype(np.int64)
        except Exception:   # lint: broad-except-ok (speculative
            # vectorization probe of an arbitrary user extractor — ANY
            # failure means "not elementwise", per-row fallback below)
            pass
        if keys is None:
            keys = np.array(
                [int32_key(self.key_extractor(
                    {n: v[i].item() for n, v in cols.items()}))
                 for i in range(len(tss))], np.int64)
        own = self._owner_np(keys)
        tss = np.ascontiguousarray(tss, np.int64)
        arrs = {n: np.asarray(v) for n, v in cols.items()}
        for c in range(self._kk):
            idx = np.nonzero(own == c)[0]
            if not len(idx):
                continue
            self._chunks[c].append(
                ({n: v[idx] for n, v in arrs.items()}, tss[idx]))
            self._rows[c] += len(idx)
        while any(r >= self._col_cap for r in self._rows):
            self._ship_one()

    def emit_device_batch(self, batch):
        raise WindFlowError(
            "key-aligned staging emitter received a device batch; "
            "TPU-fed mesh consumers keep the data-sharded ingest")

    # -- assembly ------------------------------------------------------------
    def _col_take(self, c: int):
        """Materialize and take up to ``col_cap`` rows of column ``c``
        (record items stack to SoA first); the remainder stays
        buffered."""
        from windflow_tpu.batch import _stack_records
        ob = self._items[c]
        if ob.items:
            if self._shard_probe is not None:
                self._shard_probe.items(ob.items)
            soa = _stack_records(ob.items)
            if not isinstance(soa, dict):
                raise WindFlowError(
                    "key-aligned ingest stages dict-shaped records "
                    f"(got {type(ob.items[0]).__name__}); disable "
                    "Config.key_aligned_ingest for this graph")
            self._chunks[c].append(
                ({n: np.asarray(v) for n, v in soa.items()},
                 np.asarray(ob.tss, np.int64)))
            self._items[c] = _OpenBatch()
        if not self._chunks[c]:
            return None
        names = list(self._chunks[c][0][0])
        cat = {n: _concat([ch[0][n] for ch in self._chunks[c]])
               for n in names}
        tcat = _concat([ch[1] for ch in self._chunks[c]])
        m = len(tcat)
        take = min(m, self._col_cap)
        if take < m:
            self._chunks[c] = [({n: a[take:] for n, a in cat.items()},
                                tcat[take:])]
            self._rows[c] = m - take
        else:
            self._chunks[c] = []
            self._rows[c] = 0
        return {n: a[:take] for n, a in cat.items()}, tcat[:take]

    def _pending_min_ts(self):
        lo = None
        for c in range(self._kk):
            for ch in self._chunks[c]:
                if len(ch[1]):
                    m = int(ch[1].min())
                    lo = m if lo is None else min(lo, m)
            if self._items[c].tss:
                m = min(self._items[c].tss)
                lo = m if lo is None else min(lo, m)
        return lo

    def _ship_one(self) -> None:
        # the pack of an aligned edge: per-column takes assembled into
        # the (data, key) blocks; the transfer nests as wf.h2d
        with flightrec.span("wf.pack") as sp:
            sp.note(n=self._assemble_ship())

    def _assemble_ship(self) -> int:
        """Rows shipped (0: nothing was buffered)."""
        takes = [self._col_take(c) for c in range(self._kk)]
        if not any(t is not None for t in takes):
            return 0
        cap, kk, dd, blk = (self.output_batch_size, self._kk, self._dd,
                            self._blk)
        first = next(t for t in takes if t is not None)
        lanes = {n: np.zeros((cap,) + a.shape[1:], a.dtype)
                 for n, a in first[0].items()}
        ts = np.zeros(cap, np.int64)
        valid = np.zeros(cap, bool)
        total = 0
        for c, t in enumerate(takes):
            if t is None:
                continue
            colv, colt = t
            m = len(colt)
            total += m
            # column rows split row-major over the dd data blocks: row r
            # lands at block r//blk of column c — exactly the order the
            # aligned step's data-axis gather reconstructs
            for d in range(dd):
                lo = d * blk
                hi = min(m, lo + blk)
                if hi <= lo:
                    break
                g0 = (d * kk + c) * blk
                seg = slice(g0, g0 + (hi - lo))
                for n, a in colv.items():
                    lanes[n][seg] = a[lo:hi]
                ts[seg] = colt[lo:hi]
                valid[seg] = True
        if total == 0:
            return 0
        # watermark capped at the minimum buffered data timestamp: a
        # skew-retained row must never become late against this
        # channel's own stamp (frontier capped identically — the
        # place-then-fire shortcut must not outrun retained rows)
        wm = self._wm
        pend = self._pending_min_ts()
        if wm != WM_NONE and pend is not None:
            wm = min(wm, pend)
        on = ts[valid]
        ts_lo, ts_hi = int(on.min()), int(on.max())
        seq = self._new_seq()
        with flightrec.span("wf.h2d", batch=seq, n=total, cap=cap) as sp:
            payload = {n: jax.device_put(a, self._sharding)
                       for n, a in lanes.items()}
            db = DeviceBatch(payload, jax.device_put(ts, self._sharding),
                             jax.device_put(valid, self._sharding),
                             watermark=wm, size=total, frontier=wm,
                             ts_max=ts_hi, ts_min=ts_lo,
                             trace=self._trace_of(seq, flightrec.STAGED),
                             seq=seq)
            # every chip is shipped its own block only: moved == logical
            nb = _db_nbytes(db)
            sp.note(bytes=nb, logical=nb, shards=kk * dd)
        if self.stats is not None:
            self.stats.h2d_bytes += nb
            self.stats.h2d_logical_bytes += nb
        staging.device_bytes.note(nb)
        self.batches_shipped += 1
        self.rows_shipped += total
        self._send(0, db)
        return total

    def flush(self, wm):
        self._note_wm(wm)
        while any(self._rows) or any(ob.items for ob in self._items):
            before = (self.batches_shipped, self.rows_shipped)
            self._ship_one()
            if (self.batches_shipped, self.rows_shipped) == before:
                break   # defensive: never spin on an empty remainder


class DeviceKeyByEmitter(Emitter):
    """TPU→TPU KEYBY edge (reference GPU→GPU ``KeyBy_Emitter_GPU``,
    ``keyby_emitter_gpu.hpp:519-583``): one compiled program splits the batch
    into ``num_dests`` masked views by ``splitmix64(key) % num_dests`` (the
    same placement as the host-side keyed staging emitter).  The reference
    builds per-key index chains with sort kernels and copies per
    destination; here every destination shares the SAME immutable device
    buffers and differs only in its validity mask — consumers are
    mask-aware, so no sort, gather, or copy happens at the edge at all.
    Empty partitions still ship (an all-invalid mask) — skipping them
    would force a host sync on the partition counts."""

    can_emit_host_items = False

    def __init__(self, dests, key_extractor):
        super().__init__(dests, output_batch_size=0)
        self.key_extractor = key_extractor
        self._splits = {}
        #: shard-plane sketch (monitoring/shard_ledger.py): when
        #: attached at graph build, the split PROGRAM below also updates
        #: an on-device count-min/candidate state threaded through as
        #: one donated operand — zero extra dispatches; None leaves one
        #: check per batch
        self._sketch = None
        self._sk_state = None
        #: key compactor (parallel/compaction.py) with placement
        #: override, attached at graph build: the split program remaps
        #: slotted keys to ``slot % n`` destinations (hot keys balanced
        #: deterministically) with the cold tail on the splitmix hash —
        #: the same placement the host keyed staging emitter applies
        self._compactor = None

    def attach_shard_sketch(self, sketch) -> None:
        """Fold the shard-plane sketch update into the split program
        (called by the ledger at graph build, before any compile)."""
        self._sketch = sketch
        self._splits = {}   # force the sketch variant at first compile
        sketch.register_device_state(lambda: self._sk_state)

    def attach_compactor(self, comp) -> None:
        """Fold the remap placement override into the split program
        (called by the graph build, before any compile): the remap
        tables ride as two read-only operands, re-passed unchanged in
        steady state — zero extra dispatches."""
        self._compactor = comp
        self._splits = {}   # force the remap variant at first compile

    def _get_split(self, capacity: int):
        import jax
        import jax.numpy as jnp
        split = self._splits.get(capacity)
        if split is None:
            n = len(self.dests)
            key_fn = self.key_extractor
            sketched = self._sketch is not None
            if sketched:
                from windflow_tpu.monitoring.shard_ledger import \
                    device_sketch_update
            if self._compactor is not None:
                from windflow_tpu.parallel.compaction import lookup_slots

            def split(payload, ts, valid, keys, sk=None, tk=None,
                      tsl=None):
                if keys is None:
                    with flightrec.phase("wf.fn"):
                        keys = jax.vmap(key_fn)(payload).astype(jnp.int32)
                # splitmix64 placement, bit-identical to the host staging
                # emitter's — a keyed operator fed by both a host edge and
                # a device edge must see each key on ONE replica
                h = (_splitmix64_dev(keys) % jnp.uint64(n)).astype(jnp.int32)
                if tk is not None:
                    # compaction placement override: slotted keys place
                    # by slot % n (the host keyed emitter's place_np),
                    # the cold tail keeps the hash
                    slot, hit = lookup_slots(tk, tsl, keys, valid)
                    h = jnp.where(hit, (slot % jnp.int32(n))
                                  .astype(jnp.int32), h)
                dest = jnp.where(valid, h, jnp.int32(n))
                # no per-destination sort or gather: consumers are
                # mask-aware, so every destination shares the SAME
                # immutable payload/ts/keys buffers and differs only in
                # its validity mask — O(capacity) total work instead of
                # O(capacity * num_dests) sorts+copies
                masks = [dest == d for d in range(n)]
                if sk is None:
                    return keys, masks
                # shard plane: the key-skew sketch updates INSIDE this
                # same program (int32 histograms of the batch, added
                # densely to the donated state) — the dispatch count is
                # unchanged
                return keys, masks, device_sketch_update(
                    sk, keys, valid, n, dest=dest)

            from windflow_tpu.monitoring.jit_registry import wf_jit
            split = wf_jit(split, op_name="emitter.device_keyby_split",
                           donate_argnums=(4,) if sketched else ())
            self._splits[capacity] = split
        return split

    def emit_device_batch(self, batch):
        comp_args = ()
        if self._compactor is not None:
            comp_args = self._compactor.tables()
        if self._sketch is None:
            if comp_args:
                keys, masks = self._get_split(batch.capacity)(
                    batch.payload, batch.ts, batch.valid, batch.keys,
                    None, *comp_args)
            else:
                keys, masks = self._get_split(batch.capacity)(
                    batch.payload, batch.ts, batch.valid, batch.keys)
        else:
            if self._sk_state is None:
                from windflow_tpu.monitoring.shard_ledger import \
                    device_sketch_init
                self._sk_state = device_sketch_init(len(self.dests))
            keys, masks, self._sk_state = self._get_split(batch.capacity)(
                batch.payload, batch.ts, batch.valid, batch.keys,
                self._sk_state, *comp_args)
        for d, mask in enumerate(masks):
            self._send(d, DeviceBatch(batch.payload, batch.ts, mask,
                                      keys=keys,
                                      watermark=batch.watermark, size=None,
                                      frontier=batch.frontier,
                                      ts_max=batch.ts_max,
                                      ts_min=batch.ts_min,
                                      trace=batch.trace,
                                      seq=batch.seq))


class DevicePassEmitter(Emitter):
    """TPU→TPU edge: device batches move by handle (no copies, no transfers).

    Forward/rebalancing round-robins destinations; broadcast shares the handle
    (immutability makes the reference's ``delete_counter`` multicast protocol
    unnecessary); keyby passes through — key grouping is resolved inside the
    consuming operator against the batch's key lane, and across chips by
    resharding collectives (parallel/mesh.py), not by emitter-side splits."""

    can_emit_host_items = False

    def __init__(self, dests, routing: RoutingMode):
        super().__init__(dests, output_batch_size=0)
        self.routing = routing
        self._next = 0

    def emit_device_batch(self, batch: DeviceBatch):
        if self.routing == RoutingMode.BROADCAST:
            for d in range(len(self.dests)):
                self._send(d, batch)
        else:
            d = self._next
            self._next = (self._next + 1) % len(self.dests)
            self._send(d, batch)


class DeviceToHostEmitter(Emitter):
    """TPU→host boundary (reference GPU→CPU paths,
    ``keyby_emitter_gpu.hpp:594-638``): transfers the batch back columnar
    (``device_to_host`` — one bulk copy per lane) and routes the whole
    HostBatch through the inner host emitter; only keyby falls back to
    per-tuple routing, as in the reference's per-dest re-split."""

    def __init__(self, inner: Emitter):
        super().__init__(inner.dests, inner.output_batch_size)
        self.inner = inner

    def bind_observability(self, stats, ring, flight):
        super().bind_observability(stats, ring, flight)
        self.inner.bind_observability(stats, ring, flight)

    def emit(self, item, ts, wm, shared=False, tid=None):
        self.inner.emit(item, ts, wm, shared, tid=tid)

    def emit_device_batch(self, batch: DeviceBatch):
        from windflow_tpu.batch import device_to_host
        if self.stats is not None:
            self.stats.d2h_bytes += _db_nbytes(batch)
        hb = device_to_host(batch)
        if hb.items:  # all-invalid batches (post-filter, empty split
            self.inner.emit_host_batch(hb)  # partitions) carry no data

    def emit_host_batch(self, hb):
        self.inner.emit_host_batch(hb)

    def propagate_punctuation(self, wm):
        self.inner.propagate_punctuation(wm)

    def flush(self, wm):
        self.inner.flush(wm)


def create_emitter(routing: RoutingMode,
                   dests,
                   output_batch_size: int,
                   src_is_tpu: bool,
                   dst_is_tpu: bool,
                   key_extractor: Optional[Callable] = None,
                   mesh=None) -> Emitter:
    """Pick the emitter for an edge from (routing, src-on-TPU, dst-on-TPU),
    mirroring the reference's dispatch (``multipipe.hpp:236-350``)."""
    if dst_is_tpu:
        dst_op = dests[0][0].op if dests else None
        if mesh is not None and not src_is_tpu \
                and routing == RoutingMode.KEYBY \
                and key_extractor is not None \
                and getattr(dst_op, "_ingest_mode", None) == "aligned":
            # key-aligned mesh ingest (ROADMAP item 4b): the graph build
            # marked this key-sharded consumer aligned (host-fed only),
            # so each record stages straight to its owning key shard and
            # the sharded step skips its cross-chip collectives.  The
            # placement bound is the consumer's dense key/slot space
            # (mesh._aligned_slot_bound — FFAT/reduce max_keys, stateful
            # num_key_slots).
            from windflow_tpu.parallel.mesh import _aligned_slot_bound
            return AlignedMeshStageEmitter(dests, output_batch_size,
                                           key_extractor, mesh,
                                           _aligned_slot_bound(dst_op))
        if routing == RoutingMode.KEYBY and len(dests) > 1 \
                and key_extractor is not None:
            # Key-partitioned delivery: each key's tuples always reach the
            # same replica, preserving per-key arrival order for shared
            # device state (reference: keyby routing is what makes stateful
            # Map_GPU/Filter_GPU correct across replicas).
            if src_is_tpu:
                return DeviceKeyByEmitter(dests, key_extractor)
            return KeyedDeviceStageEmitter(dests, output_batch_size,
                                           key_extractor, mesh=mesh)
        if src_is_tpu:
            return DevicePassEmitter(dests, routing)
        return DeviceStageEmitter(dests, output_batch_size, mesh=mesh)
    # host destination
    if src_is_tpu and routing != RoutingMode.KEYBY and dests \
            and all(getattr(r.op, "columnar", False) for r, _ in dests):
        # Columnar sinks consume DeviceBatches whole (bulk D2H inside the
        # sink replica, zero per-tuple Python); keyed columnar sinks still
        # need per-key routing and take the record path below.
        return DevicePassEmitter(dests, routing)
    if routing == RoutingMode.KEYBY:
        inner = KeyByEmitter(dests, output_batch_size, key_extractor)
    elif routing == RoutingMode.BROADCAST:
        inner = BroadcastEmitter(dests, output_batch_size)
    else:
        inner = ForwardEmitter(dests, output_batch_size)
    if src_is_tpu:
        return DeviceToHostEmitter(inner)
    return inner


class SplittingEmitter(Emitter):
    """Splitting logic at a MultiPipe split point (reference
    ``splitting_emitter.hpp:49-``): the user function maps a tuple to one
    branch index or an iterable of indexes; one inner emitter per branch
    (reference "tree mode", ``splitting_emitter.hpp:65-70``)."""

    def __init__(self, split_fn: Callable, branch_emitters: Sequence[Emitter]):
        super().__init__([], output_batch_size=0)
        self.split_fn = split_fn
        self.branches = list(branch_emitters)
        self._device_splits = {}  # capacity -> compiled split or None

    def bind_observability(self, stats, ring, flight):
        super().bind_observability(stats, ring, flight)
        for b in self.branches:
            b.bind_observability(stats, ring, flight)

    def emit(self, item, ts, wm, shared=False, tid=None):
        self._route(item, ts, wm, self.split_fn(item), shared, tid)

    def _route(self, item, ts, wm, dest, shared, tid):
        """Single place for the split routing semantics (int vs iterable,
        multicast CoW flag, origin-id branch suffixing) — shared by the
        host-tuple path and the device-batch host fallback."""
        if isinstance(dest, int):
            self.branches[dest].emit(item, ts, wm, shared, tid=tid)
            return
        dest = list(dest)
        # Multicast: every branch sees the same object; mark it shared so
        # in-place consumers copy lazily before mutating — no eager
        # per-branch deepcopy (reference pairs multicast with the
        # consumer-side copyOnWrite, map.hpp:57-215).
        multi = shared or len(dest) > 1
        for d in dest:
            # branch-suffix the origin id: multicast delivers the SAME
            # tuple to several branches, and a diamond re-merge into a
            # DETERMINISTIC stage needs the copies' ids distinct
            btid = tid + (-1, d) if tid is not None else None
            self.branches[d].emit(item, ts, wm, multi, tid=btid)

    def _get_device_split(self, capacity: int, payload):
        """Compile one mask-only split program per capacity
        (reference ``Splitting_Emitter_GPU`` / ``split_gpu``,
        ``splitting_emitter_gpu.hpp:53``, ``multipipe.hpp:1244-1281``).
        Requires a JAX-traceable single-destination split function; falls
        back to the host per-tuple path (returns None) for Python-level or
        multicast split functions."""
        if capacity in self._device_splits:
            return self._device_splits[capacity]
        import jax
        import jax.numpy as jnp
        n = len(self.branches)
        split_fn = self.split_fn
        compiled = None
        try:
            shape = jax.eval_shape(lambda p: jax.vmap(split_fn)(p), payload)
            ok = (getattr(shape, "shape", None) == (capacity,)
                  and jnp.issubdtype(shape.dtype, jnp.integer))
        except Exception:   # lint: broad-except-ok (eval_shape probe of an
            # arbitrary user split function — ANY failure means "host
            # per-tuple path", the documented fallback)
            ok = False
        if ok:
            def compiled(payload, ts, valid):
                with flightrec.phase("wf.fn"):
                    idx = jax.vmap(split_fn)(payload).astype(jnp.int32)
                dest = jnp.where(valid, idx, jnp.int32(n))
                # mask-only split: every branch shares the same immutable
                # buffers with its own validity mask (see DeviceKeyByEmitter)
                return [dest == b for b in range(n)]

            from windflow_tpu.monitoring.jit_registry import wf_jit
            compiled = wf_jit(compiled, op_name="emitter.device_split")

        self._device_splits[capacity] = compiled
        return compiled

    def emit_device_batch(self, batch: DeviceBatch):
        split = self._get_device_split(batch.capacity, batch.payload)
        if split is not None:
            # Device-native split: branches share the same immutable
            # buffers with per-branch validity masks; empty partitions
            # still ship (all-invalid) — skipping them would force a host
            # sync on the partition counts.
            masks = split(batch.payload, batch.ts, batch.valid)
            for b, mask in enumerate(masks):
                self.branches[b].emit_device_batch(
                    DeviceBatch(batch.payload, batch.ts, mask,
                                watermark=batch.watermark,
                                size=None, frontier=batch.frontier,
                                ts_max=batch.ts_max,
                                ts_min=batch.ts_min,
                                trace=batch.trace, seq=batch.seq))
            return
        # Fallback: host-side per-tuple split (Python or multicast split fn).
        # A device-only branch emitter cannot accept host items, but that is
        # an error only for a tuple actually ROUTED there — a non-traceable
        # split that happens to route exclusively to host branches keeps
        # working (same contract as the reference, whose GPU split requires
        # a __host__ __device__ functor, splitting_emitter_gpu.hpp).
        host_ok = [type(em).can_emit_host_items for em in self.branches]
        from windflow_tpu.batch import device_to_host
        hb = device_to_host(batch)
        for item, ts in zip(hb.items, hb.tss):
            dest = self.split_fn(item)
            if not isinstance(dest, int):
                dest = list(dest)
            for b in ((dest,) if isinstance(dest, int) else dest):
                if not host_ok[b]:
                    raise WindFlowError(
                        "split after a TPU stage routed a tuple to a TPU "
                        f"branch (branch {b}) through the host fallback, "
                        "so the split function must be JAX-traceable and "
                        "single-destination (got a Python-level or "
                        "multicast split function); make the split "
                        "function traceable or insert a host stage before "
                        "the TPU branch")
            self._route(item, ts, hb.watermark, dest, False, None)

    def propagate_punctuation(self, wm):
        for b in self.branches:
            b.propagate_punctuation(wm)

    def flush(self, wm):
        for b in self.branches:
            b.flush(wm)
