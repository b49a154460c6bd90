"""Data plane: stream messages as batches.

TPU-first re-design of the reference message layer
(``/root/reference/wf/single_t.hpp``, ``batch_cpu_t.hpp``, ``batch_gpu_t.hpp``):

* The reference's host-side unit is ``Single_t``/``Batch_CPU_t`` — a vector of
  ``{tuple, ts}`` plus watermark slots.  Here :class:`HostBatch` plays that
  role: a list of arbitrary Python records with parallel timestamp list and a
  scalar watermark.

* The reference's device unit is ``Batch_GPU_t`` — a device array of
  ``batch_item_gpu_t{tuple, ts}`` with keyby support arrays and a per-batch
  CUDA stream (``batch_gpu_t.hpp:51-229``).  Here :class:`DeviceBatch` holds a
  **structure-of-arrays pytree** of JAX arrays (leading dim = static capacity),
  an ``int64`` timestamp lane, and a validity mask.  Static capacity + mask is
  the XLA answer to ragged batches: every compiled program sees one shape, so
  it is traced and tiled once.  Asynchronous dispatch replaces CUDA streams —
  JAX ops enqueue without blocking, so the host driver naturally keeps several
  batches in flight (the reference's 2-deep double buffering,
  ``forward_emitter_gpu.hpp:254-300``).

Watermarks are host metadata: the reference embeds per-destination watermark
slots in every message (``single_t.hpp:159-178``) because messages are shared
pointers multicast across thread queues.  Here routing is done by a host
driver that tracks watermarks per channel, so one scalar per batch suffices.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from windflow_tpu import staging
from windflow_tpu.monitoring import recorder as flightrec
from windflow_tpu.monitoring.jit_registry import wf_jit

TS_DTYPE = jnp.int64
#: Watermark value meaning "no watermark yet".
WM_NONE = -1
#: Watermark value attached to the end-of-stream punctuation.
WM_MAX = (1 << 62)


@dataclasses.dataclass
class Punctuation:
    """Control message carrying only a watermark (reference: punctuation flag
    on ``Single_t``/``Batch_t``, ``single_t.hpp:54``).  ``watermark == WM_MAX``
    marks end-of-stream."""

    watermark: int

    @property
    def is_eos(self) -> bool:
        return self.watermark >= WM_MAX


@dataclasses.dataclass
class HostBatch:
    """A batch of host-resident records (reference ``Batch_CPU_t``,
    ``batch_cpu_t.hpp:51-205``).

    ``items[i]`` is an arbitrary Python object; ``tss[i]`` its timestamp in
    microseconds.  ``watermark`` is the minimum watermark folded over the
    inputs that produced this batch (the reference folds min-watermark in
    ``Batch_CPU_t::addTuple``)."""

    items: list
    tss: list
    watermark: int = WM_NONE
    #: optional per-item ORIGIN ids (tuples: source ordinal, replica, seq,
    #: expansion...) — assigned at sources and relayed by one-to-one /
    #: one-to-many host stages so DETERMINISTIC ordering can break
    #: timestamp ties config-independently (reference Single_t id field,
    #: ``single_t.hpp:50-183``); None when unavailable (aggregates emit
    #: fresh streams, device edges strip them — TPU ops are DEFAULT-only)
    ids: list = None
    #: True when this batch object is multicast to several inboxes
    #: (BROADCAST edges); in-place-capable consumers must copy before
    #: mutating (reference ``copyOnWrite`` + ``delete_counter`` multicast,
    #: ``map.hpp:57-215``, ``single_t.hpp:54``).
    shared: bool = False
    #: flight-recorder trace lane: ``(trace_id, t_origin_usec)`` on the
    #: 1-in-N sampled batch, None otherwise (monitoring/recorder.py).
    #: Relayed by whole-batch paths; host per-tuple stages start fresh
    #: traces at their emitter — lineage across a record explosion is not
    #: a single batch's journey.
    trace: tuple = None

    def __len__(self) -> int:
        return len(self.items)

    def ids_or_nones(self):
        """Per-item origin ids, None-filled when the batch carries none."""
        return self.ids if self.ids is not None \
            else (None,) * len(self.items)


class DeviceBatch:
    """A batch resident in TPU HBM (reference ``Batch_GPU_t``,
    ``batch_gpu_t.hpp:51-229``) as a structure-of-arrays pytree.

    Attributes
    ----------
    payload : pytree of jnp arrays, each with leading dimension ``capacity``.
    ts      : int64 [capacity] timestamps (microseconds).
    valid   : bool [capacity] mask; padding slots are False.  The reference
              carries an exact ``size``; a mask keeps shapes static for XLA.
    keys    : optional int32 [capacity] dense key-slot ids, attached by the
              keyby boundary (reference: ``dist_keys_cpu`` + per-key index
              chains built by ``keyby_emitter_gpu.hpp:519-583``; here key
              grouping is done with XLA sorts/segment ops at use sites).
    watermark, size : host-side metadata.  ``watermark`` is the min-folded
              stamp safe to propagate downstream (a host edge may re-split
              the batch per tuple).  ``frontier`` is the NEWEST watermark
              observed when the batch content was fixed at staging; it is
              only valid for the consuming operator's own firing decision
              *after* placing all the batch's tuples (place-then-fire), so
              it never propagates past the consumer — it saves time windows
              one batch of firing lag over the conservative stamp.
              ``ts_min``/``ts_max`` are the DATA timestamp extrema of
              the staged lanes (host-known at staging for free; ``None``
              for device-born batches) — outer bounds that stay valid
              through mask-only stages (map/filter/split can only shrink
              the valid set), letting the TB ring size itself to the
              batch pane spread and the data-vs-watermark lag without
              any device sync.
    """

    __slots__ = ("payload", "ts", "valid", "keys", "watermark", "_frontier",
                 "_size", "ts_max", "ts_min", "trace", "seq")

    def __init__(self, payload, ts, valid, keys=None, watermark: int = WM_NONE,
                 size: Optional[int] = None, frontier: Optional[int] = None,
                 ts_max: Optional[int] = None,
                 ts_min: Optional[int] = None,
                 trace: Optional[tuple] = None, seq: int = 0):
        self.payload = payload
        self.ts = ts
        self.valid = valid
        self.keys = keys
        self.watermark = watermark
        self._frontier = frontier
        self._size = size
        self.ts_max = ts_max
        self.ts_min = ts_min
        #: flight-recorder trace lane (monitoring/recorder.py):
        #: ``(trace_id, t_origin_usec)`` when this batch is the 1-in-N
        #: sampled one, else None.  Host metadata only — never transferred.
        self.trace = trace
        #: the recorder's sequence number of the staged batch this one
        #: descends from: the ``batch=`` its layer spans share from wire
        #: encode to the sink (0 when the recorder is off or the batch
        #: was born on the device).  Relayed wherever ``trace`` is.
        self.seq = seq

    @property
    def frontier(self) -> int:
        """Newest known watermark at batch-content fix time; falls back to
        the propagated stamp.  Never below ``watermark``."""
        if self._frontier is None:
            return self.watermark
        return max(self._frontier, self.watermark)

    @property
    def size(self) -> int:
        """Number of valid items.  Lazily counted: reading it after a filter
        forces a device sync, so hot paths use :attr:`known_size` instead."""
        if self._size is None:
            self._size = int(self.valid.sum())
        return self._size

    @property
    def known_size(self) -> Optional[int]:
        return self._size

    @property
    def capacity(self) -> int:
        return int(self.valid.shape[0])

    def __len__(self) -> int:
        return self.size


def transfer_nbytes(batch: DeviceBatch) -> int:
    """Whole-batch transfer size (payload + ts + valid lanes): the ONE
    definition behind the H2D/D2H byte counters (stats_record.hpp parity)
    wherever no packed staging buffer exists to measure exactly — shared
    by the staging emitters, the TPU→host boundary, and columnar sinks so
    the two directions can never drift apart."""
    return sum(getattr(l, "nbytes", 0)
               for l in jax.tree.leaves(batch.payload)) \
        + getattr(batch.ts, "nbytes", 0) + getattr(batch.valid, "nbytes", 0)


def staged_nbytes(batch: DeviceBatch) -> int:
    """Bytes the host really shipped to place a staged batch: every lane's
    shard, once per device of this process that holds one.  Equal to
    :func:`transfer_nbytes` on one device; on a mesh a lane replicated
    along an axis is shipped to each chip of that axis (``P("data")`` on
    a ``(data=1, key=4)`` mesh: the whole batch four times), and on a
    multi-host mesh only this process's shards count."""
    def moved(a) -> int:
        sh = getattr(a, "sharding", None)
        if sh is None:
            return getattr(a, "nbytes", 0)
        return math.prod(sh.shard_shape(a.shape)) * a.dtype.itemsize \
            * len(sh.addressable_devices)
    return sum(moved(l) for l in jax.tree.leaves(batch.payload)) \
        + moved(batch.ts) + moved(batch.valid)


# ---------------------------------------------------------------------------
# Host <-> device conversion (the reference's pinned-staging H2D/D2H path,
# forward_emitter_gpu.hpp:254-300 and Batch_GPU_t::transfer2CPU).
# ---------------------------------------------------------------------------

def _stack_records(items: Sequence[Any]):
    """Convert a list of per-tuple pytrees (scalars, tuples, dicts, ...) into
    one structure-of-arrays pytree of numpy arrays."""
    treedef = jax.tree.structure(items[0])
    leaves = [jax.tree.leaves(it) for it in items]
    cols = [np.asarray(col) for col in zip(*leaves)]
    return jax.tree.unflatten(treedef, cols)


def _pad_leading(arr: np.ndarray, capacity: int) -> np.ndarray:
    n = arr.shape[0]
    if n == capacity:
        return arr
    pad = [(0, capacity - n)] + [(0, 0)] * (arr.ndim - 1)
    return np.pad(arr, pad)


#: cached unpack programs for packed staging, keyed by
#: (leaf treedef/dtypes, capacity) — one trace per batch shape
_UNPACK_CACHE: dict = {}

# 32-bit word packing: host↔device links are dominated by per-TRANSFER
# latency, not bandwidth (the reference stages one contiguous pinned array
# of batch_item_gpu_t for the same reason, forward_emitter_gpu.hpp:254-300),
# so all lanes of a batch ride ONE uint32 buffer.  Only 32-bit bitcasts are
# used on device — the TPU X64-rewrite pass implements no 64-bit bitcast —
# int64 lanes travel as arithmetic lo/hi word pairs, in two planes (a
# lane's low words, then its high words: staging.join_planes), in the
# staged buffer as in the egress buffer, so the device reads and writes
# them by contiguous slice; float64 lanes make a batch unpackable (TPU has
# no native f64 anyway: stage f32).  Packing, layout, and the host-buffer
# recycling pool live in windflow_tpu/staging.

_words = staging.lane_words
_packable_dtype = staging.packable_dtype


def unpack_body(dtypes, capacity: int, wire=None):
    """The raw (un-jitted) unpack closure behind :func:`_get_unpack`:
    ``b -> (payload_cols, ts, valid, n_valid)``.  Exposed separately so
    the megastep executor (windflow_tpu/megastep.py) can inline the
    SAME decode — wire decompression included — into its K-sweep scan
    body instead of paying one unpack dispatch per batch."""
    if wire is not None:
        from windflow_tpu.wire import build_wire_decode
        decode = build_wire_decode(wire, dtypes, capacity)

        @flightrec.phase("wf.unpack")
        def unpack_fn(b):
            cols = decode(b)
            n_valid = b[-1].astype(jnp.int32)
            return cols[:-1], cols[-1], \
                jnp.arange(capacity, dtype=jnp.int32) < n_valid, \
                n_valid
    else:
        @flightrec.phase("wf.unpack")
        def unpack_fn(b):
            cols, off = [], 0
            for dt in dtypes + ("int64",):
                d = np.dtype(dt)
                if d.itemsize == 8:
                    cols.append(staging.join_planes(
                        b[off:off + capacity],
                        b[off + capacity:off + 2 * capacity]).astype(d))
                    off += 2 * capacity
                else:
                    cols.append(jax.lax.bitcast_convert_type(
                        b[off:off + capacity], d))
                    off += capacity
            n_valid = b[-1].astype(jnp.int32)
            return cols[:-1], cols[-1], \
                jnp.arange(capacity, dtype=jnp.int32) < n_valid, n_valid
    return unpack_fn


def _get_unpack(treedef, dtypes, capacity: int, wire=None):
    """Cached device program re-typing one packed uint32 staging buffer
    into payload columns + ts lane + validity mask (derived on device from
    the trailing fill-count word — never transferred separately, and cached
    per capacity, not per fill level).  The extra scalar output is the
    pool's recycling GATE: it depends on the transferred buffer like every
    other output, but it is never handed to a consumer, so no downstream
    ``donate_argnums`` (ops/chained.py, windflow_tpu/fusion) can delete it
    out from under ``StagingPool.acquire``'s readiness sync.

    ``wire`` (a ``wire.WireFormat``) switches the program to the wire-
    compressed layout: the columnar decode (``wire.build_wire_decode``)
    is inlined AHEAD of the mask derivation inside this SAME program —
    decompression costs zero extra dispatches, and each distinct wire
    descriptor keys its own cached program (a fresh compile, never a
    re-trace of an existing one)."""
    key = (treedef, dtypes, capacity, wire)
    unpack = _UNPACK_CACHE.get(key)
    if unpack is None:
        unpack = wf_jit(unpack_body(dtypes, capacity, wire=wire),
                        op_name="staging.unpack")
        _UNPACK_CACHE[key] = unpack
    return unpack


def stage_packed(buf: np.ndarray, treedef, dtypes, capacity: int, n: int,
                 watermark: int = WM_NONE, device=None,
                 frontier: Optional[int] = None,
                 ts_max: Optional[int] = None, ts_min: Optional[int] = None,
                 pool=None, trace: Optional[tuple] = None,
                 wire=None, logical_nbytes: Optional[int] = None,
                 seq: int = 0) -> DeviceBatch:
    """ONE host→device transfer of a packed staging buffer (built by
    ``staging.PackedBatchBuilder`` or the inline pack in ``_stage_soa``)
    into a DeviceBatch.  When ``pool`` is given, ``buf`` is recycled with
    the unpack output as its gate — the device owns the buffer until the
    unpack has executed, so reuse can never race the (asynchronous)
    transfer (staging.StagingPool).  ``wire`` marks ``buf`` as a wire-
    compressed buffer (windflow_tpu/wire.py): the matching columnar
    decode is inlined into the unpack program itself, and
    ``logical_nbytes`` keeps the byte accounting honest (wire bytes =
    the transfer, logical bytes = the decoded lanes)."""
    unpack = _get_unpack(treedef, dtypes, capacity, wire=wire)
    with flightrec.span("wf.h2d", batch=seq, bytes=buf.nbytes):
        dbuf = jnp.asarray(buf) if device is None \
            else jax.device_put(buf, device)
    # device-plane accounting (monitoring/device_metrics): every fused
    # staging transfer credits the process-wide staged-byte gauge —
    # wire bytes as shipped, logical bytes as decoded
    staging.device_bytes.note(buf.nbytes, logical_nbytes)
    with flightrec.span("wf.dispatch", op="staging.unpack", batch=seq):
        cols, ts, valid, gate = unpack(dbuf)
    if pool is not None:
        # gate on the unpack's private scalar output, NOT a lane the
        # consumer sees: a donated lane's deletion happens at the host's
        # (async) dispatch enqueue, which proves nothing about the H2D
        # DMA that is still reading `buf`
        pool.release(buf, gate=gate)
    return DeviceBatch(jax.tree.unflatten(treedef, cols), ts, valid,
                       watermark=watermark, size=n, frontier=frontier,
                       ts_max=ts_max, ts_min=ts_min, trace=trace, seq=seq)


def _stage_soa(soa, tss, n: int, capacity: int, watermark: int,
               device, frontier: Optional[int] = None,
               trace: Optional[tuple] = None, seq: int = 0) -> DeviceBatch:
    """Shared staging tail: pad an SoA numpy pytree + timestamps to
    ``capacity``, build the validity mask, optionally pin to a device.

    When every payload column is a 1-D packable lane (4-byte, or int64),
    all lanes plus timestamps ride ONE host→device transfer as a uint32
    buffer, re-typed on device by a cached program; the validity mask is
    derived on device from ``n``, never transferred."""
    # data-ts extrema of the real lanes: free host metadata for TB ring
    # sizing (DeviceBatch.ts_min/ts_max)
    _t = np.asarray(tss[:n])
    ts_max = int(np.max(_t)) if n else None
    ts_min = int(np.min(_t)) if n else None
    leaves, treedef = jax.tree.flatten(soa)
    if isinstance(device, jax.sharding.Sharding) and jax.process_count() > 1:
        # multi-host staging: `capacity` is the GLOBAL lane count; this
        # process contributes its local slice (capacity / process_count
        # lanes) and the global batch is assembled shard-locally — the
        # graph-level form of parallel/multihost.stage_local.  Every
        # process must stage batches in lockstep (same count, same order):
        # the sharded programs downstream are collective.
        nproc = jax.process_count()
        local_cap = capacity // nproc
        if n > local_cap:
            raise ValueError(
                f"local batch of {n} exceeds per-process capacity "
                f"{local_cap} (= {capacity}/{nproc})")

        def assemble(a):
            a = _pad_leading(np.ascontiguousarray(a), local_cap)
            return jax.make_array_from_process_local_data(
                device, a, (capacity,) + a.shape[1:])

        payload = jax.tree.map(assemble, soa)
        ts = assemble(np.asarray(tss, dtype=np.int64))
        valid = assemble(np.arange(local_cap) < n)
        # ts extrema deliberately NOT attached (ADVICE r5 medium): they
        # describe only this process's local slice of a globally sharded
        # batch, and attaching them would let windows/ffat_tpu
        # _regrow_for_span make DIFFERENT ring-growth decisions per
        # process, desynchronizing sharded state shapes.  The eviction-
        # cadence regrow (SPMD-consistent n_evicted sums) remains the
        # ring's growth path on multi-host meshes.
        out = DeviceBatch(payload, ts, valid, watermark=watermark,
                          size=None, frontier=frontier,
                          ts_max=None, ts_min=None, trace=trace)
        # device-plane accounting: this process's local shard share of the
        # assembled global batch (the packed path credits via stage_packed)
        staging.device_bytes.note(transfer_nbytes(out) // nproc)
        return out
    packable = (
        device is None or isinstance(device, jax.Device)
    ) and all(l.ndim == 1 and _packable_dtype(l.dtype) for l in leaves)
    if packable:
        dtypes = tuple(str(np.dtype(l.dtype)) for l in leaves)
        pool = staging.default_pool()
        # pooled buffer + streaming pack (staging.PackedBatchBuilder):
        # steady-state staging allocates no numpy buffers, and the final
        # word carries n, so the unpack program is cached per capacity,
        # not per fill level (no per-partial-batch recompiles, and no
        # extra scalar transfer)
        b = staging.PackedBatchBuilder(dtypes, capacity, pool=pool)
        b.append(leaves, np.asarray(tss, dtype=np.int64))
        return stage_packed(b.finish(), treedef, dtypes, capacity, n,
                            watermark=watermark, device=device,
                            frontier=frontier, ts_max=ts_max,
                            ts_min=ts_min, pool=pool, trace=trace, seq=seq)
    # host buffers go STRAIGHT to their placement: a sharding splits on
    # the host and each chip receives only its own shard (staging through
    # jnp.asarray first would land the whole batch on device 0 and
    # re-shard from there).  The same two layer spans as the packed path
    # (docs/OBSERVABILITY.md): the assembly of the padded host lanes is
    # the pack, the per-lane, per-shard puts are the transfer.
    def put(a):
        return jnp.asarray(a) if device is None \
            else jax.device_put(a, device)
    with flightrec.span("wf.pack", n=n):
        lanes = jax.tree.map(
            lambda a: _pad_leading(np.ascontiguousarray(a), capacity), soa)
        ts_h = _pad_leading(np.asarray(tss, dtype=np.int64), capacity)
        valid_h = np.arange(capacity) < n
    with flightrec.span("wf.h2d", batch=seq, n=n, cap=capacity) as sp:
        out = DeviceBatch(jax.tree.map(put, lanes), put(ts_h), put(valid_h),
                          watermark=watermark, size=n, frontier=frontier,
                          ts_max=ts_max, ts_min=ts_min, trace=trace,
                          seq=seq)
        moved, logical = staged_nbytes(out), transfer_nbytes(out)
        sp.note(bytes=moved, logical=logical,
                shards=len(device.addressable_devices)
                if isinstance(device, jax.sharding.Sharding) else 1)
    # per-lane transfers: still a staged batch for the device-plane
    # accounting stage_packed credits on the fused path
    staging.device_bytes.note(moved, logical)
    return out


def host_to_device(batch: HostBatch, capacity: Optional[int] = None,
                   device=None, frontier: Optional[int] = None,
                   trace: Optional[tuple] = None,
                   seq: int = 0) -> DeviceBatch:
    """Stage a HostBatch into device buffers, padding to ``capacity``."""
    n = len(batch)
    if n == 0:
        raise ValueError("cannot stage an empty batch")
    cap = capacity or n
    if n > cap:
        raise ValueError(f"batch of {n} items exceeds capacity {cap}")
    return _stage_soa(_stack_records(batch.items), batch.tss, n, cap,
                      batch.watermark, device, frontier,
                      trace=trace if trace is not None else batch.trace,
                      seq=seq)


def columns_to_device(cols, tss, capacity: int, watermark: int = WM_NONE,
                      device=None, frontier: Optional[int] = None,
                      trace: Optional[tuple] = None,
                      seq: int = 0) -> DeviceBatch:
    """Stage columnar (SoA numpy) data directly into a DeviceBatch — the
    zero-per-tuple-Python path used by bulk sources (windflow_tpu/io) and the
    columnar staging emitter.  ``cols`` is a dict of [n]-leading numpy
    arrays, ``tss`` an int64 [n] array; n must be <= capacity."""
    n = len(tss)
    if n == 0:
        raise ValueError("cannot stage an empty column batch")
    if n > capacity:
        raise ValueError(f"column batch of {n} exceeds capacity {capacity}")
    return _stage_soa(dict(cols), tss, n, capacity, watermark, device,
                      frontier, trace=trace, seq=seq)


#: cached pack programs for single-transfer egress, keyed by the payload's
#: (treedef, shape/dtype) signature and the lanes of a front copy (or None)
_EGRESS_PACK_CACHE: dict = {}

#: fewest leading lanes a front copy takes: a copy of a few hundred KB
#: costs its latency, not its bytes (``device_to_columns``), so a shorter
#: front saves nothing (the idiom of ``session_kernels.FRONT_MIN``)
FRONT_MIN_LANES = 4096


def front_lanes(cap: int, extent: int) -> Optional[int]:
    """Leading lanes to copy of a ``cap``-lane batch whose rows are
    expected to end by lane ``extent``: the smallest of ``cap / 2``,
    ``cap / 4``, ... that is not under :data:`FRONT_MIN_LANES` and holds
    twice the extent, or ``None`` where none does or the batch is too
    small to be worth a second program (the whole batch then)."""
    if cap <= 2 * FRONT_MIN_LANES:
        return None
    need = max(2 * extent, FRONT_MIN_LANES)
    front, lanes = None, cap // 2
    while lanes >= need:
        front, lanes = lanes, lanes // 2
    return front


def device_to_columns(batch: DeviceBatch):
    """Transfer a DeviceBatch's valid lanes to host as SoA numpy columns —
    the egress twin of :func:`columns_to_device`: ONE device→host transfer
    for the whole batch and NO per-record Python object construction
    (VERDICT r2: the per-tuple dict build in ``device_to_host`` capped
    every TPU→Sink edge).  All 1-D lanes plus the timestamp and validity
    lanes are bitcast-packed into a single byte buffer on device (a cached
    program) and re-typed host-side with numpy views — per-transfer
    latency, not bandwidth, dominates host↔device links.  Returns
    ``(cols, tss)`` where ``cols`` mirrors the payload pytree with ``[n]``-
    leading numpy arrays and ``tss`` is an int64 ``[n]`` array.  Reference:
    the GPU→CPU boundary is also one bulk pinned D2H copy before any
    per-tuple work (``keyby_emitter_gpu.hpp:594-638``)."""
    return ColumnarEgress(batch).columns()


def _np_local(a):
    """Device→host view of an array that may span processes (multi-host
    run): a fully-addressable array transfers whole; otherwise this
    process reads ONLY its addressable shards — deduplicated by shard
    index (axis replication repeats content per device) and concatenated
    in index order.  Each host's sink thereby consumes the rows its own
    key shards produced (SURVEY §5.8: per-process sinks)."""
    if isinstance(a, jax.Array) and not a.is_fully_addressable:
        seen = {}
        for s in a.addressable_shards:
            key = tuple((sl.start or 0, sl.stop) for sl in s.index)
            seen.setdefault(key, s.data)
        parts = [np.asarray(d) for _, d in sorted(seen.items())]
        return np.concatenate(parts, axis=0)
    return np.asarray(a)


def _egress_packable(batch: DeviceBatch):
    leaves, treedef = jax.tree.flatten(batch.payload)
    cap = batch.capacity
    # numpy-leaf batches (the megastep drain's zero-copy per-batch
    # slices) must take the host fallback: device-packing them would
    # round-trip already-host-resident lanes through HBM
    # a lane may carry a small record a row (trailing dimensions: a
    # window aggregate of several numbers); it rides flattened
    ok = all(getattr(l, "ndim", 0) >= 1 and l.shape[0] == cap
             and (_packable_dtype(l.dtype) or l.dtype == jnp.bool_)
             and isinstance(l, jax.Array) and l.is_fully_addressable
             for l in leaves)
    return ok, leaves, treedef, cap


def _egress_pack(batch: DeviceBatch, leaves, treedef, cap,
                 front: Optional[int] = None):
    """Device program producing the batch's single uint32 egress buffer:
    every lane, or with ``front`` the leading ``front`` lanes of each
    (the same word layout at that capacity) followed by a two-word header,
    the batch's valid lanes counted over ALL ``cap`` lanes and its extent
    (1 + the index of the last valid lane, 0 for none)."""
    specs = tuple((str(np.dtype(l.dtype)), tuple(l.shape[1:]))
                  for l in leaves)
    key = (treedef, specs, cap, front)
    pack = _EGRESS_PACK_CACHE.get(key)
    if pack is None:
        def to_words(l):
            # only 32-bit device bitcasts (see packing note above):
            # 64-bit lanes leave as arithmetic lo/hi uint32 pairs
            l = (l if front is None else l[:front]).reshape(-1)
            if l.dtype == jnp.bool_:
                return [l.astype(jnp.uint32)]
            if np.dtype(l.dtype).itemsize == 8:
                v = l.astype(jnp.int64) if l.dtype != jnp.int64 else l
                lo = (v & jnp.int64(0xFFFFFFFF)).astype(jnp.uint32)
                hi = ((v >> 32) & jnp.int64(0xFFFFFFFF)).astype(jnp.uint32)
                return [lo, hi]
            return [jax.lax.bitcast_convert_type(l, jnp.uint32)]

        @flightrec.phase("wf.egress.pack")
        def pack_fn(lvs, ts, vld):
            parts = []
            for l in lvs:
                parts.extend(to_words(l))
            parts.extend(to_words(ts))
            parts.extend(to_words(vld))
            if front is not None:
                last = jnp.arange(1, cap + 1, dtype=jnp.int32)
                parts.append(jnp.stack(
                    [vld.sum(dtype=jnp.int32),
                     jnp.max(jnp.where(vld, last, 0))]).astype(jnp.uint32))
            return jnp.concatenate(parts)
        pack = wf_jit(pack_fn, op_name="staging.egress_pack")
        _EGRESS_PACK_CACHE[key] = pack
    return pack(leaves, batch.ts, batch.valid), specs


def _egress_unpack(raw, treedef, specs, lanes, n):
    """Re-type a packed buffer of ``lanes`` lanes a leaf (words after them
    are not read) and select its valid lanes; ``n`` is their count where
    it is known.  Returns ``(cols, tss, extent)``."""
    def take(off, dt, trail=()):
        d = np.dtype(dt)
        w = lanes * math.prod(trail)
        if d == np.bool_:
            col = raw[off:off + w].astype(np.bool_)
        elif d.itemsize == 8:
            col = staging.join_planes(raw[off:off + w],
                                      raw[off + w:off + 2 * w]) \
                .astype(d, copy=False)
            off += w
        else:
            col = raw[off:off + w].view(d)
        return col.reshape((lanes,) + trail), off + w

    off = 0
    cols_flat = []
    for dt, trail in specs:
        col, off = take(off, dt, trail)
        cols_flat.append(col)
    tss, off = take(off, "int64")
    valid = raw[off:off + lanes].astype(np.bool_)
    if n is not None and bool(valid[:n].all()):
        sel, extent = slice(None, n), n
    else:
        sel = np.nonzero(valid)[0]
        extent = int(sel[-1]) + 1 if len(sel) else 0
    cols = jax.tree.unflatten(treedef, [c[sel] for c in cols_flat])
    return cols, tss[sel], extent


class ColumnarEgress:
    """One DeviceBatch's columnar egress, STARTED: the pack program is
    dispatched (asynchronous, the cached ``staging.egress_pack``) and the
    packed buffer's device→host copy requested, so the copy follows the
    step that fills the batch without the host waiting for either.
    :meth:`is_ready` asks the device whether that step has run;
    :meth:`columns` blocks for what is still in flight and re-types the
    bytes.  A batch that cannot be packed (``_egress_packable``: numpy
    leaves, lanes this process does not hold whole) keeps its lanes and
    takes ``_columns_fallback``; its readiness is its validity lane's.

    With ``front`` lanes (the columnar sink's guess, from what the edge
    delivered before: :func:`front_lanes`) only the leading ``front``
    lanes of a packable batch held by one device are packed and copied,
    behind a header that says where the batch's rows end.  The header
    decides in :meth:`columns`: rows that end inside the front are all in
    the buffer; rows beyond it (an overflow) send the whole batch through
    the whole-batch pack and copy, waited for in place.  Only the size of
    the first copy is a guess, never a row.  After :meth:`columns`,
    ``lanes_copied`` is what crossed the link and ``extent`` where the
    rows ended (``None`` on the fallback)."""

    __slots__ = ("batch", "_packed", "front", "lanes_copied", "extent")

    def __init__(self, batch: DeviceBatch,
                 front: Optional[int] = None) -> None:
        self.batch = batch
        ok, leaves, treedef, cap = _egress_packable(batch)
        self._packed = self.front = self.extent = None
        self.lanes_copied = cap
        if ok:
            if front is not None and front < cap and all(
                    len(a.devices()) == 1
                    for a in (*leaves, batch.ts, batch.valid)):
                self.front = self.lanes_copied = front
            buf, specs = _egress_pack(batch, leaves, treedef, cap,
                                      self.front)
            buf.copy_to_host_async()
            self._packed = (buf, treedef, specs, cap)

    @property
    def overflowed(self) -> bool:
        """Did the rows end beyond the front that was copied first?"""
        return self.front is not None and self.lanes_copied > self.front

    def is_ready(self) -> bool:
        gate = self.batch.valid if self._packed is None else self._packed[0]
        # a numpy lane (the megastep drain's slices) is on the host already
        return not isinstance(gate, jax.Array) or gate.is_ready()

    def columns(self):
        if self._packed is None:
            return _columns_fallback(self.batch)
        buf, treedef, specs, cap = self._packed
        # the wait for the chip and the link is a span of its own: what
        # is left of the ``wf.sink.d2h`` around it is the re-typing
        with flightrec.wait("d2h"):
            raw = np.asarray(buf)
        lanes, n = cap, self.batch.known_size
        if self.front is not None:
            n, extent = int(raw[-2]), int(raw[-1])
            if extent <= self.front:
                lanes = self.front
            else:
                whole, _ = _egress_pack(
                    self.batch, jax.tree.leaves(self.batch.payload),
                    treedef, cap)
                with flightrec.wait("d2h"):
                    raw = np.asarray(whole)
                self.lanes_copied = self.front + cap
        cols, tss, self.extent = _egress_unpack(raw, treedef, specs, lanes,
                                                n)
        return cols, tss


def device_to_columns_multi(batches):
    """Columnar egress of device batches, each a :class:`DeviceBatch` or a
    :class:`ColumnarEgress` started earlier (the columnar sink starts one
    at receipt and hands it here when it delivers): every batch's pack
    and copy are started before the first is waited for, one packed
    buffer a batch.  Returns a list of ``(cols, tss)`` in input order."""
    started = [b if isinstance(b, ColumnarEgress) else ColumnarEgress(b)
               for b in batches]
    return [e.columns() for e in started]


def _lanes_to_host(batch: DeviceBatch):
    """``(payload, ts, valid)`` of a batch that takes no packed buffer as
    numpy lanes: its blocking reads, one wait (the selection of the rows
    is the caller's own work)."""
    with flightrec.wait("d2h"):
        return (jax.tree.map(_np_local, batch.payload),
                _np_local(batch.ts), _np_local(batch.valid))


def _columns_fallback(batch: DeviceBatch):
    payload, ts, valid = _lanes_to_host(batch)
    n = batch.known_size
    if n is not None and len(valid) == batch.capacity \
            and bool(valid[:n].all()):
        # staged batches carry prefix validity: slice, no gather
        return jax.tree.map(lambda a: a[:n], payload), ts[:n]
    idx = np.nonzero(valid)[0]
    return jax.tree.map(lambda a: a[idx], payload), ts[idx]


def device_to_host(batch: DeviceBatch) -> HostBatch:
    """Transfer a DeviceBatch back to host records (reference
    ``Batch_GPU_t::transfer2CPU``), dropping padding slots.

    The transfer itself is columnar — one bulk ``np.asarray`` per lane, like
    the reference's single pinned D2H copy — and record construction uses
    ``tolist()`` + ``dict(zip(...))`` on the common flat-dict payload shape
    rather than per-tuple pytree calls."""
    payload, ts, valid = _lanes_to_host(batch)
    idx = np.nonzero(valid)[0]
    tss = ts[idx].tolist()
    if isinstance(payload, dict) and all(
            hasattr(a, "ndim") for a in payload.values()):
        # flat dict of array lanes only: a nested pytree value (e.g. a
        # multi-leaf window aggregate) has no ndim and takes the generic
        # tree path below
        cols = {n: a[idx] for n, a in payload.items()}
        if all(c.ndim == 1 for c in cols.values()):
            names = list(cols)
            items = [dict(zip(names, vals))
                     for vals in zip(*(cols[n].tolist() for n in names))]
            return HostBatch(items=items, tss=tss,
                             watermark=batch.watermark, trace=batch.trace)
    treedef = jax.tree.structure(payload)
    cols = [leaf[idx] for leaf in jax.tree.leaves(payload)]
    items = [jax.tree.unflatten(treedef, [c[i] for c in cols])
             for i in range(len(idx))]
    # Unwrap 0-d numpy scalars for ergonomic host-side records.
    items = [jax.tree.map(lambda v: v.item() if np.ndim(v) == 0 else v, it)
             for it in items]
    return HostBatch(items=items, tss=tss, watermark=batch.watermark,
                     trace=batch.trace)
