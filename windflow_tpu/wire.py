"""Wire plane: columnar compression of staged batches with device decode.

The staged path feeds the chip over the host→device link; where that
link bounds end-to-end numbers, fewer bytes on it is the lever (the
compile-the-pipeline stance of arXiv 2207.00257 extended to the decode
step).  Whether it does is measured, not assumed: under
``Config.wire_compression="auto"`` every staging edge times its link
and its codec and encodes only where the link time saved exceeds the
codec time (:class:`WireEncoder`) — a 19 MB/s tunnel keeps the codec, a
host-attached chip ships raw and compiles no decode variant.  This
module shrinks the wire: the staging plane's packed
uint32 buffer (``staging.PackedBatchBuilder``) is re-encoded lane by
lane with cheap columnar codecs before the ONE fused transfer, and the
inverse decode is a traced stage folded into the SAME device unpack
program ``batch.stage_packed`` already dispatches — compressed batches
cost **zero extra dispatches** and the compressed bytes never
materialize on host after the pack.

Codecs (per lane, chosen per reseed cadence from the measured data):

* ``raw``    — passthrough words (the fallback; also any lane whose data
  defeats every other codec this batch).
* ``const``  — all rows equal: 2 header words carry the value
  (all-null/constant lanes collapse to nothing).
* ``delta``  — zigzag deltas bit-packed at 8/16/32 bits (+ width 0 for a
  constant stride of 0) behind an int64 base: monotone-ish ts/id lanes.
  Arithmetic wraps two's-complement on both sides, so reconstruction is
  exact for the full int64 domain.
* ``delta2`` — delta-of-delta behind base + first delta: constant-cadence
  timestamp lanes collapse to width 0 (a handful of header words).
* ``dict``   — low-cardinality lanes: a ≤64Ki-entry sorted value table
  (stable between reseeds, shipped with each batch) + bit-packed indices.

Codec choice is re-evaluated every ``reseed_every`` batches (the key-
compaction reseed cadence); between reseeds each batch pays only a
vectorized fit-check + encode pass per lane, and a lane whose data stops
fitting its codec degrades to ``raw`` for that batch (counted, and the
next batch reseeds).  The per-lane codec descriptor is host metadata:
it keys the cached decode program (a new descriptor compiles a fresh
program — never a re-trace of an existing one, so the recompile
tripwire stays quiet) and rides no wire bytes beyond the per-batch
headers (bases, dict tables).

Wire buffer layout (padded to a :func:`staging.size_class` so the pool
recycles across codec churn — the size-class keying fix)::

    [lane0 header+payload | lane1 ... | ts lane | pad ... | n]

Requires a declared/inferred record spec on the feeding edge
(``Source_Builder.withRecordSpec`` / ``DeviceSource.batch_fn``
inference): an undeclared-spec source under ``Config.wire_compression``
downgrades to raw passthrough with a named preflight warning (WF606)
instead of silently guessing lane semantics.  Mesh-sharded staging keeps
the uncompressed per-lane path (its transfers are assembled per shard,
not packed); ``Config.wire_compression`` / ``WF_TPU_WIRE=0`` is the kill
switch, leaving one flag check per staged batch, and ``=1`` forces the
codec whatever the link.

Host packing uses little-endian byte views (every supported host);
device-side unpacking is pure 32-bit word arithmetic, endian-agnostic.
"""

from __future__ import annotations

import time
from typing import List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from windflow_tpu import staging

#: codec kind tags (descriptor fields are plain strings/ints so the
#: descriptor tuple is hashable — it keys the cached decode program)
RAW, CONST, DELTA, DELTA2, DICT = "raw", "const", "delta", "delta2", "dict"

#: an encoder's standing decision (WireEncoder.decision); the fourth
#: value is RAW
FORCED, PENDING, ENCODE = "forced", "pending", "encode"

#: largest dictionary a lane may ship per batch (16-bit indices)
DICT_MAX = 1 << 16
#: dictionaries at/below this size pack 8-bit indices
DICT_SMALL = 1 << 8


class LaneCodec(NamedTuple):
    """Static per-lane codec descriptor: ``kind``, packed bits per
    element (``width`` in {0, 8, 16, 32}), and ``extra`` (padded dict
    table size; 0 otherwise).  Hashable — part of the decode-program
    cache key."""

    kind: str
    width: int = 32
    extra: int = 0


class WireFormat(NamedTuple):
    """Whole-buffer descriptor: one :class:`LaneCodec` per lane
    (payload lanes in order, then the implicit int64 ts lane) plus the
    size-class-padded word count of the wire buffer."""

    codecs: Tuple[LaneCodec, ...]
    words: int


RAW_CODEC = LaneCodec(RAW, 32, 0)


def _packed_words(count: int, width: int) -> int:
    if width == 0 or count <= 0:
        return 0
    per = 32 // width
    return (count + per - 1) // per


def lane_wire_words(codec: LaneCodec, dtype, capacity: int) -> int:
    """Static wire words one lane occupies under ``codec`` (headers are
    always int64 → 2 words each; dict entries are raw lane words)."""
    w = staging.lane_words(dtype)
    if codec.kind == RAW:
        return w * capacity
    if codec.kind == CONST:
        return 2
    if codec.kind == DELTA:
        return 2 + _packed_words(capacity - 1, codec.width)
    if codec.kind == DELTA2:
        return 4 + _packed_words(capacity - 2, codec.width)
    if codec.kind == DICT:
        return codec.extra * w + _packed_words(capacity, codec.width)
    raise ValueError(f"unknown lane codec kind {codec.kind!r}")


def wire_words_total(fmt_codecs, dtypes, capacity: int) -> int:
    """Unpadded wire words of a whole batch (+1 for the fill count)."""
    return 1 + sum(lane_wire_words(c, d, capacity)
                   for c, d in zip(fmt_codecs, dtypes))


# ---------------------------------------------------------------------------
# host-side encode (numpy, vectorized — runs once per staged batch)
# ---------------------------------------------------------------------------

def _zigzag(d: np.ndarray) -> np.ndarray:
    """Signed int64 deltas → unsigned zigzag (small magnitudes of either
    sign become small unsigned values).  Shift overflow wraps two's-
    complement, matching the device-side inverse exactly."""
    return ((d << 1) ^ (d >> 63)).astype(np.uint64)


def _width_for(zz_max: int) -> Optional[int]:
    if zz_max == 0:
        return 0
    if zz_max < (1 << 8):
        return 8
    if zz_max < (1 << 16):
        return 16
    if zz_max < (1 << 32):
        return 32
    return None


def _pack_width(vals: np.ndarray, width: int) -> np.ndarray:
    """Bit-pack uint32 values at ``width`` bits into little-endian
    uint32 words (byte-aligned widths only — the device unpack is a
    shift+mask, no cross-word fields)."""
    if width == 0 or len(vals) == 0:
        return np.empty(0, np.uint32)
    if width == 32:
        return np.ascontiguousarray(vals, np.uint32)
    per = 32 // width
    words = np.zeros((len(vals) + per - 1) // per, np.uint32)
    view = words.view(np.uint8 if width == 8 else np.uint16)
    view[:len(vals)] = vals.astype(view.dtype)
    return words


def _i64_header(v: int) -> List[np.ndarray]:
    """An int64 header value as [lo, hi] uint32 words (python-int
    masking: exact for the full signed domain)."""
    v = int(v)
    return [np.array([v & 0xFFFFFFFF], np.uint32),
            np.array([(v >> 32) & 0xFFFFFFFF], np.uint32)]


def _pow2ceil(n: int) -> int:
    return 1 << max(0, (max(1, n) - 1).bit_length())


class _LaneState:
    """Per-lane encoder state: the current codec choice plus the dict
    table it was chosen with (tables stay stable between reseeds so the
    per-batch fit check is one searchsorted pass)."""

    __slots__ = ("codec", "table")

    def __init__(self) -> None:
        self.codec: Optional[LaneCodec] = None
        self.table: Optional[np.ndarray] = None


class WireStats:
    """Wire-plane counters for ``stats()["Staging"]["Wire"]`` and the
    OpenMetrics ``wf_wire_*`` families.  Plain int adds (telemetry
    tolerance of the staging plane's other counters)."""

    __slots__ = ("batches", "raw_batches", "fallback_lanes", "reseeds",
                 "logical_bytes", "wire_bytes", "encode_usec")

    def __init__(self) -> None:
        self.batches = 0          # compressed batches shipped
        self.raw_batches = 0      # batches where compression lost
        self.fallback_lanes = 0   # per-batch codec misfits (lane → raw)
        self.reseeds = 0
        self.logical_bytes = 0    # decoded bytes (what raw would ship)
        self.wire_bytes = 0       # bytes actually transferred
        self.encode_usec = 0.0

    def note_raw(self, nbytes: int) -> None:
        """One batch shipped as its logical buffer (compression lost on
        it, or the edge decided raw): accrued at FULL size on both byte
        counters, so ``compression_ratio`` is the blended transfer
        truth, not the compressed-batches-only flatter (the honesty
        contract)."""
        self.raw_batches += 1
        self.wire_bytes += nbytes
        self.logical_bytes += nbytes

    def merge(self, other: "WireStats") -> None:
        self.batches += other.batches
        self.raw_batches += other.raw_batches
        self.fallback_lanes += other.fallback_lanes
        self.reseeds += other.reseeds
        self.logical_bytes += other.logical_bytes
        self.wire_bytes += other.wire_bytes
        self.encode_usec += other.encode_usec

    def to_json(self) -> dict:
        ratio = (round(self.logical_bytes / self.wire_bytes, 4)
                 if self.wire_bytes else None)
        return {
            "batches": self.batches,
            "raw_batches": self.raw_batches,
            "fallback_lanes": self.fallback_lanes,
            "reseeds": self.reseeds,
            "logical_bytes": self.logical_bytes,
            "wire_bytes": self.wire_bytes,
            "compression_ratio": ratio,
            "encode_usec": round(self.encode_usec, 1),
        }


class WireEncoder:
    """Per-emitter lane encoder: turns one finished logical staging
    buffer into a (usually much smaller) wire buffer + its
    :class:`WireFormat`.  Codec choice per lane is re-evaluated every
    ``reseed_every`` encoded batches; in between, each batch pays one
    vectorized fit-check+encode pass per lane.  A batch compression
    cannot shrink ships the logical buffer unchanged (``fmt=None``).

    With ``link_rate`` (a callable: words of one transfer -> measured
    host→device bytes per second; the runtime passes
    ``StagingPool.link_rate``) the encoder DECIDES, once, whether its
    edge encodes at all.  The link is timed when the encoder is made,
    on a buffer of the edge's logical size; the first batch is encoded
    as usual with the steady pass clocked apart from the codec choice
    (``np.unique`` and friends run once per reseed cadence and would
    bias a slow link towards raw); the edge keeps the codec only if
    the link time of the bytes it saved on that batch exceeds that
    pass.  The batch itself ships by the decision, so an edge that
    decides raw never hands out a :class:`WireFormat` and no decode
    variant of ``staging.unpack`` is compiled for it; from then on
    :attr:`ships_raw` tells the emitter to skip the encoder.  Without
    ``link_rate`` the codec is forced (``Config.wire_compression``
    True): every batch is encoded as before."""

    def __init__(self, dtypes: Sequence, capacity: int,
                 reseed_every: int = 64, link_rate=None,
                 clock=time.perf_counter) -> None:
        self.dtypes = tuple(np.dtype(d) for d in dtypes) \
            + (np.dtype(np.int64),)             # + implicit ts lane
        self.capacity = capacity
        self.reseed_every = max(1, reseed_every)
        self._clock = clock
        self._lane_words = [staging.lane_words(d) for d in self.dtypes]
        self._offsets = []
        off = 0
        for w in self._lane_words:
            self._offsets.append(off)
            off += w * capacity
        self._logical_words = off + 1
        self._lanes = [_LaneState() for _ in self.dtypes]
        self._since = self.reseed_every     # force choice on first batch
        self.stats = WireStats()
        #: "forced" (no link given), else "pending" until the first
        #: batch was measured, then "encode" or "raw" for good
        self.decision = FORCED if link_rate is None else PENDING
        #: what the decision was taken from (stats()["Staging"]["Wire"])
        self.link_bytes_per_sec = None if link_rate is None \
            else float(link_rate(self._logical_words))
        self.codec_bytes_per_sec = None
        self.codec_usec = None      # steady encode pass of the batch
        self.saved_bytes = None     # logical - wire bytes of that batch

    @property
    def ships_raw(self) -> bool:
        """The edge measured its link faster than its codec: callers
        ship the logical buffer and count it (``stats.note_raw``)
        without calling :meth:`encode` (which would still ship raw,
        after a codec pass nobody needs)."""
        return self.decision == RAW

    def _decide(self, codec_s: float, wire_words: int) -> None:
        """The one comparison: link time of the bytes the codec saved
        on this batch against the steady encode pass that saved them."""
        logical = self._logical_words * 4
        self.saved_bytes = max(0, logical - wire_words * 4)
        self.codec_usec = round(codec_s * 1e6, 1)
        self.codec_bytes_per_sec = logical / max(codec_s, 1e-9)
        saved_s = self.saved_bytes / max(self.link_bytes_per_sec, 1e-9)
        self.decision = ENCODE if saved_s > codec_s else RAW

    def decision_json(self) -> dict:
        return {"dtypes": [str(d) for d in self.dtypes],
                "capacity": self.capacity,
                "decision": self.decision,
                "link_bytes_per_sec": self.link_bytes_per_sec,
                "codec_bytes_per_sec": self.codec_bytes_per_sec,
                "codec_usec": self.codec_usec,
                "saved_bytes": self.saved_bytes}

    # -- lane value views ---------------------------------------------------
    def _values(self, buf: np.ndarray, i: int) -> np.ndarray:
        """Lane ``i`` of the logical buffer as int64 work values (signed
        interpretation for 4-byte lanes; the two word planes recombined
        for 8-byte) — the exact domain the device decode reconstructs."""
        off, w, cap = self._offsets[i], self._lane_words[i], self.capacity
        if w == 1:
            return buf[off:off + cap].view(np.int32).astype(np.int64)
        return staging.join_planes(buf[off:off + cap],
                                   buf[off + cap:off + 2 * cap])

    def _raw_words(self, buf: np.ndarray, i: int) -> np.ndarray:
        off, w = self._offsets[i], self._lane_words[i]
        return buf[off:off + w * self.capacity]

    # -- codec selection (reseed cadence) -----------------------------------
    def _choose(self, v: np.ndarray, i: int) -> None:
        st = self._lanes[i]
        dt = self.dtypes[i]
        cap = self.capacity
        best, best_w = RAW_CODEC, lane_wire_words(RAW_CODEC, dt, cap)
        prev_table = st.table if (st.codec is not None
                                  and st.codec.kind == DICT) else None
        st.table = None
        if cap >= 1 and bool((v == v[0]).all()):
            c = LaneCodec(CONST)
            w = lane_wire_words(c, dt, cap)
            if w < best_w:
                best, best_w = c, w
        if cap >= 2:
            d = np.diff(v)
            wd = _width_for(int(_zigzag(d).max()))
            if wd is not None:
                c = LaneCodec(DELTA, wd)
                w = lane_wire_words(c, dt, cap)
                if w < best_w:
                    best, best_w = c, w
            if cap >= 3:
                wdd = _width_for(int(_zigzag(np.diff(d)).max()))
                if wdd is not None:
                    c = LaneCodec(DELTA2, wdd)
                    w = lane_wire_words(c, dt, cap)
                    if w < best_w:
                        best, best_w = c, w
        uniq = np.unique(v)
        if prev_table is not None:
            # UNION with the previous table: a low-cardinality lane
            # whose batches sample the value space converges on the
            # full set instead of flip-flopping dict→raw per batch —
            # each flip would mint a new descriptor and recompile the
            # decode; the pow2 padding usually keeps the grown table's
            # descriptor (and its compiled program) stable
            uniq = np.unique(np.concatenate([prev_table, uniq]))
        if len(uniq) <= DICT_MAX:
            padded = _pow2ceil(len(uniq))
            c = LaneCodec(DICT, 8 if padded <= DICT_SMALL else 16, padded)
            w = lane_wire_words(c, dt, cap)
            if w < best_w:
                best, best_w = c, w
                st.table = np.concatenate(
                    [uniq, np.full(padded - len(uniq), uniq[-1],
                                   np.int64)])
        st.codec = best

    # -- per-batch encode ---------------------------------------------------
    def _encode_lane(self, buf, v: np.ndarray,
                     i: int) -> Tuple[List[np.ndarray], LaneCodec]:
        """Encode lane ``i`` under its current codec; a misfit (data
        stopped matching the choice) degrades to raw for this batch and
        forces a reseed at the next."""
        st = self._lanes[i]
        c = st.codec or RAW_CODEC
        out = self._try_encode(buf, v, i, c, st)
        if out is not None:
            return out, c
        self.stats.fallback_lanes += 1
        self._since = self.reseed_every     # re-choose next batch
        return [self._raw_words(buf, i)], RAW_CODEC

    def _try_encode(self, buf, v, i, c: LaneCodec,
                    st: _LaneState) -> Optional[List[np.ndarray]]:
        if c.kind == RAW:
            return [self._raw_words(buf, i)]
        if c.kind == CONST:
            if not bool((v == v[0]).all()):
                return None
            return _i64_header(v[0])
        if c.kind == DELTA:
            d = np.diff(v)
            zz = _zigzag(d)
            if len(zz) and int(zz.max()) >= (1 << max(1, c.width)):
                return None
            if c.width == 0 and len(zz) and int(zz.max()) != 0:
                return None
            return _i64_header(v[0]) \
                + [_pack_width(zz.astype(np.uint32), c.width)]
        if c.kind == DELTA2:
            d = np.diff(v)
            dd = np.diff(d)
            zz = _zigzag(dd)
            if len(zz) and int(zz.max()) >= (1 << max(1, c.width)):
                return None
            if c.width == 0 and len(zz) and int(zz.max()) != 0:
                return None
            return _i64_header(v[0]) + _i64_header(d[0] if len(d) else 0) \
                + [_pack_width(zz.astype(np.uint32), c.width)]
        if c.kind == DICT:
            table = st.table
            if table is None:
                return None
            idx = np.searchsorted(table, v)
            idx = np.clip(idx, 0, len(table) - 1)
            if not bool((table[idx] == v).all()):
                return None
            w = self._lane_words[i]
            if w == 1:
                tw = [(table & np.int64(0xFFFFFFFF)).astype(np.uint32)]
            else:
                # an 8-byte lane's table ships as the lane does: its
                # entries' low words, then their high words
                tw = list(staging.split_planes(table))
            return tw + [_pack_width(idx.astype(np.uint32), c.width)]
        return None

    def encode(self, buf: np.ndarray,
               pool=None) -> Tuple[np.ndarray, Optional[WireFormat]]:
        """Encode one FINISHED logical staging buffer (tail zeroed, fill
        count stamped at ``buf[-1]``).  Returns ``(wire_buf, fmt)`` —
        the wire buffer is acquired from ``pool`` at its size class and
        ``buf`` is released back (host-only use, no gate) — or
        ``(buf, None)`` when compression would not shrink the transfer
        (the caller ships the logical buffer exactly as before)."""
        clock = self._clock
        t0 = clock()
        if buf.shape[0] != self._logical_words:
            # capacity drift (defensive): ship raw rather than corrupt
            return buf, None
        if self._since >= self.reseed_every:
            for i in range(len(self.dtypes)):
                self._choose(self._values(buf, i), i)
            self._since = 0
            self.stats.reseeds += 1
        self._since += 1
        t_steady = clock()      # what every batch pays starts here
        parts: List[List[np.ndarray]] = []
        used: List[LaneCodec] = []
        total = 1
        for i in range(len(self.dtypes)):
            st = self._lanes[i]
            # raw lanes copy words straight through: no int64 lift, no
            # fit check — the steady-state cost of an incompressible
            # lane is one memcpy, nothing more
            v = None if (st.codec is None or st.codec.kind == RAW) \
                else self._values(buf, i)
            arrs, c = self._encode_lane(buf, v, i)
            parts.append(arrs)
            used.append(c)
            total += lane_wire_words(c, self.dtypes[i], self.capacity)
        padded = min(staging.size_class(total), self._logical_words)
        wire = None
        if padded < self._logical_words:
            wire = pool.acquire(padded) if pool is not None \
                else np.empty(padded, np.uint32)
            off = 0
            for arrs in parts:
                for a in arrs:
                    wire[off:off + len(a)] = a
                    off += len(a)
            # pad gap is never read by the decode program; recycled
            # buffers arrive with undefined contents anyway
            # (StagingPool contract)
            wire[-1] = buf[-1]
        t1 = clock()
        self.stats.encode_usec += (t1 - t0) * 1e6
        if self.decision == PENDING:
            self._decide(t1 - t_steady, padded)
        if wire is None or self.decision == RAW:
            # compression lost on this batch, or loses to the link on
            # this edge: the logical buffer ships unchanged
            if wire is not None and pool is not None:
                pool.release(wire, None)    # host-only scratch: no gate
            self.stats.note_raw(buf.nbytes)
            return buf, None
        if pool is not None:
            pool.release(buf, None)     # host-only scratch: no gate
        self.stats.batches += 1
        self.stats.logical_bytes += self._logical_words * 4
        self.stats.wire_bytes += padded * 4
        return wire, WireFormat(tuple(used), padded)

    def codec_table(self) -> list:
        """Current per-lane codec choices (stats surface)."""
        return [{"lane": i, "dtype": str(d),
                 "codec": (st.codec.kind if st.codec else "unseeded"),
                 "width": (st.codec.width if st.codec else None),
                 "dict_size": (st.codec.extra if st.codec else 0)}
                for i, (d, st) in enumerate(zip(self.dtypes, self._lanes))]


# ---------------------------------------------------------------------------
# device-side decode (traced; inlined into batch._get_unpack's program)
# ---------------------------------------------------------------------------

def build_wire_decode(fmt: WireFormat, dtypes, capacity: int):
    """Traced inverse of :class:`WireEncoder`: maps the uint32 wire
    buffer to the typed payload columns + int64 ts lane, for
    ``batch._get_unpack`` to inline AHEAD of its existing valid-mask
    derivation — the whole decode rides the one unpack dispatch the
    staged path already pays (zero extra dispatches, pinned by
    tests/test_wire.py via the jit registry).  ``dtypes`` are the
    payload lane dtype strings; the ts lane is implicit."""
    import jax.numpy as jnp

    all_dts = tuple(np.dtype(d) for d in dtypes) + (np.dtype(np.int64),)

    def _unpack_width(b, off, count, width):
        if width == 0 or count <= 0:
            return jnp.zeros(max(count, 0), jnp.uint32)
        if width == 32:
            return b[off:off + count]
        per = 32 // width
        idx = jnp.arange(count, dtype=jnp.int32)
        w = b[off + idx // per]
        sh = ((idx % per) * width).astype(jnp.uint32)
        return (w >> sh) & jnp.uint32((1 << width) - 1)

    _i64 = staging.join_planes

    def _unzigzag(zz):
        z = zz.astype(jnp.int64)
        return (z >> 1) ^ -(z & 1)

    def _cumsum_i64(d):
        """Inclusive prefix sum of int64 ``d``, two's-complement wrap —
        ``jnp.cumsum(d)`` bit for bit, as a few int32 scans over limbs
        narrow enough that no scan overflows.  XLA:TPU emulates a 64-bit
        cumsum as a u32-pair reduce-window: ~30 s of compile per decode
        program at 262144 lanes for a v5e, and every delta/delta2
        descriptor is its own program."""
        bits = ((2 ** 31 - 1) // max(1, d.shape[0])).bit_length() - 1
        acc = jnp.zeros(d.shape, jnp.int64)
        for lo in range(0, 64, bits):
            limb = ((d >> lo) & ((1 << bits) - 1)).astype(jnp.int32)
            acc = acc + (jnp.cumsum(limb).astype(jnp.int64) << lo)
        return acc

    def _from_i64(v, dt):
        import jax
        if dt.itemsize == 8:
            return v if dt == np.dtype(np.int64) \
                else v.astype(jnp.uint64)
        w = (v & jnp.int64(0xFFFFFFFF)).astype(jnp.uint32)
        return jax.lax.bitcast_convert_type(w, dt)

    def _words_to_dtype(w32, dt):
        import jax
        return jax.lax.bitcast_convert_type(w32, dt) \
            if dt != np.dtype(np.uint32) else w32

    def decode(b):
        cols = []
        off = 0
        for c, dt in zip(fmt.codecs, all_dts):
            w = staging.lane_words(dt)
            if c.kind == RAW:
                if w == 2:
                    cols.append(_i64(
                        b[off:off + capacity],
                        b[off + capacity:off + 2 * capacity]).astype(dt))
                else:
                    cols.append(_words_to_dtype(b[off:off + capacity], dt))
            elif c.kind == CONST:
                v = _i64(b[off], b[off + 1])
                cols.append(jnp.broadcast_to(_from_i64(v, dt),
                                             (capacity,)))
            elif c.kind == DELTA:
                base = _i64(b[off], b[off + 1])
                zz = _unpack_width(b, off + 2, capacity - 1, c.width)
                d = _unzigzag(zz)
                v = base + jnp.concatenate(
                    [jnp.zeros(1, jnp.int64), _cumsum_i64(d)])
                cols.append(_from_i64(v, dt))
            elif c.kind == DELTA2:
                base = _i64(b[off], b[off + 1])
                d0 = _i64(b[off + 2], b[off + 3])
                zz = _unpack_width(b, off + 4, capacity - 2, c.width)
                dd = _unzigzag(zz)
                d = d0 + jnp.concatenate(
                    [jnp.zeros(1, jnp.int64), _cumsum_i64(dd)])
                v = base + jnp.concatenate(
                    [jnp.zeros(1, jnp.int64), _cumsum_i64(d)])
                cols.append(_from_i64(v, dt))
            elif c.kind == DICT:
                idx = _unpack_width(b, off + c.extra * w, capacity,
                                    c.width).astype(jnp.int32)
                if w == 1:
                    tw = b[off:off + c.extra]
                    cols.append(_words_to_dtype(tw[idx], dt))
                else:
                    cols.append(_from_i64(
                        _i64(b[off:off + c.extra][idx],
                             b[off + c.extra:off + 2 * c.extra][idx]), dt))
            else:
                raise ValueError(f"unknown lane codec {c.kind!r}")
            off += lane_wire_words(c, dt, capacity)
        return cols

    return decode


# ---------------------------------------------------------------------------
# graph attachment + stats surfaces
# ---------------------------------------------------------------------------

def _wire_setting(cfg) -> Optional[bool]:
    """``Config.wire_compression`` as True (forced), False (off) or
    None ("auto")."""
    v = getattr(cfg, "wire_compression", "auto")
    if v in (True, 1, "1", "on", "true"):
        return True
    if v in (False, 0, None, "", "0", "off", "false"):
        return False
    return None


def wire_enabled(cfg) -> bool:
    """Whether ``Config.wire_compression`` attaches the plane at all:
    True/False ("1"/"0") are explicit; "auto" (the default) attaches it
    exactly when the default backend is a real accelerator.  On the CPU
    backend host and "device" share memory, so there is no link to
    measure and nothing attaches.  Attached under "auto" is not
    "encoding": each staging edge then times its own link and its own
    codec and encodes only where the link is the slower
    (:class:`WireEncoder`); only an explicit True forces the codec.  A
    backend that cannot initialize raises here: it is never read as
    "no compression"."""
    v = _wire_setting(cfg)
    if v is not None:
        return v
    import jax
    return jax.default_backend() != "cpu"


def iter_stage_emitters(graph):
    """Yield ``(edge_src_op, route_op, emitter)`` for every host→device
    staging emitter in a BUILT graph, descending into keyed staging
    emitters' per-partition inner emitters and split branches — the one
    walk shared by :func:`attach_wire` and :func:`wire_section`."""
    from windflow_tpu.parallel.emitters import (DeviceStageEmitter,
                                                KeyedDeviceStageEmitter,
                                                SplittingEmitter)

    def expand(a, route_op, em):
        if em is None:
            return
        if isinstance(em, KeyedDeviceStageEmitter):
            for inner in em._inner:
                yield a, route_op, inner
        elif isinstance(em, DeviceStageEmitter):
            yield a, route_op, em

    for edge in graph._edges():
        if edge[0] == "op":
            _, a, b = edge
            for rep in a.replicas:
                yield from expand(a, b, rep.emitter)
        else:
            _, mp = edge
            src = mp.operators[-1]
            heads = [c.operators[0] for c in mp.split_children
                     if c.operators]
            for rep in src.replicas:
                em = rep.emitter
                if not isinstance(em, SplittingEmitter):
                    continue
                for head, br in zip(heads, em.branches):
                    yield from expand(src, head, br)


def attach_wire(graph) -> None:
    """Enable wire compression on the staging emitters whose feeding
    edge has a declared/inferred record spec (the WF606 contract:
    spec-less edges stay raw passthrough — preflight already named
    them).  Called by ``PipeGraph._build`` after wiring, before any
    batch stages; with ``Config.wire_compression`` off this is never
    called and no encoder attaches anywhere."""
    from windflow_tpu.analysis.preflight import _UNKNOWN, propagate_specs
    try:
        in_specs, _ = propagate_specs(graph)
    except Exception:  # lint: broad-except-ok (abstract eval of
        # arbitrary user kernels — the wire plane degrades to raw
        # passthrough, it must never take the build down)
        in_specs = {}
    reseed = getattr(graph.config, "key_compaction_reseed", 64)
    # only an explicit True forces the codec; under "auto" each edge
    # decides from its own measured link and codec times
    forced = _wire_setting(graph.config) is True
    for _src, route_op, em in iter_stage_emitters(graph):
        if em._stage_target is not None:
            continue    # mesh staging: per-shard assembly, not packed
        spec = in_specs.get(id(route_op))
        if spec is None or spec is _UNKNOWN:
            continue    # WF606: documented raw-passthrough downgrade
        em.enable_wire(reseed, measured=not forced)


def wire_section(graph) -> dict:
    """``stats()["Staging"]["Wire"]``: merged wire-plane counters over
    the graph's staging emitters, the current per-lane codec table
    (one table per distinct lane layout) and, per encoder, what it
    decided and from which two measurements (``decisions``)."""
    enabled = wire_enabled(graph.config)
    agg = WireStats()
    codecs = []
    decisions = []
    emitters = 0
    for _src, _route, em in iter_stage_emitters(graph):
        for enc in getattr(em, "_wire_encoders", {}).values():
            emitters += 1
            agg.merge(enc.stats)
            if enc.stats.batches and len(codecs) < 8:
                codecs.append(enc.codec_table())
            if len(decisions) < 8:
                decisions.append(enc.decision_json())
    out = {"enabled": enabled, "encoders": emitters}
    out.update(agg.to_json())
    out["codecs"] = codecs[0] if len(codecs) == 1 else codecs
    out["decisions"] = decisions
    return out
