"""FrameSource: bulk binary/CSV ingestion through the native parser.

The TPU-native answer to the reference's high-rate ingestion paths (Kafka
consumer poll loops, ``kafka_source.hpp:270-310``; and the test drivers that
generate tuples in tight C++ loops): instead of one Python object per tuple,
the source pulls **byte chunks** from the user, parses them to columns in C++
(``native/wf_host.cpp`` wf_parse_frames / wf_parse_csv), and hands whole
columns to the staging emitter — so a batch travels from bytes to TPU HBM
without any per-tuple Python work.  Falls back to numpy parsing when the
native library is unavailable.

Binary frames bound for a packed one-chip staging edge skip the columns:
the native parse writes each field once, at the width and the offset the
staged batch holds it, straight into the edge's pooled staging buffer
(``FrameSourceReplica._ingest_in_place``, wf_parse_frames_packed; the
edge answers ``Emitter.packed_destination``).  The buffer that ships is
word for word the one the columns would have given.

Record wire format (``fmt="frames"``): little-endian ``int64 key, int64 ts,
nv × float64 values``.  CSV (``fmt="csv"``): ``key,ts,v0[,v1...]`` lines.
"""

from __future__ import annotations

from typing import Callable, Iterable, List, Optional

import numpy as np

from windflow_tpu import native
from windflow_tpu.basic import RoutingMode, TimePolicy, WindFlowError, \
    current_time_usecs
from windflow_tpu.meta import adapt
from windflow_tpu.monitoring import recorder as flightrec
from windflow_tpu.ops.base import Operator
from windflow_tpu.ops.source import BaseSourceReplica, Source


def _fits_int32(lo: int, hi: int) -> bool:
    """The key lane's width rule: int32 when every key of a chunk (its
    min ``lo`` and max ``hi``) fits and the chunk's keys are of one sign;
    a chunk that straddles zero keeps the wire's int64, as it always has."""
    return -(1 << 31) <= lo and hi < (1 << 31) and (lo < 0) == (hi < 0)


class FrameSourceReplica(BaseSourceReplica):
    def __init__(self, op: "FrameSource", index: int) -> None:
        super().__init__(op, index)
        self._chunks = None
        self._carry = b""
        # the one-pass route (_ingest_in_place) is taken for binary frames
        # with the native library loaded and a value dtype it writes; the
        # emitter's answer decides the rest, once
        self._in_place_kind = native.PACKED_VALUE_KINDS.get(op.value_dtype)
        self._in_place = op.fmt == "frames" and native.is_available() \
            and self._in_place_kind is not None
        self._in_place_names = ("key",) + tuple(op.fields)
        # the columns' dtypes with a narrow (False) and a wide (True) key
        self._in_place_dtypes = {
            wide: (key,) + (op.value_dtype.name,) * op.nv
            for wide, key in ((False, "int32"), (True, "int64"))}
        self._in_place_wide = False     # the last chunk's key width

    def start(self) -> None:
        self._chunks = iter(self.op.chunks_fn(self.context))

    def tick(self, max_items: int) -> bool:
        if self._exhausted:
            return False
        try:
            chunk = next(self._chunks)
        except StopIteration:
            self._flush_carry()
            self._exhausted = True
            self._terminate()
            return True  # termination (EOS cascade) is progress
        buf = self._carry + chunk
        if buf:     # a paced iterator yields b"" while nothing is due
            self._ingest(buf)
        return True

    def _flush_carry(self) -> None:
        if self._carry:
            if self.op.fmt == "csv" and not self._carry.endswith(b"\n"):
                # a file without a trailing newline still ends in a complete
                # record; an unterminated binary frame is genuinely partial
                self._carry += b"\n"
            self._ingest(self._carry, final=True)

    def _ingest(self, buf: bytes, final: bool = False) -> None:
        if self._in_place and self._ingest_in_place(buf, final):
            return
        with flightrec.span("wf.parse", bytes=len(buf)) as sp:
            parsed = self._parse(buf, final)
            sp.note(n=0 if parsed is None else len(parsed[1]))
        if parsed is None:
            return
        cols, tss, row_wms = parsed
        self.emitter.emit_columns(cols, tss, self.current_wm,
                                  row_wms=row_wms)
        self._count_toward_punctuation(len(tss))

    def _ingest_in_place(self, buf: bytes, final: bool) -> bool:
        """The one-pass route: the native parse writes each field of a
        frame once, at its staged width, into the staging buffer the
        emitter's open batch ships — same words, same stamps and the same
        cuts as ``_parse`` + ``emit_columns``, with no column in between.
        False (and the two-pass route from then on) when the emitter has
        no such destination: a mesh or keyed staging edge, a host edge."""
        nv = self.op.nv
        rec = native.frame_record_bytes(nv)
        n = len(buf) // rec
        em, names, dtypes = self.emitter, self._in_place_names, \
            self._in_place_dtypes
        # the chunk's key width, by _parse's rule.  Guessed from the chunk
        # before and checked against the keys the parse reads, before its
        # rows are committed: a chunk written at the wrong width is
        # written again.  A chunk that will split a batch ships rows
        # before its last key is read, so its keys are scanned first.
        wide, checked = self._in_place_wide, False
        # ingress time: one arrival stamp a chunk, as _parse gives its rows
        ts_fixed = max(current_time_usecs(), self._last_ts) \
            if self.time_policy == TimePolicy.INGRESS else None
        ts_top = self._last_ts
        pos = 0
        while pos < n:
            with flightrec.span("wf.parse", direct=1) as sp:
                dest = em.packed_destination(names, dtypes[wide])
                if dest is None:
                    # asked before the chunk's first row: nothing is
                    # written, and an edge's answer stands
                    sp.note(n=0, bytes=0)
                    self._in_place = False
                    return False
                bld, lane_off = dest
                if not checked and n > bld.room:
                    checked = True
                    if wide == _fits_int32(*native.frames_key_range(buf, nv)):
                        wide = not wide
                        sp.note(n=0, bytes=0)
                        continue
                m, ts_lo, ts_hi, k_lo, k_hi = native.parse_frames_packed(
                    buf, pos * rec, nv, bld.buf, lane_off, bld.capacity,
                    2 if wide else 1, self._in_place_kind, bld.n,
                    min(bld.room, n - pos), ts_fixed)
                if not checked:
                    checked = True      # m == n: the whole chunk was read
                    if wide == _fits_int32(k_lo, k_hi):
                        wide = not wide
                        sp.note(n=0, bytes=0)
                        continue
                sp.note(n=m, bytes=m * rec)
            # the row frontier after the slice's last row: the running
            # max of event time (_parse's row_wms, read at that row)
            ts_top = max(ts_top, ts_hi)
            em.commit_packed(m, ts_lo, ts_hi, max(ts_top, 0))
            pos += m
        self._in_place_wide = wide
        self._carry = b"" if final else buf[n * rec:]
        self._last_ts = ts_top
        self._advance_wm(ts_top)
        self.stats.outputs_sent += n
        self._count_toward_punctuation(n)
        return True

    def _parse(self, buf: bytes, final: bool):
        """Bytes to the columns the emitter takes: the native parse and
        the column shaping.  None when ``buf`` holds no whole record."""
        nv = self.op.nv
        if self.op.fmt == "frames":
            keys, tss, vals, consumed = native.parse_frames(buf, nv)
        else:
            keys, tss, vals, consumed = native.parse_csv(buf, nv)
        self._carry = b"" if final else buf[consumed:]
        n = len(keys)
        if n == 0:
            return None
        if self.time_policy == TimePolicy.INGRESS:
            # every record of the chunk arrived with the chunk: one arrival
            # stamp (monotone vs earlier chunks), not a synthetic +arange
            # ramp that would place timestamps in the wall-clock future
            base = max(current_time_usecs(), self._last_ts)
            tss = np.full(n, base, dtype=np.int64)
            row_wms = tss
        else:
            # per-row frontier: running max event ts (reference
            # Source_Shipper advances the watermark per tuple) — lets the
            # staging emitter stamp batches that split this chunk exactly
            row_wms = np.maximum(np.maximum.accumulate(tss),
                                 max(self._last_ts, 0))
        self._last_ts = max(self._last_ts, int(tss.max()))
        self._advance_wm(self._last_ts)
        self.stats.outputs_sent += n
        # int32 keys on device when they fit: every keyed device operator
        # interns int32 keys (KeyedDeviceStageEmitter._key32), so staging
        # the full int64 wire key usually doubles the lane's bytes for no
        # extra key space — but keys outside int32 (e.g. 64-bit hash ids)
        # keep their width so host-side consumers never see collisions
        keys = keys.astype(np.int64)
        if _fits_int32(int(keys.min()), int(keys.max())):
            keys = keys.astype(np.int32)
        cols = {"key": keys}
        vd = self.op.value_dtype
        for i, name in enumerate(self.op.fields):
            cols[name] = np.ascontiguousarray(vals[:, i].astype(vd,
                                                                copy=False))
        return cols, tss, row_wms


class FrameSource(Source):
    """Bulk source over a byte-chunk generator.

    ``chunks_fn`` (optionally taking a RuntimeContext) yields ``bytes``
    objects; records may span chunk boundaries (the remainder is carried).
    ``fields`` names the ``nv`` float64 value columns; records surface
    downstream as ``{"key": int, <field>: float, ...}``.

    TPU-first dtype policy: value columns are staged as **float32** by
    default even though the wire format is float64 — the TPU has no native
    f64 (XLA emulates it with 32-bit pairs at several times the cost) and
    f32 halves the staged bytes.  Pass ``value_dtype=np.float64`` for full
    wire precision; keys keep int64 whenever they don't fit int32."""

    replica_class = FrameSourceReplica

    def __init__(self, chunks_fn: Callable[..., Iterable[bytes]],
                 nv: int = 1, fields: Optional[List[str]] = None,
                 fmt: str = "frames", name: str = "frame_source",
                 parallelism: int = 1, output_batch_size: int = 0,
                 value_dtype=np.float32) -> None:
        if fmt not in ("frames", "csv"):
            raise WindFlowError(f"unknown frame format '{fmt}'")
        if fields is not None and len(fields) != nv:
            raise WindFlowError("fields must name all nv value columns")
        Operator.__init__(self, name, parallelism, routing=RoutingMode.NONE,
                          output_batch_size=output_batch_size)
        self.chunks_fn = adapt(chunks_fn, 0)
        self.nv = nv
        self.fields = fields or [f"v{i}" for i in range(nv)]
        self.fmt = fmt
        #: device dtype for value columns.  float32 by default — the wire
        #: format is float64, but the TPU has no native f64 (XLA emulates
        #: it with 32-bit pairs); pass np.float64 to keep full precision.
        self.value_dtype = np.dtype(value_dtype)
        self.ts_extractor = None
