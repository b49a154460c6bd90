"""Sink operator (reference ``/root/reference/wf/sink.hpp:56-``): terminal
consumer.  The user function receives each tuple, and ``None`` once at
end-of-stream (the reference passes an empty ``std::optional`` at EOS).

Columnar mode (``withColumnarSink``): on TPU→Sink edges the user function
instead receives one :class:`SinkColumns` per device batch — the payload as
SoA numpy columns plus the timestamp lane — skipping per-record Python
object construction entirely (the egress twin of the columnar ingest path,
``windflow_tpu/io``; reference GPU→CPU bulk D2H,
``keyby_emitter_gpu.hpp:594-638``)."""

from __future__ import annotations

import collections
import dataclasses
from typing import Any, Callable, Optional

from windflow_tpu import batch as wfbatch
from windflow_tpu.basic import RoutingMode
from windflow_tpu.meta import adapt
from windflow_tpu.monitoring import recorder as flightrec
from windflow_tpu.ops.base import Operator, Replica


@dataclasses.dataclass
class SinkColumns:
    """One device batch delivered columnar: ``cols`` mirrors the payload
    pytree with ``[n]``-leading numpy arrays; ``tss`` is int64 ``[n]``."""

    cols: Any
    tss: Any
    watermark: int

    def __len__(self) -> int:
        return len(self.tss)


#: deliveries (of rows) a columnar sink replica looks back on to size the
#: next copy
FRONT_HISTORY = 8


class SinkReplica(Replica):
    def __init__(self, op: "Sink", index: int) -> None:
        super().__init__(op, index)
        self._fn = adapt(op.fn, 1)
        #: columnar egresses in flight (``batch.ColumnarEgress``), oldest
        #: first.  Empty on a sink that holds nothing, which is all the
        #: driver's sweep checks (``PipeGraph._sweep``)
        self._pending = []
        self.deliveries_ready = 0   # delivered without a wait
        self.deliveries_waited = 0  # the bound or end of stream waited
        self.pending_max = 0
        #: where the rows of the last packed deliveries that held any ended
        #: (their ``extent``): what the next batch's front copy is sized
        #: from.  An empty batch says nothing about where rows lie when
        #: they come: a window that fires once in forty batches, all over
        #: its output grid, must not pay a second copy for every firing
        self._extents = collections.deque(maxlen=FRONT_HISTORY)
        self.front_copies = 0       # batches whose front was copied first
        self.front_overflows = 0    # ... of them, fetched whole after all

    def process_single(self, item, ts, wm):
        self._fn(item, self.context)

    def process_device_batch(self, batch):
        # A sink fed directly by a TPU operator pulls the batch to host
        # (reference GPU→CPU boundary): columnar sinks get the SoA lanes in
        # one bulk copy, record sinks get per-tuple dicts.  The egress copy
        # moves the timestamp and validity lanes too, so the D2H counter
        # uses the shared whole-batch definition (batch.transfer_nbytes).
        if self.op.columnar:
            # The copy starts now: the batch's pack program is enqueued
            # behind the step that fills it and the host copy requested
            # (JAX dispatch is asynchronous), and nobody waits.  What the
            # device reports done is delivered, in receipt order, here and
            # once a driver sweep; only when MORE than ``defer`` batches
            # are in flight does the driver block, and then for the
            # oldest, never for the step it was just handed (the reference
            # hides D2H behind per-batch CUDA streams the same way).  EOS
            # drains the queue.
            self._pending.append(wfbatch.ColumnarEgress(
                batch, self._front_lanes(batch.capacity)))
            self.pending_max = max(self.pending_max, len(self._pending))
            self.deliver(keep=self.op.columnar_defer)
            return
        nbytes = wfbatch.transfer_nbytes(batch)
        self.stats.d2h_bytes += nbytes
        with flightrec.span("wf.sink.d2h", batch=batch.seq, bytes=nbytes,
                            lanes=batch.capacity, cap=batch.capacity):
            hb = wfbatch.device_to_host(batch)
        with flightrec.span("wf.sink.deliver", batch=batch.seq,
                            rows=len(hb.items)):
            for item, ts in zip(hb.items, hb.tss):
                self.context._set_context(ts, batch.watermark)
                self._fn(item, self.context)

    def _front_lanes(self, cap: int) -> Optional[int]:
        """Leading lanes to copy of the next ``cap``-lane batch, from
        where the rows of the last :data:`FRONT_HISTORY` deliveries that
        held rows ended (``None``: the whole batch, as until that many
        were seen).  A longer extent widens the next copy at once, and it
        narrows only when every delivery looked back on fits the narrower
        one, so a steady stream settles on one size."""
        if len(self._extents) < FRONT_HISTORY:
            return None
        return wfbatch.front_lanes(cap, max(self._extents))

    def oldest_ready(self) -> bool:
        """Has the device finished the oldest batch in flight?  (Only
        asked of a replica that holds one.)"""
        return self._pending[0].is_ready()

    def deliver(self, keep: Optional[int] = None) -> bool:
        """Deliver, oldest first, the batches in flight whose step the
        device reports done, and stop at the first that is not, unless
        more than ``keep`` are in flight: then wait for it (``None``
        never waits).  Finding nothing to deliver opens no span and
        allocates nothing."""
        delivered = False
        while self._pending:
            ready = self.oldest_ready()
            if not ready and (keep is None or len(self._pending) <= keep):
                break
            self._deliver_oldest(waited=int(not ready))
            delivered = True
        return delivered

    def drain(self, limit: int = 0) -> bool:
        # the inbox, then what the device has finished meanwhile: the
        # driver's sweep comes here with an empty inbox when the oldest
        # batch in flight is ready (PipeGraph._sweep)
        progressed = super().drain(limit)
        return self.deliver() or progressed

    def _deliver_oldest(self, waited: int) -> None:
        egress = self._pending.pop(0)
        if waited:
            self.deliveries_waited += 1
        else:
            self.deliveries_ready += 1
        b = egress.batch
        # ``waited`` says whether the driver blocked for the batch's step
        # here or found it done; ``lanes`` and ``bytes`` what crossed the
        # link of the batch's ``cap`` lanes: the front, the front and then
        # the whole batch (an overflow), or the whole batch
        with flightrec.span("wf.sink.d2h", batch=b.seq, cap=b.capacity,
                            waited=waited) as sp:
            (cols, tss), = wfbatch.device_to_columns_multi([egress])
            lanes = egress.lanes_copied
            nbytes = wfbatch.transfer_nbytes(b) * lanes // b.capacity
            sp.note(bytes=nbytes, lanes=lanes)
        self.stats.d2h_bytes += nbytes
        if egress.front is not None:
            self.front_copies += 1
            self.front_overflows += egress.overflowed
        if egress.extent:
            self._extents.append(egress.extent)
        if len(tss):
            self.context._set_context(int(tss[-1]), b.watermark)
            with flightrec.span("wf.sink.deliver", batch=b.seq,
                                rows=len(tss)):
                self._fn(SinkColumns(cols, tss, b.watermark), self.context)

    def on_eos(self):
        self.deliver(keep=0)
        self._fn(None, self.context)


class Sink(Operator):
    replica_class = SinkReplica
    is_terminal = True

    def __init__(self, fn: Callable[[Optional[Any]], None], name: str = "sink",
                 parallelism: int = 1,
                 routing: RoutingMode = RoutingMode.FORWARD,
                 key_extractor=None, columnar: bool = False,
                 columnar_defer: int = 2) -> None:
        super().__init__(name, parallelism, routing=routing,
                         key_extractor=key_extractor)
        self.fn = fn
        #: columnar sinks receive SinkColumns per device batch instead of
        #: per-record dicts (host-batch edges still deliver records)
        self.columnar = columnar
        #: the user callback trails the stream by up to this many batches:
        #: a batch is delivered when the device reports its step done, and
        #: the driver waits (for the oldest) only while more than this
        #: many are in flight; 0 converts at receipt
        self.columnar_defer = max(0, columnar_defer)

    def dump_stats(self) -> dict:
        st = super().dump_stats()
        if self.columnar:
            reps = self.replicas
            st["Sink_deliveries_ready"] = sum(r.deliveries_ready
                                              for r in reps)
            st["Sink_deliveries_waited"] = sum(r.deliveries_waited
                                               for r in reps)
            st["Sink_pending_max"] = max((r.pending_max for r in reps),
                                         default=0)
            st["Sink_front_copies"] = sum(r.front_copies for r in reps)
            st["Sink_front_overflows"] = sum(r.front_overflows
                                             for r in reps)
        return st
