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

import dataclasses
from typing import Any, Callable, Optional

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


class SinkReplica(Replica):
    def __init__(self, op: "Sink", index: int) -> None:
        super().__init__(op, index)
        self._fn = adapt(op.fn, 1)
        self._pending = []          # deferred device batches (columnar)
        self._pending_bytes = 0     # their transfer size (wf.sink.d2h)

    def process_single(self, item, ts, wm):
        self._fn(item, self.context)

    def process_device_batch(self, batch):
        # A sink fed directly by a TPU operator pulls the batch to host
        # (reference GPU→CPU boundary): columnar sinks get the SoA lanes in
        # one bulk copy, record sinks get per-tuple dicts.  The egress copy
        # moves the timestamp and validity lanes too, so the D2H counter
        # uses the shared whole-batch definition (batch.transfer_nbytes).
        from windflow_tpu.batch import transfer_nbytes
        nbytes = transfer_nbytes(batch)
        self.stats.d2h_bytes += nbytes
        if self.op.columnar:
            # Deferred conversion: hold the last ``defer`` batches and pull
            # the oldest — JAX dispatch is asynchronous, so the device→host
            # transfer of batch i overlaps the compute of batches i+1.. and
            # the per-transfer link latency leaves the critical path (the
            # reference hides D2H behind per-batch CUDA streams the same
            # way).  EOS drains the queue.
            self._pending.append(batch)
            self._pending_bytes += nbytes
            if len(self._pending) > self.op.columnar_defer:
                # drain the whole queue in ONE device->host transfer
                pend, self._pending = self._pending, []
                self._deliver_columns(pend)
            return
        from windflow_tpu.batch import device_to_host
        with flightrec.span("wf.sink.d2h", batch=batch.seq, batches=1,
                            bytes=nbytes, lanes=batch.capacity):
            hb = device_to_host(batch)
        with flightrec.span("wf.sink.deliver", batch=batch.seq,
                            rows=len(hb.items)):
            for item, ts in zip(hb.items, hb.tss):
                self.context._set_context(ts, batch.watermark)
                self._fn(item, self.context)

    def _deliver_columns(self, batches):
        from windflow_tpu.batch import device_to_columns_multi
        nbytes, self._pending_bytes = self._pending_bytes, 0
        # one transfer for the whole queue: the span carries its first
        # batch's number, how many ride with it and the lanes they hold
        # (rows and padding alike: the copy moves whole batches)
        with flightrec.span("wf.sink.d2h", batch=batches[0].seq,
                            batches=len(batches), bytes=nbytes,
                            lanes=sum(b.capacity for b in batches)):
            columns = device_to_columns_multi(batches)
        for b, (cols, tss) in zip(batches, columns):
            if len(tss):
                self.context._set_context(int(tss[-1]), b.watermark)
                with flightrec.span("wf.sink.deliver", batch=b.seq,
                                    rows=len(tss)):
                    self._fn(SinkColumns(cols, tss, b.watermark),
                             self.context)

    def on_eos(self):
        if self._pending:
            self._deliver_columns(self._pending)
            self._pending = []
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
        #: batches held before conversion (transfer/compute overlap); the
        #: user callback trails the stream by up to this many batches
        self.columnar_defer = max(0, columnar_defer)
