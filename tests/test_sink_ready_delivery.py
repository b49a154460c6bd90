"""The columnar sink (ops/sink.py, docs/OBSERVABILITY.md "wf.sink.d2h"):
a batch's egress starts at receipt, a batch is delivered when the device
reports it done, in receipt order, and the driver waits only for the
OLDEST batch in flight and only over the ``defer`` bound.

The device's answer is the test's to give: ``Gated`` stands where
``batch.ColumnarEgress`` does, runs the real pack, copy and unpack, and
says ``is_ready()`` what the test tells it to.  Nothing here times the
egress; one micro-assert times the sweep's check of an idle sink."""

import dataclasses
import struct
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import windflow_tpu as wf
from test_layer_spans import _Annotation  # (the fake capture)
from windflow_tpu import batch as wfbatch
from windflow_tpu.basic import default_config
from windflow_tpu.batch import DeviceBatch
from windflow_tpu.io import FrameSource
from windflow_tpu.monitoring.openmetrics import (parse_exposition,
                                                 render_openmetrics)
from windflow_tpu.ops.sink import Sink, SinkColumns, SinkReplica

CAP = 8


class Gated(wfbatch.ColumnarEgress):
    """The real egress with the device's answer replaced: a packed batch
    is ready when the test says so (``ready``), a batch that cannot be
    packed answers for itself (``ready`` None)."""

    made: list = []
    blocked_on: list = []       # seq of every batch waited for, in order

    def __init__(self, b, front=None):
        super().__init__(b, front)
        self.ready = False if self._packed is not None else None
        Gated.made.append(self)

    def is_ready(self):
        return super().is_ready() if self.ready is None else self.ready

    def columns(self):
        if not self.is_ready():
            Gated.blocked_on.append(self.batch.seq)
        return super().columns()


@pytest.fixture
def gated(monkeypatch):
    monkeypatch.setattr(wfbatch, "ColumnarEgress", Gated)
    Gated.made, Gated.blocked_on = [], []
    return Gated


def _batch(seq, n=5, on_host=False):
    """A batch of ``n`` rows whose values say which batch they are of."""
    xp = np if on_host else jnp
    lane = np.arange(CAP)
    return DeviceBatch(
        {"key": xp.asarray(lane.astype(np.int32)),
         "value": xp.asarray((100.0 * seq + lane).astype(np.float32))},
        xp.asarray((1000 * seq + lane).astype(np.int64)),
        xp.asarray(lane < n), watermark=1000 * seq, size=n, seq=seq)


def _sink(defer=2):
    got = []
    op = Sink(got.append, columnar=True, columnar_defer=defer)
    rep = SinkReplica(op, 0)
    op.replicas = [rep]
    return op, rep, got


def _seqs(got):
    """Which batch each delivery was of (``None`` for the end marker)."""
    return [None if c is None else int(c.cols["value"][0]) // 100
            for c in got]


def _is_whole(c, seq, n=5):
    assert isinstance(c, SinkColumns) and len(c) == n
    np.testing.assert_array_equal(c.cols["key"], np.arange(n))
    np.testing.assert_array_equal(c.cols["value"],
                                  100.0 * seq + np.arange(n))
    np.testing.assert_array_equal(c.tss, 1000 * seq + np.arange(n))
    assert c.watermark == 1000 * seq


# -- (a) receipt order, whatever order the device finishes in ----------------

def test_delivery_is_in_receipt_order_when_later_batches_finish_first(gated):
    op, rep, got = _sink(defer=3)
    for seq in (1, 2, 3):
        rep.process_device_batch(_batch(seq))
    assert got == [] and len(rep._pending) == 3
    gated.made[2].ready = True
    gated.made[1].ready = True
    assert not rep.deliver() and got == []    # the oldest still runs
    gated.made[0].ready = True
    assert rep.deliver()
    assert _seqs(got) == [1, 2, 3] and rep._pending == []
    for seq, c in zip((1, 2, 3), got):
        _is_whole(c, seq)
    assert gated.blocked_on == []


# -- (b) the bound: the oldest is waited for, never the newest ---------------

def test_under_the_bound_nothing_is_waited_for(gated):
    op, rep, got = _sink(defer=2)
    rep.process_device_batch(_batch(1))
    rep.process_device_batch(_batch(2))
    assert got == [] and gated.blocked_on == []
    assert [e.batch.seq for e in rep._pending] == [1, 2]


def test_over_the_bound_the_wait_is_for_the_oldest(gated):
    op, rep, got = _sink(defer=2)
    for seq in (1, 2, 3, 4):
        rep.process_device_batch(_batch(seq))
    # the third receipt waited for batch 1, the fourth for batch 2; the
    # batch just handed over was never waited for
    assert gated.blocked_on == [1, 2] and _seqs(got) == [1, 2]
    assert [e.batch.seq for e in rep._pending] == [3, 4]
    assert rep.deliveries_waited == 2 and rep.deliveries_ready == 0


def test_a_receipt_delivers_what_became_ready_before_it_waits(gated):
    op, rep, got = _sink(defer=2)
    rep.process_device_batch(_batch(1))
    rep.process_device_batch(_batch(2))
    gated.made[0].ready = gated.made[1].ready = True
    rep.process_device_batch(_batch(3))
    assert _seqs(got) == [1, 2] and gated.blocked_on == []
    assert rep.deliveries_ready == 2 and rep.deliveries_waited == 0


# -- (d) end of stream -------------------------------------------------------

def test_end_of_stream_drains_everything_then_the_end_marker(gated):
    op, rep, got = _sink(defer=5)
    for seq in (1, 2, 3):
        rep.process_device_batch(_batch(seq))
    gated.made[1].ready = True
    rep.on_eos()
    assert _seqs(got) == [1, 2, 3, None] and rep._pending == []
    assert gated.blocked_on == [1, 3]
    assert (rep.deliveries_ready, rep.deliveries_waited) == (1, 2)


# -- (e) defer=0 -------------------------------------------------------------

@pytest.mark.parametrize("ready", [False, True])
def test_defer_zero_delivers_at_receipt(monkeypatch, gated, ready):
    if ready:
        # the device has finished by the time the sink asks
        monkeypatch.setattr(Gated, "is_ready", lambda self: True)
    op, rep, got = _sink(defer=0)
    for seq in (1, 2):
        rep.process_device_batch(_batch(seq))
        assert _seqs(got) == list(range(1, seq + 1)) and rep._pending == []
    assert gated.blocked_on == ([] if ready else [1, 2])
    assert (rep.deliveries_ready, rep.deliveries_waited) \
        == ((2, 0) if ready else (0, 2))
    assert Sink(print, columnar=True, columnar_defer=-3).columnar_defer == 0


# -- (g) a batch that cannot be packed ---------------------------------------

def test_an_unpackable_batch_takes_the_fallback_and_keeps_its_place(gated):
    op, rep, got = _sink(defer=3)
    rep.process_device_batch(_batch(1))
    rep.process_device_batch(_batch(2, on_host=True))
    rep.process_device_batch(_batch(3))
    e1, e2, e3 = gated.made
    assert e1._packed is not None and e2._packed is None
    assert e2.is_ready()                # numpy lanes: on the host already
    assert got == []                    # ... but behind batch 1
    e1.ready = True
    assert rep.deliver()
    assert _seqs(got) == [1, 2] and gated.blocked_on == []
    _is_whole(got[1], 2)
    e3.ready = True
    rep.on_eos()
    assert _seqs(got) == [1, 2, 3, None]


def test_an_empty_batch_is_counted_and_not_handed_to_the_function(gated):
    op, rep, got = _sink(defer=0)
    rep.process_device_batch(_batch(1, n=0))
    assert got == [] and rep._pending == []
    assert rep.deliveries_waited == 1


# -- the egress itself -------------------------------------------------------

def test_readiness_is_the_packed_buffers_or_the_validity_lanes():
    packed = wfbatch.ColumnarEgress(_batch(1))
    assert isinstance(packed._packed[0], jax.Array)
    jax.block_until_ready(packed._packed[0])
    assert packed.is_ready()
    host = wfbatch.ColumnarEgress(_batch(2, on_host=True))
    assert host._packed is None and host.is_ready()
    for e, seq in ((packed, 1), (host, 2)):
        cols, tss = e.columns()
        _is_whole(SinkColumns(cols, tss, 1000 * seq), seq)


def test_columns_of_batches_and_of_started_egresses_come_in_input_order():
    """``device_to_columns_multi`` takes a batch or an egress started
    earlier, and is what the sink delivers through."""
    started = wfbatch.ColumnarEgress(_batch(2))
    out = wfbatch.device_to_columns_multi(
        [_batch(1), started, _batch(3, on_host=True), _batch(4, n=CAP)])
    assert [int(c["value"][0]) // 100 for c, _ in out] == [1, 2, 3, 4]
    assert [len(t) for _, t in out] == [5, 5, 5, CAP]
    one = wfbatch.device_to_columns(_batch(1))
    np.testing.assert_array_equal(one[0]["value"], out[0][0]["value"])
    np.testing.assert_array_equal(one[1], out[0][1])
    assert wfbatch.device_to_columns_multi([]) == []


def test_the_sink_delivers_through_device_to_columns_multi(monkeypatch, gated):
    """(The benchmark's rehearsals break the timed path there.)"""
    seen = []
    real = wfbatch.device_to_columns_multi

    def watched(batches):
        seen.append([b.batch.seq for b in batches])
        return real(batches)

    monkeypatch.setattr(wfbatch, "device_to_columns_multi", watched)
    op, rep, got = _sink(defer=1)
    for seq in (1, 2, 3):
        rep.process_device_batch(_batch(seq))
    rep.on_eos()
    assert seen == [[1], [2], [3]]


# -- through a graph: the sweep's hook, the counters, the span ---------------

class _Stream:
    """Chunks of one full staging batch each, handed over when the test
    says (``send``); between them the source yields nothing, as a paced
    reader does; ``stop`` ends the stream."""

    def __init__(self, cap):
        self.cap, self.send, self.stop = cap, 0, False

    def chunks(self):
        sent = 0
        while not self.stop:
            if sent < self.send:
                yield b"".join(
                    struct.pack("<qqd", i % 4, 1000 * sent + i,
                                100.0 * (sent + 1) + i % 4)
                    for i in range(self.cap))
                sent += 1
            else:
                yield b""


def _graph(name, defer=2, cap=64, **cfg_kw):
    stream = _Stream(cap)
    src = FrameSource(stream.chunks, nv=1, fmt="frames",
                      output_batch_size=cap)
    src.record_spec = {"key": np.int32(0), "v0": np.float32(0.0)}
    got = []
    snk = wf.Sink_Builder(got.append).withColumnarSink(defer=defer).build()
    cfg = dataclasses.replace(default_config,
                              punctuation_interval_usec=10 ** 12, **cfg_kw)
    g = wf.PipeGraph(name, wf.ExecutionMode.DEFAULT, wf.TimePolicy.EVENT,
                     config=cfg)
    g.add_source(src).add(wf.MapTPU_Builder(
        lambda t: {"key": t["key"], "value": t["v0"]}).build()).add_sink(snk)
    return g, stream, snk, got


def _step_until(g, cond, limit=500):
    for _ in range(limit):
        if cond():
            return
        g.step()
    raise AssertionError("the graph did not get there")


def _finish(g, stream):
    stream.stop = True
    _step_until(g, g.is_done)
    g.wait_end()


# -- (c) the sweep's hook ----------------------------------------------------

def test_a_ready_batch_is_delivered_in_the_sweep_that_finds_it_ready(gated):
    g, stream, snk, got = _graph("sink_sweep_hook")
    g.start()
    rep, = snk.replicas
    assert g._columnar_sinks == [rep]
    stream.send = 1
    _step_until(g, lambda: rep._pending)
    for _ in range(5):                  # not ready, under the bound:
        g.step()                        # sweeps come and go
    assert got == [] and len(rep._pending) == 1 and not rep.inbox
    gated.made[0].ready = True
    g.step()
    assert _seqs(got) == [1] and not rep._pending and len(got[0]) == 64
    assert gated.blocked_on == []
    _finish(g, stream)
    assert got[-1] is None and _seqs(got) == [1, None]


# -- (f) the counters and the span's ``waited`` ------------------------------

def test_counters_and_the_spans_waited_say_what_happened(monkeypatch, gated):
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Annotation)
    _Annotation.made = []
    g, stream, snk, got = _graph("sink_counters", defer=2)
    g.start()
    rep, = snk.replicas
    stream.send = 1
    _step_until(g, lambda: len(gated.made) == 1)
    gated.made[0].ready = True          # batch 1: found ready by a sweep
    _step_until(g, lambda: len(got) == 1)
    stream.send = 4                     # 2, 3 held; 4 makes the sink wait
    _step_until(g, lambda: len(gated.made) == 4)   # for 2
    assert _seqs(got) == [1, 2] and gated.blocked_on == [2]
    gated.made[2].ready = True          # 3 ready, 4 not, at the end
    _finish(g, stream)
    assert _seqs(got) == [1, 2, 3, 4, None]
    assert gated.blocked_on == [2, 4]

    st = g.stats()
    row, = [o for o in st["Operators"] if o["Operator_type"] == "Sink"]
    assert row["Sink_deliveries_ready"] == 2
    assert row["Sink_deliveries_waited"] == 2
    assert row["Sink_pending_max"] == 3
    d2h = [a.counts for a in _Annotation.made if a.name == "wf.sink.d2h"]
    assert [c["waited"] for c in d2h] == [0, 1, 0, 1]
    # four whole-batch copies (a batch this small is never copied by its
    # front): ``lanes`` copied = the batch's ``cap``, ``bytes`` = its lanes
    # (key 4 + value 4 + ts 8 + valid 1 a lane), summed in the counter
    assert all("batches" not in c and c["lanes"] == c["cap"] == 64
               and c["bytes"] == 64 * 17 for c in d2h)
    assert st["Bytes_D2H_total"] == sum(c["bytes"] for c in d2h)
    assert row["Sink_front_copies"] == row["Sink_front_overflows"] == 0
    assert [c["batch"] for c in d2h] == sorted(c["batch"] for c in d2h)
    rows = [a.counts["rows"] for a in _Annotation.made
            if a.name == "wf.sink.deliver"]
    assert rows == [64] * 4
    fams = parse_exposition(render_openmetrics(st))
    by = {labels["outcome"]: value for _n, labels, value
          in fams["wf_operator_sink_deliveries_total"]["samples"]}
    assert by == {"ready": 2, "waited": 2}
    (_n, _l, most), = fams["wf_operator_sink_pending_max"]["samples"]
    assert most == 3
    for name in ("front_copies", "front_overflows"):
        (_n, _l, v), = fams[f"wf_operator_sink_{name}_total"]["samples"]
        assert v == 0


def test_a_record_sink_has_no_columnar_counters_and_is_not_polled():
    got = []
    src = (wf.Source_Builder(lambda: iter(
        {"key": i % 4, "value": np.float32(i)} for i in range(64)))
        .withOutputBatchSize(32).build())
    g = wf.PipeGraph("record_sink")
    g.add_source(src).add(wf.MapTPU_Builder(lambda t: t).build()) \
        .add_sink(wf.Sink_Builder(got.append).build())
    g.run()
    assert len(got) == 65 and got[-1] is None
    assert g._columnar_sinks == []
    row, = [o for o in g.stats()["Operators"]
            if o["Operator_type"] == "Sink"]
    assert not any(k.startswith("Sink_") for k in row)


# -- (h) an idle sink costs the sweep one attribute check --------------------

def test_a_sink_that_holds_nothing_costs_the_sweep_one_attribute_check():
    g, stream, snk, got = _graph("sink_idle_cost")
    g.start()
    stream.send = 2
    _step_until(g, lambda: sum(len(c) for c in got) == 128)
    _finish(g, stream)
    rep, = g._columnar_sinks
    assert rep._pending == []
    called = []
    rep.oldest_ready = lambda: called.append(1)     # never reached
    t0 = time.perf_counter()
    for _ in range(10_000):
        for r in g._columnar_sinks:                 # the hook's whole cost
            if r._pending and r.oldest_ready():
                raise AssertionError("an idle sink was drained")
    per_sweep = (time.perf_counter() - t0) / 10_000
    assert not called
    assert per_sweep < 5e-6, \
        f"the idle sink's check costs {per_sweep * 1e6:.2f}us/sweep"


# -- a sink drained by the host pool -----------------------------------------

def test_a_pooled_columnar_sink_delivers_every_row_in_order(gated):
    g, stream, snk, got = _graph("sink_pooled", host_worker_threads=2)
    g.start()
    rep, = snk.replicas
    assert rep in g._pool_replicas and g._columnar_sinks == [rep]
    stream.send = 3
    _step_until(g, lambda: len(gated.made) == 3)
    assert _seqs(got) == [1] and gated.blocked_on == [1]
    for e in gated.made:
        e.ready = True
    g.step()                            # the hook, after the pool's barrier
    assert _seqs(got) == [1, 2, 3]
    _finish(g, stream)
    assert _seqs(got) == [1, 2, 3, None]
