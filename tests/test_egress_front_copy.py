"""The packed egress's front copy (batch.py ``ColumnarEgress(batch, front)``,
ops/sink.py ``SinkReplica._front_lanes``; docs/OBSERVABILITY.md "wf.sink.d2h"):
a columnar sink copies the leading lanes of an output batch that its own
deliveries say hold the rows, and the batch's header, not the guess, decides
whether that was enough.  Whatever the guess, the rows delivered are the
whole-batch path's, in receipt order, each batch under its own watermark.

Everything runs on the CPU backend; nothing here is timed."""

import dataclasses
import struct

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import windflow_tpu as wf
from test_layer_spans import _Annotation  # (the fake capture)
from windflow_tpu import batch as wfbatch
from windflow_tpu.basic import default_config
from windflow_tpu.batch import (FRONT_MIN_LANES, ColumnarEgress, DeviceBatch,
                                front_lanes)
from windflow_tpu.io import FrameSource
from windflow_tpu.monitoring.openmetrics import (parse_exposition,
                                                 render_openmetrics)
from windflow_tpu.ops.sink import FRONT_HISTORY, Sink, SinkReplica

CAP = 32768
FRONT = 8192

#: every kind of leaf ``_egress_pack`` takes: a validity-like flag, the
#: 32-bit bitcasts, the 64-bit word pairs, and a small record a row
LEAVES = {
    "bool": (np.bool_, ()),
    "int32": (np.int32, ()),
    "uint32": (np.uint32, ()),
    "float32": (np.float32, ()),
    "int64": (np.int64, ()),
    "uint64": (np.uint64, ()),
    "int32x3": (np.int32, (3,)),
    "int64x2": (np.int64, (2,)),
}

#: where a batch's rows lie: (lanes that are valid, of CAP)
LAYOUTS = {
    "prefix": np.arange(3000),
    # what a filter leaves behind: not a prefix, but inside the front
    "scattered": np.arange(1, FRONT, 7),
    "up_to_the_last_front_lane": np.array([0, 17, FRONT - 1]),
    "empty": np.arange(0),
}


def _values(dtype, trail, cap):
    """A value a lane and word that says which lane it is, the high word
    of a 64-bit lane and the sign included."""
    lane = np.arange(cap * int(np.prod(trail, dtype=np.int64)),
                     dtype=np.int64).reshape((cap,) + trail)
    d = np.dtype(dtype)
    if d == np.bool_:
        return lane % 3 == 0
    if d.kind == "f":
        return (lane * 0.25 - 1000.0).astype(d)
    if d.itemsize == 8:
        wide = (lane << 33) + lane - (1 << 40)
        return wide.astype(d)           # uint64: wraps, as the lane would
    return (lane * 7 - 50_000).astype(d)


def _batch(valid_lanes, leaf="int32", cap=CAP, seq=0, size=None):
    dtype, trail = LEAVES[leaf]
    valid = np.zeros(cap, bool)
    valid[valid_lanes] = True
    return DeviceBatch(
        {"key": jnp.asarray(np.arange(cap, dtype=np.int32)),
         "value": jnp.asarray(_values(dtype, trail, cap))},
        jnp.asarray(1_000_000 * seq + np.arange(cap, dtype=np.int64)),
        jnp.asarray(valid), watermark=1000 * seq, size=size, seq=seq)


def _same(a, b):
    (cols_a, tss_a), (cols_b, tss_b) = a, b
    assert cols_a.keys() == cols_b.keys()
    for name in cols_a:
        assert cols_a[name].dtype == cols_b[name].dtype
        np.testing.assert_array_equal(cols_a[name], cols_b[name])
    assert tss_a.dtype == tss_b.dtype == np.int64
    np.testing.assert_array_equal(tss_a, tss_b)


# -- the egress: front and whole give the same columns -----------------------

@pytest.mark.parametrize("layout", LAYOUTS)
@pytest.mark.parametrize("leaf", LEAVES)
def test_front_and_whole_paths_give_identical_columns(leaf, layout):
    lanes = LAYOUTS[layout]
    b = _batch(lanes, leaf)
    whole, front = ColumnarEgress(b), ColumnarEgress(b, FRONT)
    assert whole.front is None and front.front == FRONT
    assert front._packed[0].shape[0] * CAP \
        == (whole._packed[0].shape[0]) * FRONT + 2 * CAP    # + the header
    got = front.columns()
    _same(got, whole.columns())
    # ... and they are the rows the batch holds, by the plain definition
    dtype, trail = LEAVES[leaf]
    np.testing.assert_array_equal(got[0]["key"], lanes)
    np.testing.assert_array_equal(got[0]["value"],
                                  _values(dtype, trail, CAP)[lanes])
    np.testing.assert_array_equal(got[1], lanes)
    extent = int(lanes[-1]) + 1 if len(lanes) else 0
    assert front.extent == whole.extent == extent
    assert not front.overflowed and not whole.overflowed
    assert (front.lanes_copied, whole.lanes_copied) == (FRONT, CAP)


@pytest.mark.parametrize("size", [None, 3000])
def test_a_known_size_changes_nothing(size):
    """(A staged batch knows its row count; an operator's output does
    not.  The front path reads the count from its header either way.)"""
    b = _batch(LAYOUTS["prefix"], "int64", size=size)
    _same(ColumnarEgress(b, FRONT).columns(), ColumnarEgress(b).columns())


@pytest.mark.parametrize("leaf", ["int32", "int64x2", "bool"])
@pytest.mark.parametrize("lanes", [
    np.arange(FRONT + 1),                       # a prefix one lane too long
    np.array([5, FRONT]),                       # one row just beyond
    np.concatenate([np.arange(100), [CAP - 1]]),    # ... at the far end
], ids=["prefix", "just_beyond", "far_end"])
def test_an_overflow_fetches_the_whole_batch(leaf, lanes):
    b = _batch(lanes, leaf)
    front = ColumnarEgress(b, FRONT)
    got = front.columns()
    _same(got, ColumnarEgress(b).columns())
    np.testing.assert_array_equal(got[0]["key"], lanes)
    assert front.overflowed and front.extent == lanes[-1] + 1
    assert front.lanes_copied == FRONT + CAP
    assert front.columns()[1].shape == got[1].shape     # asked again:
    assert front.lanes_copied == FRONT + CAP            # counted once


def test_a_batch_that_cannot_be_packed_never_takes_the_front_path():
    # float64 lanes are not packable (staging.packable_dtype), nor are
    # lanes already on the host: both take the fallback, whole
    wide = DeviceBatch({"value": jnp.arange(CAP, dtype=jnp.float64) / 3},
                       jnp.arange(CAP, dtype=jnp.int64),
                       jnp.arange(CAP) < 100)
    host = DeviceBatch({"value": np.arange(CAP, dtype=np.int32)},
                       np.arange(CAP, dtype=np.int64), np.arange(CAP) < 100)
    for b in (wide, host):
        e = ColumnarEgress(b, FRONT)
        assert e._packed is None and e.front is None
        cols, tss = e.columns()
        assert len(tss) == 100 and e.extent is None
        assert e.lanes_copied == CAP and not e.overflowed


def test_lanes_spread_over_devices_never_take_the_front_path():
    """The mesh step's output: a slice of its front would be a collective."""
    devs = jax.devices()
    if len(devs) < 2:
        pytest.skip("one device")
    mesh = jax.sharding.Mesh(np.array(devs[:2]), ("key",))
    spread = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec("key"))
    b = _batch(LAYOUTS["prefix"])
    b = DeviceBatch(jax.device_put(b.payload, spread),
                    jax.device_put(b.ts, spread),
                    jax.device_put(b.valid, spread))
    e = ColumnarEgress(b, FRONT)
    assert e._packed is not None and e.front is None
    _same(e.columns(), ColumnarEgress(_batch(LAYOUTS["prefix"])).columns())
    assert e.lanes_copied == CAP


def test_callers_that_name_no_front_stay_whole():
    b = _batch(LAYOUTS["prefix"])
    before = set(wfbatch._EGRESS_PACK_CACHE)
    _same(wfbatch.device_to_columns(b), ColumnarEgress(b).columns())
    (out,) = wfbatch.device_to_columns_multi([b])
    _same(out, ColumnarEgress(b).columns())
    assert all(k[3] is None for k in set(wfbatch._EGRESS_PACK_CACHE)
               - before)


# -- the rule that sizes the front -------------------------------------------

@pytest.mark.parametrize("cap, extent, lanes", [
    (262144, 5300, 16384), (262144, 6600, 16384), (262144, 0, 4096),
    (262144, 2048, 4096), (262144, 2049, 8192), (262144, 8192, 16384),
    (262144, 8193, 32768), (262144, 65536, 131072), (262144, 65537, None),
    # twice the extent does not fit half the batch: whole
    (65536, 47900, None), (32768, 10100, None),
    # a batch at or under twice the floor is never worth a second program
    (8192, 0, None), (2 * FRONT_MIN_LANES, 10, None), (1024, 0, None),
    (16384, 0, 4096), (16384, 2049, 8192), (16384, 4097, None),
    # any capacity, not only powers of two
    (1179648, 100, 4608), (100000, 3000, 6250),
])
def test_front_lanes(cap, extent, lanes):
    assert front_lanes(cap, extent) == lanes
    if lanes is not None:
        assert lanes >= max(FRONT_MIN_LANES, 2 * extent) and lanes <= cap // 2
        assert lanes // 2 < max(FRONT_MIN_LANES, 2 * extent)   # the smallest


# -- the sink: what it delivered sizes the next copy -------------------------

class Watched(ColumnarEgress):
    """The real egress; the test looks at every one the sink made, and
    may have the device say "not yet" (``hold``) so that batches stay in
    flight up to the sink's bound, as behind a busy chip."""

    made: list = []
    hold = False

    def __init__(self, b, front=None):
        super().__init__(b, front)
        Watched.made.append(self)

    def is_ready(self):
        return not Watched.hold and super().is_ready()


@pytest.fixture
def watched(monkeypatch):
    monkeypatch.setattr(wfbatch, "ColumnarEgress", Watched)
    Watched.made, Watched.hold = [], False
    return Watched


def _sink(defer=0):
    got = []
    op = Sink(got.append, columnar=True, columnar_defer=defer)
    rep = SinkReplica(op, 0)
    op.replicas = [rep]
    return op, rep, got


def _feed(rep, extents, cap=CAP, seq0=1, scattered=False):
    for i, extent in enumerate(extents):
        lanes = np.arange(extent)
        if scattered and extent:
            lanes = np.unique(np.concatenate([lanes[::3], [extent - 1]]))
        rep.process_device_batch(_batch(lanes, cap=cap, seq=seq0 + i))


def _fronts(egresses):
    return [e.front for e in egresses]


def test_the_first_deliveries_go_whole_then_the_front_engages(watched):
    op, rep, got = _sink()
    _feed(rep, [1000] * (FRONT_HISTORY + 3))
    assert _fronts(watched.made) == [None] * FRONT_HISTORY + [4096] * 3
    assert all(len(c) == 1000 for c in got)
    st = op.dump_stats()
    assert st["Sink_front_copies"] == 3 and st["Sink_front_overflows"] == 0
    assert rep.stats.d2h_bytes == (FRONT_HISTORY * CAP + 3 * 4096) * 17


def test_deliveries_in_flight_do_not_count_until_they_are_delivered(watched):
    """The guess is made at receipt from what HAS been delivered: with
    ``defer`` batches in flight the front engages that much later."""
    watched.hold = True
    op, rep, got = _sink(defer=2)
    _feed(rep, [1000] * (FRONT_HISTORY + 4))
    rep.on_eos()
    assert _fronts(watched.made) == [None] * (FRONT_HISTORY + 2) + [4096] * 2
    assert [len(c) for c in got[:-1]] == [1000] * (FRONT_HISTORY + 4)


@pytest.mark.parametrize("cap", [1024, 8192])
def test_a_batch_of_8192_lanes_or_fewer_always_goes_whole(watched, cap):
    op, rep, got = _sink()
    _feed(rep, [10] * (FRONT_HISTORY + 4), cap=cap)
    assert _fronts(watched.made) == [None] * (FRONT_HISTORY + 4)
    assert op.dump_stats()["Sink_front_copies"] == 0
    assert rep.stats.d2h_bytes == (FRONT_HISTORY + 4) * cap * 17


def test_the_front_grows_after_one_overflow_and_shrinks_after_eight_fits(
        watched):
    op, rep, got = _sink()
    cap = 65536
    _feed(rep, [1000] * FRONT_HISTORY, cap=cap)
    _feed(rep, [1000, 5000], cap=cap, seq0=20)      # fits; overflows 4096
    assert _fronts(watched.made[-2:]) == [4096, 4096]
    assert [e.overflowed for e in watched.made[-2:]] == [False, True]
    # at once: the next copy holds twice the longer extent ...
    _feed(rep, [1000] * (FRONT_HISTORY - 1), cap=cap, seq0=30)
    assert _fronts(watched.made[-7:]) == [16384] * 7
    # ... and narrows only when all eight looked back on fit the narrower
    _feed(rep, [1000, 1000], cap=cap, seq0=40)
    assert _fronts(watched.made[-2:]) == [16384, 4096]
    # headroom: an extent that fits but leaves less than as much again
    # widens the next copy with no overflow
    _feed(rep, [3000, 1000], cap=cap, seq0=50)
    assert _fronts(watched.made[-2:]) == [4096, 8192]
    assert not any(e.overflowed for e in watched.made[-4:])
    # an extent that no half of the batch holds twice: whole
    _feed(rep, [20000, 1000], cap=cap, seq0=60)
    assert _fronts(watched.made[-2:]) == [8192, None]
    st = op.dump_stats()
    assert st["Sink_front_overflows"] == 2
    assert st["Sink_front_copies"] == sum(e.front is not None
                                          for e in watched.made)
    assert [len(c) for c in got] == [
        int(e.batch.valid.sum()) for e in watched.made]


def test_an_overflow_is_delivered_whole_in_receipt_order(watched):
    watched.hold = True
    op, rep, got = _sink(defer=3)
    _feed(rep, [500] * FRONT_HISTORY)
    rep.deliver(keep=0)
    del got[:]
    # three in flight at once: front, overflow, front
    _feed(rep, [700, 9000, 600], seq0=11, scattered=True)
    assert got == [] and _fronts(watched.made[-3:]) == [4096] * 3
    rep.on_eos()
    assert got[-1] is None
    a, b, c = got[:-1]
    for cols, seq, extent in ((a, 11, 700), (b, 12, 9000), (c, 13, 600)):
        lanes = np.unique(np.concatenate([np.arange(extent)[::3],
                                          [extent - 1]]))
        np.testing.assert_array_equal(cols.cols["key"], lanes)
        np.testing.assert_array_equal(cols.tss, 1_000_000 * seq + lanes)
        assert cols.watermark == 1000 * seq
    st = op.dump_stats()
    assert (st["Sink_front_copies"], st["Sink_front_overflows"]) == (3, 1)
    assert [e.lanes_copied for e in watched.made[-3:]] \
        == [4096, 4096 + CAP, 4096]


def test_an_empty_batch_delivers_nothing_and_says_nothing_of_the_rows(
        watched):
    """Where rows lie when they come is learnt from deliveries that held
    some: a window that fires all over its output grid once in many
    batches (YSB's) keeps the whole-batch copy, firing or not."""
    op, rep, got = _sink()
    _feed(rep, [0] * (FRONT_HISTORY + 2))
    assert got == [] and _fronts(watched.made) == [None] * 10
    assert watched.made[-1].extent == 0
    firing = np.arange(7, CAP, 13)
    for seq in range(FRONT_HISTORY + 2):
        _feed(rep, [0] * 5, seq0=100 * seq)
        rep.process_device_batch(_batch(firing, seq=100 * seq + 50))
    assert set(_fronts(watched.made)) == {None}
    assert [len(c) for c in got] == [len(firing)] * (FRONT_HISTORY + 2)
    assert op.dump_stats()["Sink_front_copies"] == 0
    # ... and between batches of rows at the front, empty ones change
    # nothing: their own copy is the front's too
    op, rep, got = _sink()
    _feed(rep, [1000] * FRONT_HISTORY + [0] * 20 + [1000, 0])
    assert _fronts(watched.made[-22:]) == [4096] * 22
    assert op.dump_stats()["Sink_front_overflows"] == 0


def test_each_replica_looks_back_on_its_own_deliveries(watched):
    op = Sink(lambda c: None, columnar=True, columnar_defer=0)
    op.replicas = [SinkReplica(op, 0), SinkReplica(op, 1)]
    _feed(op.replicas[0], [100] * (FRONT_HISTORY + 1))
    _feed(op.replicas[1], [100] * 2)
    assert _fronts(watched.made[-3:]) == [4096, None, None]
    assert op.dump_stats()["Sink_front_copies"] == 1


# -- through a graph: the counters and the span say what crossed the link ----

class _Stream:
    """Chunks of as many records as the test says (``send``), handed over
    one a call of ``more``; between them the source yields nothing."""

    def __init__(self):
        self.queue, self.stop, self.sent = [], False, 0

    def chunks(self):
        while not self.stop:
            if self.queue:
                n = self.queue.pop(0)
                self.sent += 1
                yield b"".join(
                    struct.pack("<qqd", i % 4, 10_000 * self.sent + i,
                                float(self.sent))
                    for i in range(n))
            else:
                yield b""


def test_counters_and_the_span_equal_what_was_copied(monkeypatch):
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Annotation)
    _Annotation.made = []
    cap = 16384
    stream = _Stream()
    src = FrameSource(stream.chunks, nv=1, fmt="frames",
                      output_batch_size=cap)
    src.record_spec = {"key": np.int32(0), "v0": np.float32(0.0)}
    got = []
    snk = wf.Sink_Builder(got.append).withColumnarSink(defer=0).build()
    # a short punctuation ships each chunk as a batch of its own, part full
    cfg = dataclasses.replace(default_config, punctuation_interval_usec=1000)
    g = wf.PipeGraph("sink_front_copy", wf.ExecutionMode.DEFAULT,
                     wf.TimePolicy.EVENT, config=cfg)
    g.add_source(src).add(wf.MapTPU_Builder(
        lambda t: {"key": t["key"], "value": t["v0"]}).build()).add_sink(snk)
    g.start()
    # eight whole, two fronts, an overflow, a front at twice its extent
    sizes = [900] * FRONT_HISTORY + [900, 900, 6000, 900]
    for i, n in enumerate(sizes):
        stream.queue.append(n)
        for _ in range(2000):
            if len(got) > i:
                break
            g.step()
        assert len(got) == i + 1 and len(got[i]) == n, (i, len(got))
    stream.stop = True
    while not g.is_done():
        g.step()
    g.wait_end()
    assert got[-1] is None and [len(c) for c in got[:-1]] == sizes
    for i, c in enumerate(got[:-1]):
        np.testing.assert_array_equal(c.cols["value"], float(i + 1))
        np.testing.assert_array_equal(c.tss,
                                      10_000 * (i + 1) + np.arange(sizes[i]))

    d2h = [a.counts for a in _Annotation.made if a.name == "wf.sink.d2h"]
    # (after the overflow, twice 6000 lanes fit no half of 16384: whole)
    copied = [cap] * FRONT_HISTORY + [4096, 4096, 4096 + cap, cap]
    assert [c["lanes"] for c in d2h] == copied
    assert all(c["cap"] == cap and "batches" not in c for c in d2h)
    assert [c["bytes"] for c in d2h] == [17 * n for n in copied]
    st = g.stats()
    assert st["Bytes_D2H_total"] == 17 * sum(copied)
    row, = [o for o in st["Operators"] if o["Operator_type"] == "Sink"]
    assert row["Sink_front_copies"] == 3 and row["Sink_front_overflows"] == 1
    rows = [a.counts["rows"] for a in _Annotation.made
            if a.name == "wf.sink.deliver"]
    assert rows == sizes
    fams = parse_exposition(render_openmetrics(st))
    (_n, _l, copies), = fams["wf_operator_sink_front_copies_total"]["samples"]
    (_n, _l, over), = fams["wf_operator_sink_front_overflows_total"]["samples"]
    assert (copies, over) == (3, 1)
