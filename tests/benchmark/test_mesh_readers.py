"""The readers of the four-chip cell's own per-layer metrics, each on a
hand-made reduction whose answer is known, and on a one-chip trace, where
there is no mesh to read.  No device, and nothing is timed."""

import os
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness, trace_reduce  # noqa: E402

RECORDED = os.path.join(ROOT, "benchmark", "testdata")   # one chip, PR 24
S = 1e9


def reader(name):
    return harness.load_module("layer_metrics", name)


def plane(chip, busy_s, ops):
    """One chip's plane: one module event of ``busy_s`` seconds from 0 and
    the operations ``[(name, seconds)]`` laid end to end inside it."""
    events, at = [], 0.0
    for name, secs in ops:
        events.append((name, at * S, (at + secs) * S, {}))
        at += secs
    return (f"/device:TPU:{chip}",
            [(trace_reduce.MODULES_LINE,
              [("jit_local(1)", 0.0, busy_s * S, {})]),
             (trace_reduce.OPS_LINE, events)])


FUSION = "%fusion.7 = f32[8]{0} fusion(%p), kind=kLoop"
GATHER = ("%all-gather.3 = f32[32]{0} all-gather(%p), replica_groups={}, "
          "dimensions={0}")
START = "%all-reduce-start.1 = f32[8]{0} all-reduce-start(%p), to_apply=%add"
DONE = "%all-reduce-done.1 = f32[8]{0} all-reduce-done(%all-reduce-start.1)"


def four_chips():
    """Busy 1, 1, 1, 2 s; a 0.4 s all-gather on chip 1 and the two halves
    of an asynchronous all-reduce, 0.1 + 0.1 s, on chip 3: collective
    operations on two of the four planes."""
    return trace_reduce.reduce_planes([
        plane(0, 1.0, [(FUSION, 1.0)]),
        plane(1, 1.0, [(FUSION, 0.6), (GATHER, 0.4)]),
        plane(2, 1.0, [(FUSION, 1.0)]),
        plane(3, 2.0, [(FUSION, 1.8), (START, 0.1), (DONE, 0.1)]),
    ])


def one_chip():
    return trace_reduce.reduce_planes([plane(0, 1.0, [(FUSION, 0.5),
                                                      (GATHER, 0.5)])])


@pytest.mark.parametrize("name,trace,expect", [
    # 0.6 s of collectives over the four chips / 5 s busy over the four
    ("collective_dev_share.sat", four_chips, 100 * 0.6 / 5.0),
    # (2 - 1) / mean(1, 1, 1, 2)
    ("chip_busy_skew.sat", four_chips, 100 * 1.0 / 1.25),
    ("collective_dev_share.sat", one_chip, None),
    ("chip_busy_skew.sat", one_chip, None),
    ("collective_dev_share.sat", lambda: None, None),
    ("chip_busy_skew.sat", lambda: None, None),
])
def test_device_readers_on_a_hand_made_reduction(name, trace, expect):
    got = reader(name).read(trace(), {}, {})
    assert got is None if expect is None else got == pytest.approx(expect)


@pytest.mark.parametrize("opcode,collective", [
    ("all-gather", True), ("all-reduce", True), ("all-to-all", True),
    ("reduce-scatter", True), ("collective-permute", True),
    ("all-gather-start", True), ("collective-permute-done", True),
    ("fusion", False), ("scatter", False), ("custom-call", False),
    ("dynamic-update-slice", False)])
def test_collective_is_told_by_the_opcode(opcode, collective):
    m = reader("collective_dev_share.sat")
    name = trace_reduce.short_op(f"%x.1 = f32[8]{{0}} {opcode}(%p)")
    assert name == f"x.1 {opcode}"
    assert m.is_collective(name) is collective


def balanced_mesh():
    return trace_reduce.reduce_planes(
        [plane(i, 1.0, [(FUSION, 1.0)]) for i in range(4)])


def test_a_mesh_without_collectives_reads_zero_not_nothing():
    assert reader("collective_dev_share.sat").read(balanced_mesh(), {}, {}) \
        == 0.0
    assert reader("chip_busy_skew.sat").read(balanced_mesh(), {}, {}) == 0.0


MESH_SPANS = [{"batch": 1, "n": 1024, "cap": 1024, "bytes": 4 * 17408,
               "logical": 17408, "shards": 4},
              {"batch": 2, "n": 256, "cap": 1024, "bytes": 4 * 17408,
               "logical": 17408, "shards": 4}]
PACKED_SPANS = [{"batch": 1, "bytes": 16388}, {"batch": 2, "bytes": 16388}]


@pytest.mark.parametrize("name,fn,spans,expect", [
    ("ingest_replication.sat", "replication", MESH_SPANS, 4.0),
    ("ingest_replication.sat", "replication", PACKED_SPANS, None),
    ("ingest_replication.sat", "replication",
     MESH_SPANS + PACKED_SPANS, 4.0),
    ("ingest_replication.sat", "replication",
     [dict(MESH_SPANS[0], bytes=17408)], 1.0),      # a chip its keys only
    ("unpacked_batch_fill_share.sat", "fill", MESH_SPANS,
     100 * 1280 / 2048),
    ("unpacked_batch_fill_share.sat", "fill", PACKED_SPANS, None),
])
def test_span_readers_on_hand_made_spans(name, fn, spans, expect):
    got = getattr(reader(name), fn)(spans)
    assert got is None if expect is None else got == pytest.approx(expect)


@pytest.mark.parametrize("name", ["ingest_replication.sat",
                                  "unpacked_batch_fill_share.sat"])
def test_span_readers_find_nothing_in_a_one_chip_trace(name):
    """The recorded trace is ``ffat_sum.steady``'s: its ``wf.h2d`` spans are
    the packed transfer's and carry ``bytes`` alone."""
    window = {"trace_dir": RECORDED, "trace0": {"pulled": 0}}
    spans = reader("ingest_replication.sat").h2d_spans(window)
    assert len(spans) > 0 and all("bytes" in s for s in spans)
    assert reader(name).read({"devices": {0: {}}}, {}, window) is None
    # an untraced run, and a run whose trace was not written
    assert reader(name).read(None, {}, {"trace_dir": None}) is None
    assert reader(name).read({"devices": {0: {}}}, {},
                             {"trace_dir": os.path.join(RECORDED, "none"),
                              "trace0": {"pulled": 0}}) is None
