"""``benchmark/program_spans.py``: from the program's ``wf.*`` spans in a
profiler trace to host time per layer and the labelled idle gaps of chip 0.
Hand-made traces for the arithmetic, the recorded one for the names; no
device, and nothing is timed."""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness, program_spans as ps  # noqa: E402

RECORDED = os.path.join(ROOT, "benchmark", "testdata", "spans.xplane.pb")
EXPECT = os.path.join(ROOT, "benchmark", "testdata", "spans.expect.json")
NEW = ["parse_host_ms_per_batch", "pack_host_ms_per_batch",
       "encode_host_ms_per_batch", "h2d_host_ms_per_batch",
       "unpack_dispatch_host_ms_per_batch",
       "step_dispatch_host_ms_per_batch", "sink_host_ms_per_batch",
       "sweep_self_ms_per_batch", "batch_fill_share",
       "idle_unattributed_share"]
S = 1e9                                     # the traces below are in seconds


def ev(name, a, b, **stats):
    return (name, a * S, b * S, stats)


def trace(lines, busy, lo, hi):
    return {"lines": lines, "t_lo": lo * S, "t_hi": hi * S,
            "busy": np.array(busy, np.float64).reshape(-1, 2) * S}


def one_sweep():
    """One sweep of 10 s on the driver thread: a tick (1-6) holding a
    parse (1-3) and a pack (3-6) whose encode (4-5) holds nothing, then a
    drain (6-9) with an unpack dispatch that compiled."""
    return [ev("wf.sweep", 0, 10, sweep=1),
            ev("wf.source.tick", 1, 6),
            ev("wf.parse", 1, 3, n=100, bytes=2400),
            ev("wf.pack", 3, 6, n=100),
            ev("wf.wire.encode", 4, 5, batch=7, n=100, cap=128, bytes=900,
               logical=1600),
            ev("wf.drain", 6, 9, op="ffat"),
            ev("wf.dispatch", 6.5, 8.5, op="staging.unpack", batch=7),
            ev("wf.compile", 7, 8, op="staging.unpack")]


def test_nesting_and_self_time_from_the_times_alone():
    sp = ps.analyse(trace([one_sweep()], [], 0, 10))
    self_s = {ps.show(k): v for k, v in sp["self_s"].items()}
    assert self_s == pytest.approx({
        "wf.sweep": 1 + 1, "wf.parse": 2,     # (the tick: none of its own)
        "wf.pack": 2, "wf.wire.encode": 1, "wf.drain op=ffat": 0.5 + 0.5,
        "wf.dispatch op=staging.unpack": 0.5 + 0.5,
        "wf.compile op=staging.unpack": 1})
    # self times telescope to the sweep, totals are plain durations
    assert sum(self_s.values()) == pytest.approx(10)
    assert sp["driver_self_s"] == pytest.approx(10)
    assert sp["total_s"][("wf.pack", None)] == pytest.approx(3)
    assert sp["count"][("wf.sweep", None)] == 1
    assert sp["sweep_cover"] == pytest.approx(1.0)
    assert sp["fill"] == (100, 128)


def test_a_gap_is_shared_out_by_overlap_not_by_midpoint():
    """The chip is busy 0-2.5 and 5.5-10: the gap 2.5-5.5 straddles the
    parse (to 3), the pack's own time (3-4, 5-5.5) and the encode (4-5).
    Its midpoint lies in the encode; by overlap each gets its part."""
    sp = ps.analyse(trace([one_sweep()], [(0, 2.5), (5.5, 10)], 0, 10))
    assert sp["idle_s"] == pytest.approx(3)
    assert {ps.show(k): v for k, v in sp["gaps_s"].items()} \
        == pytest.approx({"wf.parse": 0.5, "wf.pack": 1.5,
                          "wf.wire.encode": 1.0})
    assert sp["unattributed_s"] == pytest.approx(0)


def test_what_no_span_covers_is_unattributed():
    """Traced span 0-12 with the sweep ending at 10 and the chip idle all
    along: the last two seconds are the benchmark's own loop."""
    sp = ps.analyse(trace([one_sweep()], [], 0, 12))
    assert sp["idle_s"] == pytest.approx(12)
    assert sp["unattributed_s"] == pytest.approx(2)
    assert sum(sp["gaps_s"].values()) == pytest.approx(10)
    assert sp["sweep_cover"] == pytest.approx(10 / 12)


@pytest.mark.parametrize("child_first", [False, True])
def test_a_child_covering_its_parent_takes_the_self_time_once(child_first):
    """A pack and the encode inside it with the same start and end to the
    nanosecond: the stretch is counted once, for the one the trace lists
    later; one that also ends a little after its parent hides no more
    than the parent's stretch."""
    pair = [ev("wf.wire.encode", 3, 6, n=1, cap=1),
            ev("wf.pack", 3, 6, n=1)]
    line = [ev("wf.sweep", 0, 10, sweep=1)] \
        + (pair if child_first else pair[::-1])
    sp = ps.analyse(trace([line], [], 0, 10))
    inner = pair[-1][0] if child_first else pair[0][0]
    outer = pair[0][0] if child_first else pair[-1][0]
    assert sp["self_s"][(inner, None)] == pytest.approx(3)
    assert sp["self_s"].get((outer, None), 0) == pytest.approx(0)
    assert sum(sp["self_s"].values()) == pytest.approx(10)
    late = [ev("wf.sweep", 0, 10, sweep=1), ev("wf.pack", 3, 6, n=1),
            ev("wf.wire.encode", 4, 6.001, n=1, cap=1)]
    sp = ps.analyse(trace([late], [], 0, 10))
    assert sp["self_s"][("wf.pack", None)] == pytest.approx(1)
    assert sp["self_s"][("wf.wire.encode", None)] == pytest.approx(2.001)
    assert sum(sp["self_s"].values()) == pytest.approx(10)


def test_a_pool_threads_span_names_the_drivers_wait():
    """The driver's sweep waits 6-9 for a replica drained on a pool
    thread (its own line): the gap inside is the pool span's; the
    driver's own spans keep what they cover."""
    driver = [ev("wf.sweep", 0, 10, sweep=1),
              ev("wf.source.tick", 1, 6), ev("wf.parse", 1, 6, n=1, bytes=1)]
    pool = [ev("wf.drain", 6.5, 8.5, op="hostmap")]
    sp = ps.analyse(trace([pool, driver], [(0, 5), (9.5, 10)], 0, 10))
    assert {ps.show(k): v for k, v in sp["gaps_s"].items()} \
        == pytest.approx({"wf.parse": 1.0, "wf.drain op=hostmap": 2.0,
                          "wf.sweep": 0.5 + 1.0})
    assert sp["unattributed_s"] == pytest.approx(0)
    # both threads' self times are the layers'; the driver's telescope
    assert sp["self_s"][("wf.drain", "hostmap")] == pytest.approx(2)
    assert sp["driver_self_s"] == pytest.approx(10)


def test_the_generators_wait_spans_sweeps_and_takes_their_own_time():
    """``generator.idle`` opens inside one tick and closes inside a later
    one.  The spinning sweeps' and ticks' own time under it is waiting;
    real work under it (a drain) keeps its name."""
    line = [ev("wf.sweep", 0, 10, sweep=1), ev("wf.source.tick", 1, 4),
            ev("generator.idle", 2, 25),
            ev("wf.drain", 5, 9, op="ffat"),
            ev("wf.sweep", 11, 20, sweep=2), ev("wf.source.tick", 12, 13),
            ev("wf.sweep", 21, 30, sweep=3), ev("wf.source.tick", 22, 29),
            ev("source.pull", 25.5, 28)]
    sp = ps.analyse(trace([line], [], 0, 30))
    assert {ps.show(k): v for k, v in sp["self_s"].items()} \
        == pytest.approx({
            "wf.sweep": 1 + 1, "wf.source.tick": 1 + 0.5 + 1,
            "generator.idle": (5 - 2) + (25 - 9), "wf.drain op=ffat": 4,
            "source.pull": 2.5})
    assert sp["unattributed_s"] == pytest.approx(0)


def test_a_program_without_spans_gives_nothing():
    line = [ev("source.pull", 0, 1), ev("sink.callback", 2, 3)]
    assert ps.analyse(trace([line], [(0, 3)], 0, 3)) is None
    assert ps.analyse(trace([], [(0, 3)], 0, 3)) is None


def window(tmp_path, traced=True):
    d = str(tmp_path / "trace")
    return {"trace_dir": d, "batch": 100,
            "trace0": {"pulled": 0} if traced else None,
            "trace1": {"pulled": 200} if traced else None,
            "open": {"t": 0}, "close": {"t": 1}}


@pytest.mark.parametrize("suffix,cell", [(".sat", "ysb.saturated"),
                                         (".steady", "ffat_sum.steady")])
def test_readers_resolve_by_name_and_read_the_spans(tmp_path, monkeypatch,
                                                    suffix, cell):
    c = harness.resolve_cell(cell)
    entries = [m for m in c["per_layer"] if m["source"] == "program_span"]
    assert [m["name"] for m in entries] == [n + suffix for n in NEW]
    w = window(tmp_path)
    monkeypatch.setitem(ps._loaded, w["trace_dir"], ps.analyse(
        trace([one_sweep()], [(0, 2.5), (5.5, 11)], 0, 12)))
    got = {k: v["value"] for k, v in harness.read_metrics(
        c, entries, "layer_metrics", None, w).items()}
    # two batches' worth pulled in the traced span: ms per batch
    assert got == pytest.approx({
        "parse_host_ms_per_batch" + suffix: 1000.0,
        "pack_host_ms_per_batch" + suffix: 1000.0,
        "encode_host_ms_per_batch" + suffix: 500.0,
        "h2d_host_ms_per_batch" + suffix: 0.0,
        "unpack_dispatch_host_ms_per_batch" + suffix: 1000.0,
        "step_dispatch_host_ms_per_batch" + suffix: 0.0,
        "sink_host_ms_per_batch" + suffix: 0.0,
        "sweep_self_ms_per_batch" + suffix: 1500.0,
        "batch_fill_share" + suffix: 100 * 100 / 128,
        "idle_unattributed_share" + suffix: 100 * 1 / 4})


@pytest.mark.parametrize("suffix,cell", [(".sat", "ysb.saturated"),
                                         (".steady", "ffat_sum.steady")])
def test_readers_return_none_untraced_and_for_a_spanless_program(
        tmp_path, monkeypatch, suffix, cell):
    c = harness.resolve_cell(cell)
    entries = [m for m in c["per_layer"] if m["source"] == "program_span"]
    # untraced: no trace directory is even looked at
    assert harness.read_metrics(c, entries, "layer_metrics", None,
                                window(tmp_path, traced=False)) == {}
    # traced, but no .xplane.pb was written
    assert harness.read_metrics(c, entries, "layer_metrics", None,
                                window(tmp_path)) == {}
    # traced on a program that has no spans (the parent commit)
    w = window(tmp_path / "parent")
    monkeypatch.setitem(ps._loaded, w["trace_dir"], None)
    assert harness.read_metrics(c, entries, "layer_metrics", None, w) == {}


@pytest.fixture(scope="module")
def recorded():
    return ps.analyse(ps.read_trace(RECORDED))


def test_recorded_trace_holds_every_span_the_cell_reaches(recorded):
    with open(EXPECT) as f:
        expect = json.load(f)
    names = {k[0] for k in recorded["count"]}
    assert set(expect["spans"]) <= names
    assert {"wf.sweep", "wf.source.tick", "wf.parse", "wf.pack",
            "wf.wire.encode", "wf.h2d", "wf.dispatch", "wf.drain",
            "wf.sink.d2h", "wf.sink.deliver"} <= names
    ops = {k[1] for k in recorded["count"] if k[0] == "wf.dispatch"}
    assert set(expect["dispatch_ops"]) == ops and "staging.unpack" in ops
    n, cap = recorded["fill"]
    assert (n, cap) == tuple(expect["fill"]) and 0 < n <= cap
    assert recorded["idle_s"] == pytest.approx(expect["idle_s"])
    assert recorded["unattributed_s"] == pytest.approx(
        expect["unattributed_s"], abs=1e-9)
    assert recorded["sweep_cover"] > 0.5
    # the driver thread's self times sum to the time it spent in spans
    assert recorded["driver_self_s"] <= recorded["span_s"] + 1e-6
    assert "wf.sweep" in ps.tables(recorded)


def test_recorded_trace_through_the_readers(recorded, tmp_path,
                                            monkeypatch):
    c = harness.resolve_cell("ffat_sum.steady")
    entries = [m for m in c["per_layer"] if m["source"] == "program_span"]
    w = window(tmp_path)
    monkeypatch.setitem(ps._loaded, w["trace_dir"], recorded)
    got = harness.read_metrics(c, entries, "layer_metrics", None, w)
    assert set(got) == {n + ".steady" for n in NEW}
    assert 0 < got["batch_fill_share.steady"]["value"] <= 100
    assert 0 <= got["idle_unattributed_share.steady"]["value"] <= 100
    assert all(v["value"] >= 0 for v in got.values())
