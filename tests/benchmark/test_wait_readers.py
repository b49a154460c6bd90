"""``benchmark/wait_spans.py`` and the readers over it: the driver thread's
waits for the chip apart from its work, from the program's ``wf.wait.*``
spans (PR 51).  Hand-made traces through ``program_spans.analyse`` for the
arithmetic, the recorded trace of a program before the spans for the
``None``; no device, and nothing is timed."""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness, program_spans as ps  # noqa: E402
from benchmark import wait_spans as ws  # noqa: E402

RECORDED = os.path.join(ROOT, "benchmark", "testdata", "spans.xplane.pb")
S = 1e9
READERS = ["held_wait_ms_per_batch", "d2h_wait_ms_per_batch",
           "chip_wait_ms_per_batch", "host_work_ms_per_batch",
           "idle_under_wait_share"]
#: the cells that report the readers today: those whose lists no accepted
#: test pins (``PERF.md`` section 7, follow-ups for a ``benchmark`` issue)
LISTED = ["ffat_sum_mesh4.saturated", "nexmark_q5.saturated",
          "nexmark_q11.saturated", "nexmark_q20.saturated"]


def ev(name, a, b, **stats):
    return (name, a * S, b * S, stats)


def driver_line():
    """Three sweeps of the driver thread.  The first pulls, parses and
    packs (a pool wait and an H2D inside the pack), dispatches a join
    step that reads the step before's held count, and delivers a batch
    whose copy it waits for; the second dispatches a megastep group and
    blocks in its drain, reads what a window's flush pass fired, then the
    generator has nothing due and its idle span stays open over a third,
    spinning sweep."""
    return [
        ev("wf.sweep", 0, 20, sweep=1),
        ev("wf.source.tick", 1, 6), ev("source.pull", 1, 2),
        ev("wf.parse", 2, 3, n=100, bytes=2400),
        ev("wf.pack", 3, 6, n=100), ev("wf.pool.wait", 3, 3.5),
        ev("wf.h2d", 3.5, 4, batch=7, bytes=900),
        ev("wf.drain", 6, 12, op="join"),
        ev("wf.dispatch", 6.5, 10, op="join", batch=7),
        ev("wf.wait.held", 7, 9.5, batch=7),
        ev("wf.drain", 12, 19, op="sink"),
        ev("wf.sink.d2h", 12.5, 17, batch=6, waited=0),
        ev("wf.wait.d2h", 13, 16),
        ev("wf.sink.deliver", 17, 18, batch=6, rows=10),
        ev("sink.callback", 17.2, 17.8),
        ev("wf.sweep", 20, 30, sweep=2),
        ev("wf.dispatch", 21, 22, op="megastep.cb", batch=8, k=8),
        ev("wf.megastep.drain", 22, 25, batch=8, k=8),
        ev("wf.drain", 25, 26, op="ffat"), ev("wf.wait.flush", 25.2, 25.7),
        ev("wf.source.tick", 26, 29), ev("generator.idle", 27, 40),
        ev("wf.sweep", 31, 38, sweep=3), ev("wf.source.tick", 32, 33)]


#: a replica drained on a pool thread meanwhile: its work is not the
#: driver's
POOL_LINE = [ev("wf.drain", 6, 8, op="hostmap")]
#: the chip idles 2-2.5 (under the parse) and 13.5-15.5 (under the wait
#: for the copy)
BUSY = [(0, 2), (2.5, 13.5), (15.5, 40)]


def analysis(lines, busy=BUSY):
    return ps.analyse({
        "lines": lines, "t_lo": 0.0, "t_hi": 40 * S,
        "busy": np.array(busy, np.float64).reshape(-1, 2) * S})


def window(tmp_path, traced=True):
    return {"trace_dir": str(tmp_path / "trace"), "batch": 100,
            "trace0": {"pulled": 0} if traced else None,
            "trace1": {"pulled": 200} if traced else None}


def reader(name):
    return harness.load_module("layer_metrics", name)


def read_all(monkeypatch, tmp_path, sp):
    w = window(tmp_path)
    monkeypatch.setitem(ps._loaded, w["trace_dir"], sp)
    return {n: reader(n + ".sat").read(None, {}, w) for n in READERS}


# two batches' worth pulled in the traced span: seconds / 2, in ms
EXPECT = {
    # 7-9.5 inside the join's dispatch (and not the flush's read: that
    # is no step's)
    "held_wait_ms_per_batch": 2.5 / 2 * 1e3,
    # 13-16 inside the sink's span
    "d2h_wait_ms_per_batch": 3.0 / 2 * 1e3,
    # + the pool's gate 3-3.5, the K-group's drain 22-25 and the flush
    # pass's read 25.2-25.7
    "chip_wait_ms_per_batch": (2.5 + 3.0 + 0.5 + 3.0 + 0.5) / 2 * 1e3,
    # the driver's 40 s in spans, less 13 of generator.idle and the 9.5
    # it stood blocked
    "host_work_ms_per_batch": (40 - 13 - 9.5) / 2 * 1e3,
    # 2 of the chip's 2.5 idle seconds lie under a wait
    "idle_under_wait_share": 100 * 2.0 / 2.5}


@pytest.mark.parametrize("name", READERS)
@pytest.mark.parametrize("pool", [False, True], ids=["driver", "with_pool"])
def test_reader_on_a_hand_made_trace(tmp_path, monkeypatch, name, pool):
    lines = [driver_line()] + ([POOL_LINE] if pool else [])
    got = read_all(monkeypatch, tmp_path, analysis(lines))
    assert got[name] == pytest.approx(EXPECT[name])


@pytest.mark.parametrize("pool", [False, True], ids=["driver", "with_pool"])
def test_wait_and_work_add_up_to_the_drivers_spans_less_the_idle(pool):
    """``chip_wait + host_work`` = the driver line's span time less
    ``generator.idle``, whatever a pool thread did meanwhile; and the
    work is the self time of every other name the driver holds."""
    sp = analysis([driver_line()] + ([POOL_LINE] if pool else []))
    self_s = {ps.show(k): v for k, v in sp["self_s"].items()}
    assert sp["driver_self_s"] == pytest.approx(40)
    assert self_s["generator.idle"] == pytest.approx(13)
    assert ws.wait_seconds(sp) + ws.work_seconds(sp) \
        == pytest.approx(sp["driver_self_s"] - self_s["generator.idle"])
    by_name = sum(v for k, v in self_s.items()
                  if not ws.is_wait(k.split(" ")[0])
                  and k not in ("generator.idle", "wf.drain op=hostmap"))
    assert ws.work_seconds(sp) == pytest.approx(by_name) \
        == pytest.approx(17.5)
    # the spans around the waits keep their own work alone
    assert self_s["wf.dispatch op=join"] == pytest.approx(1.0)
    assert self_s["wf.sink.d2h"] == pytest.approx(1.5)
    assert self_s["wf.pack"] == pytest.approx(2.0)


def without(line, drop):
    return [e for e in line if not drop(e[0])]


@pytest.mark.parametrize("name", READERS)
def test_a_program_that_does_not_name_its_waits_reads_none(
        tmp_path, monkeypatch, name):
    """The parent of PR 51: ``wf.pool.wait`` and ``wf.megastep.drain``
    are there, no ``wf.wait.*``: its dispatch and sink spans hold waits
    that cannot be told from work, so nothing is answered."""
    old = without(driver_line(), lambda n: n.startswith(ws.WAIT_PREFIX))
    assert ("wf.pool.wait", None) in analysis([old])["count"]
    got = read_all(monkeypatch, tmp_path, analysis([old]))
    assert got[name] is None
    # no spans at all, and an untraced run
    assert read_all(monkeypatch, tmp_path / "none", None)[name] is None
    assert reader(name + ".sat").read(
        None, {}, window(tmp_path, traced=False)) is None


@pytest.mark.parametrize("name", READERS)
def test_a_wait_that_never_opened_reads_zero_not_none(tmp_path, monkeypatch,
                                                      name):
    """A cell without a shell operator, a megastep group or a full pool:
    the program names its waits (the sink's is there at every delivery),
    the others read 0."""
    only_d2h = without(driver_line(), lambda n: ws.is_wait(n)
                       and n != ws.D2H)
    got = read_all(monkeypatch, tmp_path, analysis([only_d2h]))
    want = {"held_wait_ms_per_batch": 0.0,
            "d2h_wait_ms_per_batch": EXPECT["d2h_wait_ms_per_batch"],
            "chip_wait_ms_per_batch": EXPECT["d2h_wait_ms_per_batch"],
            "host_work_ms_per_batch": (40 - 13 - 3.0) / 2 * 1e3,
            "idle_under_wait_share": EXPECT["idle_under_wait_share"]}
    assert got[name] == pytest.approx(want[name])


@pytest.mark.parametrize("name", READERS)
def test_recorded_trace_of_a_program_before_the_waits_reads_none(
        tmp_path, monkeypatch, name):
    """``benchmark/testdata/spans.xplane.pb`` (PR 24's program): the
    accepted ``test_recorded_trace_through_the_readers`` holds the cell's
    ``program_span`` readers on it to an exact set of names."""
    sp = ps.analyse(ps.read_trace(RECORDED))
    assert sp is not None and ("wf.sink.d2h", None) in sp["count"]
    assert read_all(monkeypatch, tmp_path, sp)[name] is None


def test_the_waits_are_the_programs_vocabulary():
    from windflow_tpu.monitoring import recorder
    assert all(ws.is_wait(name) for name in recorder.WAITS)
    assert {n for n in recorder.WAITS
            if not n.startswith(ws.WAIT_PREFIX)} == set(ws.OLDER_WAITS)
    assert recorder.WAIT_PREFIX == ws.WAIT_PREFIX
    assert {ws.HELD, ws.D2H} <= set(recorder.WAITS)
    assert ps.WAITING == "generator.idle"
    # no layer span that times work is mistaken for a wait
    assert not [n for n in ("wf.sweep", "wf.dispatch", "wf.sink.d2h",
                            "wf.h2d", "wf.drain", "wf.pack", "source.pull",
                            "sink.callback", "generator.idle")
                if ws.is_wait(n)]


@pytest.mark.parametrize("name", READERS)
def test_the_manifest_has_the_reader_and_its_cells_report_it(name):
    """The entry is there with its reader's file, and the cells below
    report it.  Nothing else is held: where in ``per_layer`` it stands,
    what other cells a later PR appends to its list (or whether the list
    goes, as ISSUE 51 wanted: ``PERF.md`` section 7) and what ``.steady``
    twins a ``benchmark`` PR adds are theirs to choose."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    entry, = [e for e in m["per_layer"] if e["name"] == name + ".sat"]
    unit = "%" if name == "idle_under_wait_share" else "ms"
    layer = {"held_wait_ms_per_batch": "fused operator program",
             "d2h_wait_ms_per_batch": "egress / sink",
             "chip_wait_ms_per_batch": "driver sweep",
             "host_work_ms_per_batch": "driver sweep",
             "idle_under_wait_share": "device"}[name]
    assert {k: v for k, v in entry.items() if k != "workloads"} == {
        "name": name + ".sat", "unit": unit, "better": "lower",
        "source": "program_span", "layer": layer, "moves": "tuples_per_s"}
    assert set(LISTED) <= set(entry.get("workloads", LISTED))
    assert os.path.isfile(os.path.join(
        ROOT, "benchmark", "layer_metrics", name + ".sat.py"))
    for cell in LISTED:
        assert name + ".sat" in {
            e["name"] for e in harness.resolve_cell(cell)["per_layer"]}
