"""``nexmark_q11.saturated``: a rehearsal of the whole run in-process on
the CPU backend at tiny sizes, a broken timed path, its control, its
entries in the manifest (additions only, against the parent's manifest),
and the readers of its three per-layer metrics on hand-made and recorded
traces.  No device metric is printed or asserted here."""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness, trace_reduce  # noqa: E402
from test_bench_harness import run, tiny_cell  # noqa: E402

CELL = "nexmark_q11.saturated"
# a pass of 8 x 1024 events at 100 000 a second spans 81.92 ms; a bidder
# is live for (4 + 10 + 1) x 50 events = 7.5 ms, then waits out 20 ms
SIZES = dict(gap_usec=20_000, max_keys=2048, active_people=4,
             hot_bidder_stride=8, event_rate=100_000)
RECORDED = os.path.join(ROOT, "benchmark", "testdata")
S = 1e9


def reader(name):
    return harness.load_module("layer_metrics", name)


@pytest.fixture(scope="module")
def window():
    return run(tiny_cell(CELL), seconds=0.8, **SIZES)


def test_cell_runs_and_every_row_is_checked(window):
    w = window
    assert w["correct"], w["checks"]
    assert {c["name"] for c in w["checks"]} == {
        "rows_missing_or_extra", "key_wid_mismatches", "result_rows_absent",
        "count_mismatches", "dropped_tuples"}
    assert all(c["limit"] == 0 for c in w["checks"])
    # ~164 persons a pass, a session each: hundreds of rows
    assert w["rows"] >= 300 and w["failed"] == 0 and w["attempted"] > 0
    assert w["open"]["pulled"] >= harness.WARMUP_MIN_BATCHES * 1024
    assert w["n_total"] == w["open"]["pulled"] + w["tuples_in_window"]
    assert w["t_open"] < w["t_stop"] <= w["t_last_delivery"]
    assert w["compiled_after_open"] == {}


def test_cell_reports_its_metrics(window):
    cell = tiny_cell(CELL)
    e2e = harness.read_metrics(cell, cell["end_to_end"], "end_to_end", None,
                               window)
    assert set(e2e) == {"tuples_per_s", "setup_s"}
    assert all(v["value"] > 0 for v in e2e.values())
    layer = harness.read_metrics(cell, cell["per_layer"], "layer_metrics",
                                 None, window)
    sources = {m["name"]: m["source"] for m in cell["per_layer"]}
    assert layer and all(sources[k] == "program_counter" for k in layer)
    # the egress copies whole output batches: a key, two times, a count,
    # a stamp and a flag a lane, for every lane of every batch
    assert 30 < layer["d2h_bytes_per_tuple.sat"]["value"] < 60
    json.dumps(layer)


@pytest.mark.parametrize("fault", ["count_off", "end_off", "rows_lost"])
def test_broken_timed_path_is_not_correct(monkeypatch, fault):
    """The rest of a run with the timed path broken underneath: one
    delivered batch is altered where the program hands it to the sink."""
    from windflow_tpu import batch
    real = batch.device_to_columns_multi
    calls = {"n": 0, "hit": 0}

    def broken(batches):
        out = list(real(batches))
        calls["n"] += 1
        for i, (cols, tss) in enumerate(out):
            if calls["hit"] or not len(tss):
                continue
            calls["hit"] = 1
            if fault == "rows_lost":
                cols = {k: np.asarray(v)[:-1] for k, v in cols.items()}
                tss = tss[:-1]
            else:
                v = np.array(cols["value"])
                v[0, 0 if fault == "count_off" else 1] += 1
                cols = dict(cols, value=v)
            out[i] = (cols, tss)
        return out

    monkeypatch.setattr(batch, "device_to_columns_multi", broken)
    w = run(tiny_cell(CELL), seconds=0.3, **SIZES)
    assert calls["hit"] and not w["correct"]
    bad = {c["name"] for c in w["checks"] if not c["ok"]}
    assert bad == ({"count_mismatches"} if fault != "rows_lost" else
                   {"rows_missing_or_extra", "key_wid_mismatches",
                    "count_mismatches"})


@pytest.mark.parametrize("seed", [1, 2**31 + 17, 77])
def test_the_control_fails_by_the_row_set(seed):
    """Event time rounded to the millisecond moves a session's start and
    end by up to half a millisecond: the (bidder, start) rows no longer
    match, and the comparison says so; the reference in the program's
    place passes."""
    cell = tiny_cell(CELL)
    mod = cell["config_module"]
    cfg = harness.with_sizes(cell["config"], {
        "batch": 1024, "ring_batches": 8, **SIZES})
    ring = mod.make_ring(seed, cfg)
    n = 8 * 1024 * 5 + 2000
    exp = mod.expected(cfg, ring, n, cell["mix"])
    k, w, v = mod.control(cfg, ring, n, cell["mix"])
    checks = {c["name"]: c for c in mod.compare(
        cfg, {"key": k, "wid": w, "value": v}, exp)}
    assert not checks["key_wid_mismatches"]["ok"]
    assert checks["key_wid_mismatches"]["value"] > len(exp.key) / 2
    assert not checks["count_mismatches"]["ok"]
    assert all(c["limit"] == 0 for c in checks.values())
    same = mod.compare(cfg, {"key": exp.key, "wid": exp.wid,
                             "value": exp.value}, exp)
    assert all(c["ok"] for c in same)


def test_control_py_reads_the_cell(capsys, monkeypatch):
    from benchmark import control
    cell = harness.resolve_cell(CELL)
    cfg = harness.with_sizes(cell["config"], {
        "batch": 1024, "ring_batches": 8, **SIZES})
    monkeypatch.setattr(harness, "resolve_cell", lambda name: dict(
        cell, config=cfg, mix=dict(cell["mix"], event_rate=100_000)))
    assert control.main(["--workload", CELL, "--tuples", "30000",
                         "--seeds", "5"]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["control"] == CELL and line["rows"] > 0
    assert not all(c["ok"] for c in line["checks"])


# ---------------------------------------------------------------------------
# the manifest: additions only
# ---------------------------------------------------------------------------

#: name -> its ``workloads`` at the parent commit (7f08677), for the
#: entries that listed ``nexmark_q5.saturated`` there
Q5_LISTS_NOT_TAKEN = {"placement_dev_share.sat", "stage2_dev_ms_per_batch.sat"}
NEW_LAYERS = ["sort_dev_share.sat", "sink_rows_per_batch.sat",
              "egress_fill_share.sat"]


def parent_manifest():
    """``BENCHMARK.json`` as the parent commit had it: this one with
    every addition of this PR taken away again."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    m["configs"] = [c for c in m["configs"] if c["name"] != "nexmark_q11"]
    m["workloads"] = [w for w in m["workloads"] if w["name"] != CELL]
    m["per_layer"] = [e for e in m["per_layer"]
                      if e["name"] not in NEW_LAYERS]
    for e in m["end_to_end"] + m["per_layer"]:
        if "workloads" in e:
            e["workloads"] = [w for w in e["workloads"] if w != CELL]
    return m


def test_the_manifest_lists_the_cell_as_additions_only():
    import hashlib
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    # what is left when this PR's additions are taken away is the
    # parent's manifest, entry for entry and in order
    parent = json.dumps(parent_manifest(), sort_keys=True)
    assert hashlib.sha256(parent.encode()).hexdigest() == PARENT_MANIFEST
    assert [c["name"] for c in m["workloads"]][-1] == CELL
    assert len(m["workloads"]) == 5 and len(m["configs"]) == 5
    assert [c["chips"] for c in m["workloads"]].count(4) == 1
    assert m["workloads"][-1]["chips"] == 1
    assert m["workloads"][-1]["traffic"] == "saturated"
    assert m["configs"][-1]["name"] == "nexmark_q11" \
        and m["configs"][-1]["reduced"] == []
    assert len(m["configs"][-1]["source"]) <= 200
    assert all(len(x["why"]) <= 200 for x in m["workloads"] + m["configs"])
    assert [e["name"] for e in m["per_layer"][-3:]] == NEW_LAYERS
    assert all(e["workloads"] == [CELL] and e["moves"] == "tuples_per_s"
               for e in m["per_layer"][-3:])
    assert [e["layer"] for e in m["per_layer"][-3:]] == [
        "fused operator program", "egress / sink", "egress / sink"]
    # every list that holds Q5's cell holds this one after it, but the
    # two that read Q5's own placement and second stage
    for e in m["end_to_end"] + m["per_layer"]:
        lists = e.get("workloads", ())
        if "nexmark_q5.saturated" in lists:
            assert (lists[-1] == CELL) \
                == (e["name"] not in Q5_LISTS_NOT_TAKEN), e["name"]
        elif e["name"] not in NEW_LAYERS:
            assert CELL not in lists, e["name"]
    cell = harness.resolve_cell(CELL)
    assert cell["chips"] == 1 and cell["mix"]["rate"] == "always_due"
    assert cell["config"]["step_program"] == "nexmark_q11_step"
    assert cell["config"]["reduced"] == {}
    reported = {e["name"] for e in cell["end_to_end"]}
    assert reported == {"tuples_per_s", "setup_s"}
    assert "window_out_lanes_per_batch.sat" in {
        e["name"] for e in cell["per_layer"]}


PARENT_MANIFEST = (
    "732889f2524b92b298bcc77e60e597bf"
    "5085cf3cb7685a5e792c63f2c545ca9a")


def test_the_roofline_counts_the_lanes_and_the_rows_touched():
    cell = harness.resolve_cell(CELL)
    prog = harness.load_module("roofline", "nexmark_q11_step")
    least = prog.least_bytes(cell["config"])
    lanes = 262144 * 16
    # the lanes read once, and far less than one pass over the state
    assert lanes < least < lanes + 212992 * 25
    assert prog.MODULES == r"^jit_step_session$"
    dev = reader("step_dev_ms_per_batch.sat")
    red = modules(jit_step_session=0.4, jit_step=0.1, jit_unpack_fn=0.1)
    w = {"trace0": {"pulled": 0}, "trace1": {"pulled": 4 * 1024},
         "batch": 1024, "config": {"step_program": "nexmark_q11_step"}}
    assert dev.step_seconds(red, w) == pytest.approx(0.4)
    assert dev.read(red, {}, w) == pytest.approx(100.0)


# ---------------------------------------------------------------------------
# the readers
# ---------------------------------------------------------------------------

def modules(**secs):
    at, events = 0.0, []
    for name, s in secs.items():
        events.append((f"{name}(123)", at * S, (at + s) * S, {}))
        at += s
    return trace_reduce.reduce_planes([(
        "/device:TPU:0", [(trace_reduce.MODULES_LINE, events)])])


SORT = ("%sort.3 = (s32[262144]{0:T(1024)S(1)}, s32[262144]{0:T(1024)S(1)}, "
        "s32[262144]{0:T(1024)S(1)}) sort(s32[262144]{0:T(1024)S(1)} "
        "%select.1, s32[262144]{0:T(1024)} %convert.7, s32[262144]{0:T(1024)}"
        " %iota.9), dimensions={0}, is_stable=false, to_apply=%compare")
GATHER = ("%fusion.64 = u32[262144]{0:T(1024)S(1)} fusion(u32[262144]{0:T("
          "1024)} %gte.971, s32[262144]{0:T(1024)S(1)} %fusion.566), "
          "kind=kCustom, calls=%fused_computation.64")


def ops_trace(*ops):
    at, mods, events = 0.0, [], []
    for name, s in ops:
        events.append((name, at * S, (at + s) * S, {}))
        at += s
    mods.append(("jit_step_session(1)", 0.0, at * S, {}))
    return trace_reduce.reduce_planes([(
        "/device:TPU:0", [(trace_reduce.MODULES_LINE, mods),
                          (trace_reduce.OPS_LINE, events)])])


def test_sort_share_reads_the_sort_operations():
    m = reader("sort_dev_share.sat")
    red = ops_trace((SORT, 0.3), (GATHER, 0.5), (SORT, 0.2))
    assert m.sort_seconds(red) == pytest.approx(0.5)
    assert m.read(red, {}, {}) == pytest.approx(50.0)
    # a step that orders nothing, an untraced run, an empty trace
    assert m.read(ops_trace((GATHER, 0.5)), {}, {}) is None
    assert m.read(None, {}, {}) is None
    assert m.read({"devices": {}, "busy_s": 0.0}, {}, {}) is None
    # the recorded one-chip trace (ffat_sum.steady, PR 24) sorts its batch
    path = trace_reduce.find_xplane(RECORDED)
    rec = trace_reduce.reduce_planes(trace_reduce.read_planes(path))
    share = m.read(rec, {}, {})
    assert share is None or 0.0 < share < 100.0


def test_sink_rows_and_fill_from_the_sinks_spans():
    rows = reader("sink_rows_per_batch.sat")
    fill = reader("egress_fill_share.sat")
    events = [("wf.sink.d2h", {"batch": 1, "batches": 3, "bytes": 900,
                               "lanes": 3072}),
              ("wf.sink.deliver", {"batch": 1, "rows": 20}),
              ("wf.sink.deliver", {"batch": 2, "rows": 44}),
              ("wf.sink.d2h", {"batch": 4, "batches": 1, "bytes": 300,
                               "lanes": 1024})]
    t = rows.sink_totals(events)
    assert t == {"rows": 64, "lanes": 4096, "deliveries": 2, "copies": 2}
    # a program whose copies do not say their lanes (the parent's)
    old = [(n, {k: v for k, v in st.items() if k != "lanes"})
           for n, st in events]
    assert rows.sink_totals(old)["lanes"] is None
    assert rows.sink_totals([]) == {"rows": 0, "lanes": None,
                                    "deliveries": 0, "copies": 0}
    # untraced, and a traced run whose file was not written
    for m in (rows, fill):
        assert m.read(None, {}, {"trace_dir": None}) is None
        assert m.read({}, {}, {"trace_dir": os.path.join(RECORDED, "none"),
                               "trace0": {"pulled": 0}}) is None


def test_sink_readers_on_the_recorded_trace(monkeypatch):
    """The recorded trace with the program's spans (PR 24, before the
    copies said their lanes): the rows are read, the fill is not."""
    rows = reader("sink_rows_per_batch.sat")
    fill = reader("egress_fill_share.sat")
    path = os.path.join(RECORDED, "spans.xplane.pb")
    monkeypatch.setattr(trace_reduce, "find_xplane", lambda d: path)
    window = {"trace_dir": RECORDED, "trace0": {"pulled": 0},
              "trace1": {"pulled": 8 * 1024}, "batch": 1024}
    t = rows.totals_of(window)
    assert t["deliveries"] > 0 and t["rows"] > 0 and t["lanes"] is None
    assert rows.read({}, {}, window) == pytest.approx(t["rows"] / 8)
    assert fill.read({}, {}, window) is None
    # with lanes said, the share is rows over lanes
    monkeypatch.setattr(rows, "sink_events", lambda p: [
        ("wf.sink.d2h", {"lanes": 2048}), ("wf.sink.deliver", {"rows": 512})])
    assert fill.read({}, {}, window) == pytest.approx(25.0)
