"""``nexmark_q9.saturated``: a rehearsal of the whole run in-process on
the CPU backend at tiny sizes, a broken timed path, its control, its
entries in the manifest (present and as the issue names them, every
entry of the parent's manifest present and unchanged but for appended
cell names), and the readers of its three per-layer metrics on a
hand-made phase table.  No device metric is printed or asserted here."""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import device_phases as dp  # noqa: E402
from benchmark import harness  # noqa: E402
from test_bench_harness import run, tiny_cell  # noqa: E402

CELL = "nexmark_q9.saturated"
# 100 000 events a second: an auction lives 1-33 340 usec (up to 3 334
# events, three and a quarter batches of 1024), a pass of 8 x 1024
# events spans 81.92 ms
SIZES = dict(build_capacity=512, out_capacity=256, active_people=4, hot_bidder_stride=8,
             event_rate=100_000)
NEW_LAYERS = ["join_match_dev_ms_per_batch.sat",
              "join_carry_dev_ms_per_batch.sat",
              "join_close_dev_ms_per_batch.sat"]
#: the accepted metrics whose readers have something to read in the cell
TAKEN = {"tuples_per_s", "throttle_share.sat", "h2d_bytes_per_tuple.sat",
         "d2h_bytes_per_tuple.sat", "compiles_in_window.sat",
         "step_dev_ms_per_batch.sat", "step_hbm_roofline.sat",
         "device_idle.sat", "idle_unattributed_share.sat",
         "unscoped_dev_share.sat", "parse_host_ms_per_batch.sat",
         "pack_host_ms_per_batch.sat", "encode_host_ms_per_batch.sat",
         "h2d_host_ms_per_batch.sat",
         "unpack_dispatch_host_ms_per_batch.sat",
         "step_dispatch_host_ms_per_batch.sat",
         "sink_host_ms_per_batch.sat", "sweep_self_ms_per_batch.sat",
         "batch_fill_share.sat", "unpack_dev_ms_per_batch.sat",
         "operator_fn_dev_ms_per_batch.sat",
         "window_out_lanes_per_batch.sat", "sink_rows_per_batch.sat",
         "egress_fill_share.sat"}


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
    # ~490 auctions a pass, most with a qualifying bid: hundreds of rows
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
    # the egress copies whole output batches: a key, two times, five
    # numbers, a stamp and a flag a lane, for every lane of every batch
    # (a quarter as many lanes as the input's: SIZES)
    assert 15 < layer["d2h_bytes_per_tuple.sat"]["value"] < 25
    json.dumps(layer)


@pytest.mark.parametrize("fault", ["price_off", "count_off", "rows_lost"])
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
                v[0, 0 if fault == "price_off" else 4] += 1
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
def test_the_control_fails_by_the_numbers_compared(seed):
    """Event time rounded to the millisecond moves auctions' starts and
    bids across both ends of their intervals: the (auction, dateTime)
    rows no longer match, and the comparison says so; the reference in
    the program's place passes."""
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
# the manifest: this PR's entries present, the parent's unchanged
# ---------------------------------------------------------------------------

def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_manifest_holds_the_cell_as_the_issue_names_it():
    m = manifest()
    [cfg] = [c for c in m["configs"] if c["name"] == "nexmark_q9"]
    assert cfg["reduced"] == [] and cfg["source"].startswith(
        "NEXMark query 9, winning bids (Apache Beam nexmark Query9")
    assert cfg["file"] == "benchmark/configs/nexmark_q9.json"
    [cell] = [w for w in m["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("nexmark_q9", "saturated", 1)
    assert "holds little by nature" in cell["why"]
    # at most 200 characters in every source and why, wherever they are
    assert all(len(c["source"]) <= 200 and len(c["why"]) <= 200
               for c in m["configs"])
    assert all(len(w["why"]) <= 200 for w in m["workloads"])
    # one four-chip cell: at most half the cells, rounded down
    assert [w["chips"] for w in m["workloads"]].count(4) == 1 \
        <= len(m["workloads"]) // 2
    mine = {e["name"]: e for e in m["per_layer"] if e["name"] in NEW_LAYERS}
    assert list(mine) == NEW_LAYERS         # in this order, wherever
    for e in mine.values():
        assert e == {"name": e["name"], "unit": "ms", "better": "lower",
                     "source": "device_trace",
                     "layer": "fused operator program",
                     "moves": "tuples_per_s", "workloads": [CELL]}
        assert os.path.isfile(os.path.join(
            ROOT, "benchmark", "layer_metrics", e["name"] + ".py"))
    lists = {e["name"] for e in m["end_to_end"] + m["per_layer"]
             if CELL in e.get("workloads", ())}
    assert lists == TAKEN | set(NEW_LAYERS)
    resolved = harness.resolve_cell(CELL)
    assert resolved["mix"]["rate"] == "always_due"
    assert resolved["mix"]["chunk_bytes"] == 1048576
    assert resolved["config"]["step_program"] == "nexmark_q9_step"
    assert resolved["config"]["reduced"] == {}
    assert {e["name"] for e in resolved["end_to_end"]} \
        == {"tuples_per_s", "setup_s"}


def test_every_entry_of_the_parents_manifest_is_there_unchanged():
    """Against ``BENCHMARK.json`` as the commit this PR starts from had
    it (kept as data beside the tests): every configuration, cell and
    metric it had is present, in its order and key for key, and a
    ``workloads`` list has at most grown at its end.  Entries a later PR
    appends do not break this."""
    with open(os.path.join(ROOT, "tests", "benchmark", "data",
                           "manifest_before_pr36.json")) as f:
        old = json.load(f)
    new = manifest()
    for key in ("command", "paths", "run_seconds"):
        assert new[key] == old[key]
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        was = [e["name"] for e in old[group]]
        now = [e["name"] for e in new[group]]
        assert [n for n in now if n in set(was)] == was, group
        at = {e["name"]: e for e in new[group]}
        for e in old[group]:
            mine = dict(at[e["name"]])
            if "workloads" in e:
                had = e["workloads"]
                assert mine["workloads"][:len(had)] == had, e["name"]
                mine["workloads"] = had
            assert mine == e, e["name"]


def test_the_roofline_counts_the_lanes_and_the_rows_touched():
    cell = harness.resolve_cell(CELL)
    prog = harness.load_module("roofline", "nexmark_q9_step")
    least = prog.least_bytes(cell["config"])
    lanes = 262144 * 24
    auctions = 262144 * 3 // 50
    # the lanes read once, two state rows and one result row an auction
    assert lanes < least < lanes + auctions * 150
    assert prog.MODULES == r"^jit_step_join$"
    import re
    assert re.search(prog.MODULES, "jit_step_join")
    assert not re.search(prog.MODULES, "jit_step_session")


# ---------------------------------------------------------------------------
# the readers
# ---------------------------------------------------------------------------

def phase_table(**secs):
    """A reduction as ``device_phases.reduce_planes`` gives it, with the
    seconds named by phase (in ``jit_step_join``, operator
    ``winning_bids``)."""
    rows = {("jit_step_join", "winning_bids", "wf." + ph.replace("_", ".")):
            (s, 0) for ph, s in secs.items()}
    rows[("jit_unpack_fn", None, "wf.unpack")] = (0.4, 0)
    busy = sum(s for s, _ in rows.values())
    return {"chips": 1, "busy_s": busy, "leaf_s": busy, "rows": rows,
            "unscoped_ops": {}, "unnamed_s": 0.0, "parts": {}, "runs": {}}


def test_the_three_metrics_read_the_joins_phases(monkeypatch):
    red = phase_table(join_sort=0.08, join_match=0.32, join_carry=0.02,
                      join_close=0.1, fn=0.01)
    window = {"trace_dir": "somewhere", "batch": 1024,
              "trace0": {"pulled": 0}, "trace1": {"pulled": 100 * 1024}}
    monkeypatch.setattr(dp, "load", lambda w: red)
    match, carry, close = (reader(n) for n in NEW_LAYERS)
    # ms per 1024 tuples pulled: 100 batches in the span
    assert match.read(None, {}, window) == pytest.approx(4.0)
    assert carry.read(None, {}, window) == pytest.approx(0.2)
    assert close.read(None, {}, window) == pytest.approx(1.0)
    # a program without the join (the parent), an untraced run
    other = phase_table(session_carry=0.5)
    monkeypatch.setattr(dp, "load", lambda w: other)
    assert [reader(n).read(None, {}, window) for n in NEW_LAYERS] \
        == [None] * 3
    monkeypatch.undo()
    assert [reader(n).read(None, {}, {"trace_dir": None})
            for n in NEW_LAYERS] == [None] * 3


def test_every_phase_a_metric_reads_is_declared_by_the_program():
    import re

    from windflow_tpu.monitoring import recorder
    read = set()
    for name in NEW_LAYERS:
        with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                               name + ".py")) as f:
            read |= set(re.findall(r'"(wf\.[a-z_.]+)"', f.read()))
    assert read == {"wf.join.sort", "wf.join.match", "wf.join.carry",
                    "wf.join.close"} <= set(recorder.PHASES)
    assert all(recorder.PHASES[p][0] == "fused operator program"
               for p in read)
