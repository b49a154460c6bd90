"""``nexmark_q6.saturated``: a rehearsal of the whole run in-process on
the CPU backend at tiny sizes, a broken timed path, its control, its
entries in the manifest (present and as the issue names them, every
entry of the parent's manifest present and unchanged but for appended
cell names), its roofline and the readers of its three per-layer
metrics.  No device metric is printed or asserted here, and no number
that depends on the host's speed."""

import json
import os
import re
import sys

import numpy as np
import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import device_phases as dp  # noqa: E402
from benchmark import harness  # noqa: E402
from test_bench_harness import run, tiny_cell  # noqa: E402

CELL = "nexmark_q6.saturated"
# 100 000 events a second: an auction lives 1-33 340 usec, a pass of
# 8 x 1024 events spans 81.92 ms and creates ~164 persons
SIZES = dict(build_capacity=512, out_capacity=256, max_keys=256,
             active_people=4, hot_bidder_stride=8, event_rate=100_000)
NEW_LAYERS = ["stage2_rows_per_batch.sat", "partial_window_share.sat",
              "order_dev_ms_per_batch.sat"]
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
         "egress_fill_share.sat", "stage2_dev_ms_per_batch.sat"}


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
        "count_mismatches", "rows_out_of_order", "counter_mismatches",
        "dropped_tuples"}
    assert all(c["limit"] == 0 for c in w["checks"])
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
    # the share of leading partial windows is the reference's own
    mod = cell["config_module"]
    c = mod.LAST_COUNTERS
    assert c["CB_rows_out_of_order"] == 0
    assert c["CB_windows_fired"] == window["rows"]
    assert layer["partial_window_share.sat"]["value"] == pytest.approx(
        100.0 * c["CB_partial_windows"] / window["rows"])
    assert 0 < layer["partial_window_share.sat"]["value"] < 100
    json.dumps(layer)


@pytest.mark.parametrize("fault", ["sum_off", "count_off", "rows_lost"])
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
                v[0, 0 if fault == "sum_off" else 4] += 1
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


def test_a_window_that_does_not_wait_is_not_correct(monkeypatch):
    """The deployment with a count window that counts every row in the
    step that brings it, whatever the watermark says: the run ends and
    is not ``correct``, by the window's own counter, even where no
    seller's rows crossed a step."""
    import windflow_tpu as wf
    from windflow_tpu.windows.count_ordered_tpu import OrderedCountWindowsTPU

    class Hasty(OrderedCountWindowsTPU):
        def _wm_adj(self, wm):
            from windflow_tpu.windows.session_kernels import TS_MAX
            return TS_MAX       # nothing waits: rows are counted as they come

    b = wf.Ffat_WindowsTPU_Builder
    real = b.build

    def build(self):
        op = real(self)
        if isinstance(op, OrderedCountWindowsTPU):
            op.__class__ = Hasty
        return op

    monkeypatch.setattr(b, "build", build)
    w = run(tiny_cell(CELL), seconds=0.3, **SIZES)
    assert not w["correct"]
    bad = {c["name"] for c in w["checks"] if not c["ok"]}
    assert bad >= {"rows_out_of_order", "counter_mismatches"}
    assert "dropped_tuples" not in bad


@pytest.mark.parametrize("seed", [1, 2**31 + 17, 77])
def test_the_control_fails_by_the_numbers_compared(seed):
    """A seller's auctions counted in the order they open: every row is
    there under its own key and wid, the sums of the sellers whose
    auctions close in another order differ, and the comparison says so;
    the reference in the program's place passes."""
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
    assert checks["key_wid_mismatches"]["ok"]
    assert not checks["count_mismatches"]["ok"]
    assert checks["count_mismatches"]["value"] > len(exp.key) / 20
    assert all(c["limit"] == 0 for c in checks.values())
    same = mod.compare(cfg, {"key": exp.key, "wid": exp.wid,
                             "value": exp.value}, exp)
    assert all(c["ok"] for c in same)
    # ... and so does the answer without its leading partial windows
    full = exp.value[:, 1] == cfg["graph"]["window_rows"]
    short = mod.compare(cfg, {"key": exp.key[full], "wid": exp.wid[full],
                              "value": exp.value[full]}, exp)
    assert not all(c["ok"] for c in short) and exp.partial > 0


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
    [cfg] = [c for c in m["configs"] if c["name"] == "nexmark_q6"]
    assert cfg["reduced"] == [] and cfg["source"].startswith(
        "NEXMark query 6, average selling price by seller (Apache Beam "
        "nexmark Query6")
    assert cfg["file"] == "benchmark/configs/nexmark_q6.json"
    [cell] = [w for w in m["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("nexmark_q6", "saturated", 1)
    assert "two device programs a batch" in cell["why"]
    assert all(len(c["source"]) <= 200 and len(c["why"]) <= 200
               for c in m["configs"])
    assert all(len(w["why"]) <= 200 for w in m["workloads"])
    assert [w["chips"] for w in m["workloads"]].count(4) == 1 \
        <= len(m["workloads"]) // 2
    mine = {e["name"]: e for e in m["per_layer"] if e["name"] in NEW_LAYERS}
    assert list(mine) == NEW_LAYERS         # in this order, wherever
    want = {"stage2_rows_per_batch.sat": ("rows", "higher", "program_span"),
            "partial_window_share.sat": ("%", "lower", "program_counter"),
            "order_dev_ms_per_batch.sat": ("ms", "lower", "device_trace")}
    for e in mine.values():
        unit, better, source = want[e["name"]]
        assert e == {"name": e["name"], "unit": unit, "better": better,
                     "source": source, "layer": "fused operator program",
                     "moves": "tuples_per_s", "workloads": [CELL]}
        assert os.path.isfile(os.path.join(
            ROOT, "benchmark", "layer_metrics", e["name"] + ".py"))
    lists = {e["name"] for e in m["end_to_end"] + m["per_layer"]
             if CELL in e.get("workloads", ())}
    assert lists == TAKEN | set(NEW_LAYERS)
    # not the lists an accepted test pins to other cells, nor the one a
    # benchmark issue is asked to retire
    assert not lists & {"join_match_dev_ms_per_batch.sat",
                        "join_carry_dev_ms_per_batch.sat",
                        "join_close_dev_ms_per_batch.sat",
                        "sort_dev_share.sat", "wire_encoded_share.sat"}
    resolved = harness.resolve_cell(CELL)
    assert resolved["mix"]["rate"] == "always_due"
    assert resolved["mix"]["chunk_bytes"] == 1048576
    assert resolved["mix"]["event_rate"] == 1_000_000
    assert resolved["config"]["step_program"] == "nexmark_q6_step"
    assert resolved["config"]["reduced"] == {}
    g = resolved["config"]["graph"]
    assert (g["batch"], g["max_keys"], g["window_rows"], g["slide_rows"],
            g["out_capacity"]) == (262144, 212992, 10, 1, 32768)
    assert {e["name"] for e in resolved["end_to_end"]} \
        == {"tuples_per_s", "setup_s"}


def test_every_entry_of_the_parents_manifest_is_there_unchanged():
    """Against ``BENCHMARK.json`` as the commit this PR starts from had
    it (kept as data beside the tests): every configuration, cell and
    metric it had is present, in its order and key for key, and a
    ``workloads`` list has at most grown at its end.  Entries a later PR
    appends do not break this."""
    with open(os.path.join(ROOT, "tests", "benchmark", "data",
                           "manifest_before_pr44.json")) as f:
        old = json.load(f)
    new = manifest()
    for key in ("command", "paths", "run_seconds"):
        assert new[key] == old[key]
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        was = [e["name"] for e in old[group]]
        now = [e["name"] for e in new[group]]
        assert now[:len(was)] == was, group      # appended at the end
        at = {e["name"]: e for e in new[group]}
        for e in old[group]:
            mine = dict(at[e["name"]])
            if "workloads" in e:
                had = e["workloads"]
                assert mine["workloads"][:len(had)] == had, e["name"]
                mine["workloads"] = had
            assert mine == e, e["name"]


def test_the_roofline_counts_both_programs_of_a_batch():
    cell = harness.resolve_cell(CELL)
    prog = harness.load_module("roofline", "nexmark_q6_step")
    q9 = harness.load_module("roofline", "nexmark_q9_step")
    cfg = cell["config"]
    least = prog.least_bytes(cfg)
    assert least == prog.join_bytes(cfg) + prog.window_bytes(cfg)
    auctions = 262144 * 3 // 50
    # the join's count is Q9's and the seller (4 B) in two state rows
    # and one handed-over row an auction
    assert prog.join_bytes(cfg) == pytest.approx(
        q9.least_bytes(harness.resolve_cell("nexmark_q9.saturated")[
            "config"]) + 262144 * 3 / 50 * 3 * 4)
    # behind it, per row: read once, the nine cells and the count it
    # touches, the cell and the count it changes, one result row
    per_row = prog.window_bytes(cfg) / (262144 * 3 / 50)
    assert per_row == 44 + (9 * 16 + 8) + (16 + 8) + 52
    assert auctions * 200 < prog.window_bytes(cfg) < auctions * 300
    assert re.search(prog.MODULES, "jit_step_join")
    assert re.search(prog.MODULES, "jit_step_w2")
    assert not re.search(prog.MODULES, "jit_step")
    assert not re.search(prog.MODULES, "jit_step_session")
    assert not re.search(prog.MODULES, "jit_step_join_pairs")


# ---------------------------------------------------------------------------
# the readers
# ---------------------------------------------------------------------------

def test_stage2_rows_reads_rows_in_of_the_later_stages(monkeypatch):
    rows = reader("stage2_rows_per_batch.sat")
    spans = [{"op": "filter_tpu|winning_bids", "out_cap": "32768"},
             {"op": "selling_price", "out_cap": "131072", "stage": "2"},
             {"op": "selling_price_row"},
             {"op": "filter_tpu|winning_bids", "out_cap": "32768"},
             {"op": "selling_price", "out_cap": "131072", "stage": "2",
              "rows_in": "10100"},
             {"op": "selling_price", "out_cap": "131072", "stage": "2",
              "rows_in": "9900"}]
    assert rows.later_stage_rows(spans) == (20000, 2)
    assert rows.later_stage_rows(spans[:4]) is None
    lanes = reader("window_out_lanes_per_batch.sat")
    monkeypatch.setattr(lanes, "dispatch_spans", lambda w: spans)
    window = {"trace_dir": "x", "batch": 1024, "trace0": {"pulled": 0},
              "trace1": {"pulled": 2 * 1024}}
    assert rows.read(None, {}, window) == pytest.approx(10000.0)
    # a program whose spans say no stage (the parent), an untraced run
    monkeypatch.setattr(lanes, "dispatch_spans", lambda w: spans[:1])
    assert rows.read(None, {}, window) is None
    monkeypatch.undo()
    assert rows.read(None, {}, {"trace_dir": None}) is None


def test_partial_window_share_reads_the_windows_counters(monkeypatch):
    share = reader("partial_window_share.sat")
    assert share.share({"CB_windows_fired": 400,
                        "CB_partial_windows": 50}) == 12.5
    assert share.share(None) is None and share.share({}) is None
    assert share.share({"CB_windows_fired": 0,
                        "CB_partial_windows": 0}) is None
    # a configuration that keeps no counters, one that does not exist
    assert share.read(None, {}, {"config": {"name": "ffat_sum"}}) is None
    assert share.read(None, {}, {"config": {"name": "no_such"}}) is None


def test_order_dev_ms_reads_the_phase(monkeypatch):
    order = reader("order_dev_ms_per_batch.sat")
    rows = {("jit_step_w2", "selling_price", "wf.order"): (0.06, 0),
            ("jit_step_w2", "selling_price", "wf.place"): (0.2, 0),
            ("jit_step_join", "winning_bids", "wf.join.sort"): (0.3, 0)}
    busy = sum(s for s, _ in rows.values())
    red = {"chips": 1, "busy_s": busy, "leaf_s": busy, "rows": rows,
           "unscoped_ops": {}, "unnamed_s": 0.0, "parts": {}, "runs": {}}
    window = {"trace_dir": "somewhere", "batch": 1024,
              "trace0": {"pulled": 0}, "trace1": {"pulled": 100 * 1024}}
    monkeypatch.setattr(dp, "load", lambda w: red)
    assert order.read(None, {}, window) == pytest.approx(0.6)
    del rows[("jit_step_w2", "selling_price", "wf.order")]
    assert order.read(None, {}, window) is None
    monkeypatch.undo()
    assert order.read(None, {}, {"trace_dir": None}) is None
    from windflow_tpu.monitoring import recorder
    assert recorder.PHASES["wf.order"][0] == "fused operator program"
