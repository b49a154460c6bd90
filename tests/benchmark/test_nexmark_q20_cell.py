"""``nexmark_q20.saturated``: a rehearsal of the whole run in-process on
the CPU backend at tiny sizes, a broken timed path, its control, its
entries in the manifest (present and as the issue names them, every
entry of the parent's manifest present and unchanged but for appended
cell names), its roofline count, and the reader of its per-layer metric
on a hand-made phase table.  No device metric is printed or asserted
here."""

import ast
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

CELL = "nexmark_q20.saturated"
# 100 000 events a second: a bid comes at most 1 670 usec before its
# auction and 16 670 after it, a batch of 1024 events spans 10.24 ms, a
# pass of 8 x 1024 events 81.92 ms; one batch can pair ~600 bids (the hot
# auction's, where it is of category 10), so an output of 512 lanes holds
# rows back now and then
SIZES = dict(lower_usec=5_000, upper_usec=60_000, out_capacity=512,
             probe_capacity=256, active_people=4, hot_bidder_stride=8,
             event_rate=100_000)
NEW_LAYER = "join_table_dev_ms_per_batch.sat"
#: the pair form's own readers of the phases it shares with the fold
#: form (whose three lists an accepted test pins to nexmark_q9's cell)
PAIR_LAYERS = ["join_pairs_match_dev_ms_per_batch.sat",
               "join_pairs_carry_dev_ms_per_batch.sat",
               "join_pairs_close_dev_ms_per_batch.sat"]
NEW_LAYERS = [NEW_LAYER] + PAIR_LAYERS
JOIN_LAYERS = ["join_match_dev_ms_per_batch.sat",
               "join_carry_dev_ms_per_batch.sat",
               "join_close_dev_ms_per_batch.sat", NEW_LAYER]
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
         "egress_fill_share.sat", "sort_dev_share.sat"}


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
        "count_mismatches", "rows_after_watermark", "counter_mismatches",
        "dropped_tuples"}
    assert all(c["limit"] == 0 for c in w["checks"])
    # a bid in five is a row: thousands a run
    assert w["rows"] >= 2000 and w["failed"] == 0 and w["attempted"] > 0
    assert w["rows"] > w["n_total"] // 10
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
    # the egress copies whole output batches: a key, a time, nine
    # numbers, a stamp and a flag a lane, for every lane of every batch
    # (half as many lanes as the input's: SIZES)
    assert 40 < layer["d2h_bytes_per_tuple.sat"]["value"] < 55
    json.dumps(layer)


@pytest.mark.parametrize("fault", ["seller_off", "time_off", "url_off",
                                   "rows_lost", "row_twice", "stamp_early",
                                   "probe_dropped"])
def test_broken_timed_path_is_not_correct(monkeypatch, fault):
    """The rest of a run with the timed path broken underneath: one
    delivered batch is altered where the program hands it to the sink
    (a number of a row, rows lost or doubled, a row stamped under a
    watermark already handed on), or the join says it gave up a bid the
    reference pairs."""
    from windflow_tpu import batch
    from windflow_tpu.windows.join_tpu import IntervalJoinPairsTPU
    mod = harness.load_module("configs", "nexmark_q20")
    column = {"seller_off": 5, "time_off": mod.A_DATETIME, "url_off": 3}
    if fault == "probe_dropped":
        said = IntervalJoinPairsTPU.dump_stats

        def dump_stats(self):
            st = said(self)
            if "Join_probe_matched" in st:
                st["Join_probe_matched"] -= 1
                st["Join_probe_missed_no_build"] += 1
            return st

        monkeypatch.setattr(IntervalJoinPairsTPU, "dump_stats", dump_stats)
    real = batch.device_to_columns_multi
    calls = {"n": 0, "hit": 0}

    def broken(batches):
        out = list(real(batches))
        calls["n"] += 1
        for i, (cols, tss) in enumerate(out):
            if calls["hit"] or len(tss) < 2 or calls["n"] < 3:
                continue
            calls["hit"] = 1
            if fault == "probe_dropped":
                continue
            if fault == "stamp_early":
                tss = np.array(tss)
                tss[0] = 0
            elif fault == "rows_lost":
                cols = {k: np.asarray(v)[:-1] for k, v in cols.items()}
                tss = tss[:-1]
            elif fault == "row_twice":
                cols = {k: np.array(v) for k, v in cols.items()}
                for v in cols.values():
                    v[1] = v[0]
            else:
                v = np.array(cols["value"])
                v[0, column[fault]] += 1
                cols = dict(cols, value=v)
            out[i] = (cols, tss)
        return out

    monkeypatch.setattr(batch, "device_to_columns_multi", broken)
    w = run(tiny_cell(CELL), seconds=0.3, **SIZES)
    assert calls["hit"] and not w["correct"]
    bad = {c["name"] for c in w["checks"] if not c["ok"]}
    assert bad == {
        "seller_off": {"count_mismatches"}, "time_off": {"count_mismatches"},
        "url_off": {"count_mismatches"},
        "stamp_early": {"rows_after_watermark"},
        "probe_dropped": {"counter_mismatches"},
        "rows_lost": {"rows_missing_or_extra", "key_wid_mismatches",
                      "count_mismatches"},
        # as many rows as expected, one place taken twice and one empty
        "row_twice": {"key_wid_mismatches", "count_mismatches"}}[fault]


def sized(cell):
    return harness.with_sizes(cell["config"], {
        "batch": 1024, "ring_batches": 8, **SIZES})


@pytest.mark.parametrize("seed", [1, 2**31 + 17, 77])
def test_the_control_fails_by_the_numbers_compared(seed):
    """Event time rounded to the millisecond moves every row's ``wid``
    (no bid is stamped on a whole millisecond): no delivered row finds
    its place, and the comparison says so by ``key_wid_mismatches``; the
    reference in the program's place passes."""
    cell = tiny_cell(CELL)
    mod, cfg = cell["config_module"], sized(cell)
    ring = mod.make_ring(seed, cfg)
    n = 8 * 1024 * 5 + 2000
    exp = mod.expected(cfg, ring, n, cell["mix"])
    k, w, v = mod.control(cfg, ring, n, cell["mix"])
    checks = {c["name"]: c for c in mod.compare(
        cfg, {"key": k, "wid": w, "value": v}, exp)}
    assert not checks["key_wid_mismatches"]["ok"]
    # every one of the control's rows and every expected row
    assert checks["key_wid_mismatches"]["value"] == len(k) + len(exp.key)
    assert not checks["count_mismatches"]["ok"]
    assert all(c["limit"] == 0 for c in checks.values())
    k, w, v = exp.rows()
    assert len(k) == len(exp.key) > 1000
    same = mod.compare(cfg, {"key": k, "wid": w, "value": v}, exp)
    assert all(c["ok"] for c in same)
    # delivered in any order: the check is by position
    order = np.random.default_rng(seed).permutation(len(k))
    same = mod.compare(cfg, {"key": k[order], "wid": w[order],
                             "value": v[order]}, exp)
    assert all(c["ok"] for c in same)


def test_control_py_reads_the_cell(capsys, monkeypatch):
    from benchmark import control
    cell = harness.resolve_cell(CELL)
    cfg = sized(cell)
    monkeypatch.setattr(harness, "resolve_cell", lambda name: dict(
        cell, config=cfg, mix=dict(cell["mix"], event_rate=100_000)))
    assert control.main(["--workload", CELL, "--tuples", "30000",
                         "--seeds", "5"]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["control"] == CELL and line["rows"] > 0
    bad = {c["name"] for c in line["checks"] if not c["ok"]}
    assert "key_wid_mismatches" in bad


# ---------------------------------------------------------------------------
# the manifest: this PR's entries present, the parent's unchanged
# ---------------------------------------------------------------------------

def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_manifest_holds_the_cell_as_the_issue_names_it():
    m = manifest()
    [cfg] = [c for c in m["configs"] if c["name"] == "nexmark_q20"]
    assert cfg["reduced"][0] == "retention" and cfg["source"].startswith(
        "NEXmark q20, expand bid with auction (nexmark-flink queries/q20.sql")
    assert cfg["file"] == "benchmark/configs/nexmark_q20.json"
    [cell] = [w for w in m["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("nexmark_q20", "saturated", 1)
    # at most 200 characters in every source and why, wherever they are
    assert all(len(c["source"]) <= 200 and len(c["why"]) <= 200
               for c in m["configs"])
    assert all(len(w["why"]) <= 200 for w in m["workloads"])
    # one four-chip cell: at most half the cells, rounded down
    assert [w["chips"] for w in m["workloads"]].count(4) == 1 \
        <= len(m["workloads"]) // 2
    at = {e["name"]: e for e in m["per_layer"]}
    for name in NEW_LAYERS:
        mine = dict(at[name])
        # the cell first; a later cell on the pair form may follow it
        assert mine.pop("workloads")[0] == CELL
        assert mine == {"name": name, "unit": "ms", "better": "lower",
                        "source": "device_trace",
                        "layer": "fused operator program",
                        "moves": "tuples_per_s"}
        assert os.path.isfile(os.path.join(
            ROOT, "benchmark", "layer_metrics", name + ".py"))
    lists = {e["name"] for e in m["end_to_end"] + m["per_layer"]
             if CELL in e.get("workloads", ())}
    # at least these (a later metric that reads something here may list
    # the cell too, and so may the three join_*_dev_ms_per_batch.sat of
    # PR 36 once the accepted test of nexmark_q9's cell lets them)
    assert lists >= TAKEN | set(NEW_LAYERS)
    resolved = harness.resolve_cell(CELL)
    assert resolved["mix"]["rate"] == "always_due"
    assert resolved["mix"]["chunk_bytes"] == 1048576
    assert resolved["config"]["step_program"] == "nexmark_q20_step"
    assert list(resolved["config"]["reduced"]) == cfg["reduced"] \
        == ["retention", "columns"]
    assert {e["name"] for e in resolved["end_to_end"]} \
        == {"tuples_per_s", "setup_s"}
    g = resolved["config"]["graph"]
    assert (g["batch"], g["max_keys"], g["lower_usec"], g["upper_usec"],
            g["probe_capacity"], g["out_capacity"]) \
        == (262144, 655360, 1_000, 10_000_000, 1024, 65536)


def test_every_entry_of_the_parents_manifest_is_there_unchanged():
    """Against ``BENCHMARK.json`` as the commit this PR starts from had
    it (kept as data beside the tests): every configuration, cell and
    metric it had is present, in its order and key for key, and a
    ``workloads`` list has at most grown at its end.  Entries a later PR
    appends do not break this."""
    with open(os.path.join(ROOT, "tests", "benchmark", "data",
                           "manifest_before_pr38.json")) as f:
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
    prog = harness.load_module("roofline", "nexmark_q20_step")
    least = prog.least_bytes(cell["config"])
    lanes = 262144 * 32
    auctions, pairs = 262144 * 3 // 50, 262144 * 46 // 50 // 5
    # the lanes read once, a table row an auction, a result row a pair
    assert lanes + auctions * 24 + pairs * 52 - 96 < least \
        < lanes + auctions * 24 + pairs * 52 + 96
    assert prog.MODULES == r"^jit_step_join_pairs$"
    assert re.search(prog.MODULES, "jit_step_join_pairs")
    # Q9's roofline reads the fold form's program and no other
    q9 = harness.load_module("roofline", "nexmark_q9_step")
    assert not re.search(q9.MODULES, "jit_step_join_pairs")
    assert not re.search(prog.MODULES, "jit_step_join")


def test_the_stream_is_what_the_configuration_says():
    """At the cell's own sizes, one seed: the bounded join is q20's on
    this stream, and the sizes the deployment states hold."""
    cell = harness.resolve_cell(CELL)
    mod, cfg = cell["config_module"], cell["config"]
    ring = mod.make_ring(2147484019, cfg)
    rec = ring["rec"]
    assert len(rec) == 40 * 262144
    auctions = rec[mod.KIND] == mod.AUCTION
    assert int(auctions.sum()) == 629_148
    # one bid in five pairs, a few per cent of the bids come first
    bids = int((rec[mod.KIND] == mod.BID).sum())
    assert 0.17 < ring["pairs_a_pass"] / bids < 0.23
    assert 150 <= ring["lead_reach_usec"] <= 210
    assert 40_000 < ring["max_pairs_a_batch"] <= 2 * 65_536
    assert set(np.unique(rec[mod.CATEGORY][auctions]).tolist()) \
        == {10.0, 11.0, 12.0, 13.0, 14.0}
    one = mod.one_pass(rec, cfg)
    before = (one.mine >= 0) & (one.tss < one.tss[np.maximum(one.mine, 0)])
    assert 0.03 < before.sum() / bids < 0.06
    # an unbounded retention would pair a bid with the pass before
    with pytest.raises(ValueError, match="a pass earlier"):
        mod.check_bounded_join(rec, harness.with_sizes(
            cfg, {"upper_usec": 10_300_000}))
    with pytest.raises(ValueError, match="outside"):
        mod.check_bounded_join(rec, harness.with_sizes(
            cfg, {"lower_usec": 50}))


def test_a_program_without_the_pair_form_is_refused_at_once(monkeypatch):
    import windflow_tpu as wf
    cell = harness.resolve_cell(CELL)
    monkeypatch.delattr(wf.Interval_JoinTPU_Builder, "withBoundaries")
    with pytest.raises(RuntimeError, match="row a matched pair"):
        cell["config_module"].make_ring(1, sized(cell))


# ---------------------------------------------------------------------------
# the reader
# ---------------------------------------------------------------------------

def phase_table(**secs):
    """A reduction as ``device_phases.reduce_planes`` gives it, with the
    seconds named by phase (in ``jit_step_join_pairs``, operator
    ``expand_bid``)."""
    rows = {("jit_step_join_pairs", "expand_bid",
             "wf." + ph.replace("_", ".")): (s, 0) for ph, s in secs.items()}
    rows[("jit_unpack_fn", None, "wf.unpack")] = (0.4, 0)
    busy = sum(s for s, _ in rows.values())
    return {"chips": 1, "busy_s": busy, "leaf_s": busy, "rows": rows,
            "unscoped_ops": {}, "unnamed_s": 0.0, "parts": {}, "runs": {}}


def test_the_metrics_read_the_pair_forms_phases(monkeypatch):
    red = phase_table(join_sort=0.08, join_match=0.32, join_carry=0.02,
                      join_close=0.1, join_table=0.25, fn=0.01)
    window = {"trace_dir": "somewhere", "batch": 1024,
              "trace0": {"pulled": 0}, "trace1": {"pulled": 100 * 1024}}
    monkeypatch.setattr(dp, "load", lambda w: red)
    match, carry, close, table = (reader(n) for n in JOIN_LAYERS)
    # ms per 1024 tuples pulled: 100 batches in the span
    assert table.read(None, {}, window) == pytest.approx(2.5)
    assert match.read(None, {}, window) == pytest.approx(4.0)
    assert carry.read(None, {}, window) == pytest.approx(0.2)
    assert close.read(None, {}, window) == pytest.approx(1.0)
    # the pair form's own three read the same phases in its module only
    p_match, p_carry, p_close = (reader(n) for n in PAIR_LAYERS)
    assert p_match.read(None, {}, window) == pytest.approx(4.0)
    assert p_carry.read(None, {}, window) == pytest.approx(0.2)
    assert p_close.read(None, {}, window) == pytest.approx(1.0)
    fold = dict(red, rows={("jit_step_join",) + k[1:]: v
                           for k, v in red["rows"].items()})
    monkeypatch.setattr(dp, "load", lambda w: fold)
    assert match.read(None, {}, window) == pytest.approx(4.0)
    assert [r.read(None, {}, window)
            for r in (p_match, p_carry, p_close)] == [None] * 3
    # a program without the phase (the parent; Q9's fold form), and an
    # untraced run: nothing to read, and nothing raised
    other = phase_table(join_match=0.5)
    monkeypatch.setattr(dp, "load", lambda w: other)
    assert table.read(None, {}, window) is None
    monkeypatch.undo()
    assert table.read(None, {}, {"trace_dir": None}) is None


def test_every_phase_a_metric_reads_is_declared_by_the_program():
    from windflow_tpu.monitoring import recorder
    reads = {}
    for name in NEW_LAYERS:
        with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                               name + ".py")) as f:
            tree = ast.parse(f.read())
        # the string constants that are a phase's name and nothing more
        reads[name] = {n.value for n in ast.walk(tree)
                       if isinstance(n, ast.Constant)
                       and isinstance(n.value, str)
                       and re.fullmatch(r"wf\.[a-z_.]+", n.value)}
        assert reads[name] <= set(recorder.PHASES)
        assert all(recorder.PHASES[ph][0] == "fused operator program"
                   for ph in reads[name])
    assert reads == {
        NEW_LAYER: {"wf.join.table"},
        PAIR_LAYERS[0]: {"wf.join.sort", "wf.join.match"},
        PAIR_LAYERS[1]: {"wf.join.carry"},
        PAIR_LAYERS[2]: {"wf.join.close"}}
