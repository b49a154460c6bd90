"""``nexmark_q16.saturated``: a rehearsal of the whole run in-process on
the CPU backend at tiny sizes, a broken timed path, its control, its
entries in the manifest (present and as the issue names them, every
entry of the parent's manifest present and unchanged but for appended
cell names), its stream's shapes, its reference on a hand-made stream,
its roofline and the readers of its three per-layer metrics.  No device
metric is printed or asserted here, and no number that depends on the
host's speed."""

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
from benchmark.generator import frame_dtype  # noqa: E402
from test_bench_harness import run, tiny_cell  # noqa: E402

CELL = "nexmark_q16.saturated"
SIZES = dict(max_keys=64, cold_channels=40, bidder_space=256,
             auction_space=1024, out_capacity=64, active_people=4,
             hot_bidder_stride=8, event_rate=100_000)
NEW_LAYERS = ["distinct_dev_ms_per_batch.sat",
              "agg_fold_dev_ms_per_batch.sat", "distinct_new_share.sat"]
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
        "count_mismatches", "counter_mismatches", "dropped_tuples"}
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
    # the share of new members is the counters' own, and small: the ring
    # was replayed many times over
    c = cell["config_module"].LAST_COUNTERS
    assert c["Agg_rows_out"] == window["rows"]
    assert c["Agg_output_overflow"] == c["Agg_keys_refused"] == 0
    assert layer["distinct_new_share.sat"]["value"] == pytest.approx(
        100.0 * c["Agg_members_new"] / c["Agg_members_tested"])
    assert 0 < layer["distinct_new_share.sat"]["value"] < 10
    json.dumps(layer)


@pytest.mark.parametrize("fault", ["count_off", "member_twice", "rows_lost"])
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
                v[0, 1 if fault == "count_off" else 4] += 1
                cols = dict(cols, value=v)
            out[i] = (cols, tss)
        return out

    monkeypatch.setattr(batch, "device_to_columns_multi", broken)
    w = run(tiny_cell(CELL), seconds=0.3, **SIZES)
    assert calls["hit"] and not w["correct"]
    bad = {c["name"] for c in w["checks"] if not c["ok"]}
    assert bad == ({"count_mismatches"} if fault != "rows_lost" else
                   {"counter_mismatches"})


def test_an_aggregate_that_counts_a_member_again_is_not_correct(monkeypatch):
    """The deployment with sets that forget between steps (the tables
    emptied before every batch): every row is there, a member seen again
    is counted again, and the run is not ``correct`` by the numbers
    compared."""
    import jax.numpy as jnp

    from windflow_tpu.windows.rolling_tpu import RollingAggregateTPU
    real = RollingAggregateTPU._step

    def forgetful(self, batch):
        if self._state is not None:
            self._state = dict(self._state, sets=[
                jnp.zeros_like(t) for t in self._state["sets"]])
        return real(self, batch)

    monkeypatch.setattr(RollingAggregateTPU, "_step", forgetful)
    w = run(tiny_cell(CELL), seconds=0.3, **SIZES)
    assert not w["correct"]
    bad = {c["name"] for c in w["checks"] if not c["ok"]}
    assert bad == {"count_mismatches"}


@pytest.mark.parametrize("seed", [1, 2**31 + 17, 77])
def test_the_control_fails_by_the_numbers_compared(seed):
    """Sets that forget from pass to pass: every row is there under its
    own channel and total_bids, the distinct counts of every row past a
    channel's first pass differ, and the comparison says so; the
    reference in the program's place passes."""
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
    assert checks["count_mismatches"]["value"] > len(k) / 2
    assert all(c["limit"] == 0 for c in checks.values())
    exp = mod.expected(cfg, ring, n, cell["mix"])
    same = mod.compare(cfg, {"key": k, "wid": w, "value": exp.at(k, w)},
                       exp)
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
# the stream and the reference
# ---------------------------------------------------------------------------

def test_the_stream_has_the_sources_shapes():
    """At the cell's own sizes (one ring, no graph): half the bids
    through four channels, ~12 bids a cold channel a batch, three bids in
    four from the hot bidder, the price bands as published, every id
    inside the space the sets are built for."""
    cell = harness.resolve_cell(CELL)
    mod, cfg = cell["config_module"], cell["config"]
    cfg = harness.with_sizes(cfg, {"ring_batches": 2})
    rec = mod.make_ring(2**31 + 49, cfg)["rec"]
    assert rec.dtype == frame_dtype(5) and rec.dtype.itemsize == 56
    bid = rec[mod.KIND] == mod.BID
    assert bid.mean() == pytest.approx(46 / 50, abs=1e-4)
    chan = rec[mod.CHANNEL][bid].astype(np.int64)
    assert chan.min() == 0 and chan.max() == 10_003
    assert (chan < 4).mean() == pytest.approx(0.5, abs=0.005)
    per = np.bincount(chan, minlength=10_004)
    assert per[:4].min() > 0.12 * bid.sum() and per[4:].min() > 0
    assert per[4:].mean() / 2 == pytest.approx(12.05, abs=0.2)  # a batch
    who = rec[mod.BIDDER][bid].astype(np.int64) - mod.FIRST_PERSON_ID
    hot = (who % 100 == 1) & (who // 100 == np.flatnonzero(bid) // 5000)
    assert hot.mean() == pytest.approx(0.75, abs=0.01)
    assert 0 <= who.min() and who.max() < cfg["graph"]["bidder_space"]
    auction = rec["k"][bid] - mod.FIRST_AUCTION_ID
    assert 0 <= auction.min() \
        and auction.max() < cfg["graph"]["auction_space"]
    rank = mod.ranks_of(rec[mod.PRICE][bid].astype(np.int64))
    assert (rank == 0).mean() == pytest.approx(4 / 6, abs=0.01)
    assert (rank == 2).mean() < 1e-5                # nearly empty
    # another seed, another stream; the same seed, the same
    again = mod.make_ring(2**31 + 49, cfg)["rec"]
    other = mod.make_ring(2**31 + 50, cfg)["rec"]
    assert again.tobytes() == rec.tobytes() != other.tobytes()


def test_an_id_outside_its_space_is_refused_by_make_ring():
    cell = harness.resolve_cell(CELL)
    mod = cell["config_module"]
    cfg = harness.with_sizes(cell["config"], {
        "batch": 1024, "ring_batches": 4, **SIZES, "bidder_space": 8})
    with pytest.raises(ValueError, match=r"bidder outside \[0, 8\)"):
        mod.make_ring(5, cfg)
    cfg = harness.with_sizes(cell["config"], {
        "batch": 1024, "ring_batches": 4, **SIZES, "max_keys": 20})
    with pytest.raises(ValueError, match=r"channel outside \[0, 20\)"):
        mod.make_ring(5, cfg)


def hand_made():
    """Six bids and two bystanders; (channel, bidder, auction, price):
    channel 2 sees bidder 7 twice (low then high) and auction 30 twice."""
    mod = harness.resolve_cell(CELL)["config_module"]
    rec = np.zeros(8, frame_dtype(5))
    rows = [(2, 7, 30, 50), (5, 7, 30, 50), None, (2, 7, 31, 20_000),
            (2, 8, 30, 2_000_000), None, (5, 9, 32, 9_999),
            (2, 8, 30, 10_000)]
    for i, r in enumerate(rows):
        if r is None:
            rec[i][mod.KIND] = mod.AUCTION
            continue
        rec[i][mod.KIND] = mod.BID
        rec[i][mod.CHANNEL], who, what, rec[i][mod.PRICE] = r
        rec[i][mod.BIDDER] = who + mod.FIRST_PERSON_ID
        rec[i]["k"] = what + mod.FIRST_AUCTION_ID
    return mod, rec


def test_the_reference_on_a_hand_made_stream():
    mod, rec = hand_made()
    # two and a half passes, one event a microsecond
    exp = mod.ChannelStatistics(rec, 20, 8, 1_000_000)
    assert exp.total.tolist() == [0, 0, 10, 0, 0, 5, 0, 0]
    assert exp.key.tolist() == [2, 5]
    at = lambda c, n: exp.at(np.array([c]), np.array([n]))[0].tolist()  # noqa: E731
    #            minute r1 r2 r3 | bidders: all r1 r2 r3 | auctions
    assert at(2, 1) == [0, 1, 0, 0, 1, 1, 0, 0, 1, 1, 0, 0]
    assert at(2, 2) == [0, 1, 1, 0, 1, 1, 1, 0, 2, 1, 1, 0]
    assert at(2, 3) == [0, 1, 1, 1, 2, 1, 1, 1, 2, 1, 1, 1]
    assert at(2, 4) == [0, 1, 2, 1, 2, 1, 2, 1, 2, 1, 2, 1]
    # the second pass adds its bids and no member
    assert at(2, 5) == [0, 2, 2, 1, 2, 1, 2, 1, 2, 1, 2, 1]
    assert at(2, 10) == [0, 3, 5, 2, 2, 1, 2, 1, 2, 1, 2, 1]
    assert at(5, 2) == [0, 2, 0, 0, 2, 2, 0, 0, 2, 2, 0, 0]
    assert at(5, 4) == [0, 4, 0, 0, 2, 2, 0, 0, 2, 2, 0, 0]
    # the minute of the day of the bid's own event time
    late = mod.ChannelStatistics(rec, 20, 8, 1)     # an event a second
    assert late.at(np.array([2]), np.array([10]))[0][0] == 19 // 60
    slow = mod.ChannelStatistics(rec, 8 * 500, 8, 1)
    assert slow.at(np.array([2]), np.array([2000]))[0][0] == 3999 // 60


def test_the_comparison_holds_a_channel_to_rising_rows_and_its_total():
    mod, rec = hand_made()
    cfg = {"check": {"count_mismatches": 0}}
    good_k, good_n = np.array([2, 5, 2, 5, 2]), np.array([4, 2, 7, 5, 10])

    def verdict(k, n, tweak=None):
        exp = mod.ChannelStatistics(rec, 20, 8, 1_000_000)
        v = exp.at(k, n)
        if tweak:
            tweak(v)
        return {c["name"]: c["value"] for c in mod.compare(
            cfg, {"key": k, "wid": n, "value": v}, exp)}
    ok = verdict(good_k, good_n)
    assert set(ok.values()) == {0.0}
    # wherever the batches were cut: other rows of the same stream pass
    assert set(verdict(np.array([5, 2, 2, 5]),
                       np.array([1, 9, 10, 5])).values()) == {0.0}
    # a row again, rows out of order, a row past the stream's end
    assert verdict(np.array([2, 2, 5]), np.array([10, 10, 5]))[
        "key_wid_mismatches"] == 1
    assert verdict(good_k[::-1], good_n[::-1])["key_wid_mismatches"] == 3
    assert verdict(np.array([2, 5]), np.array([11, 5]))[
        "key_wid_mismatches"] == 1
    # a channel's last row short of its total; a channel without a row
    assert verdict(good_k[:-1], good_n[:-1])["rows_missing_or_extra"] == 1
    assert verdict(np.array([2]), np.array([10]))[
        "rows_missing_or_extra"] == 1
    assert verdict(np.zeros(0, int), np.zeros(0, int))[
        "result_rows_absent"] == 1

    def recount(v):
        v[2, 4] += 1
    assert verdict(good_k, good_n, recount)["count_mismatches"] == 1


# ---------------------------------------------------------------------------
# the manifest: this PR's entries present, the parent's unchanged
# ---------------------------------------------------------------------------

def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_manifest_holds_the_cell_as_the_issue_names_it():
    m = manifest()
    [cfg] = [c for c in m["configs"] if c["name"] == "nexmark_q16"]
    assert cfg["reduced"] == [] and cfg["source"] == (
        "NEXmark q16, channel statistics (nexmark-flink queries/q16.sql: "
        "bid GROUP BY channel, day; COUNT(*) and COUNT(DISTINCT "
        "bidder|auction) by price rank); generator: 4 hot channels 1/2, "
        "10 000 cold")
    assert cfg["file"] == "benchmark/configs/nexmark_q16.json"
    [cell] = [w for w in m["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("nexmark_q16", "saturated", 1)
    assert all(len(c["source"]) <= 200 and len(c["why"]) <= 200
               for c in m["configs"])
    assert all(len(w["why"]) <= 200 for w in m["workloads"])
    assert [w["chips"] for w in m["workloads"]].count(4) == 1 \
        <= len(m["workloads"]) // 2
    mine = {e["name"]: e for e in m["per_layer"] if e["name"] in NEW_LAYERS}
    assert list(mine) == NEW_LAYERS         # in this order, wherever
    want = {"distinct_dev_ms_per_batch.sat": ("ms", "lower",
                                              "device_trace"),
            "agg_fold_dev_ms_per_batch.sat": ("ms", "lower",
                                              "device_trace"),
            "distinct_new_share.sat": ("%", "lower", "program_counter")}
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
    resolved = harness.resolve_cell(CELL)
    assert resolved["mix"]["rate"] == "always_due"
    assert resolved["mix"]["chunk_bytes"] == 1048576
    assert resolved["mix"]["event_rate"] == 1_000_000
    assert resolved["config"]["step_program"] == "nexmark_q16_step"
    assert resolved["config"]["reduced"] == {}
    g = resolved["config"]["graph"]
    assert (g["batch"], g["max_keys"], g["bidder_space"],
            g["auction_space"], g["out_capacity"], g["mesh"]) \
        == (262144, 10240, 212992, 655360, 16384, 0)
    assert resolved["config"]["stream"]["ring_batches"] == 40
    assert {e["name"] for e in resolved["end_to_end"]} \
        == {"tuples_per_s", "setup_s"}


def test_every_entry_of_the_parents_manifest_is_there_unchanged():
    """Against ``BENCHMARK.json`` as the commit this PR starts from had
    it (kept as data beside the tests): every configuration, cell and
    metric it had is present, in its order and key for key, and a
    ``workloads`` list has at most grown at its end.  Entries a later PR
    appends do not break this."""
    with open(os.path.join(ROOT, "tests", "benchmark", "data",
                           "manifest_before_pr49.json")) as f:
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


def test_the_configuration_states_its_sets_as_built():
    from windflow_tpu.windows.rolling_kernels import DistinctGroup
    cfg = harness.resolve_cell(CELL)["config"]
    g = cfg["graph"]
    tables = [DistinctGroup(("t", "r1", "r2", "r3"), g[s])
              for s in ("bidder_space", "auction_space")]
    assert [t.bits for t in tables] == [4, 4]
    words = [g["max_keys"] * t.words_per_key for t in tables]
    assert words == [10240 * 26624, 10240 * 81920]
    assert sum(words) * 4 == 4_445_962_240      # 4.45 GB of sets
    assert "1.09 GB" in cfg["device_state"] \
        and "3.36 GB" in cfg["device_state"]
    assert len(cfg["guarantees"]) == 5 and cfg["assumed"]["replay"]


def test_the_roofline_counts_what_the_step_must_move():
    cell = harness.resolve_cell(CELL)
    prog = harness.load_module("roofline", "nexmark_q16_step")
    cfg = cell["config"]
    bids = 262144 * 46 / 50
    touched = prog.channels_touched(bids)
    assert 10_003 < touched < 10_004
    assert prog.least_bytes(cfg) == pytest.approx(
        262144 * 28             # the lanes read once
        + bids * 2 * 2 * 4      # a word read and written a member tested
        + touched * 2 * 17 * 4  # the touched channels' state
        + touched * 108)        # a row a touched channel
    # the sets are most of it, and all of it is little: the step is
    # bound by how many words it touches, not by how many bytes
    assert 0.25 < bids * 16 / prog.least_bytes(cfg) < 0.35
    assert re.search(prog.MODULES, "jit_step_rolling")
    assert not re.search(prog.MODULES, "jit_step")
    assert not re.search(prog.MODULES, "jit_step_session")


# ---------------------------------------------------------------------------
# the readers
# ---------------------------------------------------------------------------

def test_distinct_new_share_reads_the_aggregates_counters():
    share = reader("distinct_new_share.sat")
    assert share.share({"Agg_members_tested": 400,
                        "Agg_members_new": 50}) == 12.5
    assert share.share({"Agg_members_tested": 400,
                        "Agg_members_new": 0}) == 0.0
    assert share.share(None) is None and share.share({}) is None
    assert share.share({"Agg_members_tested": 0,
                        "Agg_members_new": 0}) is None
    # a program without the counters (the parent's operators have none)
    assert share.share({"CB_windows_fired": 4}) is None
    # a configuration that keeps no counters, one that does not exist
    assert share.read(None, {}, {"config": {"name": "ffat_sum"}}) is None
    assert share.read(None, {}, {"config": {"name": "no_such"}}) is None


def test_the_two_device_readers_read_their_phases(monkeypatch):
    distinct = reader("distinct_dev_ms_per_batch.sat")
    fold = reader("agg_fold_dev_ms_per_batch.sat")
    op = "channel_statistics"
    rows = {("jit_step_rolling", op, "wf.agg.distinct"): (0.9, 0),
            ("jit_step_rolling", op, "wf.agg.sort"): (0.1, 0),
            ("jit_step_rolling", op, "wf.agg.fold"): (0.2, 0),
            ("jit_step_rolling", op, "wf.agg.rows"): (0.05, 0),
            ("jit_step_rolling", "filter_tpu", "wf.fn"): (0.3, 0)}
    busy = sum(s for s, _ in rows.values())
    red = {"chips": 1, "busy_s": busy, "leaf_s": busy, "rows": rows,
           "unscoped_ops": {}, "unnamed_s": 0.0, "parts": {}, "runs": {}}
    window = {"trace_dir": "somewhere", "batch": 1024,
              "trace0": {"pulled": 0}, "trace1": {"pulled": 100 * 1024}}
    monkeypatch.setattr(dp, "load", lambda w: red)
    assert distinct.read(None, {}, window) == pytest.approx(9.0)
    assert fold.read(None, {}, window) == pytest.approx(3.5)
    # a program without the phases (the parent): nothing to read
    for k in [k for k in rows if k[2].startswith("wf.agg")]:
        del rows[k]
    assert distinct.read(None, {}, window) is None
    assert fold.read(None, {}, window) is None
    monkeypatch.undo()
    assert distinct.read(None, {}, {"trace_dir": None}) is None
    assert fold.read(None, {}, {"trace_dir": None}) is None
    from windflow_tpu.monitoring import recorder
    assert all(recorder.PHASES[p][0] == "fused operator program"
               for p in ("wf.agg.sort", "wf.agg.distinct", "wf.agg.fold",
                         "wf.agg.rows"))
