"""``nexmark_q5.saturated``: a rehearsal of the whole run in-process on
the CPU backend at tiny sizes, its control, its entries in the manifest,
and the readers of its three per-layer metrics on hand-made traces.  No
device metric is printed or asserted here."""

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

CELL = "nexmark_q5.saturated"
SIZES = dict(slide_usec=50_000, max_keys=512)     # window 100 ms: TINY
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
    # 10 000 events a window of 100 ms, a row a slide: many windows closed
    assert w["rows"] >= 8 and w["failed"] == 0 and w["attempted"] > 0
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
    # one row of 204 lanes a batch leaves the chip, not a grid of keys
    assert layer["d2h_bytes_per_tuple.sat"]["value"] < 16
    json.dumps(layer)


def test_broken_run_is_not_correct(window):
    """What the sink delivered, with one digest off by one and with one
    row lost, against the same reference."""
    cell = tiny_cell(CELL)
    mod, cfg = cell["config_module"], harness.with_sizes(
        cell["config"], {**window["config"]["graph"],
                         **window["config"]["stream"]})
    ring = mod.make_ring(2**31 + 17, cfg)
    exp = mod.expected(cfg, ring, window["n_total"], cell["mix"])
    ok = {"key": exp.key, "wid": exp.wid, "value": exp.value}
    assert all(c["ok"] for c in mod.compare(cfg, ok, exp))
    off = dict(ok, value=exp.value + (np.arange(len(exp.key)) == 3)[:, None]
               * np.array([0, 0, 1]))
    assert [c["name"] for c in mod.compare(cfg, off, exp) if not c["ok"]] \
        == ["count_mismatches"]
    lost = {k: v[:-1] for k, v in ok.items()}
    assert not all(c["ok"] for c in mod.compare(cfg, lost, exp))


@pytest.mark.parametrize("seed", [1, 2**31 + 17])
def test_the_control_fails_count_mismatches(seed):
    """Event time rounded to the millisecond moves the bids of a pane's
    last half millisecond into the next pane: the counts and the digest
    of auctions move, and the comparison says so.  At a length where the
    rounding opens no window of its own, so the rows line up."""
    cell = tiny_cell(CELL)
    mod = cell["config_module"]
    cfg = harness.with_sizes(cell["config"], {
        "batch": 1024, "ring_batches": 8, "window_usec": 100_000, **SIZES})
    ring = mod.make_ring(seed, cfg)
    n = 8 * 1024 * 5 + 2000
    exp = mod.expected(cfg, ring, n, cell["mix"])
    k, w, v = mod.control(cfg, ring, n, cell["mix"])
    checks = {c["name"]: c for c in mod.compare(
        cfg, {"key": k, "wid": w, "value": v}, exp)}
    assert checks["rows_missing_or_extra"]["ok"]
    assert not checks["count_mismatches"]["ok"]
    assert checks["count_mismatches"]["value"] >= 1
    assert checks["count_mismatches"]["limit"] == 0


def test_the_manifest_lists_the_cell_as_additions_only():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    assert [c["name"] for c in m["workloads"]][-1] == CELL
    assert len(m["workloads"]) == 4
    assert [c["chips"] for c in m["workloads"]].count(4) == 1
    assert m["configs"][-1]["name"] == "nexmark_q5" \
        and m["configs"][-1]["reduced"] == []
    assert len(m["configs"][-1]["source"]) <= 200
    new = [e["name"] for e in m["per_layer"][-3:]]
    assert new == ["window_out_lanes_per_batch.sat",
                   "placement_dev_share.sat", "stage2_dev_ms_per_batch.sat"]
    assert all(e["workloads"] == [CELL] and e["moves"] == "tuples_per_s"
               and e["layer"] == "fused operator program"
               for e in m["per_layer"][-3:])
    # every .sat metric that YSB's cell reports, this one reports too,
    # but two: the megastep's share (the scan stands down for a first
    # window stage that feeds a second on the device: nothing is read
    # there) and the codec's share, which test_wire_encoded_share.py
    # pins to YSB's cell alone (every edge ships raw here as there)
    ysb_only = {"megastep_share.sat", "wire_encoded_share.sat"}
    for e in m["end_to_end"] + m["per_layer"]:
        if "ysb.saturated" in e.get("workloads", ()):
            assert (e["workloads"][-1] == CELL) \
                == (e["name"] not in ysb_only), e["name"]
    cell = harness.resolve_cell(CELL)
    assert cell["chips"] == 1 and cell["mix"]["rate"] == "always_due"
    assert cell["config"]["step_program"] == "nexmark_q5_step"
    prog = harness.load_module("roofline", "nexmark_q5_step")
    least = prog.least_bytes(cell["config"])
    # the lanes read, and under one state pass a batch
    assert 262144 * 16 < least < 655360 * 66 * 8


# ---------------------------------------------------------------------------
# the readers
# ---------------------------------------------------------------------------

def spans(*rows):
    return [dict(op=op, batch=b, **({"out_cap": c} if c else {}))
            for op, b, c in rows]


def test_window_out_lanes_reads_the_first_stage():
    m = reader("window_out_lanes_per_batch.sat")
    two_stages = spans(("staging.unpack", 1, None),
                       ("bids_per_auction", 1, 1179648), ("hot_item", 1, 204),
                       ("hot_item_row", 1, None),
                       ("staging.unpack", 2, None),
                       ("bids_per_auction", 2, 1179648), ("hot_item", 2, 204))
    assert m.first_stage_lanes(two_stages) == 1179648
    # a ring regrown in the traced span: the mean over its dispatches
    assert m.first_stage_lanes(spans(("w", 1, 100), ("w", 2, 300))) == 200
    # a program whose dispatches note no capacity (the parent's)
    assert m.first_stage_lanes(spans(("staging.unpack", 1, None),
                                     ("campaign_counts", 1, None))) is None
    assert m.first_stage_lanes([]) is None
    # an untraced run, a run whose trace was not written, and the
    # recorded trace of a commit before out_cap existed
    assert m.read(None, {}, {"trace_dir": None}) is None
    assert m.read({}, {}, {"trace_dir": os.path.join(RECORDED, "none"),
                           "trace0": {"pulled": 0}}) is None
    window = {"trace_dir": RECORDED, "trace0": {"pulled": 0}}
    assert len(m.dispatch_spans(window)) > 0
    assert m.read({}, {}, window) is None


def modules(**secs):
    """A one-chip reduction whose programs ran for ``secs`` each."""
    at, events = 0.0, []
    for name, s in secs.items():
        events.append((f"{name}(123)", at * S, (at + s) * S, {}))
        at += s
    return trace_reduce.reduce_planes([(
        "/device:TPU:0", [(trace_reduce.MODULES_LINE, events)])])


def test_stage2_dev_ms_reads_the_later_stages_programs():
    m = reader("stage2_dev_ms_per_batch.sat")
    window = {"trace0": {"pulled": 0}, "trace1": {"pulled": 4 * 1024},
              "batch": 1024}
    red = modules(jit_step=0.4, jit_step_w2=0.2, jit_step_w3=0.04,
                  jit_unpack_fn=0.1, jit_step_w=0.5)
    assert m.read(red, {}, window) == pytest.approx(240.0 / 4)
    # one window stage, or a program that does not name its stages
    assert m.read(modules(jit_step=0.4, jit_mega=0.1), {}, window) is None
    assert m.read(None, {}, window) is None
    assert m.read(red, {}, dict(window, trace0=None)) is None
    # the roofline file counts both stages as the configuration's step
    prog = harness.load_module("roofline", "nexmark_q5_step")
    dev = reader("step_dev_ms_per_batch.sat")
    w = dict(window, config={"step_program": "nexmark_q5_step"})
    assert dev.step_seconds(red, w) == pytest.approx(0.6)
    assert prog.MODULES == r"^jit_(step|step_w2|mega)$"


SCATTER64 = ("%fusion.259 = (u32[43253826]{0:T(1024)}, u32[43253826]{0:T(1024)"
             "}) fusion(u32[43253826]{0:T(1024)} %broadcast.2748, u32[43253826]"
             "{0:T(1024)} %broadcast.2748.clone, s32[262144]{0:T(1024)S(1)} "
             "%broadcast_select_fusion.8, u32[262144]{0:T(1024)S(1)} %gte.770, "
             "u32[262144]{0:T(1024)S(1)} %broadcast.103), kind=kCustom, "
             "calls=%fused_computation.259")
SCATTER32 = ("%fusion.258 = s32[43253826]{0:T(1024)} fusion(s32[262144]{0:T("
             "1024)S(1)} %gte.704, s32[262144]{0:T(1024)S(1)} %gte.705, s32[]"
             "{:T(128)} %constant.203), kind=kCustom, calls=%fused_computation")
SCATTER_PRED = ("%fusion.72 = pred[130]{0:T(512)(128)(4,1)} fusion(s32[1179648]"
                "{0:T(1024)S(1)} %custom-call.189, pred[1179648]{0:T(1024)(128)"
                "(4,1)} %gte.1007, pred[]{:T(512)} %constant.2129), "
                "kind=kCustom, calls=%fused_computation.2336")
GATHER = ("%fusion.64 = u32[1179648]{0:T(1024)S(1)} fusion(u32[1179648]{0:T("
          "1024)} %gte.971, s32[1179648]{0:T(1024)S(1)} %fusion.566), "
          "kind=kCustom, calls=%fused_computation.64")
TABLE_GATHER = ("%fusion.2 = s32[262144]{0:T(1024)} fusion(s32[1024]{0:T(1024)"
                "S(1)} %copy-done, s32[262144]{0:T(1024)S(1)} %clamp.1), "
                "kind=kCustom, calls=%fused_computation.2")
LOOP = ("%add_select_fusion.12 = (u32[655360,66]{0,1:T(8,128)}, u32[655360,66]"
        "{0,1:T(8,128)}) fusion(u32[655361,66]{0,1:T(8,128)} %b, s32[655360,66]"
        "{0,1:T(8,128)} %c), kind=kLoop, calls=%fused_computation.9")
PLAIN = "%scatter.3 = f32[64]{0} scatter(f32[64]{0} %p, s32[8]{0} %i, f32[8] %u)"
SORT = ("%sort = (s32[262144]{0:T(1024)S(1)}, s32[262144]{0:T(1024)S(1)}) "
        "sort(s32[262144]{0:T(1024)S(1)} %copy-done.48), dimensions={0}")


@pytest.mark.parametrize("event,scatter", [
    (SCATTER64, True), (SCATTER32, True), (SCATTER_PRED, True),
    (PLAIN, True), (GATHER, False), (TABLE_GATHER, False), (LOOP, False),
    (SORT, False), ("jit_step(123)", False)])
def test_a_scatter_is_told_from_a_gather_by_its_shapes(event, scatter):
    assert reader("placement_dev_share.sat").is_scatter(event) is scatter


def test_placement_share_on_a_recorded_trace():
    """The recorded one-chip trace (``ffat_sum.steady``, PR 24) holds the
    count-based step's scatters: the share is read, above nothing and
    under the whole; a mesh trace and an untraced run give none."""
    m = reader("placement_dev_share.sat")
    path = trace_reduce.find_xplane(RECORDED)
    red = trace_reduce.reduce_planes(trace_reduce.read_planes(path))
    share = m.read(red, {}, {"trace_dir": RECORDED})
    assert 0.0 < share < 100.0
    assert share == pytest.approx(
        100.0 * m.scatter_seconds(path) / red["busy_s"])
    assert m.read(None, {}, {"trace_dir": RECORDED}) is None
    assert m.read(dict(red, devices={0: {}, 1: {}}), {},
                  {"trace_dir": RECORDED}) is None
    assert m.read(red, {}, {"trace_dir": os.path.join(RECORDED, "none")}) \
        is None
    assert m.read(red, {}, {}) is None
