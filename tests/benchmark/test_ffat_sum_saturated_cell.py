"""``ffat_sum.saturated``, the first queued cell.  PR 44 measured it for
entry and LEFT IT OUT: its runs split in two by whether the megastep
edge holds its K-groups (``PERF.md`` sections 6 and 7: ~9.3 M tuples/s
where 89 % of the batches are scanned, ~11.2 M where a quarter are, on
either side of the PR), so it stays queued (``tests/benchmark/
queued_cells.py`` builds the manifest that lists it).  Kept here so
that the cell is ready for the issue that enters it: a rehearsal of the
whole run in-process on the CPU backend at tiny sizes, a broken timed
path, its control, what its entries will be, and its roofline.  No
device metric is printed or asserted here, and no number that depends
on the host's speed."""

import json
import os
import re
import sys

import numpy as np
import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402
from test_bench_harness import TINY, run, tiny_cell  # noqa: E402

CELL = "ffat_sum.saturated"
#: the accepted lists whose readers returned a number on the cell's
#: traced chip runs of PR 44, both sides (PERF.md section 6): the lists
#: an entry appends the cell's name to
READ_ON_THE_CHIP = {
    "throttle_share.sat", "h2d_bytes_per_tuple.sat", "megastep_share.sat",
    "compiles_in_window.sat", "step_dev_ms_per_batch.sat",
    "step_hbm_roofline.sat", "d2h_bytes_per_tuple.sat", "device_idle.sat",
    "parse_host_ms_per_batch.sat", "pack_host_ms_per_batch.sat",
    "encode_host_ms_per_batch.sat", "h2d_host_ms_per_batch.sat",
    "unpack_dispatch_host_ms_per_batch.sat",
    "step_dispatch_host_ms_per_batch.sat", "sink_host_ms_per_batch.sat",
    "sweep_self_ms_per_batch.sat", "batch_fill_share.sat",
    "idle_unattributed_share.sat", "mosaic_dev_share.sat",
    "window_out_lanes_per_batch.sat", "unpack_dev_ms_per_batch.sat",
    "operator_fn_dev_ms_per_batch.sat", "place_dev_ms_per_batch.sat",
    "ring_pass_dev_ms_per_batch.sat", "fire_dev_ms_per_batch.sat",
    "group_dev_ms_per_batch.sat", "unscoped_dev_share.sat"}


def listed(name=CELL):
    """The cell through the manifest that lists it (the queued one,
    until ``BENCHMARK.json`` does)."""
    return tiny_cell(name)


@pytest.fixture(scope="module")
def window():
    return run(listed(), seconds=0.5)


def test_cell_runs_and_every_row_is_checked(window):
    w = window
    assert w["correct"], w["checks"]
    assert {c["name"] for c in w["checks"]} == {
        "rows_missing_or_extra", "key_wid_mismatches", "result_rows_absent",
        "sum_max_rel_err", "dropped_tuples"}
    assert w["rows"] > 100 and w["failed"] == 0 and w["attempted"] > 0
    assert w["open"]["pulled"] >= harness.WARMUP_MIN_BATCHES * 1024
    assert w["n_total"] == w["open"]["pulled"] + w["tuples_in_window"]
    assert w["t_open"] < w["t_stop"] <= w["t_last_delivery"]
    # an always-due mix: the throughput cell reads no latency
    assert w["latencies_ms"] is None


def test_cell_reports_its_metrics(window):
    cell = listed()
    e2e = harness.read_metrics(cell, cell["end_to_end"], "end_to_end", None,
                               window)
    assert set(e2e) == {"tuples_per_s", "setup_s"}
    assert all(v["value"] > 0 for v in e2e.values())
    layer = harness.read_metrics(cell, cell["per_layer"], "layer_metrics",
                                 None, window)
    sources = {m["name"]: m["source"] for m in cell["per_layer"]}
    assert layer and all(sources[k] == "program_counter" for k in layer)
    # every staged batch either ran inside a scan or was dispatched
    # alone; on the CPU backend no plane is built (K = 1): nothing to read
    share = harness.load_module("layer_metrics", "megastep_share.sat")
    assert "megastep_share.sat" not in layer
    assert share.read(None, {"megastep_scanned": 0,
                             "megastep_per_batch": 0}, window) is None
    assert share.read(None, {"megastep_scanned": 0,
                             "megastep_per_batch": 40}, window) == 0.0
    assert share.read(None, {"megastep_scanned": 32,
                             "megastep_per_batch": 8}, window) == 80.0
    # the egress copies lanes, not tuples pulled: a fired row is a key,
    # a window id, a sum, a stamp and a flag
    lanes = 1024 // (TINY["win"] // (TINY["win"] // TINY["slide"])) \
        + 2 * TINY["n_keys"] + 8
    assert window["close"]["d2h_bytes"] - window["open"]["d2h_bytes"] \
        <= (window["close"]["sweeps"] + 64) * lanes * 32
    json.dumps(layer)


def test_the_cell_resolves_with_the_issues_traffic_and_sizes():
    cell = listed()
    assert cell["chips"] == 1
    assert cell["mix"] == {**cell["mix"], "rate": "always_due",
                           "chunk_bytes": 4096, "event_rate": 100_000}
    resolved = harness.resolve_cell(CELL, tiny_root())
    assert resolved["mix"] == {**resolved["mix"], "rate": "always_due",
                               "chunk_bytes": 1048576,
                               "event_rate": 1000000}
    g = resolved["config"]["graph"]
    assert (g["batch"], g["n_keys"], g["win"], g["slide"], g["mesh"]) \
        == (262144, 1024, 1024, 128, 0)
    assert {e["name"] for e in resolved["end_to_end"]} \
        == {"tuples_per_s", "setup_s"}
    # every list whose reader read a number on the chip has a reader file
    for name in READ_ON_THE_CHIP:
        assert os.path.isfile(os.path.join(
            ROOT, "benchmark", "layer_metrics", name + ".py")), name
    # never a list an accepted test pins to other cells
    assert not READ_ON_THE_CHIP & {
        "wire_encoded_share.sat", "join_match_dev_ms_per_batch.sat",
        "join_carry_dev_ms_per_batch.sat", "join_close_dev_ms_per_batch.sat"}


def tiny_root():
    from queued_cells import root_of
    return root_of(CELL)


@pytest.mark.parametrize("fault", ["sum_off", "rows_lost"])
def test_broken_timed_path_is_not_correct(monkeypatch, fault):
    """The rest of a run with the timed path broken underneath: one
    delivered batch is altered where the program hands it to the sink."""
    from windflow_tpu import batch
    real = batch.device_to_columns_multi
    calls = {"hit": 0}

    def broken(batches):
        out = list(real(batches))
        for i, (cols, tss) in enumerate(out):
            if calls["hit"] or not len(tss):
                continue
            calls["hit"] = 1
            if fault == "rows_lost":
                cols = {k: np.asarray(v)[:-1] for k, v in cols.items()}
                tss = tss[:-1]
            else:
                v = np.array(cols["value"])
                v[0] *= 1.01
                cols = dict(cols, value=v)
            out[i] = (cols, tss)
        return out

    monkeypatch.setattr(batch, "device_to_columns_multi", broken)
    w = run(listed(), seconds=0.3)
    assert calls["hit"] and not w["correct"]
    bad = {c["name"] for c in w["checks"] if not c["ok"]}
    assert bad == ({"sum_max_rel_err"} if fault == "sum_off" else
                   {"rows_missing_or_extra", "key_wid_mismatches",
                    "sum_max_rel_err"})


@pytest.mark.parametrize("seed", [1, 2**31 + 17])
def test_the_control_fails_by_the_numbers_compared(seed):
    """The reference with a bfloat16 value lane in the program's place is
    not ``correct`` by the sum's limit and by nothing else; the
    reference itself passes."""
    cell = listed()
    mod = cell["config_module"]
    cfg = harness.with_sizes(cell["config"], TINY)
    ring = mod.make_ring(seed, cfg)
    n = 8 * 1024 * 3 + 500
    exp = mod.expected(cfg, ring, n, cell["mix"])
    k, w, v = mod.control(cfg, ring, n, cell["mix"])
    checks = {c["name"]: c for c in mod.compare(
        cfg, {"key": k, "wid": w, "value": v}, exp)}
    assert [n for n, c in checks.items() if not c["ok"]] \
        == ["sum_max_rel_err"]
    same = mod.compare(cfg, {"key": exp.key, "wid": exp.wid,
                             "value": exp.value.astype(np.float32)}, exp)
    assert all(c["ok"] for c in same)


# ---------------------------------------------------------------------------
# the manifest: PR 44 lists no entry of this cell
# ---------------------------------------------------------------------------

def test_pr44_left_the_parents_manifest_as_it_was_for_this_cell():
    """PR 44's own additions (``nexmark_q6``) stand beside every entry
    of the parent's manifest, unchanged; whether this cell is listed is
    a later issue's business and is not pinned here."""
    with open(os.path.join(ROOT, "tests", "benchmark", "data",
                           "manifest_before_pr44.json")) as f:
        old = json.load(f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        new = json.load(f)
    assert CELL not in [w["name"] for w in old["workloads"]]
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        at = {e["name"]: e for e in new[group]}
        for e in old[group]:
            mine = dict(at[e["name"]])
            if "workloads" in e:
                had = e["workloads"]
                assert mine["workloads"][:len(had)] == had, e["name"]
                mine["workloads"] = had
            assert mine == e, e["name"]


def test_the_roofline_counts_the_lanes_the_state_and_the_fired_rows():
    cell = harness.resolve_cell(CELL, tiny_root())
    prog = harness.load_module(
        "roofline", cell["config"]["step_program"])
    lanes_in, state, fired = prog.parts(cell["config"])
    assert lanes_in == 262144 * 16
    assert state == 1024 * (8 * 4 + 8)
    assert fired == 262144 * 7 / 8 / 128 * 24
    assert prog.least_bytes(cell["config"]) == lanes_in + 2 * state + fired
    # one batch alone, or K of them in the megastep's scan
    assert re.search(prog.MODULES, "jit_step")
    assert re.search(prog.MODULES, "jit_mega")
    assert not re.search(prog.MODULES, "jit_step_w2")
