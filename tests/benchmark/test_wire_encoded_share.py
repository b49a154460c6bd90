"""``wire_encoded_share.*``: how often the wire codec engaged, read from
the counts ``harness.counters`` already takes.  Rehearsed on the CPU
backend at a tiny size; the share itself is a count, not a device
metric."""

import pytest

from benchmark import harness
from test_bench_harness import run, tiny_cell

READERS = [("wire_encoded_share.sat", "ysb.saturated", "tuples_per_s"),
           ("wire_encoded_share.steady", "ffat_sum.steady",
            "latency_p95_ms")]


@pytest.mark.parametrize("name,cell,moves", READERS)
def test_the_reader_is_listed_with_its_cell(name, cell, moves):
    entry = [m for m in harness.resolve_cell(cell)["per_layer"]
             if m["name"] == name]
    assert len(entry) == 1
    assert entry[0]["workloads"] == [cell] and entry[0]["moves"] == moves
    assert entry[0]["source"] == "program_counter"
    assert entry[0]["layer"] == "staging: pack, wire encode, H2D"
    other = [c for _n, c, _m in READERS if c != cell][0]
    assert name not in {m["name"] for m in
                        harness.resolve_cell(other)["per_layer"]}


@pytest.mark.parametrize("name,cell,_moves", READERS)
def test_the_reader_divides_encoded_by_all_staged(name, cell, _moves):
    read = harness.load_module("layer_metrics", name).read
    assert read(None, {"wire_batches": 0, "wire_raw_batches": 40}, {}) == 0.0
    assert read(None, {"wire_batches": 30, "wire_raw_batches": 10},
                {}) == 75.0
    assert read(None, {"wire_batches": 8, "wire_raw_batches": 0},
                {}) == 100.0
    # nothing packed was staged (a mesh), or a program without the count
    assert read(None, {"wire_batches": 0, "wire_raw_batches": 0}, {}) is None
    assert read(None, {}, {}) is None


@pytest.mark.parametrize("forced,share", [(False, 0.0), (True, 100.0)])
def test_a_rehearsed_window_reads_the_share(monkeypatch, forced, share):
    """From ``harness.counters``' delta over a whole tiny run: under
    "auto" with the plane attached the edge times its link (a memcpy
    here), decides raw and the share reads 0; with the codec forced
    every batch counts as encoded."""
    from windflow_tpu import wire
    monkeypatch.setattr(wire, "wire_enabled", lambda cfg: True)
    if forced:
        monkeypatch.setattr(wire, "_wire_setting", lambda cfg: True)
    else:
        # the CPU backend's "link" is a memcpy: name its rate, so the
        # rehearsal does not rest on a host's timing
        from windflow_tpu import staging
        monkeypatch.setattr(staging.StagingPool, "link_rate",
                            lambda self, nwords: 1e12)
    w = run(tiny_cell("ffat_sum.steady", rate=40_000), seconds=1.0)
    assert w["correct"], w["checks"]
    stats = harness.delta(w["open"], w["close"])
    assert stats["wire_batches"] + stats["wire_raw_batches"] > 0
    cell = tiny_cell("ffat_sum.steady")
    got = harness.read_metrics(
        cell, [m for m in cell["per_layer"]
               if m["name"].startswith("wire_encoded_share")],
        "layer_metrics", None, w)
    assert got == {"wire_encoded_share.steady":
                   {"value": share, "unit": "%"}}
    # the staged-batch count that batch_span_ms.steady divides by is
    # there whichever way the edge decided
    assert harness.load_module("layer_metrics", "batch_span_ms.steady") \
        .read(None, stats, w) > 0
