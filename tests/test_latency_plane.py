"""Latency plane (monitoring/latency_ledger.py): segment-sum honesty,
the SLO enter/latch/clear state machine, megastep shared_k accounting,
and the off-path micro-assert.

The honesty property is the plane's contract: the five critical-path
segments are a running-max boundary walk over each sampled trace's span
events, so their per-graph totals MUST telescope to the end-to-end
histogram's sum exactly — at every megastep K, with and without
map/filter fusion, with and without wire compression.  A decomposition
that does not sum to the whole is attributing latency that never
happened (or hiding latency that did), and the SLO verdict would
blame the wrong segment.
"""

import dataclasses
import warnings

import numpy as np
import pytest

import windflow_tpu as wf
from windflow_tpu.monitoring.latency_ledger import SEGMENTS, LatencyLedger

N = 4096
CAP = 256
KEYS = 8


# ---------------------------------------------------------------------------
# harness: the packed-frames source (the megastep-eligible edge shape,
# same staging as tests/test_megastep.py) feeding map -> filter -> window
# ---------------------------------------------------------------------------

def _frames_blob(n, nkeys=KEYS, seed=11):
    rng = np.random.default_rng(seed)
    rec = np.zeros(n, dtype=[("k", "<i8"), ("ts", "<i8"), ("v", "<f8")])
    rec["k"] = rng.integers(0, nkeys, n)
    rec["ts"] = np.arange(n, dtype=np.int64) * 500
    rec["v"] = rng.random(n)
    return rec.tobytes()


def _source(n=N, cap=CAP):
    blob = _frames_blob(n)
    step = cap * 24

    def chunks():
        for i in range(0, len(blob), step):
            yield blob[i:i + step]

    from windflow_tpu.io.frames import FrameSource
    return FrameSource(chunks, nv=1, fields=["v"], output_batch_size=cap)


def _traced_cfg(**kw):
    kw.setdefault("flight_recorder", True)
    kw.setdefault("trace_sample_every", 2)
    kw.setdefault("latency_ledger", True)
    kw.setdefault("key_compaction", False)
    return dataclasses.replace(wf.default_config, **kw)


def _graph(cfg, n=N, cap=CAP, fused=True, name="lat_app"):
    fired = []
    m = (wf.MapTPU_Builder(lambda t: {"key": t["key"], "v": t["v"] * 2.0})
         .withName("m").build())
    f = (wf.FilterTPU_Builder(lambda t: (t["key"] & 7) != 7)
         .withName("f").build())
    w = (wf.Ffat_WindowsTPU_Builder(lambda t: t["v"], lambda a, b: a + b)
         .withCBWindows(64, 32).withKeyBy(lambda t: t["key"])
         .withMaxKeys(KEYS).withName("win").build())
    snk = (wf.Sink_Builder(lambda r: fired.append(r) if r is not None
                           else None).withName("snk").build())
    g = wf.PipeGraph(name, config=cfg, time_policy=wf.TimePolicy.EVENT)
    pipe = g.add_source(_source(n, cap))
    pipe.add(m)
    if fused:
        pipe.chain(f)
    else:
        pipe.add(f)
    pipe.add(w).add_sink(snk)
    return g, fired


def _run(cfg, **kw):
    g, fired = _graph(cfg, **kw)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        g.run()
    return g, fired


# ---------------------------------------------------------------------------
# segment-sum honesty: the five segments telescope to the e2e span,
# exactly, at K=1/4/8 x fused/unfused x wire on/off
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("wire", [False, True],
                         ids=["wire_off", "wire_on"])
@pytest.mark.parametrize("fused", [True, False],
                         ids=["fused", "unfused"])
@pytest.mark.parametrize("k", [1, 4, 8])
def test_segment_sum_honesty(k, fused, wire):
    cfg = _traced_cfg(megastep_sweeps=k, wire_compression=wire)
    g, fired = _run(cfg, fused=fused)
    assert fired, "empty output proves nothing"
    lp = g.stats()["Latency_plane"]
    assert lp["enabled"]
    assert lp["traces_decomposed"] > 0
    assert lp["traces_dropped"] == 0
    assert lp["events_lost"] == 0
    # every trace is fully accounted: segment totals sum to the e2e
    # histogram sum (the boundary walk telescopes by construction)
    seg_sum = sum(lp["segments_total_usec"].values())
    e2e_sum = lp["e2e_usec"]["sum"]
    assert seg_sum == pytest.approx(e2e_sum, rel=1e-9, abs=0.5), \
        (k, fused, wire, lp["segments_total_usec"], lp["e2e_usec"])
    assert set(lp["segments_total_usec"]) == set(SEGMENTS)
    # per-op totals are the same decomposition grouped the other way
    per_op_sum = sum(e["total_usec"] for e in lp["per_op"].values())
    assert per_op_sum == pytest.approx(seg_sum, rel=1e-6, abs=0.5)
    shares = [e["budget_share"] for e in lp["per_op"].values()]
    assert all(0.0 <= s <= 1.0 for s in shares)
    assert sum(shares) == pytest.approx(1.0, abs=0.01)


# ---------------------------------------------------------------------------
# megastep accounting: shared_k traces, per-edge K, freshness floor
# ---------------------------------------------------------------------------

def test_megastep_shared_k_and_floor():
    cfg = _traced_cfg(megastep_sweeps=4, trace_sample_every=1)
    g, _ = _run(cfg, name="lat_ms_app")
    st = g.stats()
    edge = st["Megastep"]["edges"][0]
    assert edge["megasteps"] > 0, "megastep never assembled"
    lp = st["Latency_plane"]
    win = lp["per_op"]["win"]
    # traces that drained through a K-group carry shared_k: full wall
    # value in the histogram, 1/K credit in device_busy_usec
    assert win["shared_k_traces"] > 0
    assert win["megastep_k"] == 4
    assert win["freshness_floor_usec"] is None \
        or win["freshness_floor_usec"] >= 0
    dev = (win["segments_usec"].get("dispatched_to_device_done")
           or {}).get("sum", 0.0)
    assert win["device_busy_usec"] <= dev + 0.5


def test_freshness_gauge_populates():
    cfg = _traced_cfg(trace_sample_every=1)
    g, _ = _run(cfg, name="lat_fresh_app")
    win = g.stats()["Latency_plane"]["per_op"]["win"]
    fresh = win.get("freshness_usec")
    assert fresh is not None and fresh["count"] > 0


# ---------------------------------------------------------------------------
# SLO state machine: enter is immediate, the verdict latches, clear
# needs clear_after consecutive in-budget evaluations
# ---------------------------------------------------------------------------

class _NoRings:
    rings = ()


def _feed(led, e2e_usec, n, op="win", seg="emitted_to_dispatched"):
    for _ in range(n):
        led._recent.append((float(e2e_usec), [(op, seg, float(e2e_usec))]))


def test_slo_enter_latch_clear():
    led = LatencyLedger(_NoRings(), slo_ms=1.0, window=64,
                        clear_after=3, min_samples=8)
    # under min_samples: no evaluation at all
    _feed(led, 5000.0, 4)
    led.tick()
    assert not led.slo_active and led.verdict is None
    # enter: immediate once the window holds min_samples over budget
    _feed(led, 5000.0, 4)
    led.tick()
    assert led.slo_active and led.slo_entered == 1
    v = led.verdict
    assert v["state"] == "SLO_VIOLATED"
    assert v["dominant_op"] == "win"
    assert v["dominant_segment"] == "emitted_to_dispatched"
    assert "emitted→dispatched" in v["message"]
    assert v["budget_ms"] == 1.0
    # latch: still over, entered does not re-count
    led.tick()
    assert led.slo_active and led.slo_entered == 1
    # rotate the window to in-budget traces: one or two OK evaluations
    # must NOT clear (hysteresis), the third does
    led._recent.clear()
    _feed(led, 100.0, 16, seg="collected_to_sunk")
    led.tick()
    assert led.slo_active, "cleared after 1 OK tick"
    led.tick()
    assert led.slo_active, "cleared after 2 OK ticks"
    led.tick()
    assert not led.slo_active and led.slo_cleared == 1
    assert led.verdict is None
    assert led.last_verdict is not None  # forensics survive the clear
    # re-enter counts a fresh violation
    led._recent.clear()
    _feed(led, 9000.0, 8)
    led.tick()
    assert led.slo_active and led.slo_entered == 2


def test_slo_verdict_surfaces_in_health():
    # a sub-microsecond budget every real run violates instantly
    cfg = _traced_cfg(trace_sample_every=1, latency_slo_ms=0.001)
    g, _ = _graph(cfg, name="lat_slo_app")
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        g.start()
        while not g.is_done():
            if not g.step():
                break
            g.health_tick()
        g.wait_end()
        g.health_tick()
    st = g.stats()
    slo = st["Latency_plane"]["slo"]
    assert slo["active"] and slo["entered"] >= 1
    v = slo["verdict"]
    assert v is not None and v["state"] == "SLO_VIOLATED"
    assert v["dominant_op"] in st["Latency_plane"]["per_op"]
    assert v["dominant_segment"] in SEGMENTS
    # the health plane carries the verdict on the dominant op ONLY —
    # one slow op does not paint the whole graph red
    h = st["Health"]
    assert h["graph_state"] == "SLO_VIOLATED"
    for name, hv in h["verdicts"].items():
        if name == v["dominant_op"]:
            assert hv["state"] == "SLO_VIOLATED"
            assert hv["slo"]["message"] == v["message"]
        else:
            assert hv["state"] != "SLO_VIOLATED"
            assert "slo" not in hv


# ---------------------------------------------------------------------------
# off path: latency_ledger=False (or no recorder) means the plane is
# never built — one `is not None` check is the whole cost
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cfg_kw", [
    {"latency_ledger": False},
    {"flight_recorder": False},
], ids=["ledger_off", "recorder_off"])
def test_off_path_never_builds(cfg_kw):
    cfg = _traced_cfg(**cfg_kw)
    g, fired = _run(cfg, name="lat_off_app")
    assert fired
    assert g._latency is None
    assert all(getattr(rep, "latency", None) is None
               for rep in g._all_replicas)
    assert g.stats()["Latency_plane"] == {"enabled": False}
