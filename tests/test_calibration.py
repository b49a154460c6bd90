"""Calibration plane (monitoring/calibration.py): the provenance
vocabulary on every surfaced modeled number, the calibration store's
load/degrade contract (device-kind gate, TTL staleness, kill switch),
the live roofline ledger's rate accounting + ROOFLINE_DEGRADED
enter/latch/clear hysteresis, the OpenMetrics/postmortem surfaces, the
wf_calibrate --check exit codes, and the off-path micro-assert.

The honesty property is the plane's contract: a number computed from a
constant must say so (``modeled``), a probe-measured replacement must
carry its age (``calibrated(<age>)``) and must DEGRADE back to the
modeled default — loudly, once — when it goes stale or was recorded on
different hardware.  A dead measurement silently outranking a live
model is exactly the failure mode this plane exists to kill.
"""

import dataclasses
import json
import os
import subprocess
import sys
import time
import types
import warnings

import numpy as np
import pytest

import windflow_tpu as wf
from windflow_tpu.monitoring import calibration as cal

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

N = 4096
CAP = 256
KEYS = 8


@pytest.fixture(autouse=True)
def _clean_store():
    """Every test starts and ends uncalibrated: the default store is
    process-global (that is its point), so leakage between tests would
    flip provenance tags in unrelated suites."""
    cal.set_default_store(None)
    yield
    cal.set_default_store(None)


def _store_doc(recorded_at=None, device_kind=None, constants=None,
               jax_version="0.0-test"):
    return {
        "schema": cal.SCHEMA,
        "recorded_at": time.time() if recorded_at is None else recorded_at,
        "device_kind": device_kind or cal.live_device_kind() or "cpu",
        "backend": "cpu",
        "jax_version": jax_version,
        "constants": constants or {
            "ici_bytes_per_sec": 42e9,
            "h2d_bytes_per_sec": 1e9,
            "hbm_bytes_per_sec": 5e9,
            "dispatch_overhead_usec": 8.0,
            "sampled_sync_usec": 2.0,
            "kernel_step_usec": 500.0,
        },
    }


def _install(**kw):
    store = cal.CalibrationStore(_store_doc(**kw), path="<test>")
    cal.set_default_store(store)
    return store


# ---------------------------------------------------------------------------
# harness: the latency-plane pipeline (packed frames -> map -> filter ->
# window), driven with health_tick per sweep so the roofline ring fills
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


def _cfg(**kw):
    kw.setdefault("key_compaction", False)
    return dataclasses.replace(wf.default_config, **kw)


def _graph(cfg, n=N, cap=CAP, name="cal_app"):
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
    g.add_source(_source(n, cap)).add(m).add(f).add(w).add_sink(snk)
    return g, fired


def _drive(g):
    """step + health_tick per sweep (the monitor cadence, worst case)."""
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        g.start()
        while not g.is_done():
            if not g.step():
                break
            g.health_tick()
        g.wait_end()
        g.health_tick()


# ---------------------------------------------------------------------------
# provenance vocabulary + store validation
# ---------------------------------------------------------------------------

def test_calibrated_tag_ages_and_vocabulary():
    assert cal.calibrated_tag(90) == "calibrated(90s)"
    assert cal.calibrated_tag(2 * 3600) == "calibrated(2h)"
    assert cal.calibrated_tag(3 * 86400) == "calibrated(3d)"
    for tag in ("measured", "modeled", "interpret",
                cal.calibrated_tag(5)):
        assert cal.legal_provenance(tag), tag
    for tag in ("guessed", "", None, 1.0, "calibrated"):
        assert not cal.legal_provenance(tag), tag


@pytest.mark.parametrize("mutate, msg", [
    (lambda d: d.update(schema="wf-calibration/999"), "schema"),
    (lambda d: d.update(recorded_at="yesterday"), "recorded_at"),
    (lambda d: d.update(device_kind=""), "device_kind"),
    (lambda d: d.update(jax_version=None), "jax_version"),
    (lambda d: d.update(constants={}), "constants"),
    (lambda d: d["constants"].update(warp_drive_factor=9.0), "unknown"),
    (lambda d: d["constants"].update(hbm_bytes_per_sec=float("nan")),
     "finite"),
    (lambda d: d["constants"].update(hbm_bytes_per_sec=-1.0), "finite"),
], ids=["schema", "recorded_at", "device_kind", "jax_version",
        "empty_constants", "unknown_key", "nan", "negative"])
def test_corrupt_store_rejected(mutate, msg):
    doc = _store_doc()
    mutate(doc)
    with pytest.raises(cal.CalibrationError):
        cal.CalibrationStore(doc)


def test_corrupt_file_degrades_graph_build_with_warning(tmp_path):
    bad = tmp_path / "cal.json"
    bad.write_text("{not json")
    cfg = _cfg(calibration=str(bad))
    g, _ = _graph(cfg, n=512, name="cal_bad_app")
    with pytest.warns(RuntimeWarning, match="running uncalibrated"):
        g.start()                       # _build() loads the store
    while not g.is_done():
        if not g.step():
            break
    g.wait_end()
    # the process stays on its modeled defaults
    v, prov = cal.constant("ici_bytes_per_sec")
    assert prov == "modeled"
    assert v == cal.MODELED_DEFAULTS["ici_bytes_per_sec"]


# ---------------------------------------------------------------------------
# constant(): the calibrated round trip and every degrade path
# ---------------------------------------------------------------------------

def test_constant_round_trip_flips_value_and_tag():
    v, prov = cal.constant("ici_bytes_per_sec")
    assert prov == "modeled"
    assert v == cal.MODELED_DEFAULTS["ici_bytes_per_sec"]
    _install()
    v, prov = cal.constant("ici_bytes_per_sec")
    assert v == 42e9
    assert cal.is_calibrated(prov)
    # no modeled default exists for the H2D rate: measured or absent
    v, prov = cal.constant("h2d_bytes_per_sec")
    assert (v, cal.is_calibrated(prov)) == (1e9, True)
    # clearing the store restores the modeled default
    cal.set_default_store(None)
    v, prov = cal.constant("ici_bytes_per_sec")
    assert prov == "modeled"
    assert v == cal.MODELED_DEFAULTS["ici_bytes_per_sec"]
    assert cal.constant("h2d_bytes_per_sec") == (None, None)


def test_hbm_peak_is_keyed_by_device_kind(monkeypatch):
    """The roofline ceiling is the PUBLISHED peak of the live device
    kind; a kind nobody published a figure for has no ceiling at all —
    never another chip's number."""
    assert cal.live_device_kind() not in cal.HBM_PEAK_BYTES_PER_SEC
    assert cal.constant("hbm_bytes_per_sec") == (None, None)
    assert "hbm_bytes_per_sec" not in cal.provenance_summary()["constants"]
    monkeypatch.setattr(cal, "_device_kind_cache", "TPU v5 lite")
    assert cal.constant("hbm_bytes_per_sec") == (819e9, "modeled")
    monkeypatch.setattr(cal, "_device_kind_cache", "TPU v99")
    assert cal.constant("hbm_bytes_per_sec") == (None, None)


def test_constant_missing_key_stays_modeled():
    _install(constants={"hbm_bytes_per_sec": 5e9})
    v, prov = cal.constant("dispatch_overhead_usec")
    assert prov == "modeled"
    assert v == cal.MODELED_DEFAULTS["dispatch_overhead_usec"]


def test_device_kind_mismatch_degrades_with_one_warning():
    _install(device_kind="TPU v99")
    with warnings.catch_warnings(record=True) as wlog:
        warnings.simplefilter("always")
        v, prov = cal.constant("dispatch_overhead_usec")
        v2, prov2 = cal.constant("ici_bytes_per_sec")
    assert prov == prov2 == "modeled"
    assert v == cal.MODELED_DEFAULTS["dispatch_overhead_usec"]
    kind_warns = [w for w in wlog if "device kind" in str(w.message)]
    assert len(kind_warns) == 1, "the mismatch warning must fire ONCE"


def test_ttl_staleness_degrades_with_one_warning():
    _install(recorded_at=time.time() - cal.TTL_S - 3600)
    with warnings.catch_warnings(record=True) as wlog:
        warnings.simplefilter("always")
        v, prov = cal.constant("ici_bytes_per_sec")
        v2, _ = cal.constant("ici_bytes_per_sec")
    assert prov == "modeled"
    assert v == v2 == cal.MODELED_DEFAULTS["ici_bytes_per_sec"]
    stale = [w for w in wlog if "days old" in str(w.message)]
    assert len(stale) == 1, "the staleness warning must fire ONCE"
    # freshness is judged at read time: the SAME store read with a
    # clock inside the TTL serves the calibrated value
    v, prov = cal.constant("ici_bytes_per_sec",
                           now=time.time() - cal.TTL_S - 3000)
    assert (v, cal.is_calibrated(prov)) == (42e9, True)


def test_kill_switch_blocks_config_load(tmp_path, monkeypatch):
    path = tmp_path / "cal.json"
    path.write_text(json.dumps(_store_doc()))
    monkeypatch.setenv("WF_TPU_CALIBRATION", "0")
    assert cal.killed()
    g, _ = _graph(_cfg(calibration=str(path)), n=512, name="cal_kill_app")
    assert cal.default_store() is None
    _, prov = cal.constant("ici_bytes_per_sec")
    assert prov == "modeled"


def test_provenance_summary_shape():
    _install()
    s = cal.provenance_summary()
    assert s["schema"] == cal.SCHEMA
    assert s["enabled"] is True
    assert set(s["constants"]) == set(cal.MODELED_DEFAULTS)
    for key, slot in s["constants"].items():
        assert cal.legal_provenance(slot["provenance"]), key
        assert cal.is_calibrated(slot["provenance"]), key
    assert s["store"]["fresh"] is True


# ---------------------------------------------------------------------------
# provenance threads through stats(): sweep bytes, shard ICI, tenant
# ICI — and the calibrated store flips the bandwidth tags
# ---------------------------------------------------------------------------

def test_sweep_section_bytes_carry_provenance():
    g, fired = _graph(_cfg(), name="cal_sweep_app")
    _drive(g)
    assert fired
    sweep = g.stats()["Sweep"]
    assert sweep["totals"]["bytes_provenance"] == "modeled"
    hops = [h for h in sweep["per_hop"].values()
            if "bytes_per_tuple" in h]
    assert hops, "no hop attributed bytes"
    for h in hops:
        assert h["bytes_provenance"] == "modeled"
    wire = sweep.get("wire")
    if wire:
        assert wire["bytes_provenance"] == "measured"


def _mesh_graph(n_keys=16):
    from windflow_tpu.parallel import mesh as M
    mesh = M.make_mesh(8, data=2)
    cfg = dataclasses.replace(wf.default_config, mesh=mesh)
    rng = np.random.default_rng(3)
    ks = rng.integers(0, n_keys, 8 * 128)
    src = (wf.Source_Builder(lambda: iter(
        {"key": int(k), "v": float(i)} for i, k in enumerate(ks)))
        .withOutputBatchSize(128).build())
    win = (wf.Ffat_WindowsTPU_Builder(lambda t: t["v"],
                                      lambda a, b: a + b)
           .withCBWindows(8, 4).withKeyBy(lambda t: t["key"])
           .withMaxKeys(n_keys).withName("mwin").build())
    g = wf.PipeGraph("cal_mesh", wf.ExecutionMode.DEFAULT, config=cfg)
    g.add_source(src).add(win).add_sink(
        wf.Sink_Builder(lambda r: None).build())
    return g


def test_shard_ici_model_provenance_flips_calibrated():
    g = _mesh_graph()
    g.run()
    sec = g.stats()["Shard"]
    ici = sec["per_op"]["mwin"]["ici"]
    # uncalibrated: the structural model divides by the modeled default
    assert ici["provenance"] == "modeled"
    assert ici["ici_bandwidth_provenance"] == "modeled"
    assert ici["ici_bandwidth_assumed_bps"] == \
        cal.MODELED_DEFAULTS["ici_bytes_per_sec"]
    assert sec["totals"]["ici_provenance"] == "modeled"
    assert sec["totals"]["ici_time_provenance"] == "modeled"
    usec_modeled = ici["ici_usec_per_dispatch"]
    # calibrated: the TIME column flips tag AND value; the BYTES half
    # stays structural (the collective shape is derived, not measured)
    _install()
    sec = g.stats()["Shard"]
    ici = sec["per_op"]["mwin"]["ici"]
    assert cal.is_calibrated(ici["ici_bandwidth_provenance"])
    assert ici["ici_bandwidth_assumed_bps"] == 42e9
    assert ici["provenance"] == "modeled"
    assert cal.is_calibrated(sec["totals"]["ici_time_provenance"])
    assert sec["totals"]["ici_provenance"] == "modeled"
    # both readings are rounded to 3 decimals, so compare loosely —
    # the point is the value moved WITH the bandwidth, 90e9 -> 42e9
    expected = usec_modeled * cal.MODELED_DEFAULTS["ici_bytes_per_sec"] \
        / 42e9
    assert ici["ici_usec_per_dispatch"] == pytest.approx(expected,
                                                         rel=0.10)
    assert ici["ici_usec_per_dispatch"] > usec_modeled


def test_tenant_rows_carry_ici_provenance():
    from windflow_tpu.monitoring.tenant_ledger import default_ledger
    default_ledger().reset()
    g = _mesh_graph()
    g.config.tenant = "cal_tenant"
    g.run()
    ten = g.stats()["Tenant"]
    agg = ten["tenants"]["cal_tenant"]
    assert agg["ici_provenance"] == "modeled"
    _install()
    agg = g.stats()["Tenant"]["tenants"]["cal_tenant"]
    assert agg["ici_provenance"] == "modeled"  # bytes stay structural
    default_ledger().reset()


# ---------------------------------------------------------------------------
# roofline ledger: deterministic rate accounting + the verdict machine
# (synthetic graph, synthetic clock — zero weather)
# ---------------------------------------------------------------------------

def _fake_graph(names=("win",), bpt=None):
    ops = []
    for name in names:
        rep = types.SimpleNamespace(
            stats=types.SimpleNamespace(inputs_received=0))
        ops.append(types.SimpleNamespace(name=name, is_tpu=True,
                                         replicas=[rep]))
    ledger = None
    if bpt is not None:
        ledger = types.SimpleNamespace(section=lambda: {
            "per_hop": {n: {"steady_bytes_per_tuple": bpt,
                            "bytes_provenance": "modeled"}
                        for n in names}})
    return types.SimpleNamespace(_operators=ops, _ledger=ledger)


def _feed(led, g, t, rate, ticks, dt=1.0):
    for _ in range(ticks):
        t += dt
        for op in g._operators:
            op.replicas[0].stats.inputs_received += int(rate * dt)
        led.tick(now_s=t)
    return t


def test_roofline_rates_exact_and_telescope_vs_decomposition():
    """The gauge's arithmetic is the bench roofline's: achieved B/s =
    tup/s x B/tuple, ratio = achieved/bandwidth.  On a synthetic clock
    the ring rate is exact, so the telescoped ratio must agree with the
    independently computed decomposition well inside the 10% acceptance
    bound."""
    _install(constants={"hbm_bytes_per_sec": 48000.0})
    g = _fake_graph(bpt=24.0)
    led = cal.RooflineLedger(g)
    _feed(led, g, 0.0, rate=1000.0, ticks=10)
    sec = led.section()
    hop = sec["per_hop"]["win"]
    assert hop["achieved_tuples_per_sec"] == pytest.approx(1000.0)
    assert hop["tuples_per_sec_provenance"] == "measured"
    assert hop["bytes_per_tuple"] == 24.0
    assert hop["bytes_per_tuple_provenance"] == "modeled"
    assert hop["achieved_bytes_per_sec"] == pytest.approx(24000.0)
    assert hop["roofline_tuples_per_sec"] == pytest.approx(2000.0)
    # the telescoping check: ratio from the gauge vs the bench-style
    # decomposition computed independently from its factors
    expected = (1000.0 * 24.0) / 48000.0
    assert hop["ratio_vs_roofline"] == pytest.approx(expected, rel=0.10)
    assert hop["ratio_vs_roofline"] == pytest.approx(0.5, abs=1e-6)
    assert sec["bandwidth_bytes_per_sec"] == 48000.0
    assert cal.is_calibrated(sec["bandwidth_provenance"])
    assert sec["dominant_op"] == "win"


def test_roofline_degraded_enter_latch_clear():
    g = _fake_graph()
    led = cal.RooflineLedger(g)
    # under MIN_SAMPLES: no verdict however bad the rates look
    t = _feed(led, g, 0.0, rate=1000.0, ticks=led.MIN_SAMPLES - 2)
    t = _feed(led, g, t, rate=10.0, ticks=1)
    assert led.verdict is None
    # fill the baseline, then collapse: the FIRST breach tick must not
    # enter (hysteresis), the ENTER_AFTER'th does
    g2 = _fake_graph()
    led2 = cal.RooflineLedger(g2)
    t = _feed(led2, g2, 0.0, rate=1000.0, ticks=led2.MIN_SAMPLES + 2)
    assert led2.verdict is None
    t = _feed(led2, g2, t, rate=100.0, ticks=1)
    assert led2.verdict is None, "entered after one breach tick"
    t = _feed(led2, g2, t, rate=100.0, ticks=1)
    v = led2.verdict
    assert v is not None and led2.entered == 1
    assert v["state"] == "ROOFLINE_DEGRADED"
    assert v["dominant_op"] == "win"
    assert v["ratio_vs_baseline"] < cal.DEGRADE_RATIO
    assert v["baseline_tuples_per_sec"] > v["current_tuples_per_sec"]
    # idle ticks (a drained graph) are NOT recovery: the verdict latches
    for _ in range(5):
        t += 1.0
        led2.tick(now_s=t)
    assert led2.verdict is v, "idle ticks cleared the latch"
    # recovery: CLEAR_AFTER consecutive healthy ticks clear, not fewer
    t = _feed(led2, g2, t, rate=1000.0, ticks=led2.CLEAR_AFTER - 1)
    assert led2.verdict is not None, "cleared early"
    t = _feed(led2, g2, t, rate=1000.0, ticks=1)
    assert led2.verdict is None and led2.cleared == 1
    assert led2.last_verdict is v      # forensics survive the clear


def test_drained_graph_never_latches():
    g = _fake_graph()
    led = cal.RooflineLedger(g)
    t = _feed(led, g, 0.0, rate=1000.0, ticks=led.MIN_SAMPLES + 2)
    # the stream ends: counters freeze, ticks continue (monitor thread)
    for _ in range(20):
        t += 1.0
        led.tick(now_s=t)
    assert led.verdict is None and led.entered == 0


# ---------------------------------------------------------------------------
# live integration: the real pipeline's Roofline section, the health
# verdict attribution, OpenMetrics, webui marker, postmortem + doctor
# ---------------------------------------------------------------------------

def test_roofline_section_on_real_graph(monkeypatch):
    # warm full-suite runs finish in well under the wall-clock tick
    # throttle; zero it so every health_tick samples a rate
    monkeypatch.setattr(cal.RooflineLedger, "TICK_MIN_INTERVAL_S", 0.0)
    g, fired = _graph(_cfg(), name="cal_live_app")
    _drive(g)
    assert fired
    sec = g.stats()["Roofline"]
    assert sec["enabled"]
    assert sec["per_hop"], "no hop ever sampled a rate"
    assert sec["dominant_op"] in sec["per_hop"]
    # the CPU backend has no published HBM peak and nothing calibrated
    # one: the section carries rates but no ceiling, no ratio
    assert sec["bandwidth_bytes_per_sec"] is None
    assert sec["bandwidth_provenance"] is None
    for name, hop in sec["per_hop"].items():
        assert hop["achieved_tuples_per_sec"] > 0, name
        assert hop["tuples_per_sec_provenance"] == "measured"
        assert "ratio_vs_roofline" not in hop
        if "bytes_per_tuple" in hop:       # sweep-ledger join
            assert hop["bytes_per_tuple_provenance"] == "modeled"
            assert hop["achieved_bytes_per_sec"] == pytest.approx(
                hop["achieved_tuples_per_sec"] * hop["bytes_per_tuple"],
                rel=0.01)
    assert set(sec["calibration"]["constants"]) \
        == {k for k, v in cal.MODELED_DEFAULTS.items() if v is not None}
    assert sec["verdict"] is None


def test_roofline_verdict_surfaces_in_health_dominant_op_only():
    g, _ = _graph(_cfg(), name="cal_health_app")
    _drive(g)
    v = {"state": "ROOFLINE_DEGRADED", "dominant_op": "m",
         "current_tuples_per_sec": 10.0,
         "baseline_tuples_per_sec": 1000.0,
         "ratio_vs_baseline": 0.01, "degrade_ratio": 0.5,
         "entered_tick": 9}
    g._roofline.verdict = g._roofline.last_verdict = v
    g.health_tick()
    h = g.stats()["Health"]
    assert h["graph_state"] == "ROOFLINE_DEGRADED"
    for name, hv in h["verdicts"].items():
        if name == "m":
            assert hv["state"] == "ROOFLINE_DEGRADED"
            assert hv["roofline"]["ratio_vs_baseline"] == 0.01
        else:
            assert hv["state"] != "ROOFLINE_DEGRADED"
            assert "roofline" not in hv


def test_openmetrics_roofline_and_provenance_families(monkeypatch):
    from windflow_tpu.monitoring.openmetrics import (parse_exposition,
                                                     render_openmetrics)
    monkeypatch.setattr(cal.RooflineLedger, "TICK_MIN_INTERVAL_S", 0.0)
    _install()
    g, _ = _graph(_cfg(), name="cal_om_app")
    _drive(g)
    fams = parse_exposition(render_openmetrics(g.stats()))
    sec = g.stats()["Roofline"]
    tps = {lab["operator"]: val for _, lab, val in
           fams["wf_roofline_achieved_tuples_per_sec"]["samples"]}
    for name, hop in sec["per_hop"].items():
        assert tps[name] == pytest.approx(
            hop["achieved_tuples_per_sec"], rel=0.5)
    for _, lab, _ in fams["wf_roofline_bytes_per_tuple"]["samples"]:
        assert cal.legal_provenance(lab["provenance"])
    degraded = fams["wf_roofline_degraded"]["samples"]
    assert degraded and degraded[0][2] == 0
    # the info family: one sample per constant, provenance as a label
    prov = {lab["constant"]: lab["provenance"] for _, lab, _ in
            fams["wf_provenance"]["samples"]}
    assert set(prov) == set(cal.MODELED_DEFAULTS)
    assert all(cal.legal_provenance(p) for p in prov.values())
    assert any(p.startswith("calibrated(") for p in prov.values())
    # modeled gauges carry the provenance label
    sweep = fams.get("wf_sweep_bytes_per_tuple")
    assert sweep and sweep["samples"]
    for _, lab, _ in sweep["samples"]:
        assert lab["provenance"] == "modeled"


def test_webui_marks_modeled_cells():
    from windflow_tpu.monitoring.webui import INDEX_HTML
    assert "provenance" in INDEX_HTML
    assert "XLA cost-table estimate" in INDEX_HTML


def _wf_doctor(*args):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "wf_doctor.py"),
         *args], capture_output=True, text=True, timeout=60)


@pytest.fixture()
def cal_bundle(tmp_path):
    _install()
    g, _ = _graph(_cfg(), name="cal_pm_app")
    _drive(g)
    bundle = g.dump_postmortem(str(tmp_path / "pm"), reason="manual")
    with open(os.path.join(bundle, "manifest.json")) as f:
        manifest = json.load(f)
    assert "calibration.json" in manifest["files"]
    assert "roofline.json" in manifest["files"]
    return bundle


def test_postmortem_calibration_roundtrips_wf_doctor(cal_bundle):
    r = _wf_doctor("--check", cal_bundle)
    assert r.returncode == 0, r.stderr
    r = _wf_doctor(cal_bundle)
    assert r.returncode == 0, r.stderr
    assert "calibration:" in r.stdout
    assert "roofline:" in r.stdout
    with open(os.path.join(cal_bundle, "calibration.json")) as f:
        doc = json.load(f)
    assert doc["schema"] == cal.SCHEMA
    for slot in doc["constants"].values():
        assert cal.legal_provenance(slot["provenance"])


def test_wf_doctor_rejects_corrupt_calibration_section(cal_bundle):
    cp = os.path.join(cal_bundle, "calibration.json")
    with open(cp) as f:
        doc = json.load(f)
    doc["constants"]["hbm_bytes_per_sec"]["provenance"] = "vibes"
    with open(cp, "w") as f:
        json.dump(doc, f)
    r = _wf_doctor("--check", cal_bundle)
    assert r.returncode == 1
    assert "provenance" in r.stderr


def test_wf_doctor_accepts_pre_calibration_bundle(cal_bundle):
    # a bundle written before this plane existed: no calibration.json,
    # no roofline.json, no manifest entries — it must still validate
    mp = os.path.join(cal_bundle, "manifest.json")
    with open(mp) as f:
        manifest = json.load(f)
    manifest["files"] = [n for n in manifest["files"]
                         if n not in ("calibration.json",
                                      "roofline.json")]
    with open(mp, "w") as f:
        json.dump(manifest, f)
    os.remove(os.path.join(cal_bundle, "calibration.json"))
    os.remove(os.path.join(cal_bundle, "roofline.json"))
    r = _wf_doctor("--check", cal_bundle)
    assert r.returncode == 0, r.stderr


# ---------------------------------------------------------------------------
# wf_calibrate --check: the CI gate's exit-code contract
# ---------------------------------------------------------------------------

def _wf_calibrate(*args, env_extra=None):
    env = dict(os.environ)
    env.pop("WF_TPU_CALIBRATION", None)
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "wf_calibrate.py"),
         *args], capture_output=True, text=True, timeout=60, env=env)


def test_wf_calibrate_check_exit_codes(tmp_path):
    fresh = tmp_path / "fresh.json"
    fresh.write_text(json.dumps(_store_doc()))
    r = _wf_calibrate("--check", str(fresh))
    assert r.returncode == 0, r.stderr + r.stdout
    assert "OK" in r.stdout

    stale = tmp_path / "stale.json"
    stale.write_text(json.dumps(
        _store_doc(recorded_at=time.time() - cal.TTL_S - 86400)))
    r = _wf_calibrate("--check", str(stale))
    assert r.returncode == 1
    assert "days old" in r.stderr

    corrupt = tmp_path / "corrupt.json"
    corrupt.write_text("{broken")
    r = _wf_calibrate("--check", str(corrupt))
    assert r.returncode == 1

    r = _wf_calibrate("--check", str(tmp_path / "missing.json"))
    assert r.returncode == 1

    r = _wf_calibrate("--check", str(fresh),
                      env_extra={"WF_TPU_CALIBRATION": "0"})
    assert r.returncode == 2
    assert "kill switch" in r.stderr


def test_wf_calibrate_check_is_jax_free(tmp_path):
    """--check must run on scrape/CI hosts with no jax: poison the
    import and make sure the gate still answers."""
    fresh = tmp_path / "fresh.json"
    fresh.write_text(json.dumps(_store_doc()))
    poison = tmp_path / "jax.py"
    poison.write_text("raise ImportError('no jax on this host')\n")
    r = _wf_calibrate("--check", str(fresh), env_extra={
        "PYTHONPATH": str(tmp_path)})
    assert r.returncode == 0, r.stderr + r.stdout


# ---------------------------------------------------------------------------
# off path: roofline_plane=False builds nothing; the residue is one
# `is not None` check per call site (micro-asserted)
# ---------------------------------------------------------------------------

def test_off_path_never_builds():
    g, fired = _graph(_cfg(roofline_plane=False), name="cal_off_app")
    _drive(g)
    assert fired
    assert g._roofline is None
    assert g.stats()["Roofline"] == {"enabled": False}
    if g._health is not None:
        assert g._health.roofline is None
    # off-path budget (the tenant/latency plane stance): with every
    # cadence plane off, health_tick is a handful of attribute checks
    g2, _ = _graph(_cfg(roofline_plane=False, health_watchdog=False,
                        flight_recorder=False), name="cal_off2_app")
    _drive(g2)
    t0 = time.perf_counter()
    for _ in range(10_000):
        g2.health_tick()
    per_call = (time.perf_counter() - t0) / 10_000
    assert per_call < 5e-6, \
        f"disabled health_tick costs {per_call * 1e6:.2f}us/call"


def test_config_calibration_installs_store(tmp_path):
    path = tmp_path / "cal.json"
    path.write_text(json.dumps(_store_doc()))
    g, fired = _graph(_cfg(calibration=str(path)), n=512,
                      name="cal_cfg_app")
    _drive(g)
    assert fired
    store = cal.default_store()
    assert store is not None and store.path == str(path)
    sec = g.stats()["Roofline"]
    assert cal.is_calibrated(sec["bandwidth_provenance"])
    assert sec["bandwidth_bytes_per_sec"] == 5e9
