"""Health-plane contracts (docs/OBSERVABILITY.md "Health plane"): the
watchdog state machine over a healthy run, seeded-stall detection with
root-cause attribution (in stats, the raised error, and the OpenMetrics
exposition), crash-path FAILED attribution + END_APP delivery, postmortem
bundles round-tripping through wf_doctor --check, and the
watchdog-disabled off-path budget."""

import dataclasses
import json
import os
import subprocess
import sys
import time

import pytest

import windflow_tpu as wf
from windflow_tpu.basic import default_config
from windflow_tpu.monitoring.health import (BACKPRESSURED, FAILED, OK,
                                            STALLED, HealthPlane)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _graph(cfg, n=3000, cap=256, name="health_app", sink_fn=None):
    src = (wf.Source_Builder(
        lambda: iter({"key": i % 8, "v": float(i)} for i in range(n)))
        .withName("src").withOutputBatchSize(cap).build())
    m = (wf.MapTPU_Builder(lambda t: {"key": t["key"], "v": t["v"] * 2.0})
         .withName("mtpu").build())
    snk = (wf.Sink_Builder(sink_fn or (lambda t, ctx=None: None))
           .withName("snk").build())
    g = wf.PipeGraph(name, wf.ExecutionMode.DEFAULT, config=cfg)
    g.add_source(src).add(m).add_sink(snk)
    return g, snk


def _cfg(tmp_path=None, **kw):
    if tmp_path is not None:
        kw.setdefault("log_dir", str(tmp_path))
    return dataclasses.replace(default_config, **kw)


# ---------------------------------------------------------------------------
# healthy run: all OK, zero stalls
# ---------------------------------------------------------------------------

def test_healthy_run_reports_all_ok(tmp_path):
    g, _ = _graph(_cfg(tmp_path))
    g.run()
    h = g.stats()["Health"]
    assert h["enabled"] is True
    assert h["graph_state"] == OK
    assert {v["state"] for v in h["verdicts"].values()} == {OK}
    assert h["stall_events"] == 0
    assert h["last_stall"] is None
    assert h["samples_taken"] > 0
    # JSON-clean: the section ships in every NEW_REPORT payload
    json.dumps(h)


def test_health_disabled_off_path(tmp_path, monkeypatch):
    """Off means off by what it does, not by a clock: no ``HealthPlane``
    is built and a tick evaluates nothing (counted, as
    test_annotation_off_path_is_one_attribute_check counts annotations)."""
    counts = {"built": 0, "sampled": 0}
    init, sample = HealthPlane.__init__, HealthPlane.sample

    def counting_init(self, *a, **k):
        counts["built"] += 1
        init(self, *a, **k)

    def counting_sample(self, *a, **k):
        counts["sampled"] += 1
        return sample(self, *a, **k)

    monkeypatch.setattr(HealthPlane, "__init__", counting_init)
    monkeypatch.setattr(HealthPlane, "sample", counting_sample)
    g, _ = _graph(_cfg(tmp_path, health_watchdog=False))
    g.run()
    assert g._health is None
    assert g.stats()["Health"] == {"enabled": False}
    for _ in range(100):
        g.health_tick()
    assert counts == {"built": 0, "sampled": 0}
    # the same counters see the plane when it is on
    g, _ = _graph(_cfg(tmp_path), name="health_on_app")
    g.run()
    g.health_tick()
    assert counts["built"] == 1 and counts["sampled"] >= 1


# ---------------------------------------------------------------------------
# seeded stall: detection, attribution, enriched error, postmortem
# ---------------------------------------------------------------------------

def test_seeded_stall_attributed_to_wedged_sink(tmp_path):
    """A sink that stops draining stalls the graph: the error must name
    it (regression for the bare "routing bug?" message), stats()["Health"]
    must show the STALLED verdict, and the bundle must validate."""
    g, snk = _graph(_cfg(tmp_path, health_stall_grace_usec=50_000),
                    name="stall_app")
    g.start()
    snk.replicas[0].drain = lambda limit=0: False   # wedged: never drains
    with pytest.raises(wf.WindFlowError) as ei:
        g.wait_end()
    msg = str(ei.value)
    assert "routing bug?" not in msg
    assert "root cause 'snk'" in msg
    assert "queue" in msg and "message(s) pending" in msg
    # the same diagnosis in stats: STALLED latched on the root cause
    h = g.stats()["Health"]
    assert h["graph_state"] == STALLED
    assert h["verdicts"]["snk"]["state"] == STALLED
    assert h["verdicts"]["snk"]["queue_depth"] > 0
    assert h["verdicts"]["src"]["state"] == OK
    assert h["stall_events"] == 1    # exactly one: no double count
    assert h["last_stall"]["root_cause"] == "snk"
    # a state-change timeline entry recorded the degradation
    assert any("snk" in e["changes"] for e in h["timeline"])


def test_stall_exposes_nonzero_stall_counter_in_openmetrics(tmp_path):
    from windflow_tpu.monitoring.openmetrics import (parse_exposition,
                                                     render_openmetrics)
    g, snk = _graph(_cfg(tmp_path, health_stall_grace_usec=50_000))
    g.start()
    snk.replicas[0].drain = lambda limit=0: False
    with pytest.raises(wf.WindFlowError):
        g.wait_end()
    fams = parse_exposition(render_openmetrics(g.stats()))
    stalls = fams["wf_stall_events_total"]["samples"]
    assert stalls and stalls[0][2] >= 1
    # enum gauge: exactly one active state per operator, snk on stalled
    by_op = {}
    for name, labels, value in fams["wf_operator_health"]["samples"]:
        if value == 1:
            assert labels["operator"] not in by_op
            by_op[labels["operator"]] = labels["state"]
    assert by_op["snk"] == "stalled"
    assert by_op["src"] == "ok"


def test_stall_postmortem_roundtrips_wf_doctor(tmp_path):
    g, snk = _graph(_cfg(tmp_path, health_stall_grace_usec=50_000),
                    name="pm_app")
    g.start()
    snk.replicas[0].drain = lambda limit=0: False
    with pytest.raises(wf.WindFlowError) as ei:
        g.wait_end()
    bundle = g._postmortem_dir
    assert bundle is not None and os.path.isdir(bundle)
    assert bundle in str(ei.value)     # the error points at the bundle
    with open(os.path.join(bundle, "manifest.json")) as f:
        manifest = json.load(f)
    assert manifest["schema"] == "wf-postmortem/1"
    assert manifest["app"] == "pm_app"
    assert set(manifest["files"]) >= {"stats.json", "health.json",
                                      "events.json", "jit.json"}
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "wf_doctor.py"),
         "--check", bundle], capture_output=True, text=True)
    assert r.returncode == 0, r.stderr
    assert "OK" in r.stdout
    # the human render names the root cause
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "wf_doctor.py"),
         bundle], capture_output=True, text=True)
    assert r.returncode == 0, r.stderr
    assert "ROOT CAUSE: 'snk'" in r.stdout


def test_wf_doctor_check_rejects_corrupt_bundle(tmp_path):
    g, snk = _graph(_cfg(tmp_path, health_stall_grace_usec=50_000))
    g.start()
    snk.replicas[0].drain = lambda limit=0: False
    with pytest.raises(wf.WindFlowError):
        g.wait_end()
    bundle = g._postmortem_dir
    hp = os.path.join(bundle, "health.json")
    with open(hp) as f:
        h = json.load(f)
    h["verdicts"]["snk"]["state"] = "ZOMBIE"      # illegal state
    with open(hp, "w") as f:
        json.dump(h, f)
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "wf_doctor.py"),
         "--check", bundle], capture_output=True, text=True)
    assert r.returncode == 1
    assert "illegal state" in r.stderr


def test_manual_postmortem_on_healthy_graph(tmp_path):
    g, _ = _graph(_cfg(tmp_path))
    g.run()
    bundle = g.dump_postmortem(str(tmp_path / "pm"), reason="manual")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "wf_doctor.py"),
         "--check", bundle], capture_output=True, text=True)
    assert r.returncode == 0, r.stderr


# ---------------------------------------------------------------------------
# crash path: FAILED attribution + abnormal-termination telemetry
# ---------------------------------------------------------------------------

def test_operator_crash_marked_failed_with_attribution(tmp_path):
    def boom(t):
        if t["v"] > 500:
            raise ValueError("seeded operator crash")
    cfg = _cfg(tmp_path)
    src = (wf.Source_Builder(
        lambda: iter({"key": i % 8, "v": float(i)} for i in range(3000)))
        .withName("src").withOutputBatchSize(256).build())
    bad = wf.Map_Builder(boom).withName("bad_map").build()
    snk = wf.Sink_Builder(lambda t, ctx=None: None).withName("snk").build()
    g = wf.PipeGraph("crash_app", wf.ExecutionMode.DEFAULT, config=cfg)
    g.add_source(src).add(bad).add_sink(snk)
    with pytest.raises(ValueError, match="seeded operator crash"):
        g.run()
    h = g.stats()["Health"]
    assert h["verdicts"]["bad_map"]["state"] == FAILED
    assert "ValueError" in h["verdicts"]["bad_map"]["failure"]
    assert h["graph_state"] == FAILED
    # crash postmortem written BEFORE finalize tore the graph down
    assert g._postmortem_dir is not None
    with open(os.path.join(g._postmortem_dir, "manifest.json")) as f:
        assert json.load(f)["reason"].startswith("crash: ValueError")


def test_monitor_sends_end_app_on_crash(tmp_path):
    """Satellite regression: abnormal termination must still deliver a
    final report + END_APP (the dashboard used to show crashed apps live
    forever), with the Aborted marker set."""
    from test_monitoring import StubDashboard
    stub = StubDashboard()
    stub.start()
    cfg = _cfg(tmp_path, tracing_enabled=True,
               dashboard_host="127.0.0.1", dashboard_port=stub.port,
               health_stall_grace_usec=50_000)
    g, snk = _graph(cfg, name="crash_monitored")
    g.start()
    snk.replicas[0].drain = lambda limit=0: False
    with pytest.raises(wf.WindFlowError):
        g.wait_end()
    stub.join(timeout=5)
    assert stub.messages, "dashboard never contacted"
    mtype, ident, payload = stub.messages[-1]
    assert mtype == 2, "END_APP missing on the crash path"
    report = json.loads(payload.rstrip(b"\0"))
    assert report.get("Aborted") is True
    assert report["Health"]["verdicts"]["snk"]["state"] == STALLED


# ---------------------------------------------------------------------------
# state machine unit behavior
# ---------------------------------------------------------------------------

def test_backpressure_verdict_on_deep_queue(tmp_path):
    """An operator holding a deep backlog (but inside the stall grace) is
    BACKPRESSURED, and recovers to OK once the backlog drains."""
    cfg = _cfg(tmp_path, health_backpressure_depth=2,
               health_stall_grace_usec=60_000_000)
    g, snk = _graph(cfg, n=4000, cap=128)
    g.start()
    rep = snk.replicas[0]
    real = type(rep).drain
    rep.drain = lambda limit=0: False       # hold the backlog briefly
    for _ in range(40):
        if len(rep.inbox) >= 2:
            break
        g.step()
    assert len(rep.inbox) >= 2, "backlog never built"
    assert g._health.sample()["snk"]["state"] == BACKPRESSURED
    del rep.drain                           # un-wedge (restore the method)
    assert rep.drain.__func__ is real
    g.wait_end()
    assert g._health.sample()["snk"]["state"] == OK


def test_stall_latch_clears_on_progress(tmp_path):
    g, snk = _graph(_cfg(tmp_path, health_stall_grace_usec=50_000))
    g.start()
    rep = snk.replicas[0]
    real = type(rep).drain
    rep.drain = lambda limit=0: False
    with pytest.raises(wf.WindFlowError):
        g.wait_end()
    assert g._health.sample()["snk"]["state"] == STALLED  # latched
    # un-wedge: restore the real drain and let the backlog clear
    rep.drain = lambda limit=0: real(rep, limit)
    while rep.inbox:
        rep.drain(0)
    assert g._health.sample()["snk"]["state"] == OK
    g._finalize(dump=False)


def test_watchdog_then_hard_stall_counts_one_event(tmp_path):
    """A cadence tick that detects the stall first (grace elapsed) and
    the subsequent wait_end hard-stall confirmation are ONE stall, not
    two — the latch carries the 'already counted' fact between them."""
    g, snk = _graph(_cfg(tmp_path, health_stall_grace_usec=20_000))
    g.start()
    snk.replicas[0].drain = lambda limit=0: False
    for _ in range(20):
        g.step()                    # build a pending backlog
    g._health.sample()              # baseline progress observation
    time.sleep(0.05)                # let the grace window elapse
    v = g._health.sample()          # cadence detection: counts the stall
    assert v["snk"]["state"] == STALLED
    assert g._health.stall_events == 1
    with pytest.raises(wf.WindFlowError):
        g.wait_end()                # hard-stall confirmation: no recount
    assert g._health.stall_events == 1
    # the hard stall re-dumped a FRESH frame over the watchdog bundle
    with open(os.path.join(g._postmortem_dir, "manifest.json")) as f:
        assert json.load(f)["reason"] == "stall"


def test_crash_after_manual_snapshot_still_bundles(tmp_path):
    """A routine mid-run dump_postmortem must not suppress the crash
    bundle: the on-disk reason must be the crash, not the snapshot."""
    def boom(t):
        if t["v"] > 500:
            raise ValueError("late crash")
    cfg = _cfg(tmp_path)
    src = (wf.Source_Builder(
        lambda: iter({"key": i % 8, "v": float(i)} for i in range(3000)))
        .withName("src").withOutputBatchSize(256).build())
    bad = wf.Map_Builder(boom).withName("bad_map").build()
    snk = wf.Sink_Builder(lambda t, ctx=None: None).withName("snk").build()
    g = wf.PipeGraph("snap_app", wf.ExecutionMode.DEFAULT, config=cfg)
    g.add_source(src).add(bad).add_sink(snk)
    g.start()
    g.dump_postmortem(str(tmp_path / "snap"), reason="manual snapshot")
    with pytest.raises(ValueError):
        g.wait_end()
    assert g._postmortem_dir != str(tmp_path / "snap")
    with open(os.path.join(g._postmortem_dir, "manifest.json")) as f:
        assert json.load(f)["reason"].startswith("crash: ValueError")


def test_postmortem_during_unbundled_stall_does_not_deadlock(tmp_path):
    """Regression: dump_postmortem holds the bundle lock while its stats
    section re-samples the watchdog; an operator newly past the grace
    window used to fire the cadence auto-bundle from inside that sample
    and re-enter the non-reentrant lock on the same thread."""
    g, snk = _graph(_cfg(tmp_path, health_stall_grace_usec=20_000))
    g.start()
    snk.replicas[0].drain = lambda limit=0: False
    for _ in range(20):
        g.step()                    # pending backlog, no health tick yet
    g._health.sample()              # baseline observation
    time.sleep(0.05)                # grace elapses with NO cadence tick
    done = {}

    def dump():
        done["dir"] = g.dump_postmortem(str(tmp_path / "pm"))
    import threading
    t = threading.Thread(target=dump, daemon=True)
    t.start()
    t.join(timeout=10)
    assert not t.is_alive(), "dump_postmortem deadlocked"
    assert os.path.isdir(done["dir"])
    g._finalize(dump=False)


def test_compile_storm_baselined_per_graph(tmp_path):
    """The jit registry is process-global: a prior graph's recompiles
    must not flag a fresh graph's same-named operator; recompiles during
    THIS run past the threshold must."""
    from windflow_tpu.monitoring.jit_registry import default_registry
    entry = default_registry().entry("mtpu")
    before = entry.recompiles
    try:
        entry.recompiles = before + 10          # a prior graph's storm
        entry.compiles = max(entry.compiles, 1)  # keep snapshot() visible
        g, _ = _graph(_cfg(tmp_path, health_recompile_storm=4))
        g.start()
        v = g._health.sample()
        assert v["mtpu"]["compile_storm"] is False   # baselined away
        entry.recompiles += 4                   # storm DURING this run
        v = g._health.sample()
        assert v["mtpu"]["compile_storm"] is True
        assert v["mtpu"]["state"] == BACKPRESSURED
        g.wait_end()
    finally:
        entry.recompiles = before


def test_manual_snapshot_does_not_consume_stall_auto_bundle(tmp_path):
    """A routine dump_postmortem must not use up the watchdog's
    once-per-graph stall auto-bundle (streaming deployments never reach
    wait_end's hard-stall dump)."""
    g, snk = _graph(_cfg(tmp_path, health_stall_grace_usec=20_000))
    g.start()
    g.dump_postmortem(str(tmp_path / "snap"), reason="manual snapshot")
    snk.replicas[0].drain = lambda limit=0: False
    for _ in range(20):
        g.step()
    g._health.sample()              # baseline
    time.sleep(0.05)                # grace elapses
    g._health.sample()              # cadence stall: auto-bundle fires
    assert g._health.stall_events == 1
    assert g._postmortem_dir != str(tmp_path / "snap")
    with open(os.path.join(g._postmortem_dir, "manifest.json")) as f:
        assert json.load(f)["reason"].startswith("watchdog: stalled")
    g._finalize(dump=False)


def test_format_diagnosis_no_root_cause():
    msg = HealthPlane.format_diagnosis({"root_cause": None, "verdicts": {
        "src": {"state": OK, "queue_depth": 0,
                "last_advance_age_usec": 0}}})
    assert "source starvation" in msg
