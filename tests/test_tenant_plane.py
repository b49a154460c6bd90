"""Tenant plane (monitoring/tenant_ledger.py): per-tenant attribution
across two co-resident graphs, the OVER_BUDGET enter/latch/clear state
machine, the tenancy advisor's golden plan, the OpenMetrics / postmortem
/ wf_tenant surfaces, the dashboard multi-app tenant-label merge, the
two-graph MonitoringThread lifecycle, and the off-path micro-assert.

The attribution honesty property is the plane's contract: the per-tenant
H2D/D2H byte totals are the SAME per-replica counters
``stats()["Bytes_H2D_total"]`` sums, so each tenant's roll-up must equal
its graph's own totals exactly, and the sum across tenants must
reconcile against the process staged-transfer delta
(``attributed.staged_fraction`` — the CI-gated >= 0.9 floor).  A ledger
that attributes less than it measures would hand PR 20's scheduler a
plan built on missing bytes."""

import dataclasses
import json
import os
import subprocess
import sys
import time

import pytest

import windflow_tpu as wf
from windflow_tpu.analysis import tenancy
from windflow_tpu.basic import default_config
from windflow_tpu.monitoring.health import OK, OVER_BUDGET
from windflow_tpu.monitoring.openmetrics import (parse_exposition,
                                                 render_openmetrics)
from windflow_tpu.monitoring.tenant_ledger import (CLEAR_AFTER,
                                                   ENTER_AFTER,
                                                   _TenantTrack,
                                                   default_ledger)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

N = 4096
CAP = 256
KEYS = 8


def _graph(name, tenant, prefix, keys_fn, budget=0, n=N, cap=CAP,
           **cfg_kw):
    """One keyed source→map→window→sink graph with per-graph DISTINCT
    op names (the compile-ms prefix rule attributes by name)."""
    cfg = dataclasses.replace(default_config, tenant=tenant,
                              hbm_budget_bytes=budget, **cfg_kw)
    src = (wf.Source_Builder(
        lambda: iter({"key": keys_fn(i), "v": float(i)}
                     for i in range(n)))
        .withName(f"{prefix}_src").withOutputBatchSize(cap).build())
    m = (wf.MapTPU_Builder(lambda t: {"key": t["key"], "v": t["v"] * 2.0})
         .withName(f"{prefix}_map").build())
    w = (wf.Ffat_WindowsTPU_Builder(lambda t: t["v"], lambda a, b: a + b)
         .withCBWindows(64, 32).withKeyBy(lambda t: t["key"])
         .withMaxKeys(KEYS).withName(f"{prefix}_win").build())
    snk = (wf.Sink_Builder(lambda r: None)
           .withName(f"{prefix}_snk").build())
    g = wf.PipeGraph(name, wf.ExecutionMode.DEFAULT, config=cfg)
    g.add_source(src).add(m).add(w).add_sink(snk)
    return g


def _drive(g):
    g.start()
    while not g.is_done():
        if not g.step():
            break
        g.health_tick()
    g.wait_end()
    g.health_tick()


@pytest.fixture(scope="module")
def two_tenants():
    """Two seeded graphs — Zipf-hot ('acme') + uniform ('blue') — in ONE
    process sharing the default ledger.  Returns the graphs, the
    process-level tenant section, and each graph's stats, all captured
    while the accounting epoch is intact."""
    led = default_ledger()
    led.reset()
    graphs = {}
    g = _graph("ten_acme_app", "acme", "za",
               lambda i: 0 if i % 4 else i % KEYS,
               budget=64 << 20)                    # generous: within
    _drive(g)
    graphs["acme"] = g
    g = _graph("ten_blue_app", "blue", "zb", lambda i: i % KEYS,
               budget=64 << 20)
    _drive(g)
    graphs["blue"] = g
    stats = {t: g.stats() for t, g in graphs.items()}
    return graphs, led.section(), stats


# ---------------------------------------------------------------------------
# attribution sums to the graphs' own totals + process reconciliation
# ---------------------------------------------------------------------------

def test_attribution_sums_to_graph_totals(two_tenants):
    graphs, sec, stats = two_tenants
    assert sec["enabled"]
    assert set(sec["tenants"]) >= {"acme", "blue"}
    for tenant, g in graphs.items():
        agg = sec["tenants"][tenant]
        st = stats[tenant]
        # the SAME per-replica counters stats() sums: exact equality
        assert agg["h2d_bytes"] == st["Bytes_H2D_total"], tenant
        assert agg["d2h_bytes"] == st["Bytes_D2H_total"], tenant
        assert agg["graphs"] == [g.name]
        assert agg["dispatches"] > 0
        assert agg["resident_state_bytes"] > 0, \
            "window operator state never attributed"
        # per-op rows carry this graph's distinct names only
        assert all(op.startswith(("za_", "zb_")) for op in agg["per_op"])
        assert agg["heaviest_op"] in agg["per_op"]
        assert agg["budget"]["pressure"] is not None
        assert not agg["budget"]["active"]


def test_staged_fraction_reconciles(two_tenants):
    _, sec, _ = two_tenants
    att = sec["attributed"]
    assert att["staged_bytes_process_total"] > 0
    # at least 90% of the process's staged device bytes must attribute
    # to tenants; the seeded two-graph run attributes everything
    assert att["staged_fraction"] >= 0.9
    assert att["staged_bytes_tenants_total"] == \
        sum(t["h2d_bytes"] for t in sec["tenants"].values())


def test_stats_tenant_section_focuses_own_graph(two_tenants):
    graphs, _, stats = two_tenants
    for tenant, g in graphs.items():
        ten = stats[tenant]["Tenant"]
        assert ten["enabled"]
        assert ten["tenant"] == tenant          # the OpenMetrics label
        assert ten["graph"]["graph"] == g.name  # focused row
        # every graph's dump still carries the WHOLE process table: one
        # tenant's stats dump is enough for the advisor to plan across
        assert set(ten["tenants"]) >= {"acme", "blue"}


def test_dump_trace_carries_tenant(two_tenants, tmp_path):
    graphs, _, _ = two_tenants
    g = graphs["acme"]
    if g._recorder is None:
        pytest.skip("flight recorder off in this config")
    path = g.dump_trace(str(tmp_path / "t.json"))
    with open(path) as f:
        trace = json.load(f)
    assert trace["otherData"]["tenant"]["tenant"] == "acme"


# ---------------------------------------------------------------------------
# budget state machine: sustained entry, latch, hysteresis clear
# ---------------------------------------------------------------------------

def test_tenant_track_enter_latch_clear():
    tr = _TenantTrack("t", budget_bytes=100)
    # one over tick is a spike, not a verdict (sustained entry)
    tr.tick(150, "g", "op")
    assert not tr.active and tr.verdict is None
    for _ in range(ENTER_AFTER - 1):
        tr.tick(150, "g", "op")
    assert tr.active and tr.entered == 1
    v = tr.verdict
    assert v["state"] == "OVER_BUDGET"
    assert v["overage_bytes"] == 50
    assert v["heaviest_op"] == "op" and v["graph"] == "g"
    assert "100 B" in v["message"]
    # latch: still over, entered does not re-count
    tr.tick(160, "g", "op")
    assert tr.active and tr.entered == 1
    assert tr.verdict["hbm_bytes"] == 160    # verdict tracks the level
    # hysteresis: CLEAR_AFTER - 1 under-budget ticks must NOT clear
    for i in range(CLEAR_AFTER - 1):
        tr.tick(50, "g", "op")
        assert tr.active, f"cleared after {i + 1} OK tick(s)"
    tr.tick(50, "g", "op")
    assert not tr.active and tr.cleared == 1
    assert tr.verdict is None
    assert tr.last_verdict is not None       # forensics survive
    # re-enter counts a fresh violation (and needs sustaining again)
    tr.tick(150, "g", "op")
    assert not tr.active
    tr.tick(150, "g", "op")
    assert tr.active and tr.entered == 2


def test_tenant_track_no_budget_is_inert():
    tr = _TenantTrack("t", budget_bytes=0)
    for _ in range(10):
        tr.tick(1 << 40, "g", "op")
    assert not tr.active and tr.verdict is None and tr.entered == 0
    assert tr.budget_json(1 << 40)["pressure"] is None


def test_over_budget_paints_health_on_heaviest_op_and_latches():
    led = default_ledger()
    g = _graph("ten_ob_app", "ob_tenant", "ob", lambda i: i % KEYS,
               budget=1)                     # 1 B: every run violates
    _drive(g)
    # sustained entry at tick cadence (force bypasses the wall throttle)
    for _ in range(ENTER_AFTER):
        led.tick(tenant="ob_tenant", force=True)
    ten = g.stats()["Tenant"]
    bud = ten["tenants"]["ob_tenant"]["budget"]
    assert bud["active"] and bud["entered"] >= 1
    assert bud["pressure"] > 1.0
    v = bud["verdict"]
    assert v["state"] == "OVER_BUDGET"
    assert v["graph"] == g.name
    heaviest = v["heaviest_op"]
    assert heaviest in ten["tenants"]["ob_tenant"]["per_op"]
    # the health plane paints the verdict on the heaviest op ONLY —
    # one hungry operator does not paint the whole graph
    g.health_tick()
    h = g.stats()["Health"]
    assert h["graph_state"] == OVER_BUDGET
    for name, hv in h["verdicts"].items():
        if name == heaviest:
            assert hv["state"] == OVER_BUDGET
            assert hv["over_budget"]["message"] == v["message"]
        else:
            assert hv["state"] != OVER_BUDGET
            assert "over_budget" not in hv
    # the verdict latched past termination (frozen attribution rows)
    assert led.verdict_for(g.name) is not None


# ---------------------------------------------------------------------------
# off path: tenant_ledger=False never registers — one `is None` check
# ---------------------------------------------------------------------------

def test_off_path_never_registers():
    led = default_ledger()
    g = _graph("ten_off_app", "off_tenant", "off", lambda i: i % KEYS,
               tenant_ledger=False)
    _drive(g)
    assert g._tenant is None
    assert g.stats()["Tenant"] == {"enabled": False}
    assert "off_tenant" not in led.section()["tenants"]
    if g._health is not None:
        assert g._health.tenant is None
    # off-path budget (the health plane's stance): the disabled tenant
    # hook inside health_tick is ONE attribute check — with health off
    # too the whole tick must stay orders of magnitude under a sample
    g2 = _graph("ten_off2_app", "off_tenant", "of2", lambda i: i % KEYS,
                tenant_ledger=False, health_watchdog=False,
                flight_recorder=False)
    _drive(g2)
    t0 = time.perf_counter()
    for _ in range(10_000):
        g2.health_tick()
    per_call = (time.perf_counter() - t0) / 10_000
    assert per_call < 5e-6, \
        f"disabled health_tick costs {per_call * 1e6:.2f}us/call"


# ---------------------------------------------------------------------------
# tenancy advisor: rank order + the golden four-action plan
# ---------------------------------------------------------------------------

def _synthetic_section():
    """Three tenants: an over-budget hog (throttle + rescale + drain),
    a within-budget latency hot-spot (rebalance), and an idle one."""
    def agg(graphs, resident, per_op, heaviest, budget=None,
            latency_share=None, **kw):
        out = {"graphs": graphs, "dispatches": kw.get("dispatches", 10),
               "compile_ms": 1.0, "h2d_bytes": 1000,
               "h2d_logical_bytes": 1000, "d2h_bytes": 100,
               "resident_state_bytes": resident,
               "ici_bytes_per_tuple": 0.0, "latency_usec_total": 0.0,
               "latency_share": latency_share, "per_op": per_op,
               "heaviest_op": heaviest}
        if budget is not None:
            out["budget"] = budget
        return out

    hog_verdict = {"state": "OVER_BUDGET", "tenant": "hog",
                   "hbm_bytes": 250, "budget_bytes": 100,
                   "overage_bytes": 150, "graph": "hog_g",
                   "heaviest_op": "h_win", "message": "hog over"}
    return {
        "enabled": True,
        "tenants": {
            "hog": agg(["hog_g"], 250,
                       {"h_win": {"dispatches": 5,
                                  "resident_bytes": 200},
                        "h_map": {"dispatches": 5,
                                  "resident_bytes": 50}},
                       "h_win",
                       budget={"budget_bytes": 100, "hbm_bytes": 250,
                               "pressure": 2.5, "active": True,
                               "entered": 1, "cleared": 0,
                               "verdict": hog_verdict,
                               "last_verdict": hog_verdict}),
            "warm": agg(["warm_g"], 50,
                        {"w_map": {"dispatches": 8,
                                   "resident_bytes": 50}},
                        "w_map", latency_share=0.7,
                        budget={"budget_bytes": 1000, "hbm_bytes": 50,
                                "pressure": 0.05, "active": False,
                                "entered": 0, "cleared": 0,
                                "verdict": None, "last_verdict": None}),
            "idle": agg(["idle_g"], 10,
                        {"i_map": {"dispatches": 1,
                                   "resident_bytes": 10}},
                        "i_map"),
        },
        "attributed": {"staged_bytes_tenants_total": 3000,
                       "staged_bytes_process_total": 3000,
                       "staged_fraction": 1.0},
        "overhead": {"collects": 3, "collect_ms_total": 0.5,
                     "last_collect_ms": 0.1},
    }


def test_advisor_rank_order():
    ranked = tenancy.rank(_synthetic_section())
    # worst pressure first; budget-less tenants last
    assert [r["tenant"] for r in ranked] == ["hog", "warm", "idle"]
    assert ranked[0]["over_budget"] and ranked[0]["pressure"] == 2.5
    assert ranked[0]["heaviest_op_bytes"] == 200
    assert ranked[2]["pressure"] is None


def test_advisor_golden_plan():
    p = tenancy.plan(_synthetic_section())
    assert p["advisor"] == "tenancy/1"
    assert p["tenants_total"] == 3
    assert p["over_budget_tenants"] == ["hog"]
    assert p["worst_pressure"] == 2.5
    assert p["actionable"] == 2
    by_tenant = {t["tenant"]: t for t in p["tenants"]}
    # the golden plan: hog gets all three memory actions, in order
    kinds = [a["kind"] for a in by_tenant["hog"]["actions"]]
    assert kinds == ["throttle_admission", "rescale_tenant",
                     "drain_shards"]
    acts = {a["kind"]: a for a in by_tenant["hog"]["actions"]}
    assert acts["throttle_admission"]["factor"] == 3  # ceil(2.5)
    assert acts["rescale_tenant"]["shed_bytes"] == 150
    assert acts["drain_shards"]["op"] == "h_win"
    # warm: within budget but hot on latency — rebalance only
    kinds = [a["kind"] for a in by_tenant["warm"]["actions"]]
    assert kinds == ["rebalance_hot_tenant"]
    assert by_tenant["idle"]["actions"] == []
    json.dumps(p)    # the plan is JSON-clean


# ---------------------------------------------------------------------------
# OpenMetrics: wf_tenant_* families round-trip the same numbers; the
# tenant base label rides every family; label escaping holds
# ---------------------------------------------------------------------------

def _samples(fams, name):
    return fams[name]["samples"]


def test_openmetrics_tenant_families_round_trip(two_tenants):
    _, _, stats = two_tenants
    st = stats["acme"]
    fams = parse_exposition(render_openmetrics(st))
    ten = st["Tenant"]
    # per-tenant families carry the SAME numbers the section reports
    for tenant, agg in ten["tenants"].items():
        rows = {lab["tenant"]: val for _, lab, val
                in _samples(fams, "wf_tenant_hbm_bytes")}
        assert rows[tenant] == agg["resident_state_bytes"]
        rows = {lab["tenant"]: val for _, lab, val
                in _samples(fams, "wf_tenant_dispatches_total")}
        assert rows[tenant] == agg["dispatches"]
        rows = {lab["tenant"]: val for _, lab, val
                in _samples(fams, "wf_tenant_h2d_bytes_total")}
        assert rows[tenant] == agg["h2d_bytes"]
        rows = {lab["tenant"]: val for _, lab, val
                in _samples(fams, "wf_tenant_budget_pressure")}
        assert rows[tenant] == pytest.approx(
            agg["budget"]["pressure"], abs=1e-4)
    frac = [(lab, val) for _, lab, val in _samples(
        fams, "wf_tenant_attributed_staged_fraction")]
    assert frac and frac[0][1] == pytest.approx(
        ten["attributed"]["staged_fraction"], abs=1e-4)
    # the tenant base label rides every per-operator family: the
    # disambiguator for the dashboard's merged multi-app exposition
    for _, lab, _ in _samples(fams, "wf_operator_outputs_total"):
        assert lab["tenant"] == "acme"


def test_openmetrics_over_budget_enum_state():
    g = _graph("ten_om_ob_app", "om_ob_tenant", "oo",
               lambda i: i % KEYS, budget=1)
    _drive(g)
    for _ in range(ENTER_AFTER):
        default_ledger().tick(tenant="om_ob_tenant", force=True)
    g.health_tick()
    fams = parse_exposition(render_openmetrics(g.stats()))
    health = {(lab["operator"], lab["state"]): val for _, lab, val
              in _samples(fams, "wf_operator_health")}
    assert any(state == "over_budget" and val == 1
               for (_, state), val in health.items())
    over = {lab["tenant"]: val for _, lab, val
            in _samples(fams, "wf_tenant_over_budget")}
    assert over["om_ob_tenant"] == 1


def test_openmetrics_tenant_label_escaping():
    nasty = 'we"ird\\ten\nant'
    g = _graph("ten_esc_app", nasty, "esc", lambda i: i % KEYS)
    _drive(g)
    fams = parse_exposition(render_openmetrics(g.stats()))
    tenants = {lab["tenant"] for _, lab, _
               in _samples(fams, "wf_tenant_hbm_bytes")}
    assert nasty in tenants     # escaped on the wire, intact parsed


# ---------------------------------------------------------------------------
# dashboard /metrics: two same-topology apps merge into ONE strict-valid
# exposition, kept apart by the app/tenant labels (the collision fix)
# ---------------------------------------------------------------------------

def test_dashboard_metrics_two_same_topology_apps():
    import urllib.request
    from windflow_tpu.monitoring import DashboardServer
    server = DashboardServer(tcp_port=0, http_port=0).start()
    try:
        for tenant in ("twin_a", "twin_b"):
            # SAME app name, SAME op names — only the tenant differs
            g = _graph("twin_app", tenant, "tw", lambda i: i % KEYS,
                       tracing_enabled=True,
                       dashboard_host="127.0.0.1",
                       dashboard_port=server.tcp_port, n=1024)
            _drive(g)
        with urllib.request.urlopen(
                f"http://127.0.0.1:{server.http_port}/metrics",
                timeout=5) as r:
            assert r.status == 200
            text = r.read().decode()
        fams = parse_exposition(text)   # strict: one TYPE per family
        pairs = {(lab.get("app"), lab.get("tenant"))
                 for _, lab, _ in _samples(fams,
                                           "wf_operator_outputs_total")}
        # identical topology + identical app name: without the tenant
        # label these samples would collide indistinguishably
        assert {("twin_app", "twin_a"), ("twin_app", "twin_b")} <= pairs
    finally:
        server.stop()


# ---------------------------------------------------------------------------
# monitor lifecycle: two graphs in one process each assert END_APP;
# abnormal termination carries the Aborted marker per graph
# ---------------------------------------------------------------------------

def test_monitor_two_graphs_end_app_and_abort():
    from windflow_tpu.monitoring import DashboardServer
    server = DashboardServer(tcp_port=0, http_port=0).start()
    try:
        ok = _graph("mt_ok_app", "mt_ok", "mo", lambda i: i % KEYS,
                    tracing_enabled=True, dashboard_host="127.0.0.1",
                    dashboard_port=server.tcp_port, n=1024)
        _drive(ok)

        def boom(t):
            if t["v"] > 500:
                raise ValueError("seeded operator crash")
            return {"key": t["key"], "v": t["v"]}
        cfg = dataclasses.replace(
            default_config, tenant="mt_bad", tracing_enabled=True,
            dashboard_host="127.0.0.1", dashboard_port=server.tcp_port)
        src = (wf.Source_Builder(
            lambda: iter({"key": i % KEYS, "v": float(i)}
                         for i in range(3000)))
            .withName("mb_src").withOutputBatchSize(CAP).build())
        m = wf.Map_Builder(boom).withName("mb_map").build()
        snk = (wf.Sink_Builder(lambda r: None)
               .withName("mb_snk").build())
        bad = wf.PipeGraph("mt_bad_app", wf.ExecutionMode.DEFAULT,
                           config=cfg)
        bad.add_source(src).add(m).add_sink(snk)
        with pytest.raises(ValueError, match="seeded operator crash"):
            bad.run()
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            records = {a.name: a for a in server.apps.values()}
            if {"mt_ok_app", "mt_bad_app"} <= set(records) \
                    and all(r.ended for r in records.values()):
                break
            time.sleep(0.05)
        records = {a.name: a for a in server.apps.values()}
        assert {"mt_ok_app", "mt_bad_app"} <= set(records)
        # END_APP landed per graph — neither stays "live" forever
        assert records["mt_ok_app"].ended
        assert records["mt_bad_app"].ended
        # the abnormal path carries the Aborted marker; the normal one
        # does not
        assert records["mt_bad_app"].reports[-1].get("Aborted") is True
        assert not records["mt_ok_app"].reports[-1].get("Aborted")
    finally:
        server.stop()


# ---------------------------------------------------------------------------
# wf_tenant CLI: rank/plan render, --check budget gate, exit codes
# ---------------------------------------------------------------------------

def _wf_tenant(*args):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "wf_tenant.py"),
         *args], capture_output=True, text=True, timeout=60)


def test_wf_tenant_on_real_stats(two_tenants, tmp_path):
    _, _, stats = two_tenants
    path = tmp_path / "stats.json"
    path.write_text(json.dumps(stats["acme"]))
    # both tenants within budget: --check passes
    r = _wf_tenant("--check", "--stats", str(path))
    assert r.returncode == 0, r.stderr + r.stdout
    assert "OK" in r.stdout
    # render names both tenants with budget bars
    r = _wf_tenant("--stats", str(path))
    assert "acme" in r.stdout and "blue" in r.stdout
    r = _wf_tenant("--json", "--stats", str(path))
    assert json.loads(r.stdout)["advisor"] == "tenancy/1"


def test_wf_tenant_check_gates_over_budget(tmp_path):
    path = tmp_path / "tenant.json"
    path.write_text(json.dumps(_synthetic_section()))  # bare section
    r = _wf_tenant("--check", "--stats", str(path))
    assert r.returncode == 1
    assert "OVER BUDGET" in r.stdout and "hog" in r.stdout
    # the plan run exits 0 (actionable) and names the golden actions
    r = _wf_tenant("--stats", str(path))
    assert r.returncode == 0
    for needle in ("throttle_admission", "rescale_tenant",
                   "drain_shards", "rebalance_hot_tenant"):
        assert needle in r.stdout, needle


def test_wf_tenant_check_gates_attribution_gap(tmp_path):
    sec = _synthetic_section()
    for name in list(sec["tenants"]):
        sec["tenants"][name].pop("budget", None)   # nothing over budget
    sec["attributed"]["staged_fraction"] = 0.5
    path = tmp_path / "tenant.json"
    path.write_text(json.dumps(sec))
    r = _wf_tenant("--check", "--stats", str(path))
    assert r.returncode == 1
    assert "ATTRIBUTION GAP" in r.stdout
    # the floor is tunable: --min-fraction under the reported value passes
    r = _wf_tenant("--check", "--min-fraction", "0.4",
                   "--stats", str(path))
    assert r.returncode == 0


def test_wf_tenant_rejects_missing_section(tmp_path):
    path = tmp_path / "bare.json"
    path.write_text(json.dumps({"PipeGraph_name": "x"}))
    r = _wf_tenant("--stats", str(path))
    assert r.returncode == 2
    assert "no enabled 'Tenant' section" in r.stderr


# ---------------------------------------------------------------------------
# postmortem: tenant.json rides the bundle, wf_doctor renders +
# validates it, corrupt sections reject, old bundles stay valid
# ---------------------------------------------------------------------------

def _wf_doctor(*args):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "wf_doctor.py"),
         *args], capture_output=True, text=True, timeout=60)


@pytest.fixture()
def tenant_bundle(tmp_path):
    default_ledger().reset()   # isolate: the bundle snapshots the
    g = _graph("ten_pm_app", "pm_tenant", "pm", lambda i: i % KEYS,
               budget=1, log_dir=str(tmp_path))
    _drive(g)
    for _ in range(ENTER_AFTER):
        default_ledger().tick(tenant="pm_tenant", force=True)
    bundle = g.dump_postmortem(str(tmp_path / "pm"), reason="manual")
    with open(os.path.join(bundle, "manifest.json")) as f:
        manifest = json.load(f)
    assert "tenant.json" in manifest["files"]
    return bundle


def test_postmortem_tenant_roundtrips_wf_doctor(tenant_bundle):
    r = _wf_doctor("--check", tenant_bundle)
    assert r.returncode == 0, r.stderr
    # the jax-free render names the worst-pressure tenant + the verdict
    r = _wf_doctor(tenant_bundle)
    assert r.returncode == 0, r.stderr
    assert "tenancy:" in r.stdout
    assert "pm_tenant" in r.stdout
    assert "OVER BUDGET (latched)" in r.stdout


def test_wf_doctor_rejects_corrupt_tenant_section(tenant_bundle):
    tp = os.path.join(tenant_bundle, "tenant.json")
    with open(tp) as f:
        ten = json.load(f)
    ten["tenants"]["pm_tenant"]["budget"]["verdict"]["state"] = "HUNGRY"
    with open(tp, "w") as f:
        json.dump(ten, f)
    r = _wf_doctor("--check", tenant_bundle)
    assert r.returncode == 1
    assert "OVER_BUDGET" in r.stderr
    # structurally wrong type rejects too
    ten["tenants"] = ["not", "a", "mapping"]
    with open(tp, "w") as f:
        json.dump(ten, f)
    r = _wf_doctor("--check", tenant_bundle)
    assert r.returncode == 1
    assert "tenants must be an object" in r.stderr


def test_wf_doctor_accepts_pre_tenant_bundle(tenant_bundle):
    # a bundle written before the tenant plane existed has no
    # tenant.json and no manifest entry — it must still validate
    mp = os.path.join(tenant_bundle, "manifest.json")
    with open(mp) as f:
        manifest = json.load(f)
    manifest["files"] = [n for n in manifest["files"]
                         if n != "tenant.json"]
    with open(mp, "w") as f:
        json.dump(manifest, f)
    os.remove(os.path.join(tenant_bundle, "tenant.json"))
    r = _wf_doctor("--check", tenant_bundle)
    assert r.returncode == 0, r.stderr
