"""Sweep-ledger + fusion-advisor contracts (docs/OBSERVABILITY.md
"Sweep ledger & fusion advisor"): exact per-hop dispatch counts on a
known 3-op chain (and the chained pair's REAL single dispatch), per-hop
bytes matching an independent XLA cost measurement, a seeded
donation-miss caught, the advisor's golden plan on the bench graph
shape, the OpenMetrics/trace/postmortem surfaces, and the kill-switch
off-path budget."""

import dataclasses
import importlib.util
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import windflow_tpu as wf
from windflow_tpu.basic import default_config

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

N_BATCHES = 8
CAP = 256


def _cfg(tmp_path=None, **kw):
    if tmp_path is not None:
        kw.setdefault("log_dir", str(tmp_path))
    # these tests pin the UNFUSED sweep: they assert the pre-fusion
    # per-hop dispatch/byte contracts (one jitted dispatch per operator
    # hop); the fused-sweep contracts live in tests/test_fusion.py
    kw.setdefault("whole_chain_fusion", False)
    return dataclasses.replace(default_config, **kw)


def _spec():
    return {"key": np.int32(0), "v": np.float32(0.0)}


def _source(n=N_BATCHES * CAP, cap=CAP):
    # typed values, so host staging infers exactly the declared
    # int32/float32 record spec (untyped Python ints stage as int64 and
    # the payload model would understate the real lanes)
    return (wf.Source_Builder(
        lambda: iter({"key": np.int32(i % 8), "v": np.float32(i)}
                     for i in range(n)))
        .withName("src").withOutputBatchSize(cap)
        .withRecordSpec(_spec()).build())


def _three_op_graph(cfg, chained=False):
    """src -> ma -> fb -> mc -> snk; with ``chained`` the (ma, fb) pair
    fuses into ONE XLA program via MultiPipe.chain."""
    ma = (wf.MapTPU_Builder(lambda t: {"key": t["key"], "v": t["v"] * 2.0})
          .withName("ma").build())
    fb = (wf.FilterTPU_Builder(lambda t: (t["key"] & 1) == 0)
          .withName("fb").build())
    mc = (wf.MapTPU_Builder(lambda t: {"key": t["key"], "v": t["v"] + 1.0})
          .withName("mc").build())
    snk = wf.Sink_Builder(lambda t, ctx=None: None).withName("snk").build()
    g = wf.PipeGraph("sweep_app", wf.ExecutionMode.DEFAULT, config=cfg)
    pipe = g.add_source(_source())
    pipe.add(ma)
    pipe.chain(fb) if chained else pipe.add(fb)
    pipe.add(mc).add_sink(snk)
    return g


@pytest.fixture(scope="module")
def run_graph(tmp_path_factory):
    """One shared 3-op run: the per-hop dispatch, donation, OpenMetrics
    and postmortem contracts all read the same ledger section."""
    g = _three_op_graph(_cfg(tmp_path_factory.mktemp("sweep")))
    g.run()
    return g


# ---------------------------------------------------------------------------
# dispatch accounting
# ---------------------------------------------------------------------------

def test_three_op_chain_exact_dispatches(run_graph):
    sweep = run_graph.stats()["Sweep"]
    assert sweep["enabled"] is True
    for name in ("ma", "fb", "mc"):
        hop = sweep["per_hop"][name]
        assert hop["batches"] == N_BATCHES
        assert hop["dispatches"] == N_BATCHES
        assert hop["dispatches_per_batch"] == 1.0
        assert hop["capacity"] == CAP
    # hop-boundary residency: ma/fb feed the next TPU hop on device
    # (fusion fuel); mc's output leaves for the host sink
    assert sweep["per_hop"]["ma"]["resident_output"] is True
    assert sweep["per_hop"]["fb"]["resident_output"] is True
    assert sweep["per_hop"]["mc"]["resident_output"] is False
    assert sweep["totals"]["dispatches_per_batch"] == 3.0
    # JSON-clean: the section ships in every NEW_REPORT payload
    json.dumps(sweep)


def test_chained_pair_shows_one_dispatch(tmp_path):
    """ops/chained.py fusion is visible in the ledger: the fused ma|fb
    hop pays ONE jitted dispatch per batch where the unchained pair
    (previous test) pays two."""
    g = _three_op_graph(_cfg(tmp_path), chained=True)
    g.run()
    sweep = g.stats()["Sweep"]
    assert "ma" not in sweep["per_hop"] and "fb" not in sweep["per_hop"]
    hop = sweep["per_hop"]["ma|fb"]
    assert hop["batches"] == N_BATCHES
    assert hop["dispatches"] == N_BATCHES
    assert hop["dispatches_per_batch"] == 1.0
    assert sweep["totals"]["dispatches_per_batch"] == 2.0


# ---------------------------------------------------------------------------
# byte attribution vs an independent XLA cost measurement
# ---------------------------------------------------------------------------

def test_per_hop_bytes_match_independent_cost(tmp_path, monkeypatch):
    """The map hop's attributed bytes/batch must match what XLA's
    compiled cost analysis reports for the IDENTICAL program measured
    outside the ledger, and the totals must sum the hops."""
    import jax
    import jax.numpy as jnp
    from windflow_tpu.monitoring import jit_registry

    monkeypatch.setattr(jit_registry, "COST_MODE", "compiled")
    fn = lambda t: {"key": t["key"], "v": t["v"] * 2.0}
    ma = wf.MapTPU_Builder(fn).withName("bytes_ma").build()
    snk = wf.Sink_Builder(lambda t, ctx=None: None).withName("snk").build()
    g = wf.PipeGraph("sweep_bytes", wf.ExecutionMode.DEFAULT,
                     config=_cfg(tmp_path))
    g.add_source(_source()).add(ma).add_sink(snk)
    g.run()
    sweep = g.stats()["Sweep"]
    hop = sweep["per_hop"]["bytes_ma"]
    assert hop["dispatches_per_batch"] == 1.0

    def step(payload, valid):
        return jax.vmap(fn)(payload)

    payload = {"key": jnp.zeros(CAP, jnp.int32),
               "v": jnp.zeros(CAP, jnp.float32)}
    valid = jnp.ones(CAP, bool)
    ca = jax.jit(step).lower(payload, valid).compile().cost_analysis()
    d = ca[0] if isinstance(ca, (list, tuple)) else ca
    measured = float(d["bytes accessed"])
    assert measured > 0
    assert abs(hop["bytes_per_batch"] - measured) / measured < 0.10, \
        (hop, measured)
    # the per-hop bytes sum to the section's totals
    total = sum(h["bytes_per_tuple"] for h in sweep["per_hop"].values()
                if h.get("bytes_per_tuple") is not None)
    assert abs(sweep["totals"]["bytes_per_tuple"] - total) < 0.1
    # payload-vs-overhead split against the declared record spec:
    # int32 + float32 payload + ts/valid lanes = 17 B/tuple model
    assert hop["payload_bytes_per_tuple"] == 17
    assert hop["excess_vs_model"] == pytest.approx(
        hop["bytes_per_tuple"] / 17, abs=0.01)


@pytest.mark.slow
def test_window_hop_bytes_match_kernel_measurement(tmp_path, monkeypatch):
    """Acceptance-shaped: on a bench-shaped pipeline the WINDOW hop's
    per-batch attributed bytes land within 10% of the raw FFAT kernel
    step's measured bytes (XLA's compiled cost analysis of the same
    shape, taken independently)."""
    import math

    import jax
    import jax.numpy as jnp

    from windflow_tpu.monitoring import jit_registry
    from windflow_tpu.windows.ffat_kernels import (make_ffat_state,
                                                   make_ffat_step)

    monkeypatch.setattr(jit_registry, "COST_MODE", "compiled")
    K, WIN, SLIDE = 16, 64, 16
    lift = lambda t: t["v"]
    comb = lambda a, b: a + b
    key_fn = lambda t: t["key"]
    win = (wf.Ffat_WindowsTPU_Builder(lift, comb)
           .withCBWindows(WIN, SLIDE).withKeyBy(key_fn)
           .withMaxKeys(K).withName("slow_win").build())
    snk = wf.Sink_Builder(lambda t, ctx=None: None).withName("snk").build()
    g = wf.PipeGraph("sweep_win", wf.ExecutionMode.DEFAULT,
                     config=_cfg(tmp_path))
    g.add_source(_source(n=32 * CAP)).add(win).add_sink(snk)
    g.run()
    hop = g.stats()["Sweep"]["per_hop"]["slow_win"]
    # 32 data batches; the EOS flush may add one synthetic batch
    assert hop["batches"] in (32, 33)

    Pn = math.gcd(WIN, SLIDE)
    step_fn = make_ffat_step(CAP, K, Pn, WIN // Pn, SLIDE // Pn,
                             lift, comb, key_fn)
    state = make_ffat_state(jnp.zeros((), jnp.float32), K, WIN // Pn)
    payload = {"key": jnp.zeros(CAP, jnp.int32),
               "v": jnp.zeros(CAP, jnp.float32)}
    ts = jnp.zeros(CAP, jnp.int64)
    valid = jnp.ones(CAP, bool)
    ca = (jax.jit(step_fn, donate_argnums=(0,))
          .lower(state, payload, ts, valid).compile().cost_analysis())
    d = ca[0] if isinstance(ca, (list, tuple)) else ca
    measured = float(d["bytes accessed"])
    assert measured > 0
    assert abs(hop["bytes_per_batch"] - measured) / measured < 0.10, \
        (hop, measured)
    # the steady-state number excludes the EOS flush entirely: exact
    # (same program, same cost table)
    steady = hop["steady_bytes_per_tuple"] * CAP
    assert abs(steady - measured) / measured < 0.01, (steady, measured)


# ---------------------------------------------------------------------------
# donation misses
# ---------------------------------------------------------------------------

def test_seeded_donation_miss_caught(run_graph):
    """MapTPU's step returns same-shape/dtype buffers without donating
    its inputs: every batch pays a whole-buffer copy the ledger must
    flag as a donation miss."""
    sweep = run_graph.stats()["Sweep"]
    miss = sweep["per_hop"]["ma"]["donation_miss"]
    assert miss["candidate_leaves"] >= 1
    assert miss["bytes_per_batch"] > 0
    assert miss["donates_some_args"] is False
    assert sweep["totals"]["donation_miss_bytes_per_batch"] > 0


def test_ffat_state_donation_recorded(tmp_path):
    """The FFAT step donates its state (argnum 0): the registry's
    donation audit must record it, so the ledger never flags the state
    round-trip as a miss."""
    win = (wf.Ffat_WindowsTPU_Builder(lambda t: t["v"],
                                      lambda a, b: a + b)
           .withCBWindows(64, 16).withKeyBy(lambda t: t["key"])
           .withMaxKeys(16).withName("don_win").build())
    snk = wf.Sink_Builder(lambda t, ctx=None: None).withName("snk").build()
    g = wf.PipeGraph("sweep_don", wf.ExecutionMode.DEFAULT,
                     config=_cfg(tmp_path))
    g.add_source(_source()).add(win).add_sink(snk)
    g.run()
    from windflow_tpu.monitoring.jit_registry import default_registry
    entry = default_registry().snapshot()["don_win"]
    assert entry["donation"]["donated_argnums"] == [0]


# ---------------------------------------------------------------------------
# fusion advisor
# ---------------------------------------------------------------------------

def _bench_shape_graph():
    """The staged end-to-end pipeline shape (map + chained filter ->
    keyed FFAT window -> sink) the advisor's golden plan targets."""
    src = (wf.Source_Builder(lambda: iter(()))
           .withOutputBatchSize(4096).withName("src")
           .withRecordSpec({"key": np.int32(0), "v0": np.float32(0.0)})
           .build())
    m = wf.MapTPU_Builder(
        lambda t: {"key": t["key"], "v0": t["v0"] * 1.5 + 1.0}).build()
    f = wf.FilterTPU_Builder(lambda t: (t["key"] & 7) != 7).build()
    w = (wf.Ffat_WindowsTPU_Builder(lambda t: t["v0"],
                                    lambda a, b: a + b)
         .withCBWindows(1024, 128).withKeyBy(lambda t: t["key"])
         .withMaxKeys(256).build())
    snk = wf.Sink_Builder(lambda r: None).build()
    g = wf.PipeGraph("bench_shape")
    pipe = g.add_source(src)
    pipe.add(m)
    pipe.chain(f)
    pipe.add(w).add_sink(snk)
    return g


def test_advisor_golden_plan_on_bench_graph():
    """>= 1 ranked fusion candidate on the bench pipeline, with
    projected bytes- and dispatches-saved (the acceptance contract):
    the already-chained map|filter pair plus the window hop lower into
    one program under whole-chain fusion."""
    from windflow_tpu.analysis.fusion import plan
    p = plan(_bench_shape_graph())
    assert len(p["chains"]) >= 1
    top = p["chains"][0]
    assert top["ops"] == ["map_tpu|filter_tpu", "ffat_windows_tpu"]
    assert top["links"] == ["whole_chain"]
    assert top["provable_now"] is False
    assert top["dispatches_saved_per_batch"] >= 1
    assert top["projected_bytes_saved_per_batch"] > 0
    json.dumps(p)


def test_advisor_unchained_pair_is_provable_now(tmp_path):
    """A map->filter pair composed with add() (not chain()) is a fusion
    candidate TODAY: the advisor must rank it as provable via
    MultiPipe.chain, with measured dispatch counts when given a live
    sweep section."""
    from windflow_tpu.analysis.fusion import plan
    g = _three_op_graph(_cfg(tmp_path))
    g.run()
    p = plan(g, sweep=g.stats()["Sweep"])
    assert p["chains"], p
    top = p["chains"][0]
    assert top["ops"] == ["ma", "fb", "mc"]
    assert all(k == "chainable" for k in top["links"])
    assert top["provable_now"] is True
    assert top["basis"] == "measured"
    assert top["dispatches_per_batch_now"] == 3.0
    assert top["dispatches_saved_per_batch"] == 2.0
    assert top["projected_bytes_saved_per_batch"] > 0


@pytest.mark.slow
def test_advisor_cli_emits_ranked_json_plan(tmp_path):
    """tools/wf_advisor.py round trip: module factory -> ranked JSON
    plan on stdout, exit 0 when candidates exist."""
    app = tmp_path / "advisor_app.py"
    app.write_text(
        "import numpy as np\n"
        "import windflow_tpu as wf\n\n"
        "def make_graph():\n"
        "    src = (wf.Source_Builder(lambda: iter(()))\n"
        "           .withOutputBatchSize(512).withName('src')\n"
        "           .withRecordSpec({'key': np.int32(0),\n"
        "                            'v': np.float32(0.0)}).build())\n"
        "    a = wf.MapTPU_Builder(\n"
        "        lambda t: {'key': t['key'], 'v': t['v'] * 2.0}).build()\n"
        "    b = wf.FilterTPU_Builder(\n"
        "        lambda t: (t['key'] & 1) == 0).build()\n"
        "    snk = wf.Sink_Builder(lambda r: None).build()\n"
        "    g = wf.PipeGraph('cli_app')\n"
        "    g.add_source(src).add(a).add(b).add_sink(snk)\n"
        "    return g\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(tmp_path))
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "wf_advisor.py"),
         "advisor_app", "--json"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=180)
    assert r.returncode == 0, r.stderr
    p = json.loads(r.stdout)
    assert p["graph"] == "cli_app"
    assert p["chains"][0]["ops"] == ["map_tpu", "filter_tpu"]
    assert p["chains"][0]["provable_now"] is True


# ---------------------------------------------------------------------------
# surfaces: OpenMetrics, trace metadata, postmortem + wf_doctor
# ---------------------------------------------------------------------------

def test_openmetrics_sweep_families_render_and_parse(run_graph):
    from windflow_tpu.monitoring.openmetrics import (parse_exposition,
                                                     render_openmetrics)
    fams = parse_exposition(render_openmetrics(run_graph.stats()))
    disp = fams["wf_sweep_dispatches_per_batch"]["samples"]
    ops = {labels["operator"]: value for _, labels, value in disp}
    assert ops["ma"] == 1.0 and ops["fb"] == 1.0 and ops["mc"] == 1.0
    assert "wf_sweep_bytes_per_tuple" in fams
    miss = fams["wf_sweep_donation_miss_bytes_per_batch"]["samples"]
    assert any(v > 0 for _, _, v in miss)


def test_dump_trace_metadata_carries_sweep(run_graph, tmp_path):
    path = run_graph.dump_trace(str(tmp_path / "t_trace.json"))
    with open(path) as f:
        trace = json.load(f)
    sweep = trace["otherData"]["sweep"]
    assert sweep["enabled"] is True
    assert "ma" in sweep["per_hop"]


def _load_doctor():
    spec = importlib.util.spec_from_file_location(
        "wf_doctor", os.path.join(REPO, "tools", "wf_doctor.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_postmortem_sweep_section_roundtrips_wf_doctor(run_graph,
                                                       tmp_path):
    doctor = _load_doctor()
    d = run_graph.dump_postmortem(str(tmp_path / "bundle"),
                                  reason="sweep test")
    bundle = doctor.load_bundle(d)
    doctor.validate(bundle)
    assert bundle["sections"]["sweep.json"]["enabled"] is True
    diag = doctor.diagnose(bundle)
    assert diag["sweep_top_hop"]["op"] in ("ma", "fb", "mc")
    assert "ma" in diag["donation_misses"]
    text = doctor.render_text(diag)
    assert "hottest hop" in text and "donation miss" in text
    # a corrupted sweep section must fail --check, not render garbage
    sweep_path = os.path.join(d, "sweep.json")
    with open(sweep_path) as f:
        sweep = json.load(f)
    sweep["per_hop"]["ma"]["bytes_per_tuple"] = "lots"
    with open(sweep_path, "w") as f:
        json.dump(sweep, f)
    with pytest.raises(doctor.BundleError):
        doctor.validate(doctor.load_bundle(d))


# ---------------------------------------------------------------------------
# kill switch
# ---------------------------------------------------------------------------

def test_kill_switch_off_path_budget(tmp_path):
    g = _three_op_graph(_cfg(tmp_path, sweep_ledger=False))
    g.run()
    assert g._ledger is None
    assert g.stats()["Sweep"] == {"enabled": False}
    # off-path budget (mirrors test_health_disabled_off_path): the
    # disabled read site is ONE `is not None` check — micro-assert it
    # stays orders of magnitude under a real section build.  The
    # per-batch path carries no ledger hook at all either way (the
    # dispatch counter belongs to the compile watcher).
    t0 = time.perf_counter()
    for _ in range(10_000):
        g._plane_section(g._ledger)
    per_call = (time.perf_counter() - t0) / 10_000
    assert per_call < 5e-6, \
        f"disabled sweep section costs {per_call * 1e6:.2f}us/call"
    from windflow_tpu.monitoring.openmetrics import render_openmetrics
    assert "wf_sweep_" not in render_openmetrics(g.stats())
