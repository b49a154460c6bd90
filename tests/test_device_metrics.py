"""Device-plane observability contracts (docs/OBSERVABILITY.md "Device
plane"): compile-watcher counters + recompile detection + one-time storm
warning, first-compile cost capture, the CPU ``memory_stats() is None``
guard, the OpenMetrics exposition golden format (label escaping, bucket
monotonicity, counter-vs-gauge typing) with the ``wf_metrics --check``
round trip, the dashboard ``/metrics`` endpoint, gauge sampling without a
dashboard (starvation regression), the profiler bridge, and the
annotation off-path budget."""

import dataclasses
import json
import os
import subprocess
import sys
import time
import warnings

import jax
import jax.numpy as jnp
import pytest

import windflow_tpu as wf
from windflow_tpu.basic import default_config
from windflow_tpu.monitoring.jit_registry import (default_registry,
                                                  wf_jit)
from windflow_tpu.monitoring.openmetrics import (parse_exposition,
                                                 render_openmetrics)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _graph(name, n=3000, cap=512, **cfg_kw):
    cfg_kw.setdefault("flight_recorder", True)
    cfg_kw.setdefault("trace_sample_every", 2)
    cfg = dataclasses.replace(default_config, **cfg_kw)
    src = (wf.Source_Builder(
        lambda: iter({"key": i % 8, "v": float(i)} for i in range(n)))
        .withName("src").withOutputBatchSize(cap).build())
    m = (wf.MapTPU_Builder(lambda t: {"key": t["key"], "v": t["v"] * 2.0})
         .withName(f"{name}_map").build())
    seen = []
    snk = (wf.Sink_Builder(lambda t, ctx=None: seen.append(t))
           .withName("snk").build())
    g = wf.PipeGraph(name, wf.ExecutionMode.DEFAULT, config=cfg)
    g.add_source(src).add(m).add_sink(snk)
    return g, seen


@pytest.fixture(scope="module")
def ran_stats():
    """One shared small traced run: (graph, stats dict)."""
    g, seen = _graph("dm_shared")
    g.run()
    assert seen
    return g, g.stats()


# ---------------------------------------------------------------------------
# compile watcher: counts, recompiles, warning, cost table
# ---------------------------------------------------------------------------

def test_wf_jit_counts_and_cost_table():
    f = wf_jit(lambda x: x * 2 + 1, op_name="dm_probe_basic")
    f(jnp.ones(16, jnp.float32))
    f(jnp.ones(16, jnp.float32))      # cache hit: no second compile
    e = default_registry().snapshot()["dm_probe_basic"]
    assert e["compiles"] == 1
    assert e["recompiles"] == 0
    assert e["compile_ms_total"] > 0
    # CPU backend provides cost analysis: FLOPs + bytes accessed captured
    # on the first compile (mode 'lowered' by default, see jit_registry)
    assert e["cost"] is not None
    assert e["cost"]["flops"] > 0
    assert e["cost"]["bytes_accessed"] > 0


def test_wf_jit_recompile_exactly_once_plus_one_time_warning():
    f = wf_jit(lambda x: x + 1, op_name="dm_probe_recompile")
    f(jnp.ones(8, jnp.float32))
    # forced shape change: exactly one recompile count + one warning
    with pytest.warns(RuntimeWarning, match="signature changed"):
        f(jnp.ones(12, jnp.float32))
    e = default_registry().snapshot()["dm_probe_recompile"]
    assert e["compiles"] == 2 and e["recompiles"] == 1
    # same shape again: nothing moves
    f(jnp.ones(12, jnp.float32))
    e = default_registry().snapshot()["dm_probe_recompile"]
    assert e["compiles"] == 2 and e["recompiles"] == 1
    # a THIRD signature recompiles again but warns no second time
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        f(jnp.ones(16, jnp.float32))
    assert not [w for w in rec if "wf_jit" in str(w.message)]
    e = default_registry().snapshot()["dm_probe_recompile"]
    assert e["compiles"] == 3 and e["recompiles"] == 2


def test_wf_jit_python_scalar_args_do_not_fabricate_recompiles():
    """jax.jit traces a weak-typed Python scalar once per dtype, not per
    value — the signature must key scalars by type or every distinct int
    would count as a recompile (and fire a false storm warning) while
    JAX never re-traces."""
    f = wf_jit(lambda x, k: x * k, op_name="dm_probe_scalar")
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        for k in range(4):
            f(jnp.arange(4), k)
    assert not [w for w in rec if "wf_jit" in str(w.message)]
    e = default_registry().snapshot()["dm_probe_scalar"]
    assert e["compiles"] == 1 and e["recompiles"] == 0


def test_wf_jit_fresh_instance_is_compile_not_recompile():
    a = wf_jit(lambda x: x - 1, op_name="dm_probe_instances")
    a(jnp.ones(8, jnp.float32))
    b = wf_jit(lambda x: x - 1, op_name="dm_probe_instances")
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        b(jnp.ones(24, jnp.float32))   # new instance, new shape: no storm
    assert not [w for w in rec if "wf_jit" in str(w.message)]
    e = default_registry().snapshot()["dm_probe_instances"]
    assert e["compiles"] == 2 and e["recompiles"] == 0


def test_operator_shape_change_recompiles():
    """The real op wiring: one MapTPU jit fed two capacities."""
    m = wf.MapTPU_Builder(lambda t: {"v": t["v"] * 2.0}) \
        .withName("dm_op_shape").build()
    m._jit_step({"v": jnp.ones(64, jnp.float32)}, jnp.ones(64, bool))
    with pytest.warns(RuntimeWarning, match="signature changed"):
        m._jit_step({"v": jnp.ones(128, jnp.float32)}, jnp.ones(128, bool))
    e = default_registry().snapshot()["dm_op_shape"]
    assert e["compiles"] == 2 and e["recompiles"] == 1


# ---------------------------------------------------------------------------
# stats()["Device"]: per-op table, CPU memory guard, staging accounting
# ---------------------------------------------------------------------------

def test_device_section_schema_and_cpu_guard(ran_stats):
    _, st = ran_stats
    dev = st["Device"]
    # per-op compile table covers the graph's device operator
    e = dev["jit"]["dm_shared_map"]
    assert e["compiles"] >= 1
    assert e["recompiles"] == 0
    assert e["compile_ms_total"] > 0
    assert e["cost"] is not None and e["cost"]["flops"] > 0
    totals = dev["jit_totals"]
    assert totals["compiles"] >= totals["ops_compiled"] >= 1
    # CPU guard: memory_stats() is None on the CPU backend — reported,
    # not crashed on
    assert dev["memory"], "no local devices reported"
    for d in dev["memory"]:
        assert d["platform"] == "cpu"
        assert d["stats"] is None
    assert dev["live_buffers"]["count"] >= 0
    # the staged run shipped real bytes through the staging accounting
    assert dev["staging"]["staged_device_bytes_total"] > 0
    assert dev["staging"]["staged_device_batches_total"] > 0
    json.dumps(dev)     # the whole section must ship in NEW_REPORT


# ---------------------------------------------------------------------------
# OpenMetrics exposition: golden format, escaping, typing, round trip
# ---------------------------------------------------------------------------

def test_openmetrics_golden_format_real_stats(ran_stats):
    _, st = ran_stats
    text = render_openmetrics(st)
    fams = parse_exposition(text)       # raises on any format violation
    assert fams["wf_operator_outputs_total"]["type"] == "counter"
    assert fams["wf_queue_depth"]["type"] == "gauge"
    assert fams["wf_throughput_tps"]["type"] == "gauge"
    assert fams["wf_jit_compiles_total"]["type"] == "counter"
    assert fams["wf_service_latency_usec"]["type"] == "histogram"
    # histogram really exposes buckets: _bucket/_sum/_count samples
    names = {n for n, _, _ in fams["wf_service_latency_usec"]["samples"]}
    assert names == {"wf_service_latency_usec_bucket",
                     "wf_service_latency_usec_sum",
                     "wf_service_latency_usec_count"}
    # every sample carries the app label
    for fam in fams.values():
        for _, labels, _ in fam["samples"]:
            assert labels.get("app") == "dm_shared"
    # watermark-lag gauge exists for the graph's operators
    lag_ops = {lab["operator"] for _, lab, _
               in fams["wf_watermark_lag_usec"]["samples"]}
    assert "dm_shared_map" in lag_ops or "snk" in lag_ops


def test_openmetrics_label_escaping_round_trips():
    nasty = 'evil"op\\name\nnewline'
    stats = {
        "PipeGraph_name": 'app"with\\quirks',
        "Operators": [{"Operator_name": nasty,
                       "Replicas": [{"Inputs_received": 3,
                                     "Outputs_sent": 2}]}],
    }
    text = render_openmetrics(stats)
    fams = parse_exposition(text)
    ops = [lab["operator"] for _, lab, _
           in fams["wf_operator_outputs_total"]["samples"]]
    assert ops == [nasty]     # escaped on the wire, intact after parsing


def test_openmetrics_parser_rejects_violations():
    ok = ("# TYPE wf_x_total counter\n"
          "wf_x_total 1\n")
    parse_exposition(ok)
    with pytest.raises(ValueError, match="without a preceding"):
        parse_exposition("wf_orphan 1\n")
    with pytest.raises(ValueError, match="decrease"):
        parse_exposition(
            "# TYPE wf_h histogram\n"
            'wf_h_bucket{le="1"} 5\n'
            'wf_h_bucket{le="2"} 3\n'
            'wf_h_bucket{le="+Inf"} 3\n'
            "wf_h_sum 4\n"
            "wf_h_count 3\n")
    with pytest.raises(ValueError, match="no \\+Inf"):
        parse_exposition(
            "# TYPE wf_h histogram\n"
            'wf_h_bucket{le="1"} 5\n'
            "wf_h_sum 4\n"
            "wf_h_count 5\n")
    with pytest.raises(ValueError, match="_count"):
        parse_exposition(
            "# TYPE wf_h histogram\n"
            'wf_h_bucket{le="+Inf"} 4\n'
            "wf_h_sum 4\n"
            "wf_h_count 5\n")
    with pytest.raises(ValueError, match="negative counter"):
        parse_exposition("# TYPE wf_c_total counter\nwf_c_total -1\n")


def test_wf_metrics_check_round_trip(ran_stats, tmp_path):
    g, st = ran_stats
    path = tmp_path / "stats.json"
    path.write_text(json.dumps(st))
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "wf_metrics.py"),
         str(path), "--check"],
        capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stderr
    assert "OK" in proc.stdout
    # render mode emits parseable text too
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "wf_metrics.py"),
         str(path)],
        capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0
    parse_exposition(proc.stdout)


# ---------------------------------------------------------------------------
# dashboard /metrics endpoint
# ---------------------------------------------------------------------------

def test_dashboard_metrics_endpoint():
    import urllib.request
    from windflow_tpu.monitoring import DashboardServer
    server = DashboardServer(tcp_port=0, http_port=0).start()
    try:
        g, _ = _graph("dm_dash", tracing_enabled=True,
                      dashboard_host="127.0.0.1",
                      dashboard_port=server.tcp_port)
        g.run()
        with urllib.request.urlopen(
                f"http://127.0.0.1:{server.http_port}/metrics",
                timeout=5) as r:
            assert r.status == 200
            assert r.headers["Content-Type"].startswith("text/plain")
            text = r.read().decode()
        fams = parse_exposition(text)
        # the acceptance surface: throughput, latency histograms,
        # watermark lag, and the device plane, all scrapeable
        for family in ("wf_operator_outputs_total", "wf_throughput_tps",
                       "wf_service_latency_usec", "wf_watermark_lag_usec",
                       "wf_jit_compiles_total", "wf_live_buffer_bytes"):
            assert fams[family]["samples"], f"{family} empty"
        apps = {lab.get("app") for _, lab, _
                in fams["wf_operator_outputs_total"]["samples"]}
        assert "dm_dash" in apps
    finally:
        server.stop()


# ---------------------------------------------------------------------------
# monitor gauge starvation (satellite): sample without a dashboard
# ---------------------------------------------------------------------------

def test_monitor_samples_without_dashboard():
    from windflow_tpu.monitoring.monitor import MonitoringThread
    # dashboard_port points at nothing: connection refused -> no shipping
    g, _ = _graph("dm_headless", n=20000, cap=256,
                  dashboard_port=1)     # port 1: guaranteed refused
    g.start()
    mt = MonitoringThread(g, interval=0.02)
    mt.start()
    deadline = time.monotonic() + 2.0
    while not g.is_done() and time.monotonic() < deadline:
        g.step()
        time.sleep(0.002)
    g.wait_end()
    mt.stop()
    assert mt.active is False           # never connected
    # the regression: before the split, zero samples were taken when the
    # TCP connection was down and the rolling windows never advanced
    assert mt.samples_taken >= 1
    assert len(g._thr_samples) >= 2


# ---------------------------------------------------------------------------
# profiler bridge + annotation off-path budget
# ---------------------------------------------------------------------------

@pytest.mark.slow   # jax.profiler start/stop serializes an xplane capture
#                     (~17s on CPU CI regardless of capture length)
def test_profile_bridge_writes_capture(tmp_path):
    g, seen = _graph("dm_prof", n=20000, cap=256)
    g.start()
    d = g.profile(duration_ms=150, log_dir=str(tmp_path / "xprof"))
    g.wait_end()
    assert seen
    assert os.path.isdir(d)
    prof = os.path.join(d, "plugins", "profile")
    assert os.path.isdir(prof) and os.listdir(prof)


def test_dump_trace_carries_profiler_cross_reference(tmp_path):
    g, _ = _graph("dm_xref")
    g.run()
    path = g.dump_trace(str(tmp_path / "dm_xref_trace.json"))
    with open(path) as f:
        trace = json.load(f)
    other = trace["otherData"]
    # the layer spans are inside a profiler capture itself: this file
    # carries their table, on its own (wall) clock, and points at no
    # second file to line up by eye
    assert "profiler_annotation_format" not in other
    assert "profiler_dir" not in other
    assert other["clock"] == "wall_usec"
    assert other["layers"] == g.stats()["Layers"]
    assert other["layers"]["wf.dispatch"]["count"] > 0


class _CountingAnnotation:
    count = 0
    dispatches = 0

    def __init__(self, name, **counts):
        _CountingAnnotation.count += 1
        _CountingAnnotation.dispatches += name == "wf.dispatch" \
            and counts.get("op", "").endswith("_map")

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **counts):
        pass


def test_annotation_off_path_is_one_attribute_check(monkeypatch):
    """Recorder off => no root span => no site ever constructs a
    TraceAnnotation or writes a table (the documented off-path budget:
    one `is None` check per site)."""
    monkeypatch.setattr(jax.profiler, "TraceAnnotation",
                        _CountingAnnotation)
    _CountingAnnotation.count = 0
    g, _ = _graph("dm_annot_off", flight_recorder=False)
    g.run()
    assert _CountingAnnotation.count == 0
    assert g.stats()["Layers"] == {}
    # and with it on EVERY dispatch is annotated, whatever the sampling
    _CountingAnnotation.count = _CountingAnnotation.dispatches = 0
    g, _ = _graph("dm_annot_on", flight_recorder=True,
                  trace_sample_every=64)
    g.run()
    launched = sum(r["Device_programs_launched"]
                   for op in g.stats()["Operators"]
                   if op["Operator_name"] == "dm_annot_on_map"
                   for r in op["Replicas"])
    assert _CountingAnnotation.dispatches == launched > 1
