"""wfir contracts (docs/ANALYSIS.md "wfir"): golden StableHLO substring
fixtures pin every WF90x detector against jaxlib text drift (one seeded
violation + one clean twin per code), real lowerings prove the
donation/callback markers on the jax this repo actually runs, the WF901
aligned/unaligned mesh reduce twin from the acceptance contract, the
preflight/stats/postmortem wiring, the wf_ir CLI round trip, the
zero-extra-compile pin (the audit parses the compile watcher's existing
first-compile lowering — registry counters must not move), the WF905
static/runtime donation-miss cross-validation, the registry
capture-failure one-time warning, and the kill-switch off-path budget."""

import dataclasses
import importlib.util
import json
import os
import subprocess
import sys
import time
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import windflow_tpu as wf
from windflow_tpu.analysis import ir_audit
from windflow_tpu.basic import default_config

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CAP = 256
N = 8 * CAP


def _spec():
    return {"key": np.int32(0), "v": np.float32(0.0)}


def _source(name="ira_src", n=N, cap=CAP):
    return (wf.Source_Builder(
        lambda: iter({"key": np.int32(i % 8), "v": np.float32(i)}
                     for i in range(n)))
        .withName(name).withOutputBatchSize(cap)
        .withRecordSpec(_spec()).build())


def _map_graph(app, map_name, src_name):
    m = (wf.MapTPU_Builder(lambda t: {"key": t["key"], "v": t["v"] * 2.0})
         .withName(map_name).build())
    snk = wf.Sink_Builder(lambda r: None).withName("snk").build()
    g = wf.PipeGraph(app, wf.ExecutionMode.DEFAULT,
                     config=dataclasses.replace(default_config))
    g.add_source(_source(src_name)).add(m).add_sink(snk)
    return g


@pytest.fixture(scope="module")
def run_graph():
    """One shared run: the audit/stats/postmortem/cross-validation
    contracts all read the same compiled programs."""
    g = _map_graph("ira_app", "ira_ma", "ira_src_shared")
    g.run()
    return g


# ---------------------------------------------------------------------------
# golden StableHLO fixtures: one seeded violation + one clean twin per code
# ---------------------------------------------------------------------------

CLEAN_TWIN = """module @jit_step {
  func.func public @main(%arg0: tensor<64xf32>) -> (tensor<64xf32>) {
    %0 = stablehlo.multiply %arg0, %arg0 : tensor<64xf32>
    %1 = "stablehlo.reduce_window"(%0) <{window = dense<1> : tensor<2xi64>}> : (tensor<64xf32>) -> tensor<64xf32>
    return %1 : tensor<64xf32>
  }
}"""

GOLD_COLLECTIVE = """module @jit_step {
  func.func public @main(%arg0: tensor<16x4xf32>) -> (tensor<128x4xf32>) {
    %0 = "stablehlo.all_gather"(%arg0) <{all_gather_dim = 0 : i64, replica_groups = dense<[[0, 1, 2, 3, 4, 5, 6, 7]]> : tensor<1x8xi64>, use_global_device_ids}> : (tensor<16x4xf32>) -> tensor<128x4xf32>
    return %0 : tensor<128x4xf32>
  }
}"""

#: region-bearing collective on a SCALAR operand (the drop-count psum
#: every mesh layout keeps): must parse numel from the region's closing
#: line, never from the replica_groups attribute tensor
GOLD_SCALAR_REDUCE = """module @jit_step {
  func.func public @main(%arg0: tensor<i64>) -> (tensor<i64>) {
    %0 = "stablehlo.all_reduce"(%arg0) <{replica_groups = dense<[[0, 1, 2, 3, 4, 5, 6, 7]]> : tensor<1x8xi64>, use_global_device_ids}> ({
    ^bb0(%arg1: tensor<i64>, %arg2: tensor<i64>):
      %1 = stablehlo.add %arg1, %arg2 : tensor<i64>
      stablehlo.return %1 : tensor<i64>
    }) : (tensor<i64>) -> tensor<i64>
    return %0 : tensor<i64>
  }
}"""

GOLD_CALLBACK = """module @jit_step {
  func.func public @main(%arg0: tensor<64xf32>) -> (tensor<64xf32>) {
    %0 = stablehlo.custom_call @xla_python_cpu_callback(%arg0) {api_version = 2 : i32} : (tensor<64xf32>) -> tensor<64xf32>
    return %0 : tensor<64xf32>
  }
}"""

GOLD_CALLBACK_ATTR = """module @jit_step {
  func.func public @main(%arg0: tensor<64xf32>) -> (tensor<64xf32>) {
    %0 = "stablehlo.custom_call"(%arg0) {call_target_name = "xla_ffi_python_gpu_callback"} : (tensor<64xf32>) -> tensor<64xf32>
    return %0 : tensor<64xf32>
  }
}"""

GOLD_WIDE = """module @jit_step {
  func.func public @main(%arg0: tensor<64xf32>) -> (tensor<64xf64>) {
    %0 = stablehlo.convert %arg0 : (tensor<64xf32>) -> tensor<64xf64>
    return %0 : tensor<64xf64>
  }
}"""

GOLD_DYNAMIC = """module @jit_step {
  func.func public @main(%arg0: tensor<?xf32>, %arg1: tensor<2xi32>) -> (tensor<?x4xf32>) {
    %0 = stablehlo.dynamic_reshape %arg0, %arg1 : (tensor<?xf32>, tensor<2xi32>) -> tensor<?x4xf32>
    return %0 : tensor<?x4xf32>
  }
}"""

GOLD_ALIASED = """module @jit_step {
  func.func public @main(%arg0: tensor<64xf32> {tf.aliasing_output = 0 : i32}) -> (tensor<64xf32>) {
    %0 = stablehlo.add %arg0, %arg0 : tensor<64xf32>
    return %0 : tensor<64xf32>
  }
}"""

GOLD_TRANSFER = """module @jit_step {
  func.func public @main(%arg0: tensor<f32>, %arg1: !stablehlo.token) -> (!stablehlo.token) {
    %0 = "stablehlo.send"(%arg0, %arg1) <{channel_handle = #stablehlo.channel_handle<handle = 1, type = 2>, is_host_transfer = true}> : (tensor<f32>, !stablehlo.token) -> !stablehlo.token
    return %0 : !stablehlo.token
  }
}"""

GOLD_MOSAIC = """module @jit_step {
  func.func public @main(%arg0: tensor<64xf32>) -> (tensor<64xf32>) {
    %0 = stablehlo.custom_call @tpu_custom_call(%arg0) {backend_config = ""} : (tensor<64xf32>) -> tensor<64xf32>
    return %0 : tensor<64xf32>
  }
}"""


def _codes(findings):
    return sorted({d.code for d in findings})


def test_wf901_collective_fixture_and_clean_twin():
    facts = ir_audit.extract_facts(GOLD_COLLECTIVE)
    assert facts["collectives"] == ["all_gather"]
    assert _codes(ir_audit.program_findings(
        "p", facts, promised_collective_free=True)) == ["WF901"]
    assert _codes(ir_audit.program_findings(
        "p", facts, alignable_unaligned=True)) == ["WF901"]
    # no graph context -> a collective is not a finding by itself
    assert ir_audit.program_findings("p", facts) == []
    clean = ir_audit.extract_facts(CLEAN_TWIN)
    assert clean["collectives"] == []
    assert ir_audit.program_findings(
        "p", clean, promised_collective_free=True) == []


def test_wf901_cross_key_classification():
    """Only NON-scalar collectives whose replica groups span >1 key
    coordinate count as the traffic aligned ingest eliminates: scalar
    counter psums and within-column data-axis gathers are excluded."""
    from windflow_tpu.parallel import mesh as M
    mesh = M.make_mesh(8, data=2)
    axis = mesh.axis_names.index(M.KEY_AXIS)
    key_of = {}
    for idx in np.ndindex(mesh.devices.shape):
        key_of[int(mesh.devices[idx].id)] = idx[axis]
    by_key = {}
    for dev, k in key_of.items():
        by_key.setdefault(k, []).append(dev)
    data_groups = sorted(sorted(v) for v in by_key.values())
    all_ids = sorted(key_of)

    def facts_for(groups, numel):
        return {"collectives": ["all_gather"],
                "collective_ops": [
                    {"op": "all_gather", "groups": groups, "numel": numel}]}

    # whole-mesh non-scalar gather: crossing
    assert ir_audit.cross_key_collectives(
        facts_for([all_ids], 16), mesh) == ["all_gather"]
    # data-axis (same-key-column) gather: NOT crossing
    assert ir_audit.cross_key_collectives(
        facts_for(data_groups, 16), mesh) == []
    # scalar reduce over the whole mesh (drop-count telemetry): excluded
    assert ir_audit.cross_key_collectives(
        facts_for([all_ids], 1), mesh) == []
    # unparseable groups: conservative — counted as crossing
    assert ir_audit.cross_key_collectives(
        facts_for(None, 16), mesh) == ["all_gather"]
    # the region-op fixture parses the operand from the closing line,
    # not the replica_groups attribute tensor
    scalar = ir_audit.extract_facts(GOLD_SCALAR_REDUCE)
    assert scalar["collective_ops"] == [
        {"op": "all_reduce", "groups": [[0, 1, 2, 3, 4, 5, 6, 7]],
         "numel": 1}]
    assert ir_audit.cross_key_collectives(scalar, mesh) == []
    # legacy facts without the detail fall back to every collective
    assert ir_audit.cross_key_collectives(
        {"collectives": ["all_to_all"]}, mesh) == ["all_to_all"]


def test_wf902_callback_fixture_and_clean_twin():
    for text in (GOLD_CALLBACK, GOLD_CALLBACK_ATTR):
        facts = ir_audit.extract_facts(text)
        assert len(facts["callbacks"]) == 1
        assert _codes(ir_audit.program_findings("p", facts)) == ["WF902"]
    clean = ir_audit.extract_facts(CLEAN_TWIN)
    assert clean["callbacks"] == []
    assert ir_audit.program_findings("p", clean) == []


def test_wf903_wide_dtype_fixture_and_clean_twin():
    facts = ir_audit.extract_facts(GOLD_WIDE, backend="tpu")
    assert facts["wide_dtypes"] == ["f64"]
    found = ir_audit.program_findings("p", facts)
    assert _codes(found) == ["WF903"]
    # a warning, never a preflight blocker: the framework's own int64
    # timestamp lanes put i64 into every ts-carrying TPU program
    assert found[0].severity == "warning"
    # same program on a CPU backend: 64-bit is legal there
    cpu = ir_audit.extract_facts(GOLD_WIDE, backend="cpu")
    assert ir_audit.program_findings("p", cpu) == []
    # i64 in ATTRIBUTE position (dense window shapes etc.) never counts —
    # the clean twin carries one on purpose
    clean = ir_audit.extract_facts(CLEAN_TWIN, backend="tpu")
    assert clean["wide_dtypes"] == []
    assert ir_audit.program_findings("p", clean) == []


def test_wf904_dynamic_fixture_and_clean_twin():
    facts = ir_audit.extract_facts(GOLD_DYNAMIC)
    assert "dynamic_reshape" in facts["dynamic"]
    assert "dynamic_dimension" in facts["dynamic"]
    assert _codes(ir_audit.program_findings("p", facts)) == ["WF904"]
    assert ir_audit.extract_facts(CLEAN_TWIN)["dynamic"] == []


def test_wf905_donation_fixture_and_aliased_twin():
    # donated operand, zero aliasing attributes in the module: miss
    facts = ir_audit.extract_facts(CLEAN_TWIN, donated_leaves=2)
    assert facts["aliased_outputs"] == 0
    assert _codes(ir_audit.program_findings("p", facts)) == ["WF905"]
    # the twin carries jax's tf.aliasing_output marker: donation landed
    ok = ir_audit.extract_facts(GOLD_ALIASED, donated_leaves=1)
    assert ok["aliased_outputs"] == 1
    assert ir_audit.program_findings("p", ok) == []
    # nothing donated -> nothing to miss
    assert ir_audit.program_findings(
        "p", ir_audit.extract_facts(CLEAN_TWIN)) == []


def test_wf906_transfer_fixture_and_clean_twin():
    facts = ir_audit.extract_facts(GOLD_TRANSFER)
    assert facts["transfers"] == ["send"]
    assert _codes(ir_audit.program_findings("p", facts)) == ["WF906"]
    assert ir_audit.extract_facts(CLEAN_TWIN)["transfers"] == []


def test_wf907_mosaic_fixture_and_clean_twin():
    # Pallas resolved ON, TPU backend, no Mosaic custom call: downgrade
    facts = ir_audit.extract_facts(CLEAN_TWIN, backend="tpu")
    assert facts["mosaic_calls"] == 0
    assert _codes(ir_audit.program_findings(
        "p", facts, expect_mosaic=True)) == ["WF907"]
    # twin: the tpu_custom_call is present (and is NOT a WF902 callback)
    ok = ir_audit.extract_facts(GOLD_MOSAIC, backend="tpu")
    assert ok["mosaic_calls"] == 1 and ok["callbacks"] == []
    assert ir_audit.program_findings("p", ok, expect_mosaic=True) == []
    # on CPU the interpreter fallback is the contract, not a downgrade
    cpu = ir_audit.extract_facts(CLEAN_TWIN, backend="cpu")
    assert ir_audit.program_findings("p", cpu, expect_mosaic=True) == []


# ---------------------------------------------------------------------------
# real lowerings: the markers hold on the jax this repo runs
# ---------------------------------------------------------------------------

def test_real_lowering_donation_markers():
    """jax's aliasing attribute appears exactly when the donated operand
    can alias an output — extract_facts + record_lowered read the real
    thing, not just the golden fixtures."""
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")  # jax's own unused-donation warn
        ok = jax.jit(lambda s: s + 1.0, donate_argnums=(0,)) \
            .lower(jnp.zeros(64, jnp.float32))
        bad = jax.jit(lambda s: s.sum(), donate_argnums=(0,)) \
            .lower(jnp.zeros(64, jnp.float32))
    facts_ok = ir_audit.extract_facts(ok.as_text(), donated_leaves=1)
    assert facts_ok["aliased_outputs"] >= 1
    assert ir_audit.program_findings("p", facts_ok) == []
    facts_bad = ir_audit.extract_facts(bad.as_text(), donated_leaves=1)
    assert facts_bad["aliased_outputs"] == 0
    assert _codes(ir_audit.program_findings("p", facts_bad)) == ["WF905"]
    # record_lowered counts the donated leaves from args_info itself
    ir_audit.record_lowered("ira_real_don", ("sig",), bad)
    stored = ir_audit.store_snapshot()["ira_real_don"][0]
    assert stored["donated_leaves"] == 1
    assert stored["aliased_outputs"] == 0


def test_real_lowering_callback_marker():
    def cb(t):
        v = jax.pure_callback(lambda a: np.sin(a),
                              jax.ShapeDtypeStruct((), jnp.float32),
                              t["v"], vmap_method="sequential")
        return {"key": t["key"], "v": v}
    low = jax.jit(jax.vmap(cb)).lower(
        {"key": jax.ShapeDtypeStruct((64,), jnp.int32),
         "v": jax.ShapeDtypeStruct((64,), jnp.float32)})
    facts = ir_audit.extract_facts(low.as_text())
    assert facts["callbacks"], facts
    assert _codes(ir_audit.program_findings("p", facts)) == ["WF902"]


# ---------------------------------------------------------------------------
# graph-level wiring: audit_graph, stats, postmortem + wf_doctor
# ---------------------------------------------------------------------------

def test_run_graph_audits_clean(run_graph):
    report = ir_audit.audit_graph(run_graph, dry_lower=False)
    assert report.programs_audited >= 1
    assert report.findings == [] and report.pending == []
    assert "ira_ma" in report.op_names
    sec = run_graph.stats()["IR_audit"]
    assert sec["enabled"] is True
    assert sec["programs_audited"] >= 1 and sec["findings"] == []
    json.dumps(sec)


def _load_doctor():
    spec = importlib.util.spec_from_file_location(
        "wf_doctor", os.path.join(REPO, "tools", "wf_doctor.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_postmortem_ir_audit_section_roundtrips_wf_doctor(run_graph,
                                                          tmp_path):
    doctor = _load_doctor()
    d = run_graph.dump_postmortem(str(tmp_path / "bundle"),
                                  reason="wfir test")
    bundle = doctor.load_bundle(d)
    doctor.validate(bundle)
    sec = bundle["sections"]["ir_audit.json"]
    assert sec["enabled"] is True and sec["programs_audited"] >= 1
    diag = doctor.diagnose(bundle)
    assert diag["ir_audit"]["programs_audited"] >= 1
    assert "IR audit" in doctor.render_text(diag)
    # a corrupted section must fail --check, not render garbage
    path = os.path.join(d, "ir_audit.json")
    with open(path) as f:
        sec = json.load(f)
    sec["findings"] = [{"code": "OOPS"}]
    with open(path, "w") as f:
        json.dump(sec, f)
    with pytest.raises(doctor.BundleError):
        doctor.validate(doctor.load_bundle(d))


# ---------------------------------------------------------------------------
# WF901 acceptance twin: aligned vs unaligned mesh reduce
# ---------------------------------------------------------------------------

def _mesh_reduce_run(aligned, tag):
    from windflow_tpu.parallel import mesh as M
    mesh = M.make_mesh(8, data=1)
    kk = mesh.shape[M.KEY_AXIS]
    cap, K = 16 * 8, 4 * kk
    rng = np.random.default_rng(5)
    records = [{"key": int(k), "value": float(v)}
               for k, v in zip(rng.integers(0, K, 4 * cap),
                               rng.integers(0, 97, 4 * cap))]
    cfg = dataclasses.replace(default_config, mesh=mesh,
                              key_aligned_ingest=aligned)
    src = (wf.Source_Builder(lambda: iter(records))
           .withOutputBatchSize(cap).build())
    red = (wf.ReduceTPU_Builder(
            lambda a, b: {"key": jnp.maximum(a["key"], b["key"]),
                          "value": jnp.maximum(a["value"], b["value"])})
           .withKeyBy(lambda t: t["key"]).withMaxKeys(K)
           .withMonoidCombiner("max").withName(f"ira_red_{tag}").build())
    g = wf.PipeGraph(f"ira_mesh_{tag}", config=cfg)
    g.add_source(src).add(red).add_sink(
        wf.Sink_Builder(lambda r: None).build())
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        g.run()
    report = ir_audit.audit_graph(g, dry_lower=False)
    return red, report


def test_wf901_mesh_reduce_aligned_vs_unaligned_twin():
    """The acceptance contract: the aligned-ingest mesh program audits
    with ZERO WF901 (its only cross-key collective is the scalar
    drop-count psum every layout keeps) while the unaligned twin — whose
    [K]-table pmax combine rides the key axis — yields at least one."""
    red_a, rep_a = _mesh_reduce_run(True, "a")
    assert getattr(red_a, "_ingest_mode", None) == "aligned"
    assert [d for d in rep_a.findings if d.code == "WF901"] == []
    red_u, rep_u = _mesh_reduce_run(False, "u")
    assert getattr(red_u, "_ingest_mode", None) is None
    wf901 = [d for d in rep_u.findings if d.code == "WF901"]
    assert len(wf901) >= 1
    assert "aligned ingest" in wf901[0].message


# ---------------------------------------------------------------------------
# WF905 cross-validation: the static miss and the runtime counters agree
# ---------------------------------------------------------------------------

def test_wf905_static_and_runtime_donation_miss_cross_validate(run_graph):
    """Satellite contract: the IR-level donation audit and the sweep
    ledger's runtime counters are two views of one defect class — a
    donated-but-unaliasable program is flagged statically (WF905) while
    the ledger charges real bytes for undonated candidate buffers."""
    # runtime half: the map hop re-copies its undonated buffers
    sweep = run_graph.stats()["Sweep"]
    assert sweep["totals"]["donation_miss_bytes_per_batch"] > 0
    hop = next(h for name, h in sweep["per_hop"].items()
               if "ira_ma" in name)
    assert hop["donation_miss"]["bytes_per_batch"] > 0
    # static half: a donated operand no output can alias
    from windflow_tpu.monitoring.jit_registry import wf_jit
    step = wf_jit(lambda s, x: s.sum() + x.sum(),
                  op_name="ira_unaliasable", donate_argnums=(0,))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        step(jnp.ones(128, jnp.float32), jnp.ones(128, jnp.float32))
    facts = ir_audit.store_snapshot()["ira_unaliasable"][0]
    assert facts["donated_leaves"] == 1 and facts["aliased_outputs"] == 0
    assert "WF905" in _codes(
        ir_audit.program_findings("ira_unaliasable", facts))


# ---------------------------------------------------------------------------
# preflight integration: check() folds the dry-lower audit
# ---------------------------------------------------------------------------

def _cb_kernel(t):
    v = jax.pure_callback(lambda a: np.sin(a),
                          jax.ShapeDtypeStruct((), jnp.float32),
                          t["v"], vmap_method="sequential")
    return {"key": t["key"], "v": v}


# wfir shares wfverify's inline suppression; the token on the def line
# below is the seeded fixture test_preflight_suppression reads
def _cb_kernel_suppressed(t):  # wfverify: ok (seeded wfir suppression fixture)
    v = jax.pure_callback(lambda a: np.sin(a),
                          jax.ShapeDtypeStruct((), jnp.float32),
                          t["v"], vmap_method="sequential")
    return {"key": t["key"], "v": v}


def _unstarted_graph(app, fn, name):
    src = (wf.Source_Builder(lambda: iter(()))
           .withOutputBatchSize(64).withName(f"{name}_src")
           .withRecordSpec(_spec()).build())
    m = wf.MapTPU_Builder(fn).withName(name).build()
    g = wf.PipeGraph(app)
    g.add_source(src).add(m).add_sink(
        wf.Sink_Builder(lambda r: None).build())
    return g


def test_preflight_check_folds_dry_lower_audit():
    """check() on an UNSTARTED graph dry-lowers the user kernels over
    the preflight record specs: a host callback inside one surfaces as
    WF902 before anything ever compiles; the clean twin stays silent."""
    g = _unstarted_graph("ira_pf_cb", _cb_kernel, "ira_pf_cb_map")
    ds = g.check()
    assert "WF902" in {d.code for d in ds}
    assert g._ir_audit_report.dry_lowered >= 1
    g2 = _unstarted_graph(
        "ira_pf_clean",
        lambda t: {"key": t["key"], "v": t["v"] * 2.0}, "ira_pf_clean_m")
    ds2 = g2.check()
    assert {d.code for d in ds2} & {"WF901", "WF902", "WF903", "WF904",
                                    "WF905", "WF906", "WF907"} == set()
    assert g2._ir_audit_report.dry_lowered >= 1


def test_preflight_suppression_shares_wfverify_syntax():
    g = _unstarted_graph("ira_pf_sup", _cb_kernel_suppressed,
                         "ira_pf_sup_map")
    ds = g.check()
    assert "WF902" not in {d.code for d in ds}
    assert g._ir_audit_report.suppressed >= 1


# ---------------------------------------------------------------------------
# CLI round trip
# ---------------------------------------------------------------------------

CLEAN_APP = """\
import numpy as np
import windflow_tpu as wf

def make_graph():
    src = (wf.Source_Builder(lambda: iter(()))
           .withOutputBatchSize(256).withName("cli_src")
           .withRecordSpec({"key": np.int32(0), "v": np.float32(0.0)})
           .build())
    m = (wf.MapTPU_Builder(lambda t: {"key": t["key"], "v": t["v"] * 2.0})
         .withName("cli_map").build())
    g = wf.PipeGraph("cli_clean")
    g.add_source(src).add(m).add_sink(
        wf.Sink_Builder(lambda r: None).build())
    return g
"""

VIOLATING_APP = """\
import jax
import numpy as np
import windflow_tpu as wf

def _cb(t):
    v = jax.pure_callback(lambda a: np.sin(a),
                          jax.ShapeDtypeStruct((), np.float32),
                          t["v"], vmap_method="sequential")
    return {"key": t["key"], "v": v}

def make_graph():
    src = (wf.Source_Builder(lambda: iter(()))
           .withOutputBatchSize(256).withName("cli_bad_src")
           .withRecordSpec({"key": np.int32(0), "v": np.float32(0.0)})
           .build())
    m = wf.MapTPU_Builder(_cb).withName("cli_bad_map").build()
    g = wf.PipeGraph("cli_bad")
    g.add_source(src).add(m).add_sink(
        wf.Sink_Builder(lambda r: None).build())
    return g
"""


def test_cli_json_strict_roundtrip(tmp_path):
    """tools/wf_ir.py: --drive runs the graphs, --json emits per-app
    reports, --strict propagates the seeded WF902 as exit 1 while the
    clean app audits 0 errors; WF_TPU_IR_AUDIT=0 is a usage error."""
    (tmp_path / "cli_clean_app.py").write_text(CLEAN_APP)
    (tmp_path / "cli_bad_app.py").write_text(VIOLATING_APP)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(tmp_path))
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "wf_ir.py"),
         "cli_clean_app", "cli_bad_app", "--drive", "512", "--json",
         "--strict"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=300)
    assert r.returncode == 1, (r.stdout, r.stderr)
    out = json.loads(r.stdout)
    clean = out["cli_clean_app"]
    assert clean["graph"] == "cli_clean"
    assert clean["errors"] == 0 and clean["programs_audited"] >= 1
    bad = out["cli_bad_app"]
    assert bad["errors"] >= 1
    assert "WF902" in {f["code"] for f in bad["findings"]}
    # the driven run compiles the framework staging programs too: the
    # orphan sweep covers them
    assert out["(framework programs)"]["programs_audited"] >= 1
    # kill switch refuses to pretend it audited anything
    r2 = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "wf_ir.py"),
         "cli_clean_app"],
        capture_output=True, text=True, timeout=300, cwd=REPO,
        env=dict(env, WF_TPU_IR_AUDIT="0"))
    assert r2.returncode == 2
    assert "WF_TPU_IR_AUDIT=0" in r2.stderr


# ---------------------------------------------------------------------------
# zero extra compiles + kill switch + capture-failure warning
# ---------------------------------------------------------------------------

def test_audit_performs_zero_extra_compiles(run_graph):
    """The audit parses the compile watcher's existing first-compile
    lowering; auditing (including the dry-lower pass, which uses
    client-side ``jit().lower()`` only) must leave every registry
    compile counter untouched."""
    from windflow_tpu.monitoring.jit_registry import default_registry
    before = default_registry().totals()
    ir_audit.audit_graph(run_graph, dry_lower=False)
    ir_audit.process_report()
    ir_audit.audit_orphans(set())
    g = _unstarted_graph(
        "ira_zero_compiles",
        lambda t: {"key": t["key"], "v": t["v"] * 2.0}, "ira_zc_map")
    rep = ir_audit.audit_graph(g, dry_lower=True)
    assert rep.dry_lowered >= 1
    assert default_registry().totals() == before


def test_kill_switch_off_path_budget(monkeypatch):
    g = _map_graph("ira_kill_app", "ira_kill_ma", "ira_kill_src")
    g.config = dataclasses.replace(g.config, ir_audit=False)
    g.run()
    assert g.stats()["IR_audit"] == {"enabled": False}
    assert ir_audit.audit_graph(g).programs_audited == 0
    # off-path budget: the disabled section is ONE flag check
    t0 = time.perf_counter()
    for _ in range(10_000):
        g._ir_audit_section()
    per_call = (time.perf_counter() - t0) / 10_000
    assert per_call < 5e-6, \
        f"disabled ir_audit section costs {per_call * 1e6:.2f}us/call"
    # process switch: capture and every report become no-ops
    monkeypatch.setattr(ir_audit, "ENABLED", False)
    ir_audit.record_lowered("ira_kill_never", ("sig",), None)
    assert "ira_kill_never" not in ir_audit.store_snapshot()
    assert ir_audit.process_report().programs_audited == 0
    assert ir_audit.audit_orphans(set()).programs_audited == 0
    assert ir_audit.audit_graph(g).programs_audited == 0


def test_capture_failure_warns_once_and_reports_pending(monkeypatch):
    """Satellite contract: a lowering-capture failure inside the
    registry's cost path warns ONCE per op (naming the op and the
    consequence) instead of silently leaving a program that looks
    audited-clean — and the audit reports the op as pending."""
    def boom(op_name, sig, lowered):
        raise RuntimeError("seeded capture failure")
    monkeypatch.setattr(ir_audit, "record_lowered", boom)
    g = _map_graph("ira_capfail_app", "ira_capfail_ma", "ira_capfail_src")
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        g.run()
    mine = [str(x.message) for x in w
            if "lowering capture failed" in str(x.message)
            and "ira_capfail_ma" in str(x.message)]
    assert len(mine) == 1, mine
    assert "pending" in mine[0] and "RuntimeError" in mine[0]
    report = ir_audit.audit_graph(g, dry_lower=False)
    assert "ira_capfail_ma" in report.pending
