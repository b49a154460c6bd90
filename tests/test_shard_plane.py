"""Shard-plane + reshard-advisor contracts (docs/OBSERVABILITY.md
"Shard plane & reshard advisor"): a seeded Zipf-skew keyby graph whose
hot key/shard the ledger provably names, the sketch-vs-exact accuracy
bound, in-program sketches on device-keyby and fused-chain edges with
ZERO extra dispatches, mesh per-key-shard attribution + the ICI model,
the OpenMetrics/trace/postmortem surfaces, the reshard plan contract,
and the kill-switch off-path budget."""

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

N_BATCHES = 16
CAP = 256
N = N_BATCHES * CAP
HOT_KEY = 7
PAR = 4


def _cfg(tmp_path=None, **kw):
    if tmp_path is not None:
        kw.setdefault("log_dir", str(tmp_path))
    return dataclasses.replace(default_config, **kw)


def _zipf_keys(n=N, n_keys=64, hot=HOT_KEY, share=0.4, seed=5):
    rng = np.random.default_rng(seed)
    ks = rng.integers(0, n_keys, n)
    ks[rng.random(n) < share] = hot
    return ks


ZIPF_KEYS = _zipf_keys()


def _records(ks=ZIPF_KEYS):
    return iter({"key": int(k), "v": float(i)} for i, k in enumerate(ks))


def _zipf_graph(cfg, name="zipf_app", par=PAR):
    """src -> keyed ReduceTPU at parallelism ``par`` -> sink: the keyed
    staging emitter partitions by splitmix64(key) % par, so the seeded
    hot key pins one shard."""
    src = (wf.Source_Builder(_records).withOutputBatchSize(CAP)
           .withName("src").build())
    red = (wf.ReduceTPU_Builder(
        lambda a, b: {"key": b["key"], "v": a["v"] + b["v"]})
        .withKeyBy(lambda t: t["key"]).withParallelism(par)
        .withName("red").build())
    snk = wf.Sink_Builder(lambda t, ctx=None: None).withName("snk").build()
    g = wf.PipeGraph(name, wf.ExecutionMode.DEFAULT, config=cfg)
    g.add_source(src).add(red).add_sink(snk)
    return g


@pytest.fixture(scope="module")
def zipf_run(tmp_path_factory):
    """One shared seeded-skew run: the attribution, accuracy, surface,
    and advisor contracts all read the same ledger section."""
    g = _zipf_graph(_cfg(tmp_path_factory.mktemp("shard")))
    g.run()
    return g, g.stats()["Shard"]


# ---------------------------------------------------------------------------
# seeded-skew attribution: the acceptance contract
# ---------------------------------------------------------------------------

def _expected_shard_counts(ks=ZIPF_KEYS, par=PAR):
    from windflow_tpu.parallel.emitters import splitmix64_int
    out = np.zeros(par, np.int64)
    for k in ks:
        out[splitmix64_int(int(k)) % par] += 1
    return out


def test_zipf_hot_shard_and_key_attributed(zipf_run):
    _, sec = zipf_run
    assert sec["enabled"] is True
    load = sec["per_op"]["red"]["load"]
    expected = _expected_shard_counts()
    # per-shard load is EXACT on the keyed staging edge (the counts are
    # the routing's own placement over the full key column)
    assert load["tuples"] == [int(c) for c in expected]
    assert load["total_tuples"] == N
    assert load["hot_shard"] == int(expected.argmax())
    assert load["imbalance_ratio"] == pytest.approx(
        expected.max() / expected.mean(), abs=1e-3)
    assert load["imbalance_ratio"] > 1.5      # the skew is visible
    # the injected hot key is ranked first and placed on its real shard
    top = load["hot_keys"][0]
    assert top["key"] == HOT_KEY
    assert top["shard"] == load["hot_shard"]
    assert load["hot_key_share"] == pytest.approx(0.4, abs=0.05)
    # graph totals point at the same operator
    assert sec["totals"]["max_imbalance_op"] == "red"
    assert sec["totals"]["hot_key_op"] == "red"
    json.dumps(sec)     # ships in every NEW_REPORT payload


#: absolute slack: expected CMS collision mass is ~total/width per row
SKETCH_SLACK = 4 * N / 2048


def test_sketch_estimate_within_accuracy_bound(zipf_run):
    """Count-min estimates never undercount, and with 64 distinct keys
    against a 4x2048 sketch the collision mass keeps the hot key's
    estimate within a few percent of the exact count."""
    _, sec = zipf_run
    load = sec["per_op"]["red"]["load"]
    assert load["basis"] == "cms"     # unbounded key space: sketched
    true_hot = int((ZIPF_KEYS == HOT_KEY).sum())
    est = load["hot_keys"][0]["est_tuples"]
    assert est >= true_hot
    assert est <= true_hot * 1.05 + SKETCH_SLACK


def test_per_replica_runtime_attribution(zipf_run):
    """The gauges that existed only per-operator are now per shard:
    each replica row carries its own queue/lag/dispatch/latency (and
    HBM bytes where the cost table attributed)."""
    g, sec = zipf_run
    entry = sec["per_op"]["red"]
    assert entry["parallelism"] == PAR and entry["keyed"] is True
    reps = entry["replicas"]
    assert [r["shard"] for r in reps] == list(range(PAR))
    # every shard processed its own partition: inputs track the load
    load = sec["per_op"]["red"]["load"]
    for r, expect in zip(reps, load["tuples"]):
        assert r["inputs"] == expect
        assert r["queue_depth"] == 0          # drained at EOS
        assert r["dispatches"] >= 1
    # non-keyed ops carry replica attribution too (no load table)
    assert "load" not in sec["per_op"]["snk"]
    assert len(sec["per_op"]["snk"]["replicas"]) == 1


# ---------------------------------------------------------------------------
# in-program sketches: zero extra dispatches
# ---------------------------------------------------------------------------

def _split_dispatches():
    from windflow_tpu.monitoring.jit_registry import default_registry
    e = default_registry().snapshot().get("emitter.device_keyby_split")
    return (e or {}).get("dispatches", 0)


def _dev_keyby_graph(cfg, name):
    import jax.numpy as jnp
    src = (wf.Source_Builder(_records).withOutputBatchSize(CAP)
           .withName("src").build())
    m = (wf.MapTPU_Builder(lambda t: {"key": t["key"], "v": t["v"] * 2.0})
         .withName("m").build())
    st = (wf.MapTPU_Builder(
        lambda t, s: ({"key": t["key"], "run": s + t["v"]}, s + t["v"]))
        .withInitialState(jnp.zeros((), jnp.float32))
        .withKeyBy(lambda t: t["key"]).withNumKeySlots(64).withDenseKeys()
        .withParallelism(2).withName("st").build())
    snk = wf.Sink_Builder(lambda t, ctx=None: None).withName("snk").build()
    g = wf.PipeGraph(name, wf.ExecutionMode.DEFAULT, config=cfg)
    g.add_source(src).add(m).add(st).add_sink(snk)
    return g


def test_device_keyby_in_program_sketch_zero_extra_dispatches(tmp_path):
    """The TPU->TPU keyby edge's sketch rides INSIDE the split program:
    the ledger-on run pays exactly as many split dispatches as the
    ledger-off run, and the merged sketch still names the hot key."""
    d0 = _split_dispatches()
    g_off = _dev_keyby_graph(_cfg(tmp_path, shard_ledger=False), "dk_off")
    g_off.run()
    off_disp = _split_dispatches() - d0
    assert off_disp == N_BATCHES
    d1 = _split_dispatches()
    g_on = _dev_keyby_graph(_cfg(tmp_path), "dk_on")
    g_on.run()
    on_disp = _split_dispatches() - d1
    assert on_disp == off_disp       # zero extra dispatches
    load = g_on.stats()["Shard"]["per_op"]["st"]["load"]
    assert load["total_tuples"] == N
    assert load["hot_keys"][0]["key"] == HOT_KEY
    # per-shard counts match the split program's own placement
    from windflow_tpu.parallel.emitters import splitmix64_int
    expected = np.zeros(2, np.int64)
    for k in ZIPF_KEYS:
        expected[splitmix64_int(int(k)) % 2] += 1
    assert load["tuples"] == [int(c) for c in expected]


def _chain_sketch_graph(cfg, name, par=1):
    """Map + Filter chained into a keyed consumer: at parallelism 1 the
    chain program extracts the consumer's keys and carries the sketch;
    above it the edge is a DeviceKeyByEmitter whose split program does."""
    import jax.numpy as jnp
    src = (wf.Source_Builder(_records).withOutputBatchSize(CAP)
           .withName("src").build())
    ma = (wf.MapTPU_Builder(lambda t: {"key": t["key"], "v": t["v"] * 2.0})
          .withName("ma").build())
    fb = (wf.FilterTPU_Builder(lambda t: t["v"] >= 0.0)
          .withName("fb").build())
    st = (wf.MapTPU_Builder(
        lambda t, s: ({"key": t["key"], "run": s + t["v"]}, s + t["v"]))
        .withInitialState(jnp.zeros((), jnp.float32))
        .withKeyBy(lambda t: t["key"]).withNumKeySlots(64).withDenseKeys()
        .withParallelism(par).withName("st").build())
    snk = wf.Sink_Builder(lambda t, ctx=None: None).withName("snk").build()
    g = wf.PipeGraph(name, wf.ExecutionMode.DEFAULT, config=cfg)
    pipe = g.add_source(src)
    pipe.add(ma)
    pipe.chain(fb)
    pipe.add(st).add_sink(snk)
    return g


def test_fused_chain_sketch_rides_the_chain_program(tmp_path):
    """A chained pair forwarding a downstream KEYBY consumer's keys
    extracts them in-program (PR 7); the sketch folds into that SAME
    program — dispatches per batch stay 1.0 and the hot key surfaces."""
    g = _chain_sketch_graph(_cfg(tmp_path, whole_chain_fusion=False),
                            "fused_sketch")
    g.run()
    sweep = g.stats()["Sweep"]
    assert sweep["per_hop"]["ma|fb"]["dispatches_per_batch"] == 1.0
    load = g.stats()["Shard"]["per_op"]["st"]["load"]
    assert load["total_tuples"] == N
    assert load["hot_keys"][0]["key"] == HOT_KEY


def test_chain_into_parallel_keyby_counts_once(tmp_path):
    """A chained pair feeding a keyed consumer at parallelism 2 routes
    through a DeviceKeyByEmitter whose split program sketches the
    stream; the chain program must NOT sketch it again (regression:
    total_tuples would read 2x)."""
    g = _chain_sketch_graph(_cfg(tmp_path, whole_chain_fusion=False),
                            "chain_par_keyby", par=2)
    g.run()
    load = g.stats()["Shard"]["per_op"]["st"]["load"]
    assert load["total_tuples"] == N          # counted exactly once
    assert sum(load["tuples"]) == N
    assert load["hot_keys"][0]["key"] == HOT_KEY


# ---------------------------------------------------------------------------
# mesh: per-key-shard load + the ICI model
# ---------------------------------------------------------------------------

def _mesh_graph(n_keys=16, aligned=True):
    from windflow_tpu.parallel import mesh as M
    mesh = M.make_mesh(8, data=2)
    cfg = dataclasses.replace(default_config, mesh=mesh,
                              key_aligned_ingest=aligned)
    ks = _zipf_keys(n=8 * 128, n_keys=n_keys, hot=3, share=0.5)
    src = (wf.Source_Builder(lambda: iter(
        {"key": int(k), "v": float(i)} for i, k in enumerate(ks)))
        .withOutputBatchSize(128).build())
    win = (wf.Ffat_WindowsTPU_Builder(lambda t: t["v"], lambda a, b: a + b)
           .withCBWindows(8, 4).withKeyBy(lambda t: t["key"])
           .withMaxKeys(n_keys).withName("mwin").build())
    g = wf.PipeGraph("mesh_shard", wf.ExecutionMode.DEFAULT, config=cfg)
    g.add_source(src).add(win).add_sink(
        wf.Sink_Builder(lambda r: None).build())
    return g, ks


def test_mesh_key_shard_attribution_and_ici_model():
    g, ks = _mesh_graph()
    g.run()
    entry = g.stats()["Shard"]["per_op"]["mwin"]
    load = entry["load"]
    # dense_range placement: chip i owns keys [i*K/kk, (i+1)*K/kk) —
    # per-key-shard load is EXACT (dense histogram over max_keys)
    assert load["placement"] == "dense_range"
    assert load["basis"] == "exact"
    hist = np.bincount(ks, minlength=16)
    expected = hist.reshape(4, 4).sum(axis=1)     # key axis = 4
    assert load["tuples"] == [int(c) for c in expected]
    assert load["hot_shard"] == 0                 # key 3 lives on shard 0
    assert load["hot_keys"][0]["key"] == 3
    assert load["hot_keys"][0]["shard"] == 0
    # ICI model: this host-fed window takes KEY-ALIGNED ingest by
    # default since the wire round — only the within-column data-axis
    # hop remains, and the model names it
    ici = entry["ici"]
    assert ici["collective"] == "all_gather(data|key-aligned)"
    assert ici["mesh"] == {"data": 2, "key": 4}
    assert ici["ici_bytes_per_tuple"] > 0
    assert g.stats()["Shard"]["totals"]["ici_bytes_per_tuple"] > 0
    # kill switch restores the data-sharded ingest + full all_gather,
    # with MORE modeled ICI bytes than the aligned path
    g2, _ = _mesh_graph(aligned=False)
    g2.run()
    ici2 = g2.stats()["Shard"]["per_op"]["mwin"]["ici"]
    assert ici2["collective"] == "all_gather(data)"
    assert ici2["ici_bytes_per_tuple"] > ici["ici_bytes_per_tuple"]


def test_mesh_arbitrary_keys_mod_placement():
    """A mesh keyed reduce WITHOUT withMaxKeys hash-shards lanes to
    their owner chip by uint32(key) % n — the sketch mirrors that
    placement (regression: the load table read all zeros)."""
    from windflow_tpu.parallel import mesh as M
    mesh = M.make_mesh(8, data=2)
    cfg = dataclasses.replace(default_config, mesh=mesh)
    ks = _zipf_keys(n=8 * 128, n_keys=1 << 20, hot=9, share=0.5, seed=3)
    src = (wf.Source_Builder(lambda: iter(
        {"key": int(k), "v": 1.0} for k in ks))
        .withOutputBatchSize(128).build())
    red = (wf.ReduceTPU_Builder(
        lambda a, b: {"key": b["key"], "v": a["v"] + b["v"]})
        .withKeyBy(lambda t: t["key"]).withName("arb").build())
    g = wf.PipeGraph("mesh_arb", wf.ExecutionMode.DEFAULT, config=cfg)
    g.add_source(src).add(red).add_sink(
        wf.Sink_Builder(lambda r: None).build())
    g.run()
    load = g.stats()["Shard"]["per_op"]["arb"]["load"]
    assert load["placement"] == "mod" and load["n_shards"] == 8
    expected = np.bincount((ks.astype(np.int64) & 0xFFFFFFFF) % 8,
                           minlength=8)
    assert load["tuples"] == [int(c) for c in expected]
    assert load["hot_shard"] == int(expected.argmax())
    assert load["hot_keys"][0]["key"] == 9
    assert load["hot_keys"][0]["shard"] == 9 % 8


@pytest.mark.slow
def test_mesh_soak_shard_consistency():
    """Nightly leg: a longer skewed mesh run — section stays internally
    consistent (loads sum to totals, every read idempotent) across
    repeated stats reads while the graph streams."""
    g, ks = _mesh_graph()
    g.start()
    reads = 0
    while not g.is_done():
        if not g.step():
            break
        sec = g.stats()["Shard"]
        load = sec["per_op"]["mwin"].get("load")
        if load and load["total_tuples"]:
            assert sum(load["tuples"]) <= len(ks)
            reads += 1
    g.wait_end()
    final = g.stats()["Shard"]["per_op"]["mwin"]["load"]
    assert sum(final["tuples"]) == len(ks)
    assert reads > 0


# ---------------------------------------------------------------------------
# reshard advisor: plan contract + CLI
# ---------------------------------------------------------------------------

def test_reshard_plan_names_hot_shard_first(zipf_run):
    from windflow_tpu.analysis.resharding import plan
    _, sec = zipf_run
    p = plan(sec, graph_name="zipf_app")
    assert p["ops"][0]["op"] == "red"
    assert p["ops"][0]["hot_shard"] == \
        sec["per_op"]["red"]["load"]["hot_shard"]
    assert p["actionable"] >= 1
    kinds = [a["kind"] for a in p["ops"][0]["actions"]]
    # 40% of the stream on one key exceeds the mean per-shard load:
    # routing cannot fix it, the plan must say so
    assert "split_hot_key" in kinds
    assert p["ops"][0]["actions"][-1]["key"] == HOT_KEY \
        or any(a.get("key") == HOT_KEY for a in p["ops"][0]["actions"])
    json.dumps(p)


def test_reshard_plan_emits_move_override():
    """Synthetic section with medium-hot keys stacked on one shard: the
    plan moves them (key->shard override, the executor contract) and
    the projected imbalance improves."""
    from windflow_tpu.analysis.resharding import plan
    section = {
        "enabled": True,
        "per_op": {"agg": {
            "parallelism": 4, "keyed": True, "replicas": [],
            "load": {
                "n_shards": 4, "placement": "splitmix",
                "total_tuples": 4000, "batches": 10,
                "tuples": [2200, 600, 600, 600],
                "imbalance_ratio": 2.2, "hot_shard": 0, "basis": "exact",
                "hot_keys": [
                    {"key": 11, "est_tuples": 800, "share": 0.2,
                     "shard": 0},
                    {"key": 12, "est_tuples": 700, "share": 0.175,
                     "shard": 0},
                ],
                "hot_key_share": 0.2,
            },
        }},
        "totals": {},
    }
    p = plan(section, graph_name="synth")
    acts = p["ops"][0]["actions"]
    assert acts and acts[0]["kind"] == "move_keys"
    moves = acts[0]["moves"]
    assert all(m["from_shard"] == 0 for m in moves)
    assert acts[0]["override"] == {str(m["key"]): m["to_shard"]
                                   for m in moves}
    assert acts[0]["projected_imbalance_ratio"] < 2.2


def test_wf_shard_cli_round_trip(zipf_run, tmp_path):
    """tools/wf_shard.py reads a stats dump jax-free and ranks the
    seeded hot shard first with a rebalance plan (exit 0)."""
    g, _ = zipf_run
    dump = tmp_path / "stats.json"
    dump.write_text(json.dumps(g.stats(), default=str))
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "wf_shard.py"),
         "--stats", str(dump), "--json"],
        capture_output=True, text=True, cwd=REPO, timeout=60)
    assert r.returncode == 0, r.stderr
    p = json.loads(r.stdout)
    assert p["ops"][0]["op"] == "red"
    assert p["ops"][0]["hot_keys"][0]["key"] == HOT_KEY
    assert p["actionable"] >= 1
    # text render names the hot shard and the plan
    r2 = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "wf_shard.py"),
         "--stats", str(dump)],
        capture_output=True, text=True, cwd=REPO, timeout=60)
    assert r2.returncode == 0
    assert "hot shard" in r2.stdout and "PLAN" in r2.stdout


# ---------------------------------------------------------------------------
# surfaces: OpenMetrics, trace metadata, postmortem + wf_doctor, health
# ---------------------------------------------------------------------------

def test_openmetrics_shard_families_and_replica_labels(zipf_run):
    from windflow_tpu.monitoring.openmetrics import (parse_exposition,
                                                     render_openmetrics)
    g, sec = zipf_run
    fams = parse_exposition(render_openmetrics(g.stats()))
    # wf_shard_* families carry the SAME numbers as the section
    load = sec["per_op"]["red"]["load"]
    tuples = {labels["shard"]: v for _, labels, v
              in fams["wf_shard_tuples_total"]["samples"]
              if labels["operator"] == "red"}
    assert tuples == {str(i): float(c)
                      for i, c in enumerate(load["tuples"])}
    imb = {labels["operator"]: v for _, labels, v
           in fams["wf_shard_imbalance_ratio"]["samples"]}
    assert imb["red"] == pytest.approx(load["imbalance_ratio"])
    assert fams["wf_shard_hot_key_share"]["samples"]
    q = {labels["shard"] for _, labels, v
         in fams["wf_shard_queue_depth"]["samples"]
         if labels["operator"] == "red"}
    assert q == {"0", "1", "2", "3"}
    # per-replica collapse fixed: the per-operator counter families
    # carry one sample per replica with a `replica` label
    per_rep = [(labels["replica"], v) for _, labels, v
               in fams["wf_operator_inputs_total"]["samples"]
               if labels["operator"] == "red"]
    assert sorted(r for r, _ in per_rep) == ["0", "1", "2", "3"]
    assert sorted(v for _, v in per_rep) == sorted(
        float(c) for c in load["tuples"])


def test_shard_families_absent_when_disabled(tmp_path):
    from windflow_tpu.monitoring.openmetrics import render_openmetrics
    g = _zipf_graph(_cfg(tmp_path, shard_ledger=False), name="off_app")
    g.run()
    assert "wf_shard_" not in render_openmetrics(g.stats())


def test_dump_trace_metadata_carries_shard(zipf_run, tmp_path):
    g, _ = zipf_run
    path = g.dump_trace(str(tmp_path / "t_trace.json"))
    with open(path) as f:
        trace = json.load(f)
    shard = trace["otherData"]["shard"]
    assert shard["enabled"] is True
    assert shard["per_op"]["red"]["load"]["hot_keys"][0]["key"] == HOT_KEY


def _load_doctor():
    spec = importlib.util.spec_from_file_location(
        "wf_doctor", os.path.join(REPO, "tools", "wf_doctor.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_postmortem_shard_roundtrips_wf_doctor(zipf_run, tmp_path):
    doctor = _load_doctor()
    g, sec = zipf_run
    d = g.dump_postmortem(str(tmp_path / "bundle"), reason="shard test")
    bundle = doctor.load_bundle(d)
    doctor.validate(bundle)
    shard = bundle["sections"]["shard.json"]
    assert shard["per_op"]["red"]["load"]["tuples"] == \
        sec["per_op"]["red"]["load"]["tuples"]
    diag = doctor.diagnose(bundle)
    si = diag["shard_imbalance"]
    assert si["op"] == "red" and si["hot_key"] == HOT_KEY
    text = doctor.render_text(diag)
    assert "worst imbalance 'red'" in text
    # a corrupted shard section must fail --check, not render garbage
    spath = os.path.join(d, "shard.json")
    with open(spath) as f:
        obj = json.load(f)
    obj["per_op"]["red"]["load"]["imbalance_ratio"] = "lots"
    with open(spath, "w") as f:
        json.dump(obj, f)
    with pytest.raises(doctor.BundleError):
        doctor.validate(doctor.load_bundle(d))
    # old bundles without the section still validate (optional section)
    os.remove(spath)
    mpath = os.path.join(d, "manifest.json")
    with open(mpath) as f:
        manifest = json.load(f)
    manifest["files"].remove("shard.json")
    with open(mpath, "w") as f:
        json.dump(manifest, f)
    doctor.validate(doctor.load_bundle(d))


def test_health_verdict_names_hot_shard(tmp_path):
    """BACKPRESSURED/STALLED attribution names the specific hot shard,
    and the stall diagnosis joins the ledger's hot-key table."""
    g = _zipf_graph(_cfg(tmp_path), name="health_shard")
    g.run()
    red = g._operators[1]
    assert red.name == "red"
    # wedge one replica: pending input on shard 2, replica alive
    red.replicas[2].inbox.append((0, object()))
    for rep in red.replicas:
        rep.done = False
    verdicts = g._health.sample()
    hs = verdicts["red"].get("hot_shard")
    assert hs and hs["shard"] == 2 and hs["queue_depth"] == 1
    diag = g._health.diagnose_stall()
    assert diag["root_cause"] == "red"
    assert diag["shard"]["hot_keys"][0]["key"] == HOT_KEY
    msg = g._health.format_diagnosis(diag)
    assert "hot shard 2" in msg
    assert f"key {HOT_KEY}" in msg
    # restore terminated state so the fixture graph stays clean
    red.replicas[2].inbox.clear()
    for rep in red.replicas:
        rep.done = True


# ---------------------------------------------------------------------------
# the in-program update: bit-exact against the host paths, no 64-bit
# scatter, engaged on every batch of the four-chip cell's graph
# ---------------------------------------------------------------------------

def _one_key_full(rng, cap):
    return np.full(cap, 40_961, np.int32), np.ones(cap, bool)


def _invalid_mixed(rng, cap):
    return (rng.integers(-2**31, 2**31, cap).astype(np.int32),
            rng.random(cap) < 0.7)


def _negative_keys(rng, cap):
    return (rng.integers(-1000, 0, cap).astype(np.int32),
            rng.random(cap) < 0.9)


def _filtered_uniform(rng, cap):
    k = rng.integers(0, 4096, cap).astype(np.int32)
    return k, (k & 7) != 7


def _none_valid(rng, cap):
    return rng.integers(0, 64, cap).astype(np.int32), np.zeros(cap, bool)


#: (id, capacity, batch maker): the full-capacity case puts a whole
#: default-capacity batch on ONE counter (the f32 accumulator's
#: exactness); 8 lanes is under CAND_PER_BATCH's stride
SKETCH_CASES = [
    ("invalid_mixed", 1024, _invalid_mixed),
    ("full_capacity_one_key", 262144, _one_key_full),
    ("negative_keys", 512, _negative_keys),
    ("short_capacity", 8, _invalid_mixed),
    ("filtered_uniform", 4096, _filtered_uniform),
    ("none_valid", 256, _none_valid),
]


def _host_reference(batches, n_shards):
    """What the host paths give on the same keys: the count-min rows
    and ``total`` of ``ShardSketch.update_host``, the shard counts of
    ``np.bincount`` over the splitmix placement."""
    from windflow_tpu.monitoring import shard_ledger as sl
    ref = sl.ShardSketch(n_shards)
    counts = np.zeros(n_shards, np.int64)
    for keys, valid in batches:
        k = keys[valid].astype(np.int64)
        ref.update_host(k)
        d = (sl._splitmix64_np(k) % np.uint64(n_shards)).astype(np.intp)
        counts += np.bincount(d, minlength=n_shards)
    return ref.cms, counts, ref.total


def _run_device_sketch(batches, n_shards, with_dest):
    import jax
    import jax.numpy as jnp
    from windflow_tpu.monitoring import shard_ledger as sl
    # as the sites do: one jitted program, the state donated
    step = jax.jit(
        lambda st, k, v, d: sl.device_sketch_update(
            st, k, v, n_shards, dest=d if with_dest else None),
        donate_argnums=(0,))
    st = sl.device_sketch_init(n_shards)
    for keys, valid in batches:
        h = sl._splitmix64_np(keys.astype(np.int64))
        dest = np.where(valid, (h % np.uint64(n_shards)).astype(np.int32),
                        n_shards).astype(np.int32)
        st = step(st, jnp.asarray(keys), jnp.asarray(valid),
                  jnp.asarray(dest))
    return st


@pytest.mark.parametrize("with_dest", [False, True],
                         ids=["dest_none", "dest_given"])
@pytest.mark.parametrize("n_shards", [1, 4])
@pytest.mark.parametrize("case", SKETCH_CASES,
                         ids=[c[0] for c in SKETCH_CASES])
def test_device_sketch_bit_identical_to_host(case, n_shards, with_dest):
    from windflow_tpu.monitoring import shard_ledger as sl
    _, cap, make = case
    rng = np.random.default_rng(cap + n_shards)
    batches = [make(rng, cap) for _ in range(3)]
    st = _run_device_sketch(batches, n_shards, with_dest)
    cms, counts, total = _host_reference(batches, n_shards)
    assert np.array_equal(np.asarray(st["cms"]), cms)
    assert np.array_equal(np.asarray(st["counts"]), counts)
    assert int(st["total"]) == total == sum(int(v.sum()) for _, v in batches)
    assert int(st["batches"]) == len(batches)
    init = sl.device_sketch_init(n_shards)
    assert {k: (v.shape, v.dtype) for k, v in st.items()} \
        == {k: (v.shape, v.dtype) for k, v in init.items()}
    # the candidate ring holds strided valid lanes of the last batches
    keys, valid = batches[-1]
    c = min(sl.CAND_PER_BATCH, cap)
    lanes = slice(None, None, max(1, cap // c))
    want = keys[lanes][:c][valid[lanes][:c]]
    assert set(want.tolist()) <= set(np.asarray(st["cand"]).tolist())


@pytest.mark.parametrize("n_bins", [1, 5, 64, 100, 2048])
def test_device_hist32_is_bincount(n_bins):
    import jax
    import jax.numpy as jnp
    from windflow_tpu.monitoring import shard_ledger as sl
    rng = np.random.default_rng(n_bins)
    # indices past the last bin (an invalid lane's ``dest``) are dropped
    idx = rng.integers(0, n_bins + 3, 5000).astype(np.int32)
    valid = rng.random(5000) < 0.8
    got = jax.jit(lambda i, v: sl.device_hist32(i, v, n_bins))(
        jnp.asarray(idx), jnp.asarray(valid))
    assert got.dtype == np.int32 and got.shape == (n_bins,)
    want = np.bincount(idx[valid & (idx < n_bins)], minlength=n_bins)
    assert np.array_equal(np.asarray(got), want)


def test_hist32_past_the_f32_exact_range_scatters_in_32_bits(monkeypatch):
    """A lane count an f32 accumulator could not hold takes the int32
    scatter-add, decided from the traced shape: same counts."""
    import jax
    import jax.numpy as jnp
    from windflow_tpu.monitoring import shard_ledger as sl
    monkeypatch.setattr(sl, "HIST_EXACT_LANES", 16)
    rng = np.random.default_rng(3)
    batches = [_invalid_mixed(rng, 64) for _ in range(2)]
    st = _run_device_sketch(batches, 4, False)
    cms, counts, total = _host_reference(batches, 4)
    assert np.array_equal(np.asarray(st["cms"]), cms)
    assert np.array_equal(np.asarray(st["counts"]), counts)
    assert int(st["total"]) == total
    jaxpr = jax.make_jaxpr(lambda i, v: sl.device_hist32(i, v, 2048))(
        jnp.zeros(64, jnp.int32), jnp.zeros(64, bool))
    assert _scatters(jaxpr.jaxpr) and not _wide_scatters(jaxpr.jaxpr)


def _scatters(jaxpr):
    """Every scatter equation of a jaxpr, nested jaxprs included."""
    out = []
    for eqn in jaxpr.eqns:
        if "scatter" in eqn.primitive.name:
            out.append(eqn)
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)     # a ClosedJaxpr
                if hasattr(sub, "eqns"):
                    out.extend(_scatters(sub))
    return out


def _wide_scatters(jaxpr):
    """Scatters whose operand or update lane is 64 bits wide (a pair of
    u32 scatters on a v5e: 18.5 ms each over 262144 lanes, PERF.md)."""
    return [e for e in _scatters(jaxpr)
            if any(np.dtype(e.invars[i].aval.dtype).itemsize >= 8
                   for i in (0, 2))]


def _state_spec(n_shards):
    import jax
    from windflow_tpu.monitoring import shard_ledger as sl
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                        sl.device_sketch_init(n_shards))


def _assert_sketched_program_is_narrow(fn, args, n_shards):
    """``fn(*args)`` ends in the sketch state: no 64-bit scatter in the
    program, and the state leaves with the layout it came in with."""
    import jax
    closed, out = jax.make_jaxpr(fn, return_shape=True)(*args)
    assert not _wide_scatters(closed.jaxpr), _wide_scatters(closed.jaxpr)
    assert "dot_general" in str(closed)      # the sketch is in there
    spec = _state_spec(n_shards)
    assert {k: (v.shape, v.dtype) for k, v in out[-1].items()} \
        == {k: (v.shape, v.dtype) for k, v in spec.items()}


def test_wide_scatter_detector_sees_a_64_bit_scatter():
    """The structural tests below cannot pass vacuously: the parent's
    form of one count-min row is found."""
    import jax
    import jax.numpy as jnp
    old = jax.make_jaxpr(jax.jit(
        lambda c, i, v: c.at[0, i].add(v.astype(jnp.int64))))(
            jnp.zeros((4, 2048), jnp.int64), jnp.zeros(256, jnp.int32),
            jnp.zeros(256, bool))
    assert len(_wide_scatters(old.jaxpr)) == 1


def test_sketched_chain_program_has_no_64_bit_scatter(tmp_path):
    import jax
    g = _chain_sketch_graph(_cfg(tmp_path, whole_chain_fusion=False),
                            "chain_struct")
    g.run()
    execs = [e for op in g._operators
             for e in (op._fusion_exec, getattr(op, "_chain", None))
             if e is not None and e._sketch is not None]
    assert len(execs) == 1 and execs[0]._sk_state is not None
    S = jax.ShapeDtypeStruct
    args = ({"key": S((CAP,), np.int32), "v": S((CAP,), np.float32)},
            S((CAP,), np.bool_), _state_spec(execs[0]._sk_n))
    _assert_sketched_program_is_narrow(execs[0]._jit._fn, args,
                                       execs[0]._sk_n)


def test_sketched_keyby_split_program_has_no_64_bit_scatter(tmp_path):
    import jax
    from windflow_tpu.parallel.emitters import DeviceKeyByEmitter
    g = _dev_keyby_graph(_cfg(tmp_path), "dk_struct")
    g.run()
    ems = [rep.emitter for op in g._operators for rep in op.replicas
           if isinstance(rep.emitter, DeviceKeyByEmitter)]
    assert ems and all(em._sk_state is not None for em in ems)
    em = ems[0]
    S = jax.ShapeDtypeStruct
    n = len(em.dests)
    args = ({"key": S((CAP,), np.int32), "v": S((CAP,), np.float32)},
            S((CAP,), np.int64), S((CAP,), np.bool_), S((CAP,), np.int32),
            _state_spec(n))
    _assert_sketched_program_is_narrow(em._get_split(CAP)._fn, args, n)


BENCH_SIZES = dict(batch=1024, n_keys=32, win=64, slide=16, ring_batches=4,
                   campaigns=10, ads_per_campaign=4, window_usec=100_000)


def _bench_graph(config_name):
    """A graph of ``benchmark/configs/`` at a tiny size, fed its own
    ring once: (graph, ring)."""
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    from benchmark import harness
    mod = harness.load_module("configs", config_name)
    with open(os.path.join(REPO, "benchmark", "configs",
                           config_name + ".json")) as f:
        cfg = harness.with_sizes(json.load(f), BENCH_SIZES)
    ring = mod.make_ring(2**31 + 27, cfg)
    rec = ring["rec"].copy()
    rec["t"] = np.arange(len(rec)) * 10      # event time, monotone
    buf = rec.tobytes()

    def chunks():
        for i in range(0, len(buf), 4096):
            yield buf[i:i + 4096]

    return mod.build_graph(cfg, ring, chunks, lambda c: None), ring


def _sketch_sites(g):
    return [getter for sk in g._shard._sketches.values()
            for getter in sk._device_states]


def test_mesh_cell_graph_sketches_every_batch_and_every_tuple():
    """The four-chip cell's graph (``ffat_sum_mesh4`` on the CPU
    device-count mesh): the chain program carries the sketch, it ran on
    every dispatch and counted every tuple that passed the filter —
    nothing sampled, no batch skipped."""
    g, ring = _bench_graph("ffat_sum_mesh4")
    g.run()
    assert g.config.mesh is not None and len(_sketch_sites(g)) == 1
    chain = [op for op in g._operators
             if getattr(op, "_chain", None) is not None
             and op._chain._sketch is not None]
    assert len(chain) == 1
    dispatches = chain[0]._chain._jit.dispatches
    load = g.stats()["Shard"]["per_op"]["ffat"]["load"]
    k = ring["rec"]["k"]
    assert dispatches >= len(k) // BENCH_SIZES["batch"]
    assert load["batches"] == dispatches
    assert load["total_tuples"] == int(((k & 7) != 7).sum())


@pytest.mark.parametrize("config_name", ["ffat_sum", "ysb"])
def test_one_chip_bench_graphs_register_no_in_program_sketch(config_name):
    """What "the one-chip cells bypass the mechanism" rests on: whole-
    chain fusion folds their chains into the window step, and no
    program of theirs carries ``device_sketch_update``."""
    g, _ = _bench_graph(config_name)
    g.run()
    assert g.config.mesh is None and g._shard is not None
    assert _sketch_sites(g) == []


# ---------------------------------------------------------------------------
# kill switch + overhead budget
# ---------------------------------------------------------------------------

def test_kill_switch_off_path_budget(tmp_path):
    g = _zipf_graph(_cfg(tmp_path, shard_ledger=False), name="ks_app")
    g.run()
    assert g._shard is None
    assert g.stats()["Shard"] == {"enabled": False}
    # no sketch attached anywhere: the keyed staging emitter keeps its
    # one `is not None` check per tuple and nothing else
    src = g._operators[0]
    for rep in src.replicas:
        em = rep.emitter
        assert em._sketch is None and em._sk_buf == []
    # off-path budget (mirrors the sweep ledger's): the disabled read
    # site is ONE `is not None` check — micro-assert it stays orders of
    # magnitude under a real section build
    t0 = time.perf_counter()
    for _ in range(10_000):
        g._plane_section(g._shard)
    per_call = (time.perf_counter() - t0) / 10_000
    assert per_call < 5e-6, \
        f"disabled shard section costs {per_call * 1e6:.2f}us/call"


@pytest.mark.slow  # ~15s: this on/off A/B rides the nightly leg
def test_sketch_overhead_within_budget(tmp_path_factory):
    """Overhead smoke (documented budget <2%): ledger on vs off over
    the same seeded keyed pipeline.  CPU CI timing is noisy, so the
    assertion leaves generous slack — it exists to catch a sketch that
    lands on the per-TUPLE path (orders of magnitude, not percent)."""
    ks = _zipf_keys(n=16 * 1024, seed=9)

    def run_once(enabled, i):
        cfg = _cfg(tmp_path_factory.mktemp("ovh"), shard_ledger=enabled)
        src = (wf.Source_Builder(
            lambda: iter({"key": int(k), "v": 1.0} for k in ks))
            .withOutputBatchSize(1024).withName("src").build())
        red = (wf.ReduceTPU_Builder(
            lambda a, b: {"key": b["key"], "v": a["v"] + b["v"]})
            .withKeyBy(lambda t: t["key"]).withParallelism(2)
            .withName("red").build())
        g = wf.PipeGraph(f"ovh_{enabled}_{i}", wf.ExecutionMode.DEFAULT,
                         config=cfg)
        g.add_source(src).add(red).add_sink(
            wf.Sink_Builder(lambda t, ctx=None: None).build())
        t0 = time.perf_counter()
        g.run()
        return time.perf_counter() - t0

    run_once(True, 0)                   # warm compile caches
    on = min(run_once(True, i) for i in range(1, 4))
    off = min(run_once(False, i) for i in range(1, 4))
    assert on < off * 1.5 + 0.25, \
        f"ledger-on run {on:.3f}s vs off {off:.3f}s exceeds budget slack"
