"""Whole-chain fusion executor contracts (windflow_tpu/fusion):
record-for-record equivalence of fused vs.
unfused execution across the graph families (window tails CB/TB, keyed
reduce, dense-key stateful, all-stateless, split/merge boundaries),
the exact one-jitted-dispatch-per-batch accounting through the sweep
ledger, zero donation misses on the bench-shaped graph, keys-lane
forwarding through chains into KEYBY consumers, and the
``WF_TPU_FUSE`` kill-switch off-path."""

import dataclasses
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import windflow_tpu as wf
from windflow_tpu.basic import default_config
from windflow_tpu.monitoring.jit_registry import default_registry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CAP = 64
N = CAP * 6
N_KEYS = 8


def _cfg(fuse: bool, **kw):
    return dataclasses.replace(default_config, whole_chain_fusion=fuse,
                               **kw)


def _records_sink(got):
    def sink(r, ctx=None):
        if r is None:
            return
        got.append(tuple(sorted(r.items())) if isinstance(r, dict)
                   else float(r))
    return wf.Sink_Builder(sink).withName("snk").build()


def _source(event_time=False, n=N, cap=CAP):
    if event_time:
        return (wf.Source_Builder(
            lambda: iter({"key": np.int32(i % N_KEYS),
                          "v": np.float32(i),
                          "ts": np.int64(i * 1000)} for i in range(n)))
            .withName("src").withTimestampExtractor(lambda t: t["ts"])
            .withOutputBatchSize(cap).build())
    return (wf.Source_Builder(
        lambda: iter({"key": np.int32(i % N_KEYS), "v": np.float32(i)}
                     for i in range(n)))
        .withName("src").withOutputBatchSize(cap)
        .withRecordSpec({"key": np.int32(0), "v": np.float32(0.0)})
        .build())


def _map_filter():
    ma = (wf.MapTPU_Builder(lambda t: {"key": t["key"], "v": t["v"] * 2.0})
          .withName("ma").build())
    fb = (wf.FilterTPU_Builder(lambda t: (t["key"] & 1) == 0)
          .withName("fb").build())
    return ma, fb


def _tail(kind):
    if kind == "cb_window":
        return (wf.Ffat_WindowsTPU_Builder(lambda t: t["v"],
                                           lambda a, b: a + b)
                .withCBWindows(8, 4).withKeyBy(lambda t: t["key"])
                .withMaxKeys(N_KEYS).withName("win").build())
    if kind == "tb_window":
        return (wf.Ffat_WindowsTPU_Builder(lambda t: t["v"],
                                           lambda a, b: a + b)
                .withTBWindows(16_000, 8_000)
                .withKeyBy(lambda t: t["key"])
                .withMaxKeys(N_KEYS).withName("win").build())
    if kind == "reduce":
        return (wf.ReduceTPU_Builder(
            lambda a, b: {"key": a["key"], "v": a["v"] + b["v"]})
            .withKeyBy(lambda t: t["key"]).withName("red").build())
    if kind == "stateful_dense":
        return (wf.MapTPU_Builder(
            lambda t, s: ({"key": t["key"], "v": t["v"] + s}, s + 1.0))
            .withInitialState(np.float32(0.0))
            .withKeyBy(lambda t: t["key"]).withNumKeySlots(N_KEYS * 2)
            .withDenseKeys().withName("sm").build())
    if kind == "stateful_intern":
        # host-interning tail: the executor must fuse ONLY the stateless
        # prefix (the intern's distinct-key D2H cannot run mid-program)
        return (wf.MapTPU_Builder(
            lambda t, s: ({"key": t["key"], "v": t["v"] + s}, s + 1.0))
            .withInitialState(np.float32(0.0))
            .withKeyBy(lambda t: t["key"]).withNumKeySlots(N_KEYS * 2)
            .withName("sm").build())
    assert kind == "stateless"
    return None


def _run_family(kind, fuse):
    got = []
    event = kind == "tb_window"
    tl = _tail(kind)
    ma, fb = _map_filter()
    g = wf.PipeGraph(f"fuse_{kind}", wf.ExecutionMode.DEFAULT,
                     wf.TimePolicy.EVENT if event
                     else wf.TimePolicy.INGRESS,
                     config=_cfg(fuse))
    p = g.add_source(_source(event_time=event))
    p.add(ma)
    p.add(fb)
    if tl is not None:
        p.add(tl)
    p.add_sink(_records_sink(got))
    g.run()
    return sorted(got), g


# ---------------------------------------------------------------------------
# record-for-record fused vs unfused A/B (the acceptance contract)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["cb_window", "tb_window", "reduce",
                                  "stateful_dense", "stateful_intern",
                                  "stateless"])
def test_fused_equals_unfused(kind):
    unfused, _ = _run_family(kind, fuse=False)
    fused, g = _run_family(kind, fuse=True)
    assert fused == unfused
    assert len(fused) > 0
    segs = [s["name"] for s in g._fused_segments]
    if kind == "stateless":
        assert segs == ["ma|fb"]
    elif kind == "stateful_intern":
        assert segs == ["ma|fb"]        # prefix only: intern tail excluded
    else:
        assert len(segs) == 1 and segs[0].startswith("ma|fb|")


def test_fused_equals_unfused_split_graph():
    """Fusion must stop at split boundaries yet still fuse the runs
    INSIDE each branch; both configurations agree record for record."""
    def run(fuse):
        got = [[], []]

        def mk(i):
            def sink(r, ctx=None):
                if r is None:
                    return
                got[i].append(tuple(sorted(r.items())))
            return wf.Sink_Builder(sink).build()

        g = wf.PipeGraph("fuse_split", wf.ExecutionMode.DEFAULT,
                         wf.TimePolicy.INGRESS, config=_cfg(fuse))
        p = g.add_source(_source())
        p.add(wf.MapTPU_Builder(
            lambda t: {"key": t["key"], "v": t["v"] + 1.0})
            .withName("pre").build())
        p.split(lambda t: t["key"] % 2, 2)
        for b in range(2):
            br = p.select(b)
            br.add(wf.MapTPU_Builder(
                lambda t: {"key": t["key"], "v": t["v"] * 3.0})
                .withName(f"m{b}").build())
            br.add(wf.FilterTPU_Builder(lambda t: (t["key"] & 3) != 3)
                   .withName(f"f{b}").build())
            br.add_sink(mk(b))
        g.run()
        return [sorted(x) for x in got], g

    a, _ = run(False)
    b, g = run(True)
    assert a == b
    # one fused segment per branch; the pre-split op stays unfused
    assert sorted(s["name"] for s in g._fused_segments) \
        == ["m0|f0", "m1|f1"]


def test_fused_equals_unfused_merged_sources():
    """A merge feeding the chain head: the merge edge redirects into the
    fused host like any op edge; results agree with the unfused run."""
    def run(fuse):
        got = []
        g = wf.PipeGraph("fuse_merge", wf.ExecutionMode.DEFAULT,
                         wf.TimePolicy.INGRESS, config=_cfg(fuse))
        p1 = g.add_source(_source(n=N // 2))
        src2 = (wf.Source_Builder(
            lambda: iter({"key": np.int32(i % N_KEYS),
                          "v": np.float32(1000 + i)}
                         for i in range(N // 2)))
            .withName("src2").withOutputBatchSize(CAP).build())
        p2 = g.add_source(src2)
        merged = p1.merge(p2)
        ma, fb = _map_filter()
        merged.add(ma)
        merged.add(fb)
        merged.add(_tail("cb_window"))
        merged.add_sink(_records_sink(got))
        g.run()
        return sorted(got), g

    a, _ = run(False)
    b, g = run(True)
    assert a == b and len(a) > 0
    assert [s["name"] for s in g._fused_segments] == ["ma|fb|win"]


# ---------------------------------------------------------------------------
# dispatch accounting: a fused N-op chain = ONE jitted dispatch per batch
# ---------------------------------------------------------------------------

def test_fused_chain_exactly_one_dispatch_per_batch():
    """The acceptance contract: the fused 3-op chain's program pays
    exactly one jitted dispatch per data batch (registry counter — the
    CB EOS flush is a separate one-shot program), the member hops pay
    zero, and the ledger's sweep total collapses to 1/batch."""
    default_registry().reset()
    _, g = _run_family("cb_window", fuse=True)
    n_batches = N // CAP
    entry = default_registry().snapshot()["ma|fb|win"]
    assert entry["dispatches"] == n_batches
    sweep = g.stats()["Sweep"]
    for m in ("ma", "fb"):
        hop = sweep["per_hop"][m]
        assert hop["dispatches"] == 0
        assert hop["fused_into"] == "ma|fb|win"
    host = sweep["per_hop"]["win"]
    assert host["fused_program"] == "ma|fb|win"
    assert host["fused_members"] == ["ma", "fb", "win"]
    assert host["dispatches_per_batch"] == 1.0
    assert sweep["totals"]["dispatches_per_batch"] == 1.0
    fus = sweep["fusion"]
    assert fus["enabled"] is True
    assert fus["fused_chains"] == ["ma|fb|win"]
    assert fus["dispatches_saved_per_batch"] == 2.0
    assert fus["bytes_saved_per_batch"] > 0
    json.dumps(sweep)


def test_fused_stateless_chain_dispatch_attribution():
    """An all-stateless fused segment's program lives on the host op's
    FusedStatelessExec — the ledger must still attribute its dispatches
    to the host hop (the _op_wrappers fused-exec arm)."""
    default_registry().reset()
    _, g = _run_family("stateless", fuse=True)
    sweep = g.stats()["Sweep"]
    assert sweep["per_hop"]["ma"]["dispatches"] == 0
    host = sweep["per_hop"]["fb"]
    assert host["dispatches"] == N // CAP
    assert host["dispatches_per_batch"] == 1.0
    assert sweep["totals"]["dispatches_per_batch"] == 1.0


def test_kill_switch_restores_per_hop_dispatches():
    """WF_TPU_FUSE=0 / Config.whole_chain_fusion=False: every hop pays
    its own dispatch again and no segments are installed."""
    _, g = _run_family("cb_window", fuse=False)
    assert g._fused_segments == []
    sweep = g.stats()["Sweep"]
    for m in ("ma", "fb", "win"):
        assert sweep["per_hop"][m]["dispatches_per_batch"] == 1.0
        assert "fused_into" not in sweep["per_hop"][m]
    assert sweep["totals"]["dispatches_per_batch"] == 3.0
    assert sweep["fusion"]["enabled"] is False


# ---------------------------------------------------------------------------
# donation: zero misses on the bench-shaped graph (fused AND unfused)
# ---------------------------------------------------------------------------

def _bench_shaped_graph(fuse):
    src = _source()
    m = (wf.MapTPU_Builder(
        lambda t: {"key": t["key"], "v": t["v"] * 1.5 + 1.0})
        .withName("map_tpu").build())
    f = (wf.FilterTPU_Builder(lambda t: (t["key"] & 7) != 7)
         .withName("filter_tpu").build())
    w = (wf.Ffat_WindowsTPU_Builder(lambda t: t["v"], lambda a, b: a + b)
         .withCBWindows(16, 8).withKeyBy(lambda t: t["key"])
         .withMaxKeys(N_KEYS).withName("win").build())
    snk = wf.Sink_Builder(lambda r: None).withName("snk").build()
    g = wf.PipeGraph("bench_shape", wf.ExecutionMode.DEFAULT,
                     wf.TimePolicy.INGRESS, config=_cfg(fuse))
    pipe = g.add_source(src)
    pipe.add(m)
    pipe.chain(f)       # the bench graph's chained pair
    pipe.add(w).add_sink(snk)
    return g


@pytest.mark.parametrize("fuse", [False, True])
def test_bench_graph_zero_donation_misses(fuse):
    """The donation satellite's acceptance: with the chained-pair step
    donating its (provably unshared) staged inputs and the FFAT state
    already donated, the bench-shaped graph shows ZERO donation-miss
    bytes — fused and unfused alike."""
    g = _bench_shaped_graph(fuse)
    g.run()
    sweep = g.stats()["Sweep"]
    assert sweep["totals"]["donation_miss_bytes_per_batch"] == 0.0
    for name, hop in sweep["per_hop"].items():
        assert "donation_miss" not in hop, (name, hop)


def test_staging_pool_survives_donated_gates():
    """Input donation deletes the staged valid/payload lanes; the pool's
    recycling gate must survive that — it rides the unpack program's
    PRIVATE scalar output no consumer can donate (batch.stage_packed),
    so acquire never syncs on a deleted array."""
    g = _bench_shaped_graph(False)
    g.run()     # chained pair donates staged payload+valid every batch
    from windflow_tpu import staging
    st = staging.default_pool().stats()
    assert st["releases"] > 0       # buffers really were recycled


# ---------------------------------------------------------------------------
# keys lane through chains (the ChainedTPU satellite)
# ---------------------------------------------------------------------------

def _keyed_consumer_graph(chained, par=1, fuse=False):
    got = []
    src = _source()
    ma, fb = _map_filter()
    sm = (wf.MapTPU_Builder(
        lambda t, s: ({"key": t["key"], "v": t["v"] + s}, s + 1.0))
        .withInitialState(np.float32(0.0))
        .withKeyBy(lambda t: t["key"]).withNumKeySlots(N_KEYS * 2)
        .withParallelism(par).withName("sm").build())
    g = wf.PipeGraph("keys_lane", wf.ExecutionMode.DEFAULT,
                     wf.TimePolicy.INGRESS, config=_cfg(fuse))
    p = g.add_source(src)
    p.add(ma)
    (p.chain if chained else p.add)(fb)
    p.add(sm).add_sink(_records_sink(got))
    g.run()
    return sorted(got)


def test_keyby_after_fused_chain_preserves_keys_lane():
    """Regression for the dropped keys lane: a ChainedTPU feeding a
    KEYBY consumer now extracts the consumer's keys inside its own
    program (on the chain's OUTPUT records) and ships them on the keys
    lane — the consumer's standalone ``.key_extract`` program never
    compiles, and results match the unchained graph exactly."""
    default_registry().reset()
    chained = _keyed_consumer_graph(chained=True)
    snap = set(default_registry().snapshot())
    assert "sm.key_extract" not in snap
    default_registry().reset()
    unchained = _keyed_consumer_graph(chained=False)
    assert "sm.key_extract" in set(default_registry().snapshot())
    assert chained == unchained and len(chained) > 0


@pytest.mark.slow
def test_keyby_after_fused_chain_multi_replica_routing():
    """At parallelism 2 the keyby emitter consumes the chain-forwarded
    keys lane for placement: every key still lands on one replica and
    the results match the single-replica run.  Slow: two extra full
    graph runs buying a routing-consistency check the par-1 regression
    above already anchors."""
    base = _keyed_consumer_graph(chained=True, par=1)
    multi = _keyed_consumer_graph(chained=True, par=2)
    assert multi == base


# ---------------------------------------------------------------------------
# stats / observability contracts for fused members
# ---------------------------------------------------------------------------

def test_member_stats_attributed_from_fused_hop():
    _, g = _run_family("cb_window", fuse=True)
    stats = g.stats()
    ops = {o["Operator_name"]: o for o in stats["Operators"]}
    assert ops["ma"]["Fused_into"] == "ma|fb|win"
    assert ops["fb"]["Fused_into"] == "ma|fb|win"
    assert "Fused_into" not in ops["win"]
    host_inputs = sum(r["Inputs_received"]
                      for r in ops["win"]["Replicas"])
    assert host_inputs == N
    assert sum(r["Inputs_received"] for r in ops["ma"]["Replicas"]) == N
    # the report stays JSON-clean with fused segments installed
    json.dumps(stats, default=str)


def test_health_reads_fused_members_as_terminated():
    """Inert member replicas must read as cleanly terminated — never
    STALLED — under the watchdog."""
    _, g = _run_family("cb_window", fuse=True)
    health = g.stats()["Health"]
    if health.get("enabled", True):
        for name in ("ma", "fb"):
            v = health["verdicts"][name]
            assert v["state"] == "OK", v


# ---------------------------------------------------------------------------
# advisor --verify (projected vs realized)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_advisor_verify_cli_roundtrip(tmp_path):
    """tools/wf_advisor.py --verify: a fusion-ON run's stats dump
    verifies against the module's plan — every executable chain
    realized one dispatch/batch, exit 0."""
    g = _bench_shaped_graph(True)
    g.run()
    dump = tmp_path / "stats.json"
    dump.write_text(json.dumps({"Sweep": g.stats()["Sweep"]},
                               default=str))
    app = tmp_path / "verify_app.py"
    app.write_text(
        "import numpy as np\n"
        "import windflow_tpu as wf\n\n"
        "def make_graph():\n"
        "    src = (wf.Source_Builder(lambda: iter(()))\n"
        "           .withOutputBatchSize(64).withName('src')\n"
        "           .withRecordSpec({'key': np.int32(0),\n"
        "                            'v': np.float32(0.0)}).build())\n"
        "    m = wf.MapTPU_Builder(\n"
        "        lambda t: {'key': t['key'], 'v': t['v'] * 1.5 + 1.0})\\\n"
        "        .withName('map_tpu').build()\n"
        "    f = wf.FilterTPU_Builder(\n"
        "        lambda t: (t['key'] & 7) != 7)\\\n"
        "        .withName('filter_tpu').build()\n"
        "    w = (wf.Ffat_WindowsTPU_Builder(lambda t: t['v'],\n"
        "                                    lambda a, b: a + b)\n"
        "         .withCBWindows(16, 8).withKeyBy(lambda t: t['key'])\n"
        "         .withMaxKeys(8).withName('win').build())\n"
        "    snk = wf.Sink_Builder(lambda r: None).build()\n"
        "    g = wf.PipeGraph('bench_shape')\n"
        "    p = g.add_source(src)\n"
        "    p.add(m)\n"
        "    p.chain(f)\n"
        "    p.add(w).add_sink(snk)\n"
        "    return g\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=f"{tmp_path}{os.pathsep}{REPO}")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "wf_advisor.py"),
         "verify_app", "--verify", str(dump), "--json"],
        capture_output=True, text=True, env=env, timeout=120)
    assert out.returncode == 0, (out.stdout, out.stderr)
    payload = json.loads(out.stdout)
    assert payload["chains"], payload
    realized = [c for c in payload["chains"] if c.get("realized")]
    assert realized, payload
    assert realized[0]["realized"]["dispatches_per_batch"] <= 1.05
