"""Megastep executor suite (windflow_tpu/megastep.py):
fold K consecutive batch sweeps into ONE compiled scan
program on eligible staged edges.

The contracts pinned here:

- **Record-for-record A/B**: K=1 (the kill switch, per-batch cadence
  verbatim) vs K=4/K=8 produce identical sunk records across every
  foldable operator family — CB/TB FFAT windows, sorted and
  declared-dense reduces, dense-keys stateful map — wire compression
  on or off.
- **Dispatch pin**: one megastep = ONE ``megastep.<op>`` program
  dispatch in the jit registry serving K logical batches; the sweep
  ledger's per-hop ``dispatches_per_batch`` drops below 1 honestly.
- **Trace-lane / latency honesty**: flight-recorder spans and the
  end-to-end latency histogram are stamped PER LOGICAL BATCH at the
  megastep drain, never once per megastep.
- **Durability**: epochs round up to a multiple of K
  (``round_epoch_to_megastep``), land only between megasteps, and the
  chaos kill→restore→diff cell stays exactly-once under K=4.
- **WF608 preflight**: a forced ``WF_TPU_MEGASTEP=K`` graph whose edge
  cannot fold names the downgrade (the WF606/WF607 contract applied to
  the megastep plane); auto stays silent.
"""

import dataclasses
import json
import os
import warnings

import numpy as np
import pytest

import jax.numpy as jnp

import windflow_tpu as wf
from windflow_tpu.durability import chaos
from windflow_tpu.megastep import (MegastepPlane, resolve_megastep,
                                   round_epoch_to_megastep)
from windflow_tpu.monitoring.jit_registry import default_registry

FAMILIES = ("window_cb", "window_tb", "reduce_sorted", "reduce_dense",
            "stateful")

N = 4096
CAP = 256
KEYS = 8


# ---------------------------------------------------------------------------
# harness: a frames source (packed columnar staging — the eligible edge
# shape) feeding one foldable tail per family
# ---------------------------------------------------------------------------

def _frames_blob(n, nkeys=KEYS, seed=7):
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


def _tail(family):
    if family == "window_cb":
        return (wf.Ffat_WindowsTPU_Builder(lambda t: t["v"],
                                           lambda a, b: a + b)
                .withCBWindows(64, 32).withKeyBy(lambda t: t["key"])
                .withMaxKeys(KEYS).withName("w").build())
    if family == "window_tb":
        return (wf.Ffat_WindowsTPU_Builder(lambda t: t["v"],
                                           lambda a, b: a + b)
                .withTBWindows(16_000, 4_000)
                .withKeyBy(lambda t: t["key"]).withMaxKeys(KEYS)
                .withLateness(8_000).withName("w").build())
    if family == "reduce_sorted":
        return (wf.ReduceTPU_Builder(
                    lambda a, b: {"key": a["key"], "v": a["v"] + b["v"]})
                .withKeyBy(lambda t: t["key"]).withName("w").build())
    if family == "reduce_dense":
        return (wf.ReduceTPU_Builder(lambda a, b: a)
                .withKeyBy(lambda t: t["key"]).withMaxKeys(KEYS)
                .withSumCombiner().withName("w").build())
    if family == "stateful":
        def f(rec, st):
            st = {"acc": st["acc"] + rec["v"]}
            return {"key": rec["key"], "v": st["acc"]}, st
        return (wf.MapTPU_Builder(f)
                .withKeyBy(lambda t: t["key"])
                .withInitialState({"acc": jnp.float32(0)})
                .withNumKeySlots(KEYS).withDenseKeys()
                .withName("w").build())
    raise ValueError(family)


def _run(family, k, n=N, cap=CAP, **cfg_kw):
    """One graph run at megastep_sweeps=k; returns (sunk records,
    Megastep stats section, completed graph)."""
    fired = []
    # dense kinds under default key_compaction attach a host-admission
    # compactor — a DIFFERENT (deliberate, WF608-named) downgrade; off
    # here so the suite exercises the fold itself
    cfg_kw.setdefault("key_compaction", False)
    cfg = dataclasses.replace(wf.default_config, megastep_sweeps=k,
                              **cfg_kw)
    g = wf.PipeGraph(f"ms_{family}_{k}", config=cfg,
                     time_policy=wf.TimePolicy.EVENT)
    g.add_source(_source(n, cap)).add(_tail(family)).add_sink(
        wf.Sink_Builder(lambda r: fired.append(r)
                        if r is not None else None).build())
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        g.run()
    return fired, g.stats()["Megastep"], g


def _norm(rs):
    out = []
    for r in rs:
        out.append(tuple(sorted(
            (k, round(float(v), 4) if isinstance(v, (float, np.floating))
             else (int(v) if isinstance(v, (int, np.integer)) else v))
            for k, v in r.items())))
    return out


# ---------------------------------------------------------------------------
# record-for-record A/B: K=1 vs K=4 / K=8
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("family", FAMILIES)
def test_ab_record_identical_k4(family):
    base, ms1, _ = _run(family, 1)
    fold, ms4, _ = _run(family, 4)
    assert _norm(base) == _norm(fold), family
    assert base, "empty output proves nothing"
    # K=1 is the kill switch: no plane, no edges
    assert ms1["k"] == 1 and ms1["edges"] == []
    e = ms4["edges"][0]
    assert e["k"] == 4 and e["megasteps"] > 0
    # every logical batch is accounted: folded + warm-up + fallback
    assert e["batches"] == e["megasteps"] * 4
    assert e["batches"] + e["warmup_batches"] + e["fallback_batches"] \
        == N // CAP


def test_ab_record_identical_k8_window():
    base, _, _ = _run("window_cb", 1, n=8192)
    fold, ms8, _ = _run("window_cb", 8, n=8192)
    assert _norm(base) == _norm(fold)
    e = ms8["edges"][0]
    assert e["k"] == 8 and e["megasteps"] > 0


def test_ab_record_identical_wire_on():
    """Wire compression composes: the scan body inlines the same wire
    decode the per-batch unpack runs."""
    base, _, _ = _run("window_cb", 1, wire_compression=True)
    fold, ms, _ = _run("window_cb", 4, wire_compression=True)
    assert _norm(base) == _norm(fold)
    assert ms["edges"][0]["megasteps"] > 0


def test_auto_resolves_per_backend():
    """'auto' keeps per-batch cadence on CPU (the dispatch fold pays off
    only when host pacing, not compute, bounds the edge) and a forced
    integer wins everywhere."""
    cfg = dataclasses.replace(wf.default_config, megastep_sweeps="auto")
    import jax
    expect = 1 if jax.default_backend() == "cpu" else 8
    assert resolve_megastep(cfg) == expect
    cfg = dataclasses.replace(wf.default_config, megastep_sweeps=4)
    assert resolve_megastep(cfg) == 4
    cfg = dataclasses.replace(wf.default_config, megastep_sweeps="1")
    assert resolve_megastep(cfg) == 1


# ---------------------------------------------------------------------------
# dispatch accounting: 1 program per K sweeps (jit registry + ledger)
# ---------------------------------------------------------------------------

def test_dispatch_pinned_one_program_per_megastep():
    before = dict(default_registry().dispatch_counts())
    _, ms, g = _run("window_cb", 4)
    after = default_registry().dispatch_counts()
    mega = {n: after[n] - before.get(n, 0)
            for n in after if n.startswith("megastep.")}
    e = ms["edges"][0]
    assert e["megasteps"] >= 2
    # the pin: exactly ONE megastep program dispatch per K-sweep group —
    # a fold that grew extra dispatches would show here
    assert sum(mega.values()) == e["megasteps"], mega
    # ...and the ledger divides it honestly: the tail hop served K
    # batches per dispatch, so dispatches/batch drops below 1
    hop = g.stats()["Sweep"]["per_hop"]["w"]
    assert hop["batches"] >= N // CAP    # + the FFAT EOS flush launch
    assert hop["dispatches"] < hop["batches"]
    assert hop["dispatches_per_batch"] < 1.0
    json.dumps(ms)      # ships in every stats payload


def test_k1_registers_no_megastep_programs():
    before = dict(default_registry().dispatch_counts())
    _, ms, _ = _run("reduce_dense", 1)
    after = default_registry().dispatch_counts()
    grew = [n for n in after if n.startswith("megastep.")
            and after[n] > before.get(n, 0)]
    assert grew == []
    assert ms["edges"] == []


# ---------------------------------------------------------------------------
# trace-lane / latency honesty at K granularity (flight recorder + p99)
# ---------------------------------------------------------------------------

def test_per_batch_spans_and_e2e_p99_under_k8():
    """A megastep serves K logical batches; the flight recorder and the
    end-to-end latency histogram must say K, not 1 — one span chain and
    one e2e sample PER BATCH, stamped at the drain."""
    n = 8192
    _, ms, g = _run("window_cb", 8, n=n, flight_recorder=True,
                    trace_sample_every=1)
    e = ms["edges"][0]
    assert e["megasteps"] >= 2
    ev = g._recorder.events()
    dispatched = [x for x in ev if x["stage"] == "dispatched"]
    sunk = [x for x in ev if x["stage"] == "sunk"]
    # per-batch honesty: a lazy implementation stamping once per
    # megastep would record ~megasteps spans, not ~batches
    assert len(dispatched) >= e["batches"]
    assert len(sunk) >= e["batches"]
    lat = g.stats()["Latency"]["end_to_end_usec"]
    assert lat["count"] >= e["batches"]
    assert lat["count"] > e["megasteps"]
    assert 0 < lat["p50"] <= lat["p99"]


# ---------------------------------------------------------------------------
# durability: epochs on megastep boundaries + chaos kill/restore A/B
# ---------------------------------------------------------------------------

def test_round_epoch_to_megastep_unit():
    """The configured cadence reads as LOGICAL sweeps and converts to
    driver sweeps (one driver sweep = K logical sweeps when folded):
    ceil(eps/K), so every epoch covers the same stream extent it
    covered per-batch."""
    plane = MegastepPlane(4)
    plane.edges.append(object())    # active needs >=1 edge
    cfg = dataclasses.replace(wf.default_config,
                              durability_epoch_sweeps=3)
    assert round_epoch_to_megastep(cfg, plane) == 1   # 3 -> 1 megastep
    assert cfg.durability_epoch_sweeps == 1
    cfg.durability_epoch_sweeps = 8
    assert round_epoch_to_megastep(cfg, plane) == 2   # 8 -> 2 megasteps
    cfg.durability_epoch_sweeps = 1
    assert round_epoch_to_megastep(cfg, plane) is None   # stable point
    assert cfg.durability_epoch_sweeps == 1
    inactive = MegastepPlane(1)
    cfg.durability_epoch_sweeps = 3
    assert round_epoch_to_megastep(cfg, inactive) is None
    assert cfg.durability_epoch_sweeps == 3


def _force_default(monkeypatch, **kw):
    """The chaos cell factories build from wf.default_config; pin the
    megastep knobs there for the cell's lifetime."""
    for k, v in kw.items():
        monkeypatch.setattr(wf.default_config, k, v)


def test_chaos_kill_restore_megastep_epochs(tmp_path, monkeypatch):
    """The exactly-once cell under K=4: the Kafka-fed CB-window family
    folds (wire on makes its record path a packed staged edge), its
    epoch cadence rounds 3->4 so every checkpoint quiesce lands between
    megasteps, a mid-epoch kill + restore replays — and the sunk output
    diffs record-for-record empty against the uninterrupted run."""
    _force_default(monkeypatch, megastep_sweeps=4, wire_compression=True)
    base = chaos.make_cell("window_cb", str(tmp_path / "ck_a"), n=N)
    chal = chaos.make_cell("window_cb", str(tmp_path / "ck_b"), n=N)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        gb = chaos.run_baseline(base["factory"])
        # the baseline actually folded, and the cell's epoch_sweeps=3
        # converted to whole megasteps (ceil(3/4) = 1 driver sweep)
        ms = gb.stats()["Megastep"]
        assert ms["k"] == 4 and ms["edges"][0]["megasteps"] > 0
        assert gb.config.durability_epoch_sweeps == 1
        # driver sweeps are K-granular, so the kill count is too
        gc = chaos.run_killed_and_restored(
            chal["factory"], chaos.KillSpec("mid_epoch", after=2))
    diff = chaos.diff_records(base["read"](), chal["read"]())
    assert diff is None, diff
    assert gc.stats()["Durability"]["restored_epoch"] is not None


def test_epoch_cadence_keeps_logical_sweep_meaning(tmp_path):
    """durability_epoch_sweeps reads as LOGICAL batch sweeps under a
    folded edge (round_epoch_to_megastep converts to driver sweeps):
    the K=4 run of the same stream commits at least as many epochs as
    K=1, never K x fewer."""
    def committed(k):
        fired = []
        cfg = dataclasses.replace(
            wf.default_config, megastep_sweeps=k, key_compaction=False,
            durability=str(tmp_path / f"ck_{k}"),
            durability_epoch_sweeps=4,
            punctuation_interval_usec=10 ** 12)
        g = wf.PipeGraph(f"ms_epoch_{k}", config=cfg,
                         time_policy=wf.TimePolicy.EVENT)
        g.add_source(_source()).add(_tail("window_cb")).add_sink(
            wf.Sink_Builder(lambda r: fired.append(r)).build())
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            g.run()
        return g.stats()["Durability"]["epochs_committed"]

    c1, c4 = committed(1), committed(4)
    assert c1 > 0
    # the conversion guard: without ceil(eps/K) the folded run would
    # cover ~K x more stream per epoch and commit ~c1/K epochs
    assert c4 >= c1


# ---------------------------------------------------------------------------
# WF608: forced K>1 downgrades are NAMED at preflight, auto is silent
# ---------------------------------------------------------------------------

def _cfgk(k, **kw):
    c = dataclasses.replace(wf.default_config, megastep_sweeps=k)
    for a, v in kw.items():
        setattr(c, a, v)
    return c


def _spec_source():
    return (wf.Source_Builder(lambda: iter(()))
            .withOutputBatchSize(256)
            .withRecordSpec({"key": np.int32(0),
                             "v": np.float32(0)}).build())


def _win():
    return (wf.Ffat_WindowsTPU_Builder(lambda t: t["v"],
                                       lambda a, b: a + b)
            .withCBWindows(64, 32).withKeyBy(lambda t: t["key"])
            .withMaxKeys(8).build())


def _host_reduce():
    return (wf.Reduce_Builder(
        lambda item, st: st.__setitem__("n", st.get("n", 0) + 1), dict)
        .withKeyBy(lambda t: t["key"]).build())


def _wf608(g):
    return [d for d in g.check() if d.code == "WF608"]


def test_wf608_eligible_forced_is_clean():
    g = wf.PipeGraph("ok", config=_cfgk(8))
    g.add_source(_spec_source()).add(_win()).add_sink(
        wf.Sink_Builder(lambda r: None).build())
    assert _wf608(g) == []


def test_wf608_host_operator_tail():
    g = wf.PipeGraph("host", config=_cfgk(8))
    g.add_source(_spec_source()).add(_host_reduce()).add_sink(
        wf.Sink_Builder(lambda r: None).build())
    ds = _wf608(g)
    assert len(ds) == 1 and ds[0].severity == "warning"
    assert "host operator" in ds[0].message
    assert ds[0].hint       # documents the correctness-neutral downgrade


def test_wf608_specless_source():
    g = wf.PipeGraph("specless", config=_cfgk(8))
    g.add_source(wf.Source_Builder(lambda: iter(()))
                 .withOutputBatchSize(256).build()) \
        .add(_win()).add_sink(wf.Sink_Builder(lambda r: None).build())
    ds = _wf608(g)
    assert len(ds) == 1 and "spec" in ds[0].message


def test_wf608_compacted_key_space_and_the_fix():
    def graph(**cfg_kw):
        g = wf.PipeGraph("compacted", config=_cfgk(8, **cfg_kw))
        g.add_source(_spec_source()).add(
            wf.ReduceTPU_Builder(lambda a, b: a)
            .withKeyBy(lambda t: t["key"]).withMaxKeys(8)
            .withSumCombiner().build()).add_sink(
            wf.Sink_Builder(lambda r: None).build())
        return g

    ds = _wf608(graph(key_compaction=True))
    assert len(ds) == 1 and "compacted key space" in ds[0].message
    # the hint's own advice clears the warning
    assert _wf608(graph(key_compaction=False)) == []


def test_wf608_auto_is_silent():
    g = wf.PipeGraph("auto", config=_cfgk("auto"))
    g.add_source(_spec_source()).add(_host_reduce()).add_sink(
        wf.Sink_Builder(lambda r: None).build())
    assert _wf608(g) == []


def test_wf608_fused_stateless_prelude_is_clean():
    """Stateless map/filter between source and window fuse into the
    tail segment — the effective tail still folds, no warning."""
    g = wf.PipeGraph("fused", config=_cfgk(8))
    p = g.add_source(_spec_source())
    p.add(wf.MapTPU_Builder(
        lambda t: {"key": t["key"], "v": t["v"] * 2}).build())
    p.chain(wf.FilterTPU_Builder(lambda t: (t["key"] & 1) == 0).build())
    p.add(_win()).add_sink(wf.Sink_Builder(lambda r: None).build())
    assert _wf608(g) == []
