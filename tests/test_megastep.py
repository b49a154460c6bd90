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
- **The hold**: an edge queues a packet only while its group can still
  fill before the next external drain, judged from the offer intervals
  and the drain period it measured itself; a packet it will not hold
  takes the per-batch ship at once, behind whatever was queued
  (``unheld_batches``).  The tests stamp the offers and the drains on a
  clock of their own.
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


def _run(family, k, n=N, cap=CAP, started=None, **cfg_kw):
    """One graph run at megastep_sweeps=k; returns (sunk records,
    Megastep stats section, completed graph).  ``started(g)`` runs
    between ``g.start()`` and the first sweep."""
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
        g.start()
        if started is not None:
            started(g)
        g.wait_end()
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
# the hold: decided per offer from what the edge has observed
# ---------------------------------------------------------------------------

HOLD_N = 16384          # 64 batches of CAP lanes: room for the rule to learn
PERIOD = 100_000        # usec between the external drains the tests stamp


class _Drive:
    """Stamps an edge's offers and external drains on a clock the test
    owns: ``gap(self)`` usec pass before every offer, an external drain
    follows the offer once ``PERIOD`` has passed since the last (at most
    ``drains`` of them); real punctuations are off.  ``tail_log`` is
    what reached the tail replica per batch, in order: (seq, watermark,
    frontier, tuples)."""

    def __init__(self, g, gap, drains=10 ** 9, rule=True):
        self.g = g
        self.now = 1_000_000
        self.gap = gap
        self.drains = drains
        self.offers = 0
        self.events = []    # (queued before, unheld by, queued after, took)
        self.tail_log = []
        self.tail = tail = g._source_replicas[0].emitter.dests[0][0]
        receive = tail.receive

        def logged(ch, msg):
            if hasattr(msg, "seq"):
                self.tail_log.append((msg.seq, msg.watermark,
                                      msg.frontier, msg.known_size))
            return receive(ch, msg)
        tail.receive = logged
        edges = g._megastep_plane.edges
        self.edge = edge = edges[0] if edges else None
        if edge is None:
            return
        edge._clock = lambda: self.now
        if not rule:            # the parent: every warm packet is queued
            edge._group_can_fill = lambda now: True
        offer = edge.offer
        self._last_drain = self.now

        def stamped(pkt):
            self.now += self.gap(self)
            self.offers += 1
            q0, u0 = len(edge._q), edge.unheld_batches
            took = offer(pkt)
            self.events.append((q0, edge.unheld_batches - u0,
                                len(edge._q), took))
            if self.now - self._last_drain >= PERIOD and self.drains > 0:
                self.drains -= 1
                self._last_drain = self.now
                edge.external_drain()
            return took
        edge.offer = stamped


def _run_driven(family, k, gap, cfg_kw=None, setup=None, **drive_kw):
    """One driven run: (sunk records, the edge's summary, the drive).
    ``setup(drive)`` runs once the graph has started."""
    drives = []

    def started(g):
        drives.append(_Drive(g, gap, **drive_kw))
        if setup is not None:
            setup(drives[0])
    fired, ms, _ = _run(family, k, n=HOLD_N, started=started,
                        punctuation_interval_usec=10 ** 12,
                        **(cfg_kw or {}))
    return fired, (ms["edges"][0] if ms["edges"] else None), drives[0]


def _slow(_d):
    return PERIOD // 2          # two offers a drain: K = 4 never fills


def _fast(_d):
    return PERIOD // 16         # sixteen offers a drain: four groups of 4


@pytest.mark.parametrize("family", FAMILIES)
def test_hold_slow_offers_are_never_held(family):
    """Offers slower than period / K: once the edge has seen two drains
    and its ring of intervals, every packet reaches the tail before the
    next offer, no scan ever runs, and what the tail and the sink see
    is the K = 1 run's, stamp for stamp."""
    base, _, d1 = _run_driven(family, 1, _slow)
    fold, e, d4 = _run_driven(family, 4, _slow)
    assert base and _norm(base) == _norm(fold)
    assert d1.tail_log == d4.tail_log and len(d4.tail_log) == HOLD_N // CAP
    assert e["megasteps"] == 0 and e["batches"] == 0
    known = next(i for i, ev in enumerate(d4.events) if ev[1])
    assert known <= e["warmup_batches"] + 9     # eight intervals, two drains
    # the first judgement may find a packet queued before the edge knew
    q0, unheld, q1, took = d4.events[known]
    assert (unheld, q1, took) == (q0 + 1, 0, False) and q0 <= 1
    for ev in d4.events[known + 1:]:
        assert ev == (0, 1, 0, False)
    assert e["unheld_batches"] == len(d4.events) - known + q0
    # an unheld batch is a per-batch ship while warm
    assert e["fallback_batches"] + e["warmup_batches"] == HOLD_N // CAP
    assert e["fallback_batches"] >= e["unheld_batches"]


def test_unheld_batches_in_stats_and_openmetrics():
    """``g.stats()["Megastep"]["edges"][i]`` carries ``unheld_batches``
    beside the three buckets it always had, and the exposition renders
    the same numbers (strict parser round trip)."""
    from windflow_tpu.monitoring.openmetrics import (parse_exposition,
                                                     render_openmetrics)
    _, e, d = _run_driven("window_cb", 4, _slow)
    assert e["unheld_batches"] > 0
    st = {"PipeGraph_name": "hold", "Megastep": {"k": 4, "edges": [e]}}
    text = render_openmetrics(st)
    parse_exposition(text)      # strict: raises on any violation
    want = {'wf_megastep_dispatches_total{': e["megasteps"],
            'wf_megastep_unheld_batches_total{': e["unheld_batches"],
            'path="scanned"': e["batches"],
            'path="fallback"': e["fallback_batches"],
            'path="warmup"': e["warmup_batches"]}
    for mark, value in want.items():
        line = [ln for ln in text.splitlines()
                if ln.startswith("wf_megastep") and mark in ln]
        assert len(line) == 1, (mark, line)
        assert float(line[0].rsplit(" ", 1)[1]) == float(value), mark
        assert 'operator="w"' in line[0]
    # a graph with no folded edge renders no megastep family
    assert "wf_megastep" not in render_openmetrics(
        {"PipeGraph_name": "k1", "Megastep": {"k": 1, "edges": []}})


@pytest.mark.parametrize("family", FAMILIES)
def test_hold_fast_offers_fill_groups_as_before(family):
    """Offers faster than period / K: the edge holds and scans exactly
    as it did before the rule (the same drive with the rule off)."""
    then, e0, _ = _run_driven(family, 4, _fast, rule=False)
    fold, e, d = _run_driven(family, 4, _fast)
    assert fold and _norm(then) == _norm(fold)
    assert e["unheld_batches"] == 0
    assert e["megasteps"] >= 10
    assert e == e0


@pytest.mark.parametrize("family", FAMILIES)
def test_hold_releases_the_queue_fifo_when_the_stream_slows(family):
    """A stream that stalls with packets queued: the next offer finds
    the drain overdue, ships what is queued ahead of the packet in
    hand, and both count as unheld."""
    slowed = []

    def gap(d):
        # fast until the edge knows both quantities and holds two
        if slowed or (d.offers >= 40 and len(d.edge._q) == 2):
            slowed.append(d.offers)
            return 2 * PERIOD
        return PERIOD // 16
    base, _, _ = _run_driven(family, 1, gap)     # no edge: gap never asked
    fold, e, d = _run_driven(family, 4, gap)
    assert base and _norm(base) == _norm(fold)
    released = [ev for ev in d.events if ev[1] > 1]
    assert released == [(2, 3, 0, False)]
    assert d.events.index(released[0]) == slowed[0]
    # the two queued and then the packet in hand: per-batch arrivals at
    # the tail stay in staging order, none twice
    seqs = [row[0] for row in d.tail_log]
    assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
    assert e["megasteps"] > 0 and e["unheld_batches"] >= 3
    assert e["batches"] + e["warmup_batches"] + e["fallback_batches"] \
        == HOLD_N // CAP


@pytest.mark.parametrize("family", FAMILIES)
def test_hold_waits_for_two_drains_before_it_judges(family):
    """One external drain says nothing of a period: slow offers are
    queued as they always were, and groups fill."""
    then, e0, _ = _run_driven(family, 4, _slow, drains=1, rule=False)
    fold, e, d = _run_driven(family, 4, _slow, drains=1)
    assert fold and _norm(then) == _norm(fold)
    assert e["unheld_batches"] == 0 and e["megasteps"] >= 10
    assert e == e0


@pytest.mark.parametrize("family", FAMILIES)
def test_hold_leaves_nothing_parked_at_a_quiesce(family, tmp_path):
    """A durability quiesce is an external drain: with the rule shipping
    at once and with it holding, the barrier finds the edge's queue and
    the tail's inbox empty, and epochs commit."""
    # the quiesces are the only external drains here: one a driver sweep
    # (a period of one offer: nothing can fill), or one in eight
    for name, eps in (("slow", 4), ("fast", 32)):
        seen = []

        def setup(d):
            def hook(site):
                if site == "post_quiesce":
                    seen.append((len(d.edge._q), len(d.tail.inbox)))
            d.g._durability.chaos_hook = hook

        _, e, d = _run_driven(
            family, 4, _fast, drains=0, setup=setup,
            cfg_kw={"durability": str(tmp_path / name),
                    "durability_epoch_sweeps": eps})
        assert len(seen) >= 3 and set(seen) == {(0, 0)}, (name, seen)
        assert d.g.stats()["Durability"]["epochs_committed"] == len(seen)
        assert (e["unheld_batches"] > 0) == (name == "slow"), (name, e)
        assert (e["megasteps"] > 0) == (name == "fast"), (name, e)


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
