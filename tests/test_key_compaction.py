"""Device-side key compaction (windflow_tpu/parallel/compaction.py):
record-for-record A/B of the compacted dense
fast path against the sorted arbitrary-key path and the declared-dense
baseline across the reduce / stateful / FFAT-keyed families,
overflow-to-sorted correctness under adversarial key streams (all-cold,
all-hot, Zipf-shift mid-run), the pinned-table overflow contracts
(FFAT masks + counts, stateful surfaces the interner's num_key_slots
error), concurrent sibling-replica admission, the zero-extra-dispatch
pin through the jit registry, churn/hit-rate surfacing in
``stats()["Shard"]``, the remap-restore chaos cell, and the
``WF_TPU_KEY_COMPACTION`` kill-switch off-path."""

import dataclasses
import time

import jax.numpy as jnp
import numpy as np
import pytest

import windflow_tpu as wf
from windflow_tpu.basic import WindFlowError, default_config
from windflow_tpu.monitoring.jit_registry import default_registry
from windflow_tpu.parallel.compaction import KEY_SENTINEL, KeyCompactor

CAP = 64


def _cfg(compact=True, **kw):
    return dataclasses.replace(default_config, key_compaction=compact,
                               **kw)


def _sink(got):
    def s(r, ctx=None):
        if r is None:
            return
        got.append(tuple(sorted((k, float(v)) for k, v in r.items()))
                   if isinstance(r, dict) else float(r))
    return wf.Sink_Builder(s).withName("snk").build()


def _run_reduce(stream, *, compact=True, monoid="max", max_keys=None,
                name="red", cap=CAP, **cfg_kw):
    got = []
    src = (wf.Source_Builder(lambda: iter(stream))
           .withOutputBatchSize(cap).withName("src").build())
    b = (wf.ReduceTPU_Builder(
            lambda a, b: {"key": jnp.maximum(a["key"], b["key"]),
                          "v": jnp.maximum(a["v"], b["v"])})
         .withKeyBy(lambda t: t["key"]).withName(name))
    if monoid is not None:
        b = b.withMonoidCombiner(monoid)
    if max_keys is not None:
        b = b.withMaxKeys(max_keys)
    op = b.build()
    g = wf.PipeGraph("kc_reduce", wf.ExecutionMode.DEFAULT,
                     config=_cfg(compact, **cfg_kw))
    g.add_source(src).add(op).add_sink(_sink(got))
    g.run()
    return got, op, g


def _stream(n, key_of, v_of=None):
    v_of = v_of or (lambda i: -2.0 - ((i * 29) % 83) / 7.0)
    return [{"key": np.int32(key_of(i)), "v": np.float32(v_of(i))}
            for i in range(n)]


# ---------------------------------------------------------------------------
# record-for-record A/B: compacted vs sorted vs declared-dense
# ---------------------------------------------------------------------------

def test_compacted_reduce_matches_sorted_and_dense():
    """Arbitrary sparse int32 keys, declared monoid: the compacted step
    (dense slots + overflow lane, one program) must emit exactly the
    sorted path's records; the same stream remapped into [0, K) through
    the declared-dense baseline must agree too."""
    stream = _stream(512, lambda i: (i * 7) % 23 + 1000)
    compacted, op, _ = _run_reduce(stream, compact=True)
    sorted_, _, _ = _run_reduce(stream, compact=False)
    assert compacted == sorted_ and len(compacted) > 0
    s = op._compactor.summary()
    assert s["hit_rate"] == 1.0 and s["overflow_share"] == 0.0
    # declared-dense baseline over the same values, keys shifted to
    # [0, 23): per-key results must match the compacted run's
    base = _stream(512, lambda i: (i * 7) % 23)
    dense, _, _ = _run_reduce(base, compact=False, max_keys=23)
    shift = [tuple((k, v - 1000.0 if k == "key" else v) for k, v in r)
             for r in compacted]
    assert shift == dense


def test_undeclared_reduce_keeps_sorted_path():
    """No monoid declared: compaction must not attach (the dense
    scatter-combine needs the declared-monoid contract) and records
    stay the sorted path's."""
    stream = _stream(256, lambda i: (i * 11) % 19 + 500)
    a, op, _ = _run_reduce(stream, compact=True, monoid=None)
    b, _, _ = _run_reduce(stream, compact=False, monoid=None)
    assert a == b and op._compactor is None


def test_stateful_compacted_matches_interned():
    """Host-fed interning stateful: the compactor becomes the
    device-resident interner — identical records, miss-free remap."""
    def run(compact):
        got = []
        stream = _stream(512, lambda i: (i * 13) % 37 - 5,
                         v_of=lambda i: float(i))
        src = (wf.Source_Builder(lambda: iter(stream))
               .withOutputBatchSize(CAP).withName("src").build())
        op = (wf.MapTPU_Builder(
                lambda t, s: ({"key": t["key"], "v": t["v"] + s},
                              s + 1.0))
              .withInitialState(np.float32(0.0))
              .withKeyBy(lambda t: t["key"])
              .withNumKeySlots(64).withName("sm").build())
        g = wf.PipeGraph("kc_stateful", wf.ExecutionMode.DEFAULT,
                         config=_cfg(compact))
        g.add_source(src).add(op).add_sink(_sink(got))
        g.run()
        return got, op
    a, op_a = run(True)
    b, op_b = run(False)
    assert a == b and len(a) == 512
    assert op_b._compactor is None and len(op_b._interner) == 37
    s = op_a._compactor.summary()
    assert s["pinned"] and s["hit_rate"] == 1.0
    assert len(op_a._interner) == 0     # no host interning happened


def test_ffat_compacted_matches_declared_with_user_keys():
    """withCompactedKeys vs a withMaxKeys baseline whose extractor
    applies the same dense mapping by hand: same windows, same values —
    and the fired records carry the USER's keys, not remap slots, even
    when admission order scrambles the slot assignment (staggered
    arrival) and at the EOS partial-window flush."""
    def stream():
        for i in range(768):
            k = 1015 - (i * 7) % 16 if i >= 128 else 1010 + (i % 3)
            yield {"key": np.int32(k), "v": np.float32(i)}

    def run(mode):
        got = []
        src = (wf.Source_Builder(stream)
               .withOutputBatchSize(CAP).withName("src").build())
        b = wf.Ffat_WindowsTPU_Builder(lambda t: t["v"],
                                       lambda a, b: a + b) \
            .withCBWindows(8, 4).withName("w")
        if mode == "compact":
            b = b.withKeyBy(lambda t: t["key"]).withCompactedKeys()
        else:
            b = b.withKeyBy(lambda t: t["key"] - 1000).withMaxKeys(16)
        op = b.build()
        g = wf.PipeGraph("kc_ffat", wf.ExecutionMode.DEFAULT,
                         config=_cfg(True))
        g.add_source(src).add(op).add_sink(_sink(got))
        g.run()
        return got, op

    a, op_a = run("compact")
    b, _ = run("dense")
    norm = sorted(tuple((k, v - 1000.0 if k == "key" else v)
                        for k, v in r) for r in a)
    assert norm == sorted(b) and len(a) > 0
    assert op_a._compactor.summary()["hit_rate"] == 1.0


# ---------------------------------------------------------------------------
# adversarial key streams: the overflow lane keeps the sorted contract
# ---------------------------------------------------------------------------

def test_all_cold_stream_overflows_to_sorted():
    """Distinct keys far beyond the slot budget: nearly every lane
    misses, the full-width sorted fallback (lax.cond big path) runs,
    and records still match the sorted path exactly."""
    stream = _stream(2048, lambda i: i * 3 + 7)
    a, op, _ = _run_reduce(stream, compact=True, cap=128,
                           key_compaction_slots=32)
    b, _, _ = _run_reduce(stream, compact=False, cap=128)
    assert a == b and len(a) == 2048
    s = op._compactor.summary()
    assert s["big_fallbacks"] > 0 and s["overflow_share"] > 0.9


def test_all_hot_stream_stays_dense():
    """Key cardinality under the slot budget: everything admits at the
    staging boundary, zero overflow, zero churn."""
    stream = _stream(1024, lambda i: (i % 8) * 1000)
    a, op, _ = _run_reduce(stream, compact=True)
    b, _, _ = _run_reduce(stream, compact=False)
    assert a == b
    s = op._compactor.summary()
    assert s["hit_rate"] == 1.0 and s["churn"] == 0
    assert s["big_fallbacks"] == 0


def test_zipf_shift_mid_run_reseeds_and_churns():
    """Hot set shifts mid-stream on a FULL table: the reseed cadence
    folds the shard sketch's new hot candidates in, evicting provably
    colder slots (the churn counter) — records equal the sorted path
    throughout the shift."""
    def key_of(i):
        if i < 1024:
            return 100 + i % 16          # fills the 16-slot table
        return 9000 + i % 4 if i % 8 else 100 + i % 16

    stream = _stream(4096, key_of)
    a, op, _ = _run_reduce(stream, compact=True, cap=128,
                           key_compaction_slots=16,
                           key_compaction_reseed=4)
    b, _, _ = _run_reduce(stream, compact=False, cap=128)
    assert a == b
    s = op._compactor.summary()
    assert s["reseeds"] > 0
    assert s["churn"] > 0, s
    assert op._compactor.slot_of(9000) is not None   # new hot key seated


def test_sentinel_key_rides_overflow_lane():
    """A record keyed exactly INT32_MAX (the table sentinel) is never
    admitted and never wrong: it rides the sorted overflow lane."""
    stream = _stream(128, lambda i: 2**31 - 1 if i % 16 == 0 else i % 5)
    a, op, _ = _run_reduce(stream, compact=True)
    b, _, _ = _run_reduce(stream, compact=False)
    assert a == b
    assert op._compactor.slot_of(int(KEY_SENTINEL)) is None
    assert op._compactor.summary()["overflow_tuples"] > 0


def test_sentinel_key_deactivates_stateful_to_intern():
    """The stateful plane has a lossless intern fallback: a sentinel
    user key deactivates the compactor (instead of dropping the record)
    and the run matches plain interning."""
    def run(compact):
        got = []
        stream = _stream(256, lambda i: 2**31 - 1 if i == 40 else i % 9,
                         v_of=lambda i: float(i))
        src = (wf.Source_Builder(lambda: iter(stream))
               .withOutputBatchSize(CAP).withName("src").build())
        op = (wf.MapTPU_Builder(
                lambda t, s: ({"key": t["key"], "v": t["v"] + s},
                              s + 1.0))
              .withInitialState(np.float32(0.0))
              .withKeyBy(lambda t: t["key"])
              .withNumKeySlots(32).withName("sm").build())
        g = wf.PipeGraph("kc_sentinel", wf.ExecutionMode.DEFAULT,
                         config=_cfg(compact))
        g.add_source(src).add(op).add_sink(_sink(got))
        g.run()
        return got, op
    a, op_a = run(True)
    b, _ = run(False)
    assert a == b and len(a) == 256     # the sentinel record survived
    assert op_a._compactor is None or not op_a._compactor.active


def test_ffat_slot_overflow_masks_and_counts():
    """More distinct keys than the pinned slot budget: the table keeps
    serving the admitted keys (no deactivation, no error — the
    operator's documented out-of-range contract), the rejected keys'
    lanes are masked invalid and counted (``full_rejects`` + the miss
    counters), and the admitted keys' windows still match a
    declared-dense run over the stream filtered to those keys."""
    stream = [{"key": np.int32(i % 8), "v": np.float32(i)}
              for i in range(512)]

    def run(records, mode):
        got = []
        src = (wf.Source_Builder(lambda: iter(records))
               .withOutputBatchSize(CAP).withName("src").build())
        b = wf.Ffat_WindowsTPU_Builder(lambda t: t["v"],
                                       lambda a, b: a + b) \
            .withCBWindows(8, 4).withKeyBy(lambda t: t["key"]) \
            .withName("w")
        b = (b.withCompactedKeys() if mode == "compact"
             else b.withMaxKeys(8))
        op = b.build()
        g = wf.PipeGraph("kc_full", wf.ExecutionMode.DEFAULT,
                         config=_cfg(True, key_compaction_slots=4))
        g.add_source(src).add(op).add_sink(_sink(got))
        g.run()
        return got, op

    a, op = run(stream, "compact")
    s = op._compactor.summary()
    assert s["full_rejects"] > 0 and "deactivated" not in s
    assert 0.0 < s["hit_rate"] < 1.0
    admitted = {k for k in range(8)
                if op._compactor.slot_of(k) is not None}
    assert len(admitted) == 4
    base, _ = run([r for r in stream if int(r["key"]) in admitted],
                  "dense")
    assert sorted(a) == sorted(base) and len(a) > 0


def test_stateful_slot_overflow_raises_interner_error():
    """Distinct keys beyond num_key_slots on the pinned intern-fallback
    compactor: the overflow surfaces as the interner's num_key_slots
    error on that very batch — the admission path deactivates to the
    lossless host interner instead of swallowing the overflow into
    silently masked records."""
    stream = _stream(256, lambda i: i % 12, v_of=lambda i: float(i))
    src = (wf.Source_Builder(lambda: iter(stream))
           .withOutputBatchSize(CAP).withName("src").build())
    op = (wf.MapTPU_Builder(
            lambda t, s: ({"key": t["key"], "v": t["v"] + s}, s + 1.0))
          .withInitialState(np.float32(0.0))
          .withKeyBy(lambda t: t["key"])
          .withNumKeySlots(8).withName("sm").build())
    g = wf.PipeGraph("kc_overflow", wf.ExecutionMode.DEFAULT,
                     config=_cfg(True))
    g.add_source(src).add(op).add_sink(_sink([]))
    with pytest.raises(WindFlowError, match="num_key_slots"):
        g.run()


def test_ffat_dead_admission_path_fails_loudly():
    """A compacted window has NO lossless fallback: if the host
    admission path dies (speculative probe failure / admission
    anomaly), the next dispatch raises with the withMaxKeys hint
    instead of silently masking every not-yet-admitted key's records
    forever."""
    op = (wf.Ffat_WindowsTPU_Builder(lambda t: t["v"],
                                     lambda a, b: a + b)
          .withCBWindows(8, 4).withKeyBy(lambda t: t["key"])
          .withCompactedKeys().withName("w").build())

    def gen():
        # runs after the graph build attached the compactor, before
        # the first batch ships — the probe-failure state
        op._compactor.deactivate()
        for i in range(256):
            yield {"key": np.int32(i % 4), "v": np.float32(i)}

    src = (wf.Source_Builder(gen)
           .withOutputBatchSize(CAP).withName("src").build())
    g = wf.PipeGraph("kc_dead", wf.ExecutionMode.DEFAULT,
                     config=_cfg(True))
    g.add_source(src).add(op).add_sink(_sink([]))
    with pytest.raises(WindFlowError, match="admission"):
        g.run()


def test_stateful_restore_across_kill_switch():
    """The remap is the key→slot half of per-key state: a compacted
    checkpoint restored with the plane OFF folds the mapping into the
    host interner (rows keep meaning the same keys — no silent
    re-intern-from-slot-0 corruption), and an interned checkpoint
    restored with the plane ON keeps the interner path (a fresh remap
    would assign conflicting slots)."""
    def run(compact):
        got = []
        stream = _stream(256, lambda i: (i * 13) % 37 - 5,
                         v_of=lambda i: float(i))
        src = (wf.Source_Builder(lambda: iter(stream))
               .withOutputBatchSize(CAP).withName("src").build())
        op = (wf.MapTPU_Builder(
                lambda t, s: ({"key": t["key"], "v": t["v"] + s},
                              s + 1.0))
              .withInitialState(np.float32(0.0))
              .withKeyBy(lambda t: t["key"])
              .withNumKeySlots(64).withName("sm").build())
        g = wf.PipeGraph("kc_xkill", wf.ExecutionMode.DEFAULT,
                         config=_cfg(compact))
        g.add_source(src).add(op).add_sink(_sink(got))
        g.run()
        return op

    op_a = run(True)            # compacted run
    op_b = run(False)           # interned run
    blob_a = op_a.snapshot_state()
    blob_b = op_b.snapshot_state()
    # compacted checkpoint -> plane-off operator: mapping adopted
    op_b.restore_state(blob_a)
    assert op_b._interner._ids == op_a._compactor.export_mapping()
    # interned checkpoint -> compacted operator: interner owns the rows
    assert op_a._compactor is not None
    op_a.restore_state(blob_b)
    assert op_a._compactor is None
    assert op_a._interner._ids == blob_b["interner"]


def test_concurrent_admission_keeps_table_consistent():
    """Sibling host emitter replicas drain on the worker pool and admit
    into ONE consumer's compactor concurrently: admission, rebuild and
    the table/placement reads hold the lock, so the sorted key mirror,
    the slot mirror and the dict stay mutually consistent (regression:
    dict-changed-size mid ``_rebuild`` / torn ``(_tk, _tsl)`` pairs /
    double-popped free slots)."""
    import threading

    comp = KeyCompactor(256, name="hammer")
    errs = []

    def worker(seed):
        rng = np.random.RandomState(seed)
        try:
            for _ in range(200):
                comp.observe(rng.randint(0, 300, 32).astype(np.int64))
                comp.place_np(rng.randint(0, 300, 16).astype(np.int64),
                              4)
        except Exception as e:      # noqa: BLE001 — the regression
            errs.append(e)

    ts = [threading.Thread(target=worker, args=(s,)) for s in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert errs == []
    n = len(comp._key_slot)
    keys = np.sort(np.fromiter(comp._key_slot.keys(), np.int32,
                               count=n))
    assert np.array_equal(keys, comp._tk[:n])
    for k, slot in comp._key_slot.items():
        pos = int(np.searchsorted(comp._tk[:n], np.int32(k)))
        assert comp._tsl[pos] == slot
    # every slot accounted for exactly once: occupied + free partition
    assert sorted(list(comp._key_slot.values())
                  + list(comp._free)) == list(range(256))


# ---------------------------------------------------------------------------
# the bounded (withMaxKeys) reroute: the PR 1 drop path retired
# ---------------------------------------------------------------------------

def test_bounded_reduce_reroutes_out_of_range_instead_of_dropping():
    """withMaxKeys + monoid with out-of-range keys: compaction routes
    them down the overflow/sorted lane (kept, counted) — the records
    equal the UNDECLARED sorted path's, and no RuntimeWarning fires."""
    import warnings
    stream = _stream(320, lambda i: i % 10)
    with warnings.catch_warnings():
        warnings.simplefilter("error", RuntimeWarning)
        a, op, _ = _run_reduce(stream, compact=True, max_keys=6)
    b, _, _ = _run_reduce(stream, compact=False, monoid=None)
    assert a == b                       # out-of-range keys KEPT
    st = op.dump_stats()
    n_oor = sum(1 for t in stream if t["key"] >= 6)
    assert st["Out_of_range_keys_rerouted"] == n_oor
    assert "Out_of_range_keys_dropped" not in st
    assert op._compactor.bounded


# ---------------------------------------------------------------------------
# zero extra dispatches + stats surfacing
# ---------------------------------------------------------------------------

def test_zero_extra_dispatch_per_batch():
    """The remap rides the consumer's ONE program (tables are read-only
    operands, cstats is donated): the jit registry shows exactly one
    dispatch per batch for the hop and no second remap program."""
    default_registry().reset()
    stream = _stream(512, lambda i: (i * 7) % 23 + 1000)
    _, op, _ = _run_reduce(stream, compact=True, name="zed")
    snap = default_registry().snapshot()
    assert snap["zed.compact"]["dispatches"] == 512 // CAP
    others = [k for k in snap if k.startswith("zed") and
              k != "zed.compact" and snap[k]["dispatches"]]
    assert others == [], f"extra programs dispatched: {others}"
    assert snap["zed.compact"]["recompiles"] == 0


def test_stats_shard_section_carries_compaction():
    """stats()["Shard"].per_op.<op>.compaction surfaces hit rate /
    overflow share / churn beside the load sketch, and dump_stats
    carries the same summary."""
    stream = _stream(512, lambda i: (i * 7) % 23 + 1000)
    _, op, g = _run_reduce(stream, compact=True)
    sec = g.stats()["Shard"]["per_op"][op.name]["compaction"]
    assert sec["hit_rate"] == 1.0
    assert sec["tuples"] == 512
    assert {"slots", "occupied", "overflow_share", "churn",
            "churn_per_sweep", "reseeds"} <= set(sec)
    assert op.dump_stats()["Key_compaction"]["tuples"] == 512


# ---------------------------------------------------------------------------
# durable state: the remap restores exactly (kill -> restore -> diff)
# ---------------------------------------------------------------------------

def test_chaos_remap_restores_record_for_record(tmp_path):
    """window_compact chaos cell: the compacted FFAT's pane rings index
    by remap slots, so a replay under a different key->slot assignment
    would emit wrong keys — the kill -> restore -> diff proves the
    remap snapshot restores bit-exactly through the epoch protocol."""
    from windflow_tpu.durability import chaos
    base = chaos.make_cell("window_compact", str(tmp_path / "ck_a"))
    chal = chaos.make_cell("window_compact", str(tmp_path / "ck_b"))
    v = chaos.run_ab(base["factory"], chal["factory"],
                     chaos.default_kill("window_compact", "mid_epoch"),
                     base["read"], chal["read"])
    assert v["diff"] is None, v["diff"]
    assert v["restored_epoch"] is not None
    assert v["records"] > 0


def test_compactor_snapshot_round_trip():
    """Unit: snapshot/restore reproduces the key->slot table, the free
    list, and the cadence counters on a fresh instance."""
    c = KeyCompactor(8, reseed_every=4, name="u")
    c.observe(np.array([5, 9, 5, 130], np.int64))
    c.on_batch()
    blob = c.snapshot()
    r = KeyCompactor(8, reseed_every=4, name="u")
    r.restore(blob)
    assert r.slot_of(5) == c.slot_of(5)
    assert r.slot_of(130) == c.slot_of(130)
    assert sorted(r._free) == sorted(c._free)
    assert np.array_equal(r._tk, c._tk)
    assert np.array_equal(r._tsl, c._tsl)


# ---------------------------------------------------------------------------
# kill switch: off-path is one `is not None` check
# ---------------------------------------------------------------------------

def test_kill_switch_attaches_nothing():
    stream = _stream(256, lambda i: (i * 7) % 23 + 1000)
    _, op, g = _run_reduce(stream, compact=False)
    assert op._compactor is None and op._cstats is None
    for o in g._operators:
        assert o._compactor is None
        for rep in o.replicas:
            em = rep.emitter
            if em is not None:
                assert getattr(em, "_compactor", None) is None
    assert "Key_compaction" not in op.dump_stats()
    assert "compaction" not in g.stats()["Shard"]["per_op"][op.name]
    # off-path budget: the disabled stats read is one attribute check —
    # micro-assert it stays orders of magnitude under a summary build
    t0 = time.perf_counter()
    for _ in range(10_000):
        op._compactor is not None
    per_call = (time.perf_counter() - t0) / 10_000
    assert per_call < 1e-6


def test_ffat_compacted_keys_require_plane():
    """withCompactedKeys under WF_TPU_KEY_COMPACTION=0 fails loudly at
    the first batch with the declare-withMaxKeys hint."""
    src = (wf.Source_Builder(
        lambda: iter([{"key": np.int32(5), "v": np.float32(1.0)}] * 64))
        .withOutputBatchSize(32).withName("src").build())
    op = (wf.Ffat_WindowsTPU_Builder(lambda t: t["v"],
                                     lambda a, b: a + b)
          .withCBWindows(8, 4).withKeyBy(lambda t: t["key"])
          .withCompactedKeys().withName("w").build())
    g = wf.PipeGraph("kc_kill", wf.ExecutionMode.DEFAULT,
                     config=_cfg(False))
    g.add_source(src).add(op).add_sink(_sink([]))
    with pytest.raises(WindFlowError, match="withMaxKeys"):
        g.run()


# ---------------------------------------------------------------------------
# preflight: WF404 advice + the WF402 compacted-mesh extension
# ---------------------------------------------------------------------------

def test_preflight_wf404_bounded_without_monoid():
    def graph(declare):
        src = (wf.Source_Builder(lambda: iter([{"key": np.int32(1),
                                                "v": np.float32(1.0)}]))
               .withOutputBatchSize(8)
               .withRecordSpec({"key": np.int32(0),
                                "v": np.float32(0.0)})
               .withName("src").build())
        b = (wf.ReduceTPU_Builder(
                lambda a, b: {"key": a["key"], "v": a["v"] + b["v"]})
             .withKeyBy(lambda t: t["key"]).withMaxKeys(8)
             .withName("red"))
        if declare:
            b = b.withSumCombiner()
        g = wf.PipeGraph("kc_wf404", wf.ExecutionMode.DEFAULT,
                         config=_cfg(True))
        g.add_source(src).add(b.build()).add_sink(_sink([]))
        return g

    assert any(d.code == "WF404" for d in graph(False).check())
    # declared monoid: the advice disappears
    assert not any(d.code == "WF404" for d in graph(True).check())


def test_preflight_wf405_monoid_comb_divergence():
    """WF405: the declared kind REPLACES the combiner on the dense/
    compacted stages, so a combiner that provably diverges from it
    leafwise must be flagged — with compaction default-on, the natural
    ``{"key": a["key"], ...}`` idiom under a declared "sum" silently
    emits key*count for every admitted key (found live by the e2e
    verify harness)."""
    def graph(comb, monoid):
        src = (wf.Source_Builder(lambda: iter([{"key": np.int32(1),
                                                "v": np.float32(1.0)}]))
               .withOutputBatchSize(8)
               .withRecordSpec({"key": np.int32(0),
                                "v": np.float32(0.0)})
               .withName("src").build())
        op = (wf.ReduceTPU_Builder(comb)
              .withKeyBy(lambda t: t["key"])
              .withMonoidCombiner(monoid).withName("red").build())
        g = wf.PipeGraph("kc_wf405", wf.ExecutionMode.DEFAULT,
                         config=_cfg(True))
        g.add_source(src).add(op).add_sink(_sink([]))
        return g

    def codes(g):
        return [d.code for d in g.check()]

    # key passthrough under "sum": the dense scatter ADDS equal keys
    d = [x for x in graph(
        lambda a, b: {"key": a["key"], "v": a["v"] + b["v"]},
        "sum").check() if x.code == "WF405"]
    assert len(d) == 1 and "'key'" in d[0].message
    # same passthrough under idempotent "max" is the blessed idiom
    assert "WF405" not in codes(graph(
        lambda a, b: {"key": a["key"], "v": jnp.maximum(a["v"], b["v"])},
        "max"))
    # recognized monoid primitive of the WRONG kind on a value leaf
    assert "WF405" in codes(graph(
        lambda a, b: {"key": a["key"] + b["key"],
                      "v": jnp.maximum(a["v"], b["v"])}, "sum"))
    # fully matching leafwise combiners stay silent
    assert "WF405" not in codes(graph(
        lambda a, b: {"key": jnp.maximum(a["key"], b["key"]),
                      "v": jnp.maximum(a["v"], b["v"])}, "max"))
    assert "WF405" not in codes(graph(
        lambda a, b: {"key": a["key"] + b["key"], "v": a["v"] + b["v"]},
        "sum"))
    # inconclusive structure (where-based max) never false-positives
    assert "WF405" not in codes(graph(
        lambda a, b: {"key": a["key"],
                      "v": jnp.where(a["v"] > b["v"], a["v"], b["v"])},
        "max"))
    # key copied into a VALUE leaf is not the blessed idiom: output 'v'
    # diverges under the declared max even though the SOURCE is the key
    src = (wf.Source_Builder(lambda: iter([{"key": np.int32(1),
                                            "v": np.int32(1)}]))
           .withOutputBatchSize(8)
           .withRecordSpec({"key": np.int32(0), "v": np.int32(0)})
           .withName("src").build())
    op = (wf.ReduceTPU_Builder(
            lambda a, b: {"key": jnp.maximum(a["key"], b["key"]),
                          "v": a["key"]})
          .withKeyBy(lambda t: t["key"])
          .withMonoidCombiner("max").withName("red").build())
    g = wf.PipeGraph("kc_wf405_xleaf", wf.ExecutionMode.DEFAULT,
                     config=_cfg(True))
    g.add_source(src).add(op).add_sink(_sink([]))
    d = [x for x in g.check() if x.code == "WF405"]
    assert len(d) == 1 and "'v'" in d[0].message


# ---------------------------------------------------------------------------
# KeyCompactor unit contracts: reseed cost bound + reserved-key counter
# ---------------------------------------------------------------------------

def test_reseed_one_estimation_pass():
    """Eviction during one reseed pays ONE sketch-estimation pass over
    the residents (coldest-first walk), not one full rescan per
    admitted candidate — the O(slots^2) stall this pins down ran
    inline on the consumer step path."""
    from windflow_tpu.parallel.compaction import KeyCompactor

    class Sketch:
        def __init__(self):
            self.calls = 0
            # resident coldness: key k has weight k (1..4 resident)
            self.hot = [(100 + i, 1000 - i) for i in range(4)]

        def hot_candidates(self, limit):
            return self.hot[:limit]

        def _estimate(self, k):
            self.calls += 1
            return int(k)

    comp = KeyCompactor(4, reseed_every=1, name="reseed_cost")
    comp.observe(np.arange(1, 5, dtype=np.int64))   # fill: keys 1..4
    sk = Sketch()
    comp.bind_sketch(sk)
    comp.reseed()
    # all four hot candidates (est ~1000) clear 2x vs residents 1..4
    assert comp.churn == 4
    assert set(comp._key_slot) == {100, 101, 102, 103}
    # ONE pass over the 4 residents, not 4 candidates x 4 residents
    assert sk.calls == 4


def test_packed_min_liveness_at_ts_floor():
    """Packed "min" scatter: the ts column rides NEGATED with identity
    I64MAX, and -(I64MIN+1) == I64MAX — a lane ts at the int64 floor
    must not read its row back as dead (record silently dropped vs the
    sorted path's bit-identical contract)."""
    from windflow_tpu.parallel import compaction
    cap, T = 8, 4
    body = compaction.make_compacted_reduce(
        cap, T, "min",
        lambda a, b: {"v": jnp.minimum(a["v"], b["v"])},
        None, None, True)
    i64min = np.iinfo(np.int64).min
    keys = jnp.asarray([0, 1, 2, 3, 0, 1, 2, 3], jnp.int32)
    payload = {"v": jnp.asarray(np.arange(8), jnp.float32)}
    valid = jnp.ones(cap, bool)
    for floor_ts in (i64min, i64min + 1):
        ts = jnp.full(cap, floor_ts, jnp.int64)
        out_p, out_ts, out_valid, _ = body(keys, payload, ts, valid,
                                           compaction.cstats_init())
        assert int(jnp.sum(out_valid)) == 4
        np.testing.assert_allclose(
            np.asarray(out_p["v"])[:4], [0.0, 1.0, 2.0, 3.0])


def test_observe_one_lock_free_on_full_table():
    """A full evictable table must not serialize the per-tuple emit
    path on the compactor lock: cold keys are counted (full_rejects)
    without admission, and a held lock cannot block the read."""
    from windflow_tpu.parallel.compaction import KEY_SENTINEL, KeyCompactor
    comp = KeyCompactor(2, name="full_fast")
    comp.observe(np.asarray([1, 2], np.int64))
    assert not comp._free
    with comp._lock:           # would deadlock if the path locked
        comp.observe_one(99)
        comp.observe_one(int(KEY_SENTINEL))
    assert comp.slot_of(99) is None
    s = comp.summary()
    assert s["full_rejects"] == 1 and s["sentinel_rejects"] == 1


def test_sentinel_key_counted_not_silent():
    """A real key equal to the INT32_MAX table sentinel is never
    admitted, and the encounter is COUNTED (sentinel_rejects) instead
    of vanishing into generic overflow."""
    from windflow_tpu.parallel.compaction import KEY_SENTINEL, KeyCompactor
    comp = KeyCompactor(4, name="sentinel")
    comp.observe(np.asarray([int(KEY_SENTINEL), 7], np.int64))
    assert comp.slot_of(7) is not None
    assert comp.slot_of(int(KEY_SENTINEL)) is None
    assert comp.summary()["sentinel_rejects"] == 1
