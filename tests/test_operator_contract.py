"""What an operator says it is, and that every asking module hears it.

Five questions used to be ``isinstance`` ladders in the modules that ask
them (fusion/executor.py, analysis/fusion.py, megastep.py,
graph/pipegraph.py, analysis/preflight.py + durability/rebucket.py);
they are declarations on ``Operator`` now (ops/base.py: ``chain_role``,
``inlines_prelude()``, ``megastep_tail()``, ``reports_fire_freshness``,
``snapshot_kind`` / ``snapshot_shapeless``).  ``EXPECTED`` is the answer
of the ladders of commit 6b85ddc for every device operator kind the
builders produce, written out: a case that fails here is an answer that
changed.  ``Toy`` is an operator the package has never heard of: it gets
the safe answer from every asking module.
"""

import dataclasses

import jax.numpy as jnp
import pytest

import windflow_tpu as wf
from windflow_tpu.batch import DeviceBatch
from windflow_tpu.ops.base import Operator
from windflow_tpu.ops.chained import fuse
from windflow_tpu.ops.tpu import _TPUReplica

CAP = 64
KEYS = 8


def key(t):
    return t["key"]


def _add(a, b):
    return {"key": jnp.maximum(a["key"], b["key"]), "v": a["v"] + b["v"]}


def _stateful(builder, dense):
    b = (builder.withKeyBy(key).withInitialState({"acc": jnp.float32(0)})
         .withNumKeySlots(KEYS))
    return (b.withDenseKeys() if dense else b).build()


def _stateful_map(dense):
    def f(rec, st):
        st = {"acc": st["acc"] + rec["v"]}
        return {"key": rec["key"], "v": st["acc"]}, st
    return _stateful(wf.MapTPU_Builder(f), dense)


def _stateful_filter(dense):
    def f(rec, st):
        st = {"acc": st["acc"] + rec["v"]}
        return st["acc"] > 1.0, st
    return _stateful(wf.FilterTPU_Builder(f), dense)


def _ffat(**kw):
    b = wf.Ffat_WindowsTPU_Builder(lambda t: t["v"], lambda a, b: a + b)
    b = b.withKeyBy(key)
    if kw.get("tb"):
        b = b.withTBWindows(1000, 500)
    else:
        b = b.withCBWindows(8, 4)
    b = b.withCompactedKeys() if kw.get("compacted") else b.withMaxKeys(KEYS)
    return b.withParallelism(kw.get("parallelism", 1)).build()


def _join(pairs):
    if pairs:
        b = (wf.Interval_JoinTPU_Builder(lambda b, p, ts: p["v"] - b["v"])
             .withBoundaries(5, 300).withMaxKeys(4096)
             .withProbeCapacity(32))
    else:
        b = (wf.Interval_JoinTPU_Builder(lambda b, p, ts: p["v"],
                                         lambda a, b: a + b)
             .withIntervalLength(lambda r: r["len"]).withBuildCapacity(32))
    return (b.withBuildSide(lambda r: r["b"] == 1).withKeyBy(key)
            .withOutputCapacity(CAP).build())


def _compacted(op):
    """``op`` as the graph build leaves it under ``Config.key_compaction``:
    behind a KeyCompactor (parallel/compaction.attach_compaction)."""
    cfg = dataclasses.replace(wf.default_config, key_compaction=True)
    g = wf.PipeGraph("contract_compacted", config=cfg)
    g.add_source(wf.Source_Builder(lambda: iter(()))
                 .withOutputBatchSize(CAP).build()).add(op).add_sink(
        wf.Sink_Builder(lambda r: None).build())
    g.run()
    assert op._compactor is not None
    return op


#: every device operator kind the builders can produce
KINDS = {
    "map": lambda: wf.MapTPU_Builder(lambda t: t).build(),
    "filter": lambda: wf.FilterTPU_Builder(lambda t: t["v"] > 0).build(),
    "chained": lambda: fuse(KINDS["map"](), KINDS["filter"]()),
    "reduce_sorted": lambda: wf.ReduceTPU_Builder(_add).withKeyBy(key)
    .build(),
    "reduce_dense": lambda: wf.ReduceTPU_Builder(_add).withKeyBy(key)
    .withMaxKeys(KEYS).withSumCombiner().build(),
    "reduce_global": lambda: wf.ReduceTPU_Builder(_add).build(),
    "reduce_compacted": lambda: _compacted(
        wf.ReduceTPU_Builder(_add).withKeyBy(key).withSumCombiner()
        .build()),
    "stateful_map_dense": lambda: _stateful_map(True),
    "stateful_map_interned": lambda: _stateful_map(False),
    "stateful_filter_dense": lambda: _stateful_filter(True),
    "stateful_filter_interned": lambda: _stateful_filter(False),
    "ffat_count": lambda: _ffat(),
    "ffat_time": lambda: _ffat(tb=True),
    "ffat_time_parallel": lambda: _ffat(tb=True, parallelism=2),
    "ffat_compacted_keys": lambda: _ffat(compacted=True),
    "session": lambda: wf.Session_WindowsTPU_Builder(
        lambda t: t["v"], lambda a, b: a + b).withGap(100).withKeyBy(key)
    .withMaxKeys(KEYS).build(),
    "interval_join": lambda: _join(False),
    "interval_join_pairs": lambda: _join(True),
    "count_ordered": lambda: wf.Ffat_WindowsTPU_Builder(
        lambda t: t["v"], lambda a, b: a + b).withCBWindows(8, 4)
    .withKeyBy(key).withMaxKeys(KEYS).withEventTimeOrder().build(),
    "rolling_aggregate": lambda: wf.Rolling_AggregateTPU_Builder(
        lambda t, ts: {"v": t["v"], "who": t["key"]}).withSum("v")
    .withDistinct("who", space=KEYS).withKeyBy(key).withMaxKeys(KEYS)
    .build(),
}

#: kind -> the parent's answers, a column a question (``QUESTIONS``).
#: ``tail`` is ``megastep.tail_kind``'s kind or a phrase of its reason;
#: ``snapshot`` the ``kind`` string its checkpoint blob carries.  The last
#: question, ``unknown_state``, the parent answered False for all of them.
COLUMNS = ("chain", "prelude", "tail", "freshness", "snapshot")
_ROWS = {
    "map": ("member", False, "unsupported tail operator MapTPU",
            False, None),
    "filter": ("member", False, "unsupported tail operator FilterTPU",
               False, None),
    "chained": ("member", False, "unsupported tail operator ChainedTPU",
                False, None),
    "reduce_sorted": ("tail", True, "reduce_sorted", False, "reduce_tpu"),
    "reduce_dense": ("tail", True, "reduce_dense", False, "reduce_tpu"),
    "reduce_global": ("tail", True, "reduce_sorted", False, "reduce_tpu"),
    "reduce_compacted": ("tail", True, "compacted key space", False,
                         "reduce_tpu"),
    "stateful_map_dense": ("tail", True, "stateful", False, "stateful_tpu"),
    "stateful_map_interned": ("tail", False, "host-interning stateful",
                              False, "stateful_tpu"),
    "stateful_filter_dense": ("tail", True, "stateful", False,
                              "stateful_tpu"),
    "stateful_filter_interned": ("tail", False, "host-interning stateful",
                                 False, "stateful_tpu"),
    "ffat_count": ("tail", True, "ffat_cb", True, "ffat_tpu"),
    "ffat_time": ("tail", True, "ffat_tb", True, "ffat_tpu"),
    "ffat_time_parallel": ("tail", True, "parallel window state", True,
                           "ffat_tpu"),
    # unbuilt: the graph build has attached no compactor yet
    "ffat_compacted_keys": ("tail", False, "ffat_cb", True, "ffat_tpu"),
    "session": ("tail", True, "session windows", True, "session_tpu"),
    "interval_join": ("tail", True, "interval join", True,
                      "interval_join_tpu"),
    "interval_join_pairs": ("tail", True, "interval join", True,
                            "interval_join_pairs_tpu"),
    # PR 44: the count window in event-time order, on the same shell
    "count_ordered": ("tail", True, "count windows in event-time order",
                      True, "count_ordered_tpu"),
    # PR 49: the rolling aggregate with declared leaves, on the same
    # shell; no window closes in it, so no freshness gauge
    "rolling_aggregate": ("tail", True, "rolling aggregate", False,
                          "rolling_aggregate_tpu"),
}
EXPECTED = {k: dict(zip(COLUMNS, row), unknown_state=False)
            for k, row in _ROWS.items()}
TAIL_KINDS = {"ffat_cb", "ffat_tb", "reduce_dense", "reduce_sorted",
              "stateful"}


def _source(n=0):
    rows = [{"key": i % KEYS, "v": float(i)} for i in range(n)]
    return (wf.Source_Builder(lambda: iter(rows)).withOutputBatchSize(CAP)
            .withName("src").build())


def _behind_a_map(op):
    """An unbuilt graph ``src -> map -> op -> sink`` and its fused
    segments as the executor plans them."""
    from windflow_tpu.fusion.executor import plan_segments
    g = wf.PipeGraph("contract_chain")
    head = wf.MapTPU_Builder(lambda t: t).withName("head").build()
    g.add_source(_source()).add(head).add(op).add_sink(
        wf.Sink_Builder(lambda r: None).build())
    return g, plan_segments(g)


# -- the questions, one asking module each -----------------------------------

def ask_chain(op):
    """analysis/fusion.py + fusion/executor.py: behind a map the operator
    is linked into the chain; ahead of a map only a member is fused past."""
    from windflow_tpu.analysis.fusion import fusible_chains
    g = wf.PipeGraph("contract_role")
    after = wf.MapTPU_Builder(lambda t: t).withName("after").build()
    g.add_source(_source()).add(op).add(after).add_sink(
        wf.Sink_Builder(lambda r: None).build())
    past = any(op in c["ops"] and after in c["ops"]
               for c in fusible_chains(g))
    assert op.chain_role == ("member" if past else "tail")
    return op.chain_role


def ask_prelude(op):
    """fusion/executor.py: behind a map (where the edge links at all) a
    tail hosts the segment exactly where it inlines a prelude; a member
    hosts an all-stateless one and has no program to inline into."""
    _, segments = _behind_a_map(op)
    hosts = any(s["members"][-1] is op for s in segments)
    if op.parallelism == 1:
        assert hosts == (op.chain_role == "member" or op.inlines_prelude())
    return op.inlines_prelude()


def ask_tail(op):
    from windflow_tpu.megastep import tail_kind
    kind, why = tail_kind(op)
    assert (kind is None) != (why is None)
    return kind, why


def ask_freshness(op):
    return op.reports_fire_freshness


def ask_snapshot(op):
    return op.snapshot_kind if type(op).snapshot_state \
        is not Operator.snapshot_state else None


def ask_unknown_state(op):
    from windflow_tpu.analysis.preflight import \
        _checkpoints_unrebucketable_state
    return _checkpoints_unrebucketable_state(op)


QUESTIONS = {"chain": ask_chain, "prelude": ask_prelude, "tail": ask_tail,
             "freshness": ask_freshness, "snapshot": ask_snapshot,
             "unknown_state": ask_unknown_state}


@pytest.mark.parametrize("question", sorted(QUESTIONS))
@pytest.mark.parametrize("kind", sorted(KINDS))
def test_the_answer_is_the_parents(kind, question):
    assert set(EXPECTED) == set(KINDS)
    got = QUESTIONS[question](KINDS[kind]())
    want = EXPECTED[kind][question]
    if question != "tail":
        assert got == want
    elif want in TAIL_KINDS:
        assert got == (want, None)
    else:
        assert got[0] is None and want in got[1]


# -- the checkpoint kinds -----------------------------------------------------

STATEFUL = sorted(k for k, e in EXPECTED.items() if e["snapshot"])
SHAPELESS = {"reduce_tpu", "session_tpu", "interval_join_tpu",
             "interval_join_pairs_tpu", "count_ordered_tpu",
             "rolling_aggregate_tpu"}


@pytest.mark.parametrize("kind", STATEFUL)
def test_a_blob_of_its_kind_rebuckets(kind):
    """At unchanged parallelism every blob comes back as it went; onto
    another shape the kinds whose state has no shard shape still do (what
    the three ``..._is_known_to_preflight_and_rebucket`` tests said), and
    the others go through their rule (tests/test_durability.py holds the
    rules to their results)."""
    from windflow_tpu.durability.rebucket import _RULES, rebucket_blob
    op = KINDS[kind]()
    blob = {"kind": op.snapshot_kind, "state": {}}
    for p in (1, 2):
        assert rebucket_blob(op, blob, p, p, None, None) is blob
    if op.snapshot_kind in SHAPELESS:
        assert op.snapshot_kind not in _RULES and op.snapshot_shapeless
        assert rebucket_blob(op, blob, 1, 2, None, None) is blob
    else:
        assert op.snapshot_kind in _RULES and not op.snapshot_shapeless


def test_the_host_reduce_states_its_kind():
    from windflow_tpu.analysis.preflight import \
        _checkpoints_unrebucketable_state
    from windflow_tpu.durability.rebucket import rebucket_blob
    red = wf.Reduce_Builder(lambda i, s: None, dict).withKeyBy(key).build()
    assert red.snapshot_kind == "reduce_host"
    assert not _checkpoints_unrebucketable_state(red)
    blob = {"kind": "reduce_host", "replicas": [{1: "a", 2: "b"}]}
    out = rebucket_blob(red, blob, 1, 3, None, None)
    assert out["kind"] == "reduce_host" and len(out["replicas"]) == 3
    assert {k: v for d in out["replicas"] for k, v in d.items()} \
        == {1: "a", 2: "b"}


@pytest.mark.parametrize("kind", ["session", "ffat_count"])
def test_a_snapshot_written_below_the_stated_kind_is_unknown(kind):
    """Identity is on the implementation of ``snapshot_state``: a
    subclass that overrides it keeps its base's ``snapshot_kind`` and is
    still a kind nobody has seen."""
    from windflow_tpu.analysis.preflight import \
        _checkpoints_unrebucketable_state
    op = KINDS[kind]()

    class Below(type(op)):
        def snapshot_state(self):
            return {"kind": "mine"}

    op.__class__ = Below
    assert op.snapshot_kind == EXPECTED[kind]["snapshot"]
    assert _checkpoints_unrebucketable_state(op)


# -- an operator the package has never heard of --------------------------------

class Toy(Operator):
    """A device operator that hands its batch on and checkpoints a count:
    nothing in the package names it, and it declares nothing."""

    replica_class = _TPUReplica

    def __init__(self, name="toy", key_extractor=None):
        super().__init__(name, 1, is_tpu=True, key_extractor=key_extractor)
        self.batches = 0

    def _step(self, batch):
        self.batches += 1
        return DeviceBatch(batch.payload, batch.ts, batch.valid,
                           watermark=batch.watermark, size=batch._size,
                           frontier=batch.frontier, ts_max=batch.ts_max,
                           ts_min=batch.ts_min)

    def snapshot_state(self):
        return {"kind": "toy", "batches": self.batches}

    def restore_state(self, blob):
        self.batches = blob["batches"]


def test_the_toy_ends_a_fused_chain_and_is_refused_a_prelude():
    from windflow_tpu.analysis.fusion import fusible_chains
    toy = Toy()
    assert toy.chain_role is None and not toy.inlines_prelude()
    g = wf.PipeGraph("contract_toy")
    maps = [wf.MapTPU_Builder(lambda t: t).withName(f"m{i}").build()
            for i in range(4)]
    g.add_source(_source()).add(maps[0]).add(maps[1]).add(toy) \
        .add(maps[2]).add(maps[3]).add_sink(
            wf.Sink_Builder(lambda r: None).build())
    chains = [[o.name for o in c["ops"]] for c in fusible_chains(g)]
    assert chains == [["m0", "m1"], ["m2", "m3"]]
    from windflow_tpu.fusion.executor import plan_segments
    assert [s["member_names"] for s in plan_segments(g)] == chains


def test_the_toy_tails_no_scan_and_is_told_why():
    from windflow_tpu.megastep import tail_kind
    kind, why = tail_kind(Toy())
    assert kind is None and why == "unsupported tail operator Toy"


def test_the_toy_runs_unfused_and_binds_no_gauge():
    """Through a whole graph: the maps around it run (the chain ends at
    it on both sides), its replica gets no latency ledger, and the window
    behind it does."""
    got = []
    toy = Toy()
    win = (wf.Ffat_WindowsTPU_Builder(lambda t: t["v"], lambda a, b: a + b)
           .withKeyBy(key).withCBWindows(4, 4).withMaxKeys(KEYS)
           .withName("win").build())
    cfg = dataclasses.replace(wf.default_config, latency_ledger=True,
                              flight_recorder=True)
    g = wf.PipeGraph("contract_toy_run", config=cfg)
    g.add_source(_source(4 * CAP)) \
        .add(wf.MapTPU_Builder(
            lambda t: {"key": t["key"], "v": t["v"] + 1}).withName("up")
            .build()) \
        .add(toy).add(win).add_sink(
            wf.Sink_Builder(lambda r: got.append(r) if r is not None
                            else None).build())
    g.run()
    assert toy.batches == 4 and len(got) == 4 * CAP // 4
    assert sum(r["value"] for r in got) \
        == sum(float(i) + 1 for i in range(4 * CAP))
    ops = {o["Operator_name"]: o for o in g.stats()["Operators"]}
    assert not any("Fused_into" in o for o in ops.values())
    assert g._latency is not None
    assert all(r.latency is None for r in toy.replicas)
    assert all(r.latency is g._latency for r in win.replicas)


def test_the_toy_checkpoints_a_kind_nobody_can_rebucket():
    from windflow_tpu.analysis.preflight import \
        _checkpoints_unrebucketable_state
    from windflow_tpu.durability.rebucket import RescaleError, rebucket_blob
    toy = Toy(key_extractor=key)
    assert toy.snapshot_kind is None and not toy.snapshot_shapeless
    assert _checkpoints_unrebucketable_state(toy)
    blob = toy.snapshot_state()
    assert rebucket_blob(toy, blob, 1, 1, None, None) is blob
    with pytest.raises(RescaleError, match="'toy' has no re-bucketing"):
        rebucket_blob(toy, blob, 1, 2, None, None)


def test_preflight_names_the_toy_on_a_mesh(tmp_path):
    from windflow_tpu.parallel.mesh import make_mesh
    cfg = dataclasses.replace(wf.default_config,
                              durability=str(tmp_path / "ck"),
                              mesh=make_mesh(2))
    g = wf.PipeGraph("contract_toy_mesh", config=cfg)
    g.add_source(_source()).add(Toy(key_extractor=key)).add_sink(
        wf.Sink_Builder(lambda r: None).build())
    assert any(d.code == "WF604" and "'toy'" in d.message
               for d in g.check())
