"""Mesh execution through the public graph API (VERDICT r1 item 2): with
``Config.mesh`` set, staging emitters lay batches out data-sharded and
FfatWindowsTPU / ReduceTPU compile their sharded variants inside a normal
``PipeGraph.run()`` — the multi-chip path is no longer a standalone layer.
Runs on the virtual 8-device CPU mesh (conftest)."""

import dataclasses

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

import windflow_tpu as wf
from windflow_tpu.basic import Config
from windflow_tpu.parallel.mesh import KEY_AXIS, make_mesh

N_KEYS = 4
LENGTH = 384
WIN, SLIDE = 16, 4


def stream():
    return [{"key": i % N_KEYS, "value": i, "ts": i * 1000}
            for i in range(LENGTH)]


def oracle_cb():
    per_key = {}
    for t in stream():
        per_key.setdefault(t["key"], []).append(t["value"])
    count, total = 0, 0
    for vals in per_key.values():
        w = 0
        while w * SLIDE < len(vals):
            count += 1
            total += sum(vals[w * SLIDE: w * SLIDE + WIN])
            w += 1
    return count, total


def _mesh_cfg(data=2):
    return dataclasses.replace(Config(), mesh=make_mesh(8, data=data))


def test_ffat_tpu_cb_on_mesh():
    exp = oracle_cb()
    acc = {"count": 0, "total": 0}

    def on_result(r):
        if r is not None:
            acc["count"] += 1
            acc["total"] += int(r["value"])

    src = (wf.Source_Builder(lambda: iter(stream()))
           .withOutputBatchSize(64).build())
    op = (wf.Ffat_WindowsTPU_Builder(lambda t: t["value"],
                                     lambda a, b: a + b)
          .withCBWindows(WIN, SLIDE)
          .withKeyBy(lambda t: t["key"])
          .withMaxKeys(N_KEYS).build())
    snk = wf.Sink_Builder(on_result).build()
    g = wf.PipeGraph("ffat_mesh", wf.ExecutionMode.DEFAULT,
                     config=_mesh_cfg())
    g.add_source(src).add(wf.MapTPU_Builder(lambda t: t).build()) \
        .add(op).add_sink(snk)
    g.run()

    assert (acc["count"], acc["total"]) == exp
    # the window state must actually live key-sharded on the mesh
    assert op._states[0]["cur"].sharding.spec == P(KEY_AXIS)


def _run_cb_on_mesh(records, batch=64, n_keys=8):
    """The count-window sum of ``records`` on the (data=2, key=4) mesh:
    rows ``{(key, wid): value}``, the operator and the graph."""
    got = {}
    src = (wf.Source_Builder(lambda: iter(records))
           .withOutputBatchSize(batch).build())
    op = (wf.Ffat_WindowsTPU_Builder(lambda t: t["value"],
                                     lambda a, b: a + b)
          .withName("cb").withCBWindows(WIN, SLIDE)
          .withKeyBy(lambda t: t["key"]).withMaxKeys(n_keys).build())
    snk = wf.Sink_Builder(
        lambda r: got.__setitem__((r["key"], r["wid"]), r["value"])
        if r is not None else None).build()
    # no punctuation cuts a batch short: the counts below are per batch
    g = wf.PipeGraph("ffat_mesh_own", wf.ExecutionMode.DEFAULT,
                     config=dataclasses.replace(
                         _mesh_cfg(), punctuation_interval_usec=10 ** 12))
    g.add_source(src).add(wf.MapTPU_Builder(lambda t: t).build()) \
        .add(op).add_sink(snk)
    g.run()
    return got, op, g


def _cb_oracle(records):
    per_key, exp = {}, {}
    for t in records:
        per_key.setdefault(t["key"], []).append(t["value"])
    for k, vals in per_key.items():
        w = 0
        while w * SLIDE < len(vals):
            exp[(k, w)] = sum(vals[w * SLIDE: w * SLIDE + WIN])
            w += 1
    return exp


@pytest.mark.parametrize("case,wide", [("uniform", 0), ("one_shard", 6)])
def test_cb_on_mesh_counts_its_many_round_steps(monkeypatch, case, wide):
    """A key shard's count-window step runs over the lanes it owns, a
    quarter of a 64-tuple batch on four key shards (``CB_step_lanes``
    16, ``step_cap`` on its ``wf.dispatch``); a batch whose keys all sit
    on one shard takes that shard four rounds, a step counted in
    ``CB_wide_steps`` (six full batches here), with the oracle's rows
    either way."""
    from windflow_tpu.monitoring.openmetrics import (parse_exposition,
                                                     render_openmetrics)
    import windflow_tpu.ops.tpu as tpu_mod
    notes, real = [], tpu_mod.flightrec.span

    class Spy:
        def __init__(self, name, kw):
            self.kw, self.inner = dict(kw, name=name), real(name, **kw)

        def __enter__(self):
            self.sp = self.inner.__enter__()
            return self

        def note(self, **kw):
            self.kw.update(kw)
            return self.sp.note(**kw)

        def __exit__(self, *a):
            notes.append(self.kw)
            return self.inner.__exit__(*a)

    monkeypatch.setattr(tpu_mod.flightrec, "span",
                        lambda name, **kw: Spy(name, kw))
    keys = (lambda i: i % 8) if case == "uniform" else (lambda i: i % 2)
    records = [{"key": keys(i), "value": i} for i in range(LENGTH)]
    got, op, g = _run_cb_on_mesh(records)
    assert got == _cb_oracle(records)
    st = next(o for o in g.stats()["Operators"]
              if o["Operator_name"] == "cb")
    assert st["CB_step_lanes"] == 16 and st["CB_wide_steps"] == wide
    assert op._states[0]["n_wide"].sharding.spec == P(KEY_AXIS)
    caps = [kw["step_cap"] for kw in notes
            if kw["name"] == "wf.dispatch" and kw.get("op") == "cb"]
    assert caps and set(caps) == {16}
    assert not [kw for kw in notes if kw.get("op") != "cb"
                and "step_cap" in kw]
    fams = parse_exposition(render_openmetrics(g.stats()))
    for fam, value in (("wf_operator_cb_wide_steps_total", wide),
                       ("wf_operator_cb_step_lanes", 16)):
        assert [(s[1]["operator"], s[2]) for s in fams[fam]["samples"]] \
            == [("cb", value)]


@pytest.mark.parametrize("blob_has_it", [True, False])
def test_cb_many_round_count_survives_snapshot_and_restore(blob_has_it):
    """The counter lanes ride the checkpoint blob; a blob from before
    them (or from one chip) restores as 0, and a re-bucketing onto
    another key axis keeps the total."""
    from windflow_tpu.durability import rebucket
    records = [{"key": i % 2, "value": i} for i in range(LENGTH)]
    _, op, _ = _run_cb_on_mesh(records)
    assert op.dump_stats()["CB_wide_steps"] == 6
    blob = op.snapshot_state()
    assert blob["states"][0]["n_wide"].tolist() == [6, 0, 0, 0]
    if not blob_has_it:
        del blob["states"][0]["n_wide"]
    fresh = (wf.Ffat_WindowsTPU_Builder(lambda t: t["value"],
                                        lambda a, b: a + b)
             .withName("cb").withCBWindows(WIN, SLIDE)
             .withKeyBy(lambda t: t["key"]).withMaxKeys(8).build())
    fresh.config, fresh.mesh = op.config, op.mesh
    fresh.restore_state(blob)
    assert fresh._states[0]["n_wide"].sharding.spec == P(KEY_AXIS)
    assert fresh.dump_stats()["CB_wide_steps"] == (6 if blob_has_it else 0)
    assert fresh.dump_stats()["CB_step_lanes"] == 16
    # four key shards -> two: the total in lane 0; off the mesh: gone
    old, two = {"data": 2, "key": 4}, {"data": 4, "key": 2}
    st2 = rebucket.rebucket_blob(op, blob, 1, 1, old, two)["states"][0]
    st1 = rebucket.rebucket_blob(op, blob, 1, 1, old, None)["states"][0]
    assert "n_wide" not in st1
    if blob_has_it:
        assert st2["n_wide"].tolist() == [6, 0]
    else:
        assert "n_wide" not in st2


@pytest.mark.parametrize("form,batch", [("generic", 64), ("narrow", 8),
                                        ("wide", 64)])
def test_ffat_tpu_tb_on_mesh(monkeypatch, form, batch):
    """Time-based FFAT windows through the mesh path (VERDICT r2 item 2):
    key-sharded pane rings with per-shard clocks, watermark frontier
    replicated, results exact vs the host oracle.  With a declared sum
    past the contraction's constant each key shard scatters its batch
    under ``shard_map``: into the panes it spans (8 tuples: 3 panes) or,
    past ``NARROW_PLACE_PANES`` (64 tuples: 16 panes), into its whole
    ring, counted a shard."""
    from windflow_tpu.windows import ffat_kernels
    monkeypatch.setattr(ffat_kernels, "DENSE_PLACE_MAX_CELLS", 0)
    TWIN, TSLIDE = 16_000, 4_000
    per_key = {}
    for t in stream():
        per_key.setdefault(t["key"], []).append((t["ts"], t["value"]))
    exp = {}
    for k, pts in per_key.items():
        wids = set()
        for ts, _ in pts:
            last = ts // TSLIDE
            first = max(0, -(-(ts - TWIN + 1) // TSLIDE))
            wids.update(range(first, last + 1))
        for w in wids:
            vals = [v for ts, v in pts
                    if w * TSLIDE <= ts < w * TSLIDE + TWIN]
            if vals:
                exp[(k, w)] = sum(vals)

    got = {}
    src = (wf.Source_Builder(lambda: iter(stream()))
           .withTimestampExtractor(lambda t: t["ts"])
           .withOutputBatchSize(batch).build())
    op = (wf.Ffat_WindowsTPU_Builder(lambda t: t["value"],
                                     lambda a, b: a + b)
          .withTBWindows(TWIN, TSLIDE)
          .withKeyBy(lambda t: t["key"])
          .withMaxKeys(N_KEYS))
    op = (op if form == "generic" else op.withSumCombiner()).build()
    snk = wf.Sink_Builder(
        lambda r: got.__setitem__((r["key"], r["wid"]), r["value"])
        if r is not None else None).build()
    g = wf.PipeGraph("ffat_mesh_tb", wf.ExecutionMode.DEFAULT,
                     wf.TimePolicy.EVENT, config=_mesh_cfg())
    g.add_source(src).add(op).add_sink(snk)
    g.run()

    assert got == exp
    # pane rings and per-shard clocks must actually live key-sharded
    assert op._states[0]["cells"].sharding.spec == P(KEY_AXIS)
    assert op._states[0]["base"].sharding.spec == P(KEY_AXIS)
    st = op.dump_stats()
    assert st["Late_tuples_dropped"] == 0
    assert op._states[0]["n_wide"].sharding.spec == P(KEY_AXIS)
    if form == "generic":
        assert "TB_placement" not in st
    else:
        assert st["TB_placement"] == "scatter"
        assert (st["TB_wide_placements"] > 0) == (form == "wide")


def test_keyed_reduce_tpu_on_mesh_fold():
    """Generic (all_gather + fold) cross-chip combine: payload lanes keep
    their real values, so the record's key field survives."""
    acc = {}
    src = (wf.Source_Builder(lambda: iter(stream()))
           .withOutputBatchSize(64).build())
    op = (wf.ReduceTPU_Builder(
            lambda a, b: {"key": b["key"], "value": a["value"] + b["value"],
                          "ts": b["ts"]})
          .withKeyBy(lambda t: t["key"]).withMaxKeys(N_KEYS).build())
    snk = wf.Sink_Builder(
        lambda r: acc.__setitem__(r["key"], acc.get(r["key"], 0)
                                  + int(r["value"]))
        if r is not None else None).build()
    g = wf.PipeGraph("red_mesh", config=_mesh_cfg())
    g.add_source(src).add(op).add_sink(snk)
    g.run()

    per_key = {}
    for t in stream():
        per_key[t["key"]] = per_key.get(t["key"], 0) + t["value"]
    assert acc == per_key


def test_keyed_reduce_tpu_on_mesh_pmax():
    """withMonoidCombiner("max"): the cross-chip combine rides ONE pmax
    collective.  Strictly negative values (a zero-identity bug would win
    every max) and a real key lane in the record — max(k, k) == k across
    chips, so the key survives the collective (unlike psum's
    all-leaves-summed contract)."""
    got = {}
    src = (wf.Source_Builder(
            lambda: iter({"key": i % N_KEYS, "value": -1.0 - (i % 97)}
                         for i in range(LENGTH)))
           .withOutputBatchSize(64).build())
    op = (wf.ReduceTPU_Builder(
            lambda a, b: {"key": jnp.maximum(a["key"], b["key"]),
                          "value": jnp.maximum(a["value"], b["value"])})
          .withKeyBy(lambda t: t["key"]).withMaxKeys(N_KEYS)
          .withMonoidCombiner("max").build())
    snk = wf.Sink_Builder(
        lambda r: got.__setitem__(
            int(r["key"]), max(got.get(int(r["key"]), -1e30),
                               float(r["value"])))
        if r is not None else None).build()
    g = wf.PipeGraph("red_mesh_pmax", config=_mesh_cfg())
    g.add_source(src).add(op).add_sink(snk)
    g.run()

    per_key = {}
    for i in range(LENGTH):
        k, v = i % N_KEYS, -1.0 - (i % 97)
        per_key[k] = max(per_key.get(k, -1e30), v)
    assert got == per_key


def test_keyed_reduce_tpu_on_mesh_psum():
    """psum cross-chip combine: every payload lane must be zero-absorbing
    sum-like, so the key rides only the extractor (derived from the raw
    value lane, pre-combine); output rows arrive in dense key order.

    Pins the DATA-SHARDED ingest explicitly: a declared dense mesh
    reduce defaults to key-aligned ingest since the pallas round
    (mesh.mark_aligned_ingest), whose column-fill batching changes the
    per-batch record cadence this test counts — the aligned twin lives
    in tests/test_pallas_kernels.py."""
    got = []
    src = (wf.Source_Builder(lambda: iter({"value": i}
                                          for i in range(LENGTH)))
           .withOutputBatchSize(64).build())
    op = (wf.ReduceTPU_Builder(lambda a, b: {"value": a["value"] + b["value"]})
          .withKeyBy(lambda t: t["value"] % N_KEYS)
          .withMaxKeys(N_KEYS).withSumCombiner().build())
    snk = wf.Sink_Builder(
        lambda r: got.append(int(r["value"])) if r is not None else None) \
        .build()
    g = wf.PipeGraph("red_mesh_psum",
                     config=dataclasses.replace(
                         _mesh_cfg(), key_aligned_ingest=False))
    g.add_source(src).add(op).add_sink(snk)
    g.run()

    # every 64-tuple batch contains all 4 keys, so each batch yields exactly
    # 4 records compacted in dense-key order 0..3
    assert len(got) == (LENGTH // 64) * N_KEYS
    per_key = {k: 0 for k in range(N_KEYS)}
    for j, v in enumerate(got):
        per_key[j % N_KEYS] += v
    expect = {k: sum(i for i in range(LENGTH) if i % N_KEYS == k)
              for k in range(N_KEYS)}
    assert per_key == expect


def test_global_reduce_tpu_on_mesh():
    got = []
    src = (wf.Source_Builder(lambda: iter({"v": float(i)}
                                          for i in range(256)))
           .withOutputBatchSize(64).build())
    op = wf.ReduceTPU_Builder(lambda a, b: {"v": a["v"] + b["v"]}).build()
    snk = wf.Sink_Builder(
        lambda r: got.append(r["v"]) if r is not None else None).build()
    g = wf.PipeGraph("gred_mesh", config=_mesh_cfg(data=4))
    g.add_source(src).add(op).add_sink(snk)
    g.run()
    assert sum(got) == sum(range(256))
    assert len(got) == 4  # one combined record per staged batch


def test_mesh_requires_divisible_batch():
    import pytest
    cfg = _mesh_cfg()
    src = (wf.Source_Builder(lambda: iter(stream()))
           .withOutputBatchSize(60).build())  # 60 % 8 devices != 0
    g = wf.PipeGraph("bad", config=cfg)
    g.add_source(src) \
        .add(wf.MapTPU_Builder(lambda t: t).build()) \
        .add_sink(wf.Sink_Builder(lambda r: None).build())
    with pytest.raises(wf.WindFlowError, match="not divisible"):
        g.run()


def test_keyed_reduce_tpu_on_mesh_arbitrary_keys():
    """Keyed mesh Reduce WITHOUT withMaxKeys: keys from the full int32
    range (negative, huge) hash-shard to their owner chip over an
    all_to_all; nothing is dropped and per-key totals are exact
    (VERDICT r2 item 5; reference reduce_gpu.hpp:227-258)."""
    import numpy as np
    rnd = np.random.default_rng(9)
    raw_keys = rnd.integers(-2**31, 2**31, 37).astype(np.int64)
    items = [{"key": int(raw_keys[i % len(raw_keys)]), "value": i}
             for i in range(LENGTH)]

    acc = {}
    src = (wf.Source_Builder(lambda: iter(items))
           .withOutputBatchSize(64).build())
    op = (wf.ReduceTPU_Builder(
            lambda a, b: {"key": b["key"], "value": a["value"] + b["value"]})
          .withKeyBy(lambda t: t["key"]).build())   # NO withMaxKeys
    snk = wf.Sink_Builder(
        lambda r: acc.__setitem__(int(r["key"]),
                                  acc.get(int(r["key"]), 0)
                                  + int(r["value"]))
        if r is not None else None).build()
    g = wf.PipeGraph("red_mesh_arb", config=_mesh_cfg())
    g.add_source(src).add(op).add_sink(snk)
    g.run()

    exp = {}
    for t in items:
        k = np.int32(t["key"] & 0xFFFFFFFF).item() \
            if t["key"] >= 2**31 else t["key"]
        exp[k] = exp.get(k, 0) + t["value"]
    assert acc == exp
    assert op.num_dropped_tuples() == 0


def test_mesh_arbitrary_keys_int32_max_not_dropped():
    """A genuine key of INT32_MAX must not be mistaken for the reduce's
    invalid-lane sentinel and silently dropped (the sort lane is int64 with
    an out-of-range sentinel)."""
    items = [{"key": 2**31 - 1, "value": i} for i in range(64)]
    acc = {}
    src = (wf.Source_Builder(lambda: iter(items))
           .withOutputBatchSize(64).build())
    op = (wf.ReduceTPU_Builder(
            lambda a, b: {"key": b["key"], "value": a["value"] + b["value"]})
          .withKeyBy(lambda t: t["key"]).build())
    snk = wf.Sink_Builder(
        lambda r: acc.__setitem__(int(r["key"]),
                                  acc.get(int(r["key"]), 0)
                                  + int(r["value"]))
        if r is not None else None).build()
    g = wf.PipeGraph("red_mesh_maxkey", config=_mesh_cfg())
    g.add_source(src).add(op).add_sink(snk)
    g.run()
    assert acc == {2**31 - 1: sum(range(64))}
    assert op.num_dropped_tuples() == 0


def test_mesh_long_stream_soak():
    """Long-stream soak of the mesh path (hundreds of staged batches
    through the sharded FFAT step): state rolls far past the ring length,
    counters stay exact, nothing leaks or drifts."""
    n = 12_800                      # 200 staged batches of 64
    acc = {"count": 0, "total": 0}
    src = (wf.Source_Builder(
            lambda: iter({"key": i % N_KEYS, "value": i, "ts": i * 1000}
                         for i in range(n)))
           .withOutputBatchSize(64).build())
    op = (wf.Ffat_WindowsTPU_Builder(lambda t: t["value"],
                                     lambda a, b: a + b)
          .withCBWindows(WIN, SLIDE).withKeyBy(lambda t: t["key"])
          .withMaxKeys(N_KEYS).build())
    snk = wf.Sink_Builder(
        lambda r: (acc.__setitem__("count", acc["count"] + 1),
                   acc.__setitem__("total", acc["total"] + int(r["value"])))
        if r is not None else None).build()
    g = wf.PipeGraph("mesh_soak", config=_mesh_cfg())
    g.add_source(src).add(op).add_sink(snk)
    g.run()

    per_key = {}
    for i in range(n):
        per_key.setdefault(i % N_KEYS, []).append(i)
    count = total = 0
    for vals in per_key.values():
        w = 0
        while w * SLIDE < len(vals):
            count += 1
            total += sum(vals[w * SLIDE: w * SLIDE + WIN])
            w += 1
    assert (acc["count"], acc["total"]) == (count, total)


def test_stateful_map_tpu_on_mesh_sharded_state():
    """Keyed stateful MapTPU on the mesh: the dense slot table is sharded
    along the key axis, lanes merge back with one psum, and per-key running
    sums stay exact across hundreds of batches."""
    import jax.numpy as jnp
    n = 1024
    acc = {}
    src = (wf.Source_Builder(lambda: iter({"key": i % 8, "value": float(i)}
                                          for i in range(n)))
           .withOutputBatchSize(64).build())
    sm = (wf.MapTPU_Builder(
            lambda t, s: ({"key": t["key"], "run": s + t["value"]},
                          s + t["value"]))
          .withInitialState(jnp.zeros((), jnp.float32))
          .withKeyBy(lambda t: t["key"]).withNumKeySlots(8)
          .withDenseKeys().build())
    snk = wf.Sink_Builder(
        lambda r: acc.__setitem__(int(r["key"]), float(r["run"]))
        if r is not None else None).build()
    g = wf.PipeGraph("mesh_stateful", config=_mesh_cfg())
    g.add_source(src).add(sm).add_sink(snk)
    g.run()
    exp = {k: sum(float(i) for i in range(n) if i % 8 == k)
           for k in range(8)}
    assert acc == exp
    assert sm._state.sharding.spec == P(KEY_AXIS)

    # interned (non-dense) variant with a filter
    kept = []
    src2 = (wf.Source_Builder(lambda: iter({"key": 100 + (i % 4),
                                            "value": i} for i in range(256)))
            .withOutputBatchSize(64).build())
    sf = (wf.FilterTPU_Builder(
            lambda t, s: ((s + 1) % 2 == 1, s + 1))   # keep every other
          .withInitialState(jnp.zeros((), jnp.int32))
          .withKeyBy(lambda t: t["key"]).withNumKeySlots(8).build())
    snk2 = wf.Sink_Builder(
        lambda r: kept.append(int(r["value"])) if r is not None else None) \
        .build()
    g2 = wf.PipeGraph("mesh_stateful_f", config=_mesh_cfg())
    g2.add_source(src2).add(sf).add_sink(snk2)
    g2.run()
    # per key, occurrences alternate keep/drop starting with keep
    exp2 = sorted(i for i in range(256) if (i // 4) % 2 == 0)
    assert sorted(kept) == exp2


def test_mesh_stateful_out_of_range_keys_dropped():
    """Dense keys outside [0, num_key_slots) must drop on the mesh exactly
    as on a single chip — no shard owns them, so no zeroed ghost records."""
    import jax.numpy as jnp
    got = []
    src = (wf.Source_Builder(
            lambda: iter({"key": (99 if i % 3 == 0 else i % 8),
                          "value": float(i)} for i in range(192)))
           .withOutputBatchSize(64).build())
    sm = (wf.MapTPU_Builder(
            lambda t, s: ({"key": t["key"], "run": s + t["value"]},
                          s + t["value"]))
          .withInitialState(jnp.zeros((), jnp.float32))
          .withKeyBy(lambda t: t["key"]).withNumKeySlots(8)
          .withDenseKeys().build())
    snk = wf.Sink_Builder(
        lambda r: got.append(int(r["key"])) if r is not None else None) \
        .build()
    g = wf.PipeGraph("mesh_oor", config=_mesh_cfg())
    g.add_source(src).add(sm).add_sink(snk)
    g.run()
    n_in_range = sum(1 for i in range(192) if i % 3 != 0)
    assert len(got) == n_in_range
    assert all(0 <= k < 8 for k in got)
