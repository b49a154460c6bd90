"""Pallas TPU kernels for the FFAT hot loop (windflow_tpu/kernels):
record-for-record A/B of the kernel-backed
programs against the ``WF_TPU_PALLAS=0`` lax path across the
window_cb / window_tb / dense-reduce / compacted families (including
TB ring regrow and CB EOS-flush edges), kernel-level bit-equality
against the lax compositions they replace, the zero-dispatch-delta pin
through the jit registry, chaos kill→restore→diff with the kernels on,
the WF607 forced-downgrade warnings, the off-path budget (the kill
switch builds NO kernels), and the key-aligned mesh ingest extension
to the sharded dense reduce / stateful paths (this PR's ROADMAP
item-4 satellite).

Tier-1 runs the kernels under the Pallas interpreter
(``interpret=True`` — the real kernel bodies, emulated on CPU);
Mosaic-compiled behavior is the same trace on a TPU backend."""

import dataclasses
import warnings
from collections import defaultdict

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import windflow_tpu as wf
from windflow_tpu import kernels as pk
from windflow_tpu.basic import Config, default_config
from windflow_tpu.monitoring.jit_registry import default_registry
from windflow_tpu.windows import ffat_kernels as fk
from windflow_tpu.windows.grouping import dense_rank, invert_perm, \
    order_and_hist


def _cfg(pallas, **kw):
    return dataclasses.replace(default_config, pallas_kernels=pallas,
                               **kw)


# ---------------------------------------------------------------------------
# gate resolution
# ---------------------------------------------------------------------------

def test_resolution_modes():
    """auto on the CPU backend selects the kernels under the
    interpreter (tier-1 executes the real bodies); "0" is the kill
    switch; forcing on CPU also interprets."""
    assert jax.default_backend() == "cpu"
    auto = pk.resolve_pallas(Config(pallas_kernels="auto"))
    assert auto is not None and auto.interpret
    assert pk.resolve_pallas(Config(pallas_kernels="0")) is None
    assert pk.resolve_pallas(Config(pallas_kernels=False)) is None
    forced = pk.resolve_pallas(Config(pallas_kernels="1"))
    assert forced is not None and forced.interpret
    assert pk.pallas_forced(Config(pallas_kernels="1"))
    assert not pk.pallas_forced(Config(pallas_kernels="auto"))


def test_kill_switch_builds_no_kernels():
    """Off-path budget: under WF_TPU_PALLAS=0 the step builders resolve
    once and build ZERO pallas_calls — the lax path verbatim."""
    before = pk.pallas_build_count()
    step = fk.make_ffat_step(64, 4, 4, 4, 1, lambda t: t["v"],
                             lambda a, b: a + b, lambda t: t["k"],
                             monoid="sum", pallas=None)
    state = fk.make_ffat_state(jnp.zeros((), jnp.int64), 4, 4)
    payload = {"k": jnp.arange(64, dtype=jnp.int32) % 4,
               "v": jnp.arange(64, dtype=jnp.int64)}
    jax.jit(step)(state, payload, jnp.arange(64, dtype=jnp.int64),
                  jnp.ones(64, bool))
    assert pk.pallas_build_count() == before
    # and the active path builds at least one per region
    step_p = fk.make_ffat_step(64, 4, 4, 4, 1, lambda t: t["v"],
                               lambda a, b: a + b, lambda t: t["k"],
                               monoid="sum",
                               pallas=pk.PallasMode(interpret=True))
    jax.jit(step_p)(state, payload, jnp.arange(64, dtype=jnp.int64),
                    jnp.ones(64, bool))
    assert pk.pallas_build_count() > before


# ---------------------------------------------------------------------------
# kernel-level bit-equality against the lax compositions
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,NB", [(8, 5), (256, 5), (257, 1025),
                                  (1000, 2), (3, 3), (512, 257),
                                  (4096, 4096)])
def test_grouping_kernel_matches_lax(B, NB):
    """order/rank/hist from the one-pass kernel == the counting-sort
    trio (order_and_hist / dense_rank) bit for bit, across tile edges
    (B % 256), bucket-pad edges (NB % 128), and the gate ceiling."""
    rng = np.random.default_rng(B * 31 + NB)
    ids = jnp.asarray(rng.integers(0, NB, B), jnp.int32)
    dest, rank, hist = jax.jit(
        lambda i: pk.grouping_rank_hist(i, NB, True))(ids)
    order_ref, hist_ref = order_and_hist(ids, NB)
    rank_ref, counts_ref, _, _ = dense_rank(ids, NB)
    assert np.array_equal(np.asarray(hist), np.asarray(hist_ref))
    assert np.array_equal(np.asarray(invert_perm(dest)),
                          np.asarray(order_ref))
    assert np.array_equal(np.asarray(rank), np.asarray(rank_ref)[:B])
    assert np.array_equal(np.asarray(hist)[:NB],
                          np.asarray(counts_ref))


def test_grouping_gate_bounds():
    from windflow_tpu.kernels.pallas_ffat import MAX_BUCKETS, MAX_LANES
    assert not pk.grouping_supported(64, MAX_BUCKETS + 1)
    assert not pk.grouping_supported(MAX_LANES + 1, 16)
    assert pk.grouping_supported(64, 16)


@pytest.mark.parametrize("monoid", ["sum", "max", "min"])
@pytest.mark.parametrize("dt", [jnp.int32, jnp.int64, jnp.float32,
                                jnp.float64])
def test_sliding_fold_matches_lax(monoid, dt):
    """The pane-combine kernel against _monoid_fill +
    _sliding_reduce_plain: bit-identical for max/min/int-sum by
    identical combine schedule; f32 sums ride the MXU banded matmul —
    exact on integer-valued data (this test), psum-grade otherwise."""
    rng = np.random.default_rng(7)
    for K, NPP, R in [(4, 10, 3), (7, 33, 8), (128, 300, 1),
                      (3, 9, 9), (16, 130, 7), (1, 5, 5)]:
        vals = {"a": jnp.asarray(rng.integers(-50, 50, (K, NPP)), dt),
                "b": jnp.asarray(rng.integers(0, 9, (K, NPP)), dt)}
        valid = jnp.asarray(rng.random((K, NPP)) < 0.7)
        op = {"sum": jnp.add, "max": jnp.maximum,
              "min": jnp.minimum}[monoid]
        comb = lambda x, y: jax.tree.map(op, x, y)
        ref = jax.jit(lambda v, va: fk._sliding_reduce_plain(
            comb, va, v, R, 1, monoid))(vals, valid)
        got = jax.jit(lambda v, va: pk.sliding_fold(
            v, va, R, monoid, True))(vals, valid)
        for k in vals:
            assert np.array_equal(np.asarray(got[k]),
                                  np.asarray(ref[k])), (K, NPP, R, k)


def test_fold_gate_bounds():
    """fold_supported mirrors table_leaf_ok's backend stance: compiled
    Mosaic keeps to f32/i32 (int64 pane aggregates fall back to lax on
    a real TPU — CPU tier-1 cannot observe a Mosaic lowering failure,
    so the gate must), bool is excluded everywhere, and the pane axis
    is bounded by the VMEM block (MAX_FOLD_PANES)."""
    from windflow_tpu.kernels.pallas_ffat import MAX_FOLD_PANES
    v32 = {"a": jnp.zeros((4, 16), jnp.float32)}
    v64 = {"a": jnp.zeros((4, 16), jnp.int64)}
    vb = {"a": jnp.zeros((4, 16), jnp.bool_)}
    assert pk.fold_supported(v32, 4, "sum", True)
    assert pk.fold_supported(v32, 4, "sum", False)
    assert pk.fold_supported(v64, 4, "max", True)
    assert not pk.fold_supported(v64, 4, "max", False)
    assert not pk.fold_supported(vb, 4, "max", True)
    assert not pk.fold_supported(v32, 4, None, True)
    wide = {"a": jnp.zeros((4, MAX_FOLD_PANES + 1), jnp.float32)}
    assert not pk.fold_supported(wide, 4, "sum", True)


def test_sliding_fold_float_sum_tolerance():
    """Non-integer f32 sums: the banded matmul reassociates (the psum
    tolerance the declared-"sum" contract already implies) — close, not
    necessarily bitwise."""
    rng = np.random.default_rng(11)
    vals = jnp.asarray(rng.random((8, 64), np.float32))
    valid = jnp.ones((8, 64), bool)
    comb = lambda a, b: a + b
    ref = fk._sliding_reduce_plain(comb, valid, vals, 5, 1, "sum")
    got = pk.sliding_fold(vals, valid, 5, "sum", True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5)


@pytest.mark.parametrize("monoid", ["sum", "max", "min"])
def test_dense_table_matches_scatter(monoid):
    """The segmented-reduce kernel against the one-scatter combine:
    slot tables, packed [B, W] carrier columns, the ts max column, and
    the liveness count, across slot-space edges."""
    rng = np.random.default_rng(5)
    for B, S in [(64, 8), (300, 17), (100, 4096), (5, 1)]:
        row = jnp.asarray(rng.integers(0, S + 1, B), jnp.int32)
        v1 = jnp.asarray(rng.integers(-100, 100, B), jnp.int64)
        v2 = jnp.asarray(rng.integers(0, 50, (B, 3)), jnp.float32)
        ts = jnp.asarray(rng.integers(0, 10 ** 9, B), jnp.int64)
        i1 = pk.monoid_identity_py(monoid, v1.dtype)
        i2 = pk.monoid_identity_py(monoid, v2.dtype)

        def lax_ref(row, v1, v2, ts):
            b1 = jnp.full((S + 1,), i1, v1.dtype)
            t1 = fk._monoid_scatter(b1.at[row], monoid)(v1)[:S]
            b2 = jnp.full((S + 1, 3), i2, v2.dtype)
            t2 = fk._monoid_scatter(b2.at[row], monoid)(v2)[:S]
            t3 = jnp.full(S + 1, -1, jnp.int64).at[row].max(ts)[:S]
            return t1, t2, t3

        r1, r2, r3 = jax.jit(lax_ref)(row, v1, v2, ts)
        g1, g2, g3 = jax.jit(lambda r, a, b, t: pk.dense_monoid_table(
            r, [a, b, t], [monoid, monoid, "max"], [i1, i2, -1], S,
            True))(row, v1, v2, ts)
        for g, r_ in [(g1, r1), (g2, r2), (g3, r3)]:
            assert np.array_equal(np.asarray(g), np.asarray(r_)), \
                (B, S, monoid)


# ---------------------------------------------------------------------------
# graph-level record-for-record A/B (pallas vs kill switch)
# ---------------------------------------------------------------------------

def _run_cb(pallas, monoid, n=500, batch=64):
    out = []
    op = (lambda a, b: a + b) if monoid in (None, "sum") \
        else (lambda a, b: jnp.maximum(a, b))
    src = (wf.Source_Builder(lambda: iter(
        [{"key": i % 5, "v": float(i % 97)} for i in range(n)]))
        .withOutputBatchSize(batch).build())
    wb = (wf.Ffat_WindowsTPU_Builder(lambda t: t["v"], op)
          .withCBWindows(16, 4).withKeyBy(lambda t: t["key"])
          .withMaxKeys(5))
    if monoid:
        wb = wb.withMonoidCombiner(monoid)
    g = wf.PipeGraph(f"pcb_{pallas}_{monoid}", config=_cfg(pallas))
    g.add_source(src).add(wb.build()).add_sink(
        wf.Sink_Builder(lambda r: out.append(
            (int(r["key"]), int(r["wid"]), float(r["value"])))
            if r is not None else None).build())
    g.run()
    return out


@pytest.mark.parametrize("monoid", ["sum", "max", None])
def test_window_cb_record_identical(monoid):
    """CB windows (grouping + pane-combine kernels on the monoid path,
    grouping alone on the generic path), incl. the partial-window EOS
    flush riding the same restored state: pallas on == kill switch,
    record for record."""
    a = _run_cb("auto", monoid)
    b = _run_cb("0", monoid)
    assert a and a == b


def _run_tb(pallas, jump=False):
    out = []
    n = 400

    def ts_of(i):
        # a mid-stream time jump widens the pane span past the
        # first-batch estimate, forcing the auto-sized ring to REGROW —
        # the rebuilt step must keep its pallas selection
        return i * 1000 + (300_000 if jump and i >= n // 2 else 0)

    src = (wf.Source_Builder(lambda: iter(
        [{"key": i % 4, "v": i, "ts": ts_of(i)} for i in range(n)]))
        .withTimestampExtractor(lambda t: t["ts"])
        .withOutputBatchSize(48).build())
    w = (wf.Ffat_WindowsTPU_Builder(lambda t: t["v"],
                                    lambda a, b: a + b)
         .withTBWindows(16000, 4000).withKeyBy(lambda t: t["key"])
         .withMaxKeys(4).build())
    g = wf.PipeGraph(f"ptb_{pallas}_{jump}", wf.ExecutionMode.DEFAULT,
                     wf.TimePolicy.EVENT, config=_cfg(pallas))
    g.add_source(src).add(w).add_sink(
        wf.Sink_Builder(lambda r: out.append(
            (int(r["key"]), int(r["wid"]), int(r["value"])))
            if r is not None else None).build())
    g.run()
    return out, w


@pytest.mark.parametrize("jump", [False, True])
def test_window_tb_record_identical(jump):
    """TB windows (the (key, pane) grouping kernel) incl. the
    EOS-flush loop; jump=True drives a mid-stream ring REGROW, whose
    step rebuild must keep the kernels (and stay record-identical)."""
    a, wa = _run_tb("auto", jump)
    b, wb = _run_tb("0", jump)
    assert a and sorted(a) == sorted(b)
    if jump:
        assert wa.NP > 2 * wa.R     # the regrow actually happened
        assert wa._tb_counter("n_evicted") == 0


def _run_dense_reduce(pallas, n=600):
    out = []
    src = (wf.Source_Builder(lambda: iter(
        [{"key": i % 23, "v": i * 3} for i in range(n)]))
        .withOutputBatchSize(128).build())
    r = (wf.ReduceTPU_Builder(
            lambda a, b: {"key": a["key"], "v": a["v"] + b["v"]})
         .withKeyBy(lambda t: t["key"]).withMaxKeys(23)
         .withMonoidCombiner("sum").build())
    g = wf.PipeGraph(f"pdr_{pallas}",
                     config=_cfg(pallas, key_compaction=False))
    g.add_source(src).add(r).add_sink(
        wf.Sink_Builder(lambda t: out.append((int(t["key"]),
                                              int(t["v"])))
                        if t is not None else None).build())
    g.run()
    return out


def test_dense_reduce_record_identical():
    a = _run_dense_reduce("auto")
    b = _run_dense_reduce("0")
    assert a and a == b


def _run_compacted(pallas, monoid, n=800):
    out = []
    comb = (lambda a, b: {"key": a["key"], "v": a["v"] + b["v"]}) \
        if monoid == "sum" else \
        (lambda a, b: {"key": a["key"],
                       "v": jnp.maximum(a["v"], b["v"])})
    src = (wf.Source_Builder(lambda: iter(
        [{"key": (i * 2654435761) % 10007, "v": i % 1000}
         for i in range(n)]))
        .withOutputBatchSize(256).build())
    r = (wf.ReduceTPU_Builder(comb)
         .withKeyBy(lambda t: t["key"]).withMonoidCombiner(monoid)
         .build())
    g = wf.PipeGraph(f"pcr_{pallas}_{monoid}", config=_cfg(pallas))
    g.add_source(src).add(r).add_sink(
        wf.Sink_Builder(lambda t: out.append((int(t["key"]),
                                              int(t["v"])))
                        if t is not None else None).build())
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        g.run()
    return out


@pytest.mark.parametrize("monoid", ["sum", "max"])
def test_compacted_reduce_record_identical(monoid):
    """The compacted arbitrary-key path: the dense half's one-scatter
    combine (packed int64 carrier under max, per-leaf under sum) rides
    the segmented-reduce kernel; the overflow/sorted lane and the rank
    merge are unchanged — output record-identical to the kill switch."""
    a = _run_compacted("auto", monoid)
    b = _run_compacted("0", monoid)
    assert a and a == b


# ---------------------------------------------------------------------------
# zero dispatch delta + chaos restore
# ---------------------------------------------------------------------------

def test_zero_dispatch_delta():
    """The kernels trace INTO the existing wf_jit programs: the jit
    registry's per-program dispatch counts are identical between pallas
    on and the kill switch — zero extra programs, zero extra
    dispatches per batch."""
    snaps = {}
    for pallas in ("auto", "0"):
        default_registry().reset()
        _run_cb(pallas, "sum", n=512, batch=64)
        snaps[pallas] = {k: v["dispatches"]
                        for k, v in default_registry().snapshot().items()}
    assert snaps["auto"] == snaps["0"]


def test_chaos_kill_restore_diff_with_pallas(tmp_path):
    """Durability chaos with the kernels ON: kill mid-epoch on the
    fused map→CB-window chain, restore, diff record-for-record — the
    restored graph rebuilds its step programs with the same pallas
    selection (snapshot/restore carries no kernel state; programs are
    rebuilt through _build_step)."""
    from windflow_tpu.durability import chaos
    assert pk.resolve_pallas(default_config) is not None, \
        "chaos cells must actually exercise the kernels on CPU tier-1"
    base = chaos.make_cell("window_cb", str(tmp_path / "ck_a"), n=4096)
    chal = chaos.make_cell("window_cb", str(tmp_path / "ck_b"), n=4096)
    v = chaos.run_ab(base["factory"], chal["factory"],
                     chaos.default_kill("window_cb", "mid_epoch"),
                     base["read"], chal["read"])
    assert v["diff"] is None
    assert v["records"] > 0


# ---------------------------------------------------------------------------
# WF607: forced downgrades are named
# ---------------------------------------------------------------------------

def test_wf607_forced_generic_combiner_warns():
    src = (wf.Source_Builder(lambda: iter(()))
           .withOutputBatchSize(32).build())
    w = (wf.Ffat_WindowsTPU_Builder(lambda t: t["v"],
                                    lambda a, b: a + b)
         .withCBWindows(8, 4).withKeyBy(lambda t: t["k"])
         .withMaxKeys(4).build())
    g = wf.PipeGraph("wf607", config=_cfg("1"))
    g.add_source(src).add(w).add_sink(
        wf.Sink_Builder(lambda r: None).build())
    found = [d for d in g.check() if d.code == "WF607"]
    assert found and found[0].node == w.name
    assert "generic" in found[0].message


def test_wf607_forced_on_mesh_warns():
    """Mesh graphs keep the lax bodies (shard_map factories) — forcing
    the kernels there must be NAMED, not silently ignored."""
    from windflow_tpu.parallel import mesh as M
    mesh = M.make_mesh(8, data=2)
    kk = mesh.shape[M.KEY_AXIS]
    src = (wf.Source_Builder(lambda: iter(()))
           .withOutputBatchSize(16 * 8).build())
    w = (wf.Ffat_WindowsTPU_Builder(lambda t: t["v"],
                                    lambda a, b: a + b)
         .withCBWindows(8, 4).withKeyBy(lambda t: t["k"])
         .withMaxKeys(4 * kk).withSumCombiner().build())
    g = wf.PipeGraph("wf607m", config=_cfg("1", mesh=mesh))
    g.add_source(src).add(w).add_sink(
        wf.Sink_Builder(lambda r: None).build())
    found = [d for d in g.check() if d.code == "WF607"]
    assert found and "mesh" in found[0].message


def test_wf607_auto_mode_is_silent():
    src = (wf.Source_Builder(lambda: iter(()))
           .withOutputBatchSize(32).build())
    w = (wf.Ffat_WindowsTPU_Builder(lambda t: t["v"],
                                    lambda a, b: a + b)
         .withCBWindows(8, 4).withKeyBy(lambda t: t["k"])
         .withMaxKeys(4).build())
    g = wf.PipeGraph("wf607b", config=_cfg("auto"))
    g.add_source(src).add(w).add_sink(
        wf.Sink_Builder(lambda r: None).build())
    assert [d for d in g.check() if d.code == "WF607"] == []


# ---------------------------------------------------------------------------
# key-aligned mesh ingest: sharded dense reduce / stateful (satellite)
# ---------------------------------------------------------------------------

def _mesh_cfg(aligned, data=2, **kw):
    from windflow_tpu.parallel import mesh as M
    mesh = M.make_mesh(8, data=data)
    return mesh, dataclasses.replace(default_config, mesh=mesh,
                                     key_aligned_ingest=aligned, **kw)


def _run_mesh_reduce_max(aligned, data=2):
    from windflow_tpu.parallel import mesh as M
    mesh, cfg = _mesh_cfg(aligned, data)
    kk = mesh.shape[M.KEY_AXIS]
    cap, K = 16 * 8, 4 * kk
    rng = np.random.default_rng(5)
    records = [{"key": int(k), "value": -1.0 - float(v)}
               for k, v in zip(rng.integers(0, K, 6 * cap),
                               rng.integers(0, 97, 6 * cap))]
    outs = []
    src = (wf.Source_Builder(lambda: iter(records))
           .withOutputBatchSize(cap).build())
    red = (wf.ReduceTPU_Builder(
            lambda a, b: {"key": jnp.maximum(a["key"], b["key"]),
                          "value": jnp.maximum(a["value"], b["value"])})
           .withKeyBy(lambda t: t["key"]).withMaxKeys(K)
           .withMonoidCombiner("max").build())
    g = wf.PipeGraph(f"amr_{aligned}", config=cfg)
    g.add_source(src).add(red).add_sink(
        wf.Sink_Builder(lambda t: outs.append(
            (int(t["key"]), float(t["value"])))
            if t is not None else None).build())
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        g.run()
    agg = {}
    for k, v in outs:
        agg[k] = max(agg.get(k, -1e30), v)
    ici = (((g.stats().get("Shard") or {}).get("per_op") or {})
           .get(red.name) or {}).get("ici") or {}
    return agg, getattr(red, "_ingest_mode", None), ici


def test_aligned_mesh_dense_reduce_identical_and_collective_drops():
    """Sharded dense reduce under key-aligned ingest: per-key results
    identical to the data-sharded psum/pmax layout, the consumer is
    stamped aligned, and the ICI model stops charging the [K]-table
    collective (the aligned kind names the within-column gather)."""
    a, mode_a, ici_a = _run_mesh_reduce_max(True)
    b, mode_b, ici_b = _run_mesh_reduce_max(False)
    assert mode_a == "aligned" and mode_b is None
    assert a and a == b
    assert "key-aligned" in ici_a.get("collective", "")
    assert "psum" in ici_b.get("collective", "")
    assert ici_a["ici_bytes_per_tuple"] < ici_b["ici_bytes_per_tuple"]


def test_aligned_mesh_generic_reduce_identical():
    """Generic (undeclared) combiner on a declared key space: aligned
    ingest also kills the all_gather+fold table combine; totals
    identical per key."""
    from windflow_tpu.parallel import mesh as M

    def run(aligned):
        mesh, cfg = _mesh_cfg(aligned)
        kk = mesh.shape[M.KEY_AXIS]
        cap, K = 16 * 8, 4 * kk
        rng = np.random.default_rng(6)
        records = [{"key": int(k), "value": int(v)}
                   for k, v in zip(rng.integers(0, K, 6 * cap),
                                   rng.integers(0, 97, 6 * cap))]
        outs = []
        src = (wf.Source_Builder(lambda: iter(records))
               .withOutputBatchSize(cap).build())
        red = (wf.ReduceTPU_Builder(
                lambda a, b: {"key": a["key"],
                              "value": a["value"] + b["value"]})
               .withKeyBy(lambda t: t["key"]).withMaxKeys(K).build())
        g = wf.PipeGraph(f"agr_{aligned}", config=cfg)
        g.add_source(src).add(red).add_sink(
            wf.Sink_Builder(lambda t: outs.append(
                (int(t["key"]), int(t["value"])))
                if t is not None else None).build())
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            g.run()
        agg = defaultdict(int)
        for k, v in outs:
            agg[k] += v
        return dict(agg), getattr(red, "_ingest_mode", None)

    a, ma = run(True)
    b, mb = run(False)
    assert ma == "aligned" and mb is None
    assert a and a == b


@pytest.mark.parametrize("is_filter", [False, True])
def test_aligned_mesh_dense_stateful_identical(is_filter):
    """Dense-key stateful Map/Filter under key-aligned ingest: per-key
    output SEQUENCES identical to the data-sharded psum-merge layout —
    state evolution preserves per-key arrival order through the
    aligned placement."""
    from windflow_tpu.parallel import mesh as M

    def run(aligned):
        mesh, cfg = _mesh_cfg(aligned)
        kk = mesh.shape[M.KEY_AXIS]
        cap, S = 16 * 8, 4 * kk
        rng = np.random.default_rng(7 + is_filter)
        records = [{"k": int(k), "v": int(v)}
                   for k, v in zip(rng.integers(0, S, 5 * cap),
                                   rng.integers(0, 100, 5 * cap))]
        outs = []
        src = (wf.Source_Builder(lambda: iter(records))
               .withOutputBatchSize(cap).build())
        if is_filter:
            fn = lambda t, s: ((s + t["v"]) % 3 != 0, s + t["v"])
            op = (wf.FilterTPU_Builder(fn)
                  .withInitialState(jnp.int64(0))
                  .withKeyBy(lambda t: t["k"]).withNumKeySlots(S)
                  .withDenseKeys().build())
        else:
            fn = lambda t, s: ({"k": t["k"], "v": s + t["v"]},
                               s + t["v"])
            op = (wf.MapTPU_Builder(fn).withInitialState(jnp.int64(0))
                  .withKeyBy(lambda t: t["k"]).withNumKeySlots(S)
                  .withDenseKeys().build())
        g = wf.PipeGraph(f"ams_{aligned}_{is_filter}", config=cfg)
        g.add_source(src).add(op).add_sink(
            wf.Sink_Builder(lambda t: outs.append(
                (int(t["k"]), int(t["v"])))
                if t is not None else None).build())
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            g.run()
        per_key = defaultdict(list)
        for k, v in outs:
            per_key[k].append(v)
        return dict(per_key), getattr(op, "_ingest_mode", None)

    a, ma = run(True)
    b, mb = run(False)
    assert ma == "aligned" and mb is None
    assert a and a == b


def test_aligned_mesh_reduce_drops_out_of_range_keys():
    """Out-of-range keys clip onto an edge column host-side and mask
    out on device — dropped and counted exactly like the unaligned
    dense-table contract."""
    from windflow_tpu.parallel import mesh as M
    mesh, cfg = _mesh_cfg(True)
    kk = mesh.shape[M.KEY_AXIS]
    cap, K = 16 * 8, 4 * kk
    rng = np.random.default_rng(9)
    keys = rng.integers(-3, K + 3, 4 * cap)
    records = [{"key": int(k), "value": -1.0 - float(i % 7)}
               for i, k in enumerate(keys)]
    outs = []
    src = (wf.Source_Builder(lambda: iter(records))
           .withOutputBatchSize(cap).build())
    red = (wf.ReduceTPU_Builder(
            lambda a, b: {"key": jnp.maximum(a["key"], b["key"]),
                          "value": jnp.maximum(a["value"], b["value"])})
           .withKeyBy(lambda t: t["key"]).withMaxKeys(K)
           .withMonoidCombiner("max").build())
    g = wf.PipeGraph("aoor", config=cfg)
    g.add_source(src).add(red).add_sink(
        wf.Sink_Builder(lambda t: outs.append(int(t["key"]))
                        if t is not None else None).build())
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        g.run()
    n_oor = int(np.sum((keys < 0) | (keys >= K)))
    assert red.num_dropped_tuples() == n_oor
    assert outs and all(0 <= k < K for k in outs)


# ---------------------------------------------------------------------------
# TPU cross-lowering (no chip): the Pallas -> Mosaic lowering and the
# XLA:TPU compile run on the CPU backend, so a kernel that cannot lower
# for a TPU fails HERE, not at a graph's first batch on the chip
# ---------------------------------------------------------------------------

# bench.CONFIGS["tpu"] — the shape chip_smoke.py drives
_CAP, _K, _P, _R, _D = 262144, 1024, 128, 8, 1
_S = jax.ShapeDtypeStruct


def _lowers_for_tpu(fn, *shapes) -> str:
    assert jax.config.jax_enable_x64, "the package's x64 state is the point"
    return jax.export.export(jax.jit(fn), platforms=["tpu"])(*shapes) \
        .mlir_module()


def _ffat_step_shapes(monoid, pallas=pk.PallasMode(False)):
    step = fk.make_ffat_step(_CAP, _K, _P, _R, _D, lambda x: x["v"],
                             lambda a, b: a + b, lambda x: x["k"],
                             monoid=monoid, pallas=pallas)
    state = jax.eval_shape(
        lambda: fk.make_ffat_state(jnp.zeros((), jnp.float32), _K, _R))
    batch = ({"k": _S((_CAP,), jnp.int32), "v": _S((_CAP,), jnp.float32)},
             _S((_CAP,), jnp.int64), _S((_CAP,), jnp.bool_))
    return step, state, batch


def test_grouping_kernel_lowers_for_tpu():
    assert pk.grouping_supported(_CAP, _K + 1)
    text = _lowers_for_tpu(
        lambda ids: pk.grouping_rank_hist(ids, _K + 1, False),
        _S((_CAP,), jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("monoid,dt", [("sum", jnp.float32),
                                       ("max", jnp.float32),
                                       ("sum", jnp.int32)])
def test_sliding_fold_kernel_lowers_for_tpu(monoid, dt):
    npp = _R - 1 + _CAP // _P + 2
    vals, valid = _S((_K, npp), dt), _S((_K, npp), jnp.bool_)
    assert pk.fold_supported(vals, _R, monoid, False)
    text = _lowers_for_tpu(
        lambda v, m: pk.sliding_fold(v, m, _R, monoid, False), vals, valid)
    assert "tpu_custom_call" in text


def test_dense_table_kernel_lowers_for_tpu():
    text = _lowers_for_tpu(
        lambda r, v, c: pk.dense_monoid_table(
            r, [v, c], ["sum", "max"], [0.0, 0], _K, False),
        _S((_CAP,), jnp.int32), _S((_CAP,), jnp.float32),
        _S((_CAP,), jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("monoid", [None, "sum"])
def test_ffat_step_with_kernels_lowers_for_tpu(monoid):
    """The CB step a default TPU graph builds — generic combiner
    (grouping kernel) and declared sum (grouping + pane fold)."""
    step, state, batch = _ffat_step_shapes(monoid)
    assert "tpu_custom_call" in _lowers_for_tpu(step, state, *batch)


@pytest.mark.slow   # ~40 s: two real XLA:TPU compiles
@pytest.mark.parametrize("with_kernels", [False, True])
def test_cb_step_compiles_inside_scan_for_v5e(monkeypatch, with_kernels):
    """What the megastep executor builds (``lax.scan`` around the CB
    step, K=8) compiles for a v5e chip.  It did not while the K-long
    fired-count running sum was int64: XLA:TPU emulates that cumsum as
    a u32-pair reduce-window it cannot place in scoped VMEM inside a
    while body (windows/ffat_kernels.py).  AOT against a described
    topology — the real XLA:TPU and Mosaic compilers, no chip."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    monkeypatch.setenv("TPU_ACCELERATOR_TYPE", "v5litepod-4")
    monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "localhost")
    sh = SingleDeviceSharding(
        topologies.get_topology_desc("v5e:2x2", "tpu").devices[0])
    step, state, batch = _ffat_step_shapes(
        None, pk.PallasMode(False) if with_kernels else None)

    def mega(st, payload, ts, valid):
        def body(carry, x):
            st2, out, fired, out_ts = step(carry, *x)
            return st2, (out, fired, out_ts)
        return jax.lax.scan(body, st, (payload, ts, valid))

    stacked = jax.tree.map(lambda s: _S((8,) + s.shape, s.dtype), batch)
    compiled = jax.jit(mega, in_shardings=sh, out_shardings=sh) \
        .lower(state, *stacked).compile()
    assert ("tpu_custom_call" in compiled.as_text()) == with_kernels


@pytest.fixture
def v5e_chip(monkeypatch):
    """One chip of a described v5e host, for AOT compiles by the real
    XLA:TPU compiler; skipped where the topology cannot be described."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    monkeypatch.setenv("TPU_ACCELERATOR_TYPE", "v5litepod-4")
    monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "localhost")
    try:
        topo = topologies.get_topology_desc("v5e:2x2", "tpu")
    except Exception as e:      # noqa: BLE001 - whatever libtpu raises
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("in_scan", [False, True])
def test_ysb_tb_step_compiles_for_v5e_without_a_scatter(v5e_chip, in_scan):
    """The time-based step at YSB's size (100 campaigns, 65 panes, 262144
    lanes, int64 counts, declared sum), alone and inside the K=8
    ``lax.scan`` of ``megastep.ffat_tb``: the chip's compiler takes the
    dense placement as ONE convolution with nothing materialized beside
    it, and leaves no scatter in the program (~3 s each, no chip)."""
    from test_monoid_combiner import ysb_step_shapes
    step, state, batch = ysb_step_shapes(100, 65, 262144, np.int64)
    fn = step
    if in_scan:
        def fn(st, *stacked):
            def body(carry, x):
                st2, out, fired, out_ts, _n = step(carry, *x)
                return st2, (out, fired, out_ts)
            return jax.lax.scan(body, st, stacked)
        batch = jax.tree.map(lambda s: _S((8,) + s.shape, s.dtype), batch)
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:        # a described chip's entry could not be read back
        compiled = jax.jit(fn, in_shardings=v5e_chip,
                           out_shardings=v5e_chip) \
            .lower(state, *batch).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)
    text = compiled.as_text()
    assert text.count(" convolution(") == 1 and " scatter(" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


@pytest.mark.slow   # ~160 s: one real XLA:TPU compile at the cell's size
def test_q16_rolling_step_compiles_for_v5e_in_the_sorts_own_order(v5e_chip):
    """``tests/test_nexmark_q16.py`` reads in the lowered text what the
    rolling aggregate's step ASKS for; this is what the chip's compiler
    makes of it, at ``nexmark_q16.saturated``'s own sizes and from
    shapes alone (no table is allocated): the program's only sorts are
    the ones it wrote (one a distinct group, one more a group that
    brings the run-ends to the front, one for the rows: XLA:TPU sorts an
    unmarked scatter's updates itself, and did inside
    ``wf.agg.distinct`` before the step said its order); each table is
    read in the body of one ``while``, a gather of ``chunk_lanes``
    indices a trip, and written by one scatter outside it, both marked
    ``indices_are_sorted=true``; and every byte of state is aliased,
    with no room beside it for a copy of a table into or out of a
    loop."""
    from benchmark import harness
    from windflow_tpu.windows.rolling_tpu import RollingAggregateTPU
    q16 = harness.load_module("configs", "nexmark_q16")
    cfg = harness.resolve_cell("nexmark_q16.saturated")["config"]
    graph = q16.build_graph(cfg, None, lambda: iter(()), lambda c: None)
    [op] = [o for o in graph._topo_operators()
            if isinstance(o, RollingAggregateTPU)]
    B = cfg["graph"]["batch"]
    S = lambda shape, dt: jax.ShapeDtypeStruct(   # noqa: E731
        shape, dt, sharding=v5e_chip)
    payload = {"key": S((B,), jnp.int32),
               **{f"v{i}": S((B,), jnp.float32)
                  for i in range(q16.N_FIELDS)}}
    state = jax.tree.map(lambda a: S(a.shape, a.dtype),
                         jax.eval_shape(op._make_state, payload))
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:        # a described chip's entry could not be read back
        compiled = jax.jit(op._make_step(B), donate_argnums=(0,)).lower(
            state, payload, S((B,), jnp.int64), S((B,), jnp.bool_),
            S((), jnp.int64)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)
    import re
    lines = compiled.as_text().splitlines()

    def phase(ln):      # XLA's own instructions may carry no name
        m = re.search(r'op_name="[^"]*?(wf\.agg\.\w+)', ln)
        return m.group(1) if m else None

    from windflow_tpu.windows.rolling_kernels import chunk_lanes
    sorts = [phase(ln) for ln in lines if " sort(" in ln]
    assert sorted(sorts, key=str) \
        == ["wf.agg.distinct"] * len(op.distinct) + ["wf.agg.rows"] \
        + ["wf.agg.sort"] * len(op.distinct)
    for kind in (" gather(", " scatter("):
        table = [ln for ln in lines
                 if kind in ln and phase(ln) == "wf.agg.distinct"]
        assert len(table) == len(op.distinct)
        assert all("indices_are_sorted=true" in ln for ln in table)
        # a chunk of words a trip of a loop; ONE scatter a table, outside
        assert all(("/while/body/" in ln) == (kind == " gather(")
                   for ln in table)
        if kind == " gather(":
            assert all(f"= u32[{chunk_lanes(B)}]{{" in ln for ln in table)
    loops = [ln for ln in lines if " while(" in ln]
    assert len(loops) == len(op.distinct) \
        and all(phase(ln) == "wf.agg.distinct" for ln in loops)
    # ... each reads one table, which rides the loop as it is
    assert sorted(int(n) for ln in loops for n in set(
        re.findall(r"u32\[(\d{7,})\]", ln))) \
        == sorted(int(t.shape[0]) for t in state["sets"])
    held = sum(int(np.prod(a.shape)) * a.dtype.itemsize
               for a in jax.tree.leaves(state))
    mem = compiled.memory_analysis()    # aliased: the leaves, padded to tiles
    assert mem.alias_size_in_bytes >= held > 4 << 30
    assert mem.temp_size_in_bytes < 1 << 30     # no copy of either table
