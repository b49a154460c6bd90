"""Declared-monoid combiners (withMonoidCombiner: sum | max | min).

The declaration routes count-based FFAT onto the scatter-combine /
flagless-fold fast paths and time-based FFAT onto the sort-free ring
placement — for max/min those paths are IDEMPOTENT, so results must be
bit-identical to the default flag-aware machinery (no float-reorder
tolerance needed, unlike "sum").

Values are strictly NEGATIVE floats throughout: any slot the kernels
fill with 0 instead of the monoid identity (-inf for max) would win a
max and corrupt a window, so these streams catch identity bugs that
non-negative data hides.  Reference anchor: the CUDA FFAT pays its
sort/tree machinery for every combiner alike
(``ffat_replica_gpu.hpp:751,917``); the declared-monoid bypass is
TPU-side design, not ported behavior.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import windflow_tpu as wf
from windflow_tpu.windows.ffat_kernels import (make_ffat_state,
                                               make_ffat_step,
                                               make_ffat_tb_state,
                                               make_ffat_tb_step)

CAP, K, WIN, SLIDE = 512, 8, 64, 16
Pn = math.gcd(WIN, SLIDE)
R, D = WIN // Pn, SLIDE // Pn


def _batches(n, rng, negative=True):
    out = []
    for i in range(n):
        vals = rng.random(CAP, dtype=np.float32)
        if negative:
            vals = -1.0 - vals          # all < -1: identity bugs surface
        out.append((
            {"k": jnp.asarray(rng.integers(0, K, CAP), jnp.int32),
             "v": jnp.asarray(vals)},
            jnp.asarray(np.arange(CAP) + i * CAP, jnp.int64),
            jnp.asarray(rng.random(CAP) > 0.15),     # invalid lanes too
        ))
    return out


def _run_cb(monoid, comb, batches, grouping="rank_scatter"):
    step = jax.jit(make_ffat_step(CAP, K, Pn, R, D, lambda x: x["v"], comb,
                                  lambda x: x["k"], monoid=monoid,
                                  grouping=grouping))
    st = make_ffat_state(jnp.zeros((), jnp.float32), K, R)
    fired = {}
    for payload, ts, valid in batches:
        st, out, ov, _ = step(st, payload, ts, valid)
        ovn = np.asarray(ov)
        keys = np.asarray(out["key"])[ovn]
        wids = np.asarray(out["wid"])[ovn]
        vals = np.asarray(out["value"])[ovn]
        for k_, w_, v_ in zip(keys, wids, vals):
            fired[(int(k_), int(w_))] = float(v_)
    return fired, st


@pytest.mark.parametrize("monoid,comb", [
    ("max", lambda a, b: jnp.maximum(a, b)),
    ("min", lambda a, b: jnp.minimum(a, b)),
])
def test_cb_monoid_scatter_path_bit_identical_to_default(monoid, comb):
    """Declared max/min (idempotent) on the CB scatter-combine path must
    equal the undeclared flag-aware path EXACTLY, windows and state."""
    rng = np.random.default_rng(11)
    batches = _batches(6, rng)
    got, st_m = _run_cb(monoid, comb, batches)
    want, st_d = _run_cb(None, comb, batches)
    assert got == want and len(got) > 0
    for a, b in zip(jax.tree.leaves(st_m), jax.tree.leaves(st_d)):
        if a.dtype == jnp.bool_ or jnp.issubdtype(a.dtype, jnp.integer):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_cb_monoid_flagless_fold_under_argsort_grouping():
    """monoid + argsort grouping exercises the permutation path with the
    identity-filled flagless fold (no scatter-combine) — still exact."""
    rng = np.random.default_rng(12)
    batches = _batches(5, rng)
    got, _ = _run_cb("max", lambda a, b: jnp.maximum(a, b), batches,
                     grouping="argsort")
    want, _ = _run_cb(None, lambda a, b: jnp.maximum(a, b), batches,
                      grouping="argsort")
    assert got == want and len(got) > 0


def test_cb_declared_sum_still_matches_int_oracle():
    """The legacy sum declaration through the generalized plumbing:
    integer sums are exact, so declared == undeclared bitwise."""
    rng = np.random.default_rng(13)
    batches = []
    for i in range(5):
        payload = {"k": jnp.asarray(rng.integers(0, K, CAP), jnp.int32),
                   "v": jnp.asarray(rng.integers(-50, 50, CAP), jnp.int32)}
        batches.append((payload,
                        jnp.asarray(np.arange(CAP) + i * CAP, jnp.int64),
                        jnp.asarray(rng.random(CAP) > 0.1)))
    step_kw = dict(sum_like=True)    # legacy spelling must still work

    def run(**kw):
        step = jax.jit(make_ffat_step(
            CAP, K, Pn, R, D, lambda x: x["v"], lambda a, b: a + b,
            lambda x: x["k"], **kw))
        st = make_ffat_state(jnp.zeros((), jnp.int32), K, R)
        fired = {}
        for payload, ts, valid in batches:
            st, out, ov, _ = step(st, payload, ts, valid)
            m = np.asarray(ov)
            for k_, w_, v_ in zip(np.asarray(out["key"])[m],
                                  np.asarray(out["wid"])[m],
                                  np.asarray(out["value"])[m]):
                fired[(int(k_), int(w_))] = int(v_)
        return fired
    assert run(**step_kw) == run() and len(run()) > 0


def test_tb_monoid_scatter_placement_matches_default():
    """TB max through the sort-free scatter placement == the grouped
    default, against a python oracle."""
    stream = [{"key": i % 3, "value": -1.0 - ((i * 37) % 101) / 10.0,
               "ts": i * 1000} for i in range(240)]
    per_key = {}
    for t in stream:
        per_key.setdefault(t["key"], []).append((t["ts"], t["value"]))
    # oracle: per-key max over every [w*4000, w*4000+16000) window
    exp = {}
    for k_, pts in per_key.items():
        tmax = max(ts for ts, _ in pts)
        w = 0
        while w * 4000 <= tmax:
            vals = [v for ts, v in pts
                    if w * 4000 <= ts < w * 4000 + 16000]
            if vals:
                exp[(k_, w)] = max(vals)
            w += 1
    # windows whose span starts after the last tuple never fire; also the
    # trailing partials fire at EOS — both covered by comparing sets
    for declare in (False, True):
        got = {}
        src = (wf.Source_Builder(lambda: iter(stream))
               .withTimestampExtractor(lambda t: t["ts"])
               .withOutputBatchSize(31).build())
        b = (wf.Ffat_WindowsTPU_Builder(
                lambda t: t["value"], lambda a, b: jnp.maximum(a, b))
             .withKeyBy(lambda t: t["key"]).withMaxKeys(3)
             .withTBWindows(16_000, 4_000))
        if declare:
            b = b.withMonoidCombiner("max")
        snk = wf.Sink_Builder(
            lambda r: got.__setitem__((r["key"], r["wid"]), r["value"])
            if r is not None else None).build()
        g = wf.PipeGraph("ffat_tb_max", wf.ExecutionMode.DEFAULT,
                         wf.TimePolicy.EVENT)
        g.add_source(src).add(b.build()).add_sink(snk)
        g.run()
        assert got == exp, (declare, len(got), len(exp))


def test_whole_graph_cb_sliding_min_matches_oracle():
    """Builder plumbing end-to-end: withMonoidCombiner("min") on CB
    windows through PipeGraph.run() against a python sliding-min oracle."""
    N, NK, W, S = 4000, 5, 32, 8
    vals = [-(1.0 + ((i * 13) % 97)) for i in range(N)]

    def gen():
        for i in range(N):
            yield {"key": i % NK, "v": vals[i]}

    per_key = {}
    for i in range(N):
        per_key.setdefault(i % NK, []).append(vals[i])
    exp = {}
    for k_, vs in per_key.items():
        wid = 0
        start = 0
        while start + W <= len(vs):
            exp[(k_, wid)] = min(vs[start:start + W])
            wid += 1
            start += S
    got = {}
    src = wf.Source_Builder(gen).withOutputBatchSize(256).build()
    op = (wf.Ffat_WindowsTPU_Builder(
            lambda t: t["v"], lambda a, b: jnp.minimum(a, b))
          .withCBWindows(W, S).withKeyBy(lambda t: t["key"])
          .withMaxKeys(NK).withMonoidCombiner("min").build())
    snk = wf.Sink_Builder(
        lambda r: got.__setitem__((r["key"], r["wid"]), r["value"])
        if r is not None else None).build()
    g = wf.PipeGraph("ffat_cb_min", wf.ExecutionMode.DEFAULT)
    g.add_source(src).add(op).add_sink(snk)
    g.run()
    for key, v in exp.items():
        assert key in got and abs(got[key] - v) < 1e-6, key
    # EOS flushes trailing partial windows beyond the oracle's full ones
    assert len(got) >= len(exp)


def test_unknown_monoid_rejected():
    with pytest.raises(wf.WindFlowError, match="monoid"):
        (wf.Ffat_WindowsTPU_Builder(lambda t: t["v"],
                                    lambda a, b: a * b)
         .withCBWindows(32, 8).withMaxKeys(4)
         .withMonoidCombiner("product").build())
    with pytest.raises(wf.WindFlowError, match="monoid"):
        (wf.ReduceTPU_Builder(lambda a, b: a)
         .withKeyBy(lambda t: t["key"]).withMaxKeys(4)
         .withMonoidCombiner("product").build())
    with pytest.raises(ValueError, match="monoid"):
        make_ffat_step(64, 4, 8, 4, 1, lambda x: x["v"],
                       lambda a, b: a + b, lambda x: x["k"],
                       monoid="product")
    with pytest.raises(ValueError, match="monoid"):
        make_ffat_tb_step(64, 4, 1000, 4, 1, 64, lambda x: x["v"],
                          lambda a, b: a + b, lambda x: x["k"],
                          monoid="product")


def test_tb_kernel_monoid_min_negative_and_positive():
    """Direct TB kernel check with mixed-sign values and a min monoid
    (identity +inf): declared == undeclared exactly."""
    B, KK, P_usec, RR, DD, NP = 128, 4, 1000, 4, 1, 64
    rng = np.random.default_rng(14)

    def run(monoid):
        step = jax.jit(make_ffat_tb_step(
            B, KK, P_usec, RR, DD, NP, lambda x: x["v"],
            lambda a, b: jnp.minimum(a, b), lambda x: x["k"],
            monoid=monoid))
        st = make_ffat_tb_state(jnp.zeros((), jnp.float32), KK, NP)
        fired = {}
        for i in range(4):
            payload = {"k": jnp.asarray(rng.integers(0, KK, B), jnp.int32),
                       "v": jnp.asarray(
                           rng.standard_normal(B).astype(np.float32))}
            ts = jnp.asarray(np.arange(B) * 250 + i * B * 250, jnp.int64)
            valid = jnp.asarray(rng.random(B) > 0.2)
            wm = jnp.asarray((i * B * 250) // P_usec, jnp.int64)
            st, out, f, _, _ = step(st, payload, ts, valid, wm)
            m = np.asarray(f)
            for k_, w_, v_ in zip(np.asarray(out["key"])[m],
                                  np.asarray(out["wid"])[m],
                                  np.asarray(out["value"])[m]):
                fired[(int(k_), int(w_))] = float(v_)
        return fired
    rng = np.random.default_rng(14)
    a = run("min")
    rng = np.random.default_rng(14)
    b = run(None)
    assert a == b and len(a) > 0


@pytest.mark.parametrize("horizon,lateness,expect_drops", [
    (12, 12_000, False),  # allowance covers the disorder horizon
    (30, 2_000, True),    # disorder beyond lateness + window span: drops
])
def test_tb_monoid_with_lateness_and_disorder_matches_default(
        horizon, lateness, expect_drops):
    """Declared-max TB placement under an out-of-order stream WITH a
    lateness allowance: the sort-free scatter path must agree with the
    grouped default exactly — late-but-allowed tuples land in already-open
    panes via scatter-combine, and too-late drops must be counted the
    same on both paths."""
    rnd = __import__("random").Random(40)
    stream = [{"key": i % 3, "value": -1.0 - ((i * 53) % 89) / 9.0,
               "ts": i * 1000} for i in range(300)]
    # shuffle within a fixed disorder horizon; drops require the
    # disorder to exceed lateness + the 20_000 us window span (panes
    # stay in the ring while any window over them is open)
    for i in range(0, 300 - horizon, horizon):
        seg = stream[i:i + horizon]
        rnd.shuffle(seg)
        stream[i:i + horizon] = seg

    def run(declare):
        got = {}
        drops = {}
        src = (wf.Source_Builder(lambda: iter(stream))
               .withTimestampExtractor(lambda t: t["ts"])
               .withOutputBatchSize(23).build())
        b = (wf.Ffat_WindowsTPU_Builder(
                lambda t: t["value"], lambda a, b: jnp.maximum(a, b))
             .withKeyBy(lambda t: t["key"]).withMaxKeys(3)
             .withTBWindows(20_000, 5_000).withLateness(lateness))
        if declare:
            b = b.withMonoidCombiner("max")
        op = b.build()
        snk = wf.Sink_Builder(
            lambda r: got.__setitem__((r["key"], r["wid"]), r["value"])
            if r is not None else None).build()
        g = wf.PipeGraph("ffat_tb_max_late", wf.ExecutionMode.DEFAULT,
                         wf.TimePolicy.EVENT)
        g.add_source(src).add(op).add_sink(snk)
        g.run()
        drops["late"] = op.dump_stats()["Late_tuples_dropped"]
        return got, drops

    got_m, d_m = run(True)
    got_d, d_d = run(False)
    assert got_m == got_d and len(got_m) > 0
    assert d_m == d_d
    if expect_drops:
        assert d_m["late"] > 0   # the drop path itself was exercised


def _run_reduce_graph(stream, declare, max_keys=None):
    # key_compaction OFF: this file pins the LEGACY declared-dense
    # contract (out-of-range keys dropped + warned) that only exists
    # under the WF_TPU_KEY_COMPACTION=0 kill switch since PR 11 —
    # the default-on reroute behavior is pinned by
    # tests/test_key_compaction.py
    import dataclasses

    from windflow_tpu.basic import default_config
    got = []
    src = (wf.Source_Builder(lambda: iter(stream))
           .withOutputBatchSize(64).build())
    b = (wf.ReduceTPU_Builder(
            lambda a, b: {"key": jnp.maximum(a["key"], b["key"]),
                          "v": jnp.maximum(a["v"], b["v"])})
         .withKeyBy(lambda t: t["key"]))
    if max_keys is not None:
        b = b.withMaxKeys(max_keys)
    if declare:
        b = b.withMonoidCombiner("max")
    op = b.build()
    snk = wf.Sink_Builder(
        lambda r: got.append((int(r["key"]), float(r["v"])))
        if r is not None else None).build()
    g = wf.PipeGraph("reduce_dense", wf.ExecutionMode.DEFAULT,
                     config=dataclasses.replace(default_config,
                                                key_compaction=False))
    g.add_source(src).add(op).add_sink(snk)
    g.run()
    return got, op


def test_single_chip_dense_reduce_matches_sorted_path():
    """withMaxKeys + withMonoidCombiner on ONE chip: the sort-free dense
    scatter-combine table must emit exactly the records of the sorted
    segmented reduce (same per-batch distinct keys, ascending order, same
    values) — negative values so an identity bug wins a max."""
    stream = [{"key": i % 7, "v": -2.0 - ((i * 29) % 83) / 7.0}
              for i in range(512)]
    dense, op_d = _run_reduce_graph(stream, declare=True, max_keys=7)
    sorted_, _ = _run_reduce_graph(stream, declare=False)
    assert dense == sorted_ and len(dense) > 0
    assert op_d.dump_stats().get("Out_of_range_keys_dropped", 0) == 0


def test_single_chip_dense_reduce_drops_and_counts_out_of_range():
    """Keys outside [0, max_keys) cannot live in the dense table: they are
    dropped and surface in Out_of_range_keys_dropped (the documented
    withMaxKeys key-space contract), while the undeclared sorted path
    keeps them."""
    stream = [{"key": i % 10, "v": -1.0 - float(i % 13)}
              for i in range(320)]
    dense, op_d = _run_reduce_graph(stream, declare=True, max_keys=6)
    sorted_, _ = _run_reduce_graph(stream, declare=False)
    n_out_of_range = sum(1 for t in stream if t["key"] >= 6)
    assert op_d.dump_stats()["Out_of_range_keys_dropped"] == n_out_of_range
    assert sorted(set(k for k, _ in dense)) == list(range(6))
    # in-range records agree with the sorted path's in-range subset
    assert dense == [(k, v) for k, v in sorted_ if k < 6]


def test_single_chip_dense_reduce_non_keyed_single_record():
    """Non-keyed declared reduce: the dense path must emit ONE record per
    batch (K=1 global segment, the mesh contract) — not a max_keys-lane
    batch with one valid row."""
    stream = [{"v": -3.0 - float(i % 11)} for i in range(256)]
    got = []
    src = (wf.Source_Builder(lambda: iter(stream))
           .withOutputBatchSize(64).build())
    op = (wf.ReduceTPU_Builder(
            lambda a, b: {"v": jnp.maximum(a["v"], b["v"])})
          .withMaxKeys(4096).withMonoidCombiner("max").build())
    snk = wf.Sink_Builder(
        lambda r: got.append(float(r["v"])) if r is not None else None) \
        .build()
    g = wf.PipeGraph("reduce_dense_nonkeyed", wf.ExecutionMode.DEFAULT)
    g.add_source(src).add(op).add_sink(snk)
    g.run()
    exp = [max(t["v"] for t in stream[lo:lo + 64])
           for lo in range(0, 256, 64)]
    assert got == exp


def test_single_chip_dense_drop_warns_once_and_notes_stats():
    """ADVICE r5 low (ops/tpu.py): adding withMaxKeys + withMonoidCombiner
    for speed silently switches ReduceTPU from the sorted path (keeps
    arbitrary int32 keys) to the dense-table contract (out-of-range keys
    dropped).  The FIRST observed drop must surface one RuntimeWarning
    plus a persistent note in dump_stats — and only once."""
    import warnings
    stream = [{"key": (17 if i % 5 == 0 else i % 4), "v": -1.0 - i}
              for i in range(256)]
    with pytest.warns(RuntimeWarning, match="dense-table contract") as rec:
        _, op = _run_reduce_graph(stream, declare=True, max_keys=4)
        st = op.dump_stats()
    assert sum("dense-table" in str(w.message) for w in rec) == 1
    assert st["Out_of_range_keys_dropped"] == \
        sum(1 for t in stream if t["key"] >= 4)
    assert "dense-table contract" in st["Out_of_range_keys_note"]
    with warnings.catch_warnings():        # warned once, never again
        warnings.simplefilter("error", RuntimeWarning)
        st2 = op.dump_stats()
    assert "dense-table contract" in st2["Out_of_range_keys_note"]


def test_single_chip_dense_no_drop_no_warning():
    """In-range streams must stay silent: no warning, no stats note."""
    import warnings
    stream = [{"key": i % 4, "v": -1.0 - i} for i in range(256)]
    with warnings.catch_warnings():
        warnings.simplefilter("error", RuntimeWarning)
        _, op = _run_reduce_graph(stream, declare=True, max_keys=4)
        st = op.dump_stats()
    assert st["Out_of_range_keys_dropped"] == 0
    assert "Out_of_range_keys_note" not in st


# ---------------------------------------------------------------------------
# dense placement (time-based step, declared monoid): one one-hot
# contraction in place of the scatter-combine, bit for bit
# ---------------------------------------------------------------------------

from windflow_tpu.windows import ffat_kernels as fk  # noqa: E402

TB = dict(B=512, K=6, P=1000, R=4, D=2, NP=32)
_COMB = {"sum": lambda a, b: a + b, "max": jnp.maximum, "min": jnp.minimum}


def _tb_stream(dtype, values, rng, n=5, **quirks):
    """``n`` batches of (payload, ts, valid, wm_pane) for the TB kernel.
    Event time advances ~40 panes a batch with jitter, so lanes land in
    several panes of one batch; a tenth of the keys lie outside
    ``[0, K)`` and a fifth of the lanes are invalid.  ``quirks``:
    ``late`` stamps an eighth of the lanes far behind the ring's base,
    ``dead_batch`` makes one batch invalid in every lane."""
    B, K, P = TB["B"], TB["K"], TB["P"]
    info = np.iinfo(dtype)
    out = []
    for i in range(n):
        if values == "ones":
            v = np.ones(B, dtype)
        elif values == "mixed":
            v = rng.integers(-1000, 1000, B).astype(dtype)
        else:       # a quarter to half of the range each: cells wrap
            v = (rng.integers(info.max // 4, info.max // 2, B)
                 * rng.choice([1, 1, 1, -1], B)).astype(dtype)
        ts = (i * 40 * P + rng.integers(0, 12 * P, B)).astype(np.int64)
        valid = rng.random(B) > 0.2
        if quirks.get("late") and i >= 2:
            ts[::8] = rng.integers(0, 2 * P, len(ts[::8]))
        if quirks.get("dead_batch") and i == 2:
            valid[:] = False
        keys = rng.integers(-1, K + 1, B).astype(np.int32)
        out.append(({"k": jnp.asarray(keys), "v": jnp.asarray(v)},
                    jnp.asarray(ts), jnp.asarray(valid),
                    jnp.asarray(i * 40 - 4, jnp.int64)))
    return out


def _run_tb(monoid, stream, zero, D=TB["D"], key_base=None):
    """The whole record of a run: every output lane of every step, and
    the final state."""
    step = jax.jit(make_ffat_tb_step(
        TB["B"], TB["K"], TB["P"], TB["R"], D, TB["NP"],
        lambda x: x["v"], _COMB[monoid], lambda x: x["k"], monoid=monoid,
        key_base_fn=None if key_base is None
        else (lambda: jnp.int32(key_base))))
    st = make_ffat_tb_state(zero, TB["K"], TB["NP"])
    trail = []
    for payload, ts, valid, wm in stream:
        st, out, fired, out_ts, n_adv = step(st, payload, ts, valid, wm)
        f = np.asarray(fired)
        trail.append((np.asarray(out["key"])[f], np.asarray(out["wid"])[f],
                      np.asarray(out["value"])[f], np.asarray(out_ts)[f],
                      int(n_adv)))
    return trail, jax.tree.map(np.asarray, st)


def _assert_same_run(a, b):
    (trail_a, st_a), (trail_b, st_b) = a, b
    assert sum(len(t[0]) for t in trail_a) > 0
    for x, y in zip(jax.tree.leaves(trail_a), jax.tree.leaves(trail_b)):
        assert np.array_equal(x, y)
    # cells of panes no tuple reached hold whatever the merge left there
    for name in st_a:
        if name == "n_wide":    # says which form placed, not what
            continue
        x, y = st_a[name], st_b[name]
        if name == "cells":
            x, y = (np.where(s["cell_valid"], s["cells"], 0)
                    for s in (st_a, st_b))
        assert np.array_equal(x, y), name
        assert x.dtype == y.dtype


def _dense_and_scatter(monkeypatch, monoid, stream, zero, **kw):
    dense = _run_tb(monoid, stream, zero, **kw)
    monkeypatch.setattr(fk, "DENSE_PLACE_MAX_CELLS", 0)   # the parent's form
    return dense, _run_tb(monoid, stream, zero, **kw)


@pytest.mark.parametrize("values", ["ones", "mixed", "huge"])
@pytest.mark.parametrize("dtype", [np.int64, np.int32])
def test_tb_dense_placement_is_the_scatter_bit_for_bit(monkeypatch, dtype,
                                                       values):
    """An integer sum placed by the contraction == the scatter-add, in
    the leaf's own width, wrap-around included."""
    plan = fk.tb_placement("sum", [np.zeros((), dtype)], TB["K"], TB["NP"],
                           TB["B"])
    assert plan["placement"] == "dense" \
        and plan["limbs"] == [np.dtype(dtype).itemsize]     # 8-bit limbs
    stream = _tb_stream(dtype, values, np.random.default_rng(29))
    dense, scatter = _dense_and_scatter(monkeypatch, "sum", stream,
                                        jnp.zeros((), dtype))
    _assert_same_run(dense, scatter)
    assert dense[1]["cells"].dtype == dtype
    if values == "huge":        # some cell's sum did leave the range
        p, ts, ok, _ = (jax.tree.map(np.asarray, x) for x in stream[0])
        cell = {}
        for k, t, v in zip(p["k"][ok], ts[ok] // TB["P"], p["v"][ok]):
            cell[(k, t)] = cell.get((k, t), 0) + int(v)
        assert max(abs(c) for c in cell.values()) > np.iinfo(dtype).max


@pytest.mark.parametrize("quirk,D", [("late", 2), ("dead_batch", 2),
                                     ("plain", 6)])
def test_tb_dense_placement_drops_what_the_scatter_drops(monkeypatch,
                                                         quirk, D):
    """Late lanes, an all-invalid batch, and the gap panes of hopping
    windows (``D > R``: slide 6 panes, window 4) never reach a cell on
    either form; ``n_late`` and the fired rows agree."""
    stream = _tb_stream(np.int64, "mixed", np.random.default_rng(31),
                        **{quirk: True})
    dense, scatter = _dense_and_scatter(monkeypatch, "sum", stream,
                                        jnp.zeros((), jnp.int64), D=D)
    _assert_same_run(dense, scatter)
    assert (dense[1]["n_late"] > 0) == (quirk == "late")


@pytest.mark.parametrize("monoid,dtype", [("max", np.int64),
                                          ("min", np.float32),
                                          ("sum", np.float32)])
def test_tb_count_by_contraction_leaves_the_values_alone(monkeypatch,
                                                         monoid, dtype):
    """``max`` / ``min`` and float sums keep their scatter-combine (same
    values, same rounding order); only ``partial_has`` comes from the
    contraction's count column."""
    plan = fk.tb_placement(monoid, [np.zeros((), dtype)], TB["K"], TB["NP"],
                           TB["B"])
    assert plan == {"placement": "scatter", "limbs": [0], "count": True,
                    "limb_bits": 8}
    rng = np.random.default_rng(37)
    stream = _tb_stream(np.int64, "mixed", rng)
    if dtype is np.float32:
        stream = [({"k": p["k"], "v": -1.0 - jnp.asarray(
            rng.random(TB["B"], dtype=np.float32))}, *rest)
            for p, *rest in stream]
    dense, scatter = _dense_and_scatter(monkeypatch, monoid, stream,
                                        jnp.zeros((), dtype))
    _assert_same_run(dense, scatter)


def ysb_step_shapes(K, NP, B, dtype, monoid="sum"):
    """The TB step as YSB's graph builds it (a 10 s tumbling window, one
    ``count_lane`` of ``dtype`` under a declared monoid) with the shapes
    of its arguments: (step, state, batch)."""
    S = jax.ShapeDtypeStruct
    step = make_ffat_tb_step(B, K, 10_000_000, 1, 1, NP,
                             lambda e: e["one"], _COMB[monoid],
                             lambda e: e["campaign"], monoid=monoid)
    state = jax.eval_shape(lambda: make_ffat_tb_state(
        jnp.zeros((), dtype), K, NP))
    return step, state, (
        {"campaign": S((B,), np.int32), "one": S((B,), dtype)},
        S((B,), np.int64), S((B,), np.bool_), S((), np.int64))


def _placement_jaxpr(K, NP, B, dtype, monoid="sum"):
    step, state, batch = ysb_step_shapes(K, NP, B, dtype, monoid)
    return jax.make_jaxpr(step)(state, *batch).jaxpr


def _lane_scatters(jaxpr, B):
    """Scatters of the jaxpr whose indices or updates run over the
    batch's ``B`` lanes: the placement's, nothing else in the step."""
    from test_shard_plane import _scatters
    return [e for e in _scatters(jaxpr)
            if any(B in e.invars[i].aval.shape for i in (1, 2))]


def test_a_grid_past_the_constant_keeps_the_scatter(monkeypatch):
    """The decision is static and by size: a constant just under this
    grid's cells leaves the count and the sum on the scatter, with the
    same results."""
    from test_shard_plane import _wide_scatters
    B, K, NP = TB["B"], TB["K"], TB["NP"]
    stream = _tb_stream(np.int64, "huge", np.random.default_rng(41))
    dense = _run_tb("sum", stream, jnp.zeros((), jnp.int64))
    assert "dot_general" in str(_placement_jaxpr(K, NP, B, np.int64))
    monkeypatch.setattr(fk, "DENSE_PLACE_MAX_CELLS", K * NP - 1)
    plan = fk.tb_placement("sum", [np.zeros((), np.int64)], K, NP, B)
    assert plan["placement"] == "scatter" and not plan["count"]
    jaxpr = _placement_jaxpr(K, NP, B, np.int64)
    assert "dot_general" not in str(jaxpr)
    # the wide placement's two, and the narrow one's count and 1 to 3
    # limb scatters of the switch (23-bit limbs at 512 lanes), in 32 bits
    lane = _lane_scatters(jaxpr, B)
    assert len(lane) == 2 + 1 + (1 + 2 + 3)
    assert len([e for e in lane if e in _wide_scatters(jaxpr)]) == 1
    _assert_same_run(dense, _run_tb("sum", stream, jnp.zeros((), jnp.int64)))


def test_ysb_sized_step_places_with_a_contraction_and_no_scatter():
    """The TB step as ``benchmark/configs/ysb.py`` builds it (100
    campaigns, the auto-sized 65 panes, 262144 lanes, the int64 ``1`` of
    ``count_lane`` under a declared sum): the placement is a
    ``dot_general`` and no scatter runs over the batch's lanes, so the
    18 ms int64 scatter-add (PERF.md section 6, PR 29) cannot come back
    unseen.  A float leaf keeps its one scatter."""
    from test_shard_plane import _wide_scatters
    K, NP, B = 100, 65, 262144
    plan = fk.tb_placement("sum", [np.zeros((), np.int64)], K, NP, B)
    assert plan == {"placement": "dense", "limbs": [11], "count": True,
                    "limb_bits": 6}
    jaxpr = _placement_jaxpr(K, NP, B, np.int64)
    assert "dot_general" in str(jaxpr)
    assert _lane_scatters(jaxpr, B) == [] and _wide_scatters(jaxpr) == []
    assert make_ffat_tb_state(jnp.zeros((), jnp.int64), K, NP)[
        "cells"].dtype == jnp.int64         # count_lane is as wide as ever
    flt = _placement_jaxpr(K, NP, B, np.float32)
    assert "dot_general" in str(flt) and len(_lane_scatters(flt, B)) == 1
    assert fk.tb_placement("sum", [np.zeros((), np.float32)], K, NP,
                           B)["placement"] == "scatter"
    # lanes the f32 accumulator could not count exactly: no contraction
    assert not fk.tb_placement("sum", [np.zeros((), np.int64)], K, NP,
                               (1 << 24) + 1)["count"]


# ---------------------------------------------------------------------------
# narrow placement (PR 31): a scatter-placed batch goes into the panes it
# spans, a 64-bit sum in 32-bit limbs; identical to the wide placement
# ---------------------------------------------------------------------------

def _span_stream(rng, dtype, values, span, n=7, stride=3, wm_lag=4,
                 key_lo=-1, **quirks):
    """``n`` batches whose valid lanes fall in ``span`` consecutive panes
    starting ``stride`` panes after the previous batch's first, then an
    all-invalid flush under an infinite watermark.  ``wm_lag=None``: the
    watermark never moves before the flush, so the ring fills to its end
    and rolls by capacity.  ``quirks``: ``late``, ``dead_batch`` (as
    ``_tb_stream``), ``one_cell`` (every lane of every batch on one
    cell)."""
    B, K, P = TB["B"], TB["K"], TB["P"]
    out = []
    for i in range(n):
        if values == "ones":
            v = np.ones(B)
        elif values == "small":
            v = rng.integers(0, 1000, B)
        elif values == "mixed":
            v = rng.integers(-1000, 1000, B)
        elif values == "float":
            v = -1.0 - rng.random(B)
        else:   # a quarter to half of the range each: cells wrap
            info = np.iinfo(dtype)
            v = rng.integers(info.max // 4, info.max // 2, B) \
                * rng.choice([1, 1, 1, -1], B)
        ts = ((i * stride + rng.integers(0, span, B)) * P
              + rng.integers(0, P, B)).astype(np.int64)
        ts[:span] = (i * stride + np.arange(span)) * P     # every pane hit
        valid = rng.random(B) > 0.2
        valid[:span] = True
        keys = rng.integers(key_lo, key_lo + K + 2, B).astype(np.int32)
        keys[:span] = key_lo + 1
        if quirks.get("late") and i >= 5:
            ts[span::8] = rng.integers(0, 2 * P, len(ts[span::8]))
        if quirks.get("dead_batch") and i == 2:
            valid[:] = False
        if quirks.get("one_cell"):
            ts[:], keys[:], valid[:] = i * stride * P, key_lo + 3, True
        wm = -100 if wm_lag is None else i * stride - wm_lag
        out.append(({"k": jnp.asarray(keys), "v": jnp.asarray(v.astype(dtype))},
                    jnp.asarray(ts), jnp.asarray(valid),
                    jnp.asarray(wm, jnp.int64)))
    for _ in range(3):
        out.append(({"k": jnp.zeros(B, jnp.int32), "v": jnp.zeros(B, dtype)},
                    jnp.zeros(B, jnp.int64), jnp.zeros(B, bool),
                    jnp.asarray(1 << 40, jnp.int64)))
    return out


def _narrow_and_wide(monkeypatch, monoid, stream, zero, **kw):
    """The same run with the narrow placement and with the whole-ring
    scatter alone (a span no ring is narrower than), both past the
    contraction's constant."""
    monkeypatch.setattr(fk, "DENSE_PLACE_MAX_CELLS", 0)
    narrow = _run_tb(monoid, stream, zero, **kw)
    monkeypatch.setattr(fk, "NARROW_PLACE_PANES", TB["NP"])
    wide = _run_tb(monoid, stream, zero, **kw)
    assert wide[1]["n_wide"] == 0           # no narrow form to miss
    return narrow, wide


S_ = fk.NARROW_PLACE_PANES
NARROW_CASES = {
    # name: (monoid, dtype, values, stream kwargs, run kwargs, wide steps)
    "span_below": ("sum", np.int64, "mixed", dict(span=S_ - 2), {}, 0),
    "span_at": ("sum", np.int64, "mixed", dict(span=S_), {}, 0),
    "span_above": ("sum", np.int64, "mixed", dict(span=S_ + 1), {}, 7),
    "ring_end": ("sum", np.int64, "mixed",
                 dict(span=2, n=14, wm_lag=None), {}, 0),
    "ring_end_wide": ("sum", np.int64, "mixed",
                      dict(span=S_ + 2, n=12, wm_lag=None, stride=4), {}, 12),
    "dead_batch": ("sum", np.int64, "mixed",
                   dict(span=2, dead_batch=True), {}, 0),
    "late": ("sum", np.int64, "mixed", dict(span=2, late=True), {}, 0),
    "key_base": ("sum", np.int64, "mixed", dict(span=3, key_lo=39),
                 dict(key_base=40), 0),
    "ones": ("sum", np.int64, "ones", dict(span=2), {}, 0),
    "small": ("sum", np.int64, "small", dict(span=2), {}, 0),
    "wraps": ("sum", np.int64, "huge", dict(span=2), {}, 0),
    "uint64": ("sum", np.uint64, "huge", dict(span=2), {}, 0),
    "int32": ("sum", np.int32, "huge", dict(span=2), {}, 0),
    "float32": ("sum", np.float32, "float", dict(span=2), {}, 0),
    "max": ("max", np.int64, "mixed", dict(span=3), {}, 0),
    "min": ("min", np.float32, "float", dict(span=3), {}, 0),
    "gaps": ("sum", np.int64, "mixed", dict(span=3, stride=6),
             dict(D=6), 0),
    "one_cell": ("sum", np.int64, "huge", dict(span=1, one_cell=True), {}, 0),
}


@pytest.mark.parametrize("case", sorted(NARROW_CASES))
def test_tb_narrow_placement_is_the_wide_one(monkeypatch, case):
    """Cells, flags, every fired row and every counter agree between a
    batch scattered into the panes it spans and the same batch scattered
    into the whole ring; ``n_wide`` counts the steps that spanned more."""
    monoid, dtype, values, skw, rkw, n_wide = NARROW_CASES[case]
    stream = _span_stream(np.random.default_rng(43), dtype, values, **skw)
    narrow, wide = _narrow_and_wide(monkeypatch, monoid, stream,
                                    jnp.zeros((), dtype), **rkw)
    _assert_same_run(narrow, wide)
    st = narrow[1]
    assert st["n_wide"] == n_wide and st["n_wide"].dtype == np.int64
    assert st["cells"].dtype == dtype
    assert (st["n_late"] > 0) == (case == "late")
    assert (st["n_evicted"] > 0) == case.startswith("ring_end")


@pytest.mark.parametrize("bits", [14, 23])
def test_narrow_limbs_wrap_as_the_64_bit_scatter_add_does(monkeypatch, bits):
    """Values over the whole int64 range, several to a cell: the uint32
    limb sums widened in uint64 give the int64 scatter-add's wrapped
    total, at the limb width of 262144 lanes (14 bits, five limbs) and
    at this batch's own (23 bits, three)."""
    assert fk.narrow_limb_bits(TB["B"]) == 23
    monkeypatch.setattr(fk, "narrow_limb_bits", lambda B: bits)
    stream = _span_stream(np.random.default_rng(47), np.int64, "huge",
                          span=2)
    narrow, wide = _narrow_and_wide(monkeypatch, "sum", stream,
                                    jnp.zeros((), jnp.int64))
    _assert_same_run(narrow, wide)
    p, ts, ok, _ = (jax.tree.map(np.asarray, x) for x in stream[0])
    cell = {}
    for k, t, v in zip(p["k"][ok], ts[ok] // TB["P"], p["v"][ok]):
        cell[(k, t)] = cell.get((k, t), 0) + int(v)
    assert max(abs(c) for c in cell.values()) > np.iinfo(np.int64).max


@pytest.mark.parametrize("B", [1, 512, 262144, 262145, 1 << 24, 1 << 31])
def test_narrow_limb_bits_cannot_wrap_a_uint32(B):
    b = fk.narrow_limb_bits(B)
    assert ((1 << b) - 1) * B < 1 << 32 <= ((1 << (b + 1)) - 1) * B
    assert fk.narrow_limb_bits(262144) == 14


def test_every_lane_of_a_real_batch_on_one_cell_fills_the_limbs():
    """262144 lanes of -1 on one cell: every 14-bit limb is full in every
    lane, so each uint32 limb sum is the largest there can be
    ((2^14 - 1) x 2^18 = 2^32 - 2^18) and the cell reads -262144."""
    B, K, NP, P = 262144, 3, 2 * S_ + 2, 1000
    step = jax.jit(make_ffat_tb_step(
        B, K, P, 2, 1, NP, lambda e: e["v"], _COMB["sum"],
        lambda e: e["k"], monoid="sum"))
    st = make_ffat_tb_state(jnp.zeros((), jnp.int64), K, NP)
    real = fk.DENSE_PLACE_MAX_CELLS
    fk.DENSE_PLACE_MAX_CELLS = 0
    try:
        st, *_ = step(st, {"k": jnp.full(B, 1, jnp.int32),
                           "v": jnp.full(B, -1, jnp.int64)},
                      jnp.full(B, 5 * P, jnp.int64), jnp.ones(B, bool),
                      jnp.asarray(-10, jnp.int64))
    finally:
        fk.DENSE_PLACE_MAX_CELLS = real
    cells = np.where(st["cell_valid"], st["cells"], 0)
    assert cells[1, 5] == -B and np.count_nonzero(cells) == 1
    assert int(st["n_wide"]) == 0


def _placement_cond(jaxpr, B):
    """The ``cond`` of a step's jaxpr whose two branches each scatter
    over the batch's lanes: (narrow, wide) in the order ``lax.cond``
    keeps them (index 0 is the false branch: the wide placement)."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "cond" and len(eqn.params["branches"]) == 2:
            wide, narrow = (b.jaxpr for b in eqn.params["branches"])
            if _lane_scatters(wide, B) and _lane_scatters(narrow, B):
                return narrow, wide
    return None


def test_q5_sized_step_scatters_narrow_and_in_32_bits():
    """The first window stage of ``benchmark/configs/nexmark_q5.py`` at
    its own sizes (655 360 keys x 66 panes, 262144 lanes, a declared
    int64 sum of ones): the narrow branch holds no 64-bit scatter and no
    scatter into ``(K + 1) x NP`` cells: every target is ``[K + 1, S]``
    of 32 bits; the wide branch is the parent's two scatters, and
    nothing else in the step scatters over the lanes."""
    from test_shard_plane import _wide_scatters
    K, NP, B = 655360, 66, 262144
    S = fk.NARROW_PLACE_PANES
    plan = fk.tb_placement("sum", [np.zeros((), np.int64)], K, NP, B)
    assert plan["placement"] == "scatter" and not plan["count"]
    step = make_ffat_tb_step(B, K, 5_000_000, 2, 1, NP,
                             lambda e: jnp.int64(1), _COMB["sum"],
                             lambda e: e["key"], monoid="sum")
    SD = jax.ShapeDtypeStruct
    state = jax.eval_shape(lambda: make_ffat_tb_state(
        jnp.zeros((), jnp.int64), K, NP))
    jaxpr = jax.make_jaxpr(step)(
        state, {"key": SD((B,), np.int32)}, SD((B,), np.int64),
        SD((B,), np.bool_), SD((), np.int64)).jaxpr
    narrow, wide = _placement_cond(jaxpr, B)
    assert _wide_scatters(narrow) == []
    targets = {tuple(e.invars[0].aval.shape) for e in _lane_scatters(narrow, B)}
    assert targets == {(K + 1, S)}
    assert {np.dtype(e.invars[0].aval.dtype).itemsize
            for e in _lane_scatters(narrow, B)} == {4}
    # five limbs at 14 bits: the switch holds 1 + ... + 5, the count 1
    assert len(_lane_scatters(narrow, B)) == 16
    assert [tuple(e.invars[0].aval.shape) for e in _lane_scatters(wide, B)] \
        == [(K + 1, NP)] * 2 and len(_wide_scatters(wide)) == 1
    assert len(_lane_scatters(jaxpr, B)) == 16 + 2


def test_ysb_step_never_traces_the_scatter_branch(monkeypatch):
    """YSB's plan is ``dense``: its step lowers to the same text whatever
    the narrow span is, holds no placement ``cond``, and carries
    ``n_wide`` through untouched."""
    K, NP, B = 100, 65, 32768
    def lowered():
        step, state, batch = ysb_step_shapes(K, NP, B, np.int64)
        return jax.jit(step).lower(state, *batch).as_text()
    text = lowered()
    monkeypatch.setattr(fk, "NARROW_PLACE_PANES", 2)
    assert lowered() == text
    step, state, batch = ysb_step_shapes(K, NP, B, np.int64)
    jaxpr = jax.make_jaxpr(step)(state, *batch).jaxpr
    assert _placement_cond(jaxpr, B) is None
    assert _lane_scatters(jaxpr, B) == []
    out_state = jaxpr.outvars[:len(jax.tree.leaves(state))]
    names = sorted(state)
    assert out_state[names.index("n_wide")] \
        is jaxpr.invars[names.index("n_wide")]


# -- the whole YSB graph of the benchmark, small ---------------------------

YSB_SIZES = dict(batch=1024, ring_batches=4, campaigns=10,
                 ads_per_campaign=4, window_usec=20_000)
YSB_RATE = 100_000           # events per second of event time


def _run_ysb(monkeypatch, n_total, **cfg_kw):
    """``benchmark/configs/ysb.py``'s graph over ``n_total`` tuples of
    its own ring, stamped as the benchmark's generator stamps them:
    (graph, result columns, the reference's windows)."""
    import dataclasses
    import json
    import os
    import sys
    repo = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
    if repo not in sys.path:
        sys.path.insert(0, repo)
    from benchmark import harness
    mod = harness.load_module("configs", "ysb")
    with open(os.path.join(repo, "benchmark", "configs", "ysb.json")) as f:
        cfg = harness.with_sizes(json.load(f), YSB_SIZES)
    ring = mod.make_ring(2**31 + 29, cfg)
    rec = np.tile(ring["rec"], -(-n_total // len(ring["rec"])))[:n_total]
    rec["t"] = np.arange(n_total) * 1_000_000 // YSB_RATE
    buf = rec.tobytes()

    def chunks():
        for i in range(0, len(buf), 4096):
            yield buf[i:i + 4096]

    cols = []
    base = wf.Config()
    monkeypatch.setattr(wf, "Config",
                        lambda: dataclasses.replace(base, **cfg_kw))
    g = mod.build_graph(cfg, ring, chunks,
                        lambda c: cols.append(c.cols) if c is not None
                        else None)
    g.run()
    got = {n: np.concatenate([np.asarray(c[n]) for c in cols])
           for n in ("key", "wid", "value")}
    exp = mod.expected(cfg, ring, n_total, {"event_rate": YSB_RATE})
    return g, mod.compare(cfg, got, exp), got


@pytest.mark.parametrize("path", ["per_batch", "megastep"])
def test_ysb_graph_counts_match_the_reference_on_both_paths(monkeypatch,
                                                            path):
    """Every (campaign, window) count of the benchmark's YSB graph
    against ``benchmark/reference.py``, with the window step on its own
    and inside the K = 4 ``lax.scan`` of ``megastep.ffat_tb``; the
    operator says which placement its program holds, in ``g.stats()``,
    in the OpenMetrics export and on the ``wf.compile`` span."""
    from test_layer_spans import _Annotation
    from windflow_tpu.monitoring.openmetrics import (parse_exposition,
                                                     render_openmetrics)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Annotation)
    _Annotation.made = []
    kw = dict(megastep_sweeps=4, punctuation_interval_usec=10 ** 12,
              wire_compression=False) if path == "megastep" else {}
    g, checks, got = _run_ysb(monkeypatch, 24 * 1024, **kw)
    assert all(c["ok"] for c in checks), checks
    assert len(got["key"]) > 50 and got["value"].dtype == np.int64
    scanned = sum(e["batches"] for e in g.stats()["Megastep"]["edges"])
    assert (scanned >= 8) == (path == "megastep")
    op = next(o for o in g.stats()["Operators"]
              if o["Operator_name"] == "campaign_counts")
    assert op["TB_placement"] == "dense" and op["TB_placement_limbs"] == 8
    fams = parse_exposition(render_openmetrics(g.stats()))
    assert {s[1]["placement"]: s[2] for s in
            fams["wf_operator_tb_placement"]["samples"]} \
        == {"dense": 1, "scatter": 0}
    compiles = {a.counts["op"]: a.counts.get("placement")
                for a in _Annotation.made if a.name == "wf.compile"}
    steps = {k: v for k, v in compiles.items() if v is not None}
    assert set(steps.values()) == {"dense"}
    assert any(k.startswith("megastep.") for k in steps) \
        == (path == "megastep")


def test_ysb_graph_scatters_narrow_inside_the_scan(monkeypatch):
    """The same graph past the contraction's constant, its window step
    inside the K = 4 ``lax.scan``: the ``cond`` on the observed span and
    the limb ``switch`` run under the scan, every count matches the
    reference, and no batch of the ordered stream needed the whole ring."""
    monkeypatch.setattr(fk, "DENSE_PLACE_MAX_CELLS", 0)
    g, checks, got = _run_ysb(
        monkeypatch, 24 * 1024, megastep_sweeps=4,
        punctuation_interval_usec=10 ** 12, wire_compression=False)
    assert all(c["ok"] for c in checks), checks
    assert sum(e["batches"] for e in g.stats()["Megastep"]["edges"]) >= 8
    op = next(o for o in g.stats()["Operators"]
              if o["Operator_name"] == "campaign_counts")
    assert op["TB_placement"] == "scatter"
    assert op["TB_wide_placements"] == 0


def test_float_window_reports_the_scatter_placement():
    stream = [{"key": i % 3, "value": float(i), "ts": i * 1000}
              for i in range(200)]
    src = (wf.Source_Builder(lambda: iter(stream))
           .withTimestampExtractor(lambda t: t["ts"])
           .withOutputBatchSize(32).build())
    op = (wf.Ffat_WindowsTPU_Builder(lambda t: t["value"],
                                     lambda a, b: a + b)
          .withKeyBy(lambda t: t["key"]).withMaxKeys(3)
          .withTBWindows(16_000, 4_000).withSumCombiner().build())
    assert "TB_placement" not in op.dump_stats()     # nothing built yet
    g = wf.PipeGraph("ffat_tb_float", wf.ExecutionMode.DEFAULT,
                     wf.TimePolicy.EVENT)
    g.add_source(src).add(op).add_sink(
        wf.Sink_Builder(lambda r: None).build())
    g.run()
    st = op.dump_stats()
    assert st["TB_placement"] == "scatter" and st["TB_placement_limbs"] == 0
