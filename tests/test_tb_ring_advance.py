"""A time-window step advances its pane ring only in a step in which it has
to: each of its four advances (after the three fire passes, and the
capacity roll with its eviction accounting) is a ``lax.cond`` on the shift
the step computes.  A roll by 0 is the identity, so every output lane and
every state leaf is bit for bit what the always-rolling step gave; the
state scalar ``n_ring_advances`` (``TB_ring_advances``) counts the steps
that moved."""

from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import windflow_tpu as wf
from windflow_tpu.parallel.mesh import KEY_AXIS
from windflow_tpu.windows import ffat_kernels as fk

ADD = lambda a, b: a + b  # noqa: E731
PANE = 1000                   # usec a pane
K, NP, R, D = 8, 8, 2, 1
MW = NP // D + 2


def parent_roll_left(flags, values, k):
    """The roll as the step made it before the conditionals (PR 34),
    whatever the shift: kept here as the reference, not in the package."""
    n = flags.shape[1]
    idx = jnp.arange(n, dtype=jnp.int64) + k
    inb = idx < n
    idxc = jnp.clip(idx, 0, n - 1).astype(jnp.int32)
    f = jnp.take(flags, idxc, axis=1) & inb[None, :]
    v = jax.tree.map(lambda a: jnp.take(a, idxc, axis=1), values)
    return f, v


def always_rolling(step):
    """``step`` traced with each conditional advance of its ring replaced
    by what the parent ran in every step: the three fire-pass rolls by
    :func:`parent_roll_left`, the capacity roll by its own rolling branch
    (eviction mask, horizon, roll) whatever ``shift_cap`` is.  Every
    other ``cond`` of the step stays one."""
    def ref(*args):
        real, rolled = jax.lax.cond, []

        def cond(pred, true_fn, false_fn, *ops):
            name = getattr(true_fn, "__name__", "")
            if name == "roll_left":
                rolled.append(name)
                return parent_roll_left(*ops)
            if name == "make_room":
                rolled.append(name)
                return true_fn(*ops)
            return real(pred, true_fn, false_fn, *ops)

        with mock.patch.object(jax.lax, "cond", cond):
            out = step(*args)
        assert sorted(rolled) == ["make_room"] + ["roll_left"] * 3
        return out
    return ref


def lanes(B, keys, panes, wm, offset=0):
    """One batch of ``B`` lanes: a tuple a (key, pane) pair, padded with
    invalid lanes; ``wm`` is the watermark's pane."""
    keys, panes = np.asarray(keys), np.asarray(panes)
    n = len(keys)
    assert n <= B
    pad = lambda a, dt: np.concatenate(   # noqa: E731
        [a, np.zeros(B - n, a.dtype)]).astype(dt)
    return ({"k": jnp.asarray(pad(keys, np.int32)),
             "one": jnp.ones(B, jnp.int64)},
            jnp.asarray(pad(panes * PANE + offset, np.int64)),
            jnp.asarray(np.arange(B) < n), jnp.int64(wm))


def every_key(panes, but=()):
    """(keys, panes) with a tuple of every key (less ``but``) in each of
    ``panes``, key 0 twice."""
    ks = [k for k in range(K) if k not in but]
    keys = [k for _ in panes for k in ks + [0]]
    return keys, [p for p in panes for _ in ks + [0]]


EMPTY = ([], [])
INF = 1 << 60

# name -> (lanes a batch, drop_tainted, monoid, [(keys, panes, wm)], checks)
# checks: what the final state and the fired passes of the LAST listed
# step must show, so that each case is known to drive what it is named for
CASES = {
    # nothing fires, nothing is evicted: no conditional is taken
    "no_shift": (256, True, "sum", [
        (*every_key([0, 1]), -1), (*every_key([1]), -1),
        (*every_key([0, 1, 2]), -1)],
        dict(advances=0, passes=[False, False, False], base=0)),
    # the watermark passed windows 0 and 1 before this batch: pass A1
    "fire_in_A1": (256, True, "sum", [
        (*every_key([0, 1]), -1), (*every_key([3]), 3)],
        dict(advances=1, passes=[True, False, False], base=2)),
    # a lagging watermark left data in the ring's last pane, whose window
    # ends beyond the ring: A1's roll brings it in range, A2 fires it
    "fire_in_A2": (256, True, "sum", [
        (*every_key(range(8)), -1), (*every_key([9]), 9)],
        dict(advances=1, passes=[True, True, False], base=8)),
    # an ordered stream: the batch itself completes window 0, pass B
    "fire_in_B": (256, True, "sum", [
        (*every_key([0, 1]), -1), (*every_key([1, 2]), 2)],
        dict(advances=1, passes=[False, False, True], base=1)),
    # the watermark jumps over an idle gap: two passes reach the windows,
    # the capacity roll makes room (nothing left to evict), pass B walks
    # the empty windows of the gap: all four advances in one step
    "idle_gap": (256, True, "sum", [
        (*every_key(range(8)), -1), (*every_key([20]), 20)],
        dict(advances=1, passes=[True, True, False], base=18, evicted=0)),
    # an undersized ring under a lagging watermark: the capacity roll
    # evicts panes 2..4 of seven keys, then the watermark catches up and
    # window 4 (pane 4 lost, pane 5 kept) is suppressed, or fires partial
    "capacity_roll_drop": (256, True, "sum", [
        (*every_key([2, 3, 4, 5], but=[7]), -1),
        (*every_key([12], but=[7]), -1), (*every_key([13]), 13)],
        dict(advances=2, evicted=3 * 7, horizon=[5] * 7 + [-(1 << 60)],
             dropped=True)),
    "capacity_roll_count": (256, False, "sum", [
        (*every_key([2, 3, 4, 5], but=[7]), -1),
        (*every_key([12], but=[7]), -1), (*every_key([13]), 13)],
        dict(advances=2, evicted=3 * 7, horizon=[5] * 7 + [-(1 << 60)],
             dropped=False)),
    # end of stream: empty batches under an infinite watermark until the
    # frontier stops; 16 lanes a batch, so the output is the compacted
    # form (40 rows) and windows that do not fit wait for the next step
    "flush_loop": (16, True, "sum", [
        (list(range(8)) * 2, [0] * 8 + [1] * 8, -1),
        (list(range(8)) * 2, [2] * 8 + [3] * 8, -1),
        (list(range(8)) * 2, [4] * 8 + [5] * 8, -1)]
        + [(*EMPTY, INF)] * 6,
        dict(advances=2, base=6)),
    # the same through a combiner the step knows nothing of (Q5's second
    # stage): the ring is advanced by the same code
    "flush_loop_generic": (16, True, None, [
        (list(range(8)) * 2, [0] * 8 + [1] * 8, -1),
        (list(range(8)) * 2, [2] * 8 + [3] * 8, -1)]
        + [(*EMPTY, INF)] * 4,
        dict(advances=1, base=4)),
}


def build(B, drop_tainted, monoid):
    step = fk.make_ffat_tb_step(
        B, K, PANE, R, D, NP, lambda e: e["one"], ADD, lambda e: e["k"],
        monoid=monoid, drop_tainted=drop_tainted)
    return step, fk.make_ffat_tb_state(jnp.zeros((), jnp.int64), K, NP)


def assert_same(got, want, what):
    got_l, got_t = jax.tree.flatten(got)
    want_l, want_t = jax.tree.flatten(want)
    assert got_t == want_t, what
    for i, (a, b) in enumerate(zip(got_l, want_l)):
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype and a.shape == b.shape, (what, i)
        assert np.array_equal(a, b), (what, i)


@pytest.mark.parametrize("case", sorted(CASES))
def test_step_is_bit_identical_to_the_always_rolling_step(case):
    """Lane for lane of every output and leaf for leaf of the state,
    after every step of the sequence."""
    B, drop_tainted, monoid, batches, checks = CASES[case]
    step, st = build(B, drop_tainted, monoid)
    new, ref = jax.jit(step), jax.jit(always_rolling(step))
    st_ref = st
    n_adv = []
    for i, (keys, panes, wm) in enumerate(batches):
        args = lanes(B, keys, panes, wm, offset=i)
        st, *outs = new(st, *args)
        st_ref, *outs_ref = ref(st_ref, *args)
        assert_same(outs, outs_ref, f"outputs of step {i}")
        assert_same(st, st_ref, f"state after step {i}")
        n_adv.append(int(outs[-1]))
    # the case drove what it is named for
    assert int(st["n_ring_advances"]) == checks["advances"]
    if "base" in checks:
        assert int(st["base"]) == checks["base"]
    if "passes" in checks:          # the grid form: [K, A1 | A2 | B]
        fired = np.asarray(outs[1]).reshape(K, 3, MW)
        assert fired.any(axis=(0, 2)).tolist() == checks["passes"]
    if "evicted" in checks:
        assert int(st["n_evicted"]) == checks["evicted"]
    if "horizon" in checks:
        assert np.asarray(st["horizon"]).tolist() == checks["horizon"]
    if "dropped" in checks:
        assert (int(st["n_win_dropped"]) > 0) == checks["dropped"]
    if case.startswith("flush_loop"):
        assert n_adv[-1] == 0 and sum(n_adv) == int(st["win_next"]) > 0


def test_counter_counts_the_steps_with_a_positive_shift():
    """An ordered stream, 0.4 panes of event time a batch, the watermark
    at the batch's end: a window closes, and the ring moves, in the steps
    whose batch crosses a pane boundary past the first window's end."""
    B = 256
    step, st = build(B, True, "sum")
    step = jax.jit(step)
    moved = []
    for i in range(20):
        lo, hi = int(i * 0.4 * PANE), int((i + 1) * 0.4 * PANE) - 1
        ts = np.linspace(lo, hi, K).astype(np.int64)
        base = int(st["base"])
        st, _out, fired, _ts, n_adv = step(
            st, {"k": jnp.arange(B, dtype=jnp.int32) % K,
                 "one": jnp.ones(B, jnp.int64)},
            jnp.asarray(np.concatenate([ts, np.zeros(B - K, np.int64)])),
            jnp.asarray(np.arange(B) < K), jnp.int64(hi // PANE))
        moved.append(int(st["base"]) > base)
        assert moved[-1] == (int(n_adv) > 0) == bool(np.asarray(fired).any())
    # window w ends with pane w + 1: the batch that reaches pane w + 2
    assert [i for i, m in enumerate(moved) if m] == [5, 7, 10, 12, 15, 17]
    assert int(st["n_ring_advances"]) == sum(moved) == 6
    assert st["n_ring_advances"].dtype == np.int64


# -- structure: the ring is touched inside conditionals alone ---------------

def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        for sub in (v if isinstance(v, (list, tuple)) else (v,)):
            sub = getattr(sub, "jaxpr", sub)        # a ClosedJaxpr
            if hasattr(sub, "eqns"):
                yield sub


def _over_the_ring(jaxpr, names, grid, into_conds=False):
    """Equations named in ``names`` with an operand that starts with the
    shape ``grid``; unless ``into_conds``, only those with no ``cond``
    between them and the program's root."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in names and any(
                tuple(getattr(v.aval, "shape", ())[:2]) == grid
                for v in eqn.invars):
            out.append(eqn)
        if into_conds or eqn.primitive.name != "cond":
            for sub in _sub_jaxprs(eqn):
                out.extend(_over_the_ring(sub, names, grid, into_conds))
    return out


def _conds(jaxpr):
    """The outermost conditionals of a program."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "cond":
            out.append(eqn)
        else:
            for sub in _sub_jaxprs(eqn):
                out.extend(_conds(sub))
    return out


def _ring_conds(jaxpr, grid):
    """The outermost conditionals with a branch that gathers along the
    ring of ``grid`` cells."""
    return [e for e in _conds(jaxpr) if any(
        _over_the_ring(b.jaxpr, {"gather"}, grid, into_conds=True)
        for b in e.params["branches"])]


Q5 = dict(K=655360, NP=66, B=262144)


def q5_step():
    SD = jax.ShapeDtypeStruct
    step = fk.make_ffat_tb_step(
        Q5["B"], Q5["K"], 5_000_000, 2, 1, Q5["NP"], lambda e: jnp.int64(1),
        ADD, lambda e: e["key"], monoid="sum", drop_tainted=True)
    state = jax.eval_shape(lambda: fk.make_ffat_tb_state(
        jnp.zeros((), jnp.int64), Q5["K"], Q5["NP"]))
    batch = ({"key": SD((Q5["B"],), np.int32)}, SD((Q5["B"],), np.int64),
             SD((Q5["B"],), np.bool_), SD((), np.int64))
    return step, state, batch


def assert_ring_moves_in_conditionals_alone(jaxpr, grid):
    # every gather / take along the ring lies in a conditional's branch
    assert _over_the_ring(jaxpr, {"gather"}, grid) == []
    # and no conditional was batched into a select of both its branches
    # (what ``vmap`` makes of a ``cond``): nothing selects over the ring
    # outside a branch
    assert _over_the_ring(jaxpr, {"select_n"}, grid) == []
    # three fire-pass rolls + the capacity roll + three folds
    ring = _ring_conds(jaxpr, grid)
    assert len(ring) == 7
    for e in ring:
        assert e.invars[0].aval.shape == ()          # a scalar decides


def test_q5_sized_step_touches_the_ring_inside_conditionals_alone():
    """The first window stage of ``benchmark/configs/nexmark_q5.py`` at
    its own sizes (655 360 keys x 66 panes, 262144 lanes)."""
    step, state, batch = q5_step()
    grid = (Q5["K"], Q5["NP"])
    jaxpr = jax.make_jaxpr(step)(state, *batch).jaxpr
    assert_ring_moves_in_conditionals_alone(jaxpr, grid)
    # the detector is not blind: the always-rolling step has the parent's
    # four gathers of the cells and of the flags at the top, and the
    # eviction mask's select
    parent = jax.make_jaxpr(always_rolling(step))(state, *batch).jaxpr
    assert len(_over_the_ring(parent, {"gather"}, grid)) == 8
    assert len(_over_the_ring(parent, {"select_n"}, grid)) >= 1
    # ... and it sees what batching does to a conditional
    batched = jax.make_jaxpr(jax.vmap(
        lambda f, k: jax.lax.cond(k > 0, lambda f: jnp.roll(f, 1, 1),
                                  lambda f: f, f)))(
        jax.ShapeDtypeStruct((2, 4, 6), np.bool_),
        jax.ShapeDtypeStruct((2,), np.int64)).jaxpr
    assert _conds(batched) == []
    assert "select_n" in str(batched)


def test_q5_sized_step_keeps_its_conditionals_under_scan():
    """As ``megastep.ffat_tb`` runs it: the step as the body of a
    ``lax.scan`` over a group of eight batches, the ring the carry."""
    step, state, batch = q5_step()
    payload, ts, valid, wm = batch
    group = lambda s: jax.ShapeDtypeStruct((8,) + s.shape, s.dtype)  # noqa: E731

    def mega(carry, xs):
        def body(carry, x):
            st, out, fired, out_ts, _n = step(carry, x["payload"], x["ts"],
                                              x["valid"], x["wm"])
            return st, (out, out_ts, fired)
        return jax.lax.scan(body, carry, xs)

    xs = jax.tree.map(group, {"payload": payload, "ts": ts, "valid": valid,
                              "wm": wm})
    jaxpr = jax.make_jaxpr(mega)(state, xs).jaxpr
    scans = [e for e in jaxpr.eqns if e.primitive.name == "scan"]
    assert len(scans) == 1
    assert_ring_moves_in_conditionals_alone(
        scans[0].params["jaxpr"].jaxpr, (Q5["K"], Q5["NP"]))
    text = jax.jit(mega).lower(state, xs).as_text()
    assert text.count("stablehlo.case") + text.count("stablehlo.if") >= 7


def test_q5_sized_step_keeps_its_conditionals_under_shard_map():
    """As ``make_sharded_ffat_tb_step`` builds it on the virtual CPU mesh:
    a key shard's ring is ``[K / 4, NP]`` and moves in conditionals."""
    from windflow_tpu.parallel import mesh as M
    mesh = M.make_mesh(4)
    sharded = M.make_sharded_ffat_tb_step(
        mesh, Q5["B"], Q5["K"], 5_000_000, 2, 1, Q5["NP"],
        lambda e: jnp.int64(1), ADD, lambda e: e["key"], drop_tainted=True,
        monoid="sum", op_name="q5.mesh")
    state = jax.eval_shape(lambda: M.make_sharded_ffat_tb_state(
        jnp.zeros((), jnp.int64), 8, Q5["NP"], mesh))
    state = {k: jax.ShapeDtypeStruct(
        ((Q5["K"],) + v.shape[1:]) if v.shape[0] == 8 else v.shape, v.dtype)
        for k, v in state.items()}
    _step, _state, batch = q5_step()
    jaxpr = jax.make_jaxpr(sharded._fn)(state, *batch).jaxpr
    inner = [e for e in jaxpr.eqns if e.primitive.name == "shard_map"]
    assert len(inner) == 1
    assert_ring_moves_in_conditionals_alone(
        inner[0].params["jaxpr"], (Q5["K"] // 4, Q5["NP"]))
    text = jax.jit(sharded._fn).lower(state, *batch).as_text()
    assert text.count("stablehlo.case") + text.count("stablehlo.if") >= 7


# -- the counter through the operator ---------------------------------------

TWIN, TSLIDE = 16_000, 4_000


def tb_stream(n=200):
    return [{"key": i % 4, "value": i, "ts": i * 500} for i in range(n)]


def run_tb_graph(config=None, batch=32, builder=lambda b: b):
    got = {}
    src = (wf.Source_Builder(lambda: iter(tb_stream()))
           .withTimestampExtractor(lambda t: t["ts"])
           .withOutputBatchSize(batch).build())
    op = builder(wf.Ffat_WindowsTPU_Builder(lambda t: t["value"], ADD)
                 .withName("tb").withTBWindows(TWIN, TSLIDE)
                 .withKeyBy(lambda t: t["key"]).withMaxKeys(4)).build()
    snk = wf.Sink_Builder(
        lambda r: got.__setitem__((r["key"], r["wid"]), r["value"])
        if r is not None else None).build()
    kw = {"config": config} if config is not None else {}
    g = wf.PipeGraph("tb_ring", wf.ExecutionMode.DEFAULT,
                     wf.TimePolicy.EVENT, **kw)
    g.add_source(src).add(op).add_sink(snk)
    g.run()
    return got, op, g


@pytest.mark.parametrize("monoid", [False, True])
def test_operator_reports_its_ring_advances(monoid):
    """``TB_ring_advances`` in ``g.stats()["Operators"]`` and in the
    exposition, with or without a declared monoid: fewer than the steps
    (a batch is 16 ms of event time and 4 panes, but the flush steps and
    the steps before the first window closes move nothing)."""
    from windflow_tpu.monitoring.openmetrics import (parse_exposition,
                                                     render_openmetrics)
    got, op, g = run_tb_graph(
        builder=(lambda b: b.withSumCombiner()) if monoid else (lambda b: b))
    assert len(got) > 20
    st = next(o for o in g.stats()["Operators"]
              if o["Operator_name"] == "tb")
    launched = sum(r["Device_programs_launched"] for r in st["Replicas"])
    assert 0 < st["TB_ring_advances"] < launched
    assert st["TB_ring_advances"] == int(op._states[0]["n_ring_advances"])
    assert ("TB_wide_placements" in st) == monoid
    fams = parse_exposition(render_openmetrics(g.stats()))
    assert [(s[1]["operator"], s[2]) for s in
            fams["wf_operator_tb_ring_advances_total"]["samples"]] \
        == [("tb", st["TB_ring_advances"])]


def _mesh_cfg():
    from windflow_tpu.basic import Config
    from windflow_tpu.parallel.mesh import make_mesh
    return Config(mesh=make_mesh(8, data=2))


def test_the_states_scalars_are_listed_once_where_the_state_is_made():
    """``TB_SCALARS`` is the scalar-shaped leaves of a freshly made state,
    and the mesh step and the re-bucketer read that one list: a counter
    added to the state and not to it fails here, not at a restore."""
    from windflow_tpu.durability import rebucket
    from windflow_tpu.parallel import mesh
    state = fk.make_ffat_tb_state(jax.ShapeDtypeStruct((), jnp.float32),
                                  K, NP)
    scalars = {k for k, v in state.items()
               if k != "cells" and jnp.ndim(v) == 0}
    assert set(fk.TB_SCALARS) == scalars
    assert len(set(fk.TB_SCALARS)) == len(fk.TB_SCALARS)
    assert set(fk.TB_ALIGNED) | {"max_seen"} | set(fk.TB_COUNTERS) == scalars
    assert mesh.TB_SCALARS is fk.TB_SCALARS is rebucket.TB_SCALARS
    assert rebucket.TB_COUNTERS is fk.TB_COUNTERS
    assert rebucket.TB_ALIGNED is fk.TB_ALIGNED


@pytest.mark.parametrize("blob_has_it", [True, False])
def test_ring_advances_survive_snapshot_restore_and_rebucket(blob_has_it):
    """The counter rides the checkpoint blob, a lane a key shard on a
    mesh; a blob from before it restores as 0, and a re-bucketing onto
    another key axis, or off the mesh, keeps the total."""
    from windflow_tpu.durability import rebucket
    _, op, _ = run_tb_graph(config=_mesh_cfg())
    assert op._states[0]["n_ring_advances"].sharding.spec == P(KEY_AXIS)
    total = op.dump_stats()["TB_ring_advances"]
    assert total > 0
    blob = op.snapshot_state()
    lanes_ = np.asarray(blob["states"][0]["n_ring_advances"])
    assert lanes_.shape == (4,) and lanes_.sum() == total
    if not blob_has_it:
        del blob["states"][0]["n_ring_advances"]
    fresh = (wf.Ffat_WindowsTPU_Builder(lambda t: t["value"], ADD)
             .withName("tb").withTBWindows(TWIN, TSLIDE)
             .withKeyBy(lambda t: t["key"]).withMaxKeys(4).build())
    fresh.config, fresh.mesh = op.config, op.mesh
    fresh.restore_state(blob)
    assert fresh._states[0]["n_ring_advances"].sharding.spec == P(KEY_AXIS)
    assert fresh.dump_stats()["TB_ring_advances"] \
        == (total if blob_has_it else 0)
    old, two = {"data": 2, "key": 4}, {"data": 4, "key": 2}
    st2 = rebucket.rebucket_blob(op, blob, 1, 1, old, two)["states"][0]
    st1 = rebucket.rebucket_blob(op, blob, 1, 1, old, None)["states"][0]
    if blob_has_it:
        assert np.asarray(st2["n_ring_advances"]).tolist() == [total, 0]
        assert np.asarray(st1["n_ring_advances"]).shape == ()
        assert int(st1["n_ring_advances"]) == total
    else:
        assert "n_ring_advances" not in st2 and "n_ring_advances" not in st1


def test_observability_doc_lists_the_counter():
    import os
    doc = open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "..", "docs", "OBSERVABILITY.md")).read()
    for name in ("TB_ring_advances", "TB_wide_placements",
                 "wf_operator_tb_ring_advances_total", "n_ring_advances"):
        assert name in doc
