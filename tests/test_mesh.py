"""Multi-chip sharding tests on the virtual 8-device CPU mesh.

Validates the ICI-collective paths (SURVEY.md §5.8 TPU-native equivalent,
BASELINE.json "keyby-sharded Reduce … linear scaling to 8 chips"): keyed
reduce via psum and via gather+fold, and FFAT window state sharded along the
key axis, against host oracles."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from windflow_tpu.parallel import mesh as M


def _rand_batch(cap, K, seed=0):
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, K, cap)
    vals = rng.integers(0, 100, cap).astype(np.float32)
    return keys, vals


def _put(mesh, payload, valid, spec):
    sh = jax.sharding.NamedSharding(mesh, spec)
    return (jax.tree.map(lambda a: jax.device_put(a, sh), payload),
            jax.device_put(valid, sh))


@pytest.mark.parametrize("data", [1, 2])
def test_sharded_keyed_reduce_psum(data):
    cap, K = 64, 16
    keys, vals = _rand_batch(cap, K)
    mesh = M.make_mesh(8, data=data)
    payload = {"k": jnp.asarray(keys, jnp.int32), "v": jnp.asarray(vals)}
    payload, valid = _put(mesh, payload, jnp.ones(cap, bool),
                          jax.sharding.PartitionSpec(("data", "key")))
    red = M.make_sharded_keyed_reduce(
        mesh, cap, K, lambda a, b: {"k": b["k"], "v": a["v"] + b["v"]},
        lambda x: x["k"], use_psum=True)
    table, has = red(payload, valid)
    expect = np.zeros(K)
    for k, v in zip(keys, vals):
        expect[k] += v
    has = np.asarray(has)
    np.testing.assert_allclose(np.asarray(table["v"])[has], expect[has],
                               rtol=1e-6)


@pytest.mark.parametrize("monoid,op,ident", [
    ("max", max, -1e30), ("min", min, 1e30)])
def test_sharded_keyed_reduce_monoid_collective(monoid, op, ident):
    """Declared max/min ride one pmax/pmin collective (r5
    withMonoidCombiner): results must match the oracle on strictly
    NEGATIVE values (a zero-identity bug would win every max), and the
    record's key leaf must survive the collective intact (max(i, i) == i
    across chips — unlike psum, where a key leaf is part of the
    declared-sum contract)."""
    cap, K = 64, 16
    keys, vals = _rand_batch(cap, K)
    vals = -1.0 - vals        # all < -1
    mesh = M.make_mesh(8, data=2)
    payload = {"k": jnp.asarray(keys, jnp.int32), "v": jnp.asarray(vals)}
    payload, valid = _put(mesh, payload, jnp.ones(cap, bool),
                          jax.sharding.PartitionSpec(("data", "key")))
    jop = jnp.maximum if monoid == "max" else jnp.minimum
    red = M.make_sharded_keyed_reduce(
        mesh, cap, K, lambda a, b: {"k": b["k"], "v": jop(a["v"], b["v"])},
        lambda x: x["k"], monoid=monoid)
    table, has = red(payload, valid)
    has = np.asarray(has)
    expect = np.full(K, ident)
    seen = np.zeros(K, bool)
    for k, v in zip(keys, vals):
        expect[k] = op(expect[k], v)
        seen[k] = True
    np.testing.assert_array_equal(has, seen)
    np.testing.assert_allclose(np.asarray(table["v"])[has], expect[has])
    np.testing.assert_array_equal(np.asarray(table["k"])[has],
                                  np.arange(K)[has])


def test_sharded_keyed_reduce_generic_fold():
    cap, K = 64, 16
    keys, vals = _rand_batch(cap, K)
    mesh = M.make_mesh(8, data=2)
    payload = {"k": jnp.asarray(keys, jnp.int32), "v": jnp.asarray(vals)}
    payload, valid = _put(mesh, payload, jnp.ones(cap, bool),
                          jax.sharding.PartitionSpec(("data", "key")))
    red = M.make_sharded_keyed_reduce(
        mesh, cap, K,
        lambda a, b: {"k": b["k"], "v": jnp.maximum(a["v"], b["v"])},
        lambda x: x["k"])
    table, has = red(payload, valid)
    has = np.asarray(has)
    expect = np.full(K, -1.0)
    seen = np.zeros(K, bool)
    for k, v in zip(keys, vals):
        expect[k] = max(expect[k], v)
        seen[k] = True
    np.testing.assert_array_equal(has, seen)
    np.testing.assert_allclose(np.asarray(table["v"])[has], expect[has])


@pytest.mark.parametrize("data,win,slide", [(1, 8, 4), (2, 8, 4), (2, 6, 2)])
def test_sharded_ffat_matches_host_oracle(data, win, slide):
    cap, K = 64, 16
    keys, vals = _rand_batch(cap, K, seed=3)
    mesh = M.make_mesh(8, data=data)
    Pn = math.gcd(win, slide)
    R, D = win // Pn, slide // Pn
    payload = {"k": jnp.asarray(keys, jnp.int32), "v": jnp.asarray(vals)}
    payload, valid = _put(mesh, payload, jnp.ones(cap, bool),
                          jax.sharding.PartitionSpec("data"))
    state = M.make_sharded_ffat_state(jnp.zeros((), jnp.float32), K, R, mesh)
    step = M.make_sharded_ffat_step(mesh, cap, K, Pn, R, D,
                                    lambda x: x["v"], lambda a, b: a + b,
                                    lambda x: x["k"])
    ts = jax.device_put(jnp.arange(cap, dtype=jnp.int64),
                        M.batch_sharding(mesh))
    # two consecutive batches to exercise the carried state across steps
    got = []
    for rep in range(2):
        state, out, fired, _ = step(state, payload, ts, valid)
        f = np.asarray(fired)
        got += list(zip(np.asarray(out["key"])[f].tolist(),
                        np.asarray(out["wid"])[f].tolist(),
                        np.asarray(out["value"])[f].tolist()))
    per_key = {}
    for _ in range(2):
        for k, v in zip(keys, vals):
            per_key.setdefault(int(k), []).append(float(v))
    exp = []
    for k, vs in per_key.items():
        for end in range(win, len(vs) + 1, slide):
            exp.append((k, (end - win) // slide, sum(vs[end - win:end])))
    got, exp = sorted(got), sorted(exp)
    assert len(got) == len(exp)
    for g, e in zip(got, exp):
        assert g[0] == e[0] and g[1] == e[1]
        assert abs(g[2] - e[2]) < 1e-3


def test_sharded_ffat_matches_single_chip():
    """The sharded program and the single-device operator program must agree
    bit-for-bit on fired windows (metamorphic: resharding must not change
    results — the §4 oracle style applied to the mesh)."""
    from windflow_tpu.windows.ffat_tpu import make_ffat_state, make_ffat_step
    cap, K, win, slide = 32, 8, 4, 2
    keys, vals = _rand_batch(cap, K, seed=7)
    Pn = math.gcd(win, slide)
    R, D = win // Pn, slide // Pn
    payload = {"k": jnp.asarray(keys, jnp.int32), "v": jnp.asarray(vals)}
    valid = jnp.ones(cap, bool)
    ts = jnp.arange(cap, dtype=jnp.int64)

    ref_state = make_ffat_state(jnp.zeros((), jnp.float32), K, R)
    ref_step = jax.jit(make_ffat_step(cap, K, Pn, R, D, lambda x: x["v"],
                                      lambda a, b: a + b, lambda x: x["k"]))
    _, rout, rfired, _ = ref_step(ref_state, payload, ts, valid)

    mesh = M.make_mesh(8, data=2)
    spayload, svalid = _put(mesh, payload, valid,
                            jax.sharding.PartitionSpec("data"))
    sstate = M.make_sharded_ffat_state(jnp.zeros((), jnp.float32), K, R, mesh)
    sstep = M.make_sharded_ffat_step(mesh, cap, K, Pn, R, D,
                                     lambda x: x["v"], lambda a, b: a + b,
                                     lambda x: x["k"])
    _, sout, sfired, _ = sstep(sstate, spayload,
                               jax.device_put(ts, M.batch_sharding(mesh)),
                               svalid)

    def fired_set(out, fired):
        f = np.asarray(fired)
        return sorted(zip(np.asarray(out["key"])[f].tolist(),
                          np.asarray(out["wid"])[f].tolist(),
                          np.asarray(out["value"])[f].tolist()))

    assert fired_set(rout, rfired) == fired_set(sout, sfired)


def _drive_sharded_ffat_pair(comb, values, step_kwargs):
    """Shared equivalence runner: drive the key-sharded FFAT step 5 batches
    with and without the declared fast path; return both sorted firing
    lists (signature changes only need editing here)."""
    cap, K, Pn, R, D = 64, 8, 4, 4, 1
    mesh = M.make_mesh(8, data=2)
    payload = {"k": jnp.arange(cap, dtype=jnp.int32) % K, "v": values}
    ts = jnp.arange(cap, dtype=jnp.int64)
    valid = jnp.ones(cap, bool)
    sh = M.batch_sharding(mesh)
    outs = []
    for kwargs in ({}, step_kwargs):
        step = M.make_sharded_ffat_step(
            mesh, cap, K, Pn, R, D, lambda x: x["v"], comb,
            lambda x: x["k"], **kwargs)
        st = M.make_sharded_ffat_state(jnp.zeros((), jnp.int64), K, R, mesh)
        got = []
        for it in range(5):     # enough batches per key to fire windows
            p5 = {"k": jax.device_put(payload["k"], sh),
                  "v": jax.device_put(payload["v"] - it, sh)}
            st, out, fired, _ = step(st, p5, jax.device_put(ts, sh),
                                     jax.device_put(valid, sh))
            f = np.asarray(fired)
            got.extend(zip(np.asarray(out["key"])[f].tolist(),
                           np.asarray(out["wid"])[f].tolist(),
                           np.asarray(out["value"])[f].tolist()))
        outs.append(sorted(got))
    return outs


@pytest.mark.parametrize("name,comb,values,step_kwargs", [
    # flagless declared-sum fold, bitwise on integer lifts
    ("sum", lambda a, b: a + b,
     (jnp.arange(64, dtype=jnp.int64) * 3) % 101, dict(sum_like=True)),
    # declared-max scatter-combine with per-shard key bases; negative int
    # lifts — a zero-identity bug in any shard corrupts its windows
    ("max", jnp.maximum,
     -1 - ((jnp.arange(64, dtype=jnp.int64) * 7) % 89),
     dict(monoid="max")),
])
def test_sharded_ffat_declared_path_matches_default(name, comb, values,
                                                    step_kwargs):
    default, declared = _drive_sharded_ffat_pair(comb, values, step_kwargs)
    assert default == declared and default, name


# ---------------------------------------------------------------------------
# Owned-lane compaction of the key-sharded count-window step (PR 33): a key
# shard sorts the lanes it owns to the front and runs the step built at
# capacity // kk lanes over them, one round where they fit (uniform keys),
# a round more for every further capacity // kk it owns, counted a step.
# ---------------------------------------------------------------------------

OWN_CAP, OWN_K, OWN_KK = 64, 16, 4          # 16 lanes and 4 keys a shard


def _own_case(case, step):
    """Keys / valid of one 64-lane batch, and which of the four shards is
    expected to take more than one round over it."""
    rng = np.random.default_rng(100 + step)
    lane = np.arange(OWN_CAP)
    keys = (lane * 5 + step) % OWN_K        # 16 lanes a shard, interleaved
    valid = np.ones(OWN_CAP, bool)
    wide = [0, 0, 0, 0]
    if case == "uniform":                   # 14 lanes a shard: one round
        valid = lane % 8 != 7
    elif case == "exact":                   # n_own == capacity // kk
        pass
    elif case == "one_more":                # shard 0 owns 17, shard 1 15
        keys[np.flatnonzero(keys // 4 == 1)[3]] = 2
        wide = [1, 0, 0, 0]
    elif case == "one_shard":               # every lane on shard 2
        keys = 8 + rng.integers(0, 4, OWN_CAP)
        wide = [0, 0, 1, 0]
    elif case == "empty_shard":             # shard 1 owns nothing
        valid = keys // 4 != 1
    elif case in ("random", "vector_lift"):     # a mix over the steps
        keys = rng.integers(0, OWN_K, OWN_CAP)
        valid = rng.random(OWN_CAP) < 0.9
        own = np.bincount(keys[valid] // 4, minlength=4)
        wide = (own > OWN_CAP // OWN_KK).astype(int).tolist()
    return keys.astype(np.int32), valid, wide


def _drive_own(mesh, case, steps, lanes=None, monkeypatch=None):
    """Drive the sharded step over ``steps`` batches of ``case``; with
    ``lanes`` the step is built as if a shard's share were that many lanes
    (the whole batch: the parent's program)."""
    if lanes is not None:
        monkeypatch.setattr(M, "ffat_owned_lanes", lambda m, c: lanes)
    Pn, R, D = 2, 3, 1
    lift, spec = lambda x: x["v"], jnp.zeros((), jnp.float32)
    if case == "vector_lift":       # a leaf that cannot ride the sort
        lift = lambda x: jnp.stack([x["v"], -2 * x["v"]])
        spec = jnp.zeros((2,), jnp.float32)
    step = M.make_sharded_ffat_step(mesh, OWN_CAP, OWN_K, Pn, R, D,
                                    lift, lambda a, b: a + b,
                                    lambda x: x["k"])
    state = M.make_sharded_ffat_state(spec, OWN_K, R, mesh)
    sh = M.batch_sharding(mesh)
    outs, want = [], np.zeros((steps, 4), int)
    for i in range(steps):
        keys, valid, want[i] = _own_case(case, i)
        vals = np.random.default_rng(7 + i).random(OWN_CAP) \
            .astype(np.float32)
        # the batch's newest valid stamp sits on ONE shard's lane
        ts = 1000 * i + np.arange(OWN_CAP, dtype=np.int64)
        put = lambda a: jax.device_put(jnp.asarray(a), sh)
        state, out, fired, out_ts = step(
            state, {"k": put(keys), "v": put(vals)}, put(ts), put(valid))
        outs.append((jax.tree.map(np.asarray, out), np.asarray(fired),
                     np.asarray(out_ts), int(ts[valid].max())))
    return outs, jax.tree.map(np.asarray, state), want


def _rows_by_key(out, fired):
    """The fired rows in (key, wid) order: ``(key, wid, value)``."""
    order = np.lexsort((out["wid"][fired], out["key"][fired]))
    return tuple(a[fired][order]
                 for a in (out["key"], out["wid"], out["value"]))


@pytest.mark.parametrize("case", ["uniform", "exact", "one_more",
                                  "one_shard", "empty_shard", "random",
                                  "vector_lift"])
def test_owned_lane_step_is_the_whole_batch_program(case, monkeypatch):
    """Rows, fired lanes, hand-on stamps and the whole state of the
    compacting step are the whole-batch program's (the parent's): bit for
    bit and lane for lane while every shard's lanes fit its share of the
    batch; where a shard takes more rounds, the same (key, wid) rows
    with sums inside float rounding (a pane's fold is cut where the round
    is) and a key's windows still in order.  The state's counter says how
    many steps each shard took more than one round for."""
    mesh = M.make_mesh(8, data=2)           # (data=2, key=4)
    got, gstate, want = _drive_own(mesh, case, 6)
    ref, rstate, _ = _drive_own(mesh, case, 6, lanes=OWN_CAP,
                                monkeypatch=monkeypatch)
    np.testing.assert_array_equal(gstate.pop(M.CB_WIDE_STEPS),
                                  want.sum(axis=0))
    assert not rstate.pop(M.CB_WIDE_STEPS).any()    # one round, always
    same = (np.testing.assert_array_equal if not want.any() else
            lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-6))
    for name in gstate:
        if name in ("carry", "cur"):        # values: where the flag says
            flag = gstate[name + "_valid"]
            same(gstate[name][flag], rstate[name][flag])
        else:
            np.testing.assert_array_equal(gstate[name], rstate[name])
    n_rows, exact = 0, True
    for (out, fired, out_ts, newest), (rout, rfired, rts, _), wide \
            in zip(got, ref, want):
        # as many rows a shard, the WHOLE batch's newest stamp on each
        per = lambda f: f.reshape(OWN_KK, -1).sum(axis=1)
        np.testing.assert_array_equal(per(fired), per(rfired))
        assert (out_ts[fired] == newest).all() and not out_ts[~fired].any()
        exact &= not wide.any()
        if exact:                           # lane for lane, bit for bit
            np.testing.assert_array_equal(fired, rfired)
            for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(rout)):
                np.testing.assert_array_equal(a[fired], b[fired])
        (k, w, v), (rk, rw, rv) = (_rows_by_key(out, fired),
                                   _rows_by_key(rout, rfired))
        np.testing.assert_array_equal(k, rk)
        np.testing.assert_array_equal(w, rw)
        np.testing.assert_allclose(v, rv, rtol=1e-6)
        # a key's windows leave in order, round after round
        for key in np.unique(out["key"][fired]):
            wids = out["wid"][fired][out["key"][fired] == key]
            assert (np.diff(wids) > 0).all()
        n_rows += int(fired.sum())
        if case == "empty_shard":           # owns no lane: fires nothing
            assert not fired.reshape(OWN_KK, -1)[1].any()
    assert n_rows


def test_owned_lane_step_matches_host_oracle_and_single_chip():
    """Uniform keys: every shard takes one round, and the rows are the
    host oracle's and the one-chip step's."""
    from windflow_tpu.windows.ffat_tpu import make_ffat_state, make_ffat_step
    mesh = M.make_mesh(4)
    got, state, _ = _drive_own(mesh, "uniform", 5)
    assert not state[M.CB_WIDE_STEPS].any()
    Pn, R, D = 2, 3, 1
    one = jax.jit(make_ffat_step(OWN_CAP, OWN_K, Pn, R, D, lambda x: x["v"],
                                 lambda a, b: a + b, lambda x: x["k"]))
    st = make_ffat_state(jnp.zeros((), jnp.float32), OWN_K, R)
    per_key, rows, one_rows = {}, [], []
    for i, (out, fired, _, _) in enumerate(got):
        keys, valid, _ = _own_case("uniform", i)
        vals = np.random.default_rng(7 + i).random(OWN_CAP) \
            .astype(np.float32)
        for k, v in zip(keys[valid], vals[valid]):
            per_key.setdefault(int(k), []).append(float(v))
        rows += list(zip(out["key"][fired].tolist(),
                         out["wid"][fired].tolist(),
                         out["value"][fired].tolist()))
        st, o, f, _ = one(st, {"k": jnp.asarray(keys),
                               "v": jnp.asarray(vals)},
                          jnp.arange(OWN_CAP, dtype=jnp.int64),
                          jnp.asarray(valid))
        f = np.asarray(f)
        one_rows += list(zip(np.asarray(o["key"])[f].tolist(),
                             np.asarray(o["wid"])[f].tolist(),
                             np.asarray(o["value"])[f].tolist()))
    assert sorted(rows) == sorted(one_rows)         # bit for bit
    win, slide = Pn * R, Pn * D
    exp = sorted((k, (end - win) // slide, sum(vs[end - win:end]))
                 for k, vs in per_key.items()
                 for end in range(win, len(vs) + 1, slide))
    assert [r[:2] for r in sorted(rows)] == [e[:2] for e in exp]
    np.testing.assert_allclose([r[2] for r in sorted(rows)],
                               [e[2] for e in exp], rtol=1e-6)


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold."""
    for e in jaxpr.eqns:
        yield e
        for v in e.params.values():
            for j in (v if isinstance(v, (list, tuple)) else [v]):
                j = getattr(j, "jaxpr", j)
                if hasattr(j, "eqns"):
                    yield from _eqns(j)


def _moves(jaxpr, B):
    """The gathers, scatters and sorts of a jaxpr (nested ones too) that
    read or write a ``B``-lane array: ``(primitive, output shape)``."""
    found = []
    for e in _eqns(jaxpr):
        if e.primitive.name.startswith(("gather", "scatter", "sort")) \
                and any(getattr(v.aval, "shape", ())[:1] == (B,)
                        for v in list(e.invars) + list(e.outvars)):
            found.append((e.primitive.name, e.outvars[0].aval.shape))
    return found


def _key_shard_jaxpr(B, lanes, K_local):
    from windflow_tpu.windows.ffat_kernels import make_ffat_state
    step = M._make_key_shard_ffat_step(
        B, lanes, K_local, 4, 4, 1, lambda x: x["v"], lambda a, b: a + b,
        lambda x: x["k"], lambda: jnp.int32(K_local))
    st = make_ffat_state(jnp.zeros((), jnp.float32), K_local, 4)
    st[M.CB_WIDE_STEPS] = jnp.zeros((1,), jnp.int64)
    return jax.make_jaxpr(step)(
        st, {"k": jnp.zeros(B, jnp.int32), "v": jnp.zeros(B, jnp.float32)},
        jnp.zeros(B, jnp.int64), jnp.ones(B, bool)).jaxpr


def test_the_step_moves_no_whole_batch_lane_but_the_compaction():
    """The only gather, scatter or sort of the key shard's step that
    touches a ``capacity``-lane array is the compaction's own: one stable
    sort on the ownership flag, the key and the lifted value riding it.
    The step's sixteen gathers and scatters sit in the rounds' loop at
    ``capacity // kk`` lanes; the whole-batch program (the parent's) has
    them all at ``capacity``."""
    B, lanes, K_local = 2048, 512, 8
    jaxpr = _key_shard_jaxpr(B, lanes, K_local)
    assert _moves(jaxpr, B) == [("sort", (B,))]
    sort, = [e for e in jaxpr.eqns if e.primitive.name == "sort"]
    assert sort.params["num_keys"] == 1 and sort.params["is_stable"]
    assert [str(v.aval.dtype) for v in sort.invars] \
        == ["int32", "int32", "float32"]        # flag, key, lifted value
    loop, = [e for e in jaxpr.eqns if e.primitive.name == "while"]
    assert len(_moves(loop.params["body_jaxpr"].jaxpr, lanes)) >= 8
    assert "cond" not in {e.primitive.name for e in jaxpr.eqns}
    whole = _moves(_key_shard_jaxpr(B, B, K_local), B)
    assert len(whole) >= 8


def test_the_mesh_step_lowers_as_jit_local():
    """The benchmark finds the step in a device trace as the XLA module
    ``jit_local`` (``benchmark/roofline/ffat_cb_step_sharded.py``)."""
    mesh = M.make_mesh(4)
    step = M.make_sharded_ffat_step(mesh, OWN_CAP, OWN_K, 2, 3, 1,
                                    lambda x: x["v"], lambda a, b: a + b,
                                    lambda x: x["k"])
    state = M.make_sharded_ffat_state(jnp.zeros((), jnp.float32), OWN_K, 3,
                                      mesh)
    sh = M.batch_sharding(mesh)
    put = lambda a: jax.device_put(a, sh)
    text = step._jit.lower(
        state, {"k": put(jnp.zeros(OWN_CAP, jnp.int32)),
                "v": put(jnp.zeros(OWN_CAP, jnp.float32))},
        put(jnp.zeros(OWN_CAP, jnp.int64)),
        put(jnp.ones(OWN_CAP, bool))).as_text()
    assert "module @jit_local " in text
    assert M.ffat_owned_lanes(mesh, OWN_CAP) == OWN_CAP // 4


def test_aligned_layout_keeps_its_program():
    """The ``"aligned"`` layout's lanes are the owned ones already: no
    compaction on top of it (the equations of the plain step at
    ``capacity // kk`` lanes, no rounds), the counter carried through."""
    mesh = M.make_mesh(4)
    sh = jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec(("data", "key")))
    put = lambda a: jax.device_put(a, sh)
    args = ({"k": put(jnp.zeros(OWN_CAP, jnp.int32)),
             "v": put(jnp.zeros(OWN_CAP, jnp.float32))},
            put(jnp.zeros(OWN_CAP, jnp.int64)), put(jnp.ones(OWN_CAP, bool)))

    def names(ingest):
        step = M.make_sharded_ffat_step(
            mesh, OWN_CAP, OWN_K, 2, 3, 1, lambda x: x["v"],
            lambda a, b: a + b, lambda x: x["k"], ingest=ingest)
        state = M.make_sharded_ffat_state(jnp.zeros((), jnp.float32), OWN_K,
                                          3, mesh)
        prims = [e.primitive.name for e in _eqns(
            jax.make_jaxpr(step._fn)(state, *args).jaxpr)]
        return step, state, prims

    step, state, aligned = names("aligned")
    _, _, data = names("data")
    for prim in ("sort", "while", "dynamic_update_slice"):
        assert aligned.count(prim) < data.count(prim)
    new_state, *_ = step(state, *args)
    assert not np.asarray(new_state[M.CB_WIDE_STEPS]).any()
