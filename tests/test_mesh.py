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
