"""The O(n) dense-key grouping permutation (windows/grouping.py) and its
wiring into the FFAT steps (their ``grouping=`` parameter).

Two layers of evidence, mirroring how the argsort path earned trust:
1. the permutation itself is bit-identical to ``jnp.argsort(stable=True)``
   across bucket widths (single-digit, radix), batch sizes (chunk-padding
   edges), and skews;
2. the CB and TB FFAT steps produce bit-identical outputs AND state under
   both groupings — including a NON-commutative combiner, which fails if
   arrival order within a key is ever perturbed.

Every FFAT graph test runs the ``rank_scatter`` steps: the operator builds
no other.

Reference anchor: the grouping the reference buys with
``thrust::sort_by_key`` (``keyby_emitter_gpu.hpp:519-583``).
"""

import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from windflow_tpu.windows.ffat_kernels import (agg_spec_for, make_ffat_state,
                                               make_ffat_step,
                                               make_ffat_tb_state,
                                               make_ffat_tb_step)
from windflow_tpu.windows.grouping import counting_order


# the two heaviest cells (~8s each: bench digit width, 2-digit radix)
# ride the nightly leg (wfverify-round headroom pass); the remaining
# cells keep every algorithm branch (radix digit counts, sub-chunk
# padding, degenerate buckets) in the tier-1 gate
@pytest.mark.parametrize("B,nbuckets", [
    pytest.param(4096, 257, marks=pytest.mark.slow),  # bench digit width
    (1000, 7),        # few buckets
    (64, 257),        # one chunk exactly
    (63, 3),          # sub-chunk + padding
    (31, 5),          # below one chunk
    (4096, 70000),    # radix (3 digits)
    (300, 1),         # all ids equal
    pytest.param(512, 300, marks=pytest.mark.slow),   # radix (2 digits)
])
def test_counting_order_matches_stable_argsort(B, nbuckets):
    rng = np.random.default_rng(B * 31 + nbuckets)
    ids = jnp.asarray(rng.integers(0, nbuckets, B), jnp.int32)
    got = jax.jit(lambda x: counting_order(x, nbuckets))(ids)
    want = jnp.argsort(ids, stable=True)
    assert (got == want).all()
    # the helpers built on it: auto_order picks an algorithm but must be
    # bit-identical; invert_perm must invert any permutation sort-free
    from windflow_tpu.windows.grouping import auto_order, invert_perm
    assert (auto_order(ids, nbuckets) == want).all()
    assert (invert_perm(got) == jnp.argsort(got)).all()


@pytest.mark.slow  # ~10s: the skew/sorted-input matrix rides the
# nightly leg (wfverify-round headroom pass); the parametrized
# stable-argsort equality above keeps counting_order covered in tier-1
def test_counting_order_skewed_and_sorted_inputs():
    for ids_np in [
        np.zeros(500, np.int32),                       # one hot bucket
        np.arange(500, dtype=np.int32) % 3,            # round-robin
        np.sort(np.random.default_rng(0).integers(0, 9, 500)).astype(
            np.int32),                                 # already grouped
        np.concatenate([np.full(499, 7, np.int32), [0]]),  # tail singleton
    ]:
        ids = jnp.asarray(ids_np)
        got = counting_order(ids, int(ids_np.max()) + 1)
        want = jnp.argsort(ids, stable=True)
        assert (got == want).all()


# -- kernel-level equivalence ----------------------------------------------

def _random_batches(rng, cap, K, n_batches, ts_jitter=False):
    for i in range(n_batches):
        n = rng.integers(cap // 2, cap + 1)
        keys = rng.integers(0, K + 2, cap)      # includes out-of-range keys
        vals = rng.random(cap).astype(np.float32)
        ts = np.arange(cap, dtype=np.int64) * 1000 + i * cap * 1000
        if ts_jitter:
            ts = ts + rng.integers(-2000, 2000, cap)
        valid = np.zeros(cap, bool)
        valid[:n] = True
        yield (jnp.asarray(keys, jnp.int32), jnp.asarray(vals),
               jnp.asarray(ts), jnp.asarray(valid))


# non-commutative, associative: 2x2 matrix product over (value, 1) lifts
def _mat_lift(x):
    v = x["v"]
    one = jnp.ones((), v.dtype)
    return {"a": one, "b": v, "c": jnp.zeros((), v.dtype), "d": one}


def _mat_comb(m1, m2):
    return {"a": m1["a"] * m2["a"] + m1["b"] * m2["c"],
            "b": m1["a"] * m2["b"] + m1["b"] * m2["d"],
            "c": m1["c"] * m2["a"] + m1["d"] * m2["c"],
            "d": m1["c"] * m2["b"] + m1["d"] * m2["d"]}


@pytest.mark.parametrize("comb_kind", ["sum", "noncommutative"])
def test_cb_step_bitwise_equal_across_groupings(comb_kind):
    cap, K, P, R, D = 96, 5, 4, 4, 1
    if comb_kind == "sum":
        lift, comb = (lambda x: x["v"]), (lambda a, b: a + b)
    else:
        lift, comb = _mat_lift, _mat_comb
    key_fn = lambda x: x["k"]
    steps = {
        g: jax.jit(make_ffat_step(cap, K, P, R, D, lift, comb, key_fn,
                                  grouping=g))
        for g in ("rank_scatter", "argsort")
    }
    spec = agg_spec_for(lift, {"k": jnp.zeros((cap,), jnp.int32),
                               "v": jnp.zeros((cap,), jnp.float32)})
    states = {g: make_ffat_state(spec, K, R) for g in steps}
    rngs = {g: np.random.default_rng(7) for g in steps}
    for _ in range(4):
        outs = {}
        for g, step in steps.items():
            keys, vals, ts, valid = next(
                _random_batches(rngs[g], cap, K, 1))
            states[g], out, fired, out_ts = step(
                states[g], {"k": keys, "v": vals}, ts, valid)
            outs[g] = (out, fired, out_ts)
        for (a, b) in zip(jax.tree.leaves(outs["rank_scatter"]),
                          jax.tree.leaves(outs["argsort"])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        for (a, b) in zip(jax.tree.leaves(states["rank_scatter"]),
                          jax.tree.leaves(states["argsort"])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("comb_kind", ["sum", "noncommutative"])
def test_tb_step_bitwise_equal_across_groupings(comb_kind):
    cap, K, P_usec, R, D, NP = 96, 5, 1000, 4, 2, 32
    if comb_kind == "sum":
        lift, comb = (lambda x: x["v"]), (lambda a, b: a + b)
    else:
        lift, comb = _mat_lift, _mat_comb
    key_fn = lambda x: x["k"]
    steps = {
        g: jax.jit(make_ffat_tb_step(cap, K, P_usec, R, D, NP, lift, comb,
                                     key_fn, grouping=g))
        for g in ("rank_scatter", "argsort")
    }
    spec = agg_spec_for(lift, {"k": jnp.zeros((cap,), jnp.int32),
                               "v": jnp.zeros((cap,), jnp.float32)})
    states = {g: make_ffat_tb_state(spec, K, NP) for g in steps}
    rngs = {g: np.random.default_rng(11) for g in steps}
    for i in range(4):
        outs = {}
        for g, step in steps.items():
            keys, vals, ts, valid = next(
                _random_batches(rngs[g], cap, K, 1, ts_jitter=True))
            wm = jnp.int64((i + 1) * cap * 1000 // P_usec - R)
            states[g], out, fired, out_ts, n_adv = step(
                states[g], {"k": keys, "v": vals}, ts, valid, wm)
            outs[g] = (out, fired, out_ts, n_adv)
        for (a, b) in zip(jax.tree.leaves(outs["rank_scatter"]),
                          jax.tree.leaves(outs["argsort"])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        for (a, b) in zip(jax.tree.leaves(states["rank_scatter"]),
                          jax.tree.leaves(states["argsort"])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_cb_step_scatter_add_fast_path_matches_sorted():
    """sum_like + rank_scatter takes the scatter-add bypass (no
    permutation); with integer-valued floats addition is exact in any
    order, so outputs and state must EQUAL the argsort path's."""
    cap, K, P, R, D = 96, 5, 4, 4, 1
    lift, comb = (lambda x: x["v"]), (lambda a, b: a + b)
    key_fn = lambda x: x["k"]
    steps = {
        g: jax.jit(make_ffat_step(cap, K, P, R, D, lift, comb, key_fn,
                                  sum_like=True, grouping=g))
        for g in ("rank_scatter", "argsort")
    }
    spec = agg_spec_for(lift, {"k": jnp.zeros((cap,), jnp.int32),
                               "v": jnp.zeros((cap,), jnp.float32)})
    states = {g: make_ffat_state(spec, K, R) for g in steps}
    rng = np.random.default_rng(23)
    for _ in range(5):
        n = rng.integers(cap // 2, cap + 1)
        keys = rng.integers(0, K + 2, cap)
        vals = rng.integers(0, 1000, cap).astype(np.float32)
        valid = np.zeros(cap, bool)
        valid[:n] = True
        batch = ({"k": jnp.asarray(keys, jnp.int32),
                  "v": jnp.asarray(vals)},
                 jnp.asarray(np.arange(cap, dtype=np.int64)),
                 jnp.asarray(valid))
        outs = {}
        for g, step in steps.items():
            states[g], out, fired, out_ts = step(states[g], *batch)
            outs[g] = (out, fired, out_ts)
        for (a, b) in zip(jax.tree.leaves(outs["rank_scatter"]),
                          jax.tree.leaves(outs["argsort"])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        for (a, b) in zip(jax.tree.leaves(states["rank_scatter"]),
                          jax.tree.leaves(states["argsort"])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_cb_step_scatter_add_wide_keyspace():
    """K > one radix digit (256) still takes the scatter-add path (the
    rank pass is a single counting sweep whatever K is; gate is 4096)."""
    cap, K, P, R, D = 128, 300, 4, 4, 1
    lift, comb = (lambda x: x["v"]), (lambda a, b: a + b)
    key_fn = lambda x: x["k"]
    steps = {
        g: jax.jit(make_ffat_step(cap, K, P, R, D, lift, comb, key_fn,
                                  sum_like=True, grouping=g))
        for g in ("rank_scatter", "argsort")
    }
    spec = agg_spec_for(lift, {"k": jnp.zeros((cap,), jnp.int32),
                               "v": jnp.zeros((cap,), jnp.float32)})
    states = {g: make_ffat_state(spec, K, R) for g in steps}
    rng = np.random.default_rng(31)
    for _ in range(3):
        keys = rng.integers(0, K, cap)
        vals = rng.integers(0, 100, cap).astype(np.float32)
        batch = ({"k": jnp.asarray(keys, jnp.int32), "v": jnp.asarray(vals)},
                 jnp.asarray(np.arange(cap, dtype=np.int64)),
                 jnp.ones(cap, bool))
        outs = {}
        for g, step in steps.items():
            states[g], out, fired, out_ts = step(states[g], *batch)
            outs[g] = (out, fired, out_ts)
        for (a, b) in zip(jax.tree.leaves(outs["rank_scatter"]),
                          jax.tree.leaves(outs["argsort"])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_cb_step_scatter_add_float_tolerance():
    """Random floats: scatter-add order may differ, so results are close,
    not bitwise (the psum tolerance the declaration implies)."""
    cap, K, P, R, D = 128, 7, 4, 8, 2
    lift, comb = (lambda x: x["v"]), (lambda a, b: a + b)
    key_fn = lambda x: x["k"]
    steps = {
        g: jax.jit(make_ffat_step(cap, K, P, R, D, lift, comb, key_fn,
                                  sum_like=True, grouping=g))
        for g in ("rank_scatter", "argsort")
    }
    spec = agg_spec_for(lift, {"k": jnp.zeros((cap,), jnp.int32),
                               "v": jnp.zeros((cap,), jnp.float32)})
    states = {g: make_ffat_state(spec, K, R) for g in steps}
    rng = np.random.default_rng(29)
    for i in range(4):
        keys = rng.integers(0, K, cap)
        vals = rng.random(cap).astype(np.float32)
        batch = ({"k": jnp.asarray(keys, jnp.int32), "v": jnp.asarray(vals)},
                 jnp.asarray(np.arange(cap, dtype=np.int64)),
                 jnp.ones(cap, bool))
        outs = {}
        for g, step in steps.items():
            states[g], out, fired, out_ts = step(states[g], *batch)
            outs[g] = (out, fired)
        np.testing.assert_array_equal(np.asarray(outs["rank_scatter"][1]),
                                      np.asarray(outs["argsort"][1]))
        np.testing.assert_allclose(
            np.asarray(outs["rank_scatter"][0]["value"]),
            np.asarray(outs["argsort"][0]["value"]), rtol=1e-5, atol=1e-4)


def test_tb_step_scatter_add_matches_grouped():
    """TB sum_like placement (sort-free scatter-add into the pane ring):
    integer-valued floats make addition order-exact, so outputs and state
    must EQUAL the grouped path's across batches with late/out-of-order
    timestamps."""
    cap, K, P_usec, R, D, NP = 96, 5, 1000, 4, 2, 32
    lift, comb = (lambda x: x["v"]), (lambda a, b: a + b)
    key_fn = lambda x: x["k"]
    steps = {
        sl: jax.jit(make_ffat_tb_step(cap, K, P_usec, R, D, NP, lift, comb,
                                      key_fn, sum_like=sl))
        for sl in (True, False)
    }
    spec = agg_spec_for(lift, {"k": jnp.zeros((cap,), jnp.int32),
                               "v": jnp.zeros((cap,), jnp.float32)})
    states = {sl: make_ffat_tb_state(spec, K, NP) for sl in steps}
    rng = np.random.default_rng(41)
    for i in range(5):
        n = rng.integers(cap // 2, cap + 1)
        keys = rng.integers(0, K + 2, cap)
        vals = rng.integers(0, 500, cap).astype(np.float32)
        ts = (np.arange(cap, dtype=np.int64) * 1000 + i * cap * 1000
              + rng.integers(-3000, 3000, cap))
        valid = np.zeros(cap, bool)
        valid[:n] = True
        wm = jnp.int64((i + 1) * cap - R)
        batch = ({"k": jnp.asarray(keys, jnp.int32), "v": jnp.asarray(vals)},
                 jnp.asarray(ts), jnp.asarray(valid))
        outs = {}
        for sl, step in steps.items():
            states[sl], out, fired, out_ts, n_adv = step(
                states[sl], *batch, wm)
            outs[sl] = (out, fired, out_ts, n_adv)
        # fired mask + non-value lanes must match exactly; value lanes
        # only where fired (non-fired rows carry path-dependent garbage,
        # gated by `fired` for every consumer)
        f_t, f_f = np.asarray(outs[True][1]), np.asarray(outs[False][1])
        np.testing.assert_array_equal(f_t, f_f)
        np.testing.assert_array_equal(np.asarray(outs[True][3]),
                                      np.asarray(outs[False][3]))
        for name in outs[True][0]:
            for la, lb in zip(jax.tree.leaves(outs[True][0][name]),
                              jax.tree.leaves(outs[False][0][name])):
                la, lb = np.asarray(la), np.asarray(lb)
                m = f_f.reshape(f_f.shape + (1,) * (la.ndim - 1))
                np.testing.assert_array_equal(np.where(m, la, 0),
                                              np.where(m, lb, 0))
        np.testing.assert_array_equal(
            np.where(f_f, np.asarray(outs[True][2]), 0),
            np.where(f_f, np.asarray(outs[False][2]), 0))
        # state equality is masked for "cells": the grouped path leaves
        # stale values in cell_valid==False slots where scatter-add
        # writes zeros — semantically identical (readers gate on
        # cell_valid); every other field must match exactly
        cv = np.asarray(states[False]["cell_valid"])
        for name in states[False]:
            a, b = states[True][name], states[False][name]
            if name == "cells":
                for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
                    la, lb = np.asarray(la), np.asarray(lb)
                    np.testing.assert_array_equal(
                        np.where(cv.reshape(cv.shape + (1,) * (la.ndim - 2)),
                                 la, 0),
                        np.where(cv.reshape(cv.shape + (1,) * (lb.ndim - 2)),
                                 lb, 0))
            else:
                for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
                    np.testing.assert_array_equal(np.asarray(la),
                                                  np.asarray(lb))
