"""Session windows on the device (``windows/session_tpu.py``) and NEXmark
Q11 (user sessions) at small sizes on the CPU backend: the operator, a
batch at a time, against a per-tuple oracle in every case its contract
names; the benchmark's graph and its closed-form reference against the
same oracle; and what the operator must not do (a 64-bit scatter, a
change to another configuration's step program)."""

import hashlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import windflow_tpu as wf  # noqa: E402
from benchmark import harness  # noqa: E402
from windflow_tpu.batch import WM_NONE, DeviceBatch  # noqa: E402
from windflow_tpu.windows import ffat_kernels as fk  # noqa: E402
from windflow_tpu.windows import session_kernels as sk  # noqa: E402

q11 = harness.load_module("configs", "nexmark_q11")

GAP = 1000


# ---------------------------------------------------------------------------
# the per-tuple oracle: the same semantics, one event at a time
# ---------------------------------------------------------------------------

def oracle(keys, tss, gap=GAP, values=None):
    """Rows ``(key, start, end, count[, sum of values])`` sorted: each
    key's tuples in time order, a new session where the next tuple is
    ``gap`` or more after the last."""
    per_key = {}
    for i, (k, t) in enumerate(zip(np.asarray(keys).tolist(),
                                   np.asarray(tss).tolist())):
        per_key.setdefault(k, []).append((t, i))
    rows = []
    for k, pts in per_key.items():
        cur = None
        for t, i in sorted(pts):
            v = 0 if values is None else int(values[i])
            if cur is not None and t - cur[1] < gap:
                cur[1], cur[2], cur[3] = t, cur[2] + 1, cur[3] + v
            else:
                if cur is not None:
                    rows.append((k, cur[0], cur[1] + gap, cur[2], cur[3]))
                cur = [t, t, 1, v]
        rows.append((k, cur[0], cur[1] + gap, cur[2], cur[3]))
    rows.sort()
    return rows if values is not None else [r[:4] for r in rows]


# ---------------------------------------------------------------------------
# the operator, a batch at a time
# ---------------------------------------------------------------------------

def count_op(K, gap=GAP, lateness=0):
    return (wf.Session_WindowsTPU_Builder(lambda e: jnp.int64(1),
                                          lambda a, b: a + b)
            .withGap(gap).withKeyBy(lambda e: e["k"]).withMaxKeys(K)
            .withLateness(lateness).build())


def feed(op, B, keys, tss, wm=None):
    """One batch of capacity ``B`` through the operator's step; the rows
    it closed, sorted.  ``wm``: the batch's watermark (its newest stamp
    where not given)."""
    n = len(keys)
    assert n <= B
    pad = lambda a, dt: jnp.asarray(  # noqa: E731
        np.r_[np.asarray(a, dt), np.zeros(B - n, dt)])
    if wm is None:
        wm = int(max(tss)) if n else WM_NONE
    batch = DeviceBatch({"k": pad(keys, np.int32)}, pad(tss, np.int64),
                        jnp.asarray(np.arange(B) < n), watermark=wm)
    return rows_of(op._step(batch))


def rows_of(out):
    ok = np.asarray(out.valid)
    p = {k: np.asarray(v)[ok] for k, v in out.payload.items()}
    assert np.array_equal(np.asarray(out.ts)[ok], p["end"] - 1)
    return sorted(zip(p["key"].tolist(), p["start"].tolist(),
                      p["end"].tolist(), p["value"].tolist()))


def stream(op, B, keys, tss, shuffle=None):
    """The whole stream through the operator in batches of ``B`` tuples
    and the end-of-stream flush; all rows, sorted."""
    rows = []
    for lo in range(0, len(keys), B):
        k, t = keys[lo:lo + B], tss[lo:lo + B]
        wm = int(t.max())
        if shuffle is not None:
            p = shuffle.permutation(len(k))
            k, t = k[p], t[p]
        rows += feed(op, B, k, t, wm=wm)
    for out in op._flush():
        rows += rows_of(out)
    return sorted(rows)


def bursts(rng, n_keys, n, burst=40, quiet=3 * GAP):
    """A stream in time order: keys bid in bursts (steps under the gap),
    then fall silent for more than the gap, as Q11's bidders do."""
    t = np.cumsum(rng.integers(1, 8, n)).astype(np.int64)
    t += (np.arange(n) // (burst * 4)) * quiet
    k = (rng.integers(0, 6, n) + (np.arange(n) // burst) * 3) % n_keys
    return k.astype(np.int32), t


@pytest.mark.parametrize("B", [64, 256])
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_bursts_against_the_oracle(seed, B):
    rng = np.random.default_rng(seed)
    k, t = bursts(rng, 50, 2000)
    op = count_op(64)
    assert stream(op, B, k, t) == oracle(k, t)
    st = op.dump_stats()
    assert st["Sessions_open"] == 0 and st["Late_tuples_dropped"] == 0
    assert st["Sessions_closed"] == len(oracle(k, t))
    assert st["Sessions_closed_early"] == 0


@pytest.mark.parametrize("seed", [4, 5])
def test_any_order_inside_a_batch(seed):
    rng = np.random.default_rng(seed)
    k, t = bursts(rng, 50, 1500)
    assert stream(count_op(64), 128, k, t, shuffle=rng) == oracle(k, t)


def test_a_session_over_many_batches():
    # key 3 bids every 10 usec through nine batches; key 5 once
    t = np.arange(9 * 32, dtype=np.int64) * 10
    k = np.full(len(t), 3, np.int32)
    k[40] = 5
    op = count_op(8)
    rows = []
    for lo in range(0, len(t), 32):
        rows += feed(op, 32, k[lo:lo + 32], t[lo:lo + 32])
    # key 5's session ended at 400 + GAP: fired by the watermark 1590
    assert rows == [(5, 400, 400 + GAP, 1)]
    assert int(jnp.sum(op._state["open"])) == 1
    [last] = op._flush()
    assert rows_of(last) == [(3, 0, int(t[-1]) + GAP, len(t) - 1)]
    assert op._flush() == []                      # once


def test_several_sessions_of_one_key_inside_one_batch():
    # key 1: three runs cut by the gap; key 2: one run; in one batch
    t = np.array([0, 10, 20, 1500, 1510, 4000, 5, 15], np.int64)
    k = np.array([1, 1, 1, 1, 1, 1, 2, 2], np.int32)
    op = count_op(4)
    got = feed(op, 16, k, t, wm=4000)
    # the watermark 4000 passed every window but the last run's
    assert got == [(1, 0, 20 + GAP, 3), (1, 1500, 1510 + GAP, 2),
                   (2, 5, 15 + GAP, 2)]
    [rest] = op._flush()
    assert rows_of(rest) == [(1, 4000, 4000 + GAP, 1)]
    assert sorted(got + rows_of(rest)) == oracle(k, t)


@pytest.mark.parametrize("step,sessions", [(GAP - 1, 1), (GAP, 2),
                                           (GAP + 1, 2)])
@pytest.mark.parametrize("split", [False, True])
def test_a_bid_exactly_the_gap_later_starts_a_session(step, sessions, split):
    """Beam's rule, inside one batch and across two."""
    t = np.array([100, 100 + step], np.int64)
    k = np.array([7, 7], np.int32)
    op = count_op(8)
    if split:
        rows = feed(op, 4, k[:1], t[:1]) + feed(op, 4, k[1:], t[1:])
    else:
        rows = feed(op, 4, k, t)
    rows += [r for o in op._flush() for r in rows_of(o)]
    assert sorted(rows) == oracle(k, t) and len(rows) == sessions
    if sessions == 2:
        assert rows[0][2] <= rows[1][1]           # they do not overlap


def test_a_key_returns_after_its_session_fired():
    op = count_op(4)
    assert feed(op, 8, [2, 2], [0, 50]) == []
    assert feed(op, 8, [3], [50 + GAP]) == [(2, 0, 50 + GAP, 2)]
    assert feed(op, 8, [2, 2], [5000, 5010]) == [(3, 50 + GAP,
                                                  50 + 2 * GAP, 1)]
    [rest] = op._flush()
    assert rows_of(rest) == [(2, 5000, 5010 + GAP, 2)]
    assert op.dump_stats()["Sessions_closed_early"] == 0


def test_an_idle_stretch_closes_every_session_and_holds_the_overflow():
    """300 keys with an open session, a batch capacity of 64: after an
    idle stretch of event time one tuple's watermark closes them all; the
    step emits what its output holds, in key order, holds the rest back
    in the state and says how many; later steps and the flush emit them,
    and the watermark handed on waits for them."""
    K, B = 300, 64
    op = count_op(K)
    keys = np.arange(K, dtype=np.int32)
    for lo in range(0, K, B):
        assert feed(op, B, keys[lo:lo + B], np.full(len(keys[lo:lo + B]),
                                                    10 + lo)) == []
    assert sk.session_out_capacity(B, K) == B
    far = 50 * GAP
    out1 = op._step(DeviceBatch(
        {"k": jnp.zeros(B, jnp.int32)}, jnp.full(B, far, jnp.int64),
        jnp.asarray(np.arange(B) < 1), watermark=far))
    first = rows_of(out1)
    # key 0's old session is displaced by the new tuple; 63 more fit
    assert len(first) == B and first[0] == (0, 10, 10 + GAP, 1)
    assert [r[0] for r in first] == list(range(B))
    assert int(op._prev_held) == K - B
    # nothing has been said downstream about the watermark that closed
    # them: batch 1 carries the one before it
    assert out1.watermark < far
    wms, rows = [], list(first)
    for _ in range(4):
        out = op._step(DeviceBatch(
            {"k": jnp.zeros(B, jnp.int32)}, jnp.full(B, far, jnp.int64),
            jnp.zeros(B, bool), watermark=far))
        rows += rows_of(out)
        wms.append(out.watermark)
    assert int(op._prev_held) == 0
    # held back while rows were: the far watermark leaves only with the
    # batch after the step that emptied the state of ready rows
    assert all(w < far for w in wms[:-1])
    for o in op._flush():
        rows += rows_of(o)
    every = oracle(np.r_[keys, 0], np.r_[10 + (keys // B) * B, far])
    assert sorted(rows) == every and len(rows) == K + 1
    st = op.dump_stats()
    assert st["Session_rows_held_back"] == (K - B) + (K - 2 * B) \
        + (K - 3 * B) + (K - 4 * B)
    assert st["Sessions_closed"] == K + 1 and st["Sessions_open"] == 0


def test_the_watermark_handed_on_trails_by_one_step():
    op = count_op(4)
    outs = [op._step(DeviceBatch(
        {"k": jnp.zeros(4, jnp.int32)}, jnp.full(4, t, jnp.int64),
        jnp.asarray([True, False, False, False]), watermark=t))
        for t in (100, 200, 300)]
    assert [o.watermark for o in outs] == [WM_NONE, 100, 200]


def test_the_flush_takes_as_many_passes_as_it_needs():
    K, B = 200, 32
    op = count_op(K)
    keys = np.arange(K, dtype=np.int32)
    for lo in range(0, K, B):
        feed(op, B, keys[lo:lo + B], np.full(len(keys[lo:lo + B]), 7),
             wm=0)
    outs = op._flush()
    assert len(outs) == -(-K // B)
    rows = sorted(r for o in outs for r in rows_of(o))
    assert rows == [(int(k), 7, 7 + GAP, 1) for k in keys]


def test_a_late_tuple_is_counted_and_dropped():
    op = count_op(4, lateness=100)
    assert feed(op, 4, [1, 1], [1000, 1010], wm=1010) == []
    # 905 is older than 1010 - 100: late; 915 is inside the lateness and
    # joins the open session although it lies before its first tuple
    assert feed(op, 4, [1, 1, 2], [905, 915, 1020], wm=1020) == []
    assert op.num_dropped_tuples() == 1
    rows = sorted(r for o in op._flush() for r in rows_of(o))
    assert rows == [(1, 915, 1010 + GAP, 3), (2, 1020, 1020 + GAP, 1)]
    assert op.dump_stats()["Late_tuples_dropped"] == 1


def test_lateness_holds_sessions_open():
    op = count_op(4, lateness=500)
    assert feed(op, 4, [1], [0], wm=0) == []
    # the window [0, GAP) has ended by the clock, not by clock - lateness
    assert feed(op, 4, [2], [GAP + 100], wm=GAP + 100) == []
    assert feed(op, 4, [2], [GAP + 600], wm=GAP + 600) == [(1, 0, GAP, 1)]


def test_a_session_displaced_before_the_watermark_is_counted():
    """One open session a key: a tuple the gap or more after it, while
    the watermark lags behind its end, closes it then, and says so."""
    op = count_op(4)
    assert feed(op, 4, [1], [100], wm=0) == []
    assert feed(op, 4, [1], [100 + GAP], wm=50) == [(1, 100, 100 + GAP, 1)]
    assert op.dump_stats()["Sessions_closed_early"] == 1
    # with the watermark past its end the same close is not early
    assert feed(op, 4, [1], [100 + 3 * GAP], wm=100 + 3 * GAP) \
        == [(1, 100 + GAP, 100 + 2 * GAP, 1)]
    assert op.dump_stats()["Sessions_closed_early"] == 1


def test_keys_outside_the_key_space_are_masked():
    op = count_op(4)
    feed(op, 8, [0, 3, 4, -1, 99], [1, 2, 3, 4, 5])
    rows = sorted(r for o in op._flush() for r in rows_of(o))
    assert [r[0] for r in rows] == [0, 3]


def test_a_generic_combiner_carries_a_record():
    """``comb`` is no monoid anyone declared: the session's count, the
    sum of its values, its largest value and the time it was bid."""
    def lift(e):
        return {"n": jnp.int64(1), "sum": e["v"].astype(jnp.int64),
                "top": e["v"], "at": e["t"]}

    def comb(a, b):
        b_wins = b["top"] > a["top"]
        return {"n": a["n"] + b["n"], "sum": a["sum"] + b["sum"],
                "top": jnp.where(b_wins, b["top"], a["top"]),
                "at": jnp.where(b_wins, b["at"], a["at"])}

    rng = np.random.default_rng(9)
    k, t = bursts(rng, 40, 1200)
    v = rng.permutation(len(k)).astype(np.int32)       # distinct values
    B, K = 128, 64
    op = (wf.Session_WindowsTPU_Builder(lift, comb).withGap(GAP)
          .withKeyBy(lambda e: e["k"]).withMaxKeys(K).build())
    got = []
    for lo in list(range(0, len(k), B)) + [None]:
        if lo is None:
            outs = op._flush()
        else:
            n = len(k[lo:lo + B])
            pad = lambda a: jnp.asarray(  # noqa: E731
                np.r_[a[lo:lo + B], np.zeros(B - n, a.dtype)])
            outs = [op._step(DeviceBatch(
                {"k": pad(k), "v": pad(v), "t": pad(t)}, pad(t),
                jnp.asarray(np.arange(B) < n),
                watermark=int(t[lo:lo + B].max())))]
        for o in outs:
            ok = np.asarray(o.valid)
            p = jax.tree.map(lambda a: np.asarray(a)[ok], o.payload)
            got += list(zip(p["key"].tolist(), p["start"].tolist(),
                            p["end"].tolist(), p["value"]["n"].tolist(),
                            p["value"]["sum"].tolist(),
                            p["value"]["top"].tolist(),
                            p["value"]["at"].tolist()))
    exp = oracle(k, t, values=v)
    assert sorted(r[:5] for r in got) == exp
    at = dict(zip(v.tolist(), t.tolist()))
    for key, start, end, n, _s, top, when in got:
        inside = (k == key) & (t >= start) & (t < end)
        assert top == v[inside].max() and when == at[top]
        assert n == inside.sum()


def test_snapshot_and_restore_mid_session():
    rng = np.random.default_rng(11)
    k, t = bursts(rng, 50, 1600)
    B = 128
    whole = stream(count_op(64), B, k, t)
    op = count_op(64)
    rows = []
    cut = 5 * B
    for lo in range(0, cut, B):
        rows += feed(op, B, k[lo:lo + B], t[lo:lo + B])
    assert int(jnp.sum(op._state["open"])) > 0         # mid-session
    blob = op.snapshot_state()
    assert blob["kind"] == "session_tpu"
    assert all(isinstance(a, np.ndarray)
               for a in jax.tree.leaves(blob["state"]))
    import pickle
    blob = pickle.loads(pickle.dumps(blob))
    again = count_op(64)
    assert again.snapshot_state() is None              # never stepped
    again.restore_state(blob)
    rows += stream(again, B, k[cut:], t[cut:])
    assert sorted(rows) == whole == oracle(k, t)


# ---------------------------------------------------------------------------
# the program
# ---------------------------------------------------------------------------

def _step_args(B, K):
    S = jax.ShapeDtypeStruct
    step = sk.make_session_step(B, K, GAP, lambda e: jnp.int64(1),
                                lambda a, b: a + b, lambda e: e["k"])
    state = jax.eval_shape(
        lambda: sk.make_session_state(jnp.zeros((), jnp.int64), K))
    return step, (state, {"k": S((B,), np.int32)}, S((B,), np.int64),
                  S((B,), np.bool_), S((), np.int64))


def _scatters(jaxpr):
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name.startswith("scatter"):
            out.append(eqn)
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    out.extend(_scatters(sub))
    return out


def test_the_step_has_no_64_bit_scatter():
    """Closed rows and runs reach their places by 32-bit scatters of
    indices and gathers (a 64-bit scatter costs ten 32-bit ones on a
    v5e); the count lane is int64 all the same."""
    step, args = _step_args(256, 512)
    closed = jax.make_jaxpr(step)(*args)
    found = _scatters(closed.jaxpr)
    assert len(found) >= 3                  # both branches, both phases
    wide = [e for e in found
            if any(np.dtype(e.invars[i].aval.dtype).itemsize >= 8
                   for i in (0, 2))]
    assert not wide, wide
    assert not [e for e in found if "add" in e.primitive.name]


@pytest.mark.parametrize("B,K", [(64, 300), (1024, 16), (262144, 212992)])
def test_the_output_batch_is_the_input_batchs(B, K):
    assert sk.session_out_capacity(B, K) == B
    if B <= 1024:
        step, args = _step_args(B, K)
        _st, out, fired, out_ts, held = jax.eval_shape(step, *args)
        assert fired.shape == out_ts.shape == out["key"].shape == (B,)
        assert out["key"].dtype == np.int32 and held.shape == ()
        assert {k: v.dtype for k, v in out.items()} == {
            "key": np.int32, "start": np.int64, "end": np.int64,
            "value": np.int64}


def test_wide_batches_take_the_64_bit_ordering():
    """A batch that spans more event time than 31 bits of microseconds
    sorts on int64 stamps: same rows."""
    t = np.array([0, 5, 1 << 33, (1 << 33) + GAP - 1, 1 << 40], np.int64)
    k = np.array([1, 1, 1, 1, 2], np.int32)
    op = count_op(4)
    rows = feed(op, 8, k, t, wm=0)
    rows += [r for o in op._flush() for r in rows_of(o)]
    assert sorted(rows) == oracle(k, t)


def _lowered_sha(step, *args):
    text = jax.jit(step).lower(*args).as_text()
    return hashlib.sha256(text.encode()).hexdigest()


def test_other_configurations_steps_are_the_parents():
    """YSB's (dense placement) and Q5's (narrow scatter) window steps
    lower to the text they had when last changed on purpose (PR 35: the
    ring's advances under conditionals, ``n_ring_advances`` in the
    state; before it the text of 7f08677, unmoved through PR 32 to 34;
    this backend): the session operator adds its own program and touches
    no other."""
    S = jax.ShapeDtypeStruct

    def tb(B, K, R, D, NP):
        step = fk.make_ffat_tb_step(
            B, K, 1000, R, D, NP, lambda e: e["one"], lambda a, b: a + b,
            lambda e: e["k"], monoid="sum", drop_tainted=True)
        state = jax.eval_shape(lambda: fk.make_ffat_tb_state(
            jnp.zeros((), jnp.int64), K, NP))
        return step, state, {"k": S((B,), np.int32),
                             "one": S((B,), np.int64)}, \
            S((B,), np.int64), S((B,), np.bool_), S((), np.int64)

    assert _lowered_sha(*tb(4096, 100, 1, 1, 65)) == PARENT_SHA["ysb"]
    assert _lowered_sha(*tb(1024, 4096, 2, 1, 66)) == PARENT_SHA["q5"]


PARENT_SHA = {
    "ysb": ("921982b686d6954cd1aa29dc6e6e3443"
            "aa30fb0d35819e78d9d00527e5bf95c6"),
    "q5": ("d31879cb37ee0797dbc4801c64dec94a"
           "658713baaf294438e4b40ac4fb26e066"),
}


# ---------------------------------------------------------------------------
# the graph: public builders, default Config()
# ---------------------------------------------------------------------------

def tiny_cfg(**graph):
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "nexmark_q11.json")) as f:
        cfg = json.load(f)
    cfg["graph"].update(dict(batch=512, gap_usec=20_000, max_keys=2048),
                        **graph)
    cfg["stream"].update(ring_batches=8, active_people=4,
                         hot_bidder_stride=8, event_rate=100_000)
    return cfg


def run_q11(rec, cfg, chunk=300):
    got = []

    def chunks():
        for lo in range(0, len(rec), chunk):
            yield rec[lo:lo + chunk].tobytes()

    def sink(c):
        if c is not None:
            got.append({k: np.asarray(v) for k, v in c.cols.items()})

    g = q11.build_graph(cfg, None, chunks, sink)
    g.run()
    cat = lambda n: np.concatenate([b[n] for b in got])  # noqa: E731
    return {n: cat(n) for n in ("key", "wid", "value")}, g


@pytest.fixture(scope="module")
def replayed():
    """The generator's own stream, two and a third passes of a ring,
    through the benchmark's graph."""
    cfg = tiny_cfg()
    ring = q11.make_ring(2**31 + 5, cfg)
    n = len(ring["rec"]) * 7 // 3
    rec = ring["rec"][np.arange(n) % len(ring["rec"])].copy()
    rec["t"] = np.arange(n) * 10                # 100 000 events a second
    got, g = run_q11(rec, cfg)
    return cfg, ring, rec, got, g


def test_the_graph_agrees_with_the_oracle(replayed):
    cfg, _ring, rec, got, _g = replayed
    bid = rec[q11.KIND] == q11.BID
    exp = oracle(rec[q11.BIDDER][bid].astype(np.int64),
                 rec["t"][bid], gap=cfg["graph"]["gap_usec"])
    rows = sorted(zip(got["key"].tolist(), got["wid"].tolist(),
                      got["value"][:, 1].tolist(),
                      got["value"][:, 0].tolist()))
    assert rows == exp and len(rows) > 100


def test_the_closed_form_agrees_with_the_oracle(replayed):
    cfg, ring, rec, got, _g = replayed
    exp = q11.user_sessions(q11._bid_keys(ring), len(rec), 100_000,
                            cfg["graph"]["gap_usec"])
    bid = rec[q11.KIND] == q11.BID
    orc = oracle(rec[q11.BIDDER][bid].astype(np.int64), rec["t"][bid],
                 gap=cfg["graph"]["gap_usec"])
    assert list(zip(exp.key.tolist(), exp.wid.tolist(),
                    exp.value[:, 1].tolist(),
                    exp.value[:, 0].tolist())) == orc
    assert all(c["ok"] for c in q11.compare(cfg, got, exp))


def test_the_graph_is_one_fused_program_a_batch(replayed):
    *_, g = replayed
    st = g.stats()
    ops = {o["Operator_name"]: o for o in st["Operators"]}
    sess = ops["bids_per_session"]
    assert sess["Operator_type"] == "SessionWindowsTPU"
    # the bid filter rides in the session step's program
    assert ops["filter_tpu"]["Fused_into"] == "filter_tpu|bids_per_session"
    assert sess["Sessions_open"] == 0 and sess["Sessions_closed"] > 100
    assert sess["Session_rows_held_back"] == 0
    assert sess["Late_tuples_dropped"] == 0 == st["Dropped_tuples"]
    assert sess["Sessions_closed_early"] == 0
    assert sess["Session_out_capacity"] == 512
    from windflow_tpu.monitoring.jit_registry import default_registry
    names = set(default_registry().snapshot())
    assert "filter_tpu|bids_per_session" in names
    # the end of stream ran the step's own program: nothing compiled there
    assert not [n for n in names if "flush" in n and "session" in n]
    # no scan: the tail keeps per-batch dispatch, and says why
    from windflow_tpu.megastep import tail_kind
    [op] = [o for o in g._operators if o.name == "bids_per_session"]
    kind, why = tail_kind(op)
    assert kind is None and "session" in why
    assert all(e["batches"] == 0 for e in st["Megastep"]["edges"])


@pytest.mark.parametrize("fault", ["touching", "clock", "held_back"])
def test_a_wrong_program_fails_a_check(replayed, fault):
    """Sessions with Flink's touching rule, with a clock rounded to the
    millisecond, and with a row that was held back and never emitted."""
    cfg, ring, rec, got, _g = replayed
    gap = cfg["graph"]["gap_usec"]
    exp = q11.user_sessions(q11._bid_keys(ring), len(rec), 100_000, gap)
    if fault == "held_back":
        wrong = {k: v[1:] for k, v in got.items()}
    else:
        keys = np.where(rec[q11.KIND] == q11.BID, rec[q11.BIDDER], -1) \
            .astype(np.int64)
        tss = rec["t"].astype(np.int64)
        if fault == "clock":
            tss = (tss + 500) // 1000 * 1000
        if fault == "touching":
            # a stream in which some bidder bids exactly the gap later
            keys, tss = keys.copy(), tss.copy()
            first = int(np.flatnonzero(keys >= 0)[0])
            later = int(np.searchsorted(tss, tss[first] + gap))
            assert tss[later] == tss[first] + gap
            keys[first + 1:later + 1] = np.where(
                keys[first + 1:later + 1] == keys[first], -1,
                keys[first + 1:later + 1])
            keys[later] = keys[first]
            exp = q11.Sessions(*_as_rows(q11.sessions_of(keys, tss, gap),
                                         gap))
            k, f, l, c = q11.sessions_of(keys, tss, gap + 1)   # merges
        else:
            k, f, l, c = q11.sessions_of(keys, tss, gap)
        wrong = {"key": k, "wid": f, "value": np.stack([c, l + gap], 1)}
    checks = q11.compare(cfg, wrong, exp)
    assert not all(c["ok"] for c in checks), fault


def _as_rows(sessions, gap):
    k, f, l, c = sessions
    order = np.lexsort((f, k))
    n = len(k)
    return (k[order], f[order], np.stack([c, l + gap], 1)[order],
            np.zeros(n, bool), np.full(n, -1, np.int64))


def test_sessions_that_would_cross_passes_are_refused():
    cfg = tiny_cfg(gap_usec=39_000)      # a pass spans 40.96 ms
    with pytest.raises(ValueError, match="replay period"):
        q11.make_ring(3, cfg)
    ok = tiny_cfg()
    ring = q11.make_ring(3, ok)
    with pytest.raises(ValueError, match="event rate"):
        q11.expected(ok, ring, 100, {"event_rate": 1_000_000})


def test_a_program_without_the_builder_is_refused_at_once(monkeypatch):
    monkeypatch.delattr(wf, "Session_WindowsTPU_Builder")
    with pytest.raises(RuntimeError, match="session-window"):
        q11.build_graph(tiny_cfg(), None, lambda: iter(()), lambda c: None)
    with pytest.raises(RuntimeError, match="session-window"):
        q11.make_ring(1, tiny_cfg())


def test_the_operator_refuses_a_mesh_and_more_replicas():
    from windflow_tpu.parallel.mesh import make_mesh
    op = count_op(8)
    op.mesh = make_mesh(4)
    with pytest.raises(wf.WindFlowError, match="mesh"):
        op.build_replicas(wf.ExecutionMode.DEFAULT, wf.TimePolicy.EVENT)
    with pytest.raises(wf.WindFlowError, match="one replica"):
        (wf.Session_WindowsTPU_Builder(lambda e: 1, lambda a, b: a + b)
         .withGap(5).withMaxKeys(4).withParallelism(2).build())
    with pytest.raises(wf.WindFlowError, match="withGap"):
        wf.Session_WindowsTPU_Builder(lambda e: 1,
                                      lambda a, b: a + b).build()
    with pytest.raises(wf.WindFlowError, match="gap"):
        (wf.Session_WindowsTPU_Builder(lambda e: 1, lambda a, b: a + b)
         .withGap(0).build())


def test_a_mesh_graph_refuses_the_operator():
    from windflow_tpu.parallel.mesh import make_mesh
    cfg = tiny_cfg()
    import windflow_tpu.basic as basic
    real = basic.Config

    def meshed(*a, **kw):
        return real(*a, mesh=make_mesh(4), **kw)
    wf.Config, keep = meshed, wf.Config
    try:
        g = q11.build_graph(cfg, None, lambda: iter(()), lambda c: None)
        with pytest.raises(wf.WindFlowError, match="mesh"):
            g.run()
    finally:
        wf.Config = keep


def test_dispatch_span_says_out_cap(replayed, monkeypatch):
    """The operator's ``wf.dispatch`` notes ``out_cap`` although the
    output batch has the input's capacity, and the sink's ``wf.sink.d2h``
    says the lanes it copies."""
    from windflow_tpu.monitoring import recorder
    seen = []
    real = recorder.span

    class Spy:
        def __init__(self, name, kw):
            self.name, self.kw, self.inner = name, dict(kw), real(name, **kw)

        def __enter__(self):
            self.sp = self.inner.__enter__()
            return self

        def note(self, **kw):
            self.kw.update(kw)
            return self.sp.note(**kw)

        def __exit__(self, *a):
            seen.append((self.name, self.kw))
            return self.inner.__exit__(*a)

    import windflow_tpu.ops.sink as sink_mod
    import windflow_tpu.ops.tpu as tpu_mod
    for mod in (sink_mod, tpu_mod):
        monkeypatch.setattr(mod.flightrec, "span",
                            lambda name, **kw: Spy(name, kw))
    cfg, _ring, rec, _got, _g = replayed
    run_q11(rec[:4096], cfg)
    caps = [kw["out_cap"] for n, kw in seen
            if n == "wf.dispatch" and kw.get("op") == "bids_per_session"]
    assert caps and set(caps) == {512}
    assert not [kw for n, kw in seen if n == "wf.dispatch"
                and kw.get("op") == "session_row" and "out_cap" in kw]
    d2h = [kw for n, kw in seen if n == "wf.sink.d2h"]
    assert d2h and all(kw["lanes"] == 512 for kw in d2h)


@pytest.mark.parametrize("n_ready", [10, 64, 65, 700, 1500])
def test_rows_at_the_front_are_gathered_there(n_ready):
    """An output batch of 1024 lanes or more gathers its rows into its
    first sixteenth where they fit there, and whole where not: the same
    rows either way."""
    K, B = 1500, 1024
    assert B >= sk.FRONT_MIN and B // sk.FRONT_DIV == 64
    op = count_op(K)
    keys = np.arange(K, dtype=np.int32)
    for lo in range(0, K, B):
        # the first n_ready keys bid at time 5, the others much later
        t = np.where(keys[lo:lo + B] < n_ready, 5, 50 * GAP)
        assert feed(op, B, keys[lo:lo + B], t, wm=0) == []
    got = feed(op, B, [], [], wm=10 * GAP)
    assert got == [(k, 5, 5 + GAP, 1) for k in range(min(n_ready, B))]
    assert op.dump_stats()["Session_rows_held_back"] == max(0, n_ready - B)
    rest = sorted(r for o in op._flush() for r in rows_of(o))
    assert len(got) + len(rest) == K


def test_a_leaf_wider_than_a_scalar_follows_the_sort_by_gather():
    """Scalar lanes ride the sort; a leaf with a trailing dimension (a
    histogram a session) follows by its permutation: same folds."""
    def lift(e):
        return {"n": jnp.int64(1),
                "hist": jax.nn.one_hot(e["v"] % 4, 4, dtype=jnp.int32)}

    rng = np.random.default_rng(21)
    k, t = bursts(rng, 30, 600)
    v = rng.integers(0, 100, len(k)).astype(np.int32)
    B = 128
    op = (wf.Session_WindowsTPU_Builder(lift, lambda a, b: jax.tree.map(
        jnp.add, a, b)).withGap(GAP).withKeyBy(lambda e: e["k"])
        .withMaxKeys(32).build())
    outs = []
    for lo in range(0, len(k), B):
        n = len(k[lo:lo + B])
        pad = lambda a: jnp.asarray(  # noqa: E731
            np.r_[a[lo:lo + B], np.zeros(B - n, a.dtype)])
        p = rng.permutation(B)              # any order inside a batch
        outs.append(op._step(DeviceBatch(
            {"k": pad(k)[p], "v": pad(v)[p]}, pad(t)[p],
            jnp.asarray(np.arange(B) < n)[p],
            watermark=int(t[lo:lo + B].max()))))
    outs += op._flush()
    rows = 0
    for o in outs:
        ok = np.asarray(o.valid)
        p = jax.tree.map(lambda a: np.asarray(a)[ok], o.payload)
        for key, start, end, n, hist in zip(
                p["key"], p["start"], p["end"], p["value"]["n"],
                p["value"]["hist"]):
            inside = (k == key) & (t >= start) & (t < end)
            assert n == inside.sum()
            assert hist.tolist() == np.bincount(v[inside] % 4,
                                                minlength=4).tolist()
            rows += 1
    assert rows == len(oracle(k, t))
