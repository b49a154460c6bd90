"""The interval join on the device (``windows/join_tpu.py``) and NEXmark
Q9 (winning bids) at small sizes on the CPU backend: the operator against
a per-tuple oracle, a batch at a time and through ``PipeGraph``, in every
case its contract names; the benchmark's graph and its closed-form
reference against the same oracle; and what the operator must not do (a
scatter, a pass as wide as a key space, a change to another
configuration's step program)."""

import hashlib
import json
import os
import pickle
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
from windflow_tpu.windows import join_kernels as jk  # noqa: E402
from windflow_tpu.windows import session_kernels as sk  # noqa: E402

q9 = harness.load_module("configs", "nexmark_q9")

LANES = ("k", "b", "len", "v", "w")


# ---------------------------------------------------------------------------
# the per-tuple oracle: the same semantics, one event at a time
# ---------------------------------------------------------------------------

class Oracle:
    """Build rows ``(k, t, 1, len, reserve, _)`` and probes ``(k, u, 0,
    _, price, who)``, a step at a time: rows ``(key, start, end, price,
    bid time, who, count)`` and the operator's counters."""

    def __init__(self, lateness=0):
        self.open, self.rows, self.wm, self.lateness = {}, [], None, lateness
        self.n = dict.fromkeys(
            ("late", "opened", "closed", "unmatched", "displaced",
             "matched", "miss_build", "miss_interval", "miss_pred"), 0)

    def _close(self, key):
        s = self.open.pop(key)
        self.n["closed"] += 1
        if s["n"]:
            self.rows.append((key, s["t"], s["end"], s["price"], s["at"],
                              s["who"], s["n"]))
        else:
            self.n["unmatched"] += 1

    def step(self, events, wm):
        """``events``: tuples ``(k, t, is_build, len, v, w)`` in any
        order; ``wm``: the batch's watermark (None: none yet)."""
        live = [e for e in events if self.wm is None or e[1] >= self.wm]
        self.n["late"] += len(events) - len(live)
        for k, t, b, ln, v, w in sorted(live, key=lambda e: (e[1], 1 - e[2])):
            s = self.open.get(k)
            if b:
                if s is not None:
                    self.n["displaced"] += 1
                    self._close(k)
                self.n["opened"] += 1
                self.open[k] = dict(t=t, end=t + max(ln, 0), res=v, n=0,
                                    price=None, at=None, who=None)
            elif s is None or t < s["t"]:
                self.n["miss_build"] += 1
            elif t >= s["end"]:
                self.n["miss_interval"] += 1
            elif v < s["res"]:
                self.n["miss_pred"] += 1
            else:
                self.n["matched"] += 1
                s["n"] += 1
                if s["price"] is None or v > s["price"]:
                    s["price"], s["at"], s["who"] = float(v), t, int(w)
        if wm is not None:
            adj = wm - self.lateness
            self.wm = adj if self.wm is None else max(self.wm, adj)
            for k in [k for k, s in self.open.items() if s["end"] <= self.wm]:
                self._close(k)

    def flush(self):
        for k in list(self.open):
            self._close(k)
        return sorted(self.rows)


def oracle_rows(ev, B=None):
    """All rows of the stream ``ev`` (arrays by lane name + ``t``) fed
    in time order, ``B`` events a step (the rows do not depend on it)."""
    o = Oracle()
    n = len(ev["t"])
    B = B or n
    for lo in range(0, n, B):
        s = slice(lo, lo + B)
        o.step(list(zip(*(ev[x][s].tolist()
                          for x in ("k", "t", "b", "len", "v", "w")))),
               int(ev["t"][s].max()))
    return o.flush(), o


# ---------------------------------------------------------------------------
# the operator, a batch at a time
# ---------------------------------------------------------------------------

def lift(build, probe, ts):
    return {"price": probe["v"], "at": ts, "who": probe["w"]}


def higher(a, b):
    # the left operand stands on a tie: the earlier bid
    b_wins = b["price"] > a["price"]
    return jax.tree.map(lambda x, y: jnp.where(b_wins, y, x), a, b)


def join_op(C=64, lateness=0, match=True, out=None):
    b = (wf.Interval_JoinTPU_Builder(lift, higher)
         .withBuildSide(lambda e: e["b"] == 1)
         .withIntervalLength(lambda e: e["len"])
         .withKeyBy(lambda e: e["k"]).withBuildCapacity(C)
         .withLateness(lateness))
    if match:
        b = b.withMatch(lambda build, probe: probe["v"] >= build["v"])
    if out is not None:
        b = b.withOutputCapacity(out)
    return b.build()


DTYPES = dict(k=np.int32, b=np.int32, len=np.int32, v=np.float32, w=np.int32)


def batch_of(B, ev, wm=None, dtypes=DTYPES):
    n = len(ev["t"])
    assert n <= B
    pad = lambda a, dt: jnp.asarray(  # noqa: E731
        np.r_[np.asarray(a, dt), np.zeros(B - n, dt)])
    if wm is None:
        wm = int(max(ev["t"])) if n else WM_NONE
    return DeviceBatch({x: pad(ev[x], dtypes[x]) for x in LANES},
                       pad(ev["t"], np.int64),
                       jnp.asarray(np.arange(B) < n), watermark=wm)


def events(*rows):
    """``(k, t, is_build, len, v, w)`` tuples to lanes (the stamps and
    the lengths int64: a test may hand the operator wide ones)."""
    cols = list(zip(*rows)) if rows else [()] * 6
    return {x: np.asarray(c, np.int64 if x in ("t", "len") else DTYPES[x])
            for x, c in zip(("k", "t", "b", "len", "v", "w"), cols)}


def rows_of(out):
    ok = np.asarray(out.valid)
    p = jax.tree.map(lambda a: np.asarray(a)[ok], out.payload)
    assert np.array_equal(np.asarray(out.ts)[ok], p["end"] - 1)
    # the rows lie at the front of the output batch
    assert not ok[int(ok.sum()):].any()
    v = p["value"]
    return sorted(zip(p["key"].tolist(), p["start"].tolist(),
                      p["end"].tolist(), v["price"].tolist(),
                      v["at"].tolist(), v["who"].tolist(),
                      p["count"].tolist()))


def feed(op, B, ev, wm=None):
    return rows_of(op._step(batch_of(B, ev, wm)))


def cut(ev, s):
    return {x: a[s] for x, a in ev.items()}


def stream(op, B, ev, shuffle=None):
    """The whole stream through the operator in batches of ``B`` tuples
    and the end-of-stream flush; all rows, sorted."""
    rows = []
    for lo in range(0, len(ev["t"]), B):
        part = cut(ev, slice(lo, lo + B))
        wm = int(part["t"].max())
        if shuffle is not None:
            part = cut(part, shuffle.permutation(len(part["t"])))
        rows += feed(op, B, part, wm=wm)
    for out in op._flush():
        rows += rows_of(out)
    return sorted(rows)


def auctions(rng, n, p_build=0.08, life=300, spread=12):
    """A stream in time order shaped like Q9's: build rows take new keys
    in turn, probes aim at the newest few keys and a few not yet there."""
    t = np.cumsum(rng.integers(1, 4, n)).astype(np.int64)
    b = (rng.random(n) < p_build).astype(np.int32)
    newest = np.cumsum(b)
    k = np.where(b == 1, newest,
                 np.maximum(newest - rng.integers(0, spread, n) + 2, 0))
    return {"k": k.astype(np.int32), "t": t, "b": b,
            "len": rng.integers(1, life, n).astype(np.int32),
            "v": rng.integers(1, 50, n).astype(np.float32),
            "w": rng.integers(0, 1000, n).astype(np.int32)}


STATS = {"Join_build_opened": "opened", "Join_build_closed": "closed",
         "Join_build_unmatched": "unmatched",
         "Join_build_displaced": "displaced",
         "Join_probe_matched": "matched",
         "Join_probe_missed_no_build": "miss_build",
         "Join_probe_missed_interval": "miss_interval",
         "Join_probe_missed_predicate": "miss_pred",
         "Late_tuples_dropped": "late"}


def counters_agree(op, o):
    st = op.dump_stats()
    assert {k: st[k] for k in STATS} == {k: o.n[v] for k, v in STATS.items()}
    return st


@pytest.mark.parametrize("B", [64, 256, 1024])
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_random_streams_against_the_oracle(seed, B):
    """Batches cut at arbitrary points: intervals straddle one and
    several batches (``B`` 64: a life of up to 300 usec spans four)."""
    rng = np.random.default_rng(seed)
    ev = auctions(rng, 2500)
    op = join_op()
    exp, o = oracle_rows(ev, B)
    assert stream(op, B, ev) == exp and len(exp) > 100
    st = counters_agree(op, o)
    assert st["Join_build_open"] == 0 and st["Join_rows_held_back"] == 0
    assert st["Join_build_opened"] == st["Join_build_closed"] \
        == int(ev["b"].sum())
    assert st["Join_build_closed"] - st["Join_build_unmatched"] == len(exp)
    assert st["Join_probe_matched"] == sum(r[-1] for r in exp)
    assert min(st[k] for k in STATS if "missed" in k) > 0


@pytest.mark.parametrize("seed", [4, 5])
def test_any_order_inside_a_batch(seed):
    rng = np.random.default_rng(seed)
    ev = auctions(rng, 1500)
    assert stream(join_op(), 128, ev, shuffle=rng) == oracle_rows(ev)[0]


def test_without_a_predicate_every_probe_inside_matches():
    rng = np.random.default_rng(6)
    ev = auctions(rng, 800)
    got = stream(join_op(match=False), 128, ev)
    ev["v"][ev["b"] == 1] = 0               # a reserve every price meets
    assert got == oracle_rows(ev)[0]


@pytest.mark.parametrize("split", [False, True])
def test_the_interval_is_closed_below_and_open_above(split):
    """A probe at exactly ``t`` is in, one at exactly ``t + length`` is
    out; a probe before its build row finds none; inside one batch and
    across two."""
    ev = events((7, 100, 1, 50, 5, 0),      # [100, 150), reserve 5
                (7, 90, 0, 0, 9, 1),        # before its build row
                (7, 100, 0, 0, 6, 2),       # exactly t: in
                (7, 149, 0, 0, 7, 3),       # the last microsecond: in
                (7, 150, 0, 0, 99, 4),      # exactly t + length: out
                (7, 120, 0, 0, 4, 5),       # under the reserve
                (8, 120, 0, 0, 50, 6))      # a key with no build row
    op = join_op()
    if split:
        order = np.argsort(ev["t"], kind="stable")
        first, second = cut(ev, order[:3]), cut(ev, order[3:])
        rows = feed(op, 8, first, wm=100) + feed(op, 8, second, wm=149)
    else:
        rows = feed(op, 8, ev, wm=149)
    assert rows == []                       # the watermark is at 149
    rows = feed(op, 8, events(), wm=150)
    assert rows == [(7, 100, 150, 7.0, 149, 3, 2)]
    st = op.dump_stats()
    assert (st["Join_probe_matched"], st["Join_probe_missed_predicate"],
            st["Join_probe_missed_no_build"]
            + st["Join_probe_missed_interval"]) == (2, 1, 3)
    assert st["Join_build_open"] == 0 and op._flush() == []


def test_on_a_tie_the_earlier_probe_wins():
    ev = events((1, 10, 1, 100, 1, 0), (1, 20, 0, 0, 30, 7),
                (1, 30, 0, 0, 30, 8), (1, 40, 0, 0, 30, 9),
                (1, 50, 0, 0, 10, 6))
    for B, shuffle in ((8, None), (2, None), (8, np.random.default_rng(0))):
        rows = stream(join_op(), B, ev, shuffle=shuffle)
        assert rows == [(1, 10, 110, 30.0, 20, 7, 4)], B


def test_a_build_row_nothing_matched_leaves_no_row_and_is_counted():
    ev = events((1, 10, 1, 20, 5, 0), (2, 11, 1, 20, 5, 0),
                (2, 15, 0, 0, 4, 1),        # under key 2's reserve
                (3, 16, 1, 20, 5, 0), (3, 17, 0, 0, 5, 2))
    op = join_op()
    assert stream(op, 8, ev) == [(3, 16, 36, 5.0, 17, 2, 1)]
    st = op.dump_stats()
    assert st["Join_build_unmatched"] == 2 and st["Join_build_closed"] == 3


def test_a_key_is_used_again_after_its_row_closed():
    op = join_op()
    assert feed(op, 4, events((5, 0, 1, 10, 1, 0), (5, 5, 0, 0, 3, 1))) == []
    assert feed(op, 4, events((6, 10, 0, 0, 1, 0))) \
        == [(5, 0, 10, 3.0, 5, 1, 1)]
    assert op.dump_stats()["Join_build_open"] == 0      # evicted
    assert feed(op, 4, events((5, 20, 1, 10, 1, 0), (5, 21, 0, 0, 8, 2),
                              (5, 15, 0, 0, 9, 3))) == []
    assert [rows_of(o) for o in op._flush()] \
        == [[(5, 20, 30, 8.0, 21, 2, 1)]]
    st = op.dump_stats()
    # the probes at 10 (key 6) and 15 (key 5) found no build row
    assert st["Join_probe_missed_no_build"] == 2
    assert st["Join_build_displaced"] == 0


@pytest.mark.parametrize("split", [False, True])
def test_a_newer_build_row_displaces_the_open_one(split):
    """One open build row a key: the older fires at once with what it
    had matched, before the watermark passed its end, and is counted."""
    a = events((4, 0, 1, 100, 1, 0), (4, 10, 0, 0, 5, 1))
    b = events((4, 50, 1, 100, 1, 0), (4, 60, 0, 0, 7, 2),
               (9, 20, 1, 100, 1, 0), (9, 50, 1, 10, 1, 0))
    op = join_op()
    if split:
        assert feed(op, 8, a, wm=10) == []
        rows = feed(op, 8, b, wm=60)
    else:
        rows = feed(op, 8, {x: np.r_[a[x], b[x]] for x in a}, wm=60)
    # key 4's first row leaves although the watermark stands at 60 < 100;
    # key 9's first had matched nothing: no row; key 9's second closed
    assert rows == [(4, 0, 100, 5.0, 10, 1, 1)]
    st = op.dump_stats()
    assert st["Join_build_displaced"] == 2
    assert st["Join_build_unmatched"] == 2 and st["Join_build_open"] == 1
    assert [rows_of(o) for o in op._flush()] \
        == [[(4, 50, 150, 7.0, 60, 2, 1)]]


def test_late_rows_are_dropped_and_counted():
    op = join_op(lateness=100)
    assert feed(op, 4, events((1, 1000, 1, 500, 1, 0)), wm=1000) == []
    # 895 is older than 1000 - 100: late, on either side
    ev = events((1, 1010, 0, 0, 5, 1), (1, 895, 0, 0, 9, 2),
                (2, 890, 1, 50, 1, 0), (2, 905, 1, 300, 1, 0))
    assert feed(op, 4, ev, wm=1020) == []
    assert op.num_dropped_tuples() == 2
    rows = sorted(r for o in op._flush() for r in rows_of(o))
    assert rows == [(1, 1000, 1500, 5.0, 1010, 1, 1)]
    st = op.dump_stats()
    assert st["Late_tuples_dropped"] == 2 and st["Join_build_opened"] == 2


def test_lateness_holds_build_rows_open():
    op = join_op(lateness=500)
    assert feed(op, 4, events((1, 0, 1, 100, 1, 0), (1, 5, 0, 0, 2, 1)),
                wm=5) == []
    # [0, 100) has ended by the clock, not by the clock less the lateness
    assert feed(op, 4, events((2, 400, 0, 0, 1, 0)), wm=400) == []
    assert feed(op, 4, events((2, 600, 0, 0, 1, 0)), wm=600) \
        == [(1, 0, 100, 2.0, 5, 1, 1)]


def test_more_closed_rows_than_the_output_holds_are_held_back():
    """40 build rows with a match, a batch capacity of 16: after an idle
    stretch one watermark closes them all; the step emits what its output
    holds, keeps the rest in the carry and says how many; later steps
    emit them, and the watermark handed on waits for them."""
    K, B = 40, 16
    op = join_op(C=64)
    for lo in range(0, K, B // 2):
        ks = range(lo, min(lo + B // 2, K))
        ev = events(*[(k, 10, 1, 50, 1, 0) for k in ks],
                    *[(k, 20, 0, 0, 2 + k, k) for k in ks])
        assert feed(op, B, ev, wm=10) == []
    assert jk.join_out_capacity(B) == B
    assert op.dump_stats()["Join_build_open"] == K
    far = 10_000
    out1 = op._step(batch_of(B, events(), wm=far))
    rows = rows_of(out1)
    assert len(rows) == B and op._held(op._prev_held) == K - B
    # nothing has been said downstream about the watermark that closed
    # them: this batch carries the one before it
    assert out1.watermark < far
    wms = []
    for _ in range(3):
        out = op._step(batch_of(B, events(), wm=far))
        rows += rows_of(out)
        wms.append(out.watermark)
    assert op._held(op._prev_held) == 0
    # the far watermark leaves only with the batch after the step that
    # emptied the carry of ready rows
    assert wms[0] < far and wms[1] < far and wms[2] == far
    assert sorted(rows) == [(k, 10, 60, 2.0 + k, 20, k, 1)
                            for k in range(K)]
    st = op.dump_stats()
    assert st["Join_rows_held_back"] == (K - B) + (K - 2 * B)
    assert st["Join_build_closed"] == K and st["Join_build_open"] == 0
    assert op._flush() == []


def test_the_watermark_handed_on_trails_by_one_step():
    op = join_op()
    outs = [op._step(batch_of(4, events((1, t, 0, 0, 1, 0)), wm=t))
            for t in (100, 200, 300)]
    assert [o.watermark for o in outs] == [WM_NONE, 100, 200]


def test_the_flush_takes_as_many_passes_as_it_needs():
    K, B = 50, 16
    op = join_op(C=64)
    for lo in range(0, K, B // 2):
        ks = range(lo, min(lo + B // 2, K))
        feed(op, B, events(*[(k, 7, 1, 10**6, 1, 0) for k in ks],
                           *[(k, 8, 0, 0, 3, k) for k in ks]), wm=7)
    outs = op._flush()
    assert len(outs) == -(-K // B)
    assert sorted(r for o in outs for r in rows_of(o)) \
        == [(k, 7, 7 + 10**6, 3.0, 8, k, 1) for k in range(K)]
    assert op._flush() == []                      # once


@pytest.mark.parametrize("seed", [6, 7])
def test_an_output_of_fewer_lanes_than_the_batch(seed):
    """``withOutputCapacity(n)``: the batch handed on has ``n`` lanes;
    what a step closes beyond them waits in the carry and leaves with a
    later step or the flush: the same rows as the oracle's."""
    ev = auctions(np.random.default_rng(seed), 1500, p_build=0.15, life=40)
    op = join_op(C=256, out=4)
    out = op._step(batch_of(64, cut(ev, slice(0, 64))))
    assert out.capacity == 4 == op.dump_stats()["Join_out_capacity"]
    op = join_op(C=256, out=4)
    exp, o = oracle_rows(ev, 64)
    assert stream(op, 64, ev) == exp
    st = op.dump_stats()
    assert st["Join_rows_held_back"] > 0
    # a closed row that waits in the carry still stands before a later
    # probe of its key: a miss by the interval, not for want of a row
    split = ("Join_probe_missed_no_build", "Join_probe_missed_interval")
    assert {k: st[k] for k in STATS if k not in split} \
        == {k: o.n[v] for k, v in STATS.items() if k not in split}
    assert sum(st[k] for k in split) \
        == o.n["miss_build"] + o.n["miss_interval"]
    assert jk.join_out_capacity(64, 4) == 4
    assert jk.join_out_capacity(64, 4096) == 64     # never more lanes
    with pytest.raises(wf.WindFlowError, match="withOutputCapacity"):
        join_op(out=0)


def test_more_displaced_rows_than_the_output_holds_stop_the_graph():
    """A displaced row must leave in its own step: an output with fewer
    lanes than a step has of them is an error by name, never a row that
    stays open behind its successor."""
    ev = events(*[(1, 10 * i, 1, 1000, 1, 0) for i in range(4)],
                *[(1, 10 * i + 5, 0, 0, 2, i) for i in range(4)])
    op = join_op(out=2)                 # three displaced, each with a bid
    op._step(batch_of(8, ev, wm=40))
    with pytest.raises(wf.WindFlowError, match="withOutputCapacity"):
        op._step(batch_of(8, events(), wm=41))
    fits = join_op(out=3)
    assert feed(fits, 8, ev, wm=40) == [
        (1, 10 * i, 10 * i + 1000, 2.0, 10 * i + 5, i, 1) for i in range(3)]


def test_carry_overflow_stops_the_graph_by_name():
    """More build rows open at once than ``withBuildCapacity``: an error
    that names the capacity, a step late or at the flush; never a silent
    loss."""
    op = join_op(C=4)
    ev = events(*[(k, 10, 1, 1000, 1, 0) for k in range(6)])
    op._step(batch_of(8, ev, wm=10))              # 6 to keep, 4 lanes
    with pytest.raises(wf.WindFlowError, match=r"withBuildCapacity\(4\)"):
        op._step(batch_of(8, events(), wm=11))
    again = join_op(C=4)
    again._step(batch_of(8, ev, wm=10))
    with pytest.raises(wf.WindFlowError, match="2 were lost"):
        again._flush()
    exact = join_op(C=6)                          # as many as it holds
    exact._step(batch_of(8, ev, wm=10))
    exact._step(batch_of(8, events(), wm=11))
    assert exact.dump_stats()["Join_build_open"] == 6


def test_keys_may_grow_without_a_key_space():
    """Nothing is indexed by key: ids near 2**31 work as small ones do,
    negative ones are masked."""
    big = (1 << 31) - 5
    ev = events((big, 10, 1, 50, 1, 0), (big, 20, 0, 0, 4, 1),
                (3, 11, 1, 50, 1, 0), (3, 21, 0, 0, 5, 2),
                (-1, 12, 1, 50, 1, 0), (-1, 22, 0, 0, 6, 3))
    assert stream(join_op(), 8, ev) == [(3, 11, 61, 5.0, 21, 2, 1),
                                        (big, 10, 60, 4.0, 20, 1, 1)]


def test_a_batch_may_span_any_event_time():
    """Event time less the batch's oldest stamp is sorted as two int32
    keys: a batch that spans more than 31 bits of microseconds, or holds
    an interval that long, gives the same rows."""
    far = 1 << 40
    ev = events((1, 0, 1, 1 << 30, 1, 0), (1, 5, 0, 0, 3, 1),
                (2, far, 1, 10, 1, 0), (2, far + 9, 0, 0, 4, 2),
                (2, far + 10, 0, 0, 9, 3), (1, (1 << 30) - 1, 0, 0, 2, 4))
    op = join_op()
    rows = rows_of(op._step(batch_of(8, ev, wm=0,
                                     dtypes=dict(DTYPES, len=np.int64))))
    rows += [r for o in op._flush() for r in rows_of(o)]
    assert sorted(rows) == oracle_rows(ev)[0] \
        == [(1, 0, 1 << 30, 3.0, 5, 1, 2), (2, far, far + 10, 4.0, far + 9,
                                           2, 1)]


def test_snapshot_and_restore_mid_stream():
    rng = np.random.default_rng(11)
    ev = auctions(rng, 1600)
    B = 128
    whole = stream(join_op(), B, ev)
    op = join_op()
    rows, cut_at = [], 5 * B
    for lo in range(0, cut_at, B):
        part = cut(ev, slice(lo, lo + B))
        rows += feed(op, B, part)
    assert op.dump_stats()["Join_build_open"] > 0      # mid-interval
    blob = op.snapshot_state()
    assert blob["kind"] == "interval_join_tpu"
    assert all(isinstance(a, np.ndarray)
               for a in jax.tree.leaves(blob["state"]))
    blob = pickle.loads(pickle.dumps(blob))
    again = join_op()
    assert again.snapshot_state() is None              # never stepped
    again.restore_state(blob)
    rows += stream(again, B, cut(ev, slice(cut_at, None)))
    assert sorted(rows) == whole == oracle_rows(ev)[0]


def test_a_leaf_wider_than_a_scalar_follows_the_sort_by_gather():
    """Scalar lanes ride the sort; a leaf with a trailing dimension (a
    histogram of the matched prices a build row) follows by gather."""
    rng = np.random.default_rng(21)
    ev = auctions(rng, 600)
    op = (wf.Interval_JoinTPU_Builder(
        lambda b, p, ts: {"hist": jax.nn.one_hot(
            p["w"] % 4, 4, dtype=jnp.int32), "last": ts},
        lambda a, b: {"hist": a["hist"] + b["hist"], "last": b["last"]})
        .withBuildSide(lambda e: e["b"] == 1)
        .withIntervalLength(lambda e: e["len"])
        .withKeyBy(lambda e: e["k"]).withBuildCapacity(64).build())
    exp = {(r[0], r[1]): r for r in oracle_rows(
        dict(ev, v=np.where(ev["b"] == 1, 0, ev["v"])))[0]}
    outs = []
    for lo in range(0, 600, 128):
        outs.append(op._step(batch_of(128, cut(ev, slice(lo, lo + 128)))))
    outs += op._flush()
    seen = 0
    for o in outs:
        ok = np.asarray(o.valid)
        p = jax.tree.map(lambda a: np.asarray(a)[ok], o.payload)
        for key, start, end, n, hist, last in zip(
                p["key"], p["start"], p["end"], p["count"],
                p["value"]["hist"], p["value"]["last"]):
            inside = (ev["k"] == key) & (ev["b"] == 0) \
                & (ev["t"] >= start) & (ev["t"] < end)
            assert n == inside.sum() == exp[(key, start)][-1]
            assert hist.tolist() == np.bincount(ev["w"][inside] % 4,
                                                minlength=4).tolist()
            assert last == ev["t"][inside].max()
            seen += 1
    assert seen == len(exp) > 20


# ---------------------------------------------------------------------------
# through PipeGraph, public builders
# ---------------------------------------------------------------------------

def run_graph(ev, batch, C=64, order=None, config=None, lateness=0):
    n = len(ev["t"])
    order = np.arange(n) if order is None else order

    def gen():
        for i in order:
            yield {"t": int(ev["t"][i]),
                   **{x: DTYPES[x](ev[x][i]) for x in LANES}}

    got = []
    src = (wf.Source_Builder(gen).withTimestampExtractor(lambda e: e["t"])
           .withOutputBatchSize(batch).build())
    keep = wf.FilterTPU_Builder(lambda e: e["w"] >= 0).build()
    join = (wf.Interval_JoinTPU_Builder(
        lambda b, p, ts: {"price": p["v"], "at": ts, "who": p["w"]}, higher)
        .withName("join")
        .withBuildSide(lambda e: e["b"] == 1)
        .withIntervalLength(lambda e: e["len"])
        .withMatch(lambda b, p: p["v"] >= b["v"])
        .withKeyBy(lambda e: e["k"]).withBuildCapacity(C)
        .withLateness(lateness).build())
    snk = wf.Sink_Builder(lambda r: got.append(
        (int(r["key"]), int(r["start"]), int(r["end"]),
         float(r["value"]["price"]), int(r["value"]["at"]),
         int(r["value"]["who"]), int(r["count"])))
        if r is not None else None).build()
    g = wf.PipeGraph("join_graph", wf.ExecutionMode.DEFAULT,
                     wf.TimePolicy.EVENT, config=config or wf.Config())
    pipe = g.add_source(src)
    pipe.add(keep)
    pipe.add(join).add_sink(snk)
    g.run()
    return sorted(got), g


@pytest.mark.parametrize("seed,batch", [(31, 96), (32, 500)])
def test_the_operator_through_pipegraph(seed, batch):
    rng = np.random.default_rng(seed)
    ev = auctions(rng, 3000)
    got, g = run_graph(ev, batch)
    exp, o = oracle_rows(ev)
    assert got == exp and len(got) > 100
    st = g.stats()
    ops = {x["Operator_name"]: x for x in st["Operators"]}
    j = ops["join"]
    assert j["Operator_type"] == "IntervalJoinTPU"
    assert ops["filter_tpu"]["Fused_into"] == "filter_tpu|join"
    # the split of the probes that found no open build row into "none on
    # the key" and "outside its interval" follows the batch cuts (an
    # expired row is evicted by a step); everything else is the oracle's
    for stat, name in STATS.items():
        if stat not in ("Join_probe_missed_no_build",
                        "Join_probe_missed_interval"):
            assert j[stat] == o.n[name], stat
    assert j["Join_probe_missed_no_build"] \
        + j["Join_probe_missed_interval"] \
        == o.n["miss_build"] + o.n["miss_interval"]
    assert j["Join_build_open"] == 0 == st["Dropped_tuples"]
    assert j["Join_build_capacity"] == 64
    assert j["Join_out_capacity"] == batch
    # the counters have their families in the exposition
    from windflow_tpu.monitoring.openmetrics import (parse_exposition,
                                                     render_openmetrics)
    fams = parse_exposition(render_openmetrics(st))
    for fam, stat in (("wf_operator_join_build_rows_total", None),
                      ("wf_operator_join_probes_total", None),
                      ("wf_operator_join_build_open", "Join_build_open"),
                      ("wf_operator_join_rows_held_back_total",
                       "Join_rows_held_back")):
        assert fam in fams, fam
    by = {labels.get("event") or labels.get("outcome"): value
          for f in ("wf_operator_join_build_rows_total",
                    "wf_operator_join_probes_total")
          for _name, labels, value in fams[f]["samples"]}
    assert by["opened"] == j["Join_build_opened"]
    assert by["matched"] == j["Join_probe_matched"]
    assert by["missed_predicate"] == j["Join_probe_missed_predicate"]


def test_disorder_inside_a_batch_through_pipegraph():
    """Events swapped within blocks of eight, watermarks by the source's
    running maximum, a lateness wider than a block's span (the
    punctuation may cut a batch inside one): the same rows."""
    rng = np.random.default_rng(33)
    ev = auctions(rng, 1200, life=3000)
    order = np.concatenate([lo + rng.permutation(min(8, 1200 - lo))
                            for lo in range(0, 1200, 8)])
    got, g = run_graph(ev, 64, C=512, order=order, lateness=100)
    assert g.stats()["Dropped_tuples"] == 0
    assert got == oracle_rows(ev)[0]


def test_a_mesh_and_more_replicas_are_refused():
    from windflow_tpu.parallel.mesh import make_mesh
    op = join_op()
    op.mesh = make_mesh(4)
    with pytest.raises(wf.WindFlowError, match="mesh"):
        op.build_replicas(wf.ExecutionMode.DEFAULT, wf.TimePolicy.EVENT)
    b = lambda: (wf.Interval_JoinTPU_Builder(lift, higher)  # noqa: E731
                 .withBuildSide(lambda e: e["b"] == 1)
                 .withIntervalLength(lambda e: e["len"])
                 .withKeyBy(lambda e: e["k"]))
    with pytest.raises(wf.WindFlowError, match="one replica"):
        b().withBuildCapacity(8).withParallelism(2).build()
    with pytest.raises(wf.WindFlowError, match="withBuildCapacity"):
        b().build()
    with pytest.raises(wf.WindFlowError, match="withKeyBy"):
        (wf.Interval_JoinTPU_Builder(lift, higher)
         .withBuildSide(lambda e: e["b"] == 1)
         .withIntervalLength(lambda e: e["len"])
         .withBuildCapacity(8).build())
    with pytest.raises(wf.WindFlowError, match="withBuildSide"):
        (wf.Interval_JoinTPU_Builder(lift, higher)
         .withKeyBy(lambda e: e["k"]).withBuildCapacity(8).build())
    rng = np.random.default_rng(1)
    with pytest.raises(wf.WindFlowError, match="mesh"):
        run_graph(auctions(rng, 200), 64,
                  config=wf.Config(mesh=make_mesh(4)))


# ---------------------------------------------------------------------------
# the program
# ---------------------------------------------------------------------------

def _step_args(B, C):
    S = jax.ShapeDtypeStruct
    step = jk.make_join_step(
        B, C, lambda e: e["k"], lambda e: e["b"] == 1, lambda e: e["len"],
        lambda b, p: p["v"] >= b["v"], lift, higher)
    one = {x: S((), DTYPES[x]) for x in LANES}
    state = jax.eval_shape(lambda: jk.make_join_state(
        one, {"price": S((), np.float32), "at": S((), np.int64),
              "who": S((), np.int32)}, C))
    return step, (state, {x: S((B,), DTYPES[x]) for x in LANES},
                  S((B,), np.int64), S((B,), np.bool_), S((), np.int64))


def _primitives(jaxpr, found=None):
    found = {} if found is None else found
    for eqn in jaxpr.eqns:
        found[eqn.primitive.name] = found.get(eqn.primitive.name, 0) + 1
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _primitives(sub, found)
    return found


def test_the_step_has_no_scatter_and_no_pass_over_a_key_space():
    """Rows reach the output and the carry by one sort of (class, lane)
    and gathers: no scatter of either width (a 64-bit one costs 18.5 ms
    over 262144 lanes on a v5e), and no array wider than the carry plus
    the batch: the step's cost does not grow with the keys."""
    B, C = 256, 32
    step, args = _step_args(B, C)
    closed = jax.make_jaxpr(step)(*args)
    found = _primitives(closed.jaxpr)
    assert not [p for p in found if p.startswith("scatter")], found
    # both sides into (key, time) order, then the closed rows to the front
    assert found["sort"] == 2
    assert "cond" not in found              # under FRONT_MIN: one gather

    def widest(jaxpr):
        w = 0
        for eqn in jaxpr.eqns:
            for v in eqn.outvars:
                w = max(w, max(getattr(v.aval, "shape", ()) or (0,)))
            for p in eqn.params.values():
                for sub in (p if isinstance(p, (list, tuple)) else (p,)):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        w = max(w, widest(sub))
        return w
    assert widest(closed.jaxpr) == B + C


@pytest.mark.parametrize("B,C", [(64, 16), (1024, 64), (262144, 1024)])
def test_the_output_batch_is_the_input_batchs(B, C):
    assert jk.join_out_capacity(B) == B
    if B <= 1024:
        step, args = _step_args(B, C)
        st, out, fired, out_ts, held = jax.eval_shape(step, *args)
        assert fired.shape == out_ts.shape == out["key"].shape == (B,)
        assert held.shape == (2,) and held.dtype == np.int64
        assert {k: v.dtype for k, v in out.items() if k != "value"} == {
            "key": np.int32, "start": np.int64, "end": np.int64,
            "count": np.int32}
        assert jax.tree.map(lambda a: a.shape, st) \
            == jax.tree.map(lambda a: a.shape, args[0])
        assert st["key"].shape == (C,)


@pytest.mark.parametrize("n_ready", [10, 64, 65, 700])
def test_rows_at_the_front_are_gathered_there(n_ready):
    """An output batch of 1024 lanes or more gathers its rows from the
    first sixteenth of the order where they fit there, and whole where
    not: the same rows either way."""
    B = 1024
    assert B >= sk.FRONT_MIN and B // sk.FRONT_DIV == 64
    op = join_op(C=1024)
    ks = np.arange(800)
    for lo in range(0, 800, B // 2):
        k = ks[lo:lo + B // 2]
        ev = events(*[(int(x), 5, 1, 10 if x < n_ready else 10**6, 1, 0)
                      for x in k],
                    *[(int(x), 6, 0, 0, 3, int(x)) for x in k])
        assert feed(op, B, ev, wm=5) == []
    got = feed(op, B, events(), wm=1000)
    assert got == [(k, 5, 15, 3.0, 6, k, 1) for k in range(n_ready)]
    rest = sorted(r for o in op._flush() for r in rows_of(o))
    assert len(got) + len(rest) == 800


def _lowered_sha(step, *args):
    text = jax.jit(step).lower(*args).as_text()
    return hashlib.sha256(text.encode()).hexdigest()


def test_the_session_step_is_the_parents():
    """The (key, time) sort with its riders moved out of the session
    step into ``session_kernels.sort_lanes``, which the join shares: the
    session step lowers to the text it had at the parent commit (this
    backend; Q11's functions at a small size)."""
    S = jax.ShapeDtypeStruct
    B, K = 1024, 2048
    step = sk.make_session_step(
        B, K, 10_000_000, lambda e: jnp.int64(1), lambda a, b: a + b,
        lambda e: e["v1"].astype(jnp.int32) - 1000)
    state = jax.eval_shape(
        lambda: sk.make_session_state(jnp.zeros((), jnp.int64), K))
    payload = {"key": S((B,), np.int32),
               **{f"v{i}": S((B,), np.float32) for i in range(5)}}
    assert _lowered_sha(step, state, payload, S((B,), np.int64),
                        S((B,), np.bool_), S((), np.int64)) \
        == PARENT_SESSION_SHA


PARENT_SESSION_SHA = ("ecd79da022f410c89de02527faee4aa7"
                      "57a7034b705fbb769ffc4d0cd3c4c5db")


# ---------------------------------------------------------------------------
# the benchmark's graph: public builders, default Config()
# ---------------------------------------------------------------------------

def tiny_cfg(**graph):
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "nexmark_q9.json")) as f:
        cfg = json.load(f)
    cfg["graph"].update(dict(batch=512, build_capacity=512,
                             out_capacity=128), **graph)
    # 100 000 events a second: an auction lives 1-33 340 usec (up to
    # 3 334 events, six and a half batches), a pass spans 40.96 ms
    cfg["stream"].update(ring_batches=8, active_people=4,
                         hot_bidder_stride=8, event_rate=100_000)
    return cfg


def run_q9(rec, cfg, chunk=300):
    got = []

    def chunks():
        for lo in range(0, len(rec), chunk):
            yield rec[lo:lo + chunk].tobytes()

    def sink(c):
        if c is not None:
            got.append({k: np.asarray(v) for k, v in c.cols.items()})

    g = q9.build_graph(cfg, None, chunks, sink)
    g.run()
    cat = lambda n: np.concatenate([b[n] for b in got])  # noqa: E731
    return {n: cat(n) for n in ("key", "wid", "value")}, g


def oracle_of(rec):
    """The per-tuple oracle over the benchmark's records."""
    kind = rec[q9.KIND]
    ev = {"k": rec["k"].astype(np.int64), "t": rec["t"].astype(np.int64),
          "b": (kind == q9.AUCTION).astype(np.int32),
          "len": rec[q9.LENGTH].astype(np.int64),
          "v": rec[q9.PRICE].astype(np.float64),
          "w": rec[q9.BIDDER].astype(np.int64)}
    return oracle_rows(cut(ev, kind != q9.PERSON))


@pytest.fixture(scope="module")
def replayed():
    """The generator's own stream, two and a third passes of a ring,
    through the benchmark's graph."""
    cfg = tiny_cfg()
    ring = q9.make_ring(2**31 + 5, cfg)
    n = len(ring["rec"]) * 7 // 3
    rec = ring["rec"][np.arange(n) % len(ring["rec"])].copy()
    rec["t"] = np.arange(n) * 10                # 100 000 events a second
    got, g = run_q9(rec, cfg)
    return cfg, ring, rec, got, g


def as_rows(key, wid, value):
    v = np.asarray(value).reshape(-1, 5)
    return sorted(zip(np.asarray(key).tolist(), np.asarray(wid).tolist(),
                      v[:, 3].tolist(), v[:, 0].astype(float).tolist(),
                      v[:, 2].tolist(), v[:, 1].tolist(), v[:, 4].tolist()))


def test_the_graph_agrees_with_the_oracle(replayed):
    _cfg, _ring, rec, got, _g = replayed
    exp, _ = oracle_of(rec)
    assert as_rows(got["key"], got["wid"], got["value"]) == exp
    assert len(exp) > 300


def test_the_closed_form_agrees_with_the_oracle(replayed):
    cfg, ring, rec, got, _g = replayed
    exp, counts = q9.winning_bids(ring["rec"], len(rec), 100_000)
    orc, o = oracle_of(rec)
    assert as_rows(exp.key, exp.wid, exp.value) == orc
    assert list(zip(exp.key.tolist(), exp.wid.tolist())) \
        == sorted(zip(exp.key.tolist(), exp.wid.tolist()))
    assert all(c["ok"] for c in q9.compare(cfg, got, exp))
    assert counts == {
        "auctions": o.n["opened"], "matched": o.n["matched"],
        "under_reserve": o.n["miss_pred"],
        "no_open_auction": o.n["miss_build"] + o.n["miss_interval"]}
    # the last pass is partial: the stream ends inside open auctions
    assert len(rec) % len(ring["rec"]) and exp.value[:, 3].max() \
        > rec["t"][-1]


def test_the_graph_is_one_fused_program_a_batch(replayed):
    cfg, ring, rec, _got, g = replayed
    st = g.stats()
    ops = {o["Operator_name"]: o for o in st["Operators"]}
    j = ops["winning_bids"]
    assert j["Operator_type"] == "IntervalJoinTPU"
    # the person filter rides in the join step's program
    assert ops["filter_tpu"]["Fused_into"] == "filter_tpu|winning_bids"
    _, counts = q9.winning_bids(ring["rec"], len(rec), 100_000)
    assert j["Join_build_opened"] == j["Join_build_closed"] \
        == counts["auctions"]
    assert j["Join_probe_matched"] == counts["matched"]
    assert j["Join_probe_missed_predicate"] == counts["under_reserve"]
    assert j["Join_probe_missed_no_build"] \
        + j["Join_probe_missed_interval"] == counts["no_open_auction"]
    assert j["Join_probe_missed_no_build"] > 0 \
        and j["Join_probe_missed_interval"] > 0
    assert j["Join_build_open"] == 0 == j["Join_build_displaced"]
    assert j["Join_rows_held_back"] == 0
    assert j["Late_tuples_dropped"] == 0 == st["Dropped_tuples"]
    assert j["Join_out_capacity"] == 128          # withOutputCapacity
    from windflow_tpu.monitoring.jit_registry import default_registry
    names = set(default_registry().snapshot())
    assert "filter_tpu|winning_bids" in names
    # the end of stream ran the step's own program: nothing compiled there
    assert not [n for n in names if "flush" in n and "winning" in n]
    # no scan: the tail keeps per-batch dispatch, and says why
    assert all(e["batches"] == 0 for e in st["Megastep"]["edges"])


@pytest.mark.parametrize("fault", ["expires_late", "clock", "no_reserve",
                                   "later_on_a_tie", "held_back"])
def test_a_wrong_program_fails_a_check(replayed, fault):
    """Winning bids with auctions that take bids for 500 usec after they
    expire, with a clock rounded to the millisecond, without the reserve,
    with the later bid winning a tie, and with a row that was held back
    and never emitted."""
    cfg, ring, rec, got, _g = replayed
    exp, _ = q9.winning_bids(ring["rec"], len(rec), 100_000)
    rec = rec.copy()
    if fault == "held_back":
        wrong = {k: v[1:] for k, v in got.items()}
    else:
        tss = rec["t"].astype(np.int64)
        if fault == "expires_late":
            rec[q9.LENGTH] = np.where(rec[q9.KIND] == q9.AUCTION,
                                      rec[q9.LENGTH] + 500, 0)
        elif fault == "clock":
            tss = (tss + 500) // 1000 * 1000
        elif fault == "no_reserve":
            rec[q9.PRICE] = np.where(rec[q9.KIND] == q9.AUCTION, 0,
                                     rec[q9.PRICE])
        elif fault == "later_on_a_tie":
            # few distinct prices, so that bids tie
            rec[q9.PRICE] = np.where(rec[q9.KIND] == q9.BID,
                                     np.minimum(rec[q9.PRICE], 1000),
                                     rec[q9.PRICE] // 1000)
        k, w, v, _ = q9.winners_of(rec, tss)
        if fault == "expires_late":
            v[:, 3] -= 500                  # the rows say the true expires
        if fault == "later_on_a_tie":
            order = np.lexsort((w, k))
            k, w, v = k[order], w[order], v[order]
            exp = q9.WinningBids(k, w, v.copy(), np.zeros(len(k), bool),
                                 np.full(len(k), -1))
            bid = rec[q9.KIND] == q9.BID
            for i in range(len(k)):         # the LAST bid at the price
                last = np.flatnonzero(
                    bid & (rec["k"] == k[i]) & (tss >= w[i])
                    & (tss < v[i, 3]) & (rec[q9.PRICE] == v[i, 0]))[-1]
                v[i, 1], v[i, 2] = rec[q9.BIDDER][last], tss[last]
            assert np.any(v[:, 2] != exp.value[:, 2])
        wrong = {"key": k, "wid": w, "value": v}
    checks = q9.compare(cfg, wrong, exp)
    assert not all(c["ok"] for c in checks), fault


def test_intervals_that_would_cross_passes_are_refused():
    cfg = tiny_cfg()
    ring = q9.make_ring(3, cfg)
    rec = ring["rec"].copy()
    a = np.flatnonzero(rec[q9.KIND] == q9.AUCTION)
    # the ring's last auction lives on into the next pass, and a bid at
    # the ring's start is on its id
    rec[q9.LENGTH][a[-1]] = 30_000
    first_bid = np.flatnonzero(rec[q9.KIND] == q9.BID)[0]
    rec["k"][first_bid] = rec["k"][a[-1]]
    with pytest.raises(ValueError, match="next pass"):
        q9.one_pass(rec, 100_000)
    rec[q9.LENGTH][a[0]] = 90_000           # a pass spans 81.92 ms
    with pytest.raises(ValueError, match="whole replay period"):
        q9.one_pass(rec, 100_000)
    with pytest.raises(ValueError, match="event rate"):
        q9.expected(cfg, ring, 100, {"event_rate": 1_000_000})


def test_the_stream_has_the_sources_shapes():
    """The mix, the hot-auction rule (Q5's ring, id for id), the price
    and auction-length distributions."""
    cfg = tiny_cfg(batch=4096)
    ring = q9.make_ring(7, cfg)
    rec = ring["rec"]
    q5 = harness.load_module("configs", "nexmark_q5")
    same = q5.make_ring(7, {"graph": {"batch": 4096, "max_keys": 1 << 62},
                            "stream": cfg["stream"]})["rec"]
    assert np.array_equal(rec["k"], same["k"])
    assert np.array_equal(rec[q9.KIND], same[q9.KIND])
    kind = rec[q9.KIND]
    n = len(rec)
    assert [round((kind == x).sum() * 50 / n) for x in (0, 1, 2)] \
        == [1, 3, 46]
    bid, auc = kind == q9.BID, kind == q9.AUCTION
    p = rec[q9.PRICE][bid]
    assert p.min() >= 1 and p.max() <= 10**6
    # log-uniform: a sixth of the prices a decade
    per_decade = np.histogram(np.log10(p), bins=6, range=(0, 6))[0]
    assert np.all(np.abs(per_decade / bid.sum() - 1 / 6) < 0.02)
    assert rec[q9.RESERVE][auc].max() <= 2 * 10**6
    ln = rec[q9.LENGTH][auc]
    horizon = q9.horizon_usec(100_000)
    assert horizon == 16_670 and ln.min() >= 1 and ln.max() <= 2 * horizon
    assert abs(ln.mean() / horizon - 1) < 0.05
    assert np.all(rec[q9.LENGTH][~auc] == 0)
    assert rec[q9.BIDDER][bid].min() >= q9.FIRST_PERSON_ID
    assert q9.horizon_usec(1_000_000) == 1667


def test_a_program_without_the_builder_is_refused_at_once(monkeypatch):
    monkeypatch.delattr(wf, "Interval_JoinTPU_Builder")
    with pytest.raises(RuntimeError, match="two-input keyed operator"):
        q9.build_graph(tiny_cfg(), None, lambda: iter(()), lambda c: None)
    with pytest.raises(RuntimeError, match="two-input keyed operator"):
        q9.make_ring(1, tiny_cfg())


def test_dispatch_span_says_out_cap(replayed, monkeypatch):
    """The operator's ``wf.dispatch`` notes ``out_cap``: the lanes of
    the batch it hands on (``withOutputCapacity``)."""
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

    import windflow_tpu.ops.tpu as tpu_mod
    monkeypatch.setattr(tpu_mod.flightrec, "span",
                        lambda name, **kw: Spy(name, kw))
    cfg, _ring, rec, _got, _g = replayed
    run_q9(rec[:4096], cfg)
    caps = [kw["out_cap"] for n, kw in seen
            if n == "wf.dispatch" and kw.get("op") == "winning_bids"]
    assert caps and set(caps) == {128}
