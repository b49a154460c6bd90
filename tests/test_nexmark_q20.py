"""The interval join's pair form (``windows/join_tpu.py``: a row a
matched pair, the build side retained by key, probes that wait for their
build row) and NEXmark q20 (expand bid with auction) at small sizes on
the CPU backend: the operator against a per-tuple oracle written here, a
batch at a time and through ``PipeGraph``, in every case its contract
names; the benchmark's graph and its closed-form reference against the
same oracle; and what the operator must not do (a 64-bit scatter, a
change to the fold form's program)."""

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
from windflow_tpu.basic import WindFlowError  # noqa: E402
from windflow_tpu.batch import WM_NONE, DeviceBatch  # noqa: E402
from windflow_tpu.windows import join_kernels as jk  # noqa: E402

q20 = harness.load_module("configs", "nexmark_q20")

LANES = ("k", "b", "v", "w")
DTYPES = dict(k=np.int32, b=np.int32, v=np.float32, w=np.int32)


# ---------------------------------------------------------------------------
# the per-tuple oracle: the same semantics, one event at a time
# ---------------------------------------------------------------------------

class Oracle:
    """Build rows ``(k, t, 1, v, w)`` and probes ``(k, u, 0, v, w)``, a
    step at a time: rows ``(key, t, u, build v, build w, probe v, probe
    w)`` and the operator's counters."""

    def __init__(self, lower, upper, lateness=0, match=True):
        self.lower, self.upper, self.lateness = lower, upper, lateness
        self.match = match
        self.tab, self.pend, self.wm, self.rows = {}, [], None, []
        self.n = dict.fromkeys(
            ("late", "built", "replaced", "evicted", "matched",
             "miss_build", "miss_interval", "miss_pred", "waited",
             "pend_max"), 0)

    def _meet(self, build, probe):
        """A probe against the build row it met: a row, or a miss."""
        (t, bv, bw), (k, u, _, pv, pw) = build, probe
        if not -self.lower <= u - t < self.upper:
            self.n["miss_interval"] += 1
        elif self.match and pv < bv:
            self.n["miss_pred"] += 1
        else:
            self.n["matched"] += 1
            return [(k, t, u, float(bv), int(bw), float(pv), int(pw))]
        return []

    def step(self, events, wm):
        """``events``: tuples ``(k, t, is_build, v, w)`` in any order;
        ``wm``: the batch's watermark (None: none yet).  Returns the
        rows the step completed."""
        live = [e for e in events if self.wm is None or e[1] >= self.wm]
        self.n["late"] += len(events) - len(live)
        now = self.wm
        if wm is not None:
            now = wm - self.lateness if now is None \
                else max(now, wm - self.lateness)
        rows, cur, waiting = [], {}, []
        lanes = [(e, False) for e in self.pend] + [(e, True) for e in live]
        for e, fresh in sorted(lanes, key=lambda x: (x[0][0], x[0][1],
                                                      1 - x[0][2])):
            k, t, b, v, w = e
            if b:
                if k in cur or k in self.tab:
                    self.n["replaced"] += 1
                self.n["built"] += 1
                cur[k] = (t, v, w)
            elif k in cur:          # at or before it in its own batch
                rows += self._meet(cur[k], e)
            else:
                waiting.append((e, fresh))
        self.tab.update(cur)
        self.pend = []
        for e, fresh in waiting:
            k, u = e[0], e[1]
            if k in self.tab:
                rows += self._meet(self.tab[k], e)
            elif now is not None and now >= u + self.lower:
                self.n["miss_build"] += 1
            else:
                self.pend.append(e)
                self.n["waited"] += fresh
        self.n["pend_max"] = max(self.n["pend_max"], len(self.pend))
        self.wm = now
        if now is not None:
            for k in [k for k, r in self.tab.items()
                      if r[0] + self.upper <= now]:
                del self.tab[k]
                self.n["evicted"] += 1
        self.rows += rows
        return sorted(rows)

    def flush(self):
        self.n["miss_build"] += len(self.pend)
        self.pend = []
        return sorted(self.rows)


def oracle_rows(ev, B=None, **kw):
    """All rows of the stream ``ev`` fed in time order, ``B`` events a
    step."""
    o = Oracle(**kw)
    n = len(ev["t"])
    B = B or n
    for lo in range(0, n, B):
        s = slice(lo, lo + B)
        o.step(list(zip(*(ev[x][s].tolist()
                          for x in ("k", "t", "b", "v", "w")))),
               int(ev["t"][s].max()))
    return o.flush(), o


# ---------------------------------------------------------------------------
# the operator, a batch at a time
# ---------------------------------------------------------------------------

def join_fn(build, probe, ts):
    return {"bv": build["v"], "bw": build["w"], "pv": probe["v"],
            "pw": probe["w"], "at": ts}


def pair_op(lower=5, upper=300, K=4096, P=64, lateness=0, match=True,
            out=None):
    b = (wf.Interval_JoinTPU_Builder(join_fn)
         .withBuildSide(lambda e: e["b"] == 1)
         .withBoundaries(lower, upper).withMaxKeys(K).withProbeCapacity(P)
         .withKeyBy(lambda e: e["k"]).withLateness(lateness))
    if match:
        b = b.withMatch(lambda build, probe: probe["v"] >= build["v"])
    if out is not None:
        b = b.withOutputCapacity(out)
    return b.build()


def batch_of(B, ev, wm=None):
    n = len(ev["t"])
    assert n <= B
    pad = lambda a, dt: jnp.asarray(  # noqa: E731
        np.r_[np.asarray(a, dt), np.zeros(B - n, dt)])
    if wm is None:
        wm = int(max(ev["t"])) if n else WM_NONE
    return DeviceBatch({x: pad(ev[x], DTYPES[x]) for x in LANES},
                       pad(ev["t"], np.int64),
                       jnp.asarray(np.arange(B) < n), watermark=wm)


def events(*rows):
    """``(k, t, is_build, v, w)`` tuples to lanes."""
    cols = list(zip(*rows)) if rows else [()] * 5
    return {x: np.asarray(c, np.int64 if x == "t" else DTYPES[x])
            for x, c in zip(("k", "t", "b", "v", "w"), cols)}


def rows_of(out):
    ok = np.asarray(out.valid)
    p = jax.tree.map(lambda a: np.asarray(a)[ok], out.payload)
    # the moment the pair is complete
    assert np.array_equal(np.asarray(out.ts)[ok],
                          np.maximum(p["build_ts"], p["probe_ts"]))
    v = p["value"]
    assert np.array_equal(v["at"], p["probe_ts"])
    return sorted(zip(p["key"].tolist(), p["build_ts"].tolist(),
                      p["probe_ts"].tolist(), v["bv"].tolist(),
                      v["bw"].tolist(), v["pv"].tolist(), v["pw"].tolist()))


def feed(op, B, ev, wm=None):
    return rows_of(op._step(batch_of(B, ev, wm)))


def cut(ev, s):
    return {x: a[s] for x, a in ev.items()}


def stream(op, B, ev, shuffle=None, oracle=None):
    """The whole stream through the operator in batches of ``B`` tuples
    and the end-of-stream flush; all rows, sorted.  With an ``oracle``
    the rows are held to it step by step, and the counters before the
    flush (whose infinite watermark evicts every row)."""
    rows = []
    for lo in range(0, len(ev["t"]), B):
        part = cut(ev, slice(lo, lo + B))
        wm = int(part["t"].max())
        if shuffle is not None:
            part = cut(part, shuffle.permutation(len(part["t"])))
        got = feed(op, B, part, wm=wm)
        if oracle is not None:
            assert got == oracle.step(list(zip(*(
                part[x].tolist() for x in ("k", "t", "b", "v", "w")))), wm)
        rows += got
    if oracle is not None:
        counters_agree(op, oracle)
    for out in op._flush():
        rows += rows_of(out)
    return sorted(rows)


def auctions(rng, n, p_build=0.08, spread=12, lead=3, keys=None):
    """A stream in time order shaped like q20's: build rows take new
    keys in turn, probes aim at the newest few keys and a few not yet
    there (the generator's id lead)."""
    t = np.cumsum(rng.integers(1, 4, n)).astype(np.int64)
    b = (rng.random(n) < p_build).astype(np.int32)
    newest = np.cumsum(b)
    k = np.where(b == 1, newest,
                 np.maximum(newest - rng.integers(0, spread, n) + lead, 0))
    if keys is not None:
        k = k % keys            # keys come round again: rows are replaced
    return {"k": k.astype(np.int32), "t": t, "b": b,
            "v": rng.integers(1, 50, n).astype(np.float32),
            "w": rng.integers(0, 1000, n).astype(np.int32)}


STATS = {"Join_build_built": "built", "Join_build_replaced": "replaced",
         "Join_build_evicted": "evicted",
         "Join_probe_matched": "matched",
         "Join_probe_missed_no_build": "miss_build",
         "Join_probe_missed_interval": "miss_interval",
         "Join_probe_missed_predicate": "miss_pred",
         "Join_probe_waited": "waited",
         "Join_probe_pending_max": "pend_max",
         "Late_tuples_dropped": "late"}


def counters_agree(op, o):
    st = op.dump_stats()
    assert {k: st[k] for k in STATS} == {k: o.n[v] for k, v in STATS.items()}
    assert st["Join_build_retained"] == len(o.tab)
    assert st["Join_probe_pending"] == len(o.pend)
    return st


@pytest.mark.parametrize("lower", [0, 4, 40])
@pytest.mark.parametrize("B", [64, 256, 1024])
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_random_streams_against_the_oracle(seed, B, lower):
    """Rows and every counter, step by step: probes on the newest few
    keys and on keys not yet there, a retention a few batches long."""
    rng = np.random.default_rng(seed)
    ev = auctions(rng, 6 * B + 17)
    o = Oracle(lower, 5 * B)
    op = pair_op(lower, 5 * B, P=256)
    got = stream(op, B, ev, oracle=o)
    assert got == o.flush() and len(got) > B
    if lower:
        assert o.n["waited"] > 0
    assert o.n["evicted"] > 0 and o.n["miss_pred"] > 0
    st = op.dump_stats()
    assert st["Join_probe_missed_no_build"] == o.n["miss_build"]
    assert st["Join_build_retained"] == 0       # the flush evicts


@pytest.mark.parametrize("seed", [4, 5])
def test_any_order_inside_a_batch(seed):
    rng = np.random.default_rng(seed)
    ev = auctions(rng, 1500)
    o = Oracle(6, 700)
    op = pair_op(6, 700)
    assert stream(op, 256, ev, shuffle=rng, oracle=o) == o.flush()


@pytest.mark.parametrize("seed", [6, 7])
def test_keys_that_come_round_again_replace_their_row(seed):
    rng = np.random.default_rng(seed)
    ev = auctions(rng, 2000, p_build=0.2, keys=40)
    o = Oracle(3, 10_000)
    op = pair_op(3, 10_000, K=40)
    assert stream(op, 128, ev, oracle=o) == o.flush()
    assert op.dump_stats()["Join_build_replaced"] == o.n["replaced"] > 100


def test_without_a_predicate_every_probe_inside_matches():
    rng = np.random.default_rng(8)
    ev = auctions(rng, 900)
    o = Oracle(5, 400, match=False)
    op = pair_op(5, 400, match=False)
    assert stream(op, 128, ev, oracle=o) == o.flush()
    assert op.dump_stats()["Join_probe_missed_predicate"] == 0


@pytest.mark.parametrize("split", [False, True])
@pytest.mark.parametrize("lower", [0, 7])
def test_the_interval_is_closed_below_and_open_above(lower, split):
    """``t - lower <= u < t + upper``, to the microsecond, whether the
    probes share the build row's batch or come a batch later."""
    t, upper = 100, 50
    build = [(1, t, 1, 0, 9)]
    probes = [(1, t - lower - 1, 0, 1, 1), (1, t - lower, 0, 1, 2),
              (1, t, 0, 1, 3), (1, t + upper - 1, 0, 1, 4),
              (1, t + upper, 0, 1, 5)]
    op = pair_op(lower, upper)
    if split:
        got = feed(op, 16, events(*build), wm=0) \
            + feed(op, 16, events(*probes), wm=0)
    else:
        got = feed(op, 16, events(*build, *probes), wm=0)
    assert sorted(r[-1] for r in got) == [2, 3, 4]
    st = op.dump_stats()
    assert st["Join_probe_matched"] == 3
    assert st["Join_probe_missed_interval"] == 2


@pytest.mark.parametrize("late_by", [0, 1])
def test_a_probe_waits_across_a_batch_boundary(late_by):
    """A probe on a key with no build row waits in the state; the build
    row of the next batch pairs it (no later than ``lower`` after it),
    and one that comes ``lower + 1`` after it misses it."""
    lower = 10
    op = pair_op(lower, 100)
    assert feed(op, 8, events((5, 50, 0, 3, 1)), wm=50) == []
    st = op.dump_stats()
    assert st["Join_probe_pending"] == st["Join_probe_waited"] == 1
    t = 50 + lower + late_by
    got = feed(op, 8, events((5, t, 1, 2, 7)), wm=50)
    st = op.dump_stats()
    assert st["Join_probe_pending"] == 0
    if late_by:
        assert got == [] and st["Join_probe_missed_interval"] == 1
    else:
        assert got == [(5, t, 50, 2.0, 7, 3.0, 1)]
        assert st["Join_probe_matched"] == 1
    assert st["Join_probe_missed_no_build"] == 0


@pytest.mark.parametrize("wm,waits", [(59, True), (60, False)])
def test_a_probe_waits_out_lower_and_is_a_miss(wm, waits):
    """To the microsecond: the probe at 50 waits while the watermark is
    under ``50 + lower``, and is a miss in the step that reaches it."""
    op = pair_op(10, 100)
    assert feed(op, 8, events((5, 50, 0, 3, 1)), wm=50) == []
    assert feed(op, 8, events(), wm=wm) == []
    st = op.dump_stats()
    assert st["Join_probe_pending"] == int(waits)
    assert st["Join_probe_missed_no_build"] == int(not waits)
    assert [r for o in op._flush() for r in rows_of(o)] == []
    st = op.dump_stats()
    assert st["Join_probe_pending"] == 0
    assert st["Join_probe_missed_no_build"] == 1        # the flush's, else
    assert st["Join_probe_pending_max"] == st["Join_probe_waited"] == 1


def test_with_lower_zero_nothing_waits_past_its_step():
    op = pair_op(0, 100)
    # the build row of its own microsecond stands before the probe
    got = feed(op, 8, events((1, 7, 0, 1, 1), (1, 7, 1, 0, 2),
                             (2, 7, 0, 1, 3)), wm=7)
    assert got == [(1, 7, 7, 0.0, 2, 1.0, 1)]
    st = op.dump_stats()
    assert st["Join_probe_pending"] == st["Join_probe_waited"] == 0
    assert st["Join_probe_missed_no_build"] == 1


@pytest.mark.parametrize("wm,evicted", [(149, False), (150, True)])
def test_a_build_row_is_evicted_at_t_plus_upper(wm, evicted):
    """To the microsecond: the row at 100 stands while the watermark is
    under 150; a probe of the step after finds it there (outside its
    interval: a miss by the interval) or finds nothing and waits."""
    op = pair_op(5, 50)
    assert feed(op, 8, events((1, 100, 1, 0, 9)), wm=100) == []
    assert feed(op, 8, events(), wm=wm) == []
    st = op.dump_stats()
    assert st["Join_build_retained"] == int(not evicted)
    assert st["Join_build_evicted"] == int(evicted)
    assert feed(op, 8, events((1, 150, 0, 1, 1)), wm=150) == []
    st = op.dump_stats()
    assert st["Join_probe_missed_interval"] == int(not evicted)
    assert st["Join_probe_pending"] == int(evicted)
    assert st["Join_build_evicted"] == 1
    # a new row on the key: it replaces nothing, and pairs what waited
    got = feed(op, 8, events((1, 154, 1, 0, 8)), wm=154)
    assert got == ([(1, 154, 150, 0.0, 8, 1.0, 1)] if evicted else [])
    assert op.dump_stats()["Join_build_replaced"] == 0


@pytest.mark.parametrize("split", [False, True])
def test_a_newer_build_row_replaces_the_retained_one(split):
    """One retained row a key: a probe between two build rows of its
    batch meets the older (at or before it); where the older came a
    batch earlier it is gone once the newer is written, and the probe
    meets the newer (inside ``lower`` of it); a probe of a later batch
    meets the newer."""
    first, second = (1, 10, 1, 0, 1), (1, 20, 1, 0, 2)
    between, after = (1, 15, 0, 1, 5), (1, 30, 0, 1, 6)
    op = pair_op(5, 100)
    if split:
        got = feed(op, 8, events(first), wm=0) \
            + feed(op, 8, events(between, second), wm=0)
    else:
        got = feed(op, 8, events(first, between, second), wm=0)
    got += feed(op, 8, events(after), wm=0)
    assert sorted(got) == [
        (1, 20, 15, 0.0, 2, 1.0, 5) if split else (1, 10, 15, 0.0, 1, 1.0, 5),
        (1, 20, 30, 0.0, 2, 1.0, 6)]
    st = op.dump_stats()
    assert st["Join_build_built"] == 2 and st["Join_build_replaced"] == 1
    assert st["Join_build_retained"] == 1 and st["Join_build_evicted"] == 0


def test_late_rows_are_dropped_and_counted():
    op = pair_op(5, 100)
    feed(op, 8, events((1, 50, 1, 0, 1)), wm=60)
    # older than the watermark of the steps before: both sides
    got = feed(op, 8, events((1, 59, 0, 1, 2), (2, 58, 1, 0, 3),
                             (1, 60, 0, 1, 4)), wm=60)
    assert got == [(1, 50, 60, 0.0, 1, 1.0, 4)]
    st = op.dump_stats()
    assert st["Late_tuples_dropped"] == 2 == op.num_dropped_tuples()
    assert st["Join_build_built"] == 1


def test_lateness_holds_rows_and_probes_longer():
    rng = np.random.default_rng(9)
    ev = auctions(rng, 1200)
    o = Oracle(6, 300, lateness=200)
    op = pair_op(6, 300, lateness=200)
    assert stream(op, 128, ev, oracle=o) == o.flush()


def test_more_pairs_than_the_output_holds_are_held_back():
    """Pairs beyond the output's lanes wait in the state, in order, and
    the watermark handed on waits with them."""
    B, OC = 64, 8
    op = pair_op(0, 1000, out=OC)
    build = [(k, 10, 1, 0, k) for k in range(4)]
    probes = [(k % 4, 20 + k, 0, 1, k) for k in range(14)]
    out = op._step(batch_of(B, events(*build, *probes), wm=50))
    assert out.valid.shape == (OC,) and out.watermark == WM_NONE
    rows = rows_of(out)
    assert len(rows) == OC
    assert op.dump_stats()["Join_rows_held_back"] == 6
    wms = []
    for wm in (60, 70, 80):
        out = op._step(batch_of(B, events(), wm=wm))
        wms.append(out.watermark)
        rows += rows_of(out)
        assert len(rows) == 14
    # the step after the one that held back hands no watermark on; then
    # it trails by a step again
    assert wms == [WM_NONE, 60, 70]
    assert sorted(rows) == oracle_rows(
        events(*build, *probes), lower=0, upper=1000)[0]
    assert [o for o in op._flush()] == []


def test_held_back_pairs_leave_before_newer_ones_and_at_the_flush():
    B, OC = 32, 4
    op = pair_op(0, 1000, out=OC)
    build = [(1, 10, 1, 0, 0)]
    first = rows_of(op._step(batch_of(B, events(
        *build, *[(1, 20 + i, 0, 1, i) for i in range(7)]), wm=30)))
    second = rows_of(op._step(batch_of(B, events(
        *[(1, 40 + i, 0, 1, 10 + i) for i in range(3)]), wm=50)))
    assert [r[-1] for r in first] == [0, 1, 2, 3]
    assert [r[-1] for r in second] == [4, 5, 6, 10]
    rest = [rows_of(o) for o in op._flush()]
    assert [[r[-1] for r in rows] for rows in rest] == [[11, 12]]


def test_the_watermark_handed_on_is_held_at_the_oldest_waiting_probe():
    op = pair_op(100, 1000)
    wms = []
    for ev, wm in ((events((9, 40, 0, 1, 1)), 50), (events(), 60),
                   (events(), 70), (events((9, 120, 1, 0, 2)), 120),
                   (events(), 130), (events(), 140)):
        wms.append(op._step(batch_of(8, ev, wm=wm)).watermark)
    # one step behind, and never past the probe that waits at 40: the
    # row it leaves in the fourth step is stamped 120
    assert wms == [WM_NONE, 40, 40, 40, 120, 130]


def test_pending_overflow_stops_the_graph_by_name():
    op = pair_op(50, 1000, P=4)
    feed(op, 16, events(*[(k, 10, 0, 1, k) for k in range(5)]), wm=10)
    with pytest.raises(WindFlowError, match=r"withProbeCapacity\(4\)"):
        feed(op, 16, events(), wm=11)


def test_held_overflow_stops_the_graph_by_name():
    op = pair_op(0, 1000, out=4)
    feed(op, 32, events((1, 10, 1, 0, 0),
                        *[(1, 20 + i, 0, 1, i) for i in range(9)]), wm=30)
    with pytest.raises(WindFlowError, match=r"withOutputCapacity\(4\)"):
        feed(op, 32, events(), wm=31)


def test_a_key_outside_the_key_space_stops_the_graph_by_name():
    op = pair_op(K=16)
    feed(op, 8, events((16, 10, 1, 0, 0)), wm=10)
    with pytest.raises(WindFlowError, match=r"withMaxKeys\(16\)"):
        op._flush()


def test_a_batch_may_span_any_event_time():
    """Times ride the sort as two int32 keys and the table as two int32
    words: any int64 event time under 2**60 is exact."""
    far = 1 << 40
    ev = events((1, 0, 1, 0, 1), (1, 5, 0, 3, 2),
                (2, far, 1, 0, 3), (2, far + 9, 0, 4, 4),
                (2, far + 10, 0, 9, 5), (1, 9, 0, 2, 6))
    op = pair_op(0, 10)
    rows = rows_of(op._step(batch_of(8, ev, wm=0)))
    more = feed(op, 8, events((2, far + 3, 0, 1, 7)), wm=0)
    assert rows == oracle_rows(ev, lower=0, upper=10)[0] \
        == [(1, 0, 5, 0.0, 1, 3.0, 2), (1, 0, 9, 0.0, 1, 2.0, 6),
            (2, far, far + 9, 0.0, 3, 4.0, 4)]
    assert more == [(2, far, far + 3, 0.0, 3, 1.0, 7)]


@pytest.mark.parametrize("n_keys", [20, 400])
def test_windows_and_the_whole_width_give_the_same_rows(n_keys):
    """The table is written and read through windows of a batch's eighth
    where the build rows and the waiting probes fit them, and over the
    whole width where not."""
    B = 1024
    assert B // jk.TABLE_DIV == 128
    op = pair_op(0, 10_000, P=512)
    build = [(k, 10, 1, 0, k) for k in range(n_keys)]
    # the second batch's probes all look their row up in the table
    probes = [(k % n_keys, 20 + k, 0, 1, k) for k in range(500)]
    assert feed(op, B, events(*build), wm=10) == []
    got = feed(op, B, events(*probes, *[(k + n_keys, 600, 1, 0, 0)
                                        for k in range(n_keys)]), wm=600)
    assert got == oracle_rows(events(*build, *probes), lower=0,
                              upper=10_000)[0]
    assert op.dump_stats()["Join_build_retained"] == 2 * n_keys


def test_snapshot_and_restore_mid_stream():
    """The retained table, the waiting probes and the held-back pairs
    all restore: the rest of the stream gives the rows of one run."""
    rng = np.random.default_rng(11)
    ev = auctions(rng, 1600)
    B = 128
    whole = stream(pair_op(20, 600, out=48), B, ev)
    op = pair_op(20, 600, out=48)
    rows, cut_at = [], 5 * B
    for lo in range(0, cut_at, B):
        rows += feed(op, B, cut(ev, slice(lo, lo + B)))
    st = op.dump_stats()
    assert st["Join_build_retained"] > 0 and st["Join_probe_pending"] > 0
    assert int(op._state["held"]["n"]) > 0
    blob = op.snapshot_state()
    assert blob["kind"] == "interval_join_pairs_tpu"
    assert all(isinstance(a, np.ndarray)
               for a in jax.tree.leaves(blob["state"]))
    blob = pickle.loads(pickle.dumps(blob))
    again = pair_op(20, 600, out=48)
    assert again.snapshot_state() is None              # never stepped
    again.restore_state(blob)
    rows += stream(again, B, cut(ev, slice(cut_at, None)))
    assert sorted(rows) == whole == oracle_rows(ev, B, lower=20,
                                                upper=600)[0]


def test_a_leaf_wider_than_a_scalar_follows_by_gather():
    """Scalar lanes ride the sorts; a leaf with a trailing dimension is
    kept in the table and follows by gather."""
    B = 32
    op = (wf.Interval_JoinTPU_Builder(
        lambda b, p, u: {"tag": b["tag"] + p["tag"]})
        .withBuildSide(lambda e: e["b"] == 1).withBoundaries(5, 100)
        .withMaxKeys(8).withProbeCapacity(4)
        .withKeyBy(lambda e: e["k"]).build())

    def batch(rows, wm):
        n = len(rows)
        k, t, b = (np.r_[np.array(c), np.zeros(B - n, np.int64)]
                   for c in zip(*rows))
        tag = np.zeros((B, 3), np.int32)
        tag[:n] = np.arange(n)[:, None] * 10 + np.arange(3) + 1
        return DeviceBatch(
            {"k": jnp.asarray(k, jnp.int32), "b": jnp.asarray(b, jnp.int32),
             "tag": jnp.asarray(tag)}, jnp.asarray(t, jnp.int64),
            jnp.asarray(np.arange(B) < n), watermark=wm)
    out = op._step(batch([(1, 10, 1), (1, 12, 0), (2, 13, 0)], 13))
    ok = np.asarray(out.valid)
    assert np.asarray(out.payload["value"]["tag"])[ok].tolist() \
        == [[12, 14, 16]]
    out = op._step(batch([(2, 15, 1)], 15))     # the probe that waited
    ok = np.asarray(out.valid)
    assert np.asarray(out.payload["value"]["tag"])[ok].tolist() \
        == [[22, 24, 26]]


# ---------------------------------------------------------------------------
# through PipeGraph
# ---------------------------------------------------------------------------

def run_graph(ev, batch, lower=5, upper=400, P=64, order=None, config=None,
              lateness=0, out=None):
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
    b = (wf.Interval_JoinTPU_Builder(join_fn).withName("join")
         .withBuildSide(lambda e: e["b"] == 1)
         .withBoundaries(lower, upper).withMaxKeys(4096)
         .withProbeCapacity(P)
         .withMatch(lambda b, p: p["v"] >= b["v"])
         .withKeyBy(lambda e: e["k"]).withLateness(lateness))
    if out is not None:
        b = b.withOutputCapacity(out)

    def sink(r, ctx=None):
        if r is not None:
            v = r["value"]
            got.append((int(r["key"]), int(r["build_ts"]),
                        int(r["probe_ts"]), float(v["bv"]), int(v["bw"]),
                        float(v["pv"]), int(v["pw"])))
    snk = wf.Sink_Builder(sink).build()
    g = wf.PipeGraph("pair_graph", wf.ExecutionMode.DEFAULT,
                     wf.TimePolicy.EVENT, config=config or wf.Config())
    pipe = g.add_source(src)
    pipe.add(keep)
    pipe.add(b.build()).add_sink(snk)
    g.run()
    return sorted(got), g


def graph_oracle(ev, **kw):
    """The rows of the whole stream whatever the batch cuts: on a
    stream in time order with one build row a key they do not depend on
    them (a probe meets its key's one row, before or after it)."""
    return oracle_rows(ev, **kw)


@pytest.mark.parametrize("seed,batch", [(31, 96), (32, 500)])
def test_the_operator_through_pipegraph(seed, batch):
    rng = np.random.default_rng(seed)
    ev = auctions(rng, 3000)
    got, g = run_graph(ev, batch, lower=20, upper=2000)
    exp, o = graph_oracle(ev, lower=20, upper=2000)
    assert got == exp and len(got) > 500
    st = g.stats()
    ops = {x["Operator_name"]: x for x in st["Operators"]}
    j = ops["join"]
    assert j["Operator_type"] == "IntervalJoinPairsTPU"
    assert ops["filter_tpu"]["Fused_into"] == "filter_tpu|join"
    # the split of the probes that found no row to pair with into "none
    # on the key" and "outside its interval" follows the batch cuts (a
    # row past t + upper is evicted by a step); all else is the oracle's
    for stat in ("Join_build_built", "Join_build_replaced",
                 "Join_probe_matched", "Join_probe_missed_predicate",
                 "Late_tuples_dropped"):
        assert j[stat] == o.n[STATS[stat]], stat
    assert j["Join_probe_missed_no_build"] \
        + j["Join_probe_missed_interval"] \
        == o.n["miss_build"] + o.n["miss_interval"]
    assert j["Join_build_replaced"] == 0 == st["Dropped_tuples"]
    assert j["Join_probe_pending"] == 0 == j["Join_build_retained"]
    assert j["Join_build_evicted"] == j["Join_build_built"]
    assert j["Join_probe_waited"] > 0
    assert j["Join_out_capacity"] == batch
    assert (j["Join_max_keys"], j["Join_probe_capacity"]) == (4096, 64)
    # per-batch dispatch, and the reason says which operator
    assert all(e["batches"] == 0 for e in st["Megastep"]["edges"])
    # the counters have their families in the exposition
    from windflow_tpu.monitoring.openmetrics import (parse_exposition,
                                                     render_openmetrics)
    fams = parse_exposition(render_openmetrics(st))
    by = {}
    for f in ("wf_operator_join_build_rows_total",
              "wf_operator_join_probes_total"):
        for _name, labels, value in fams[f]["samples"]:
            by[labels.get("event") or labels.get("outcome")] = value
    assert by["built"] == j["Join_build_built"]
    assert by["evicted"] == j["Join_build_evicted"]
    assert by["replaced"] == 0
    assert by["matched"] == j["Join_probe_matched"]
    assert by["missed_no_build"] == j["Join_probe_missed_no_build"]
    assert by["waited"] == j["Join_probe_waited"]
    for fam, stat in (
            ("wf_operator_join_build_retained", "Join_build_retained"),
            ("wf_operator_join_probes_pending", "Join_probe_pending"),
            ("wf_operator_join_probes_pending_max",
             "Join_probe_pending_max"),
            ("wf_operator_join_rows_held_back_total",
             "Join_rows_held_back")):
        [(_n, _l, value)] = fams[fam]["samples"]
        assert value == j[stat], fam


def test_disorder_and_a_small_output_through_pipegraph():
    """Events swapped within blocks of eight, watermarks by the source's
    running maximum, a lateness wider than a block's span, an output of
    under half the batch (rows are held back and the sink still sees
    every one)."""
    rng = np.random.default_rng(33)
    ev = auctions(rng, 1200)
    order = np.concatenate([lo + rng.permutation(min(8, 1200 - lo))
                            for lo in range(0, 1200, 8)])
    got, g = run_graph(ev, 64, lower=30, upper=3000, order=order,
                       lateness=100, out=24)
    st = g.stats()
    assert st["Dropped_tuples"] == 0
    assert got == graph_oracle(ev, lower=30, upper=3000)[0]
    j = {x["Operator_name"]: x for x in st["Operators"]}["join"]
    assert j["Join_rows_held_back"] > 0


def test_a_mesh_more_replicas_and_mixed_forms_are_refused():
    from windflow_tpu.parallel.mesh import make_mesh
    op = pair_op()
    op.mesh = make_mesh(4)
    with pytest.raises(WindFlowError, match="mesh"):
        op.build_replicas(wf.ExecutionMode.DEFAULT, wf.TimePolicy.EVENT)
    b = lambda: (wf.Interval_JoinTPU_Builder(join_fn)  # noqa: E731
                 .withBuildSide(lambda e: e["b"] == 1)
                 .withKeyBy(lambda e: e["k"]))
    full = lambda: b().withBoundaries(0, 10).withMaxKeys(8)  # noqa: E731
    with pytest.raises(WindFlowError, match="one replica"):
        full().withProbeCapacity(4).withParallelism(2).build()
    with pytest.raises(WindFlowError, match="withBoundaries"):
        b().withMaxKeys(8).withProbeCapacity(4).build()
    with pytest.raises(WindFlowError, match="withMaxKeys"):
        b().withBoundaries(0, 10).withProbeCapacity(4).build()
    with pytest.raises(WindFlowError, match="withProbeCapacity"):
        full().build()
    for lower, upper in ((-1, 10), (0, 0), (5, -5)):
        with pytest.raises(WindFlowError, match="0 <= lower"):
            b().withBoundaries(lower, upper).withMaxKeys(8) \
                .withProbeCapacity(4).build()
    # a join function folds nothing, and lift / comb retain nothing
    with pytest.raises(WindFlowError, match="form that folds"):
        full().withProbeCapacity(4).withBuildCapacity(8).build()
    with pytest.raises(WindFlowError, match="form that folds"):
        full().withProbeCapacity(4).withIntervalLength(lambda e: 1).build()
    with pytest.raises(WindFlowError, match=r"emits\s+pairs"):
        (wf.Interval_JoinTPU_Builder(join_fn, lambda a, b: a)
         .withBuildSide(lambda e: e["b"] == 1).withKeyBy(lambda e: e["k"])
         .withIntervalLength(lambda e: 1).withBuildCapacity(8)
         .withBoundaries(0, 10).build())
    rng = np.random.default_rng(1)
    with pytest.raises(WindFlowError, match="mesh"):
        run_graph(auctions(rng, 200), 64,
                  config=wf.Config(mesh=make_mesh(4)))


def test_the_pair_form_names_its_program_key_space_and_phases():
    # what it says to fusion, megastep, preflight and the re-bucketer:
    # tests/test_operator_contract.py, case ``interval_join_pairs``
    from windflow_tpu.monitoring import recorder
    op = pair_op()
    assert op.notes_out_cap and op.key_space() == 4096
    assert op.program_name == "step_join_pairs"
    assert recorder.PHASES["wf.join.table"][0] == "fused operator program"


# ---------------------------------------------------------------------------
# the program
# ---------------------------------------------------------------------------

def _step_args(B, K=512, P=32, out=None):
    S = jax.ShapeDtypeStruct
    step = jk.make_join_pairs_step(
        B, K, P, lambda e: e["k"], lambda e: e["b"] == 1,
        lambda b, p: p["v"] >= b["v"], join_fn, 5, 300, out)
    one = {x: S((), DTYPES[x]) for x in LANES}
    reads_b, reads_p = jk.pair_reads(join_fn, lambda b, p: p["v"] >= b["v"],
                                     one)
    state = jax.eval_shape(lambda: jk.make_join_pairs_state(
        one, reads_b, reads_p, K, P, jk.join_out_capacity(B, out)))
    return step, (state, {x: S((B,), DTYPES[x]) for x in LANES},
                  S((B,), np.int64), S((B,), np.bool_), S((), np.int64))


def _equations(jaxpr, found=None):
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        found.append(eqn)
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _equations(sub, found)
    return found


def test_the_step_scatters_32_bit_words_and_keeps_the_lanes_it_reads():
    """The table is written by scatters of 32-bit words (a 64-bit one
    costs 8x over the same lanes on a v5e): the build row's time goes in
    as two int32 words; and it keeps, of a row, the leaves ``join`` and
    ``match`` read of the build side: ``v`` and ``w`` here, not ``k`` or
    ``b``."""
    step, args = _step_args(256)
    closed = jax.make_jaxpr(step)(*args)
    scatters = [e for e in _equations(closed.jaxpr)
                if e.primitive.name.startswith("scatter")]
    assert scatters
    for e in scatters:
        assert all(v.aval.dtype.itemsize <= 4 for v in e.invars), e
    state = args[0]
    assert [a.dtype for a in state["tab"]["row"]] == [np.float32, np.int32]
    assert state["tab"]["hi"].dtype == state["tab"]["lo"].dtype == np.int32
    assert len(state["held"]["b"]) == 2 and len(state["held"]["p"]) == 2
    # a window of an eighth of the batch, the whole width where not
    assert any(e.primitive.name == "cond" for e in closed.jaxpr.eqns)


@pytest.mark.parametrize("B,out", [(64, None), (1024, 256),
                                   (262144, 65536)])
def test_the_output_batch_and_the_state_are_sized_as_built(B, out):
    OC = jk.join_out_capacity(B, out)
    assert OC == (out or B)
    if B <= 1024:
        step, args = _step_args(B, out=out)
        st, rows, fired, out_ts, held = jax.eval_shape(step, *args)
        assert fired.shape == out_ts.shape == rows["key"].shape == (OC,)
        assert held.shape == (5,) and held.dtype == np.int64
        assert {k: v.dtype for k, v in rows.items() if k != "value"} == {
            "key": np.int32, "build_ts": np.int64, "probe_ts": np.int64}
        assert jax.tree.map(lambda a: (a.shape, a.dtype), st) \
            == jax.tree.map(lambda a: (a.shape, a.dtype), args[0])
        assert st["held"]["key"].shape == (OC,)
        assert st["pend"]["key"].shape == (32,)
        assert st["tab"]["hi"].shape == (512,)


def _lowered_sha(step, *args):
    text = jax.jit(step).lower(*args).as_text()
    return hashlib.sha256(text.encode()).hexdigest()


def test_the_fold_forms_step_is_the_parents():
    """The pair form is a second step beside ``make_join_step``, which
    lowers to the text it had at the parent commit (this backend; Q9's
    functions at a small size): ``jit_step_join`` is the program Q9's
    numbers were read on."""
    S = jax.ShapeDtypeStruct
    B, C = 1024, 64
    lanes = ("k", "b", "len", "v", "w")
    dt = dict(DTYPES, len=np.int32)

    def higher(a, b):
        b_wins = b["price"] > a["price"]
        return jax.tree.map(lambda x, y: jnp.where(b_wins, y, x), a, b)
    lift = lambda b, p, ts: {"price": p["v"], "at": ts,  # noqa: E731
                             "who": p["w"]}
    step = jk.make_join_step(
        B, C, lambda e: e["k"], lambda e: e["b"] == 1, lambda e: e["len"],
        lambda b, p: p["v"] >= b["v"], lift, higher, 256)
    one = {x: S((), dt[x]) for x in lanes}
    state = jax.eval_shape(lambda: jk.make_join_state(
        one, {"price": S((), np.float32), "at": S((), np.int64),
              "who": S((), np.int32)}, C))
    assert _lowered_sha(step, state, {x: S((B,), dt[x]) for x in lanes},
                        S((B,), np.int64), S((B,), np.bool_),
                        S((), np.int64)) == PARENT_JOIN_SHA


PARENT_JOIN_SHA = "e5e0ec751c5452e448cea20c8e12031b5ce0f243216bfdccf6e2b4f15ccbae6c"


# ---------------------------------------------------------------------------
# the benchmark's graph: public builders, default Config()
# ---------------------------------------------------------------------------

def tiny_cfg(**graph):
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "nexmark_q20.json")) as f:
        cfg = json.load(f)
    # 100 000 events a second: a bid comes up to 1 970 usec before its
    # auction and 16 670 after it; a batch of 512 events spans 5.12 ms,
    # a pass of 16 batches 81.92 ms
    cfg["graph"].update(dict(batch=512, lower_usec=4_000,
                             upper_usec=60_000, probe_capacity=128,
                             out_capacity=256), **graph)
    cfg["stream"].update(ring_batches=16, active_people=4,
                         hot_bidder_stride=8, event_rate=100_000)
    return cfg


def run_q20(rec, cfg, chunk=300):
    got = []

    def chunks():
        for lo in range(0, len(rec), chunk):
            yield rec[lo:lo + chunk].tobytes()

    def sink(c):
        if c is not None:
            got.append({k: np.asarray(v) for k, v in c.cols.items()})

    g = q20.build_graph(cfg, None, chunks, sink)
    g.run()
    cat = lambda n: np.concatenate([b[n] for b in got])  # noqa: E731
    return {n: cat(n) for n in ("key", "wid", "value")}, g


def oracle_of(rec, cfg):
    """The per-tuple oracle over the benchmark's records, the whole
    stream in one step (on this stream the rows do not depend on the
    cuts: one auction an id and a pass, the one before evicted)."""
    kind = rec[q20.KIND]
    ev = {"k": rec["k"].astype(np.int64) - q20.FIRST_AUCTION_ID,
          "t": rec["t"].astype(np.int64),
          "b": (kind == q20.AUCTION).astype(np.int32),
          # the predicate as the oracle has it: probe v >= build v
          "v": np.where(kind == q20.AUCTION,
                        (rec[q20.CATEGORY] != q20.WANTED_CATEGORY) * 2.0,
                        1.0),
          "w": rec[q20.BIDDER].astype(np.int64)}
    keep = kind != q20.PERSON
    g = cfg["graph"]
    o = Oracle(g["lower_usec"], g["upper_usec"])
    n, B = len(rec), g["batch"]
    # the columns the oracle's rows do not carry, by the event's (key,
    # time): a bid's price, channel and url, an auction's category,
    # reserve and length
    i = np.flatnonzero(keep)
    more = dict(zip(
        zip(ev["k"][i].tolist(), ev["t"][i].tolist()),
        zip(*(rec[x][i].astype(np.int64).tolist()
              for x in (q20.PRICE, q20.CHANNEL, q20.URL)))))
    for lo in range(0, n, B):
        s = np.flatnonzero(keep[lo:lo + B]) + lo
        o.step(list(zip(*(ev[x][s].tolist()
                          for x in ("k", "t", "b", "v", "w")))),
               int(ev["t"][min(lo + B, n) - 1]))
    rows = []
    for k, t, u, _bv, bw, _pv, pw in o.flush():
        price, channel, url = more[(k, u)]
        reserve, category, length = more[(k, t)]
        rows.append((k + q20.FIRST_AUCTION_ID, u, pw, price, channel, url,
                     t, bw, category, reserve, t + length))
    return sorted(rows), o


@pytest.fixture(scope="module")
def replayed():
    """The generator's own stream, two and a third passes of a ring,
    through the benchmark's graph."""
    cfg = tiny_cfg()
    ring = q20.make_ring(2**31 + 5, cfg)
    n = len(ring["rec"]) * 7 // 3
    rec = ring["rec"][np.arange(n) % len(ring["rec"])].copy()
    rec["t"] = np.arange(n) * 10                # 100 000 events a second
    got, g = run_q20(rec, cfg)
    return cfg, ring, rec, got, g


def as_rows(key, wid, value):
    v = np.asarray(value).reshape(-1, q20.N_VALUES)
    return sorted(zip(np.asarray(key).tolist(), np.asarray(wid).tolist(),
                      *(v[:, i].tolist() for i in range(q20.N_VALUES))))


def test_the_graph_agrees_with_the_oracle(replayed):
    cfg, _ring, rec, got, _g = replayed
    exp, _ = oracle_of(rec, cfg)
    assert as_rows(got["key"], got["wid"], got["value"]) == exp
    assert len(exp) > 2000


def test_the_closed_form_agrees_with_the_oracle(replayed):
    cfg, ring, rec, got, _g = replayed
    exp = q20.expected(cfg, ring, len(rec), {"event_rate": 100_000})
    orc, o = oracle_of(rec, cfg)
    assert as_rows(*exp.rows()) == orc and len(exp.key) == len(orc)
    assert all(c["ok"] for c in q20.compare(cfg, got, exp))
    assert exp.counts == {
        "matched": o.n["matched"], "missed_predicate": o.n["miss_pred"],
        "missed_no_build": o.n["miss_build"], "built": o.n["built"]}
    assert o.n["miss_interval"] == 0 == o.n["replaced"] == o.n["late"]
    # the last pass is partial, and bids waited across batch cuts
    assert len(rec) % len(ring["rec"]) and o.n["waited"] > 0
    assert o.n["miss_build"] > 0


def test_the_graph_is_one_fused_program_a_batch(replayed):
    cfg, ring, rec, _got, g = replayed
    st = g.stats()
    ops = {o["Operator_name"]: o for o in st["Operators"]}
    j = ops["expand_bid"]
    assert j["Operator_type"] == "IntervalJoinPairsTPU"
    # the person filter rides in the join step's program
    assert ops["filter_tpu"]["Fused_into"] == "filter_tpu|expand_bid"
    counts = q20.expected(cfg, ring, len(rec),
                          {"event_rate": 100_000}).counts
    assert j["Join_build_built"] == counts["built"]
    assert j["Join_probe_matched"] == counts["matched"]
    assert j["Join_probe_missed_predicate"] == counts["missed_predicate"]
    assert j["Join_probe_missed_no_build"] == counts["missed_no_build"]
    assert j["Join_probe_missed_interval"] == 0 == j["Join_build_replaced"]
    assert j["Join_build_evicted"] == counts["built"]
    assert j["Join_build_retained"] == 0 == j["Join_probe_pending"]
    assert 0 < j["Join_probe_pending_max"] <= j["Join_probe_waited"]
    assert j["Late_tuples_dropped"] == 0 == st["Dropped_tuples"]
    assert j["Join_out_capacity"] == 256          # withOutputCapacity
    from windflow_tpu.monitoring.jit_registry import default_registry
    names = set(default_registry().snapshot())
    assert "filter_tpu|expand_bid" in names
    # the end of stream ran the step's own program: nothing compiled there
    assert not [n for n in names if "flush" in n and "expand" in n]
    # no scan: the tail keeps per-batch dispatch
    assert all(e["batches"] == 0 for e in st["Megastep"]["edges"])


@pytest.mark.parametrize("fault", ["clock", "no_category", "never_waits",
                                   "keeps_for_ever", "row_lost"])
def test_a_wrong_program_fails_a_check(replayed, fault):
    """Expanded bids with a clock rounded to the millisecond, without
    the category filter, from a join in which no bid waits for its
    auction, from one that never forgets an auction (a bid on an id not
    yet created meets the auction that id had a pass earlier), and with
    one row lost."""
    cfg, ring, rec, got, _g = replayed
    mix = {"event_rate": 100_000}
    exp = q20.expected(cfg, ring, len(rec), mix)
    wrong_cfg = json.loads(json.dumps(cfg))
    wrong_ring = ring
    if fault == "row_lost":
        wrong = {k: v[1:] for k, v in got.items()}
    elif fault == "clock":
        # the control's values come a pass at a time
        wrong = dict(zip(("key", "wid", "value"),
                         q20.control(cfg, ring, len(rec), mix)))
        assert isinstance(wrong["value"], list)
    elif fault == "keeps_for_ever":
        # the auction of the pass before, where this pass's comes later
        k, w, v = (a.copy() for a in exp.rows())
        at = q20.A_DATETIME
        early = np.flatnonzero((v[:, at] > w) & (w >= exp.one.period))
        assert len(early) > 10
        v[early, at] -= exp.one.period
        wrong = {"key": k, "wid": w, "value": v}
    else:
        if fault == "no_category":
            rec2 = ring["rec"].copy()
            rec2[q20.CATEGORY] = q20.WANTED_CATEGORY
            wrong_ring = {"rec": rec2}
        else:
            wrong_cfg["graph"]["lower_usec"] = 0
        wrong = dict(zip(("key", "wid", "value"), q20.ExpandedBids(
            wrong_ring["rec"], wrong_cfg, len(rec)).rows()))
    checks = q20.compare(cfg, wrong, exp)
    bad = {c["name"] for c in checks if not c["ok"]}
    assert bad and (fault != "keeps_for_ever"
                    or bad == {"count_mismatches"}), fault
    assert ("key_wid_mismatches" in bad) == (fault != "keeps_for_ever")


def test_a_join_that_is_not_q20s_on_the_stream_is_refused():
    """``make_ring`` holds the retention that was cut to the stream: a
    bid further from its auction than the bounds, and a retention that
    reaches the pass before, are refused."""
    with pytest.raises(ValueError, match="outside"):
        q20.make_ring(3, tiny_cfg(lower_usec=500))
    with pytest.raises(ValueError, match="outside"):
        q20.make_ring(3, tiny_cfg(upper_usec=5_000))
    with pytest.raises(ValueError, match="a pass earlier"):
        q20.make_ring(3, tiny_cfg(upper_usec=72_000))
    with pytest.raises(ValueError, match="held back take"):
        q20.make_ring(3, tiny_cfg(out_capacity=16))
    ring = q20.make_ring(3, tiny_cfg())
    assert 1_500 < ring["lead_reach_usec"] <= 2_000
    assert ring["bids_without_auction_a_pass"] >= 0


def test_the_stream_has_the_sources_shapes():
    cfg = tiny_cfg(batch=4096, out_capacity=2048)
    rec = q20.make_ring(5, cfg)["rec"]
    kind = rec[q20.KIND]
    n = len(rec)
    assert abs((kind == q20.BID).sum() / n - 46 / 50) < 1e-3
    assert abs((kind == q20.AUCTION).sum() / n - 3 / 50) < 1e-3
    # Q5's ring, seed for seed: kinds and ids
    q5 = harness.load_module("configs", "nexmark_q5")
    rec5 = q5.make_ring(5, {"graph": {"batch": 4096, "max_keys": 1 << 62},
                            "stream": cfg["stream"]})["rec"]
    assert np.array_equal(rec["k"], rec5["k"])
    assert np.array_equal(kind, rec5[q5.KIND])
    cats = rec[q20.CATEGORY][kind == q20.AUCTION]
    assert set(np.unique(cats).tolist()) == {10.0, 11.0, 12.0, 13.0, 14.0}
    assert abs((cats == 10).mean() - 0.2) < 0.02
    # half the bids on the hot auction: runs of hundreds on one key
    bids = rec["k"][kind == q20.BID]
    hot = (bids - q20.FIRST_AUCTION_ID) % 100 == 0
    assert 0.49 < hot.mean() < 0.53
    price = rec[q20.PRICE][kind == q20.BID]
    assert price.max() <= 1_000_000 and price.min() >= 1
    # an auction's reserve is two prices summed, its length Q9's rule
    auction = kind == q20.AUCTION
    assert 2 <= rec[q20.RESERVE][auction].min() \
        and rec[q20.RESERVE][auction].max() <= 2_000_000
    q9 = harness.load_module("configs", "nexmark_q9")
    top = 2 * q9.horizon_usec(cfg["stream"]["event_rate"])
    assert 1 <= rec[q20.LENGTH][auction].min() \
        and top // 2 < rec[q20.LENGTH][auction].max() <= top
    # a bid's channel and url are Q5's draws
    assert np.array_equal(rec[q20.CHANNEL][~auction], rec5["v3"][~auction])
    assert np.array_equal(rec[q20.URL][~auction], rec5["v4"][~auction])
    # the seller of an auction: the hot one three times in four
    sellers = rec[q20.SELLER][kind == q20.AUCTION]
    _, counts = np.unique(sellers, return_counts=True)
    assert counts.max() > 10 * np.median(counts)


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
    run_q20(rec[:4096], cfg)
    caps = [kw["out_cap"] for n, kw in seen
            if n == "wf.dispatch" and kw.get("op") == "expand_bid"]
    assert caps and set(caps) == {256}
