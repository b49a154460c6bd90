"""NEXmark Q5 (hot items) at small sizes on the CPU backend: the two-stage
window graph of ``benchmark/configs/nexmark_q5.py`` against its plain
reference and a per-tuple oracle, the first stage's compacted rows for
every key, and what the graph forced on the program: a window step whose
output batch is sized by what it can fire, window stages told apart by
their program's name, ``out_cap=`` on ``wf.dispatch``, a sink lane that
carries a small record a row."""

import collections
import hashlib
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import windflow_tpu as wf  # noqa: E402
from benchmark import harness  # noqa: E402
from benchmark.generator import frame_dtype  # noqa: E402
from windflow_tpu.io import FrameSource  # noqa: E402
from windflow_tpu.windows import ffat_kernels as fk  # noqa: E402

q5 = harness.load_module("configs", "nexmark_q5")

WINDOW, SLIDE = 10_000, 5_000          # usec: 10 ms / 5 ms of event time
BATCH, KEYS = 512, 256


def tiny_cfg(**graph):
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "nexmark_q5.json")) as f:
        cfg = json.load(f)
    cfg["graph"].update(dict(batch=BATCH, window_usec=WINDOW,
                             slide_usec=SLIDE, max_keys=KEYS), **graph)
    cfg["stream"]["ring_batches"] = 8
    return cfg


# ---------------------------------------------------------------------------
# per-tuple oracles: the same semantics, one event at a time
# ---------------------------------------------------------------------------

def oracle_counts(auctions, tss, kinds):
    """``{(auction, window): bids}`` over windows ``[w * SLIDE, w * SLIDE
    + WINDOW)``, ``w >= 0``."""
    panes = WINDOW // SLIDE
    cells = collections.Counter()
    for a, t, k in zip(auctions.tolist(), tss.tolist(), kinds.tolist()):
        if k != q5.BID:
            continue
        p = t // SLIDE
        for w in range(max(0, p - panes + 1), p + 1):
            cells[(a, w)] += 1
    return cells


def oracle_hot_items(auctions, tss, kinds):
    """Rows ``(auction, window, count, auctions, bids)`` sorted by
    window: the most bids, ties to the lowest id."""
    by_w = collections.defaultdict(list)
    for (a, w), c in oracle_counts(auctions, tss, kinds).items():
        by_w[w].append((-c, a))
    rows = []
    for w in sorted(by_w):
        c, a = min(by_w[w])
        rows.append((a, w, -c, len(by_w[w]), -sum(x for x, _ in by_w[w])))
    return np.array(rows, np.int64).reshape(-1, 5)


# ---------------------------------------------------------------------------
# streams
# ---------------------------------------------------------------------------

def frames(auctions, tss, kinds=None):
    rec = np.zeros(len(auctions), dtype=frame_dtype(q5.N_FIELDS))
    rec["k"] = auctions
    rec["t"] = tss
    rec[q5.KIND] = q5.BID if kinds is None else kinds
    return rec


def run_q5(rec, chunk=300, pause_after=None, cfg=None):
    """The benchmark's own graph over ``rec``; returns rows ``(auction,
    window, count, auctions, bids)`` sorted by window, and the graph."""
    cfg = cfg or tiny_cfg()
    got = []

    def chunks():
        for lo in range(0, len(rec), chunk):
            yield rec[lo:lo + chunk].tobytes()
            if pause_after is not None and lo <= pause_after < lo + chunk:
                # the source falls silent: the cadence punctuation cuts
                # the batch that is filling
                t_end = time.monotonic() + 0.35
                while time.monotonic() < t_end:
                    yield b""

    def sink(c):
        if c is not None:
            got.append({k: np.asarray(v) for k, v in c.cols.items()})

    g = q5.build_graph(cfg, None, chunks, sink)
    g.run()
    cat = lambda n: np.concatenate([b[n] for b in got]) if got \
        else np.empty(0, np.int64)  # noqa: E731
    rows = np.c_[cat("key"), cat("wid"),
                 cat("value").reshape(-1, 3)].astype(np.int64)
    return rows[np.argsort(rows[:, 1], kind="stable")], g


def moving_hot_key():
    """The generator's own stream: two and a third passes of a ring."""
    cfg = tiny_cfg()
    ring = q5.make_ring(2**31 + 5, cfg)["rec"]
    n = len(ring) * 7 // 3
    rec = ring[np.arange(n) % len(ring)].copy()
    rec["t"] = np.arange(n) * 10            # 100 000 events a second
    return rec


def a_tie():
    """Auctions 1009 and 1005 both get 40 bids in window 0 (and the
    higher id gets them first); 1007 gets 39."""
    a = np.r_[np.full(40, 1009), np.full(39, 1007), np.full(40, 1005),
              1000 + np.arange(20, 120)]
    return frames(a, np.arange(len(a)) * 60)


def an_empty_pane():
    """Bids in panes 0 and 1, none for three slides, then panes 5 and 6:
    window 2 to 3 hold nothing and give no row."""
    rng = np.random.default_rng(3)
    t = np.r_[np.sort(rng.integers(0, 2 * SLIDE, 700)),
              np.sort(rng.integers(5 * SLIDE, 7 * SLIDE, 900))]
    return frames(1000 + rng.integers(0, 90, len(t)), t)


def eos_inside_a_window():
    """The stream ends 0.3 of a slide into pane 3: windows 2 and 3 fire
    at end of stream with what they hold."""
    rng = np.random.default_rng(4)
    t = np.sort(rng.integers(0, int(3.3 * SLIDE), 1500))
    kinds = np.where(np.arange(len(t)) % 50 < 4, q5.AUCTION, q5.BID)
    return frames(1000 + rng.integers(0, 200, len(t)), t, kinds)


CASES = {"moving_hot_key": (moving_hot_key, None),
         "a_tie": (a_tie, None),
         "an_empty_pane": (an_empty_pane, None),
         "eos_inside_a_window": (eos_inside_a_window, None),
         "a_punctuation_cut_batch": (moving_hot_key, 5000)}


@pytest.mark.parametrize("case", sorted(CASES))
def test_q5_graph_matches_the_per_tuple_oracle(case):
    make, pause_after = CASES[case]
    rec = make()
    rows, g = run_q5(rec, pause_after=pause_after)
    want = oracle_hot_items(rec["k"], rec["t"], rec[q5.KIND])
    assert len(want) >= 2
    assert np.array_equal(rows, want)
    st = g.stats()
    assert st["Dropped_tuples"] == 0
    if case == "a_tie":
        assert rows[0].tolist()[:3] == [1005, 0, 40]
    if case == "an_empty_pane":
        assert rows[:, 1].tolist() == [0, 1, 4, 5, 6]


@pytest.mark.parametrize("case,wide", [("moving_hot_key", False),
                                       ("an_empty_pane", True)])
def test_q5_counts_its_wide_placements(monkeypatch, case, wide):
    """Past the contraction's constant (the cell's 43 M cells are; this
    small grid is made to be) the first stage scatters each batch into
    the panes it spans: ``TB_wide_placements`` stays 0 on the ordered
    bid stream and counts the batch that straddles a gap of more than
    ``NARROW_PLACE_PANES`` panes, with the oracle's rows either way."""
    from windflow_tpu.monitoring.openmetrics import (parse_exposition,
                                                     render_openmetrics)
    monkeypatch.setattr(fk, "DENSE_PLACE_MAX_CELLS", 0)
    rec = CASES[case][0]()
    rows, g = run_q5(rec)
    assert np.array_equal(
        rows, oracle_hot_items(rec["k"], rec["t"], rec[q5.KIND]))
    st = g.stats()
    op = next(o for o in st["Operators"]
              if o["Operator_name"] == "bids_per_auction")
    assert op["TB_placement"] == "scatter"
    assert (op["TB_wide_placements"] > 0) == wide
    fams = parse_exposition(render_openmetrics(st))
    assert [(s[1]["operator"], s[2]) for s in
            fams["wf_operator_tb_wide_placements_total"]["samples"]] \
        == [("bids_per_auction", op["TB_wide_placements"])]


def test_q5_graph_matches_the_plain_reference():
    """Through ``expected`` and ``compare``, as a run of the cell does."""
    cfg = tiny_cfg()
    ring = q5.make_ring(2**31 + 5, cfg)
    rec = moving_hot_key()
    rows, _ = run_q5(rec)
    exp = q5.expected(cfg, ring, len(rec), {"event_rate": 100_000})
    got = {"key": rows[:, 0], "wid": rows[:, 1], "value": rows[:, 2:]}
    checks = q5.compare(cfg, got, exp)
    assert all(c["ok"] for c in checks), checks
    # a wrong digest, a wrong winner and a lost row each fail
    bad = dict(got, value=got["value"] + np.array([0, 1, 0]))
    assert not all(c["ok"] for c in q5.compare(cfg, bad, exp))
    bad = dict(got, key=got["key"] + (np.arange(len(rows)) == 1))
    assert not all(c["ok"] for c in q5.compare(cfg, bad, exp))
    bad = {k: v[1:] for k, v in got.items()}
    assert not all(c["ok"] for c in q5.compare(cfg, bad, exp))


@pytest.mark.parametrize("n_total,offset", [
    (3000, 0), (8 * BATCH, 0), (8 * BATCH * 7 // 3, 0), (20011, 0),
    (8 * BATCH * 7 // 3, 500)])
def test_closed_form_matches_the_per_tuple_oracle(n_total, offset):
    """The reference counts a pane from whole passes of the ring plus its
    ends; the oracle walks the materialized stream."""
    cfg = tiny_cfg()
    ring = q5.make_ring(77, cfg)["rec"]
    keys = q5._bid_keys({"rec": ring})
    got = q5.hot_items(keys, n_total, 100_000, WINDOW, SLIDE, KEYS,
                       stamp_offset_usec=offset)
    i = np.arange(n_total)
    rec = ring[i % len(ring)]
    want = oracle_hot_items(rec["k"], i * 10 + offset, rec[q5.KIND])
    assert np.array_equal(np.c_[got.key, got.wid, got.value], want)
    # a window is full once a later event passed its end
    last = (n_total - 1) * 10 + offset
    assert np.array_equal(got.full, (got.wid * SLIDE + WINDOW) <= last)


def test_the_ring_follows_the_generators_rule():
    cfg = tiny_cfg()
    rec = q5.make_ring(5, cfg)["rec"]
    i = np.arange(len(rec))
    kind = rec[q5.KIND].astype(int)
    assert np.bincount(kind[:4000]).tolist() == [80, 240, 3680]
    # auctions are numbered in order from FIRST_AUCTION_ID
    assert np.array_equal(rec["k"][kind == q5.AUCTION],
                          1000 + np.arange(np.count_nonzero(kind == 1)))
    bids = kind == q5.BID
    last = q5.last_auction(i)
    rel = last[bids] - (rec["k"][bids] - 1000)
    hot = (rec["k"][bids] - 1000) == last[bids] // 100 * 100
    assert 0.45 < hot.mean() < 0.56
    # the others go to the newest hundred and at most ten ids ahead
    assert rel[~hot].min() >= -q5.AUCTION_ID_LEAD and rel[~hot].max() <= 100
    assert np.all(rec["k"][bids] - 1000 < KEYS)
    with pytest.raises(ValueError):
        q5.make_ring(5, tiny_cfg(max_keys=100))


# ---------------------------------------------------------------------------
# the first stage alone: every (auction, window) row
# ---------------------------------------------------------------------------

def test_first_stage_fires_every_cell_once_through_the_compacted_batch():
    """The sliding count's rows as the second stage is handed them,
    against the oracle's count of every (auction, window): a cold key
    lost in the compaction, or a row fired twice, fails."""
    rec = moving_hot_key()
    got = []

    def chunks():
        for lo in range(0, len(rec), 300):
            yield rec[lo:lo + 300].tobytes()

    src = FrameSource(chunks, nv=q5.N_FIELDS, fmt="frames",
                      output_batch_size=BATCH)
    src.record_spec = {"key": np.int32(0), **{
        f"v{i}": np.float32(0.0) for i in range(q5.N_FIELDS)}}
    flt = wf.FilterTPU_Builder(lambda e: e[q5.KIND] == float(q5.BID)).build()
    win = (wf.Ffat_WindowsTPU_Builder(lambda e: jnp.int64(1),
                                      lambda a, b: a + b)
           .withName("bids_per_auction").withTBWindows(WINDOW, SLIDE)
           .withKeyBy(lambda e: e["key"] - 1000).withMaxKeys(KEYS)
           .withSumCombiner().build())
    caps = set()

    def sink(c):
        if c is not None:
            got.append({k: np.asarray(v) for k, v in c.cols.items()})

    snk = wf.Sink_Builder(sink).withColumnarSink().build()
    g = wf.PipeGraph("q5_stage1", wf.ExecutionMode.DEFAULT,
                     wf.TimePolicy.EVENT, config=wf.Config())
    g.add_source(src).add(flt).add(win).add_sink(snk)
    step = win._step

    def spy(batch, ridx=0):
        out = step(batch, ridx)
        caps.add(out.capacity)
        return out
    win._step = spy
    g.run()
    rows = collections.Counter()
    for b in got:
        for a, w, c in zip(b["key"].tolist(), b["wid"].tolist(),
                           b["value"].tolist()):
            assert (a + 1000, w) not in rows, "fired twice"
            rows[(a + 1000, w)] = c
    want = oracle_counts(rec["k"], rec["t"], rec[q5.KIND])
    assert len(want) > 1000 and rows == want
    # the batch handed on: one window's keys and the rows a batch opens
    assert caps == {KEYS + BATCH * 2}
    assert fk.tb_out_capacity(BATCH, KEYS, 2, 1, win.NP) == KEYS + BATCH * 2


# ---------------------------------------------------------------------------
# the window step's output batch
# ---------------------------------------------------------------------------

def _tb_step_shapes(B, K, R, D, NP):
    S = jax.ShapeDtypeStruct
    step = fk.make_ffat_tb_step(B, K, 1000, R, D, NP, lambda e: e["one"],
                                lambda a, b: a + b, lambda e: e["k"],
                                monoid="sum", drop_tainted=True)
    state = jax.eval_shape(lambda: fk.make_ffat_tb_state(
        jnp.zeros((), jnp.int64), K, NP))
    args = ({"k": S((B,), np.int32), "one": S((B,), np.int64)},
            S((B,), np.int64), S((B,), np.bool_), S((), np.int64))
    return step, state, args


@pytest.mark.parametrize("K,B,R,D", [(512, 1024, 2, 1), (4096, 256, 4, 2),
                                     (64, 16, 1, 1)])
def test_window_output_capacity_does_not_grow_with_the_grid(K, B, R, D):
    """Static per built step: ``K`` lanes for one window's keys and
    ``ceil(R / D)`` for every lane of the input batch, whatever the ring;
    the whole ``K x 3 (NP // D + 2)`` grid only while that is smaller."""
    lanes = {}
    for NP in (2 * R, 66, 130, 514):
        step, state, args = _tb_step_shapes(B, K, R, D, NP)
        _st, out, fired, out_ts, _n = jax.eval_shape(step, state, *args)
        lanes[NP] = fired.shape[0]
        assert out["key"].shape == out["wid"].shape == out_ts.shape \
            == out["value"].shape == fired.shape
        assert lanes[NP] == fk.tb_out_capacity(B, K, R, D, NP) \
            == min(K * 3 * (NP // D + 2), K + B * -(-R // D))
    assert lanes[66] == lanes[130] == lanes[514] == K + B * -(-R // D)


def test_the_rule_keeps_ysbs_grid_and_compacts_q5s():
    # YSB: 100 campaigns x 201 slots stay one grid (smaller than a batch)
    assert fk.tb_out_capacity(262144, 100, 1, 1, 65) == 100 * 201 == 20100
    # Q5: 133.7 M lanes of grid become one window's keys + two batches
    assert 655360 * 3 * 68 == 133_693_440
    assert fk.tb_out_capacity(262144, 655360, 2, 1, 66) \
        == 655360 + 2 * 262144 == 1_179_648


def _digest(tree) -> str:
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(tree):
        a = np.ascontiguousarray(np.asarray(leaf))
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


def test_ysb_step_is_bit_identical_to_the_parents():
    """The YSB window step (100 campaigns, 65 panes, declared int64 sum,
    drop policy) on five seeded batches that fire 3.3 windows each: every
    lane of every output and the final state hash to what the commit
    before the compaction gave (918cfd9, same seeds, this backend)."""
    B, K, NP, P = 32768, 100, 65, 10_000
    rng = np.random.default_rng(7)
    step = jax.jit(fk.make_ffat_tb_step(
        B, K, P, 1, 1, NP, lambda e: e["one"], lambda a, b: a + b,
        lambda e: e["campaign"], monoid="sum", drop_tainted=True))
    st = fk.make_ffat_tb_state(jnp.zeros((), jnp.int64), K, NP)
    trail = []
    for i in range(5):
        ts = (i * B + np.arange(B)).astype(np.int64)
        payload = {"campaign": jnp.asarray(
            rng.integers(0, K, B).astype(np.int32)),
            "one": jnp.ones(B, jnp.int64)}
        valid = jnp.asarray(rng.random(B) < 0.33)
        st, out, fired, out_ts, n_adv = step(
            st, payload, jnp.asarray(ts), valid,
            jnp.int64((ts[-1] - 500) // P))
        trail.append((out, fired, out_ts, n_adv))
    assert fired.shape == (20100,)
    assert int(st.pop("n_wide")) == 0       # new in PR 31; the rest as was
    # new in PR 35: every one of the five steps fired windows and advanced
    assert int(st.pop("n_ring_advances")) == 5
    assert _digest((trail, st)) == ("c692f131125060f60866a92e023ae6a0"
                                    "d6622524764aefd23e6f96d975db7880")


def test_ffat_sum_step_is_bit_identical_to_the_parents():
    """The count-based step of ``ffat_sum`` (user combiner, f32 sums) on
    four seeded batches, against the parent's hash: nothing of it moved."""
    import math
    B, K, WIN, SL = 4096, 16, 64, 16
    P = math.gcd(WIN, SL)
    rng = np.random.default_rng(11)
    step = jax.jit(fk.make_ffat_step(
        B, K, P, WIN // P, SL // P, lambda e: e["v"] * 1.5 + 1.0,
        lambda a, b: a + b, lambda e: e["key"]))
    st = fk.make_ffat_state(jnp.zeros((), jnp.float32), K, WIN // P)
    trail = []
    for i in range(4):
        payload = {"key": jnp.asarray(rng.integers(0, K, B).astype(np.int32)),
                   "v": jnp.asarray(rng.random(B).astype(np.float32))}
        ts = jnp.asarray((i * B + np.arange(B)).astype(np.int64))
        valid = jnp.asarray(rng.random(B) < 0.9)
        st, out, fired, out_ts = step(st, payload, ts, valid)
        trail.append((out, fired, out_ts))
    assert _digest((trail, st)) == ("07da8521098e1d834f819d46aafc52c9"
                                    "4faff0eee2689ce46d894676c731c8a7")


def _drive(step, st, batches, P, B):
    """Run ``batches`` then flush with empty batches and an infinite
    watermark until the frontier stops; every fired row, per step."""
    per_step = []

    def take(out, fired, out_ts):
        f = np.asarray(fired)
        per_step.append(np.c_[np.asarray(out["key"])[f],
                              np.asarray(out["wid"])[f],
                              np.asarray(out["value"])[f],
                              np.asarray(out_ts)[f]])
    for keys, ts, wm in batches:
        payload = {"k": jnp.asarray(keys, jnp.int32),
                   "one": jnp.ones(len(keys), jnp.int64)}
        st, out, fired, out_ts, _ = step(
            st, payload, jnp.asarray(ts, jnp.int64),
            jnp.ones(len(keys), bool), jnp.int64(wm))
        take(out, fired, out_ts)
    zero = {"k": jnp.zeros(B, jnp.int32), "one": jnp.zeros(B, jnp.int64)}
    for _ in range(200):
        st, out, fired, out_ts, n_adv = step(
            st, zero, jnp.zeros(B, jnp.int64), jnp.zeros(B, bool),
            jnp.int64(1 << 60))
        take(out, fired, out_ts)
        if int(n_adv) == 0:
            break
    else:
        raise AssertionError("the flush loop did not end")
    return per_step, st


def test_windows_that_do_not_fit_wait_and_none_is_lost_or_fired_twice():
    """64 keys, batches of 16 lanes: the output holds 80 rows.  Every key
    gets data in six windows while the watermark stands still, then it
    jumps past them all: 384 rows are due at once and leave 64 a step
    (one whole window; the next one waits), in window order, each once."""
    K, B, P, NP = 64, 16, 1000, 66
    step, st, _ = _tb_step_shapes(B, K, 1, 1, NP)
    assert fk.tb_out_capacity(B, K, 1, 1, NP) == 80
    step = jax.jit(step)
    st = fk.make_ffat_tb_state(jnp.zeros((), jnp.int64), K, NP)
    batches = []
    for w in range(6):
        for lo in range(0, K, B):
            for rep in range(1 + (lo == 0)):      # keys 0..15 count twice
                batches.append((np.arange(lo, lo + B),
                                np.full(B, w * P + rep), -1))
    batches.append((np.arange(B), np.full(B, 6 * P + 1), 6))
    per_step, st = _drive(step, st, batches, P, B)
    rows = np.concatenate(per_step)
    want = np.array([(k, w, 1 + (k < 16), (w + 1) * P - 1)
                     for w in range(6) for k in range(K)]
                    + [(k, 6, 1, 7 * P - 1) for k in range(B)])
    assert np.array_equal(rows, want)            # window by window, in order
    sizes = [len(r) for r in per_step if len(r)]
    # the last step takes window 5 and the 16 rows of window 6 behind it
    assert sizes == [64] * 5 + [80]
    assert int(st["n_win_dropped"]) == 0 and int(st["n_evicted"]) == 0


def test_two_windows_share_a_step_when_both_fit():
    """Sparse windows (8 keys each) go out together: the prefix that fits
    behind the rows already in the batch, not one window a pass."""
    K, B, P, NP = 64, 16, 1000, 66
    step, _, _ = _tb_step_shapes(B, K, 1, 1, NP)
    step = jax.jit(step)
    st = fk.make_ffat_tb_state(jnp.zeros((), jnp.int64), K, NP)
    batches = [(np.arange(8).repeat(2) + 8 * w,
                np.full(B, w * P), -1) for w in range(7)]
    batches.append((np.zeros(B, int), np.full(B, 8 * P), 8))
    per_step, st = _drive(step, st, batches, P, B)
    fired_at_the_jump = per_step[len(batches) - 1]
    assert len(fired_at_the_jump) == 7 * 8       # all seven in one step
    assert np.array_equal(np.unique(fired_at_the_jump[:, 1]), np.arange(7))
    assert np.all(fired_at_the_jump[:, 2] == 2)


def test_drop_policy_counts_a_suppressed_window_once_when_it_goes():
    """A window tainted by an eviction is suppressed and counted in the
    pass that advances past it, not in one that left it waiting."""
    K, B, P, NP = 8, 4, 1000, 4
    S = fk.tb_out_capacity(B, K, 1, 1, NP)
    assert S == K + B                               # compacting
    step, _, _ = _tb_step_shapes(B, K, 1, 1, NP)
    step = jax.jit(step)
    st = fk.make_ffat_tb_state(jnp.zeros((), jnp.int64), K, NP)
    # panes 0..3 fill the ring; pane 9 forces a roll that evicts them
    batches = [(np.arange(B), np.full(B, p * P), -1) for p in range(4)]
    batches.append((np.arange(B), np.full(B, 9 * P), -1))
    per_step, st = _drive(step, st, batches, P, B)
    rows = np.concatenate(per_step)
    assert rows[:, 1].tolist() == [9] * B           # only the clean window
    assert int(st["n_evicted"]) == 4 * B
    assert int(st["n_win_dropped"]) == 4 * B        # each lost (key, window)


# ---------------------------------------------------------------------------
# tracing: the stages told apart, the capacity handed on
# ---------------------------------------------------------------------------

class _Annotation:
    """Stands where ``jax.profiler.TraceAnnotation`` does and keeps the
    name and counts a capture would."""

    made = []

    def __init__(self, name, **counts):
        self.name, self.counts = name, dict(counts)
        _Annotation.made.append(self)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **counts):
        self.counts.update(counts)


def test_two_window_stages_are_told_apart(monkeypatch):
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Annotation)
    _Annotation.made = []
    rows, g = run_q5(moving_hot_key()[:6000])
    assert len(rows)
    ops = {op.name: op for op in g._operators}
    first, second = ops["bids_per_auction"], ops["hot_item"]
    assert (first.window_stage, second.window_stage) == (1, 2)
    assert (first.program_name, second.program_name) == ("step", "step_w2")
    # the module names a device trace shows (jit_<function>)
    from windflow_tpu.megastep import _raw_fn
    assert _raw_fn(first._jit_step).__name__ == "step"
    assert _raw_fn(second._jit_step).__name__ == "step_w2"
    # spans: two window dispatches a batch, each under its operator's
    # name, each noting the capacity of the batch it hands on
    disp = [a.counts for a in _Annotation.made if a.name == "wf.dispatch"]
    by_op = collections.defaultdict(set)
    for c in disp:
        by_op[c["op"]].add(c.get("out_cap"))
    assert by_op["hot_item"] == {3 * (second.NP + 2)}     # 1 key: the grid
    cap1 = KEYS + 2 * BATCH
    assert by_op["bids_per_auction"] == {cap1}
    assert by_op["hot_item_row"] == {None}                # same capacity
    assert "out_cap" not in next(c for c in disp
                                 if c["op"] == "staging.unpack")
    # g.stats(): both stages by name, the first with its placement
    st = {o["Operator_name"]: o for o in g.stats()["Operators"]}
    assert st["bids_per_auction"]["TB_placement"] in ("dense", "scatter")
    assert "TB_placement" not in st["hot_item"]           # no declared monoid
    assert st["hot_item"]["Late_tuples_dropped"] == 0


def test_a_window_after_a_split_or_a_map_is_still_the_second_stage():
    from windflow_tpu.windows.ffat_tpu import (FfatWindowsTPU,
                                               number_window_stages)
    mk = lambda n: (wf.Ffat_WindowsTPU_Builder(  # noqa: E731
        lambda e: e, lambda a, b: a + b).withName(n)
        .withTBWindows(10, 10).build())
    w1, w2, w3, lone = mk("w1"), mk("w2"), mk("w3"), mk("lone")
    m = wf.MapTPU_Builder(lambda e: e).build()
    ups = {id(m): [(w1, False)], id(w2): [(m, True)],
           id(w3): [(w2, False), (w1, False)]}
    number_window_stages([w1, m, w2, w3, lone], ups)
    assert [o.window_stage for o in (w1, w2, w3, lone)] == [1, 2, 3, 1]
    assert isinstance(w3, FfatWindowsTPU) and w3.program_name == "step_w3"


def test_the_columnar_sink_carries_a_record_a_row():
    """A lane with trailing dimensions rides the one packed egress copy
    (it fell back to a transfer per lane before)."""
    from windflow_tpu.batch import (DeviceBatch, _egress_packable,
                                    device_to_columns)
    cap = 12
    rng = np.random.default_rng(0)
    value = rng.integers(-2**62, 2**62, (cap, 3))
    pair = rng.random((cap, 2, 2)).astype(np.float32)
    valid = np.arange(cap) % 3 != 1
    b = DeviceBatch({"key": jnp.arange(cap, dtype=jnp.int32),
                     "value": jnp.asarray(value), "pair": jnp.asarray(pair),
                     "flag": jnp.asarray(valid)},
                    jnp.arange(cap, dtype=jnp.int64) * 7,
                    jnp.asarray(valid))
    assert _egress_packable(b)[0]
    cols, tss = device_to_columns(b)
    assert np.array_equal(cols["value"], value[valid])
    assert cols["value"].dtype == np.int64
    assert np.array_equal(cols["pair"], pair[valid])
    assert np.array_equal(cols["key"], np.arange(cap)[valid])
    assert np.array_equal(tss, (np.arange(cap) * 7)[valid])


@pytest.mark.parametrize("stages,edges", [(1, 1), (2, 0)])
def test_megastep_scans_only_a_tail_that_feeds_the_host(stages, edges):
    """The K-scan's one drain copies the stacked outputs to the host: the
    copy a sink would make anyway.  A first window stage that feeds a
    second one on the device keeps its per-batch dispatch (on the chip
    the scan over Q5's first stage drained 274 MB a group and split the
    runs of the cell by how often a group happened to fill)."""
    import dataclasses
    rec = moving_hot_key()[:4000]

    def chunks():
        for lo in range(0, len(rec), 300):
            yield rec[lo:lo + 300].tobytes()

    src = FrameSource(chunks, nv=q5.N_FIELDS, fmt="frames",
                      output_batch_size=BATCH)
    src.record_spec = {"key": np.int32(0), **{
        f"v{i}": np.float32(0.0) for i in range(q5.N_FIELDS)}}
    win = (wf.Ffat_WindowsTPU_Builder(lambda e: jnp.int64(1),
                                      lambda a, b: a + b)
           .withTBWindows(WINDOW, SLIDE).withKeyBy(lambda e: e["key"] - 1000)
           .withMaxKeys(KEYS).withSumCombiner().build())
    got = []
    snk = wf.Sink_Builder(got.append).withColumnarSink().build()
    g = wf.PipeGraph("q5_mega", wf.ExecutionMode.DEFAULT,
                     wf.TimePolicy.EVENT, config=dataclasses.replace(
                         wf.default_config, megastep_sweeps=4))
    pipe = g.add_source(src).add(win)
    if stages == 2:
        pipe = pipe.add(wf.Ffat_WindowsTPU_Builder(
            lambda r: r["value"], lambda a, b: a + b).withName("total")
            .withTBWindows(SLIDE, SLIDE).withSumCombiner().build())
    pipe.add_sink(snk)
    import warnings
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")      # WF608: forced K, stood down
        g.run()
    assert len(g.stats()["Megastep"]["edges"]) == edges
    total = sum(int(np.sum(c.cols["value"])) for c in got if c is not None)
    # no filter here: every event lies in two windows, but those of the
    # first pane, which lie in one
    assert total == 2 * len(rec) - int(np.count_nonzero(rec["t"] < SLIDE))
