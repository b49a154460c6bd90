"""``nexmark_q20``: NEXmark query 20, *expand bid with auction* — every
bid widened with the record of the auction it names, where the auction is
of category 10 — over the generator's 1 : 3 : 46 person / auction / bid
mix with its moving hot auction: graph builder, stream schema and plain
reference.

    SELECT ... FROM bid AS B INNER JOIN auction AS A ON B.auction = A.id
    WHERE A.category = 10;

An auction event is retained on its id; a bid is paired with the auction
of its id, whichever of the two arrives first (the generator bids on ids
it creates a moment later).  The graph drops the persons and runs one
keyed join on the device that emits a row a matched pair, both sides at
the stream's rate; a row is (auction, the bid's dateTime, [bidder, price,
channel, url, the auction's dateTime, seller, category, reserve,
expires]): every column of q20 that the 56 B frame carries."""

from __future__ import annotations

import dataclasses
import resource
import sys
import time
from typing import NamedTuple, Optional

import numpy as np

from benchmark import harness
from benchmark import reference as ref

q5 = harness.load_module("configs", "nexmark_q5")
q9 = harness.load_module("configs", "nexmark_q9")
q11 = harness.load_module("configs", "nexmark_q11")

PERSON, AUCTION, BID = q5.PERSON, q5.AUCTION, q5.BID
# frame value lanes: kind, then a bid's bidder, price, channel and url;
# an auction event carries its seller, reserve, category and length
# (expires - dateTime, as nexmark_q9 has it) on the same four
KIND, BIDDER, PRICE, CHANNEL, URL = "v0", "v1", "v2", "v3", "v4"
SELLER, RESERVE, CATEGORY, LENGTH = BIDDER, PRICE, CHANNEL, URL
N_FIELDS = 5
# the generator's constants (the configuration's "published")
FIRST_AUCTION_ID = q5.FIRST_AUCTION_ID
FIRST_PERSON_ID = q11.FIRST_PERSON_ID
FIRST_CATEGORY_ID, NUM_CATEGORIES = 10, 5
WANTED_CATEGORY = 10             # WHERE A.category = 10
ROUND_USEC = q11.ROUND_USEC      # the control's clock: whole milliseconds
# a row's value: bidder, price, channel, url | the auction's dateTime,
# seller, category, reserve, expires
N_VALUES = 9
A_DATETIME, EXPIRES = 4, 8       # the two that are times
CHECK_ROWS = 1 << 23             # rows compared at a time
TS_NONE = -(1 << 63)


def require_pair_join() -> None:
    """A program whose join folds its probes into one row a build row
    cannot run the deployment, and says so at once instead of building
    half a graph."""
    import windflow_tpu as wf
    if not hasattr(getattr(wf, "Interval_JoinTPU_Builder", None),
                   "withBoundaries"):
        raise RuntimeError(
            "this program has no join that emits a row a matched pair "
            "against a retained build side (windflow_tpu."
            "Interval_JoinTPU_Builder.withBoundaries): it does not "
            "support widening bids with their auction (nexmark_q20)")


def step_usec(event_rate: int) -> int:
    """Event time between two events: a row's ``wid`` (the bid's
    dateTime) names its event only where that is a whole number of
    microseconds."""
    if 1_000_000 % event_rate:
        raise ValueError("events are not a whole number of microseconds "
                         "apart: a bid's dateTime would not name it")
    return 1_000_000 // event_rate


def auction_of(rec: np.ndarray) -> np.ndarray:
    """For every event of one pass that is a bid, the position in the
    pass of the auction event of its id (-1: the pass creates no such
    auction; -1 also where the event is no bid)."""
    a = np.flatnonzero(rec[KIND] == AUCTION)
    ids = rec["k"][a].astype(np.int64)
    at = np.full(int(rec["k"].max(initial=0)) + 2, -1, np.int64)
    if len(np.unique(ids)) != len(ids):
        raise ValueError("a pass of the ring creates an auction id twice")
    at[ids] = a
    return np.where(rec[KIND] == BID, at[rec["k"].astype(np.int64)], -1)


class OnePass(NamedTuple):
    """The join over one pass of the ring, by position in the pass."""
    tss: np.ndarray       # int64 [R]: stamps, from the pass's start
    period: int           # event time a pass spans
    mine: np.ndarray      # int64 [R]: the position of a bid's auction
    pair: np.ndarray      # bool [R]: the bid is a row of the answer


def one_pass(rec: np.ndarray, cfg: dict, round_usec: int = 0) -> OnePass:
    g, s = cfg["graph"], cfg["stream"]
    tss = q11._stamps(len(rec), s["event_rate"], round_usec)
    mine = auction_of(rec)
    has = mine >= 0
    d = tss - tss[np.maximum(mine, 0)]
    pair = has & (d >= -g["lower_usec"]) & (d < g["upper_usec"]) \
        & (rec[CATEGORY][np.maximum(mine, 0)] == WANTED_CATEGORY)
    return OnePass(tss, q11.period_usec(len(rec), s["event_rate"]), mine,
                   pair)


def check_bounded_join(rec: np.ndarray, cfg: dict) -> dict:
    """q20's join never forgets an auction; here a record is retained
    ``upper_usec`` and a bid waits ``lower_usec``.  Raises unless, on
    this stream, the bounded join gives q20's own answer: every bid's
    auction of the same pass lies within ``[-lower, +upper)`` of it, and
    no bid can meet (or find retained) an auction of another pass.
    Returns what the stream needs of the deployment's sizes."""
    g, s = cfg["graph"], cfg["stream"]
    step_usec(s["event_rate"])
    one = one_pass(rec, cfg)
    has = one.mine >= 0
    d = (one.tss - one.tss[np.maximum(one.mine, 0)])[has]
    if len(d) and (d.min() < -g["lower_usec"] or d.max() >= g["upper_usec"]):
        raise ValueError(
            "a bid lies outside [-lower, +upper) of the auction of its "
            f"pass ({int(d.min())} .. {int(d.max())} usec): the bounded "
            "join would not give q20's answer")
    reach = int(max(-d.min(initial=0), 0))       # a bid before its auction
    span = g["batch"] * 1_000_000 // s["event_rate"]
    # a step looks a row up as retained until the watermark of the step
    # before passed t + upper: the row of the pass before must be gone by
    # then for every bid of this pass, the earliest lead bid included
    if g["upper_usec"] + g["lower_usec"] + span + reach >= one.period:
        raise ValueError(
            "a bid could meet the auction its id had a pass earlier: "
            f"upper {g['upper_usec']} + lower {g['lower_usec']} + a "
            f"batch's span {span} + the id lead's reach {reach} usec "
            f"reach the replay's period {one.period}")
    # rows one batch of consecutive events can complete, the replay's
    # wrap included
    csum = np.concatenate([[0], np.cumsum(np.r_[one.pair, one.pair])])
    n = min(g["batch"], len(rec))
    most = int((csum[n:n + len(rec)] - csum[:len(rec)]).max(initial=0))
    if most > 2 * g["out_capacity"]:
        raise ValueError(
            f"{most} pairs complete in one batch of {g['batch']} events: "
            f"more than an output of {g['out_capacity']} lanes and as "
            "many held back take")
    waiting = np.flatnonzero((one.mine < 0) & (rec[KIND] == BID))
    return {"max_pairs_a_batch": most, "lead_reach_usec": reach,
            "pairs_a_pass": int(one.pair.sum()),
            "bids_without_auction_a_pass": len(waiting)}


def make_ring(seed: int, cfg: dict) -> dict:
    require_pair_join()
    g, s = cfg["graph"], cfg["stream"]
    n = g["batch"] * s["ring_batches"]
    # the frame, the kinds and every id are Q5's, seed for seed
    rec = q5.make_ring(seed, {"graph": {"batch": g["batch"],
                                        "max_keys": g["max_keys"]},
                              "stream": s})["rec"]
    rng = np.random.default_rng([seed, 20])
    is_auction = rec[KIND] == AUCTION
    # all under 2**24, so the float32 lanes hold them exactly.  A bid's
    # price; an auction's reserve = its initial bid + a price (Beam)
    rec[PRICE] = np.where(is_auction, q9.prices(n, rng) + q9.prices(n, rng),
                          q9.prices(n, rng))
    # a bid's channel and url stay Q5's draws
    rec[CATEGORY] = np.where(
        is_auction, FIRST_CATEGORY_ID + rng.integers(0, NUM_CATEGORIES, n),
        rec[CHANNEL])
    rec[LENGTH] = np.where(
        is_auction, 1 + (rng.random(n) * max(
            2 * q9.horizon_usec(s["event_rate"]), 1)).astype(np.int64),
        rec[URL])
    # the bidder of a bid, the seller of an auction: the hot one three
    # times in four (hotBiddersRatio = hotSellersRatio = 4)
    rec[BIDDER] = FIRST_PERSON_ID + q11.bidders(
        n, rng, s["active_people"], s["hot_bidder_stride"])
    return {"rec": rec, **check_bounded_join(rec, cfg)}


def _rss_gb() -> float:
    with open("/proc/self/statm") as f:
        return int(f.read().split()[1]) * resource.getpagesize() / 1e9


class Delivered:
    """The sink's side of one run, kept for the check.  The harness keeps
    every delivered column and concatenates each before the check; at
    ~8 x 10^7 rows of nine int64 that copy alone is 5.8 GB, so a batch's
    ``value`` stays here (the check takes the batches one group at a
    time) and the harness is handed ``key`` and ``wid``.  The key is
    copied: the egress hands it on as a view of its whole packed buffer,
    which would stay with it.  Also kept: the watermark each batch was
    delivered under (a row stamped under the watermark of an EARLIER
    batch left too late) and the graph, whose counters the check reads
    once the stream has ended."""

    def __init__(self, sink_fn) -> None:
        self.sink_fn = sink_fn
        self.values = []
        self.watermark = TS_NONE
        self.rows_after_watermark = 0
        self.graph = None
        self.rss_first_gb = None

    def __call__(self, c) -> None:
        if c is None:
            return self.sink_fn(None)
        cols = c.cols
        self.values.append(cols["value"])
        # the harness stamps the delivery: first, before this sink's own
        self.sink_fn(dataclasses.replace(c, cols={
            "key": np.array(cols["key"]), "wid": cols["wid"],
            "value": cols["value"][:0]}))
        self.rows_after_watermark += int(
            np.count_nonzero(np.asarray(c.tss) < self.watermark))
        self.watermark = max(self.watermark, int(c.watermark))
        if self.rss_first_gb is None:
            self.rss_first_gb = _rss_gb()

    def drop(self) -> None:
        self.values.clear()
        self.graph = None

    def join_counters(self) -> dict:
        """The join operator's counters (``g.stats()``), read after the
        end of the stream."""
        [op] = [o for o in self.graph.stats()["Operators"]
                if o["Operator_name"] == "expand_bid"]
        return op


def build_graph(cfg: dict, ring: Optional[dict], chunks_fn, sink_fn):
    """The deployment's graph.  With a run's ``ring`` (``make_ring``'s
    dict) the sink's side of the run is kept in ``ring["run"]`` for the
    check (:class:`Delivered`); without one ``sink_fn`` is the sink."""
    import jax.numpy as jnp

    import windflow_tpu as wf
    from windflow_tpu.io import FrameSource
    g = cfg["graph"]
    require_pair_join()
    if ring is not None:
        if ring.get("run") is not None:      # the priming graph's
            ring["run"].drop()
        sink_fn = ring["run"] = Delivered(sink_fn)
    src = FrameSource(chunks_fn, nv=N_FIELDS, fmt="frames",
                      output_batch_size=g["batch"])
    src.record_spec = {"key": np.int32(0),
                       **{f"v{i}": np.float32(0.0) for i in range(N_FIELDS)}}
    both = wf.FilterTPU_Builder(lambda e: e[KIND] != float(PERSON)).build()
    expand = (wf.Interval_JoinTPU_Builder(
        lambda auction, bid, ts: {
            "bidder": bid[BIDDER], "price": bid[PRICE],
            "channel": bid[CHANNEL], "url": bid[URL],
            "seller": auction[SELLER], "category": auction[CATEGORY],
            "reserve": auction[RESERVE], "length": auction[LENGTH]})
        .withName("expand_bid")
        .withBuildSide(lambda e: e[KIND] == float(AUCTION))
        .withMatch(lambda auction, bid:
                   auction[CATEGORY] == float(WANTED_CATEGORY))
        .withBoundaries(g["lower_usec"], g["upper_usec"])
        .withKeyBy(lambda e: e["key"] - FIRST_AUCTION_ID)
        .withMaxKeys(g["max_keys"])
        .withProbeCapacity(g["probe_capacity"])
        .withOutputCapacity(g["out_capacity"]).build())
    i64 = lambda a: a.astype(jnp.int64)   # noqa: E731

    def layout(r):
        v = r["value"]
        return {"key": r["key"] + FIRST_AUCTION_ID, "wid": r["probe_ts"],
                "value": jnp.stack([
                    i64(v["bidder"]), i64(v["price"]), i64(v["channel"]),
                    i64(v["url"]), r["build_ts"], i64(v["seller"]),
                    i64(v["category"]), i64(v["reserve"]),
                    r["build_ts"] + i64(v["length"])])}

    row = wf.MapTPU_Builder(layout).withName("expanded_bid_row").build()
    snk = wf.Sink_Builder(sink_fn).withColumnarSink().build()
    graph = wf.PipeGraph("bench_" + cfg["name"], wf.ExecutionMode.DEFAULT,
                         wf.TimePolicy.EVENT, config=wf.Config())
    pipe = graph.add_source(src)
    pipe.add(both)
    pipe.add(expand).add(row).add_sink(snk)
    if ring is not None:
        ring["run"].graph = graph
    return graph


# ---------------------------------------------------------------------------
# the plain reference: numpy, int64, nothing of the program
# ---------------------------------------------------------------------------

class ExpandedBids:
    """The expected rows of a stream of ``n_total`` events, in closed
    form over the replay: the answer of one whole pass, once for every
    whole pass, and that of the last, partial pass (a bid and its auction
    both inside it).  A row is named by its bid: pass ``p``, position
    ``j``; its ``wid`` is ``p * period + tss[j]``.  ``run``: the sink's
    side of the run the rows are compared with, where there was one."""

    def __init__(self, rec: np.ndarray, cfg: dict, n_total: int,
                 round_usec: int = 0,
                 run: Optional[Delivered] = None) -> None:
        self.rec, self.one = rec, one_pass(rec, cfg, round_usec)
        self.run = run
        self.step = step_usec(cfg["stream"]["event_rate"])
        self.whole, self.rest = divmod(int(n_total), len(rec))
        j = np.arange(len(rec))
        self.last = self.one.pair & (j < self.rest) \
            & (self.one.mine < self.rest)
        self.per_pass = int(self.one.pair.sum())
        self.n_rows = self.whole * self.per_pass + int(self.last.sum())
        self._table = None
        # the join's counts, as the operator's counters say them
        bids = rec[KIND] == BID
        met = (self.one.mine >= 0) & bids
        n_b = int(np.count_nonzero(bids))
        n_met = int(np.count_nonzero(met))
        met_r = met & (j < self.rest) & (self.one.mine < self.rest)
        self.counts = {
            "matched": self.n_rows,
            "missed_predicate": self.whole * (n_met - self.per_pass)
            + int(np.count_nonzero(met_r)) - int(self.last.sum()),
            "missed_no_build": self.whole * (n_b - n_met)
            + int(np.count_nonzero(bids & (j < self.rest) & ~met_r)),
            "built": self.whole * int(np.count_nonzero(
                rec[KIND] == AUCTION)) + int(np.count_nonzero(
                    rec[KIND][:self.rest] == AUCTION)),
        }

    @property
    def key(self):
        """As long as the answer (the harness asks its length); the rows
        themselves are made by :meth:`rows`, a pass at a time."""
        return range(self.n_rows)

    @property
    def table(self) -> np.ndarray:
        """int64 ``[R, N_VALUES]``: the value of the row a bid at that
        place of a pass would be, its two times from the pass's start
        (one gather a delivered row in the check, not nine)."""
        if self._table is None:
            rec, one = self.rec, self.one
            mine = np.maximum(one.mine, 0)
            i64 = lambda a: np.asarray(a).astype(np.int64)   # noqa: E731
            t = one.tss[mine]
            self._table = np.stack([
                i64(rec[BIDDER]), i64(rec[PRICE]), i64(rec[CHANNEL]),
                i64(rec[URL]), t, i64(rec[SELLER][mine]),
                i64(rec[CATEGORY][mine]), i64(rec[RESERVE][mine]),
                t + i64(rec[LENGTH][mine])], axis=1)
        return self._table

    def values_at(self, p, j) -> np.ndarray:
        """The values of the rows of the bids at places ``j`` of passes
        ``p``."""
        v = self.table[j]
        shift = np.asarray(p) * self.one.period
        v[:, A_DATETIME] += shift
        v[:, EXPIRES] += shift
        return v

    def rows_of_pass(self, p: int):
        """``(key, wid, value)`` of pass ``p``, by position."""
        at = np.flatnonzero(self.last if p == self.whole
                            else self.one.pair)
        return (self.rec["k"][at].astype(np.int64),
                self.one.tss[at] + p * self.one.period,
                self.values_at(p, at))

    def passes(self):
        return range(self.whole + (1 if self.rest else 0))

    def rows(self):
        """Every expected row (small streams)."""
        parts = [self.rows_of_pass(p) for p in self.passes()]
        if not parts:
            return (np.empty(0, np.int64), np.empty(0, np.int64),
                    np.empty((0, N_VALUES), np.int64))
        return tuple(np.concatenate(x) for x in zip(*parts))


def expected(cfg: dict, ring: dict, n_total: int, mix: dict) -> ExpandedBids:
    q9._rate(cfg, mix)
    return ExpandedBids(ring["rec"], cfg, n_total, run=ring.get("run"))


def control(cfg: dict, ring: dict, n_total: int, mix: dict):
    """The configuration states no float precision (ids, prices and times
    are exact); the control lowers the precision of the one lane every
    row depends on: event time rounded to the nearest millisecond (of the
    time since its pass of the ring began).  No bid is stamped on a whole
    millisecond (every 50th event is a person), so every row's ``wid``
    moves.  The values come a pass at a time (a list, which
    :func:`compare` takes as it takes the sink's batches)."""
    q9._rate(cfg, mix)
    ctl = ExpandedBids(ring["rec"], cfg, n_total, round_usec=ROUND_USEC)
    parts = [ctl.rows_of_pass(p) for p in ctl.passes()]
    if not parts:
        return ctl.rows()
    k, w, v = zip(*parts)
    return np.concatenate(k), np.concatenate(w), list(v)


def _groups(values: list):
    """The value batches, taken off ``values`` in groups of about
    ``CHECK_ROWS`` rows (what was compared is let go)."""
    values.reverse()
    while values:
        group, n = [], 0
        while values and n < CHECK_ROWS:
            group.append(np.asarray(values.pop()).reshape(-1, N_VALUES))
            n += len(group[-1])
        yield group[0] if len(group) == 1 else np.concatenate(group)


def _counter_checks(run: Delivered, exp: ExpandedBids) -> list:
    """What the stated guarantees say beyond the rows' content: no row
    left under a watermark that had passed it, and the join's own counts
    (a bid that waited and was paired, one whose auction never came, a
    record evicted) are the reference's."""
    op, c = run.join_counters(), exp.counts
    want = {"Join_probe_matched": c["matched"],
            "Join_probe_missed_predicate": c["missed_predicate"],
            "Join_probe_missed_no_build": c["missed_no_build"],
            "Join_probe_missed_interval": 0, "Join_probe_pending": 0,
            "Join_build_built": c["built"], "Join_build_replaced": 0,
            "Join_build_evicted": c["built"], "Join_build_retained": 0,
            "Late_tuples_dropped": 0}
    off = {k: (op.get(k), v) for k, v in want.items() if op.get(k) != v}
    print("benchmark: nexmark_q20 counters: " + ", ".join(
        f"{k} {op.get(k)}" for k in want) + f"; waited "
        f"{op.get('Join_probe_waited')}, pending at most "
        f"{op.get('Join_probe_pending_max')}, held back "
        f"{op.get('Join_rows_held_back')}"
        + (f"; NOT the reference's: {off}" if off else ""),
        file=sys.stderr, flush=True)
    run.graph = None
    return [ref.check("rows_after_watermark", run.rows_after_watermark, 0),
            ref.check("counter_mismatches", len(off), 0)]


def compare(cfg: dict, got: dict, exp: ExpandedBids) -> list:
    """By position, not by sorting ~8 x 10^7 rows: a delivered row's
    ``wid`` is its bid's dateTime, which names its pass and its place in
    the pass; the place must hold an expected row of that key, each
    (pass, place) is counted once, and the row's nine numbers are
    compared there (``count_mismatches``: rows in which any differs).
    ``got["value"]``: an array, or a list of arrays in the rows' order;
    after a run of the graph the sink's own batches (``exp.run``)."""
    t0 = time.monotonic()
    gk, gw = np.asarray(got["key"]), np.asarray(got["wid"])
    run, values = exp.run, got["value"]
    if run is not None:
        values = run.values
    elif not isinstance(values, list):
        values = [values]
    n_values = sum(np.asarray(v).size for v in values) // N_VALUES
    rss_close = _rss_gb()
    one, R = exp.one, len(exp.rec)
    n_pass = exp.whole + 1
    seen = np.zeros(n_pass * R, bool)
    wrong, lo = 0, 0
    for v in _groups(values):
        s = slice(lo, lo + len(v))
        lo += len(v)
        k, w = gk[s].astype(np.int64), gw[s].astype(np.int64)
        # keys without values or values without keys: counted below
        k, w, v = k[:len(v)], w[:len(v)], v[:len(k)]
        p, off = np.divmod(w, one.period)
        j, rem = np.divmod(off, exp.step)
        ok = (p >= 0) & (p < n_pass) & (rem == 0)
        p, j = np.where(ok, p, 0), np.where(ok, j, 0)
        ok &= np.where(p == exp.whole, exp.last[j], one.pair[j]) \
            & (exp.rec["k"][j] == k)
        if not ok.all():
            p, j, v = p[ok], j[ok], v[ok]
        seen[p * R + j] = True
        wrong += int(np.count_nonzero(
            (v != exp.values_at(p, j)).any(axis=1)))
    distinct = int(np.count_nonzero(seen))
    del seen
    # rows with no place, or a place taken, and expected rows no
    # delivered row took
    bad = (len(gk) - distinct) + (exp.n_rows - distinct)
    out = [ref.check("rows_missing_or_extra",
                     max(abs(len(gk) - exp.n_rows), abs(n_values - len(gk))),
                     0),
           ref.check("key_wid_mismatches", bad, 0),
           ref.check("result_rows_absent", 0 if len(gk) else 1, 0),
           ref.check("count_mismatches",
                     np.inf if bad or not len(gk) else wrong,
                     cfg["check"]["count_mismatches"])]
    if run is not None:
        out += _counter_checks(run, exp)
    peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6
    first = f", {run.rss_first_gb:.2f} GB at the first delivery" \
        if run is not None and run.rss_first_gb else ""
    print(f"benchmark: nexmark_q20 check: {len(gk)} rows against "
          f"{exp.n_rows} by position in {time.monotonic() - t0:.2f}s; host "
          f"memory {rss_close:.2f} GB as the check began{first}, peak "
          f"{peak:.2f} GB", file=sys.stderr, flush=True)
    return out
