"""``nexmark_q16``: NEXmark q16, *channel statistics report* — for every
channel, today's bids by price rank and the EXACT number of distinct
bidders and distinct auctions by price rank, upserted once a mini-batch —
over the generator's 1 : 3 : 46 person / auction / bid mix with its moving
hot auction, its hot bidder and its four hot channels: graph builder,
stream schema and plain reference.

    SELECT channel, DATE_FORMAT(dateTime, 'yyyy-MM-dd') AS `day`,
           max(DATE_FORMAT(dateTime, 'HH:mm')) AS `minute`,
           count(*) AS total_bids,
           count(*) FILTER (WHERE price < 10000) AS rank1_bids,
           count(*) FILTER (WHERE price >= 10000 AND price < 1000000)
               AS rank2_bids,
           count(*) FILTER (WHERE price >= 1000000) AS rank3_bids,
           count(DISTINCT bidder) AS total_bidders,
           count(DISTINCT bidder) FILTER (WHERE price < 10000)
               AS rank1_bidders,     -- rank2_, rank3_ likewise
           count(DISTINCT auction) AS total_auctions,
           count(DISTINCT auction) FILTER (WHERE price < 10000)
               AS rank1_auctions     -- rank2_, rank3_ likewise
    FROM bid GROUP BY channel, DATE_FORMAT(dateTime, 'yyyy-MM-dd');

An aggregate that never closes: the graph drops the persons and the
auctions and hands the bids to a rolling aggregate on the device, keyed
by channel, whose eight distinct counts are bit sets on the chip.  A row
is ``(channel, total_bids, [minute, rank1..3_bids, total and rank1..3
bidders, total and rank1..3 auctions])``: a group's ``total_bids`` grows
with every row of it, so it names the row."""

from __future__ import annotations

import sys

import numpy as np

from benchmark import harness
from benchmark import reference as ref

q5 = harness.load_module("configs", "nexmark_q5")
q9 = harness.load_module("configs", "nexmark_q9")
q11 = harness.load_module("configs", "nexmark_q11")

PERSON, AUCTION, BID = q5.PERSON, q5.AUCTION, q5.BID
# frame value lanes (Q5's): kind, bidder, price, channel, one spare
KIND, BIDDER, PRICE, CHANNEL = "v0", "v1", "v2", "v3"
N_FIELDS = q5.N_FIELDS
FIRST_AUCTION_ID = q5.FIRST_AUCTION_ID
FIRST_PERSON_ID = q11.FIRST_PERSON_ID
HOT_CHANNELS = 4                 # Google, Facebook, Baidu, Apple
HOT_CHANNELS_RATIO = 2           # a bid is hot with probability 1 / ratio
CHANNELS_NUMBER = 10_000         # channel-0 .. channel-9999
RANK1_BELOW, RANK3_FROM = 10_000, 1_000_000
MINUTE_USEC, DAY_MINUTES = 60_000_000, 1440
#: the numbers a row carries beside its channel and its total_bids
VALUES = ("minute", "rank1_bids", "rank2_bids", "rank3_bids",
          "total_bidders", "rank1_bidders", "rank2_bidders",
          "rank3_bidders", "total_auctions", "rank1_auctions",
          "rank2_auctions", "rank3_auctions")
N_VALUES = len(VALUES)
OPERATOR = "channel_statistics"
#: the aggregate's counters of the last run that was checked
#: (``distinct_new_share.sat`` reads them)
LAST_COUNTERS = None


def require_rolling_aggregate() -> None:
    """A program without a rolling aggregate whose leaves can be exact
    distinct counts cannot run the deployment, and says so at once
    instead of building half a graph."""
    import windflow_tpu as wf
    b = getattr(wf, "Rolling_AggregateTPU_Builder", None)
    if b is None or not hasattr(b, "withDistinct"):
        raise RuntimeError(
            "this program has no rolling keyed aggregate with distinct "
            "leaves on the device (no windflow_tpu."
            "Rolling_AggregateTPU_Builder.withDistinct): it does not "
            "support an unbounded GROUP BY with COUNT(DISTINCT) "
            "(nexmark_q16)")


def channels(n: int, rng, cold: int = CHANNELS_NUMBER) -> np.ndarray:
    """The channel of each event of the segment were it a bid (nexmark-
    flink's generator): one of the hot four with probability 1/2, else
    ``channel-<uniform[0, cold)>``, as an integer: 0..3 the hot ones,
    4 + i the cold."""
    hot = rng.integers(0, HOT_CHANNELS_RATIO, n) == 0
    return np.where(hot, rng.integers(0, HOT_CHANNELS, n),
                    HOT_CHANNELS + rng.integers(0, cold, n))


def make_ring(seed: int, cfg: dict) -> dict:
    require_rolling_aggregate()
    g, s = cfg["graph"], cfg["stream"]
    n = g["batch"] * s["ring_batches"]
    # the frame, the kinds and the auction of a bid are Q5's, seed for seed
    rec = q5.make_ring(seed, {"graph": {"batch": g["batch"],
                                        "max_keys": 1 << 62},
                              "stream": s})["rec"]
    rng = np.random.default_rng([seed, 16])
    # all under 2**24, so the float32 lanes hold them exactly
    rec[CHANNEL] = channels(n, rng, s["cold_channels"])
    rec[BIDDER] = FIRST_PERSON_ID + q11.bidders(
        n, rng, s["active_people"], s["hot_bidder_stride"])
    rec[PRICE] = q9.prices(n, rng)
    bid = rec[KIND] == BID
    for what, ids, space in (
            ("channel", rec[CHANNEL][bid], g["max_keys"]),
            ("bidder", rec[BIDDER][bid] - FIRST_PERSON_ID,
             g["bidder_space"]),
            ("auction", rec["k"][bid] - FIRST_AUCTION_ID,
             g["auction_space"])):
        if len(ids) and not (0 <= ids.min() and ids.max() < space):
            raise ValueError(
                f"a bid's {what} outside [0, {space}): "
                f"{int(ids.min())} .. {int(ids.max())}")
    return {"rec": rec}


class Run:
    """The graph of one run, kept for the check: the aggregate's own
    counters are read once the stream has ended."""

    def __init__(self) -> None:
        self.graph = None

    def counters(self) -> dict:
        [op] = [o for o in self.graph.stats()["Operators"]
                if o["Operator_name"] == OPERATOR]
        self.graph = None
        return op


def build_graph(cfg: dict, ring, chunks_fn, sink_fn):
    """The deployment's graph.  With a run's ``ring`` (``make_ring``'s
    dict) the graph is kept in ``ring["run"]`` for the check."""
    import jax.numpy as jnp

    import windflow_tpu as wf
    from windflow_tpu.io import FrameSource
    g = cfg["graph"]
    require_rolling_aggregate()
    src = FrameSource(chunks_fn, nv=N_FIELDS, fmt="frames",
                      output_batch_size=g["batch"])
    src.record_spec = {"key": np.int32(0),
                       **{f"v{i}": np.float32(0.0) for i in range(N_FIELDS)}}
    bids = wf.FilterTPU_Builder(lambda e: e[KIND] == float(BID)).build()
    i32 = lambda a: a.astype(jnp.int32)   # noqa: E731

    def lift(e, ts):
        rank1 = e[PRICE] < float(RANK1_BELOW)
        rank3 = e[PRICE] >= float(RANK3_FROM)
        rank2 = ~rank1 & ~rank3
        bidder = i32(e[BIDDER]) - FIRST_PERSON_ID
        auction = e["key"] - FIRST_AUCTION_ID
        only = lambda rank, who: jnp.where(rank, who, -1)   # noqa: E731
        return {
            "total_bids": jnp.int32(1), "rank1_bids": i32(rank1),
            "rank2_bids": i32(rank2), "rank3_bids": i32(rank3),
            "minute": i32(ts // MINUTE_USEC % DAY_MINUTES),
            "total_bidders": bidder, "rank1_bidders": only(rank1, bidder),
            "rank2_bidders": only(rank2, bidder),
            "rank3_bidders": only(rank3, bidder),
            "total_auctions": auction,
            "rank1_auctions": only(rank1, auction),
            "rank2_auctions": only(rank2, auction),
            "rank3_auctions": only(rank3, auction)}

    stats = (wf.Rolling_AggregateTPU_Builder(lift).withName(OPERATOR)
             .withSum("total_bids", "rank1_bids", "rank2_bids", "rank3_bids")
             .withMax("minute")
             .withDistinct("total_bidders", "rank1_bidders", "rank2_bidders",
                           "rank3_bidders", space=g["bidder_space"])
             .withDistinct("total_auctions", "rank1_auctions",
                           "rank2_auctions", "rank3_auctions",
                           space=g["auction_space"])
             .withKeyBy(lambda e: i32(e[CHANNEL]))
             .withMaxKeys(g["max_keys"])
             .withOutputCapacity(g["out_capacity"]).build())
    row = wf.MapTPU_Builder(lambda r: {
        "key": r["key"], "wid": r["total_bids"],
        "value": jnp.stack([r[v].astype(jnp.int64) for v in VALUES])}) \
        .withName("channel_statistics_row").build()
    snk = wf.Sink_Builder(sink_fn).withColumnarSink().build()
    graph = wf.PipeGraph("bench_" + cfg["name"], wf.ExecutionMode.DEFAULT,
                         wf.TimePolicy.EVENT, config=wf.Config())
    pipe = graph.add_source(src)
    pipe.add(bids)
    pipe.add(stats).add(row).add_sink(snk)
    if ring is not None:
        ring["run"] = Run()
        ring["run"].graph = graph
    return graph


# ---------------------------------------------------------------------------
# the plain reference: numpy, int64, nothing of the program
# ---------------------------------------------------------------------------

def ranks_of(price: np.ndarray) -> np.ndarray:
    """0 / 1 / 2: the price band of each bid (rank1 / rank2 / rank3)."""
    return (price >= RANK1_BELOW).astype(np.int64) \
        + (price >= RANK3_FROM).astype(np.int64)


def _running_distinct(group: np.ndarray, member: np.ndarray,
                      keep: np.ndarray) -> np.ndarray:
    """Over bids in arrival order, grouped by ``group`` (sorted, stable):
    how many different ``member`` ids its group has seen up to and with
    each bid, among the bids ``keep`` marks."""
    pair = group * (int(member.max(initial=0)) + 1) + member
    first = np.zeros(len(pair), bool)
    at = np.flatnonzero(keep)
    # np.unique hands back the FIRST position of each pair: the bids are
    # in arrival order within their group
    first[at[np.unique(pair[at], return_index=True)[1]]] = True
    return _running_sum(group, first)


def _running_sum(group: np.ndarray, flag: np.ndarray) -> np.ndarray:
    """Running count of ``flag`` within each run of ``group``."""
    run = np.cumsum(flag, dtype=np.int64)
    start = np.flatnonzero(np.r_[True, group[1:] != group[:-1]]) \
        if len(group) else np.zeros(0, np.int64)
    before = np.r_[0, run][start]           # the count before the run
    return run - np.repeat(before, np.diff(np.r_[start, len(group)]))


class ChannelStatistics:
    """The answer of one pass of the ring, for every group and every
    ``n``: the twelve numbers after the group's ``n``-th bid of the pass,
    the bids in arrival order within their group (``start[c] ..
    start[c + 1]``), and what a later pass adds: its bids by rank and no
    member (the replay repeats every (channel, member) pair).  ``key``
    is what the harness counts as the run's rows: ``compare`` sets it to
    the rows delivered."""

    def __init__(self, rec: np.ndarray, n_total: int, max_keys: int,
                 event_rate: int, run=None) -> None:
        self.period = len(rec)
        self.n_total = int(n_total)
        self.usec_per_event = 1_000_000 // int(event_rate)
        at = np.flatnonzero(rec[KIND] == BID)
        chan = rec[CHANNEL][at].astype(np.int64)
        by = np.argsort(chan, kind="stable")
        at, chan = at[by], chan[by]
        self.pos = at                         # place in the pass
        self._order = chan * self.period + at   # rising: by group, place
        self.start = np.searchsorted(chan, np.arange(max_keys + 1))
        rank = ranks_of(rec[PRICE][at].astype(np.int64))
        bidder = rec[BIDDER][at].astype(np.int64) - FIRST_PERSON_ID
        auction = rec["k"][at].astype(np.int64) - FIRST_AUCTION_ID
        everyone = np.ones(len(at), bool)
        cols = [_running_sum(chan, rank == r) for r in range(3)]
        for who in (bidder, auction):
            cols.append(_running_distinct(chan, who, everyone))
            cols += [_running_distinct(chan, who, rank == r)
                     for r in range(3)]
        self.cols = np.stack(cols, axis=1)                  # [bids, 11]
        self.per_pass = np.diff(self.start)                 # bids a group
        # a group's bids of the pulled stream: whole passes and the rest
        whole, rest = divmod(self.n_total, self.period)
        self.total = whole * self.per_pass + self.bids_before(rest)
        self.key = np.flatnonzero(self.total > 0)
        self.run = run

    def bids_before(self, rest: int) -> np.ndarray:
        """A group's bids among the first ``rest`` events of a pass (its
        bids lie in ``pos`` in arrival order: a binary search a group)."""
        groups = np.arange(len(self.per_pass), dtype=np.int64)
        return np.searchsorted(self._order, groups * self.period + rest) \
            - self.start[:-1]

    def at(self, key: np.ndarray, n: np.ndarray) -> np.ndarray:
        """The twelve numbers of the rows ``(key, n)``: each group's
        state after its ``n``-th bid of the stream (``1 <= n <=
        total[key]``)."""
        per = self.per_pass[key]
        passes, j = np.divmod(n - 1, per)
        i = self.start[key] + j
        last = self.start[key + 1] - 1      # the group's last bid a pass
        ts = (passes * self.period + self.pos[i]) * self.usec_per_event
        out = np.empty((len(key), N_VALUES), np.int64)
        out[:, 0] = ts // MINUTE_USEC % DAY_MINUTES
        # bids by rank: the whole passes before, and this one's so far
        out[:, 1:4] = passes[:, None] * self.cols[last, 0:3] \
            + self.cols[i, 0:3]
        # members: the first pass sees them all
        out[:, 4:] = np.where((passes > 0)[:, None], self.cols[last, 3:],
                              self.cols[i, 3:])
        return out


def expected(cfg: dict, ring: dict, n_total: int,
             mix: dict) -> ChannelStatistics:
    if mix.get("event_rate") != cfg["stream"]["event_rate"]:
        raise ValueError("the mix's event_rate is not the configuration's")
    if MINUTE_USEC * DAY_MINUTES * cfg["stream"]["event_rate"] \
            <= n_total * 1_000_000:
        raise ValueError(
            f"{n_total} events pass the stream's first day: the "
            "configuration keys by channel alone (assumed: day)")
    return ChannelStatistics(ring["rec"], n_total, cfg["graph"]["max_keys"],
                             cfg["stream"]["event_rate"],
                             run=ring.get("run"))


def control(cfg: dict, ring: dict, n_total: int, mix: dict):
    """The configuration states no float precision (ids and counts are
    exact); the control breaks the guarantee the deployment adds: a
    member seen again is counted again from the second pass of the replay
    on (a set that forgets, the answer of a program that kept counts and
    no sets).  Every row is there under its own channel and total_bids;
    the eight distinct counts of every row past a group's first pass
    differ.  Rows: each group a batch touched, after each batch of the
    stream."""
    exp = expected(cfg, ring, n_total, mix)
    batch = cfg["graph"]["batch"]
    ends = np.unique(np.r_[np.arange(batch, n_total + 1, batch), n_total])
    keys, ns, before = [], [], np.zeros(len(exp.per_pass), np.int64)
    for end in ends:
        whole, rest = divmod(int(end), exp.period)
        n = whole * exp.per_pass + exp.bids_before(rest)
        touched = np.flatnonzero(n > before)    # a row a channel touched
        keys.append(touched)
        ns.append(n[touched])
        before = n
    key, n = np.concatenate(keys), np.concatenate(ns)
    value = exp.at(key, n)
    passes = (n - 1) // exp.per_pass[key]
    last = exp.start[key + 1] - 1
    value[:, 4:] += passes[:, None] * exp.cols[last, 3:]
    return key, n, value


def _counter_checks(run: Run, rows: int) -> list:
    """What the stated guarantees say beyond the rows' content: every
    row the aggregate made was delivered, no group's row was lost to the
    output's capacity, no key and no member was refused."""
    global LAST_COUNTERS
    op = LAST_COUNTERS = run.counters()
    want = {"Agg_rows_out": rows, "Agg_output_overflow": 0,
            "Agg_keys_refused": 0, "Agg_members_refused": 0}
    off = {k: (op.get(k), v) for k, v in want.items() if op.get(k) != v}
    print("benchmark: nexmark_q16 counters: " + ", ".join(
        f"{k} {op.get(k)}" for k in (*want, "Agg_members_tested",
                                     "Agg_members_new"))
        + (f"; NOT the reference's: {off}" if off else ""),
        file=sys.stderr, flush=True)
    return [ref.check("counter_mismatches", len(off), 0)]


def compare(cfg: dict, got: dict, exp: ChannelStatistics) -> list:
    """Every delivered row at its own ``total_bids``: a row ``(channel,
    n)`` must hold the reference's numbers after the channel's ``n``-th
    bid, a channel's rows must come with ``n`` strictly increasing, and
    its last row at the channel's whole count of the pulled stream.
    ``key_wid_mismatches``: rows whose ``n`` no bid of the channel has
    or that do not increase; ``rows_missing_or_extra``: channels whose
    last row is not at their whole count (or that have bids and no row);
    ``count_mismatches``: rows in which any of the twelve numbers
    differs.  After a run of the graph the aggregate's counters too."""
    gk = np.asarray(got["key"]).astype(np.int64)
    gn = np.asarray(got["wid"]).astype(np.int64)
    gv = np.asarray(got["value"]).astype(np.int64).reshape(-1, N_VALUES)
    n_keys = len(exp.total)
    known = (gk >= 0) & (gk < n_keys)
    ok = known.copy()
    ok[known] = (gn[known] >= 1) & (gn[known] <= exp.total[gk[known]])
    # a channel's rows in delivery order: n strictly increasing
    by = np.argsort(gk, kind="stable")
    same = gk[by][1:] == gk[by][:-1]
    rising = np.ones(len(gk), bool)
    rising[by[1:]] = ~same | (gn[by][1:] > gn[by][:-1])
    bad_rows = int(np.count_nonzero(~(ok & rising)))
    last = np.zeros(n_keys, np.int64)
    np.maximum.at(last, gk[known], gn[known])
    unfinished = int(np.count_nonzero(last != exp.total))
    worst = np.inf
    if len(gk) and not bad_rows:
        worst = 0
        for lo in range(0, len(gk), 1 << 20):       # bounded memory
            sl = slice(lo, lo + (1 << 20))
            worst += int(np.count_nonzero(np.any(
                exp.at(gk[sl], gn[sl]) != gv[sl], axis=1)))
    out = [ref.check("rows_missing_or_extra", unfinished, 0),
           ref.check("key_wid_mismatches", bad_rows, 0),
           ref.check("result_rows_absent", 0 if len(gk) else 1, 0),
           ref.check("count_mismatches", worst,
                     cfg["check"]["count_mismatches"])]
    run, exp.run = exp.run, None
    exp.key = gk                    # the run's rows, for the harness
    if run is not None and run.graph is not None:
        out += _counter_checks(run, len(gk))
    return out
