"""``nexmark_q6``: NEXmark query 6, *average selling price by seller* —
for every seller, the mean of the final prices of its last ten closed
auctions, one row a closed auction — over the generator's 1 : 3 : 46
person / auction / bid mix with its moving hot auction and its hot
seller: graph builder, stream schema and plain reference.

    SELECT Istream(AVG(Q.final), Q.seller)
    FROM (SELECT Rstream(MAX(B.price) AS final, A.seller)
          FROM Auction A [ROWS UNBOUNDED], Bid B [ROWS UNBOUNDED]
          WHERE A.id = B.auction AND B.datetime < A.expires
                AND A.expires < CURRENT_TIME
          GROUP BY A.id, A.seller) [PARTITION BY A.seller ROWS 10] Q
    GROUP BY Q.seller;

The inner query is ``nexmark_q9``'s winning bids (an auction event opens
``[dateTime, expires)`` on its id, a bid matches the open auction of its
id where it meets the reserve, the winner is the highest price, then the
earlier bid) with the auction's seller carried along.  The outer one is a
count window of ten rows sliding by one over each seller's winning bids
in the order the auctions CLOSE, ``(expires, dateTime)``, cut at the
seller's start: for the seller's ``i``-th closed auction a row ``(seller,
the auction's dateTime, [sum of the last <= 10 final prices, how many,
this auction's final price, its expires, its matched bids])``.  The mean
is ``sum / n``, left to the reader so that every number is exact.  The
graph drops the persons, runs the interval join on the device and hands
its rows to the count window on the device."""

from __future__ import annotations

import sys
from typing import NamedTuple

import numpy as np

from benchmark import harness
from benchmark import reference as ref

q5 = harness.load_module("configs", "nexmark_q5")
q9 = harness.load_module("configs", "nexmark_q9")
q11 = harness.load_module("configs", "nexmark_q11")

PERSON, AUCTION, BID = q5.PERSON, q5.AUCTION, q5.BID
# frame value lanes (nexmark_q20's): kind, a bid's bidder / an auction's
# seller, a bid's price / an auction's reserve, channel, an auction's
# length in usec
KIND, BIDDER, PRICE, LENGTH = q9.KIND, q9.BIDDER, q9.PRICE, q9.LENGTH
SELLER, RESERVE = BIDDER, PRICE
N_FIELDS = q9.N_FIELDS
FIRST_AUCTION_ID = q9.FIRST_AUCTION_ID
FIRST_PERSON_ID = q9.FIRST_PERSON_ID
ROUND_USEC = q9.ROUND_USEC       # the control's clock: whole milliseconds
N_VALUES = 5                     # numbers a row carries
WINDOW_OPERATOR = "selling_price"
#: the count window's counters of the last run that was checked
#: (``partial_window_share.sat`` reads them)
LAST_COUNTERS = None


def require_ordered_count_window() -> None:
    """A program whose count window counts rows as they arrive cannot
    run the deployment, and says so at once instead of building half a
    graph."""
    q9.require_interval_join()
    import windflow_tpu as wf
    b = wf.Ffat_WindowsTPU_Builder
    if not (hasattr(b, "withEventTimeOrder")
            and hasattr(b, "withLeadingPartialWindows")):
        raise RuntimeError(
            "this program's count window counts a key's rows in the "
            "order they arrive and fires no window before it is full "
            "(no windflow_tpu.Ffat_WindowsTPU_Builder.withEventTimeOrder"
            " / withLeadingPartialWindows): it does not support a "
            "seller's moving average over its closed auctions "
            "(nexmark_q6)")


def make_ring(seed: int, cfg: dict) -> dict:
    require_ordered_count_window()
    g, s = cfg["graph"], cfg["stream"]
    n = g["batch"] * s["ring_batches"]
    # the frame, the kinds and every id are Q5's, seed for seed
    rec = q5.make_ring(seed, {"graph": {"batch": g["batch"],
                                        "max_keys": 1 << 62},
                              "stream": s})["rec"]
    rng = np.random.default_rng([seed, 6])
    is_auction = rec[KIND] == AUCTION
    # all under 2**24, so the float32 lanes hold them exactly.  A bid's
    # price; an auction's reserve = its initial bid + a price (Beam)
    rec[PRICE] = np.where(is_auction, q9.prices(n, rng) + q9.prices(n, rng),
                          q9.prices(n, rng))
    rec[LENGTH] = np.where(
        is_auction, 1 + (rng.random(n) * max(
            2 * q9.horizon_usec(s["event_rate"]), 1)).astype(np.int64), 0)
    # the bidder of a bid, the seller of an auction: the hot one three
    # times in four (hotBiddersRatio = hotSellersRatio = 4)
    rec[BIDDER] = FIRST_PERSON_ID + q11.bidders(
        n, rng, s["active_people"], s["hot_bidder_stride"])
    sellers = rec[SELLER][is_auction].astype(np.int64) - FIRST_PERSON_ID
    if len(sellers) and not (0 <= sellers.min()
                             and sellers.max() < g["max_keys"]):
        raise ValueError(
            f"a seller outside [0, {g['max_keys']}) of the person ids: "
            f"{int(sellers.min())} .. {int(sellers.max())}")
    q9.one_pass(rec, s["event_rate"])
    return {"rec": rec}


class Run:
    """The graph of one run, kept for the check: the count window's own
    counters (``CB_rows_out_of_order`` must read 0) are read once the
    stream has ended."""

    def __init__(self) -> None:
        self.graph = None

    def window_counters(self) -> dict:
        [op] = [o for o in self.graph.stats()["Operators"]
                if o["Operator_name"] == WINDOW_OPERATOR]
        self.graph = None
        return op


def build_graph(cfg: dict, ring, chunks_fn, sink_fn):
    """The deployment's graph.  With a run's ``ring`` (``make_ring``'s
    dict) the graph is kept in ``ring["run"]`` for the check."""
    import jax.numpy as jnp

    import windflow_tpu as wf
    from windflow_tpu.io import FrameSource
    g = cfg["graph"]
    require_ordered_count_window()
    src = FrameSource(chunks_fn, nv=N_FIELDS, fmt="frames",
                      output_batch_size=g["batch"])
    src.record_spec = {"key": np.int32(0),
                       **{f"v{i}": np.float32(0.0) for i in range(N_FIELDS)}}
    both = wf.FilterTPU_Builder(lambda e: e[KIND] != float(PERSON)).build()

    def higher(a, b):
        # arg-max that carries the bid; ties go to the earlier one
        b_wins = (b["price"] > a["price"]) | (
            (b["price"] == a["price"]) & (b["at"] < a["at"]))
        return {k: jnp.where(b_wins, b[k], a[k]) for k in a}

    winners = (wf.Interval_JoinTPU_Builder(
        lambda auction, bid, ts: {"price": bid[PRICE], "at": ts,
                                  "bidder": bid[BIDDER],
                                  "seller": auction[SELLER]}, higher)
        .withName("winning_bids")
        .withBuildSide(lambda e: e[KIND] == float(AUCTION))
        .withIntervalLength(lambda e: e[LENGTH].astype(jnp.int32))
        .withMatch(lambda auction, bid: bid[PRICE] >= auction[RESERVE])
        .withKeyBy(lambda e: e["key"] - FIRST_AUCTION_ID)
        .withBuildCapacity(g["build_capacity"])
        .withOutputCapacity(g["out_capacity"]).build())
    i64 = lambda a: a.astype(jnp.int64)   # noqa: E731
    # a seller's last `window` closed auctions in the order they close:
    # the join stamps a row expires - 1; the auction's dateTime (one
    # event a microsecond) breaks a tie, as dateTime - expires: the same
    # order among rows of one expires, in 32 bits (one sort operand)
    mean = (wf.Ffat_WindowsTPU_Builder(
        lambda r: {"sum": i64(r["value"]["price"]), "n": jnp.int64(1)},
        lambda a, b: {"sum": a["sum"] + b["sum"], "n": a["n"] + b["n"]})
        .withName(WINDOW_OPERATOR)
        .withCBWindows(g["window_rows"], g["slide_rows"])
        .withKeyBy(lambda r: r["value"]["seller"].astype(jnp.int32)
                   - FIRST_PERSON_ID)
        .withMaxKeys(g["max_keys"])
        .withEventTimeOrder(
            lambda r: (r["start"] - r["end"]).astype(jnp.int32))
        .withLeadingPartialWindows().build())
    row = wf.MapTPU_Builder(lambda w: {
        "key": w["key"] + FIRST_PERSON_ID, "wid": w["last"]["start"],
        "value": jnp.stack([w["value"]["sum"], w["value"]["n"],
                            i64(w["last"]["value"]["price"]),
                            w["last"]["end"],
                            i64(w["last"]["count"])])}).withName(
        "selling_price_row").build()
    snk = wf.Sink_Builder(sink_fn).withColumnarSink().build()
    graph = wf.PipeGraph("bench_" + cfg["name"], wf.ExecutionMode.DEFAULT,
                         wf.TimePolicy.EVENT, config=wf.Config())
    pipe = graph.add_source(src)
    pipe.add(both)
    pipe.add(winners).add(mean).add(row).add_sink(snk)
    if ring is not None:
        ring["run"] = Run()
        ring["run"].graph = graph
    return graph


# ---------------------------------------------------------------------------
# the plain reference: numpy, int64, nothing of the program
# ---------------------------------------------------------------------------

class SellingPrices(NamedTuple):
    """Expected result rows, sorted by (key, wid).  ``full`` / ``closer``
    as in ``reference.Windows`` (an always-due mix reads neither);
    ``partial``: the rows whose window holds fewer than ``window``
    auctions; ``run``: the graph of the run the rows are compared with,
    where there was one."""
    key: np.ndarray       # int64: the seller
    wid: np.ndarray       # int64: the closing auction's dateTime, usec
    value: np.ndarray     # int64 [n, 5]: sum, how many, final price,
    full: np.ndarray      # expires, matched bids
    closer: np.ndarray
    partial: int = 0
    run: object = None


def sellers_of(rec: np.ndarray) -> np.ndarray:
    """Auction id -> its seller, over one pass of the ring (an id is
    created once a pass)."""
    a = np.flatnonzero(rec[KIND] == AUCTION)
    ids = rec["k"][a].astype(np.int64)
    if len(np.unique(ids)) != len(ids):
        raise ValueError("a pass of the ring creates an auction id twice")
    table = np.full(int(ids.max(initial=0)) + 1, -1, np.int64)
    table[ids] = rec[SELLER][a].astype(np.int64)
    return table


def moving_sums(seller, expires, opened, price, window: int,
                leading: bool = True, order=None):
    """Per seller, over its rows in the order ``(expires, opened)`` (or
    the stable ``order`` given): the sum of the last ``window`` prices
    at each row and how many rows it holds.  Returns ``(order, sum, n)``
    in that order; without ``leading`` the rows before a seller's
    ``window``-th are marked ``n = 0``."""
    if order is None:
        order = np.lexsort((opened, expires, seller))
    s, p = seller[order], price[order].astype(np.int64)
    n_rows = len(s)
    first = np.r_[True, s[1:] != s[:-1]] if n_rows else np.zeros(0, bool)
    start = np.maximum.accumulate(np.where(first, np.arange(n_rows), 0))
    rank = np.arange(n_rows) - start
    run = np.cumsum(p)
    before = run - p                     # the sum up to the row before
    lo = np.maximum(rank - window + 1, 0) + start
    total = run - before[lo]
    n = np.minimum(rank + 1, window)
    if not leading:
        n = np.where(rank + 1 >= window, n, 0)
    return order, total, n


def selling_prices(rec: np.ndarray, n_total: int, cfg: dict,
                   round_usec: int = 0, leading: bool = True,
                   run=None) -> SellingPrices:
    """The rows of the first ``n_total`` events of the ring repeated:
    Q9's winning bids (its reference, closed form over the passes), each
    with its auction's seller, then the moving sums per seller."""
    w = q9.winning_bids(rec, n_total, cfg["stream"]["event_rate"],
                        round_usec)[0]
    seller = sellers_of(rec)[w.key]
    price, expires, bids = w.value[:, 0], w.value[:, 3], w.value[:, 4]
    order, total, n = moving_sums(seller, expires, w.wid, price,
                                  cfg["graph"]["window_rows"], leading)
    keep = n > 0
    value = np.stack([total, n, price[order], expires[order], bids[order]],
                     axis=1).reshape(-1, N_VALUES)[keep]
    key, wid = seller[order][keep], w.wid[order][keep]
    by = np.lexsort((wid, key))
    m = len(key)
    return SellingPrices(
        key[by], wid[by], value[by], np.zeros(m, bool),
        np.full(m, -1, np.int64),
        int(np.count_nonzero(value[:, 1] < cfg["graph"]["window_rows"])), run)


def expected(cfg: dict, ring: dict, n_total: int,
             mix: dict) -> SellingPrices:
    q9._rate(cfg, mix)
    return selling_prices(ring["rec"], n_total, cfg, run=ring.get("run"))


def control(cfg: dict, ring: dict, n_total: int, mix: dict):
    """The configuration states no float precision (prices and times are
    exact); the control breaks the stated guarantee this deployment adds:
    a seller's auctions are counted in the order they OPEN (their
    dateTime: the order a source would hand them over in), not in the
    order they close.  Every row is there, under its own key and wid;
    the sums of the sellers whose auctions close out of that order
    differ."""
    q9._rate(cfg, mix)
    rec = ring["rec"]
    w = q9.winning_bids(rec, n_total, cfg["stream"]["event_rate"])[0]
    seller = sellers_of(rec)[w.key]
    price, expires, bids = w.value[:, 0], w.value[:, 3], w.value[:, 4]
    order, total, n = moving_sums(
        seller, expires, w.wid, price, cfg["graph"]["window_rows"],
        order=np.lexsort((w.wid, seller)))
    value = np.stack([total, n, price[order], expires[order], bids[order]],
                     axis=1).reshape(-1, N_VALUES)
    return seller[order], w.wid[order], value


def _counter_checks(run: Run, exp: SellingPrices) -> list:
    """What the stated guarantees say beyond the rows' content: no row
    reached the window older than a watermark it had acted on, and the
    window's own counts are the reference's."""
    global LAST_COUNTERS
    op = LAST_COUNTERS = run.window_counters()
    want = {"CB_rows_out_of_order": 0, "CB_windows_fired": len(exp.key),
            "CB_partial_windows": exp.partial, "CB_rows_waiting": 0}
    off = {k: (op.get(k), v) for k, v in want.items() if op.get(k) != v}
    print("benchmark: nexmark_q6 counters: " + ", ".join(
        f"{k} {op.get(k)}" for k in want)
        + (f"; NOT the reference's: {off}" if off else ""),
        file=sys.stderr, flush=True)
    return [ref.check("rows_out_of_order",
                      op.get("CB_rows_out_of_order", np.inf), 0),
            ref.check("counter_mismatches", len(off), 0)]


def compare(cfg: dict, got: dict, exp: SellingPrices) -> list:
    """The (seller, dateTime) rows exactly, then each row's five
    numbers: ``count_mismatches`` counts the rows in which any differs.
    After a run of the graph the window's counters too."""
    gk = np.asarray(got["key"]).astype(np.int64)
    gw = np.asarray(got["wid"]).astype(np.int64)
    gv = np.asarray(got["value"]).astype(np.int64).reshape(-1, N_VALUES)
    order, bad = ref.match_rows(gk, gw, exp)
    out = [ref.check("rows_missing_or_extra", abs(len(gk) - len(exp.key)),
                     0),
           ref.check("key_wid_mismatches", bad, 0),
           ref.check("result_rows_absent", 0 if len(gk) else 1, 0)]
    worst = np.inf if bad or not len(gk) else int(np.count_nonzero(
        np.any(gv[order] != exp.value, axis=1)))
    out.append(ref.check("count_mismatches", worst,
                         cfg["check"]["count_mismatches"]))
    if exp.run is not None and exp.run.graph is not None:
        out += _counter_checks(exp.run, exp)
    return out
