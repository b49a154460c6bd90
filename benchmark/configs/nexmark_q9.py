"""``nexmark_q9``: NEXmark query 9, *winning bids* — for every auction,
the highest bid placed while it was open that met its reserve — over the
generator's 1 : 3 : 46 person / auction / bid mix with its moving hot
auction: graph builder, stream schema and plain reference.

An auction event opens the interval ``[dateTime, expires)`` on its id; a
bid matches the auction of its id that is open at its time, if its price
is at least the auction's reserve; the winner is the highest price, the
earlier bid on a tie.  The graph drops the persons and runs one keyed
interval join on the device, both sides at the stream's rate; a row is
(auction, dateTime, [price, bidder, bid time, expires, matched bids])."""

from __future__ import annotations

from typing import NamedTuple

import numpy as np

from benchmark import harness
from benchmark import reference as ref

q5 = harness.load_module("configs", "nexmark_q5")
q11 = harness.load_module("configs", "nexmark_q11")

PERSON, AUCTION, BID = q5.PERSON, q5.AUCTION, q5.BID
# frame value lanes: kind, bidder, price (a bid's) / reserve (an
# auction's), channel, the auction's length in usec
KIND, BIDDER, PRICE, LENGTH = "v0", "v1", "v2", "v4"
RESERVE = PRICE
N_FIELDS = 5
# the generator's constants (the configuration's "published")
DENOMINATOR = q5.DENOMINATOR
AUCTION_PROPORTION = q5.AUCTION_PROPORTION
IN_FLIGHT_AUCTIONS = q5.IN_FLIGHT_AUCTIONS
FIRST_AUCTION_ID = q5.FIRST_AUCTION_ID
FIRST_PERSON_ID = q11.FIRST_PERSON_ID
PRICE_DECADES = 6                # PriceGenerator: 10 ** (6 u), rounded
ROUND_USEC = q11.ROUND_USEC      # the control's clock: whole milliseconds
N_VALUES = 5                     # numbers a row carries


def require_interval_join() -> None:
    """A program from before the interval join cannot run the
    deployment, and says so at once instead of building half a graph."""
    import windflow_tpu as wf
    if not hasattr(wf, "Interval_JoinTPU_Builder"):
        raise RuntimeError(
            "this program has no two-input keyed operator "
            "(windflow_tpu.Interval_JoinTPU_Builder): it does not "
            "support joining bids to their open auction (nexmark_q9)")


def prices(n: int, rng) -> np.ndarray:
    """Beam's ``PriceGenerator.nextPrice``: log-uniform over six decades,
    in whole units."""
    return np.round(10.0 ** (rng.random(n) * PRICE_DECADES)) \
        .astype(np.int64)


def horizon_usec(event_rate: int) -> int:
    """Event time in which ``numInFlightAuctions`` auctions open: Beam
    sizes an auction's life by it (``nextAuctionLengthMs``)."""
    events = round(IN_FLIGHT_AUCTIONS * DENOMINATOR / AUCTION_PROPORTION)
    return events * 1_000_000 // event_rate


def make_ring(seed: int, cfg: dict) -> dict:
    require_interval_join()
    g, s = cfg["graph"], cfg["stream"]
    n = g["batch"] * s["ring_batches"]
    # the frame, the kinds and every id are Q5's, seed for seed
    rec = q5.make_ring(seed, {"graph": {"batch": g["batch"],
                                        "max_keys": 1 << 62},
                              "stream": s})["rec"]
    rng = np.random.default_rng([seed, 9])
    is_auction = rec[KIND] == AUCTION
    # all under 2**24, so the float32 lanes hold them exactly
    rec[PRICE] = np.where(is_auction, prices(n, rng) + prices(n, rng),
                          prices(n, rng))
    rec[LENGTH] = np.where(
        is_auction, 1 + (rng.random(n) * max(
            2 * horizon_usec(s["event_rate"]), 1)).astype(np.int64), 0)
    rec[BIDDER] = FIRST_PERSON_ID + q11.bidders(
        n, rng, s["active_people"], s["hot_bidder_stride"])
    ring = {"rec": rec}
    one_pass(rec, s["event_rate"])
    return ring


def build_graph(cfg: dict, ring: dict, chunks_fn, sink_fn):
    import jax.numpy as jnp

    import windflow_tpu as wf
    from windflow_tpu.io import FrameSource
    g = cfg["graph"]
    require_interval_join()
    src = FrameSource(chunks_fn, nv=N_FIELDS, fmt="frames",
                      output_batch_size=g["batch"])
    src.record_spec = {"key": np.int32(0),
                       **{f"v{i}": np.float32(0.0) for i in range(N_FIELDS)}}
    both = wf.FilterTPU_Builder(lambda e: e[KIND] != float(PERSON)).build()

    def higher(a, b):
        # arg-max that carries the bid; ties go to the earlier one
        b_wins = (b["price"] > a["price"]) | (
            (b["price"] == a["price"]) & (b["at"] < a["at"]))
        pick = lambda x, y: jnp.where(b_wins, y, x)  # noqa: E731
        return {"price": pick(a["price"], b["price"]),
                "at": pick(a["at"], b["at"]),
                "bidder": pick(a["bidder"], b["bidder"])}

    winners = (wf.Interval_JoinTPU_Builder(
        lambda auction, bid, ts: {"price": bid[PRICE], "at": ts,
                                  "bidder": bid[BIDDER]}, higher)
        .withName("winning_bids")
        .withBuildSide(lambda e: e[KIND] == float(AUCTION))
        .withIntervalLength(lambda e: e[LENGTH].astype(jnp.int32))
        .withMatch(lambda auction, bid: bid[PRICE] >= auction[RESERVE])
        .withKeyBy(lambda e: e["key"] - FIRST_AUCTION_ID)
        .withBuildCapacity(g["build_capacity"])
        .withOutputCapacity(g["out_capacity"]).build())
    i64 = lambda a: a.astype(jnp.int64)   # noqa: E731
    row = wf.MapTPU_Builder(lambda r: {
        "key": r["key"] + FIRST_AUCTION_ID, "wid": r["start"],
        "value": jnp.stack([i64(r["value"]["price"]),
                            i64(r["value"]["bidder"]), r["value"]["at"],
                            r["end"], i64(r["count"])])}).withName(
        "winning_bid_row").build()
    snk = wf.Sink_Builder(sink_fn).withColumnarSink().build()
    graph = wf.PipeGraph("bench_" + cfg["name"], wf.ExecutionMode.DEFAULT,
                         wf.TimePolicy.EVENT, config=wf.Config())
    pipe = graph.add_source(src)
    pipe.add(both)
    pipe.add(winners).add(row).add_sink(snk)
    return graph


# ---------------------------------------------------------------------------
# the plain reference: numpy, int64, nothing of the program
# ---------------------------------------------------------------------------

class WinningBids(NamedTuple):
    """Expected result rows, sorted by (key, wid).  ``full`` / ``closer``
    as in ``reference.Windows`` (an always-due mix reads neither)."""
    key: np.ndarray       # int64: the auction
    wid: np.ndarray       # int64: its dateTime, usec
    value: np.ndarray     # int64 [n, 5]: winning price, bidder, bid
    full: np.ndarray      # time, expires, matched bids
    closer: np.ndarray


def winners_of(rec: np.ndarray, tss: np.ndarray):
    """The join over the events ``rec`` stamped ``tss``: every bid goes
    to the newest auction event of its id at or before its time, and
    counts where it lies before that auction's ``expires`` and meets its
    reserve; per auction event the matched bids, the highest (ties: the
    earlier time, then the earlier event).  Returns ``(key, dateTime,
    value [n, 5], counts)`` for the auctions with a matched bid, and
    ``counts``: auctions, matched bids, bids under the reserve, bids
    that found no open auction."""
    i64 = lambda a: np.asarray(a).astype(np.int64)   # noqa: E731
    a = np.flatnonzero(rec[KIND] == AUCTION)
    b = np.flatnonzero(rec[KIND] == BID)
    if not len(a):
        none = np.empty(0, np.int64)
        return none, none, np.empty((0, N_VALUES), np.int64), {
            "auctions": 0, "matched": 0, "under_reserve": 0,
            "no_open_auction": len(b)}
    span = int(tss.max(initial=0)) + 1
    a = a[np.lexsort((tss[a], rec["k"][a]))]
    a_at = i64(rec["k"][a]) * span + tss[a]
    at = np.searchsorted(a_at, i64(rec["k"][b]) * span + tss[b],
                         side="right") - 1
    mine = a[np.maximum(at, 0)]
    inside = (at >= 0) & (rec["k"][mine] == rec["k"][b]) \
        & (tss[b] < tss[mine] + i64(rec[LENGTH][mine]))
    meets = i64(rec[PRICE][b]) >= i64(rec[RESERVE][mine])
    m = inside & meets
    bid, slot = b[m], at[m]
    n = np.bincount(slot, minlength=len(a))
    best = np.lexsort((bid, tss[bid], -i64(rec[PRICE][bid]), slot))
    first = np.r_[True, slot[best][1:] != slot[best][:-1]] \
        if len(best) else np.zeros(0, bool)
    win, won = bid[best][first], a[slot[best][first]]
    value = np.stack([i64(rec[PRICE][win]), i64(rec[BIDDER][win]),
                      tss[win], tss[won] + i64(rec[LENGTH][won]),
                      n[slot[best][first]]], axis=1).reshape(-1, N_VALUES)
    counts = {"auctions": len(a), "matched": int(m.sum()),
              "under_reserve": int((inside & ~meets).sum()),
              "no_open_auction": int((~inside).sum())}
    return i64(rec["k"][won]), tss[won], value, counts


def one_pass(rec: np.ndarray, event_rate: int, round_usec: int = 0):
    """One whole pass of the ring: its stamps and the event time it
    spans.  Raises where an auction of one pass could take a bid of the
    next (the closed form would then be wrong): where it outlives the
    pass by enough to meet a bid of its id, or lives a whole pass."""
    period = q11.period_usec(len(rec), event_rate)
    tss = q11._stamps(len(rec), event_rate, round_usec)
    a = np.flatnonzero(rec[KIND] == AUCTION)
    over = tss[a] + rec[LENGTH][a].astype(np.int64) - period
    if np.any(over >= tss[a]):
        raise ValueError("an auction outlives a whole replay period")
    late, b = a[over > 0], np.flatnonzero(rec[KIND] == BID)
    early = b[tss[b] < over.max(initial=0)]
    if np.any((rec["k"][late][:, None] == rec["k"][early][None, :])
              & (tss[early][None, :] < over[over > 0][:, None])):
        raise ValueError("an auction that outlives its pass of the ring "
                         "meets a bid of the next pass")
    return tss, period


def winning_bids(rec: np.ndarray, n_total: int, event_rate: int,
                 round_usec: int = 0):
    """Winning bids over the first ``n_total`` events of the ring ``rec``
    repeated, event *i* stamped ``i * 1e6 // event_rate`` usec: the rows
    of one pass, once for every whole pass (shifted by the pass's event
    time), and the rows of the last, partial pass computed directly (an
    auction the stream ends in takes the bids up to its end and fires at
    the end of stream).  Returns ``(WinningBids, counts)``."""
    R = len(rec)
    q, r = divmod(int(n_total), R)
    tss, period = one_pass(rec, event_rate, round_usec)
    whole = winners_of(rec, tss) if q else None
    parts = [(whole, p) for p in range(q)]
    if r:
        parts.append((winners_of(rec[:r], tss[:r]), q))
    if not parts:
        parts = [(winners_of(rec[:0], tss[:0]), 0)]
    key = np.concatenate([w[0] for w, _ in parts])
    wid = np.concatenate([w[1] + p * period for w, p in parts])
    shift = np.array([0, 0, 1, 1, 0], np.int64) * period
    value = np.concatenate([w[2] + p * shift for w, p in parts])
    counts = {name: sum(w[3][name] for w, _ in parts)
              for name in parts[0][0][3]}
    order = np.lexsort((wid, key))
    n = len(key)
    return WinningBids(key[order], wid[order], value[order],
                       np.zeros(n, bool), np.full(n, -1, np.int64)), counts


def _rate(cfg: dict, mix: dict) -> int:
    if int(mix["event_rate"]) != cfg["stream"]["event_rate"]:
        raise ValueError("the ring was sized for another event rate than "
                         "the mix stamps")
    return int(mix["event_rate"])


def expected(cfg: dict, ring: dict, n_total: int, mix: dict) -> WinningBids:
    return winning_bids(ring["rec"], n_total, _rate(cfg, mix))[0]


def control(cfg: dict, ring: dict, n_total: int, mix: dict):
    """The configuration states no float precision (prices and times are
    exact); the control lowers the precision of the one lane every
    result depends on: event time rounded to the nearest millisecond (of
    the time since its pass of the ring began), which moves bids across
    both ends of intervals 1-3 334 usec long."""
    w = winning_bids(ring["rec"], n_total, _rate(cfg, mix),
                     round_usec=ROUND_USEC)[0]
    return w.key, w.wid, w.value


def compare(cfg: dict, got: dict, exp: WinningBids) -> list:
    """The (auction, dateTime) rows exactly, then each row's five
    numbers: ``count_mismatches`` counts the rows in which any differs."""
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
    return out
