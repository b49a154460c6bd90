"""``nexmark_q5``: NEXmark query 5, *hot items* — which auction has seen
the most bids in the last 10 s, updated every 5 s — over the generator's
1 : 3 : 46 person / auction / bid mix with its moving hot auction: graph
builder, stream schema and plain reference.

The graph is two window stages on the device: bids per auction in a
sliding window over a key space as wide as the replayed segment's auction
ids, then, over the rows that stage fires, a non-keyed tumbling window
whose combiner is an arg-max carrying the winning auction (and two
digests: how many auctions had a bid, how many bids there were)."""

from __future__ import annotations

from typing import NamedTuple

import numpy as np

from benchmark import reference as ref
from benchmark.generator import frame_dtype

PERSON, AUCTION, BID = 0, 1, 2
KIND = "v0"              # frame value lanes: kind, bidder, price, channel,
N_FIELDS = 5             # url
# the generator's constants (the configuration's "published")
PERSON_PROPORTION, AUCTION_PROPORTION, DENOMINATOR = 1, 3, 50
HOT_AUCTION_RATIO = 2            # a bid is hot with probability 1 - 1/ratio
HOT_AUCTION_STRIDE = 100         # BidGenerator.HOT_AUCTION_RATIO
IN_FLIGHT_AUCTIONS = 100
AUCTION_ID_LEAD = 10
FIRST_AUCTION_ID = 1000
FIRST_PERSON_ID = 1000


def last_auction(i: np.ndarray) -> np.ndarray:
    """Base-0 id of the newest auction at event ``i`` of the segment
    (the generator's ``lastBase0AuctionId``; -1 before the first)."""
    epoch, off = i // DENOMINATOR, i % DENOMINATOR
    return np.where(
        off < PERSON_PROPORTION, epoch * AUCTION_PROPORTION - 1,
        epoch * AUCTION_PROPORTION
        + np.minimum(off - PERSON_PROPORTION, AUCTION_PROPORTION - 1))


def make_ring(seed: int, cfg: dict) -> dict:
    g, s = cfg["graph"], cfg["stream"]
    n = g["batch"] * s["ring_batches"]
    rng = np.random.default_rng(seed)
    i = np.arange(n, dtype=np.int64)
    off = i % DENOMINATOR
    kind = np.where(off < PERSON_PROPORTION, PERSON,
                    np.where(off < PERSON_PROPORTION + AUCTION_PROPORTION,
                             AUCTION, BID))
    last = last_auction(i)
    hot = rng.integers(0, HOT_AUCTION_RATIO, n) > 0
    lo = np.maximum(last - IN_FLIGHT_AUCTIONS, 0)
    cold = lo + (rng.random(n) * (last - lo + 1 + AUCTION_ID_LEAD)) \
        .astype(np.int64)
    bid_auction = np.where(
        hot, last // HOT_AUCTION_STRIDE * HOT_AUCTION_STRIDE, cold)
    rec = np.empty(n, dtype=frame_dtype(N_FIELDS))
    rec["k"] = np.where(kind == BID, FIRST_AUCTION_ID + bid_auction,
                        np.where(kind == AUCTION, FIRST_AUCTION_ID + last,
                                 FIRST_PERSON_ID + i // DENOMINATOR))
    rec["t"] = 0
    rec[KIND] = kind
    # under 2**24 so the float32 lanes hold them; no result reads them
    rec["v1"] = rng.integers(0, 1 << 20, n)          # bidder
    rec["v2"] = rng.integers(0, 1 << 24, n)          # price
    rec["v3"] = rng.integers(0, 4, n)                # channel
    rec["v4"] = rng.integers(0, 1 << 24, n)          # url
    if int(bid_auction[kind == BID].max(initial=0)) >= g["max_keys"]:
        raise ValueError("the segment's auction ids pass max_keys")
    return {"rec": rec}


def require_compacted_hand_over(cfg: dict) -> None:
    """A program from before a time window compacted its fired rows on
    the device hands the second stage its whole ``keys x 3 (NP // D +
    2)`` grid, 133.7 M lanes a step at this configuration's sizes: it
    cannot run the deployment, and says so at once instead of compiling
    a second stage of that capacity."""
    from windflow_tpu.windows import ffat_kernels
    if not hasattr(ffat_kernels, "tb_out_capacity"):
        raise RuntimeError(
            "this program's time window hands on its whole key x window "
            "grid: it does not support a second window stage over "
            f"{cfg['graph']['max_keys']} keys (nexmark_q5)")


def build_graph(cfg: dict, ring: dict, chunks_fn, sink_fn):
    import jax.numpy as jnp

    import windflow_tpu as wf
    from windflow_tpu.io import FrameSource
    g = cfg["graph"]
    require_compacted_hand_over(cfg)
    src = FrameSource(chunks_fn, nv=N_FIELDS, fmt="frames",
                      output_batch_size=g["batch"])
    src.record_spec = {"key": np.int32(0),
                       **{f"v{i}": np.float32(0.0) for i in range(N_FIELDS)}}
    bids = wf.FilterTPU_Builder(lambda e: e[KIND] == float(BID)).build()
    counts = (wf.Ffat_WindowsTPU_Builder(lambda e: jnp.int64(1),
                                         lambda a, b: a + b)
              .withName("bids_per_auction")
              .withTBWindows(g["window_usec"], g["slide_usec"])
              .withKeyBy(lambda e: e["key"] - FIRST_AUCTION_ID)
              .withMaxKeys(g["max_keys"]).withSumCombiner().build())

    def lift(row):
        # one fired row of the first stage: (auction, window, count)
        return {"auction": row["key"].astype(jnp.int64) + FIRST_AUCTION_ID,
                "count": row["value"], "auctions": jnp.int64(1),
                "bids": row["value"]}

    def hotter(a, b):
        # arg-max that carries the winner; ties go to the lowest id
        b_wins = (b["count"] > a["count"]) | (
            (b["count"] == a["count"]) & (b["auction"] < a["auction"]))
        pick = lambda x, y: jnp.where(b_wins, y, x)  # noqa: E731
        return {"auction": pick(a["auction"], b["auction"]),
                "count": pick(a["count"], b["count"]),
                "auctions": a["auctions"] + b["auctions"],
                "bids": a["bids"] + b["bids"]}

    # a sliding window's rows are stamped with its last microsecond, so
    # tumbling slide w + panes - 1 holds the rows of window w and no other
    top = (wf.Ffat_WindowsTPU_Builder(lift, hotter).withName("hot_item")
           .withTBWindows(g["slide_usec"], g["slide_usec"]).build())
    panes = g["window_usec"] // g["slide_usec"]
    row = wf.MapTPU_Builder(lambda r: {
        "key": r["value"]["auction"], "wid": r["wid"] - (panes - 1),
        "value": jnp.stack([r["value"]["count"], r["value"]["auctions"],
                            r["value"]["bids"]])}).withName(
        "hot_item_row").build()
    snk = wf.Sink_Builder(sink_fn).withColumnarSink().build()
    graph = wf.PipeGraph("bench_" + cfg["name"], wf.ExecutionMode.DEFAULT,
                         wf.TimePolicy.EVENT, config=wf.Config())
    pipe = graph.add_source(src)
    pipe.add(bids)
    pipe.add(counts).add(top).add(row).add_sink(snk)
    return graph


# ---------------------------------------------------------------------------
# the plain reference: numpy, int64, nothing of the program
# ---------------------------------------------------------------------------

class HotItems(NamedTuple):
    """Expected result rows, by window.  ``full`` / ``closer`` as in
    ``reference.Windows`` (an always-due mix reads neither)."""
    key: np.ndarray       # int64: the auction with the most bids
    wid: np.ndarray       # int64: the sliding window
    value: np.ndarray     # int64 [n, 3]: its count, auctions, bids
    full: np.ndarray
    closer: np.ndarray


def _bid_keys(ring: dict) -> np.ndarray:
    """Base-0 auction of each ring record, -1 where it is not a bid."""
    rec = ring["rec"]
    return np.where(rec[KIND] == BID, rec["k"] - FIRST_AUCTION_ID, -1) \
        .astype(np.int64)


def pane_counts(keys: np.ndarray, n_total: int, event_rate: int,
                slide_usec: int, n_keys: int, stamp_offset_usec: int = 0):
    """Bids per auction in each pane (one slide of event time) over the
    first ``n_total`` events of the ring ``keys`` repeated, event *i*
    stamped ``i * 1e6 // event_rate`` usec (plus ``stamp_offset_usec``,
    which only the control uses).  Yields ``(pane, hi, counts)``: ``hi``
    is the stream index one past the pane's last event."""
    R = len(keys)
    per_pass = np.bincount(keys[keys >= 0], minlength=n_keys)

    def count(lo: int, hi: int) -> np.ndarray:      # stream range [lo, hi)
        passes, rest = divmod(hi - lo, R)
        a = lo % R
        ends = [keys[a:a + rest]] if a + rest <= R \
            else [keys[a:], keys[:a + rest - R]]
        c = passes * per_pass
        for s in ends:
            c = c + np.bincount(s[s >= 0], minlength=n_keys)
        return c

    def first_at(ts: int) -> int:        # first index stamped >= ts
        return max(0, -(-((ts - stamp_offset_usec) * event_rate)
                        // 1_000_000))
    last_ts = (n_total - 1) * 1_000_000 // event_rate + stamp_offset_usec
    for p in range(last_ts // slide_usec + 1):
        lo = first_at(p * slide_usec)
        hi = min(n_total, first_at((p + 1) * slide_usec))
        yield p, hi, count(lo, hi)


def hot_items(keys: np.ndarray, n_total: int, event_rate: int,
              window_usec: int, slide_usec: int, n_keys: int,
              stamp_offset_usec: int = 0) -> HotItems:
    """One row for every window ``[w * slide, w * slide + window)``,
    ``w >= 0``, that holds a bid: a window is the sum of its panes."""
    if window_usec % slide_usec:
        raise ValueError("the window is a whole number of slides")
    r = window_usec // slide_usec
    panes = list(pane_counts(keys, n_total, event_rate, slide_usec, n_keys,
                             stamp_offset_usec))
    out_k, out_w, out_v, out_f, out_c = [], [], [], [], []
    for w in range(len(panes)):
        mine = panes[w:w + r]
        c = np.sum([p[2] for p in mine], axis=0)
        bids = int(c.sum())
        if not bids:
            continue
        best = int(np.argmax(c))         # the first of the largest: lowest id
        closed = len(mine) == r and mine[-1][1] < n_total
        out_k.append(best + FIRST_AUCTION_ID)
        out_w.append(w)
        out_v.append((int(c[best]), int(np.count_nonzero(c)), bids))
        out_f.append(closed)
        out_c.append(mine[-1][1] if closed else -1)
    return HotItems(np.array(out_k, np.int64), np.array(out_w, np.int64),
                    np.array(out_v, np.int64).reshape(-1, 3),
                    np.array(out_f, bool), np.array(out_c, np.int64))


def expected(cfg: dict, ring: dict, n_total: int, mix: dict) -> HotItems:
    g = cfg["graph"]
    return hot_items(_bid_keys(ring), n_total, int(mix["event_rate"]),
                     g["window_usec"], g["slide_usec"], g["max_keys"])


def control(cfg: dict, ring: dict, n_total: int, mix: dict):
    """The configuration states no float precision (counts are exact);
    the control lowers the precision of the one lane the result depends
    on: event time rounded to the nearest millisecond, so the bids of a
    pane's last half millisecond count with the next pane."""
    g = cfg["graph"]
    h = hot_items(_bid_keys(ring), n_total, int(mix["event_rate"]),
                  g["window_usec"], g["slide_usec"], g["max_keys"],
                  stamp_offset_usec=500)
    return h.key, h.wid, h.value


def compare(cfg: dict, got: dict, exp: HotItems) -> list:
    """The (auction, window) rows exactly, then each row's three
    numbers: ``count_mismatches`` counts the rows in which any differs."""
    gk = np.asarray(got["key"]).astype(np.int64)
    gw = np.asarray(got["wid"]).astype(np.int64)
    gv = np.asarray(got["value"]).astype(np.int64).reshape(-1, 3)
    # one row a window: match by window, so that a wrong winner is a
    # (key, window) mismatch and not a row missing and a row extra
    order = np.argsort(gw, kind="stable")
    gk, gw, gv = gk[order], gw[order], gv[order]
    same_set = len(gw) == len(exp.wid) and bool(np.all(gw == exp.wid))
    out = [ref.check("rows_missing_or_extra",
                     abs(len(gw) - len(exp.wid))
                     or int(np.count_nonzero(gw != exp.wid)), 0),
           ref.check("key_wid_mismatches",
                     int(np.count_nonzero(gk != exp.key)) if same_set
                     else np.inf, 0),
           ref.check("result_rows_absent", 0 if len(gw) else 1, 0)]
    worst = int(np.count_nonzero(np.any(gv != exp.value, axis=1))) \
        if same_set and len(gw) else np.inf
    out.append(ref.check("count_mismatches", worst,
                         cfg["check"]["count_mismatches"]))
    return out
