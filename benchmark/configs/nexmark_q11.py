"""``nexmark_q11``: NEXmark query 11, *user sessions* — how many bids did
a user make in each session they were active — over the generator's
1 : 3 : 46 person / auction / bid mix with its moving hot bidder: graph
builder, stream schema and plain reference.

A session is a maximal run of one bidder's bids in which each follows the
last by less than the gap; its window is ``[first bid, last bid + gap)``.
The graph keys the bids by bidder and runs one session-window operator on
the device; a row is (bidder, session start, [count, session end])."""

from __future__ import annotations

from typing import NamedTuple

import numpy as np

from benchmark import harness
from benchmark import reference as ref

q5 = harness.load_module("configs", "nexmark_q5")

BID = q5.BID
KIND, BIDDER = "v0", "v1"   # frame value lanes: kind, bidder, price,
N_FIELDS = 5                # channel, url
# the generator's constants (the configuration's "published")
DENOMINATOR = q5.DENOMINATOR
HOT_BIDDERS_RATIO = 4            # a bid is hot with probability 1 - 1/ratio
PERSON_ID_LEAD = 10
FIRST_PERSON_ID = 1000
ROUND_USEC = 1000                # the control's clock: whole milliseconds


def require_session_windows() -> None:
    """A program from before session windows cannot run the deployment,
    and says so at once instead of building half a graph."""
    import windflow_tpu as wf
    if not hasattr(wf, "Session_WindowsTPU_Builder"):
        raise RuntimeError(
            "this program has no session-window operator "
            "(windflow_tpu.Session_WindowsTPU_Builder): it does not "
            "support windows whose boundaries come from the data "
            "(nexmark_q11)")


def bidders(n: int, rng, active_people: int, stride: int) -> np.ndarray:
    """Base-0 bidder of each event of the segment were it a bid (Beam's
    ``BidGenerator``): the hot bidder with probability 3/4 (the newest
    person id rounded down to a multiple of ``stride``,
    ``BidGenerator.HOT_BIDDER_RATIO``, plus 1), else one of the newest
    ``active_people`` persons or the next ``PERSON_ID_LEAD`` ids."""
    last = np.arange(n, dtype=np.int64) // DENOMINATOR   # newest person
    hot = rng.integers(0, HOT_BIDDERS_RATIO, n) > 0
    people = last + 1
    active = np.minimum(people, active_people)
    cold = people - active \
        + (rng.random(n) * (active + PERSON_ID_LEAD)).astype(np.int64)
    return np.where(hot, last // stride * stride + 1, cold)


def make_ring(seed: int, cfg: dict) -> dict:
    require_session_windows()
    g, s = cfg["graph"], cfg["stream"]
    n = g["batch"] * s["ring_batches"]
    # the frame, the kinds and the auction of a bid are Q5's, seed for seed
    rec = q5.make_ring(seed, {"graph": {"batch": g["batch"],
                                        "max_keys": 1 << 62},
                              "stream": s})["rec"]
    rng = np.random.default_rng([seed, 11])
    who = bidders(n, rng, s["active_people"], s["hot_bidder_stride"])
    if int(who.max(initial=0)) >= g["max_keys"]:
        raise ValueError("the segment's bidder ids pass max_keys")
    # under 2**24, so the float32 lane holds it
    rec[BIDDER] = FIRST_PERSON_ID + who
    ring = {"rec": rec}
    # a bidder's activity plus the gap has to fit inside one replay
    # period, or its sessions cross passes and the closed form is wrong
    one_pass(_bid_keys(ring), s["event_rate"], g["gap_usec"])
    return ring


def build_graph(cfg: dict, ring: dict, chunks_fn, sink_fn):
    import jax.numpy as jnp

    import windflow_tpu as wf
    from windflow_tpu.io import FrameSource
    g = cfg["graph"]
    require_session_windows()
    src = FrameSource(chunks_fn, nv=N_FIELDS, fmt="frames",
                      output_batch_size=g["batch"])
    src.record_spec = {"key": np.int32(0),
                       **{f"v{i}": np.float32(0.0) for i in range(N_FIELDS)}}
    bids = wf.FilterTPU_Builder(lambda e: e[KIND] == float(BID)).build()
    sessions = (wf.Session_WindowsTPU_Builder(lambda e: jnp.int64(1),
                                              lambda a, b: a + b)
                .withName("bids_per_session").withGap(g["gap_usec"])
                .withKeyBy(lambda e: e[BIDDER].astype(jnp.int32)
                           - FIRST_PERSON_ID)
                .withMaxKeys(g["max_keys"]).build())
    row = wf.MapTPU_Builder(lambda r: {
        "key": r["key"] + FIRST_PERSON_ID, "wid": r["start"],
        "value": jnp.stack([r["value"], r["end"]])}).withName(
        "session_row").build()
    snk = wf.Sink_Builder(sink_fn).withColumnarSink().build()
    graph = wf.PipeGraph("bench_" + cfg["name"], wf.ExecutionMode.DEFAULT,
                         wf.TimePolicy.EVENT, config=wf.Config())
    pipe = graph.add_source(src)
    pipe.add(bids)
    pipe.add(sessions).add(row).add_sink(snk)
    return graph


# ---------------------------------------------------------------------------
# the plain reference: numpy, int64, nothing of the program
# ---------------------------------------------------------------------------

class Sessions(NamedTuple):
    """Expected result rows, sorted by (key, wid).  ``full`` / ``closer``
    as in ``reference.Windows`` (an always-due mix reads neither)."""
    key: np.ndarray       # int64: the bidder
    wid: np.ndarray       # int64: the session's first event time, usec
    value: np.ndarray     # int64 [n, 2]: its bids, its end (last + gap)
    full: np.ndarray
    closer: np.ndarray


def _bid_keys(ring: dict) -> np.ndarray:
    """Bidder of each ring record, -1 where it is not a bid."""
    rec = ring["rec"]
    return np.where(rec[KIND] == BID, rec[BIDDER], -1).astype(np.int64)


def sessions_of(keys: np.ndarray, tss: np.ndarray, gap_usec: int):
    """Sessions of the events ``(keys, tss)`` (a negative key is no bid):
    per key in time order, cut where the next bid is ``gap_usec`` or more
    after the last.  Returns ``(key, first, last, count)`` by (key,
    first)."""
    sel = keys >= 0
    k, t = keys[sel], tss[sel]
    order = np.lexsort((t, k))
    k, t = k[order], t[order]
    if not len(k):
        e = np.empty(0, np.int64)
        return e, e, e, e
    cut = np.r_[True, (k[1:] != k[:-1]) | (t[1:] - t[:-1] >= gap_usec)]
    starts = np.flatnonzero(cut)
    ends = np.r_[starts[1:], len(k)] - 1
    return k[starts], t[starts], t[ends], ends - starts + 1


def _stamps(n: int, event_rate: int, round_usec: int = 0) -> np.ndarray:
    ts = np.arange(n, dtype=np.int64) * 1_000_000 // event_rate
    if round_usec:            # the control's clock: to the nearest unit,
        # counted from the start of the pass (every pass rounds alike)
        ts = (ts + round_usec // 2) // round_usec * round_usec
    return ts


def period_usec(n_ring: int, event_rate: int) -> int:
    """Event time one pass of the ring spans; the closed form needs every
    pass stamped alike, so it has to be whole."""
    if n_ring * 1_000_000 % event_rate:
        raise ValueError("a pass of the ring does not span a whole "
                         "number of microseconds of event time")
    return n_ring * 1_000_000 // event_rate


def one_pass(keys: np.ndarray, event_rate: int, gap_usec: int,
             round_usec: int = 0):
    """The sessions of one whole pass of the ring, its stamps and the
    event time it spans.  Raises where a key's last session of a pass
    and its first of the next would touch."""
    period = period_usec(len(keys), event_rate)
    tss = _stamps(len(keys), event_rate, round_usec)
    whole = sessions_of(keys, tss, gap_usec)
    k, first, last, _ = whole
    if len(k):
        head = np.r_[True, k[1:] != k[:-1]]
        tail = np.r_[head[1:], True]
        if np.any(first[head] + period - last[tail] < gap_usec):
            raise ValueError(
                "a bidder's activity plus the gap does not fit inside one "
                "replay period: sessions would cross passes")
    return whole, tss, period


def user_sessions(keys: np.ndarray, n_total: int, event_rate: int,
                  gap_usec: int, round_usec: int = 0) -> Sessions:
    """Sessions over the first ``n_total`` events of the ring ``keys``
    repeated, event *i* stamped ``i * 1e6 // event_rate`` usec: the
    sessions of one pass, once for every whole pass and once more over
    the events of the last, partial one.  Raises where a key's sessions
    would cross from one pass into the next (the closed form would then
    be wrong)."""
    R = len(keys)
    q, r = divmod(int(n_total), R)
    whole, tss, period = one_pass(keys, event_rate, gap_usec, round_usec)
    parts = [(whole, p) for p in range(q)]
    if r:
        parts.append((sessions_of(keys[:r], tss[:r], gap_usec), q))

    def cat(i: int, shift: int = 0) -> np.ndarray:
        return np.concatenate([s[i] + p * shift for s, p in parts]) \
            if parts else np.empty(0, np.int64)
    key, wid, cnt = cat(0), cat(1, period), cat(3)
    end = cat(2, period) + gap_usec
    order = np.lexsort((wid, key))
    n = len(key)
    return Sessions(key[order], wid[order],
                    np.stack([cnt[order], end[order]], axis=1)
                    .astype(np.int64).reshape(n, 2),
                    np.zeros(n, bool), np.full(n, -1, np.int64))


def expected(cfg: dict, ring: dict, n_total: int, mix: dict) -> Sessions:
    if int(mix["event_rate"]) != cfg["stream"]["event_rate"]:
        raise ValueError("the ring was sized for another event rate than "
                         "the mix stamps")
    return user_sessions(_bid_keys(ring), n_total, int(mix["event_rate"]),
                         cfg["graph"]["gap_usec"])


def control(cfg: dict, ring: dict, n_total: int, mix: dict):
    """The configuration states no float precision (counts and times are
    exact); the control lowers the precision of the one lane the result
    depends on: event time rounded to the nearest millisecond (of the
    time since its pass of the ring began), so a session's start and end
    move by up to half a millisecond."""
    s = user_sessions(_bid_keys(ring), n_total, int(mix["event_rate"]),
                      cfg["graph"]["gap_usec"], round_usec=ROUND_USEC)
    return s.key, s.wid, s.value


def compare(cfg: dict, got: dict, exp: Sessions) -> list:
    """The (bidder, session start) rows exactly, then each row's count
    and end: ``count_mismatches`` counts the rows in which either
    differs."""
    gk = np.asarray(got["key"]).astype(np.int64)
    gw = np.asarray(got["wid"]).astype(np.int64)
    gv = np.asarray(got["value"]).astype(np.int64).reshape(-1, 2)
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
