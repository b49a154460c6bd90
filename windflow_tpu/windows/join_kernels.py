"""Interval-join device programs (no operator-layer dependencies).

Two kinds of rows meet on a key.  A BUILD row opens the interval ``[t,
t + length)`` of event time on its key; a PROBE row at ``u`` matches the
build row of its key with ``t <= u < t + length`` if the predicate holds;
matched probes are lifted and folded per build row; the build row closes,
leaves one result row (none where nothing matched) and is evicted once the
watermark passes ``t + length``.

State is sized by what is OPEN, not by the key space: a carry of ``C``
build rows (key, interval, the row itself, its fold so far).  One step,
per fixed-capacity batch of ``B`` lanes, over ``C + B`` lanes:

1. the carried rows and the batch are sorted together by (key, event
   time, build before probe), with each row's end and the leaves of the
   row that ``match`` / ``lift`` read riding the sort
   (``session_kernels.sort_lanes``; what a row held in the carry, and the
   rest of a build row, are fetched for the few lanes that need them);
2. lanes are cut into runs where the key changes or a build row stands:
   one run is one build row and the probes that follow it on its key.
   One segmented scan hands the run's build row down its lanes (interval
   test, ``match``, ``lift``); a second, from each run's end back to its
   start, folds the lifted matches into the build row's lane;
3. a build row whose key sees a newer one before its end is DISPLACED and
   closes at once; the others close where ``t + length <= watermark``.
   One sort by (class, lane) brings the closed rows to the front, the
   rows that stay open behind them: the output batch is the front of that
   order, the next carry the ``C`` lanes after the rows that left.  Closed
   rows beyond the output's capacity are HELD BACK in the carry (the
   operator holds its hand-on watermark back with them); rows beyond the
   carry's capacity are lost and counted (``overflow``: the operator
   stops the graph).

Nothing is indexed by key and no pass is as wide as a key space; there is
no scatter at all (a 64-bit one costs 18.5 ms over 262144 lanes on a v5e, a
32-bit one 2.2-3, a sort with operands riding 0.4: ``PERF.md`` section 6).
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from windflow_tpu.monitoring.recorder import phase
from windflow_tpu.windows.ffat_kernels import _b, _flag_comb, _seg_scan
from windflow_tpu.windows.session_kernels import (FRONT_DIV, FRONT_MIN,
                                                  TS_MAX, TS_MIN, _rows,
                                                  sort_lanes)

#: sorts behind every key: a lane that holds no row
NO_KEY = (1 << 31) - 1
#: what a step counts, in the state as int64 scalars
COUNTERS = ("n_late", "n_opened", "n_closed", "n_unmatched", "n_displaced",
            "n_matched", "n_miss_build", "n_miss_interval", "n_miss_pred",
            "n_held", "n_overflow")


def join_out_capacity(capacity: int, asked: Optional[int] = None) -> int:
    """Lanes of the batch one join step hands on: the input batch's, or
    the fewer the operator was built with (``withOutputCapacity``: a
    deployment that knows how many build rows one batch can close sizes
    what it hands on by that, not by the batch).  The rows the watermark
    closes take those lanes and wait in the carry where they do not fit;
    a displaced row must leave in its own step, and a step with more of
    them than lanes stops the graph (``overflow``)."""
    return int(capacity) if asked is None else min(int(asked), int(capacity))


def make_join_state(row_spec, agg_spec, C: int):
    """The carry: ``C`` build rows that are open (or closed and held
    back), and the step's scalars."""
    rows = lambda spec: jax.tree.map(   # noqa: E731
        lambda s: jnp.zeros((C,) + s.shape, s.dtype), spec)
    state = {
        "open": jnp.zeros((C,), bool),
        "key": jnp.zeros((C,), jnp.int32),
        "start": jnp.zeros((C,), jnp.int64),
        "end": jnp.zeros((C,), jnp.int64),
        "row": rows(row_spec),          # the build row itself
        "agg": rows(agg_spec),          # its fold so far (where n > 0)
        "n": jnp.zeros((C,), jnp.int32),
        # newest (lateness-adjusted) watermark a step has acted on: a
        # row older than it is late
        "wm": jnp.full((), TS_MIN, jnp.int64),
    }
    state.update({c: jnp.zeros((), jnp.int64) for c in COUNTERS})
    return state


def _seg_scan_back(fold, ends, values):
    """Inclusive segmented scan from each lane to its segment's END
    (``ends`` marks the last lane of a segment): lane ``i`` holds
    ``fold(x_i, fold(x_i+1, ...))``, operands in lane order."""
    flip = lambda a: jnp.flip(a, 0)     # noqa: E731
    back = _seg_scan(lambda later, earlier: fold(earlier, later),
                     flip(ends), jax.tree.map(flip, values))
    return jax.tree.map(flip, back)


def _reads(fns, row_spec):
    """Which leaves of the build row and of the probe row the pair
    functions ``fns`` (each ``fn(build, probe, ts)``) read: two lists of
    bools in leaf order.  A leaf no function reads stays out of the
    sort (an operand more is ~20 s more of compile and a pass more over
    the lanes)."""
    from jax.extend.core import Literal
    n = len(jax.tree.leaves(row_spec))
    closed = jax.make_jaxpr(
        lambda b, p, ts: [f(b, p, ts) for f in fns])(
        row_spec, row_spec, jax.ShapeDtypeStruct((), jnp.int64))
    read = {v for eqn in closed.jaxpr.eqns for v in eqn.invars
            if not isinstance(v, Literal)}
    read.update(v for v in closed.jaxpr.outvars
                if not isinstance(v, Literal))
    flags = [v in read for v in closed.jaxpr.invars]
    return flags[:n], flags[n:2 * n]


def make_join_step(capacity: int, C: int, key_fn: Callable,
                   build_fn: Callable, length_fn: Callable,
                   match_fn: Optional[Callable], lift: Callable,
                   comb: Callable, out_capacity: Optional[int] = None):
    """Per-batch join program: ``step(state, payload, ts, valid, wm_adj)
    -> (state, out, fired, out_ts, held)``.  ``wm_adj`` is the
    lateness-adjusted watermark in event-time microseconds
    (:data:`TS_MIN` while there is none).  ``held`` is int64 ``[2]``: the
    closed rows this step left in the carry for want of room in the
    output, and the rows it LOST for want of room: in the carry, or
    displaced rows in an output of fewer lanes (``out_capacity``) than
    there were of them.  The end
    of stream is the same program on a batch with no valid lane under
    the watermark :data:`TS_MAX`, repeated while rows are held back:
    nothing compiles at the end of a stream."""
    B, C = int(capacity), int(C)
    N, OC = C + B, join_out_capacity(capacity, out_capacity)
    pair_fns = [lift]
    if match_fn is not None:
        pair_fns.append(lambda b, p, ts: match_fn(b, p))
    either = _flag_comb(comb)       # comb where both stand, else the one

    def fold(a, b):
        """``a`` before ``b`` in (key, time) order, either may be empty;
        the run's last lane says what stands behind the run."""
        has, val = either(a["has"], a["val"], b["has"], b["val"])
        return {"val": val, "has": has, "n": a["n"] + b["n"],
                "next_build": b["next_build"], "next_rel": b["next_rel"]}

    def step(state, payload, ts, valid, wm_adj):
        with phase("wf.fn"):
            keys = jax.vmap(key_fn)(payload).astype(jnp.int32)
            builds = jax.vmap(build_fn)(payload).astype(bool)
            length = jax.vmap(length_fn)(payload).astype(jnp.int64)
        ts = ts.astype(jnp.int64)
        ok = valid & (keys >= 0) & (keys < NO_KEY)
        late = ok & (ts < state["wm"])
        ok = ok & ~late
        wm_now = jnp.maximum(state["wm"], wm_adj)

        with phase("wf.join.sort"):
            # the carried rows in front of the batch's: all are build rows
            cat = lambda a, b: jnp.concatenate([a, b])   # noqa: E731
            live = cat(state["open"], ok)
            is_build = cat(state["open"], ok & builds)
            sid = jnp.where(live, cat(state["key"], keys), NO_KEY)
            at = cat(state["start"], ts)
            till = cat(state["end"], jnp.minimum(
                ts + jnp.clip(length, 0, TS_MAX), TS_MAX))
            t0 = jnp.min(jnp.where(live, at, jnp.int64(TS_MAX)))
            t0 = jnp.where(jnp.any(live), t0, jnp.int64(0))
            # event time less t0, twice, plus the side bit (a build row
            # before a probe of its microsecond), as two int32 keys: the
            # order holds whatever event time the lanes span
            rel2 = jnp.where(live, at - t0, 0) * 2 + (live & ~is_build)
            hi = (rel2 >> 31).astype(jnp.int32)
            lo = (rel2 & ((1 << 31) - 1)).astype(jnp.int32)
            # a build row's end less t0, twice, plus whether it carries a
            # fold already; a probe lane says so by -1
            had = jnp.pad(state["n"] > 0, (0, B))
            end2 = jnp.where(is_build, (till - t0) * 2 + had, -1)
            # of the row itself only the leaves match / lift read ride
            rows = jax.tree.map(cat, state["row"], payload)
            leaves, tree = jax.tree.flatten(rows)
            reads_b, reads_p = _reads(pair_fns, jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype), rows))
            (skey, shi, slo), ridden, _, order = sort_lanes(
                (sid, hi, lo),
                {"end2": end2, "row": [a for a, b, p in zip(
                    leaves, reads_b, reads_p) if b or p]})
        with phase("wf.join.match"):
            riding = iter(ridden["row"])
            s_leaves = [next(riding) if b or p else jnp.zeros_like(a)
                        for a, b, p in zip(leaves, reads_b, reads_p)]
            s_row = jax.tree.unflatten(tree, s_leaves)
            live = skey < NO_KEY
            is_build = live & (slo % 2 == 0)
            is_probe = live & ~is_build
            srel = ((shi.astype(jnp.int64) << 31)
                    | slo.astype(jnp.int64)) >> 1
            s_end = ridden["end2"] >> 1
            had = is_build & (ridden["end2"] % 2 == 1)
            kstart = jnp.concatenate(
                [jnp.array([True]), skey[1:] != skey[:-1]])
            seg = kstart | is_build
            # every lane of a run sees the build row that opens it
            head = _seg_scan(lambda first, _: first, seg, {
                "has": is_build, "end": s_end,
                "row": [a for a, b in zip(s_leaves, reads_b) if b]})
            heading = iter(head["row"])
            h_row = jax.tree.unflatten(
                tree, [next(heading) if b else jnp.zeros_like(a)
                       for a, b in zip(s_leaves, reads_b)])
            no_build = is_probe & ~head["has"]
            outside = is_probe & head["has"] & (srel >= head["end"])
            inside = is_probe & head["has"] & (srel < head["end"])
            when = t0 + srel
        with phase("wf.fn"):
            lifted = jax.vmap(lift)(h_row, s_row, when)
            fits = jax.vmap(match_fn)(h_row, s_row).astype(bool) \
                if match_fn is not None else jnp.ones((N,), bool)
        with phase("wf.join.match"):
            matched = inside & fits
            refused = inside & ~fits
            # what stands behind a run, said in its last lane: a newer
            # build row of the same key, and when
            send = jnp.concatenate([seg[1:], jnp.array([True])])
            runs = _seg_scan_back(fold, send, {
                "val": lifted, "has": matched,
                "n": matched.astype(jnp.int32),
                "next_build": jnp.concatenate(
                    [is_build[1:] & ~kstart[1:], jnp.array([False])]),
                "next_rel": jnp.concatenate(
                    [srel[1:], jnp.zeros((1,), jnp.int64)]),
            })
        with phase("wf.join.close"):
            wm_rel = jnp.maximum(wm_now - t0, -1)
            has = had | runs["has"]
            displaced = is_build & runs["next_build"] \
                & (runs["next_rel"] < s_end)
            ready = is_build & ~displaced & (s_end <= wm_rel)
            forced = displaced & has
            due = ready & has
            unmatched = (displaced | ready) & ~has
            keep = is_build & ~displaced & ~ready
            count = lambda m: jnp.sum(m, dtype=jnp.int32)   # noqa: E731
            n_due = count(forced) + count(due)
            n_out = jnp.minimum(n_due, OC)
            n_stay = n_due - n_out + count(keep)
            # closed rows first (those a step must emit before those the
            # watermark closed), then the rows that stay, in lane order
            cls = jnp.where(forced, 0, jnp.where(due, 1,
                                                 jnp.where(keep, 2, 3)))
            _, front = jax.lax.sort(
                (cls.astype(jnp.int32), jnp.arange(N, dtype=jnp.int32)),
                num_keys=1)

            def rows_at(src, hit):
                """The build rows in the lanes ``src`` (those ``hit``
                says; 0 elsewhere), whole: what the batch folded into
                them, after what the carry held."""
                take = lambda a: a[src]     # noqa: E731
                origin = take(order)
                was = jnp.minimum(origin, C - 1)        # its carry lane
                old = take(had)
                _, agg = either(
                    old, jax.tree.map(lambda a: a[was], state["agg"]),
                    take(runs["has"]), jax.tree.map(take, runs["val"]))
                rows = {"key": take(skey), "first": t0 + take(srel),
                        "last": t0 + take(s_end), "agg": agg,
                        "count": take(runs["n"])
                        + jnp.where(old, state["n"][was], 0),
                        "origin": origin}
                return jax.tree.map(
                    lambda a: jnp.where(_b(hit, a), a, 0), rows)

            def rows_into(W):
                """The output batch from the first ``W`` lanes of the
                order (the rows all lie there)."""
                def gather():
                    hit = jnp.arange(W, dtype=jnp.int32) < n_out
                    acc = dict(rows_at(front[:W], hit), fired=hit)
                    del acc["origin"]
                    return jax.tree.map(lambda a: jnp.pad(
                        a, [(0, OC - W)] + [(0, 0)] * (a.ndim - 1)), acc)
                return gather

            W = OC // FRONT_DIV
            if OC < FRONT_MIN:
                acc = rows_into(OC)()
            else:
                acc = jax.lax.cond(n_out <= W, rows_into(W), rows_into(OC))
            out, fired, out_ts = _rows(acc, 0)
            out["count"] = acc["count"]
        with phase("wf.join.carry"):
            stays = jnp.arange(C, dtype=jnp.int32) < n_stay
            kept = rows_at(jax.lax.dynamic_slice(front, (n_out,), (C,)),
                           stays)
            origin = kept["origin"]
            wide = lambda m: jnp.sum(m, dtype=jnp.int64)   # noqa: E731
            counts = {
                "n_late": wide(late), "n_opened": wide(ok & builds),
                "n_closed": n_out.astype(jnp.int64) + wide(unmatched),
                "n_unmatched": wide(unmatched),
                "n_displaced": wide(displaced),
                "n_matched": wide(matched),
                "n_miss_build": wide(no_build),
                "n_miss_interval": wide(outside),
                "n_miss_pred": wide(refused),
                "n_held": (n_due - n_out).astype(jnp.int64),
                "n_overflow": (jnp.maximum(n_stay - C, 0) + jnp.maximum(
                    count(forced) - OC, 0)).astype(jnp.int64),
            }
            new_state = {
                "open": stays, "key": kept["key"], "start": kept["first"],
                "end": kept["last"], "agg": kept["agg"], "n": kept["count"],
                # the build row itself, whole, from where it came in
                "row": jax.tree.map(
                    lambda a: jnp.where(_b(stays, a[origin]), a[origin], 0),
                    rows),
                "wm": wm_now,
            }
            new_state.update({c: state[c] + counts[c] for c in COUNTERS})
        return new_state, out, fired, out_ts, \
            jnp.stack([counts["n_held"], counts["n_overflow"]])

    return step


# ---------------------------------------------------------------------------
# the pair form: a row a matched pair, the build side retained by key
# ---------------------------------------------------------------------------

#: a table row that was never written: its time lies under every event's
NONE_HI = -(1 << 31)
#: lanes of the windows in which a pair step writes its build rows into
#: the table and looks its waiting probes up: the batch's, over this (a
#: scatter and a gather cost by the lane, hit or not; a step with more of
#: either than a window holds takes the whole batch's width instead)
TABLE_DIV = 8
#: what a pair step counts, in the state as int64 scalars
PAIR_COUNTERS = ("n_late", "n_built", "n_replaced", "n_matched",
                 "n_miss_build", "n_miss_interval", "n_miss_pred",
                 "n_waited", "n_held", "n_overflow")


def _words(t):
    """An int64 time as two int32 words (a 64-bit scatter costs 8x a
    32-bit one): ``t = hi * 2**31 + lo``, ``0 <= lo < 2**31``."""
    return ((t >> 31).astype(jnp.int32),
            (t & ((1 << 31) - 1)).astype(jnp.int32))


def _time(hi, lo):
    return (hi.astype(jnp.int64) << 31) | lo.astype(jnp.int64)


def retained(hi, lo, upper: int, wm):
    """Which table rows (time words ``hi`` / ``lo``) stand under the
    watermark ``wm``: written, and ``t + upper`` still over it.  The test
    that evicts: the step's lookups and the operator's gauge share it."""
    return (hi != NONE_HI) & (_time(hi, lo) + upper > wm)


def pair_reads(join_fn: Callable, match_fn: Optional[Callable], row_spec):
    """Which leaves of the build row and of the probe row ``join`` and
    ``match`` read (two lists of bools in leaf order): the table keeps,
    and the sorts carry, only those."""
    fns = [join_fn]
    if match_fn is not None:
        fns.append(lambda b, p, ts: match_fn(b, p))
    return _reads(fns, row_spec)


def make_join_pairs_state(row_spec, reads_b, reads_p, K: int, P: int,
                          HC: int):
    """The pair form's state: the retained build rows, keyed and dense
    over ``[0, K)`` (time as two int32 words and the leaves the pair
    functions read), ``P`` probes that wait for their build row (whole
    rows), ``HC`` pairs a full output batch held back, and the step's
    scalars."""
    leaves, _ = jax.tree.flatten(row_spec)
    col = lambda n: lambda s: jnp.zeros((n,) + s.shape, s.dtype)  # noqa: E731
    kept = lambda n, reads: [col(n)(s) for s, r in zip(leaves, reads)  # noqa: E731
                             if r]
    state = {
        "tab": {"hi": jnp.full((K,), NONE_HI, jnp.int32),
                "lo": jnp.zeros((K,), jnp.int32), "row": kept(K, reads_b)},
        "pend": {"live": jnp.zeros((P,), bool),
                 "key": jnp.zeros((P,), jnp.int32),
                 "at": jnp.zeros((P,), jnp.int64),
                 "row": jax.tree.map(col(P), row_spec)},
        "held": {"n": jnp.zeros((), jnp.int32),
                 "key": jnp.zeros((HC,), jnp.int32),
                 "t": jnp.zeros((HC,), jnp.int64),
                 "u": jnp.zeros((HC,), jnp.int64),
                 "b": kept(HC, reads_b), "p": kept(HC, reads_p)},
        "wm": jnp.full((), TS_MIN, jnp.int64),
        # most probes that ever waited at once
        "pend_max": jnp.zeros((), jnp.int64),
    }
    state.update({c: jnp.zeros((), jnp.int64) for c in PAIR_COUNTERS})
    return state


def make_join_pairs_step(capacity: int, K: int, P: int, key_fn: Callable,
                         build_fn: Callable, match_fn: Optional[Callable],
                         join_fn: Callable, lower: int, upper: int,
                         out_capacity: Optional[int] = None):
    """Per-batch program of the pair form: ``step(state, payload, ts,
    valid, wm_adj) -> (state, out, fired, out_ts, held)``, the signature
    of :func:`make_join_step`.  A build row at ``t`` is retained for the
    probes of its key with ``t - lower <= u < t + upper``; every such
    probe that ``match`` lets through leaves as one row, ``join(build,
    probe, u)``, in the step in which both are known.

    One step, over ``N = P + B`` lanes (the waiting probes in front of
    the batch):

    1. one sort by (key, event time, build before probe), the leaves the
       pair functions read riding, and one segmented scan that hands each
       run's build row down its lanes: a probe with a build row of its
       key at or before it IN ITS BATCH is tested against that one;
    2. one stable sort by class brings, in this order, the pairs so
       found, the probes still WAITING (no build row before them in the
       batch) and the batch's build rows to the front;
    3. the table: the build rows (the newest a key) are written into it,
       32-bit words only, then the waiting probes look their key up in
       it (an earlier step's row, or a later row of their own batch).
       Both touch a window of ``B // TABLE_DIV`` lanes where the rows
       fit, the whole width where not.  A row is looked up as live while
       ``t + upper`` lies over the watermark of the steps before: it is
       evicted by that test, not by a pass over the table;
    4. a waiting probe that found no live row stays in the state (the
       next ``P`` pending lanes) until the watermark reaches ``u +
       lower``, then it is a miss;
    5. the output is the front of the class order, ``join`` applied to
       its lanes: the pairs of (1), then those of (3) where they lie (a
       waiting probe that stayed unmatched leaves a hole).  Pairs that
       do not fit, or any while older ones are held back, go through
       the ``held`` lanes in order (one more sort, only then).

    ``held`` is int64 ``[5]``: pairs held back after this step; pairs
    LOST for want of held lanes; waiting probes lost for want of pending
    lanes; rows whose key lies outside ``[0, K)``; the event time of the
    oldest probe still waiting (:data:`TS_MAX` where none), which the
    watermark handed on may not pass."""
    B, K, P = int(capacity), int(K), int(P)
    LOWER, UPPER = int(lower), int(upper)
    N, OC = P + B, join_out_capacity(capacity, out_capacity)
    HC = OC
    W = min(max(B // TABLE_DIV, 1), N)
    iota_n = jnp.arange(N, dtype=jnp.int32)
    count = lambda m: jnp.sum(m, dtype=jnp.int32)   # noqa: E731
    wide = lambda m: jnp.sum(m, dtype=jnp.int64)   # noqa: E731

    def step(state, payload, ts, valid, wm_adj):
        with phase("wf.fn"):
            keys = jax.vmap(key_fn)(payload).astype(jnp.int32)
            builds = jax.vmap(build_fn)(payload).astype(bool)
        ts = ts.astype(jnp.int64)
        in_range = (keys >= 0) & (keys < K)
        ok = valid & in_range
        late = ok & (ts < state["wm"])
        ok = ok & ~late
        wm_before = state["wm"]
        wm_now = jnp.maximum(wm_before, wm_adj)
        tab, pend, held_rows = state["tab"], state["pend"], state["held"]

        with phase("wf.join.sort"):
            cat = lambda a, b: jnp.concatenate([a, b])   # noqa: E731
            live = cat(pend["live"], ok)
            is_build = jnp.pad(ok & builds, (P, 0))
            sid = jnp.where(live, cat(pend["key"], keys), NO_KEY)
            at = cat(pend["at"], ts)
            t0 = jnp.min(jnp.where(live, at, jnp.int64(TS_MAX)))
            t0 = jnp.where(jnp.any(live), t0, jnp.int64(0))
            rel2 = jnp.where(live, at - t0, 0) * 2 + (live & ~is_build)
            hi, lo = _words(rel2)
            rows = jax.tree.map(cat, pend["row"], payload)
            leaves, tree = jax.tree.flatten(rows)
            reads_b, reads_p = pair_reads(join_fn, match_fn, jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype), rows))
            rides = [b or p for b, p in zip(reads_b, reads_p)]
            (skey, shi, slo), ridden, _, order = sort_lanes(
                (sid, hi, lo), [a for a, r in zip(leaves, rides) if r])

        def whole(read, which, lanes):
            """A row tree of ``lanes`` lanes from the leaves ``read``,
            those ``which`` marks (0 where a leaf was not kept)."""
            it = iter(read)
            return jax.tree.unflatten(tree, [
                next(it) if w else jnp.zeros((lanes,) + a.shape[1:], a.dtype)
                for a, w in zip(leaves, which)])

        def of(read, have, want):
            """Of the leaves ``read`` (those ``have`` marks), the ones
            ``want`` marks."""
            it = iter(read)
            out = []
            for h, w in zip(have, want):
                a = next(it) if h else None
                if w:
                    out.append(a)
            return out

        with phase("wf.join.match"):
            live = skey < NO_KEY
            is_build = live & (slo % 2 == 0)
            is_probe = live & ~is_build
            srel = _time(shi, slo) >> 1
            kstart = jnp.concatenate(
                [jnp.array([True]), skey[1:] != skey[:-1]])
            seg = kstart | is_build
            own_b = of(ridden, rides, reads_b)
            head = _seg_scan(lambda first, _: first, seg, {
                "has": is_build, "rel": srel, "row": own_b})
            before = is_probe & head["has"]
            inside = before & (srel - head["rel"] < UPPER)
            waiting = is_probe & ~head["has"]
        with phase("wf.fn"):
            fits = jax.vmap(match_fn)(
                whole(head["row"], reads_b, N),
                whole(of(ridden, rides, reads_p), reads_p, N)).astype(bool) \
                if match_fn is not None else jnp.ones((N,), bool)
        with phase("wf.join.match"):
            matched1 = inside & fits
            n0, n1, n2 = count(matched1), count(waiting), count(is_build)
            # pairs, waiting probes, build rows, then the rest; stable,
            # so each class stays in (key, time) order
            cls = jnp.where(matched1, 0, jnp.where(
                waiting, 1, jnp.where(is_build, 2, 3))).astype(jnp.int32)
            _, c, _, _ = sort_lanes((cls,), {
                "key": skey, "rel": srel, "head_rel": head["rel"],
                "own": ridden, "head": head["row"], "origin": order})

        def table_pass(M):
            """Steps 3 and 4 over windows of ``M`` lanes of the class
            order (``M == N``: over all of it)."""
            def win(a, start):
                return a if M == N else jax.lax.dynamic_slice_in_dim(
                    a, start, M)

            def put(a, start, new, mask):
                if M == N:
                    return jnp.where(_b(mask, a), new, a)
                cur = jax.lax.dynamic_slice_in_dim(a, start, M)
                return jax.lax.dynamic_update_slice_in_dim(
                    a, jnp.where(_b(mask, cur), new, cur), start, 0)

            def lanes_of(off, n):
                start = jnp.clip(off, 0, N - M)
                lane = start + jnp.arange(M, dtype=jnp.int32)
                return start, (lane >= off) & (lane < off + n)

            def run():
                with phase("wf.join.table"):
                    # the batch's build rows, the newest of a key
                    sb, mb = lanes_of(n0 + n1, n2)
                    kb = win(c["key"], sb)
                    tb = t0 + win(c["rel"], sb)
                    newer = jnp.concatenate(
                        [mb[1:] & (kb[1:] == kb[:-1]), jnp.array([False])])
                    winner = mb & ~newer
                    at_b = jnp.where(mb, kb, 0)
                    old_hi = tab["hi"][at_b]
                    old_live = retained(old_hi, tab["lo"][at_b], UPPER,
                                        wm_before)
                    n_replaced = wide(winner & old_live) \
                        + wide(mb) - wide(winner)
                    idx = jnp.where(winner, kb, K)
                    hi_b, lo_b = _words(tb)
                    put_row = lambda t, a: t.at[idx].set(   # noqa: E731
                        a, mode="drop")
                    new_tab = {
                        "hi": put_row(tab["hi"], hi_b),
                        "lo": put_row(tab["lo"], lo_b),
                        "row": [put_row(t, win(a, sb)) for t, a in zip(
                            tab["row"], of(c["own"], rides, reads_b))]}
                    # the waiting probes, against the table as it now is
                    sw, mu = lanes_of(n0, n1)
                    ku = win(c["key"], sw)
                    u = t0 + win(c["rel"], sw)
                    at_u = jnp.where(mu, ku, 0)
                    g_hi, g_lo = new_tab["hi"][at_u], new_tab["lo"][at_u]
                    g_t = _time(g_hi, g_lo)
                    g_row = [t[at_u] for t in new_tab["row"]]
                    found = mu & retained(g_hi, g_lo, UPPER, wm_before)
                    d = u - g_t
                    inside2 = found & (d >= -LOWER) & (d < UPPER)
                with phase("wf.fn"):
                    fits2 = jax.vmap(match_fn)(
                        whole(g_row, reads_b, M), whole(
                            [win(a, sw) for a in of(c["own"], rides,
                                                    reads_p)],
                            reads_p, M)).astype(bool) \
                        if match_fn is not None else jnp.ones((M,), bool)
                with phase("wf.join.table"):
                    matched2 = inside2 & fits2
                    nobuild = mu & ~found
                    missed = nobuild & (wm_now >= u + LOWER)
                    pending = nobuild & ~missed
                    emit = put(iota_n < n0, sw, matched2, mu)
                    t_abs = put(t0 + c["head_rel"], sw, g_t, mu)
                    b_rows = [put(a, sw, g, mu)
                              for a, g in zip(c["head"], g_row)]
                with phase("wf.join.carry"):
                    # the probes that go on waiting, to the front
                    n_pend = count(pending)
                    _, pick = jax.lax.sort(
                        ((~pending).astype(jnp.int32),
                         jnp.arange(M, dtype=jnp.int32)), num_keys=1)
                    pick = jnp.pad(pick[:P], (0, max(P - M, 0)))
                    stays = jnp.arange(P, dtype=jnp.int32) < n_pend
                    origin = win(c["origin"], sw)[pick]
                    new_pend = {
                        "live": stays,
                        "key": jnp.where(stays, ku[pick], 0),
                        "at": jnp.where(stays, u[pick], 0),
                        "row": jax.tree.map(lambda a: jnp.where(
                            _b(stays, a[origin]), a[origin], 0), rows)}
                return new_tab, new_pend, emit, t_abs, b_rows, {
                    "n_replaced": n_replaced,
                    "n_matched": wide(matched2),
                    "n_miss_build": wide(missed),
                    "n_miss_interval": wide(found & ~inside2),
                    "n_miss_pred": wide(inside2 & ~fits2),
                    # probes of this batch that wait for a later one
                    "n_waited": wide(pending & (win(c["origin"], sw) >= P)),
                    "n_pend": n_pend.astype(jnp.int64),
                    "oldest": jnp.min(jnp.where(pending, u,
                                                jnp.int64(TS_MAX)))}
            return run

        if W == N:
            new_tab, new_pend, emit, t_abs, b_rows, seen = table_pass(N)()
        else:
            new_tab, new_pend, emit, t_abs, b_rows, seen = jax.lax.cond(
                (n1 <= W) & (n2 <= W), table_pass(W), table_pass(N))

        with phase("wf.join.close"):
            pairs = {"key": c["key"], "t": t_abs, "u": t0 + c["rel"],
                     "b": b_rows, "p": of(c["own"], rides, reads_p)}
            n_old = held_rows["n"]
            n_all = n_old + count(emit)
            n_out = jnp.minimum(n_all, OC)
            n_left = jnp.minimum(n_all - n_out, HC)
            old = {k: v for k, v in held_rows.items() if k != "n"}

            def front():
                # every pair lies in the first OC lanes of the order
                return (jax.tree.map(lambda a: a[:OC], pairs), emit[:OC],
                        old)

            def queued():
                # the pairs held back first, then this step's, in order
                flag = jnp.concatenate(
                    [jnp.arange(HC, dtype=jnp.int32) < n_old, emit])
                _, at = jax.lax.sort(
                    ((~flag).astype(jnp.int32),
                     jnp.arange(HC + N, dtype=jnp.int32)), num_keys=1)
                both = jax.tree.map(lambda h, a: jnp.concatenate([h, a]),
                                    old, pairs)
                out_at, held_at = at[:OC], at[OC:OC + HC]
                kept = jnp.arange(HC, dtype=jnp.int32) < n_left
                return (jax.tree.map(lambda a: a[out_at], both),
                        jnp.arange(OC, dtype=jnp.int32) < n_out,
                        jax.tree.map(lambda a: jnp.where(
                            _b(kept, a[held_at]), a[held_at], 0), both))

            rows_out, fired, new_held = jax.lax.cond(
                (n_old == 0) & (n0 + n1 <= OC), front, queued)
            new_held["n"] = n_left
        with phase("wf.fn"):
            value = jax.vmap(join_fn)(whole(rows_out["b"], reads_b, OC),
                                      whole(rows_out["p"], reads_p, OC),
                                      rows_out["u"])
        with phase("wf.join.close"):
            blank = lambda a: jnp.where(_b(fired, a), a, 0)  # noqa: E731
            out = jax.tree.map(blank, {
                "key": rows_out["key"], "build_ts": rows_out["t"],
                "probe_ts": rows_out["u"], "value": value})
            out_ts = blank(jnp.maximum(rows_out["t"], rows_out["u"]))
            counts = {
                "n_late": wide(late), "n_built": wide(ok & builds),
                "n_replaced": seen["n_replaced"],
                "n_matched": wide(matched1) + seen["n_matched"],
                "n_miss_build": seen["n_miss_build"],
                "n_miss_interval": wide(before & ~inside)
                + seen["n_miss_interval"],
                "n_miss_pred": wide(inside & ~fits) + seen["n_miss_pred"],
                "n_waited": seen["n_waited"],
                "n_held": n_left.astype(jnp.int64),
                "n_overflow": (n_all - n_out - n_left).astype(jnp.int64)
                + jnp.maximum(seen["n_pend"] - P, 0),
            }
            new_state = {"tab": new_tab, "pend": new_pend, "held": new_held,
                         "wm": wm_now,
                         "pend_max": jnp.maximum(state["pend_max"],
                                                 seen["n_pend"])}
            new_state.update({k: state[k] + counts[k]
                              for k in PAIR_COUNTERS})
        return new_state, out, fired, out_ts, jnp.stack([
            counts["n_held"], (n_all - n_out - n_left).astype(jnp.int64),
            jnp.maximum(seen["n_pend"] - P, 0),
            wide(valid & ~in_range), seen["oldest"]])

    return step
