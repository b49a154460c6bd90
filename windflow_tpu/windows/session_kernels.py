"""Session-window device programs (no operator-layer dependencies).

A session of a key is a maximal run of that key's tuples in which each
follows the last by LESS than ``gap`` of event time; its window is
``[first, last + gap)``.  The touching rule is Beam's: a tuple exactly
``gap`` after the previous one starts a new session (``[a, a + gap)`` and
``[a + gap, ...)`` do not overlap).

State is dense over a static key space ``[0, K)``: one OPEN session a key
(``open``, ``first``, ``last``, the aggregate leaves) plus scalars.  One
step, per fixed-capacity batch:

1. lanes are ordered by (key, event time) (any order inside a batch is
   fine), cut into runs where the key changes or the gap is reached, and
   each run is folded by ``lift`` / ``comb`` with one segmented scan;
2. per key, its first and last run reach the key domain through one
   32-bit scatter of lane indices each, then gathers (the compaction
   pattern of ``ffat_kernels.make_ffat_tb_step``): the first run is merged
   into the key's open session where their windows intersect, the last
   run becomes the key's open session, and every session so displaced
   (the old one, a first run that is not the last, the runs between)
   closes at once;
3. every open session with ``last + gap <= watermark`` closes.

Closed rows are compacted into an output batch of
:func:`session_out_capacity` lanes.  The displaced sessions of step 2 are
at most one a run, so at most one a lane: they always fit.  The sessions
of step 3 take what room is left, in key order; the rest are HELD BACK
exactly where they are, in the state, still ready: a later step (or the
end-of-stream flush) emits them, and no tuple that is not late can reach
them (a ready session ends at or before the watermark).  The number held
back leaves the step as its own scalar.

64-bit scatters cost 10-13x a 32-bit one on a v5e (``PERF.md`` section 6,
PR 27 / 29 / 31): every scatter here moves int32 lane or row indices, and
the values follow by gather.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from windflow_tpu.monitoring.recorder import phase
from windflow_tpu.windows.ffat_kernels import _b, _seg_scan

#: "no watermark yet" / "older than any event" in event-time microseconds
TS_MIN = -(1 << 60)
#: the watermark of the end of stream
TS_MAX = 1 << 60


def session_out_capacity(capacity: int, K: int) -> int:
    """Lanes of the batch one session step hands on.  A session that a
    step MUST emit (its key's slot is taken by a later run) is followed
    by a run of the same batch, so there are at most as many as lanes:
    ``capacity`` always holds them, whatever the gap and the batch's
    span.  Sessions the watermark closes (at most ``K`` at once, after
    an idle stretch of event time) share the same lanes and wait in the
    state where they do not fit."""
    del K       # the watermark's closes are held back, not sized for
    return int(capacity)


def make_session_state(agg_spec, K: int):
    """Dense per-key session state over ``[0, K)``."""
    return {
        "open": jnp.zeros((K,), bool),
        "first": jnp.zeros((K,), jnp.int64),    # event time of the first
        "last": jnp.zeros((K,), jnp.int64),     # ... and the newest tuple
        "agg": jax.tree.map(
            lambda s: jnp.zeros((K,) + s.shape, s.dtype), agg_spec),
        # newest (lateness-adjusted) watermark a step has acted on: a
        # tuple older than it is late
        "wm": jnp.full((), TS_MIN, jnp.int64),
        "n_late": jnp.zeros((), jnp.int64),     # late tuples dropped
        "n_closed": jnp.zeros((), jnp.int64),   # rows emitted
        # sessions closed by a later tuple of their key before the
        # watermark passed their end (0 on a stream stamped in order)
        "n_early": jnp.zeros((), jnp.int64),
        # ready rows left in the state by a full output batch, summed
        # over the steps that left them
        "n_held": jnp.zeros((), jnp.int64),
    }


#: the rows of an output batch lie at its front, so a step whose rows
#: fit into the first ``OC // FRONT_DIV`` lanes gathers only those (a
#: gather costs by the lane, hit or not: 2-7 ms over 262144 lanes on a
#: v5e, and a session step closes a few thousand rows a batch); output
#: batches under ``FRONT_MIN`` lanes are gathered whole
FRONT_DIV = 16
FRONT_MIN = 1024


def sort_lanes(keys, riders):
    """Lanes sorted by ``keys`` (a tuple of ``[n]`` arrays, the most
    significant first) with the leaves of ``riders`` brought along:
    ``(sorted keys, sorted riders, iota, order)``, ``iota`` the lane
    numbers ``0 .. n - 1`` and ``order`` the lane each sorted lane came
    from.  A lane of scalars rides the sort as one more
    operand (the sort is 0.3 ms over 262144 lanes on a v5e, a gather by
    its permutation 2 ms a 32-bit lane); wider leaves follow by gather."""
    iota = jnp.arange(keys[0].shape[0], dtype=jnp.int32)
    leaves, tree = jax.tree.flatten(riders)
    rides = [a.ndim == 1 for a in leaves]
    done = jax.lax.sort(
        (*keys, *(a for a, r in zip(leaves, rides) if r), iota),
        num_keys=len(keys))
    order = done[-1]
    riding = iter(done[len(keys):-1])
    return done[:len(keys)], jax.tree.unflatten(
        tree, [next(riding) if r else a[order]
               for a, r in zip(leaves, rides)]), iota, order


def _spread(src, n: int):
    """Gather indices for ``src`` (-1 = no row): a lane without a row
    reads the element of its own position, not all of them element 0."""
    lane = jnp.arange(src.shape[0], dtype=jnp.int32)
    return jnp.where(src >= 0, src, lane % n)


def _close_ready(open_, first, last, agg, wm_adj, gap: int, acc, OC: int):
    """Close the open sessions the watermark has passed, the first that
    fit behind the ``acc["n"]`` rows already in the output, in key order.
    Returns ``(open, acc, n_held)``."""
    K = open_.shape[0]
    ready = open_ & (last + gap <= wm_adj)
    pos = acc["n"] + jnp.cumsum(ready.astype(jnp.int32)) - 1
    emit = ready & (pos < OC)
    n_rows = acc["n"] + jnp.sum(emit, dtype=jnp.int32)

    def rows_into(W):
        """The new rows gathered into the first ``W`` lanes (they all
        lie there) and laid over the rows ``acc`` holds."""
        def gather():
            # ONE 32-bit scatter: which key each output lane takes
            src = jnp.full((W,), -1, jnp.int32) \
                .at[jnp.where(emit, pos, W)] \
                .set(jnp.arange(K, dtype=jnp.int32), mode="drop")
            at = _spread(src, K)
            wide = lambda a: jnp.pad(   # noqa: E731
                a, [(0, OC - W)] + [(0, 0)] * (a.ndim - 1))
            hit = wide(src >= 0)
            take = lambda a, old: jnp.where(   # noqa: E731
                _b(hit, old), wide(a[at]), old)
            return {
                "key": jnp.where(hit, wide(src), acc["key"]),
                "first": take(first, acc["first"]),
                "last": take(last, acc["last"]),
                "agg": jax.tree.map(take, agg, acc["agg"]),
                "fired": acc["fired"] | hit,
                "n": n_rows,
            }
        return gather

    W = OC // FRONT_DIV
    if OC < FRONT_MIN:
        acc = rows_into(OC)()
    else:
        acc = jax.lax.cond(n_rows <= W, rows_into(W), rows_into(OC))
    return open_ & ~emit, acc, jnp.sum(ready & ~emit, dtype=jnp.int64)


def _empty_acc(agg, OC: int):
    return {
        "key": jnp.zeros((OC,), jnp.int32),
        "first": jnp.zeros((OC,), jnp.int64),
        "last": jnp.zeros((OC,), jnp.int64),
        "agg": jax.tree.map(
            lambda a: jnp.zeros((OC,) + a.shape[1:], a.dtype), agg),
        "fired": jnp.zeros((OC,), bool),
        "n": jnp.zeros((), jnp.int32),
    }


def _rows(acc, gap: int):
    """The output batch of a step or a flush: ``(out, fired, out_ts)``.
    A row's timestamp is its window's last microsecond."""
    end = acc["last"] + gap
    out = {"key": acc["key"], "start": acc["first"], "end": end,
           "value": acc["agg"]}
    return out, acc["fired"], end - 1


def make_session_step(capacity: int, K: int, gap: int, lift: Callable,
                      comb: Callable, key_fn: Optional[Callable]):
    """Per-batch session program: ``step(state, payload, ts, valid,
    wm_adj) -> (state, out, fired, out_ts, n_held)``.  ``wm_adj`` is the
    lateness-adjusted watermark in event-time microseconds
    (:data:`TS_MIN` while there is none); ``n_held`` counts the ready
    sessions this step left in the state for want of room.  The end of
    stream is the same program on a batch with no valid lane under the
    watermark :data:`TS_MAX`, repeated while ``n_held`` is not 0 (as the
    time window flushes): nothing compiles at the end of a stream."""
    B = int(capacity)
    OC = session_out_capacity(B, K)
    GAP = int(gap)

    def fold(a, b):
        # a run keeps its first lane's time and whether that lane opened
        # its key; the aggregates fold
        return {"first": a["first"], "k0": a["k0"],
                "agg": comb(a["agg"], b["agg"])}

    def lanes(sid, rel, lifted, t0):
        """Sort, cut, fold; returns the key domain's view (per key: has
        it a run, its first and its last run) and the runs in between.
        ``rel`` is event time less ``t0``, int32 where the batch's span
        allows and int64 where not."""
        gap_c = jnp.asarray(min(GAP, int(jnp.iinfo(rel.dtype).max)),
                            rel.dtype)
        skey, srel, slift, iota, live = ordered(sid, rel, lifted)
        kstart, rstart, run = runs(skey, srel, slift, gap_c)
        return carried(skey, srel, iota, live, kstart, rstart, run, t0)

    @phase("wf.session.sort")
    def ordered(sid, rel, lifted):
        (skey, srel), slift, iota, _ = sort_lanes((sid, rel), lifted)
        return skey, srel, slift, iota, skey < K

    @phase("wf.session.scan")
    def runs(skey, srel, slift, gap_c):
        kstart = jnp.concatenate([jnp.array([True]), skey[1:] != skey[:-1]])
        # same key: sorted by time, so the difference is >= 0 and in range
        rstart = kstart | jnp.concatenate(
            [jnp.array([False]), srel[1:] - srel[:-1] >= gap_c])
        run = _seg_scan(fold, rstart,
                        {"first": srel, "k0": kstart, "agg": slift})
        return kstart, rstart, run

    @phase("wf.session.carry")
    def carried(skey, srel, iota, live, kstart, rstart, run, t0):
        kend = jnp.concatenate([kstart[1:], jnp.array([True])])
        rend = jnp.concatenate([rstart[1:], jnp.array([True])])
        some_cut = jnp.any(rstart & ~kstart & live)

        def index_of(mask):
            # 32-bit scatter of lane indices: key -> the lane that ends
            # the run wanted (one a key)
            return jnp.full((K,), -1, jnp.int32) \
                .at[jnp.where(mask & live, skey, K)].set(iota, mode="drop")

        def pick(mask):
            """The run that ends in the lane ``mask`` names for each key:
            ``(lane or -1, run)``."""
            src = index_of(mask)
            at = _spread(src, B)
            return src, {"first": t0 + run["first"][at].astype(jnp.int64),
                         "last": t0 + srel[at].astype(jnp.int64),
                         "agg": jax.tree.map(lambda a: a[at], run["agg"])}

        src_l, last_run = pick(kend)
        # no run is cut inside the batch (the usual case: a batch spans
        # less than the gap): a key's first run is its last
        src_f, first_run = jax.lax.cond(
            some_cut, lambda: pick(rend & run["k0"]),
            lambda: (src_l, last_run))
        between = {
            "flag": rend & ~run["k0"] & ~kend & live,
            "key": skey,
            "first": t0 + run["first"].astype(jnp.int64),
            "last": t0 + srel.astype(jnp.int64),
            "agg": run["agg"],
        }
        return src_l >= 0, src_f != src_l, first_run, last_run, between

    def step(state, payload, ts, valid, wm_adj):
        with phase("wf.fn"):
            keys = jax.vmap(key_fn)(payload).astype(jnp.int32) \
                if key_fn is not None else jnp.zeros(B, jnp.int32)
        ts = ts.astype(jnp.int64)
        ok = valid & (keys >= 0) & (keys < K)
        late = ok & (ts < state["wm"])
        ok = ok & ~late
        with phase("wf.fn"):
            lifted = jax.vmap(lift)(payload)

        t0 = jnp.min(jnp.where(ok, ts, jnp.int64(TS_MAX)))
        t0 = jnp.where(jnp.any(ok), t0, jnp.int64(0))
        rel = jnp.where(ok, ts - t0, 0)
        sid = jnp.where(ok, keys, K)
        narrow = jnp.max(rel) < (1 << 31) - 1
        has, multi, f_run, l_run, between = jax.lax.cond(
            narrow,
            lambda: lanes(sid, rel.astype(jnp.int32), lifted, t0),
            lambda: lanes(sid, rel, lifted, t0))
        with phase("wf.session.carry"):
            multi = has & multi

            open_, first, last, agg = (state["open"], state["first"],
                                       state["last"], state["agg"])
            # the first run joins the open session where their windows
            # intersect (it may also lie before it, inside the lateness)
            overlap = open_ & has & (f_run["first"] < last + GAP) \
                & (first < f_run["last"] + GAP)
            m_first = jnp.where(overlap, jnp.minimum(first, f_run["first"]),
                                first)
            m_last = jnp.where(overlap, jnp.maximum(last, f_run["last"]), last)
            m_agg = jax.tree.map(
                lambda both, old: jnp.where(_b(overlap, both), both, old),
                comb(agg, f_run["agg"]), agg)
            # displaced: the old session (with the first run, where joined)
            # by a later run, and a first run that stands alone by a later one
            take_l = has & (multi | ~overlap)   # the last run takes the slot
            x_old = open_ & take_l
            y_first = has & multi & ~overlap
            new_open = open_ | has
            new_first = jnp.where(take_l, l_run["first"], m_first)
            new_last = jnp.where(take_l, l_run["last"], m_last)
            new_agg = jax.tree.map(
                lambda l, m: jnp.where(_b(take_l, l), l, m), l_run["agg"],
                m_agg)

            n_forced = jnp.sum(x_old, dtype=jnp.int32) \
                + jnp.sum(y_first, dtype=jnp.int32) \
                + jnp.sum(between["flag"], dtype=jnp.int32)
            early = lambda flag, end: jnp.sum(   # noqa: E731
                flag & (end + GAP > wm_adj), dtype=jnp.int64)
            n_early = early(x_old, m_last) + early(y_first, f_run["last"]) \
                + early(between["flag"], between["last"])

        with phase("wf.session.close"):
            def displaced(_):
                cat = lambda *a: jnp.concatenate(a)   # noqa: E731
                flag = cat(x_old, y_first, between["flag"])
                rows = jnp.arange(K, dtype=jnp.int32)
                c_key = cat(rows, rows, between["key"])
                c_first = cat(m_first, f_run["first"], between["first"])
                c_last = cat(m_last, f_run["last"], between["last"])
                c_agg = jax.tree.map(cat, m_agg, f_run["agg"], between["agg"])
                n = flag.shape[0]
                pos = jnp.cumsum(flag.astype(jnp.int32)) - 1
                src = jnp.full((OC,), -1, jnp.int32) \
                    .at[jnp.where(flag, pos, OC)] \
                    .set(jnp.arange(n, dtype=jnp.int32), mode="drop")
                hit = src >= 0
                at = _spread(src, n)
                return {
                    "key": jnp.where(hit, c_key[at], 0),
                    "first": jnp.where(hit, c_first[at], 0),
                    "last": jnp.where(hit, c_last[at], 0),
                    "agg": jax.tree.map(
                        lambda a: jnp.where(_b(hit, a[at]), a[at], 0), c_agg),
                    "fired": hit,
                    "n": n_forced,          # one a run at most: <= B = OC
                }

            acc = jax.lax.cond(n_forced > 0, displaced,
                               lambda _: _empty_acc(agg, OC), None)
            new_open, acc, n_held = _close_ready(
                new_open, new_first, new_last, new_agg, wm_adj, GAP, acc, OC)

            new_state = {
                "open": new_open, "first": new_first, "last": new_last,
                "agg": new_agg,
                "wm": jnp.maximum(state["wm"], wm_adj),
                "n_late": state["n_late"] + jnp.sum(late, dtype=jnp.int64),
                "n_closed": state["n_closed"] + acc["n"].astype(jnp.int64),
                "n_early": state["n_early"] + n_early,
                "n_held": state["n_held"] + n_held,
            }
            out, fired, out_ts = _rows(acc, GAP)
        return new_state, out, fired, out_ts, n_held

    return step
