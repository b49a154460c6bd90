"""Rolling keyed aggregate with declared leaves: the device program (no
operator-layer dependencies).

An aggregate that never closes (SQL's ``GROUP BY`` without a window): a
group's state lives across batches, dense over ``[0, K)``, and every
step hands on one UPSERT row for each group its batch touched, holding
the group's leaves after the batch's last record of it.

A leaf is declared, not a closure over state.  ``lift(record, ts)`` (the
record and its event time, int64 usec) gives a dict ``{leaf: value}`` and
each leaf says what folds it:

* a **plain** leaf, one of the monoids ``ffat_kernels`` knows (``sum``,
  ``min``, ``max``).  An integer ``sum`` is kept as int64 whatever width
  it was lifted at (a count of ones lifted as int32 costs one sort
  operand, and a group's total does not wrap): only ONE batch's partial
  must fit the lifted width;
* a **distinct** leaf: the lift gives a member id in ``[0, space)`` or a
  negative number where the record adds none (SQL's ``FILTER``), and the
  leaf's value is how many different members its group has seen.  Exact:
  every group holds a bit a member.

**The sets.**  The distinct leaves of one :class:`DistinctGroup` are
filters of ONE member (q16's ``count(DISTINCT bidder)`` and the same
``FILTER (WHERE price < 10000)``): in one record all that give a member
give the same one.  They share a table: a member takes ``b`` adjacent
bits of a 32-bit word (``b`` = the leaves rounded up to a power of two),
a bit a leaf, so a record tests and sets all its leaves of a group with
one read and one write.  A table is flat ``uint32 [K * ceil(space /
(32 / b))]``, a group's words adjacent, donated to the step and updated
where it lies.

One step, per fixed-capacity batch of ``B`` lanes:

1. ``wf.fn``: key, lift; a lane's word address and bits for every group.
2. ``wf.agg.sort``: one sort a group by word address (so by key), the
   bits riding; the plain leaves ride the first.  A batch's duplicates of
   one member, and its members that share a word, fall into one run.
3. ``wf.agg.distinct``: a word is read once and written once a RUN.
   The bits of a run are OR-ed down it (a segmented scan), so a run's
   last lane holds the run's whole OR; a second sort a group brings
   those lanes to the front, still in address order (a run-end's
   address is its own).  Their words are gathered a chunk of
   :func:`chunk_lanes` at a time, as many chunks as the batch has runs
   (a loop on the device): a gather costs by the index, so a skewed
   batch pays for its words and not for its lanes.  The bits that are
   new are kept a run, and the words go back, OR-ed with their runs'
   bits, in ONE scatter a table over all the compacted lanes (those
   past the runs one past the table, dropped): a scatter pays a pass
   over its whole table each time it is called, and little by the
   index (``PERF.md`` section 6, PR 52).  Both accesses go by the
   sort's own order, which XLA is told (``indices_are_sorted``): a
   scatter it does not know to be sorted costs twice one it knows.
4. ``wf.agg.fold``: the plain leaves folded down each key's run; the new
   members summed down the RUNS, and a key's read off at the rank its
   last lane has among its group's run-ends; at the last lane of each
   key the group's state is read, folded and written (32-bit words: an
   8-byte leaf as two).
5. ``wf.agg.rows``: those last lanes compacted to the front of the
   output batch (``OC`` lanes): one row a group.  A step that touched
   more groups than ``OC`` says so (the operator stops the graph).

Every gather and scatter moves 32-bit words (a 64-bit one costs ten on
a v5e: ``PERF.md`` section 6, PR 44).
"""

from __future__ import annotations

from typing import Callable, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from windflow_tpu.monitoring.recorder import phase
from windflow_tpu.windows.ffat_kernels import (_MONOID_OPS, _monoid_identity,
                                               resolve_monoid)

#: what a step counts, in the state as int64 scalars
COUNTERS = ("n_rows", "n_tested", "n_new", "n_key_refused",
            "n_member_refused", "n_overflow", "n_words")
#: a dead lane's sort key: behind every word of every table
DEAD = np.int32(np.iinfo(np.int32).max)


class DistinctGroup(NamedTuple):
    """Distinct leaves that are filters of one member and share a table."""
    leaves: Tuple[str, ...]
    space: int

    @property
    def bits(self) -> int:
        """Bits a member takes in a word: a bit a leaf, a power of two."""
        return 1 << max(len(self.leaves) - 1, 0).bit_length()

    @property
    def members_per_word(self) -> int:
        return 32 // self.bits

    @property
    def words_per_key(self) -> int:
        return -(-self.space // self.members_per_word)

    @property
    def lowest_bits(self) -> int:
        """A word with the lowest bit of every member set."""
        return sum(1 << (i * self.bits)
                   for i in range(self.members_per_word))


def check_plan(plain: dict, groups: Sequence[DistinctGroup], K: int,
               label: str = "rolling aggregate") -> None:
    """Raise ``ValueError`` where the declarations cannot be built."""
    names = list(plain) + [n for g in groups for n in g.leaves]
    if not names:
        raise ValueError(f"{label}: no leaf declared")
    if len(set(names)) != len(names) or "key" in names:
        raise ValueError(
            f"{label}: leaf names must differ from each other and from "
            f"'key': {names}")
    for kind in plain.values():
        if resolve_monoid(False, kind) is None:
            raise ValueError(f"{label}: a plain leaf needs a monoid")
    for g in groups:
        if not 1 <= len(g.leaves) <= 32:
            raise ValueError(
                f"{label}: a distinct group has 1 to 32 leaves (a bit "
                f"each of a 32-bit word), got {len(g.leaves)}")
        if g.space < 1:
            raise ValueError(f"{label}: a distinct leaf needs space >= 1")
        if K * g.words_per_key >= DEAD:
            raise ValueError(
                f"{label}: {K} keys x {g.words_per_key} words of the "
                f"distinct leaves {g.leaves} is {K * g.words_per_key} "
                "words; a table is addressed in 31 bits")


def state_dtype(kind: str, dtype):
    """What a plain leaf lifted as ``dtype`` is kept as."""
    dt = jnp.dtype(dtype)
    if kind == "sum" and jnp.issubdtype(dt, jnp.integer):
        return jnp.dtype(jnp.int64)
    return dt


def _n_words(dtype) -> int:
    size = jnp.dtype(dtype).itemsize
    if size not in (4, 8):
        raise ValueError(
            f"a rolling aggregate keeps 4- and 8-byte leaves, not {dtype}")
    return size // 4


def _to_words(a):
    """``[n]`` of a 4- or 8-byte dtype -> its uint32 words, low first."""
    if a.dtype.itemsize == 4:
        return [jax.lax.bitcast_convert_type(a, jnp.uint32)]
    u = jax.lax.bitcast_convert_type(a, jnp.uint64)
    return [(u & 0xFFFFFFFF).astype(jnp.uint32),
            (u >> 32).astype(jnp.uint32)]


def _from_words(words, dtype):
    dtype = jnp.dtype(dtype)
    if dtype.itemsize == 4:
        return jax.lax.bitcast_convert_type(words[0], dtype)
    u = words[0].astype(jnp.uint64) | (words[1].astype(jnp.uint64) << 32)
    return jax.lax.bitcast_convert_type(u, dtype)


def out_capacity(capacity: int, K: int, declared: Optional[int]) -> int:
    """Lanes of the batch one step hands on: ``withOutputCapacity``, else
    a row for every group a batch can touch, rounded up to a power of
    two."""
    if declared is not None:
        return int(declared)
    return 1 << max(min(int(K), int(capacity)) - 1, 0).bit_length()


def chunk_lanes(capacity: int) -> int:
    """Lanes of one table gather: a step reads a group's runs this many
    at a time, ``ceil(runs / chunk_lanes)`` times (a gather costs by the
    index, ``PERF.md`` section 6, PR 52: a skewed batch pays for its
    words)."""
    return max(int(capacity) // 8, 1)


def make_rolling_state(lift_spec: dict, plain: dict,
                       groups: Sequence[DistinctGroup], K: int):
    """``lift_spec``: one lifted record (shape/dtype of every leaf)."""
    words = []
    for name, kind in plain.items():
        dt = state_dtype(kind, lift_spec[name].dtype)
        ident = jnp.broadcast_to(_monoid_identity(kind, dt), (K,))
        words += _to_words(ident)
    state = {
        # a group's plain leaves as 32-bit words, a key a column
        "plain": jnp.stack(words) if words
        else jnp.zeros((0, K), jnp.uint32),
        # how many members a group has seen, a row a distinct leaf
        "count": jnp.zeros((sum(len(g.leaves) for g in groups), K),
                           jnp.int32),
        "sets": [jnp.zeros((K * g.words_per_key,), jnp.uint32)
                 for g in groups],
    }
    state.update({c: jnp.zeros((), jnp.int64) for c in COUNTERS})
    return state


def _before(a, fill):
    """Each lane's left neighbour (``fill`` before the first)."""
    return jnp.concatenate([jnp.full((1,), fill, a.dtype), a[:-1]])


def _after(a, fill):
    return jnp.concatenate([a[1:], jnp.full((1,), fill, a.dtype)])


def make_rolling_step(capacity: int, K: int, lift: Callable, plain: dict,
                      groups: Sequence[DistinctGroup],
                      key_fn: Optional[Callable],
                      out_lanes: Optional[int] = None):
    """Per-batch program: ``step(state, payload, ts, valid, wm_adj) ->
    (state, out, fired, out_ts, held)``.  ``out`` is ``{"key", leaf:
    value, ...}`` over :func:`out_capacity` lanes, the rows at the front
    in key order, each stamped with the batch's newest event time;
    ``held`` is int64 ``[2]``: always 0 (nothing waits), and the groups
    the batch touched beyond the output's lanes."""
    B, K = int(capacity), int(K)
    OC = out_capacity(B, K, out_lanes)
    groups = list(groups)
    plain_names = list(plain)
    combs = {n: _MONOID_OPS[plain[n]][1] for n in plain_names}
    # the sort that brings the plain leaves into key order: the first
    # group's (a word address sorts by key too), else one by key alone
    wpk0 = groups[0].words_per_key if groups else 1
    # the compacted run-ends are read C lanes at a time
    C = chunk_lanes(B)
    pad = -B % C

    def lanes(payload, ts, valid):
        with phase("wf.fn"):
            keys = jax.vmap(key_fn)(payload).astype(jnp.int32) \
                if key_fn is not None else jnp.zeros(B, jnp.int32)
            lifted = jax.vmap(lift)(payload, ts)
            inside = (keys >= 0) & (keys < K)
            live = valid & inside
            addrs, bits, tested, refused = [], [], 0, 0
            for g in groups:
                ids = [lifted[n].astype(jnp.int32) for n in g.leaves]
                m = ids[0]
                for i in ids[1:]:
                    m = jnp.maximum(m, i)
                fits = live & (m >= 0) & (m < g.space)
                f = jnp.zeros(B, jnp.uint32)
                for j, i in enumerate(ids):
                    gives = live & (i >= 0)
                    takes = gives & fits & (i == m)
                    f = f | (takes.astype(jnp.uint32) << j)
                    tested = tested + jnp.sum(takes, dtype=jnp.int64)
                    refused = refused + jnp.sum(gives & ~takes,
                                                dtype=jnp.int64)
                per = g.members_per_word
                word = jnp.where(fits, m // per, 0)
                shift = (jnp.where(fits, m % per, 0)
                         * g.bits).astype(jnp.uint32)
                addrs.append(jnp.where(
                    live, keys * g.words_per_key + word, DEAD))
                bits.append(f << shift)
            if not groups:
                addrs.append(jnp.where(live, keys, DEAD))
            riders = [lifted[n] for n in plain_names]
            n_key_refused = jnp.sum(valid & ~inside, dtype=jnp.int64)
        return addrs, bits, riders, live, tested, refused, n_key_refused

    def or_down_runs(first, bits):
        """Each lane: the OR of its run's bits up to it."""
        def op(a, b):
            fa, va = a
            fb, vb = b
            return fa | fb, jnp.where(fb, vb, va | vb)
        return jax.lax.associative_scan(op, (first, bits))[1]

    def fold_down_runs(first, riders):
        """Each lane: its key's plain leaves folded up to it."""
        if not riders:
            return []

        def op(a, b):
            fa, va = a
            fb, vb = b
            return fa | fb, [jnp.where(fb, y, combs[n](x, y))
                             for n, x, y in zip(plain_names, va, vb)]
        return jax.lax.associative_scan(op, (first, riders))[1]

    def test_and_set(g: DistinctGroup, table, saddr, sbits):
        """One group's sorted lanes against its table, a read and a
        write a RUN.  Returns the table; each lane's rank among the
        run-ends (how many runs end at or before it); a leaf, each
        run's count of new members, the runs at the front in address
        order (a lane of rank ``r`` closes the ``r`` first of them).
        A lane past the runs (all at the back of the compaction) reads
        nothing and writes one past the table: the indices are the
        sort's own order."""
        size = table.shape[0]
        first = saddr != _before(saddr, -1)
        ends = (saddr != _after(saddr, -1)) & (saddr != DEAD)
        run = or_down_runs(first, sbits)
        rank = jnp.cumsum(ends, dtype=jnp.int32)
        # a lane that ends no run goes to the back and carries no bits
        caddr, cbits = jax.lax.sort(
            (jnp.where(ends, saddr, DEAD),
             jnp.where(ends, run, jnp.uint32(0))), num_keys=1)
        at = jnp.minimum(jnp.pad(caddr, (0, pad), constant_values=DEAD),
                         size)
        cbits = jnp.pad(cbits, (0, pad))

        def read(i, old):
            words = table.at[jax.lax.dynamic_slice(at, (i * C,), (C,))].get(
                mode="fill", fill_value=0, indices_are_sorted=True)
            return jax.lax.dynamic_update_slice(old, words, (i * C,))

        # a gather costs by the index: as many chunks as there are runs
        old = jax.lax.fori_loop(0, (rank[-1] + (C - 1)) // C, read,
                                jnp.zeros_like(cbits))
        # a scatter pays a pass over its whole table however few its
        # indices: ONE, and a run with no new bit writes back what it
        # read (a hole punched into the index would hide its order)
        table = table.at[at].set(old | cbits, mode="drop",
                                 indices_are_sorted=True)
        new = cbits & ~old
        low = jnp.uint32(g.lowest_bits)
        return table, rank, [
            jax.lax.population_count((new >> j) & low).astype(jnp.int32)
            for j in range(len(g.leaves))]

    def step(state, payload, ts, valid, wm_adj):
        del wm_adj      # nothing waits for a watermark
        ts = ts.astype(jnp.int64)
        addrs, bits, riders, live, tested, refused, n_key_refused = \
            lanes(payload, ts, valid)
        with phase("wf.agg.sort"):
            # the first sort's operands: the address, the first group's
            # bits where there is a group, then the plain leaves
            n_ahead = 1 + bool(groups)
            done = [jax.lax.sort((addrs[0], *bits[:1], *riders),
                                 num_keys=1)]
            done += [jax.lax.sort((a, b), num_keys=1)
                     for a, b in zip(addrs[1:], bits[1:])]
        with phase("wf.agg.distinct"):
            sets, ranks, fresh = [], [], []
            for g, table, d in zip(groups, state["sets"], done):
                table, rank, new = test_and_set(g, table, d[0], d[1])
                sets.append(table)
                ranks.append(rank)
                fresh.append(new)
        with phase("wf.agg.fold"):
            skey = done[0][0] // wpk0 if wpk0 > 1 else done[0][0]
            touched = skey < K                  # a dead lane's is not
            kfirst = skey != _before(skey, -1)
            klast = touched & (skey != _after(skey, -1))
            folded = fold_down_runs(kfirst, list(done[0][n_ahead:]))
            # every sort holds a key's lanes in the same places: a key's
            # last lane has, a group, the rank of the key's last run,
            # and the new members up to that run are a sum down the runs
            running = [jnp.stack([jnp.cumsum(c) for c in new])
                       for new in fresh]
            words = [jax.lax.bitcast_convert_type(skey, jnp.uint32)]
            words += [jax.lax.bitcast_convert_type(r, jnp.uint32)
                      for r in ranks]
            n_fold = len(words)
            for a in folded:
                words += _to_words(a)
            at_lane = jnp.stack(words)                      # [W, B]
        with phase("wf.agg.rows"):
            n_touched = jnp.sum(klast, dtype=jnp.int32)
            lane = jnp.arange(B, dtype=jnp.int32)
            ends = jax.lax.sort(jnp.where(klast, lane, DEAD))
            ends = ends[:OC] if OC <= B else jnp.pad(
                ends, (0, OC - B), constant_values=DEAD)
            fired = jnp.arange(OC, dtype=jnp.int32) < n_touched
            at_row = at_lane.at[:, jnp.where(fired, ends, 0)].get(
                mode="promise_in_bounds")                   # [W, OC]
            krow = jax.lax.bitcast_convert_type(at_row[0], jnp.int32)
            read = jnp.where(fired, krow, 0)
            write = jnp.where(fired, krow, K)
        with phase("wf.agg.fold"):
            # the new members of a key: a difference of those sums, read
            # where the key's last run and the key before's lie (a fired
            # row's key has a run in every group)
            new_members = []
            for w, sums in zip(at_row[1:n_fold], running):
                last_run = jnp.where(
                    fired, jax.lax.bitcast_convert_type(w, jnp.int32) - 1, 0)
                upto = sums.at[:, last_run].get(mode="promise_in_bounds")
                new_members.append(jnp.diff(upto, axis=1, prepend=0))
            count = state["count"]
            if new_members:
                seen = count.at[:, read].get(mode="promise_in_bounds") \
                    + jnp.concatenate(new_members)
                count = count.at[:, write].set(seen, mode="drop")
            table = state["plain"]
            values, w_part, w_state, put = [], n_fold, 0, []
            if plain_names:
                held = table.at[:, read].get(mode="promise_in_bounds")
            for n, a in zip(plain_names, folded):
                nw = _n_words(a.dtype)
                part = _from_words(at_row[w_part:w_part + nw], a.dtype)
                w_part += nw
                dt = state_dtype(plain[n], a.dtype)
                nw = _n_words(dt)
                was = _from_words(held[w_state:w_state + nw], dt)
                w_state += nw
                now = combs[n](was, part.astype(dt))
                values.append(now)
                put += _to_words(now)
            if plain_names:
                table = table.at[:, write].set(jnp.stack(put), mode="drop")
        with phase("wf.agg.rows"):
            # lanes past the rows are not valid: what they hold is unread
            out = {"key": read, **dict(zip(plain_names, values))}
            distinct = [n for g in groups for n in g.leaves]
            out.update({n: seen[i].astype(jnp.int64)
                        for i, n in enumerate(distinct)})
            newest = jnp.max(jnp.where(live, ts,
                                       jnp.iinfo(jnp.int64).min))
            out_ts = jnp.where(fired, newest, 0)
            over = jnp.maximum(n_touched - OC, 0).astype(jnp.int64)
            counts = {
                "n_rows": jnp.sum(fired, dtype=jnp.int64),
                "n_tested": tested,
                "n_new": sum((jnp.sum(sums[:, -1], dtype=jnp.int64)
                              for sums in running),
                             jnp.zeros((), jnp.int64)),
                "n_key_refused": n_key_refused,
                "n_member_refused": refused,
                "n_overflow": over,
                "n_words": sum((r[-1].astype(jnp.int64) for r in ranks),
                               jnp.zeros((), jnp.int64))}
            new_state = {"plain": table, "count": count, "sets": sets}
            new_state.update({c: state[c] + counts[c] for c in COUNTERS})
        return new_state, out, fired, out_ts, jnp.stack(
            [jnp.zeros((), jnp.int64), over])

    return step


def make_release(groups: Sequence[DistinctGroup], plain: dict,
                 lift_spec: dict, K: int, n_keys: int):
    """``release(state, first) -> state``: the ``n_keys`` groups from
    ``first`` on let go, their leaves back at the identity and their sets
    empty, in place (what a day's roll-over does with the day before)."""
    n_keys = int(n_keys)

    @phase("wf.agg.fold")
    def release(state, first):
        first = jnp.clip(first.astype(jnp.int32), 0, K - n_keys)
        fresh = make_rolling_state(lift_spec, plain, groups, n_keys)
        put = jax.lax.dynamic_update_slice
        new = dict(state)
        zero = jnp.int32(0)
        new["plain"] = put(state["plain"], fresh["plain"], (zero, first))
        new["count"] = put(state["count"], fresh["count"], (zero, first))
        new["sets"] = [put(t, z, (first * g.words_per_key,))
                       for g, t, z in zip(groups, state["sets"],
                                          fresh["sets"])]
        return new

    return release
