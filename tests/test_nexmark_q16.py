"""The rolling keyed aggregate (``windows/rolling_tpu.py``) and NEXmark q16
(channel statistics) at small sizes on the CPU backend: the operator a
batch at a time through ``op._step(DeviceBatch(...))`` and through
``PipeGraph`` and the public builder against an oracle of plain Python
sets, in every case the operator's contract names; the lowered step's
structure; and the benchmark's graph and its closed-form reference
against the same oracle."""

import os
import pickle
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import windflow_tpu as wf  # noqa: E402
from benchmark import harness  # noqa: E402
from windflow_tpu.batch import DeviceBatch  # noqa: E402
from windflow_tpu.windows import rolling_kernels as rk  # noqa: E402

q16 = harness.load_module("configs", "nexmark_q16")

KEYS, SPACE = 6, 70
LEAVES = ("n", "low_n", "top", "who", "low_who", "high_who", "what")


def lift(r, ts):
    low = r["v"] < 5
    return {"n": jnp.int32(1), "low_n": low.astype(jnp.int32),
            "top": r["v"] + (ts % 3).astype(jnp.int32) * 0,
            "who": r["m"], "low_who": jnp.where(low, r["m"], -1),
            "high_who": jnp.where(low, -1, r["m"]), "what": r["a"]}


def builder(keys=KEYS, out=None, space=SPACE):
    b = (wf.Rolling_AggregateTPU_Builder(lift).withName("agg")
         .withSum("n", "low_n").withMax("top")
         .withDistinct("who", "low_who", "high_who", space=space)
         .withDistinct("what", space=200)
         .withKeyBy(lambda r: r["k"]).withMaxKeys(keys))
    return b if out is None else b.withOutputCapacity(out)


class Oracle:
    """Plain Python: a dict of groups, a set a distinct leaf."""

    def __init__(self, keys=KEYS, space=SPACE):
        self.groups, self.keys, self.space = {}, keys, space
        self.refused = self.words = 0

    def fresh(self):
        return {"n": 0, "low_n": 0, "top": -2**31, "who": set(),
                "low_who": set(), "high_who": set(), "what": set()}

    def batch(self, k, m, a, v):
        """Fold one batch; the rows it upserts, in key order."""
        touched, words = set(), set()
        who, what = groups_of(self.space)
        for k_, m_, a_, v_ in zip(k.tolist(), m.tolist(), a.tolist(),
                                  v.tolist()):
            if not 0 <= k_ < self.keys:
                self.refused += 1
                continue
            g = self.groups.setdefault(k_, self.fresh())
            touched.add(k_)
            # a record reads a word of either table: its member's, or
            # its group's first where it gives none the table holds
            words.add(("who", k_, m_ // who.members_per_word
                       if 0 <= m_ < self.space else 0))
            words.add(("what", k_, a_ // what.members_per_word
                       if 0 <= a_ < 200 else 0))
            g["n"] += 1
            g["low_n"] += v_ < 5
            g["top"] = max(g["top"], v_)
            if 0 <= m_ < self.space:
                g["who"].add(m_)
                g["low_who" if v_ < 5 else "high_who"].add(m_)
            if 0 <= a_ < 200:
                g["what"].add(a_)
        self.words += len(words)
        return [self.row(k_) for k_ in sorted(touched)]

    def members(self):
        """Members over all sets: what ``Agg_members_new`` has counted."""
        return sum(len(s) for g in self.groups.values()
                   for s in g.values() if isinstance(s, set))

    def row(self, k_):
        g = self.groups[k_]
        return (k_,) + tuple(len(g[n]) if isinstance(g[n], set) else g[n]
                             for n in LEAVES)


def batch_of(B, k, m, a, v, ts=None, wm=0):
    n = len(k)
    pad = lambda x, dt: jnp.asarray(  # noqa: E731
        np.r_[np.asarray(x, dt), np.zeros(B - n, dt)])
    ts = np.arange(n) if ts is None else ts
    return DeviceBatch({"k": pad(k, np.int32), "m": pad(m, np.int32),
                        "a": pad(a, np.int32), "v": pad(v, np.int32)},
                       pad(ts, np.int64), jnp.asarray(np.arange(B) < n),
                       watermark=wm)


def rows_of(out):
    ok = np.asarray(out.valid)
    p = {n: np.asarray(a)[ok] for n, a in out.payload.items()}
    return [tuple(int(p[n][i]) for n in ("key",) + LEAVES)
            for i in range(int(ok.sum()))]


def groups_of(space=SPACE):
    """``builder``'s two distinct groups."""
    return (rk.DistinctGroup(("who", "low_who", "high_who"), space),
            rk.DistinctGroup(("what",), 200))


def tables_of(oracle):
    """The bit tables the oracle's sets amount to: a bit a (group,
    member, leaf), where ``rolling_kernels`` says it lies."""
    tables = []
    for g in groups_of(oracle.space):
        t = np.zeros(oracle.keys * g.words_per_key, np.uint32)
        for k_, leaves in oracle.groups.items():
            for j, n in enumerate(g.leaves):
                for m_ in leaves[n]:
                    t[k_ * g.words_per_key + m_ // g.members_per_word] |= \
                        np.uint32(1 << (m_ % g.members_per_word * g.bits + j))
        tables.append(t)
    return tables


def a_stream(seed, n=1500, keys=KEYS, space=SPACE):
    """Skewed as the source is: one hot member three times in four, one
    hot key one time in three; ids that run past both ends."""
    rng = np.random.default_rng(seed)
    k = np.where(rng.random(n) < 1 / 3, 2, rng.integers(-1, keys + 1, n))
    m = np.where(rng.random(n) < 3 / 4, 9 + np.arange(n) // 200,
                 rng.integers(-2, space + 3, n))
    return (k.astype(np.int32), m.astype(np.int32),
            rng.integers(0, 200, n).astype(np.int32),
            rng.integers(0, 10, n).astype(np.int32))


def drive(op, B, k, m, a, v, oracle, split=None, make=None):
    for i, lo in enumerate(range(0, len(k), B)):
        if split is not None and i == split:
            blob = pickle.loads(pickle.dumps(op.snapshot_state()))
            assert blob["kind"] == "rolling_aggregate_tpu"
            assert all(isinstance(x, np.ndarray)
                       for x in jax.tree.leaves(blob["state"]))
            op = make()
            assert op.snapshot_state() is None          # never stepped
            op.restore_state(blob)
        s = slice(lo, lo + B)
        got = rows_of(op._step(batch_of(B, k[s], m[s], a[s], v[s])))
        assert got == oracle.batch(k[s], m[s], a[s], v[s]), i
        for mine, theirs in zip(op._state["sets"], tables_of(oracle)):
            assert np.array_equal(np.asarray(mine), theirs), i
        st = op.dump_stats()
        assert st["Agg_members_new"] == oracle.members(), i
        assert st["Agg_words_touched"] == oracle.words, i
    assert op._flush() == []
    return op


# ---------------------------------------------------------------------------
# the operator a batch at a time
# ---------------------------------------------------------------------------

def _adversarial(name):
    """Batches built against the way the sets are read and written: at
    the addresses of the sorted runs' last lanes, a word read and
    written once a run.  ``(B, [(k, m, a, v), ...])``."""
    B = 32
    full = lambda x: np.full(B, x, np.int32)      # noqa: E731
    if name == "one_word":
        # a whole batch in ONE word of either table: one run of B lanes;
        # the second step's only new bits ride a middle lane
        m, a = full(8), full(32)
        m2, a2 = m.copy(), a.copy()
        m2[B // 2], a2[B // 2] = 9, 33
        return B, [(full(2), m, a, full(1)), (full(2), m2, a2, full(1)),
                   (full(2), m2[::-1], a2[::-1], full(7))]
    if name == "all_dead":
        # no lane names a group: every index is the one past the table
        k = np.where(np.arange(B) % 2 == 0, -1, KEYS).astype(np.int32)
        live = (full(1), full(3), full(3), full(1))
        return B, [live, (k, full(3), full(3), full(1)), live]
    if name == "last_word":
        # the tables' LAST words (index size - 1) beside dead lanes
        # (index size, dropped), and the first word beside them
        k = np.where(np.arange(B) % 3 == 0, KEYS,
                     np.where(np.arange(B) % 3 == 1, KEYS - 1, 0))
        m = np.where(k == 0, 0, SPACE - 1 - np.arange(B) % 2)
        a = np.where(k == 0, 0, 199 - np.arange(B) % 5)
        return B, [(k.astype(np.int32), m.astype(np.int32),
                    a.astype(np.int32), np.arange(B, dtype=np.int32) % 10)] * 2
    if name == "again":
        # the second step re-tests exactly the first step's members: no
        # bit is new, every word is written back as it was read
        k, m, a, v = (x[:B] for x in a_stream(166))
        return B, [(k, m, a, v), (k[::-1], m[::-1], a[::-1], v[::-1]),
                   (k, m, a, v)]
    if name == "two_groups":
        # one group's runs break where the other's go on: members that
        # change word every lane beside one auction, then the reverse
        i = np.arange(B)
        walk, stay = (i * 8 % 64).astype(np.int32), full(40)
        return B, [(full(3), walk, stay, full(2)),
                   (full(3), stay, (i * 32 % 192).astype(np.int32), full(8)),
                   (np.where(i < B // 2, 3, 4).astype(np.int32),
                    np.where(i % 4 < 2, 16, 17).astype(np.int32),
                    np.where(i % 6 < 3, 64, 65).astype(np.int32), full(5))]
    raise KeyError(name)


ADVERSARIAL = ("one_word", "all_dead", "last_word", "again", "two_groups")


@pytest.mark.parametrize("case", [
    *((seed, B) for B in (64, 256) for seed in (161, 162, 2**31 + 16)),
    *ADVERSARIAL], ids=str)
def test_every_step_against_the_sets(case):
    """Every step's rows, its tables bit for bit and its count of new
    members against plain Python sets: a skewed stream, and batches made
    to break the read and the write of the tables."""
    stream = not isinstance(case, str)
    if stream:
        B, (k, m, a, v) = case[1], a_stream(case[0])
    else:
        B, batches = _adversarial(case)
        k, m, a, v = (np.concatenate(x) for x in zip(*batches))
    oracle = Oracle()
    op = drive(builder().build(), B, k, m, a, v, oracle)
    st = op.dump_stats()
    assert st["Agg_keys_refused"] == oracle.refused
    assert st["Agg_output_overflow"] == 0
    assert op.num_dropped_tuples() == oracle.refused
    if stream:
        assert oracle.refused > 0
        assert st["Agg_members_refused"] > 0        # ids past the space
        assert oracle.members() < st["Agg_members_tested"]


def runs_batch(B, n_runs, keys, turn=0):
    """``B`` live lanes that fall into exactly ``n_runs`` runs in either
    table (``n_runs`` 0: none names a group): lane ``i`` names the
    ``i % n_runs``-th (key, word) pair of both, and within the word a
    member that ``turn`` and the lane's pass over the pairs choose."""
    i = np.arange(B)
    if n_runs == 0:
        return (np.full(B, -1, np.int32), i.astype(np.int32) % 8,
                i.astype(np.int32), i.astype(np.int32) % 10)
    who, what = groups_of()
    pair, within = i % n_runs, i // n_runs + turn
    k, word = pair % keys, pair // keys
    assert word.max() < min(SPACE // who.members_per_word,
                            200 // what.members_per_word)
    m = word * who.members_per_word + within % who.members_per_word
    a = word * what.members_per_word + within % what.members_per_word
    return (k.astype(np.int32), m.astype(np.int32), a.astype(np.int32),
            (i % 10).astype(np.int32))


@pytest.mark.parametrize("runs", ["none", "one", "a_chunk",
                                  "a_chunk_and_one", "every_lane"])
@pytest.mark.parametrize("B", [32, 51])
def test_every_trip_count_of_the_walk_over_the_runs(B, runs):
    """A step reads its runs a chunk of ``chunk_lanes`` at a time, as
    many chunks as it has runs: none (no trip), one run, exactly a
    chunk, a chunk and one, and every lane a word of its own (all the
    chunks; at 51 lanes the last one runs past the batch).  Three steps
    of each: new members, the same words with other members, and the
    first again (no new bit)."""
    C, keys = rk.chunk_lanes(B), 10
    assert (C, -B % C) == {32: (4, 0), 51: (6, 3)}[B]
    n_runs = {"none": 0, "one": 1, "a_chunk": C, "a_chunk_and_one": C + 1,
              "every_lane": B}[runs]
    batches = [runs_batch(B, n_runs, keys, turn) for turn in (0, 1, 0)]
    k, m, a, v = (np.concatenate(x) for x in zip(*batches))
    oracle = Oracle(keys=keys)
    op = drive(builder(keys=keys).build(), B, k, m, a, v, oracle)
    st = op.dump_stats()
    assert st["Agg_words_touched"] == oracle.words == 3 * 2 * n_runs
    assert st["Agg_keys_refused"] == oracle.refused == (0 if n_runs
                                                        else 3 * B)
    assert st["Agg_members_tested"] == (3 * 3 * B if n_runs else 0)
    assert st["Agg_rows_out"] == 3 * min(n_runs, keys)
    assert st["Agg_members_refused"] == st["Agg_output_overflow"] == 0


@pytest.mark.parametrize("case", [(162, 64), *ADVERSARIAL], ids=str)
def test_a_word_is_written_once_a_step_in_any_order(case, monkeypatch):
    """A scatter applies the updates of one index in no promised order
    (on the CPU backend the last lane wins; the chip need not agree).
    A step writes a word once: a run's last lane alone carries the run's
    address, the one value it writes the word it read and the run's
    whole OR.  Handed every table scatter's updates in a shuffled order,
    the steps leave the same tables, rows and counts."""
    from jax._src.lax import slicing
    real, shuffled = slicing.scatter, []
    tables = {(KEYS * g.words_per_key,) for g in groups_of()}
    assert len(tables) == 2

    def scatter(operand, indices, updates, dnums, **kw):
        if operand.dtype == jnp.uint32 and operand.shape in tables:
            order = np.random.default_rng(len(shuffled)).permutation(
                updates.shape[0])
            shuffled.append(operand.shape)
            indices, updates = indices[order], updates[order]
            kw["indices_are_sorted"] = False
        return real(operand, indices, updates, dnums, **kw)

    monkeypatch.setattr(slicing, "scatter", scatter)
    test_every_step_against_the_sets(case)
    assert set(shuffled) == tables and len(shuffled) == 2


@pytest.mark.parametrize("seed,B", [(161, 64), (2**31 + 16, 256)])
def test_the_words_touched_are_the_batchs_distinct_words(seed, B):
    """``Agg_words_touched`` is the reference's count of the different
    words a batch's records name, a table: under the source's skew far
    fewer than the members tested; one a record and table where no two
    records share a word."""
    oracle = Oracle()
    op = drive(builder().build(), B, *a_stream(seed), oracle)
    st = op.dump_stats()
    assert 0 < st["Agg_words_touched"] == oracle.words
    assert st["Agg_words_touched"] < st["Agg_members_tested"] // 2
    # every record a word of its own in either table: two a record
    lone = builder(keys=8).build()
    k, m, a, v = runs_batch(32, 32, 8)
    lone._step(batch_of(32, k, m, a, v))
    st = lone.dump_stats()
    assert st["Agg_words_touched"] == 2 * 32
    assert st["Agg_members_tested"] == 3 * 32


def one_group(members, values=None, B=16):
    """One step of one group; its row."""
    op = builder(keys=1).build()
    n = len(members)
    values = np.zeros(n) if values is None else values
    [row] = rows_of(op._step(batch_of(
        B, np.zeros(n), members, np.zeros(n), values)))
    return dict(zip(("key",) + LEAVES, row)), op


def test_duplicates_of_one_member_in_a_batch_count_once():
    row, op = one_group([7] * 12)
    assert (row["n"], row["who"], row["low_who"]) == (12, 1, 1)
    assert op.dump_stats()["Agg_members_new"] == 3      # who, low_who, what


def test_two_members_in_one_word_in_one_batch():
    # 4 bits a member (three leaves round up to four): 8 members a word
    g = rk.DistinctGroup(("who", "low_who", "high_who"), SPACE)
    assert (g.bits, g.members_per_word) == (4, 8)
    row, _ = one_group([8, 9, 15, 9, 8, 16])    # 8, 9, 15 share word 1
    assert row["who"] == 4
    row, _ = one_group([8, 9], values=[1, 7])   # one low, one high
    assert (row["who"], row["low_who"], row["high_who"]) == (2, 1, 1)


def test_a_member_seen_in_an_earlier_batch_is_not_counted_again():
    op = builder(keys=1).build()
    z = np.zeros
    rows = [rows_of(op._step(batch_of(8, z(3), mem, z(3), z(3))))[0]
            for mem in ([1, 2, 3], [3, 2, 1], [9, 1, 10])]
    assert [r[LEAVES.index("who") + 1] for r in rows] == [3, 3, 5]
    assert [r[1] for r in rows] == [3, 6, 9]            # n goes on
    assert op.dump_stats()["Agg_members_new"] == 2 * 5 + 1


def test_the_filtered_leaves_and_the_total_as_their_union():
    # member 4 bids low then high: in both ranks, once in the total
    row, _ = one_group([4, 4, 5, 6], values=[1, 8, 2, 9])
    assert (row["who"], row["low_who"], row["high_who"]) == (3, 2, 2)
    assert (row["n"], row["low_n"], row["top"]) == (4, 2, 9)


def test_leaves_of_one_call_that_name_two_members_are_refused():
    def two(r, ts):
        return {"x": r["m"], "y": r["a"]}
    op = (wf.Rolling_AggregateTPU_Builder(two)
          .withDistinct("x", "y", space=50).withKeyBy(lambda r: r["k"])
          .withMaxKeys(1).build())
    out = op._step(batch_of(4, [0, 0], [3, 4], [3, 9], [0, 0]))
    p = {n: np.asarray(x)[0] for n, x in out.payload.items()}
    # lane 0 agrees (3, 3); lane 1 gives 4 and 9: the larger stands
    assert (p["x"], p["y"]) == (1, 2)
    assert op.dump_stats()["Agg_members_refused"] == 1


def test_a_key_out_of_range_is_refused_and_counted():
    op = builder().build()
    out = op._step(batch_of(8, [-1, KEYS, 2, 10**6], [1, 1, 1, 1],
                            [0] * 4, [0] * 4))
    assert [r[0] for r in rows_of(out)] == [2]
    st = op.dump_stats()
    assert st["Agg_keys_refused"] == 3 and st["Agg_rows_out"] == 1
    assert op.num_dropped_tuples() == 3


def test_a_short_batch_and_an_empty_one():
    """A punctuation cuts a batch short: the invalid lanes name no group
    and set no bit; an empty batch upserts nothing."""
    op = builder().build()
    oracle = Oracle()
    k, m, a, v = a_stream(163, n=70)
    for lo, hi in ((0, 3), (3, 3), (3, 64), (64, 70)):
        s = slice(lo, hi)
        got = rows_of(op._step(batch_of(64, k[s], m[s], a[s], v[s])))
        assert got == oracle.batch(k[s], m[s], a[s], v[s])


def test_rows_are_stamped_with_the_batchs_newest_event_time():
    op = builder().build()
    out = op._step(batch_of(8, [1, 3, 1], [1, 2, 3], [0] * 3, [0] * 3,
                            ts=np.array([50, 70, 60])))
    ok = np.asarray(out.valid)
    assert np.asarray(out.ts)[ok].tolist() == [70, 70]
    assert out.capacity == 8        # min(keys, capacity), a power of two


def test_end_of_stream_owes_no_row():
    op = builder().build()
    assert op._flush() == []                            # never stepped
    op._step(batch_of(8, [1], [1], [1], [1]))
    assert op._flush() == [] and op._flush() == []
    assert op.dump_stats()["Agg_rows_out"] == 1


def test_more_groups_than_the_output_holds_stop_the_graph_by_name():
    op = builder(out=2).build()
    op._step(batch_of(8, [0, 1, 2], [1] * 3, [1] * 3, [1] * 3))
    with pytest.raises(wf.WindFlowError, match=r"'agg'.*1 groups more"):
        op._step(batch_of(8, [0], [1], [1], [1]))
    # ... and through a graph, at the latest when the stream ends
    rows = [{"k": np.int32(i % 5), "m": np.int32(i), "a": np.int32(0),
             "v": np.int32(0)} for i in range(40)]
    g = wf.PipeGraph("q16_overflow", wf.ExecutionMode.DEFAULT)
    g.add_source(wf.Source_Builder(lambda: iter(rows))
                 .withOutputBatchSize(16).build()) \
        .add(builder(out=2).build()) \
        .add_sink(wf.Sink_Builder(lambda r: None).build())
    with pytest.raises(wf.WindFlowError, match="withOutputCapacity"):
        g.run()


def test_a_days_roll_over_starts_new_groups_and_lets_the_old_go():
    """The key is (day % 2) x channels + channel: day 1 starts groups of
    its own beside day 0's; once day 0 is over its slots are released,
    and day 2 finds them empty."""
    C = 3
    op = builder(keys=2 * C).build()
    oracle = Oracle(keys=2 * C)
    rng = np.random.default_rng(164)

    def day(d, n=40):
        k = (d % 2) * C + rng.integers(0, C, n)
        return (k.astype(np.int32), rng.integers(0, 20, n),
                rng.integers(0, 20, n), rng.integers(0, 10, n))

    def step(b):
        got = rows_of(op._step(batch_of(64, *b)))
        assert got == oracle.batch(*b)
        return got

    step(day(0)), step(day(0))
    first = step(day(1))                    # new groups, from nothing
    assert all(r[0] >= C and r[1] <= 40 for r in first)
    assert rows_of(op._step(batch_of(64, *day(0, 5))))[0][1] > 5
    op.release_keys(0, C)                   # yesterday's sets let go
    for c in range(C):
        oracle.groups.pop(c, None)
    second = step(day(2))                   # day 2 in day 0's slots
    assert all(r[0] < C and r[1] <= 40 for r in second)
    assert step(day(1)) and step(day(2))    # day 1 untouched by it
    with pytest.raises(wf.WindFlowError, match="release_keys"):
        op.release_keys(4, 3)


@pytest.mark.parametrize("split", [1, 4])
def test_snapshot_and_restore_between_two_steps(split):
    k, m, a, v = a_stream(165, n=600)
    make = lambda: builder().build()   # noqa: E731
    drive(make(), 64, k, m, a, v, Oracle(), split=split, make=make)


def test_a_mesh_and_more_than_one_replica_are_refused_at_build():
    with pytest.raises(wf.WindFlowError, match="one replica"):
        builder().withParallelism(2).build()
    from windflow_tpu.parallel.mesh import make_mesh
    g = wf.PipeGraph("q16_mesh", wf.ExecutionMode.DEFAULT,
                     config=wf.Config(mesh=make_mesh(4)))
    g.add_source(wf.Source_Builder(lambda: iter(()))
                 .withOutputBatchSize(16).build()) \
        .add(builder().build()) \
        .add_sink(wf.Sink_Builder(lambda r: None).build())
    with pytest.raises(wf.WindFlowError, match="does not run on a mesh"):
        g.run()


@pytest.mark.parametrize("what,match", [
    (lambda b: b.withKeyBy(None), "withKeyBy"),
    (lambda b: b.withMaxKeys(0), "withMaxKeys"),
    (lambda b: b.withOutputCapacity(0), "withOutputCapacity"),
    (lambda b: b.withSum("who"), "must differ"),
    (lambda b: b.withDistinct(*(f"l{i}" for i in range(33)), space=4),
     "1 to 32 leaves"),
    (lambda b: b.withDistinct("big", space=2**37), "31 bits"),
    (lambda b: b.withMin("key"), "must differ"),
])
def test_what_cannot_be_built_says_so(what, match):
    with pytest.raises(wf.WindFlowError, match=match):
        what(builder()).build()


def test_a_lift_that_gives_other_leaves_than_declared_says_so():
    op = (wf.Rolling_AggregateTPU_Builder(lambda r, ts: {"x": r["m"]})
          .withSum("x", "y").withKeyBy(lambda r: r["k"]).withMaxKeys(2)
          .build())
    with pytest.raises(wf.WindFlowError, match=r"lift gives \['x'\]"):
        op._step(batch_of(4, [0], [1], [1], [1]))


def test_plain_leaves_alone_and_their_widths():
    """No distinct leaf: one sort by key.  An integer sum lifted as int32
    is kept as int64; a float sum and an int64 minimum keep their own."""
    def plain(r, ts):
        return {"n": jnp.int32(2**29), "f": r["v"].astype(jnp.float32) / 2,
                "first": ts, "low": r["v"]}
    op = (wf.Rolling_AggregateTPU_Builder(plain).withSum("n", "f")
          .withMin("first", "low").withKeyBy(lambda r: r["k"])
          .withMaxKeys(4).build())
    for lo in (100, 40):
        out = op._step(batch_of(8, [1, 3, 1, 1], [0] * 4, [0] * 4,
                                [5, 6, 7, 2], ts=lo + np.arange(4)))
    p = {n: np.asarray(x)[:2] for n, x in out.payload.items()}
    assert p["key"].tolist() == [1, 3]
    assert p["n"].dtype == np.int64 and p["n"].tolist() == [6 * 2**29,
                                                            2 * 2**29]
    assert p["f"].dtype == np.float32 and p["f"].tolist() == [14.0, 6.0]
    assert p["first"].dtype == np.int64 and p["first"].tolist() == [40, 41]
    assert p["low"].tolist() == [2, 6]
    assert op.dump_stats()["Agg_set_bytes"] == 0


# ---------------------------------------------------------------------------
# the lowered step
# ---------------------------------------------------------------------------

def _lowered_step():
    op = builder().build()
    b = batch_of(64, [1], [1], [1], [1])
    op._ensure(b)
    return op._state, op._jit_step._jit.lower(
        op._state, b.payload, b.ts, b.valid, jnp.int64(0))


def test_the_step_updates_its_tables_in_place_and_moves_32_bit_words():
    """Every state leaf is donated and aliased to an output (the tables
    are updated where they lie), and no gather or scatter of the step
    moves a 64-bit element."""
    state, lowered = _lowered_step()
    text = lowered.as_text()
    n_state = len(jax.tree.leaves(state))
    assert len(re.findall(r"tf\.aliasing_output", text)) == n_state
    hlo = lowered.compile().as_text()
    moved = re.findall(r"= (\w+)\[[^\]]*\][^=]*? (?:gather|scatter)\(", hlo)
    assert moved and not [t for t in moved if t in ("s64", "u64", "f64")]
    names = set(re.findall(r"wf\.agg\.\w+", hlo))
    assert names == {"wf.agg.sort", "wf.agg.distinct", "wf.agg.fold",
                     "wf.agg.rows"}


def test_the_step_reads_and_writes_its_tables_in_the_sorts_own_order():
    """The sets are gathered and scattered at the sorted run-ends' own
    addresses, and the step says so: a table is gathered from a chunk
    of ``chunk_lanes`` at a time inside one loop, and scattered into
    once, all the compacted lanes; every gather from and scatter into
    a bit table carries ``indices_are_sorted = true`` (a compiler that
    is not told sorts the updates itself, or scatters twice to five
    times slower: ``PERF.md`` section 6, PR 49 and PR 52); none claims
    ``unique_indices``; the step sorts once a distinct group, once more
    a group to bring the run-ends to the front, and once for the rows,
    and no more; and all of its state is still updated in place."""
    state, lowered = _lowered_step()
    text = lowered.as_text()
    sizes = {int(t.shape[0]) for t in state["sets"]}
    assert len(sizes) == len(state["sets"]) == 2
    flat = r"tensor<(\d+)xui32>, tensor<%dx1xi32>"
    gathers = re.findall(
        r'"stablehlo\.gather"\([^)]*\) <\{([^\n]*?)\}> : \('
        + flat % rk.chunk_lanes(64), text)
    scatters = re.findall(
        r'"stablehlo\.scatter"\([^)]*\) <\{([^\n]*?)\}> \(\{.*?\n\s*\}\) : \('
        + flat % 64, text, re.S)
    for found in (gathers, scatters):
        assert sorted(int(n) for _, n in found) == sorted(sizes)
        for attrs, _ in found:
            assert "indices_are_sorted = true" in attrs
    assert "unique_indices = true" not in text
    assert len(re.findall(r'"stablehlo\.sort"', text)) == 2 * len(sizes) + 1
    assert len(re.findall(r"stablehlo\.while", text)) == len(sizes)
    assert len(re.findall(r"tf\.aliasing_output", text)) \
        == len(jax.tree.leaves(state))


# ---------------------------------------------------------------------------
# through PipeGraph, the bid filter fused in as the prelude
# ---------------------------------------------------------------------------

def test_the_graph_against_the_sets():
    k, m, a, v = a_stream(166, n=2000)
    kind = np.random.default_rng(7).integers(0, 3, len(k))
    rows = [{"k": k[i], "m": m[i], "a": a[i], "v": v[i],
             "kind": np.int32(kind[i])} for i in range(len(k))]
    got = []
    g = wf.PipeGraph("q16_graph", wf.ExecutionMode.DEFAULT)
    g.add_source(wf.Source_Builder(lambda: iter(rows))
                 .withOutputBatchSize(128).build()) \
        .add(wf.FilterTPU_Builder(lambda r: r["kind"] == 2).build()) \
        .add(builder().build()) \
        .add_sink(wf.Sink_Builder(
            lambda r: got.append(r) if r is not None else None).build())
    g.run()
    oracle = Oracle()
    keep = kind == 2
    oracle.batch(k[keep], m[keep], a[keep], v[keep])
    last = {}
    for r in got:                   # a group's rows in order, n rising
        assert r["n"] > last.get(r["key"], {"n": 0})["n"]
        last[int(r["key"])] = r
    assert {k_: tuple(int(r[n]) for n in ("key",) + LEAVES)
            for k_, r in last.items()} \
        == {k_: oracle.row(k_) for k_ in oracle.groups}
    ops = {o["Operator_name"]: o for o in g.stats()["Operators"]}
    assert ops["filter_tpu"]["Fused_into"] == "filter_tpu|agg"
    assert ops["agg"]["Agg_rows_out"] == len(got)
    assert g.get_num_dropped_tuples() == oracle.refused
    assert all(e["batches"] == 0 for e in g.stats()["Megastep"]["edges"])
    # the exposition has the counters, by outcome
    from windflow_tpu.monitoring.openmetrics import (parse_exposition,
                                                     render_openmetrics)
    fams = parse_exposition(render_openmetrics(g.stats()))
    agg = ops["agg"]
    members = {lab["outcome"]: v for _, lab, v in
               fams["wf_operator_agg_members_total"]["samples"]
               if lab["operator"] == "agg"}
    assert members == {
        "new": agg["Agg_members_new"],
        "seen": agg["Agg_members_tested"] - agg["Agg_members_new"],
        "refused": agg["Agg_members_refused"]}
    assert 0 < agg["Agg_words_touched"] <= agg["Agg_members_tested"]
    for fam, stat in (("wf_operator_agg_rows_total", "Agg_rows_out"),
                      ("wf_operator_agg_words_touched_total",
                       "Agg_words_touched"),
                      ("wf_operator_agg_keys_refused_total",
                       "Agg_keys_refused"),
                      ("wf_operator_agg_output_overflow_total",
                       "Agg_output_overflow")):
        [(_, lab, v)] = fams[fam]["samples"]
        assert lab["operator"] == "agg" and v == agg[stat]


# ---------------------------------------------------------------------------
# the benchmark's graph and its closed-form reference
# ---------------------------------------------------------------------------

SIZES = dict(batch=1024, ring_batches=8, max_keys=64, cold_channels=40,
             bidder_space=256, auction_space=1024, out_capacity=64,
             active_people=4, hot_bidder_stride=8, event_rate=100_000)


def tiny_cfg():
    cell = harness.resolve_cell("nexmark_q16.saturated")
    return harness.with_sizes(cell["config"], SIZES)


def q16_oracle(rec, max_keys):
    """Per-bid Python sets: ``{(channel, n): the twelve numbers after the
    channel's n-th bid}``."""
    out, groups = {}, {}
    for e in rec[rec[q16.KIND] == q16.BID]:
        c = int(e[q16.CHANNEL])
        g = groups.setdefault(c, {"n": 0, "bids": [0, 0, 0],
                                  "who": [set() for _ in range(4)],
                                  "what": [set() for _ in range(4)],
                                  "minute": 0})
        price = int(e[q16.PRICE])
        r = 0 if price < 10_000 else 1 if price < 1_000_000 else 2
        g["n"] += 1
        g["bids"][r] += 1
        g["minute"] = max(g["minute"], int(e["t"]) // 60_000_000 % 1440)
        for sets, who in ((g["who"], int(e[q16.BIDDER]) - 1000),
                          (g["what"], int(e["k"]) - 1000)):
            sets[0].add(who)
            sets[1 + r].add(who)
        out[(c, g["n"])] = (g["minute"], *g["bids"],
                            *(len(s) for s in g["who"]),
                            *(len(s) for s in g["what"]))
    return out


@pytest.fixture(scope="module")
def replayed():
    """The generator's own stream, two and a third passes of a ring,
    through the benchmark's graph."""
    cfg = tiny_cfg()
    ring = q16.make_ring(2**31 + 16, cfg)
    n = len(ring["rec"]) * 7 // 3
    rec = ring["rec"][np.arange(n) % len(ring["rec"])].copy()
    rec["t"] = np.arange(n) * 10                # 100 000 events a second
    chunk = 73 * rec.dtype.itemsize
    buf = rec.tobytes()
    cols = []
    g = q16.build_graph(
        cfg, ring, lambda: (buf[i:i + chunk]
                            for i in range(0, len(buf), chunk)),
        lambda c: cols.append(c.cols) if c is not None else None)
    g.run()
    got = {n: np.concatenate([np.asarray(c[n]) for c in cols])
           for n in ("key", "wid", "value")}
    return cfg, ring, rec, got, g


def test_the_benchmarks_graph_agrees_with_the_sets(replayed):
    cfg, _ring, rec, got, _g = replayed
    oracle = q16_oracle(rec, cfg["graph"]["max_keys"])
    value = got["value"].reshape(-1, q16.N_VALUES)
    assert len(got["key"]) > 200
    for k_, n, v in zip(got["key"].tolist(), got["wid"].tolist(),
                        value.tolist()):
        assert tuple(v) == oracle[(k_, n)], (k_, n)
    # the last row of every channel holds the stream's totals
    total = {}
    for (c, n) in oracle:
        total[c] = max(total.get(c, 0), n)
    last = {}
    for k_, n in zip(got["key"].tolist(), got["wid"].tolist()):
        assert n > last.get(k_, 0)
        last[k_] = n
    assert last == total


def test_the_closed_form_agrees_with_the_sets(replayed):
    cfg, ring, rec, got, _g = replayed
    exp = q16.ChannelStatistics(ring["rec"], len(rec),
                                cfg["graph"]["max_keys"], 100_000,
                                run=ring["run"])
    oracle = q16_oracle(rec, cfg["graph"]["max_keys"])
    keys = np.array([k_ for k_, _ in oracle])
    ns = np.array([n for _, n in oracle])
    assert exp.at(keys, ns).tolist() == [list(v) for v in oracle.values()]
    assert {int(c): int(exp.total[c]) for c in exp.key} \
        == {c: max(n for c2, n in oracle if c2 == c) for c in set(keys)}
    checks = q16.compare(cfg, got, exp)
    assert all(c["ok"] for c in checks), checks
    assert {c["name"] for c in checks} == {
        "rows_missing_or_extra", "key_wid_mismatches", "result_rows_absent",
        "count_mismatches", "counter_mismatches"}
    c = q16.LAST_COUNTERS
    # two and a third passes: every member is new once, tested thrice
    assert c["Agg_members_tested"] == 4 * len(oracle)
    assert 0 < c["Agg_members_new"] < c["Agg_members_tested"] // 2


def test_the_graph_is_one_program_a_batch(replayed):
    _cfg, _ring, _rec, _got, g = replayed
    st = g.stats()
    ops = {o["Operator_name"]: o for o in st["Operators"]}
    assert ops["channel_statistics"]["Operator_type"] \
        == "RollingAggregateTPU"
    assert ops["filter_tpu"]["Fused_into"] \
        == "filter_tpu|channel_statistics"
    assert ops["channel_statistics"]["Agg_output_overflow"] == 0
    assert all(e["batches"] == 0 for e in st["Megastep"]["edges"])


@pytest.mark.parametrize("fault", ["recount", "lost_bit", "twice",
                                   "row_lost", "last_row_lost"])
def test_a_wrong_answer_fails_a_check(replayed, fault):
    cfg, ring, rec, got, _g = replayed

    class Ran(q16.Run):             # the run's counters, as they were
        graph = True

        def counters(self):
            return {"Agg_rows_out": len(got["key"]),
                    "Agg_output_overflow": 0, "Agg_keys_refused": 0,
                    "Agg_members_refused": 0}

    exp = q16.ChannelStatistics(ring["rec"], len(rec),
                                cfg["graph"]["max_keys"], 100_000)
    exp.run = Ran()
    exp.run.graph = True
    key, wid = got["key"].copy(), got["wid"].copy()
    value = got["value"].reshape(-1, q16.N_VALUES).copy()
    i = len(key) - 5
    if fault == "recount":          # a re-seen member counted again
        value[i, 4] += 1
    elif fault == "lost_bit":       # a member lost to a same-word write
        value[i, 9] -= 1
    elif fault == "twice":          # a batch's duplicates folded twice
        value[i, 1:4] *= 2
    elif fault == "row_lost":       # a touched channel without its row
        key, wid, value = (np.delete(x, 3, 0) for x in (key, wid, value))
    else:
        at = np.flatnonzero(key == key[-1])[-1]
        key, wid, value = (np.delete(x, at, 0) for x in (key, wid, value))
    checks = {c["name"]: c for c in q16.compare(
        cfg, {"key": key, "wid": wid, "value": value}, exp)}
    bad = {n for n, c in checks.items() if not c["ok"]}
    assert bad == {
        "recount": {"count_mismatches"}, "lost_bit": {"count_mismatches"},
        "twice": {"count_mismatches"},
        # the rows that are there are right: the aggregate's own count
        # of the rows it made says one is missing
        "row_lost": {"counter_mismatches"},
        "last_row_lost": {"rows_missing_or_extra",
                          "counter_mismatches"}}[fault]


def test_a_program_without_the_operator_is_refused_at_once(monkeypatch):
    monkeypatch.delattr(wf, "Rolling_AggregateTPU_Builder")
    with pytest.raises(RuntimeError, match="Rolling_AggregateTPU_Builder"):
        q16.make_ring(1, tiny_cfg())


def test_a_stream_past_its_first_day_is_refused_by_the_reference():
    cfg = tiny_cfg()
    ring = q16.make_ring(3, cfg)
    with pytest.raises(ValueError, match="first day"):
        q16.expected(cfg, ring, 86_400 * 100_000, {"event_rate": 100_000})


def _unstarted(agg, layout):
    src = (wf.Source_Builder(lambda: iter(()))
           .withRecordSpec({"k": np.int32(0), "m": np.int32(0),
                            "a": np.int32(0), "v": np.int32(0)})
           .withOutputBatchSize(64).build())
    g = wf.PipeGraph("q16_check", wf.ExecutionMode.DEFAULT)
    g.add_source(src).add(agg).add(wf.MapTPU_Builder(layout).build()) \
        .add_sink(wf.Sink_Builder(lambda r: None).build())
    return g


def test_preflight_checks_the_lift_and_the_operators_behind_the_rows():
    from windflow_tpu.analysis.preflight import check_graph
    ok = _unstarted(builder().build(),
                    lambda r: {"key": r["key"], "n": r["n"] + r["who"]})
    assert not [d for d in check_graph(ok) if d.code.startswith("WF1")]
    # a row has the declared leaves and no other
    behind = _unstarted(builder().build(), lambda r: {"x": r["value"]})
    assert [d.code for d in check_graph(behind)
            if d.code.startswith("WF1")] == ["WF101"]
    bad = (wf.Rolling_AggregateTPU_Builder(lambda r, ts: {"n": r["nope"]})
           .withSum("n").withKeyBy(lambda r: r["k"]).withMaxKeys(4)
           .withName("agg").build())
    found = [d for d in check_graph(_unstarted(bad, lambda r: r))
             if d.code == "WF101"]
    assert len(found) == 1 and "'agg'" in found[0].message
