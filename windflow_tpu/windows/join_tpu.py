"""IntervalJoinTPU: a keyed two-input interval join on the device, with a
per-build-row aggregate; IntervalJoinPairsTPU: its form that emits a row
a matched pair against a build side retained by key (below).

Both sides arrive on ONE input stream (two sources go through ``merge``
first) and ``build_side(row)`` says which side a row is on.

* A **build** row opens the interval ``[t, t + length(row))`` on its key,
  ``t`` its event time, ``length`` an int of microseconds (> 0; an empty
  interval matches nothing).
* A **probe** row at event time ``u`` **matches** the build row of its
  key with ``t <= u < t + length`` if ``match(build, probe)`` holds
  (default: always).  Matched probes are lifted (``lift(build, probe,
  u)``, ``u`` int64) and folded per build row with ``comb``, any
  associative function over a record (a pytree): probes are folded in
  event-time order, so a ``comb`` that keeps its left operand on a tie
  keeps the earliest.
* **One open build row a key.**  A second build row on a key whose first
  is still open displaces it: the older fires at once with what it had
  matched and is counted (``Join_build_displaced``; the rule the session
  window uses for a run that misses the open session).  A probe is
  matched against the NEWEST build row of its key at or before its time.
* A build row **fires** in the first step whose (lateness-adjusted)
  watermark is ``>= t + length``, and every open one at end of stream.
  Its row carries ``key``, ``start = t``, ``end = t + length``, the
  aggregate (``value``) and the number of matched probes (``count``,
  int32); the row's timestamp is the interval's last microsecond, the
  windows' convention.  A build row whose fold is empty when it closes
  emits **no row** and is counted (``Join_build_unmatched``).  The build
  row is **evicted** in that step: the state holds what is open, not what
  was ever seen.
* **Order** inside a batch is free: the step sorts by (key, event time),
  a build row before a probe of the same microsecond.  A row older than
  ``watermark - lateness`` (the watermark of the steps before its own) is
  late: dropped and counted (``Late_tuples_dropped``, ``dropped_tuples``),
  as the windows do.  Across batches a probe meets the build rows of its
  own and earlier steps only.
* A probe that matches nothing is a **miss**, never a dropped tuple,
  counted by cause: no build row of its key at or before its time in the
  state (``Join_probe_missed_no_build``: before the first, or after the
  last was evicted), outside the interval of the newest one
  (``Join_probe_missed_interval``), ``match`` false
  (``Join_probe_missed_predicate``).
* **State** is a carry of ``C`` build rows (``withBuildCapacity(C)``):
  nothing is indexed by key, no key space is declared and keys (int32,
  ``>= 0``) may grow for ever.  A step that would keep more than ``C``
  rows stops the graph with an error that names ``C``, a step late (the
  count is read when it costs no wait): never a silent loss.
* **Output.**  The batch a step hands on has ``join_out_capacity`` lanes
  (the input batch's, or the fewer of ``withOutputCapacity(n)``: the
  egress copies whole batches, so a deployment that knows how many build
  rows a batch can close says so), the closed rows compacted to its
  front.  Rows a step must emit (displaced ones) come first, and a step
  with more of them than lanes stops the graph as a full carry does;
  those the watermark closes take the room left, and the rest are held
  back in the carry (``Join_rows_held_back``) until a later step or the
  flush emits them.
  The watermark handed on is held back with them, by the session
  window's rule: batch ``n`` carries the adjusted watermark of step ``n -
  1`` once that step is known to have held nothing back.

One fixed-shape program a batch capacity (``jit_step_join`` in a device
trace), compiled once; the end of stream runs the same program on an empty
batch under an infinite watermark, so nothing compiles there.  One
replica, one chip: the operator refuses a mesh (co-partitioning two
inputs over chips is not built).

The pair form
-------------

Built with a ``join`` function and static boundaries (``withBoundaries(
lower, upper)``) in place of ``lift`` / ``comb`` and an interval length,
the operator **emits a row a matched pair** and **retains its build side
by key** (the reference lineage's ``Interval_Join``; Flink's interval
join).  Sides, keys, lateness, order inside a batch and the output batch
are as above; what differs:

* A **build** row at event time ``t`` on key ``k`` is retained for the
  probes of ``k`` with ``t - lower <= u < t + upper`` (``lower >= 0``,
  ``upper > 0``, int microseconds).  **One retained build row a key**
  (keys int32 in ``[0, K)``, ``withMaxKeys(K)``; a key outside stops the
  graph by name): a newer build row on a key replaces one that is still
  retained (``Join_build_replaced``).
* Every probe inside the interval of its key's build row for which
  ``match(build, probe)`` holds emits **one row**, ``join(build, probe,
  u)`` (any record over the lanes of both), in the step in which both are
  known: ``key`` the join key, ``build_ts = t``, ``probe_ts = u``,
  ``value`` the record; the row's timestamp is ``max(t, u)``, the moment
  the pair is complete.
* **Which build row a probe meets.**  The newest of its key at or before
  it in its own batch (the step's (key, time) order, a build row before a
  probe of its microsecond); where its batch has none, the row retained
  for its key once the batch's build rows are written: an earlier step's,
  or a later one of its own batch (the probe came first).  A probe
  outside that row's interval is ``Join_probe_missed_interval``, one that
  fails ``match`` ``Join_probe_missed_predicate``.
* **A probe may come before its build row.**  A probe that finds no
  retained row of its key WAITS in the state (``withProbeCapacity(P)``
  pending lanes; a step that would keep more stops the graph with an
  error that names ``P``) and looks again in every later step, until the
  (lateness-adjusted) watermark reaches ``u + lower``: then it is a miss
  (``Join_probe_missed_no_build``).  With ``lower = 0`` nothing waits
  past its own step.  At end of stream the waiting probes become misses.
* A build row is **evicted** once the watermark reaches ``t + upper``
  (``Join_build_evicted``; ``Join_build_retained`` says how many stand).
  A step looks a row up as retained while ``t + upper`` lies over the
  watermark of the steps before its own, so nothing passes over the table
  to evict, and the two counters are taken when they are read.
* **Output**: ``join_out_capacity`` lanes, the pairs at the front (those
  completed by a lookup lie behind those found in the batch and leave a
  hole where a waiting probe stayed unmatched).  Pairs that do not fit
  are held back in the state, in order (``Join_rows_held_back``; as many
  lanes again; a step with more stops the graph), and the hand-on
  watermark with them; it is also held at the oldest waiting probe, so it
  never passes a row still to come.

Its class is :class:`IntervalJoinPairsTPU` (the one builder chooses it
where it is given a ``join`` function and no ``comb``), its program
``jit_step_join_pairs``.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from windflow_tpu.basic import WindFlowError
from windflow_tpu.windows.join_kernels import (join_out_capacity,
                                               make_join_pairs_state,
                                               make_join_pairs_step,
                                               make_join_state,
                                               make_join_step, pair_reads,
                                               retained)
from windflow_tpu.windows.session_kernels import TS_MAX
from windflow_tpu.windows.session_tpu import _RowsBoundedByDataTPU

PROGRAM_NAME = "step_join"
PAIRS_PROGRAM_NAME = "step_join_pairs"


class _IntervalJoin(_RowsBoundedByDataTPU):
    """What the two forms of the join share: the sides, the key, the
    predicate and the output batch."""

    fixed_capacity_label = "IntervalJoinTPU"
    #: ``g.stats()`` name -> the state's counter
    counters = ()

    def megastep_tail(self):
        return None, (
            "interval join (each step's hand-on watermark waits for the "
            "previous step's held-back and overflow counts: per-batch "
            "dispatch, no scan body)")

    def __init__(self, *, build_side: Callable, key_extractor: Callable,
                 match: Optional[Callable], out_capacity: Optional[int],
                 name: str, parallelism: int, lateness: int) -> None:
        super().__init__(name, parallelism, key_extractor, lateness)
        if key_extractor is None:
            raise WindFlowError(
                f"IntervalJoinTPU '{name}' needs withKeyBy(fn): the two "
                "sides meet on a key")
        if build_side is None:
            raise WindFlowError(
                f"IntervalJoinTPU '{name}' needs withBuildSide(fn): which "
                "rows are the build side")
        if out_capacity is not None and int(out_capacity) < 1:
            raise WindFlowError(
                f"IntervalJoinTPU '{name}': withOutputCapacity(n) needs "
                "n >= 1 lanes")
        self.build_side = build_side
        self.match = match
        self.out_capacity = out_capacity

    def _row_spec(self, payload):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype), payload)

    def dump_stats(self) -> dict:
        st = super().dump_stats()
        if self._state is not None:
            for stat, counter in self.counters:
                st[stat] = self._counter(counter)
            st["Join_out_capacity"] = join_out_capacity(
                self._capacity, self.out_capacity)
        return st


class IntervalJoinTPU(_IntervalJoin):
    """Keyed interval join of the build and probe rows of one stream,
    one result row a build row (module docstring: the semantics)."""

    program_name = PROGRAM_NAME         # jit_step_join
    snapshot_kind = "interval_join_tpu"
    counters = (("Join_build_opened", "n_opened"),
                ("Join_build_closed", "n_closed"),
                ("Join_build_unmatched", "n_unmatched"),
                ("Join_build_displaced", "n_displaced"),
                ("Join_probe_matched", "n_matched"),
                ("Join_probe_missed_no_build", "n_miss_build"),
                ("Join_probe_missed_interval", "n_miss_interval"),
                ("Join_probe_missed_predicate", "n_miss_pred"),
                ("Join_rows_held_back", "n_held"))

    def __init__(self, lift: Callable, comb: Callable, *,
                 build_side: Callable, length: Callable,
                 key_extractor: Callable, build_capacity: int,
                 match: Optional[Callable] = None,
                 out_capacity: Optional[int] = None,
                 name: str = "interval_join_tpu", parallelism: int = 1,
                 lateness: int = 0) -> None:
        super().__init__(build_side=build_side, key_extractor=key_extractor,
                         match=match, out_capacity=out_capacity, name=name,
                         parallelism=parallelism, lateness=lateness)
        if length is None:
            raise WindFlowError(
                f"IntervalJoinTPU '{name}' needs withIntervalLength(fn): "
                "how long a build row's interval is")
        if build_capacity is None or int(build_capacity) < 1:
            raise WindFlowError(
                f"IntervalJoinTPU '{name}' needs withBuildCapacity(C >= "
                "1): the build rows its state holds open at once")
        self.lift = lift
        self.comb = comb
        self.length = length
        self.build_capacity = int(build_capacity)

    def _make_step(self, capacity: int):
        return make_join_step(capacity, self.build_capacity,
                              self.key_extractor, self.build_side,
                              self.length, self.match, self.lift, self.comb,
                              self.out_capacity)

    def _make_state(self, payload):
        one = self._row_spec(payload)
        agg = jax.eval_shape(self.lift, one, one,
                             jax.ShapeDtypeStruct((), jnp.int64))
        return make_join_state(one, agg, self.build_capacity)

    def _held(self, held) -> int:
        held, lost = (int(x) for x in np.asarray(held))
        if lost:
            raise WindFlowError(
                f"IntervalJoinTPU '{self.name}': a step had {lost} build "
                "rows more than it has room for ("
                f"withBuildCapacity({self.build_capacity}): the rows its "
                "state holds open at once; withOutputCapacity: the "
                "displaced rows one step can emit, "
                f"{join_out_capacity(self._capacity, self.out_capacity)}): "
                f"{lost} were lost; build the operator with more room")
        return held

    def dump_stats(self) -> dict:
        st = super().dump_stats()
        if self._state is not None:
            st["Join_build_open"] = int(jnp.sum(self._state["open"]))
            st["Join_build_capacity"] = self.build_capacity
        return st


class IntervalJoinPairsTPU(_IntervalJoin):
    """The join's pair form: one result row a matched pair, against a
    build side retained by key (module docstring, "The pair form")."""

    program_name = PAIRS_PROGRAM_NAME   # jit_step_join_pairs
    snapshot_kind = "interval_join_pairs_tpu"
    counters = (("Join_build_built", "n_built"),
                ("Join_build_replaced", "n_replaced"),
                ("Join_probe_matched", "n_matched"),
                ("Join_probe_missed_no_build", "n_miss_build"),
                ("Join_probe_missed_interval", "n_miss_interval"),
                ("Join_probe_missed_predicate", "n_miss_pred"),
                ("Join_probe_waited", "n_waited"),
                ("Join_probe_pending_max", "pend_max"),
                ("Join_rows_held_back", "n_held"))

    def __init__(self, join: Callable, *, build_side: Callable,
                 boundaries: tuple, key_extractor: Callable, max_keys: int,
                 probe_capacity: int, match: Optional[Callable] = None,
                 out_capacity: Optional[int] = None,
                 name: str = "interval_join_tpu", parallelism: int = 1,
                 lateness: int = 0) -> None:
        super().__init__(build_side=build_side, key_extractor=key_extractor,
                         match=match, out_capacity=out_capacity, name=name,
                         parallelism=parallelism, lateness=lateness)
        if boundaries is None:
            raise WindFlowError(
                f"IntervalJoinTPU '{name}' needs withBoundaries(lower, "
                "upper): a build row at t is retained for the probes with "
                "t - lower <= u < t + upper")
        lower, upper = (int(x) for x in boundaries)
        if lower < 0 or upper <= 0 or max(lower, upper) >= TS_MAX:
            raise WindFlowError(
                f"IntervalJoinTPU '{name}': withBoundaries(lower, upper) "
                f"needs 0 <= lower and 0 < upper usec, got ({lower}, "
                f"{upper})")
        if max_keys is None or not 1 <= int(max_keys) < (1 << 31) - 1:
            raise WindFlowError(
                f"IntervalJoinTPU '{name}' needs withMaxKeys(K >= 1): its "
                "retained build rows are dense over [0, K)")
        if probe_capacity is None or int(probe_capacity) < 1:
            raise WindFlowError(
                f"IntervalJoinTPU '{name}' needs withProbeCapacity(P >= "
                "1): the probes that wait for their build row at once")
        self.join = join
        self.lower, self.upper = lower, upper
        self.max_keys = int(max_keys)
        self.probe_capacity = int(probe_capacity)

    def _make_step(self, capacity: int):
        return make_join_pairs_step(
            capacity, self.max_keys, self.probe_capacity,
            self.key_extractor, self.build_side, self.match, self.join,
            self.lower, self.upper, self.out_capacity)

    def _make_state(self, payload):
        one = self._row_spec(payload)
        reads_b, reads_p = pair_reads(self.join, self.match, one)
        return make_join_pairs_state(
            one, reads_b, reads_p, self.max_keys, self.probe_capacity,
            join_out_capacity(self._capacity, self.out_capacity))

    def key_space(self):
        return self.max_keys

    def _held(self, held) -> int:
        held, lost, unkept, outside, _ = (int(x) for x in np.asarray(held))
        if outside:
            raise WindFlowError(
                f"IntervalJoinTPU '{self.name}': {outside} rows of a step "
                f"had a key outside [0, {self.max_keys}) (withMaxKeys("
                f"{self.max_keys}): the retained build rows are dense "
                "over the key space)")
        if unkept:
            raise WindFlowError(
                f"IntervalJoinTPU '{self.name}': a step had {unkept} "
                "probes more waiting for their build row than it has "
                f"room for (withProbeCapacity({self.probe_capacity})): "
                f"{unkept} were lost; build the operator with more room")
        if lost:
            oc = join_out_capacity(self._capacity, self.out_capacity)
            raise WindFlowError(
                f"IntervalJoinTPU '{self.name}': a step completed {lost} "
                "pairs more than its output batch and as many lanes "
                f"again of held-back rows take (withOutputCapacity({oc}"
                f")): {lost} were lost; build the operator with more room")
        return held

    def _hand_on(self, wm: int, held) -> int:
        # a probe that still waits will leave a row stamped at or after
        # its own time: the watermark handed on stays at the oldest
        return min(wm, int(np.asarray(held)[4]))

    def dump_stats(self) -> dict:
        st = super().dump_stats()
        if self._state is not None:
            tab = self._state["tab"]
            standing = int(jnp.sum(retained(
                tab["hi"], tab["lo"], self.upper, self._state["wm"])))
            st["Join_build_retained"] = standing
            # evicted by the lookup's validity test: counted when read
            st["Join_build_evicted"] = st["Join_build_built"] \
                - st["Join_build_replaced"] - standing
            st["Join_probe_pending"] = int(
                jnp.sum(self._state["pend"]["live"]))
            st["Join_max_keys"] = self.max_keys
            st["Join_probe_capacity"] = self.probe_capacity
        return st
