"""IntervalJoinTPU: a keyed two-input interval join on the device, with a
per-build-row aggregate.

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
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from windflow_tpu.basic import WindFlowError
from windflow_tpu.windows.join_kernels import (join_out_capacity,
                                               make_join_state,
                                               make_join_step)
from windflow_tpu.windows.session_tpu import _RowsBoundedByDataTPU

PROGRAM_NAME = "step_join"


class IntervalJoinTPU(_RowsBoundedByDataTPU):
    """Keyed interval join of the build and probe rows of one stream,
    one result row a build row (module docstring: the semantics)."""

    fixed_capacity_label = "IntervalJoinTPU"
    program_name = PROGRAM_NAME         # jit_step_join
    snapshot_kind = "interval_join_tpu"
    per_batch_reason = (
        "interval join (each step's hand-on watermark waits for the "
        "previous step's held-back and overflow counts: per-batch "
        "dispatch, no scan body)")

    def __init__(self, lift: Callable, comb: Callable, *,
                 build_side: Callable, length: Callable,
                 key_extractor: Callable, build_capacity: int,
                 match: Optional[Callable] = None,
                 out_capacity: Optional[int] = None,
                 name: str = "interval_join_tpu", parallelism: int = 1,
                 lateness: int = 0) -> None:
        super().__init__(name, parallelism, key_extractor, lateness)
        if key_extractor is None:
            raise WindFlowError(
                f"IntervalJoinTPU '{name}' needs withKeyBy(fn): the two "
                "sides meet on a key")
        if build_side is None or length is None:
            raise WindFlowError(
                f"IntervalJoinTPU '{name}' needs withBuildSide(fn) and "
                "withIntervalLength(fn): which rows open an interval, "
                "and how long")
        if build_capacity is None or int(build_capacity) < 1:
            raise WindFlowError(
                f"IntervalJoinTPU '{name}' needs withBuildCapacity(C >= "
                "1): the build rows its state holds open at once")
        self.lift = lift
        self.comb = comb
        self.build_side = build_side
        self.length = length
        self.match = match
        if out_capacity is not None and int(out_capacity) < 1:
            raise WindFlowError(
                f"IntervalJoinTPU '{name}': withOutputCapacity(n) needs "
                "n >= 1 lanes")
        self.build_capacity = int(build_capacity)
        self.out_capacity = out_capacity

    def _make_step(self, capacity: int):
        return make_join_step(capacity, self.build_capacity,
                              self.key_extractor, self.build_side,
                              self.length, self.match, self.lift, self.comb,
                              self.out_capacity)

    def _make_state(self, payload):
        one = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype), payload)
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
            for stat, counter in (
                    ("Join_build_opened", "n_opened"),
                    ("Join_build_closed", "n_closed"),
                    ("Join_build_unmatched", "n_unmatched"),
                    ("Join_build_displaced", "n_displaced"),
                    ("Join_probe_matched", "n_matched"),
                    ("Join_probe_missed_no_build", "n_miss_build"),
                    ("Join_probe_missed_interval", "n_miss_interval"),
                    ("Join_probe_missed_predicate", "n_miss_pred"),
                    ("Join_rows_held_back", "n_held")):
                st[stat] = self._counter(counter)
            st["Join_build_capacity"] = self.build_capacity
            st["Join_out_capacity"] = join_out_capacity(
                self._capacity, self.out_capacity)
        return st
