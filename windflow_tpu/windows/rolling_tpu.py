"""RollingAggregateTPU: a keyed aggregate that never closes, on the device.

SQL's ``GROUP BY`` with no window: a group's leaves live in the
operator's state across batches and every batch upserts one row for each
group it touched.  ``Rolling_AggregateTPU_Builder(lift)`` builds it;
``lift(record, ts)`` (the record and its event time, int64 usec) gives
``{leaf: value}`` and each leaf is DECLARED:

* ``withSum(*leaves)`` / ``withMin`` / ``withMax``: a plain leaf folded
  by that monoid (a filtered count is a sum of a 0 / 1 lift).  An integer
  sum is kept and handed on as int64 whatever width it was lifted at;
* ``withDistinct(*leaves, space=n)``: an EXACT distinct count.  The lift
  gives a member id in ``[0, n)``, or a negative number where the record
  adds none (SQL's ``FILTER``); the leaf's value is how many different
  members the group has seen (int64).  The leaves of ONE call are
  filters of one member (``count(DISTINCT bidder)`` and ``count(DISTINCT
  bidder) FILTER (WHERE ...)``): in a record all that give a member give
  the same one (one that differs is refused and counted,
  ``Agg_members_refused``, as is an id outside ``[0, n)``), and they
  share a table: a bit a leaf, side by side, so a record costs one read
  and one write a call, however many leaves it names.

**State**, dense over ``[0, withMaxKeys(K))``: the plain leaves as
32-bit words ``[words, K]``, a count a distinct leaf ``[leaves, K]`` and
a flat bit table a ``withDistinct`` call, ``K x ceil(space / (32 /
bits))`` uint32 (``rolling_kernels.DistinctGroup``), all donated to the
step and updated where they lie.  No sketch, no sampling, no host set.
Keys outside ``[0, K)`` are refused and counted (``Agg_keys_refused``,
``dropped_tuples``), as the window operators mask them.

**Rows.**  One row ``{"key", leaf: value, ...}`` a group a step touched,
holding every record of the group up to the batch's last, stamped with
the batch's newest event time, compacted to the front of the output
batch in key order.  Nothing is held back and nothing fires at end of
stream: the watermark handed on is the input's, a step late (the shell's
rule: it is read when it costs no wait).  ``withOutputCapacity(n)``
sizes the output batch (default: ``min(K, capacity)`` rounded up to a
power of two); a step that touched more groups than fit stops the graph
with a ``WindFlowError`` that names the operator, a step late.

**Letting groups go.**  ``release_keys(first, n)`` puts the groups
``[first, first + n)`` back at their start (leaves at the identity, sets
empty), in place: what a day's roll-over does with the day before where
the key is ``(day % 2) * channels + channel``.  The caller decides when
(between two ``g.step()``); eviction by the watermark is not built.

One fixed-shape program a batch capacity (``jit_step_rolling`` in a
device trace), compiled once, a fused prelude (a filter) inlined ahead of
it.  One replica, one chip: the operator refuses a mesh and
``parallelism > 1``.
"""

from __future__ import annotations

from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from windflow_tpu.basic import WindFlowError
from windflow_tpu.monitoring import recorder as flightrec
from windflow_tpu.monitoring.jit_registry import wf_jit
from windflow_tpu.windows.rolling_kernels import (DistinctGroup, check_plan,
                                                  make_release,
                                                  make_rolling_state,
                                                  make_rolling_step,
                                                  out_capacity, state_dtype)
from windflow_tpu.windows.session_tpu import _RowsBoundedByDataTPU

PROGRAM_NAME = "step_rolling"


class RollingAggregateTPU(_RowsBoundedByDataTPU):
    """A rolling keyed aggregate with declared leaves over a dense key
    space ``[0, max_keys)`` (module docstring: the leaves, the sets, the
    rows)."""

    fixed_capacity_label = "RollingAggregateTPU"
    program_name = PROGRAM_NAME         # jit_step_rolling
    snapshot_kind = "rolling_aggregate_tpu"
    #: no window: a window behind it is its pipeline's first stage, and
    #: no row of it closes anything a freshness gauge could date
    window_stage = None
    reports_fire_freshness = False
    #: a group's rows leave in the order of its batches, one a step: a
    #: count window behind it counts them in order
    rows_follow_data = False
    #: ``g.stats()`` name -> the state's counter
    counters = (("Agg_rows_out", "n_rows"),
                ("Agg_members_tested", "n_tested"),
                ("Agg_members_new", "n_new"),
                ("Agg_words_touched", "n_words"),
                ("Agg_keys_refused", "n_key_refused"),
                ("Agg_members_refused", "n_member_refused"),
                ("Agg_output_overflow", "n_overflow"))

    def megastep_tail(self):
        return None, (
            "rolling aggregate (each step's hand-on watermark waits for "
            "the previous step's output-overflow count: per-batch "
            "dispatch, no scan body)")

    def __init__(self, lift: Callable, *, plain: dict,
                 distinct: Sequence[DistinctGroup], max_keys: int,
                 key_extractor: Optional[Callable],
                 out_capacity: Optional[int] = None,
                 name: str = "rolling_aggregate_tpu",
                 parallelism: int = 1) -> None:
        super().__init__(name, parallelism, key_extractor, 0)
        label = f"RollingAggregateTPU '{name}'"
        if key_extractor is None:
            raise WindFlowError(
                f"{label} needs withKeyBy(fn): the group of a record")
        if max_keys is None or int(max_keys) < 1:
            raise WindFlowError(
                f"{label} needs withMaxKeys(n >= 1): its state is dense "
                "over [0, n)")
        if out_capacity is not None and int(out_capacity) < 1:
            raise WindFlowError(
                f"{label}: withOutputCapacity(n) needs n >= 1 lanes")
        try:
            check_plan(plain, distinct, int(max_keys), label)
        except ValueError as e:
            raise WindFlowError(str(e)) from None
        self.lift = lift
        self.plain = dict(plain)
        self.distinct = list(distinct)
        self.max_keys = int(max_keys)
        self.out_capacity = out_capacity
        self._jit_release = {}

    def _make_step(self, capacity: int):
        return make_rolling_step(capacity, self.max_keys, self.lift,
                                 self.plain, self.distinct,
                                 self.key_extractor, self.out_capacity)

    def _lift_spec(self, payload):
        """One lifted record, from a batch as the step sees it."""
        one = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype), payload)
        spec = jax.eval_shape(self.lift, one,
                              jax.ShapeDtypeStruct((), jnp.int64))
        declared = set(self.plain) | {n for g in self.distinct
                                      for n in g.leaves}
        if not isinstance(spec, dict) or set(spec) != declared:
            raise WindFlowError(
                f"RollingAggregateTPU '{self.name}': lift gives "
                f"{sorted(spec) if isinstance(spec, dict) else spec}, the "
                f"declared leaves are {sorted(declared)}")
        return spec

    def row_spec(self, record):
        """One upsert row, from one incoming record's spec (preflight
        checks the operators behind against it)."""
        lifted = self._lift_spec(jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((1,) + tuple(s.shape), s.dtype),
            record))
        scalar = lambda dt: jax.ShapeDtypeStruct((), dt)   # noqa: E731
        row = {"key": scalar(jnp.int32)}
        row.update({n: scalar(state_dtype(k, lifted[n].dtype))
                    for n, k in self.plain.items()})
        row.update({n: scalar(jnp.int64)
                    for g in self.distinct for n in g.leaves})
        return row

    def _make_state(self, payload):
        return make_rolling_state(self._lift_spec(payload), self.plain,
                                  self.distinct, self.max_keys)

    def _held(self, held) -> int:
        held, over = (int(x) for x in np.asarray(held))
        if over:
            raise WindFlowError(
                f"RollingAggregateTPU '{self.name}': a step touched "
                f"{over} groups more than its output batch has lanes ("
                f"{out_capacity(self._capacity, self.max_keys, self.out_capacity)}"
                "): their rows were lost; build the operator with "
                "withOutputCapacity(n) for the groups one batch can touch")
        return held

    def _flush(self) -> list:
        # nothing waits in the state: no row is owed at end of stream
        if self._state is not None and not self._flushed:
            self._flushed = True
            self._last_held()           # the last step's: it may raise
        return []

    def release_keys(self, first: int, n: int) -> None:
        """Let the groups ``[first, first + n)`` go: their leaves back at
        the identity, their sets empty, in place."""
        first, n = int(first), int(n)
        if not (0 <= first and n >= 1 and first + n <= self.max_keys):
            raise WindFlowError(
                f"RollingAggregateTPU '{self.name}': release_keys("
                f"{first}, {n}) outside [0, {self.max_keys})")
        if self._state is None:
            return
        if n not in self._jit_release:
            payload = self._payload_zero        # as the step sees it
            if self._fused_prelude is not None:
                from windflow_tpu.fusion.executor import prelude_out_spec
                payload = prelude_out_spec(
                    self._fused_prelude, payload,
                    jnp.zeros(self._capacity, bool))
            self._jit_release[n] = wf_jit(
                flightrec.operator_scope(self.name)(make_release(
                    self.distinct, self.plain, self._lift_spec(payload),
                    self.max_keys, n)),
                op_name=f"{self.name}.release", donate_argnums=(0,))
        self._state = self._jit_release[n](self._state, jnp.int32(first))

    def key_space(self):
        return self.max_keys

    def num_dropped_tuples(self) -> int:
        return self._counter("n_key_refused")

    def dump_stats(self) -> dict:
        from windflow_tpu.ops.base import Operator
        refused = self._counter("n_key_refused")
        if self.replicas:
            self.replicas[0].stats.inputs_ignored = refused
        st = Operator.dump_stats(self)
        if self._state is not None:
            for stat, counter in self.counters:
                st[stat] = self._counter(counter)
            st["Agg_out_capacity"] = out_capacity(
                self._capacity, self.max_keys, self.out_capacity)
            st["Agg_set_bytes"] = sum(
                4 * self.max_keys * g.words_per_key for g in self.distinct)
        return st
