"""OrderedCountWindowsTPU: count windows whose order within a key is EVENT
TIME, on the device.

``Ffat_WindowsTPU_Builder(lift, comb).withCBWindows(W, S)
.withEventTimeOrder(tie)`` builds it.  The count window it replaces
(``FfatWindowsTPU``) counts a key's rows in the order they ARRIVE.  That
is the order of the stream only while one host source feeds it in order:
a device operator whose rows close where the data says (the interval
join, the session window) hands a step's rows over compacted in the order
of its own sort, by key, and rows it holds back a step later still.  This
operator counts in the order ``(event time, tie(record))`` instead,
exact under the watermark:

* a row **waits** in the state until the (lateness-adjusted) watermark
  handed to the operator has passed its timestamp (``ts < watermark``:
  by the producer's word nothing older can still come), and every open
  row is released at end of stream.  Rows released together are counted
  key by key in time order, rows of one timestamp in the order of
  ``tie`` (an int of the record, e.g. an id: without one, rows of one
  key and one microsecond are counted in no stated order);
* a row that arrives OLDER than a watermark an earlier step acted on
  broke that word.  It is not dropped: it is counted
  (``CB_rows_out_of_order``) and takes its place among the rows released
  with it, behind rows of its key that left before it.  0 on a stream
  whose producers keep their word;
* the window that ends at a key's row folds its last ``W`` rows
  (``lift`` each, fold with ``comb``, oldest first) and fires every
  ``S`` rows, from the ``W``-th on; its row is ``{"key", "wid", "value",
  "last"}``: ``wid`` counts the key's windows from 0, ``last`` is the
  record that ended it, and the row is stamped as that record.  The
  windows a key had begun and not filled fire at end of stream over the
  rows they have (the upstream rule; ``last`` is zeros there);
* with ``withLeadingPartialWindows()`` the windows are cut at the key's
  START instead: the first fires at the key's first row with ``(rows - W)
  % S == 0`` over the rows there are (``W = 10, S = 1``: the mean of the
  first 1, 2, .. 9 rows, SQL's ``ROWS BETWEEN 9 PRECEDING AND CURRENT
  ROW``), and nothing more fires at end of stream.

**State** is dense over ``[0, max_keys)``: a key's last ``W - 1`` lifted
rows and its row count (rows, not panes: a step costs by the rows it
releases times ``W``, and ``W`` is at most ``MAX_WINDOW``), plus the
rows that wait, three times the input batch's lanes: a step left with more
stops the graph by name, a step late.  Keys outside ``[0,
max_keys)`` are masked, as the count window masks them.

**Output**: four times the input batch's lanes (every row a step
releases may end a window: the batch's and those that waited), the
released rows at the front in key order.

One fixed-shape program a batch capacity (``jit_step_count_ordered`` in
a device trace; ``jit_step_w<n>`` where it is the ``n``-th window stage
of its pipeline, fed by another windowing operator's rows), compiled
once; the end of stream runs the same program on an empty batch under an
infinite watermark.  One replica, one chip: the operator refuses a mesh.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from windflow_tpu.basic import WindFlowError, WinType
from windflow_tpu.batch import DeviceBatch
from windflow_tpu.monitoring import recorder as flightrec
from windflow_tpu.monitoring.jit_registry import wf_jit
from windflow_tpu.ops.base import Operator
from windflow_tpu.windows.count_ordered_kernels import (
    MAX_WINDOW, PEND_BATCHES, make_count_ordered_flush, make_count_ordered_state,
    make_count_ordered_step, out_capacity)
from windflow_tpu.windows.engine import WindowSpec
from windflow_tpu.windows.ffat_kernels import agg_spec_for
from windflow_tpu.windows.session_tpu import _RowsBoundedByDataTPU

PROGRAM_NAME = "step_count_ordered"


class OrderedCountWindowsTPU(_RowsBoundedByDataTPU):
    """Count windows per key in event-time order over a dense key space
    ``[0, max_keys)`` (module docstring: the order, the rows that wait,
    leading partial windows, the output batch)."""

    fixed_capacity_label = "OrderedCountWindowsTPU"
    snapshot_kind = "count_ordered_tpu"
    #: 1 for the first windowing operator of a pipeline; set by the
    #: graph build (``ffat_tpu.number_window_stages``)
    window_stage = 1
    count_order = "event_time"
    #: its own rows leave in key order too, but each stamped in time
    #: order within its key: a count window behind it needs no help
    rows_follow_data = False

    def __init__(self, lift: Callable, comb: Callable, spec: WindowSpec, *,
                 max_keys: int, name: str = "ffat_windows_tpu",
                 parallelism: int = 1,
                 key_extractor: Optional[Callable] = None,
                 tie: Optional[Callable] = None,
                 leading_partials: bool = False) -> None:
        super().__init__(name, parallelism, key_extractor, spec.lateness)
        if spec.win_type != WinType.CB:
            raise WindFlowError(
                f"'{name}': withEventTimeOrder orders a COUNT window "
                "(withCBWindows); a time window places a row by its "
                "timestamp already")
        if max_keys is None or int(max_keys) < 1:
            raise WindFlowError(
                f"OrderedCountWindowsTPU '{name}' needs withMaxKeys(n >= "
                "1): its state is dense over [0, n) (withCompactedKeys "
                "belongs to the count window in arrival order)")
        if spec.win_len > MAX_WINDOW:
            raise WindFlowError(
                f"OrderedCountWindowsTPU '{name}': a window of "
                f"{spec.win_len} rows; the count window in event-time "
                f"order keeps rows, not panes, and takes at most "
                f"{MAX_WINDOW}")
        self.lift = lift
        self.comb = comb
        self.spec = spec
        self.max_keys = int(max_keys)
        self.tie = tie
        self.leading_partials = bool(leading_partials)
        self._jit_flush = None
        self._rows_in = None        # the last step's, read a step late

    @property
    def program_name(self) -> str:
        return PROGRAM_NAME if self.window_stage <= 1 \
            else f"step_w{self.window_stage}"

    def megastep_tail(self):
        return None, (
            "count windows in event-time order (each step's hand-on "
            "watermark waits for the previous step's lost-row count: "
            "per-batch dispatch, no scan body)")

    def _make_step(self, capacity: int):
        return make_count_ordered_step(
            capacity, self.max_keys, self.spec.win_len, self.spec.slide,
            self.lift, self.comb, self.key_extractor, self.tie,
            self.leading_partials)

    def _make_state(self, payload):
        one = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype), payload)
        return make_count_ordered_state(
            one, agg_spec_for(self.lift, payload), self.max_keys,
            self.spec.win_len, self._capacity)

    def _held(self, held) -> int:
        held, lost, rows_in = (int(x) for x in np.asarray(held))
        self._rows_in = rows_in
        if lost:
            raise WindFlowError(
                f"OrderedCountWindowsTPU '{self.name}': a step was left "
                f"with {lost} rows more waiting for the watermark than "
                f"it has room for ({PEND_BATCHES * self._capacity} lanes, "
                f"{PEND_BATCHES} input batches): they were lost; the "
                "producer's watermark lags its rows by more than that")
        return held

    def _stage_notes(self) -> dict:
        # rows the producer handed over in the step before this one: the
        # count is read when it costs no wait
        return {} if self._rows_in is None else {"rows_in": self._rows_in}

    def key_space(self):
        return self.max_keys if self.key_extractor is not None else None

    def _flush(self) -> list:
        if self._state is None or self._flushed:
            return []
        outs = super()._flush()
        if self.leading_partials:
            return outs
        # the upstream rule: windows begun and not filled fire over the
        # rows they have
        if self._jit_flush is None:
            payload = self._payload_zero        # as the step sees it
            if self._fused_prelude is not None:
                from windflow_tpu.fusion.executor import prelude_out_spec
                payload = prelude_out_spec(
                    self._fused_prelude, payload,
                    jnp.zeros(self._capacity, bool))
            one = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
                a.shape[1:], a.dtype), payload)
            self._jit_flush = wf_jit(
                flightrec.operator_scope(self.name)(
                    make_count_ordered_flush(
                        self.max_keys, self.spec.win_len, self.spec.slide,
                        self.comb, agg_spec_for(self.lift, payload), one)),
                op_name=f"{self.name}.flush")
        out, fired, ts = self._jit_flush(self._state)
        with flightrec.wait("flush"):
            any_fired = bool(np.asarray(fired).any())
        if any_fired:
            outs.append(DeviceBatch(out, ts, fired, watermark=0, size=None))
        return outs

    def dump_stats(self) -> dict:
        # a late row is counted, not lost: no Late_tuples_dropped here
        st = Operator.dump_stats(self)
        if self._state is not None:
            st["CB_rows_out_of_order"] = self._counter("n_ooo")
            st["CB_windows_fired"] = self._counter("n_fired")
            st["CB_partial_windows"] = self._counter("n_partial")
            st["CB_rows_waiting"] = int(
                jnp.sum(self._state["pend"]["live"]))
            st["CB_order"] = "event_time"
            st["CB_out_capacity"] = out_capacity(self._capacity)
        return st

    def num_dropped_tuples(self) -> int:
        return 0
