"""SessionWindowsTPU: per-key session windows on the device.

A session of a key is a maximal run of that key's tuples in which each
follows the last by less than ``gap`` of event time.  Its window is
``[first tuple, last tuple + gap)`` and its row carries the key, the
window's ``start`` and ``end`` and the aggregate (``lift`` each tuple,
fold with ``comb``); the row's timestamp is the window's last
microsecond, the time window's convention.  A session fires in the first
step whose (lateness-adjusted) watermark is ``>= last + gap``, and every
open session fires at end of stream.

**Touching rule** (Beam's): a tuple exactly ``gap`` after the previous
one of its key starts a new session: ``[a, a + gap)`` and ``[a + gap,
...)`` do not overlap.

**Disorder.**  Inside a batch any order is fine: the step sorts by key,
then event time.  Across batches the state holds ONE open session a key:

* a tuple older than ``watermark - lateness`` (the watermark of the steps
  before its own) is late: dropped and counted (``Late_tuples_dropped``,
  ``dropped_tuples``), as the time window does;
* a key's first run of a batch joins its open session where their
  windows intersect, whichever lies first;
* a run that does not join it displaces it: the open session closes in
  that step.  Where the watermark had not yet passed its end it is
  counted (``Sessions_closed_early``; 0 on a stream whose source stamps
  in order): a later tuple inside the lateness could still have reached
  it, and will now open a session of its own instead of merging
  silently into the wrong one.

**Output.**  The batch a step hands on has ``session_out_capacity``
lanes (the input batch's), the closed rows compacted to its front.
Sessions a step must emit always fit; those the watermark closes take
the room left, in key order, and the rest are held back in the state
(``Session_rows_held_back``) until a later step or the flush emits
them.  The watermark the operator hands on is held to match: batch
``n`` carries the adjusted watermark of step ``n - 1`` once that step
is known to have held nothing back (its count is read a step late, when
it costs no wait), so no row leaves after a watermark that has passed
its end.

One fixed-shape program a batch capacity (``jit_step_session`` in a
device trace), compiled once; the end of stream runs the same program
on an empty batch under an infinite watermark, so nothing compiles there.
One replica, one chip: the operator refuses a mesh.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from windflow_tpu.basic import RoutingMode, WindFlowError
from windflow_tpu.batch import WM_NONE, DeviceBatch
from windflow_tpu.monitoring import recorder as flightrec
from windflow_tpu.monitoring.jit_registry import wf_jit
from windflow_tpu.ops.base import Operator
from windflow_tpu.ops.tpu import _TPUReplica
from windflow_tpu.windows.ffat_kernels import agg_spec_for
from windflow_tpu.windows.session_kernels import (TS_MAX, TS_MIN,
                                                  make_session_state,
                                                  make_session_step,
                                                  session_out_capacity)

PROGRAM_NAME = "step_session"


class SessionTPUReplica(_TPUReplica):
    def on_eos(self):
        for out in self.op._flush():
            self.stats.device_programs_launched += 1
            # flush outputs carry size=None; .size counts the fired mask
            # (one device sync each, at end of stream only)
            self.stats.outputs_sent += out.size
            self.emitter.emit_device_batch(out)


class _RowsBoundedByDataTPU(Operator):
    """What the device operators whose rows close where the DATA says
    share (session windows, the interval join): one replica on one chip,
    one fixed-shape program a batch capacity (``program_name`` in a
    device trace) that returns ``(state, out, fired, out_ts, held)``, the
    closed rows compacted to the front of an output batch sized by the
    operator, the rest held back in the state and the hand-on watermark
    with them, and an end of stream that runs the same program on an
    empty batch under an infinite watermark.  A subclass says how its
    step and its state are made."""

    replica_class = SessionTPUReplica
    #: the output batch is sized by what a step can close: its
    #: ``wf.dispatch`` span always says so (``out_cap``)
    notes_out_cap = True
    #: the program's name in a device trace, less the ``jit_``
    program_name = None
    chain_role = "tail"
    #: its rows are a window stage to the operators it feeds: a window
    #: behind it is stage 2 (``jit_step_w2``)
    window_stage = 1
    rows_follow_data = True
    reports_fire_freshness = True
    #: one replica, no mesh (refused at build): the state has no shard
    #: shape to change.  A subclass states its ``snapshot_kind``, and in
    #: ``megastep_tail`` why its step is no scan body
    snapshot_shapeless = True

    def __init__(self, name: str, parallelism: int,
                 key_extractor: Optional[Callable], lateness: int) -> None:
        routing = (RoutingMode.KEYBY if key_extractor is not None
                   else RoutingMode.FORWARD)
        super().__init__(name, parallelism, routing=routing, is_tpu=True,
                         key_extractor=key_extractor)
        if parallelism != 1:
            raise WindFlowError(
                f"{self.fixed_capacity_label} '{name}' runs one replica "
                "(its state lives on one chip); got parallelism "
                f"{parallelism}")
        if int(lateness) < 0:
            raise WindFlowError("lateness must be >= 0 usec")
        self.lateness = int(lateness)
        self._state = None
        self._capacity = None
        self._jit_step = None
        self._payload_zero = None   # all-invalid batch for the flush
        self._flushed = False
        # the watermark handed on, and what decides the next one: the
        # previous step's adjusted watermark and its held-back count
        # (a device scalar, read once that step is done)
        self._out_wm = WM_NONE
        self._prev_wm = WM_NONE
        self._prev_held = None

    def inlines_prelude(self) -> bool:
        # the step inlines the prelude ahead of its sort (the bid filter
        # of NEXmark Q11 rides in jit_step_session, the person filter
        # of Q9 in jit_step_join)
        return True

    def _make_step(self, capacity: int) -> Callable:
        raise NotImplementedError

    def _make_state(self, payload):
        """The state for batches of ``payload`` (as the step sees them:
        behind a fused prelude)."""
        raise NotImplementedError

    def _held(self, held) -> int:
        """The held-back count of a step that is done (one device read)."""
        return int(held)

    def _hand_on(self, wm: int, held) -> int:
        """The watermark to hand on after a step that acted on ``wm``
        and held nothing back (``held``: what that step said)."""
        return wm

    def _last_held(self):
        """What the last step said of its held-back rows, read (it may
        raise) and kept as the step gave it; None before the first."""
        if self._prev_held is None:
            return None
        with flightrec.wait("held"):
            self._held(self._prev_held)
            return np.asarray(self._prev_held)

    def build_replicas(self, mode, time_policy):
        if self.mesh is not None:
            raise WindFlowError(
                f"{self.fixed_capacity_label} '{self.name}' does not run "
                "on a mesh (its state and its step's sort of the whole "
                "batch live on one chip): build the graph without "
                "Config.mesh")
        return super().build_replicas(mode, time_policy)

    # -- per-batch program ---------------------------------------------------
    def _build_step(self, capacity: int):
        # this operator's part of the program under its own name (device
        # phases); a fused prelude's members open theirs outside it
        step = flightrec.operator_scope(self.name)(
            self._make_step(capacity))
        prelude = self._fused_prelude
        if prelude is not None:
            # whole-chain fusion: the segment's stateless members (a
            # filter) run inside this program, as in ffat_tpu
            inner = step

            def step(state, payload, ts, valid, wm_adj):
                payload, valid = prelude(payload, valid)
                return inner(state, payload, ts, valid, wm_adj)
        step.__name__ = self.program_name
        return wf_jit(step, op_name=self._fused_name or self.name,
                      donate_argnums=(0,))

    def _ensure(self, batch: DeviceBatch) -> None:
        if self._capacity is None:
            self._capacity = batch.capacity
            self._jit_step = self._build_step(batch.capacity)
            self._payload_zero = jax.tree.map(jnp.zeros_like, batch.payload)
        elif batch.capacity != self._capacity:
            raise WindFlowError(
                f"{self.fixed_capacity_label} requires a fixed upstream "
                f"batch capacity ({self._capacity}), got {batch.capacity}")
        if self._state is None:
            payload = batch.payload
            if self._fused_prelude is not None:
                from windflow_tpu.fusion.executor import prelude_out_spec
                payload = prelude_out_spec(self._fused_prelude,
                                           batch.payload, batch.valid)
            self._state = self._make_state(payload)

    def _wm_adj(self, wm: int) -> int:
        return TS_MIN if wm == WM_NONE else wm - self.lateness

    def _step(self, batch: DeviceBatch) -> DeviceBatch:
        self._ensure(batch)
        # the batch's staging-time frontier, as the time window fires on:
        # the step places every tuple of the batch before it closes
        wm = self._wm_adj(batch.frontier)
        self._state, out, fired, out_ts, held = self._jit_step(
            self._state, batch.payload, batch.ts, batch.valid,
            jnp.int64(wm))
        if self._prev_held is not None:
            # the one blocking read of the step: the counts are there
            # once the step BEFORE has run (a wait of its own, so that
            # the dispatch span around it times the host's work alone)
            with flightrec.wait("held", batch=batch.seq):
                held_before = self._held(self._prev_held)
            if held_before == 0 and self._prev_wm != TS_MIN:
                # the previous step emitted everything its watermark
                # closed
                self._out_wm = max(self._out_wm, self._hand_on(
                    self._prev_wm, self._prev_held))
        self._prev_wm, self._prev_held = wm, held
        return DeviceBatch(out, out_ts, fired, watermark=self._out_wm,
                           size=None, trace=batch.trace)

    def _flush(self) -> list:
        """End of stream: close every open row, a whole output batch at
        a time, by the step's own program on an empty batch under an
        infinite watermark (as the time window flushes)."""
        if self._state is None or self._flushed:
            return []
        self._flushed = True
        self._last_held()               # the last step's: it may raise
        cap = self._capacity
        ts0, none = jnp.zeros(cap, jnp.int64), jnp.zeros(cap, bool)
        outs = []
        while True:
            self._state, out, fired, out_ts, left = self._jit_step(
                self._state, self._payload_zero, ts0, none,
                jnp.int64(TS_MAX))
            with flightrec.wait("flush"):
                any_fired = bool(np.asarray(fired).any())
                left = self._held(left)
            if any_fired:
                outs.append(DeviceBatch(out, out_ts, fired, watermark=0,
                                        size=None))
            if left == 0:
                return outs

    # -- durable state (windflow_tpu/durability) -----------------------------
    def snapshot_state(self):
        if self._state is None:
            return None     # never stepped: nothing to restore
        return {
            "kind": self.snapshot_kind,
            "state": jax.tree.map(np.asarray, self._state),
            "capacity": self._capacity,
            "flushed": self._flushed,
            "out_wm": self._out_wm,
            "prev_wm": self._prev_wm,
            "prev_held": self._last_held(),
            "payload_zero": jax.tree.map(np.asarray, self._payload_zero),
        }

    def restore_state(self, blob):
        self._state = jax.tree.map(jnp.asarray, blob["state"])
        self._capacity = blob["capacity"]
        self._flushed = blob["flushed"]
        self._out_wm = blob["out_wm"]
        self._prev_wm = blob["prev_wm"]
        self._prev_held = blob["prev_held"]
        self._payload_zero = jax.tree.map(jnp.asarray, blob["payload_zero"])
        self._jit_step = self._build_step(self._capacity)

    # -- plumbing --------------------------------------------------------------
    def _counter(self, name: str) -> int:
        # one device sync at read time, never on the step path
        return int(self._state[name]) if self._state is not None else 0

    def num_dropped_tuples(self) -> int:
        return self._counter("n_late")

    def dump_stats(self) -> dict:
        n_late = self._counter("n_late")
        if self.replicas:
            self.replicas[0].stats.inputs_ignored = n_late
        st = super().dump_stats()
        if self._state is not None:
            st["Late_tuples_dropped"] = n_late
        return st


class SessionWindowsTPU(_RowsBoundedByDataTPU):
    """Session windows per key over a dense key space ``[0, max_keys)``
    (module docstring: semantics, the touching rule, the contract on
    disorder, the output batch)."""

    fixed_capacity_label = "SessionWindowsTPU"
    program_name = PROGRAM_NAME         # jit_step_session
    snapshot_kind = "session_tpu"

    def megastep_tail(self):
        return None, (
            "session windows (each step's hand-on watermark waits for "
            "the previous step's held-back count: per-batch dispatch, "
            "no scan body)")

    def __init__(self, lift: Callable, comb: Callable, gap_usec: int, *,
                 max_keys: int, name: str = "session_windows_tpu",
                 parallelism: int = 1,
                 key_extractor: Optional[Callable] = None,
                 lateness: int = 0) -> None:
        super().__init__(name, parallelism, key_extractor, lateness)
        if int(gap_usec) <= 0:
            raise WindFlowError("the session gap must be > 0 usec")
        if max_keys is None or int(max_keys) < 1:
            raise WindFlowError(
                f"SessionWindowsTPU '{name}' needs withMaxKeys(n >= 1): "
                "its state is dense over [0, n)")
        self.lift = lift
        self.comb = comb
        self.gap = int(gap_usec)
        self.max_keys = int(max_keys)

    def _make_step(self, capacity: int):
        return make_session_step(capacity, self.max_keys, self.gap,
                                 self.lift, self.comb, self.key_extractor)

    def _make_state(self, payload):
        return make_session_state(agg_spec_for(self.lift, payload),
                                  self.max_keys)

    def key_space(self):
        return self.max_keys if self.key_extractor is not None else None

    def dump_stats(self) -> dict:
        st = super().dump_stats()
        if self._state is not None:
            st["Sessions_open"] = int(jnp.sum(self._state["open"]))
            st["Sessions_closed"] = self._counter("n_closed")
            st["Session_rows_held_back"] = self._counter("n_held")
            st["Sessions_closed_early"] = self._counter("n_early")
            st["Session_out_capacity"] = session_out_capacity(
                self._capacity, self.max_keys)
        return st
