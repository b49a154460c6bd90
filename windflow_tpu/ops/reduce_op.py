"""Host Reduce operator (reference ``/root/reference/wf/reduce.hpp:58-176``):
per-key rolling state, emitting the updated state for every input.  State for
unseen keys starts from ``initial_state`` (the reference default-constructs
``state_t``; here a value is shallow-copied or a zero-arg factory called).
Non-keyed Reduce folds everything into one state under the empty key
(reference ``empty_key_t``)."""

from __future__ import annotations

import copy
from typing import Any, Callable, Optional

from windflow_tpu.basic import EMPTY_KEY, RoutingMode
from windflow_tpu.meta import adapt
from windflow_tpu.ops.base import Operator, Replica


class ReduceReplica(Replica):
    def __init__(self, op: "Reduce", index: int) -> None:
        super().__init__(op, index)
        self._fn = adapt(op.fn, 2)
        self._states = {}

    def _new_state(self):
        init = self.op.initial_state
        return init() if callable(init) else copy.copy(init)

    def process_single(self, item, ts, wm):
        key = (self.op.key_extractor(item)
               if self.op.key_extractor is not None else EMPTY_KEY)
        state = self._states.get(key)
        if state is None:
            state = self._new_state()
        out = self._fn(item, state, self.context)
        if out is None:  # in-place mutation variant
            out = state
        self._states[key] = out
        self.stats.outputs_sent += 1
        self.emitter.emit(copy.copy(out), ts, wm,
                          tid=self.cur_tid)


class Reduce(Operator):
    replica_class = ReduceReplica
    #: its re-bucket rule: ``durability/rebucket._rebucket_reduce_host``
    snapshot_kind = "reduce_host"

    # -- durable state (windflow_tpu/durability) -----------------------------
    def snapshot_state(self):
        """Per-replica rolling per-key state dicts (user state objects —
        must be picklable, same contract as the persistent suite's
        serializer defaults)."""
        if not self.replicas:
            return None
        return {"kind": self.snapshot_kind,
                "replicas": [dict(r._states) for r in self.replicas]}

    def restore_state(self, blob):
        for rep, st in zip(self.replicas, blob["replicas"]):
            rep._states = dict(st)

    def __init__(self, fn: Callable[[Any, Any], Any], initial_state: Any,
                 name: str = "reduce", parallelism: int = 1,
                 key_extractor: Optional[Callable] = None,
                 output_batch_size: int = 0) -> None:
        routing = RoutingMode.KEYBY if key_extractor is not None \
            else RoutingMode.FORWARD
        if key_extractor is None and parallelism > 1:
            from windflow_tpu.basic import WindFlowError
            raise WindFlowError(
                "non-keyed Reduce requires parallelism == 1 (reference: "
                "keyless operators with state cannot be replicated)")
        super().__init__(name, parallelism, routing=routing,
                         output_batch_size=output_batch_size,
                         key_extractor=key_extractor)
        self.fn = fn
        self.initial_state = initial_state
