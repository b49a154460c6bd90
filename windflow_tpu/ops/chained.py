"""Operator chaining (fusion).

The reference fuses same-parallelism FORWARD operators into one thread
(``/root/reference/wf/multipipe.hpp:553-569`` via ``combine_with_laststage``) to
save queue hops.  Here fusion has two forms, both cheaper than thread fusion:

* Host operators compose into one :class:`ChainedHost` replica — a closure
  pipeline with zero intermediate batching.
* TPU operators compose into one :class:`ChainedTPU` whose stages trace into a
  **single XLA program**, so map/filter chains fuse into one pass over HBM —
  the TPU analogue the reference cannot express (each CUDA op is a separate
  kernel launch even when chained).
"""

from __future__ import annotations

from typing import Callable, List, Tuple

from windflow_tpu.basic import WindFlowError
from windflow_tpu.batch import DeviceBatch
from windflow_tpu.meta import adapt
from windflow_tpu.ops.base import Operator, Replica
from windflow_tpu.ops.filter_op import Filter
from windflow_tpu.ops.flatmap_op import FlatMap
from windflow_tpu.ops.map_op import Map
from windflow_tpu.ops.tpu import FilterTPU, MapTPU, _TPUReplica


# ---------------------------------------------------------------------------
# Host-side fusion
# ---------------------------------------------------------------------------

def _host_specs(op) -> List[Tuple[str, Callable]]:
    if isinstance(op, ChainedHost):
        return op.specs
    if isinstance(op, Map):
        return [("map", adapt(op.fn, 1))]
    if isinstance(op, Filter):
        return [("filter", adapt(op.fn, 1))]
    if isinstance(op, FlatMap):
        return [("flatmap", adapt(op.fn, 2))]
    raise WindFlowError(f"cannot chain operator type {type(op).__name__}")


class _ChainShipper:
    __slots__ = ("call", "ts", "wm", "ctx")

    def __init__(self):
        self.call = None
        self.ts = 0
        self.wm = 0
        self.ctx = None

    def push(self, item):
        self.call(item, self.ts, self.wm, self.ctx)


class ChainedHostReplica(Replica):
    copy_on_shared = True  # fused map/filter stages may mutate in place

    def __init__(self, op: "ChainedHost", index: int) -> None:
        super().__init__(op, index)
        self._exp = 0

        def tail(item, ts, wm, ctx):
            self.stats.outputs_sent += 1
            # append the per-input output index: a fused flatmap emits
            # several outputs per input and each needs a distinct origin
            # id (same contract as flatmap_op.Shipper)
            tid = self.cur_tid
            if tid is not None:
                tid = tid + (self._exp,)
                self._exp += 1
            self.emitter.emit(item, ts, wm, tid=tid)

        call = tail
        for kind, fn in reversed(op.specs):
            call = self._make_stage(kind, fn, call)
        self._head = call

    def _make_stage(self, kind, fn, nxt):
        if kind == "map":
            def stage(item, ts, wm, ctx):
                out = fn(item, ctx)
                nxt(out if out is not None else item, ts, wm, ctx)
        elif kind == "filter":
            def stage(item, ts, wm, ctx):
                if fn(item, ctx):
                    nxt(item, ts, wm, ctx)
        else:  # flatmap
            shipper = _ChainShipper()
            shipper.call = nxt

            def stage(item, ts, wm, ctx):
                shipper.ts = ts
                shipper.wm = wm
                shipper.ctx = ctx
                fn(item, shipper, ctx)
        return stage

    def process_single(self, item, ts, wm):
        self._exp = 0
        self._head(item, ts, wm, self.context)


class ChainedHost(Operator):
    replica_class = ChainedHostReplica

    def __init__(self, specs, name, parallelism, routing, output_batch_size,
                 key_extractor):
        super().__init__(name, parallelism, routing=routing,
                         output_batch_size=output_batch_size,
                         key_extractor=key_extractor)
        self.specs = specs


# ---------------------------------------------------------------------------
# TPU-side fusion: one XLA program for the whole chain
# ---------------------------------------------------------------------------

def _tpu_specs(op):
    if isinstance(op, ChainedTPU):
        return op.specs
    # (kind, fn, the operator it came from: its wf.op.<name> scope in
    # the chain's one program)
    if isinstance(op, MapTPU):
        return [("batch_map" if op.batch_fn else "map", op.fn, op.name)]
    if isinstance(op, FilterTPU):
        return [("filter", op.fn, op.name)]
    raise WindFlowError(f"cannot chain TPU operator type {type(op).__name__}")


class ChainedTPUReplica(_TPUReplica):
    pass


class ChainedTPU(Operator):
    replica_class = ChainedTPUReplica
    chain_role = "member"

    def __init__(self, specs, name, parallelism, routing, key_extractor):
        super().__init__(name, parallelism, routing=routing, is_tpu=True,
                         key_extractor=key_extractor)
        self.specs = specs
        # The step machinery IS the fusion executor's chain program
        # (windflow_tpu/fusion FusedStatelessExec): a ChainedTPU is the
        # one-op fused segment, so pairwise chain() and whole-chain
        # fusion share a single implementation of the spec loop,
        # downstream key extraction (the keys lane the old step silently
        # dropped), and two-phase input donation.  Lazy import: the
        # executor reads specs back through _tpu_specs below.
        from windflow_tpu.fusion.executor import FusedStatelessExec
        self._chain = FusedStatelessExec(name, [self])

    def set_downstream_key_extractor(self, key_fn) -> None:
        """Forward the keys lane through the chain: the downstream KEYBY
        consumer's extractor runs inside this program on the chain's
        OUTPUT records — exactly what the consumer's own in-program
        extraction would compute — and rides the output batch's keys
        lane, so neither the keyby emitter nor a stateful consumer's
        ``.key_extract`` program pays a second dispatch.  Called by
        ``PipeGraph._build`` when this op feeds exactly one device KEYBY
        consumer."""
        self._chain.set_downstream_key_extractor(key_fn)

    def enable_input_donation(self) -> None:
        """Donate the payload/valid input buffers to the chain program
        (the sweep-ledger donation-miss fix): every staged batch's lanes
        are fresh, unshared arrays, so XLA may write outputs in place
        instead of copying whole buffers.  Only ``PipeGraph._build``
        calls this, after proving the inputs unshared — device keyby /
        broadcast / split edges alias one payload across destinations
        and stay copy-on-write.  The aliasing half is checked against
        the first batch's concrete specs (donation_aliases_cleanly)."""
        self._chain.enable_input_donation()

    def _step(self, batch: DeviceBatch) -> DeviceBatch:
        return self._chain.step(batch)


def fuse(a: Operator, b: Operator) -> Operator:
    """Fuse two chainable operators into one stage."""
    name = f"{a.name}|{b.name}"
    if a.is_tpu:
        fused = ChainedTPU(_tpu_specs(a) + _tpu_specs(b), name,
                           a.parallelism, a.routing, a.key_extractor)
    else:
        fused = ChainedHost(_host_specs(a) + _host_specs(b), name,
                            a.parallelism, a.routing, b.output_batch_size,
                            a.key_extractor)
    closers = [f for f in (a.closing_func, b.closing_func) if f is not None]
    if closers:
        # the fused replica terminates once; run every constituent's closer
        from windflow_tpu.meta import adapt
        adapted = [adapt(f, 0) for f in closers]

        def closing(ctx):
            for f in adapted:
                f(ctx)
        fused.closing_func = closing
    return fused
