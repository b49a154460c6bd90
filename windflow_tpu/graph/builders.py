"""Fluent operator builders (reference ``/root/reference/wf/builders.hpp:57-127``
and the GPU variants in ``builders_gpu.hpp:54-673``).

Method names keep the reference's camelCase (``withParallelism``,
``withKeyBy``, ``withOutputBatchSize``) so a WindFlow user can transliterate
their program; TPU builders mirror the ``*GPU_Builder`` family.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

from windflow_tpu.basic import RoutingMode, WindFlowError
from windflow_tpu.ops.filter_op import Filter
from windflow_tpu.ops.flatmap_op import FlatMap
from windflow_tpu.ops.map_op import Map
from windflow_tpu.ops.reduce_op import Reduce
from windflow_tpu.ops.sink import Sink
from windflow_tpu.ops.source import Source
from windflow_tpu.ops.tpu import FilterTPU, MapTPU, ReduceTPU
from windflow_tpu.ops.tpu_stateful import StatefulFilterTPU, StatefulMapTPU


class _BuilderBase:
    _default_name = "op"
    _closing_func: Optional[Callable] = None

    def __init__(self) -> None:
        self._name = self._default_name
        self._parallelism = 1
        self._output_batch_size = 0
        self._key_extractor: Optional[Callable] = None

    def __init_subclass__(cls, **kwargs):
        # every builder's build() applies the clauses _BuilderBase owns but
        # the per-builder constructors don't know about (closing function);
        # wrapping here keeps the ~20 build() methods oblivious
        super().__init_subclass__(**kwargs)
        orig = cls.__dict__.get("build")
        if orig is not None:
            def build(self, _orig=orig):
                op = _orig(self)
                if self._closing_func is not None:
                    op.closing_func = self._closing_func
                return op
            build.__doc__ = orig.__doc__
            cls.build = build

    def withName(self, name: str):
        self._name = name
        return self

    def withClosingFunction(self, fn: Callable):
        """Per-replica shutdown callback, run once when the replica
        terminates at EOS — ``fn(ctx)`` with the replica's RuntimeContext,
        or ``fn()`` (reference ``closing_func`` accepted by every operator
        builder, e.g. ``map.hpp:335-343``)."""
        self._closing_func = fn
        return self

    def withParallelism(self, parallelism: int):
        self._parallelism = parallelism
        return self

    def withOutputBatchSize(self, size: int):
        self._output_batch_size = size
        return self

    def withKeyBy(self, key_extractor: Callable[[Any], Any]):
        self._key_extractor = key_extractor
        return self

    def withRebalancing(self):
        """Round-robin input distribution even after an upstream KEYBY
        (reference REBALANCING routing, ``basic.hpp:87`` / builders
        ``withRebalancing``).  Mutually exclusive with withKeyBy."""
        self._rebalancing = True
        return self

    def _routing(self) -> RoutingMode:
        if getattr(self, "_broadcast", False):
            if self._key_extractor is not None \
                    or getattr(self, "_rebalancing", False):
                raise WindFlowError(
                    "withBroadcast is mutually exclusive with withKeyBy "
                    "and withRebalancing")
            return RoutingMode.BROADCAST
        if getattr(self, "_rebalancing", False):
            if self._key_extractor is not None:
                raise WindFlowError(
                    "withRebalancing and withKeyBy are mutually exclusive")
            return RoutingMode.REBALANCING
        return (RoutingMode.KEYBY if self._key_extractor is not None
                else RoutingMode.FORWARD)


class _BroadcastMixin:
    """withBroadcast for the operators the reference offers it on
    (Map/Filter/FlatMap/Sink, ``builders.hpp:252-1471``): every replica of
    the built operator receives every input tuple."""

    def withBroadcast(self):
        self._broadcast = True
        return self


class Source_Builder(_BuilderBase):
    _default_name = "source"

    def __init__(self, gen_fn: Callable) -> None:
        super().__init__()
        self._gen_fn = gen_fn
        self._ts_extractor = None
        self._record_spec = None

    def withTimestampExtractor(self, fn: Callable[[Any], int]):
        """EVENT-time sources: extract the event timestamp (µs) from each
        generated item (reference: ``Source_Shipper::pushWithTimestamp``)."""
        self._ts_extractor = fn
        return self

    def withRecordSpec(self, example: Any):
        """Declare the records this source emits — an example record
        (pytree of scalars/arrays) or a pytree of ``jax.ShapeDtypeStruct``
        — so ``PipeGraph.check()`` can abstractly evaluate every
        downstream kernel before dispatch (docs/ANALYSIS.md).  Static
        metadata only: never fed to the generator."""
        self._record_spec = example
        return self

    def withKeyBy(self, *_):
        raise WindFlowError("a Source has no input to key by")

    def withRebalancing(self):
        raise WindFlowError("a Source has no input to rebalance")

    def build(self) -> Source:
        return Source(self._gen_fn, name=self._name,
                      parallelism=self._parallelism,
                      output_batch_size=self._output_batch_size,
                      ts_extractor=self._ts_extractor,
                      record_spec=self._record_spec)


class DeviceSource_Builder(_BuilderBase):
    """Source whose batches are generated ON DEVICE by a jitted program —
    no host staging on the hot path (io/device_source.py; the reference
    has no analogue: its GPU sources stage host tuples,
    ``batch_gpu_t.hpp:51-229``).  ``batch_fn(i)`` is JAX-traceable,
    int32 batch index -> payload pytree of [capacity] leaves."""

    _default_name = "device_source"

    def __init__(self, batch_fn: Callable) -> None:
        super().__init__()
        self._batch_fn = batch_fn
        self._capacity = 0
        self._n_batches = 0
        self._ts_fn = None
        self._wm_fn = None
        self._ts_bounds_fn = None

    def withCapacity(self, n: int):
        """Lanes per generated batch (the compiled batch shape)."""
        self._capacity = n
        return self

    def withNumBatches(self, n: int):
        """Total batches across all replicas (replicas stride the index)."""
        self._n_batches = n
        return self

    def withTimestampFn(self, ts_fn: Callable, wm_fn: Callable[[int], int]):
        """EVENT time: ``ts_fn(i) -> int64[capacity]`` device lane (traced
        into the generator program) + ``wm_fn(i) -> int`` host frontier —
        the host never reads device lanes back to learn time."""
        self._ts_fn = ts_fn
        self._wm_fn = wm_fn
        return self

    def withTimestampBounds(self, ts_bounds_fn: Callable):
        """HOST fn ``i -> (ts_min, ts_max)`` bounding batch ``i``'s event
        timestamps: attaches the data-ts extrema that let downstream TB
        window rings size themselves preemptively without a device sync
        (DeviceBatch.ts_min/ts_max; EVENT time only)."""
        self._ts_bounds_fn = ts_bounds_fn
        return self

    def withKeyBy(self, *_):
        raise WindFlowError("a Source has no input to key by")

    def withRebalancing(self):
        raise WindFlowError("a Source has no input to rebalance")

    def withOutputBatchSize(self, n: int):
        raise WindFlowError(
            "DeviceSource batch size IS its capacity (withCapacity)")

    def build(self):
        from windflow_tpu.io.device_source import DeviceSource
        return DeviceSource(self._batch_fn, self._capacity, self._n_batches,
                            name=self._name, parallelism=self._parallelism,
                            ts_fn=self._ts_fn, wm_fn=self._wm_fn,
                            ts_bounds_fn=self._ts_bounds_fn)


class Map_Builder(_BroadcastMixin, _BuilderBase):
    _default_name = "map"

    def __init__(self, fn: Callable) -> None:
        super().__init__()
        self._fn = fn

    def build(self) -> Map:
        return Map(self._fn, name=self._name, parallelism=self._parallelism,
                   routing=self._routing(),
                   output_batch_size=self._output_batch_size,
                   key_extractor=self._key_extractor)


class Filter_Builder(_BroadcastMixin, _BuilderBase):
    _default_name = "filter"

    def __init__(self, fn: Callable) -> None:
        super().__init__()
        self._fn = fn

    def build(self) -> Filter:
        return Filter(self._fn, name=self._name,
                      parallelism=self._parallelism,
                      routing=self._routing(),
                      output_batch_size=self._output_batch_size,
                      key_extractor=self._key_extractor)


class FlatMap_Builder(_BroadcastMixin, _BuilderBase):
    _default_name = "flatmap"

    def __init__(self, fn: Callable) -> None:
        super().__init__()
        self._fn = fn

    def build(self) -> FlatMap:
        return FlatMap(self._fn, name=self._name,
                       parallelism=self._parallelism,
                       routing=self._routing(),
                       output_batch_size=self._output_batch_size,
                       key_extractor=self._key_extractor)


class Reduce_Builder(_BuilderBase):
    _default_name = "reduce"

    def __init__(self, fn: Callable, initial_state: Any) -> None:
        super().__init__()
        self._fn = fn
        self._initial_state = initial_state

    def withRebalancing(self):
        raise WindFlowError(
            "Reduce routes by key (or runs non-replicated); REBALANCING "
            "does not apply")

    def build(self) -> Reduce:
        return Reduce(self._fn, self._initial_state, name=self._name,
                      parallelism=self._parallelism,
                      key_extractor=self._key_extractor,
                      output_batch_size=self._output_batch_size)


class Sink_Builder(_BroadcastMixin, _BuilderBase):
    _default_name = "sink"

    def __init__(self, fn: Callable) -> None:
        super().__init__()
        self._fn = fn
        self._columnar = False
        self._columnar_defer = 2

    def withColumnarSink(self, defer: int = 2):
        """Deliver TPU→Sink batches as SoA numpy columns (``SinkColumns``)
        instead of per-record dicts — one bulk device→host copy, zero
        per-tuple Python (egress twin of the columnar ingest path).
        A batch's copy starts when the sink receives it, and the batch is
        delivered (in receipt order) when the device reports its step
        done: at a later receipt or driver sweep, without a wait.
        ``defer`` bounds how far the callback may trail the stream: only
        while more than ``defer`` batches are in flight does the driver
        wait, and then for the oldest (0 = convert at receipt).  The end
        of the stream delivers everything before ``fn(None)``."""
        self._columnar = True
        self._columnar_defer = defer
        return self

    def build(self) -> Sink:
        return Sink(self._fn, name=self._name, parallelism=self._parallelism,
                    routing=self._routing(),
                    key_extractor=self._key_extractor,
                    columnar=self._columnar,
                    columnar_defer=self._columnar_defer)


# ---------------------------------------------------------------------------
# TPU builders (reference MapGPU_Builder / FilterGPU_Builder /
# ReduceGPU_Builder, builders_gpu.hpp:54-673)
# ---------------------------------------------------------------------------

class _StatefulTPUMixin:
    """Stateful knobs shared by MapTPU/FilterTPU builders (reference:
    stateful ``MapGPU_Builder``/``FilterGPU_Builder`` variants are selected
    by the functor's (tuple, state) signature, ``builders_gpu.hpp:54-673``;
    here the per-key initial state is explicit)."""

    _initial_state = None
    _num_key_slots = 4096
    _dense_keys = False
    _assoc = None

    def withInitialState(self, state):
        """Per-key initial state prototype — switches the operator to the
        stateful keyed path (requires ``withKeyBy``).

        Skew warning: the default stateful kernel applies each key's tuples
        in order via a rank wavefront — a batch whose hottest key holds r
        tuples costs r sequential device steps, so ONE key receiving the
        whole batch degrades to batch-length serialization.  For
        ASSOCIATIVE updates, ``withAssociativeUpdate`` switches to a
        log-depth segmented scan that is immune to skew (see
        ops/tpu_stateful.py)."""
        self._initial_state = state
        return self

    def withNumKeySlots(self, n: int):
        """Capacity of the dense device state table (max distinct keys)."""
        self._num_key_slots = n
        return self

    def withDenseKeys(self):
        """Declare that the key extractor already returns dense slot ids in
        [0, num_key_slots): host-side key interning is skipped, so every
        batch is one fully-asynchronous device program (no per-batch D2H
        sync).  Out-of-range keys are masked invalid, as in FfatWindowsTPU."""
        self._dense_keys = True
        return self

    def withAssociativeUpdate(self, lift, comb, project):
        """Declare the state update associative:
        ``state' = comb(state, lift(record))`` and the output is
        ``project(record, state_including_this_record)`` (for filters,
        project returns the keep bool).  The operator then runs a log-depth
        segmented scan instead of the rank wavefront, so a single hot key
        costs the same as uniform keys.  The plain fn passed to the builder
        is ignored."""
        self._assoc = (lift, comb, project)
        return self


class MapTPU_Builder(_StatefulTPUMixin, _BuilderBase):
    _default_name = "map_tpu"

    def __init__(self, fn: Callable, batch_fn: bool = False) -> None:
        super().__init__()
        self._fn = fn
        self._batch_fn = batch_fn

    def build(self):
        if self._initial_state is not None:
            if self._batch_fn:
                raise WindFlowError(
                    "batch_fn is not supported for stateful MapTPU: the "
                    "stateful function operates per record as "
                    "fn(record, state) -> (record, state)")
            if getattr(self, "_rebalancing", False):
                raise WindFlowError(
                    "stateful TPU operators route by key; REBALANCING "
                    "does not apply")
            return StatefulMapTPU(self._fn, self._initial_state,
                                  name=self._name,
                                  parallelism=self._parallelism,
                                  key_extractor=self._key_extractor,
                                  num_key_slots=self._num_key_slots,
                                  dense_keys=self._dense_keys,
                                  assoc=self._assoc)
        return MapTPU(self._fn, name=self._name,
                      parallelism=self._parallelism,
                      batch_fn=self._batch_fn, routing=self._routing(),
                      key_extractor=self._key_extractor)


class FilterTPU_Builder(_StatefulTPUMixin, _BuilderBase):
    _default_name = "filter_tpu"

    def __init__(self, fn: Callable) -> None:
        super().__init__()
        self._fn = fn

    def build(self):
        if self._initial_state is not None:
            if getattr(self, "_rebalancing", False):
                raise WindFlowError(
                    "stateful TPU operators route by key; REBALANCING "
                    "does not apply")
            return StatefulFilterTPU(self._fn, self._initial_state,
                                     name=self._name,
                                     parallelism=self._parallelism,
                                     key_extractor=self._key_extractor,
                                     num_key_slots=self._num_key_slots,
                                     dense_keys=self._dense_keys,
                                     assoc=self._assoc)
        return FilterTPU(self._fn, name=self._name,
                         parallelism=self._parallelism,
                         routing=self._routing(),
                         key_extractor=self._key_extractor)


class ReduceTPU_Builder(_BuilderBase):
    _default_name = "reduce_tpu"

    def __init__(self, comb: Callable) -> None:
        super().__init__()
        self._comb = comb
        self._max_keys = None
        self._monoid = None

    def withRebalancing(self):
        raise WindFlowError(
            "ReduceTPU routes by key (or reduces globally); REBALANCING "
            "does not apply")

    def withMaxKeys(self, n: int):
        """Bound of the dense key space [0, n).  Required for mesh
        execution (cross-chip partial tables, Config.mesh).  On a single
        chip it is ignored by undeclared reduces (they sort arbitrary
        int32 keys) but, combined with ``withMonoidCombiner``, routes the
        reduce onto the sort-free dense scatter-combine path — keys
        outside [0, n) are then dropped and counted
        (Out_of_range_keys_dropped), the same key-space contract the mesh
        path enforces."""
        self._max_keys = int(n)
        return self

    def withSumCombiner(self):
        """Shorthand for ``withMonoidCombiner("sum")`` (strictly additive:
        ``comb(a, b) == a + b`` on every leaf)."""
        self._monoid = "sum"
        return self

    def withMonoidCombiner(self, kind: str):
        """Declare the combiner a leafwise commutative monoid — ``"sum"``
        (``a + b``), ``"max"`` (``maximum``) or ``"min"`` (``minimum``)
        on every leaf.  On a mesh, the cross-chip combine then rides ONE
        reduce collective (``lax.psum``/``pmax``/``pmin``) instead of
        all_gather + fold; on a single chip, together with
        ``withMaxKeys``, the whole sort + segmented scan is replaced by
        one dense scatter-combine pass.  The declared operation is
        applied without calling ``comb``, so the declaration must match
        the combiner exactly on every leaf (a wrong kind silently
        computes the declared operation).  This includes a record's key
        FIELD: under ``"sum"`` the output's key field is the leafwise
        sum ``key * count`` — route by the key EXTRACTOR and read the
        dense output's position (ascending key order), or prefer
        ``"max"``/``"min"``, which are idempotent and leave a key field
        intact."""
        self._monoid = kind
        return self

    def build(self) -> ReduceTPU:
        return ReduceTPU(self._comb, name=self._name,
                         parallelism=self._parallelism,
                         key_extractor=self._key_extractor,
                         max_keys=self._max_keys, monoid=self._monoid)


# ---------------------------------------------------------------------------
# Window builders (reference Keyed_Windows_Builder / Parallel_Windows_Builder /
# Paned_Windows_Builder / MapReduce_Windows_Builder / Ffat_Windows_Builder /
# Ffat_WindowsGPU_Builder, builders.hpp + builders_gpu.hpp:576)
# ---------------------------------------------------------------------------

from windflow_tpu.basic import WinType  # noqa: E402
from windflow_tpu.meta import _positional_arity  # noqa: E402
from windflow_tpu.windows.engine import WindowSpec  # noqa: E402
from windflow_tpu.windows.ops import (KeyedWindows, MapReduceWindows,  # noqa: E402
                                      PanedWindows, ParallelWindows)
from windflow_tpu.windows.ffat_op import FfatWindows  # noqa: E402
from windflow_tpu.windows.ffat_tpu import FfatWindowsTPU  # noqa: E402
from windflow_tpu.windows.join_tpu import (IntervalJoinPairsTPU,  # noqa: E402
                                           IntervalJoinTPU)
from windflow_tpu.windows.session_tpu import SessionWindowsTPU  # noqa: E402


class _WindowBuilderBase(_BuilderBase):
    def withRebalancing(self):
        raise WindFlowError(
            "window operators route by key / broadcast; REBALANCING does "
            "not apply")

    def __init__(self):
        super().__init__()
        self._win_type = None
        self._win_len = 0
        self._slide = 0
        self._lateness = 0

    def withCBWindows(self, win_len: int, slide: int):
        self._win_type = WinType.CB
        self._win_len, self._slide = int(win_len), int(slide)
        return self

    def withTBWindows(self, win_usec: int, slide_usec: int):
        self._win_type = WinType.TB
        self._win_len, self._slide = int(win_usec), int(slide_usec)
        return self

    def withLateness(self, lateness_usec: int):
        self._lateness = int(lateness_usec)
        return self

    def _spec(self) -> WindowSpec:
        if self._win_type is None:
            raise WindFlowError(
                "window operator needs withCBWindows or withTBWindows")
        if self._win_len <= 0 or self._slide <= 0:
            raise WindFlowError("window length and slide must be > 0")
        return WindowSpec(self._win_type, self._win_len, self._slide,
                          self._lateness)


def _detect_incremental(fn) -> bool:
    """Non-incremental window logic takes the item list (arity 1);
    incremental logic takes (tuple, accumulator) (arity 2) — the Python
    analogue of the reference's type-based dispatch (meta.hpp)."""
    return _positional_arity(fn) == 2


class Keyed_Windows_Builder(_WindowBuilderBase):
    _default_name = "keyed_windows"

    def __init__(self, fn):
        super().__init__()
        self._fn = fn

    def build(self) -> KeyedWindows:
        return KeyedWindows(
            self._fn, self._spec(), name=self._name,
            parallelism=self._parallelism, key_extractor=self._key_extractor,
            incremental=_detect_incremental(self._fn),
            output_batch_size=self._output_batch_size)


class Parallel_Windows_Builder(_WindowBuilderBase):
    _default_name = "parallel_windows"

    def __init__(self, fn):
        super().__init__()
        self._fn = fn

    def build(self) -> ParallelWindows:
        return ParallelWindows(
            self._fn, self._spec(), name=self._name,
            parallelism=self._parallelism, key_extractor=self._key_extractor,
            incremental=_detect_incremental(self._fn),
            output_batch_size=self._output_batch_size)


class Paned_Windows_Builder(_WindowBuilderBase):
    _default_name = "paned_windows"

    def __init__(self, plq_fn, wlq_fn):
        super().__init__()
        self._plq_fn = plq_fn
        self._wlq_fn = wlq_fn
        self._wlq_parallelism = 1

    def withParallelisms(self, plq: int, wlq: int):
        self._parallelism = plq
        self._wlq_parallelism = wlq
        return self

    def build(self) -> PanedWindows:
        return PanedWindows(
            self._plq_fn, self._wlq_fn, self._spec(),
            name=self._name,
            plq_parallelism=self._parallelism,
            wlq_parallelism=self._wlq_parallelism,
            key_extractor=self._key_extractor,
            plq_incremental=_detect_incremental(self._plq_fn),
            wlq_incremental=_detect_incremental(self._wlq_fn),
            output_batch_size=self._output_batch_size)


class MapReduce_Windows_Builder(_WindowBuilderBase):
    _default_name = "mapreduce_windows"

    def __init__(self, map_fn, reduce_fn):
        super().__init__()
        self._map_fn = map_fn
        self._reduce_fn = reduce_fn
        self._reduce_parallelism = 1

    def withParallelisms(self, map_p: int, reduce_p: int):
        self._parallelism = map_p
        self._reduce_parallelism = reduce_p
        return self

    def build(self) -> MapReduceWindows:
        return MapReduceWindows(
            self._map_fn, self._reduce_fn, self._spec(),
            name=self._name,
            map_parallelism=self._parallelism,
            reduce_parallelism=self._reduce_parallelism,
            key_extractor=self._key_extractor,
            map_incremental=_detect_incremental(self._map_fn),
            reduce_incremental=_detect_incremental(self._reduce_fn),
            output_batch_size=self._output_batch_size)


class Ffat_Windows_Builder(_WindowBuilderBase):
    _default_name = "ffat_windows"

    def __init__(self, lift_fn, comb_fn):
        super().__init__()
        self._lift = lift_fn
        self._comb = comb_fn

    def build(self) -> FfatWindows:
        return FfatWindows(
            self._lift, self._comb, self._spec(),
            name=self._name,
            parallelism=self._parallelism, key_extractor=self._key_extractor,
            lateness=self._lateness,
            output_batch_size=self._output_batch_size)


class Ffat_WindowsTPU_Builder(_WindowBuilderBase):
    """Reference ``Ffat_WindowsGPU_Builder`` (builders_gpu.hpp:576); the
    ``withNumWinPerBatch`` knob is unnecessary here — every window a batch
    completes is computed in the one fused program.  Supports both CB
    windows (rank panes) and TB windows (time-quantum panes + watermark
    firing; lateness applies)."""

    _default_name = "ffat_windows_tpu"

    def __init__(self, lift_fn, comb_fn):
        super().__init__()
        self._lift = lift_fn
        self._comb = comb_fn
        self._max_keys = 1
        self._pane_capacity = None
        self._overflow_policy = "drop"
        self._monoid = None
        self._event_time_order = False
        self._tie = None
        self._leading_partials = False

    def withEventTimeOrder(self, tie=None):
        """Count windows only: count a key's rows in the order of their
        EVENT TIME (then ``tie(record)``, an int, for rows of one
        timestamp), not in the order they arrive.  A count window fed by
        a device operator whose rows close where the data says (the
        interval join, the session window) needs it: such an operator
        hands a step's rows over in the order of its own sort and holds
        some back a step.  Rows wait in the state until the watermark
        has passed them; one that arrives older than a watermark already
        acted on is counted (``CB_rows_out_of_order``).  Builds
        :class:`~windflow_tpu.windows.count_ordered_tpu.
        OrderedCountWindowsTPU` (the semantics, whole): a window's row
        also carries the record that ended it (``last``), the state
        keeps a key's last ``win_len - 1`` rows (``win_len`` at most
        256) over ``withMaxKeys(n)``, and the declared-monoid, compacted
        key space, pane capacity and overflow policy options belong to
        the count window in arrival order."""
        self._event_time_order = True
        self._tie = tie
        return self

    def withLeadingPartialWindows(self):
        """With ``withEventTimeOrder``: cut the windows at a key's START
        instead of its end.  A key's first windows fire over the rows
        there are (``withCBWindows(10, 1)``: the fold of its first 1, 2,
        .. 9 rows, then of the last 10: SQL's ``ROWS BETWEEN 9 PRECEDING
        AND CURRENT ROW``), and no incomplete window is flushed at end
        of stream.  Off, the upstream rule stands: the first window
        fires at the ``win_len``-th row and the windows left incomplete
        fire at end of stream."""
        self._leading_partials = True
        return self

    def withMaxKeys(self, n: int):
        """Size of the dense device key space [0, n)."""
        self._max_keys = int(n)
        return self

    def withCompactedKeys(self):
        """ARBITRARY int32 keys via device-side key compaction
        (parallel/compaction.py): the graph build
        attaches a key→dense-slot remap table sized by
        ``Config.key_compaction_slots``, so the dense pane rings work
        without a declared key bound — new keys are admitted at the
        host staging boundary (and from the in-program miss ring at
        reseed cadence); keys beyond the slot budget are masked invalid
        and counted, the operator's existing out-of-range contract.
        Requires ``withKeyBy`` and ``Config.key_compaction`` on; a
        declared ``withMaxKeys`` always beats compaction when the key
        space is actually bounded (preflight WF404 says so)."""
        self._max_keys = None
        return self

    def withSumCombiner(self):
        """Declare the combiner leafwise ADDITION (``comb(a, b) == a + b``
        on every leaf — the same strictly-additive contract as
        ReduceTPU_Builder.withSumCombiner, whose mesh path rides
        ``lax.psum``).  Shorthand for ``withMonoidCombiner("sum")`` —
        see there for what the declaration buys and its exactness
        contract (a merely zero-absorbing combiner like max must declare
        its OWN kind, never "sum")."""
        self._monoid = "sum"
        return self

    def withMonoidCombiner(self, kind: str):
        """Declare the combiner a leafwise commutative monoid —
        ``"sum"`` (``a + b``), ``"max"`` (``maximum(a, b)``) or ``"min"``
        (``minimum(a, b)``) on every leaf.  Count-based windows then run
        a flagless sliding fold with half the operand traffic AND, under
        the default ``rank_scatter`` grouping with ``withMaxKeys <=
        4096`` (the bound on the rank table), skip the batch permutation
        entirely — lifts scatter-combine straight into pane cells (for
        "sum", float rounding order may differ from the sequential fold,
        exactly as under psum; max/min are idempotent, so results are
        identical).  Time-based windows gain even more: a TB tuple's
        pane cell is pure timestamp arithmetic, so placement needs no
        grouping at all and the whole sort/segmented-scan machinery
        disappears.  The declaration must match the combiner EXACTLY on
        every leaf — declaring the wrong kind silently computes the
        declared operation instead of the combiner's.  Reference anchor:
        the CUDA FFAT pays its sort/tree for every combiner alike
        (``ffat_replica_gpu.hpp:751,917``); declared monoids are the
        TPU-side win for the common aggregates (sum/count/avg via sum,
        max, min)."""
        self._monoid = kind
        return self

    def withPaneCapacity(self, n: int):
        """TB only: length of the on-device pane ring (window span panes
        plus slack for the time spread of in-flight batches; default
        ``max(2*R, R+64)``)."""
        self._pane_capacity = int(n)
        return self

    def withOverflowPolicy(self, policy: str):
        """TB ring-overflow behavior: ``"drop"`` (default — suppress windows
        that lost data panes, count them in Windows_dropped_on_overflow),
        ``"count"`` (fire them over surviving panes only; wrong aggregates,
        surfaced via Pane_cells_evicted), or ``"error"`` (raise at the next
        host checkpoint)."""
        self._overflow_policy = policy
        return self

    def build(self):
        if self._event_time_order:
            from windflow_tpu.windows.count_ordered_tpu import \
                OrderedCountWindowsTPU
            if self._pane_capacity is not None \
                    or self._overflow_policy != "drop":
                raise WindFlowError(
                    f"'{self._name}': withPaneCapacity / "
                    "withOverflowPolicy size a time window's pane ring; "
                    "withEventTimeOrder builds a count window")
            # a declared monoid is a licence to reorder the fold, which
            # this form never needs: it folds a window's rows in order
            return OrderedCountWindowsTPU(
                self._lift, self._comb, self._spec(),
                max_keys=self._max_keys, name=self._name,
                parallelism=self._parallelism,
                key_extractor=self._key_extractor, tie=self._tie,
                leading_partials=self._leading_partials)
        if self._leading_partials:
            raise WindFlowError(
                f"'{self._name}': withLeadingPartialWindows belongs to "
                "the count window in event-time order "
                "(withEventTimeOrder): the pane form in arrival order "
                "fires a window once it is full")
        return FfatWindowsTPU(
            self._lift, self._comb, self._spec(), max_keys=self._max_keys,
            name=self._name,
            parallelism=self._parallelism,
            key_extractor=self._key_extractor,
            pane_capacity=self._pane_capacity,
            overflow_policy=self._overflow_policy,
            monoid=self._monoid)


class Session_WindowsTPU_Builder(_BuilderBase):
    """Session windows per key on the device
    (:class:`~windflow_tpu.windows.session_tpu.SessionWindowsTPU`): a
    window's boundaries come from the data, a maximal run of a key's
    tuples each less than the gap after the last.  ``lift`` maps a record
    to an aggregate, ``comb`` folds two (associative; applied to whole
    lanes, as the FFAT combiners are)."""

    _default_name = "session_windows_tpu"

    def __init__(self, lift_fn, comb_fn):
        super().__init__()
        self._lift = lift_fn
        self._comb = comb_fn
        self._gap = None
        self._max_keys = 1
        self._lateness = 0

    def withRebalancing(self):
        raise WindFlowError(
            "window operators route by key / broadcast; REBALANCING does "
            "not apply")

    def withGap(self, gap_usec: int):
        """The session gap in event-time microseconds: a tuple less than
        this after the previous one of its key extends the session, one
        exactly this much later starts the next."""
        self._gap = int(gap_usec)
        return self

    def withMaxKeys(self, n: int):
        """Size of the dense device key space [0, n); keys outside it are
        masked invalid."""
        self._max_keys = int(n)
        return self

    def withLateness(self, lateness_usec: int):
        """Sessions close ``lateness_usec`` after the watermark passes
        their end, and a tuple is late once it is older than the
        watermark by more than this."""
        self._lateness = int(lateness_usec)
        return self

    def build(self) -> SessionWindowsTPU:
        if self._gap is None:
            raise WindFlowError("session windows need withGap(usec)")
        return SessionWindowsTPU(
            self._lift, self._comb, self._gap, max_keys=self._max_keys,
            name=self._name, parallelism=self._parallelism,
            key_extractor=self._key_extractor, lateness=self._lateness)


class Interval_JoinTPU_Builder(_BuilderBase):
    """Keyed interval join on the device
    (:class:`~windflow_tpu.windows.join_tpu.IntervalJoinTPU` and
    :class:`~windflow_tpu.windows.join_tpu.IntervalJoinPairsTPU`), in two
    forms.

    **One row a build row** (``Interval_JoinTPU_Builder(lift, comb)`` +
    ``withIntervalLength``): the build rows of a stream each open an
    interval of event time on their key, the probe rows are matched to
    the build row of their key that is open at their time, and one row
    leaves a build row when the watermark passes its end.  ``lift(build,
    probe, ts)`` maps a matched pair (and the probe's event time) to an
    aggregate, ``comb`` folds two (associative, any record; applied to
    whole lanes, as the FFAT combiners are).

    **One row a matched pair** (``Interval_JoinTPU_Builder(join)`` +
    ``withBoundaries(lower, upper)``, ``withMaxKeys``,
    ``withProbeCapacity``): a build row at ``t`` is retained, by key,
    for the probes with ``t - lower <= u < t + upper``; every such probe
    that ``withMatch`` lets through leaves as ``join(build, probe, u)``
    in the step in which both are known, and a probe that came before its
    build row waits for it no longer than ``lower``."""

    _default_name = "interval_join_tpu"

    def __init__(self, lift_or_join_fn, comb_fn=None):
        super().__init__()
        self._lift = lift_or_join_fn
        self._comb = comb_fn
        self._build_side = None
        self._length = None
        self._match = None
        self._capacity = None
        self._out_capacity = None
        self._boundaries = None
        self._max_keys = None
        self._probe_capacity = None
        self._lateness = 0

    def withRebalancing(self):
        raise WindFlowError(
            "the join routes by key; REBALANCING does not apply")

    def withBuildSide(self, fn):
        """``fn(row) -> bool``: True on the rows that open an interval
        (the build side), False on those matched to one (the probes)."""
        self._build_side = fn
        return self

    def withIntervalLength(self, fn):
        """``fn(build row) -> int``: the interval's length in event-time
        microseconds; the row's interval is ``[ts, ts + length)`` (the
        form that folds)."""
        self._length = fn
        return self

    def withBoundaries(self, lower_usec: int, upper_usec: int):
        """Static bounds of the form that emits pairs (the reference's
        ``Interval_Join`` ``withBoundaries``): a build row at ``t``
        meets the probes with ``t - lower <= u < t + upper``; it is
        retained for ``upper`` of event time, and a probe that came
        first waits for it for ``lower``."""
        self._boundaries = (int(lower_usec), int(upper_usec))
        return self

    def withMaxKeys(self, n: int):
        """Keys of the pair form are int32 in ``[0, n)``: its retained
        build rows are dense over them, one a key."""
        self._max_keys = int(n)
        return self

    def withProbeCapacity(self, n: int):
        """Probes the pair form holds at once while they wait for their
        build row; a step that would keep more stops the graph with an
        error."""
        self._probe_capacity = int(n)
        return self

    def withMatch(self, fn):
        """``fn(build row, probe row) -> bool``: a probe inside the
        interval matches only where this holds (default: always)."""
        self._match = fn
        return self

    def withBuildCapacity(self, n: int):
        """Build rows the state holds open at once (the carry's lanes of
        the form that folds); a step that would keep more stops the
        graph with an error."""
        self._capacity = int(n)
        return self

    def withOutputCapacity(self, n: int):
        """Lanes of the batch a step hands on (default: the input
        batch's): rows beyond them wait in the state, so ``n`` is at
        least what one batch closes (or pairs) on average."""
        self._out_capacity = int(n)
        return self

    def withLateness(self, lateness_usec: int):
        """Build rows close (are evicted) ``lateness_usec`` after the
        watermark passes their end, and a row is late once it is older
        than the watermark by more than this."""
        self._lateness = int(lateness_usec)
        return self

    def build(self):
        name = self._name
        common = dict(
            build_side=self._build_side, match=self._match,
            key_extractor=self._key_extractor,
            out_capacity=self._out_capacity, name=name,
            parallelism=self._parallelism, lateness=self._lateness)
        folds = (self._length, self._capacity)
        pairs = (self._boundaries, self._max_keys, self._probe_capacity)
        if self._comb is None:
            if any(x is not None for x in folds):
                raise WindFlowError(
                    f"IntervalJoinTPU '{name}': a join function alone "
                    "emits a row a matched pair; withIntervalLength and "
                    "withBuildCapacity belong to the form that folds "
                    "(built with lift and comb)")
            return IntervalJoinPairsTPU(
                self._lift, boundaries=self._boundaries,
                max_keys=self._max_keys,
                probe_capacity=self._probe_capacity, **common)
        if any(x is not None for x in pairs):
            raise WindFlowError(
                f"IntervalJoinTPU '{name}': withBoundaries, withMaxKeys "
                "and withProbeCapacity belong to the form that emits "
                "pairs (built with a join function alone)")
        return IntervalJoinTPU(self._lift, self._comb, length=self._length,
                               build_capacity=self._capacity, **common)


from windflow_tpu.windows.rolling_kernels import DistinctGroup  # noqa: E402
from windflow_tpu.windows.rolling_tpu import RollingAggregateTPU  # noqa: E402


class Rolling_AggregateTPU_Builder(_BuilderBase):
    """A keyed aggregate that never closes, on the device
    (:class:`~windflow_tpu.windows.rolling_tpu.RollingAggregateTPU`):
    SQL's ``GROUP BY`` without a window, one upsert row a group a batch
    touched.  ``lift(record, ts)`` maps a record and its event time
    (int64 usec) to ``{leaf: value}`` and every leaf
    is declared by one of ``withSum`` / ``withMin`` / ``withMax`` (a
    monoid folds it) or ``withDistinct`` (an exact distinct count of the
    member ids the lift gives)."""

    _default_name = "rolling_aggregate_tpu"

    def __init__(self, lift_fn):
        super().__init__()
        self._lift = lift_fn
        self._plain = {}
        self._distinct = []
        self._max_keys = None
        self._out_capacity = None

    def withRebalancing(self):
        raise WindFlowError(
            "a rolling aggregate routes by key; REBALANCING does not apply")

    def _fold(self, kind: str, leaves):
        for leaf in leaves:
            self._plain[leaf] = kind
        return self

    def withSum(self, *leaves: str):
        """These leaves are sums (a count is a sum of ones, a filtered
        count of a 0 / 1 lift); an integer sum is kept as int64."""
        return self._fold("sum", leaves)

    def withMin(self, *leaves: str):
        return self._fold("min", leaves)

    def withMax(self, *leaves: str):
        return self._fold("max", leaves)

    def withDistinct(self, *leaves: str, space: int):
        """These leaves are exact distinct counts over member ids in
        ``[0, space)`` (a negative id: the record adds none).  The leaves
        of one call are filters of ONE member and share a bit table: a
        record's ids among them must agree."""
        self._distinct.append(DistinctGroup(tuple(leaves), int(space)))
        return self

    def withMaxKeys(self, n: int):
        """Size of the dense device key space [0, n); keys outside it are
        refused and counted."""
        self._max_keys = int(n)
        return self

    def withOutputCapacity(self, n: int):
        """Lanes of the batch a step hands on: the groups one batch can
        touch (default ``min(max keys, capacity)`` rounded up to a power
        of two).  A step that touched more stops the graph."""
        self._out_capacity = int(n)
        return self

    def build(self) -> RollingAggregateTPU:
        return RollingAggregateTPU(
            self._lift, plain=self._plain, distinct=self._distinct,
            max_keys=self._max_keys, key_extractor=self._key_extractor,
            out_capacity=self._out_capacity, name=self._name,
            parallelism=self._parallelism)
