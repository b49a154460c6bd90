"""Flight recorder: per-batch span tracing + log-bucketed latency histograms.

The reference's observability layer records per-replica counters and service
times (``stats_record.hpp``) — enough when every operator blocks on its own
work.  Here dispatch is asynchronous (JAX enqueues; the chip crunches later),
so a per-operator running average no longer says where a batch spends its
time.  This module adds the missing batch-granular layer:

* **Span events.**  A sampled batch carries a trace id (``HostBatch.trace``
  / ``DeviceBatch.trace`` = ``(trace_id, t_origin_usec)``) from its birth at
  a source emitter or the staging plane all the way to the sink.  Hooks on
  the hot path append ``(trace_id, stage, t)`` records — stages ``staged``,
  ``emitted``, ``dispatched``, ``device_done``, ``collected``, ``sunk`` —
  into a preallocated per-replica **ring buffer** (:class:`ReplicaRing`):
  no allocation, no locking, no syscalls on the hot path; old events are
  overwritten when the ring wraps.

* **Sampling.**  One batch in ``Config.trace_sample_every`` is traced
  (default 64); untraced batches carry ``trace=None`` and every hook
  degenerates to one attribute check.  ``device_done`` additionally calls
  ``block_until_ready`` — a real sync — so it fires only every
  ``Config.trace_device_sync_every``-th *traced* batch (default 8, i.e.
  1 in 512 batches at the default sampling): the recorder's documented
  overhead budget is **< 2%** on the bench chain
  (tests/test_observability.py asserts it with generous slack).

* **Histograms.**  :class:`LatencyHistogram` buckets values by log2 —
  64 buckets cover 1 usec..centuries in constant memory — and reports
  ``p50/p95/p99`` by geometric interpolation inside the bucket, clamped to
  the exact observed ``[min, max]`` (so a single sample reports itself, not
  its bucket's midpoint).  Per-operator service-time histograms live in
  ``StatsRecord``; the staged→sunk end-to-end histogram is fed by sinks
  from the trace lane.

* **Export.**  :func:`chrome_trace_from_events` renders the merged rings as
  Chrome-trace JSON (the ``traceEvents`` array format) loadable in
  ``chrome://tracing`` or Perfetto; ``PipeGraph.dump_trace()`` and
  ``tools/trace_export.py`` wrap it.

* **Layer spans.**  :func:`span` brackets the host's work at every layer
  boundary of a sweep (``wf.sweep``, ``wf.parse``, ``wf.pack``,
  ``wf.wire.encode``, ``wf.h2d``, ``wf.dispatch``, ... — the table in
  docs/OBSERVABILITY.md "Span tracing").  A span is a
  ``jax.profiler.TraceAnnotation``, so under a profiler capture it lands
  on the ``/host:CPU`` plane of the same ``.xplane.pb`` as the device's
  ``XLA Modules`` line, on one clock, with its counts as event stats; an
  inactive profiler formats nothing.  Every span also adds ``count``,
  ``total_ns`` and ``self_ns`` (its duration minus its children's, by a
  per-thread stack) to the recorder's per-thread table
  (``stats()["Layers"]``), the operator's view of the same numbers when
  no profiler runs.  Spans open per sweep, per chunk and per batch,
  never per tuple; the spans of one staged batch share ``batch=``, the
  recorder's batch sequence number (a sampled batch's trace id is that
  number).  Where the thread BLOCKS for the chip the wait is an
  innermost span of its own, named in :data:`WAITS` (:func:`wait`), so
  the self time of the span around it is the host's own work.

* **Device phases.**  :func:`phase` and :func:`operator_scope` are to the
  device's ``XLA Ops`` line what :func:`span` is to the host plane: a
  ``jax.named_scope`` opened while a program is TRACED (once per
  compile, never per batch), so every operation written under it, the
  fusion XLA builds around it and everything inside a ``while`` /
  ``cond`` body opened under it carries ``wf.op.<operator>/wf.<phase>``
  in its HLO ``op_name``, which a profiler capture shows as the event's
  ``tf_op``.  It is metadata only: no operation is added and nothing is
  paid with the profiler off.  :data:`PHASES` is the one place the names
  are declared (docs/OBSERVABILITY.md "Device phases";
  ``benchmark/device_phases.py`` reads them).

When ``Config.flight_recorder`` is off, ``PipeGraph`` binds no recorder at
all: replicas hold ``ring = None`` and emitters ``flight = None``, no root
span is ever opened, and the hot path's only residue is an ``is None``
check per site.
"""

from __future__ import annotations

import contextlib
import itertools
import json
import re
import threading
import time
from typing import Dict, List, Optional

import jax
import numpy as np

from windflow_tpu.analysis import debug_concurrency as _dbg
from windflow_tpu.analysis.hotpath import hot_path
from windflow_tpu.basic import current_time_usecs

#: span stage codes (ring buffers store the code, exports the name)
STAGED = 0      # host rows fixed into a device batch (staging plane)
EMITTED = 1     # host batch formed/shipped by an emitter
DISPATCHED = 2  # device program enqueued for the batch (async!)
DEVICE_DONE = 3  # device results ready (block_until_ready, sampled subset)
COLLECTED = 4   # batch pulled from a replica inbox for processing
SUNK = 5        # batch reached a terminal (sink) replica

STAGE_NAMES = ("staged", "emitted", "dispatched", "device_done",
               "collected", "sunk")

#: span events retained across all replica rings of a graph (split evenly;
#: old events are overwritten when a ring wraps, no allocation)
RING_EVENTS = 65536


class LatencyHistogram:
    """Log2-bucketed latency histogram (microseconds).

    ``add`` costs one ``int.bit_length`` and one array increment — no
    allocation, safe on the hot path.  Percentiles interpolate
    geometrically within the winning bucket and clamp to the observed
    ``[min, max]``, which makes the empty / single-sample / boundary edge
    cases exact (tests/test_observability.py pins them).
    """

    __slots__ = ("counts", "count", "total", "min", "max")

    NBUCKETS = 64

    def __init__(self) -> None:
        self.counts = np.zeros(self.NBUCKETS, np.int64)
        self.count = 0
        self.total = 0.0
        self.min = float("inf")
        self.max = 0.0

    @hot_path
    def add(self, usec: float) -> None:
        if usec < 0:
            usec = 0.0
        # bucket b holds values in [2^(b-1), 2^b); 0 lands in bucket 0
        b = int(usec).bit_length()
        if b >= self.NBUCKETS:
            b = self.NBUCKETS - 1
        self.counts[b] += 1
        self.count += 1
        self.total += usec
        if usec < self.min:
            self.min = usec
        if usec > self.max:
            self.max = usec

    def merge(self, other: "LatencyHistogram") -> "LatencyHistogram":
        self.counts += other.counts
        self.count += other.count
        self.total += other.total
        self.min = min(self.min, other.min)
        self.max = max(self.max, other.max)
        return self

    def mean(self) -> float:
        return self.total / self.count if self.count else 0.0

    def percentile(self, p: float) -> float:
        """Value at quantile ``p`` in [0, 1].  Empty histogram -> 0.0."""
        if self.count == 0:
            return 0.0
        rank = p * self.count
        cum = 0
        for b in range(self.NBUCKETS):
            c = int(self.counts[b])
            if c == 0:
                continue
            if cum + c >= rank:
                lo = 0.0 if b == 0 else float(1 << (b - 1))
                hi = float(1 << b)
                # geometric position of the rank inside this bucket
                frac = (rank - cum) / c
                val = lo + frac * (hi - lo)
                return min(max(val, self.min), self.max)
            cum += c
        return self.max

    def bucket_counts(self) -> list:
        """Nonzero ``[upper_bound_usec, count]`` pairs (bucket ``b`` holds
        values below ``2^b``): the raw series behind the Prometheus
        ``_bucket`` exposition (monitoring/openmetrics.py), where the
        quantile summary below is not enough."""
        return [[float(1 << b) if b else 1.0, int(c)]
                for b, c in enumerate(self.counts.tolist()) if c]

    def quantiles(self) -> dict:
        """The ``p50/p95/p99`` dict shipped by ``StatsRecord.to_json`` and
        ``PipeGraph.stats()`` (empty -> all zeros, count 0); ``sum`` and
        the raw ``buckets`` ride along for the OpenMetrics histogram
        exposition."""
        return {
            "count": self.count,
            "mean": round(self.mean(), 3),
            "p50": round(self.percentile(0.50), 3),
            "p95": round(self.percentile(0.95), 3),
            "p99": round(self.percentile(0.99), 3),
            "max": round(self.max, 3) if self.count else 0.0,
            "sum": round(self.total, 3),
            "buckets": self.bucket_counts(),
        }


class ReplicaRing:
    """Preallocated span-event ring for one replica.

    ``record`` writes three scalars into preallocated numpy arrays at a
    wrapping index — no allocation, no lock.  The driver loop and the host
    worker pool never share a ring (one per replica, and a replica's drain
    is single-threaded by construction), so the lock-free write is safe;
    the monitoring thread reads a possibly-torn snapshot, which is
    acceptable for telemetry (same stance as the lock-free backpressure
    reads, graph/pipegraph.py)."""

    __slots__ = ("op_name", "replica_index", "size", "trace", "stage", "t",
                 "shared_k", "n")

    def __init__(self, op_name: str, replica_index: int, size: int) -> None:
        self.op_name = op_name
        self.replica_index = replica_index
        self.size = max(8, int(size))
        self.trace = np.zeros(self.size, np.int64)
        self.stage = np.zeros(self.size, np.int8)
        self.t = np.zeros(self.size, np.int64)
        # K of the megastep group the event's timestamp is shared with
        # (0 = the stamp is this batch's own).  The latency ledger uses it
        # to divide group-shared device time by K instead of crediting the
        # whole group's compute to every member batch (latency_ledger.py).
        self.shared_k = np.zeros(self.size, np.int16)
        self.n = 0          # total events ever recorded (wraps the index)

    @hot_path
    def record(self, trace_id: int, stage: int, t_usec: int,
               shared: int = 0) -> None:
        if _dbg.ENABLED:
            # the lock-free write is safe ONLY because one thread drains a
            # replica at a time; overlapping record()s are the race the
            # debug mode turns into a diagnostic (context-managed so an
            # exception cannot leave a stale guard entry)
            with _dbg.entry_guard(self, "ReplicaRing.record"):
                return self._record_impl(trace_id, stage, t_usec, shared)
        return self._record_impl(trace_id, stage, t_usec, shared)

    @hot_path
    def _record_impl(self, trace_id: int, stage: int, t_usec: int,
                     shared: int = 0) -> None:
        i = self.n % self.size
        self.trace[i] = trace_id
        self.stage[i] = stage
        self.t[i] = t_usec
        self.shared_k[i] = shared
        self.n += 1

    def events(self) -> List[dict]:
        """Retained events, oldest first (ring order reconstructed)."""
        k = min(self.n, self.size)
        start = self.n % self.size if self.n > self.size else 0
        out = []
        for j in range(k):
            i = (start + j) % self.size
            out.append({
                "op": self.op_name,
                "replica": self.replica_index,
                "trace": int(self.trace[i]),
                "stage": STAGE_NAMES[int(self.stage[i])],
                "t_usec": int(self.t[i]),
                "shared_k": int(self.shared_k[i]),
            })
        return out


#: the innermost open span of each thread (``.top``); a thread that has
#: none is outside every recorded sweep, and :func:`span` is inert there
_open = threading.local()


class _NoSpan:
    """What :func:`span` returns outside a recorded sweep."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def note(self, **counts) -> None:
        pass


_NO_SPAN = _NoSpan()


class _Span:
    """One open layer span: the profiler annotation plus the bookkeeping
    that turns durations into per-name totals and self times."""

    __slots__ = ("table", "name", "parent", "ann", "t0", "child_ns")

    def __init__(self, table: dict, name: str, counts: dict,
                 parent: Optional["_Span"]) -> None:
        self.table = table
        self.name = name
        self.parent = parent
        self.ann = jax.profiler.TraceAnnotation(name, **counts)
        self.child_ns = 0

    def __enter__(self):
        _open.top = self
        self.ann.__enter__()
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        dur = time.perf_counter_ns() - self.t0
        self.ann.__exit__(*exc)
        parent = self.parent
        _open.top = parent
        if parent is not None:
            parent.child_ns += dur
        row = self.table.get(self.name)
        if row is None:
            row = self.table[self.name] = [0, 0, 0]
        row[0] += 1
        row[1] += dur
        row[2] += dur - self.child_ns
        return False

    def note(self, **counts) -> None:
        """Counts known only once the work is done (encoded bytes, rows
        parsed): appended to the open profiler event, nowhere else."""
        self.ann.set_metadata(**counts)


def span(name: str, **counts):
    """Layer span under the innermost span this thread has open, in the
    table of the recorder that opened the outermost one
    (:meth:`FlightRecorder.span`: ``wf.sweep`` on the driver thread, the
    pool's ``wf.drain`` on a worker).  Outside any — the recorder is off,
    or the call is not part of a sweep — it is inert: no annotation is
    constructed, no table written."""
    top = getattr(_open, "top", None)
    if top is None:
        return _NO_SPAN
    return _Span(top.table, name, counts, top)


#: the driver thread's root span (``PipeGraph.step``)
ROOT_SPAN = "wf.sweep"
WAIT_PREFIX = "wf.wait."

#: the one vocabulary of the waits for the chip: span name -> (layer, as
#: BENCHMARK.json names it; what the thread blocks for).  Every blocking
#: read of a device value on the hot path is an innermost span named
#: here, so the self time of the span around it (``wf.dispatch``,
#: ``wf.sink.d2h``, ``wf.drain``, ``wf.pack``) is the host's own work,
#: a thread's blocked time is the sum of these names' self times
#: (``wait_ns`` of :meth:`FlightRecorder.layers`), and an idle gap of
#: the chip under one of them is the link's, not the host's.  The reason
#: is in the NAME: a reader of a capture keeps few spans' stats.
#: :func:`wait` opens the ``wf.wait.*`` ones and refuses any other; the
#: two that stood before the table keep their names and their
#: :func:`span` sites.  docs/OBSERVABILITY.md "Span tracing" carries the
#: same table.
WAITS: Dict[str, tuple] = {
    "wf.wait.held": (
        "fused operator program",
        "once a batch, the rows the step BEFORE held back or lost "
        "(session window, joins, ordered count window, rolling "
        "aggregate): the read that lets the watermark be handed on "
        "blocks until that step has run, so it falls when the chip "
        "gets faster"),
    "wf.wait.flush": (
        "fused operator program",
        "at end of stream, what a flush pass of a window fired, "
        "advanced or still holds (a pass a read: its length goes with "
        "the windows left open, not with a batch's step)"),
    "wf.wait.evicted": (
        "fused operator program",
        "a time window's eviction count under the ``error`` overflow "
        "policy, read a step in 32"),
    "wf.wait.keys": (
        "fused operator program",
        "a stateful map / filter without a declared key space: a "
        "batch's keys and validity on the host, where its new keys are "
        "interned (the wait for what fills the batch, then a small "
        "copy)"),
    "wf.wait.d2h": (
        "egress / sink",
        "an output batch's bytes on the host: the step that fills it, "
        "the pack program and what is left of the copy started at "
        "receipt (with ``waited=0`` on the ``wf.sink.d2h`` around it "
        "the step had run: the rest is the link)"),
    "wf.wait.sync": (
        "driver sweep",
        "the sampled ``block_until_ready`` behind a ``device_done`` "
        "instant of the ring (1 traced batch in "
        "``trace_device_sync_every``)"),
    "wf.pool.wait": (
        "staging: pack, wire encode, H2D",
        "a pooled staging buffer whose transfer the device has not "
        "taken yet (``StagingPool.acquire``, only when it blocks)"),
    "wf.megastep.drain": (
        "megastep (K=8 lax.scan)",
        "the blocking copy of a K-group's outputs"),
}


#: ``wait``'s argument -> the span's name, built once
_WAIT_NAMES = {name[len(WAIT_PREFIX):]: name for name in WAITS
               if name.startswith(WAIT_PREFIX)}


def wait(what: str, **counts):
    """``span("wf.wait.<what>")``: the thread blocks for the chip here.
    ``what`` is one of the ``wf.wait.*`` names of :data:`WAITS`."""
    name = _WAIT_NAMES.get(what)
    if name is None:
        raise ValueError(
            f"{WAIT_PREFIX + what!r} is not a wait for the chip: declare "
            "it in recorder.WAITS (and docs/OBSERVABILITY.md)")
    return span(name, **counts)


# ---------------------------------------------------------------------------
# Device phases: the program's own names on the device's ``XLA Ops`` line
# ---------------------------------------------------------------------------

#: the one vocabulary of device phases: name -> (layer, as BENCHMARK.json
#: names it; what the phase covers).  :func:`phase` refuses any other
#: name; docs/OBSERVABILITY.md "Device phases" carries the same table.
PHASES: Dict[str, tuple] = {
    "wf.unpack": (
        "unpack program (+ wire decode)",
        "re-typing a packed staging buffer into lanes, wire decode "
        "included (staging.unpack, and the same decode in a megastep)"),
    "wf.fn": (
        "fused operator program",
        "the user's functions over a batch: map / filter bodies, key "
        "extractors, window lifts"),
    "wf.group": (
        "fused operator program",
        "bringing a batch into key order: the grouping permutation "
        "(counting sort, sort or the Pallas kernel) and the gathers by it"),
    "wf.order": (
        "fused operator program",
        "a count window in event-time order: the sort of the rows that "
        "waited and the batch's by (released or waiting, key, event "
        "time, tie)"),
    "wf.agg.sort": (
        "fused operator program",
        "a rolling aggregate: a batch's lanes sorted by the word of each "
        "distinct group's table (so by key), the plain leaves riding"),
    "wf.agg.distinct": (
        "fused operator program",
        "a rolling aggregate's sets: a run's bits OR-ed down it, the "
        "run-ends sorted to the front, their words read a chunk of "
        "lanes a trip of one loop a table and written back by one "
        "scatter a table, the new members counted a run"),
    "wf.agg.fold": (
        "fused operator program",
        "a rolling aggregate's plain leaves folded a key and the new "
        "members summed a key, the touched groups' state read, folded "
        "and written"),
    "wf.agg.rows": (
        "fused operator program",
        "a rolling aggregate's upsert rows: each touched group's last "
        "lane compacted to the front of the output batch"),
    "wf.place": (
        "fused operator program",
        "folding a batch into pane cells and merging them into the "
        "window state: contraction, scatters, the segmented scan of an "
        "undeclared combiner"),
    "wf.ring": (
        "fused operator program",
        "upkeep of the window state whether anything fires or not: "
        "rolling the pane ring, eviction, the carried panes of a count "
        "window"),
    "wf.fire": (
        "fused operator program",
        "the sliding fold over the panes, picking and compacting the "
        "fired rows, the end-of-stream flush"),
    "wf.reduce": (
        "fused operator program",
        "a keyed reduce's fold of a batch and its merge into the table"),
    "wf.state": (
        "fused operator program",
        "a stateful map / filter: resolving slots and the per-key "
        "in-order body over the state table"),
    "wf.session.sort": (
        "fused operator program",
        "a session step's sort of its lanes by (key, event time)"),
    "wf.session.scan": (
        "fused operator program",
        "cutting the sorted lanes into runs and folding them"),
    "wf.session.carry": (
        "fused operator program",
        "carrying each key's runs into the key domain (index scatter, "
        "gathers) and merging them with the open sessions"),
    "wf.session.close": (
        "fused operator program",
        "closing what the watermark passed and compacting the rows to "
        "the front of the output batch"),
    "wf.join.sort": (
        "fused operator program",
        "an interval join bringing both sides into (key, event time) "
        "order, the carried build rows included"),
    "wf.join.match": (
        "fused operator program",
        "cutting the ordered lanes into runs (one build row and its "
        "probes), the interval and predicate tests, the segmented fold "
        "of the matched probes (the pair form: the build row handed "
        "down its run, and the sort that brings pairs, waiting probes "
        "and build rows to the front)"),
    "wf.join.carry": (
        "fused operator program",
        "what the join keeps for the next step: the open build rows "
        "gathered into the carry, the counters (the pair form: the "
        "probes that go on waiting for their build row)"),
    "wf.join.close": (
        "fused operator program",
        "picking the build rows that close, ordering them to the front "
        "and gathering the output batch; what does not fit is held back "
        "(the pair form: the pairs a step completed into its output "
        "batch, through the held-back lanes where they do not fit)"),
    "wf.join.table": (
        "fused operator program",
        "the pair form's retained build side: writing a batch's build "
        "rows into the keyed table, looking up the probes whose build "
        "row is not before them in their batch, the validity test that "
        "evicts"),
    "wf.mesh.own": (
        "mesh collectives (ICI)",
        "a key shard counting the lanes it owns and moving them to the "
        "front"),
    "wf.mesh.exchange": (
        "mesh collectives (ICI)",
        "all_gather / psum / all_to_all between the chips of a mesh"),
    "wf.shard.sketch": (
        "mesh collectives (ICI)",
        "the shard plane's key sketch (count-min rows, shard counts)"),
    "wf.egress.pack": (
        "egress / sink",
        "packing an output batch, or the leading lanes of it a columnar "
        "sink asks for, into the one buffer the sink copies"),
}
#: ``wf.op.<operator>``: who, where a phase says what
OP_SCOPE = "wf.op."

# what the tracing thread has open: a program is traced by one thread,
# and ``lax.cond`` / ``while_loop`` / ``vmap`` trace their bodies inside
# the ``with`` that calls them
_traced = threading.local()


@contextlib.contextmanager
def _scope(kind: str, name: str):
    inside = getattr(_traced, kind, None)
    if inside is not None:
        raise ValueError(
            f"device scope {name!r} opened inside {inside!r}: an op_name "
            "holds one operator and, innermost, one phase")
    setattr(_traced, kind, name)
    try:
        with jax.named_scope(name):
            yield
    finally:
        setattr(_traced, kind, None)


def phase(name: str):
    """Device phase ``name`` (one of :data:`PHASES`) around the code that
    writes its operations, as a context manager or a decorator.  Entered
    while a program is traced; phases do not nest in each other."""
    if name not in PHASES:
        raise ValueError(f"{name!r} is not a device phase: declare it in "
                         "recorder.PHASES (and docs/OBSERVABILITY.md)")
    return _scope("phase", name)


def operator_scope(op_name: str):
    """``wf.op.<operator>`` around an operator's part of a device
    program, so that a fused program's device time reads per operator.
    Opened outside the phases, once per operator on a path."""
    if getattr(_traced, "phase", None) is not None:
        raise ValueError(f"operator scope {op_name!r} opened inside the "
                         f"phase {_traced.phase!r}")
    return _scope("op", OP_SCOPE + re.sub(r"[^A-Za-z0-9_.\-]", "_",
                                          op_name))


class FlightRecorder:
    """Graph-scoped recorder: owns the per-replica rings, the trace-id
    counter and the sampling decision.  Built by ``PipeGraph._build`` when
    ``Config.flight_recorder`` is on; replicas and emitters hold direct
    references to their ring (no indirection on the hot path)."""

    def __init__(self, sample_every: int = 64,
                 device_sync_every: int = 8,
                 expected_rings: int = 1) -> None:
        self.sample_every = max(1, int(sample_every))
        self.device_sync_every = max(0, int(device_sync_every))
        self.expected_rings = max(1, int(expected_rings))
        self.rings: List[ReplicaRing] = []
        # itertools.count: __next__ is C-implemented and atomic under the
        # GIL, so concurrently-staging host-pool replicas never mint the
        # same trace id (a plain += would race and alias two batches'
        # spans in the Chrome export)
        self._seq = itertools.count(1)
        self.traces_started = 0
        #: sweep numbers for ``wf.sweep`` (PipeGraph.step)
        self.sweeps = itertools.count(1)
        #: layer-span tables, one per thread that opened a span:
        #: ``{thread ident: {name: [count, total_ns, self_ns]}}``.  Per
        #: thread so that no add races another and a thread's self times
        #: telescope exactly to its outermost spans' total.
        self._layers: Dict[int, dict] = {}

    # -- trace assignment (batch-birth sites: emitters, staging plane) ------
    def next_batch(self) -> int:
        """Sequence number of one new batch: the ``batch=`` its layer
        spans share, and its trace id if it is sampled."""
        return next(self._seq)

    def trace_of(self, seq: int) -> Optional[tuple]:
        """Sampling decision for batch ``seq``: ``(trace_id, t_origin)``
        for the 1-in-N sampled batch, None otherwise."""
        if seq % self.sample_every:
            return None
        self.traces_started += 1
        return (seq, current_time_usecs())

    def maybe_trace(self) -> Optional[tuple]:
        """One counter tick + one modulo when not sampled."""
        return self.trace_of(next(self._seq))

    # -- layer spans ---------------------------------------------------------
    def span(self, name: str, **counts) -> _Span:
        """Open ``name`` in this recorder's table of the calling thread,
        under whatever span the thread has open (none: a root).  The
        module-level :func:`span` serves every site below a root."""
        table = self._layers.setdefault(threading.get_ident(), {})
        return _Span(table, name, counts, getattr(_open, "top", None))

    def layers(self, thread: Optional[int] = None) -> dict:
        """``{name: {"count", "total_ns", "self_ns"}}`` summed over the
        threads that recorded (``stats()["Layers"]``), or of one thread
        (``threading.get_ident()`` of the driver, say).  The ``wf.sweep``
        row also carries ``wait_ns``: the self time, on the threads that
        own sweeps, of every span named in :data:`WAITS`, so that
        ``wait_ns / total_ns`` of that row is the share of its sweeps
        the driver stood blocked on the chip."""
        tables = list(self._layers.values()) if thread is None \
            else [self._layers.get(thread, {})]
        out: Dict[str, dict] = {}
        for table in tables:
            rows = list(table.items())
            for name, (count, total, self_ns) in rows:
                row = out.setdefault(name, {"count": 0, "total_ns": 0,
                                            "self_ns": 0})
                row["count"] += count
                row["total_ns"] += total
                row["self_ns"] += self_ns
            if ROOT_SPAN in table:
                root = out[ROOT_SPAN]
                root["wait_ns"] = root.get("wait_ns", 0) + sum(
                    r[2] for name, r in rows if name in WAITS)
        return out

    # -- ring registry -------------------------------------------------------
    def ring_for(self, op_name: str, replica_index: int) -> ReplicaRing:
        # RING_EVENTS splits evenly over the graph's replicas (the builder
        # passes the replica count), so total retained events stay bounded
        # regardless of graph width; the floor keeps narrow rings useful
        per = max(64, RING_EVENTS // self.expected_rings)
        ring = ReplicaRing(op_name, replica_index, per)
        self.rings.append(ring)
        return ring

    # -- export --------------------------------------------------------------
    def events(self) -> List[dict]:
        ev = [e for ring in self.rings for e in ring.events()]
        ev.sort(key=lambda e: e["t_usec"])
        return ev

    def summary(self) -> dict:
        return {
            "enabled": True,
            "sample_every": self.sample_every,
            "device_sync_every": self.device_sync_every,
            "traces_started": self.traces_started,
            "events_recorded": sum(r.n for r in self.rings),
            "events_retained": sum(min(r.n, r.size) for r in self.rings),
            "rings": len(self.rings),
        }

    def to_chrome_trace(self) -> dict:
        return chrome_trace_from_events(self.events())


def chrome_trace_from_events(events: List[dict],
                             metadata: Optional[dict] = None) -> dict:
    """Render raw span events as Chrome-trace JSON (``traceEvents`` array
    format), loadable in ``chrome://tracing`` and Perfetto.
    ``metadata`` entries are merged into ``otherData``
    (``PipeGraph.dump_trace`` puts the ledger sections there).

    Layout: one *thread* track per ``(op, replica)`` carrying instant
    events for every record, plus one *async* span per traced batch and
    stage pair (``b``/``e`` events keyed by the trace id) so a batch's
    staged→...→sunk journey reads as a nested bar across the pipeline.
    Timestamps are the recorder's wall-clock microseconds
    (``time.time_ns``), NOT the profiler's clock: what the host did
    between two device programs is read from the layer spans inside a
    ``jax.profiler`` capture (:func:`span`), not from this file."""
    trace_events: List[dict] = []
    tids = {}
    for e in events:
        key = (e["op"], e["replica"])
        if key not in tids:
            tids[key] = len(tids)
            trace_events.append({
                "name": "thread_name", "ph": "M", "pid": 1,
                "tid": tids[key],
                "args": {"name": f"{e['op']}[{e['replica']}]"},
            })
    per_trace = {}
    for e in events:
        trace_events.append({
            "name": e["stage"], "ph": "i", "s": "t",
            "ts": e["t_usec"], "pid": 1, "tid": tids[(e["op"],
                                                      e["replica"])],
            "args": {"trace": e["trace"]},
        })
        per_trace.setdefault(e["trace"], []).append(e)
    for trace_id, evs in per_trace.items():
        evs.sort(key=lambda e: e["t_usec"])
        for a, b in zip(evs, evs[1:]):
            span = {"cat": "batch", "id": trace_id, "pid": 1, "tid": 0,
                    "name": f"{a['stage']}→{b['stage']}"}
            trace_events.append(dict(span, ph="b", ts=a["t_usec"]))
            trace_events.append(dict(span, ph="e", ts=b["t_usec"]))
    other = {"source": "windflow_tpu flight recorder", "clock": "wall_usec"}
    if metadata:
        other.update(metadata)
    return {
        "traceEvents": trace_events,
        "displayTimeUnit": "ms",
        "otherData": other,
    }


def write_chrome_trace(events: List[dict], path: str,
                       metadata: Optional[dict] = None) -> str:
    with open(path, "w") as f:
        json.dump(chrome_trace_from_events(events, metadata), f)
    return path
