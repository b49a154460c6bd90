"""Device-resident sweep megastep: K batch sweeps in ONE compiled program.

PR 13 shrank the bytes and PR 14 the kernels; what bounds the staged
e2e now is the HOST PACER — every batch still pays one Python-driven
dispatch round trip (pack, ship, dispatch, drain), so throughput is
batches/s times whatever the host loop manages, not what the chip can
sustain.  This module lifts the fusion executor's move one level, from
per-sweep to per-K-sweeps (the DrJAX whole-round-as-one-program stance,
arXiv 2403.07128): a ``lax.scan`` over a staged super-batch of K packed
wire buffers whose body is the EXISTING per-sweep program — the shared
unpack decode (``batch.unpack_body``, wire decompression included)
feeding the tail operator's raw step function, extracted from the very
``wf_jit`` wrapper the per-batch path dispatches.  One program, one
host→device super-transfer, one device→host drain per K batches.

Correctness stance — the per-batch path IS the reference semantics:

* The scan body calls the tail's own traced step (``WfJit._fn``), so a
  megastep's K outputs are record-for-record what K per-batch dispatches
  produce.  ``Config.megastep_sweeps = 1`` (the kill switch) never
  builds a plane and restores today's cadence verbatim.
* Warm-up, capacity/treedef/wire-format changes, partial groups at a
  flush (quiesce, EOS, punctuation cadence), and a non-empty tail inbox
  all fall back to the per-batch ship — record-identical by
  construction, so eligibility can be conservative without being wrong.
* A group is only STARTED or CONTINUED while it can still fill before
  the next external flush ships it partial anyway.  The edge measures
  both sides itself on the host clock — the median of its last
  ``HOLD_SAMPLES`` intervals between warm offers, and the distance
  between the last two external drains — and a packet whose group
  would complete after the next drain is due takes the same per-batch
  ship at once (``MegastepEdge._group_can_fill``; counted as
  ``unheld_batches``).  A partial group at a flush is therefore what is
  left of a group that could still have filled when each of its
  packets was queued.
* Step REBUILDS (TB ring regrow, durability restore) are detected by
  wrapper object IDENTITY: the scan cache pins the wrapper it traced
  and recompiles when the operator swapped it.
* Host-side per-batch bookkeeping (watermark advance, TB span regrow,
  flight-recorder spans, stats counters) replays at K-granularity from
  the packet metadata each batch carried — the trace lane stamps
  PER-BATCH timestamps (staged at enqueue, collected/dispatched at the
  megastep, sunk at the sink), so Latency p50/p99 stays honest.

Eligible edges: a single-destination host→device staging edge
(``DeviceStageEmitter``) on a source replica, feeding one replica of a
single-chip, non-compacted FfatWindowsTPU (CB or TB), ReduceTPU
(sorted or dense declared-monoid), or dense-keys stateful map/filter —
fused preludes ride along for free (they live inside the raw step).
Everything else (host operators, host-interning stateful tails,
mesh-sharded state, compacted key spaces) downgrades to per-batch;
preflight surfaces the downgrade as WF608 when the user FORCED K>1
(analysis/preflight.py).

Dispatch accounting: one megastep is ONE registry dispatch
(``megastep.<tail>``) serving K logical batches; the tail replica's
``device_programs_launched`` advances per LOGICAL batch so the sweep
ledger's ``dispatches_per_batch`` honestly reports 1/K
(docs/OBSERVABILITY.md "Megastep in the ledger").
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from windflow_tpu import staging
from windflow_tpu.analysis.hotpath import hot_path
from windflow_tpu.basic import current_time_usecs
from windflow_tpu.batch import WM_NONE, DeviceBatch, unpack_body
from windflow_tpu.monitoring import recorder as flightrec
from windflow_tpu.monitoring.jit_registry import wf_jit

#: default K on real accelerator backends ("auto"); the CPU fallback
#: stays per-batch so the tier-1 suite exercises the verbatim cadence.
#: Not 2: a group of two only breaks even, the K x L super-batch copy
#: costing what the one saved dispatch gives, and the saving compounds
#: from 4 up.  A group that fills puts a K x batch-span floor under
#: latency (the first batch staged waits for the group to fill); a
#: stream too slow to fill one before the next external flush pays no
#: hold at all, since its edge never starts one
#: (``MegastepEdge._group_can_fill``).  K=8 against K=1 has not been
#: A/B'd on the chip (ROADMAP.md queue 1 item 2 (b)).
AUTO_K = 8

#: intervals between warm offers an edge keeps: their median is how fast
#: the host finalizes batches there.  Eight, so that one compile, one
#: ``run()`` or one stalled sweep inside an interval does not flip the
#: hold for the offers after it; the rule waits until it has all eight.
HOLD_SAMPLES = 8


def resolve_megastep(config) -> int:
    """Resolved megastep width K from ``Config.megastep_sweeps`` /
    ``WF_TPU_MEGASTEP``: "auto" → AUTO_K on tpu/gpu backends and 1 on
    the CPU fallback; an explicit integer forces that K anywhere
    (including CPU — the tests' A/B lever); K <= 1 is the kill
    switch."""
    raw = getattr(config, "megastep_sweeps", "auto")
    if raw is None:
        raw = "auto"
    if isinstance(raw, str):
        s = raw.strip().lower()
        if s in ("", "auto"):
            return AUTO_K if jax.default_backend() in ("tpu", "gpu") else 1
        raw = int(s)
    return max(1, int(raw))


def megastep_forced(config) -> int:
    """The K the user EXPLICITLY forced (> 1), or 0 when the gate is
    "auto"/kill-switch — preflight only warns about ineligible graphs
    when the user asked for a K the graph cannot honor (WF608)."""
    raw = getattr(config, "megastep_sweeps", "auto")
    if raw is None:
        return 0
    if isinstance(raw, str):
        s = raw.strip().lower()
        if s in ("", "auto"):
            return 0
        raw = int(s)
    k = int(raw)
    return k if k > 1 else 0


def tail_kind(op):
    """``(kind, None)`` when ``op`` can tail a megastep scan, else
    ``(None, reason)`` — the reason strings feed the WF608 preflight
    hint.  Kind selects the scan-body adapter (carry layout + raw step
    signature)."""
    if not getattr(op, "is_tpu", False):
        return None, "host operator (no device step to fold into a scan)"
    if getattr(op, "mesh", None) is not None:
        return None, "mesh-sharded state (per-chip collectives per batch)"
    if getattr(op, "_compactor", None) is not None:
        return None, ("compacted key space (host admission runs per "
                      "batch)")
    if getattr(op, "_fusion_exec", None) is not None:
        return None, ("all-stateless fused segment (no stateful tail "
                      "step to carry)")
    # what is left is about the operator: its own answer
    return op.megastep_tail()


def _raw_fn(wrapper):
    """The undecorated step body behind a ``wf_jit`` wrapper: the
    registry's ``WfJit`` keeps it as ``_fn``; with the watch plane off
    ``wf_jit`` returns plain ``jax.jit`` which exposes
    ``__wrapped__``."""
    if wrapper is None:
        return None
    fn = getattr(wrapper, "_fn", None)
    if fn is not None:
        return fn
    return getattr(wrapper, "__wrapped__", None)


class _SpanMeta:
    """Host-metadata stand-in for a DeviceBatch: exactly the fields
    FfatWindowsTPU._regrow_for_span reads (all host stamps, zero device
    syncs)."""

    __slots__ = ("ts_max", "ts_min", "frontier")

    def __init__(self, ts_max, ts_min, frontier):
        self.ts_max = ts_max
        self.ts_min = ts_min
        self.frontier = frontier


class MegastepEdge:
    """One eligible staging edge: the per-edge packet queue, the cached
    scan program, and the drain that replays per-batch bookkeeping.

    The feeding ``DeviceStageEmitter`` offers every finalized packed
    batch here (``offer``); acceptance queues it and the K-th packet
    runs the megastep.  Refusal (tail cold, a group that cannot fill
    before the next external drain), a signature change mid-group and
    ``external_drain`` (the emitter's flush: quiesce, EOS, punctuation)
    ship per-batch through the emitter's verbatim path — so durability
    epochs land on megastep boundaries and partial groups stay
    record-identical."""

    def __init__(self, k: int, op, rep, emitter, kind: str) -> None:
        self.k = k
        self.op = op
        self.rep = rep          # the tail operator's single replica
        self.emitter = emitter  # the feeding DeviceStageEmitter
        self.kind = kind
        self._q = []
        # scan-program cache: (tail wrapper identity, wire fmt) -> the
        # wf_jit'd scan.  The wrapper ref is STRONG on purpose: object
        # identity is the rebuild signal (regrow/restore swap it), and a
        # GC'd wrapper could otherwise recycle its id
        self._scan_wrapper = None
        self._scan_fmt = None
        self._scan = None
        # counters (plane summary, docs/OBSERVABILITY.md)
        self.megasteps = 0
        self.batches = 0            # logical batches served by scans
        self.fallback_batches = 0   # per-batch ships while warm
        self.warmup_batches = 0     # per-batch ships while cold
        # of the fallback ones: shipped at once because their group
        # could not have filled before the next external drain
        self.unheld_batches = 0
        # what the hold is decided from, all on the host clock (usec):
        # a ring of the last intervals between warm offers with its
        # sorting scratch, and when the last external drain came and how
        # long after the one before it.  0 = not seen yet.
        self._clock = current_time_usecs
        self._last_offer = 0
        self._gaps = [0] * HOLD_SAMPLES
        self._gaps_sorted = [0] * HOLD_SAMPLES
        self._n_gaps = 0
        self._last_drain = 0
        self._drain_period = 0
        # per-packet event-time span accumulation (ts_max - ts_min of the
        # staged lanes): the measured basis of the K x batch-span
        # freshness floor the latency ledger surfaces per edge
        self._span_sum_usec = 0.0
        self._span_n = 0
        # preallocated per-megastep scratch (the @hot_path contract on
        # run(): no per-group allocations).  Refilling per megastep is
        # safe: the previous group's one blocking D2H drain returned
        # before the next run() starts, so the device has consumed the
        # prior H2D of these buffers.
        self._wm_buf = np.empty(k, np.int64)
        self._trace_buf = [None] * k

    # -- eligibility at offer time -------------------------------------------
    def _tail_warm(self, cap: int) -> bool:
        """True once the tail's per-batch path has built everything the
        scan body reuses (capacity pinned, step program traced, state
        initialized, first-batch contract checks done).  Cold tails keep
        the per-batch path — which is exactly the warm-up the per-batch
        path performs."""
        op, kind = self.op, self.kind
        if op._compactor is not None or op.mesh is not None:
            return False    # attached after plane build: stand down
        if kind in ("ffat_cb", "ffat_tb"):
            if op._capacity != cap or op._jit_step is None \
                    or 0 not in op._states:
                return False
            return not (kind == "ffat_tb" and op._payload_zero is None)
        if kind == "reduce_sorted":
            return cap in op._jit_steps
        if kind == "reduce_dense":
            return ("dense", cap) in op._jit_steps
        return cap in op._steps     # stateful dense-keys

    def _wrapper(self, cap: int):
        op, kind = self.op, self.kind
        if kind in ("ffat_cb", "ffat_tb"):
            return op._jit_step
        if kind == "reduce_sorted":
            return op._jit_steps.get(cap)
        if kind == "reduce_dense":
            return op._jit_steps.get(("dense", cap))
        return op._steps.get(cap)

    @staticmethod
    def _sig_match(a, b) -> bool:
        return (a.treedef == b.treedef and a.dtypes == b.dtypes
                and a.capacity == b.capacity and a.fmt == b.fmt
                and a.buf.shape[0] == b.buf.shape[0])

    # -- the hold -----------------------------------------------------------
    @hot_path
    def _offer_interval(self, now: int) -> int:
        """Note a warm offer at ``now``; the median interval between the
        last ``HOLD_SAMPLES`` + 1 of them, 0 until there are that many."""
        last, self._last_offer = self._last_offer, now
        if last:
            self._gaps[self._n_gaps % HOLD_SAMPLES] = now - last
            self._n_gaps += 1
        if self._n_gaps < HOLD_SAMPLES:
            return 0
        s = self._gaps_sorted
        s[:] = self._gaps
        s.sort()
        return (s[HOLD_SAMPLES // 2 - 1] + s[HOLD_SAMPLES // 2]) // 2

    @hot_path
    def _group_can_fill(self, now: int) -> bool:
        """Whether a group holding the packet offered at ``now`` can
        still complete before the next external drain ships it partial:
        it needs ``k - q - 1`` more packets, one a median interval, and
        the drain is due a period after the last.  True as well while
        either quantity is unknown (the first offers after warm-up,
        fewer than two external drains seen): the edge then queues as it
        always did, so a stream whose groups fill never loses its first
        scan."""
        interval = self._offer_interval(now)
        if not interval or not self._drain_period:
            return True
        return now + (self.k - len(self._q) - 1) * interval \
            <= self._last_drain + self._drain_period

    # -- emitter contract ----------------------------------------------------
    @hot_path
    def offer(self, pkt) -> bool:
        """Queue one finalized packed batch.  False → the caller ships
        it per-batch (tail cold, or a group it would wait in cannot fill
        before the next external drain: what is queued is shipped ahead
        of it, FIFO).  A signature change against the queued group
        drains the group per-batch first — a megastep only ever runs K
        same-shaped buffers."""
        if not self._tail_warm(pkt.capacity):
            self.warmup_batches += 1
            return False
        if not self._group_can_fill(self._clock()):
            self.unheld_batches += len(self._q) + 1
            self.fallback_batches += 1      # the caller's ship
            self.drain_remainder()
            return False
        if self._q and not self._sig_match(self._q[0], pkt):
            self.drain_remainder()
        if self.kind == "ffat_tb":
            # TB host prep replays per batch IN ARRIVAL ORDER at enqueue
            # (exactly the per-batch _step preamble): span regrow —
            # which may rebuild the step; the run-time identity check
            # recompiles the scan — the fold flag, and the wm_pane the
            # scan lane carries.
            op = self.op
            front = pkt.frontier if pkt.frontier >= pkt.wm else pkt.wm
            if op._auto_np:
                op._regrow_for_span(
                    _SpanMeta(pkt.ts_max, pkt.ts_min, front))
            if front != WM_NONE:
                op._fold_stepped = True
            pkt.wm_pane = op._wm_pane(front)
        self._q.append(pkt)
        if len(self._q) >= self.k:
            self.run()
        return True

    @hot_path
    def external_drain(self) -> None:
        """The feeding emitter's flush (punctuation cadence, durability
        quiesce, EOS): ship what is queued so that a checkpoint or a
        watermark never overtakes it, and note when it came — the edge
        measures the cadence of these itself, whatever drives them.  A
        drain with no offer since the last (a quiesce's second round, an
        idle stream's punctuation) moves the date and not the period."""
        now = self._clock()
        if self._last_offer > self._last_drain > 0:
            self._drain_period = now - self._last_drain
        self._last_drain = now
        self.drain_remainder()

    @hot_path
    def drain_remainder(self) -> None:
        """Ship every queued packet per-batch (FIFO) through the
        feeding emitter's verbatim path: at an external drain, before a
        packet the edge will not hold, on a signature change, and where
        ``run()`` stands down."""
        q, self._q = self._q, []
        for pkt in q:
            self.fallback_batches += 1
            self.emitter._ship_packed(pkt)

    # -- the megastep itself -------------------------------------------------
    def _scan_for(self, wrapper, pkt):
        if self._scan is not None and self._scan_wrapper is wrapper \
                and self._scan_fmt == pkt.fmt:
            return self._scan
        self._scan = self._build_scan(wrapper, pkt)
        self._scan_wrapper = wrapper
        self._scan_fmt = pkt.fmt
        # direct operator attribute: the sweep ledger's wrapper walk
        # (monitoring/sweep_ledger._op_wrappers) finds it there, so the
        # megastep's dispatch count lands in the tail's ledger row
        self.op._megastep_jit = self._scan
        return self._scan

    def _build_scan(self, wrapper, pkt):
        """ONE wf_jit program: scan the K packed buffers through the
        shared unpack decode + the tail's raw step.  The carry is the
        tail's cross-batch state (pane ring / slot table / drop
        counter); per-batch outputs stack on the scan's ys axis."""
        raw = _raw_fn(wrapper)
        kind = self.kind
        treedef = pkt.treedef
        unpack = unpack_body(pkt.dtypes, pkt.capacity, wire=pkt.fmt)

        def decode(buf):
            cols, ts, valid, _n = unpack(buf)
            return jax.tree.unflatten(treedef, list(cols)), ts, valid

        if kind == "ffat_cb":
            def body(carry, x):
                payload, ts, valid = decode(x["buf"])
                st, out, fired, out_ts = raw(carry, payload, ts, valid)
                return st, (out, out_ts, fired)
        elif kind == "ffat_tb":
            def body(carry, x):
                payload, ts, valid = decode(x["buf"])
                st, out, fired, out_ts, _n_adv = raw(
                    carry, payload, ts, valid, x["wm"])
                return st, (out, out_ts, fired)
        elif kind == "reduce_sorted":
            def body(carry, x):
                payload, ts, valid = decode(x["buf"])
                _keys, out, out_ts, out_valid = raw(None, payload, ts,
                                                    valid)
                return carry, (out, out_ts, out_valid)
        elif kind == "reduce_dense":
            def body(carry, x):
                payload, ts, valid = decode(x["buf"])
                table, ts_t, has, n_drop = raw(None, payload, ts, valid)
                return carry + n_drop, (table, ts_t, has)
        else:   # stateful dense-keys map/filter
            def body(carry, x):
                payload, ts, valid = decode(x["buf"])
                st, out, out_valid = raw(carry, payload, valid, None)
                return st, (out, ts, out_valid)

        def mega(carry, xs):
            return jax.lax.scan(body, carry, xs)

        # state kinds donate the carry exactly like the per-batch steps
        # (ring/table updated in place); the reduce kinds' carries are
        # None or a host-referenced drop scalar — nothing to donate
        donate = (0,) if kind in ("ffat_cb", "ffat_tb", "stateful") \
            else ()
        scan = wf_jit(mega, op_name=f"megastep.{self.op.name}",
                      donate_argnums=donate)
        # the scan holds the tail's step: its compile span says the same
        scan.compile_args = wrapper.compile_args
        return scan

    def _carry_init(self):
        op, kind = self.op, self.kind
        if kind in ("ffat_cb", "ffat_tb"):
            return op._states[0]
        if kind == "stateful":
            return op._state
        if kind == "reduce_dense":
            d = op._mesh_dropped
            return jnp.int64(0) if d is None else d
        return None

    def _commit_carry(self, carry) -> None:
        op, kind = self.op, self.kind
        if kind in ("ffat_cb", "ffat_tb"):
            op._states[0] = carry
        elif kind == "stateful":
            op._state = carry
        elif kind == "reduce_dense":
            op._mesh_dropped = carry

    @hot_path
    def run(self) -> None:
        """Execute one full-K megastep: stack the queued buffers into a
        pooled super-buffer, dispatch the scan, commit the carry, then
        drain the stacked outputs ONCE and emit K per-batch
        DeviceBatches downstream with their original per-batch
        watermark/trace/frontier stamps."""
        if len(self._q) < self.k:
            return
        rep = self.rep
        if rep.inbox or rep.done:
            # warm-up stragglers (or punctuation) still queued in the
            # tail's inbox: running the scan now would overtake them —
            # fall back per-batch, order preserved
            self.drain_remainder()
            return
        wrapper = self._wrapper(self._q[0].capacity)
        raw = _raw_fn(wrapper)
        if raw is None:
            self.drain_remainder()
            return
        group, self._q = self._q, []
        mega = self._scan_for(wrapper, group[0])

        # super-batch staging: ONE pooled K*L host buffer, ONE H2D
        nwords = group[0].buf.shape[0]
        pool = group[0].pool
        seq0 = group[0].seq     # the group's spans carry its first batch
        with flightrec.span("wf.h2d", batch=seq0,
                            bytes=self.k * nwords * 4):
            sup = pool.acquire(self.k * nwords)
            for i, p in enumerate(group):
                sup[i * nwords:(i + 1) * nwords] = p.buf
                p.pool.release(p.buf, None)     # host copy done, no gate
            xs = {"buf": jax.device_put(sup.reshape(self.k, nwords))}
            if self.kind == "ffat_tb":
                for i, p in enumerate(group):
                    self._wm_buf[i] = p.wm_pane
                xs["wm"] = jax.device_put(self._wm_buf)

        # trace lane, per batch at GROUP times: collected+dispatched when
        # the scan actually launches (so emitted->dispatched measures each
        # batch's real K-wait) and device_done when the one blocking D2H
        # drain returns.  Both stamps are shared by the whole K-group, so
        # they carry shared_k=K — the latency ledger keeps the wall value
        # (each batch truly waited) but divides device-busy credit by K
        # instead of smearing the group's compute onto every batch.
        ring = self.rep.ring
        traced = self._trace_buf      # preallocated: no per-group list
        n_traced = 0
        if ring is not None:
            for p in group:
                if p.trace is not None:
                    traced[n_traced] = p.trace
                    n_traced += 1
        if n_traced:
            t_disp = current_time_usecs()
            for idx in range(n_traced):
                tr = traced[idx]
                ring.record(tr[0], flightrec.COLLECTED, t_disp,
                            shared=self.k)
                ring.record(tr[0], flightrec.DISPATCHED, t_disp,
                            shared=self.k)
        with flightrec.span("wf.dispatch", op=f"megastep.{self.kind}",
                            batch=seq0, k=self.k):
            carry, ys = mega(self._carry_init(), xs)
        # the ONE blocking D2H per megastep: materialize the stacked
        # outputs; per-batch slices below are zero-copy numpy views
        with flightrec.span("wf.megastep.drain", batch=seq0, k=self.k):
            host = jax.tree.map(np.asarray, ys)
        if n_traced:
            t_done = current_time_usecs()
            for idx in range(n_traced):
                ring.record(traced[idx][0], flightrec.DEVICE_DONE,
                            t_done, shared=self.k)
        pool.release(sup, None)     # outputs ready => device read it
        self._commit_carry(carry)
        self.megasteps += 1
        self.batches += self.k
        for p in group:
            if p.ts_max is not None and p.ts_min is not None \
                    and p.ts_max >= p.ts_min > 0:
                self._span_sum_usec += p.ts_max - p.ts_min
                self._span_n += 1

        self._emit(group, host)
        self._post_hooks()

    @hot_path
    def _emit(self, group, host) -> None:
        """Per-batch honesty at drain: each of the K logical batches
        advances the tail replica's watermark, counters, and trace
        spans exactly as its own dispatch would, then rides the tail's
        emitter downstream (the sink stamps SUNK + e2e per batch)."""
        rep, op, kind = self.rep, self.op, self.kind
        lat = rep.latency
        windowed = kind in ("ffat_cb", "ffat_tb")
        fused = op._fused_prelude is not None
        filt = bool(getattr(op, "_is_filter", False))
        for i, p in enumerate(group):
            staging.device_bytes.note(p.nbytes, p.logical_nbytes)
            rep._advance_wm(p.wm)
            rep.stats.inputs_received += p.n
            tr = p.trace
            # collected/dispatched/device_done stamped at group times in
            # run() (shared_k=K); here only the freshness gauge fires —
            # ts_i/valid_i are already host numpy from the one drain, so
            # fire-time minus window-close costs zero extra syncs
            pay = jax.tree.map(lambda a: a[i], host[0])
            ts_i = host[1][i]
            valid_i = host[2][i]
            if lat is not None and windowed and tr is not None:
                lat.note_window_fire(op.name, ts_i, valid_i)
            front = p.frontier if p.frontier >= p.wm else p.wm
            if kind in ("ffat_cb", "ffat_tb"):
                out = DeviceBatch(pay, ts_i, valid_i, watermark=p.wm,
                                  size=None)
            elif kind in ("reduce_sorted", "reduce_dense"):
                out = DeviceBatch(pay, ts_i, valid_i, watermark=p.wm,
                                  size=None, frontier=front)
            else:
                size = None if (filt or fused) else p.n
                out = DeviceBatch(pay, ts_i, valid_i, watermark=p.wm,
                                  size=size, frontier=front)
            out.trace = tr
            out.seq = p.seq
            # one LOGICAL batch served: the ledger divides the single
            # megastep dispatch by these to report 1/K honestly
            rep.stats.device_programs_launched += 1
            rep.stats.outputs_sent += out.known_size or 0
            rep.emitter.emit_device_batch(out)
            rep._maybe_hook_wm()

    def _post_hooks(self) -> None:
        """The per-batch cadence checkpoints, replayed once per
        megastep (the cadences are heuristics; crossing them once per K
        batches keeps their guarantees)."""
        op, kind = self.op, self.kind
        if kind == "ffat_tb":
            before = op._overflow_steps
            op._overflow_steps = before + self.k
            if (before + self.k) // 32 > before // 32:
                if op._auto_np:
                    op._maybe_regrow()
                if op.overflow_policy == "error":
                    op._check_overflow()
        elif kind == "reduce_dense":
            op._drop_steps += self.k
            if not op._drop_warned and op._drop_steps % 64 < self.k:
                prev = op._pending_drop
                op._pending_drop = op._mesh_dropped
                if prev is not None:
                    op._maybe_warn_drops(int(prev))

    def freshness_floor_usec(self):
        """The explicit freshness floor a K-group imposes: a batch's
        result cannot leave the device sooner than the K x mean batch
        event-time span it waited to group with (docs/OBSERVABILITY.md
        "Latency plane & SLO"); None before any scanned batch carried
        event-time extrema."""
        if not self._span_n:
            return None
        return round(self.k * self._span_sum_usec / self._span_n, 3)

    def summary(self) -> dict:
        return {
            "operator": self.op.name,
            "kind": self.kind,
            "k": self.k,
            "megasteps": self.megasteps,
            "batches": self.batches,
            "fallback_batches": self.fallback_batches,
            "warmup_batches": self.warmup_batches,
            "unheld_batches": self.unheld_batches,
            "freshness_floor_usec": self.freshness_floor_usec(),
        }


class MegastepPlane:
    """Graph-level view: the resolved K and the eligible edges.  Built
    by PipeGraph._build AFTER wire attach and fusion (both change what
    the staging emitters and tails look like); ``active`` gates the
    driver's K-granular source ticking and the durability epoch
    rounding."""

    def __init__(self, k: int) -> None:
        self.k = k
        self.edges = []

    @property
    def active(self) -> bool:
        return self.k > 1 and bool(self.edges)

    def summary(self) -> dict:
        return {"k": self.k,
                "edges": [e.summary() for e in self.edges]}


def attach_plane(config, source_replicas) -> MegastepPlane:
    """Walk the built graph's source replicas and hook a MegastepEdge
    onto every eligible staging emitter.  Conservative by design:
    anything the edge cannot prove safe stays on the per-batch path
    (auto mode silently; forced K>1 graphs get the WF608 preflight
    warning)."""
    plane = MegastepPlane(resolve_megastep(config))
    if plane.k <= 1:
        return plane
    from windflow_tpu.parallel.emitters import DeviceStageEmitter
    for rep in source_replicas:
        em = rep.emitter
        # exact type: keyed/aligned-mesh staging emitters partition or
        # shard per batch — their inner emitters are NOT single-edge
        if type(em) is not DeviceStageEmitter \
                or getattr(em, "_megastep", None) is not None:
            continue
        if em._stage_target is not None or len(em.dests) != 1:
            continue
        tail, _ch = em.dests[0]
        top = tail.op
        # exactly ONE feeding channel: a merged tail folds watermarks
        # across channels in collector arrival order, which a bypassing
        # drain cannot reproduce
        if tail.num_channels != 1 or top.parallelism != 1:
            continue
        kind, _why = tail_kind(top)
        if kind is None:
            continue
        if tail.emitter is None \
                or not hasattr(tail.emitter, "emit_device_batch"):
            continue
        # the scan's ONE drain copies the stacked outputs to the host,
        # which is where a sink would pull them anyway; a tail that
        # feeds another device operator (a second window stage) would
        # pay that copy for nothing and ship every batch back up
        if any(rep.op.is_tpu
               for rep, _ch in getattr(tail.emitter, "dests", ())):
            continue
        edge = MegastepEdge(plane.k, top, tail, em, kind)
        em._megastep = edge
        plane.edges.append(edge)
    return plane


def round_epoch_to_megastep(config, plane: MegastepPlane) -> Optional[int]:
    """Align the durability epoch cadence to megastep boundaries.

    ``Config.durability_epoch_sweeps`` counts DRIVER sweeps, and under
    an active plane one driver sweep paces K logical batch sweeps
    (PipeGraph._tick_chunk) — left alone, a configured cadence would
    checkpoint K× less data-frequently than the same graph at K=1.  So
    the configured value is read as LOGICAL sweeps, rounded UP to a
    whole number of megasteps, and stored back as driver sweeps
    (``ceil(eps / K)``): every epoch then covers the same stream extent
    it covered per-batch (within one megastep of rounding), and every
    commit's quiesce lands between megasteps — the driver's
    ``on_sweep`` site sits between driver sweeps, which are whole
    megasteps.  Returns the new stored cadence when it changed, else
    None.  Idempotent: re-applying to an already-converted value at
    the same K only shrinks toward 1 and stabilizes there."""
    if not plane.active:
        return None
    eps = getattr(config, "durability_epoch_sweeps", 0) or 0
    if eps <= 0:
        return None
    driver = max(1, (eps + plane.k - 1) // plane.k)
    if driver == eps:
        return None
    config.durability_epoch_sweeps = driver
    return driver
