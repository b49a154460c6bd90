"""Compile watcher: :func:`wf_jit`, a drop-in ``jax.jit`` with telemetry.

The flight recorder (monitoring/recorder.py) made the HOST plane legible;
the ~20 ``jax.jit`` sites across ops/, windows/, parallel/ and the staging
plane stayed a black box: nothing reported how often a program compiled,
how long compilation stalled the driver, or — the #1 silent streaming
killer — when a shape/dtype drift put an operator into a **recompilation
storm** (every batch pays a multi-ms trace+compile instead of a µs cache
hit, and the pipeline's latency SLO dies without a single error).

:func:`wf_jit` wraps ``jax.jit`` and feeds a process-wide
:class:`JitRegistry` (one aggregate entry per ``op_name``, the same
process-scope stance as ``staging.default_pool``):

* **compile count + wall time** — a call whose input signature (pytree
  structure + per-leaf shape/dtype) was never seen by this wrapper is
  timed end to end; the delta is trace+lower+backend-compile (dispatch of
  a cached program is µs — the timing is dominated by the compile).
* **recompile events** — a NEW signature after the wrapper's first
  compile increments the per-op recompile counter and, once per op name,
  raises a ``RuntimeWarning`` naming the op and both signatures.
* **dispatch count + donation audit** — every call bumps the op's
  dispatch counter (one lock-free integer add — the per-hop numerator of
  the sweep ledger, monitoring/sweep_ledger.py), and the first compile
  records which positional args were donated plus how many non-donated
  input leaves match an output leaf shape/dtype — each such leaf is a
  whole-buffer copy donation would elide (the ledger's donation-miss
  tripwire).
* **cost table** — on the first compile of an op name the watcher
  captures XLA cost analysis (FLOPs, bytes accessed) and, in ``compiled``
  mode, the executable's memory footprint.  The module constant
  ``COST_MODE`` is the mode: ``lowered`` (its value) uses the client-side
  ``Lowered.cost_analysis()`` estimate — a few ms, no second backend
  compile; ``compiled`` runs ``lowered.compile().cost_analysis()`` for
  optimized-HLO numbers plus ``memory_analysis()`` (one extra backend
  compile per op name per process: the sweep-ledger tests that need
  optimized-HLO bytes set it on the module); ``off`` disables capture.

Steady-state cost per call (the hot path): one pytree flatten, one
shape/dtype tuple, one set hash-compare — the ``@hot_path`` contract
``tools/wf_lint.py`` enforces on :meth:`WfJit._signature` /
:meth:`WfJit.__call__`.

``PipeGraph.stats()["Device"]`` ships the registry snapshot (see
monitoring/device_metrics.py); ``tools/wf_metrics.py`` and the dashboard
``GET /metrics`` render it in Prometheus text exposition format.
"""

from __future__ import annotations

import threading
import time
import warnings
from typing import Callable, Dict, Optional

import jax

from windflow_tpu.analysis.hotpath import hot_path
from windflow_tpu.monitoring import recorder as flightrec

#: cost-analysis capture mode on an op name's first compile (see module
#: docstring): "lowered" | "compiled" | "off"
COST_MODE = "lowered"


def _leaf_sig(x):
    """Hashable (shape, dtype) of one argument leaf.  Python numeric
    scalars key by TYPE, mirroring ``jax.jit``'s cache: jit traces a
    weak-typed scalar once per dtype, not per value, so keying by value
    would fabricate a recompile (and a storm warning) for every distinct
    int while JAX never re-traces.  str/bytes keep their value — they are
    only legal as static args, where the value IS the cache key."""
    dt = getattr(x, "dtype", None)
    if dt is not None:
        return (getattr(x, "shape", ()), dt)
    if isinstance(x, (str, bytes)):
        return x
    return type(x)


def format_sig(sig) -> str:
    """Human-readable signature for the recompile warning:
    ``f32[4096],i32[4096]``-style, structure elided."""
    if sig is None:
        return "<none>"
    _, leaves = sig
    parts = []
    for leaf in leaves:
        if isinstance(leaf, tuple) and len(leaf) == 2 \
                and isinstance(leaf[0], tuple):
            shape, dt = leaf
            parts.append(f"{dt}[{','.join(str(d) for d in shape)}]")
        elif isinstance(leaf, type):
            parts.append(leaf.__name__)
        else:
            parts.append(repr(leaf))
    return ",".join(parts) if parts else "<no args>"


class OpCompileEntry:
    """Aggregate compile telemetry for one op name (process-wide; several
    wrapper instances — one per operator instance or cached capacity —
    may feed the same entry)."""

    __slots__ = ("op_name", "compiles", "recompiles", "compile_ms_total",
                 "last_compile_ms", "cost", "cost_by_sig", "memory",
                 "warned", "lock", "dispatches", "donation",
                 "donation_attempted", "capture_warned")

    def __init__(self, op_name: str) -> None:
        self.op_name = op_name
        self.compiles = 0
        self.recompiles = 0
        self.compile_ms_total = 0.0
        self.last_compile_ms = 0.0
        self.cost: Optional[dict] = None     # captured on first compile
        #: cost tables per input signature: one op name may compile
        #: genuinely different programs (another graph's operator reusing
        #: the name, a different record structure) — the sweep ledger
        #: attributes each wrapper's dispatches with ITS program's bytes,
        #: not whichever program happened to compile first in the process
        self.cost_by_sig: Dict[object, Optional[dict]] = {}
        #                                      membership doubles as the
        #                                      one-attempt-per-signature
        #                                      claim (None = attempt
        #                                      failed, stays failed)
        self.memory: Optional[dict] = None   # "compiled" mode only
        self.warned = False                  # one-time recompile warning
        self.lock = threading.Lock()
        #: total jitted dispatches through every wrapper feeding this
        #: entry — the per-hop denominator of the sweep ledger
        #: (monitoring/sweep_ledger.py).  Bumped lock-free on the hot path
        #: (a torn concurrent add may undercount by a call; the ledger
        #: reads it at stats cadence, never as an exact invariant).
        self.dispatches = 0
        #: buffer-donation audit captured once, on the first compile:
        #: which positional args were donated, and how many non-donated
        #: input leaves match an output leaf shape/dtype (each one is a
        #: whole-buffer copy XLA could elide with donation — the sweep
        #: ledger's donation-miss tripwire).
        self.donation: Optional[dict] = None
        self.donation_attempted = False
        #: one-time "lowering/cost capture failed" warning (an audit
        #: skip must never be mistaken for an audit pass)
        self.capture_warned = False

    def to_json(self) -> dict:
        return {
            "compiles": self.compiles,
            "recompiles": self.recompiles,
            "compile_ms_total": round(self.compile_ms_total, 3),
            "last_compile_ms": round(self.last_compile_ms, 3),
            "dispatches": self.dispatches,
            "cost": self.cost,
            "memory": self.memory,
            "donation": self.donation,
        }


class JitRegistry:
    """Process-wide op-name → :class:`OpCompileEntry` table."""

    def __init__(self) -> None:
        self._entries: Dict[str, OpCompileEntry] = {}
        self._lock = threading.Lock()

    def entry(self, op_name: str) -> OpCompileEntry:
        with self._lock:
            e = self._entries.get(op_name)
            if e is None:
                e = self._entries[op_name] = OpCompileEntry(op_name)
            return e

    def snapshot(self) -> dict:
        """JSON-serializable per-op table (``stats()["Device"]["jit"]``).
        Ops that never compiled (entry created, no call yet) are skipped."""
        with self._lock:
            entries = dict(self._entries)
        return {name: e.to_json() for name, e in sorted(entries.items())
                if e.compiles or e.recompiles}

    def totals(self) -> dict:
        """Graph-agnostic aggregates (``stats()["Device"]["jit_totals"]``
        and the postmortem bundle's ``jit.json``)."""
        with self._lock:
            entries = tuple(self._entries.values())
        return {
            "ops_compiled": sum(1 for e in entries if e.compiles),
            "compiles": sum(e.compiles for e in entries),
            "recompiles": sum(e.recompiles for e in entries),
            "compile_ms_total": round(sum(e.compile_ms_total
                                          for e in entries), 3),
        }

    def dispatch_counts(self) -> Dict[str, int]:
        """op name -> cumulative jitted dispatches.  The sweep ledger
        snapshots this at graph build and diffs at stats time, so one
        graph's per-hop dispatch counts exclude every earlier graph that
        reused the same op names in this process."""
        with self._lock:
            entries = dict(self._entries)
        return {name: e.dispatches for name, e in entries.items()}

    def reset(self) -> None:
        """Drop every entry (tests).  Live wrappers re-create their entry
        lazily on the next compile; until then their cached dispatch
        counter feeds the detached entry, so dispatch-count tests must
        build fresh operators (fresh wrappers) after a reset."""
        with self._lock:
            self._entries.clear()


_default_registry = JitRegistry()


def default_registry() -> JitRegistry:
    """The process-wide compile registry every :func:`wf_jit` wrapper
    reports into (same sharing stance as ``staging.default_pool``)."""
    return _default_registry


class WfJit:
    """One watched ``jax.jit`` callable.  The seen-signature set is
    per-wrapper (a fresh operator instance compiling its first batch is a
    compile, not a recompile); counters aggregate per op name in the
    process-wide registry."""

    __slots__ = ("op_name", "_jit", "_fn", "_seen", "_last_sig", "_lock",
                 "_entry", "_donate", "dispatches", "cost", "compile_args")

    def __init__(self, fn: Callable, op_name: str, jit_kwargs: dict) -> None:
        self.op_name = op_name
        #: the undecorated traced body — wfverify (analysis/tracecheck.py)
        #: statically analyzes it through this handle
        self._fn = fn
        self._jit = jax.jit(fn, **jit_kwargs)
        self._seen = set()
        self._last_sig = None
        #: per-WRAPPER dispatch count next to the entry's per-NAME total:
        #: the sweep ledger attributes by wrapper so two graphs reusing
        #: one op name never pollute each other's per-hop numbers
        self.dispatches = 0
        #: cost table of THIS wrapper's compiled program (bound from the
        #: entry's per-signature table at compile time — same reason)
        self.cost: Optional[dict] = None
        #: what the owner knows of the program that is about to compile
        #: (a time window's ``placement=``): a callable returning the
        #: extra arguments of the ``wf.compile`` span, read on the cold
        #: path alone
        self.compile_args: Optional[Callable[[], dict]] = None
        # cached so the hot path's dispatch count is one attribute add —
        # no registry lookup per call; refreshed on every compile so a
        # registry reset() re-binds at the next compile
        self._entry = default_registry().entry(op_name)
        da = jit_kwargs.get("donate_argnums", ())
        self._donate = frozenset((da,) if isinstance(da, int) else da)
        # serializes the cold compile path only: replicas of one operator
        # share one wrapper and may first-call concurrently from the host
        # worker pool — without this, both would count a compile and the
        # loser could mint a spurious same-signature "recompile".  The hot path
        # stays lock-free; a racy miss there lands here and re-checks.
        self._lock = threading.Lock()

    # -- hot path ------------------------------------------------------------
    @hot_path
    def _signature(self, args, kwargs):
        """Input signature: pytree structure + per-leaf shape/dtype.  The
        whole per-batch cost of the compile watcher is building this tuple
        and one set hash-compare in :meth:`__call__`."""
        leaves, treedef = jax.tree_util.tree_flatten((args, kwargs))
        return (treedef, tuple(_leaf_sig(x) for x in leaves))

    @hot_path
    def __call__(self, *args, **kwargs):
        # sweep-ledger hook: TWO lock-free integer adds per dispatch —
        # the wrapper's own count (per-hop attribution) and the entry's
        # per-name process total; everything else the ledger reads comes
        # from counters that already exist
        self.dispatches += 1
        self._entry.dispatches += 1
        sig = self._signature(args, kwargs)
        if sig in self._seen:       # hash-compare only: steady state
            return self._jit(*args, **kwargs)
        return self._compile_call(sig, args, kwargs)

    # -- cold path: a compile is happening -----------------------------------
    def _compile_call(self, sig, args, kwargs):
        # the whole cold path holds the calling thread: the wait for a
        # sibling's compile, the cost capture's lowering, trace + compile
        extra = self.compile_args() if self.compile_args else {}
        with flightrec.span("wf.compile", op=self.op_name, **extra), \
                self._lock:
            return self._compile_call_locked(sig, args, kwargs)

    def _compile_call_locked(self, sig, args, kwargs):
        if sig in self._seen:
            # lost the race: another replica thread compiled this
            # signature while we waited — plain cached dispatch (but
            # adopt the winner's cost table for the sweep ledger)
            entry = default_registry().entry(self.op_name)
            with entry.lock:
                self.cost = entry.cost_by_sig.get(sig)
            return self._jit(*args, **kwargs)
        entry = default_registry().entry(self.op_name)
        self._entry = entry     # re-bind after a registry reset()
        is_recompile = bool(self._seen)
        prev_sig = self._last_sig
        with entry.lock:
            capture_cost = sig not in entry.cost_by_sig \
                and COST_MODE != "off"
            if capture_cost:
                entry.cost_by_sig[sig] = None   # claimed: one attempt
                #                                 per (op name, signature),
                #                                 even if the backend
                #                                 fails it
        if capture_cost:
            # BEFORE the dispatch: donated buffers are dead afterwards
            self._capture_cost(entry, sig, args, kwargs)
        with entry.lock:
            # the cost table of THIS wrapper's program (may come from an
            # earlier wrapper that compiled the same signature)
            self.cost = entry.cost_by_sig.get(sig)
        t0 = time.perf_counter()
        out = self._jit(*args, **kwargs)
        dt_ms = (time.perf_counter() - t0) * 1e3
        self._seen.add(sig)
        self._last_sig = sig
        with entry.lock:
            capture_donation = not entry.donation_attempted
            entry.donation_attempted = True
        if capture_donation:
            self._capture_donation(entry, args, kwargs, out)
        warn = False
        with entry.lock:
            entry.compiles += 1
            entry.compile_ms_total += dt_ms
            entry.last_compile_ms = dt_ms
            if is_recompile:
                entry.recompiles += 1
                if not entry.warned:
                    entry.warned = True
                    warn = True
        if warn:
            warnings.warn(
                f"wf_jit('{self.op_name}'): input signature changed from "
                f"[{format_sig(prev_sig)}] to [{format_sig(sig)}] — the "
                "operator recompiled.  A signature that keeps drifting is "
                "a recompilation storm (every batch pays trace+compile "
                "instead of a cache hit); pad batches to a fixed capacity "
                "or split the op per shape.  Counted in "
                'stats()["Device"]["jit"]; warning shown once per op.',
                RuntimeWarning, stacklevel=3)
        return out

    def _capture_cost(self, entry: OpCompileEntry, sig, args,
                      kwargs) -> None:
        """Best-effort XLA cost capture, once per (op name, input
        signature) (module docstring: 'lowered' estimate vs 'compiled'
        optimized-HLO numbers + memory footprint)."""
        cost_src = None
        memory = None
        capture_err: Optional[BaseException] = None
        try:
            lowered = self._jit.lower(*args, **kwargs)
            try:
                # IR auditor (analysis/ir_audit.py): parse this SAME
                # lowering's StableHLO into the process-wide program
                # store — zero extra compiles; one flag check when the
                # WF_TPU_IR_AUDIT kill switch is off
                from windflow_tpu.analysis import ir_audit
                ir_audit.record_lowered(self.op_name, sig, lowered)
            except Exception as e:  # lint: broad-except-ok (audit
                # capture must degrade like cost capture — warn below,
                # never break dispatch or lose the cost table)
                capture_err = e
            if COST_MODE == "compiled":
                compiled = lowered.compile()
                cost_src = compiled.cost_analysis()
                if isinstance(cost_src, (list, tuple)):
                    cost_src = cost_src[0] if cost_src else None
                mem = compiled.memory_analysis()
                if mem is not None:
                    memory = {
                        "argument_bytes":
                            getattr(mem, "argument_size_in_bytes", None),
                        "output_bytes":
                            getattr(mem, "output_size_in_bytes", None),
                        "temp_bytes":
                            getattr(mem, "temp_size_in_bytes", None),
                        "generated_code_bytes":
                            getattr(mem, "generated_code_size_in_bytes",
                                    None),
                    }
            else:
                cost_src = lowered.cost_analysis()
                if isinstance(cost_src, (list, tuple)):
                    cost_src = cost_src[0] if cost_src else None
        except Exception as e:  # lint: broad-except-ok (cost analysis is
            # a best-effort probe of backend-specific AOT APIs — any
            # failure must degrade to "no cost table", never break
            # dispatch)
            cost_src = None
            capture_err = e
        if capture_err is not None:
            # Surface the skip once per op name: a silently-missing cost
            # table / IR record used to be indistinguishable from a
            # program that audited clean.
            warn_capture = False
            with entry.lock:
                if not entry.capture_warned:
                    entry.capture_warned = True
                    warn_capture = True
            if warn_capture:
                warnings.warn(
                    f"wf_jit('{self.op_name}'): lowering capture failed "
                    f"({type(capture_err).__name__}: {capture_err}) — "
                    "this program has no cost table and no IR-audit "
                    "record (jit_registry.COST_MODE="
                    f"{COST_MODE}); wfir reports it as pending, not "
                    "clean.  Warning shown once per op.",
                    RuntimeWarning, stacklevel=2)
        cost = None
        if isinstance(cost_src, dict):
            cost = {"mode": COST_MODE}
            for key, out_key in (("flops", "flops"),
                                 ("bytes accessed", "bytes_accessed"),
                                 ("transcendentals", "transcendentals")):
                v = cost_src.get(key)
                if isinstance(v, (int, float)):
                    cost[out_key] = float(v)
        with entry.lock:
            entry.cost_by_sig[sig] = cost
            if entry.cost is None and cost is not None:
                # the entry-level table (snapshot back-compat)
                # stays first-come; per-program consumers read the
                # signature-keyed table through their wrapper
                entry.cost = cost
                entry.memory = memory
            # a failed capture stays failed: the signature's claim in
            # cost_by_sig stops every later compile of this (op name,
            # signature) from re-paying the probe — in "compiled" mode
            # that would be a whole extra backend compile per compile

    def current_cost(self) -> Optional[dict]:
        """Cost table of this wrapper's compiled program (sweep-ledger
        read path, stats cadence).  Re-reads the entry's per-signature
        table when the bound value is still ``None``: a concurrent first
        compile of the same signature may have claimed the slot before
        its capture finished, leaving this wrapper's compile-time read
        empty."""
        if self.cost is None and self._last_sig is not None:
            with self._entry.lock:
                self.cost = self._entry.cost_by_sig.get(self._last_sig)
        return self.cost

    def _capture_donation(self, entry: OpCompileEntry, args, kwargs,
                          out) -> None:
        """Buffer-donation audit, once per op name on the first compile
        (cold path): count non-donated input leaves whose shape/dtype
        matches an output leaf — each one is a whole-buffer copy XLA
        could elide with ``donate_argnums``/aliasing.  Shape/dtype
        metadata survives donation, so reading it off already-donated
        inputs is safe; everything degrades to ``None`` on failure."""
        try:
            out_pool: dict = {}
            out_bytes = 0
            for leaf in jax.tree_util.tree_leaves(out):
                nb = getattr(leaf, "nbytes", None)
                if nb is None:
                    continue
                out_bytes += int(nb)
                sig = (tuple(getattr(leaf, "shape", ())),
                       str(getattr(leaf, "dtype", None)))
                out_pool[sig] = out_pool.get(sig, 0) + 1
            cand_leaves = 0
            cand_bytes = 0
            arg_bytes = 0
            # kwargs leaves are donation candidates too: jax.jit cannot
            # donate keyword arguments at all
            operands = [(i in self._donate, a) for i, a in enumerate(args)]
            operands += [(False, v) for v in kwargs.values()]
            for donated, a in operands:
                for leaf in jax.tree_util.tree_leaves(a):
                    nb = getattr(leaf, "nbytes", None)
                    if nb is None:
                        continue
                    arg_bytes += int(nb)
                    if donated:
                        continue
                    sig = (tuple(getattr(leaf, "shape", ())),
                           str(getattr(leaf, "dtype", None)))
                    if out_pool.get(sig, 0) > 0:
                        out_pool[sig] -= 1
                        cand_leaves += 1
                        cand_bytes += int(nb)
            donation = {
                "donated_argnums": sorted(self._donate),
                "candidate_leaves": cand_leaves,
                "candidate_bytes": cand_bytes,
                "arg_bytes": arg_bytes,
                "out_bytes": out_bytes,
            }
        except Exception:  # lint: broad-except-ok (the audit walks
            # arbitrary user pytrees right after a compile — any failure
            # must degrade to "no donation table", never break dispatch)
            donation = None
        if donation is not None:
            with entry.lock:
                if entry.donation is None:
                    entry.donation = donation

    # -- AOT passthroughs (parity with jax.jit's stages API) -----------------
    def lower(self, *args, **kwargs):
        return self._jit.lower(*args, **kwargs)


def wf_jit(fn: Optional[Callable] = None, *, op_name: str,
           **jit_kwargs) -> Callable:
    """Drop-in ``jax.jit`` replacement reporting compiles / recompiles /
    compile wall time / first-compile cost into the process-wide
    :class:`JitRegistry` under ``op_name``.  All other keyword arguments
    pass straight through to ``jax.jit`` (``donate_argnums`` etc.).

    Usable both as a call (``step = wf_jit(step_fn, op_name=...)``) and a
    decorator (``@wf_jit(op_name=...)``)."""
    if fn is None:
        return lambda f: wf_jit(f, op_name=op_name, **jit_kwargs)
    return WfJit(fn, op_name, jit_kwargs)
