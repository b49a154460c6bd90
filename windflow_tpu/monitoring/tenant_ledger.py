"""Tenant plane — per-tenant attribution across every PipeGraph in the
process (docs/OBSERVABILITY.md "Tenant plane").

ROADMAP item 2 (multi-tenant serving: N PipeGraphs sharing one mesh
under HBM budgets) needs tenant labels threaded through the ledger /
OpenMetrics / postmortems and per-tenant HBM budgets enforced from
device telemetry.  This module is that measurement plane: a
process-level :class:`TenantLedger` registry that every built graph
joins (``Config.tenant``, default = the app name), attributing — at
monitor/stats cadence, with ZERO per-batch hot-path work —

- per-op jitted **dispatches** from per-wrapper counters (``WfJit``
  instances are per-operator-instance, so two graphs reusing an op
  name never cross-credit; the sweep ledger's baseline-and-diff
  stance),
- **compile wall-ms** from the process jit registry, diffed against a
  per-graph baseline snapshotted at register (per-NAME table, so two
  graphs sharing an op name split ambiguously — documented, and the
  tests use distinct names per tenant),
- **H2D/D2H wire + logical bytes** from the per-replica transfer
  counters (the same counters ``stats()["Bytes_H2D_total"]`` sums, so
  per-tenant attribution sums to the graph totals by construction),
- **resident HBM state bytes** from a guarded, depth-limited walk of
  each operator/replica's instance dict for live device arrays — the
  budget basis (cumulative staged bytes would exceed any budget by
  design; what a tenant *holds* is what a budget constrains),
- modeled **ICI bytes** from the shard ledger and the tenant's
  **latency share** from the latency plane.

``Config.hbm_budget_bytes`` declares a per-tenant budget; *sustained*
overage (``ENTER_AFTER`` consecutive over-budget ticks) enters a
latched ``OVER_BUDGET`` health verdict attributed to the tenant's
heaviest op — the latency plane's SLO_VIOLATED contract applied to
memory (enter / hold while over / clear after ``CLEAR_AFTER``
consecutive under-budget ticks, ``last_verdict`` kept for postmortems).

Kill switch: ``Config.tenant_ledger`` / ``WF_TPU_TENANT_LEDGER=0``.
Off, the graph never registers and every call site keeps exactly one
``is not None`` check (micro-asserted by tests/test_tenant_plane.py).

The section feeds ``stats()["Tenant"]``, the ``wf_tenant_*``
OpenMetrics families, postmortem ``tenant.json`` (wf_doctor renders it
jax-free), ``analysis/tenancy.py`` and ``tools/wf_tenant.py``.
"""

from __future__ import annotations

import threading
import time
import weakref
from collections import deque
from typing import Dict, List, Optional

#: consecutive over-budget ticks before OVER_BUDGET enters ("sustained
#: overage" — one transient spike at stats cadence is not a verdict)
ENTER_AFTER = 2
#: consecutive under-budget ticks before an active verdict clears (the
#: latency ledger's hysteresis constant, applied to memory)
CLEAR_AFTER = 3

#: max recursion depth of the resident-state walk (operator dict →
#: container → state object dict → array covers every shipped op)
_WALK_DEPTH = 4


def _resident_state_bytes(objs, per_obj: Optional[dict] = None) -> int:
    """Sum ``nbytes`` of live device arrays reachable from the instance
    dicts of ``objs`` (operators + replicas), deduplicated by ``id``.

    Device arrays are recognised structurally (``nbytes`` + ``devices``
    attributes — jax arrays on every backend, never numpy).  The walk
    recurses plain containers and object ``__dict__``s to a fixed depth
    and never triggers properties (instance dicts only), so it is safe
    to run against arbitrary operator state at stats cadence."""
    #: id -> the remaining depth the node was last visited with.  A
    #: node first reached through a LONG path (exhausted depth) must be
    #: revisited when a short path reaches it with budget left — a
    #: plain seen-set would let the operator's `replicas` back-reference
    #: truncation-poison the later direct visit of its state dicts.
    seen: Dict[int, int] = {}
    counted = set()   # leaf arrays count once, ever
    total = 0

    def walk(v, depth: int) -> int:
        nonlocal total
        i = id(v)
        if seen.get(i, -1) >= depth:
            return 0
        seen[i] = depth
        got = 0
        if hasattr(v, "nbytes") and hasattr(v, "devices"):
            if i in counted:
                return 0
            counted.add(i)
            try:
                got = int(v.nbytes)
            except Exception:  # lint: broad-except-ok (a deleted buffer
                # raising from .nbytes must not take telemetry down)
                got = 0
            total += got
            return got
        if depth <= 0:
            return 0
        if isinstance(v, dict):
            for x in v.values():
                got += walk(x, depth - 1)
        elif isinstance(v, (list, tuple, set, frozenset, deque)):
            for x in v:
                got += walk(x, depth - 1)
        else:
            d = getattr(v, "__dict__", None)
            if isinstance(d, dict):
                for x in d.values():
                    got += walk(x, depth - 1)
        return got

    for o in objs:
        d = getattr(o, "__dict__", None)
        if not isinstance(d, dict):
            continue
        got = 0
        for v in d.values():
            got += walk(v, _WALK_DEPTH)
        if per_obj is not None:
            name = getattr(o, "name", None)
            if name is not None:
                per_obj[name] = per_obj.get(name, 0) + got
    return total


class _TenantTrack:
    """Per-tenant budget state machine (latency ledger's SLO machine
    with a sustained-entry twist: ``ENTER_AFTER`` consecutive over
    ticks before the verdict enters)."""

    __slots__ = ("tenant", "budget_bytes", "active", "entered", "cleared",
                 "verdict", "last_verdict", "_over_ticks", "_ok_ticks")

    def __init__(self, tenant: str, budget_bytes: int) -> None:
        self.tenant = tenant
        self.budget_bytes = int(budget_bytes)
        self.active = False
        self.entered = 0
        self.cleared = 0
        self.verdict: Optional[dict] = None
        self.last_verdict: Optional[dict] = None
        self._over_ticks = 0
        self._ok_ticks = 0

    def tick(self, hbm_bytes: int, graph: Optional[str],
             heaviest_op: Optional[str]) -> None:
        if self.budget_bytes <= 0:
            return
        over = hbm_bytes > self.budget_bytes
        if over:
            self._over_ticks += 1
            self._ok_ticks = 0
            if self.active or self._over_ticks >= ENTER_AFTER:
                if not self.active:
                    self.active = True
                    self.entered += 1
                self.verdict = {
                    "state": "OVER_BUDGET",
                    "tenant": self.tenant,
                    "hbm_bytes": int(hbm_bytes),
                    "budget_bytes": self.budget_bytes,
                    "overage_bytes": int(hbm_bytes - self.budget_bytes),
                    "graph": graph,
                    "heaviest_op": heaviest_op,
                    "message": (
                        f"tenant '{self.tenant}' holds {int(hbm_bytes)} B "
                        f"resident device state against an HBM budget of "
                        f"{self.budget_bytes} B "
                        f"(+{int(hbm_bytes - self.budget_bytes)} B); "
                        f"heaviest op: {heaviest_op} (graph {graph}) — "
                        "see tools/wf_tenant.py for the shed plan"),
                }
                self.last_verdict = self.verdict
        else:
            self._over_ticks = 0
            if self.active:
                self._ok_ticks += 1
                if self._ok_ticks >= CLEAR_AFTER:
                    self.active = False
                    self.cleared += 1
                    self.verdict = None
                    self._ok_ticks = 0

    def budget_json(self, hbm_bytes: int) -> dict:
        pressure = (round(hbm_bytes / self.budget_bytes, 4)
                    if self.budget_bytes > 0 else None)
        return {
            "budget_bytes": self.budget_bytes,
            "hbm_bytes": int(hbm_bytes),
            "pressure": pressure,
            "active": self.active,
            "entered": self.entered,
            "cleared": self.cleared,
            "verdict": self.verdict,
            "last_verdict": self.last_verdict,
        }


class _GraphEntry:
    """One registered graph: weakref + the attribution baselines taken
    at register (per-wrapper dispatch counters, per-name compile-ms)."""

    __slots__ = ("ref", "name", "tenant", "wbase", "cbase", "frozen")

    def __init__(self, graph, tenant: str) -> None:
        self.ref = weakref.ref(graph)
        self.name = graph.name
        self.tenant = tenant
        from windflow_tpu.monitoring.sweep_ledger import _op_wrappers
        self.wbase: Dict[int, int] = {}
        for op in graph._operators:
            for w in _op_wrappers(op):
                self.wbase[id(w)] = w.dispatches
        from windflow_tpu.monitoring.jit_registry import default_registry
        self.cbase: Dict[str, float] = {
            name: e["compile_ms_total"]
            for name, e in default_registry().snapshot().items()}
        #: final attribution snapshot taken at graph shutdown
        #: (_finalize), so a tenant's history survives its graph
        self.frozen: Optional[dict] = None

    def collect(self) -> Optional[dict]:
        """Per-graph attribution row; ``frozen`` after shutdown, live
        otherwise, ``None`` once the graph object itself is gone and no
        snapshot was frozen."""
        g = self.ref()
        if g is None or self.frozen is not None:
            return self.frozen
        from windflow_tpu.monitoring.sweep_ledger import _op_wrappers
        from windflow_tpu.monitoring.jit_registry import default_registry
        per_op: Dict[str, dict] = {}
        dispatches = 0
        for op in g._operators:
            n = 0
            for w in _op_wrappers(op):
                n += w.dispatches - self.wbase.get(id(w), 0)
            per_op[op.name] = {"dispatches": n}
            dispatches += n
        # compile wall-ms: per-NAME registry diff against the register
        # baseline, credited to the op whose name matches (the health
        # plane's prefix rule).  Two graphs sharing an op name split
        # this ambiguously — per-wrapper compile timing does not exist.
        compile_ms = 0.0
        snap = default_registry().snapshot()
        for op in g._operators:
            ms = 0.0
            for name, e in snap.items():
                if name == op.name or name.startswith(op.name + "."):
                    ms += (e["compile_ms_total"]
                           - self.cbase.get(name, 0.0))
            if ms > 0:
                per_op[op.name]["compile_ms"] = round(ms, 3)
                compile_ms += ms
        # resident device state: the budget basis
        per_obj: Dict[str, int] = {}
        resident = _resident_state_bytes(
            list(g._operators) + list(g._all_replicas), per_obj)
        for name, b in per_obj.items():
            if name in per_op:
                per_op[name]["resident_bytes"] = b
        heaviest = None
        if per_op:
            heaviest = max(
                per_op,
                key=lambda n: (per_op[n].get("resident_bytes", 0),
                               per_op[n]["dispatches"]))
        row = {
            "graph": g.name,
            "tenant": self.tenant,
            "dispatches": dispatches,
            "compile_ms": round(compile_ms, 3),
            "h2d_bytes": sum(r.stats.h2d_bytes for r in g._all_replicas),
            "h2d_logical_bytes": sum(r.stats.h2d_logical_bytes
                                     for r in g._all_replicas),
            "d2h_bytes": sum(r.stats.d2h_bytes for r in g._all_replicas),
            "resident_state_bytes": resident,
            "per_op": per_op,
            "heaviest_op": heaviest,
        }
        # modeled ICI bytes (shard plane) and latency share (latency
        # plane) — both optional planes, both read guarded
        try:
            if g._shard is not None:
                totals = g._shard.section()["totals"]
                row["ici_bytes_per_tuple"] = totals["ici_bytes_per_tuple"]
                # the shard plane's collective model, never a counter —
                # carried so tenant aggregation stays honest about it
                row["ici_provenance"] = totals.get("ici_provenance",
                                                   "modeled")
        except Exception:  # lint: broad-except-ok (optional plane)
            pass
        try:
            if g._latency is not None:
                row["latency_usec_total"] = round(
                    sum(g._latency.segment_totals.values()), 3)
        except Exception:  # lint: broad-except-ok (optional plane)
            pass
        return row


class TenantLedger:
    """Process-level multi-graph tenant registry.  One instance per
    process (:func:`default_ledger`); every graph built with
    ``Config.tenant_ledger`` on registers itself at build and freezes
    its attribution at shutdown."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._graphs: Dict[int, _GraphEntry] = {}   # id(graph) -> entry
        self._tracks: Dict[str, _TenantTrack] = {}  # tenant -> track
        # process staged-bytes baseline: the denominator of the
        # attributed-fraction reconciliation.  staging.device_bytes is
        # cumulative across every graph the process ever ran, so the
        # fraction must be computed over the delta since this ledger
        # first saw an empty registry (or reset()).
        self._staged_base = self._snap_staged()
        self.collects = 0
        self.collect_ms_total = 0.0
        self.last_collect_ms = 0.0
        #: tick throttle: health_tick() forwards every monitor-cadence
        #: call here, and N co-resident graphs each tick at their own
        #: cadence — the wall-clock floor keeps the budget machine's
        #: collect cost at cadence rate no matter how many graphs (or
        #: how hot a stats loop) drive it.  Per-tenant timestamps: one
        #: tenant's tick must not starve another's machine.
        self.tick_min_interval_s = 0.25
        self._last_tick: Dict[str, float] = {}

    @staticmethod
    def _snap_staged() -> dict:
        from windflow_tpu import staging
        db = staging.device_bytes
        return {"staged_bytes_total": db.staged_bytes_total,
                "logical_bytes_total": db.logical_bytes_total,
                "staged_batches_total": db.staged_batches_total}

    # -- registration --------------------------------------------------------
    def register(self, graph, tenant: str,
                 budget_bytes: int = 0) -> "GraphTenantHandle":
        with self._lock:
            if not self._graphs:
                # first graph of this accounting epoch: re-anchor the
                # process staged-bytes baseline so earlier (finished +
                # unregistered) graphs don't dilute the fraction
                self._staged_base = self._snap_staged()
            self._graphs[id(graph)] = _GraphEntry(graph, tenant)
            track = self._tracks.get(tenant)
            if track is None:
                track = self._tracks[tenant] = _TenantTrack(
                    tenant, budget_bytes)
            elif budget_bytes and not track.budget_bytes:
                track.budget_bytes = int(budget_bytes)
            return GraphTenantHandle(self, graph, tenant)

    def freeze(self, graph) -> None:
        """Capture the graph's final attribution (called from
        ``PipeGraph._finalize``) so the tenant roll-up survives the
        graph's replicas being torn down."""
        with self._lock:
            entry = self._graphs.get(id(graph))
        if entry is None or entry.frozen is not None:
            return
        try:
            frozen = entry.collect()
        except Exception:  # lint: broad-except-ok (shutdown telemetry)
            frozen = None
        with self._lock:
            if frozen is not None:
                entry.frozen = frozen

    def reset(self) -> None:
        """Drop every registration and re-anchor the process baselines
        (tests: staged-byte totals are cumulative)."""
        with self._lock:
            self._graphs.clear()
            self._tracks.clear()
            self._staged_base = self._snap_staged()
            self.collects = 0
            self.collect_ms_total = 0.0
            self.last_collect_ms = 0.0

    # -- collection ----------------------------------------------------------
    def _collect_rows(self) -> List[dict]:
        with self._lock:
            entries = list(self._graphs.values())
        rows = []
        for e in entries:
            try:
                row = e.collect()
            except Exception as ex:  # lint: broad-except-ok (one broken
                # graph must not hide every other tenant's numbers)
                row = {"graph": e.name, "tenant": e.tenant,
                       "error": f"{type(ex).__name__}: {ex}"[:200]}
            if row is not None:
                rows.append(row)
        return rows

    def tick(self, tenant: Optional[str] = None,
             force: bool = False) -> None:
        """Advance the budget state machine(s) from a fresh collection
        — called from ``PipeGraph.health_tick()`` at monitor cadence,
        never on the batch path.  Wall-clock throttled per tenant
        (``tick_min_interval_s``) so a hot ``stats()`` loop cannot turn
        cadence work into per-call work; ``force`` bypasses (tests)."""
        now_s = time.monotonic()
        if not force:
            names = ([tenant] if tenant is not None
                     else list(self._tracks))
            if all(now_s - self._last_tick.get(n, 0.0)
                   < self.tick_min_interval_s for n in names):
                return
        with self._lock:
            for n in ([tenant] if tenant is not None
                      else list(self._tracks)):
                self._last_tick[n] = now_s
        t0 = time.perf_counter()
        rows = self._collect_rows()
        by_tenant: Dict[str, List[dict]] = {}
        for r in rows:
            by_tenant.setdefault(r["tenant"], []).append(r)
        with self._lock:
            tracks = dict(self._tracks)
        for name, track in tracks.items():
            if tenant is not None and name != tenant:
                continue
            trows = by_tenant.get(name, [])
            hbm = sum(r.get("resident_state_bytes", 0) for r in trows)
            graph, heaviest = None, None
            best = -1
            for r in trows:
                po = r.get("per_op") or {}
                h = r.get("heaviest_op")
                if h is None:
                    continue
                score = po.get(h, {}).get("resident_bytes", 0)
                if score > best:
                    best, graph, heaviest = score, r["graph"], h
            track.tick(hbm, graph, heaviest)
        dt = (time.perf_counter() - t0) * 1000.0
        self.collects += 1
        self.collect_ms_total += dt
        self.last_collect_ms = dt

    def verdict_for(self, graph_name: str) -> Optional[dict]:
        """The active OVER_BUDGET verdict whose heaviest op lives in
        ``graph_name`` — the one graph whose health plane paints the
        verdict (the latency plane's dominant-op contract)."""
        with self._lock:
            tracks = list(self._tracks.values())
        for t in tracks:
            v = t.verdict
            if t.active and v is not None and v.get("graph") == graph_name:
                return v
        return None

    # -- export --------------------------------------------------------------
    def section(self, focus_graph: Optional[str] = None,
                focus_tenant: Optional[str] = None) -> dict:
        """The ``stats()["Tenant"]`` payload — also the postmortem
        ``tenant.json`` body and the input contract of
        ``analysis/tenancy.py`` / ``tools/wf_tenant.py``.  The whole
        process table is reported from every graph: any one tenant's
        stats dump is enough for the advisor to plan across tenants."""
        t0 = time.perf_counter()
        rows = self._collect_rows()
        by_tenant: Dict[str, List[dict]] = {}
        for r in rows:
            by_tenant.setdefault(r["tenant"], []).append(r)
        total_latency = sum(r.get("latency_usec_total", 0.0) for r in rows)
        tenants: Dict[str, dict] = {}
        with self._lock:
            tracks = dict(self._tracks)
        for name in sorted(by_tenant):
            trows = by_tenant[name]
            agg = {
                "graphs": sorted(r["graph"] for r in trows),
                "dispatches": sum(r.get("dispatches", 0) for r in trows),
                "compile_ms": round(sum(r.get("compile_ms", 0.0)
                                        for r in trows), 3),
                "h2d_bytes": sum(r.get("h2d_bytes", 0) for r in trows),
                "h2d_logical_bytes": sum(r.get("h2d_logical_bytes", 0)
                                         for r in trows),
                "d2h_bytes": sum(r.get("d2h_bytes", 0) for r in trows),
                "resident_state_bytes": sum(
                    r.get("resident_state_bytes", 0) for r in trows),
                "ici_bytes_per_tuple": round(
                    sum(r.get("ici_bytes_per_tuple", 0.0)
                        for r in trows), 2),
                # the summed ICI column is the shard plane's structural
                # model in every contributing graph (calibration.py
                # vocabulary; the time column's bandwidth may still be
                # calibrated — see stats()["Shard"] totals)
                "ici_provenance": next(
                    (r["ici_provenance"] for r in trows
                     if "ici_provenance" in r), None),
                "latency_usec_total": round(
                    sum(r.get("latency_usec_total", 0.0)
                        for r in trows), 3),
            }
            agg["latency_share"] = (
                round(agg["latency_usec_total"] / total_latency, 4)
                if total_latency > 0 else None)
            per_op: Dict[str, dict] = {}
            for r in trows:
                for op, d in (r.get("per_op") or {}).items():
                    cur = per_op.setdefault(
                        op, {"dispatches": 0, "graph": r["graph"]})
                    cur["dispatches"] += d.get("dispatches", 0)
                    if "resident_bytes" in d:
                        cur["resident_bytes"] = (
                            cur.get("resident_bytes", 0)
                            + d["resident_bytes"])
                    if "compile_ms" in d:
                        cur["compile_ms"] = round(
                            cur.get("compile_ms", 0.0) + d["compile_ms"],
                            3)
            agg["per_op"] = per_op
            agg["heaviest_op"] = (max(
                per_op, key=lambda n: (per_op[n].get("resident_bytes", 0),
                                       per_op[n]["dispatches"]))
                if per_op else None)
            track = tracks.get(name)
            if track is not None:
                agg["budget"] = track.budget_json(
                    agg["resident_state_bytes"])
            tenants[name] = agg
        # reconciliation: tenants' attributed staged (H2D wire) bytes
        # over the process staged-transfer delta since the baseline —
        # the CI-gated hbm_attributed_fraction (>= 0.9)
        staged_now = self._snap_staged()
        process_delta = (staged_now["staged_bytes_total"]
                         - self._staged_base["staged_bytes_total"])
        tenants_total = sum(t["h2d_bytes"] for t in tenants.values())
        dt = (time.perf_counter() - t0) * 1000.0
        self.collect_ms_total += dt
        self.last_collect_ms = dt
        out = {
            "enabled": True,
            "tenants": tenants,
            "attributed": {
                "staged_bytes_tenants_total": tenants_total,
                "staged_bytes_process_total": process_delta,
                "staged_fraction": (
                    round(tenants_total / process_delta, 4)
                    if process_delta > 0 else None),
            },
            "overhead": {
                "collects": self.collects,
                "collect_ms_total": round(self.collect_ms_total, 3),
                "last_collect_ms": round(self.last_collect_ms, 3),
            },
        }
        if focus_graph is not None:
            for r in rows:
                if r["graph"] == focus_graph:
                    out["graph"] = r
                    break
        if focus_tenant is not None:
            out["tenant"] = focus_tenant
        return out


class GraphTenantHandle:
    """One graph's view of the shared ledger — what ``PipeGraph._tenant``
    holds.  The kill switch leaves this ``None`` and every call site
    keeps exactly one ``is not None`` check."""

    __slots__ = ("ledger", "tenant", "_graph_name", "_graph_ref")

    def __init__(self, ledger: TenantLedger, graph, tenant: str) -> None:
        self.ledger = ledger
        self.tenant = tenant
        self._graph_name = graph.name
        self._graph_ref = weakref.ref(graph)

    def tick(self) -> None:
        """Advance this tenant's budget machine (health_tick cadence)."""
        self.ledger.tick(self.tenant)

    def health_verdict(self) -> Optional[dict]:
        """The active OVER_BUDGET verdict iff its heaviest op lives in
        THIS graph (only the heaviest op's graph paints the verdict —
        the latency plane's dominant-op contract)."""
        return self.ledger.verdict_for(self._graph_name)

    def section(self) -> dict:
        return self.ledger.section(focus_graph=self._graph_name,
                                   focus_tenant=self.tenant)

    def freeze(self) -> None:
        """Snapshot this graph's final attribution at shutdown
        (``PipeGraph._finalize``)."""
        g = self._graph_ref()
        if g is not None:
            self.ledger.freeze(g)


_default_ledger: Optional[TenantLedger] = None
_default_lock = threading.Lock()


def default_ledger() -> TenantLedger:
    """The process-wide tenant ledger (the jit registry's singleton
    pattern): every graph in the process registers here, which is what
    makes cross-tenant attribution possible at all."""
    global _default_ledger
    with _default_lock:
        if _default_ledger is None:
            _default_ledger = TenantLedger()
        return _default_ledger
