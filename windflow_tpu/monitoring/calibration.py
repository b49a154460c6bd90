"""Calibration plane: measured-vs-modeled provenance + the live roofline.

Three planes (wire, Pallas kernels, megastep) default auto-on for TPU
backends, yet most numbers the repo holds for them are *models* — the
structural ICI collective model (``shard_ledger.ICI_BYTES_PER_SEC``), the XLA
cost-table bytes the sweep ledger attributes per hop — or an
*interpret-mode* run.  Nothing in stats()/OpenMetrics said which,
so a stale model read exactly like ground truth (ROADMAP item 1).

This module closes that gap in the PR 6/9/17/19 plane mold:

* **Provenance vocabulary.**  Every surfaced quantity that is not a
  direct measurement carries one of four tags: ``measured`` (a clock or
  byte counter on the live path), ``modeled`` (a constant or cost-table
  estimate), ``calibrated(<age>)`` (a modeled constant replaced by a
  probe measurement from ``tools/wf_calibrate.py``, with the
  measurement's age), or ``interpret`` (a Pallas interpreter run — a
  correctness vehicle, never a perf number).

* **Calibration store.**  ``tools/wf_calibrate.py`` runs a short seeded
  probe suite on the live backend and writes a versioned
  ``calibration.json`` keyed by device kind + jax version.
  ``Config.calibration`` / ``WF_TPU_CALIBRATION`` names the file; every
  modeled-constant read site goes through :func:`constant`, which
  returns ``(value, provenance)`` — the calibrated value while the
  store is fresh and matches the live device kind, the modeled default
  (with a one-time warning) once it goes stale past
  ``TTL_S`` or mismatches, and ``(None, None)``
  where there is neither: a quantity nobody measured or published is
  not surfaced at all.  ``WF_TPU_CALIBRATION=0``
  is the kill switch: no store loads anywhere and every read site
  degrades to its modeled default in one check.

* **Live roofline.**  :class:`RooflineLedger` is a roofline
  decomposition as a monitor-cadence gauge: per-hop achieved
  tuples/sec (a delta over counters the replicas already keep — zero
  per-batch work) joined with the sweep ledger's bytes/tuple and the
  calibrated memory bandwidth into ``stats()["Roofline"]`` +
  ``wf_roofline_*`` OpenMetrics families, plus a latched
  ``ROOFLINE_DEGRADED`` advisory health verdict when the dominant
  hop's throughput collapses against its own trailing baseline (the
  SLO plane's enter/latch/clear hysteresis).  ``Config.roofline_plane``
  off leaves one ``is not None`` check per call site (micro-asserted
  by tests/test_calibration.py).

The module never imports jax at module scope (``tools/wf_doctor.py``
renders the postmortem's ``calibration.json`` with no jax at all).
"""

from __future__ import annotations

import json
import math
import os
import threading
import time
import warnings
from collections import deque
from typing import Dict, Optional, Tuple

# ---------------------------------------------------------------------------
# provenance vocabulary
# ---------------------------------------------------------------------------

#: a direct measurement on the live path (clocks, byte counters)
MEASURED = "measured"
#: a constant, structural model, or XLA cost-table estimate
MODELED = "modeled"
#: a Pallas interpreter run — correctness vehicle, never a perf number
INTERPRET = "interpret"
#: prefix of the aged calibrated tag (see :func:`calibrated_tag`)
CALIBRATED_PREFIX = "calibrated("

#: schema tag of calibration.json (tools/wf_calibrate.py writes it,
#: tools/wf_doctor.py validates the postmortem copy against it)
SCHEMA = "wf-calibration/1"

#: calibration freshness TTL in seconds (default 7 days): past it the
#: store degrades to the modeled defaults with a one-time warning —
#: last week's link measurement must not masquerade as today's
TTL_S = 7 * 86400.0

#: published peak HBM bandwidth of one chip, keyed by the device kind
#: JAX reports.  v5e: Google Cloud documentation, "TPU v5e" (819 GB/s).
#: A kind that is not listed has no roofline ceiling until
#: ``wf_calibrate`` measures one — never another chip's figure.
HBM_PEAK_BYTES_PER_SEC = {"TPU v5 lite": 819e9}

#: the constants a calibration store may carry, with their modeled
#: defaults (``None`` = no default exists: measured or absent).  Every
#: read site names its key here so wf_calibrate, the doctor validation,
#: and the provenance summary agree on the set.
MODELED_DEFAULTS = {
    # ICI bandwidth the shard ledger's structural collective model
    # divides by (shard_ledger.ICI_BYTES_PER_SEC keeps the env knob)
    "ici_bytes_per_sec": 90e9,
    # host->device rate of the staged path: what wf_calibrate measured
    # on this host, or absent
    "h2d_bytes_per_sec": None,
    # memory bandwidth the roofline ceiling divides by: the published
    # peak of the live device kind (:func:`modeled_default`), or absent
    "hbm_bytes_per_sec": None,
    # per-dispatch overhead of a cached jitted program (µs)
    "dispatch_overhead_usec": 100.0,
    # cost of one sampled block_until_ready device sync (µs) — what the
    # trace lane's trace_device_sync_every batches pay
    "sampled_sync_usec": 100.0,
    # one fused FFAT kernel step at the bench shape (µs/step) — the
    # per-device-kind step timing the roofline cross-checks
    "kernel_step_usec": 0.0,
}

#: calibration keys whose probe is meaningful only on a multi-device
#: mesh — absent on single-device stores by design, not corruption
MESH_ONLY_KEYS = ("ici_bytes_per_sec",)


def calibrated_tag(age_s: float) -> str:
    """The aged provenance tag: ``calibrated(3h)`` / ``calibrated(2d)``."""
    age_s = max(0.0, float(age_s))
    if age_s < 120:
        human = f"{int(age_s)}s"
    elif age_s < 2 * 3600:
        human = f"{int(age_s // 60)}m"
    elif age_s < 2 * 86400:
        human = f"{int(age_s // 3600)}h"
    else:
        human = f"{int(age_s // 86400)}d"
    return f"{CALIBRATED_PREFIX}{human})"


def is_calibrated(tag: str) -> bool:
    return isinstance(tag, str) and tag.startswith(CALIBRATED_PREFIX)


def legal_provenance(tag) -> bool:
    """True for any tag of the four-value vocabulary (the tests hold
    every surfaced tag to it; wf_doctor validates with its own copy)."""
    return tag in (MEASURED, MODELED, INTERPRET) or is_calibrated(tag)


# ---------------------------------------------------------------------------
# the calibration store
# ---------------------------------------------------------------------------

class CalibrationError(ValueError):
    """calibration.json failed validation (corrupt, wrong schema, bad
    constant types) — a corrupt store must never silently read as
    calibrated truth."""


class CalibrationStore:
    """One validated calibration.json: measured constants keyed by the
    device kind + jax version they were probed on."""

    __slots__ = ("path", "recorded_at", "device_kind", "backend",
                 "jax_version", "constants", "probes")

    def __init__(self, doc: dict, path: Optional[str] = None) -> None:
        if not isinstance(doc, dict):
            raise CalibrationError("calibration document is not an object")
        if doc.get("schema") != SCHEMA:
            raise CalibrationError(
                f"schema {doc.get('schema')!r} != {SCHEMA!r}")
        rec = doc.get("recorded_at")
        if not isinstance(rec, (int, float)) or not math.isfinite(rec) \
                or rec <= 0:
            raise CalibrationError(f"bad recorded_at {rec!r}")
        kind = doc.get("device_kind")
        jv = doc.get("jax_version")
        if not isinstance(kind, str) or not kind:
            raise CalibrationError(f"bad device_kind {kind!r}")
        if not isinstance(jv, str) or not jv:
            raise CalibrationError(f"bad jax_version {jv!r}")
        consts = doc.get("constants")
        if not isinstance(consts, dict) or not consts:
            raise CalibrationError("constants missing or empty")
        for k, v in consts.items():
            if k not in MODELED_DEFAULTS:
                raise CalibrationError(f"unknown constant {k!r}")
            if not isinstance(v, (int, float)) or not math.isfinite(v) \
                    or v < 0:
                raise CalibrationError(f"constant {k!r} not a finite "
                                       f"non-negative number: {v!r}")
        self.path = path
        self.recorded_at = float(rec)
        self.device_kind = kind
        self.backend = doc.get("backend")
        self.jax_version = jv
        self.constants = {k: float(v) for k, v in consts.items()}
        self.probes = doc.get("probes") if isinstance(doc.get("probes"),
                                                      dict) else {}

    def age_s(self, now: Optional[float] = None) -> float:
        return max(0.0, (now if now is not None else time.time())
                   - self.recorded_at)

    def fresh(self, now: Optional[float] = None) -> bool:
        return self.age_s(now) <= TTL_S

    def to_json(self) -> dict:
        return {
            "schema": SCHEMA,
            "recorded_at": self.recorded_at,
            "device_kind": self.device_kind,
            "backend": self.backend,
            "jax_version": self.jax_version,
            "constants": dict(self.constants),
            "probes": dict(self.probes),
        }


def load(path: str) -> CalibrationStore:
    """Read + validate one calibration.json.  Raises
    :class:`CalibrationError` on any corruption (a bad store must fail
    loudly at load, never flip numbers silently)."""
    try:
        with open(path) as f:
            doc = json.load(f)
    except OSError as e:
        raise CalibrationError(f"unreadable: {e}") from e
    except ValueError as e:
        raise CalibrationError(f"not JSON: {e}") from e
    return CalibrationStore(doc, path=path)


# -- process-default store (the shard/tenant read path) ---------------------

_lock = threading.Lock()
_store: Optional[CalibrationStore] = None
_store_resolved = False
_warned: set = set()          # one-time warning keys


def _warn_once(key: str, msg: str) -> None:
    with _lock:
        if key in _warned:
            return
        _warned.add(key)
    warnings.warn(msg, RuntimeWarning, stacklevel=3)


def killed() -> bool:
    """The kill switch: ``WF_TPU_CALIBRATION=0`` (or ``off``) disables
    calibration loading process-wide — every read site returns its
    modeled default in one check (wf_calibrate --check exits 2 under
    it, the wf_ir refuse-to-report-clean stance)."""
    return os.environ.get("WF_TPU_CALIBRATION", "").lower() in ("0", "off",
                                                                "false")


def default_store() -> Optional[CalibrationStore]:
    """The process-wide store: installed by :func:`set_default_store`
    (PipeGraph._build on ``Config.calibration``) or resolved lazily from
    ``WF_TPU_CALIBRATION`` (a path).  None = uncalibrated."""
    global _store, _store_resolved
    if _store is not None or _store_resolved:
        return _store
    with _lock:
        if _store is not None or _store_resolved:
            return _store
        _store_resolved = True
    env = os.environ.get("WF_TPU_CALIBRATION", "")
    if not env or killed():
        return None
    try:
        store = load(env)
    except CalibrationError as e:
        _warn_once(f"load:{env}",
                   f"WF_TPU_CALIBRATION={env!r} failed to load ({e}) — "
                   "running uncalibrated, every modeled constant keeps "
                   "its default")
        return None
    with _lock:
        _store = store
    return _store


def set_default_store(store: Optional[CalibrationStore]) -> None:
    """Install (or clear, with re-resolution from the env) the
    process-wide store.  ``PipeGraph._build`` calls this when
    ``Config.calibration`` names a file; tests use it directly."""
    global _store, _store_resolved
    with _lock:
        _store = store
        _store_resolved = store is not None
        if store is None:
            _warned.clear()


_device_kind_cache: Optional[str] = None


def live_device_kind() -> str:
    """Device kind of the default backend (cached).  A backend that
    cannot answer raises: a store's kind gate never passes unchecked."""
    global _device_kind_cache
    if _device_kind_cache is None:
        import jax
        d = jax.devices()[0]
        _device_kind_cache = str(getattr(d, "device_kind", None)
                                 or d.platform)
    return _device_kind_cache


def modeled_default(key: str) -> Optional[float]:
    """The modeled value of ``key`` with no calibration store, or None
    where no model exists for this device."""
    if key == "hbm_bytes_per_sec":
        return HBM_PEAK_BYTES_PER_SEC.get(live_device_kind())
    return MODELED_DEFAULTS[key]


def constant(key: str, default: Optional[float] = None,
             now: Optional[float] = None
             ) -> Tuple[Optional[float], Optional[str]]:
    """THE modeled-constant read path: ``(value, provenance)``.

    Calibrated value + aged ``calibrated(...)`` tag while the default
    store is fresh, carries ``key``, and was recorded on this device
    kind; the modeled default + ``modeled`` otherwise (stale or
    kind-mismatched stores warn once and degrade — a dead measurement
    must never outrank a live model silently); ``(None, None)`` when
    the key has no modeled default either.  Called at stats
    cadence only, never per batch."""
    if default is None:
        default = modeled_default(key)
    modeled = (None, None) if default is None else (float(default), MODELED)
    store = default_store()
    if store is None:
        return modeled
    if key not in store.constants:
        return modeled
    kind = live_device_kind()
    if store.device_kind != kind:
        _warn_once(f"kind:{store.path}",
                   f"calibration {store.path or '<installed>'} was "
                   f"recorded on device kind {store.device_kind!r} but "
                   f"this process runs {kind!r} — ignoring it, every "
                   "modeled constant keeps its default")
        return modeled
    if not store.fresh(now):
        _warn_once(f"stale:{store.path}",
                   f"calibration {store.path or '<installed>'} is "
                   f"{store.age_s(now) / 86400:.1f} days old (TTL "
                   f"{TTL_S / 86400:.1f}d) — degrading to the modeled "
                   "defaults; re-run tools/wf_calibrate.py")
        return modeled
    return store.constants[key], calibrated_tag(store.age_s(now))


def provenance_summary(now: Optional[float] = None) -> dict:
    """One provenance frame for dump_trace metadata, the postmortem's
    ``calibration.json``, and the ``wf_provenance`` OpenMetrics family:
    where each modeled constant currently comes from."""
    store = default_store()
    out = {
        "schema": SCHEMA,
        "enabled": not killed(),
        "source": getattr(store, "path", None),
        "device_kind": live_device_kind(),
    }
    if store is not None:
        out["store"] = {
            "recorded_at": store.recorded_at,
            "device_kind": store.device_kind,
            "jax_version": store.jax_version,
            "age_s": round(store.age_s(now), 1),
            "fresh": store.fresh(now),
        }
    consts = {}
    for key in MODELED_DEFAULTS:
        v, prov = constant(key, now=now)
        if v is not None:       # neither measured nor modeled: omitted
            consts[key] = {"value": v, "provenance": prov}
    out["constants"] = consts
    return out


# ---------------------------------------------------------------------------
# live roofline plane
# ---------------------------------------------------------------------------

#: throughput-collapse threshold: the dominant hop's current rate below
#: this fraction of its own trailing baseline is a breach tick
DEGRADE_RATIO = 0.5


class RooflineLedger:
    """Monitor-cadence roofline gauge over counters that already exist.

    ``tick()`` (health_tick cadence) diffs each hop's cumulative
    processed-tuple counter against the previous tick — two integer
    reads per op per tick, zero per-batch work — into a bounded rate
    ring.  ``section()`` (stats cadence) joins the rings with the sweep
    ledger's per-hop bytes/tuple and the calibrated memory bandwidth
    into achieved-vs-roofline ratios.  The verdict state machine is the
    SLO plane's (latency_ledger.py): enter after ``ENTER_AFTER``
    consecutive collapse ticks once ``MIN_SAMPLES`` rates exist, latch
    while active, clear after ``CLEAR_AFTER`` consecutive OK ticks —
    judged against the hop's OWN trailing baseline, so it needs no
    absolute target."""

    ENTER_AFTER = 2
    CLEAR_AFTER = 3
    MIN_SAMPLES = 8
    WINDOW = 64
    #: wall-clock tick throttle (the tenant ledger's stance): headless
    #: runs call health_tick per sweep, and the counter walk must not
    #: become per-batch work through that path — ticks inside the
    #: interval are one compare
    TICK_MIN_INTERVAL_S = 0.2

    def __init__(self, graph) -> None:
        self._graph = graph
        self._last_tick_s: Optional[float] = None
        #: op name -> bounded ring of tuples/sec samples
        self._rings: Dict[str, deque] = {}
        #: op name -> (wall_s, cumulative inputs) at the previous tick
        self._prev: Dict[str, tuple] = {}
        self.ticks = 0
        self.entered = 0
        self.cleared = 0
        self._breach_ticks = 0
        self._ok_ticks = 0
        self.verdict: Optional[dict] = None
        self.last_verdict: Optional[dict] = None
        self._lock = threading.Lock()

    # -- cadence tick (zero per-batch work: reads existing counters) ---------
    def tick(self, now_s: Optional[float] = None) -> None:
        now_s = now_s if now_s is not None else time.monotonic()
        last = self._last_tick_s
        if last is not None and now_s - last < self.TICK_MIN_INTERVAL_S:
            return
        self._last_tick_s = now_s
        with self._lock:
            rates = {}
            for op in self._graph._operators:
                if not getattr(op, "is_tpu", False):
                    continue
                done = sum(r.stats.inputs_received for r in op.replicas)
                prev = self._prev.get(op.name)
                self._prev[op.name] = (now_s, done)
                if prev is None:
                    continue
                dt = now_s - prev[0]
                dn = done - prev[1]
                if dt <= 0 or dn <= 0:
                    # idle tick: no sample — degradation means the rate
                    # collapsed while tuples still flow, not that the
                    # run ended (a drained graph must not latch a
                    # verdict from its own completion)
                    continue
                rate = dn / dt
                ring = self._rings.get(op.name)
                if ring is None:
                    ring = self._rings[op.name] = deque(maxlen=self.WINDOW)
                ring.append(rate)
                rates[op.name] = rate
            self.ticks += 1
            self._evaluate(rates)

    def _dominant(self) -> Optional[str]:
        """The hop carrying the most cumulative tuples — the one whose
        collapse is the pipeline's story."""
        best, best_n = None, -1
        for name, (_, n) in self._prev.items():
            if n > best_n:
                best, best_n = name, n
        return best

    def _evaluate(self, rates: Dict[str, float]) -> None:
        """The enter/latch/clear machine over the dominant hop (caller
        holds the lock)."""
        dom = self._dominant()
        ring = self._rings.get(dom) if dom else None
        if not ring or len(ring) < self.MIN_SAMPLES or dom not in rates:
            # no fresh evidence this tick: an active verdict stays
            # latched (the SLO stance — silence is not recovery)
            return
        trailing = sorted(list(ring)[:-1])
        baseline = trailing[len(trailing) // 2]
        current = ring[-1]
        breach = baseline > 0 and current < DEGRADE_RATIO * baseline
        if breach:
            self._breach_ticks += 1
            self._ok_ticks = 0
            if self.verdict is None \
                    and self._breach_ticks >= self.ENTER_AFTER:
                self.entered += 1
                self.verdict = self.last_verdict = {
                    "state": "ROOFLINE_DEGRADED",
                    "dominant_op": dom,
                    "current_tuples_per_sec": round(current, 1),
                    "baseline_tuples_per_sec": round(baseline, 1),
                    "ratio_vs_baseline": round(current / baseline, 4),
                    "degrade_ratio": DEGRADE_RATIO,
                    "entered_tick": self.ticks,
                }
        else:
            self._breach_ticks = 0
            if self.verdict is not None:
                self._ok_ticks += 1
                if self._ok_ticks >= self.CLEAR_AFTER:
                    self.cleared += 1
                    self.verdict = None
                    self._ok_ticks = 0

    def health_verdict(self) -> Optional[dict]:
        """Plain read of the latest published verdict (the health
        plane's per-sample hook — same stance as the SLO/budget reads)."""
        return self.verdict

    # -- stats()["Roofline"] --------------------------------------------------
    def section(self) -> dict:
        """Per-hop achieved vs roofline (stats cadence).  Bytes/tuple
        joins from the sweep ledger (cost-table numbers — tagged
        ``modeled``); the bandwidth ceiling is the calibrated
        ``hbm_bytes_per_sec`` (tagged with ITS provenance), so the
        achieved/roofline ratio names its own trustworthiness."""
        bw, bw_prov = constant("hbm_bytes_per_sec")
        led = self._graph._ledger
        sweep_hops = {}
        if led is not None:
            try:
                sweep_hops = led.section().get("per_hop") or {}
            except Exception:  # lint: broad-except-ok (the sweep join
                # is telemetry enrichment — a ledger bug degrades the
                # roofline to rates-only, it must not take stats down)
                sweep_hops = {}
        with self._lock:
            per_hop = {}
            for name, ring in self._rings.items():
                if not ring:
                    continue
                rs = sorted(ring)
                tps = rs[len(rs) // 2]
                hop = {
                    "achieved_tuples_per_sec": round(tps, 1),
                    "samples": len(ring),
                    "tuples_per_sec_provenance": MEASURED,
                }
                sh = sweep_hops.get(name) or {}
                bpt = sh.get("steady_bytes_per_tuple") \
                    or sh.get("bytes_per_tuple")
                if bpt:
                    hop["bytes_per_tuple"] = bpt
                    hop["bytes_per_tuple_provenance"] = \
                        sh.get("bytes_provenance", MODELED)
                    achieved_bps = tps * float(bpt)
                    hop["achieved_bytes_per_sec"] = round(achieved_bps, 1)
                    if bw:
                        hop["roofline_tuples_per_sec"] = \
                            round(bw / float(bpt), 1)
                        hop["ratio_vs_roofline"] = \
                            round(achieved_bps / bw, 6)
                per_hop[name] = hop
            return {
                "enabled": True,
                "per_hop": per_hop,
                "dominant_op": self._dominant(),
                "bandwidth_bytes_per_sec": bw,
                "bandwidth_provenance": bw_prov,
                "ticks": self.ticks,
                "entered": self.entered,
                "cleared": self.cleared,
                "verdict": self.verdict,
                "last_verdict": self.last_verdict,
                "thresholds": {
                    "degrade_ratio": DEGRADE_RATIO,
                    "enter_after": self.ENTER_AFTER,
                    "clear_after": self.CLEAR_AFTER,
                    "min_samples": self.MIN_SAMPLES,
                },
                "calibration": provenance_summary(),
            }
