#!/usr/bin/env python
"""wf_doctor: render a windflow_tpu postmortem bundle into a diagnosis.

A crash or watchdog-confirmed stall writes a black-box bundle
(``PipeGraph.dump_postmortem`` — flight-recorder rings, the last stats
report, health verdict timeline + stall attribution, jit/device tables,
the sweep ledger's per-hop dispatch/HBM attribution, preflight
findings).  This tool turns that directory into a human
diagnosis — or validates it — with **no jax installed** (pure stdlib,
same scrape-host stance as ``tools/wf_metrics.py``).

Usage::

    python tools/wf_doctor.py log/app_postmortem            # diagnose
    python tools/wf_doctor.py --check log/app_postmortem    # validate:
        # manifest schema, every listed file parses, health states and
        # span stages are legal, stall attribution names a known
        # operator; exit 1 on any violation
    python tools/wf_doctor.py --json log/app_postmortem     # machine-
        # readable diagnosis (the same fields the text render shows)

The CI round trip (tests/test_health.py) seeds a stall, lets the crash
path write a bundle, and runs ``--check`` on it in a subprocess.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

#: mirrors monitoring/health.py (kept literal: this file must not import
#: the package — the package __init__ imports jax)
SCHEMA = "wf-postmortem/1"
STATES = ("OK", "ROOFLINE_DEGRADED", "SLO_VIOLATED", "OVER_BUDGET",
          "BACKPRESSURED", "STALLED", "FAILED")
#: mirrors monitoring/calibration.py (SCHEMA + the provenance
#: vocabulary — calibrated tags carry an age suffix, e.g.
#: "calibrated(3h)")
CALIBRATION_SCHEMA = "wf-calibration/1"
PROVENANCE_FIXED = ("measured", "modeled", "interpret")


def _legal_provenance(tag) -> bool:
    return tag in PROVENANCE_FIXED or (
        isinstance(tag, str) and tag.startswith("calibrated("))
#: mirrors monitoring/latency_ledger.py SEGMENTS
LATENCY_SEGMENTS = ("staged_to_emitted", "emitted_to_dispatched",
                    "dispatched_to_device_done",
                    "device_done_to_collected", "collected_to_sunk")
STAGE_NAMES = ("staged", "emitted", "dispatched", "device_done",
               "collected", "sunk")
SECTIONS = ("stats.json", "events.json", "health.json", "device.json",
            "jit.json", "preflight.json")
#: sections newer writers add; validated when present, but their absence
#: must not reject a bundle written before they existed (same schema) —
#: this tool's job is exactly the historical crash bundle
OPTIONAL_SECTIONS = ("sweep.json", "durability.json", "shard.json",
                     "reshard.json", "latency.json", "ir_audit.json",
                     "tenant.json", "roofline.json", "calibration.json")
#: reshard executor timeline events (windflow_tpu/serving/executor.py)
RESHARD_EVENTS = ("triggered", "move_keys", "split_hot_key", "admission",
                  "recovered", "scale_down", "move_skipped")


class BundleError(Exception):
    pass


def load_bundle(path: str) -> dict:
    """Read manifest + every section it lists.  Raises
    :class:`BundleError` on structural violations (the --check half);
    sections recorded under manifest ``errors`` are allowed to be
    absent — a crash-path bundle degrades per section by design."""
    if not os.path.isdir(path):
        raise BundleError(f"{path} is not a bundle directory")
    mpath = os.path.join(path, "manifest.json")
    try:
        with open(mpath) as f:
            manifest = json.load(f)
    except OSError as e:
        raise BundleError(f"no readable manifest.json: {e}") from None
    except ValueError as e:
        raise BundleError(f"manifest.json is not valid JSON: {e}") from None
    if manifest.get("schema") != SCHEMA:
        raise BundleError(f"unknown bundle schema "
                          f"{manifest.get('schema')!r} (want {SCHEMA!r})")
    for key in ("app", "reason", "written_at_usec", "files", "errors"):
        if key not in manifest:
            raise BundleError(f"manifest missing {key!r}")
    sections = {}
    for name in manifest["files"]:
        fp = os.path.join(path, name)
        try:
            with open(fp) as f:
                sections[name] = json.load(f)
        except OSError as e:
            raise BundleError(f"manifest lists {name} but it is "
                              f"unreadable: {e}") from None
        except ValueError as e:
            raise BundleError(f"{name} is not valid JSON: {e}") from None
    return {"dir": path, "manifest": manifest, "sections": sections}


def validate(bundle: dict) -> None:
    """The --check contract beyond load_bundle's structural pass."""
    manifest = bundle["manifest"]
    sections = bundle["sections"]
    missing = [s for s in SECTIONS
               if s not in sections and s not in manifest["errors"]]
    if missing:
        raise BundleError(
            f"sections neither written nor accounted for in "
            f"manifest errors: {missing}")
    health = sections.get("health.json") or {}
    verdicts = health.get("verdicts") or {}
    for op, v in verdicts.items():
        if v.get("state") not in STATES:
            raise BundleError(
                f"health.json: operator {op!r} has illegal state "
                f"{v.get('state')!r} (want one of {STATES})")
    for entry in health.get("timeline") or []:
        for op, state in (entry.get("changes") or {}).items():
            if state not in STATES:
                raise BundleError(
                    f"health.json timeline: illegal state {state!r} "
                    f"for {op!r}")
    stall = health.get("last_stall")
    if stall and stall.get("root_cause") is not None \
            and stall["root_cause"] not in verdicts:
        raise BundleError(
            f"last_stall attributes {stall['root_cause']!r} but that "
            "operator has no verdict entry")
    for e in sections.get("events.json") or []:
        if e.get("stage") not in STAGE_NAMES:
            raise BundleError(
                f"events.json: illegal span stage {e.get('stage')!r}")
    sweep = sections.get("sweep.json") or {}
    if sweep.get("enabled"):
        for op, hop in (sweep.get("per_hop") or {}).items():
            if not isinstance(hop, dict):
                raise BundleError(
                    f"sweep.json: hop {op!r} is not an object")
            for key in ("dispatches", "batches"):
                v = hop.get(key)
                if v is not None and not isinstance(v, int):
                    raise BundleError(
                        f"sweep.json: hop {op!r} field {key!r} must be "
                        f"an integer, got {v!r}")
            bpt = hop.get("bytes_per_tuple")
            if bpt is not None and (not isinstance(bpt, (int, float))
                                    or bpt < 0):
                raise BundleError(
                    f"sweep.json: hop {op!r} bytes_per_tuple {bpt!r} is "
                    "not a non-negative number")
    shard = sections.get("shard.json") or {}
    if shard.get("enabled") and "error" not in shard:
        per_op = shard.get("per_op")
        if not isinstance(per_op, dict):
            raise BundleError("shard.json: per_op must be an object")
        for op, entry in per_op.items():
            if not isinstance(entry, dict):
                raise BundleError(
                    f"shard.json: operator {op!r} entry is not an object")
            reps = entry.get("replicas")
            if reps is not None and not isinstance(reps, list):
                raise BundleError(
                    f"shard.json: operator {op!r} replicas must be a "
                    "list")
            for r in reps or []:
                if not isinstance(r, dict):
                    raise BundleError(
                        f"shard.json: operator {op!r} replica entry "
                        f"{r!r} is not an object")
                q = r.get("queue_depth")
                if not isinstance(q, int) or q < 0:
                    raise BundleError(
                        f"shard.json: operator {op!r} shard queue_depth "
                        f"{q!r} is not a non-negative integer")
            load = entry.get("load")
            if load is not None:
                if not isinstance(load, dict):
                    raise BundleError(
                        f"shard.json: operator {op!r} load is not an "
                        "object")
                ratio = load.get("imbalance_ratio")
                if ratio is not None and (
                        not isinstance(ratio, (int, float)) or ratio < 0):
                    raise BundleError(
                        f"shard.json: operator {op!r} imbalance_ratio "
                        f"{ratio!r} is not a non-negative number")
                hks = load.get("hot_keys")
                if hks is not None and not isinstance(hks, list):
                    raise BundleError(
                        f"shard.json: operator {op!r} hot_keys must be "
                        "a list")
                for hk in hks or []:
                    if not isinstance(hk, dict):
                        raise BundleError(
                            f"shard.json: operator {op!r} hot-key entry "
                            f"{hk!r} is not an object")
                    v = hk.get("est_tuples")
                    if not isinstance(v, int) or v < 0:
                        raise BundleError(
                            f"shard.json: operator {op!r} hot-key "
                            f"est_tuples {v!r} is not a non-negative "
                            "integer")
    dur = sections.get("durability.json") or {}
    if dur.get("enabled") and "error" not in dur:
        for key in ("epochs_committed", "dedupe_hits", "sink_commits"):
            v = dur.get(key)
            if not isinstance(v, int) or v < 0:
                raise BundleError(
                    f"durability.json: {key!r} must be a non-negative "
                    f"integer, got {v!r}")
        for key in ("last_checkpoint_ms", "restore_ms"):
            v = dur.get(key)
            if v is not None and (not isinstance(v, (int, float))
                                  or v < 0):
                raise BundleError(
                    f"durability.json: {key!r} must be a non-negative "
                    f"number or null, got {v!r}")
        ep = dur.get("restored_epoch")
        if ep is not None and not isinstance(ep, int):
            raise BundleError(
                f"durability.json: restored_epoch must be an integer "
                f"or null, got {ep!r}")
    rsh = sections.get("reshard.json") or {}
    if rsh.get("enabled") and "error" not in rsh:
        for key in ("plans_applied", "keys_moved", "splits_applied",
                    "admission_throttles", "preagg_folds"):
            v = rsh.get(key)
            if not isinstance(v, int) or v < 0:
                raise BundleError(
                    f"reshard.json: {key!r} must be a non-negative "
                    f"integer, got {v!r}")
        af = rsh.get("admission_factor")
        if not isinstance(af, (int, float)) or not 0 < af <= 1:
            raise BundleError(
                f"reshard.json: admission_factor must be in (0, 1], "
                f"got {af!r}")
        tl = rsh.get("timeline")
        if not isinstance(tl, list):
            raise BundleError("reshard.json: timeline must be a list")
        for e in tl:
            if not isinstance(e, dict) \
                    or e.get("event") not in RESHARD_EVENTS:
                raise BundleError(
                    f"reshard.json: illegal timeline entry {e!r}")
    ira = sections.get("ir_audit.json") or {}
    if ira.get("enabled") and "error" not in ira:
        for key in ("programs_audited", "dry_lowered", "suppressed"):
            v = ira.get(key)
            if not isinstance(v, int) or v < 0:
                raise BundleError(
                    f"ir_audit.json: {key!r} must be a non-negative "
                    f"integer, got {v!r}")
        for key in ("findings", "pending"):
            if not isinstance(ira.get(key), list):
                raise BundleError(
                    f"ir_audit.json: {key!r} must be a list")
        for f in ira["findings"]:
            if not isinstance(f, dict) \
                    or not str(f.get("code", "")).startswith("WF9"):
                raise BundleError(
                    f"ir_audit.json: finding {f!r} is not an object "
                    "with a WF9xx code")
    latp = sections.get("latency.json") or {}
    if latp.get("enabled") and "error" not in latp:
        for key in ("traces_decomposed", "traces_dropped", "events_lost"):
            v = latp.get(key)
            if not isinstance(v, int) or v < 0:
                raise BundleError(
                    f"latency.json: {key!r} must be a non-negative "
                    f"integer, got {v!r}")
        segs = latp.get("segments_total_usec")
        if not isinstance(segs, dict):
            raise BundleError(
                "latency.json: segments_total_usec must be an object")
        for seg, v in segs.items():
            if seg not in LATENCY_SEGMENTS:
                raise BundleError(
                    f"latency.json: unknown segment {seg!r} "
                    f"(want one of {LATENCY_SEGMENTS})")
            if not isinstance(v, (int, float)) or v < 0:
                raise BundleError(
                    f"latency.json: segment {seg!r} total {v!r} is not "
                    "a non-negative number")
        per_op = latp.get("per_op")
        if not isinstance(per_op, dict):
            raise BundleError("latency.json: per_op must be an object")
        for op, entry in per_op.items():
            if not isinstance(entry, dict):
                raise BundleError(
                    f"latency.json: operator {op!r} entry is not an "
                    "object")
            share = entry.get("budget_share")
            if not isinstance(share, (int, float)) or not 0 <= share <= 1:
                raise BundleError(
                    f"latency.json: operator {op!r} budget_share "
                    f"{share!r} is not in [0, 1]")
            dom = entry.get("dominant_segment")
            if dom is not None and dom not in LATENCY_SEGMENTS:
                raise BundleError(
                    f"latency.json: operator {op!r} dominant_segment "
                    f"{dom!r} is not a known segment")
            for seg in entry.get("segments_usec") or {}:
                if seg not in LATENCY_SEGMENTS:
                    raise BundleError(
                        f"latency.json: operator {op!r} histogram "
                        f"segment {seg!r} is not a known segment")
        slo = latp.get("slo") or {}
        verdict = slo.get("verdict")
        if verdict is not None:
            if not isinstance(verdict, dict) \
                    or verdict.get("state") != "SLO_VIOLATED":
                raise BundleError(
                    f"latency.json: slo.verdict {verdict!r} must be an "
                    "object with state SLO_VIOLATED")
            if verdict.get("dominant_op") is not None \
                    and verdict["dominant_op"] not in per_op:
                raise BundleError(
                    f"latency.json: slo.verdict attributes "
                    f"{verdict['dominant_op']!r} but that operator has "
                    "no per_op entry")
    ten = sections.get("tenant.json") or {}
    if ten.get("enabled") and "error" not in ten:
        tenants = ten.get("tenants")
        if not isinstance(tenants, dict):
            raise BundleError("tenant.json: tenants must be an object")
        for tname, agg in tenants.items():
            if not isinstance(agg, dict):
                raise BundleError(
                    f"tenant.json: tenant {tname!r} entry is not an "
                    "object")
            for key in ("dispatches", "h2d_bytes", "d2h_bytes",
                        "resident_state_bytes"):
                v = agg.get(key)
                if v is not None and (not isinstance(v, int) or v < 0):
                    raise BundleError(
                        f"tenant.json: tenant {tname!r} field {key!r} "
                        f"must be a non-negative integer, got {v!r}")
            budget = agg.get("budget")
            if budget is not None:
                if not isinstance(budget, dict):
                    raise BundleError(
                        f"tenant.json: tenant {tname!r} budget is not "
                        "an object")
                pressure = budget.get("pressure")
                if pressure is not None and (
                        not isinstance(pressure, (int, float))
                        or pressure < 0):
                    raise BundleError(
                        f"tenant.json: tenant {tname!r} budget pressure "
                        f"{pressure!r} is not a non-negative number")
                v = budget.get("verdict")
                if v is not None:
                    if not isinstance(v, dict) \
                            or v.get("state") != "OVER_BUDGET":
                        raise BundleError(
                            f"tenant.json: tenant {tname!r} verdict "
                            f"{v!r} must be an object with state "
                            "OVER_BUDGET")
                    if v.get("heaviest_op") is not None \
                            and v["heaviest_op"] \
                            not in (agg.get("per_op") or {}):
                        raise BundleError(
                            f"tenant.json: tenant {tname!r} verdict "
                            f"attributes {v['heaviest_op']!r} but that "
                            "operator has no per_op entry")
        attributed = ten.get("attributed")
        if attributed is not None:
            if not isinstance(attributed, dict):
                raise BundleError(
                    "tenant.json: attributed must be an object")
            frac = attributed.get("staged_fraction")
            if frac is not None and (not isinstance(frac, (int, float))
                                     or frac < 0):
                raise BundleError(
                    f"tenant.json: attributed staged_fraction {frac!r} "
                    "is not a non-negative number")
    calib = sections.get("calibration.json") or {}
    if calib and "error" not in calib:
        if calib.get("schema") != CALIBRATION_SCHEMA:
            raise BundleError(
                f"calibration.json: schema {calib.get('schema')!r} "
                f"(want {CALIBRATION_SCHEMA!r})")
        consts = calib.get("constants")
        if not isinstance(consts, dict):
            raise BundleError(
                "calibration.json: constants must be an object")
        for key, slot in consts.items():
            if not isinstance(slot, dict):
                raise BundleError(
                    f"calibration.json: constant {key!r} entry is not "
                    "an object")
            v = slot.get("value")
            if not isinstance(v, (int, float)) or v < 0:
                raise BundleError(
                    f"calibration.json: constant {key!r} value {v!r} is "
                    "not a non-negative number")
            if not _legal_provenance(slot.get("provenance")):
                raise BundleError(
                    f"calibration.json: constant {key!r} provenance "
                    f"{slot.get('provenance')!r} is not in the "
                    "measured/modeled/calibrated(age)/interpret "
                    "vocabulary")
    rfl = sections.get("roofline.json") or {}
    if rfl.get("enabled") and "error" not in rfl:
        per_hop = rfl.get("per_hop")
        if not isinstance(per_hop, dict):
            raise BundleError("roofline.json: per_hop must be an object")
        for op, hop in per_hop.items():
            if not isinstance(hop, dict):
                raise BundleError(
                    f"roofline.json: hop {op!r} entry is not an object")
            for key in ("achieved_tuples_per_sec", "bytes_per_tuple",
                        "ratio_vs_roofline"):
                v = hop.get(key)
                if v is not None and (not isinstance(v, (int, float))
                                      or v < 0):
                    raise BundleError(
                        f"roofline.json: hop {op!r} field {key!r} "
                        f"{v!r} is not a non-negative number")
            prov = hop.get("bytes_per_tuple_provenance")
            if prov is not None and not _legal_provenance(prov):
                raise BundleError(
                    f"roofline.json: hop {op!r} bytes provenance "
                    f"{prov!r} is not a legal tag")
        # a device with neither a published peak nor a calibrated
        # bandwidth carries no ceiling: both fields absent together
        if (rfl.get("bandwidth_bytes_per_sec") is not None
                or rfl.get("bandwidth_provenance") is not None) \
                and not _legal_provenance(rfl.get("bandwidth_provenance")):
            raise BundleError(
                f"roofline.json: bandwidth_provenance "
                f"{rfl.get('bandwidth_provenance')!r} is not a legal "
                "tag")
        v = rfl.get("verdict")
        if v is not None:
            if not isinstance(v, dict) \
                    or v.get("state") != "ROOFLINE_DEGRADED":
                raise BundleError(
                    f"roofline.json: verdict {v!r} must be an object "
                    "with state ROOFLINE_DEGRADED")
            if v.get("dominant_op") is not None \
                    and v["dominant_op"] not in per_hop:
                raise BundleError(
                    f"roofline.json: verdict attributes "
                    f"{v['dominant_op']!r} but that hop has no per_hop "
                    "entry")


def diagnose(bundle: dict) -> dict:
    """Condense the bundle into the fields a responder reads first."""
    manifest = bundle["manifest"]
    sections = bundle["sections"]
    health = sections.get("health.json") or {}
    verdicts = health.get("verdicts") or {}
    stats = sections.get("stats.json") or {}
    gauges = stats.get("Gauges") or {}
    jit = (sections.get("jit.json") or {}).get("totals") or {}
    stall = health.get("last_stall") or None
    bad = {op: v for op, v in verdicts.items() if v.get("state") != "OK"}
    sweep = sections.get("sweep.json") or {}
    hops = sweep.get("per_hop") or {}
    top_hop = None
    if hops:
        ranked = sorted(hops.items(),
                        key=lambda kv: kv[1].get("bytes_per_tuple") or 0,
                        reverse=True)
        name, h = ranked[0]
        top_hop = {"op": name,
                   "bytes_per_tuple": h.get("bytes_per_tuple"),
                   "dispatches_per_batch": h.get("dispatches_per_batch"),
                   "excess_vs_model": h.get("excess_vs_model")}
    donation_misses = {op: h["donation_miss"] for op, h in hops.items()
                       if h.get("donation_miss")}
    shard = sections.get("shard.json") or {}
    shard_imbalance = None
    if shard.get("enabled") and "error" not in shard:
        tot = shard.get("totals") or {}
        if tot.get("max_imbalance_op"):
            worst = (shard.get("per_op") or {}) \
                .get(tot["max_imbalance_op"]) or {}
            load = worst.get("load") or {}
            hot = (load.get("hot_keys") or [{}])[0]
            shard_imbalance = {
                "op": tot["max_imbalance_op"],
                "imbalance_ratio": tot.get("max_imbalance_ratio"),
                "hot_shard": load.get("hot_shard"),
                "hot_key": hot.get("key"),
                "hot_key_share": tot.get("hot_key_share"),
                "loads": load.get("tuples"),
                "ici_bytes_per_tuple": tot.get("ici_bytes_per_tuple"),
            }
    dur = sections.get("durability.json") or {}
    durability = None
    if dur.get("enabled") and "error" not in dur:
        durability = {
            "epochs_committed": dur.get("epochs_committed"),
            "last_checkpoint_ms": dur.get("last_checkpoint_ms"),
            "checkpoint_bytes_total": dur.get("checkpoint_bytes_total"),
            "restored_epoch": dur.get("restored_epoch"),
            "dedupe_hits": dur.get("dedupe_hits"),
            "dir": dur.get("dir"),
        }
    latp = sections.get("latency.json") or {}
    latency = None
    if latp.get("enabled") and "error" not in latp:
        ranked = sorted((latp.get("per_op") or {}).items(),
                        key=lambda kv: kv[1].get("budget_share") or 0,
                        reverse=True)
        top = None
        if ranked:
            name, entry = ranked[0]
            top = {"op": name,
                   "budget_share": entry.get("budget_share"),
                   "dominant_segment": entry.get("dominant_segment"),
                   "megastep_k": entry.get("megastep_k"),
                   "freshness_floor_usec":
                       entry.get("freshness_floor_usec")}
        slo = latp.get("slo") or {}
        latency = {
            "traces_decomposed": latp.get("traces_decomposed"),
            "traces_dropped": latp.get("traces_dropped"),
            "events_lost": latp.get("events_lost"),
            "e2e_p99_usec": (latp.get("e2e_usec") or {}).get("p99"),
            "top_op": top,
            "slo_budget_ms": slo.get("budget_ms"),
            "slo_active": slo.get("active"),
            "slo_verdict": slo.get("verdict") or slo.get("last_verdict"),
        }
    irap = sections.get("ir_audit.json") or {}
    ir_audit = None
    if irap.get("enabled") and "error" not in irap:
        ir_audit = {
            "programs_audited": irap.get("programs_audited"),
            "findings": irap.get("findings") or [],
            "suppressed": irap.get("suppressed"),
            "pending": irap.get("pending") or [],
        }
    tenp = sections.get("tenant.json") or {}
    tenancy = None
    if tenp.get("enabled") and "error" not in tenp:
        worst = None
        for tname, agg in (tenp.get("tenants") or {}).items():
            if not isinstance(agg, dict):
                continue
            budget = agg.get("budget") or {}
            row = {
                "tenant": tname,
                "graphs": agg.get("graphs") or [],
                "resident_state_bytes":
                    agg.get("resident_state_bytes"),
                "budget_bytes": budget.get("budget_bytes"),
                "pressure": budget.get("pressure"),
                "over_budget": bool(budget.get("active")),
                "heaviest_op": agg.get("heaviest_op"),
                "verdict": budget.get("verdict")
                    or budget.get("last_verdict"),
            }
            if worst is None or (row["pressure"] or -1.0) \
                    > (worst["pressure"] or -1.0):
                worst = row
        tenancy = {
            "tenants_total": len(tenp.get("tenants") or {}),
            "worst": worst,
            "attributed": tenp.get("attributed") or {},
        }
    calp = sections.get("calibration.json") or {}
    calibration = None
    if calp and "error" not in calp:
        consts = calp.get("constants") or {}
        calibration = {
            "enabled": bool(calp.get("enabled")),
            "source": calp.get("source"),
            "device_kind": calp.get("device_kind"),
            "calibrated_constants": sorted(
                k for k, s in consts.items()
                if isinstance(s, dict)
                and str(s.get("provenance", "")).startswith("calibrated(")),
            "modeled_constants": sorted(
                k for k, s in consts.items()
                if isinstance(s, dict)
                and s.get("provenance") == "modeled"),
        }
    rflp = sections.get("roofline.json") or {}
    roofline = None
    if rflp.get("enabled") and "error" not in rflp:
        worst_hop = None
        for op, hop in (rflp.get("per_hop") or {}).items():
            if not isinstance(hop, dict):
                continue
            ratio = hop.get("ratio_vs_roofline")
            if ratio is None:
                continue
            if worst_hop is None or ratio < worst_hop["ratio"]:
                worst_hop = {"op": op, "ratio": ratio,
                             "achieved_tuples_per_sec":
                                 hop.get("achieved_tuples_per_sec")}
        roofline = {
            "hops": len(rflp.get("per_hop") or {}),
            "dominant_op": rflp.get("dominant_op"),
            "bandwidth_provenance": rflp.get("bandwidth_provenance"),
            "worst_hop": worst_hop,
            "verdict": rflp.get("verdict") or rflp.get("last_verdict"),
        }
    rsh = sections.get("reshard.json") or {}
    reshard = None
    if rsh.get("enabled") and "error" not in rsh:
        reshard = {
            "plans_applied": rsh.get("plans_applied"),
            "keys_moved": rsh.get("keys_moved"),
            "splits_applied": rsh.get("splits_applied"),
            "preagg_folds": rsh.get("preagg_folds"),
            "admission_factor": rsh.get("admission_factor"),
            "quiesce_ms": rsh.get("quiesce_ms"),
            "recovery_ms": rsh.get("recovery_ms"),
            "ops": rsh.get("ops") or {},
            "timeline": rsh.get("timeline") or [],
        }
    return {
        "app": manifest.get("app"),
        "reason": manifest.get("reason"),
        "durability": durability,
        "latency": latency,
        "ir_audit": ir_audit,
        "tenancy": tenancy,
        "calibration": calibration,
        "roofline": roofline,
        "reshard": reshard,
        "written_at_usec": manifest.get("written_at_usec"),
        "graph_state": health.get("graph_state"),
        "stall_events": health.get("stall_events", 0),
        "root_cause": stall.get("root_cause") if stall else None,
        "unhealthy_operators": bad,
        "verdicts": verdicts,
        "timeline": health.get("timeline") or [],
        "throughput_1s_tps": gauges.get("throughput_1s_tps"),
        "dropped_tuples": stats.get("Dropped_tuples"),
        "recompiles": jit.get("recompiles"),
        "compile_ms_total": jit.get("compile_ms_total"),
        "span_events": len(sections.get("events.json") or []),
        "shard_imbalance": shard_imbalance,
        "sweep_top_hop": top_hop,
        "sweep_totals": sweep.get("totals") or None,
        "donation_misses": donation_misses,
        "section_errors": manifest.get("errors") or {},
    }


def _age(usec) -> str:
    return "?" if usec is None else f"{usec / 1e6:.1f}s"


def render_text(d: dict) -> str:
    lines = [
        f"wf_doctor: app '{d['app']}' — {d['reason']}",
        f"  graph state: {d['graph_state'] or '?'}   "
        f"stall events: {d['stall_events']}   "
        f"span events retained: {d['span_events']}",
    ]
    if d["root_cause"]:
        v = d["verdicts"].get(d["root_cause"], {})
        lines.append(
            f"  ROOT CAUSE: '{d['root_cause']}' stopped draining — "
            f"queue={v.get('queue_depth')}, "
            f"frontier={v.get('watermark_frontier_usec')}, "
            f"last advance {_age(v.get('last_advance_age_usec'))} ago")
    lines.append("  operators:")
    for op, v in d["verdicts"].items():
        extra = " [compile storm]" if v.get("compile_storm") else ""
        fail = f" — {v['failure']}" if v.get("failure") else ""
        lines.append(
            f"    {op:<24} {v.get('state', '?'):<14} "
            f"queue={v.get('queue_depth', 0):<6} "
            f"advance_age={_age(v.get('last_advance_age_usec'))}"
            f"{extra}{fail}")
    if d["timeline"]:
        lines.append("  verdict timeline (state changes):")
        for entry in d["timeline"][-12:]:
            changes = ", ".join(f"{op}→{s}" for op, s
                                in (entry.get("changes") or {}).items())
            lines.append(f"    t={entry.get('t_usec')}: {changes}")
    lines.append(
        f"  telemetry: throughput_1s={d['throughput_1s_tps']} tps, "
        f"dropped={d['dropped_tuples']}, "
        f"recompiles={d['recompiles']}, "
        f"compile_ms_total={d['compile_ms_total']}")
    if d.get("sweep_top_hop"):
        t = d["sweep_top_hop"]
        tot = d.get("sweep_totals") or {}
        n = lambda v: "?" if v is None else v  # cost tables may be absent
        lines.append(
            f"  sweep: hottest hop '{t['op']}' at "
            f"{n(t['bytes_per_tuple'])} B/tuple "
            f"({n(t['dispatches_per_batch'])} dispatch(es)/batch, "
            f"{n(t['excess_vs_model'])}x the record model); "
            f"graph total {n(tot.get('bytes_per_tuple'))} B/tuple over "
            f"{n(tot.get('dispatches_per_batch'))} dispatches/batch")
    if d.get("shard_imbalance"):
        s = d["shard_imbalance"]
        n = lambda v: "?" if v is None else v
        lines.append(
            f"  shard: worst imbalance '{s['op']}' at "
            f"{n(s['imbalance_ratio'])}x (hot shard {n(s['hot_shard'])}, "
            f"loads {n(s['loads'])}); hottest key {n(s['hot_key'])} "
            f"carries {n(s['hot_key_share'])} of the stream"
            + (f"; ICI {s['ici_bytes_per_tuple']} B/tuple"
               if s.get("ici_bytes_per_tuple") else ""))
    for op, miss in (d.get("donation_misses") or {}).items():
        lines.append(
            f"  donation miss: '{op}' re-copies "
            f"{miss.get('bytes_per_batch')} B/batch "
            f"({miss.get('candidate_leaves')} donatable leaf/leaves "
            "not donated)")
    if d.get("durability"):
        du = d["durability"]
        if not du.get("epochs_committed") \
                and du.get("restored_epoch") is None:
            # a crash before the first barrier leaves nothing to rebuild
            # from — saying "restartable" here would misdirect the
            # responder straight into restore()'s no-complete-epoch
            # error.  A restored graph that re-crashed before its first
            # NEW commit also reports epochs_committed 0, but its
            # restored_epoch proves the store holds complete epochs —
            # that case takes the restartable branch below.
            lines.append(
                "  durability: enabled but NO complete epoch committed "
                f"to {du['dir']!r} yet — PipeGraph.restore() has nothing "
                "to rebuild from; restart the app cold")
        else:
            lines.append(
                f"  durability: {du['epochs_committed']} epoch(s) "
                f"committed to {du['dir']!r} (last checkpoint "
                f"{du['last_checkpoint_ms']} ms, "
                f"{du['checkpoint_bytes_total']} snapshot bytes total); "
                + (f"restored from epoch {du['restored_epoch']}, "
                   f"{du['dedupe_hits']} replayed sink message(s) deduped "
                   "— restart the app with PipeGraph.restore() on this "
                   "store"
                   if du["restored_epoch"] is not None else
                   "restartable with PipeGraph.restore() on this store"))
    if d.get("latency"):
        la = d["latency"]
        n = lambda v: "?" if v is None else v
        lines.append(
            f"  latency: {n(la['traces_decomposed'])} trace(s) "
            f"decomposed (dropped={n(la['traces_dropped'])}, "
            f"ring events lost={n(la['events_lost'])}), "
            f"e2e p99 {n(la['e2e_p99_usec'])} µs")
        if la.get("top_op"):
            t = la["top_op"]
            share = t.get("budget_share")
            lines.append(
                f"    hottest op '{t['op']}' carries "
                f"{'?' if share is None else f'{share:.0%}'} of the "
                f"critical path, dominated by {n(t['dominant_segment'])}"
                + (f" (megastep K={t['megastep_k']}, freshness floor "
                   f"{n(t['freshness_floor_usec'])} µs)"
                   if t.get("megastep_k") else ""))
        if la.get("slo_budget_ms"):
            v = la.get("slo_verdict") or {}
            lines.append(
                f"    SLO budget {la['slo_budget_ms']} ms — "
                + ("VIOLATED: " + v.get("message", "?")
                   if la.get("slo_active")
                   else "within budget"
                   + (f" (last violation: {v.get('message')})"
                      if v else "")))
    if d.get("ir_audit"):
        ia = d["ir_audit"]
        finds = ia["findings"]
        lines.append(
            f"  IR audit: {ia['programs_audited']} lowered program(s) "
            f"audited — {len(finds)} WF9xx finding(s)"
            + (f", {ia['suppressed']} suppressed" if ia.get("suppressed")
               else "")
            + (f", pending (never lowered): {ia['pending']}"
               if ia.get("pending") else ""))
        for f in finds[:8]:
            lines.append(
                f"    {f.get('code')} [{f.get('severity')}] "
                f"'{f.get('node')}': {f.get('message')}")
    if d.get("tenancy"):
        tn = d["tenancy"]
        frac = (tn.get("attributed") or {}).get("staged_fraction")
        lines.append(
            f"  tenancy: {tn['tenants_total']} tenant(s) in process"
            + (f", attribution {frac:.0%} of staged bytes"
               if isinstance(frac, (int, float)) else ""))
        w = tn.get("worst")
        if w:
            n = lambda v: "?" if v is None else v
            press = w.get("pressure")
            lines.append(
                f"    worst pressure: '{w['tenant']}' at "
                f"{'?' if press is None else f'{press:.2f}x'} "
                f"({n(w['resident_state_bytes'])} B resident"
                + (f" / {w['budget_bytes']} B budget"
                   if w.get("budget_bytes") else "")
                + (f", heaviest op {w['heaviest_op']}"
                   if w.get("heaviest_op") else "") + ")")
            v = w.get("verdict")
            if v:
                tag = "OVER BUDGET (latched)" if w["over_budget"] \
                    else "last verdict"
                lines.append(f"    {tag}: {v.get('message')}")
    if d.get("calibration"):
        c = d["calibration"]
        cal = c.get("calibrated_constants") or []
        mod = c.get("modeled_constants") or []
        lines.append(
            "  calibration: "
            + (f"store '{c['source']}' for {c.get('device_kind') or '?'}"
               if c.get("enabled") else "no store loaded")
            + f" — {len(cal)} calibrated / {len(mod)} modeled constant(s)")
        if cal:
            lines.append(f"    calibrated: {', '.join(cal)}")
    if d.get("roofline"):
        r = d["roofline"]
        lines.append(
            f"  roofline: {r['hops']} fused hop(s) tracked "
            f"(bandwidth {r.get('bandwidth_provenance') or '?'})"
            + (f", dominant op '{r['dominant_op']}'"
               if r.get("dominant_op") else ""))
        w = r.get("worst_hop")
        if w and isinstance(w.get("ratio"), (int, float)):
            lines.append(
                f"    lowest ratio vs roofline: '{w['op']}' at "
                f"{w['ratio']:.3f}")
        v = r.get("verdict")
        if v:
            lines.append(
                f"    ROOFLINE DEGRADED: '{v.get('dominant_op')}' at "
                f"{v.get('ratio_vs_baseline')}x of trailing baseline")
    if d.get("reshard"):
        r = d["reshard"]
        lines.append(
            f"  Reshard executor: {r['plans_applied']} plan(s) applied "
            f"({r['keys_moved']} key(s) moved, {r['splits_applied']} "
            f"split(s), {r['preagg_folds']} tuple(s) pre-aggregated), "
            f"admission factor {r['admission_factor']}"
            + (f", last quiesce {r['quiesce_ms']} ms" if r.get(
                "quiesce_ms") is not None else "")
            + (f", recovery {r['recovery_ms']} ms" if r.get(
                "recovery_ms") is not None else ""))
        if r["timeline"]:
            lines.append("  reshard timeline:")
            for e in r["timeline"][-10:]:
                lines.append(
                    f"    t={e.get('t_usec')}: {e.get('op')} "
                    f"{e.get('event')} — {e.get('detail')}")
    if d["section_errors"]:
        lines.append(f"  degraded sections: {d['section_errors']}")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("bundle", help="postmortem bundle directory "
                                   "(PipeGraph.dump_postmortem output)")
    ap.add_argument("--check", action="store_true",
                    help="validate the bundle instead of rendering it")
    ap.add_argument("--json", action="store_true",
                    help="emit the diagnosis as JSON")
    args = ap.parse_args(argv)
    try:
        bundle = load_bundle(args.bundle)
        validate(bundle)
    except BundleError as e:
        print(f"wf_doctor: FAIL: {e}", file=sys.stderr)
        return 1
    if args.check:
        m = bundle["manifest"]
        print(f"wf_doctor: OK ({len(bundle['sections'])} sections, "
              f"app '{m['app']}', reason {m['reason']!r}"
              + (f", {len(m['errors'])} degraded" if m["errors"] else "")
              + ")")
        return 0
    d = diagnose(bundle)
    if args.json:
        json.dump(d, sys.stdout, indent=1)
        print()
    else:
        print(render_text(d))
    return 0


if __name__ == "__main__":
    sys.exit(main())
