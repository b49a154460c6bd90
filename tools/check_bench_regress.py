#!/usr/bin/env python
"""Run-over-run perf tripwire on bench_history.json.

``tools/check_bench_keys.py`` guards that the bench still EMITS its
contract keys; nothing guarded their VALUES — a hop that got 30% slower
sailed through CI as long as the key existed.  This check compares the
newest ``bench_history.json`` run per platform against the most recent
earlier run recorded under the SAME methodology (and, for e2e legs, the
same tuple count — CI runs the bench reduced) and trips on any guarded
scalar moving more than the threshold in the bad direction.

Under ``CI=1`` a regression fails (exit 1); locally it warns (exit 0),
because a laptop run racing a browser is not a regression.  Noise is
respected twice over: a key whose own recorded dispersion
(``rel_spread``) exceeds the threshold on either side of the comparison
is reported but never tripped — when the measurement's noise floor is
above the tripwire, the tripwire would only fire on weather — and a key
whose TRAILING HISTORY (the last same-methodology comparable runs)
already spreads wider than the threshold is likewise reported, not
tripped: within-run dispersion systematically understates run-to-run
variance on a shared box (five windows seconds apart share the same
weather; runs hours apart do not), and a key that historically swings
2x with no code change cannot honestly gate a 10% move.  Deterministic
keys (checkpoint bytes, seeded skew ratios) have flat histories and
stay hard-guarded.

Usage::

    python tools/check_bench_regress.py             # newest run, each
                                                    # platform in history
    python tools/check_bench_regress.py --platform cpu
    WF_BENCH_REGRESS_PCT=15 python tools/check_bench_regress.py

Wired into ``ci/run_tests.sh`` directly after the bench leg (which has
just appended the run under judgment).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HISTORY = os.path.join(REPO, "bench_history.json")

#: guarded scalars: (dotted path, higher_is_better, dispersion path or
#: None).  Dispersion gates the tripwire on that key's own noise floor.
GUARDED = (
    ("value", True, "dispersion.rel_spread"),
    ("dispatch_value", True, "dispatch_dispersion.rel_spread"),
    # sum_decl records no dispersion of its own; the chained kernel's
    # spread is the same program on the same machine minutes apart —
    # the honest noise proxy.  Same for the latency tails below: a p99
    # measured while the kernel windows spread 2x is weather.
    ("sum_decl_value", True, "dispersion.rel_spread"),
    ("e2e.tuples_per_sec", True, "e2e.dispersion.rel_spread"),
    ("e2e_device_source.tuples_per_sec", True,
     "e2e_device_source.dispersion.rel_spread"),
    ("reduce.sorted_tps", True, "reduce.sorted_dispersion.rel_spread"),
    ("reduce.dense_decl_tps", True,
     "reduce.dense_decl_dispersion.rel_spread"),
    ("latency.batch_p99_ms", False, "dispersion.rel_spread"),
    ("latency.e2e_p99_ms", False, "e2e.dispersion.rel_spread"),
    # durability plane: snapshot size is deterministic for a fixed
    # graph/cadence, so a >10% jump is a real regression (a new state
    # blob grew), not weather.  checkpoint_ms and overhead_pct are
    # deliberately NOT value-guarded here: both are short wall
    # measurements (checkpoint_ms includes an fsync; overhead_pct is the
    # ratio of two single-shot runs) whose infra jitter exceeds the
    # threshold, and no recorded dispersion describes them — the
    # overhead's hard budget lives in check_bench_keys instead.
    ("durability.checkpoint_bytes", False, None),
    # shard plane: the bench leg's stream is SEEDED, so the measured
    # imbalance and hot-key share are deterministic — any >10% move is
    # a sketch/placement regression, not weather.  Both directions
    # matter, but the ratios only drift DOWN when the sketch starts
    # losing counts, which is the failure mode worth tripping on.
    ("shard.imbalance_ratio", True, None),
    ("shard.hot_key_share", True, None),
    # key compaction: the whole round's reason to exist is the ratio —
    # compacted over sorted, measured as the median of PAIRED windows
    # (each round times both legs under the same instantaneous load),
    # so the ratio's own recorded spread is the honest noise gate.
    # hit_rate's hard 0.9 floor lives in check_bench_keys; this guards
    # the SPEED.
    ("compaction.speedup_vs_sorted", True,
     "compaction.speedup_dispersion.rel_spread"),
    # wire plane: the leg's stream is SEEDED and EVENT-timed, so the
    # measured wire bytes/tuple is deterministic — a >10% rise means a
    # codec stopped engaging (selection, fit check, or the dict union
    # broke), not weather.  LOWER is better.  compression_ratio's hard
    # 1.5x floor lives in check_bench_keys; this guards the trend.
    ("wire.wire_bytes_per_tuple", False, None),
    # reshard executor: keys_moved is fully deterministic on the seeded
    # colocated-warm-pair stream (trigger → advisor plan → apply), so
    # any change is a planner/trigger regression.  plan_apply_ms /
    # rescale_restore_ms are deliberately NOT guarded: both are short
    # single-shot wall measurements (the apply includes a full graph
    # quiesce, the restore an fsynced store replay) whose infra jitter
    # exceeds the threshold — their sanity bounds live in
    # check_bench_keys.
    ("reshard.keys_moved", True, None),
    # pallas kernels: the fused-step kernel-vs-lax ratio is the round's
    # headline (docs/PERF.md round 14).  Comparable only between runs
    # with the SAME interpret_mode (a compiled-TPU speedup and a
    # CPU-interpreter emulation measure different things — the
    # comparable() gate below); correctness has its own hard guard
    # (record_mismatch, check_bench_keys).
    ("pallas.ffat_step_speedup_vs_lax", True, None),
    ("pallas.grouping_speedup", True, None),
    # latency plane: the ledger-decomposed staged->sunk p99 at max
    # sustainable throughput (docs/OBSERVABILITY.md "Latency plane &
    # SLO") — LOWER is better.  A whole-pipeline wall tail on a shared
    # box has no recorded dispersion of its own, so the trailing-history
    # spread gate below is the honest noise floor; the hard bound (p99
    # past 2x the recorded SLO budget) lives in check_bench_keys.
    ("latency_slo.e2e_p99_ms", False, None),
    # megastep executor: the K-folded staged e2e rate is round 15's
    # headline (docs/PERF.md round 15) and the speedup over the K=1
    # kill switch is the claim the fold exists for — both gated on the
    # K-run's own recorded spread (a whole-pipeline wall measurement
    # on a shared box).  The hard floors (absolute CPU rate, the
    # 1-program-per-K-sweeps dispatch pin) live in check_bench_keys;
    # this guards the trend.
    ("megastep.e2e_tup_s", True, "megastep.dispersion.rel_spread"),
    ("megastep.speedup_vs_k1", True, "megastep.dispersion.rel_spread"),
    # tenant plane: the two-tenant leg is SEEDED, so the attributed
    # fraction is deterministic — any drop means the ledger stopped
    # reconciling (a new staging path it does not see, or a register
    # baseline bug), not weather.  HIGHER is better; the hard 0.9 floor
    # and the 2% overhead budget live in check_bench_keys — this guards
    # the trend.
    ("tenant.hbm_attributed_fraction", True, None),
)


def dig(obj, path: str):
    for part in path.split("."):
        if not isinstance(obj, dict):
            return None
        obj = obj.get(part)
    return obj


def comparable(cur: dict, prev: dict, path: str) -> bool:
    """Apples-to-apples guard: e2e legs only compare runs that pushed
    the same tuple count (CI runs the bench reduced via
    BENCH_E2E_TUPLES; comparing a 131k-tuple run against a 4M-tuple
    round would trip on configuration, not performance)."""
    # hardware gate first (docs/OBSERVABILITY.md "Calibration plane"):
    # rows recorded on different backends or device kinds measure
    # different machines, whatever the leg.  A MISSING stamp is a
    # wildcard — history predating the stamp stays comparable; only a
    # PRESENT-and-different stamp refuses.
    for stamp in ("backend", "device_kind"):
        a, b = cur.get(stamp), prev.get(stamp)
        if a is not None and b is not None and a != b:
            return False
    if path.startswith(("e2e.", "e2e_device_source.", "latency.e2e")):
        leg = "e2e_device_source" if path.startswith("e2e_device_source") \
            else "e2e"
        return dig(cur, f"{leg}.tuples") == dig(prev, f"{leg}.tuples")
    if path.startswith("durability."):
        # the durability leg sizes via BENCH_DURABILITY_TUPLES: different
        # stream lengths checkpoint different state — not comparable
        return dig(cur, "durability.tuples") == dig(prev,
                                                    "durability.tuples")
    if path.startswith("shard."):
        # the shard leg's skew numbers are seeded per tuple count
        # (BENCH_SHARD_TUPLES): a different stream is a different truth
        return dig(cur, "shard.tuples") == dig(prev, "shard.tuples")
    if path.startswith("wire."):
        # the wire leg is seeded per tuple count AND window spec (codec
        # choice sees the spec's lanes): only identical streams compare
        return dig(cur, "wire.tuples") == dig(prev, "wire.tuples")
    if path.startswith("reshard."):
        # the reshard leg's move count is seeded per tuple count
        # (BENCH_RESHARD_TUPLES): a different stream plans differently
        return dig(cur, "reshard.tuples") == dig(prev, "reshard.tuples")
    if path.startswith("pallas."):
        # interpret-mode (CPU emulated) and compiled-TPU kernel numbers
        # are different experiments; only like compares with like
        return dig(cur, "pallas.interpret_mode") == \
            dig(prev, "pallas.interpret_mode")
    if path.startswith("latency_slo."):
        # the latency-SLO leg is sized via BENCH_SLO_TUPLES and its tail
        # only compares at the SAME operating point: a different stream
        # length or label measures a different experiment
        return dig(cur, "latency_slo.tuples") == \
            dig(prev, "latency_slo.tuples") \
            and dig(cur, "latency_slo.operating_point") == \
            dig(prev, "latency_slo.operating_point")
    if path.startswith("tenant."):
        # the tenant leg is seeded per tuple count (BENCH_TENANT_TUPLES):
        # a different stream stages different bytes to reconcile
        return dig(cur, "tenant.tuples") == dig(prev, "tenant.tuples")
    if path.startswith("compaction."):
        # the compaction A/B is seeded per batch width (cfg["cap"]):
        # a different stream shape shifts the hot-set/overflow split
        # and with it the honest speedup
        return dig(cur, "compaction.tuples") == dig(prev,
                                                    "compaction.tuples")
    return True


def pick_baseline(runs: list, cur: dict):
    """Most recent run BEFORE the newest one with the same methodology
    (a methodology switch re-baselines, exactly like bench.py's
    vs_baseline); None when the newest run is the first of its kind."""
    prior = runs[:-1]
    same = [r for r in prior
            if r.get("methodology") == cur.get("methodology")]
    return same[-1] if same else None


#: trailing-history noise floor: how many prior same-methodology runs
#: to consider, and how many are needed before history can vouch for a
#: key (younger keys stay hard-guarded)
HISTORY_WINDOW = 8
HISTORY_MIN = 3


def history_spread(runs: list, cur: dict, path: str):
    """Relative spread ((max-min)/mean) of the guarded scalar over the
    trailing window of same-methodology comparable runs BEFORE the run
    under judgment; None when history is too short to vouch."""
    vals = []
    for r in runs[:-1]:
        if r.get("methodology") != cur.get("methodology"):
            continue
        if not comparable(cur, r, path):
            continue
        v = dig(r, path)
        if isinstance(v, (int, float)) and v:
            vals.append(float(v))
    vals = vals[-HISTORY_WINDOW:]
    if len(vals) < HISTORY_MIN:
        return None
    mean = sum(vals) / len(vals)
    return (max(vals) - min(vals)) / mean if mean else None


def check_platform(platform: str, runs: list, threshold: float) -> list:
    """[(path, change_pct, kind)] where kind is "regression" | "noisy"
    (own recorded dispersion above threshold) | "noisy_history"
    (trailing run-over-run spread above threshold)."""
    if len(runs) < 2:
        return []
    cur = runs[-1]
    prev = pick_baseline(runs, cur)
    if prev is None:
        return []
    findings = []
    for path, higher_better, disp_path in GUARDED:
        a, b = dig(prev, path), dig(cur, path)
        if not isinstance(a, (int, float)) \
                or not isinstance(b, (int, float)) or not a:
            continue
        if not comparable(cur, prev, path):
            continue
        change = (b - a) / a
        worse = -change if higher_better else change
        if worse <= threshold:
            continue
        noisy = False
        if disp_path is not None:
            for side in (cur, prev):
                spread = dig(side, disp_path)
                if isinstance(spread, (int, float)) \
                        and spread > threshold:
                    noisy = True
        kind = "regression"
        if noisy:
            kind = "noisy"
        else:
            hs = history_spread(runs, cur, path)
            if hs is not None and hs > threshold:
                kind = "noisy_history"
        findings.append((path, round(100 * change, 1), kind))
    return findings


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--platform", help="judge one platform only "
                                       "(default: every platform with "
                                       ">= 2 recorded runs)")
    ap.add_argument("--history", default=HISTORY,
                    help="bench_history.json path")
    args = ap.parse_args(argv)
    threshold = float(os.environ.get("WF_BENCH_REGRESS_PCT", "10")) / 100.0
    strict = os.environ.get("CI") not in (None, "", "0")
    if not os.path.exists(args.history):
        # the history is a local, git-ignored record: a fresh checkout
        # has none, and with nothing to compare nothing regressed
        print(f"check_bench_regress: OK — no history at {args.history}")
        return 0
    try:
        with open(args.history) as f:
            hist = json.load(f)
    except (OSError, ValueError) as e:
        print(f"check_bench_regress: FAIL: cannot read {args.history}: "
              f"{e}", file=sys.stderr)
        return 1
    platforms = [args.platform] if args.platform else sorted(hist)
    tripped = False
    for platform in platforms:
        runs = hist.get(platform)
        if not isinstance(runs, list):
            continue
        findings = check_platform(platform, runs, threshold)
        for path, pct, kind in findings:
            if kind == "noisy":
                print(f"check_bench_regress: note [{platform}] {path} "
                      f"moved {pct:+}% but its recorded dispersion "
                      f"exceeds the {threshold:.0%} threshold — noise "
                      "floor, not tripped")
            elif kind == "noisy_history":
                print(f"check_bench_regress: note [{platform}] {path} "
                      f"moved {pct:+}% but its trailing run-over-run "
                      f"spread already exceeds the {threshold:.0%} "
                      "threshold — historical noise floor, not tripped")
            else:
                tripped = True
                print(f"check_bench_regress: "
                      f"{'FAIL' if strict else 'WARN'} [{platform}] "
                      f"{path} regressed {pct:+}% vs the previous "
                      f"same-methodology run (threshold "
                      f"{threshold:.0%})",
                      file=sys.stderr if strict else sys.stdout)
        if not findings:
            print(f"check_bench_regress: OK [{platform}] — no guarded "
                  f"key moved more than {threshold:.0%} the wrong way")
    if tripped and strict:
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
