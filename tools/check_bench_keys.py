#!/usr/bin/env python
"""Guard the bench e2e decomposition contract (r6 CI check).

The staging-plane work is only provable through two keys in ``bench.py``
output — ``ratio_vs_kernel`` (staged e2e rate over kernel-only rate) and
``staging_share_of_staged_run`` (staged-vs-device-source delta) — and
round-over-round comparisons (BENCH_r05.json baseline: 0.7153 / 0.1964)
silently break if a bench refactor drops either.  This check fails CI
when they disappear.

Usage::

    python tools/check_bench_keys.py             # static: scan bench.py
    python tools/check_bench_keys.py OUT.json    # dynamic: check a bench
                                                 # run's captured output

With a file argument, the last JSON object found in the file (bench.py
prints its result dict as the final stdout line; log lines above it are
skipped) must carry ``e2e.ratio_vs_kernel`` and
``e2e_device_source.decomposition.staging_share_of_staged_run`` (a
section that raises ends ``bench.py`` non-zero, so no output carries an
errored section).
Without arguments, ``bench.py``'s source must still contain the code
paths that emit both keys.

Since the flight-recorder round the bench also publishes a ``latency``
section (``batch_p99_ms`` always; ``e2e_p50_ms``/``e2e_p99_ms`` when the
staged e2e leg ran) recorded into ``bench_history.json`` — the tail
numbers the observability layer steers by (docs/OBSERVABILITY.md).  This
check guards those keys the same way.

Since the static-analysis round the bench also publishes a ``preflight``
section whose ``check_ms`` times ``PipeGraph.check()`` over the
representative e2e pipeline — every ``start()`` now pays that cost, so
it must stay visible in bench_history.json (docs/ANALYSIS.md).  Guarded
here identically.

Since the device-plane round the bench also publishes a ``device``
section from the compile watcher (``compile_ms_total``, ``recompiles``,
``flops_per_batch`` where the backend reports cost analysis —
docs/OBSERVABILITY.md "Device plane").  ``recompiles`` doubles as a
regression tripwire: the bench pipelines pad to fixed capacities, so any
nonzero value is a shape-drift bug.  Guarded here identically.

Since the health round the bench also publishes a ``health`` section
(``stall_events``, ``watchdog_overhead_pct`` — docs/OBSERVABILITY.md
"Health plane") from a watchdog-on pipeline run.  ``stall_events``
doubles as a tripwire: the bench pipeline must run healthy, so any
nonzero value (or a non-OK ``graph_state``) is a watchdog
false-positive or a real runtime regression.  Guarded here identically.

Since the sweep-ledger round the bench also decomposes the roofline:
``roofline.per_hop`` (bytes/tuple + dispatches/batch per operator hop
of the staged e2e pipeline) and ``roofline.attributed_fraction`` (hop
sum over the raw kernel step's measured bytes — docs/OBSERVABILITY.md
"Sweep ledger").  Guarded here identically; their disappearance would
orphan the whole-chain-fusion plan (ROADMAP item 1) of its evidence.

Since the durability round the bench also publishes a ``durability``
section (``checkpoint_ms``, ``restore_ms``, ``checkpoint_bytes``,
``overhead_pct`` of enabling checkpointing vs checkpoint-off on the
representative graph — docs/DURABILITY.md).  ``overhead_pct`` is the
acceptance bound's evidence (<5%); its disappearance would orphan the
whole exactly-once/restore contract of its perf guard.  Guarded here
identically.

Since the shard-plane round the bench also publishes a ``shard``
section (``imbalance_ratio``, ``hot_key_share``,
``ici_bytes_per_tuple`` — docs/OBSERVABILITY.md "Shard plane") from a
seeded Zipf-skew keyby run with the shard ledger on.  The stream is
deterministic, so the skew numbers are regression tripwires (wired
into ``check_bench_regress.py``): a drifting ``imbalance_ratio`` means
the sketch or the placement hash broke, and ``sketch_overhead_pct``
doubles as the <2% budget's evidence.  Guarded here identically.

Since the key-compaction round the bench also publishes a
``compaction`` section (``speedup_vs_sorted``, ``hit_rate``,
``overflow_share``, ``churn_per_sweep`` — docs/PERF.md round 12) from
a seeded Zipf arbitrary-key reduce A/B: the compacted remap path vs
the legacy sorted path on the same batch.  ``hit_rate`` hard-fails
below 0.9 — under that floor the speedup number is measuring the
overflow lane, not the dense fast path — and ``speedup_vs_sorted`` is
tripwired in ``check_bench_regress.py``.  Guarded here identically.

Since the wfverify round the bench also publishes a ``verify`` section
(``findings``, ``check_ms`` — docs/ANALYSIS.md "wfverify") timing the
object-level kernel verifier over the representative pipeline.
``findings`` doubles as a tripwire: the bench kernels ship clean, so
any nonzero unsuppressed count is a verifier false positive or a real
kernel regression — both block.  Guarded here identically.

Since the pallas round the bench also publishes a ``pallas`` section
(``kernels_active``, ``ffat_step_speedup_vs_lax``, ``grouping_speedup``,
``interpret_mode``, ``record_mismatch`` — docs/PERF.md round 14) from a
seeded kernel-vs-lax A/B of the fused FFAT step.  ``record_mismatch``
hard-fails: the kernel-backed step must be bit-identical to the lax
build on the integer-valued seed stream.  ``interpret_mode`` is the
honesty flag — CPU runs emulate the kernels (slower by design), so the
speedup keys are only comparable across runs with the same flag
(``check_bench_regress.py`` gates on it).  Guarded here identically.

Since the megastep round the bench also publishes a ``megastep``
section (``k``, ``e2e_tup_s``, ``speedup_vs_k1``,
``dispatches_per_batch``, ``ratio_vs_kernel`` — docs/PERF.md round 15 /
docs/OBSERVABILITY.md "Megastep in the ledger") from a dispatch-bound
staged-e2e A/B of K folded sweeps vs the K=1 kill switch.  Two hard
gates ride on it: ``e2e_tup_s`` must clear the section's own
``e2e_floor_tup_s`` (CPU: 10x the r14 54.8k per-batch baseline), and
``dispatches_per_batch`` must equal 1/k exactly over the scanned
batches — any excess means the megastep grew extra device dispatches
and the 1-program-per-K-sweeps contract broke.  Guarded here
identically.

Since the latency-plane round the bench also publishes a
``latency_slo`` section (``operating_point``, ``slo_budget_ms``,
``e2e_p99_ms``, ``dominant_op``/``dominant_segment``,
``segment_share`` — docs/OBSERVABILITY.md "Latency plane & SLO") from
a flight-recorder-on pipeline driven at max sustainable throughput:
the ledger-decomposed staged→sunk tail against a declared budget.
Every latency row must carry its ``operating_point`` label — a p99
without the rate it was measured at is not comparable round over
round — and the measured ``e2e_p99_ms`` hard-fails past 2x the
recorded ``slo_budget_ms``: the bench pipelines must run inside their
own declared SLO with margin.  Guarded here identically.

Since the fusion round the bench also publishes a ``fusion`` section
(``fused_chains``, ``dispatches_saved``, ``bytes_saved_per_batch`` —
docs/PERF.md round 10) from the staged e2e run's sweep ledger: the
realized savings of the whole-chain fusion executor
(windflow_tpu/fusion).  Guarded here identically — the section ships
(zeroed) even under the WF_TPU_FUSE=0 kill switch, so its absence is a
bench regression, not a configuration.

Since the calibration round the bench also stamps every result with
``backend``/``device_kind``/``jax_version`` and publishes a
``calibration`` section (the provenance summary: which constants the
modeled numbers were computed from, and whether a calibration store
replaced the defaults — docs/OBSERVABILITY.md "Calibration plane").
Provenance is also a HARD honesty gate here: every provenance tag in
the output must come from the measured/modeled/calibrated(age)/
interpret vocabulary, and a run stamped ``backend == "tpu"`` whose
pallas section still reports ``interpret_mode`` true is lying about
its numbers — the TPU acceptance leg (``tpu_acceptance``: the ROADMAP
item-1 criteria next to their measured values) must never be fed by
the interpreter.
"""

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KEYS = ("ratio_vs_kernel", "staging_share_of_staged_run")
LATENCY_KEYS = ("batch_p99_ms", "e2e_p50_ms", "e2e_p99_ms",
                "operating_point")
LATENCY_SLO_KEYS = ("operating_point", "tuples_per_sec", "slo_budget_ms",
                    "e2e_p50_ms", "e2e_p99_ms", "traces_decomposed",
                    "dominant_op", "dominant_segment", "segment_share",
                    "slo_active")
ROOFLINE_KEYS = ("per_hop", "attributed_fraction")
FUSION_KEYS = ("fused_chains", "dispatches_saved", "bytes_saved_per_batch")
DEVICE_KEYS = ("compile_ms_total", "recompiles", "flops_per_batch")
HEALTH_KEYS = ("graph_state", "stall_events", "watchdog_overhead_pct")
DURABILITY_KEYS = ("checkpoint_ms", "restore_ms", "checkpoint_bytes",
                   "overhead_pct")
SHARD_KEYS = ("imbalance_ratio", "hot_key_share", "ici_bytes_per_tuple")
VERIFY_KEYS = ("findings", "check_ms")
IR_AUDIT_KEYS = ("programs_audited", "findings", "check_ms")
WIRE_KEYS = ("wire_bytes_per_tuple", "compression_ratio",
             "staging_share", "decode_dispatch_delta")
COMPACTION_KEYS = ("speedup_vs_sorted", "hit_rate", "overflow_share",
                   "churn_per_sweep")
RESHARD_KEYS = ("plan_apply_ms", "rescale_restore_ms", "keys_moved",
                "post_reshard_imbalance")
PALLAS_KEYS = ("kernels_active", "ffat_step_speedup_vs_lax",
               "grouping_speedup", "interpret_mode", "record_mismatch",
               "provenance")
MEGASTEP_KEYS = ("k", "e2e_tup_s", "e2e_floor_tup_s", "speedup_vs_k1",
                 "dispatches_per_batch", "ratio_vs_kernel")
TENANT_KEYS = ("tenants", "hbm_attributed_fraction", "budget_pressure",
               "ledger_overhead_pct")
CALIBRATION_KEYS = ("schema", "enabled", "constants")
STAMP_KEYS = ("backend", "device_kind", "jax_version")
TPU_ACCEPTANCE_KEYS = ("grouping_speedup", "grouping_speedup_target",
                       "grouping_speedup_met", "e2e_wire_bytes_per_tuple",
                       "ici_bytes_per_tuple", "megastep_ratio_vs_kernel",
                       "interpret_mode")
# the full provenance vocabulary (docs/OBSERVABILITY.md "Calibration
# plane"): three fixed tags plus the age-stamped calibrated(...) form
PROVENANCE_FIXED = ("measured", "modeled", "interpret")


def legal_provenance(tag) -> bool:
    return tag in PROVENANCE_FIXED or (
        isinstance(tag, str) and tag.startswith("calibrated("))


def fail(msg: str) -> None:
    print(f"check_bench_keys: FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def check_source() -> None:
    with open(os.path.join(REPO, "bench.py")) as f:
        src = f.read()
    missing = [k for k in KEYS if f'"{k}"' not in src]
    if missing:
        fail(f"bench.py no longer emits {missing} — the e2e "
             "decomposition contract (docs/PERF.md) is broken")
    for section, keys, contract in (
            ("latency", LATENCY_KEYS, "docs/OBSERVABILITY.md"),
            ("latency_slo", LATENCY_SLO_KEYS,
             "latency ledger — docs/OBSERVABILITY.md latency plane "
             "& SLO"),
            ("roofline", ROOFLINE_KEYS,
             "sweep ledger — docs/OBSERVABILITY.md sweep-ledger"),
            ("fusion", FUSION_KEYS,
             "whole-chain fusion — docs/PERF.md round 10"),
            ("preflight", ("check_ms",), "docs/ANALYSIS.md"),
            ("verify", VERIFY_KEYS,
             "wfverify — docs/ANALYSIS.md wfverify section"),
            ("ir_audit", IR_AUDIT_KEYS,
             "wfir — docs/ANALYSIS.md wfir section"),
            ("device", DEVICE_KEYS,
             "compile watcher — docs/OBSERVABILITY.md device-plane"),
            ("health", HEALTH_KEYS,
             "watchdog — docs/OBSERVABILITY.md health-plane"),
            ("shard", SHARD_KEYS,
             "shard plane — docs/OBSERVABILITY.md shard-plane"),
            ("compaction", COMPACTION_KEYS,
             "key compaction — docs/PERF.md round 12"),
            ("wire", WIRE_KEYS,
             "wire compression — docs/PERF.md round 13 / "
             "docs/OBSERVABILITY.md wire plane"),
            ("durability", DURABILITY_KEYS,
             "checkpoint/restore — docs/DURABILITY.md"),
            ("reshard", RESHARD_KEYS,
             "reshard executor + rescale restore — "
             "docs/OBSERVABILITY.md reshard-executor / "
             "docs/DURABILITY.md rescale-on-restore"),
            ("pallas", PALLAS_KEYS,
             "Pallas kernels — docs/PERF.md round 14"),
            ("megastep", MEGASTEP_KEYS,
             "megastep executor — docs/PERF.md round 15 / "
             "docs/OBSERVABILITY.md megastep-in-the-ledger"),
            ("tenant", TENANT_KEYS,
             "tenant plane — docs/OBSERVABILITY.md tenant-plane"),
            # the calibration section's inner keys come from
            # provenance_summary() (not bench.py literals) — the static
            # pass guards the section name + the hardware stamp;
            # check_output validates the summary's shape dynamically
            ("calibration", STAMP_KEYS,
             "calibration plane — docs/OBSERVABILITY.md "
             "calibration-plane"),
            ("tpu_acceptance", TPU_ACCEPTANCE_KEYS,
             "TPU acceptance leg — ROADMAP item 1 / "
             "docs/OBSERVABILITY.md calibration-plane")):
        missing = [k for k in keys if f'"{k}"' not in src] \
            + ([] if f'"{section}"' in src else [section])
        if missing:
            fail(f"bench.py no longer emits the {section} section keys "
                 f"{missing} ({contract} contract)")
    print("check_bench_keys: OK (bench.py source emits "
          + ", ".join(KEYS + ("latency", "latency_slo", "preflight",
                              "verify", "device", "health", "shard",
                              "compaction", "fusion", "durability",
                              "reshard", "pallas")) + ")")


def last_json_object(path: str):
    """The bench result dict: last line of the file that parses as a JSON
    object (bench.py prints it as its final stdout line)."""
    obj = None
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line.startswith("{"):
                try:
                    cand = json.loads(line)
                except ValueError:
                    continue
                if isinstance(cand, dict):
                    obj = cand
    return obj


def check_output(path: str) -> None:
    result = last_json_object(path)
    if result is None:
        fail(f"no JSON result object found in {path}")
    e2e = result.get("e2e")
    if not isinstance(e2e, dict):
        fail("bench result has no 'e2e' section")
    if "ratio_vs_kernel" not in e2e:
        fail("'e2e.ratio_vs_kernel' missing from bench output")
    dev = result.get("e2e_device_source")
    if not isinstance(dev, dict):
        fail("bench output has no 'e2e_device_source' section")
    decomp = dev.get("decomposition", {})
    if "staging_share_of_staged_run" not in decomp:
        fail("'e2e_device_source.decomposition."
             "staging_share_of_staged_run' missing from bench output")
    share = decomp["staging_share_of_staged_run"]
    lat = result.get("latency")
    if not isinstance(lat, dict):
        fail("'latency' section missing from bench output")
    if "batch_p99_ms" not in lat:
        fail("'latency.batch_p99_ms' missing from bench output")
    if not lat.get("operating_point"):
        # unlabeled latency rows are not comparable round over round:
        # a p99 means nothing without the rate it was measured at
        fail("'latency.operating_point' missing — latency rows must "
             "name their operating point")
    lslo = result.get("latency_slo")
    if isinstance(lslo, dict):
        missing = [k for k in LATENCY_SLO_KEYS if k not in lslo]
        if missing:
            fail(f"'latency_slo' section missing {missing} from bench "
                 "output")
        if not lslo.get("operating_point"):
            fail("'latency_slo.operating_point' empty — latency rows "
                 "must name their operating point")
        budget = lslo.get("slo_budget_ms")
        p99 = lslo.get("e2e_p99_ms")
        if isinstance(budget, (int, float)) and budget > 0 \
                and isinstance(p99, (int, float)) and p99 > 2 * budget:
            # the shipped bench pipeline must run inside its own
            # declared SLO with margin: a p99 past 2x the budget is a
            # latency regression on the representative shape, not noise
            fail(f"latency_slo e2e_p99_ms={p99} exceeds 2x the recorded "
                 f"SLO budget ({budget} ms) on the shipped bench shape")
        if not lslo.get("traces_decomposed"):
            fail("latency_slo leg decomposed no traces — the ledger's "
                 "harvest or the recorder's sampling broke")
    else:
        # the latency-SLO leg is an in-process flight-recorder run with
        # no environmental failure mode — its absence IS the regression
        fail("bench latency_slo section absent")
    dev_sec = result.get("device")
    if isinstance(dev_sec, dict):
        missing = [k for k in DEVICE_KEYS if k not in dev_sec]
        if missing:
            fail(f"'device' section missing {missing} from bench output")
        if dev_sec.get("recompiles"):
            # fixed-capacity pipelines must never re-trace: a nonzero
            # recompile count is the shape-drift regression the compile
            # watcher exists to catch
            fail(f"bench run recompiled {dev_sec['recompiles']} time(s) — "
                 "recompilation storm in a fixed-capacity pipeline")
    else:
        # like preflight, the watcher is environment-independent: its
        # absence IS the observability regression this guard catches
        fail("bench device section absent")
    health = result.get("health")
    if isinstance(health, dict):
        missing = [k for k in HEALTH_KEYS if k not in health]
        if missing:
            fail(f"'health' section missing {missing} from bench output")
        if health.get("stall_events") or health.get("graph_state") != "OK":
            # the bench pipeline must run healthy: a stall event or a
            # degraded graph verdict here is either a watchdog
            # false-positive or a real runtime regression — both block
            fail(f"bench health run degraded: {health}")
    else:
        # like preflight, the watchdog leg is device-free — its absence
        # IS the observability regression this guard catches
        fail("bench health section absent")
    roof = result.get("roofline")
    if not isinstance(roof, dict):
        fail("'roofline' section missing from bench output")
    if isinstance(result.get("e2e"), dict):
        # the staged e2e leg ran: the sweep ledger must have attributed
        # its hops (docs/OBSERVABILITY.md "Sweep ledger")
        if not isinstance(roof.get("per_hop"), dict) \
                or not roof["per_hop"]:
            fail("'roofline.per_hop' missing or empty — the sweep "
                 "ledger's per-hop attribution is broken")
        if roof.get("measured_bytes_per_tuple") \
                and not isinstance(roof.get("attributed_fraction"),
                                   (int, float)):
            fail("'roofline.attributed_fraction' missing although the "
                 "kernel step's bytes were measured — per-hop bytes "
                 "did not attribute")
    fus = result.get("fusion")
    if isinstance(fus, dict):
        missing = [k for k in FUSION_KEYS if k not in fus]
        if missing:
            fail(f"'fusion' section missing {missing} from bench output")
    else:
        # the fusion section derives from the e2e sweep ledger with no
        # environmental failure mode (it ships zeroed under the
        # WF_TPU_FUSE kill switch) — its absence IS the regression
        fail("bench fusion section absent from bench output")
    shard = result.get("shard")
    if isinstance(shard, dict):
        missing = [k for k in SHARD_KEYS if k not in shard]
        if missing:
            fail(f"'shard' section missing {missing} from bench output")
        hot = shard.get("hot_key")
        if hot is not None and hot != 7:
            # the shard leg injects key 7 as 40% of the stream — the
            # ledger failing to name it means the sketch broke
            fail(f"shard leg misattributed the seeded hot key: got "
                 f"{hot!r}, injected 7")
        ovh = shard.get("sketch_overhead_pct")
        if isinstance(ovh, (int, float)) and ovh > 2.0:
            fail(f"shard sketch overhead {ovh}% exceeds the 2% budget "
                 "(docs/OBSERVABILITY.md shard plane)")
    else:
        # the shard leg runs on any backend with no environmental
        # failure mode — its absence IS the regression
        fail("bench shard section absent")
    compc = result.get("compaction")
    if isinstance(compc, dict):
        missing = [k for k in COMPACTION_KEYS if k not in compc]
        if missing:
            fail(f"'compaction' section missing {missing} from bench "
                 "output")
        hr = compc.get("hit_rate")
        if not isinstance(hr, (int, float)) or hr < 0.9:
            # the leg seeds the remap with the Zipf stream's hot set
            # before measuring: a hit rate under 0.9 means admission,
            # the lookup, or the seeding walk broke — the speedup
            # number above it would be measuring the overflow lane
            fail(f"compaction hit_rate={hr!r} below the 0.9 floor on "
                 "the seeded Zipf leg (docs/PERF.md round 12)")
        if compc.get("big_fallbacks"):
            # ~2% of lanes miss per batch — nowhere near the
            # capacity//32 overflow budget, so any full-width fallback
            # here means the miss accounting broke
            fail(f"compaction leg took {compc['big_fallbacks']} "
                 "full-width sorted fallbacks on a 2%-miss stream")
    else:
        # the compaction leg is an in-process kernel A/B with no
        # environmental failure mode — its absence IS the regression
        fail("bench compaction section absent")
    wr = result.get("wire")
    if isinstance(wr, dict):
        missing = [k for k in WIRE_KEYS if k not in wr]
        if missing:
            fail(f"'wire' section missing {missing} from bench output")
        cr = wr.get("compression_ratio")
        if not isinstance(cr, (int, float)) or cr < 1.5:
            # the seeded leg runs the e2e record spec (dict key lane,
            # raw f32 value, cadence ts): under 1.5x means a codec,
            # the selection, or the encoder broke — the wire round's
            # whole claim (docs/PERF.md round 13)
            fail(f"wire compression_ratio={cr!r} below the 1.5x floor "
                 "on the e2e record spec")
        dd = wr.get("decode_dispatch_delta")
        if dd:
            # the decode is traced INTO the existing unpack program;
            # ANY nonzero per-batch dispatch delta means it grew its
            # own dispatch — the zero-extra-dispatch contract broke
            fail(f"wire decode_dispatch_delta={dd} — decompression "
                 "added device dispatches (must ride staging.unpack)")
    else:
        # the wire leg is an in-process seeded A/B with no
        # environmental failure mode — its absence IS the regression
        fail("bench wire section absent")
    dura = result.get("durability")
    if isinstance(dura, dict):
        missing = [k for k in DURABILITY_KEYS if k not in dura]
        if missing:
            fail(f"'durability' section missing {missing} from bench "
                 "output")
        ov = dura.get("overhead_pct")
        if isinstance(ov, (int, float)) and ov > 15.0:
            # the budget is 5% (docs/DURABILITY.md), but overhead_pct is
            # the ratio of two short single-shot timed runs whose own
            # noise is ~±13% on this infra (check_bench_regress excludes
            # it for the same reason) — hard-fail only past a
            # noise-padded bound a real hot-path regression clears
            fail(f"durability overhead_pct={ov} is far past the 5% "
                 "budget — checkpointing has become a hot-path cost")
        elif isinstance(ov, (int, float)) and ov > 5.0:
            print(f"check_bench_keys: note: durability overhead_pct={ov} "
                  "above the 5% budget — single-sample ratio, rerun to "
                  "separate regression from timing noise")
    else:
        # the durability leg runs against the in-memory broker with no
        # environmental failure mode — its absence IS the regression
        fail("bench durability section absent")
    rsh = result.get("reshard")
    if isinstance(rsh, dict):
        missing = [k for k in RESHARD_KEYS if k not in rsh]
        if missing:
            fail(f"'reshard' section missing {missing} from bench "
                 "output")
        if not rsh.get("keys_moved"):
            # the seeded colocated-warm-pair stream is deterministic:
            # a leg that moved no keys means the trigger, the plan, or
            # the apply path broke
            fail("reshard leg moved no keys on the seeded "
                 "colocated-warm-pair stream — the executor's "
                 "trigger→plan→apply path broke")
        pri = rsh.get("post_reshard_imbalance")
        if isinstance(pri, (int, float)) and pri > 2.5:
            fail(f"post_reshard_imbalance={pri} — the applied move did "
                 "not repair the window imbalance on the seeded stream")
    else:
        # the reshard leg runs in-process on a seeded stream with no
        # environmental failure mode — its absence IS the regression
        fail("bench reshard section absent")
    pal = result.get("pallas")
    if isinstance(pal, dict):
        missing = [k for k in PALLAS_KEYS if k not in pal]
        if missing:
            fail(f"'pallas' section missing {missing} from bench output")
        if pal.get("record_mismatch"):
            # the canary: the kernel-backed step's first batch must be
            # BIT-IDENTICAL to the lax build's on the integer-valued
            # seed stream — any mismatch is a kernel correctness
            # regression, not a perf question (docs/PERF.md round 14)
            fail("pallas record-mismatch canary tripped: the "
                 "kernel-backed FFAT step diverged from the lax path")
        if pal.get("kernels_active") and pal.get("interpret_mode") is None:
            fail("pallas section reports active kernels without an "
                 "interpret_mode flag — the speedup numbers are "
                 "uninterpretable without it")
    else:
        # the pallas leg is an in-process kernel A/B with no
        # environmental failure mode — its absence IS the regression
        fail("bench pallas section absent")
    msec = result.get("megastep")
    if isinstance(msec, dict):
        missing = [k for k in MEGASTEP_KEYS if k not in msec]
        if missing:
            fail(f"'megastep' section missing {missing} from bench "
                 "output")
        floor = msec.get("e2e_floor_tup_s") or 0
        tps = msec.get("e2e_tup_s")
        if isinstance(tps, (int, float)) and floor and tps < floor:
            # the r15 acceptance floor: the K-folded staged e2e must
            # hold 10x the r14 per-batch CPU baseline — falling under
            # it means the megastep stopped scanning (check the
            # fallback_batches count) or the driver loop regressed
            fail(f"megastep e2e_tup_s={tps} under the "
                 f"{floor} floor (docs/PERF.md round 15)")
        kk, dpb = msec.get("k"), msec.get("dispatches_per_batch")
        if isinstance(kk, int) and kk > 1:
            if not isinstance(dpb, (int, float)):
                fail("megastep ran with K>1 but dispatches_per_batch "
                     "is absent — no batch was ever scanned (the "
                     "plane downgraded or the warm check never passed)")
            if abs(dpb * kk - 1.0) > 1e-6:
                # the 1-program-per-K-sweeps contract, pinned by the
                # jit registry's megastep.* dispatch count: over the
                # scanned batches the ratio is 1/K EXACTLY — warmup
                # and EOS-remainder batches are reported separately
                fail(f"megastep dispatches_per_batch={dpb} != 1/{kk} — "
                     "the folded program grew extra device dispatches")
    else:
        # the megastep leg is an in-process staged-e2e A/B with no
        # environmental failure mode — its absence IS the regression
        fail("bench megastep section absent")
    tenant = result.get("tenant")
    if isinstance(tenant, dict):
        missing = [k for k in TENANT_KEYS if k not in tenant]
        if missing:
            fail(f"'tenant' section missing {missing} from bench "
                 "output")
        frac = tenant.get("hbm_attributed_fraction")
        if not isinstance(frac, (int, float)) or frac < 0.9:
            # the reconciliation floor (docs/OBSERVABILITY.md tenant
            # plane): the ledger must attribute at least 90% of the
            # process's staged device bytes to tenants — under it the
            # per-tenant numbers are not trustworthy enough to schedule
            # against
            fail(f"tenant hbm_attributed_fraction={frac!r} below the "
                 "0.9 reconciliation floor on the seeded two-tenant "
                 "leg")
        ovh = tenant.get("ledger_overhead_pct")
        if isinstance(ovh, (int, float)) and ovh > 2.0:
            fail(f"tenant ledger overhead {ovh}% exceeds the 2% budget "
                 "(docs/OBSERVABILITY.md tenant plane)")
    else:
        # the tenant leg is an in-process seeded two-graph run with no
        # environmental failure mode — its absence IS the regression
        fail("bench tenant section absent")
    ver = result.get("verify")
    if isinstance(ver, dict):
        missing = [k for k in VERIFY_KEYS if k not in ver]
        if missing:
            fail(f"'verify' section missing {missing} from bench output")
        if ver.get("findings"):
            # the bench pipeline's kernels ship clean: a nonzero
            # unsuppressed finding count is either a wfverify false
            # positive or a real kernel regression — both block
            fail(f"bench verify run reported {ver['findings']} "
                 "unsuppressed wfverify finding(s) on the shipped "
                 "bench kernels")
    else:
        # wfverify is device-free (static analysis of live callables) —
        # its absence IS the analysis regression this guard catches
        fail("bench verify section absent")
    ira = result.get("ir_audit")
    if isinstance(ira, dict):
        missing = [k for k in IR_AUDIT_KEYS if k not in ira]
        if missing:
            fail(f"'ir_audit' section missing {missing} from bench "
                 "output")
        if not ira.get("programs_audited"):
            # the bench legs above compiled dozens of wf_jit programs
            # through the compile watcher: zero captured lowerings means
            # the registry hook or the capture path broke
            fail("bench ir_audit audited zero programs — the compile "
                 "watcher's lowering capture (analysis/ir_audit.py) "
                 "stopped recording")
        if ira.get("findings"):
            # shipped bench programs audit clean on the IR: a nonzero
            # WF9xx count is a lowering regression (a host callback, a
            # 64-bit survivor, a donation miss in a compiled program)
            # or an auditor false positive — both block
            fail(f"bench ir_audit reported {ira['findings']} WF9xx "
                 "finding(s) on the shipped bench programs")
    else:
        # the IR audit parses lowerings already captured in-process —
        # device-free, no environmental failure mode: its absence IS
        # the analysis regression this guard catches
        fail("bench ir_audit section absent")
    pf = result.get("preflight")
    if isinstance(pf, dict):
        if "check_ms" not in pf:
            fail("'preflight.check_ms' missing from bench output")
    else:
        # unlike the device-source leg, preflight is device-free — it has
        # no legitimate environmental failure mode, so an error IS the
        # analysis regression this guard exists to catch
        fail("bench preflight timing absent")
    for k in STAMP_KEYS:
        if not result.get(k):
            # an unstamped result can be diffed against any hardware's
            # history — check_bench_regress's comparability gate needs
            # the stamp to refuse cross-hardware comparisons
            fail(f"bench result missing the {k!r} hardware stamp "
                 "(docs/OBSERVABILITY.md calibration plane)")
    calib = result.get("calibration")
    if isinstance(calib, dict):
        missing = [k for k in CALIBRATION_KEYS if k not in calib]
        if missing:
            fail(f"'calibration' section missing {missing} from bench "
                 "output")
        for key, slot in (calib.get("constants") or {}).items():
            tag = (slot or {}).get("provenance") \
                if isinstance(slot, dict) else None
            if not legal_provenance(tag):
                fail(f"calibration constant {key!r} carries illegal "
                     f"provenance {tag!r} — the vocabulary is "
                     "measured/modeled/calibrated(age)/interpret")
    else:
        # the provenance summary is pure-host bookkeeping with no
        # environmental failure mode — its absence IS the regression
        fail("bench calibration section absent")
    if pal.get("provenance") is not None \
            and not legal_provenance(pal["provenance"]):
        fail(f"pallas provenance {pal['provenance']!r} is not in the "
             "measured/modeled/calibrated(age)/interpret vocabulary")
    if result.get("backend") == "tpu":
        # the honesty gate: a TPU-stamped row whose kernel timings came
        # from the Pallas interpreter is not a TPU measurement — the
        # fallback must never masquerade as acceptance evidence
        if pal.get("interpret_mode"):
            fail("result stamped backend=tpu but the pallas section "
                 "ran under the interpreter (interpret_mode=true) — "
                 "interpreter timings must never be recorded as TPU "
                 "measurements")
        acc = result.get("tpu_acceptance")
        if not isinstance(acc, dict):
            fail("backend=tpu result has no 'tpu_acceptance' section "
                 "(ROADMAP item 1 acceptance numbers)")
        missing = [k for k in TPU_ACCEPTANCE_KEYS if k not in acc]
        if missing:
            fail(f"'tpu_acceptance' section missing {missing} from "
                 "bench output")
        if acc.get("interpret_mode"):
            fail("tpu_acceptance leg claims interpret-mode numbers — "
                 "acceptance evidence must be compiled-chip measurements")
        for k in ("grouping_provenance", "wire_provenance",
                  "ici_provenance", "megastep_provenance"):
            if k in acc and not legal_provenance(acc[k]):
                fail(f"tpu_acceptance {k}={acc[k]!r} is not a legal "
                     "provenance tag")
    if isinstance(result.get("e2e"), dict):
        missing = [k for k in ("e2e_p50_ms", "e2e_p99_ms") if k not in lat]
        if missing:
            fail(f"latency section missing {missing} although the staged "
                 "e2e leg ran")
    print("check_bench_keys: OK (ratio_vs_kernel="
          f"{e2e['ratio_vs_kernel']}, staging_share_of_staged_run="
          f"{share}, latency={lat})")


if __name__ == "__main__":
    if len(sys.argv) > 1:
        check_output(sys.argv[1])
    else:
        check_source()
