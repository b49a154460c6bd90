#!/usr/bin/env bash
# CI entry point: run the whole suite on the CPU backend (the conftest pins
# JAX to CPU and forces an 8-device virtual mesh so every multi-chip
# sharding path compiles and executes without TPU hardware), then the
# multi-chip dry run.  Nothing here measures: the chip is checked by
# `python chip_smoke.py` and measured by `benchmark/run.py` on a machine
# that has one (PERF.md, benchmark/README.md).
set -euo pipefail
cd "$(dirname "$0")/.."

# static analysis first: wf_lint is pure AST (~1s, no jax import) and
# fails on any hot-path/except/lock-discipline violation before anything
# expensive runs
python tools/wf_lint.py

# wfverify stage (object-level, imports jax + the graphs): every kernel
# the repo ships — the representative e2e pipeline and one graph per chaos
# family — must verify clean under --strict (zero unsuppressed
# trace-safety/recompile/donation/determinism findings) before the test
# legs spend minutes.  The deliberately-violating determinism family
# (chaos "wallclock") is excluded by design: tests/test_tracecheck.py
# asserts it IS flagged.
python tools/wf_verify.py --strict \
    tools.verify_targets:bench_e2e \
    tools.verify_targets:wire_ingest \
    tools.verify_targets:pallas_window \
    tools.verify_targets:megastep_latency \
    tools.verify_targets:chaos_window_cb \
    tools.verify_targets:chaos_window_tb \
    tools.verify_targets:chaos_reduce \
    tools.verify_targets:chaos_stateful \
    tools.verify_targets:chaos_stateless_chain

# wfir stage (IR-level, runs the graphs): --drive feeds a seeded
# synthetic stream into every composed-only target and audits the
# lowered StableHLO of EVERY program the runs compile — collectives on
# promised-collective-free edges, host callbacks, 64-bit survivors,
# dynamic shapes, donation misses, D2H syncs, lost Mosaic custom calls
# (WF901-WF907) — plus an orphan sweep over the framework's own staging
# programs.  Zero extra compiles: the audit parses the compile
# watcher's existing first-compile lowering.
python tools/wf_ir.py --strict --drive 8192 \
    tools.verify_targets:bench_e2e \
    tools.verify_targets:wire_ingest \
    tools.verify_targets:pallas_window \
    tools.verify_targets:megastep_latency \
    tools.verify_targets:chaos_window_cb \
    tools.verify_targets:chaos_window_tb \
    tools.verify_targets:chaos_reduce \
    tools.verify_targets:chaos_stateful \
    tools.verify_targets:chaos_stateless_chain

# fast tier-1 gate: the staging-plane contracts (pool reuse, fused
# transfer round-trip, prefetch ordering), the observability contracts
# (histogram percentile math, trace-export schema, recorder-off zero-cost,
# the <2% overhead budget), the analysis contracts (preflight diagnostic
# codes, wf_lint fixtures, debug-mode race detector), the device-plane
# contracts (compile watcher, OpenMetrics exposition, HBM-gauge CPU
# guard), the shard-plane contracts (seeded Zipf-skew attribution,
# sketch accuracy bound, dispatch neutrality of the in-program sketch,
# reshard plan, kill-switch off-path budget),
# the health-plane contracts (watchdog state machine, stall
# attribution, postmortem/wf_doctor round trip, crash-path END_APP),
# the key-compaction contracts (record-for-record compacted vs sorted
# vs declared-dense A/B, overflow-to-sorted under adversarial streams,
# zero-extra-dispatch pin, churn/hit-rate surfacing, remap chaos
# restore), the pallas-kernel contracts (kernel-vs-lax record A/B
# across window/reduce families incl. regrow + EOS edges, bit-equality
# of the kernel bodies, zero-dispatch-delta pin, WF607, aligned-ingest
# extension, kill-switch off-path), the megastep contracts
# (record-for-record K=1 vs K>1 A/B across operator families, the
# 1-program-per-K-sweeps dispatch pin, WF608 downgrade preflight,
# per-batch trace-lane honesty, megastep-aligned durability epochs),
# and the durability contracts (one chaos kill->restore->record-diff cell
# per mechanism, checkpoint store layout/GC, WF602 restore validation,
# sink EOS fence, off-path budget — the full family x kill point x
# fusion soak matrix is slow-marked for the nightly leg) fail
# in seconds, before the full suite spends minutes.  The full-suite run
# below repeats them — accepted: the gate's job is fast failure.  The
# full suite is the tier-1 gate's own line (same filter, same six xdist
# workers with one file to a worker = the same pass count; the ~3min of
# slow-marked soak/two-process/fuzz-tail tests stay out of it); run them
# explicitly with `pytest -m slow` on the nightly leg.
python -m pytest tests/test_staging.py tests/test_observability.py \
    tests/test_analysis.py tests/test_device_metrics.py \
    tests/test_health.py tests/test_sweep_ledger.py \
    tests/test_fusion.py tests/test_durability.py \
    tests/test_shard_plane.py tests/test_tracecheck.py \
    tests/test_key_compaction.py tests/test_reshard.py \
    tests/test_wire.py tests/test_pallas_kernels.py \
    tests/test_megastep.py tests/test_latency_plane.py \
    tests/test_ir_audit.py tests/test_tenant_plane.py \
    tests/test_calibration.py -q -m 'not slow'
python -m pytest tests/ -q -m 'not slow' -p xdist -n 6 --dist loadfile
python __graft_entry__.py 8
# calibration gate: probe the CI backend, then verify the written store
# is fresh + valid for THIS device kind (exit 1 = stale/corrupt/missing,
# exit 2 = kill switch set — CI must never silently run uncalibrated
# while claiming otherwise).  The store is CI-local scratch, not an
# artifact: production stores come from `wf_calibrate` on real chips.
python tools/wf_calibrate.py --out /tmp/wf_ci_calibration.json
python tools/wf_calibrate.py --check /tmp/wf_ci_calibration.json
rm -f /tmp/wf_ci_calibration.json
# nightly leg (CI_NIGHTLY=1): the slow-marked tail — the RSS soaks, the
# two-OS-process DCN validation, the 100k ordering-perf pair, the
# heaviest fuzz seeds and spec-sweep cells, the grouping/sketch-overhead
# heavies (wfverify-round headroom pass), the v5e AOT compile of the CB
# step inside lax.scan, the chaos
# soak matrix, and the xplane-serialize profile capture — runs here so
# deselecting `slow` above never leaves them uncovered
if [ "${CI_NIGHTLY:-0}" != "0" ]; then
    python -m pytest tests/ -q -m slow
fi
