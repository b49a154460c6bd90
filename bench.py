"""Benchmark: FFAT sliding-window sum throughput on one chip (the north-star
metric, BASELINE.json: "tuples/sec/chip on FFAT sliding-window sum; p99
window latency").

Runs the flagship per-batch program (see ``__graft_entry__.entry``): staged
batches of ``CAP`` tuples over ``K`` keys, count-based sliding window
``WIN``/``SLIDE`` decomposed into panes, all fired windows of all keys
computed in one fused XLA program per batch.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.
The reference publishes no in-repo numbers (BASELINE.md — `published: {}`),
so ``vs_baseline`` is measured against our own previous round's number for
the same platform, kept in ``bench_history.json`` (a local, git-ignored file).

The bench runs on the TPU JAX finds, in this one process (a chip belongs
to one process at a time, so nothing here starts a child).  Without a TPU
it exits non-zero and measures nothing; ``BENCH_PLATFORM=cpu`` is the
explicit request for a CPU-backend run (CI plumbing check — its rows carry
``backend: cpu`` and a unit that says so).  Any section that raises ends
the run non-zero: a partial artifact is not a result.
"""

import json
import math
import os
import sys
import time
from typing import Optional

# device-plane observability: the bench opts into the full compiled-HLO
# cost analysis (the compile watcher's default is the cheap "lowered"
# estimate — tier-1 wall budget); must be set before windflow_tpu import
os.environ.setdefault("WF_TPU_COST_ANALYSIS", "compiled")

HISTORY_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "bench_history.json")

#: per-platform workload configs (kept stable across rounds so
#: round-over-round vs_baseline is meaningful per platform)
CONFIGS = {
    # sweet spot on v5e: the sliding-reduce kernel is dispatch-bound
    # below ~128k tuples per staged batch
    "tpu": dict(cap=262144, keys=1024, win=1024, slide=128,
                warmup=6, steps=40, lat_steps=20,
                e2e_tuples=16 * 262144, e2e_warm_tuples=2 * 262144),
    # explicit BENCH_PLATFORM=cpu: smaller so CI finishes in minutes.
    # e2e_tuples sized so per-run graph re-tracing (~0.6 s, memory
    # round4-state) amortizes: at r5's ~4.5e6 tup/s steady the 64-batch
    # run lasts ~1.5 s, putting the steady window at >half the run.
    "cpu": dict(cap=65536, keys=256, win=1024, slide=128,
                warmup=2, steps=10, lat_steps=5,
                e2e_tuples=64 * 65536, e2e_warm_tuples=2 * 65536),
}


def a100_anchor(win: int, slide: int) -> dict:
    """Bandwidth-bound throughput ceiling of the REFERENCE's CUDA kernel
    sequence, on A100-SXM-40GB (1.555e12 B/s HBM2e).  The per-tuple byte
    model depends only on the window spec (capacity and key count cancel
    per tuple to first order).

    Per-tuple HBM byte model of the reference CB keyed path (records
    16 B — batch_item_gpu_t carries tuple + u64 timestamp, win_result_t
    key + gwid + aggregate):
      sort    thrust::sort_by_key radix over (i32 key, i32 seq): 4 passes
              x read+write x 8 B   (ffat_replica_gpu.hpp:751; the keyed
              emitter pays the same sort AGAIN, keyby_emitter_gpu.hpp:548
              — not counted, keeping the ceiling conservative)
      lift    read 16 + write 16   (Lifting_Kernel_CB_Keyed, :741)
      add     leaf copy D2D read+write 16 (flatfat_gpu.hpp add_cb :226)
      update  ~1 tree combine per inserted leaf: 2 reads + 1 write x 16
              (Init/Update_TreeLevel_Kernel, flatfat_gpu.hpp:60-89)
      results per window ~2*log2(win) node reads x 16 + 24 B result write
              (Compute_Results_Kernel canonical-range walk,
              flatfat_gpu.hpp:91-139), amortized over ``slide`` tuples
    The ceiling assumes 100% of peak bandwidth with perfect overlap — a
    real A100 run sits strictly below it."""
    rec = 16
    sort_b = 4 * 2 * 8
    lift_b = 2 * rec
    add_b = 2 * rec
    update_b = 3 * rec
    results_b = (2 * math.log2(win) * rec + 24) / slide
    bytes_per_tuple = sort_b + lift_b + add_b + update_b + results_b
    hbm = 1.555e12
    ceiling = hbm / bytes_per_tuple
    return {
        "bytes_per_tuple": round(bytes_per_tuple, 1),
        "components_bytes": {"sort": sort_b, "lift": lift_b, "add": add_b,
                             "tree_update": update_b,
                             "window_results": round(results_b, 2)},
        "a100_hbm_b_s": hbm,
        "a100_tps_ceiling": round(ceiling, 1),
        "target_a100_tps": round(0.9 * ceiling, 1),
    }


def xla_bytes_accessed(jitted, state, batch) -> float:
    """MEASURED per-step memory traffic from XLA's compiled cost analysis
    (bytes accessed across all memory spaces), replacing the 16-B payload
    floor of earlier rounds.  None when the backend doesn't report it."""
    ca = jitted.lower(state, *batch).compile().cost_analysis()
    d = ca[0] if isinstance(ca, (list, tuple)) else ca
    val = (d or {}).get("bytes accessed")
    return float(val) if val else None


def _median_disp(rates: list) -> tuple:
    """Median of a list of window rates + the shared dispersion dict
    (one definition for the per-dispatch and scan-chained loops so the
    two numbers always carry identical statistics)."""
    rates = sorted(rates)
    med = rates[len(rates) // 2]
    disp = {"windows": len(rates), "min": round(rates[0], 1),
            "max": round(rates[-1], 1),
            "rel_spread": round((rates[-1] - rates[0]) / med, 4)}
    return med, disp


def run_bench(platform: str, cfg: dict, jax) -> dict:
    import jax.numpy as jnp
    import numpy as np

    from windflow_tpu.windows.ffat_kernels import (make_ffat_state,
                                                   make_ffat_step)

    CAP, K = cfg["cap"], cfg["keys"]
    Pn = math.gcd(cfg["win"], cfg["slide"])
    R, D = cfg["win"] // Pn, cfg["slide"] // Pn

    lift = lambda x: x["v"]
    comb = lambda a, b: a + b
    key_fn = lambda x: x["k"]

    step_fn = make_ffat_step(CAP, K, Pn, R, D, lift, comb, key_fn)
    step = jax.jit(step_fn, donate_argnums=(0,))

    rng = np.random.default_rng(0)
    dev = jax.devices()[0]
    # A few pre-staged batches cycled round-robin, so host staging cost is
    # off the timed path (the driver loop overlaps staging with compute in
    # production; here we isolate device throughput).
    batches = []
    for i in range(8):
        payload = {
            "k": jax.device_put(
                jnp.asarray(rng.integers(0, K, CAP), jnp.int32), dev),
            "v": jax.device_put(
                jnp.asarray(rng.random(CAP, dtype=np.float32)), dev),
        }
        ts = jax.device_put(jnp.arange(CAP, dtype=jnp.int64), dev)
        valid = jax.device_put(jnp.ones(CAP, bool), dev)
        batches.append((payload, ts, valid))

    state = make_ffat_state(jnp.zeros((), jnp.float32), K, R)
    state = jax.device_put(state, dev)

    def time_steps(stp, st):
        """Warm up, then MEDIAN of 5 timing windows with the dispersion
        reported (a single window's scheduling jitter can halve it).
        One methodology for every kernel variant so the numbers stay
        comparable."""
        for i in range(cfg["warmup"]):
            p, t, v = batches[i % len(batches)]
            st, out, fired, _ = stp(st, p, t, v)
        jax.block_until_ready(st)
        rates = []
        for _ in range(5):
            t0 = time.perf_counter()
            for i in range(cfg["steps"]):
                p, t, v = batches[i % len(batches)]
                st, out, fired, _ = stp(st, p, t, v)
            jax.block_until_ready(st)
            rates.append(cfg["steps"] * CAP / (time.perf_counter() - t0))
        med, disp = _median_disp(rates)
        return med, disp, st

    dispatch_tps, dispatch_disp, state = time_steps(step, state)

    # Scan-chained chip throughput: the per-dispatch loop above pays one
    # host dispatch PER STEP.  Chaining `steps` batch-steps under
    # ``lax.scan`` runs the whole window as ONE device program, so the
    # measurement is chip throughput, not dispatch-jitter throughput.
    # (The CB step compiles inside a scan for v5e since its fired-count
    # running sum went int32 — tests/test_pallas_kernels.py holds the AOT
    # check; there is no second chaining method.)  A tiny accumulator
    # over the fired-window outputs is threaded through the carry so XLA
    # cannot dead-code-eliminate the firing/compaction stages.
    from jax import lax

    stacked = {
        "k": jnp.stack([b[0]["k"] for b in batches]),
        "v": jnp.stack([b[0]["v"] for b in batches]),
        "ts": jnp.stack([b[1] for b in batches]),
        "valid": jnp.stack([b[2] for b in batches]),
    }
    idxs = jnp.asarray(np.arange(cfg["steps"]) % len(batches), jnp.int32)

    def time_chained(fn, st):
        """Dispatch-amortized chip throughput of ``fn``: median of 5
        ``lax.scan`` windows of ``steps`` batch-steps each."""
        def chained(st, idxs, sb):
            def body(carry, i):
                st, acc_n, acc_v = carry
                p = {"k": lax.dynamic_index_in_dim(sb["k"], i,
                                                   keepdims=False),
                     "v": lax.dynamic_index_in_dim(sb["v"], i,
                                                   keepdims=False)}
                t = lax.dynamic_index_in_dim(sb["ts"], i, keepdims=False)
                v = lax.dynamic_index_in_dim(sb["valid"], i,
                                             keepdims=False)
                st, out, out_valid, _ = fn(st, p, t, v)
                acc_n = acc_n + jnp.sum(out_valid).astype(jnp.int32)
                leaf = jax.tree.leaves(out["value"])[0]
                acc_v = acc_v + jnp.sum(
                    jnp.where(out_valid, leaf, 0.0)).astype(jnp.float32)
                return (st, acc_n, acc_v), None
            carry0 = (st, jnp.int32(0), jnp.float32(0.0))
            (st, n, sv), _ = lax.scan(body, carry0, idxs)
            return st, n, sv
        ch = jax.jit(chained, donate_argnums=(0,))
        st, n, sv = ch(st, idxs, stacked)   # compile + warm
        jax.block_until_ready(sv)
        rates = []
        for _ in range(5):
            t0 = time.perf_counter()
            st, n, sv = ch(st, idxs, stacked)
            jax.block_until_ready(sv)
            rates.append(cfg["steps"] * CAP / (time.perf_counter() - t0))
        return _median_disp(rates)

    methodology = "scan_chained_median_of_5"
    tuples_per_sec, dispersion = time_chained(step_fn, jax.device_put(
        make_ffat_state(jnp.zeros((), jnp.float32), K, R), dev))

    # the same workload with the combiner DECLARED sum-like (flagless
    # sliding fold, windows/ffat_kernels._sliding_reduce_plain): reported
    # alongside — `value` stays the default-path number
    step_sum_fn = make_ffat_step(CAP, K, Pn, R, D, lift, comb, key_fn,
                                 sum_like=True)
    sum_tps, _ = time_chained(step_sum_fn, jax.device_put(
        make_ffat_state(jnp.zeros((), jnp.float32), K, R), dev))

    # p99 per-batch latency: timed with a sync per step (dispatch pipeline
    # drained), so it is an upper bound on steady-state window latency.
    lats = []
    for i in range(cfg["lat_steps"]):
        p, t, v = batches[i % len(batches)]
        t1 = time.perf_counter()
        state, out, fired, _ = step(state, p, t, v)
        jax.block_until_ready(out)
        lats.append(time.perf_counter() - t1)
    p99_ms = float(np.percentile(np.array(lats) * 1e3, 99))

    # Roofline + A100 anchor (BASELINE.md "Concrete A100 anchor" holds the
    # full derivation).  target_a100_tps makes the ">= 90% of CUDA-A100"
    # north star falsifiable: it is 90% of the bandwidth-bound CEILING of
    # the reference's own kernel sequence at this exact shape — sort,
    # lift, leaf copy, tree update, window walks (flatfat_gpu.hpp:60-139,
    # ffat_replica_gpu.hpp:741-864) — on A100-SXM-40GB HBM (1.555 TB/s).
    # A real A100 run sits below its ceiling, so beating the target beats
    # the reference.  hbm_utilization uses XLA's MEASURED bytes-accessed
    # for our step (not the 16-B payload floor of earlier rounds).
    anchor = a100_anchor(cfg["win"], cfg["slide"])
    step_bytes = xla_bytes_accessed(step, state, batches[0])
    roofline = {
        "target_a100_tps": anchor["target_a100_tps"],
        "a100_ceiling_tps": anchor["a100_tps_ceiling"],
        "a100_bytes_per_tuple_model": anchor["bytes_per_tuple"],
        "vs_a100_target": round(tuples_per_sec
                                / anchor["target_a100_tps"], 4),
        "payload_bytes_per_tuple": 16,
    }
    if step_bytes is not None:
        roofline["measured_bytes_per_step"] = step_bytes
        roofline["measured_bytes_per_tuple"] = round(step_bytes / CAP, 1)
        from windflow_tpu.monitoring import calibration
        hbm_bw, hbm_prov = calibration.constant("hbm_bytes_per_sec")
        if hbm_bw is not None:
            # published peak of THIS device kind (or a calibrated
            # measurement); an unlisted kind gets no roofline number
            roofline["hbm_peak_gb_s"] = round(hbm_bw / 1e9)
            roofline["hbm_bw_provenance"] = hbm_prov
            util = (tuples_per_sec / CAP) * step_bytes / hbm_bw
            roofline["hbm_utilization"] = round(util, 4)
            if util > 1.0:
                # cost analysis sums every HLO's operand/result bytes
                # PRE-fusion; fused producers never touch HBM, so the
                # "measured" bytes are an upper bound on real traffic
                roofline["hbm_utilization_note"] = (
                    "xla cost-analysis bytes are a pre-fusion upper "
                    "bound; utilization > 1 means fusion elides most of "
                    "that traffic — treat bytes as bound, not "
                    "measurement")
    out = {
        "value": round(tuples_per_sec, 1),
        "methodology": methodology,
        "dispersion": dispersion,
        "dispatch_value": round(dispatch_tps, 1),
        "dispatch_dispersion": dispatch_disp,
        "sum_decl_value": round(sum_tps, 1),
        "sum_decl_methodology": methodology,
        "p99_batch_latency_ms": round(p99_ms, 3),
        "roofline": roofline,
        "config": {"cap": CAP, "keys": K, "win": cfg["win"],
                   "slide": cfg["slide"], "platform": platform,
                   "device": str(dev)},
    }
    return out


def run_bench_reduce(platform: str, cfg: dict, jax) -> dict:
    """Keyed per-batch ReduceTPU throughput (BASELINE.md harness list:
    keyed Reduce_GPU, ``tests/merge_tests_gpu`` ``_kb_`` variants), both
    single-chip paths: the sorted segmented reduce (arbitrary combiner)
    and the declared-monoid dense scatter table (withMaxKeys +
    withMonoidCombiner) — kernel-level, pre-staged batches, the FFAT
    methodology (median of 5 windows)."""
    import jax.numpy as jnp
    import numpy as np

    import windflow_tpu as wf
    from windflow_tpu.batch import DeviceBatch

    CAP, K = cfg["cap"], cfg["keys"]
    rng = np.random.default_rng(4)
    dev = jax.devices()[0]
    payload = {
        "key": jax.device_put(
            jnp.asarray(rng.integers(0, K, CAP), jnp.int32), dev),
        "v": jax.device_put(
            jnp.asarray(rng.random(CAP, dtype=np.float32)), dev),
    }
    batch = DeviceBatch(payload,
                        jax.device_put(
                            jnp.arange(CAP, dtype=jnp.int64), dev),
                        jax.device_put(jnp.ones(CAP, bool), dev))
    # ONE combiner for both paths (leafwise max) so the speedup is
    # apples-to-apples: the sorted baseline folds the identical function
    # the declared path replaces with scatter-max
    comb = lambda a, b: {"key": jnp.maximum(a["key"], b["key"]),
                         "v": jnp.maximum(a["v"], b["v"])}
    out = {}
    for label, declare in (("sorted_tps", False), ("dense_decl_tps", True)):
        b = wf.ReduceTPU_Builder(comb).withKeyBy(lambda t: t["key"])
        if declare:
            b = b.withMaxKeys(K).withMonoidCombiner("max")
        op = b.build()
        for _ in range(cfg["warmup"]):
            o = op._step(batch)
        jax.block_until_ready(o.payload)
        rates = []
        for _ in range(5):
            t0 = time.perf_counter()
            for _ in range(cfg["steps"]):
                o = op._step(batch)
            jax.block_until_ready(o.payload)
            rates.append(cfg["steps"] * CAP / (time.perf_counter() - t0))
        med, disp = _median_disp(rates)
        out[label] = round(med, 1)
        out[label.replace("_tps", "_dispersion")] = disp
    out["dense_speedup"] = round(out["dense_decl_tps"]
                                 / out["sorted_tps"], 2)
    return out


def run_bench_compaction(platform: str, cfg: dict, jax) -> dict:
    """Device-side key compaction A/B (parallel/compaction.py, guarded
    by tools/check_bench_keys.py + check_bench_regress.py): the seeded
    Zipf ARBITRARY-key reduce — keys drawn Zipf(1.5) and scrambled to
    arbitrary int32 values, so no ``withMaxKeys`` declaration is
    possible — run through the same declared-monoid ReduceTPU twice:
    the legacy sorted segmented path vs the compacted remap (dense slot
    table + overflow lane in one program).  Both paths fold the same
    batch back-to-back in one process, so the speedup ratio holds even
    when the box is loaded.  The Zipf tail keeps ~2% of lanes missing
    the warm table every batch, so the measured number pays the FULL
    compacted machinery: lookup, packed scatter, overflow sort, rank
    merge — not just the all-hit fast lane."""
    import numpy as np

    import windflow_tpu as wf
    from windflow_tpu.batch import DeviceBatch
    from windflow_tpu.parallel.compaction import KeyCompactor

    import jax.numpy as jnp

    CAP = cfg["cap"]
    SLOTS = 1024
    rng = np.random.default_rng(7)
    # rank-scramble: hot ranks land on arbitrary int32 values, not the
    # dense small ints a withMaxKeys user would declare
    r = rng.zipf(1.5, CAP).astype(np.uint64)
    keys = ((r * np.uint64(0x9E3779B97F4A7C15) >> np.uint64(31))
            & np.uint64(0x7FFFFFFE)).astype(np.int32)
    dev = jax.devices()[0]
    payload = {"key": jax.device_put(jnp.asarray(keys), dev),
               "v": jax.device_put(
                   jnp.asarray(rng.random(CAP, dtype=np.float32)), dev)}
    batch = DeviceBatch(payload,
                        jax.device_put(
                            jnp.arange(CAP, dtype=jnp.int64), dev),
                        jax.device_put(jnp.ones(CAP, bool), dev))
    comb = lambda a, b: {"key": jnp.maximum(a["key"], b["key"]),
                         "v": jnp.maximum(a["v"], b["v"])}
    ops = {}
    comp = None
    for label in ("sorted", "compacted"):
        op = (wf.ReduceTPU_Builder(comb).withKeyBy(lambda t: t["key"])
              .withMonoidCombiner("max").build())
        if label == "compacted":
            comp = KeyCompactor(SLOTS, name="bench_compact")
            op.enable_compaction(comp)
            # warm admission, hottest-first — what the emitter/sketch
            # seeding converges to on a steady stream
            u, cnt = np.unique(keys, return_counts=True)
            comp.observe(u[np.argsort(-cnt)][:SLOTS])
        for _ in range(cfg["warmup"]):
            o = op._step(batch)
        jax.block_until_ready(o.payload)
        ops[label] = op

    def window(op) -> float:
        t0 = time.perf_counter()
        for _ in range(cfg["steps"]):
            o = op._step(batch)
        jax.block_until_ready(o.payload)
        return cfg["steps"] * CAP / (time.perf_counter() - t0)

    # paired windows: each round times sorted then compacted under the
    # same instantaneous box load, so the per-round ratio is immune to
    # the slow load drift that skews a sequential leg-then-leg A/B
    # (the ratio IS the guarded scalar — check_bench_regress trips it)
    rates = {"sorted": [], "compacted": []}
    ratios = []
    for _ in range(5):
        s, c = window(ops["sorted"]), window(ops["compacted"])
        rates["sorted"].append(s)
        rates["compacted"].append(c)
        ratios.append(c / s)
    out = {}
    for label, rs in rates.items():
        med, disp = _median_disp(rs)
        out[label + "_tps"] = round(med, 1)
        out[label + "_dispersion"] = disp
    med, disp = _median_disp(ratios)
    out["speedup_vs_sorted"] = round(med, 2)
    out["speedup_dispersion"] = disp
    s = comp.summary()
    out["hit_rate"] = s["hit_rate"]
    out["overflow_share"] = s["overflow_share"]
    out["churn_per_sweep"] = s["churn_per_sweep"]
    out["big_fallbacks"] = s["big_fallbacks"]
    out["tuples"] = s["tuples"]
    return out


def run_bench_wire(platform: str, cfg: dict, jax) -> dict:
    """Wire-compression A/B (windflow_tpu/wire.py, guarded by
    tools/check_bench_keys.py + check_bench_regress.py): a SEEDED
    EVENT-time stream over the e2e record spec (i64 id/ts cadence lane,
    low-cardinality key lane, f32 value lane) driven through the
    staged FFAT pipeline twice — wire compression ON vs the
    WF_TPU_WIRE kill switch.  Reports the measured wire bytes/tuple +
    compression ratio (deterministic: EVENT time pins the ts lane's
    codec, so check_bench_regress can tripwire the scalar) and the
    DECODE DISPATCH DELTA: per-staged-batch ``staging.unpack``
    dispatches compressed minus kill-switch, which the zero-extra-
    dispatch contract pins at exactly 0 (the decode is traced INTO the
    unpack program, docs/OBSERVABILITY.md "Wire plane")."""
    import dataclasses

    import numpy as np

    import windflow_tpu as wf
    from windflow_tpu.monitoring.jit_registry import default_registry

    CAP, K, NB = 4096, 256, 16
    n = NB * CAP
    rng = np.random.default_rng(5)
    ks = rng.integers(0, K, n)
    vs = rng.integers(0, 1024, n)

    def records():
        for i in range(n):
            yield {"key": int(ks[i]),
                   "v0": np.float32(vs[i] / 1024.0),
                   "ts": 1_000 + i * 7}

    reg = default_registry()

    def run(wire_on: bool):
        cfgg = dataclasses.replace(wf.default_config,
                                   wire_compression=wire_on)
        cfgg.punctuation_interval_usec = 10 ** 12   # determinism
        src = (wf.Source_Builder(records)
               .withTimestampExtractor(lambda t: t["ts"])
               .withOutputBatchSize(CAP)
               .withRecordSpec({"key": np.int64(0),
                                "v0": np.float32(0.0),
                                "ts": np.int64(0)}).build())
        w = (wf.Ffat_WindowsTPU_Builder(lambda t: t["v0"],
                                        lambda a, b: a + b)
             .withCBWindows(cfg["win"], cfg["slide"])
             .withKeyBy(lambda t: t["key"]).withMaxKeys(K).build())
        g = wf.PipeGraph("bench_wire", wf.ExecutionMode.DEFAULT,
                         wf.TimePolicy.EVENT, config=cfgg)
        g.add_source(src).add(w).add_sink(
            wf.Sink_Builder(lambda r: None)
            .withColumnarSink(defer=4).build())
        base = reg.dispatch_counts().get("staging.unpack", 0)
        t0 = time.perf_counter()
        g.run()
        wall = time.perf_counter() - t0
        disp = reg.dispatch_counts().get("staging.unpack", 0) - base
        st = g.stats()
        return (st["Staging"]["Wire"], disp,
                st["Bytes_H2D_total"], st["Bytes_H2D_logical_total"],
                wall)

    ws_on, d_on, h2d_on, log_on, wall_on = run(True)
    ws_off, d_off, h2d_off, _log_off, wall_off = run(False)
    batches = max(1, ws_on["batches"] + ws_on["raw_batches"])
    return {
        # wire_bytes_per_tuple from the H2D total: raw-shipped batches
        # (if any) count at their full size, so the number is the real
        # transfer cost per tuple, not just the compressed batches'
        "wire_bytes_per_tuple": round(h2d_on / n, 3),
        "logical_bytes_per_tuple": round(log_on / n, 3),
        "compression_ratio": round(log_on / h2d_on, 4) if h2d_on
        else None,
        "decode_dispatch_delta": round((d_on - d_off) / batches, 4),
        "unpack_dispatches_on": d_on,
        "unpack_dispatches_off": d_off,
        "raw_batches": ws_on["raw_batches"],
        "fallback_lanes": ws_on["fallback_lanes"],
        "encode_usec": ws_on["encode_usec"],
        "killswitch_h2d_bytes": h2d_off,
        "wall_on_s": round(wall_on, 3),
        "wall_off_s": round(wall_off, 3),
        "codecs": ws_on["codecs"],
        "tuples": n,
    }


def _e2e_graph(cfg: dict, n_tuples: int, chunks, lat_sink, config=None):
    """Build the whole-framework pipeline (VERDICT r2 item 3: benchmark what
    ``PipeGraph.run()`` sustains, not the raw kernel): columnar byte ingest →
    staging → MapTPU → FilterTPU → FfatWindowsTPU → columnar Sink.  Matches
    the reference's measurement harnesses, which time whole pipelines
    (BASELINE.md: Source→Map_GPU→Filter_GPU→Sink, ``tests/graph_tests_gpu``).

    ``config``: optional :class:`windflow_tpu.Config` threaded to the
    graph — the megastep section forces ``megastep_sweeps`` through it."""
    import windflow_tpu as wf
    from windflow_tpu.io import FrameSource

    import numpy as np

    CAP, K = cfg["cap"], cfg["keys"]
    src = FrameSource(chunks, nv=1, fmt="frames", output_batch_size=CAP)
    # declared record spec (frames stage as i32 key + f32 value lanes):
    # gives preflight a chain to eval and the sweep ledger its
    # payload-vs-overhead byte model (per-hop excess_vs_model)
    src.record_spec = {"key": np.int32(0), "v0": np.float32(0.0)}
    m = wf.MapTPU_Builder(
        lambda t: {"key": t["key"], "v0": t["v0"] * 1.5 + 1.0}).build()
    f = wf.FilterTPU_Builder(lambda t: (t["key"] & 7) != 7).build()
    w = (wf.Ffat_WindowsTPU_Builder(lambda t: t["v0"], lambda a, b: a + b)
         .withCBWindows(cfg["win"], cfg["slide"])
         .withKeyBy(lambda t: t["key"]).withMaxKeys(K).build())
    snk = wf.Sink_Builder(lat_sink).withColumnarSink(defer=4).build()
    g = wf.PipeGraph("bench_e2e", wf.ExecutionMode.DEFAULT,
                     wf.TimePolicy.INGRESS, config=config)
    pipe = g.add_source(src)
    pipe.add(m)
    pipe.chain(f)        # Map+Filter fuse into ONE XLA program (chaining)
    pipe.add(w).add_sink(snk)
    return g


def _measure_e2e_graph(graph_factory, n_tuples: int, CAP: int,
                       kernel_tps: float) -> dict:
    """Time one ``PipeGraph.run()`` built by ``graph_factory(lat_sink)``
    and estimate the steady-state rate (shared by the staged and
    device-source e2e modes)."""
    import numpy as np

    lats = []
    rows = [0]
    first_out = [None]

    def lat_sink(c):
        if c is None:
            return
        if first_out[0] is None:
            # first result: every program of the pipeline is now compiled
            first_out[0] = time.perf_counter()
        rows[0] += len(c)
        now = time.time() * 1e6
        tss = np.asarray(c.tss, np.float64)
        tss = tss[tss > 0]      # EOS-flush rows carry ts=0: not steady-state
        if len(tss):
            lats.append(now - tss)

    g = graph_factory(lat_sink)
    t0 = time.perf_counter()
    g.run()
    t_end = time.perf_counter()
    elapsed = t_end - t0
    # sweep ledger (monitoring/sweep_ledger.py): per-hop dispatch/HBM
    # attribution of THIS run — main() folds the median run's section
    # into roofline.per_hop so the 8x bytes/tuple excess is named hop by
    # hop in bench_history.json
    _st = g.stats()
    sweep = _st.get("Sweep")
    # wire plane (windflow_tpu/wire.py): the staged run's measured
    # compression — main() folds it into the guarded `wire` section
    wire_stats = (_st.get("Staging") or {}).get("Wire")
    # megastep plane (windflow_tpu/megastep.py): resolved K and
    # per-edge scan/fallback accounting of THIS run — the megastep
    # section reads it for its dispatches_per_batch number
    megastep_stats = _st.get("Megastep")
    # steady-state window: from the first sink result (compilation and
    # first-batch warmup done) to the end; the first batch's tuples are out
    # of the window.  The total number is reported alongside.  The steady
    # estimate is only meaningful when the window covers a real share of
    # the run — with few batches the deferred sink emits everything near
    # EOS and the window collapses — otherwise fall back to the full-run
    # number.
    steady_s = (t_end - first_out[0]) if first_out[0] else elapsed
    steady_tuples = max(1, n_tuples - CAP)
    full_rate = n_tuples / elapsed
    if steady_s < 0.2 * elapsed or n_tuples < 6 * CAP:
        steady_rate, estimator = full_rate, "full_run_fallback"
    else:
        steady_rate, estimator = steady_tuples / steady_s, "steady"
    # Sanity guard (VERDICT r3: a collapsed steady window once produced
    # 4.96e8 tup/s on CPU — 140x the kernel rate, physically impossible):
    # the pipeline can never beat its own kernel.  The guard is the
    # kernel rate when known, else a loose multiple of the full-run rate
    # — steady legitimately exceeds full-run by the trace-time share
    # (r5: a 2x faster kernel shrank runs until tracing was half the
    # elapsed time, and a 3x-full-rate guard rejected every honest
    # steady reading; e2e_tuples was also raised to amortize).
    implausible = (steady_rate > 2 * kernel_tps if kernel_tps
                   else steady_rate > 10 * full_rate)
    if estimator == "steady" and implausible:
        estimator = (f"full_run_rejected_outlier"
                     f"(steady={steady_rate:.3g})")
        steady_rate = full_rate
    lat_all = (np.concatenate(lats) if lats else np.array([0.0])) / 1e3
    return {
        "tuples_per_sec": round(steady_rate, 1),
        "steady_estimator": estimator,
        "tuples_per_sec_incl_compile": round(n_tuples / elapsed, 1),
        "p99_window_latency_ms": round(float(np.percentile(lat_all, 99)), 3),
        "p50_window_latency_ms": round(float(np.percentile(lat_all, 50)), 3),
        "window_rows": rows[0],
        "tuples": n_tuples,
        "elapsed_s": round(elapsed, 3),
        "sweep": sweep,
        "wire_stats": wire_stats,
        "megastep_stats": megastep_stats,
    }


def _median_of_runs(one_run, n_runs: int) -> dict:
    """Repeat a whole-graph e2e measurement and report the median run with
    dispersion — the kernel's median-of-windows methodology applied at the
    run level (VERDICT r4 item 6: a single e2e run could not distinguish
    the 0.86→0.74 ratio slide from noise)."""
    runs = [one_run() for _ in range(n_runs)]
    runs.sort(key=lambda r: r["tuples_per_sec"])
    med = dict(runs[len(runs) // 2])
    rates = [r["tuples_per_sec"] for r in runs]
    med["dispersion"] = {
        "runs": n_runs, "min": rates[0], "max": rates[-1],
        "rel_spread": round((rates[-1] - rates[0])
                            / med["tuples_per_sec"], 4),
    }
    return med


def run_bench_e2e(platform: str, cfg: dict, jax,
                  kernel_tps: float = 0.0) -> dict:
    """End-to-end framework throughput + p99 window latency, median of
    ``BENCH_E2E_RUNS`` (default 3) full runs.

    Tuples enter as binary frame bytes (columnar native ingest) and leave
    through a columnar sink; INGRESS time stamps each tuple's arrival in
    wall microseconds, so ``sink receipt − row timestamp`` is the event
    arrival → window result latency through staging, emitters, the driver
    loop, device programs, and egress.  XLA's persistent compilation cache
    is enabled (main) and a small warmup graph (same shapes) is run first
    so the timed runs measure the framework, not the compiler."""
    import numpy as np


    CAP, K = cfg["cap"], cfg["keys"]
    n_tuples = int(os.environ.get("BENCH_E2E_TUPLES", cfg["e2e_tuples"]))
    n_runs = int(os.environ.get("BENCH_E2E_RUNS", "3"))
    rng = np.random.default_rng(1)

    def make_blob(n):
        rec = np.empty(n, dtype=[("k", "<i8"), ("t", "<i8"), ("v", "<f8")])
        rec["k"] = rng.integers(0, K, n)
        rec["t"] = np.arange(n)          # overwritten by INGRESS stamping
        rec["v"] = rng.random(n)
        return rec.tobytes()

    def chunker(blob, chunk_bytes=1 << 20):
        def chunks():
            for lo in range(0, len(blob), chunk_bytes):
                yield blob[lo:lo + chunk_bytes]
        return chunks

    # warmup: compile every program shape (staging CAP, ffat state, sink)
    warm = _e2e_graph(cfg, cfg["e2e_warm_tuples"],
                      chunker(make_blob(cfg["e2e_warm_tuples"])),
                      lambda c: None)
    warm.run()

    blob = make_blob(n_tuples)
    return _median_of_runs(
        lambda: _measure_e2e_graph(
            lambda lat_sink: _e2e_graph(cfg, n_tuples, chunker(blob),
                                        lat_sink),
            n_tuples, CAP, kernel_tps),
        n_runs)


def run_bench_e2e_device(platform: str, cfg: dict, jax,
                         kernel_tps: float = 0.0) -> dict:
    """Device-resident-source e2e (VERDICT r4 item 3): the same pipeline
    shape as :func:`run_bench_e2e` but the source batches are GENERATED ON
    DEVICE (io/device_source.py), so no host→device staging is on the hot
    path.  ``ratio_vs_kernel`` here measures pure framework dispatch
    (driver loop, emitters, program launches); the gap between this and
    the staged e2e number is the staging/link share — the decomposition
    that turns the r3/r4 'link-bound' hypothesis into a measurement."""
    import jax.numpy as jnp
    import numpy as np

    import windflow_tpu as wf

    CAP, K = cfg["cap"], cfg["keys"]
    n_tuples = int(os.environ.get("BENCH_E2E_TUPLES", cfg["e2e_tuples"]))
    n_runs = int(os.environ.get("BENCH_E2E_RUNS", "3"))
    NB = max(1, n_tuples // CAP)
    n_tuples = NB * CAP

    def batch_fn(i):
        # cheap on-device synth: lane-derived keys/values, index-mixed so
        # batches differ; matches the staged blob's key range
        lane = jnp.arange(CAP, dtype=jnp.int32)
        mixed = (lane * 2654435761 + i * 40503) & 0x7FFFFFFF
        return {"key": mixed % K,
                "v0": (mixed % 1024).astype(jnp.float32) / 1024.0}

    def build(lat_sink, nb=None):
        src = (wf.DeviceSource_Builder(batch_fn)
               .withCapacity(CAP).withNumBatches(nb or NB).build())
        m = wf.MapTPU_Builder(
            lambda t: {"key": t["key"], "v0": t["v0"] * 1.5 + 1.0}).build()
        f = wf.FilterTPU_Builder(lambda t: (t["key"] & 7) != 7).build()
        w = (wf.Ffat_WindowsTPU_Builder(lambda t: t["v0"],
                                        lambda a, b: a + b)
             .withCBWindows(cfg["win"], cfg["slide"])
             .withKeyBy(lambda t: t["key"]).withMaxKeys(K).build())
        snk = wf.Sink_Builder(lat_sink).withColumnarSink(defer=4).build()
        g = wf.PipeGraph("bench_e2e_dev", wf.ExecutionMode.DEFAULT,
                         wf.TimePolicy.INGRESS)
        pipe = g.add_source(src)
        pipe.add(m)
        pipe.chain(f)
        pipe.add(w).add_sink(snk)
        return g

    # warmup: compile the program shapes with a 2-batch stream (the
    # staged path's e2e_warm_tuples idea — not a discarded full run)
    warm_nb = min(2, NB)
    _measure_e2e_graph(lambda ls: build(ls, nb=warm_nb),
                       warm_nb * CAP, CAP, kernel_tps)
    return _median_of_runs(
        lambda: _measure_e2e_graph(build, n_tuples, CAP, kernel_tps),
        n_runs)


def run_bench_megastep(platform: str, cfg: dict, jax,
                       kernel_tps: float = 0.0) -> dict:
    """Megastep A/B (windflow_tpu/megastep.py, guarded by
    tools/check_bench_keys.py + check_bench_regress.py): the staged e2e
    pipeline driven at a DISPATCH-BOUND batch size (small cap, many
    sweeps — the regime the host pacer dominates and the megastep
    exists to fix), once with ``megastep_sweeps`` forced to K and once
    with the K=1 kill switch.  Reports the K-run's steady tuples/sec
    (the guarded floor: CPU >= 10x the r14 54.8k per-batch baseline),
    the measured speedup over the kill-switch run, and the dispatch
    accounting the jit registry pins: one ``megastep.*`` program
    dispatch serves K staged batches, so ``dispatches_per_batch`` over
    the scanned batches is 1/K exactly — warmup (first-batch compile
    probe) and EOS-remainder batches ship per-batch and are reported
    next to it, not hidden in it (docs/OBSERVABILITY.md "Megastep in
    the ledger")."""
    import dataclasses

    import numpy as np

    import windflow_tpu as wf
    from windflow_tpu.megastep import AUTO_K
    from windflow_tpu.monitoring.jit_registry import default_registry


    # dispatch-bound workload: 1k-row sweeps make the per-batch host
    # cost (emitter finalize, drain, ring stamps, sink fold) the
    # dominant term — at the default e2e cap the pipeline is
    # compute-bound on CPU and folding dispatches cannot show
    ms_cfg = dict(cfg, cap=1024, keys=64, win=256, slide=64)
    CAP = ms_cfg["cap"]
    n_tuples = int(os.environ.get("BENCH_MEGASTEP_TUPLES",
                                  2048 * CAP))
    n_runs = int(os.environ.get("BENCH_MEGASTEP_RUNS", "3"))
    K = int(os.environ.get("BENCH_MEGASTEP_K", str(AUTO_K)))
    rng = np.random.default_rng(3)

    rec = np.empty(n_tuples, dtype=[("k", "<i8"), ("t", "<i8"),
                                    ("v", "<f8")])
    rec["k"] = rng.integers(0, ms_cfg["keys"], n_tuples)
    rec["t"] = np.arange(n_tuples)   # overwritten by INGRESS stamping
    rec["v"] = rng.random(n_tuples)
    blob = rec.tobytes()

    def chunks():
        for lo in range(0, len(blob), 1 << 20):
            yield blob[lo:lo + (1 << 20)]

    reg = default_registry()

    def measure(k):
        config = dataclasses.replace(wf.default_config,
                                     megastep_sweeps=k)
        # determinism (same stance as the wire section): periodic
        # punctuations flush partial megastep groups mid-run and turn
        # the scanned/fallback split into wall-clock weather
        config.punctuation_interval_usec = 10 ** 12

        def build(lat_sink):
            return _e2e_graph(ms_cfg, n_tuples, chunks, lat_sink,
                              config=config)

        _measure_e2e_graph(build, n_tuples, CAP, kernel_tps)  # warm
        base = sum(n_disp for name, n_disp in
                   reg.dispatch_counts().items()
                   if name.startswith("megastep."))
        med = _median_of_runs(
            lambda: _measure_e2e_graph(build, n_tuples, CAP,
                                       kernel_tps), n_runs)
        mega_disp = sum(n_disp for name, n_disp in
                        reg.dispatch_counts().items()
                        if name.startswith("megastep.")) - base
        return med, mega_disp

    med_k, disp_k = measure(K)
    med_1, _ = measure(1)

    ms = med_k.pop("megastep_stats") or {}
    med_1.pop("megastep_stats", None)
    edge = (ms.get("edges") or [{}])[0]
    scanned = edge.get("batches", 0)
    megasteps = edge.get("megasteps", 0)
    tps_k, tps_1 = med_k["tuples_per_sec"], med_1["tuples_per_sec"]
    return {
        "k": ms.get("k", K),
        "e2e_tup_s": tps_k,
        # the guarded floor (check_bench_keys): 10x the r14 CPU
        # per-batch staged-e2e baseline (54.8k tup/s).  On TPU the
        # acceptance criterion is ratio_vs_kernel (roofline-relative),
        # not an absolute rate
        "e2e_floor_tup_s": 548_000 if platform == "cpu" else 0,
        "e2e_tup_s_k1": tps_1,
        "speedup_vs_k1": round(tps_k / tps_1, 4) if tps_1 else 0.0,
        "ratio_vs_kernel": round(tps_k / kernel_tps, 4)
        if kernel_tps else 0.0,
        # over the SCANNED batches: one compiled program per K sweeps,
        # pinned by the registry's megastep.* dispatch count (the
        # median-of-n run loop makes the count n_runs * megasteps)
        "dispatches_per_batch": round(megasteps / scanned, 4)
        if scanned else None,
        "megastep_dispatches": disp_k,
        "megasteps": megasteps,
        "scanned_batches": scanned,
        "fallback_batches": edge.get("fallback_batches", 0),
        "warmup_batches": edge.get("warmup_batches", 0),
        "steady_estimator": med_k["steady_estimator"],
        "p99_window_latency_ms": med_k["p99_window_latency_ms"],
        "dispersion": med_k.get("dispersion"),
        "tuples": n_tuples,
    }


def run_bench_latency_slo(platform: str, cfg: dict, jax,
                          kernel_tps: float = 0.0) -> dict:
    """Latency-mode leg (windflow_tpu/monitoring/latency_ledger.py,
    guarded by tools/check_bench_keys.py + check_bench_regress.py): a
    representative source→map→window→sink pipeline driven unthrottled —
    the p99 this records is the tail AT max sustainable throughput, the
    operating point named in the row — with the flight recorder and
    latency ledger ON and a declared SLO budget.  Reports the
    ledger-decomposed staged→sunk p50/p99, the dominant (operator,
    segment) pair, per-segment shares, and the SLO verdict state.
    check_bench_keys hard-fails the shipped shape when the measured p99
    exceeds 2x the recorded budget — the bench pipelines must run
    inside their own declared SLO with margin."""
    import dataclasses

    import numpy as np
    import windflow_tpu as wf

    budget_ms = float(os.environ.get("BENCH_SLO_MS", "1000"))
    # many-batch shape (the e2e cap would make the whole CPU run ONE
    # staged batch — nothing to decompose): 64 batches of 4k tuples
    slo_cfg = dict(cfg, cap=4096, keys=64, win=256, slide=64)
    CAP, K = slo_cfg["cap"], slo_cfg["keys"]
    n = int(os.environ.get("BENCH_SLO_TUPLES", str(64 * CAP)))
    # aggressive sampling (1-in-2 vs the production 1-in-64) so a
    # CI-sized run decomposes enough traces for an honest p99
    config = dataclasses.replace(
        wf.default_config, flight_recorder=True, trace_sample_every=2,
        latency_ledger=True, latency_slo_ms=budget_ms)
    src = (wf.Source_Builder(
        lambda: iter({"key": i % K, "v0": float(i)} for i in range(n)))
        .withOutputBatchSize(CAP)
        .withRecordSpec({"key": np.int32(0), "v0": np.float32(0.0)})
        .withName("slo_src").build())
    m = (wf.MapTPU_Builder(
        lambda t: {"key": t["key"], "v0": t["v0"] * 1.5 + 1.0})
        .withName("slo_map").build())
    w = (wf.Ffat_WindowsTPU_Builder(lambda t: t["v0"], lambda a, b: a + b)
         .withCBWindows(slo_cfg["win"], slo_cfg["slide"])
         .withKeyBy(lambda t: t["key"]).withMaxKeys(K)
         .withName("slo_win").build())
    snk = wf.Sink_Builder(lambda r: None).withName("slo_snk").build()
    g = wf.PipeGraph("bench_latency_slo", wf.ExecutionMode.DEFAULT,
                     wf.TimePolicy.INGRESS, config=config)
    g.add_source(src).add(m).add(w).add_sink(snk)
    t0 = time.perf_counter()
    g.start()
    while not g.is_done():
        if not g.step():
            break
        g.health_tick()     # ledger tick every sweep: worst-case cadence
    g.wait_end()
    elapsed = time.perf_counter() - t0
    g.health_tick()         # final harvest after the sink's EOS flush
    lp = g.stats()["Latency_plane"]
    e2e_q = lp.get("e2e_usec") or {}
    segs = lp.get("segments_total_usec") or {}
    total = sum(segs.values()) or 1.0
    dom_op, dom_entry = None, {}
    for name, entry in (lp.get("per_op") or {}).items():
        if (entry.get("budget_share") or 0) >= \
                (dom_entry.get("budget_share") or 0):
            dom_op, dom_entry = name, entry
    slo = lp.get("slo") or {}
    return {
        # the operating-point label check_bench_keys requires on every
        # latency row: a p99 is meaningless without the rate it was
        # measured at
        "operating_point": "max_sustainable",
        "tuples_per_sec": round(n / elapsed, 1) if elapsed else 0.0,
        "slo_budget_ms": budget_ms,
        "e2e_p50_ms": round((e2e_q.get("p50") or 0) / 1e3, 3),
        "e2e_p99_ms": round((e2e_q.get("p99") or 0) / 1e3, 3),
        "traces_decomposed": lp.get("traces_decomposed", 0),
        "dominant_op": dom_op,
        "dominant_segment": dom_entry.get("dominant_segment"),
        "segment_share": {s: round(v / total, 4)
                          for s, v in segs.items()},
        "slo_active": bool(slo.get("active")),
        "tuples": n,
    }


def run_bench_tenant(platform: str, cfg: dict, jax) -> dict:
    """Tenant-plane leg (windflow_tpu/monitoring/tenant_ledger.py,
    guarded by tools/check_bench_keys.py + check_bench_regress.py): two
    seeded tenants in ONE process — a Zipf-hot keyed pipeline and a
    uniform one — with the shared ledger attributing HBM/dispatch/byte
    totals per tenant.  Reports the reconciliation fraction (attributed
    staged bytes over process staged bytes — check_bench_keys hard-fails
    under 0.9), the worst budget pressure, and the ledger's measured
    self-cost as a share of the run (same <2% stance as the flight
    recorder and the health watchdog)."""
    import dataclasses

    import numpy as np
    import windflow_tpu as wf
    from windflow_tpu.monitoring.tenant_ledger import default_ledger

    ledger = default_ledger()
    ledger.reset()
    CAP, K = 2048, 64
    n = int(os.environ.get("BENCH_TENANT_TUPLES", str(16 * 2048)))
    budget = 64 * 1024 * 1024   # generous: pressure stays well under 1
    total = 0.0

    def leg(tenant: str, prefix: str, keys) -> None:
        nonlocal total
        config = dataclasses.replace(
            wf.default_config, tenant=tenant, hbm_budget_bytes=budget)
        src = (wf.Source_Builder(
            lambda: iter({"key": keys(i), "v0": float(i)}
                         for i in range(n)))
            .withOutputBatchSize(CAP)
            .withRecordSpec({"key": np.int32(0), "v0": np.float32(0.0)})
            .withName(f"{prefix}_src").build())
        m = (wf.MapTPU_Builder(
            lambda t: {"key": t["key"], "v0": t["v0"] * 1.5 + 1.0})
            .withName(f"{prefix}_map").build())
        w = (wf.Ffat_WindowsTPU_Builder(
            lambda t: t["v0"], lambda a, b: a + b)
            .withCBWindows(256, 64)
            .withKeyBy(lambda t: t["key"]).withMaxKeys(K)
            .withName(f"{prefix}_win").build())
        snk = wf.Sink_Builder(lambda r: None) \
            .withName(f"{prefix}_snk").build()
        g = wf.PipeGraph(f"bench_tenant_{prefix}",
                         wf.ExecutionMode.DEFAULT, wf.TimePolicy.INGRESS,
                         config=config)
        g.add_source(src).add(m).add(w).add_sink(snk)
        t0 = time.perf_counter()
        g.start()
        while not g.is_done():
            if not g.step():
                break
            g.health_tick()     # ledger tick every sweep, throttled
        g.wait_end()
        total += time.perf_counter() - t0
        g.health_tick()         # final harvest before freeze-at-finalize

    # seeded Zipf-hot keys (key 0 carries ~3/4) vs uniform round-robin
    leg("tenant_hot", "th", lambda i: 0 if i % 4 else i % K)
    leg("tenant_uni", "tu", lambda i: i % K)

    sec = ledger.section()
    pressures = [((t.get("budget") or {}).get("pressure") or 0.0)
                 for t in (sec.get("tenants") or {}).values()]
    frac = (sec.get("attributed") or {}).get("staged_fraction")
    over = sec.get("overhead") or {}
    return {
        "tenants": len(sec.get("tenants") or {}),
        "hbm_attributed_fraction":
            round(frac, 4) if frac is not None else None,
        "budget_pressure": round(max(pressures), 6) if pressures else 0.0,
        "ledger_overhead_pct": round(
            100.0 * (over.get("collect_ms_total") or 0.0)
            / (total * 1e3), 3) if total else 0.0,
        "tuples": 2 * n,
    }


def scaling_step(jax, n: int, K: int, per_chip: int, seed: int = 2):
    """Build one width-``n`` rung of the weak-scaling sweep: the key-sharded
    mesh, the compiled keyed reduce, and its staged inputs.  Shared with the
    test suite so the composition the harness runs on real hardware is the
    composition CI exercises (tests/test_mesh.py)."""
    import jax.numpy as jnp
    import numpy as np

    from windflow_tpu.parallel import mesh as meshmod

    mesh = meshmod.make_mesh(n_devices=n, data=1)
    cap = per_chip * n
    fn = meshmod.make_sharded_keyed_reduce(
        mesh, cap, K,
        lambda a, b: {"k": a["k"], "v": a["v"] + b["v"]},
        key_fn=lambda t: t["k"], use_psum=True)
    rng = np.random.default_rng(seed)
    sh = meshmod.batch_sharding(mesh)
    payload = {
        "k": jax.device_put(
            jnp.asarray(rng.integers(0, K, cap), jnp.int32), sh),
        "v": jax.device_put(
            jnp.asarray(rng.random(cap, dtype=np.float32)), sh),
    }
    valid = jax.device_put(jnp.ones(cap, bool), sh)
    return fn, payload, valid, cap


def run_bench_ysb(platform: str, cfg: dict, jax) -> dict:
    """Yahoo-Streaming-Benchmark-shaped pipeline throughput (BASELINE.md
    harness list: "YahooStreamingBench ad-analytics DAG"): columnar binary
    ingest → FilterTPU(view events) ⊕ MapTPU(ad→campaign device-table
    join), fused → per-campaign tumbling TB count windows → columnar sink,
    all through ``PipeGraph.run()``."""
    import numpy as np

    import windflow_tpu as wf
    from windflow_tpu.io import FrameSource

    CAP = cfg["cap"]
    n_ads, n_campaigns = 1000, 100
    n_tuples = int(os.environ.get("BENCH_YSB_TUPLES", cfg["e2e_tuples"]))
    rng = np.random.default_rng(3)
    table_np = rng.integers(0, n_campaigns, n_ads).astype(np.int32)

    rec = np.empty(n_tuples, dtype=[("k", "<i8"), ("t", "<i8"),
                                    ("v", "<f8")])
    rec["k"] = rng.integers(0, n_ads, n_tuples)          # ad_id
    # event time spans ~64 tumbling windows so the firing path runs in
    # steady state (not just the EOS flush)
    gap_usec = max(1, 64 * 10_000_000 // n_tuples)
    rec["t"] = np.arange(n_tuples, dtype=np.int64) * gap_usec
    rec["v"] = rng.integers(0, 3, n_tuples).astype(np.float64)  # etype
    blob = rec.tobytes()

    def chunks():
        for lo in range(0, len(blob), 1 << 20):
            yield blob[lo:lo + (1 << 20)]

    import jax.numpy as jnp
    table = jnp.asarray(table_np)
    rows = [0]

    def build():
        src = FrameSource(chunks, nv=1, fmt="frames",
                          output_batch_size=CAP)
        flt = wf.FilterTPU_Builder(lambda e: e["v0"] == 1.0).build()
        prj = wf.MapTPU_Builder(
            lambda e: {"campaign": table[e["key"]], "one": 1}).build()
        win = (wf.Ffat_WindowsTPU_Builder(lambda e: e["one"],
                                          lambda a, b: a + b)
               .withTBWindows(10_000_000, 10_000_000)
               .withKeyBy(lambda e: e["campaign"])
               .withMaxKeys(n_campaigns)
               .withSumCombiner().build())   # sort-free TB placement
        snk = (wf.Sink_Builder(
                lambda c: rows.__setitem__(0, rows[0] + len(c))
                if c is not None else None)
               .withColumnarSink().build())
        g = wf.PipeGraph("bench_ysb", wf.ExecutionMode.DEFAULT,
                         wf.TimePolicy.EVENT)
        pipe = g.add_source(src)
        pipe.add(flt)
        pipe.chain(prj)       # Filter+Map fuse into one XLA program
        pipe.add(win).add_sink(snk)
        return g

    build().run()             # warmup: compile all program shapes
    rows[0] = 0
    t0 = time.perf_counter()
    build().run()
    elapsed = time.perf_counter() - t0
    return {
        "tuples_per_sec": round(n_tuples / elapsed, 1),
        "tuples": n_tuples,
        "window_rows": rows[0],
        "elapsed_s": round(elapsed, 3),
        "shape": "FrameSource->FilterTPU+MapTPU(join)->FfatTB->colSink",
    }


def run_bench_scaling(jax, max_devices: Optional[int] = None) -> dict:
    """Keyed-Reduce weak scaling over a ``(1, n)`` key-sharded mesh
    (BASELINE.json north star: "linear scaling to 8 chips on keyed
    Reduce").  Requires > 1 REAL device: per-chip work is held constant
    (weak scaling) while the mesh widens 1 → N, so ideal efficiency is a
    flat tuples/sec/chip line.  Opt-in (``--scaling`` /
    ``BENCH_SCALING=1``) and refused on virtual/forced-CPU meshes —
    host-core-sharing virtual devices would fabricate the numbers."""
    devs = jax.devices()
    if len(devs) < 2:
        return {"skipped": f"needs >1 real device, have {len(devs)}"}
    if devs[0].platform == "cpu":
        return {"skipped": "virtual CPU mesh: scaling numbers would be "
                           "host-core-sharing artifacts"}
    n_max = min(len(devs), max_devices or len(devs))
    K = 4096
    per_chip = 1 << 20
    series = []
    n = 1
    while n <= n_max:
        fn, payload, valid, cap = scaling_step(jax, n, K, per_chip)
        for _ in range(3):
            table, has = fn(payload, valid)
        jax.block_until_ready(table)
        best = 0.0
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(10):
                table, has = fn(payload, valid)
            jax.block_until_ready(table)
            best = max(best, 10 * cap / (time.perf_counter() - t0))
        series.append({"devices": n,
                       "tuples_per_sec": round(best, 1),
                       "tuples_per_sec_per_chip": round(best / n, 1)})
        n *= 2
    base = series[0]["tuples_per_sec_per_chip"]
    for s in series:
        s["efficiency"] = round(s["tuples_per_sec_per_chip"] / base, 4)
    return {"mode": "weak", "keys": K, "tuples_per_chip": per_chip,
            "series": series}


def run_bench_pallas(platform: str, cfg: dict, jax) -> dict:
    """Pallas kernel section (windflow_tpu/kernels, docs/PERF.md round
    14): the fused FFAT step built with the hand-written kernels
    (segmented grouping + MXU pane combine) A/B'd against the pure-lax
    build of the SAME program, plus the grouping kernel standalone and
    a record-mismatch canary the CI hard-fails on.

    ``interpret_mode`` is the honesty flag: on the CPU backend the
    kernels run under the Pallas interpreter — a tier-1 correctness
    vehicle, expected SLOWER than lax (the section then runs reduced
    shapes so CI stays fast) — real speedups are compiled-TPU numbers,
    where the ≥1.3x grouping-region target applies."""
    import dataclasses

    import jax.numpy as jnp
    import numpy as np

    import windflow_tpu as wf
    from windflow_tpu import kernels as pk
    from windflow_tpu.windows.ffat_kernels import (make_ffat_state,
                                                   make_ffat_step)
    from windflow_tpu.windows.grouping import order_and_hist

    mode = pk.resolve_pallas(
        dataclasses.replace(wf.default_config, pallas_kernels="auto"))
    sec = {
        "kernels_active": 0,
        "interpret_mode": None,
        "ffat_step_speedup_vs_lax": 0.0,
        "grouping_speedup": 0.0,
        "record_mismatch": 0,
    }
    if mode is None:
        sec["note"] = "no kernel lowering on this backend (lax path)"
        sec["provenance"] = "modeled"
        return sec
    sec["interpret_mode"] = bool(mode.interpret)
    # honesty tag (docs/OBSERVABILITY.md "Calibration plane"): interpreter
    # timings are correctness numbers, never performance evidence
    sec["provenance"] = "interpret" if mode.interpret else "measured"
    sec["kernels_active"] = 3   # grouping, pane combine, dense table
    if mode.interpret:
        CAP, K, steps = 8192, 256, 3
    else:
        CAP, K, steps = cfg["cap"], cfg["keys"], cfg["steps"]
    Pn = math.gcd(cfg["win"], cfg["slide"])
    R, D = cfg["win"] // Pn, cfg["slide"] // Pn

    rng = np.random.default_rng(0)
    dev = jax.devices()[0]
    # integer-valued f32 so the MXU banded-matmul sum is EXACT and the
    # record canary can demand bitwise equality
    payload = {
        "k": jax.device_put(
            jnp.asarray(rng.integers(0, K, CAP), jnp.int32), dev),
        "v": jax.device_put(
            jnp.asarray(rng.integers(0, 97, CAP).astype(np.float32)),
            dev),
    }
    ts = jax.device_put(jnp.arange(CAP, dtype=jnp.int64), dev)
    valid = jax.device_put(jnp.ones(CAP, bool), dev)

    lift = lambda x: x["v"]          # noqa: E731
    comb = lambda a, b: a + b        # noqa: E731
    key_fn = lambda x: x["k"]        # noqa: E731

    def timed(pallas):
        step = jax.jit(make_ffat_step(CAP, K, Pn, R, D, lift, comb,
                                      key_fn, monoid="sum",
                                      pallas=pallas))
        st = jax.device_put(
            make_ffat_state(jnp.zeros((), jnp.float32), K, R), dev)
        st, out, fired, ots = step(st, payload, ts, valid)
        jax.block_until_ready(st)
        first = (st, out, fired, ots)
        rates = []
        for _ in range(5):
            t0 = time.perf_counter()
            s = st
            for _ in range(steps):
                s, out, fired, _ = step(s, payload, ts, valid)
            jax.block_until_ready(s)
            rates.append(steps * CAP / (time.perf_counter() - t0))
        rates.sort()
        return rates[len(rates) // 2], first

    tps_lax, ref = timed(None)
    tps_pal, got = timed(mode)
    sec["ffat_step_speedup_vs_lax"] = round(tps_pal / tps_lax, 4)
    sec["ffat_step_tps_pallas"] = round(tps_pal, 1)
    sec["ffat_step_tps_lax"] = round(tps_lax, 1)

    # record-mismatch canary: the kernel build's FIRST step (state +
    # fired windows) must be bit-identical to the lax build's
    mismatch = 0
    for a, b in zip(jax.tree_util.tree_leaves(ref),
                    jax.tree_util.tree_leaves(got)):
        if not np.array_equal(np.asarray(a), np.asarray(b)):
            mismatch = 1
            break
    # ...and the dense segmented-reduce kernel against the scatter —
    # int32 lanes, inside the COMPILED dtype gate (table_leaf_ok), so
    # this canary runs the same path on a real TPU as on CPU tier-1
    row = jnp.asarray(rng.integers(0, K, CAP), jnp.int32)
    v32 = jnp.asarray(rng.integers(0, 1000, CAP), jnp.int32)
    tab_pk = pk.dense_monoid_table(row, [v32], ["sum"], [0], K,
                                   mode.interpret)[0]
    tab_lax = jnp.zeros(K + 1, jnp.int32).at[row].add(v32)[:K]
    if not np.array_equal(np.asarray(tab_pk), np.asarray(tab_lax)):
        mismatch = 1
    sec["record_mismatch"] = mismatch

    # grouping kernel standalone (the profile's dominant region)
    ids = payload["k"]
    jl = jax.jit(lambda i: order_and_hist(i, K + 1))
    jp = jax.jit(lambda i: pk.order_hist(i, K + 1, mode.interpret))
    for fn in (jl, jp):
        jax.block_until_ready(fn(ids))
    ticks = {}
    for name, fn in (("lax", jl), ("pallas", jp)):
        rates = []
        for _ in range(5):
            t0 = time.perf_counter()
            for _ in range(max(3, steps)):
                out = fn(ids)
            jax.block_until_ready(out)
            rates.append((time.perf_counter() - t0) / max(3, steps))
        rates.sort()
        ticks[name] = rates[len(rates) // 2]
    sec["grouping_speedup"] = round(ticks["lax"] / ticks["pallas"], 4)
    return sec


def load_history() -> dict:
    try:
        with open(HISTORY_PATH) as f:
            h = json.load(f)
        # migrate the old single-entry-per-platform shape to run lists
        for k, v in list(h.items()):
            if isinstance(v, dict):
                h[k] = [v]
        return h
    except (OSError, ValueError):
        return {}


def pick_baseline(runs: list, now: float,
                  methodology: Optional[str] = None) -> dict:
    """The previous *round's* number, not a minutes-old rerun: the most
    recent run at least 2 hours old (rounds are ~12 h apart; same-round
    debugging reruns are minutes apart), else the oldest run recorded.
    Prefers an entry recorded under the SAME methodology so vs_baseline
    never reports a methodology switch as a speedup."""
    old = [r for r in runs if now - r.get("t", 0) >= 2 * 3600]
    pool = old if old else (runs[:1] if runs else [])
    if methodology:
        same = [r for r in pool if r.get("methodology") == methodology]
        if same:
            return same[-1]
    return pool[-1] if pool else {}


def save_history(hist: dict) -> None:
    try:
        with open(HISTORY_PATH, "w") as f:
            json.dump(hist, f, indent=2)
            f.write("\n")
    except OSError:
        pass  # read-only checkout: the stdout line is still the artifact


def main() -> int:
    import jax
    cpu_asked = os.environ.get("BENCH_PLATFORM") == "cpu"
    if cpu_asked:
        # the explicit CPU-backend run (CI plumbing check): must precede
        # backend init
        jax.config.update("jax_platforms", "cpu")
    platform = jax.devices()[0].platform
    if platform != "tpu" and not cpu_asked:
        print(f"bench: JAX found platform {platform!r}, not a TPU — "
              "nothing measured (BENCH_PLATFORM=cpu asks for a CPU-backend "
              "run on purpose)", file=sys.stderr)
        return 2
    from windflow_tpu.compile_cache import setup_compile_cache
    setup_compile_cache()

    result = {
        "metric": "ffat_sliding_window_sum_throughput",
        "value": 0.0,
        # a CPU-backend number is never written under the chip's unit
        "unit": ("tuples/sec/chip" if platform == "tpu"
                 else f"tuples/sec ({platform} backend)"),
        "vs_baseline": 1.0,
    }

    if platform == "cpu":
        # Pallas kernels resolve to interpret=True on CPU (the tier-1
        # correctness vehicle — docs/PERF.md round 14); the legacy
        # sections pin the lax build and the `pallas` section below
        # measures the kernels explicitly.  On a real TPU the auto
        # default keeps the compiled kernels on everywhere.
        import windflow_tpu as _wf
        _wf.default_config.pallas_kernels = "0"

    measured = run_bench(platform, CONFIGS[platform], jax)

    result.update(measured)

    # backend stamp (docs/OBSERVABILITY.md "Calibration plane"): every
    # result — and every history row appended below — names the backend,
    # device kind, and jax version it was measured on, so
    # check_bench_regress can refuse to compare rows across hardware.
    result["backend"] = platform
    result["device_kind"] = str(jax.devices()[0].device_kind)
    result["jax_version"] = jax.__version__

    # end-to-end framework path (VERDICT r2 item 3): sustained tuples/sec
    # through PipeGraph.run() + p99 event→window-result latency, alongside
    # the kernel number; the ratio shows what the runtime costs on top of
    # the device program.
    if "--scaling" in sys.argv or \
            os.environ.get("BENCH_SCALING") not in (None, "", "0"):
        result["scaling"] = run_bench_scaling(jax)

    result["ysb"] = run_bench_ysb(platform, CONFIGS[platform], jax)

    result["reduce"] = run_bench_reduce(platform, CONFIGS[platform],
                                        jax)

    result["compaction"] = run_bench_compaction(
        platform, CONFIGS[platform], jax)

    result["pallas"] = run_bench_pallas(platform, CONFIGS[platform],
                                        jax)

    e2e = run_bench_e2e(platform, CONFIGS[platform], jax,
                        kernel_tps=result["value"])
    e2e["ratio_vs_kernel"] = round(
        e2e["tuples_per_sec"] / result["value"], 4) \
        if result["value"] else 0.0
    if e2e["ratio_vs_kernel"] < 0.5:
        # the kernel number consumes pre-staged device batches; the e2e
        # number pays host-side ingest and host→device staging.  Report
        # what the staged path moved next to the H2D rate wf_calibrate
        # MEASURED on this host, when there is one — no link rate is
        # assumed.  Wire-honest: the run's measured wire bytes/tuple
        # where the wire stats carry one (the 16-B logical payload would
        # overstate the link share under compression).
        _ws = e2e.get("wire_stats") or {}
        _bpt = (_ws["wire_bytes"] / max(1, e2e["tuples"])
                if _ws.get("wire_bytes") else 16)
        from windflow_tpu.monitoring import calibration
        _h2d, _h2d_prov = calibration.constant("h2d_bytes_per_sec")
        e2e["h2d_bytes_per_sec"] = _h2d
        e2e["h2d_provenance"] = _h2d_prov
        e2e["gap_diagnosis"] = (
            f"staged {e2e['tuples_per_sec'] * _bpt / 1e6:.0f} MB/s at "
            f"{_bpt:.1f} wire B/tuple; H2D link "
            + (f"{_h2d / 1e6:.0f} MB/s ({_h2d_prov})" if _h2d is not None
               else "not measured (tools/wf_calibrate.py)")
            + ("" if platform == "tpu" else
               "; cpu backend: kernel and pipeline share host cores"))
    result["e2e"] = e2e

    # device-resident-source e2e: same pipeline, batches born in HBM — the
    # staged-vs-device delta decomposes e2e overhead into staging/link
    # share vs framework-dispatch share (VERDICT r4 item 3)
    e2e_dev = run_bench_e2e_device(platform, CONFIGS[platform], jax,
                                   kernel_tps=result["value"])
    e2e_dev["ratio_vs_kernel"] = round(
        e2e_dev["tuples_per_sec"] / result["value"], 4) \
        if result["value"] else 0.0
    e2e = result.get("e2e")
    if e2e:
        staged, dev = e2e["tuples_per_sec"], e2e_dev["tuples_per_sec"]
        if dev > 0 and staged > 0:
            # per-tuple time decomposition: staged-run time = dispatch
            # time + staging time (to first order)
            stage_share = max(0.0, 1.0 - staged / dev)
            e2e_dev["decomposition"] = {
                "staged_tps": staged,
                "device_source_tps": dev,
                "staging_share_of_staged_run": round(stage_share, 4),
                "note": ("device-source run has no host->device "
                         "staging; the delta is the staging/link cost "
                         "the staged e2e pays"),
            }
    result["e2e_device_source"] = e2e_dev

    # the default-config e2e runs above carry the resolved megastep K
    # (auto: per-batch on CPU, K=8 on accelerator backends) — surface
    # the scalar, drop the per-edge detail from the artifact
    for _leg in ("e2e", "e2e_device_source"):
        if isinstance(result.get(_leg), dict):
            _ms = result[_leg].pop("megastep_stats", None)
            result[_leg]["megastep_k"] = (_ms or {}).get("k", 1)

    # megastep section (windflow_tpu/megastep.py, guarded by
    # tools/check_bench_keys.py + check_bench_regress.py): the staged
    # e2e pipeline at a dispatch-bound batch size with K sweeps folded
    # into one compiled program vs the K=1 kill switch — the guarded
    # floor holds the K-run's CPU steady rate at >= 10x the r14
    # per-batch baseline, and dispatches_per_batch pins the 1-program-
    # per-K-sweeps contract via the jit registry
    result["megastep"] = run_bench_megastep(
        platform, CONFIGS[platform], jax,
        kernel_tps=result["value"])

    # wire section (windflow_tpu/wire.py, guarded by
    # tools/check_bench_keys.py + check_bench_regress.py): the seeded
    # compression A/B over the e2e record spec — wire bytes/tuple,
    # compression ratio (hard floor 1.5x), and the decode dispatch
    # delta (hard-pinned 0: the decode rides the existing unpack
    # program).  staging_share re-reports the staged-vs-device-source
    # decomposition next to the wire numbers it exists to shrink, and
    # the staged e2e run's own measured compression rides along.
    wire_sec = run_bench_wire(platform, CONFIGS[platform], jax)
    dev = result.get("e2e_device_source")
    wire_sec["staging_share"] = (
        (dev.get("decomposition") or {}).get(
            "staging_share_of_staged_run")
        if isinstance(dev, dict) else None)
    e2e_ws = None
    if isinstance(result.get("e2e"), dict):
        e2e_ws = result["e2e"].pop("wire_stats", None)
    if isinstance(result.get("e2e_device_source"), dict):
        result["e2e_device_source"].pop("wire_stats", None)
    if isinstance(e2e_ws, dict) and e2e_ws.get("wire_bytes"):
        wire_sec["e2e_compression_ratio"] = \
            e2e_ws.get("compression_ratio")
        wire_sec["e2e_wire_bytes_per_tuple"] = round(
            e2e_ws["wire_bytes"] / max(1, result["e2e"]["tuples"]), 3)
    result["wire"] = wire_sec

    # roofline decomposition (sweep ledger, guarded by
    # tools/check_bench_keys.py): the staged e2e run's per-hop ledger
    # section names where the measured bytes/tuple excess goes —
    # roofline.per_hop carries bytes/tuple + dispatches/batch per
    # operator hop, and attributed_fraction is the hop sum over the raw
    # kernel step's measured bytes (the window hop dominates a healthy
    # pipeline, so the ratio sits near 1; extra hops push it above)
    e2e_sweep = None
    if isinstance(result.get("e2e"), dict):
        e2e_sweep = result["e2e"].pop("sweep", None)
    if isinstance(result.get("e2e_device_source"), dict):
        result["e2e_device_source"].pop("sweep", None)
    roof = result.get("roofline")
    if isinstance(roof, dict):
        per_hop = {}
        for name, h in ((e2e_sweep or {}).get("per_hop") or {}).items():
            per_hop[name] = {
                "bytes_per_tuple": h.get("bytes_per_tuple"),
                "steady_bytes_per_tuple": h.get("steady_bytes_per_tuple"),
                "dispatches_per_batch": h.get("dispatches_per_batch"),
                "excess_vs_model": h.get("excess_vs_model"),
                "donation_miss": bool(h.get("donation_miss")),
            }
        roof["per_hop"] = per_hop
        # steady-state numbers: a short (CI-sized) run's EOS-flush
        # dispatch would dilute the amortized average and misread as
        # missing attribution
        attributed = sum(
            h.get("steady_bytes_per_tuple") or h.get("bytes_per_tuple")
            or 0 for h in per_hop.values())
        mbpt = roof.get("measured_bytes_per_tuple")
        roof["attributed_fraction"] = (
            round(attributed / mbpt, 4) if mbpt and attributed else None)

    # whole-chain fusion (windflow_tpu/fusion, guarded by
    # tools/check_bench_keys.py): the staged e2e run's realized fusion
    # savings — fused chain names, dispatches the sweep no longer pays
    # (N member hops -> one jitted dispatch per batch), and the interior
    # boundary bytes the fused program never materializes in HBM.
    # Recorded into bench_history.json so round-over-round comparisons
    # see fusion on/off regressions; with WF_TPU_FUSE=0 the section
    # still ships (zeros) so the keys guard holds on both paths.
    fus = (e2e_sweep or {}).get("fusion") or {}
    result["fusion"] = {
        "enabled": bool(fus.get("enabled")),
        "fused_chains": fus.get("fused_chains", []),
        "dispatches_saved": fus.get("dispatches_saved_per_batch", 0.0),
        "bytes_saved_per_batch": fus.get("bytes_saved_per_batch", 0.0),
    }

    # latency section (guarded by tools/check_bench_keys.py): the p50/p99
    # distribution numbers the flight-recorder observability layer makes
    # first-class — recorded into bench_history.json so round-over-round
    # comparisons read tails, not means (docs/OBSERVABILITY.md)
    latency = {"batch_p99_ms": result.get("p99_batch_latency_ms")}
    if result.get("e2e"):
        latency["e2e_p50_ms"] = result["e2e"].get("p50_window_latency_ms")
        latency["e2e_p99_ms"] = result["e2e"].get("p99_window_latency_ms")
    # every latency row names its operating point (check_bench_keys
    # rejects unlabeled rows): these numbers come from the default
    # unthrottled e2e runs above
    latency["operating_point"] = "default_e2e"
    result["latency"] = latency

    # latency-SLO section (windflow_tpu/monitoring/latency_ledger.py,
    # guarded by tools/check_bench_keys.py + check_bench_regress.py):
    # the ledger-decomposed staged->sunk p99 at max sustainable
    # throughput against a declared budget — check_bench_keys hard-fails
    # p99 > 2x the recorded SLO, check_bench_regress tripwires the p99
    # round over round
    result["latency_slo"] = run_bench_latency_slo(
        platform, CONFIGS[platform], jax, kernel_tps=result["value"])

    # tenant section (windflow_tpu/monitoring/tenant_ledger.py, guarded
    # by tools/check_bench_keys.py + check_bench_regress.py): two seeded
    # tenants in one process — check_bench_keys hard-fails when the
    # ledger attributes under 90% of the process's staged bytes or its
    # measured self-cost crosses 2% of the run
    result["tenant"] = run_bench_tenant(platform, CONFIGS[platform],
                                        jax)

    # preflight cost (windflow_tpu/analysis, guarded by
    # tools/check_bench_keys.py): time PipeGraph.check() over the
    # representative e2e pipeline shape so the static-analysis cost every
    # start() now pays stays visible in the perf trajectory
    import numpy as np
    import windflow_tpu as wf
    pf_cfg = CONFIGS[platform]
    m = wf.MapTPU_Builder(
        lambda t: {"key": t["key"], "v0": t["v0"] * 1.5 + 1.0}).build()
    f = wf.FilterTPU_Builder(lambda t: (t["key"] & 7) != 7).build()
    w = (wf.Ffat_WindowsTPU_Builder(lambda t: t["v0"],
                                    lambda a, b: a + b)
         .withCBWindows(pf_cfg["win"], pf_cfg["slide"])
         .withKeyBy(lambda t: t["key"])
         .withMaxKeys(pf_cfg["keys"]).build())
    src = (wf.Source_Builder(lambda: iter(()))
           .withOutputBatchSize(pf_cfg["cap"])
           .withRecordSpec({"key": np.int32(0),
                            "v0": np.float32(0.0)}).build())
    pg = wf.PipeGraph("bench_preflight")
    pipe = pg.add_source(src)
    pipe.add(m)
    pipe.chain(f)
    pipe.add(w).add_sink(wf.Sink_Builder(lambda r: None).build())
    diags = pg.check()
    result["preflight"] = {"check_ms": pg._preflight_ms,
                           "diagnostics": len(diags)}
    # wfverify (windflow_tpu/analysis/tracecheck.py, guarded by
    # tools/check_bench_keys.py): the object-level verifier's cost
    # and finding count over the same representative pipeline —
    # `findings` doubles as a tripwire: the bench kernels ship
    # clean, so any nonzero count is a verifier false positive or a
    # real kernel regression.  check() above already ran the pass
    # and kept its report (with the COLD check_ms); re-verifying
    # here would publish a warm-cache time
    vrep = pg._tracecheck_report
    if vrep is None:
        from windflow_tpu.analysis.tracecheck import verify_graph
        vrep = verify_graph(pg)
    result["verify"] = {"findings": len(vrep.diagnostics),
                        "suppressed": len(vrep.suppressed),
                        "checked_callables": vrep.checked,
                        "check_ms": vrep.check_ms}

    # wfir (windflow_tpu/analysis/ir_audit.py, guarded by
    # tools/check_bench_keys.py): context-free WF9xx audit over EVERY
    # program this bench process compiled — the real e2e/kernel/megastep
    # runs above, not a fixture.  `findings` is a hard tripwire: shipped
    # bench programs audit clean, so any nonzero count is a lowering
    # regression (a callback, a 64-bit survivor, a donation miss) or an
    # auditor false positive — both stop the bench leg.
    from windflow_tpu.analysis import ir_audit
    irep = ir_audit.process_report()
    result["ir_audit"] = {
        "programs_audited": irep.programs_audited,
        "findings": len(irep.findings),
        "check_ms": round(irep.check_ms, 3),
    }

    # health section (windflow_tpu/monitoring/health, guarded by
    # tools/check_bench_keys.py): drive a representative pipeline with the
    # watchdog ON and report stall events (any nonzero is a regression —
    # the bench pipelines must run healthy) plus the watchdog's measured
    # self-cost as a share of the run (same <2% stance as the flight
    # recorder; the plane only runs at cadence, so this stays ~0)
    import windflow_tpu as wf
    h_src = (wf.Source_Builder(
        lambda: iter({"key": i % 64, "v0": float(i)}
                     for i in range(65536)))
        .withOutputBatchSize(4096).withName("h_src").build())
    h_map = (wf.MapTPU_Builder(
        lambda t: {"key": t["key"], "v0": t["v0"] * 1.5})
        .withName("h_map").build())
    h_snk = wf.Sink_Builder(lambda r: None).withName("h_snk").build()
    h_pg = wf.PipeGraph("bench_health")
    h_pg.add_source(h_src).add(h_map).add_sink(h_snk)
    t0 = time.perf_counter()
    h_pg.start()
    while not h_pg.is_done():
        if not h_pg.step():
            break       # wait_end raises the diagnosed stall error
        h_pg.health_tick()          # every sweep: worst-case cadence
    h_pg.wait_end()
    run_usec = (time.perf_counter() - t0) * 1e6
    h = h_pg.stats()["Health"]
    result["health"] = {
        "graph_state": h["graph_state"],
        "stall_events": h["stall_events"],
        "watchdog_samples": h["samples_taken"],
        "watchdog_overhead_pct": round(
            100.0 * h["watchdog_usec_total"] / run_usec, 3)
        if run_usec else 0.0,
    }

    # durability section (windflow_tpu/durability, guarded by
    # tools/check_bench_keys.py + check_bench_regress.py): drive the
    # representative kafka->map->window->sink graph with checkpointing
    # OFF then ON (same data, same cadence contract the chaos harness
    # uses), report the checkpoint wall cost/bytes and the e2e overhead
    # of enabling durability (acceptance bound: <5%), then time a real
    # PipeGraph.restore() from the committed store — the restored run
    # replays the tail through the sink fence, so this leg doubles as an
    # exactly-once smoke (nonzero lost/duplicated output would change
    # the topic, caught by the chaos suite's record diff in CI).
    _dwork = None
    try:
        import tempfile as _tf
        from windflow_tpu.durability import chaos as _chaos
        _dn = int(os.environ.get("BENCH_DURABILITY_TUPLES", "32768"))
        _dwork = _tf.mkdtemp(prefix="bench_durability_")
        _chaos.make_cell("window_cb", "", n=_dn)["factory"]().run()  # warm
        t0 = time.perf_counter()
        _chaos.make_cell("window_cb", "", n=_dn)["factory"]().run()
        _t_off = time.perf_counter() - t0
        _dck = os.path.join(_dwork, "ckpt")
        _cell = _chaos.make_cell("window_cb", _dck, n=_dn,
                                 epoch_sweeps=16)
        t0 = time.perf_counter()
        _gd = _cell["factory"]().run()
        _t_on = time.perf_counter() - t0
        _dsec = _gd.stats()["Durability"]
        _gr = _cell["factory"]()
        _gr.restore(_dck)
        _gr.wait_end()
        result["durability"] = {
            "epochs_committed": _dsec["epochs_committed"],
            # mean over the run's epochs, not the last sample: each
            # checkpoint includes an fsync, so a single shot carries
            # I/O jitter the trend guards would trip on
            "checkpoint_ms": round(
                _dsec["checkpoint_ms_total"]
                / max(1, _dsec["epochs_committed"]), 3),
            "checkpoint_bytes": _dsec["last_checkpoint_bytes"],
            "restore_ms": _gr.stats()["Durability"]["restore_ms"],
            "overhead_pct": round(100.0 * (_t_on - _t_off)
                                  / max(_t_off, 1e-9), 2),
            "tuples": _dn,
        }
    finally:
        if _dwork is not None:
            import shutil as _sh
            _sh.rmtree(_dwork, ignore_errors=True)

    # shard-plane section (windflow_tpu/monitoring/shard_ledger, guarded
    # by tools/check_bench_keys.py + check_bench_regress.py): drive a
    # seeded Zipf-skewed keyby workload (40% of the stream on one hot
    # key) through a keyed ReduceTPU at parallelism 2 with the shard
    # ledger ON and report the measured imbalance ratio, the hot key's
    # stream share, and the ICI model total (0.0 on a single chip — the
    # key exists so the multi-chip legs guard the same schema).  The
    # stream is deterministic, so these are regression tripwires, not
    # weather: a drifting imbalance_ratio means the sketch or the
    # placement hash broke.
    import numpy as np
    import windflow_tpu as wf
    _sn = int(os.environ.get("BENCH_SHARD_TUPLES", "32768"))
    _srng = np.random.default_rng(11)
    _sk = _srng.integers(0, 64, _sn)
    _sk[_srng.random(_sn) < 0.4] = 7          # injected hot key
    def _s_build():
        src = (wf.Source_Builder(
            lambda: iter({"key": int(k), "v": 1.0} for k in _sk))
            .withOutputBatchSize(4096).withName("sh_src").build())
        red = (wf.ReduceTPU_Builder(
            lambda a, b: {"key": b["key"], "v": a["v"] + b["v"]})
            .withKeyBy(lambda t: t["key"]).withParallelism(2)
            .withName("sh_red").build())
        pg = wf.PipeGraph("bench_shard")
        pg.add_source(src).add(red).add_sink(
            wf.Sink_Builder(lambda t, ctx=None: None)
            .withName("sh_snk").build())
        return pg
    _s_build().run()     # warmup: the overhead ratio below must
    #                      compare sketch time against a steady run,
    #                      not one dominated by first-compile wall
    _s_pg = _s_build()
    t0 = time.perf_counter()
    _s_pg.run()
    _s_run_usec = (time.perf_counter() - t0) * 1e6
    _s_sec = _s_pg.stats()["Shard"]
    _s_load = _s_sec["per_op"]["sh_red"]["load"]
    _s_tot = _s_sec["totals"]
    result["shard"] = {
        "imbalance_ratio": _s_load.get("imbalance_ratio"),
        "hot_key_share": _s_load.get("hot_key_share"),
        "hot_key": (_s_load.get("hot_keys") or [{}])[0].get("key"),
        "hot_shard": _s_load.get("hot_shard"),
        "ici_bytes_per_tuple": _s_tot.get("ici_bytes_per_tuple",
                                          0.0),
        "sketch_overhead_pct": round(
            100.0 * _s_tot.get("sketch_host_update_usec", 0.0)
            / _s_run_usec, 3) if _s_run_usec else 0.0,
        "tuples": _sn,
    }

    # reshard-executor section (windflow_tpu/serving, guarded by
    # tools/check_bench_keys.py + check_bench_regress.py): two legs.
    # (1) live reshard — a seeded hash-colocated warm-key pair on a
    # keyed host Reduce at parallelism 3 with the executor ON: the
    # delta-window trigger fires, a move_keys plan applies through the
    # quiesce barrier, and the leg reports the apply wall cost, the
    # keys moved, and the post-reshard window imbalance (the number the
    # move exists to repair).  (2) rescale restore — a chaos cell
    # killed at 3 shards and restored at 2, timing the re-bucketing
    # restore (durability/rebucket.py).  Both streams are
    # deterministic: these are regression tripwires, not weather.
    _rwork = None
    try:
        import dataclasses as _rdc
        import tempfile as _tf

        import windflow_tpu as wf
        from windflow_tpu.basic import stable_hash as _sh64
        _rn = int(os.environ.get("BENCH_RESHARD_TUPLES", "24000"))
        _hot = [k for k in range(200) if _sh64(k) % 3 == 0][:2]

        def _r_stream():
            for i in range(_rn):
                r = i % 20
                k = _hot[0] if r < 5 else (
                    _hot[1] if r < 10 else (i % 12))
                yield {"key": k, "value": float(i % 97)}

        def _r_red(item, state):
            state["key"] = item["key"]
            state["n"] = state.get("n", 0) + 1

        _rcfg = _rdc.replace(wf.default_config)
        _rcfg.reshard_executor = True
        _rcfg.reshard_check_sweeps = 4
        _rcfg.reshard_trigger_ticks = 2
        _rcfg.reshard_ok_ticks = 2
        _rcfg.reshard_imbalance_threshold = 1.6
        _rcfg.punctuation_interval_usec = 10 ** 12
        _rg = wf.PipeGraph("bench_reshard", config=_rcfg)
        _rsrc = (wf.Source_Builder(_r_stream)
                 .withOutputBatchSize(256).withName("rs_src").build())
        _rred = (wf.Reduce_Builder(_r_red, dict)
                 .withKeyBy(lambda t: t["key"]).withParallelism(3)
                 .withName("rs_red").build())
        _rg.add_source(_rsrc).add(_rred).add_sink(
            wf.Sink_Builder(lambda t, ctx=None: None)
            .withName("rs_snk").build())
        _rg.run()
        _rsec = _rg.stats()["Reshard"]
        from windflow_tpu.durability import chaos as _rchaos
        _rwork = _tf.mkdtemp(prefix="bench_reshard_")
        _rv = _rchaos.run_rescale_ab(
            "reduce", "mid_epoch", _rwork, shards_kill=3,
            shards_restore=2,
            n=int(os.environ.get("BENCH_RESCALE_TUPLES", "4096")))
        if _rv["diff"] is not None:
            raise RuntimeError(f"rescale cell diverged: {_rv['diff']}")
        result["reshard"] = {
            "plans_applied": _rsec["plans_applied"],
            "keys_moved": _rsec["keys_moved"],
            "plan_apply_ms": _rsec["quiesce_ms"],
            "post_reshard_imbalance":
                (_rsec["ops"].get("rs_red") or {}).get(
                    "window_imbalance"),
            "rescale_restore_ms": _rv["restore_ms"],
            "tuples": _rn,
        }
    finally:
        if _rwork is not None:
            import shutil as _sh
            _sh.rmtree(_rwork, ignore_errors=True)

    # device-plane section (windflow_tpu/monitoring/jit_registry, guarded
    # by tools/check_bench_keys.py): the compile watcher's process totals
    # over every leg above — compile wall cost, recompile events (any
    # nonzero here is a recompilation-storm regression in the bench
    # pipelines), plus the window kernel's cost table where the backend
    # reported one
    from windflow_tpu.monitoring.jit_registry import default_registry
    reg = default_registry()
    snap = reg.snapshot()
    flops = None
    for name, entry in sorted(snap.items()):
        f = (entry.get("cost") or {}).get("flops")
        if not f:
            continue
        if flops is None:
            flops = f           # any-op fallback: first with a cost
        if "ffat" in name or "win" in name:
            flops = f           # prefer the window kernel's number
            break
    totals = reg.totals()
    result["device"] = {"ops_compiled": totals["ops_compiled"],
                        "compiles": totals["compiles"],
                        "recompiles": totals["recompiles"],
                        "compile_ms_total": totals["compile_ms_total"],
                        "flops_per_batch": flops}

    # calibration section (windflow_tpu/monitoring/calibration.py, guarded
    # by tools/check_bench_keys.py): which constants this run computed
    # modeled numbers from, and whether a calibration store replaced the
    # defaults — the bench artifact's own measured-vs-modeled manifest
    from windflow_tpu.monitoring import calibration as _calib
    result["calibration"] = _calib.provenance_summary()

    # TPU acceptance leg (ROADMAP item 1, guarded by
    # tools/check_bench_keys.py): on a REAL chip — never the CPU
    # backend, never the Pallas interpreter — record the item-1
    # acceptance numbers next to their criteria so a passing TPU round
    # is machine-checkable.  Each number names its provenance; a row
    # claiming interpret-mode timings hard-fails check_bench_keys.
    if platform == "tpu":
        pal = result.get("pallas") or {}
        _grp = pal.get("grouping_speedup")
        _e2e_wire = (result.get("wire") or {}).get(
            "e2e_wire_bytes_per_tuple")
        _msr = (result.get("megastep") or {}).get("ratio_vs_kernel")
        _interp = bool(pal.get("interpret_mode"))
        _pal_prov = "interpret" if _interp else "measured"
        result["tpu_acceptance"] = {
            "device_kind": result["device_kind"],
            "grouping_speedup": _grp,
            "grouping_speedup_target": 1.3,
            "grouping_speedup_met": (
                bool(_grp is not None and not _interp and _grp >= 1.3)),
            "grouping_provenance": _pal_prov,
            "e2e_wire_bytes_per_tuple": _e2e_wire,
            "wire_provenance": "measured",
            "ici_bytes_per_tuple": (result.get("shard") or {}).get(
                "ici_bytes_per_tuple"),
            "ici_provenance": ((result.get("calibration") or {})
                               .get("constants", {})
                               .get("ici_bytes_per_sec", {})
                               .get("provenance", "modeled")),
            "megastep_ratio_vs_kernel": _msr,
            "megastep_provenance": "measured",
            "interpret_mode": _interp,
        }

    now = time.time()
    hist = load_history()
    runs = hist.setdefault(platform, [])
    base = pick_baseline(runs, now, result.get("methodology"))
    if base.get("value"):
        if base.get("methodology") == result.get("methodology"):
            result["vs_baseline"] = round(
                result["value"] / base["value"], 4)
        elif result.get("dispatch_value") and base.get("dispatch_value"):
            # methodologies differ but both runs carry the per-dispatch
            # number: that is the one series present on both sides
            result["vs_baseline"] = round(
                result["dispatch_value"] / base["dispatch_value"], 4)
            result["vs_baseline_note"] = (
                "methodology differs from baseline; ratio compares "
                "dispatch_value on both sides")
        elif result.get("dispatch_value"):
            # the stored baseline predates scan-chaining and measured
            # per-dispatch throughput: compare like with like
            result["vs_baseline"] = round(
                result["dispatch_value"] / base["value"], 4)
            result["vs_baseline_note"] = (
                "baseline entry predates the scan-chained methodology; "
                "ratio uses dispatch_value (same per-dispatch "
                "measurement as the baseline)")
        else:
            result["vs_baseline"] = round(
                result["value"] / base["value"], 4)
            result["vs_baseline_note"] = (
                "methodology differs from baseline and no shared "
                "per-dispatch series exists; ratio is cross-methodology")
        result["prev_value"] = base["value"]
        result["prev_methodology"] = base.get("methodology")
    runs.append({"value": result["value"],
                 # comparability stamp: check_bench_regress refuses to
                 # diff rows recorded on different hardware
                 "backend": result.get("backend"),
                 "device_kind": result.get("device_kind"),
                 "jax_version": result.get("jax_version"),
                 "pallas": result.get("pallas"),
                 "tpu_acceptance": result.get("tpu_acceptance"),
                 "methodology": result.get("methodology"),
                 "dispersion": result.get("dispersion"),
                 "dispatch_value": result.get("dispatch_value"),
                 "dispatch_dispersion": result.get("dispatch_dispersion"),
                 "sum_decl_value": result.get("sum_decl_value"),
                 "sum_decl_methodology": result.get("sum_decl_methodology"),
                 "p99_batch_latency_ms": result["p99_batch_latency_ms"],
                 "roofline": result.get("roofline"),
                 "fusion": result.get("fusion"),
                 "latency": result.get("latency"),
                 "latency_slo": result.get("latency_slo"),
                 "tenant": result.get("tenant"),
                 "preflight": result.get("preflight"),
                 "verify": result.get("verify"),
                 "ir_audit": result.get("ir_audit"),
                 "device": result.get("device"),
                 "health": result.get("health"),
                 "shard": result.get("shard"),
                 "wire": result.get("wire"),
                 "megastep": result.get("megastep"),
                 "durability": result.get("durability"),
                 "e2e": result.get("e2e"),
                 "e2e_device_source": result.get("e2e_device_source"),
                 "ysb": result.get("ysb"),
                 "reduce": result.get("reduce"),
                 "compaction": result.get("compaction"),
                 "t": now,
                 "recorded_at": time.strftime("%Y-%m-%d %H:%M:%S")})
    del runs[:-48]  # retention: debugging reruns can burn through a
    #                 20-entry window in one session and rotate out the
    #                 prior round's record the baseline picker needs
    save_history(hist)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
