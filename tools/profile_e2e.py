"""End-to-end decomposition profile for the bench pipeline (VERDICT r4
item 1): break ``PipeGraph.run()`` time into its cost centers so the
kernel↔e2e gap is attacked where it actually is.

Measured pieces (each standalone, on the bench.py e2e pipeline shapes):

  ingest_parse     binary frame bytes -> host columns (native parser)
  staging          host columns -> ONE packed device transfer per batch
  device_map_filter   the chained Map+Filter program on staged batches
  device_ffat      the FFAT window step on staged batches
  egress           fired-window device batches -> host columns (packed D2H)
  e2e_wall         the whole PipeGraph.run() (async overlap included)
  per_op_service   host-side service time per operator from StatsRecords

Because XLA dispatch is asynchronous, the standalone pieces do NOT sum to
the wall time — overlap is the point.  The dominant standalone piece is
the pipeline's floor; ``e2e_wall`` minus the largest piece bounds what
better overlap could recover.

Usage:  python tools/profile_e2e.py [--cpu] [--tuples N] [--json out.json]
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--tuples", type=int, default=None)
    ap.add_argument("--json", default=None)
    args = ap.parse_args()

    import jax
    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    import numpy as np

    import bench as B
    from windflow_tpu.compile_cache import setup_compile_cache

    setup_compile_cache()   # the bench's own methodology: fresh graph
    # objects re-trace/lower every program; the persistent cache is what
    # keeps the timed run measuring the framework, not the compiler
    dev = jax.devices()[0]
    platform = dev.platform
    cfg = B.CONFIGS[platform]
    CAP, K = cfg["cap"], cfg["keys"]
    n_tuples = args.tuples or cfg["e2e_tuples"]
    n_batches = max(1, n_tuples // CAP)

    rng = np.random.default_rng(1)
    rec = np.empty(n_tuples, dtype=[("k", "<i8"), ("t", "<i8"),
                                    ("v", "<f8")])
    rec["k"] = rng.integers(0, K, n_tuples)
    rec["t"] = np.arange(n_tuples)
    rec["v"] = rng.random(n_tuples)
    blob = rec.tobytes()

    def med(fn, reps=3):
        ts = sorted(fn() for _ in range(reps))
        return ts[len(ts) // 2]

    result = {"platform": platform, "device": str(dev),
              "config": {"cap": CAP, "keys": K, "tuples": n_tuples,
                         "batches": n_batches}}

    # -- 1. ingest parse (bytes -> host columns, native path) --------------
    from windflow_tpu import native

    def parse_once():
        t0 = time.perf_counter()
        for b in range(n_batches):
            lo = b * CAP * 24
            native.parse_frames(blob[lo:lo + CAP * 24], 1)
        return time.perf_counter() - t0

    keys_np, ts_np, vals_np, _ = native.parse_frames(blob[:CAP * 24], 1)
    result["ingest_parse_s"] = round(med(parse_once), 4)

    # -- 2. staging (host columns -> one packed transfer per batch) --------
    import jax.numpy as jnp

    from windflow_tpu.batch import columns_to_device

    payload_cols = {"key": keys_np.astype(np.int32),
                    "v0": vals_np[:, 0].astype(np.float32)}

    def stage_once():
        t0 = time.perf_counter()
        outs = [columns_to_device(payload_cols, ts_np, CAP)
                for _ in range(n_batches)]
        jax.block_until_ready([o.payload for o in outs])
        return time.perf_counter() - t0

    db0 = columns_to_device(payload_cols, ts_np, CAP)
    jax.block_until_ready(db0.payload)
    result["staging_s"] = round(med(stage_once), 4)
    result["staging_mb_per_batch"] = round(CAP * 16 / 1e6, 2)

    # -- 3. device programs (pre-staged, the kernel methodology) -----------
    map_fn = lambda t: {"key": t["key"], "v0": t["v0"] * 1.5 + 1.0}
    filt = lambda t: (t["key"] & 7) != 7

    @jax.jit
    def mf(payload, valid):
        p2 = jax.vmap(map_fn)(payload)
        return p2, valid & jax.vmap(filt)(p2)

    import math

    from windflow_tpu.windows.ffat_kernels import (make_ffat_state,
                                                   make_ffat_step)
    Pn = math.gcd(cfg["win"], cfg["slide"])
    R, D = cfg["win"] // Pn, cfg["slide"] // Pn
    step = jax.jit(make_ffat_step(CAP, K, Pn, R, D, lambda x: x["v0"],
                                  lambda a, b: a + b, lambda x: x["key"]),
                   donate_argnums=(0,))
    state = jax.device_put(
        make_ffat_state(jnp.zeros((), jnp.float32), K, R), dev)

    p2, keep = mf(db0.payload, db0.valid)
    st, out, fired, _ = step(state, p2, db0.ts, keep)
    jax.block_until_ready(st)

    def dev_mf_once():
        t0 = time.perf_counter()
        for _ in range(n_batches):
            p, kp = mf(db0.payload, db0.valid)
        jax.block_until_ready(kp)
        return time.perf_counter() - t0

    result["device_map_filter_s"] = round(med(dev_mf_once), 4)

    def dev_ffat_once():
        nonlocal st
        t0 = time.perf_counter()
        for _ in range(n_batches):
            st, o, f, _ = step(st, p2, db0.ts, keep)
        jax.block_until_ready(st)
        return time.perf_counter() - t0

    result["device_ffat_s"] = round(med(dev_ffat_once), 4)

    # -- 4. egress (fired windows -> host columns, packed D2H) -------------
    from windflow_tpu.batch import DeviceBatch, device_to_columns_multi

    out_db = DeviceBatch(out, jnp.zeros(fired.shape[0], jnp.int64), fired,
                         watermark=0, size=None)

    def egress_once():
        t0 = time.perf_counter()
        for _ in range(n_batches):
            device_to_columns_multi([out_db])
        return time.perf_counter() - t0

    result["egress_s"] = round(med(egress_once), 4)

    # -- 5. whole PipeGraph.run() with per-op service times ----------------
    def chunks():
        for lo in range(0, len(blob), 1 << 20):
            yield blob[lo:lo + (1 << 20)]

    g = B._e2e_graph(cfg, n_tuples, chunks, lambda c: None)
    g.run()                                     # warm: compile everything

    g2 = B._e2e_graph(cfg, n_tuples, chunks, lambda c: None)
    t0 = time.perf_counter()
    g2.run()
    wall = time.perf_counter() - t0
    result["e2e_wall_s"] = round(wall, 4)
    result["e2e_tuples_per_sec"] = round(n_tuples / wall, 1)

    per_op = {}
    for op in g2._operators:
        per_op[op.name] = round(sum(
            r.stats.service_time_usec for r in op.replicas) / 1e6, 4)
    result["per_op_service_s"] = per_op
    result["service_total_s"] = round(sum(per_op.values()), 4)
    result["driver_residual_s"] = round(
        wall - sum(per_op.values()), 4)

    pieces = {k: result[k] for k in ("ingest_parse_s", "staging_s",
                                     "device_map_filter_s", "device_ffat_s",
                                     "egress_s")}
    result["dominant_piece"] = max(pieces, key=pieces.get)

    line = json.dumps(result, indent=2)
    print(line)
    if args.json:
        with open(args.json, "w") as f:
            f.write(line + "\n")


if __name__ == "__main__":
    main()
