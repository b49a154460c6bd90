#!/usr/bin/env python
"""wf_calibrate: probe the live backend, write calibration.json.

The shard ledger's ICI model, the tenant ledger's modeled ICI share,
and the roofline ceiling all compute from constants
(``calibration.MODELED_DEFAULTS``) that were, until this tool,
hardcoded guesses.  ``wf_calibrate`` measures them — a
short seeded probe suite on the backend this process actually has —
and writes a versioned ``calibration.json`` keyed by device kind +
jax version.  Point ``Config.calibration`` / ``WF_TPU_CALIBRATION``
at the file and every read site flips from ``modeled`` to
``calibrated(<age>)`` provenance until the store goes stale
(``calibration.TTL_S``, 7 days) or the device kind
changes (docs/OBSERVABILITY.md "Calibration plane").

Probes (all seeded, a few seconds total):

* ``h2d_bytes_per_sec`` — best host→device transfer rate of a packed
  staging buffer after one warm transfer (``staging.probe_h2d``: the
  SAME pooled buffer and transfer call the runtime stages batches
  through, and the same probe each wire-plane edge decides from, so
  the number is the link the staged e2e leg actually pays).  No
  modeled default exists for it.
* ``dispatch_overhead_usec`` — wall cost of dispatching one cached
  trivial jitted program (the per-dispatch floor the megastep fold
  amortizes).
* ``sampled_sync_usec`` — one ``block_until_ready`` device sync (what
  each ``trace_device_sync_every``-sampled batch pays).
* ``hbm_bytes_per_sec`` — effective memory bandwidth of a large
  compiled elementwise copy (the roofline ceiling; on the CPU
  backend this measures host memory, honestly).
* ``kernel_step_usec`` — one fused FFAT window step at the bench
  shape (the per-device-kind step timing the roofline cross-checks).
* ``ici_bytes_per_sec`` — psum ring bandwidth across the mesh; only
  recorded on a multi-device backend (``MESH_ONLY_KEYS``).

Usage::

    python tools/wf_calibrate.py                  # probe + write
    python tools/wf_calibrate.py --out cal.json   # elsewhere
    python tools/wf_calibrate.py --check [PATH]   # validate only:
        # exit 0 fresh+valid, 1 stale/corrupt/missing, 2 kill switch

``--check`` is pure stdlib (no jax import — loads calibration.py
file-direct, the wf_metrics pattern) so CI relay hosts can gate on it.
The refuse-to-report-clean stance: a missing or stale store exits 1,
and the ``WF_TPU_CALIBRATION=0`` kill switch exits 2 — a pipeline
that *meant* to be calibrated must hear that it is not.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_OUT = os.path.join(REPO, "calibration.json")
if REPO not in sys.path:        # script runs live with tools/ as
    sys.path.insert(0, REPO)    # sys.path[0]; the probes need the package


def _load_calibration_mod():
    """File-direct import of monitoring/calibration.py: skips the
    ``windflow_tpu`` package __init__ (which imports jax), so --check
    runs on hosts with no jax at all."""
    path = os.path.join(REPO, "windflow_tpu", "monitoring",
                        "calibration.py")
    spec = importlib.util.spec_from_file_location("_wf_calibration", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _median(xs):
    xs = sorted(xs)
    return xs[len(xs) // 2]


# ---------------------------------------------------------------------------
# probes (each returns (value, probe_detail))
# ---------------------------------------------------------------------------

def probe_h2d(jax, np, reps: int = 7):
    """Host→device staging rate over the runtime's own packed path:
    ``staging.probe_h2d``, the one probe the wire plane also decides
    from, at the size of a 256k-row int32 + float32 + ts batch."""
    from windflow_tpu import staging
    nwords = 4 * (1 << 18) + 1            # ≈ 4 MB packed
    return staging.probe_h2d(nwords, reps=reps), \
        {"buffer_bytes": nwords * 4, "reps": reps, "stat": "best"}


def probe_dispatch(jax, np, reps: int = 200):
    """Per-dispatch overhead of a cached trivial program (µs)."""
    import jax.numpy as jnp
    x = jax.device_put(jnp.zeros(8, jnp.float32))
    f = jax.jit(lambda a: a + 1.0)
    jax.block_until_ready(f(x))          # compile outside the clock
    t0 = time.perf_counter()
    y = x
    for _ in range(reps):
        y = f(y)
    jax.block_until_ready(y)
    usec = (time.perf_counter() - t0) * 1e6 / reps
    return usec, {"reps": reps}


def probe_sync(jax, np, reps: int = 50):
    """One sampled block_until_ready round trip (µs)."""
    import jax.numpy as jnp
    x = jax.device_put(jnp.zeros(8, jnp.float32))
    f = jax.jit(lambda a: a + 1.0)
    jax.block_until_ready(f(x))
    ts = []
    for _ in range(reps):
        y = f(x)
        t0 = time.perf_counter()
        jax.block_until_ready(y)
        ts.append((time.perf_counter() - t0) * 1e6)
    return _median(ts), {"reps": reps}


def probe_hbm(jax, np, reps: int = 7):
    """Effective memory bandwidth of a compiled elementwise copy: the
    program reads + writes the array once, so bytes = 2 * nbytes."""
    import jax.numpy as jnp
    n = 1 << 24                           # 64 MB f32
    x = jax.device_put(jnp.ones(n, jnp.float32))
    f = jax.jit(lambda a: a * 1.0000001)
    jax.block_until_ready(f(x))
    rates = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(f(x))
        rates.append(2 * x.nbytes / (time.perf_counter() - t0))
    return _median(rates), {"array_bytes": int(x.nbytes), "reps": reps}


def probe_kernel_step(jax, np, reps: int = 5):
    """One fused FFAT window step at the bench shape (µs/step)."""
    import jax.numpy as jnp
    from windflow_tpu.windows.ffat_kernels import (make_ffat_state,
                                                   make_ffat_step)
    cap, keys, win, slide = 8192, 256, 16, 4
    import math as _math
    pn = _math.gcd(win, slide)
    step = jax.jit(make_ffat_step(
        cap, keys, pn, win // pn, slide // pn,
        lambda x: x["v"], lambda a, b: a + b, lambda x: x["k"],
        monoid="sum"))
    rng = np.random.default_rng(1)
    payload = {
        "k": jnp.asarray(rng.integers(0, keys, cap), jnp.int32),
        "v": jnp.asarray(rng.random(cap), jnp.float32),
    }
    tss = jnp.arange(cap, dtype=jnp.int64)
    valid = jnp.ones(cap, bool)
    st = make_ffat_state(jnp.zeros((), jnp.float32), keys, win // pn)
    st, out, fired, _ = step(st, payload, tss, valid)
    jax.block_until_ready(st)
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        s = st
        for _ in range(10):
            s, out, fired, _ = step(s, payload, tss, valid)
        jax.block_until_ready(s)
        ts.append((time.perf_counter() - t0) * 1e6 / 10)
    return _median(ts), {"cap": cap, "keys": keys, "reps": reps}


def probe_ici(jax, np, reps: int = 7):
    """psum ring bandwidth across the mesh — multi-device only."""
    import jax.numpy as jnp
    ndev = jax.device_count()
    if ndev < 2:
        return None, {"note": f"single device ({ndev}) — skipped"}
    n = 1 << 20                           # 4 MB f32 per device
    x = jnp.ones((ndev, n), jnp.float32)
    f = jax.pmap(lambda a: jax.lax.psum(a, "i"), axis_name="i")
    jax.block_until_ready(f(x))
    rates = []
    # ring all-reduce moves ~2*(N-1)/N of the payload per device
    moved = 2 * (ndev - 1) / ndev * n * 4 * ndev
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(f(x))
        rates.append(moved / (time.perf_counter() - t0))
    return _median(rates), {"devices": ndev, "payload_bytes": n * 4,
                            "reps": reps}


PROBES = (
    ("h2d_bytes_per_sec", probe_h2d),
    ("dispatch_overhead_usec", probe_dispatch),
    ("sampled_sync_usec", probe_sync),
    ("hbm_bytes_per_sec", probe_hbm),
    ("kernel_step_usec", probe_kernel_step),
    ("ici_bytes_per_sec", probe_ici),
)


def calibrate(out_path: str) -> int:
    calib = _load_calibration_mod()
    if calib.killed():
        print("wf_calibrate: FAIL: WF_TPU_CALIBRATION=0 — the kill "
              "switch is on; unset it to calibrate", file=sys.stderr)
        return 2
    import jax
    import numpy as np

    from windflow_tpu.compile_cache import setup_compile_cache
    setup_compile_cache()
    dev = jax.devices()[0]
    kind = str(getattr(dev, "device_kind", None) or dev.platform)
    constants, probes = {}, {}
    for key, fn in PROBES:
        try:
            value, detail = fn(jax, np)
        except Exception as e:  # lint: broad-except-ok (one dead probe
            # must not lose the others' measurements; the key simply
            # stays modeled and the detail names why)
            probes[key] = {"error": f"{type(e).__name__}: {e}"[:200]}
            print(f"wf_calibrate: note: probe {key} failed "
                  f"({type(e).__name__}: {e})")
            continue
        probes[key] = detail
        if value is not None:
            constants[key] = round(float(value), 3)
            print(f"wf_calibrate: {key} = {constants[key]}")
        else:
            print(f"wf_calibrate: {key} skipped "
                  f"({detail.get('note', 'no value')})")
    if not constants:
        print("wf_calibrate: FAIL: every probe failed — nothing to "
              "write", file=sys.stderr)
        return 1
    store = calib.CalibrationStore({
        "schema": calib.SCHEMA,
        "recorded_at": time.time(),
        "device_kind": kind,
        "backend": dev.platform,
        "jax_version": jax.__version__,
        "constants": constants,
        "probes": probes,
    }, path=out_path)
    with open(out_path, "w") as f:
        json.dump(store.to_json(), f, indent=2)
        f.write("\n")
    print(f"wf_calibrate: wrote {out_path} ({len(constants)} constant(s) "
          f"for {kind}, jax {jax.__version__})")
    return 0


def check(path: str) -> int:
    """Validate-only (stdlib, no jax): the CI gate."""
    calib = _load_calibration_mod()
    if calib.killed():
        # the kill switch means "deliberately uncalibrated" — distinct
        # exit code so a pipeline that MEANT to calibrate can tell the
        # difference from a stale store
        print("wf_calibrate: kill switch (WF_TPU_CALIBRATION=0) — "
              "calibration disabled process-wide", file=sys.stderr)
        return 2
    try:
        store = calib.load(path)
    except calib.CalibrationError as e:
        print(f"wf_calibrate: FAIL: {path}: {e}", file=sys.stderr)
        return 1
    age = store.age_s()
    if not store.fresh():
        print(f"wf_calibrate: FAIL: {path} is {age / 86400:.1f} days old "
              f"(TTL {calib.TTL_S / 86400:.1f}d) — constants would "
              "degrade to modeled; re-run wf_calibrate", file=sys.stderr)
        return 1
    missing = [k for k in calib.MODELED_DEFAULTS
               if k not in store.constants
               and k not in calib.MESH_ONLY_KEYS]
    note = f", {len(missing)} key(s) still modeled: {missing}" \
        if missing else ""
    print(f"wf_calibrate: OK ({path}: {len(store.constants)} constant(s) "
          f"for {store.device_kind}, jax {store.jax_version}, age "
          f"{age / 3600:.1f}h{note})")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default=DEFAULT_OUT,
                    help=f"output path (default {DEFAULT_OUT})")
    ap.add_argument("--check", nargs="?", const="", metavar="PATH",
                    help="validate an existing store instead of probing "
                         "(default: --out, then WF_TPU_CALIBRATION)")
    args = ap.parse_args(argv)
    if args.check is not None:
        path = args.check or os.environ.get("WF_TPU_CALIBRATION") \
            or args.out
        return check(path)
    return calibrate(args.out)


if __name__ == "__main__":
    sys.exit(main())
