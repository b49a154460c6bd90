#!/usr/bin/env python3
"""chip_smoke.py — does the system still start on the chip?

One process drives ``PipeGraph.run()`` under a default ``Config()``
through the public API, at the widest shape the repo supports
(``bench.CONFIGS["tpu"]``: batch 262144, 1024 keys, CB 1024/128), and
compares every leg's sink output with a plain numpy oracle written
here — not with the repo's own operators.  Data is made from
``--seed``.

  A      flagship CB: binary frames (native C parser) -> MapTPU (+)
         FilterTPU (fused) -> FfatWindowsTPU CB 1024/128 keyed, generic
         combiner -> columnar sink.  16 batches = 4,194,304 tuples.
  A_sum  the same graph with the combiner declared (withSumCombiner):
         the only route to the Pallas pane-fold kernel.
  B      YSB at its published shape: 100 campaigns x 10 ads, three
         event types, view filter, ad->campaign gather, 10 s tumbling
         event-time count (withSumCombiner).
  C      declared-dense keyed reduce on leg A's stream: ReduceTPU
         withMaxKeys(1024).withMonoidCombiner("sum") — the route to the
         Pallas dense-table kernel.
  D      leg A's graph on Config(mesh=make_mesh(4)); only when >= 4 TPU
         devices are visible.

Each leg runs twice in this process: a cold wall (tracing + compiling +
running) and a warm wall.  Both are smoke observations, never
benchmark metrics.  For each default-ON path the leg reports whether it
really ENGAGED, read from ``g.stats()`` and the jit/IR registries
after the run, not from the configuration.

Stdout is two JSON lines: the report (device, versions, per-leg verdict,
walls, engagement, ``"claim": null``), then as the LAST line the verdict
``{"ok": true, "device": {"platform", "kind", "count"}}`` with exactly
those keys.  Exit code 0 and ``"ok": true`` only when JAX runs on a TPU,
the native library was built in this run from the committed sources,
and every leg agrees with its oracle; without a TPU nothing is printed
to stdout.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

# the flagship shape: bench.CONFIGS["tpu"]
CAP = 262144
N_KEYS = 1024
WIN, SLIDE = 1024, 128
N_BATCHES = 16

# YSB's published shape
YSB_CAMPAIGNS, YSB_ADS_PER_CAMPAIGN = 100, 10
YSB_WINDOW_USEC = 10_000_000
YSB_VIEW = 1            # event types: 0 purchase, 1 view, 2 click
YSB_WINDOWS = 8         # event-time span of the generated stream

FRAME = [("k", "<i8"), ("t", "<i8"), ("v", "<f8")]


# ---------------------------------------------------------------------------
# seeded streams (frames wire format: int64 key, int64 ts, float64 value)
# ---------------------------------------------------------------------------

def flagship_stream(seed: int, n: int, n_keys: int):
    """Uniform keys over ``n_keys``, U[0,1) values.  Returns
    ``(blob, keys, vals)``."""
    rng = np.random.default_rng(seed)
    rec = np.empty(n, dtype=FRAME)
    rec["k"] = rng.integers(0, n_keys, n)
    rec["t"] = np.arange(n)             # INGRESS time overwrites it
    rec["v"] = rng.random(n)
    return rec.tobytes(), rec["k"].copy(), rec["v"].copy()


def ysb_stream(seed: int, n: int):
    """YSB ad events: uniform ad ids, uniform event types, in-order event
    time spanning ``YSB_WINDOWS`` windows.  Returns
    ``(blob, ad_ids, etypes, tss, ad_to_campaign)``."""
    rng = np.random.default_rng(seed)
    n_ads = YSB_CAMPAIGNS * YSB_ADS_PER_CAMPAIGN
    table = rng.permutation(
        np.repeat(np.arange(YSB_CAMPAIGNS), YSB_ADS_PER_CAMPAIGN)) \
        .astype(np.int32)
    rec = np.empty(n, dtype=FRAME)
    rec["k"] = rng.integers(0, n_ads, n)
    gap = max(1, YSB_WINDOWS * YSB_WINDOW_USEC // n)
    rec["t"] = np.arange(n, dtype=np.int64) * gap
    rec["v"] = rng.integers(0, 3, n)
    return (rec.tobytes(), rec["k"].copy(), rec["v"].astype(np.int64),
            rec["t"].copy(), table)


def chunker(blob: bytes, chunk_bytes: int = 1 << 20):
    def chunks():
        for lo in range(0, len(blob), chunk_bytes):
            yield blob[lo:lo + chunk_bytes]
    return chunks


# ---------------------------------------------------------------------------
# oracles: plain numpy, independent of the code under test
# ---------------------------------------------------------------------------

def flagship_transform(keys, vals):
    """Host twin of legs A/C's device prelude: f32 ``v*1.5+1`` then drop
    keys whose low three bits are all set."""
    v = vals.astype(np.float32) * np.float32(1.5) + np.float32(1.0)
    keep = (keys & 7) != 7
    return keys[keep], v[keep]


def oracle_cb_windows(keys, vals, win: int, slide: int):
    """Count-based sliding windows per key in arrival order: window ``w``
    of a key covers that key's tuples ``[w*slide, w*slide+win)`` and
    exists once its first tuple arrived (partial windows flush at end of
    stream).  Returns ``{(key, wid): f64 sum}`` as sorted arrays."""
    order = np.argsort(keys, kind="stable")
    ks, vs = keys[order], vals[order].astype(np.float64)
    cuts = np.flatnonzero(np.diff(ks)) + 1
    out_k, out_w, out_v = [], [], []
    for lo, hi in zip(np.r_[0, cuts], np.r_[cuts, len(ks)]):
        n = hi - lo
        run = np.concatenate([[0.0], np.cumsum(vs[lo:hi])])
        w = np.arange(-(-n // slide))
        first = w * slide
        out_k.append(np.full(len(w), ks[lo]))
        out_w.append(w)
        out_v.append(run[np.minimum(first + win, n)] - run[first])
    return (np.concatenate(out_k).astype(np.int64),
            np.concatenate(out_w).astype(np.int64), np.concatenate(out_v))


def oracle_ysb(ad_ids, etypes, tss, table):
    """View events only, joined to their campaign, counted per 10 s
    tumbling event-time window.  Returns sorted ``(campaign, wid, n)``."""
    view = etypes == YSB_VIEW
    camp = table[ad_ids[view]].astype(np.int64)
    wid = tss[view] // YSB_WINDOW_USEC
    n_w = int(wid.max()) + 1
    counts = np.bincount(camp * n_w + wid, minlength=YSB_CAMPAIGNS * n_w)
    nz = np.flatnonzero(counts)
    return nz // n_w, nz % n_w, counts[nz]


def oracle_key_totals(keys, vals, n_keys: int):
    """Host fold of leg C: per-key tuple count and f64 value sum."""
    return (np.bincount(keys, minlength=n_keys),
            np.bincount(keys, weights=vals.astype(np.float64),
                        minlength=n_keys))


def _sorted_rows(k, w, v):
    order = np.lexsort((w, k))
    return k[order], w[order], v[order]


def compare_windows(got, exp, rtol: float) -> dict:
    """Exact (key, wid) set, values within ``rtol`` (0 = exact)."""
    gk, gw, gv = _sorted_rows(*got)
    ek, ew, ev = _sorted_rows(*exp)
    same_set = len(gk) == len(ek) and bool(
        np.array_equal(gk, ek) and np.array_equal(gw, ew))
    finite = bool(np.all(np.isfinite(gv.astype(np.float64))))
    close = same_set and finite and bool(
        np.allclose(gv, ev, rtol=rtol, atol=0.0))
    out = {"result_rows": int(len(gk)), "expected_rows": int(len(ek)),
           "window_set_exact": same_set, "finite": finite,
           "correct": bool(close and len(gk) > 0)}
    if same_set and len(gk):
        out["max_rel_err"] = float(np.max(
            np.abs(gv - ev) / np.maximum(np.abs(ev), 1e-30)))
    return out


# ---------------------------------------------------------------------------
# engagement evidence (read AFTER a run, never from the configuration)
# ---------------------------------------------------------------------------

def _mosaic_calls() -> dict:
    """op name -> Mosaic custom calls in the programs lowered so far (the
    IR audit's record of each first-compile lowering; WF907 reads the
    same facts)."""
    from windflow_tpu.analysis import ir_audit
    return {name: sum(f.get("mosaic_calls", 0) for f in facts)
            for name, facts in ir_audit.store_snapshot().items()}


class Evidence:
    """Snapshot of the process-wide kernel/IR registries before a leg's
    first run; :meth:`collect` reads what the run added."""

    def __init__(self) -> None:
        from windflow_tpu.kernels import pallas_build_count
        self._builds = pallas_build_count()
        self._mosaic = _mosaic_calls()

    def collect(self, g) -> dict:
        from windflow_tpu.kernels import pallas_build_count, resolve_pallas
        st = g.stats()
        ms = st["Megastep"]
        megasteps = sum(e["megasteps"] for e in ms["edges"])
        mega = {"engaged": megasteps > 0, "k": ms["k"],
                "megasteps": megasteps,
                "scanned_batches": sum(e["batches"] for e in ms["edges"]),
                "per_batch_fallbacks": sum(
                    e["fallback_batches"] + e["warmup_batches"]
                    for e in ms["edges"])}
        if not mega["engaged"]:
            mega["reason"] = (
                "resolved off (K=1)" if ms["k"] <= 1 else
                "no eligible staged edge" if not ms["edges"] else
                "every K-group drained per-batch before it filled")
        ws = st["Staging"]["Wire"]
        wire = {"engaged": bool(ws["batches"]),
                "compression_ratio": ws["compression_ratio"],
                "batches": ws["batches"], "raw_batches": ws["raw_batches"],
                "decisions": ws["decisions"]}
        if not wire["engaged"]:
            wire["reason"] = (
                "resolved off" if not ws["enabled"] else
                "no packed staging edge with a record spec (a mesh "
                "stages per shard, unpacked)" if not ws["encoders"] else
                "every edge measured its link faster than its codec "
                "and ships raw")
        mode = resolve_pallas(g.config)
        mosaic = {n: c - self._mosaic.get(n, 0)
                  for n, c in _mosaic_calls().items()
                  if c > self._mosaic.get(n, 0)}
        builds = pallas_build_count() - self._builds
        pallas = {
            "engaged": bool(mode is not None and not mode.interpret
                            and builds > 0 and mosaic),
            "interpret": None if mode is None else bool(mode.interpret),
            "kernel_builds": builds,
            "mosaic_custom_calls": mosaic,
        }
        if not pallas["engaged"]:
            pallas["reason"] = (
                "resolved off" if mode is None else
                "interpret mode (not a TPU backend)" if mode.interpret else
                "no kernel gate held on this graph" if not builds else
                "kernels built but no Mosaic custom call was lowered")
        return {"megastep": mega, "wire": wire, "pallas": pallas}


def _programs() -> dict:
    """op name -> (compiles, compile seconds, dispatches) so far in this
    process, from the jit registry."""
    from windflow_tpu.monitoring.jit_registry import default_registry
    return {n: (e["compiles"], e["compile_ms_total"] / 1e3, e["dispatches"])
            for n, e in default_registry().snapshot().items()}


def _programs_since(before: dict) -> dict:
    out = {}
    for name, now in _programs().items():
        was = before.get(name, (0, 0.0, 0))
        if now[2] > was[2]:
            out[name] = {"compiles": now[0] - was[0],
                         "compile_s": round(now[1] - was[1], 2),
                         "dispatches": now[2] - was[2]}
    return out


def _timed_twice(run_once) -> dict:
    """Run a leg twice in this process: cold (trace + compile + run) and
    warm (same shapes; fresh operator objects re-trace, the compiler's
    work comes back from the cache).  Evidence is taken across the cold
    run; the verdict must hold on BOTH runs."""
    ev = Evidence()
    before = _programs()
    t0 = time.perf_counter()
    g, verdict = run_once()
    cold = time.perf_counter() - t0
    engaged = ev.collect(g)
    cold_programs = _programs_since(before)
    before = _programs()
    t0 = time.perf_counter()
    _, verdict2 = run_once()
    warm = time.perf_counter() - t0
    verdict["correct"] = bool(verdict["correct"] and verdict2["correct"])
    verdict["warm_result_rows"] = verdict2["result_rows"]
    verdict.update(cold_wall_s=round(cold, 3), warm_wall_s=round(warm, 3),
                   engaged=engaged, cold_programs=cold_programs,
                   warm_programs=_programs_since(before))
    return verdict


# ---------------------------------------------------------------------------
# legs
# ---------------------------------------------------------------------------

class _Rows:
    """Columnar sink accumulator: keeps each delivered batch's columns."""

    def __init__(self) -> None:
        self.batches = []

    def __call__(self, c) -> None:
        if c is not None:
            self.batches.append({k: np.asarray(v)
                                 for k, v in c.cols.items()})

    def column(self, name, dtype):
        if not self.batches:
            return np.empty(0, dtype)
        return np.concatenate([b[name] for b in self.batches]) \
            .astype(dtype)


def _frame_source(blob: bytes, cap: int):
    """The legs' ingest: 1 MiB chunks of binary frames through the
    native parser, staged in batches of ``cap``."""
    from windflow_tpu.io import FrameSource
    src = FrameSource(chunker(blob), nv=1, fmt="frames",
                      output_batch_size=cap)
    # the declared record spec is what lets the wire plane attach
    src.record_spec = {"key": np.int32(0), "v0": np.float32(0.0)}
    return src


def _flagship_graph(blob, rows, cap, n_keys, win, slide, declared_sum,
                    config):
    import windflow_tpu as wf
    src = _frame_source(blob, cap)
    m = wf.MapTPU_Builder(
        lambda t: {"key": t["key"], "v0": t["v0"] * 1.5 + 1.0}).build()
    f = wf.FilterTPU_Builder(lambda t: (t["key"] & 7) != 7).build()
    wb = (wf.Ffat_WindowsTPU_Builder(lambda t: t["v0"], lambda a, b: a + b)
          .withName("ffat_sum" if declared_sum else "ffat")
          .withCBWindows(win, slide)
          .withKeyBy(lambda t: t["key"]).withMaxKeys(n_keys))
    if declared_sum:
        wb = wb.withSumCombiner()
    w = wb.build()
    snk = wf.Sink_Builder(rows).withColumnarSink(defer=4).build()
    g = wf.PipeGraph("smoke_flagship", wf.ExecutionMode.DEFAULT,
                     wf.TimePolicy.INGRESS, config=config)
    pipe = g.add_source(src)
    pipe.add(m)
    pipe.chain(f)            # Map + Filter fuse into one XLA program
    pipe.add(w).add_sink(snk)
    return g, w


def leg_flagship(seed: int, cap: int = CAP, n_batches: int = N_BATCHES,
                 n_keys: int = N_KEYS, win: int = WIN, slide: int = SLIDE,
                 declared_sum: bool = False, config=None) -> dict:
    """Legs A / A_sum / D.  ``state_devices`` counts the devices the
    window state ended the run on (leg D's sharding evidence)."""
    import jax
    n = cap * n_batches
    blob, keys, vals = flagship_stream(seed, n, n_keys)
    exp = oracle_cb_windows(*flagship_transform(keys, vals), win, slide)
    # n * eps bounds the relative error of any summation order of
    # positive f32 terms
    rtol = win * float(np.finfo(np.float32).eps)

    def run_once():
        rows = _Rows()
        g, w = _flagship_graph(blob, rows, cap, n_keys, win, slide,
                               declared_sum, config)
        g.run()
        got = (rows.column("key", np.int64), rows.column("wid", np.int64),
               rows.column("value", np.float64))
        verdict = compare_windows(got, exp, rtol)
        verdict["state_devices"] = len(set().union(
            *(leaf.sharding.device_set
              for leaf in jax.tree.leaves(w._states))))
        return g, verdict

    out = _timed_twice(run_once)
    out.update(tuples_in=n, rtol=rtol)
    return out


def leg_ysb(seed: int, cap: int = CAP, n_batches: int = N_BATCHES,
            config=None) -> dict:
    """Leg B: the graph of ``bench.run_bench_ysb``."""
    import jax.numpy as jnp

    import windflow_tpu as wf
    n = cap * n_batches
    blob, ad_ids, etypes, tss, table_np = ysb_stream(seed, n)
    exp = oracle_ysb(ad_ids, etypes, tss, table_np)
    table = jnp.asarray(table_np)

    def run_once():
        rows = _Rows()
        src = _frame_source(blob, cap)
        flt = wf.FilterTPU_Builder(
            lambda e: e["v0"] == float(YSB_VIEW)).build()
        prj = wf.MapTPU_Builder(
            lambda e: {"campaign": table[e["key"]], "one": 1}).build()
        win = (wf.Ffat_WindowsTPU_Builder(lambda e: e["one"],
                                          lambda a, b: a + b)
               .withName("campaign_counts")
               .withTBWindows(YSB_WINDOW_USEC, YSB_WINDOW_USEC)
               .withKeyBy(lambda e: e["campaign"])
               .withMaxKeys(YSB_CAMPAIGNS).withSumCombiner().build())
        snk = wf.Sink_Builder(rows).withColumnarSink().build()
        g = wf.PipeGraph("smoke_ysb", wf.ExecutionMode.DEFAULT,
                         wf.TimePolicy.EVENT, config=config)
        pipe = g.add_source(src)
        pipe.add(flt)
        pipe.chain(prj)      # Filter + Map(join) fuse into one program
        pipe.add(win).add_sink(snk)
        g.run()
        got = (rows.column("key", np.int64), rows.column("wid", np.int64),
               rows.column("value", np.int64))
        return g, compare_windows(got, exp, rtol=0.0)

    out = _timed_twice(run_once)
    out.update(tuples_in=n, windows=int(len(np.unique(exp[1]))))
    return out


def leg_reduce(seed: int, cap: int = CAP, n_batches: int = N_BATCHES,
               n_keys: int = N_KEYS, config=None) -> dict:
    """Leg C: per-batch keyed reduce, every lane declared ``sum``.  A
    count lane rides along so each output row names its key
    (``key_sum / n``).  Batch boundaries move with the punctuation
    timer, so the comparison is per key over all rows, plus one row per
    key per delivered batch."""
    import jax.numpy as jnp

    import windflow_tpu as wf
    n = cap * n_batches
    blob, keys, vals = flagship_stream(seed, n, n_keys)
    exp_n, exp_v = oracle_key_totals(keys, vals.astype(np.float32), n_keys)
    rtol = float(exp_n.max()) * float(np.finfo(np.float32).eps)

    def run_once():
        rows = _Rows()
        src = _frame_source(blob, cap)
        m = wf.MapTPU_Builder(
            lambda t: {"key": t["key"], "v0": t["v0"],
                       "n": jnp.int32(1)}).build()
        red = (wf.ReduceTPU_Builder(
                lambda a, b: {"key": a["key"] + b["key"],
                              "v0": a["v0"] + b["v0"], "n": a["n"] + b["n"]})
               .withName("dense_reduce").withKeyBy(lambda t: t["key"])
               .withMaxKeys(n_keys).withMonoidCombiner("sum").build())
        snk = wf.Sink_Builder(rows).withColumnarSink().build()
        g = wf.PipeGraph("smoke_reduce", wf.ExecutionMode.DEFAULT,
                         wf.TimePolicy.INGRESS, config=config)
        g.add_source(src).add(m).add(red).add_sink(snk)
        g.run()
        ksum = rows.column("key", np.int64)
        cnt = rows.column("n", np.int64)
        val = rows.column("v0", np.float64)
        ok = len(cnt) > 0 and bool(np.all(cnt > 0)) \
            and bool(np.all(ksum % np.maximum(cnt, 1) == 0))
        verdict = {"result_rows": int(len(cnt)), "delivered_batches":
                   len(rows.batches), "correct": False}
        if ok:
            key = ksum // cnt
            one_row_per_key = all(
                len(np.unique(b["key"] // b["n"])) == len(b["n"])
                for b in rows.batches)
            got_n = np.bincount(key, weights=cnt, minlength=n_keys)
            got_v = np.bincount(key, weights=val, minlength=n_keys)
            verdict["counts_exact"] = bool(
                len(got_n) == n_keys and np.array_equal(got_n, exp_n))
            verdict["finite"] = bool(np.all(np.isfinite(val)))
            verdict["correct"] = bool(
                one_row_per_key and verdict["counts_exact"]
                and verdict["finite"]
                and np.allclose(got_v, exp_v, rtol=rtol, atol=0.0))
        return g, verdict

    out = _timed_twice(run_once)
    out.update(tuples_in=n, rtol=rtol)
    return out


def leg_mesh(seed: int, n_devices: int = 4, **shape) -> dict:
    """Leg D: leg A's graph with the window state sharded over
    ``n_devices`` chips; results must equal leg A's oracle."""
    import dataclasses

    import windflow_tpu as wf
    from windflow_tpu.parallel.mesh import make_mesh
    cfg = dataclasses.replace(wf.Config(), mesh=make_mesh(n_devices))
    out = leg_flagship(seed, config=cfg, **shape)
    out["correct"] = bool(out["correct"]
                          and out["state_devices"] == n_devices)
    return out


# ---------------------------------------------------------------------------

def emit(ok: bool, devs, observed: dict) -> None:
    """Stdout, two JSON lines: the report with everything ``observed``,
    then — LAST — the verdict, which holds exactly ``ok`` and ``device``
    (``platform``, ``kind``, ``count``, as JAX reports them)."""
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    print(json.dumps({"report": "chip_smoke", "ok": ok, "device": device,
                      **observed, "claim": None}))
    print(json.dumps({"ok": ok, "device": device}), flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax

    from windflow_tpu import native
    from windflow_tpu.compile_cache import setup_compile_cache

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: JAX found platform {devs[0].platform!r} "
              f"({devs[0].device_kind}), not a TPU — nothing to check",
              file=sys.stderr)
        return 2
    cache_dir = setup_compile_cache()
    # the library this run parses with is compiled NOW from the committed
    # sources; a build failure raises
    native_so = native.build(force=True)
    if not native.is_available():
        print("chip_smoke: native library built but did not load",
              file=sys.stderr)
        return 3

    legs = {
        "A_flagship_cb": lambda: leg_flagship(args.seed),
        "A_sum_flagship_cb_declared": lambda: leg_flagship(
            args.seed, declared_sum=True),
        "B_ysb": lambda: leg_ysb(args.seed + 1),
        "C_dense_reduce": lambda: leg_reduce(args.seed),
    }
    results = {}
    for name, leg in legs.items():
        t0 = time.perf_counter()
        results[name] = leg()
        print(f"chip_smoke: {name} correct={results[name]['correct']} "
              f"rows={results[name]['result_rows']} "
              f"({time.perf_counter() - t0:.1f}s)", file=sys.stderr)
    if len(devs) >= 4:
        results["D_mesh4"] = leg_mesh(args.seed)
    else:
        results["D_mesh4"] = f"not run: {len(devs)} device(s)"

    ran = [r for r in results.values() if isinstance(r, dict)]
    ok = all(r["correct"] and r["result_rows"] > 0 for r in ran)
    try:
        import libtpu
        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = None
    emit(ok, devs, {
        "jax_version": jax.__version__,
        "libtpu_version": libtpu_version,
        "compile_cache_dir": cache_dir,
        "native_library": native_so,
        "seed": args.seed,
        "shape": {"batch": CAP, "batches": N_BATCHES, "keys": N_KEYS,
                  "win": WIN, "slide": SLIDE},
        "legs": results,
    })
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
