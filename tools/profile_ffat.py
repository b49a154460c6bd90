"""FFAT kernel component micro-profile (VERDICT r4 item 2).

Breaks the FFAT CB step (windows/ffat_kernels.make_ffat_step, bench
shapes) into its pipeline stages and times each as a standalone jitted
program, so the dominant component is MEASURED before any kernel work:

  key_extract_argsort   stable argsort of the key lane (the sort pass)
  grouping_rank_scatter the O(n) counting permutation (windows/grouping.py)
  sort_gather           argsort + payload/lift gather (sort + data motion)
  rank_scan             segment-start max-scan -> per-lane rank (pre-r5)
  rank_hist             histogram + [K+1] cumsum -> per-lane rank (live)
  pane_cells            segmented scan + scatter into [K+1, NP] pane cells
  sliding_fold          flag-aware dilated log2(R) fold over pane rows
  sliding_fold_plain    flagless fold (withSumCombiner variant)
  sliding_fold_cumsum   cumsum-diff alternative (sum-only; for comparison)
  firing_compact        per-key prefix counts + searchsorted compaction
  full_step             the complete fused step (reference point)

Each timing is the median of 5 windows of `--steps` dispatches on
pre-staged device batches (the bench.py methodology).  Components overlap
inside the fused step (XLA may fuse/elide across them), so shares are
indicative, not additive — the point is the ORDER and the dominant term.

**In-fused-step ablation** (``ablation_ms``; the PROFILE_r05 honesty
fix): standalone component times over-count what a region costs INSIDE
the fused step, where XLA overlaps and fuses across regions (the r05
components each "cost" ~100-120% of the whole step).  The ablation mode
instead swaps ONE region for an identity stub (same shapes, no work) via
the module seams the step builder calls through, re-times the WHOLE
step, and attributes ``full_ms - ablated_ms`` to the region — a real
fused-step delta, the number a Pallas win must be judged against.
Regions: ``grouping`` (order+hist), ``pane_scan`` (segmented scan),
``sliding_fold`` (window fold).

**Pallas comparison** (``pallas_compare``; docs/PERF.md round 14): the
full fused step timed with the Pallas kernels selected
(windflow_tpu/kernels, Config.pallas_kernels resolution) against the
pure-lax build of the SAME step, for both the generic-combiner path and
the declared-monoid path — the bench ``pallas`` section's
methodology, at profile shapes.  On CPU the kernels run under the
Pallas interpreter (``interpret_mode: true``): a correctness vehicle,
expected SLOWER than lax — real speedups are TPU numbers.

Usage:  python tools/profile_ffat.py [--cpu] [--json out.json]
"""

import argparse
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))


def build_components(jax, jnp, CAP, K, Pn, R):
    """Return {name: (jitted_fn, args_builder)} component programs mirroring
    the stages of windows/ffat_kernels.make_ffat_step (cited per stage)."""
    from windflow_tpu.windows.ffat_kernels import (_seg_scan,
                                                   _sliding_reduce,
                                                   _sliding_reduce_plain)

    NP1 = CAP // Pn + 2
    comb = lambda a, b: a + b

    def key_extract_argsort(payload, valid):
        keys = payload["k"]
        sk = jnp.where(valid & (keys >= 0) & (keys < K), keys, K)
        return jnp.argsort(sk, stable=True)

    def grouping_rank_scatter(payload, valid):
        from windflow_tpu.windows.grouping import counting_order
        keys = payload["k"]
        sk = jnp.where(valid & (keys >= 0) & (keys < K), keys, K)
        return counting_order(sk, K + 1)

    def sort_gather(payload, valid):
        keys = payload["k"]
        sk = jnp.where(valid & (keys >= 0) & (keys < K), keys, K)
        order = jnp.argsort(sk, stable=True)
        return sk[order], payload["v"][order]

    def rank_scan(sk_sorted):
        # the pre-r5 rank stage (kept for comparison): [CAP]-length
        # associative max-scan over segment starts
        pos = jnp.arange(CAP)
        starts = jnp.concatenate(
            [jnp.array([True]), sk_sorted[1:] != sk_sorted[:-1]])
        seg_start = jax.lax.associative_scan(
            jnp.maximum, jnp.where(starts, pos, 0))
        return pos - seg_start

    def rank_hist(payload, valid, sk_sorted):
        # the live rank stage (ffat_kernels.py step, permutation branch):
        # histogram of the UNSORTED keys + [K+1] exclusive cumsum —
        # rank = pos - bucket_start[sorted key], no [CAP]-length scan
        keys = payload["k"]
        sk = jnp.where(valid & (keys >= 0) & (keys < K), keys, K)
        hist = jnp.zeros(K + 1, jnp.int32).at[sk].add(1)
        bucket_start = jnp.cumsum(hist) - hist
        return jnp.arange(CAP) - bucket_start[sk_sorted]

    def pane_cells(sk_sorted, v_sorted, pane_rel):
        starts = jnp.concatenate(
            [jnp.array([True]), sk_sorted[1:] != sk_sorted[:-1]])
        pane_starts = starts | jnp.concatenate(
            [jnp.array([True]), pane_rel[1:] != pane_rel[:-1]])
        scanned = _seg_scan(comb, pane_starts, v_sorted)
        ends = jnp.concatenate(
            [(sk_sorted[1:] != sk_sorted[:-1])
             | (pane_rel[1:] != pane_rel[:-1]), jnp.array([True])])
        row = jnp.where(ends, sk_sorted, K)
        col = jnp.where(ends, pane_rel, 0)
        buf = jnp.zeros((K + 1, NP1), scanned.dtype)
        return buf.at[row, col].set(jnp.where(ends, scanned, 0))[:K]

    def sliding_fold(cells, cell_has):
        _, v = _sliding_reduce(comb, cell_has, cells, R, axis=1)
        return v

    def sliding_fold_plain(cells, cell_has):
        return _sliding_reduce_plain(comb, cell_has, cells, R, axis=1,
                                     monoid="sum")

    def sliding_fold_cumsum(cells, cell_has):
        # cumsum-diff: out[i] = cs[i] - cs[i-R]; sum-only alternative
        z = jnp.where(cell_has, cells, 0)
        cs = jnp.cumsum(z, axis=1)
        shifted = jnp.pad(cs, ((0, 0), (R, 0)))[:, :cs.shape[1]]
        return cs - shifted

    def firing_compact(swin, m_k, win_next, pane_base):
        done = pane_base + m_k
        n_fired = jnp.maximum(0, (done - win_next) // 1 + 1)
        run = jnp.cumsum(n_fired)
        MAXO = CAP // Pn + 2 * K + 8
        slot = jnp.arange(MAXO)
        owner = jnp.searchsorted(run, slot, side="right")
        owner_c = jnp.minimum(owner, K - 1)
        base = jnp.where(owner_c > 0, run[owner_c - 1], 0)
        j = slot - base
        col = jnp.clip(win_next[owner_c] + j - pane_base[owner_c],
                       0, swin.shape[1] - 1)
        vals = swin[owner_c, col]
        return vals, owner_c, (slot < run[K - 1])

    return {
        "key_extract_argsort": key_extract_argsort,
        "grouping_rank_scatter": grouping_rank_scatter,
        "sort_gather": sort_gather,
        "rank_scan": rank_scan,
        "rank_hist": rank_hist,
        "pane_cells": pane_cells,
        "sliding_fold": sliding_fold,
        "sliding_fold_plain": sliding_fold_plain,
        "sliding_fold_cumsum": sliding_fold_cumsum,
        "firing_compact": firing_compact,
    }, NP1


def _identity_stubs(region: str):
    """(module attr name -> stub) map swapping ONE step region for an
    identity of the same output shapes — covers both the lax bodies
    (windows/ffat_kernels) and the Pallas twins (windflow_tpu/kernels)
    so the ablation composes with either build."""
    import jax.numpy as jnp

    from windflow_tpu import kernels as pk
    from windflow_tpu.windows import ffat_kernels as fk
    if region == "grouping":
        def order_hist_stub(ids, nb, grouping=None, pallas=None):
            n = ids.shape[0]
            return (jnp.arange(n, dtype=jnp.int32),
                    jnp.zeros(nb, jnp.int32).at[ids].add(1))

        def rank_hist_stub(ids, nb, interpret):
            n = ids.shape[0]
            z = jnp.zeros(n, jnp.int32)
            return z, z, jnp.zeros(nb, jnp.int32).at[ids].add(1)

        def dense_rank_stub(ids, nb):
            n = ids.shape[0]
            z = jnp.zeros(n, jnp.int32)
            return (z, jnp.zeros(nb, jnp.int32).at[ids].add(1)[:nb],
                    ids, jnp.arange(n, dtype=jnp.int32))

        return {(fk, "_group_order_hist"): order_hist_stub,
                (fk, "_group_order"):
                    lambda ids, nb, g, pallas=None:
                        jnp.arange(ids.shape[0], dtype=jnp.int32),
                (fk, "dense_rank"): dense_rank_stub,
                (pk, "grouping_rank_hist"): rank_hist_stub,
                (pk, "order_hist"):
                    lambda ids, nb, interpret:
                        order_hist_stub(ids, nb)}
    if region == "pane_scan":
        return {(fk, "_seg_scan"): lambda comb, flags, values: values}
    if region == "sliding_fold":
        return {(fk, "_sliding_reduce"):
                    lambda comb, flags, values, R, axis: (flags, values),
                (fk, "_sliding_reduce_plain"):
                    lambda comb, flags, values, R, axis, monoid: values,
                (pk, "sliding_fold"):
                    lambda values, valid, R, monoid, interpret: values}
    raise ValueError(region)


def _time_step(jax, step, state, payload, ts, valid, steps):
    st, out, fired, _ = step(state, payload, ts, valid)
    jax.block_until_ready(st)
    import time as _time
    rates = []
    for _ in range(5):
        t0 = _time.perf_counter()
        s = st
        for _ in range(steps):
            s, out, fired, _ = step(s, payload, ts, valid)
        jax.block_until_ready(s)
        rates.append((_time.perf_counter() - t0) / steps)
    rates.sort()
    return rates[len(rates) // 2]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--json", default=None)
    args = ap.parse_args()

    import jax
    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np

    from windflow_tpu.compile_cache import setup_compile_cache
    setup_compile_cache()
    dev = jax.devices()[0]
    platform = dev.platform
    # bench.py TPU config shapes (kept identical so the shares transfer)
    if platform == "tpu":
        CAP, K, WIN, SLIDE = 262144, 1024, 1024, 128
    else:
        CAP, K, WIN, SLIDE = 65536, 256, 1024, 128
    Pn = math.gcd(WIN, SLIDE)
    R, D = WIN // Pn, SLIDE // Pn

    comps, NP1 = build_components(jax, jnp, CAP, K, Pn, R)

    rng = np.random.default_rng(0)
    payload = {"k": jax.device_put(
                   jnp.asarray(rng.integers(0, K, CAP), jnp.int32), dev),
               "v": jax.device_put(
                   jnp.asarray(rng.random(CAP, dtype=np.float32)), dev)}
    valid = jax.device_put(jnp.ones(CAP, bool), dev)

    # pre-materialize stage inputs so each component times ONLY itself
    sk_sorted, v_sorted = jax.jit(comps["sort_gather"])(payload, valid)
    rank = jax.jit(comps["rank_scan"])(sk_sorted)
    pane_rel = (rank // Pn).astype(jnp.int32)
    cells = jax.jit(comps["pane_cells"])(sk_sorted, v_sorted, pane_rel)
    cell_has = cells != 0
    m_k = jnp.full(K, NP1 - 2, jnp.int32)
    win_next = jnp.zeros(K, jnp.int64)
    pane_base = jnp.zeros(K, jnp.int64)
    jax.block_until_ready(cells)

    arg_map = {
        "key_extract_argsort": (payload, valid),
        "grouping_rank_scatter": (payload, valid),
        "sort_gather": (payload, valid),
        "rank_scan": (sk_sorted,),
        "rank_hist": (payload, valid, sk_sorted),
        "pane_cells": (sk_sorted, v_sorted, pane_rel),
        "sliding_fold": (cells, cell_has),
        "sliding_fold_plain": (cells, cell_has),
        "sliding_fold_cumsum": (cells, cell_has),
        "firing_compact": (jnp.pad(cells, ((0, 0), (R - 1, 0))), m_k,
                           win_next, pane_base),
    }

    def time_fn(fn, fargs):
        jfn = jax.jit(fn)
        out = jfn(*fargs)
        jax.block_until_ready(out)
        rates = []
        for _ in range(5):
            t0 = time.perf_counter()
            for _ in range(args.steps):
                out = jfn(*fargs)
            jax.block_until_ready(out)
            rates.append((time.perf_counter() - t0) / args.steps)
        rates.sort()
        return rates[len(rates) // 2]

    # full step reference points (the bench kernel), one per grouping
    from windflow_tpu.windows.ffat_kernels import (make_ffat_state,
                                                   make_ffat_step)
    ts = jax.device_put(jnp.arange(CAP, dtype=jnp.int64), dev)
    full_by_grouping = {}
    for grouping in ("rank_scatter", "argsort"):
        step = jax.jit(make_ffat_step(CAP, K, Pn, R, D, lambda x: x["v"],
                                      lambda a, b: a + b,
                                      lambda x: x["k"], grouping=grouping))
        state = jax.device_put(
            make_ffat_state(jnp.zeros((), jnp.float32), K, R), dev)

        def full(state):
            st, out, fired, _ = step(state, payload, ts, valid)
            return st

        st = full(state)
        jax.block_until_ready(st)
        rates = []
        for _ in range(5):
            t0 = time.perf_counter()
            for _ in range(args.steps):
                st = full(st)
            jax.block_until_ready(st)
            rates.append((time.perf_counter() - t0) / args.steps)
        rates.sort()
        full_by_grouping[grouping] = rates[len(rates) // 2]
    full_s = full_by_grouping["rank_scatter"]

    result = {
        "platform": platform, "device": str(dev),
        "config": {"cap": CAP, "keys": K, "win": WIN, "slide": SLIDE,
                   "panes": NP1, "R": R},
        "full_step_ms": round(full_s * 1e3, 4),
        "full_step_tuples_per_sec": round(CAP / full_s, 1),
        "full_step_argsort_ms": round(
            full_by_grouping["argsort"] * 1e3, 4),
        "full_step_argsort_tuples_per_sec": round(
            CAP / full_by_grouping["argsort"], 1),
        "rank_scatter_speedup": round(
            full_by_grouping["argsort"] / full_s, 4),
        "components_ms": {},
        "note": ("components are timed standalone; inside the fused step "
                 "XLA overlaps/fuses them, so shares are indicative; "
                 "full_step uses grouping=rank_scatter, "
                 "full_step_argsort the comparison-sort baseline"),
    }
    for name, fn in comps.items():
        t = time_fn(fn, arg_map[name])
        result["components_ms"][name] = {
            "ms": round(t * 1e3, 4),
            "pct_of_full": round(100 * t / full_s, 1),
        }

    # -- in-fused-step ablation (the r05 "shares are indicative" honesty
    # fix): swap ONE region for an identity stub, re-time the WHOLE
    # step; full - ablated is the region's REAL fused-step share --------
    def build_and_time(monoid=None, pallas=None, stubs=None, steps=None):
        saved = {}
        if stubs:
            for key, fn in stubs.items():
                saved[key] = getattr(key[0], key[1])
                setattr(key[0], key[1], fn)
        try:
            # stubs must stay live through the first dispatch: the jit
            # traces the module seams lazily, so timing happens inside
            # the patch window
            step = jax.jit(make_ffat_step(
                CAP, K, Pn, R, D, lambda x: x["v"], lambda a, b: a + b,
                lambda x: x["k"], monoid=monoid, pallas=pallas))
            state = jax.device_put(
                make_ffat_state(jnp.zeros((), jnp.float32), K, R), dev)
            return _time_step(jax, step, state, payload, ts, valid,
                              steps or args.steps)
        finally:
            for key, fn in saved.items():
                setattr(key[0], key[1], fn)

    result["ablation_ms"] = {}
    for region in ("grouping", "pane_scan", "sliding_fold"):
        t = build_and_time(stubs=_identity_stubs(region))
        result["ablation_ms"][region] = {
            "ablated_step_ms": round(t * 1e3, 4),
            "attributed_ms": round((full_s - t) * 1e3, 4),
            "attributed_pct_of_full": round(100 * (full_s - t) / full_s,
                                            1),
        }
    result["ablation_note"] = (
        "attributed_ms = full_step_ms - step_ms with the region swapped "
        "for an identity stub INSIDE the fused step — the real "
        "fused-step share a kernel win is judged against (standalone "
        "components_ms over-count by the XLA overlap)")

    # -- Pallas comparison block (docs/PERF.md round 14) ----------------
    from windflow_tpu.basic import Config as _Config
    from windflow_tpu.kernels import resolve_pallas
    pmode = resolve_pallas(_Config())
    pcomp = {
        "backend": platform,
        "kernels_selected": pmode is not None,
        "interpret_mode": (bool(pmode.interpret) if pmode is not None
                           else None),
        "note": ("interpret_mode=true means the kernels run under the "
                 "Pallas interpreter (CPU tier-1 correctness vehicle) — "
                 "expected SLOWER than lax; real speedups are compiled "
                 "TPU numbers"),
    }
    if pmode is not None:
        psteps = min(args.steps, 5) if pmode.interpret else args.steps
        for label, monoid in (("generic", None), ("monoid_sum", "sum")):
            t_lax = build_and_time(monoid=monoid, steps=psteps)
            t_pal = build_and_time(monoid=monoid, pallas=pmode,
                                   steps=psteps)
            pcomp[label] = {
                "lax_step_ms": round(t_lax * 1e3, 4),
                "pallas_step_ms": round(t_pal * 1e3, 4),
                "ffat_step_speedup_vs_lax": round(t_lax / t_pal, 4),
            }
    result["pallas_compare"] = pcomp
    line = json.dumps(result, indent=2)
    print(line)
    if args.json:
        with open(args.json, "w") as f:
            f.write(line + "\n")


if __name__ == "__main__":
    main()
