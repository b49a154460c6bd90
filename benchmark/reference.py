"""Plain numpy/float64 references and the comparison that decides
``correct``.  Nothing here imports the program.

The stream a run sends is the seeded ring repeated (tuple *i* is ring
record ``i % R``), so a key's arrival sequence is its ring sequence
repeated, and its running sums are known in closed form: the references
cost one pass over the ring however long the run was.  The tests hold
them against the per-tuple oracles of ``chip_smoke.py`` (copied below) on
the materialized stream.
"""

from __future__ import annotations

from typing import List, NamedTuple

import numpy as np


# ---------------------------------------------------------------------------
# the smoke's oracles, copied (chip_smoke.py stays free to change)
# ---------------------------------------------------------------------------

def oracle_cb_windows(keys, vals, win: int, slide: int):
    """Count-based sliding windows per key in arrival order: window ``w``
    of a key covers that key's tuples ``[w*slide, w*slide+win)`` and
    exists once its first tuple arrived (partial windows flush at end of
    stream).  Returns ``(key, wid, f64 sum)`` arrays."""
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


def oracle_tb_counts(groups, tss, window_usec: int, n_groups: int):
    """Tuples per group per tumbling event-time window.  Returns sorted
    ``(group, wid, count)`` of the non-empty cells."""
    wid = tss // window_usec
    n_w = int(wid.max()) + 1
    counts = np.bincount(groups.astype(np.int64) * n_w + wid,
                         minlength=n_groups * n_w)
    nz = np.flatnonzero(counts)
    return nz // n_w, nz % n_w, counts[nz]


# ---------------------------------------------------------------------------
# closed forms over the repeated ring
# ---------------------------------------------------------------------------

class Windows(NamedTuple):
    """Expected result rows, sorted by (key, wid)."""
    key: np.ndarray       # int64
    wid: np.ndarray       # int64
    value: np.ndarray     # float64 (sums) or int64 (counts)
    #: True where the window closed inside the stream (not at its end)
    full: np.ndarray
    #: global stream index of the tuple that closed a full window, -1 else
    closer: np.ndarray


def cb_windows_of_ring(keys: np.ndarray, vals: np.ndarray, keep: np.ndarray,
                       n_total: int, win: int, slide: int) -> Windows:
    """Count-based sliding-window sums per key over the first ``n_total``
    tuples of the ring ``(keys, vals)`` repeated, of the tuples ``keep``
    lets through.  ``vals`` are summed in float64 as given."""
    R = len(keys)
    pos = np.flatnonzero(keep)
    kk = keys[pos]
    if len(kk) and 0 <= kk.min() and kk.max() < 1 << 15:
        kk = kk.astype(np.int16)            # numpy radix-sorts 16-bit keys
    order = np.argsort(kk, kind="stable")
    spos = pos[order]                       # ring positions, by key, in order
    skey = keys[spos].astype(np.int64)
    cs = np.cumsum(vals[spos].astype(np.float64))
    n_keys = int(skey.max()) + 1 if len(skey) else 0
    m = np.bincount(skey, minlength=n_keys)            # per pass, per key
    off = np.concatenate([[0], np.cumsum(m)[:-1]])
    base = np.where(off > 0, cs[np.maximum(off - 1, 0)], 0.0)
    last = off + m - 1
    total = np.where(m > 0, cs[np.maximum(last, 0)] - base, 0.0)

    q, r = divmod(int(n_total), R)
    n = q * m + np.bincount(skey[spos < r], minlength=n_keys)
    live = np.flatnonzero(n > 0)
    n_w = -(-n[live] // slide)
    key = np.repeat(live, n_w)
    wid = np.arange(int(n_w.sum())) - np.repeat(np.cumsum(n_w) - n_w, n_w)
    start = wid * slide
    end = np.minimum(start + win, n[key])

    def prefix(x):
        a, b = np.divmod(x, m[key])
        part = np.where(b > 0, cs[np.maximum(off[key] + b - 1, 0)]
                        - base[key], 0.0)
        return a * total[key] + part

    full = start + win <= n[key]
    j = np.where(full, start + win - 1, 0)
    a, b = np.divmod(j, m[key])
    closer = np.where(full, a * R + spos[off[key] + b], -1)
    return Windows(key.astype(np.int64), wid.astype(np.int64),
                   prefix(end) - prefix(start), full, closer)


def tb_counts_of_ring(groups: np.ndarray, n_total: int, event_rate: int,
                      window_usec: int, n_groups: int,
                      stamp_offset_usec: int = 0) -> Windows:
    """Tuples per group per tumbling window over the first ``n_total``
    tuples of the ring ``groups`` repeated (a negative group is a tuple
    the filter drops), tuple *i* stamped ``i * 1e6 // event_rate`` usec
    (plus ``stamp_offset_usec``, which only a control uses).  A window is
    ``full`` when a later tuple's stamp passed its end."""
    R = len(groups)
    ok = groups >= 0
    per_pass = np.bincount(groups[ok], minlength=n_groups)

    def count(lo: int, hi: int) -> np.ndarray:      # stream range [lo, hi)
        q, a, b = (hi - lo) // R, lo % R, hi % R
        c = q * per_pass
        seg = [groups[a:b]] if a <= b else [groups[a:], groups[:b]]
        for s in seg:
            c = c + np.bincount(s[s >= 0], minlength=n_groups)
        return c

    last_ts = (n_total - 1) * 1_000_000 // event_rate + stamp_offset_usec

    def first_at(ts: int) -> int:        # first index stamped >= ts
        return max(0, -(-((ts - stamp_offset_usec) * event_rate)
                        // 1_000_000))
    out_k, out_w, out_v, out_f, out_c = [], [], [], [], []
    for w in range(last_ts // window_usec + 1):
        lo = first_at(w * window_usec)
        hi = min(n_total, first_at((w + 1) * window_usec))
        c = count(lo, hi)
        nz = np.flatnonzero(c)
        out_k.append(nz)
        out_w.append(np.full(len(nz), w))
        out_v.append(c[nz])
        closed = hi < n_total
        out_f.append(np.full(len(nz), closed))
        out_c.append(np.full(len(nz), hi if closed else -1))
    key, wid, val, full, closer = (np.concatenate(x) for x in
                                   (out_k, out_w, out_v, out_f, out_c))
    order = np.lexsort((wid, key))
    return Windows(key[order].astype(np.int64), wid[order].astype(np.int64),
                   val[order].astype(np.int64), full[order], closer[order])


# ---------------------------------------------------------------------------
# the comparison
# ---------------------------------------------------------------------------

def check(name: str, value, limit) -> dict:
    """One number compared, beside its limit."""
    return {"name": name, "value": float(value), "limit": float(limit),
            "ok": bool(np.isfinite(value) and value <= limit)}


def match_rows(got_key, got_wid, exp: Windows):
    """Sort the program's rows by (key, wid) and align them with the
    expected ones.  Returns ``(order, mismatches)``: ``order`` sorts the
    program's rows, ``mismatches`` counts rows missing, extra or
    duplicated (0 = the two (key, wid) sets are the same)."""
    order = np.lexsort((got_wid, got_key))
    gk, gw = got_key[order], got_wid[order]
    if len(gk) == len(exp.key):
        bad = int(np.count_nonzero((gk != exp.key) | (gw != exp.wid)))
    else:
        width = int(max(gw.max(initial=0), exp.wid.max(initial=0))) + 1
        g = np.unique(gk * width + gw)
        e = exp.key * width + exp.wid
        bad = int(len(np.setxor1d(g, e)) + (len(gk) - len(g)))
    return order, bad


def compare_windows(got_key, got_wid, got_val, exp: Windows, value_limit,
                    exact: bool) -> List[dict]:
    """Every result row against the reference: the exact (key, wid) set
    and row count, then each value — counts exactly, sums by the worst
    relative error."""
    order, bad = match_rows(got_key, got_wid, exp)
    out = [check("rows_missing_or_extra", abs(len(got_key) - len(exp.key)),
                 0),
           check("key_wid_mismatches", bad, 0),
           check("result_rows_absent", 0 if len(got_key) else 1, 0)]
    if bad or not len(got_key):
        worst = np.inf
    elif exact:
        worst = int(np.count_nonzero(got_val[order] != exp.value))
    else:
        gv = got_val[order].astype(np.float64)
        worst = float(np.max(np.abs(gv - exp.value)
                             / np.maximum(np.abs(exp.value), 1e-30))) \
            if np.all(np.isfinite(gv)) else np.inf
    out.append(check("count_mismatches" if exact else "sum_max_rel_err",
                     worst, value_limit))
    return out


def verdict(checks: List[dict]) -> bool:
    return all(c["ok"] for c in checks)
