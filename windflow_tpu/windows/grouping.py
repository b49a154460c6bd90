"""Stable O(n) dense-key grouping permutations (no comparison sort).

The FFAT steps group a batch by key (count-based) or by (key, pane)
(time-based) before folding runs.  The reference pays a comparison sort
for the same grouping (``thrust::sort_by_key``: ``flatfat_gpu.hpp`` via
``keyby_emitter_gpu.hpp:519-583``); this module replaces it with a stable
counting sort that exploits the dense-key contract (keys are ints in
``[0, K)``, enforced at the operator boundary):

1. a lane's rank *within its ``CHUNK``-lane chunk* among equal ids is
   ``CHUNK - 1`` shifted equality compares over the flat lane array —
   pure VPU work, no sort, no [C, C] pairwise tensor;
2. per-chunk bucket histograms (one O(n) scatter-add), exclusive-scanned
   across chunks (log-depth ``associative_scan`` — measured 3.5x faster
   than ``jnp.cumsum``'s lowering on CPU) to give each lane its
   cross-chunk offset, and across buckets to give each bucket its start;
3. ``dest = bucket_start[id] + cross_chunk[chunk, id] + within`` is then
   a *permutation* — one scatter of iota inverts it into gather indices.

Total work is O(n*C + (n/C)*nbuckets) element ops — O(n) for fixed
chunk/bucket sizes, minimized at C ~ sqrt(nbuckets) — versus the
O(n log n) comparison sort XLA lowers ``argsort`` to, with constants
that measure 3x+ worse on CPU (and bitonic O(n log^2 n) passes on TPU).
Bucket spaces wider than one digit (time-based pane ids) compose by LSD
radix over base-``DIGIT`` digits, each pass a stable single-digit
counting sort.

The permutation is bit-identical to ``jnp.argsort(ids, stable=True)``:
both order by (id, arrival position).  The step makers of ``ffat_kernels``
keep the argsort path as their ``grouping="argsort"`` (the reference of
tests/test_grouping.py, and what a step falls back to by itself where the
counting ids would pass int32).
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

#: within-chunk width: within-rank costs (CHUNK-1) shifted compares per
#: lane, the cross-chunk prefix table costs (n/CHUNK)*nbuckets — 32 sits
#: at the measured CPU optimum for the 256-bucket digit below.
CHUNK = 32
#: radix base: buckets per counting pass (+1 padding bucket per pass).
DIGIT = 256


def dense_rank(ids, nbuckets: int):
    """Per-lane rank among equal ids in arrival order, plus bucket counts.

    ``rank[i]`` = number of earlier lanes with the same id; ``counts[b]`` =
    occurrences of id ``b``.  The O(n) core shared by the permutation below
    and the scatter-add fast path (``make_ffat_step`` with a declared-sum
    combiner, which needs each tuple's position within its key but never a
    sorted layout).  Returns ``(rank, counts, idsp, pos)`` where ``rank``,
    ``idsp`` and ``pos`` are chunk-padded to length ``Bp >= B`` (padding
    lanes rank 0.. in their own bucket past the real ones); callers slice
    ``[:B]``."""
    B = ids.shape[0]
    C = CHUNK
    Bp = ((B + C - 1) // C) * C
    # padding lanes count into a dedicated bucket after every real one
    nb = nbuckets + 1
    idsp = ids.astype(jnp.int32)
    if Bp != B:
        idsp = jnp.concatenate(
            [idsp, jnp.full(Bp - B, nbuckets, jnp.int32)])
    NB = Bp // C
    pos = jnp.arange(Bp, dtype=jnp.int32)
    lane = pos % C

    # 1. within-chunk rank among equal ids (arrival order): count equal
    # ids in the C-1 earlier lanes of the same chunk
    within = jnp.zeros(Bp, jnp.int32)
    for d in range(1, C):
        shifted = jnp.pad(idsp, (d, 0))[:Bp]
        within = within + ((idsp == shifted) & (lane >= d))

    # 2. per-chunk histograms + exclusive scan across chunks
    flat = (pos // C) * nb + idsp
    hist = jnp.zeros(NB * nb, jnp.int32).at[flat].add(1).reshape(NB, nb)
    cross = lax.associative_scan(jnp.add, hist, axis=0) - hist
    counts = jnp.sum(hist, axis=0)
    rank = within + cross.reshape(-1)[flat]
    return rank, counts[:nbuckets], idsp, pos


def _single_digit_order(ids, nbuckets: int):
    """Stable counting-sort permutation for ids in ``[0, nbuckets)``,
    ``nbuckets`` one digit wide.  Returns gather indices ``order`` with
    ``ids[order]`` sorted, ties in arrival order."""
    order, _ = _single_digit_order_counts(ids, nbuckets)
    return order


def _single_digit_order_counts(ids, nbuckets: int):
    """``_single_digit_order`` plus the ``[nbuckets]`` histogram of ids —
    the ``dense_rank`` byproduct callers would otherwise recompute with a
    second full-length scatter-add."""
    B = ids.shape[0]
    rank, counts, idsp, pos = dense_rank(ids, nbuckets)
    Bp = pos.shape[0]
    # padding lanes went to the bucket AFTER every real one; being the
    # last-arriving members of the last bucket they occupy the tail of
    # the permutation, so ``order[:B]`` contains exactly the real lanes
    allc = jnp.concatenate(
        [counts, jnp.asarray([Bp - B], jnp.int32)])
    start = lax.associative_scan(jnp.add, allc) - allc

    # 3. dest is a permutation of [0, Bp): invert by scattering iota
    dest = start[idsp] + rank
    order = jnp.zeros(Bp, jnp.int32).at[dest].set(pos, unique_indices=True)
    return order[:B], counts


def invert_perm(order):
    """Invert a permutation in O(n): ``inv[order[i]] = i`` via one scatter
    of iota — replaces the ``argsort(order)`` idiom (a full comparison
    sort of something already known to be a permutation)."""
    n = order.shape[0]
    return jnp.zeros(n, order.dtype).at[order].set(
        jnp.arange(n, dtype=order.dtype), unique_indices=True)


def auto_order(ids, nbuckets: int):
    """Stable grouping permutation with an automatic algorithm choice:
    the O(n) counting permutation while it needs at most two radix passes
    (bucket spaces up to ``DIGIT^2``), the comparison argsort beyond —
    at 3+ passes the counting constant catches the O(n log n) sort's.
    Bit-identical either way (both order by (id, arrival))."""
    if nbuckets <= DIGIT * DIGIT:
        return counting_order(ids, nbuckets)
    return jnp.argsort(ids, stable=True)


def order_and_hist(ids, nbuckets: int):
    """``auto_order`` plus the ``[nbuckets]`` histogram of ids.  On the
    single-counting-pass path the histogram is the ``dense_rank``
    byproduct — free; the radix and argsort paths pay one O(n)
    scatter-add (the per-digit passes count digit buckets, never the
    full id space, so there is nothing to reuse there)."""
    if nbuckets <= DIGIT + 1:
        return _single_digit_order_counts(ids, nbuckets)
    order = auto_order(ids, nbuckets)
    hist = jnp.zeros(nbuckets, jnp.int32).at[ids.astype(jnp.int32)].add(1)
    return order, hist


def counting_order(ids, nbuckets: int):
    """Stable grouping permutation over dense int ids in ``[0, nbuckets)``
    (out-of-range ids must already be clamped by the caller — the FFAT
    steps map invalid lanes to bucket ``nbuckets - 1``).

    Equivalent to ``jnp.argsort(ids, stable=True)`` for such ids, in O(n):
    single counting pass up to ``DIGIT + 1`` buckets, LSD radix over
    base-``DIGIT`` digits beyond (each pass stable, so the composition
    orders by the full id, then arrival)."""
    if nbuckets <= DIGIT + 1:
        return _single_digit_order(ids, nbuckets)
    ids = ids.astype(jnp.int32)
    order = None
    div = 1
    span = nbuckets
    while span > 1:
        cur = ids if order is None else ids[order]
        o = _single_digit_order((cur // div) % DIGIT, DIGIT)
        order = o if order is None else order[o]
        div *= DIGIT
        span = -(-span // DIGIT)
    return order
