"""Pure FFAT device-program builders (no operator-layer dependencies).

The segmented-scan / pane / window-firing programs shared by the single-chip
operator (``windows/ffat_tpu.py``) and the multi-chip sharded path
(``parallel/mesh.py``).  Kept free of ``ops``/``graph`` imports so the
distribution layer can use them without cycles.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from windflow_tpu.monitoring.recorder import phase
from windflow_tpu.utils.dtypes import cast_state_update
from windflow_tpu.windows.grouping import (auto_order, dense_rank,
                                           order_and_hist)


def _group_order(ids, nbuckets: int, grouping: str, pallas=None):
    """Stable grouping permutation: ``rank_scatter`` is the O(n) dense-key
    counting sort (grouping.py; beyond two radix passes — TB (key, pane)
    spaces past DIGIT^2 buckets — auto_order falls back to the sort, where
    the counting constant no longer wins), ``argsort`` the comparison-sort
    baseline.  Bit-identical either way (both order by (id, arrival)).

    ``pallas`` (a resolved :class:`windflow_tpu.kernels.PallasMode`)
    routes the counting grouping through the single-pass Pallas kernel
    where its gates hold (windflow_tpu/kernels) — same permutation,
    traced into the same program."""
    if grouping == "rank_scatter":
        if pallas is not None:
            from windflow_tpu import kernels as pk
            if pk.grouping_supported(int(ids.shape[0]), nbuckets):
                return pk.order_hist(ids, nbuckets, pallas.interpret)[0]
        return auto_order(ids, nbuckets)
    return jnp.argsort(ids, stable=True)


def _group_order_hist(ids, nbuckets: int, grouping: str, pallas=None):
    """``_group_order`` plus the ``[nbuckets]`` histogram of ids — on the
    single-counting-pass grouping the histogram is the ``dense_rank``
    byproduct, so the CB step's rank arithmetic costs no extra pass.
    On the Pallas path both come out of the one fused kernel."""
    if grouping == "rank_scatter":
        if pallas is not None:
            from windflow_tpu import kernels as pk
            if pk.grouping_supported(int(ids.shape[0]), nbuckets):
                return pk.order_hist(ids, nbuckets, pallas.interpret)
        return order_and_hist(ids, nbuckets)
    order = jnp.argsort(ids, stable=True)
    return order, jnp.zeros(nbuckets, jnp.int32) \
        .at[ids.astype(jnp.int32)].add(1)


def _seg_scan(comb, flags, values):
    """Inclusive segmented scan: within each flagged segment, fold ``comb``.
    ``values`` is a pytree of [B, ...] leaves; ``flags`` [B] marks segment
    starts."""
    def op(a, b):
        fa, va = a
        fb, vb = b
        combined = comb(va, vb)
        v = jax.tree.map(
            lambda c, nb: jnp.where(_b(fb, c), nb, c), combined, vb)
        return (fa | fb, v)

    _, scanned = jax.lax.associative_scan(op, (flags, values))
    return scanned


def _masked_reduce_last(comb, flags, values, axis):
    """Reduce ``values`` along ``axis`` with ``comb``, skipping entries whose
    flag is False; returns (any_flag, reduction).  Flag-aware monoid:
    associative, no identity needed."""
    fc = _flag_comb(comb)

    def op(a, b):
        return fc(*a, *b)

    f, v = jax.lax.associative_scan(op, (flags, values), axis=axis)
    take = lambda x: jax.lax.index_in_dim(x, x.shape[axis] - 1, axis,
                                          keepdims=False)
    return take(f), jax.tree.map(take, v)


def _b(mask, ref):
    """Broadcast a bool mask against a leaf with trailing dims."""
    return mask.reshape(mask.shape + (1,) * (ref.ndim - mask.ndim))


def _shift_leaf(a, k: int, axis: int, fill=0):
    """Shift one leaf along ``axis`` by ``k`` toward higher indices,
    filling the vacated slots with ``fill`` (0/False by default; the
    declared-monoid fold passes the monoid identity)."""
    pad = [(0, 0)] * a.ndim
    pad[axis] = (k, 0)
    s = [slice(None)] * a.ndim
    s[axis] = slice(0, a.shape[axis])
    return jnp.pad(a, pad, constant_values=fill)[tuple(s)]


def _shift_right(flags, values, k: int, axis: int):
    """Shift along ``axis`` by ``k`` positions (toward higher indices),
    filling vacated slots with invalid entries (bool pads False)."""
    if k == 0:
        return flags, values
    return (_shift_leaf(flags, k, axis),
            jax.tree.map(lambda a: _shift_leaf(a, k, axis), values))


def _flag_comb(comb):
    """Flag-aware combine: invalid operands are skipped (associative monoid
    without needing an identity element)."""
    def op(fa, va, fb, vb):
        both = comb(va, vb)
        v = jax.tree.map(
            lambda c, xa, xb: jnp.where(_b(fb, c),
                                        jnp.where(_b(fa, c), c, xb), xa),
            both, va, vb)
        return fa | fb, v
    return op


def _sliding_reduce(comb, flags, values, R: int, axis: int):
    """``out[i] = fold(comb)`` over the valid entries among positions
    ``[i-R+1, i]`` along ``axis``.  Dilated doubling: ``log2(R)`` combines
    build power-of-two window aggregates, then the binary decomposition of
    ``R`` stitches them — the log-depth trick of the reference's FlatFAT
    levels (``flatfat_gpu.hpp:60-139``) expressed as shifts instead of a
    tree, so nothing larger than the pane sequence is ever materialized.
    A cumsum-difference fold (sums only) was tried and lost to this one at
    the pane counts in use (R = 8): it stays out."""
    op = _flag_comb(comb)
    # pow2[j] aggregates windows of width 2^j ending at each position
    pow2 = [(flags, values)]
    width = 1
    while width * 2 <= R:
        f, v = pow2[-1]
        fs, vs = _shift_right(f, v, width, axis)
        pow2.append(op(fs, vs, f, v))
        width *= 2
    # stitch R = sum of powers, walking from the window's newest end
    # backward; each added chunk sits *before* the accumulated suffix, so
    # it is the left operand of comb (order matters for non-commutative
    # combiners)
    res = None
    offset = 0
    for j in range(len(pow2) - 1, -1, -1):
        w = 1 << j
        if R & w:
            f, v = _shift_right(*pow2[j], offset, axis)
            res = (f, v) if res is None else op(f, v, *res)
            offset += w
    return res


#: declared combiner monoids (withMonoidCombiner): one source of truth
#: mapping kind -> (``.at[]`` scatter method, elementwise combine, mesh
#: reduce collective); the contract is ``comb(x, identity) == x``
#: leafwise (identity per dtype from :func:`_monoid_identity`), so
#: identity-filled slots are absorbed without a has-mask.  A new kind
#: goes here + ``_monoid_identity``.
_MONOID_OPS = {
    "sum": ("add", jnp.add, jax.lax.psum),
    "max": ("max", jnp.maximum, jax.lax.pmax),
    "min": ("min", jnp.minimum, jax.lax.pmin),
}
_MONOID_KINDS = tuple(_MONOID_OPS)


def monoid_collective(kind: str):
    """The mesh reduce collective (psum/pmax/pmin) for a monoid kind."""
    return _MONOID_OPS[kind][2]


def resolve_monoid(sum_like: bool, monoid):
    """Normalize the legacy ``sum_like`` flag into a monoid kind and
    validate it — the single gatekeeper shared by both kernel builders
    and the operator layer."""
    if sum_like and monoid is None:
        monoid = "sum"
    if monoid is not None and monoid not in _MONOID_OPS:
        raise ValueError(f"unknown monoid {monoid!r}; "
                         f"expected one of {_MONOID_KINDS}")
    return monoid


def _monoid_identity(kind: str, dtype):
    """The absorbing identity of a declared monoid for one leaf dtype."""
    dt = jnp.dtype(dtype)
    if kind == "sum":
        return jnp.zeros((), dt)
    if dt == jnp.bool_:
        # max over bool == any (ident False); min == all (ident True)
        return jnp.asarray(kind == "min", bool)
    if jnp.issubdtype(dt, jnp.floating):
        return jnp.asarray(-jnp.inf if kind == "max" else jnp.inf, dt)
    info = jnp.iinfo(dt)
    return jnp.asarray(info.min if kind == "max" else info.max, dt)


def _monoid_scatter(buf_at, kind: str):
    """The scatter-combine method of ``x.at[idx]`` for a monoid kind."""
    return getattr(buf_at, _MONOID_OPS[kind][0])


#: Dense placement (time-based step, declared monoid): the batch's
#: ``[K, NP]`` partial grid is ONE contraction of two one-hot operands
#: over the lanes on the MXU instead of a scatter-add per lane.  The
#: contraction's time grows with the grid (3.6 ns a cell and column at
#: 262144 lanes on a v5e, over a floor of 0.4 ms), a scatter-add's with
#: the lanes alone (1.9 ms in 32 bits), so a scatter is replaced while
#: the ``K * NP`` cells times the columns that replace it stay under
#: this: where the two forms met (PERF.md section 6, PR 29).
DENSE_PLACE_MAX_CELLS = 1 << 19
#: ... and a 64-bit scatter-add costs ten 32-bit ones there (18.5 ms),
#: so its columns may cover ten times the grid
_WIDE_SCATTER_COST = 10
#: either one-hot operand may be materialized in HBM (bfloat16, one row
#: a lane): together they stay under this
_DENSE_MAX_OPERAND_BYTES = 1 << 30
#: lanes whose 0/1 products the f32 accumulator still counts exactly
_DENSE_EXACT_LANES = 1 << 24
#: Narrow placement (time-based step, declared monoid, a grid past the
#: contraction): a batch whose lanes fall within this many panes of its
#: oldest scatters into ``[K + 1, NARROW_PLACE_PANES]`` targets and is
#: merged into those columns of the ring alone; a wider one (an idle
#: gap, heavy disorder, tiny panes) scatters into the whole ``[K + 1,
#: NP]`` grid as before, counted in ``n_wide``.  At 655 360 keys x 66
#: panes and 262144 lanes on a v5e (PERF.md section 6, PR 31), six
#: 32-bit scatters with their merge: 18.1 ms into 2 columns, 18.2 into
#: 4, 22.5 into 8 (a result's column costs 0.18 ms to lay out), where
#: the whole-ring int64 placement takes 64.4: 4 is free, 8 is not.
NARROW_PLACE_PANES = 4


def narrow_limb_bits(B: int) -> int:
    """Widest limb, in bits, whose sum over ``B`` lanes a uint32
    scatter-add cannot wrap: ``(2^b - 1) * B < 2^32`` (14 bits, five
    limbs of an int64, at 262144 lanes).  0: no such limb."""
    return ((2 ** 32 - 1) // B + 1).bit_length() - 1


def _rides_limbs(monoid: str, leaf) -> bool:
    """A 64-bit integer ``sum`` leaf with no trailing dims: the one kind
    of leaf whose scatter-add is worth cutting into 32-bit limbs (a
    64-bit scatter-add costs 10-13 32-bit ones on a v5e)."""
    dt = jnp.dtype(leaf.dtype)
    return monoid == "sum" and leaf.ndim == 1 \
        and jnp.issubdtype(dt, jnp.integer) and dt.itemsize == 8


def _limb_bits(B: int) -> int:
    """Widest limb, in bits, that dense placement sums exactly over
    ``B`` lanes: a limb is exact in bfloat16 up to 8 bits, and a cell's
    sum of limbs must stay under the f32 accumulator's 2^24
    (``(2^b - 1) * B <= 2^24``).  0: no such limb, not even the count."""
    if B > _DENSE_EXACT_LANES:
        return 0
    return min(8, (_DENSE_EXACT_LANES // B + 1).bit_length() - 1)


def tb_placement(monoid: Optional[str], leaves, K: int, NP: int,
                 B: int) -> dict:
    """The static plan of the time-based step's placement, from what its
    builder can observe: the monoid kind, each lift leaf's dtype and
    per-lane shape (``leaves``: anything with ``.shape`` / ``.dtype`` of
    ONE aggregate), and the sizes.  ``limbs[i]`` is the number of limb
    columns leaf ``i`` rides the contraction with (0: it keeps the
    scatter-combine: float sums keep their rounding order, ``max`` /
    ``min`` have no limb form, and past :data:`DENSE_PLACE_MAX_CELLS`
    the columns cost more than the scatter they replace); ``count`` says
    whether ``partial_has`` comes from the contraction's count column;
    ``placement`` is ``"dense"`` when no scatter is left.  What
    ``"scatter"`` leaves is sized by the step itself, batch by batch:
    into the :data:`NARROW_PLACE_PANES` panes the batch spans (a 64-bit
    integer sum as uint32 limbs, :func:`narrow_limb_bits`) or, past that
    span, into the whole ring (``n_wide`` counts those steps)."""
    b = _limb_bits(B)
    fits = monoid is not None and b > 0 \
        and 2 * B * (K + NP) <= _DENSE_MAX_OPERAND_BYTES
    count = fits and K * NP <= DENSE_PLACE_MAX_CELLS
    limbs = []
    for leaf in leaves:
        dt = jnp.dtype(leaf.dtype)
        n = 0
        if count and monoid == "sum" and tuple(leaf.shape) == () \
                and jnp.issubdtype(dt, jnp.integer):
            n = -(-dt.itemsize * 8 // b)
            if K * NP * n > DENSE_PLACE_MAX_CELLS * (
                    _WIDE_SCATTER_COST if dt.itemsize == 8 else 1):
                n = 0
        limbs.append(n)
    return {"placement": "dense" if count and all(limbs) else "scatter",
            "limbs": limbs, "count": count, "limb_bits": b}


def _to_limbs(leaf, n: int, b: int, acc=jnp.bfloat16):
    """``[B, n]`` of ``acc``: the two's-complement bits of the integer
    lane ``leaf`` cut into ``n`` limbs of ``b`` bits, lowest first
    (``acc``: what the limbs are summed in: bfloat16 operands of the
    contraction, uint32 updates of the narrow scatter)."""
    udt = jnp.dtype(f"uint{leaf.dtype.itemsize * 8}")
    u = jax.lax.bitcast_convert_type(leaf, udt)
    shifts = jnp.arange(n, dtype=udt) * b
    return ((u[:, None] >> shifts[None, :]) & udt.type((1 << b) - 1)) \
        .astype(jnp.int32).astype(acc)


def _from_limbs(part, b: int, dtype):
    """Inverse of :func:`_to_limbs` over per-cell limb SUMS ``part``
    ``[K, n, NP]`` (f32 holding integers under 2^24, or uint32): each is
    shifted back into place in the unsigned twin of ``dtype``, where the
    total wraps exactly as a scatter-add in ``dtype`` does."""
    udt = jnp.dtype(f"uint{jnp.dtype(dtype).itemsize * 8}")
    shifts = jnp.arange(part.shape[1], dtype=udt) * b
    if jnp.issubdtype(part.dtype, jnp.floating):
        part = part.astype(jnp.int32)
    return jax.lax.bitcast_convert_type(
        jnp.sum(part.astype(udt) << shifts[None, :, None],
                axis=1, dtype=udt), dtype)


def _dense_place(keys, cols, ok, K: int, NP: int, leaves, limbs, b: int):
    """Per ``(key, col)`` cell of the ``[K, NP]`` grid: the int32 count
    of ``ok`` lanes and, for every leaf with ``limbs[i] > 0``, the
    wrap-around integer sum of its values, bit-identical to a
    scatter-add (None for the others).  ``onehot(key) & ok`` ``[K, B]``
    is contracted over the lanes with ``onehot(col)`` ``[B, NP]`` scaled
    by each column of ``[B, 1 + sum(limbs)]``: the count's column of
    ones, then every leaf's limbs.  All operands are small integers in
    bfloat16 and every partial sum stays under 2^24 in the f32
    accumulator (:func:`_limb_bits`), so nothing rounds."""
    bf16 = jnp.bfloat16
    key_hot = ((keys[None, :] == jnp.arange(K, dtype=jnp.int32)[:, None])
               & ok[None, :]).astype(bf16)
    pane_hot = (cols[:, None]
                == jnp.arange(NP, dtype=jnp.int32)[None, :]).astype(bf16)
    columns = jnp.concatenate(
        [jnp.ones((keys.shape[0], 1), bf16)]
        + [_to_limbs(a, n, b) for a, n in zip(leaves, limbs) if n], axis=1)
    # the right operand stays 3-D: XLA:TPU lowers the column axis as a
    # convolution window and never materializes [B, columns * NP]
    # (flattened by hand it does, and compiles for minutes)
    grid = jax.lax.dot_general(
        key_hot, columns[:, :, None] * pane_hot[:, None, :],
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)            # [K, columns, NP]
    sums, at = [], 1
    for leaf, n in zip(leaves, limbs):
        sums.append(_from_limbs(grid[:, at:at + n], b, leaf.dtype)
                    if n else None)
        at += n
    return grid[:, 0].astype(jnp.int32), sums


def _monoid_fill(kind: str, flags, values):
    """Replace invalid entries with the monoid identity, leafwise."""
    return jax.tree.map(
        lambda a: jnp.where(_b(flags, a), a,
                            _monoid_identity(kind, a.dtype)), values)


def _sliding_reduce_plain(comb, flags, values, R: int, axis: int,
                          monoid: str):
    """Flagless dilated sliding fold for declared-monoid combiners
    (withSumCombiner / withMonoidCombiner): invalid entries are filled
    with the monoid identity once, then the log2(R) doubling runs on
    values alone — half the operand traffic of the flag-aware fold.
    Only valid when ``comb(x, identity) == x`` on every leaf."""
    zeroed = _monoid_fill(monoid, flags, values)

    # identity-fill shift: the vacated slots hold the combiner's identity
    def zshift(v, k):
        if k == 0:
            return v
        return jax.tree.map(
            lambda a: _shift_leaf(
                a, k, axis, fill=_monoid_identity(monoid, a.dtype)), v)

    pow2 = [zeroed]
    width = 1
    while width * 2 <= R:
        v = pow2[-1]
        pow2.append(comb(zshift(v, width), v))
        width *= 2
    res = None
    offset = 0
    for j in range(len(pow2) - 1, -1, -1):
        w = 1 << j
        if R & w:
            v = zshift(pow2[j], offset)
            res = v if res is None else comb(v, res)
            offset += w
    return res


def make_ffat_step(capacity: int, K: int, P: int, R: int, D: int,
                   lift: Callable, comb: Callable,
                   key_fn: Optional[Callable],
                   key_base_fn: Optional[Callable[[], Any]] = None,
                   sum_like: bool = False, grouping: str = "rank_scatter",
                   monoid: Optional[str] = None, pallas=None):
    """Build the (un-jitted) FFAT per-batch program.

    Pure-function form of the operator step so the multi-chip layer
    (``parallel/mesh.py``) can trace it *inside* ``shard_map`` with a per-shard
    key base: when ``key_base_fn`` is given, raw keys are rebased by its traced
    value, so a chip owning keys ``[base, base+K)`` sees them as ``[0, K)`` and
    out-of-range keys are masked out (the dense-key sharding answer to the
    reference's per-key device state, ``ffat_replica_gpu.hpp:438-514``).

    The output batch is COMPACTED on device: the worst case for ONE key is
    the whole batch (``capacity/(P*D)`` windows), but the *total* windows a
    batch can fire across all keys has the same bound (plus a per-key
    partial), so the egress batch is ``MAXO ~ capacity/(P*D) + 2K`` rows
    where a dense per-key grid would hold millions.  Firing is a per-key
    prefix of window ids, so compaction is pure index arithmetic — a K-long
    running sum + searchsorted — never a dense-grid scatter (a dense-grid
    device→host copy per step would dominate any end-to-end pipeline; the
    reference's ``numWinsPerBatch`` output buffer is likewise sized to
    fired windows, not the worst case, ``flatfat_gpu.hpp:60-139``).

    Declared-monoid fast path: ``monoid`` ("sum" | "max" | "min"; the
    legacy ``sum_like=True`` means ``monoid="sum"``) declares the
    combiner a leafwise commutative monoid with an absorbing identity
    (the "sum" contract is the one the mesh reduce commits to when it
    rides ``lax.psum``, parallel/mesh.py), so with ``rank_scatter``
    grouping the step skips the permutation entirely — each lane's
    within-key rank (grouping.dense_rank) gives its pane cell and lifts
    scatter-COMBINE (add/max/min) straight into the [K, NP1] grid.  No
    sorted layout, no segmented scan, no run-end detection.  The declared
    op is commutative, so only float rounding order differs from the
    sequential fold (exactly the tolerance psum already implies; max/min
    are idempotent — bit-identical either way).

    ``pallas`` (a resolved :class:`windflow_tpu.kernels.PallasMode`, or
    None for the pure-lax program): the grouping/rank pass and the
    declared-monoid sliding fold trace their Pallas kernel bodies into
    this SAME program where the kernel gates hold — no extra dispatch,
    record-for-record identical output (tests/test_pallas_kernels.py)."""
    monoid = resolve_monoid(sum_like, monoid)
    NP1 = capacity // P + 2           # pane cells incl. continuation cell
    # total fired across all keys: sum_k panes_k/D + per-key partials
    MAXO = capacity // (P * D) + 2 * K + 8
    # dense_rank runs one counting pass over K+1 buckets whatever K is;
    # the gate only bounds its [capacity/CHUNK, K+1] chunk-histogram
    # (int32) to a sane size — 4096 keys at the TPU bench capacity is a
    # ~134 MB table.  Beyond it the permutation path still applies.
    scatter_combine = (monoid is not None and grouping == "rank_scatter"
                       and K <= 4096)

    def step(state, payload, ts, valid):
        B = capacity
        kb = key_base_fn() if key_base_fn is not None else None
        with phase("wf.fn"):
            keys = jax.vmap(key_fn)(payload).astype(jnp.int32) \
                if key_fn is not None else jnp.zeros(B, jnp.int32)
        if kb is not None:
            keys = keys - jnp.int32(kb)
        ok = valid & (keys >= 0) & (keys < K)
        skey_for_sort = jnp.where(ok, keys, K)

        if scatter_combine:
            with phase("wf.group"):
                use_pk = False
                if pallas is not None:
                    from windflow_tpu import kernels as pk
                    use_pk = pk.grouping_supported(B, K + 1)
                if use_pk:
                    # fused Pallas grouping: rank + histogram in one pass
                    # (bit-identical to dense_rank — same (id, arrival)
                    # counting), traced into this same program
                    _, rank_u, hist_pk = pk.grouping_rank_hist(
                        skey_for_sort, K + 1, pallas.interpret)
                    n_k = hist_pk[:K]
                else:
                    rank_p, counts, _, _ = dense_rank(skey_for_sort, K + 1)
                    rank_u = rank_p[:B]
                    n_k = counts[:K]
            with phase("wf.fn"):
                lifts = jax.vmap(lift)(payload)
            with phase("wf.place"):
                fill0_u = state["cur_fill"][jnp.minimum(skey_for_sort, K - 1)]
                col_u = jnp.where(
                    ok, ((fill0_u + rank_u) // P).astype(jnp.int32), 0)

                def scat(leaf):
                    ident = _monoid_identity(monoid, leaf.dtype)
                    buf = jnp.full((K + 1, NP1) + leaf.shape[1:], ident,
                                   leaf.dtype)
                    return _monoid_scatter(
                        buf.at[skey_for_sort, col_u], monoid)(
                        jnp.where(_b(ok, leaf), leaf, ident))[:K]
                cells = jax.tree.map(scat, lifts)

                # carried partial pane merges by the declared op (empty cells
                # hold the monoid identity, so no has-mask is needed)
                def merge0(cur_leaf, cell_leaf):
                    ident = _monoid_identity(monoid, cell_leaf.dtype)
                    upd = jnp.where(_b(state["cur_valid"], cur_leaf),
                                    cur_leaf, ident)
                    return _monoid_scatter(cell_leaf.at[:, 0], monoid)(
                        cast_state_update(upd, cell_leaf.dtype,
                                          "FFAT pane merge"))
                cells = jax.tree.map(merge0, state["cur"], cells)
        else:
            # after a STABLE grouping by dense key, bucket b's lanes
            # occupy [start_b, start_b + hist_b), so the within-key rank
            # is index arithmetic off a histogram of the keys — no
            # [B]-length scan, no segment_sum (r5 TPU profile: the rank
            # scan was the dominant standalone stage, 0.086 ms of a
            # 0.100 ms step; a [K+1] cumsum replaces it).  The histogram
            # itself is the counting permutation's dense_rank byproduct
            # on the single-pass path — free.
            with phase("wf.group"):
                order, hist = _group_order_hist(skey_for_sort, K + 1,
                                                grouping, pallas)
                sk = skey_for_sort[order]
            with phase("wf.fn"):
                lifted = jax.vmap(lift)(payload)
            with phase("wf.group"):
                slift = jax.tree.map(lambda a: a[order], lifted)
            with phase("wf.place"):
                pos = jnp.arange(B)
                bucket_start = jnp.cumsum(hist) - hist        # exclusive
                rank = pos - bucket_start[sk]
                starts = rank == 0

                n_k = hist[:K]      # buckets < K hold exactly the ok lanes
                fill0 = state["cur_fill"][jnp.minimum(sk, K - 1)]
                pane_rel = ((fill0 + rank) // P).astype(jnp.int32)

                # pane partials: segmented scan over (key, pane) runs
                pane_starts = starts | jnp.concatenate(
                    [jnp.array([True]), pane_rel[1:] != pane_rel[:-1]])
                scanned = _seg_scan(comb, pane_starts, slift)
                ends = jnp.concatenate(
                    [(sk[1:] != sk[:-1]) | (pane_rel[1:] != pane_rel[:-1]),
                     jnp.array([True])])
                # scatter segment-end partials into dense [K+1, NP1] cells
                row = jnp.where(ends, sk, K)
                col = jnp.where(ends, pane_rel, 0)

                def scat(leaf):
                    buf = jnp.zeros((K + 1, NP1) + leaf.shape[1:], leaf.dtype)
                    return buf.at[row, col].set(
                        jnp.where(_b(ends, leaf), leaf, 0))[:K]
                cells = jax.tree.map(scat, scanned)
                cell_has = jnp.zeros((K + 1, NP1), bool) \
                    .at[row, col].set(ends)[:K]

                # merge continuation cell with the carried partial pane; comb
                # is a WHOLE-PYTREE combiner (cross-leaf combines are legal —
                # matrix products etc.), so it runs once on the tree, not per
                # leaf
                cell0 = jax.tree.map(lambda cl: cl[:, 0], cells)
                both0 = comb(state["cur"], cell0)

                def merge0(cur_leaf, cell_leaf, both_leaf):
                    use_cur = state["cur_valid"]
                    use_cell = cell_has[:, 0]
                    v = jnp.where(_b(use_cur & use_cell, both_leaf), both_leaf,
                                  jnp.where(_b(use_cur, both_leaf), cur_leaf,
                                            cell_leaf[:, 0]))
                    # carried state may be wider than the batch-derived cells
                    # (e.g. an f64 agg_spec under x64 vs f32 lifts); the cell
                    # dtype is authoritative — a promoting scatter errors in
                    # future JAX, and a kind-crossing cast is state corruption
                    # (utils.dtypes)
                    return cell_leaf.at[:, 0].set(
                        cast_state_update(v, cell_leaf.dtype,
                                          "FFAT pane merge"))
                cells = jax.tree.map(merge0, state["cur"], cells, both0)

        with phase("wf.fire"):
            m_k = ((state["cur_fill"] + n_k) // P).astype(jnp.int32)
            new_fill = ((state["cur_fill"] + n_k) % P).astype(jnp.int32)

            # full pane sequence: carry (R-1 trailing) + this batch's panes
            full = jax.tree.map(
                lambda c, p: jnp.concatenate([c, p], axis=1),
                state["carry"], cells)
            col_ix = jnp.arange(NP1)[None, :]
            pane_valid = col_ix < m_k[:, None]
            full_valid = jnp.concatenate([state["carry_valid"], pane_valid],
                                         axis=1)

            # fire windows: key k fires ends e = win_next[k] + j*D while
            # e <= done[k] — a per-key PREFIX, so no dense [K, MW] firing
            # grid is ever needed: per-key counts + a searchsorted over
            # their running sum enumerate the fired (key, window) pairs
            # directly in compacted order.  The sliding fold (log2(R)
            # dilated combines over the [K, R-1+NP1] pane sequence) stays
            # dense; window values are gathered only at the MAXO compacted
            # output slots.
            done = state["pane_base"] + m_k
            if monoid is not None:
                # declared identity-absorbing: the flag lane of the fold
                # is pure overhead here (the CB step never reads the flag
                # output — fired windows always contain data)
                use_fold = False
                if pallas is not None:
                    from windflow_tpu import kernels as pk
                    use_fold = pk.fold_supported(full, R, monoid,
                                                 pallas.interpret)
                if use_fold:
                    # Pallas pane combine: identity fill + blocked sliding
                    # fold in one VMEM-resident kernel (MXU banded matmul
                    # for f32 sums, the lax fold's own doubling schedule
                    # on the VPU otherwise — module docstring)
                    swin = pk.sliding_fold(full, full_valid, R, monoid,
                                           pallas.interpret)
                else:
                    swin = _sliding_reduce_plain(comb, full_valid, full, R,
                                                 axis=1, monoid=monoid)
            else:
                _, swin = _sliding_reduce(comb, full_valid, full, R, axis=1)

            n_fired = jnp.maximum(
                jnp.int64(0), (done - state["win_next"]) // D + 1)
            new_win_next = state["win_next"] + n_fired * D

        with phase("wf.ring"):
            # new carry: panes [pane_base+m_k-(R-1), pane_base+m_k)
            cidx = m_k[:, None] + jnp.arange(R - 1)[None, :]   # [K, R-1]
            def carry_leaf(a):
                idx = cidx.reshape(K, R - 1, *([1] * (a.ndim - 2)))
                idx = jnp.broadcast_to(idx, (K, R - 1) + a.shape[2:])
                return jnp.take_along_axis(a, idx, axis=1)
            new_carry = jax.tree.map(carry_leaf, full)
            new_carry_valid = jnp.take_along_axis(full_valid, cidx, axis=1)

            def cur_leaf(cell_leaf):
                idx = m_k.reshape(K, 1, *([1] * (cell_leaf.ndim - 2)))
                idx = jnp.broadcast_to(idx, (K, 1) + cell_leaf.shape[2:])
                return jnp.take_along_axis(cell_leaf, idx, axis=1)[:, 0]
            new_cur = jax.tree.map(cur_leaf, cells)
            new_cur_valid = new_fill > 0

        new_state = {
            "carry": new_carry,
            "carry_valid": new_carry_valid,
            "cur": new_cur,
            "cur_valid": new_cur_valid,
            "cur_fill": new_fill,
            "pane_base": done,
            "win_next": new_win_next,
        }

        with phase("wf.fire"):
            # output batch (see docstring): compacted slot i belongs to the
            # key whose fired-count running sum first exceeds i; everything
            # else is per-slot arithmetic + one gather from the sliding fold.
            # The running sum is int32 and widened after: a key fires at
            # most capacity/(P*D) + 1 windows per batch and the total is
            # bounded by MAXO.  XLA:TPU emulates an int64 cumsum as a
            # variadic u32-pair reduce-window, which it cannot place in
            # scoped VMEM once the step sits in a lax.scan body (megastep):
            # "RESOURCE_EXHAUSTED ... reduce-window (u32[8,128], u32[8,128])
            # ... Scoped allocation 19.07M, limit 16.00M" at every capacity.
            fired32 = n_fired.astype(jnp.int32)
            offs = jnp.cumsum(fired32)                         # [K]
            n_out = offs[K - 1]
            i_slot = jnp.arange(MAXO, dtype=jnp.int32)
            k_out = jnp.searchsorted(offs, i_slot, side="right") \
                .astype(jnp.int32)                             # [MAXO]
            k_c = jnp.minimum(k_out, K - 1)
            j_out = (i_slot - (offs[k_c] - fired32[k_c])) \
                .astype(jnp.int64)                             # rank in key
            e_out = state["win_next"][k_c] + j_out * D
            # window value: sliding-fold cell at the window's end pane
            widx_out = jnp.clip(
                (e_out - state["pane_base"][k_c] + (R - 2))
                .astype(jnp.int32), 0, R - 1 + NP1 - 1)        # [MAXO]
            wvals_out = jax.tree.map(lambda a: a[k_c, widx_out], swin)
            out = {
                "key": k_c + (jnp.int32(kb) if kb is not None else 0),
                "wid": (e_out - R) // D,
                "value": wvals_out,
            }
            out_valid = i_slot < n_out
            batch_ts = jnp.max(jnp.where(valid, ts, 0))
            out_ts = jnp.where(out_valid, batch_ts, 0)
        return new_state, out, out_valid, out_ts

    return step


def make_ffat_flush(K: int, P: int, R: int, D: int, comb: Callable,
                    key_base_fn: Optional[Callable[[], Any]] = None):
    """Build the (un-jitted) CB EOS flush: fire every remaining partial
    window from the carried pane history (reference EOS flush of open
    windows).  Pure-function form so the mesh layer can trace it inside
    ``shard_map`` with a per-shard key base — a plain ``jit`` over the
    key-sharded state lets XLA choose the OUTPUT layout, and each
    process's sink would read whichever key rows XLA happened to place
    locally (found by the two-process graph test)."""
    MWF = R // D + 2

    @phase("wf.fire")
    def flush(state):
        kb = key_base_fn() if key_base_fn is not None else None
        # total panes including the partial pane
        has_cur = state["cur_valid"]
        total = state["pane_base"] + has_cur.astype(jnp.int64)
        # available pane history: carry (R-1) + cur  -> [K, R]
        hist = jax.tree.map(
            lambda c, cur: jnp.concatenate([c, cur[:, None]], axis=1),
            state["carry"], state["cur"])
        hist_valid = jnp.concatenate(
            [state["carry_valid"], has_cur[:, None]], axis=1)
        # hist column i holds pane (pane_base - (R-1) + i)
        j = jnp.arange(MWF, dtype=jnp.int64)
        e = state["win_next"][:, None] + j[None, :] * D
        start = e - R
        fire = start < total[:, None]
        # gather window panes from hist: local = pane - pane_base + R-1
        lidx = (start[:, :, None] + jnp.arange(R)[None, None, :]
                - state["pane_base"][:, None, None] + (R - 1))
        inb = (lidx >= 0) & (lidx < R)
        lidx_c = jnp.clip(lidx, 0, R - 1).astype(jnp.int32)
        pane_ok = jnp.take_along_axis(
            jnp.broadcast_to(hist_valid[:, None], (K, MWF, R)),
            lidx_c, axis=2) & inb
        # panes must also be < total (cur counts once)
        pane_abs = start[:, :, None] + jnp.arange(R)[None, None, :]
        pane_ok = pane_ok & (pane_abs < total[:, None, None]) \
            & (pane_abs >= 0)

        def gather_leaf(a):
            expanded = jnp.broadcast_to(a[:, None], (K, MWF) + a.shape[1:])
            idx = lidx_c.reshape(K, MWF, R, *([1] * (a.ndim - 2)))
            idx = jnp.broadcast_to(idx, (K, MWF, R) + a.shape[2:])
            return jnp.take_along_axis(expanded, idx, axis=2)
        wpanes = jax.tree.map(gather_leaf, hist)
        any_ok, wvals = _masked_reduce_last(comb, pane_ok, wpanes, axis=2)
        fired = fire & any_ok
        wid = (e - R) // D
        out = {
            "key": (jnp.broadcast_to(
                jnp.arange(K, dtype=jnp.int32)[:, None], (K, MWF))
                + (jnp.int32(kb) if kb is not None else 0)).reshape(-1),
            "wid": wid.reshape(-1),
            "value": jax.tree.map(
                lambda a: a.reshape((K * MWF,) + a.shape[2:]), wvals),
        }
        ts = jnp.zeros((K * MWF,), jnp.int64)
        return out, fired.reshape(-1), ts

    return flush


def tb_out_capacity(capacity: int, K: int, R: int, D: int, NP: int) -> int:
    """Lanes of the batch one time-based step hands on, from its static
    shapes alone.  A step makes three fire passes over at most
    ``NP // D + 2`` windows each, so the whole grid of what it could
    fire is ``K * 3 * (NP // D + 2)`` lanes; but a tuple lies in at most
    ``ceil(R / D)`` windows, so the rows a stream fires are at most
    ``ceil(R / D)`` a tuple, and one window holds at most ``K``.  A
    batch of ``K + capacity * ceil(R / D)`` lanes therefore always takes
    one more whole window while fewer than a batch's worth of rows are
    in it: the step keeps up with any stream it is fed and its output
    does not grow with the ring.  The smaller of the two is used; where
    that is the second, the fired rows are compacted on the device and
    windows that do not fit wait for the next pass or step."""
    return min(K * 3 * (NP // D + 2), K + capacity * (-(-R // D)))


#: the scalar leaves of the time-based state made below (shape () on one
#: chip and in per-replica states; on a mesh one lane a key shard,
#: parallel/mesh.py), which a restore onto another shard shape re-lanes
#: (durability/rebucket.py).  The one list: a counter added to the state
#: and not here fails tests/test_tb_ring_advance.py at once.
TB_SCALARS = ("base", "win_next", "max_seen", "n_late", "n_evicted",
              "n_win_dropped", "n_wide", "n_ring_advances")
#: of them, the clocks that must AGREE across merged shards (the ring
#: alignment invariants) ...
TB_ALIGNED = ("base", "win_next")
#: ... and the counters, which sum (``max_seen`` merges by max; a blob
#: from before ``n_wide`` or ``n_ring_advances`` lacks it)
TB_COUNTERS = ("n_late", "n_evicted", "n_win_dropped", "n_wide",
               "n_ring_advances")


def make_ffat_tb_state(agg_spec, K: int, NP: int):
    """Dense pane-ring state for time-based FFAT: column ``i`` of ``cells``
    holds the aggregate of time pane ``base + i`` (pane = ts // P_usec) for
    each key.  All keys share the pane clock, so ``base``/``win_next`` are
    scalars — unlike the count-based state, no per-key fill tracking is
    needed (the TPU re-design of the reference's TB quantum panes,
    ``ffat_replica_gpu.hpp:92-216``)."""
    zeros = lambda shape: jax.tree.map(
        lambda s: jnp.zeros(shape + s.shape, s.dtype), agg_spec)
    return {
        "cells": zeros((K, NP)),
        "cell_valid": jnp.zeros((K, NP), bool),
        "base": jnp.zeros((), jnp.int64),      # pane index of column 0
        "win_next": jnp.zeros((), jnp.int64),  # next unfired window id
        # newest data pane ever placed: windows starting beyond it can never
        # emit, so firing never advances past it (bounds EOS flush loops)
        "max_seen": jnp.full((), -(1 << 60), jnp.int64),
        # per-key overflow taint: one past the newest DATA pane evicted by a
        # capacity roll before its windows fired; windows starting below it
        # lost data (the drop-window overflow policy suppresses them)
        "horizon": jnp.full((K,), -(1 << 60), jnp.int64),
        "n_late": jnp.zeros((), jnp.int64),    # dropped late tuples
        "n_evicted": jnp.zeros((), jnp.int64),  # pane cells lost to overflow
        "n_win_dropped": jnp.zeros((), jnp.int64),  # windows suppressed
        # steps whose batch spanned more panes than a narrow placement
        # holds, and scattered into the whole ring (NARROW_PLACE_PANES)
        "n_wide": jnp.zeros((), jnp.int64),
        # steps in which the ring advanced (fired windows freed panes, or
        # the capacity roll made room); every other step makes no pass
        # over the ring
        "n_ring_advances": jnp.zeros((), jnp.int64),
    }


def make_ffat_tb_step(capacity: int, K: int, P_usec: int, R: int, D: int,
                      NP: int, lift: Callable, comb: Callable,
                      key_fn: Optional[Callable],
                      key_base_fn: Optional[Callable[[], Any]] = None,
                      drop_tainted: bool = False,
                      grouping: str = "rank_scatter",
                      sum_like: bool = False,
                      monoid: Optional[str] = None, pallas=None):
    """Time-based FFAT per-batch program.

    Window ``w`` covers panes ``[w*D, w*D + R)`` — times
    ``[w*slide, w*slide + win)`` — and fires once the (lateness-adjusted)
    watermark passes the window end; the host passes ``wm_adj`` per batch.
    The ring holds ``NP`` panes.

    The step fires in passes around placement so a watermark/time jump
    (an idle gap in the stream) cannot evict fireable windows:

    * pass A, *before* making room for the batch, fires windows complete
      under ``min(wm, oldest batch pane)`` — the frontier below which no
      tuple of this batch (nor, by the watermark contract, any future one)
      can fall, so those windows' data is fully in the ring already.  It
      runs TWICE: one pass only fires windows whose ends are inside the
      ring, and with a lagging watermark the ring may hold data whose
      windows end beyond it — the first pass's roll brings those ends in
      range, the second fires them (two passes cover all in-ring data
      because ``NP >= 2R``, enforced by the operator).
    * the capacity roll then makes room for the batch's newest pane; panes
      it evicts belong to windows overlapping the batch's own time range —
      data loss only under a genuinely undersized ring (pane_capacity <
      window span + batch time spread), surfaced via ``n_evicted``.
    * pass B, after placement, fires what the batch itself completed —
      windows ending between the batch's oldest pane and the watermark
      (routinely non-empty: on an ordered stream these are the windows the
      batch's own tuples closed).

    The ring moves only in a step in which it has to: the roll after
    each pass and the capacity roll (with the eviction accounting that
    is trivial without it) each sit under a ``lax.cond`` on their own
    shift, so a step that fires nothing and evicts nothing makes no pass
    over the ``K x NP`` cells; the state's ``n_ring_advances``
    (``TB_ring_advances``) counts the steps that moved.  Nothing may
    ``vmap`` the step: a batched ``cond`` runs both branches.

    Returns ``(state, out, fired, out_ts, n_advanced)``; ``n_advanced``
    counts windows passed (fired or skipped-as-evicted) so drivers can loop
    EOS/catch-up flushes until the frontier genuinely stops moving (windows
    beyond an empty gap would otherwise stall behind a no-emission pass).

    ``drop_tainted`` (the drop-window overflow policy): windows whose span
    lost a DATA pane to a capacity-roll eviction are suppressed instead of
    firing a wrong partial aggregate; every suppression increments
    ``n_win_dropped``.  The reference never fires a wrong window — it
    grows/blocks instead — so wrong-but-counted is opt-in (``count``).

    ``monoid`` ("sum" | "max" | "min"; legacy ``sum_like=True`` means
    ``monoid="sum"`` — withSumCombiner / withMonoidCombiner): TB
    placement then needs NO grouping at all — the pane cell is timestamp
    arithmetic, so lifts scatter-COMBINE (add/max/min) into the ring and
    the whole sort/segmented-scan machinery disappears (for "sum", float
    rounding order may differ from the sequential fold, the psum
    tolerance; max/min are idempotent — identical either way).  On a
    grid small beside the batch (:func:`tb_placement`, static per built
    step) even the scatter goes: the cell counts, and integer sums bit
    for bit, come out of one one-hot contraction on the MXU.  Past that
    grid the scatter's cost follows the batch, not the ring: a batch
    whose lanes span at most :data:`NARROW_PLACE_PANES` panes (one
    ``lax.cond`` on the span the step observes) scatters into ``[K + 1,
    NARROW_PLACE_PANES]`` targets and is merged into those columns
    alone, a 64-bit integer ``sum`` as uint32 limbs (only those some
    lane fills) widened in the leaf's unsigned twin, bit for bit the
    int64 scatter-add; a wider batch takes the whole-ring scatter and
    is counted in the state's ``n_wide`` (``TB_wide_placements``).
    """
    monoid = resolve_monoid(sum_like, monoid)
    MW = NP // D + 2
    N_PASSES = 3                     # A1, A2 (pre-place), B (post-place)
    # the output batch: the whole [K, N_PASSES * MW] grid while that is
    # the smaller form, else OC lanes with the fired rows compacted to
    # the front, window by window (tb_out_capacity)
    OC = tb_out_capacity(capacity, K, R, D, NP)
    compact = OC < K * N_PASSES * MW
    # a compacting pass looks at no more windows than its output could
    # hold with every key in each (plus the one that overflows it), so
    # its work follows the output, not the ring
    MWP = min(MW, -(-OC // K) + 1) if compact else MW
    # undeclared combiner: the grid's cells are looked up in the sorted
    # batch (a bisection a cell) while that is less work than a scatter
    # a lane
    seek_cells = K * NP * (capacity.bit_length() + 1) <= capacity

    def roll_left(flags, values, k):
        # advance the ring by k panes (k is traced); vacated tail = invalid
        idx = jnp.arange(NP, dtype=jnp.int64) + k
        inb = idx < NP
        idxc = jnp.clip(idx, 0, NP - 1).astype(jnp.int32)
        f = jnp.take(flags, idxc, axis=1) & inb[None, :]
        v = jax.tree.map(lambda a: jnp.take(a, idxc, axis=1), values)
        return f, v

    def advance(flags, values, k):
        # a roll by 0 is the identity and most steps move nothing: only a
        # step whose ring advances pays for a pass over its K x NP cells
        return jax.lax.cond(k > 0, roll_left, lambda f, v, _k: (f, v),
                            flags, values, k)

    def fire_pass(cells, cell_valid, base, win_next, frontier, max_seen,
                  horizon, acc=None):
        """:func:`fire`, then the roll that its windows free."""
        fired, wvals, w, n_fired, n_drop, acc = fire(
            cells, cell_valid, base, win_next, frontier, max_seen, horizon,
            acc)
        new_next = win_next + n_fired
        with phase("wf.ring"):
            shift = jnp.clip(new_next * D - base, 0, NP)
            cell_valid, cells = advance(cell_valid, cells, shift)
        return (cells, cell_valid, base + shift, new_next,
                fired, wvals, w, n_fired, n_drop, acc)

    @phase("wf.fire")
    def fire(cells, cell_valid, base, win_next, frontier, max_seen, horizon,
             acc):
        """Fire windows ending <= frontier whose end pane is inside the
        ring; returns the firing outputs.  Firing is capped to
        in-ring ends: if the frontier outruns the ring, later windows wait
        for the next pass/step (the roll brings their ends in range) — every
        fired fold is exactly over its own panes.  It is also capped to
        windows starting at or before the newest data pane (``max_seen``):
        later windows can never emit, so advancing past them would let an
        infinite-watermark flush loop run forever.

        Compacting (``acc``: the step's output so far): the pass fires
        the longest prefix of those windows whose rows still fit behind
        the ``acc["n"]`` rows already there, and appends them; the rest
        wait like windows beyond the ring.  The first pass of a step
        finds the batch empty and one window is at most ``K <= OC`` rows,
        so a step with a window to fire always fires one."""
        j = jnp.arange(MWP, dtype=jnp.int64)
        w = win_next + j
        end_local = (w * D + R - 1 - base)                     # [MWP]
        fire = ((w * D + R) <= frontier) & (end_local < NP) \
            & (w * D <= max_seen)                              # [MWP] prefix
        # end_local < 0 happens only when a capacity roll evicted the whole
        # window (overload); such windows must not fire with pane-0 data
        emitable = fire & (end_local >= 0)
        eidx = jnp.clip(end_local, 0, NP - 1).astype(jnp.int32)
        n_ready = jnp.sum(fire.astype(jnp.int64))

        def fold():
            # the O(K*NP*log R) sliding fold + gathers, only when this pass
            # actually fires something (on an ordered stream the pre-place
            # passes usually fire nothing — the previous step's post-place
            # pass already did their work)
            sflag, swin = _sliding_reduce(comb, cell_valid, cells, R, axis=1)

            def pick_leaf(a):
                idx = eidx.reshape(1, MWP, *([1] * (a.ndim - 2)))
                idx = jnp.broadcast_to(idx, (K, MWP) + a.shape[2:])
                return jnp.take_along_axis(a, idx, axis=1)
            wvals = jax.tree.map(pick_leaf, swin)
            any_data = jnp.take_along_axis(
                sflag, jnp.broadcast_to(eidx[None, :], (K, MWP)), axis=1)
            # advance past fully-evicted windows (fire) but never emit them
            # (emitable): their eidx clips to pane 0, which they don't cover
            return emitable[None, :] & any_data, wvals

        def untainted():
            # the drop-window policy: (key, window) cells whose span lost
            # no data to an eviction; the others are suppressed
            return (w * D)[None, :] >= horizon[:, None]

        def n_suppressed(f, clean, gone_w):
            # counted per tainted key — including windows whose WHOLE
            # span was evicted (``gone_w``: advanced past, not emitable),
            # which can never emit but did lose that key's data
            return jnp.sum((f & ~clean).astype(jnp.int64)) \
                + jnp.sum((gone_w[None, :] & ~clean).astype(jnp.int64))

        def do_fold(_):
            f, wvals = fold()
            n_drop = jnp.zeros((), jnp.int64)
            if drop_tainted:
                clean = untainted()
                n_drop = n_suppressed(f, clean, fire & ~emitable)
                f = f & clean
            return f, wvals, n_drop

        def no_fold(_):
            zvals = jax.tree.map(
                lambda a: jnp.zeros((K, MWP) + a.shape[2:], a.dtype), cells)
            return jnp.zeros((K, MWP), bool), zvals, jnp.zeros((), jnp.int64)

        def do_fold_compact(acc):
            f, wvals = fold()
            clean = untainted() if drop_tainted else True
            # the prefix of windows whose rows fit behind those in acc
            rows = jnp.cumsum(jnp.sum(f & clean, axis=0, dtype=jnp.int32))
            fits = fire & (acc["n"] + rows <= OC)
            f = f & fits[None, :]
            n_drop = jnp.zeros((), jnp.int64)
            if drop_tainted:
                n_drop = n_suppressed(f, clean, fits & ~emitable)
                f = f & clean
            # window-major, so a window's rows lie together and in order
            ff = f.T.reshape(-1)                               # [MWP * K]
            pos = acc["n"] + jnp.cumsum(ff.astype(jnp.int32)) - 1
            # ONE 32-bit scatter: which cell each output lane takes; the
            # lanes then gather their values
            src = jnp.full((OC,), -1, jnp.int32) \
                .at[jnp.where(ff, pos, OC)] \
                .set(jnp.arange(MWP * K, dtype=jnp.int32), mode="drop")
            hit = src >= 0
            at = jnp.maximum(src, 0)

            def put(old, a):
                flat = jnp.swapaxes(a, 0, 1).reshape((MWP * K,) + a.shape[2:])
                return jnp.where(_b(hit, old), flat[at], old)
            return {
                "key": jnp.where(hit, at % K, acc["key"]),
                "wid": jnp.where(hit, w[at // K], acc["wid"]),
                "value": jax.tree.map(put, acc["value"], wvals),
                "fired": acc["fired"] | hit,
                "n": acc["n"] + jnp.sum(ff, dtype=jnp.int32),
            }, jnp.sum(fits.astype(jnp.int64)), n_drop

        def no_fold_compact(acc):
            return acc, jnp.zeros((), jnp.int64), jnp.zeros((), jnp.int64)

        if compact:
            acc, n_fired, n_drop = jax.lax.cond(
                n_ready > 0, do_fold_compact, no_fold_compact, acc)
            fired = wvals = None
        else:
            n_fired = n_ready
            fired, wvals, n_drop = jax.lax.cond(n_ready > 0, do_fold,
                                                no_fold, None)
        return fired, wvals, w, n_fired, n_drop, acc

    def step(state, payload, ts, valid, wm_pane):
        B = capacity
        kb = key_base_fn() if key_base_fn is not None else None
        with phase("wf.fn"):
            keys = jax.vmap(key_fn)(payload).astype(jnp.int32) \
                if key_fn is not None else jnp.zeros(B, jnp.int32)
        if kb is not None:
            keys = keys - jnp.int32(kb)
        ok = valid & (keys >= 0) & (keys < K)
        with phase("wf.place"):
            # a lane's pane (64-bit divisions over the batch)
            pane = ts.astype(jnp.int64) // P_usec
            if D > R:
                # hopping windows with gaps (slide > win): panes in the
                # inter-window gap belong to no window — never place or
                # count them (pane p is covered iff p mod D < R)
                ok = ok & ((pane % D) < R)

        # 1. pass A (twice): fire everything no tuple of this batch can
        # touch; the second pass reaches windows whose ends the first
        # pass's roll brought inside the ring
        min_pane = jnp.min(jnp.where(ok, pane, jnp.int64(1) << 60))
        frontier_a = jnp.minimum(wm_pane, min_pane)
        cells, cell_valid, base, win_next = (
            state["cells"], state["cell_valid"], state["base"],
            state["win_next"])
        a_outs = []
        n_win_dropped = state["n_win_dropped"]
        acc = None
        if compact:
            with phase("wf.fire"):
                acc = {
                    "key": jnp.zeros((OC,), jnp.int32),
                    "wid": jnp.zeros((OC,), jnp.int64),
                    "value": jax.tree.map(
                        lambda a: jnp.zeros((OC,) + a.shape[2:], a.dtype),
                        state["cells"]),
                    "fired": jnp.zeros((OC,), bool),
                    "n": jnp.zeros((), jnp.int32),
                }
        for _ in range(2):
            (cells, cell_valid, base, win_next,
             fired_i, wvals_i, w_i, n_i, nd_i, acc) = fire_pass(
                cells, cell_valid, base, win_next, frontier_a,
                state["max_seen"], state["horizon"], acc)
            a_outs.append((fired_i, wvals_i, w_i, n_i))
            n_win_dropped = n_win_dropped + nd_i

        # 2. capacity roll: make room for this batch's newest pane
        with phase("wf.ring"):
            max_pane = jnp.max(jnp.where(ok, pane, base))
            max_seen = jnp.maximum(
                state["max_seen"], jnp.max(jnp.where(ok, pane, -(1 << 60))))
            shift_cap = jnp.maximum(jnp.int64(0),
                                    max_pane - base - (NP - 1))

            def make_room(cell_valid, cells, horizon):
                col = jnp.arange(NP, dtype=jnp.int64)[None, :]
                evict_mask = cell_valid & (col < shift_cap)
                # per-key taint horizon: one past the newest data pane
                # lost here
                horizon = jnp.maximum(
                    horizon,
                    jnp.max(jnp.where(evict_mask, base + col + 1,
                                      -(1 << 60)), axis=1))
                cell_valid, cells = roll_left(cell_valid, cells, shift_cap)
                return (cell_valid, cells, horizon,
                        jnp.sum(evict_mask.astype(jnp.int64)))

            # the ring holds the batch's newest pane already (any ring the
            # operator sized itself): nothing is evicted, nothing moves
            cell_valid, cells, horizon, evicted = jax.lax.cond(
                shift_cap > 0, make_room,
                lambda f, v, h: (f, v, h, jnp.zeros((), jnp.int64)),
                cell_valid, cells, state["horizon"])
            base = base + shift_cap

        # 3. place the batch: sort by (key, pane), fold runs, merge cells
        rel = pane - base
        late = ok & (rel < 0)
        ok = ok & (rel >= 0)
        rel_c = jnp.clip(rel, 0, NP - 1).astype(jnp.int32)
        n_wide = state["n_wide"]
        if monoid is not None:
            # declared leafwise-monoid combiner: a tuple's pane cell is
            # pure timestamp arithmetic (no within-key rank exists in
            # TB), so placement needs NO grouping at all — lifts
            # scatter-COMBINE straight into the ring (absent cells hold
            # the monoid identity).  The reference pays its sort for
            # every TB batch regardless (thrust::sort_by_key,
            # ffat_replica_gpu.hpp:917).
            row_u = jnp.where(ok, keys, K)
            with phase("wf.fn"):
                lifted, tree = jax.tree.flatten(jax.vmap(lift)(payload))
            plan = tb_placement(
                monoid, [jax.ShapeDtypeStruct(a.shape[1:], a.dtype)
                         for a in lifted], K, NP, B)
            mop = _MONOID_OPS[monoid][1]

            def scat(leaf, width, col, limb_bits=0):
                """One leaf of the batch's partial grid ``[K, width]``,
                scatter-combined at ``(row_u, col)``; with ``limb_bits``
                a 64-bit integer sum rides as uint32 limbs, widened in
                the leaf's unsigned twin (:func:`narrow_limb_bits`)."""
                ident = _monoid_identity(monoid, leaf.dtype)
                vals = jnp.where(_b(ok, leaf), leaf, ident)
                if limb_bits and _rides_limbs(monoid, leaf):
                    n = -(-leaf.dtype.itemsize * 8 // limb_bits)
                    limbs = _to_limbs(vals, n, limb_bits, jnp.uint32)

                    def lowest(used):
                        return _from_limbs(jnp.stack(
                            [jnp.zeros((K + 1, width), jnp.uint32)
                             .at[row_u, col].add(limbs[:, j])[:K]
                             for j in range(used)], axis=1),
                            limb_bits, leaf.dtype)
                    # a limb that is zero in every lane adds nothing:
                    # scatter up to the highest one some lane fills.
                    # Counts and small non-negative values ride one
                    # scatter, negative ones (sign limbs set) every one
                    top = jnp.max(jnp.where(
                        jnp.any(limbs != 0, axis=0),
                        jnp.arange(n, dtype=jnp.int32), 0))
                    return jax.lax.switch(
                        top, [functools.partial(lowest, u + 1)
                              for u in range(n)])
                buf = jnp.full((K + 1, width) + leaf.shape[1:], ident,
                               leaf.dtype)
                return _monoid_scatter(buf.at[row_u, col], monoid)(vals)[:K]

            def scat_has(width, col):
                return (jnp.zeros((K + 1, width), jnp.int32)
                        .at[row_u, col].add(ok.astype(jnp.int32))[:K] > 0)

            def merge(cells, valid, partial):
                """``partial`` (one grid a leaf) combined into ``cells``
                of the same width, cells not ``valid`` read as the
                identity."""
                def merge_m(old_leaf, new_leaf):
                    # declared op with dtype PROMOTION, exactly like the
                    # grouped path's comb merge — a wider (e.g. f64)
                    # state stays wide; no scatter is involved so no
                    # cast is needed
                    old = jnp.where(_b(valid, old_leaf), old_leaf,
                                    _monoid_identity(monoid, old_leaf.dtype))
                    return mop(new_leaf, old)
                return jax.tree.map(merge_m, cells,
                                    jax.tree.unflatten(tree, partial))

            def place_wide(cells, cell_valid):
                col = jnp.where(ok, rel_c, 0)
                return (merge(cells, cell_valid,
                              [scat(a, NP, col) for a in lifted]),
                        cell_valid | scat_has(NP, col))

            def place_narrow(cells, cell_valid):
                # the batch's own pane span: targets, re-layout of the
                # scatters' results and the merge all follow K x S
                S = NARROW_PLACE_PANES
                col = jnp.where(ok, rel_c - c0, 0)
                cut = lambda a: jax.lax.dynamic_slice_in_dim(  # noqa: E731
                    a, c0, S, axis=1)
                paste = lambda a, new: (  # noqa: E731
                    jax.lax.dynamic_update_slice_in_dim(
                        a.astype(new.dtype), new, c0, axis=1))
                old_valid = cut(cell_valid)
                new = merge(jax.tree.map(cut, cells), old_valid,
                            [scat(a, S, col, narrow_limb_bits(B))
                             for a in lifted])
                return (jax.tree.map(paste, cells, new),
                        paste(cell_valid, old_valid | scat_has(S, col)))

            # a grid small beside the batch is placed by one one-hot
            # contraction on the MXU, exact in the leaf's own width
            # (tb_placement); past it the batch scatters, into the
            # panes it spans where those are few: a 64-bit scatter-add
            # over the lanes costs 18.5 ms on a small grid and 34 ms
            # into 43 M cells, a 32-bit one 1.9 and 2.6 ms (PERF.md
            # section 6, PR 29 and PR 31)
            with phase("wf.place"):
                if plan["count"]:
                    n_cell, sums = _dense_place(
                        keys, rel_c, ok, K, NP, lifted, plan["limbs"],
                        plan["limb_bits"])
                    col = jnp.where(ok, rel_c, 0)
                    cells = merge(cells, cell_valid,
                                  [scat(a, NP, col) if s is None else s
                                   for a, s in zip(lifted, sums)])
                    cell_valid = cell_valid | (n_cell > 0)
                elif NP > NARROW_PLACE_PANES:
                    c0 = jnp.clip(jnp.min(jnp.where(ok, rel_c, NP)), 0,
                                  NP - NARROW_PLACE_PANES)
                    narrow = jnp.max(jnp.where(ok, rel_c, 0)) - c0 \
                        < NARROW_PLACE_PANES
                    cells, cell_valid = jax.lax.cond(
                        narrow, place_narrow, place_wide, cells, cell_valid)
                    n_wide = n_wide + jnp.where(narrow, 0, 1)
                else:
                    cells, cell_valid = place_wide(cells, cell_valid)
        else:
            def place(cells):
                sid = jnp.where(ok, keys.astype(jnp.int64) * NP + rel_c,
                                jnp.int64(K) * NP)
                with phase("wf.group"):
                    if K * NP + 1 < (1 << 31):   # counting ids are int32
                        sid = sid.astype(jnp.int32)
                        order = _group_order(sid, K * NP + 1, grouping,
                                             pallas)
                    else:
                        order = jnp.argsort(sid, stable=True)
                    ssid = sid[order]
                with phase("wf.fn"):
                    lifted = jax.vmap(lift)(payload)
                with phase("wf.group"):
                    slift = jax.tree.map(lambda a: a[order], lifted)
                return fold_into(cells, ssid, slift)

            @phase("wf.place")
            def fold_into(cells, ssid, slift):
                starts = jnp.concatenate([jnp.array([True]),
                                          ssid[1:] != ssid[:-1]])
                scanned = _seg_scan(comb, starts, slift)
                if seek_cells:
                    # a grid small beside the batch looks its cells up:
                    # a cell's fold is the last lane of its run, found
                    # by bisection, where a scatter walks every lane
                    cell = jnp.arange(K * NP, dtype=ssid.dtype)
                    last = jnp.searchsorted(ssid, cell, side="right") - 1
                    at = jnp.maximum(last, 0)
                    partial_has = ((last >= 0) & (ssid[at] == cell)) \
                        .reshape(K, NP)

                    def seek(leaf):
                        got = leaf[at].reshape((K, NP) + leaf.shape[1:])
                        return jnp.where(_b(partial_has, got), got, 0)
                    partial = jax.tree.map(seek, scanned)
                else:
                    ends = jnp.concatenate([ssid[1:] != ssid[:-1],
                                            jnp.array([True])])
                    row = jnp.where(ends, ssid // NP, K).astype(jnp.int32)
                    col = jnp.where(ends, ssid % NP, 0).astype(jnp.int32)

                    def scat(leaf):
                        buf = jnp.zeros((K + 1, NP) + leaf.shape[1:],
                                        leaf.dtype)
                        return buf.at[row, col].set(
                            jnp.where(_b(ends, leaf), leaf, 0))[:K]
                    partial = jax.tree.map(scat, scanned)
                    partial_has = jnp.zeros((K + 1, NP), bool) \
                        .at[row, col].set(ends)[:K]

                # comb is a whole-pytree combiner (see CB merge above)
                both_cells = comb(cells, partial)

                def merge(old_leaf, new_leaf, both_leaf):
                    return jnp.where(
                        _b(cell_valid & partial_has, both_leaf), both_leaf,
                        jnp.where(_b(partial_has, both_leaf), new_leaf,
                                  old_leaf))
                return (jax.tree.map(merge, cells, partial, both_cells),
                        partial_has)

            # the sort and the scan run over every lane whatever it
            # holds: a batch with nothing to place (a window stage fed
            # by another's fired rows sees mostly those) skips them
            cells, partial_has = jax.lax.cond(
                jnp.any(ok), place,
                lambda cells: (cells, jnp.zeros((K, NP), bool)), cells)
            cell_valid = cell_valid | partial_has

        # 4. pass B: fire what this batch completed under the watermark
        (cells, cell_valid, base, win_next,
         fired_b, wvals_b, w_b, n_b, nd_b, acc) = fire_pass(
            cells, cell_valid, base, win_next, wm_pane, max_seen, horizon,
            acc)
        n_win_dropped = n_win_dropped + nd_b

        new_state = {
            "cells": cells,
            "cell_valid": cell_valid,
            "base": base,
            "win_next": win_next,
            "max_seen": max_seen,
            "horizon": horizon,
            "n_late": state["n_late"] + jnp.sum(late.astype(jnp.int64)),
            "n_evicted": state["n_evicted"] + evicted,
            "n_win_dropped": n_win_dropped,
            "n_wide": n_wide,
            # base moves by the four shifts alone, none of them negative
            "n_ring_advances": state["n_ring_advances"]
            + jnp.where(base > state["base"], 1, 0),
        }
        all_passes = a_outs + [(fired_b, wvals_b, w_b, n_b)]
        n_adv = sum(p[3] for p in all_passes)
        if compact:
            # outputs: the passes' rows in firing order, window by window
            out = {"key": acc["key"]
                   + (jnp.int32(kb) if kb is not None else 0),
                   "wid": acc["wid"], "value": acc["value"]}
            return new_state, out, acc["fired"], \
                (acc["wid"] * D + R) * P_usec - 1, n_adv      # end-1 (TB)
        # outputs: pass A1, A2, then B rows, [K, N_PASSES*MW] flattened
        with phase("wf.fire"):
            w2 = jnp.concatenate([p[2] for p in all_passes])
            fired = jnp.concatenate([p[0] for p in all_passes], axis=1)
            wvals = jax.tree.map(
                lambda *leaves: jnp.concatenate(leaves, axis=1),
                *[p[1] for p in all_passes])
            NM = N_PASSES * MW
            out_ts = (w2 * D + R) * P_usec - 1                 # end-1 (TB)
            out = {
                "key": (jnp.broadcast_to(
                    jnp.arange(K, dtype=jnp.int32)[:, None], (K, NM))
                    + (jnp.int32(kb) if kb is not None else 0)).reshape(-1),
                "wid": jnp.broadcast_to(w2[None, :], (K, NM)).reshape(-1),
                "value": jax.tree.map(
                    lambda a: a.reshape((K * NM,) + a.shape[2:]), wvals),
            }
            return new_state, out, fired.reshape(-1), \
                jnp.broadcast_to(out_ts[None, :], (K, NM)).reshape(-1), n_adv

    return step


def make_ffat_state(agg_spec, K: int, R: int):
    """Dense per-key FFAT device state over a static key space ``[0, K)``
    (see :class:`FfatWindowsTPU` for the layout)."""
    zeros = lambda shape: jax.tree.map(
        lambda s: jnp.zeros(shape + s.shape, s.dtype), agg_spec)
    return {
        "carry": zeros((K, R - 1)),               # trailing R-1 panes
        "carry_valid": jnp.zeros((K, R - 1), bool),
        "cur": zeros((K,)),                       # partial pane aggregate
        "cur_valid": jnp.zeros((K,), bool),
        "cur_fill": jnp.zeros((K,), jnp.int32),   # tuples in partial pane
        "pane_base": jnp.zeros((K,), jnp.int64),  # completed panes
        "win_next": jnp.full((K,), R, jnp.int64),  # next end pane
    }


def agg_spec_for(lift: Callable, payload_tree) -> Any:
    """Shape/dtype skeleton of one aggregate, from a batch payload pytree."""
    one = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype), payload_tree)
    spec = jax.eval_shape(lift, one)
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), spec)


