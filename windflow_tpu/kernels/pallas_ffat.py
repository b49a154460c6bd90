"""Pallas TPU kernels for the FFAT hot loop (ROADMAP item 3).

Three kernels, one per region that dominated the fused step's profile
(key grouping, pane fold, dense-table scatter), each a
drop-in replacement for a lax composition INSIDE the same wf_jit
program (zero dispatch-count change — the kernels are traced into the
programs the jit registry already pins):

* **Segmented grouping** (:func:`grouping_rank_hist` /
  :func:`order_hist`) — the three components that each cost ~100-120%
  of the whole fused step standalone on the v5-lite profile
  (``key_extract_argsort``, ``grouping_rank_scatter``, ``sort_gather``)
  fused into ONE two-phase tiled kernel: an on-chip running key
  histogram (sequential TPU grid = cross-tile carry in VMEM scratch),
  stable within-tile rank assignment via a strictly-lower-triangular
  ones matmul on the MXU (the 1811.09736 "reduction as matmul" mapping
  — rank/histogram/offset gathers are one-hot contractions), and the
  counting-sort destinations emitted in the same pass.  Bit-identical
  to ``grouping.order_and_hist`` (both order by (id, arrival)).
* **Pane combine / sliding fold** (:func:`sliding_fold`) — the FlatFAT
  pane fold ``out[i] = fold(comb, panes[i-R+1..i])`` as a blocked scan:
  for declared ``"sum"`` over f32 the inner combine is an MXU matmul
  against a banded 0/1 carrier matrix (the 1811.09736 scan mapping);
  every other declared monoid/dtype runs the SAME dilated-doubling
  schedule as the lax fold on the VPU — bit-identical by construction
  (identical combine tree).  Generic traced combiners stay on the lax
  path (the WF607 downgrade, docs/ANALYSIS.md).
* **Segmented reduce** (:func:`dense_monoid_table`) — the PR 11
  dense/compacted one-scatter combine re-tiled: a sequential grid
  accumulates per-tile masked reductions into an HBM-contiguous
  ``[slots]`` table resident across grid steps, replacing the
  serialized XLA scatter with vectorized masked folds.

``Config.pallas_kernels`` / ``WF_TPU_PALLAS`` resolve here
(:func:`resolve_pallas`): ``"auto"`` compiles the kernels on TPU
backends and runs them ``interpret=True`` on the CPU fallback so
tier-1 exercises the real kernel bodies; ``"1"`` forces (downgrading
with a WF607 preflight warning where no lowering exists); ``"0"`` is
the kill switch — no kernel builds, the lax path verbatim.

Float-sum caveat (the psum tolerance): the MXU
banded matmul accumulates f32 sums in contraction order where the lax
fold uses a doubling tree — exact whenever the summands are integers
below 2**24 (every record-for-record A/B family), reassociation-grade
otherwise, exactly the tolerance the declared-"sum" contract already
implies for psum.  That holds only at ``Precision.HIGHEST``: the MXU's
default single bf16 pass rounds the values to 8 mantissa bits (1.8e-3
relative error per window on a v5e — chip_smoke.py leg A_sum).  max/min
and integer sums are bit-identical unconditionally.

The compiled kernels trace with x64 off (:func:`_pallas_call`) and
shift along lanes with ``pltpu.roll`` + an iota mask
(:func:`_shift_cols`): Mosaic has no 64-bit vector types and refuses
the unaligned ``tpu.concatenate`` that ``jnp.pad`` lowers to.
"""

from __future__ import annotations

from typing import List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: lane tile of the grouping / table kernels (second-to-last dim of the
#: one-hot blocks; 256 keeps the [TILE, buckets] mask under ~4 MB VMEM
#: at the bucket ceiling below).
LANE_TILE = 256
#: key-row tile of the sliding-fold kernel.
ROW_TILE = 128
#: output-column chunk of the banded-matmul fold (band block is
#: [chunk + R - 1, chunk]).
FOLD_CHUNK = 128
#: bucket-space ceiling for the one-hot kernels: beyond it the
#: [TILE, buckets] masks outgrow VMEM and the lax path (radix /
#: scatter) keeps the job.
MAX_BUCKETS = 4096
#: lane-count ceiling: destinations are exact in f32 only below 2**24;
#: 2**22 leaves margin for the cross-tile offsets.
MAX_LANES = 1 << 22
#: window-width ceiling for the fold kernel (band block height).
MAX_FOLD_R = 512
#: pane-axis ceiling for the fold kernel: the whole (padded) pane row
#: lives in one VMEM block of [ROW_TILE, panes] per leaf (input +
#: output + the shared valid mask), so the axis must be bounded the
#: same way MAX_BUCKETS bounds the one-hot kernels — 4096 keeps a
#: worst-case 8-byte leaf block at 4 MB.  The TPU bench shape
#: (capacity 262144, P=128 → ~2064 panes) fits; wider rings keep the
#: lax fold.
MAX_FOLD_PANES = 4096

#: kernels built since import — the off-path budget assert reads this
#: (the kill switch must build NOTHING).
_BUILD_COUNT = 0


def pallas_build_count() -> int:
    return _BUILD_COUNT


class PallasMode(NamedTuple):
    """Resolved Pallas gate: ``interpret`` runs the kernel bodies under
    the Pallas interpreter (CPU tier-1) instead of Mosaic."""

    interpret: bool


def _mode_str(config) -> str:
    raw = getattr(config, "pallas_kernels", "auto")
    if raw is True:
        return "1"
    if raw is False:
        return "0"
    return str(raw).strip().lower()


def pallas_forced(config) -> bool:
    """True when the user explicitly forced the kernels on
    (``WF_TPU_PALLAS=1``) — the only mode whose downgrades warn
    (WF607); ``auto`` picks silently, mirroring WF606."""
    return _mode_str(config) in ("1", "on", "force", "true")


def resolve_pallas(config) -> Optional[PallasMode]:
    """Resolve ``Config.pallas_kernels`` against the runtime backend.

    ``None`` = lax path (kill switch, or no lowering for this
    backend).  TPU backends compile the kernels; the CPU fallback runs
    them ``interpret=True`` so tier-1 executes the real kernel bodies.
    Other backends (GPU: no Mosaic, and the TPU-shaped kernels have no
    Triton lowering here) downgrade to lax — named by WF607 when
    forced."""
    mode = _mode_str(config)
    if mode in ("0", "off", "false", ""):
        return None
    backend = jax.default_backend()
    if backend == "tpu":
        return PallasMode(interpret=False)
    if backend == "cpu":
        return PallasMode(interpret=True)
    return None


def resolve_pallas_for(op) -> Optional[PallasMode]:
    """:func:`resolve_pallas` against an OPERATOR's effective config —
    the graph-attached ``op.config`` when built inside a PipeGraph,
    else the process default (standalone operators: bench kernel legs,
    direct ``_step`` drivers).  THE one spelling of that fallback rule
    for every step builder."""
    from windflow_tpu.basic import default_config
    return resolve_pallas(getattr(op, "config", default_config))


# ---------------------------------------------------------------------------
# shared helpers
# ---------------------------------------------------------------------------

def _ceil_to(n: int, m: int) -> int:
    return -(-n // m) * m


def _pad_axis(a, new: int, axis: int, value):
    pad = new - a.shape[axis]
    if pad <= 0:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    return jnp.pad(a, widths, constant_values=value)


def _iota2(dtype, shape, dim):
    return jax.lax.broadcasted_iota(dtype, shape, dim)


def _pallas_call(kernel, *args, interpret: bool, **kw):
    """``pl.pallas_call(kernel, **kw)(*args)``; the compiled (Mosaic)
    form traces with x64 OFF.

    The package enables ``jax_enable_x64`` process-wide (int64
    timestamp lanes), under which every Python scalar, index-map result
    and ``program_id`` product in a kernel traces 64-bit — and Mosaic
    has no 64-bit vector types (the TPU lowering dies in
    ``convert_element_type`` / "failed to legalize func.return").  The
    compiled dtype gates admit 32-bit-or-narrower lanes only, so the
    whole call traces in a scoped 32-bit world instead of pinning each
    constant by hand.  The interpreter keeps the ambient x64: its gates
    admit int64/f64 lanes, which a 32-bit trace would truncate."""
    if interpret:
        return pl.pallas_call(kernel, interpret=True, **kw)(*args)
    with jax.enable_x64(False):
        return pl.pallas_call(kernel, **kw)(*args)


def _shift_cols(x, k: int, fill):
    """Shift a 2-D ``[rows, N]`` VALUE right along the lane axis by
    ``k``, filling the vacated low columns with ``fill`` (the in-kernel
    form of ``ffat_kernels._shift_leaf``).  A lane rotate + iota mask:
    ``jnp.pad`` lowers to an unaligned ``tpu.concatenate`` Mosaic
    refuses ("Input offsets outside of the first tile")."""
    if k == 0:
        return x
    n = x.shape[-1]
    if k >= n:
        return jnp.full(x.shape, fill, x.dtype)
    return jnp.where(_iota2(jnp.int32, x.shape, x.ndim - 1) >= k,
                     pltpu.roll(x, k, x.ndim - 1), fill)


def _monoid_op(kind: str):
    return {"sum": jnp.add, "max": jnp.maximum, "min": jnp.minimum}[kind]


def _identity_scalar(kind: str, dtype):
    """The monoid identity as a PYTHON scalar — the jnp form
    (``ffat_kernels._monoid_identity``) becomes a tracer under
    omnistaging, which pallas would reject as a captured constant and
    pad/fill sites would needlessly stage.  Same values per dtype."""
    dt = np.dtype(dtype)
    if kind == "sum":
        return False if dt == np.bool_ else dt.type(0).item()
    if dt == np.bool_:
        return kind == "min"
    if dt.kind == "f":
        return float("-inf") if kind == "max" else float("inf")
    info = np.iinfo(dt)
    return int(info.min if kind == "max" else info.max)


#: public spelling for callers building ``dense_monoid_table`` inits
monoid_identity_py = _identity_scalar


# ---------------------------------------------------------------------------
# kernel 1: segmented grouping — rank + histogram + counting-sort dests
# ---------------------------------------------------------------------------

def grouping_supported(n: int, nbuckets: int) -> bool:
    """Gate for the grouping kernel: the one-hot tiles bound the bucket
    space, f32 exactness bounds the lane count.  Outside it the lax
    counting/radix/argsort path keeps the job (bit-identical either
    way)."""
    return 2 <= nbuckets <= MAX_BUCKETS and 0 < n <= MAX_LANES


def grouping_rank_hist(ids, nbuckets: int, interpret: bool):
    """Single-pass segmented grouping: returns ``(dest, rank, hist)``
    for int ids in ``[0, nbuckets)`` (callers pre-clamp, exactly the
    ``grouping.py`` contract).

    * ``rank[i]`` — arrival-stable rank of lane *i* among equal ids
      (``dense_rank``'s rank, computed without its 31-pass shifted
      compare: the within-tile half is ONE [TILE, TILE] x [TILE, NB]
      strictly-lower-triangular matmul on the MXU, the cross-tile half
      the sequential grid's running histogram).
    * ``dest[i] = bucket_start[id_i] + rank[i]`` — the stable
      counting-sort destination; ``invert_perm(dest)`` is exactly
      ``jnp.argsort(ids, stable=True)`` for such ids.
    * ``hist[b]`` — occurrences of id ``b``.

    Two phases over the same tiles (one sequential TPU grid): phase 0
    accumulates the histogram; phase 1 prefix-sums it into bucket
    starts (log-shift doubling over the [NB] row) and emits
    rank/dest while re-accumulating the running per-bucket offsets."""
    global _BUILD_COUNT
    _BUILD_COUNT += 1
    B = int(ids.shape[0])
    NB = int(nbuckets)
    NBp = _ceil_to(NB, 128)
    Bp = _ceil_to(B, LANE_TILE)
    T = Bp // LANE_TILE
    ids2 = _pad_axis(ids.astype(jnp.int32), Bp, 0, NB)[None, :]

    def kernel(ids_ref, dest_ref, rank_ref, hist_ref, run, bstart):
        ph = pl.program_id(0)
        t = pl.program_id(1)
        tiles = pl.num_programs(1)
        tids = ids_ref[0, :]
        lane = _iota2(jnp.int32, (LANE_TILE, 1), 0)[:, 0]
        real = (t * LANE_TILE + lane) < B
        onehot = (tids[:, None] == _iota2(jnp.int32, (LANE_TILE, NBp), 1)) \
            & real[:, None]
        colsum = jnp.sum(onehot.astype(jnp.int32), axis=0,
                         dtype=jnp.int32)[None, :]

        @pl.when(ph == 0)
        def _phase0():
            @pl.when(t == 0)
            def _():
                run[...] = jnp.zeros_like(run)

            run[...] += colsum

            @pl.when(t == tiles - 1)
            def _():
                hist_ref[...] = run[...]

        @pl.when(ph == 1)
        def _phase1():
            @pl.when(t == 0)
            def _():
                tot = run[...]
                inc = tot
                s = 1
                while s < NBp:
                    inc = inc + _shift_cols(inc, s, 0)
                    s *= 2
                bstart[...] = inc - tot
                run[...] = jnp.zeros_like(run)

            onef = onehot.astype(jnp.float32)
            tri = (_iota2(jnp.int32, (LANE_TILE, LANE_TILE), 1)
                   < _iota2(jnp.int32, (LANE_TILE, LANE_TILE), 0)) \
                .astype(jnp.float32)
            # earlier[i, b] = lanes j < i of this tile with id_j == b —
            # the within-tile stable rank, as one MXU contraction
            earlier = jnp.dot(tri, onef,
                              preferred_element_type=jnp.float32)
            within = jnp.sum(onef * earlier, axis=1)
            # one-hot row selects = gathers: rank offset and bucket
            # start read through the same mask (f32 exact: all values
            # are counts below 2**24 — see MAX_LANES)
            cross = jnp.sum(
                onef * run[0, :].astype(jnp.float32)[None, :], axis=1)
            start = jnp.sum(
                onef * bstart[0, :].astype(jnp.float32)[None, :], axis=1)
            rank_ref[0, :] = (within + cross).astype(jnp.int32)
            dest_ref[0, :] = (within + cross + start).astype(jnp.int32)
            run[...] += colsum

    dest, rank, hist = _pallas_call(
        kernel, ids2,
        grid=(2, T),
        in_specs=[pl.BlockSpec((1, LANE_TILE), lambda p, t: (0, t))],
        out_specs=(pl.BlockSpec((1, LANE_TILE), lambda p, t: (0, t)),
                   pl.BlockSpec((1, LANE_TILE), lambda p, t: (0, t)),
                   pl.BlockSpec((1, NBp), lambda p, t: (0, 0))),
        out_shape=(jax.ShapeDtypeStruct((1, Bp), jnp.int32),
                   jax.ShapeDtypeStruct((1, Bp), jnp.int32),
                   jax.ShapeDtypeStruct((1, NBp), jnp.int32)),
        scratch_shapes=[pltpu.VMEM((1, NBp), jnp.int32),
                        pltpu.VMEM((1, NBp), jnp.int32)],
        interpret=interpret,
    )
    return dest[0, :B], rank[0, :B], hist[0, :NB]


def order_hist(ids, nbuckets: int, interpret: bool):
    """Pallas twin of ``grouping.order_and_hist``: the stable grouping
    permutation plus the id histogram.  The kernel emits counting-sort
    DESTINATIONS; one O(n) scatter of iota inverts them into gather
    indices (``grouping.invert_perm`` — the same single scatter the lax
    path already pays)."""
    from windflow_tpu.windows.grouping import invert_perm
    dest, _, hist = grouping_rank_hist(ids, nbuckets, interpret)
    return invert_perm(dest), hist


# ---------------------------------------------------------------------------
# kernel 2: pane combine / sliding fold
# ---------------------------------------------------------------------------

def _fold_leaf_dtype_ok(dtype, interpret: bool) -> bool:
    """Per-leaf dtype gate for the fold kernel — same stance as
    :func:`table_leaf_ok`: the interpreter folds any numeric dtype
    exactly; compiled Mosaic keeps to the natively tiled f32/i32 set
    (int64/f64 pane aggregates keep the lax fold on a real TPU; bool
    is excluded in both modes — its max/min degenerate to or/and and
    the lax fold owns that edge)."""
    dt = jnp.dtype(dtype)
    if dt == jnp.bool_:
        return False
    if interpret:
        return dt.kind in "fiu"
    return dt in (jnp.dtype(jnp.float32), jnp.dtype(jnp.int32))


def fold_supported(values, R: int, monoid: Optional[str],
                   interpret: bool) -> bool:
    """Gate for the sliding-fold kernel: declared monoid, 2-D
    ``[K, panes]`` leaves (scalar aggregates — trailing-dim aggregates
    keep the lax fold), kernel-foldable dtypes per backend mode, a
    band that fits the blocked matmul, and a pane axis whose full row
    fits the VMEM block (MAX_FOLD_PANES — the fold keeps whole rows
    resident, unlike the chunked one-hot kernels)."""
    if monoid not in ("sum", "max", "min") or not (1 <= R <= MAX_FOLD_R):
        return False
    leaves = jax.tree_util.tree_leaves(values)
    if not leaves or not all(l.ndim == 2 for l in leaves):
        return False
    if int(leaves[0].shape[1]) + (R - 1) > MAX_FOLD_PANES:
        return False
    return all(_fold_leaf_dtype_ok(l.dtype, interpret) for l in leaves)


def _fold_leaf(x, valid, R: int, monoid: str):
    """One leaf's in-kernel fold over a ``[rows, NPPp]`` block: the
    banded MXU matmul for f32 sums, the lax fold's OWN dilated-doubling
    schedule (bit-identical combine tree) for everything else."""
    ident = _identity_scalar(monoid, x.dtype)
    filled = jnp.where(valid, x, ident)
    if monoid == "sum" and x.dtype == jnp.float32:
        rows, npp = filled.shape
        padded = jnp.pad(filled, ((0, 0), (R - 1, 0)),
                         constant_values=0.0)
        chunks = []
        for c0 in range(0, npp, FOLD_CHUNK):
            ch = min(FOLD_CHUNK, npp - c0)
            sub = padded[:, c0:c0 + ch + R - 1]
            li = _iota2(jnp.int32, (ch + R - 1, ch), 0)
            mi = _iota2(jnp.int32, (ch + R - 1, ch), 1)
            band = ((li >= mi) & (li <= mi + (R - 1))) \
                .astype(jnp.float32)
            # HIGHEST: the MXU's default single bf16 pass keeps 8
            # mantissa bits of the VALUES (the 0/1 band is exact) —
            # measured 1.8e-3 relative error per window sum on a v5e
            chunks.append(jnp.dot(sub, band,
                                  preferred_element_type=jnp.float32,
                                  precision=jax.lax.Precision.HIGHEST))
        return jnp.concatenate(chunks, axis=1)
    # VPU path: EXACTLY ffat_kernels._sliding_reduce_plain's schedule
    # (pow2 doubling + binary stitching) so float results are
    # bit-identical to the lax fold, not merely equivalent
    op = _monoid_op(monoid)
    pow2 = [filled]
    width = 1
    while width * 2 <= R:
        v = pow2[-1]
        pow2.append(op(_shift_cols(v, width, ident), v))
        width *= 2
    res = None
    offset = 0
    for j in range(len(pow2) - 1, -1, -1):
        w = 1 << j
        if R & w:
            v = _shift_cols(pow2[j], offset, ident)
            res = v if res is None else op(v, res)
            offset += w
    return res


def sliding_fold(values, valid, R: int, monoid: str, interpret: bool):
    """Pallas pane combine: ``out[k, i] = fold(monoid-op,
    values[k, i-R+1..i])`` with invalid panes absorbed as the monoid
    identity — the kernel twin of ``_monoid_fill`` +
    ``_sliding_reduce_plain`` fused into one VMEM-resident pass,
    blocked over key rows."""
    global _BUILD_COUNT
    _BUILD_COUNT += 1
    leaves, treedef = jax.tree_util.tree_flatten(values)
    K, NPP = (int(leaves[0].shape[0]), int(leaves[0].shape[1]))
    Kp = _ceil_to(K, ROW_TILE)
    NPPp = _ceil_to(NPP, 128)
    vpad = _pad_axis(_pad_axis(valid, Kp, 0, False), NPPp, 1, False)
    lpad = [
        _pad_axis(_pad_axis(l, Kp, 0,
                            _identity_scalar(monoid, l.dtype)),
                  NPPp, 1, _identity_scalar(monoid, l.dtype))
        for l in leaves]

    def kernel(valid_ref, *refs):
        ins = refs[:len(leaves)]
        outs = refs[len(leaves):]
        v = valid_ref[...]
        for i_ref, o_ref in zip(ins, outs):
            o_ref[...] = _fold_leaf(i_ref[...], v, R, monoid)

    spec = pl.BlockSpec((ROW_TILE, NPPp), lambda k: (k, 0))
    folded = _pallas_call(
        kernel, vpad, *lpad,
        grid=(Kp // ROW_TILE,),
        in_specs=[spec] * (1 + len(leaves)),
        out_specs=tuple([spec] * len(leaves)),
        out_shape=tuple(jax.ShapeDtypeStruct((Kp, NPPp), l.dtype)
                        for l in leaves),
        interpret=interpret,
    )
    if not isinstance(folded, (list, tuple)):
        folded = (folded,)
    return jax.tree_util.tree_unflatten(
        treedef, [f[:K, :NPP] for f in folded])


# ---------------------------------------------------------------------------
# kernel 3: segmented reduce — dense monoid slot tables
# ---------------------------------------------------------------------------

def table_supported(n: int, nslots: int) -> bool:
    """Slot-space/lane-count gate for the dense-table kernel (the
    [TILE, slots] one-hot bound; beyond it the lax scatter keeps the
    job)."""
    return 1 <= nslots <= MAX_BUCKETS and 0 < n <= MAX_LANES


def table_leaf_ok(shape, dtype, interpret: bool) -> bool:
    """Per-leaf gate for the dense-table kernel: 1-D lanes or packed
    ``[B, W]`` carrier columns; under the interpreter every numeric
    dtype folds exactly, compiled Mosaic keeps to the natively tiled
    f32/i32/bool set (other dtypes stay on the lax scatter — per-leaf
    routing, values unchanged either way)."""
    if len(shape) not in (1, 2):
        return False
    if len(shape) == 2 and shape[1] > 8:
        return False
    dt = jnp.dtype(dtype)
    if interpret:
        return dt.kind in "fiub"
    return dt in (jnp.dtype(jnp.float32), jnp.dtype(jnp.int32),
                  jnp.dtype(jnp.bool_))


def routed_monoid_tables(row, payload, monoid: str,
                         nslots: int, interpret: bool,
                         lax_leaf, ts=None, ts_init: int = 0,
                         lax_ts=None, want_count: bool = False):
    """Per-leaf routing around :func:`dense_monoid_table` — THE shared
    front door for the dense/compacted reduce steps (ops/tpu.py,
    parallel/compaction.py), so the dtype gates, the ts-column probe,
    and every fallback merge live once.

    Returns ``None`` when no leaf of the ``payload`` pytree passes the
    gates (caller keeps its pure-lax body), else
    ``(table_tree, ts_table, count_table)`` where ``table_tree``
    mirrors ``payload`` with gated-out leaves computed through
    ``lax_leaf(leaf)``, ``ts_table`` is the per-slot max of ``ts``
    starting from ``ts_init`` — computed by ``lax_ts()`` instead when
    ``ts``'s int64 lanes fail the compiled dtype gate (``None`` when
    ``ts`` was not given) — and ``count_table`` the int32 per-slot
    lane count (``None`` unless ``want_count``)."""
    leaves, treedef = jax.tree_util.tree_flatten(payload)
    B = int(row.shape[0])
    if not table_supported(B, nslots):
        return None
    routed = [table_leaf_ok(l.shape, l.dtype, interpret) for l in leaves]
    if not any(routed):
        return None
    hot = [l for l, r in zip(leaves, routed) if r]
    vals = list(hot)
    ops = [monoid] * len(hot)
    inits = [_identity_scalar(monoid, l.dtype) for l in hot]
    if want_count:
        vals.append(jnp.ones(B, jnp.int32))
        ops.append("sum")
        inits.append(0)
    ts_rides = ts is not None and table_leaf_ok((B,), jnp.int64,
                                                interpret)
    if ts_rides:
        vals.append(ts)
        ops.append("max")
        inits.append(int(ts_init))
    tabs = dense_monoid_table(row, vals, ops, inits, nslots, interpret)
    it = iter(tabs[:len(hot)])
    table_tree = jax.tree_util.tree_unflatten(
        treedef, [next(it) if r else lax_leaf(l)
                  for l, r in zip(leaves, routed)])
    cnt = tabs[len(hot)] if want_count else None
    if ts_rides:
        ts_t = tabs[-1]
    else:
        ts_t = lax_ts() if (ts is not None and lax_ts is not None) \
            else None
    return table_tree, ts_t, cnt


def dense_monoid_table(row, leaves: Sequence, ops: Sequence[str],
                       inits: Sequence, nslots: int,
                       interpret: bool) -> List:
    """Segmented reduce into dense slot tables: for each leaf,
    ``table[s] = fold(op, leaf[lanes with row == s])`` over
    ``s in [0, nslots)``, starting from ``init`` (lanes whose ``row``
    falls outside ``[0, nslots)`` — the dump row of the lax scatter —
    contribute nothing).  Leaves are ``[B]`` lanes or ``[B, W]`` packed
    carrier columns; each carries its own op ("sum" | "max" | "min")
    and init, so the payload tables, the ts max column, and the
    liveness count ride ONE kernel.

    A sequential grid walks lane tiles; the tables live in the output
    block (constant index map — resident across grid steps), so the
    combine is a vectorized masked fold per tile instead of XLA's
    serialized scatter."""
    global _BUILD_COUNT
    _BUILD_COUNT += 1
    # inits must be PYTHON scalars: a jnp identity would be captured as
    # a traced constant inside the kernel closure, which pallas rejects
    inits = [i if isinstance(i, (int, float, bool))
             else np.asarray(i).item() for i in inits]
    B = int(row.shape[0])
    S = int(nslots)
    Sp = _ceil_to(S, 128)
    Bp = _ceil_to(B, LANE_TILE)
    row2 = _pad_axis(row.astype(jnp.int32), Bp, 0, S)[None, :]
    ins = []
    widths = []
    for l in leaves:
        if l.ndim == 1:
            ins.append(_pad_axis(l[None, :], Bp, 1, 0))
            widths.append(1)
        else:
            ins.append(_pad_axis(l.T, Bp, 1, 0))
            widths.append(int(l.shape[1]))

    def kernel(row_ref, *refs):
        t = pl.program_id(0)
        vrefs = refs[:len(ins)]
        orefs = refs[len(ins):]
        ids = row_ref[0, :]
        lane = _iota2(jnp.int32, (LANE_TILE, 1), 0)[:, 0]
        real = ((t * LANE_TILE + lane) < B) & (ids >= 0) & (ids < S)
        onehot = (ids[:, None] == _iota2(jnp.int32, (LANE_TILE, Sp), 1)) \
            & real[:, None]

        @pl.when(t == 0)
        def _():
            for o_ref, init in zip(orefs, inits):
                o_ref[...] = jnp.full(o_ref.shape, init, o_ref.dtype)

        for v_ref, o_ref, op, w in zip(vrefs, orefs, ops, widths):
            op_fn = _monoid_op(op)
            for col in range(w):
                v = v_ref[col, :]
                if op == "sum":
                    contrib = jnp.sum(
                        jnp.where(onehot, v[:, None],
                                  jnp.zeros((), v.dtype)),
                        axis=0, dtype=v.dtype)
                else:
                    ident = _identity_scalar(op, v.dtype)
                    contrib = (jnp.max if op == "max" else jnp.min)(
                        jnp.where(onehot, v[:, None], ident), axis=0)
                o_ref[col, :] = op_fn(o_ref[col, :], contrib)

    out_specs = tuple(pl.BlockSpec((w, Sp), lambda t: (0, 0))
                      for w in widths)
    outs = _pallas_call(
        kernel, row2, *ins,
        grid=(Bp // LANE_TILE,),
        in_specs=[pl.BlockSpec((1, LANE_TILE), lambda t: (0, t))]
        + [pl.BlockSpec((w, LANE_TILE), lambda t: (0, t))
           for w in widths],
        out_specs=out_specs,
        out_shape=tuple(jax.ShapeDtypeStruct((w, Sp), l.dtype)
                        for w, l in zip(widths, leaves)),
        interpret=interpret,
    )
    if not isinstance(outs, (list, tuple)):
        outs = (outs,)
    tables = []
    for o, l, w in zip(outs, leaves, widths):
        tables.append(o[0, :S] if l.ndim == 1 else o[:, :S].T)
    return tables
