"""Device-side key compaction: the dense fast path for arbitrary keys.

The declared-monoid dense reduce is one scatter-combine pass where the
sorted arbitrary-key path pays an argsort and a whole-record scan — a
path only ``withMaxKeys`` users could reach, because the dense
scatter-combine tables need a bounded key space.  This module closes the gap for UNDECLARED int32 key
spaces with a **device-resident key→dense-slot remap table** (the
Julia-GPU-primitives stance: keep fully generic operators on the
specialized fast path via a runtime remap):

* **Remap table.**  ``KeyCompactor`` owns a host dict ``key → stable
  slot`` mirrored on device as two arrays: ``table_keys`` (the admitted
  keys, sorted, sentinel-padded) and ``table_slots`` (the stable slot of
  each sorted position).  Lookup inside a compiled program is one
  ``searchsorted`` + gather; the sorted/slot indirection keeps slots
  STABLE across admissions (a new key shifts sorted positions, never
  slots), which is what lets stateful/FFAT state tables index by slot
  across batches.

* **Hot path, cold tail.**  A compacted ReduceTPU step scatter-combines
  remapped lanes into a dense ``[slots]`` monoid table and routes the
  remaining (miss) lanes through the EXISTING sorted segmented reduce —
  over a ``capacity//32`` overflow buffer when they fit (the common
  case), over the full batch under ``lax.cond`` when they do not
  (adversarial all-cold streams stay correct at sorted-path speed).
  Both halves run inside the consumer's one program: zero extra
  dispatches, and the merged output is bit-identical to the sorted
  path's (ascending distinct keys compacted to the front — see
  :func:`make_compacted_reduce`).

* **Seeding.**  Admission is host-driven where keys are host-visible
  anyway (the keyed staging emitter's key column, the staging probes) —
  steady state admits nothing and pays nothing.  Where keys are
  device-born (TPU→TPU edges, fused chains), the step's donated stats
  operand carries a miss-candidate ring (the PR 9 sketch pattern) and
  the reseed cadence folds it — together with the shard plane's
  count-min/hot-key candidates — into the table, evicting the coldest
  slots on a full table (the ``churn`` counter; pinned compactors for
  stateful/FFAT state never evict).

``Config.key_compaction`` / ``WF_TPU_KEY_COMPACTION=0`` is the kill
switch: no compactor attaches and every step keeps one ``is not None``
check (micro-asserted by tests/test_key_compaction.py).  Reserved key:
``INT32_MAX`` is the table's sentinel — a record keyed exactly 2^31-1
rides the overflow/sorted lane on reduce/stateful (never wrong, never
fast).  Compacted FFAT windows have NO overflow lane: a sentinel-keyed
record there follows the never-admitted-key contract (lanes masked and
counted — ``sentinel_rejects`` in the summary names the cause); declare
``withMaxKeys`` instead if INT32_MAX is a live key in your stream.
"""

from __future__ import annotations

import threading
from typing import Optional

import numpy as np

from windflow_tpu.basic import WindFlowError, int32_key

#: table sentinel: pads the sorted key array; a REAL key equal to it is
#: never admitted (its lanes take the overflow/sorted path)
KEY_SENTINEL = np.int32(2**31 - 1)
_SENT = int(KEY_SENTINEL)     # plain-int twin for the scalar hot path
#: miss-candidate ring geometry (the shard ledger's candidate pattern)
MISS_RING = 64
MISS_PER_BATCH = 8
#: overflow lane budget as a fraction of batch capacity: misses beyond
#: it take the full-width sorted fallback under lax.cond (rare).  The
#: lane's sort/merge cost scales with its width — on the CPU bench box
#: halving it from capacity//16 to //32 cut the with-miss step 22.6 →
#: 20.0 ms — so the budget is sized for a COLD TAIL (a batch missing
#: more than ~3% isn't hot-set shaped and belongs on the sorted
#: fallback until the reseed cadence catches up)
OVERFLOW_DENOM = 32


def overflow_cap(capacity: int) -> int:
    return min(capacity, max(32, capacity // OVERFLOW_DENOM))


# ---------------------------------------------------------------------------
# traced pieces (imported lazily into consumer programs — never at import)
# ---------------------------------------------------------------------------

def lookup_slots(table_keys, table_slots, keys, valid):
    """In-program remap lookup: ``(slot, hit)`` for an int32 key lane.
    ``slot`` carries the table size for misses (the stateful bodies'
    ignore sentinel); pad positions carry slot == size, so a user key
    colliding with the sentinel value reads as a miss, never a hit."""
    import jax.numpy as jnp
    size = int(table_keys.shape[0])
    k32 = keys.astype(jnp.int32)
    # scan_unrolled: ~3x cheaper than the default scan lowering for a
    # wide query lane over a small table (measured on the CPU bench box)
    pos = jnp.clip(jnp.searchsorted(table_keys, k32,
                                    method="scan_unrolled"), 0, size - 1)
    cand = table_slots[pos]
    hit = valid & (table_keys[pos] == k32) & (cand < size)
    return jnp.where(hit, cand, jnp.int32(size)), hit


def slots_to_user_keys(key_lane, table_keys, table_slots):
    """Traced inverse remap: fired records carry the SLOT in their
    "key" lane — map it back through the inverse table so downstream
    sees the user's keys, not the remap's internals (the extra T+1 row
    absorbs the sentinel-pad scatter writes)."""
    import jax.numpy as jnp
    T = int(table_keys.shape[0])
    inv = jnp.zeros(T + 1, table_keys.dtype).at[table_slots].set(
        table_keys, mode="drop")
    return inv[jnp.clip(key_lane, 0, T)].astype(key_lane.dtype)


def cstats_init():
    """Fresh on-device compaction stats state for one program site: the
    hit/miss counters plus the miss-candidate ring the reseed cadence
    reads.  One donated operand — the PR 9 sketch pattern."""
    import jax.numpy as jnp
    return {
        "hits": jnp.zeros((), jnp.int64),
        "misses": jnp.zeros((), jnp.int64),
        "batches": jnp.zeros((), jnp.int32),
        "big": jnp.zeros((), jnp.int64),
        "cand": jnp.full(MISS_RING, np.iinfo(np.int32).min, jnp.int32),
    }


def cstats_update(st, keys, hit, miss, big=None):
    """Traced stats update: counters plus a strided sample of MISS keys
    into the ring — a key carrying x% of the un-remapped stream appears
    among the candidates with probability ~x per batch, so the reseed
    cadence catches a shifted hot set with near-certainty.  The sample
    offset rotates with the batch counter: a fixed stride over a
    periodic key layout would alias onto one phase of the stream and
    never see the others."""
    import jax
    import jax.numpy as jnp
    k32 = keys.astype(jnp.int32)
    cap = int(k32.shape[0])
    c = min(MISS_PER_BATCH, cap)
    stride = max(1, cap // c)
    idx = (st["batches"] * jnp.int32(7)
           + jnp.int32(stride) * jnp.arange(c, dtype=jnp.int32)) \
        % jnp.int32(cap)
    cand_new = jnp.where(miss[idx], k32[idx],
                         jnp.int32(np.iinfo(np.int32).min))
    slots = max(1, MISS_RING // c)
    start = (st["batches"] % jnp.int32(slots)) * jnp.int32(c)
    cand = jax.lax.dynamic_update_slice(st["cand"], cand_new, (start,))
    return {
        "hits": st["hits"] + jnp.sum(hit, dtype=jnp.int64),
        "misses": st["misses"] + jnp.sum(miss, dtype=jnp.int64),
        "batches": st["batches"] + 1,
        "big": st["big"] + (jnp.zeros((), jnp.int64) if big is None
                            else big.astype(jnp.int64)),
        "cand": cand,
    }


def _pack_ok(dtype) -> bool:
    """True when a leaf dtype maps order-isomorphically into an int64
    carrier (the packed one-scatter dense combine under max/min)."""
    import jax.numpy as jnp
    dt = jnp.dtype(dtype)
    if dt == jnp.bool_ or dt in (jnp.dtype(jnp.float32),
                                 jnp.dtype(jnp.float64)):
        return True
    if jnp.issubdtype(dt, jnp.signedinteger):
        return True
    # unsigned fits the signed carrier only below 64 bits
    return jnp.issubdtype(dt, jnp.unsignedinteger) and dt.itemsize < 8


def _enc64(x):
    """Order-preserving map of one supported leaf into int64.  Floats
    use the sign-folded bitcast (exact, bijective — the scatter then
    compares INTEGERS, no float arithmetic at all); -0.0 folds onto
    +0.0 (equal under max/min) and NaNs have no total-order home, so
    packing is only used on NaN-free streams (the monoid-combiner
    contract already excludes them)."""
    import jax
    import jax.numpy as jnp
    dt = jnp.dtype(x.dtype)
    if dt == jnp.dtype(jnp.float32):
        bi = jax.lax.bitcast_convert_type(x, jnp.int32).astype(jnp.int64)
        return jnp.where(bi >= 0, bi, jnp.int64(-2**31) - bi)
    if dt == jnp.dtype(jnp.float64):
        bi = jax.lax.bitcast_convert_type(x, jnp.int64)
        # I64MIN - bi wraps (two's complement) — still bijective
        return jnp.where(bi >= 0, bi,
                         jnp.int64(np.iinfo(np.int64).min) - bi)
    return x.astype(jnp.int64)


def _dec64(c, dtype):
    """Inverse of :func:`_enc64` for one carrier column."""
    import jax
    import jax.numpy as jnp
    dt = jnp.dtype(dtype)
    if dt == jnp.dtype(jnp.float32):
        bi = jnp.where(c >= 0, c, jnp.int64(-2**31) - c).astype(jnp.int32)
        return jax.lax.bitcast_convert_type(bi, jnp.float32)
    if dt == jnp.dtype(jnp.float64):
        bi = jnp.where(c >= 0, c,
                       jnp.int64(np.iinfo(np.int64).min) - c)
        return jax.lax.bitcast_convert_type(bi, jnp.float64)
    return c.astype(dt)


def make_compacted_reduce(capacity: int, table_size: int, monoid: str,
                          comb, key_fn, prelude, bounded: bool,
                          pallas=None, owner: str = "reduce"):
    """Build the compacted keyed-reduce program body (``owner``: the
    operator's name, its ``wf.op.<name>`` scope in a device trace).

    ``(keys, payload, ts, valid[, table_keys, table_slots], cstats) ->
    (out_payload, out_ts, out_valid, cstats')`` — remapped lanes
    scatter-combine into a dense ``[table_size]`` monoid table, miss
    lanes run the sorted segmented reduce (over the ``capacity//32``
    overflow buffer, or the full batch under ``lax.cond`` when they
    exceed it), and the two result sets merge by key RANK (two
    ``searchsorted`` passes over already-sorted key lists — no extra
    sort) into exactly the sorted path's output contract: distinct keys
    ascending, compacted to the front of a ``[capacity]`` batch, zero
    padding.  Bit-identical to ``_segmented_reduce`` whenever the
    declared monoid matches the combiner exactly (the existing
    ``withMonoidCombiner`` contract).

    ``bounded`` is the declared-``withMaxKeys`` variant: the remap is
    the identity over ``[0, max_keys)`` (no table operands) and
    out-of-range keys ride the overflow lane instead of being dropped —
    the retirement of the PR 1 silent-drop/RuntimeWarning path.

    ``pallas`` (a resolved :class:`windflow_tpu.kernels.PallasMode`):
    the dense half's one-scatter combine re-tiles through the Pallas
    segmented-reduce kernel where its gates hold — the packed int64
    carrier rides as one multi-column leaf, per-leaf scatters route
    per leaf — traced into this same program, bit-identical output
    (all-integer folds on the packed path)."""
    import jax
    import jax.numpy as jnp

    from windflow_tpu.monitoring.recorder import operator_scope, phase
    from windflow_tpu.ops.tpu import _bshape, _segmented_reduce
    from windflow_tpu.windows.ffat_kernels import (_monoid_identity,
                                                   _monoid_scatter)
    T = int(table_size)
    ovf = overflow_cap(capacity)
    I64MAX = jnp.int64(np.iinfo(np.int64).max)
    I64MIN = jnp.int64(np.iinfo(np.int64).min)

    def body(keys, payload, ts, valid, *rest):
        if prelude is not None:
            # whole-chain fusion: the stateless members run inside this
            # same program and keys re-extract from its output — the
            # remap operands thread through the fused program unchanged
            payload, valid = prelude(payload, valid)
            keys = None
        with operator_scope(owner):
            if keys is None:
                with phase("wf.fn"):
                    keys = jax.vmap(key_fn)(payload).astype(jnp.int32)
            with phase("wf.reduce"):
                return fold(keys.astype(jnp.int32), payload, ts, valid,
                            *rest)

    def fold(keys, payload, ts, valid, *rest):
        if bounded:
            (cst,) = rest
            table_keys = table_slots = None
        else:
            table_keys, table_slots, cst = rest
        if bounded:
            hit = valid & (keys >= 0) & (keys < T)
            slot = keys
        else:
            slot, hit = lookup_slots(table_keys, table_slots, keys, valid)
        miss = valid & ~hit
        n_miss = jnp.sum(miss)

        # -- dense half: scatter-combine pass(es) into the [T] table ----
        # miss/invalid lanes route to dump row T (sliced off), so the
        # scatters take the RAW leaves — no per-leaf identity select.
        # The ts max-scatter doubles as the liveness bit: rows still at
        # the init identity received no lane this batch.  Lane ts of
        # exactly INT64_MIN is clamped up by one so a live row can never
        # read as dead — the one reserved ts value, documented beside
        # KEY_SENTINEL.
        row = jnp.where(hit, slot, jnp.int32(T))
        sts = jnp.maximum(ts, I64MIN + 1)
        leaves, treedef = jax.tree_util.tree_flatten(payload)
        packed = monoid in ("max", "min") and all(
            _pack_ok(l.dtype) for l in leaves)
        if packed:
            # ONE variadic-width scatter: every leaf encodes
            # order-isomorphically into int64 carrier columns
            # (scatter cost is dominated by per-index bookkeeping, not
            # update width — measured ~2.4x over per-leaf scatters on
            # the CPU bench box), and the ts max + liveness ride the
            # same pass as one extra column (negated under "min" so the
            # ts fold stays a MAX).  The "min" side needs one MORE
            # reserved ts value than the shared +1 clamp above:
            # -(I64MIN+1) == I64MAX IS the min identity, so a lane ts
            # of exactly I64MIN+1 would read its row back as dead —
            # clamp to I64MIN+2 before negating.
            tcol = sts if monoid == "max" \
                else -jnp.maximum(sts, I64MIN + 2)
            cols = [_enc64(l).reshape((capacity, -1)) for l in leaves]
            widths = [int(c.shape[1]) for c in cols]
            upd = jnp.concatenate(cols + [tcol[:, None]], axis=1)
            ident = I64MIN if monoid == "max" else I64MAX
            tbl = None
            if pallas is not None:
                from windflow_tpu import kernels as pk
                if pk.table_supported(capacity, T) \
                        and pk.table_leaf_ok(upd.shape, upd.dtype,
                                             pallas.interpret):
                    # Pallas segmented reduce over the packed carrier:
                    # all-integer masked folds — bit-identical to the
                    # variadic scatter
                    tbl = pk.dense_monoid_table(
                        row, [upd], [monoid], [ident], T,
                        pallas.interpret)[0]
            if tbl is None:
                buf = jnp.full((T + 1, int(upd.shape[1])), ident,
                               jnp.int64)
                tbl = _monoid_scatter(buf.at[row], monoid)(upd)[:T]
            has = tbl[:, -1] != ident
            ts_t = jnp.where(has, tbl[:, -1] if monoid == "max"
                             else -tbl[:, -1], I64MIN)
            outs, off = [], 0
            for leaf, w in zip(leaves, widths):
                col = tbl[:, off:off + w].reshape((T,) + leaf.shape[1:])
                outs.append(_dec64(col, leaf.dtype))
                off += w
            table = jax.tree_util.tree_unflatten(treedef, outs)
        else:
            # "sum" (or an unpackable leaf dtype): per-leaf scatters,
            # re-tiled through the Pallas kernel leaf by leaf where its
            # shape/dtype gates hold
            def scat(leaf):
                ident = _monoid_identity(monoid, leaf.dtype)
                buf = jnp.full((T + 1,) + leaf.shape[1:], ident,
                               leaf.dtype)
                return _monoid_scatter(buf.at[row], monoid)(leaf)[:T]

            def lax_ts():
                return jnp.full(T + 1, I64MIN, jnp.int64).at[row].max(
                    sts)[:T]

            routed = None
            if pallas is not None:
                from windflow_tpu import kernels as pk
                routed = pk.routed_monoid_tables(
                    row, payload, monoid, T, pallas.interpret,
                    lax_leaf=scat, ts=sts, ts_init=int(I64MIN),
                    lax_ts=lax_ts)
            if routed is not None:
                table, ts_t, _ = routed
            else:
                table = jax.tree.map(scat, payload)
                ts_t = lax_ts()
            has = ts_t != I64MIN

        # key-ascending view of the dense table: bounded slots ARE keys;
        # unbounded gathers slot rows by sorted-key position
        if bounded:
            dvals, dts, dhas = table, ts_t, has
            dkeys = jnp.arange(T, dtype=jnp.int64)
        else:
            perm = jnp.minimum(table_slots, jnp.int32(T - 1))
            live = table_slots < T
            dvals = jax.tree.map(lambda a: a[perm], table)
            dts = ts_t[perm]
            dhas = has[perm] & live
            dkeys = table_keys.astype(jnp.int64)

        n_d = jnp.sum(dhas)
        # gather-based compaction: ONE nonzero yields the live-row index
        # list, every leaf follows with a cheap gather — scatters are
        # serialized on CPU/TPU scalar cores, gathers vectorize, and the
        # index list amortizes across all leaves
        didx = jnp.nonzero(dhas, size=T, fill_value=0)[0]
        dlive = jnp.arange(T) < n_d

        def dcompact(a):
            return jnp.where(_bshape(dlive, a[didx]), a[didx],
                             jnp.zeros_like(a[didx]))

        cvals = jax.tree.map(dcompact, dvals)
        cts = dcompact(dts)
        ckeys = jnp.where(dlive, dkeys[didx], I64MAX)

        big = n_miss > ovf

        def no_miss(_):
            # all-hit batch (the steady state of a warm table over a
            # bounded hot set): the dense half IS the answer — skip the
            # overflow reduce and the rank merge entirely; lax.cond
            # executes only the taken branch at runtime, so the batch
            # pays lookup + dense scatter and nothing else
            def padd(a):
                if capacity <= T:
                    return a[:capacity]
                return jnp.concatenate(
                    [a, jnp.zeros((capacity - T,) + a.shape[1:],
                                  a.dtype)])

            return (jax.tree.map(padd, cvals), padd(cts),
                    jnp.arange(capacity) < n_d)

        def merge(okeys, ovals, ots, ovalid):
            # rank merge: two sorted, disjoint key lists interleave by
            # searchsorted rank — the output IS the sorted path's
            # layout.  The merge scatters the INDEX lanes once (int32,
            # T + W updates where W is the overflow lane's width — NOT
            # capacity-many), then every leaf gathers through the
            # merged index: 2 scatters total instead of 2 per leaf.
            W = int(okeys.shape[0])
            okeys_s = jnp.where(ovalid, okeys, I64MAX)
            n_o = jnp.sum(ovalid)
            drank = jnp.arange(T) + jnp.searchsorted(
                okeys_s, ckeys, method="scan_unrolled")
            orank = jnp.arange(W) + jnp.searchsorted(
                ckeys, okeys_s, method="scan_unrolled")
            dpos = jnp.where(dlive, drank, capacity)
            opos = jnp.where(ovalid, orank, capacity)
            gidx = jnp.zeros(capacity + 1, jnp.int32)
            gidx = gidx.at[dpos].set(
                jnp.arange(T, dtype=jnp.int32), mode="drop")
            gidx = gidx.at[opos].set(
                jnp.arange(W, dtype=jnp.int32) + T,
                mode="drop")[:capacity]
            out_valid = jnp.arange(capacity) < (n_d + n_o)

            def pick(src_d, src_o):
                src = jnp.concatenate([src_d, src_o], axis=0)
                g = src[gidx]
                return jnp.where(_bshape(out_valid, g), g,
                                 jnp.zeros_like(g))

            return (jax.tree.map(pick, cvals, ovals), pick(cts, ots),
                    out_valid)

        # -- overflow half: the cold tail on the existing sorted lane.
        # The common case gathers the misses into a [capacity//32]
        # buffer and sorts/merges at THAT width; the adversarial
        # all-cold batch falls back to the full-width sorted reduce
        # under the nested cond (sorted-path speed, never wrong).
        def ovf_small(_):
            # gather-only miss compaction: the j-th miss lives at the
            # first index whose running miss count reaches j+1 — a
            # binary search over the cumsum instead of jnp.nonzero's
            # full-width scatter lowering (~10x cheaper at this shape)
            cs = jnp.cumsum(miss.astype(jnp.int32))
            midx = jnp.minimum(
                jnp.searchsorted(cs, jnp.arange(1, ovf + 1,
                                                dtype=jnp.int32),
                                 method="scan_unrolled"),
                capacity - 1)
            mvalid = jnp.arange(ovf) < n_miss
            ok, op_, ots, ov = _segmented_reduce(
                keys[midx], jax.tree.map(lambda a: a[midx], payload),
                ts[midx], mvalid, comb, ovf)
            return merge(ok, op_, ots, ov)

        def ovf_big(_):
            return merge(*_segmented_reduce(keys, payload, ts, miss,
                                            comb, capacity))

        def with_miss(_):
            return jax.lax.cond(big, ovf_big, ovf_small, None)

        out_payload, out_ts, out_valid = jax.lax.cond(
            n_miss == 0, no_miss, with_miss, None)
        cst = cstats_update(cst, keys, hit, miss, big=big)
        return out_payload, out_ts, out_valid, cst

    return body


# ---------------------------------------------------------------------------
# the host-side compactor
# ---------------------------------------------------------------------------

class _PinnedFull(Exception):
    """Internal admission signal: a full pinned table whose consumer
    has a lossless host-interning escape (never escapes observe*)."""


class KeyCompactor:
    """Key→dense-slot remap for ONE compacted consumer operator.

    Host state is the authoritative ``key → stable slot`` dict plus the
    sorted/slot mirror arrays; ``dev_keys``/``dev_slots`` are their
    device copies, passed into the consumer's program as plain operands
    (rebuilt only on admission — steady state re-passes the same
    arrays).  ``pinned`` compactors (stateful/FFAT: slots index live
    per-key STATE) never evict; on a FULL pinned table an
    ``intern_fallback`` compactor deactivates so the consumer adopts
    the mapping into its host interner, which raises its own
    ``num_key_slots`` error on the overflowing key (the lossless
    contract), while a plain pinned table (FFAT) counts
    ``full_rejects`` and the consumer masks + counts the key's lanes —
    the operator's documented out-of-range contract.  Evictable
    compactors (per-batch reduces) recycle the coldest slots at reseed
    cadence — the ``churn`` counter — which is safe because a reduce's
    dense table is rebuilt every batch.  Thread-safety: sibling host
    emitter replicas of a parallel upstream drain CONCURRENTLY on the
    worker pool (the ShardSketch scenario), so admission, reseed,
    restore and the table/placement reads all hold ``_lock``;
    ``summary()`` may run from the monitor thread and only reads."""

    def __init__(self, slots: int, *, pinned: bool = False,
                 bounded: bool = False, reseed_every: int = 64,
                 placement_override: bool = False,
                 intern_fallback: bool = False,
                 name: str = "") -> None:
        self.slots = int(slots)
        self.pinned = pinned
        #: declared-withMaxKeys mode: the remap is the identity over
        #: [0, max_keys) — no table, the compactor only carries the
        #: stats surface and the overflow-reroute contract
        self.bounded = bounded
        self.reseed_every = max(1, int(reseed_every))
        #: keyby routing override: slotted keys place by ``slot % n``
        #: (balances hot keys deterministically); safe ONLY for
        #: per-batch consumers — moving a key between replicas
        #: mid-stream would break per-key order for stateful state
        self.placement_override = placement_override
        #: the consumer has a lossless host-interning fallback (stateful
        #: slot tables): a SENTINEL-valued user key (exactly 2^31-1,
        #: inadmissible by construction) deactivates the compactor so
        #: the consumer keeps the legacy path instead of dropping the
        #: record — a compacted REDUCE needs no such escape, its
        #: overflow lane already keeps sentinel-keyed records correct
        self.intern_fallback = intern_fallback
        self.name = name
        #: False after a host observation path failed (speculative
        #: extractor probe): consumers fall back to their legacy path
        self.active = True
        self._lock = threading.Lock()
        self._key_slot: dict = {}
        self._free = list(range(self.slots - 1, -1, -1))
        self._tk = np.full(self.slots, KEY_SENTINEL, np.int32)
        self._tsl = np.full(self.slots, self.slots, np.int32)
        self._dev = None          # (dev_keys, dev_slots) jnp mirrors
        self.admits = 0
        self.churn = 0
        self.reseeds = 0
        self.full_rejects = 0     # evictable table full at observe time
        self.sentinel_rejects = 0  # real keys == KEY_SENTINEL seen
        self._batches = 0
        self._sketch = None       # shard-plane ShardSketch (seeding)
        self._stats_getters = []  # device cstats sites (merge at read)

    # -- wiring --------------------------------------------------------------
    def bind_sketch(self, sketch) -> None:
        self._sketch = sketch

    def register_device_stats(self, getter) -> None:
        """Register one program site's live (cumulative, donated) cstats
        state getter; merged fresh at every summary/reseed read."""
        self._stats_getters.append(getter)

    # -- device mirrors ------------------------------------------------------
    def _rebuild(self) -> None:
        n = len(self._key_slot)
        tk = np.full(self.slots, KEY_SENTINEL, np.int32)
        tsl = np.full(self.slots, self.slots, np.int32)
        if n:
            ks = np.fromiter(self._key_slot.keys(), np.int32, count=n)
            sl = np.fromiter(self._key_slot.values(), np.int32, count=n)
            order = np.argsort(ks, kind="stable")
            tk[:n] = ks[order]
            tsl[:n] = sl[order]
        self._tk, self._tsl = tk, tsl
        self._dev = None          # re-uploaded lazily at next table read

    def tables(self):
        """The (table_keys, table_slots) device operands for this batch;
        uploaded only when admission changed the table.  The upload
        holds the lock so a sibling replica's mid-``_rebuild`` state
        can never pair a new key table with stale slots."""
        dev = self._dev
        if dev is None:
            import jax.numpy as jnp
            with self._lock:
                dev = self._dev
                if dev is None:
                    dev = self._dev = (jnp.asarray(self._tk),
                                       jnp.asarray(self._tsl))
        # returned from the LOCAL: a concurrent admission's _rebuild()
        # nulls self._dev, and a bare `return self._dev` could hand the
        # consumer step None between the check and the return
        return dev

    # -- admission (host-visible key paths) ----------------------------------
    def _admit(self, k32: int) -> bool:
        if k32 == int(KEY_SENTINEL):
            # reserved: rides the overflow lane (reduce/stateful);
            # compacted FFAT has NO overflow lane — its lanes are
            # masked + counted, so make the reserved-key encounter
            # visible instead of a bare False
            self.sentinel_rejects += 1
            return False
        if k32 in self._key_slot:
            return False
        if not self._free:
            if self.pinned and self.intern_fallback:
                # full pinned table with a lossless host-interning
                # escape: signal the caller to deactivate, so the
                # consumer adopts the mapping and the INTERNER raises
                # its num_key_slots error on this very key — the
                # record is never silently masked
                raise _PinnedFull
            self.full_rejects += 1
            return False          # evictable: reseed may recycle a
            # colder slot later; plain pinned (FFAT): the consumer
            # masks + counts the key's lanes (its out-of-range contract)
        self._key_slot[k32] = self._free.pop()
        self.admits += 1
        return True

    def observe(self, keys: np.ndarray) -> None:
        """Bulk host admission from a materialized key column (the keyed
        staging emitter / staging probes): new keys get slots BEFORE the
        batch ships, so host-fed consumers see a miss-free remap."""
        if not self.active:
            return
        u = np.unique(np.asarray(keys).astype(np.int64).astype(np.int32))
        if self.intern_fallback and u.size and u[-1] == KEY_SENTINEL:
            self.deactivate()   # sorted unique: the sentinel is last
            return
        full = False
        with self._lock:
            changed = False
            for k in u:
                try:
                    changed |= self._admit(int(k))
                except _PinnedFull:
                    full = True
                    break
            if changed:
                # keys admitted BEFORE the table filled still reach the
                # device mirror — their records stay on the fast path
                self._rebuild()
        if full:
            self.deactivate()   # consumer adopts the mapping; its
            # interner raises the num_key_slots error on this batch

    def observe_one(self, k32: int) -> None:
        """Scalar admission for the per-tuple emit path: pure int ops
        and a LOCK-FREE dict read in the admitted steady state (the
        emitter's no-FFI-no-allocation-per-tuple contract) — only a
        genuinely new key takes the lock."""
        if not self.active:
            return
        k = int32_key(k32)          # canonical int32 wrap, numpy-free
        if k == _SENT:
            if self.intern_fallback:
                self.deactivate()
            else:
                self.sentinel_rejects += 1
            return
        if k in self._key_slot:
            return              # steady state: GIL-atomic dict read
        if not self._free and not (self.pinned and self.intern_fallback):
            # full table: admission cannot seat the key (only the
            # reseed cadence can recycle a slot), so the per-tuple
            # path stays LOCK-FREE — a cold tail over a full table
            # must not serialize sibling emitters on the compactor
            # lock.  _free only ever shrinks outside restore(), so
            # the unlocked read is stable; the counter is telemetry
            # (racy increments acceptable).
            self.full_rejects += 1
            return
        try:
            with self._lock:
                if self._admit(k):
                    self._rebuild()
        except _PinnedFull:
            self.deactivate()

    def deactivate(self) -> None:
        """Host observation failed (speculative probe): consumers fall
        back to their legacy path at the next step check."""
        self.active = False

    def export_mapping(self) -> dict:
        """key → slot, for a consumer falling back to host interning
        after deactivation (the state table rows keyed by these slots
        must keep meaning the same keys)."""
        with self._lock:
            return dict(self._key_slot)

    # -- placement -----------------------------------------------------------
    def slot_of(self, k32: int) -> Optional[int]:
        return self._key_slot.get(int(np.int32(k32)))

    def place_np(self, keys: np.ndarray, n_dests: int):
        """Vectorized keyby placement with the remap override: slotted
        keys go to ``slot % n`` (hot keys balanced deterministically),
        the cold tail keeps the splitmix placement.  Returns the
        per-lane destination array."""
        from windflow_tpu.monitoring.shard_ledger import _splitmix64_np
        k = np.asarray(keys, np.int64)
        k32 = k.astype(np.int32)
        with self._lock:
            # consistent (tk, tsl, n) snapshot: _rebuild replaces the
            # arrays wholesale under the same lock, never in place
            tk, tsl, n = self._tk, self._tsl, len(self._key_slot)
        pos = np.searchsorted(tk[:max(1, n)], k32)
        pos = np.clip(pos, 0, max(0, n - 1))
        found = (n > 0) & (tk[pos] == k32) & (tsl[pos] < self.slots)
        slot = tsl[pos].astype(np.int64)
        h = (_splitmix64_np(k) % np.uint64(n_dests)).astype(np.int64)
        return np.where(found, slot % n_dests, h).astype(np.intp)

    def place_one(self, k32: int, n_dests: int) -> Optional[int]:
        s = self.slot_of(k32)
        return None if s is None else s % n_dests

    # -- reseed cadence ------------------------------------------------------
    def on_batch(self) -> None:
        """Per-consumer-step hook: counts batches and reseeds the table
        from the sketch + miss-ring candidates on the configured
        cadence (the only device sync the plane pays)."""
        self._batches += 1
        if self._batches % self.reseed_every == 0 and not self.bounded:
            self.reseed()

    def _miss_candidates(self) -> list:
        out = []
        sentinel = np.iinfo(np.int32).min
        for getter in self._stats_getters:
            try:
                st = getter()
                if st is None:
                    continue
                ring = np.asarray(st["cand"], np.int64)
            except Exception:  # lint: broad-except-ok (the cstats state
                # is a DONATED program operand: a read racing the
                # in-flight dispatch sees a deleted array — skip this
                # site for THIS read, the next cadence sees fresh state)
                continue
            out.extend(int(k) for k in ring if k != sentinel)
        return out

    def reseed(self) -> None:
        """Fold the shard sketch's hot candidates and the in-program
        miss rings into the table.  Pinned tables only admit; evictable
        tables recycle their coldest slots for hotter candidates (the
        churn counter counts each recycled slot)."""
        self.reseeds += 1
        cands = self._miss_candidates()
        est = {}
        if self._sketch is not None:
            try:
                for k, e in self._sketch.hot_candidates(self.slots):
                    est[int(np.int32(int(k)))] = int(e)
            except Exception:  # lint: broad-except-ok (sketch reads
                # merge donated device states — telemetry seeding
                # degrades to the miss ring, never takes the step down)
                pass
        for k in cands:
            # miss-ring candidates carry no CMS estimate — plain 0:
            # admitted only while slots are free, never able to clear
            # the 2x eviction hysteresis on a full table
            est.setdefault(k, 0)
        with self._lock:
            fresh = [k for k in est
                     if k not in self._key_slot
                     and k != int(KEY_SENTINEL)]
            if not fresh:
                return
            fresh.sort(key=lambda k: est.get(k, 0), reverse=True)
            changed = False
            residents = None
            ri = 0
            for k in fresh:
                if self._free:
                    changed |= self._admit(k)
                    continue
                if self.pinned:
                    break         # pinned tables never evict live state
                if residents is None:
                    # ONE estimation pass over the residents, coldest
                    # first — candidates walk it hottest-first, so the
                    # merge is two pointers, not O(slots^2) estimates
                    # inline on the consumer step path
                    residents = self._resident_coldness()
                if residents is None or ri >= len(residents):
                    break         # no estimates / nothing left to evict
                cold_est, coldest = residents[ri]
                if est.get(k, 0) < 2 * max(1, cold_est):
                    # 2x hysteresis against sketch noise; candidates
                    # are sorted hottest-first, so nothing later clears
                    break
                ri += 1
                changed = True
                self._key_slot[k] = self._key_slot.pop(coldest)
                self.admits += 1
                self.churn += 1
            if changed:
                self._rebuild()

    def _resident_coldness(self) -> Optional[list]:
        """``(estimate, key)`` for every resident key, coldest first —
        the eviction order one reseed consumes.  None blocks eviction
        (no sketch, or estimation failed this round)."""
        if self._sketch is None or not self._key_slot:
            return None
        out = []
        for k in self._key_slot:
            try:
                out.append((self._sketch._estimate(k), k))
            except Exception:  # lint: broad-except-ok (exact-histogram
                # sketches carry no CMS; estimation failure just blocks
                # eviction this round)
                return None
        out.sort()
        return out

    # -- read path -----------------------------------------------------------
    def summary(self) -> dict:
        """Merged host + device counters for ``stats()["Shard"]`` /
        ``dump_stats``: hit rate, overflow share, churn, occupancy."""
        hits = misses = big = 0
        batches = 0
        for getter in self._stats_getters:
            try:
                st = getter()
                if st is None:
                    continue
                hits += int(st["hits"])
                misses += int(st["misses"])
                big += int(st["big"])
                batches += int(st["batches"])
            except Exception:  # lint: broad-except-ok (donated operand
                # read racing the in-flight dispatch — skip the site
                # for this read, same stance as the sketch merge)
                continue
        total = hits + misses
        out = {
            "slots": self.slots,
            "occupied": len(self._key_slot),
            "pinned": self.pinned,
            "bounded": self.bounded,
            "batches": batches,
            "tuples": total,
            "hit_rate": round(hits / total, 4) if total else None,
            "overflow_share": round(misses / total, 4) if total else None,
            "overflow_tuples": misses,
            "big_fallbacks": big,
            "admits": self.admits,
            "churn": self.churn,
            "churn_per_sweep": round(self.churn / batches, 4)
            if batches else 0.0,
            "reseeds": self.reseeds,
            "placement_override": self.placement_override,
        }
        if self.full_rejects:
            out["full_rejects"] = self.full_rejects
        if self.sentinel_rejects:
            out["sentinel_rejects"] = self.sentinel_rejects
        if not self.active:
            out["deactivated"] = True
        return out

    # -- durable state (windflow_tpu/durability) -----------------------------
    def snapshot(self) -> dict:
        """The remap IS operator state: a restored stateful/FFAT table
        indexes rows by these slots, so replays stay record-for-record."""
        with self._lock:
            return {
                "key_slot": dict(self._key_slot),
                "free": list(self._free),
                "admits": self.admits,
                "churn": self.churn,
                "reseeds": self.reseeds,
                "batches": self._batches,
                "active": self.active,
            }

    def restore(self, blob: dict) -> None:
        with self._lock:
            self._key_slot = {int(k): int(v)
                              for k, v in blob["key_slot"].items()}
            self._free = [int(s) for s in blob["free"]]
            self.admits = blob["admits"]
            self.churn = blob["churn"]
            self.reseeds = blob["reseeds"]
            self._batches = blob["batches"]
            self.active = blob["active"]
            self._rebuild()


# ---------------------------------------------------------------------------
# graph attachment (PipeGraph._build, after the shard plane)
# ---------------------------------------------------------------------------

def attach_compaction(graph) -> None:
    """Attach KeyCompactors to every qualifying keyed consumer and wire
    the feeding emitters for host admission / placement override.  Runs
    AFTER fusion and the shard plane (preludes installed, sketches
    attached, nothing compiled yet); with ``Config.key_compaction`` off
    this never runs and every step keeps one ``is not None`` check."""
    from windflow_tpu.fusion.executor import _upstream_edges
    from windflow_tpu.monitoring.shard_ledger import HostKeyProbe
    from windflow_tpu.ops.tpu import ReduceTPU
    from windflow_tpu.ops.tpu_stateful import _StatefulTPUBase
    from windflow_tpu.parallel.emitters import (DeviceKeyByEmitter,
                                                DeviceStageEmitter,
                                                DeviceToHostEmitter,
                                                KeyedDeviceStageEmitter,
                                                SplittingEmitter)
    from windflow_tpu.windows.ffat_tpu import FfatWindowsTPU

    cfg = graph.config
    slots = max(2, int(getattr(cfg, "key_compaction_slots", 1024)))
    reseed = max(1, int(getattr(cfg, "key_compaction_reseed", 64)))
    upstreams = _upstream_edges(graph)
    sketches = graph._shard._sketches if graph._shard is not None else {}

    def host_fed(op) -> bool:
        ups = upstreams.get(id(op))
        return bool(ups) and all(not u.is_tpu for u, _ in ups)

    for op in graph._operators:
        comp = None
        if isinstance(op, ReduceTPU):
            if op.key_extractor is None:
                continue
            if op.mesh is not None:
                if op.max_keys is None:
                    # arbitrary-key mesh reduce: the remap overrides the
                    # owner hash (hot keys balanced over chips); the
                    # per-chip sort path itself is unchanged
                    comp = KeyCompactor(slots, reseed_every=reseed,
                                        placement_override=True,
                                        name=op.name)
            elif op.monoid is not None:
                bounded = op.max_keys is not None
                comp = KeyCompactor(
                    op.max_keys if bounded else slots,
                    bounded=bounded, reseed_every=reseed,
                    # slot%n placement balancing is per-batch-safe only,
                    # and meaningless for the identity (bounded) remap
                    placement_override=not bounded and op.parallelism > 1,
                    name=op.name)
        elif isinstance(op, _StatefulTPUBase):
            # device-resident interner: slots resolve in-program, so the
            # per-batch D2H intern sync disappears.  Requires every
            # feeding edge host-staged (admission sees every key before
            # its batch ships) and no fused prelude (post-prelude keys
            # are never host-visible).
            if op.dense_keys or op.mesh is not None \
                    or op._fused_prelude is not None \
                    or not host_fed(op) or len(op._interner):
                continue
            comp = KeyCompactor(op.num_key_slots, pinned=True,
                                reseed_every=reseed,
                                intern_fallback=True, name=op.name)
        elif isinstance(op, FfatWindowsTPU):
            if op.max_keys is not None or op.key_extractor is None:
                continue
            if op.mesh is not None:
                raise WindFlowError(
                    f"operator '{op.name}': compacted key spaces are "
                    "single-chip; declare withMaxKeys (divisible by the "
                    "key axis) for mesh execution")
            comp = KeyCompactor(slots, pinned=True, reseed_every=reseed,
                                name=op.name)
        if comp is None:
            continue
        comp.bind_sketch(sketches.get(id(op)))
        op.enable_compaction(comp)

    # emitter wiring: host admission + placement override, mirroring the
    # shard ledger's attach walk
    def visit(em):
        if em is None:
            return
        if isinstance(em, SplittingEmitter):
            for b in em.branches:
                visit(b)
            return
        if isinstance(em, DeviceToHostEmitter):
            visit(em.inner)
            return
        if not em.dests:
            return
        consumer = em.dests[0][0].op
        comp = consumer._compactor
        if comp is None or comp.bounded:
            return
        if isinstance(em, KeyedDeviceStageEmitter):
            # fused tails re-extract keys POST-prelude in-program
            # (make_compacted_reduce sets keys=None after the prelude);
            # host admission here would feed PRE-prelude keys into the
            # table — phantom entries the lookup never hits.  Reseeds
            # from the in-program post-prelude sketch still admit.
            if getattr(consumer, "_fused_prelude", None) is None:
                em._compactor = comp
        elif isinstance(em, DeviceKeyByEmitter):
            if comp.placement_override:
                em.attach_compactor(comp)
        elif isinstance(em, DeviceStageEmitter):
            kx = consumer.key_extractor
            if kx is not None and consumer._fused_prelude is None:
                if em._shard_probe is not None:
                    em._shard_probe.compactor = comp
                else:
                    em._shard_probe = HostKeyProbe(None, kx,
                                                   compactor=comp)

    for op in graph._operators:
        for rep in op.replicas:
            visit(rep.emitter)
