"""Shard plane: per-shard load/ICI attribution + key-skew sketches.

ROADMAP item 4 (elastic serving: "dynamic key re-sharding over ICI/DCN
on skew") assumes the hot shard can be *pinpointed* — but every gauge
shipped so far (watermark lag, queue depth, health verdicts, sweep-
ledger bytes) aggregates per OPERATOR: a keyed operator at parallelism
8 whose replica 3 holds the hot key shows one flat row, and skew stays
invisible until it becomes a stall.  This module is the measurement
plane a PR-10 resharding executor will act on (the PR 6 pattern: sweep
ledger → fusion advisor → fusion executor):

* **Key-skew sketches on the keyed edges.**  A fixed-size count-min
  sketch plus a hot-key candidate table, computed where the keys lane
  already exists:

  - *in-program* on device keyed edges and fused chains — the sketch
    state is threaded through the existing ``wf_jit`` programs (the
    keyby split, the fused chain's downstream key extraction) as one
    donated extra operand, so the update costs **zero extra
    dispatches** and no 64-bit scatter (per-batch int32 histograms,
    one matmul of one-hots each, widened into the int64 totals by a
    dense add: ``device_hist32``); the accumulated device state is
    merged to host only at monitor/stats cadence (the
    Julia-GPU-primitives stance: keep the measurement on device, never
    pull keys to host per batch);
  - *host-side numpy* at the keyed staging boundary, where
    ``native.keyby_partition`` already materializes the key lane and
    per-destination counts (the counts are free; the count-min rows are
    ``np.bincount`` passes);
  - *dense exact histograms* where the consumer declares a bounded key
    space (``withMaxKeys`` / dense ``withNumKeySlots``) — exact per-key
    counts, and on a mesh the per-key-SHARD load falls out of the key
    ranges chip *i* owns.

* **Per-shard attribution** of the per-operator-only gauges: queue
  depth, watermark frontier/lag, service-latency quantiles, HBM bytes
  (the hop's steady XLA-cost bytes × the replica's own dispatches), and
  a documented ICI model for mesh collectives (all_gather over ``data``
  for key-sharded FFAT/stateful state, psum of the dense reduce tables,
  all_to_all for arbitrary-key reduces — XLA cost tables carry no
  collective terms on the CPU backend, so the model is derived from the
  program structure ``parallel/mesh.py`` compiles and labeled as such).

Surfaces: ``PipeGraph.stats()["Shard"]``, ``wf_shard_*`` OpenMetrics
families, the webui per-shard drill-down, ``dump_trace()`` metadata,
the postmortem bundle's ``shard.json`` (``tools/wf_doctor.py`` renders
it jax-free), and the reshard advisor (``analysis/resharding.py`` /
``tools/wf_shard.py``).  ``Config.shard_ledger`` off builds no plane:
no sketch attaches anywhere and each read/update site keeps one
``is not None`` check (micro-asserted by tests/test_shard_plane.py,
same stance as the health/sweep/durability planes).
"""

from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional

import numpy as np

#: count-min geometry: DEPTH independent rows of WIDTH counters.  WIDTH
#: is a power of two <= 2^16 so each row's index is one 16-bit field of
#: the 64-bit splitmix hash (4 x 16 = the whole hash, rows independent).
SKETCH_DEPTH = 4
SKETCH_WIDTH = 2048
#: device-side hot-key candidate ring: CAND_PER_BATCH strided lanes per
#: batch overwrite a CAND_RING-slot ring — a key carrying x% of the
#: stream appears among the candidates with probability ~x per batch,
#: so over a monitor cadence a hot key is caught with near-certainty.
CAND_RING = 64
CAND_PER_BATCH = 8
#: declared key spaces up to this bound keep an EXACT dense histogram
#: instead of the sketch (a [K] int64 row per keyed edge)
EXACT_KEYS_LIMIT = 1 << 16
#: cap on the host candidate set between prunes (CMS edges)
_CAND_POOL_LIMIT = 1024

#: nominal per-chip ICI bandwidth for the collective TIME model
#: (bytes/sec; ~90 GB/s per direction is the TPU-v4-class figure).  The
#: model is structural — the CPU backend moves nothing over ICI — so
#: the time is labeled with the assumption; a calibration store's
#: probed rate replaces it (monitoring/calibration.py).
ICI_BYTES_PER_SEC = 90e9

#: hot keys retained per keyed edge in the top-K table
#: (stats()["Shard"] hot_keys, the reshard advisor's move candidates)
TOPK = 8


def _splitmix64_np(k: np.ndarray) -> np.ndarray:
    """Vectorized splitmix64 over an int64 key column — bit-identical to
    ``parallel.emitters.splitmix64_int`` / ``_splitmix64_dev`` and the
    native ``wf_hash64`` (the sketch row hashes and the shard placement
    must agree across the host, device, and native paths)."""
    with np.errstate(over="ignore"):
        x = k.astype(np.int64).view(np.uint64) \
            + np.uint64(0x9E3779B97F4A7C15)
        x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
        return x ^ (x >> np.uint64(31))


def _key32_np(k: np.ndarray) -> np.ndarray:
    """int64 -> device int32 truncation (the key space the consuming
    operator's state table collapses to — sketch exactly what routing
    and state see, ``KeyedDeviceStageEmitter._key32``)."""
    return np.asarray(k).astype(np.int64).astype(np.int32).astype(np.int64)


# ---------------------------------------------------------------------------
# device-side sketch state: threaded through existing wf_jit programs
# ---------------------------------------------------------------------------

def device_sketch_init(n_shards: int):
    """Fresh on-device sketch state for one keyed program site.  Built
    lazily at the site's first sketched batch (never at import: this
    module must not pull jax at module scope)."""
    import jax.numpy as jnp
    return {
        "cms": jnp.zeros((SKETCH_DEPTH, SKETCH_WIDTH), jnp.int64),
        "counts": jnp.zeros(max(1, n_shards), jnp.int64),
        "cand": jnp.full(CAND_RING, np.iinfo(np.int32).min, jnp.int32),
        "batches": jnp.zeros((), jnp.int32),
        "total": jnp.zeros((), jnp.int64),
    }


#: the histogram's bin index splits as ``hi * HIST_LO + lo``: the two
#: one-hot operands of the fold below are [bins / HIST_LO, N] and
#: [HIST_LO, N]
HIST_LO = 64
#: an f32 accumulator holds every integer up to 2^24, so a bin that
#: takes a whole batch stays exact while the batch has no more lanes
HIST_EXACT_LANES = 1 << 24


def device_hist32(idx, valid, n_bins: int):
    """Exact ``[n_bins]`` int32 histogram of the int32 lane ``idx`` over
    the ``valid`` lanes, with no scatter: the bin index is factored
    ``hi * HIST_LO + lo`` and the counts are ONE matmul of the two
    one-hot operands, ``onehot(hi) & valid`` ``[n_bins / HIST_LO, N]``
    against ``onehot(lo)`` ``[HIST_LO, N]``, contracted over the lanes.
    The operands are 0/1 in bfloat16 (every product exact) and the
    accumulator is f32 (exact to 2^24 per bin).  An index outside
    ``[0, n_bins)`` matches no bin and is dropped.  A lane count the
    accumulator could not hold exactly — read off the traced shape,
    the one thing this adapts to — takes an int32 scatter-add."""
    import jax
    import jax.numpy as jnp
    n = int(idx.shape[0])
    if n > HIST_EXACT_LANES:
        return jnp.zeros(n_bins, jnp.int32).at[idx].add(
            valid.astype(jnp.int32), mode="drop")
    lo_n = min(n_bins, HIST_LO)
    hi_n = -(-n_bins // lo_n)
    hi_hot = (((idx // lo_n)[None, :]
               == jnp.arange(hi_n, dtype=jnp.int32)[:, None])
              & valid[None, :]).astype(jnp.bfloat16)
    lo_hot = ((idx % lo_n)[None, :]
              == jnp.arange(lo_n, dtype=jnp.int32)[:, None]) \
        .astype(jnp.bfloat16)
    hist = jax.lax.dot_general(hi_hot, lo_hot, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)
    return hist.reshape(hi_n * lo_n)[:n_bins].astype(jnp.int32)


def device_sketch_update(state, keys, valid, n_shards: int, dest=None):
    """The in-program sketch update, TRACED INTO the host program (the
    keyby split / the fused chain step) — zero extra dispatches and no
    64-bit scatter: a batch's increments fit 32 bits, so each count-min
    row and the shard counts are a per-batch int32 histogram
    (:func:`device_hist32`) widened into the int64 totals by one dense
    add.  Every valid lane of every batch is counted; the state is
    bit-identical to what :meth:`ShardSketch.update_host` accumulates.
    ``dest`` is the per-lane destination the keyby split already
    computed (invalid lanes == ``n_shards``); ``None`` derives it from
    the same splitmix placement the emitters use."""
    # not at module scope: the recorder pulls jax
    from windflow_tpu.monitoring.recorder import phase
    with phase("wf.shard.sketch"):
        return _sketch_update(state, keys, valid, n_shards, dest)


def _sketch_update(state, keys, valid, n_shards: int, dest):
    import jax
    import jax.numpy as jnp
    from windflow_tpu.parallel.emitters import _splitmix64_dev
    k32 = keys.astype(jnp.int32)
    h = _splitmix64_dev(k32)
    n_sh = max(1, n_shards)
    rows = [device_hist32(((h >> jnp.uint64(16 * i))
                           % jnp.uint64(SKETCH_WIDTH)).astype(jnp.int32),
                          valid, SKETCH_WIDTH)
            for i in range(SKETCH_DEPTH)]
    if dest is None:
        dest = (h % jnp.uint64(n_sh)).astype(jnp.int32)
    # an invalid lane's ``dest`` is ``n_shards``: out of range, dropped
    counts = device_hist32(dest, valid, n_sh)
    cap = int(k32.shape[0])
    c = min(CAND_PER_BATCH, cap)
    stride = max(1, cap // c)
    cand_new = jnp.where(valid[::stride][:c], k32[::stride][:c],
                         jnp.int32(np.iinfo(np.int32).min))
    slots = max(1, CAND_RING // c)
    start = (state["batches"] % jnp.int32(slots)) * jnp.int32(c)
    cand = jax.lax.dynamic_update_slice(state["cand"], cand_new, (start,))
    return {"cms": state["cms"] + jnp.stack(rows).astype(jnp.int64),
            "counts": state["counts"] + counts.astype(jnp.int64),
            "cand": cand, "batches": state["batches"] + 1,
            "total": state["total"]
            + jnp.sum(valid, dtype=jnp.int32).astype(jnp.int64)}


# ---------------------------------------------------------------------------
# the per-edge sketch: host accumulators + registered device states
# ---------------------------------------------------------------------------

class ShardSketch:
    """Key-skew sketch for ONE keyed consumer operator.  Host update
    paths accumulate numpy state; device program sites register a state
    getter and are merged only when :meth:`summary` runs (stats /
    monitor cadence — the only device sync the plane ever pays).
    Counter updates are deliberately lock-free (same telemetry stance as
    the replica counters): a torn concurrent add may undercount a batch,
    and the section reads are never exact invariants."""

    def __init__(self, n_shards: int, topk: int = 8,
                 max_keys: Optional[int] = None,
                 key_axis: int = 1,
                 placement: str = "splitmix") -> None:
        self.n_shards = max(1, n_shards)
        self.topk = max(1, topk)
        #: "splitmix" (device/keyed-staging routing), "stable_hash"
        #: (host KeyByEmitter), "dense_range" (mesh key-axis ownership)
        self.placement = placement
        #: reshard-executor key→shard override (windflow_tpu/serving):
        #: set when the executor re-places keys so hot-key shard
        #: attribution follows the LIVE routing, not the derived hash
        self.override: Optional[dict] = None
        self.key_axis = max(1, key_axis)
        self.shard_counts = np.zeros(self.n_shards, np.int64)
        self.total = 0
        self.batches = 0
        self.update_usec = 0.0
        self.max_keys = max_keys if (max_keys
                                     and max_keys <= EXACT_KEYS_LIMIT) \
            else None
        if self.max_keys is not None:
            # exact dense histogram; row K is the out-of-range overflow
            self.hist = np.zeros(self.max_keys + 1, np.int64)
            self.cms = None
        else:
            self.hist = None
            self.cms = np.zeros((SKETCH_DEPTH, SKETCH_WIDTH), np.int64)
        #: CMS hot-key candidates (key -> 0); pruned by estimate
        self._cands: Dict[int, int] = {}
        #: sampled-flush weights for host KeyByEmitter edges (no key
        #: column exists there — per-tuple hashing would blow the <2%
        #: budget, so the flush path samples one key per shipped batch)
        self._sampled: Dict[int, int] = {}
        self._sampled_n = 0
        #: device program sites: callables returning the live state dict
        self._device_states: List = []
        self._lock = threading.Lock()   # candidate-dict prune only

    # -- update paths --------------------------------------------------------
    def update_host(self, keys: np.ndarray,
                    counts: Optional[np.ndarray] = None) -> None:
        """Bulk host update from a materialized key column (the keyed
        staging boundary / the staging-probe sites).  ``counts`` are the
        per-destination totals ``native.keyby_partition`` already
        computed (free when present; derived placements otherwise)."""
        t0 = time.perf_counter()
        keys = np.asarray(keys, np.int64)
        n = keys.size
        if n == 0:
            return
        self.batches += 1
        self.total += n
        if counts is not None:
            self.shard_counts += np.asarray(counts, np.int64)
        elif self.placement == "dense_range" or self.n_shards == 1:
            pass    # derived from the histogram key ranges at summary
        elif self.placement == "mod":
            # mesh arbitrary-key owner hash (uint32(key) % n — the
            # all_to_all routing in mesh.make_sharded_reduce_arbitrary)
            d = ((keys & 0xFFFFFFFF) % self.n_shards).astype(np.intp)
            self.shard_counts += np.bincount(d, minlength=self.n_shards)
        else:
            h = _splitmix64_np(keys)
            d = (h % np.uint64(self.n_shards)).astype(np.intp)
            self.shard_counts += np.bincount(d, minlength=self.n_shards)
        if self.hist is not None:
            k = np.where((keys < 0) | (keys >= self.max_keys),
                         self.max_keys, keys)
            self.hist += np.bincount(k.astype(np.intp),
                                     minlength=self.max_keys + 1)
        else:
            h = _splitmix64_np(keys)
            for i in range(SKETCH_DEPTH):
                idx = ((h >> np.uint64(16 * i))
                       % np.uint64(SKETCH_WIDTH)).astype(np.intp)
                self.cms[i] += np.bincount(idx, minlength=SKETCH_WIDTH)
            step = max(1, n // CAND_PER_BATCH)
            # per-batch rotating offset: a FIXED stride over periodic
            # key layouts (every 8th lane is the cold key, say) would
            # alias and sample the same phase forever, blinding the
            # candidate pool to the other keys entirely
            off = int((self.batches * 7) % step)
            with self._lock:
                # candidate dict writes share the prune's lock: sibling
                # replicas' emitters may update one consumer's sketch
                # concurrently, and an unlocked insert during a prune's
                # iteration would raise into the staging path
                for k in keys[off::step][:CAND_PER_BATCH]:
                    self._cands[int(k)] = 0
            if len(self._cands) > _CAND_POOL_LIMIT:
                self._prune_cands()
        self.update_usec += (time.perf_counter() - t0) * 1e6

    def note_flush(self, shard: int, n: int, sample_key=None) -> None:
        """Host KeyByEmitter hook, batch-flush granularity: exact shard
        load from the flushed batch size + one sampled key per batch
        (approximate hot-key weights — the ``"sampled"`` basis).  Never
        raises: the load counters must stay single-counted even when
        the sampled user key defeats the dict (unhashable)."""
        self.batches += 1
        self.total += n
        self.shard_counts[shard] += n
        if sample_key is None:
            return
        try:
            with self._lock:
                self._sampled[sample_key] = \
                    self._sampled.get(sample_key, 0) + n
                self._sampled_n += n
                if len(self._sampled) > _CAND_POOL_LIMIT:
                    keep = sorted(self._sampled.items(),
                                  key=lambda kv: kv[1],
                                  reverse=True)[:_CAND_POOL_LIMIT // 2]
                    self._sampled = dict(keep)
        except TypeError:
            pass    # unhashable user key: the load above still counted

    def register_device_state(self, getter) -> None:
        """Register an in-program sketch site; ``getter()`` returns its
        live (cumulative) device state dict, or None before the first
        sketched batch.  Merged fresh on every summary — cumulative
        state is never folded into the host accumulators twice."""
        self._device_states.append(getter)

    # -- read path (stats / monitor cadence) ---------------------------------
    def _prune_cands(self) -> None:
        with self._lock:
            est = [(k, self._estimate(k)) for k in self._cands]
            est.sort(key=lambda kv: kv[1], reverse=True)
            self._cands = {k: 0 for k, _ in est[:_CAND_POOL_LIMIT // 2]}

    def _estimate(self, key: int, cms: Optional[np.ndarray] = None) -> int:
        c = self.cms if cms is None else cms
        h = _splitmix64_np(np.asarray([key], np.int64))[0]
        return int(min(
            c[i][int((h >> np.uint64(16 * i)) % np.uint64(SKETCH_WIDTH))]
            for i in range(SKETCH_DEPTH)))

    def hot_candidates(self, limit: int) -> list:
        """Top candidate keys with their load estimates, for the
        key-compaction reseed (parallel/compaction.py): exact-histogram
        sketches rank their dense counts; CMS sketches merge the host
        candidate pool with every in-program site's ring and estimate
        over the merged CMS.  Returns ``[(key, est_tuples), ...]``
        ranked hottest-first, at most ``limit`` entries."""
        if self.hist is not None:
            body = self.hist[:self.max_keys]
            order = np.argsort(body)[::-1][:limit]
            return [(int(k), int(body[k])) for k in order if body[k] > 0]
        cms = self.cms.copy()
        with self._lock:
            cands = set(self._cands)
            cands.update(k for k in self._sampled
                         if isinstance(k, (int, np.integer)))
        for getter in self._device_states:
            try:
                st = getter()
                if st is None:
                    continue
                cms = cms + np.asarray(st["cms"], np.int64)
                ring = np.asarray(st["cand"], np.int64)
            except Exception:  # lint: broad-except-ok (donated operand
                # read racing the in-flight dispatch — skip the site for
                # this read, the summary() stance)
                continue
            cands.update(int(k) for k in ring
                         if k != np.iinfo(np.int32).min)
        est = [(int(k), self._estimate(int(k), cms)) for k in cands]
        est.sort(key=lambda kv: kv[1], reverse=True)
        return est[:limit]

    def shard_of(self, key: int) -> int:
        from windflow_tpu.basic import stable_hash
        from windflow_tpu.parallel.emitters import splitmix64_int
        if self.override:
            d = self.override.get(key)
            if isinstance(d, int) and 0 <= d < self.n_shards:
                return d
        if self.placement == "dense_range" and self.max_keys:
            per = max(1, self.max_keys // self.key_axis)
            return min(self.key_axis - 1, max(0, int(key)) // per)
        if self.placement == "mod":
            return (int(key) & 0xFFFFFFFF) % self.n_shards
        if self.placement == "stable_hash":
            return stable_hash(key) % self.n_shards
        k = int(key) & 0xFFFFFFFF
        k = k - (1 << 32) if k >= (1 << 31) else k
        return splitmix64_int(k) % self.n_shards

    def summary(self) -> dict:
        """Merge host + device accumulators into the section payload:
        per-shard loads, the hot-key top-K table, and the basis tag
        ("exact" | "cms" | "sampled")."""
        counts = self.shard_counts.copy()
        total = self.total
        batches = self.batches
        hist = self.hist.copy() if self.hist is not None else None
        cms = self.cms.copy() if self.cms is not None else None
        with self._lock:    # driver threads insert concurrently
            cands = set(self._cands)
        dev_fed = False
        for getter in self._device_states:
            try:
                st = getter()
                if st is None:
                    continue
                # monitor-cadence device sync: the ONLY sync the plane
                # pays
                dev_counts = np.asarray(st["counts"], np.int64)
                dev_total = int(st["total"])
                dev_batches = int(st["batches"])
                dev_cms = np.asarray(st["cms"], np.int64)
                ring = np.asarray(st["cand"], np.int64)
            except Exception:  # lint: broad-except-ok (the state is a
                # DONATED program operand: a read racing the in-flight
                # dispatch sees a deleted array — skip this site for
                # THIS read, the next cadence sees the fresh state)
                continue
            if dev_counts.size == counts.size:
                counts = counts + dev_counts
            total += dev_total
            batches += dev_batches
            if cms is None:
                # a bounded-key edge fed by an in-program site: the
                # device state carries a CMS (the program has no dense
                # histogram), so the merge view needs one
                cms = np.zeros((SKETCH_DEPTH, SKETCH_WIDTH), np.int64)
            cms = cms + dev_cms
            cands.update(int(k) for k in ring
                         if k != np.iinfo(np.int32).min)
            dev_fed = True
        if self.placement == "dense_range" and hist is not None \
                and self.key_axis > 1:
            per = max(1, self.max_keys // self.key_axis)
            counts = hist[:per * self.key_axis] \
                .reshape(self.key_axis, per).sum(axis=1)
        out = {
            "n_shards": int(counts.size),
            "placement": self.placement,
            "total_tuples": int(total),
            "batches": int(batches),
            "tuples": [int(c) for c in counts],
        }
        if total > 0 and counts.size > 1 and counts.sum() > 0:
            mean = counts.sum() / counts.size
            out["imbalance_ratio"] = round(float(counts.max() / mean), 4)
            out["hot_shard"] = int(counts.argmax())
        top: List[dict] = []
        if hist is not None and hist[:self.max_keys].sum() > 0:
            out["basis"] = "exact"
            body = hist[:self.max_keys]
            order = np.argsort(body)[::-1][:4 * self.topk]
            est_map = {int(k): int(body[k]) for k in order if body[k] > 0}
            if dev_fed and cms is not None:
                # mixed feed: an in-program site contributed tuples the
                # dense histogram never saw — join its CMS estimates so
                # shares stay honest against the merged total
                out["basis"] = "mixed"
                for k in cands:
                    est_map[k] = est_map.get(k, 0) \
                        + self._estimate(k, cms)
            ranked = sorted(est_map.items(), key=lambda kv: kv[1],
                            reverse=True)
            top = [{"key": k, "est_tuples": v}
                   for k, v in ranked[:self.topk] if v > 0]
            if hist[self.max_keys]:
                out["out_of_range_tuples"] = int(hist[self.max_keys])
        elif cms is not None and cands:
            out["basis"] = "cms"
            est = [(k, self._estimate(k, cms)) for k in cands]
            est.sort(key=lambda kv: kv[1], reverse=True)
            top = [{"key": int(k), "est_tuples": int(v)}
                   for k, v in est[:self.topk] if v > 0]
        elif self._sampled:
            out["basis"] = "sampled"
            est = sorted(self._sampled.items(), key=lambda kv: kv[1],
                         reverse=True)
            top = [{"key": k, "est_tuples": v}
                   for k, v in est[:self.topk]]
        else:
            out["basis"] = "cms" if cms is not None else "exact"
        for t in top:
            if total > 0:
                t["share"] = round(t["est_tuples"] / total, 4)
            try:
                t["shard"] = self.shard_of(t["key"])
            except (TypeError, ValueError):
                pass
        out["hot_keys"] = top
        if top and total > 0:
            out["hot_key_share"] = round(top[0]["est_tuples"] / total, 4)
        if self.update_usec:
            out["host_update_usec"] = round(self.update_usec, 1)
        return out


class HostKeyProbe:
    """Key probe on a plain (non-keyed) staging emitter feeding a keyed
    device consumer whose key extraction runs in-program (mesh FFAT /
    dense reduce / stateful): the emitter's columnar or record path
    already materializes the fields on host, so the consumer's extractor
    applies host-side at batch granularity.  Any extractor failure
    disables the probe permanently (speculative-vectorization stance of
    ``KeyedDeviceStageEmitter.emit_columns``) — the pipeline must never
    pay for a probe that cannot see.

    Doubles as the key-compaction admission point (``compactor``,
    parallel/compaction.py): a host-fed compacted consumer admits every
    key at this boundary, so its batches ship with a miss-free remap.
    A probe failure deactivates the compactor too — the consumer falls
    back to its legacy path instead of silently starving the table."""

    __slots__ = ("sketch", "key_fn", "dead", "compactor")

    def __init__(self, sketch: Optional[ShardSketch], key_fn,
                 compactor=None) -> None:
        self.sketch = sketch
        self.key_fn = key_fn
        self.compactor = compactor
        self.dead = False

    def _fail(self) -> None:
        self.dead = True
        if self.compactor is not None:
            self.compactor.deactivate()

    def columns(self, cols, n: int) -> None:
        if self.dead or n == 0:
            return
        try:
            k = np.asarray(self.key_fn(cols))
            if k.shape != (n,):
                raise ValueError("extractor is not elementwise")
            k32 = _key32_np(k)
            if self.compactor is not None:
                self.compactor.observe(k32)
            if self.sketch is not None:
                self.sketch.update_host(k32)
        except Exception:  # lint: broad-except-ok (speculative probe of
            # an arbitrary user extractor over SoA columns — ANY failure
            # means "cannot see", and telemetry must never take the
            # staging path down)
            self._fail()

    def items(self, items) -> None:
        if self.dead or not items:
            return
        try:
            keys = np.fromiter((int(self.key_fn(it)) for it in items),
                               np.int64, count=len(items))
            k32 = _key32_np(keys)
            if self.compactor is not None:
                self.compactor.observe(k32)
            if self.sketch is not None:
                self.sketch.update_host(k32)
        except Exception:  # lint: broad-except-ok (same stance as
            # columns(): a non-numeric or throwing extractor disables
            # the probe, never the staging path)
            self._fail()


# ---------------------------------------------------------------------------
# the graph-scoped ledger
# ---------------------------------------------------------------------------

def _steady_cost_bytes(op) -> Optional[float]:
    """Steady per-dispatch HBM bytes of the hop's dominant program (the
    sweep ledger's ``steady_bytes_per_tuple`` numerator, re-read here so
    per-REPLICA attribution scales it by each replica's own dispatch
    count)."""
    from windflow_tpu.monitoring.sweep_ledger import _op_wrappers
    best_d, best_ba = 0, None
    for w in _op_wrappers(op):
        if w.dispatches <= 0:
            continue
        cost = w.current_cost() or {}
        ba = cost.get("bytes_accessed")
        if isinstance(ba, (int, float)) and w.dispatches >= best_d:
            best_d, best_ba = w.dispatches, float(ba)
    return best_ba


class ShardLedger:
    """Graph-scoped shard plane: built by ``PipeGraph._build`` when
    ``Config.shard_ledger`` is on.  Construction attaches the key-skew
    sketches to the keyed edges (and the in-program sites); everything
    else is read-cadence — ``section()`` walks live replica counters and
    merges the sketches, never touching the per-batch path."""

    def __init__(self, graph) -> None:
        self._graph = graph
        self.topk = TOPK
        #: id(consumer op) -> ShardSketch (one per keyed consumer; all
        #: edges feeding that consumer share it)
        self._sketches: Dict[int, ShardSketch] = {}
        self._statics: Optional[dict] = None
        self._attach()

    # -- sketch attachment (build time) --------------------------------------
    def _sketch_for(self, consumer, n_shards: int,
                    placement: str) -> ShardSketch:
        sk = self._sketches.get(id(consumer))
        if sk is None:
            mesh = getattr(consumer, "mesh", None)
            key_axis = 1
            if mesh is not None:
                from windflow_tpu.parallel.mesh import DATA_AXIS, KEY_AXIS
                if consumer.key_space() is not None:
                    # bounded: chip i owns keys [i*K/kk, (i+1)*K/kk)
                    key_axis = mesh.shape[KEY_AXIS]
                    placement = "dense_range"
                    n_shards = key_axis
                else:
                    # arbitrary keys hash-shard to their owner chip by
                    # uint32(key) % n (mesh.make_sharded_reduce_arbitrary)
                    placement = "mod"
                    n_shards = mesh.shape[DATA_AXIS] \
                        * mesh.shape[KEY_AXIS]
            sk = ShardSketch(n_shards, topk=self.topk,
                             max_keys=consumer.key_space(),
                             key_axis=key_axis, placement=placement)
            self._sketches[id(consumer)] = sk
        return sk

    def _attach(self) -> None:
        from windflow_tpu.parallel.emitters import (AlignedMeshStageEmitter,
                                                    DeviceKeyByEmitter,
                                                    DeviceStageEmitter,
                                                    DeviceToHostEmitter,
                                                    KeyByEmitter,
                                                    KeyedDeviceStageEmitter,
                                                    SplittingEmitter)
        g = self._graph

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
            if isinstance(em, AlignedMeshStageEmitter):
                # key-aligned mesh ingest: the keys are host-visible at
                # this boundary (the emitter routed by them), so the
                # probe sees exactly the placement the columns realize
                # (dense_range ownership — _sketch_for detects the mesh)
                kx = consumer.key_extractor
                if consumer.is_keyed and kx is not None:
                    sk = self._sketch_for(consumer, consumer.parallelism,
                                          "splitmix")
                    em._shard_probe = HostKeyProbe(sk, kx)
            elif isinstance(em, KeyedDeviceStageEmitter):
                em._sketch = self._sketch_for(consumer, len(em.dests),
                                              "splitmix")
            elif isinstance(em, DeviceKeyByEmitter):
                sk = self._sketch_for(consumer, len(em.dests), "splitmix")
                em.attach_shard_sketch(sk)
            elif isinstance(em, KeyByEmitter):
                em._sketch = self._sketch_for(consumer, len(em.dests),
                                              "stable_hash")
            elif isinstance(em, DeviceStageEmitter):
                # plain staging into a keyed device consumer whose key
                # extraction runs in-program (mesh / dense / windowed):
                # probe the host-visible records with that extractor.
                # Skipped for fused-segment hosts: their extractor
                # expects POST-prelude records, but this edge stages the
                # chain HEAD's inputs — probing them would sketch keys
                # the routing never computes.
                kx = consumer.key_extractor
                if consumer.is_keyed and kx is not None \
                        and consumer.is_tpu \
                        and consumer._fused_prelude is None:
                    sk = self._sketch_for(consumer, consumer.parallelism,
                                          "splitmix")
                    em._shard_probe = HostKeyProbe(sk, kx)

        for op in g._operators:
            for rep in op.replicas:
                visit(rep.emitter)
        # fused chains / chained pairs extracting a downstream consumer's
        # keys in-program: fold the sketch into that same program
        edges = [e for e in g._edges() if e[0] == "op"]
        downstream = {id(a): b for _, a, b in edges}
        for op in g._operators:
            for exec_ in (op._fusion_exec,
                          getattr(op, "_chain", None)):
                if exec_ is None or exec_._key_extractor is None:
                    continue
                consumer = downstream.get(id(op))
                if consumer is None or not consumer.is_keyed:
                    continue
                if consumer.parallelism > 1:
                    # the edge is a DeviceKeyByEmitter whose split
                    # program already sketches this stream (attached
                    # above) — a second update in the chain program
                    # would double-count every tuple
                    continue
                sk = self._sketch_for(consumer, consumer.parallelism,
                                      "splitmix")
                exec_.attach_shard_sketch(sk, consumer.parallelism)
                break

    # -- statics: record bytes, upstream ops, effective capacities -----------
    def _compute_statics(self) -> dict:
        """Everything derivable from the built graph, computed ONCE and
        cached (the section reads at monitor/webui cadence must not
        re-walk the edge list per operator per read)."""
        from windflow_tpu.analysis.preflight import (_effective_caps,
                                                     _upstream_map,
                                                     propagate_specs,
                                                     record_nbytes)
        g = self._graph
        edges = g._edges()
        upstreams = _upstream_map(edges)
        try:
            in_specs, _ = propagate_specs(g, edges=edges,
                                          upstreams=upstreams)
        except Exception:  # lint: broad-except-ok (abstract eval of
            # arbitrary user kernels; a failure degrades the ICI model
            # to "unknown", it must never take a stats read down)
            in_specs = {}
        ups: Dict[int, list] = {}
        for edge in edges:
            if edge[0] == "op":
                _, a, b = edge
                ups.setdefault(id(b), []).append(a)
        statics = {}
        for op in g._operators:
            caps = sorted(c for c in _effective_caps(op, upstreams) if c)
            statics[id(op)] = {
                "bpt": record_nbytes(in_specs.get(id(op))),
                "ups": ups.get(id(op), []),
                "cap": getattr(op, "output_batch_size", 0)
                or (caps[0] if caps else 0),
            }
        return statics

    # -- ICI model (mesh programs) -------------------------------------------
    def _ici_model(self, op, bpt: Optional[float],
                   cap: int) -> Optional[dict]:
        """Documented model of the ICI bytes one dispatch of ``op``'s
        sharded program moves, derived from the collective structure
        ``parallel/mesh.py`` compiles (XLA cost tables carry no
        collective terms on CPU).  ``bpt`` = payload+lane bytes/tuple;
        ``cap`` = the effective batch capacity (cached statics)."""
        mesh = getattr(op, "mesh", None)
        if mesh is None or bpt is None or not cap:
            return None
        from windflow_tpu.parallel.mesh import DATA_AXIS, KEY_AXIS
        dd = mesh.shape[DATA_AXIS]
        kk = mesh.shape[KEY_AXIS]
        n = dd * kk
        from windflow_tpu.ops.tpu import ReduceTPU
        if getattr(op, "_ingest_mode", None) == "aligned":
            # key-aligned ingest (parallel/emitters.
            # AlignedMeshStageEmitter): the host pre-placed each tuple
            # on its key-owner column; only the within-column data-axis
            # gather remains, for EVERY aligned consumer kind — FFAT
            # windows, dense ReduceTPU (whose [K]-table psum/all_gather
            # vanishes entirely), dense-key stateful (whose psum lane
            # merge vanishes too)
            total = cap * bpt * (dd - 1)
            kind = "all_gather(data|key-aligned)"
        elif isinstance(op, ReduceTPU):
            if op.max_keys is not None:
                k = op.max_keys if op.key_extractor is not None else 1
                table = k * bpt
                # ring all-reduce: each of n devices sends+receives
                # ~2(n-1)/n of the table
                total = 2.0 * (n - 1) * table
                kind = f"psum([{k}] table)"
            else:
                # hash-sharded all_to_all: (n-1)/n of the lanes cross ICI
                total = cap * bpt * (n - 1) / n
                kind = "all_to_all(lanes)"
        else:
            # key-sharded state (FFAT / stateful): every key shard
            # all_gathers the data-sharded batch — each of the kk*dd
            # devices receives the cap*(dd-1)/dd lanes it lacks
            total = kk * cap * bpt * (dd - 1)
            kind = "all_gather(data)"
        # the TIME half divides by the link bandwidth — a probe-measured
        # value while a fresh calibration store covers it (provenance
        # `calibrated(<age>)`), the nominal ICI_BYTES_PER_SEC
        # otherwise (`modeled`)
        from windflow_tpu.monitoring import calibration
        ici_bps, ici_prov = calibration.constant("ici_bytes_per_sec",
                                                 ICI_BYTES_PER_SEC)
        return {
            "collective": kind,
            "mesh": {"data": dd, "key": kk},
            "ici_bytes_per_dispatch": round(total, 1),
            "ici_bytes_per_tuple": round(total / cap, 2),
            # the TIME half of the model: per-dispatch collective bytes
            # over the fabric, serialized through each chip's share at
            # the calibrated-or-nominal link bandwidth
            "ici_usec_per_dispatch": round(
                (total / n) / ici_bps * 1e6, 3),
            "ici_bandwidth_assumed_bps": ici_bps,
            "ici_bandwidth_provenance": ici_prov,
            # the BYTES half is always structural — the collective shape
            # is derived, never measured on CPU
            "provenance": calibration.MODELED,
            "model": "structural (XLA cost tables carry no collective "
                     "terms; see docs/OBSERVABILITY.md shard plane); the "
                     "measured figure is the benchmark's "
                     "collective_dev_share.sat, from a device trace",
        }

    # -- read paths ----------------------------------------------------------
    def op_summary(self, op_name: str) -> Optional[dict]:
        """Load + hot-key summary for one operator by name (the health
        plane's stall-diagnosis hook)."""
        for op in self._graph._operators:
            if op.name == op_name:
                sk = self._sketches.get(id(op))
                return sk.summary() if sk is not None else None
        return None

    def section(self) -> dict:
        from windflow_tpu.basic import current_time_usecs
        from windflow_tpu.monitoring.sweep_ledger import \
            LANE_BYTES_PER_TUPLE
        if self._statics is None:
            self._statics = self._compute_statics()
        g = self._graph
        now = current_time_usecs()
        per_op: Dict[str, dict] = {}
        worst = (0.0, None)     # (imbalance ratio, op name)
        hot = (0.0, None)       # (hot key share, op name)
        ici_bpt_total = 0.0
        ici_time_prov = None    # provenance of the ICI TIME model
        sketch_usec = 0.0
        for op in g._operators:
            ba = _steady_cost_bytes(op) if op.is_tpu else None
            replicas = []
            lags = []
            for rep in op.replicas:
                from windflow_tpu.batch import WM_MAX, WM_NONE
                wm = rep.current_wm
                front = wm if (wm != WM_NONE and wm < WM_MAX) else None
                lag = max(0, now - front) if front is not None else None
                if lag is not None:
                    lags.append(lag)
                q = rep.stats.service_hist.quantiles()
                slot = {
                    "shard": rep.index,
                    "queue_depth": len(rep.inbox),
                    "watermark_frontier_usec": front,
                    "watermark_lag_usec": lag,
                    "inputs": rep.stats.inputs_received,
                    "outputs": rep.stats.outputs_sent,
                    "dispatches": rep.stats.device_programs_launched,
                    "service_usec": {k: q.get(k)
                                     for k in ("p50", "p95", "p99")
                                     if isinstance(q, dict)},
                }
                if ba is not None:
                    slot["hbm_bytes"] = round(
                        ba * rep.stats.device_programs_launched, 1)
                replicas.append(slot)
            entry: dict = {
                "parallelism": op.parallelism,
                "keyed": op.is_keyed,
                "replicas": replicas,
            }
            if len(lags) > 1:
                entry["lag_spread_usec"] = max(lags) - min(lags)
            sk = self._sketches.get(id(op))
            if sk is not None:
                load = sk.summary()
                entry["load"] = load
                sketch_usec += load.get("host_update_usec", 0.0)
                r = load.get("imbalance_ratio")
                if isinstance(r, (int, float)) and r > worst[0]:
                    worst = (r, op.name)
                s = load.get("hot_key_share")
                if isinstance(s, (int, float)) and s > hot[0]:
                    hot = (s, op.name)
            comp = op._compactor
            if comp is not None:
                # key compaction (parallel/compaction.py): remap table
                # hit rate / overflow share / slot churn ride the shard
                # section — the same per-consumer granularity as load
                entry["compaction"] = comp.summary()
            st = self._statics.get(id(op)) or {}
            spec_bpt = st.get("bpt")
            bpt = (spec_bpt + LANE_BYTES_PER_TUPLE) \
                if spec_bpt is not None else None
            basis = "record spec"
            if bpt is None and getattr(op, "mesh", None) is not None:
                # no declared record spec: fall back to the measured
                # staging bytes per tuple of the feeding edges (padded
                # batch bytes over received tuples — an upper-ish bound)
                h2d = sum(r.stats.h2d_bytes for u in st.get("ups", ())
                          for r in u.replicas)
                inputs = sum(r.stats.inputs_received
                             for r in op.replicas)
                if h2d > 0 and inputs > 0:
                    bpt = h2d / inputs
                    basis = "measured H2D bytes/tuple"
            ici = self._ici_model(op, bpt, st.get("cap", 0))
            if ici is not None:
                ici["bytes_per_tuple_basis"] = basis
                entry["ici"] = ici
                # per key-shard slice of the collective volume (each
                # shard participates symmetrically in the gather/psum)
                ici_bpt_total += ici["ici_bytes_per_tuple"]
                ici_time_prov = ici["ici_bandwidth_provenance"]
            per_op[op.name] = entry
        from windflow_tpu.monitoring import calibration
        return {
            "enabled": True,
            "per_op": per_op,
            "totals": {
                "max_imbalance_ratio": round(worst[0], 4) if worst[1]
                else None,
                "max_imbalance_op": worst[1],
                "hot_key_share": round(hot[0], 4) if hot[1] else None,
                "hot_key_op": hot[1],
                "ici_bytes_per_tuple": round(ici_bpt_total, 2),
                # the collective-shape bytes are structural everywhere;
                # the time column inherits the bandwidth's provenance
                "ici_provenance": calibration.MODELED,
                "ici_time_provenance": ici_time_prov,
                "sketch_host_update_usec": round(sketch_usec, 1),
                "keyed_edges_sketched": len(self._sketches),
            },
        }
