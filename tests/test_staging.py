"""Staging plane (windflow_tpu/staging): host-buffer recycling pool,
fused packed transfer, and driver-loop prefetch.

The reference gets its L1 data-plane rate from a lock-free batch
recycling pool (``recycling.hpp``) and async CUDA-stream staging
(``batch_gpu_t.hpp``); these tests pin the TPU reproduction's contracts:
steady-state staging reuses pooled buffers (zero numpy allocation),
the fused packed transfer round-trips exactly, prefetch lookahead never
reorders or duplicates data under backpressure, and a pool at capacity
degrades to plain allocation instead of blocking."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import windflow_tpu as wf
from windflow_tpu import staging
from windflow_tpu.batch import WM_NONE, columns_to_device, stage_packed
from windflow_tpu.staging import PackedBatchBuilder, StagingPool


@pytest.fixture
def fresh_pool():
    """Swap in an isolated pool for the test (graph emitters bind the
    process-wide default pool at build time) and restore after."""
    pool = StagingPool()
    staging.set_default_pool(pool)
    yield pool
    staging.set_default_pool(None)


# ---------------------------------------------------------------------------
# pool mechanics
# ---------------------------------------------------------------------------

def test_pool_recycles_same_buffer():
    pool = StagingPool()
    a = pool.acquire(128)
    pool.release(a)
    b = pool.acquire(128)
    assert b is a                       # recycled, not reallocated
    assert pool.stats()["hits"] == 1 and pool.stats()["misses"] == 1


def test_pool_is_size_keyed():
    pool = StagingPool()
    a = pool.acquire(64)
    pool.release(a)
    c = pool.acquire(65)                # different size: fresh allocation
    assert c is not a and c.shape == (65,)
    assert pool.stats()["misses"] == 2


def test_pool_at_capacity_drops_instead_of_blocking():
    """Releases beyond the retention depth (or byte cap) are refused and
    counted — allocation pressure, never a deadlock."""
    pool = StagingPool(depth=2)
    bufs = [pool.acquire(32) for _ in range(5)]
    for b in bufs:
        pool.release(b)
    st = pool.stats()
    assert st["releases"] == 2 and st["drops_at_capacity"] == 3
    # acquire still works at capacity: two recycled, then fresh allocation
    out = [pool.acquire(32) for _ in range(3)]
    assert all(o.shape == (32,) for o in out)
    assert pool.stats()["hits"] == 2


def test_pool_byte_cap_refuses_retention():
    pool = StagingPool(depth=8, max_bytes=100)   # < one 32-word buffer
    b = pool.acquire(32)
    pool.release(b)
    assert pool.stats()["drops_at_capacity"] == 1
    assert pool.acquire(32) is not b             # nothing was retained


def test_pool_gate_blocks_until_device_done():
    """Re-acquiring a buffer whose gate is still in flight syncs on the
    gate (the recycling queue's blocking pop); a ready gate never syncs."""
    class Gate:
        def __init__(self):
            self.blocked = False

        def is_ready(self):
            return False

        def block_until_ready(self):
            self.blocked = True
            return self

    pool = StagingPool()
    buf = pool.acquire(16)
    gate = Gate()
    pool.release(buf, gate=gate)
    again = pool.acquire(16)
    assert again is buf
    assert gate.blocked and pool.stats()["gate_waits"] == 1

    # ready device gate: no wait counted
    buf2 = pool.acquire(16)
    arr = jnp.zeros(4)
    jax.block_until_ready(arr)
    pool.release(buf2, gate=arr)
    pool.acquire(16)
    assert pool.stats()["gate_waits"] == 1


# ---------------------------------------------------------------------------
# fused packed transfer
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [7, 32])   # partial and full fill
def test_packed_builder_round_trip(n):
    """PackedBatchBuilder + stage_packed must reproduce the lanes the
    direct (unfused) staging path produces: exact values for int32 /
    float32 / int64 (incl. negative) lanes, zero padding, prefix validity."""
    cap = 32
    cols = {
        "a": np.arange(n, dtype=np.int32) - 3,
        "b": np.linspace(-1.5, 2.5, n).astype(np.float32),
        "c": (np.arange(n, dtype=np.int64) * -(1 << 40)) + 5,
    }
    tss = np.arange(n, dtype=np.int64) * 1000 + 17
    leaves, treedef = jax.tree.flatten(cols)
    dtypes = tuple(str(l.dtype) for l in leaves)
    pool = StagingPool()
    b = PackedBatchBuilder(dtypes, cap, pool=pool)
    # stale recycled contents must not leak into padding: pre-poison
    b.buf[:] = 0xFFFFFFFF
    b.append(leaves, tss)
    db = stage_packed(b.finish(), treedef, dtypes, cap, n, watermark=123,
                      pool=pool)
    assert db.capacity == cap and db.size == n
    np.testing.assert_array_equal(np.asarray(db.valid),
                                  np.arange(cap) < n)
    np.testing.assert_array_equal(np.asarray(db.ts)[:n], tss)
    np.testing.assert_array_equal(np.asarray(db.ts)[n:], 0)
    for name in cols:
        lane = np.asarray(db.payload[name])
        np.testing.assert_array_equal(lane[:n], cols[name])
        np.testing.assert_array_equal(lane[n:], 0)


def test_packed_equals_unfused_columns_to_device(fresh_pool):
    """columns_to_device (now routed through the pooled packed path) must
    agree with a plain jnp.asarray staging of the same columns."""
    n, cap = 20, 32
    cols = {"k": np.arange(n, dtype=np.int32) % 5,
            "v": np.arange(n, dtype=np.float32) * 0.25}
    tss = np.arange(n, dtype=np.int64) * 10
    db = columns_to_device(dict(cols), tss, cap, watermark=7)
    for name in cols:
        np.testing.assert_array_equal(np.asarray(db.payload[name])[:n],
                                      cols[name])
    np.testing.assert_array_equal(np.asarray(db.ts)[:n], tss)
    assert db.ts_min == 0 and db.ts_max == (n - 1) * 10
    assert db.watermark == 7


def test_packed_builder_streams_across_appends():
    """Chunked appends land at their final packed offsets: three appends
    must produce the identical buffer as one."""
    cap = 24
    vals = np.arange(cap, dtype=np.float32)
    keys = np.arange(cap, dtype=np.int64) * 3 - 11
    tss = np.arange(cap, dtype=np.int64)
    pool = StagingPool()
    one = PackedBatchBuilder(("float32", "int64"), cap, pool=pool)
    one.append([vals, keys], tss)
    whole = one.finish().copy()
    three = PackedBatchBuilder(("float32", "int64"), cap, pool=pool)
    for lo, hi in ((0, 5), (5, 16), (16, 24)):
        three.append([vals[lo:hi], keys[lo:hi]], tss[lo:hi])
    np.testing.assert_array_equal(three.finish(), whole)


@pytest.mark.parametrize("ts_fixed", [None, 1_700_000_000_000_123],
                         ids=["event_ts", "one_arrival_stamp"])
@pytest.mark.parametrize("val", ["float32", "int32", "int64"])
@pytest.mark.parametrize("key", ["int32", "int64"])
@pytest.mark.parametrize("nv", [1, 5])
def test_in_place_writer_writes_what_append_writes(nv, key, val, ts_fixed):
    """A producer that writes the packed words itself — the native frame
    parse, given the builder's ``buf``, ``lane_layout`` and ``n``, and
    reporting its rows with ``advance`` — leaves the buffer ``append``
    leaves for the same rows as columns, word for word, in three slices
    that start mid-buffer, in a lane order that is not the wire's."""
    from windflow_tpu import native
    if not native.is_available():
        pytest.skip("no native library")
    cap, n = 64, 50
    rng = np.random.default_rng(nv)
    rec = np.zeros(n, dtype=[("k", "<i8"), ("t", "<i8"),
                             ("v", "<f8", (nv,))])
    rec["k"] = rng.integers(-90, 90, n) if key == "int32" \
        else rng.integers(-(1 << 50), 1 << 50, n)
    rec["t"] = rng.integers(-5, 1 << 45, n)
    rec["v"] = rng.normal(size=(n, nv)) * 1e6     # rounds in f32, truncates
    blob = rec.tobytes() + b"\x07" * 9            # and a piece of a record
    # lanes as emit_columns orders them: the values, then the key
    dtypes = (val,) * nv + (key,)
    pool = StagingPool()
    tss = np.full(n, ts_fixed, np.int64) if ts_fixed is not None \
        else rec["t"]
    want = PackedBatchBuilder(dtypes, cap, pool=pool)
    want.buf[:] = 0xFFFFFFFF
    want.append([rec["v"][:, i].astype(val) for i in range(nv)]
                + [rec["k"].astype(key)], tss)
    got = PackedBatchBuilder(dtypes, cap, pool=pool)
    got.buf[:] = 0xFFFFFFFF
    offs = PackedBatchBuilder.lane_layout(dtypes, cap)
    assert offs == got._offsets
    # the writer's order: key, values in wire order, ts
    lane_off = np.array([offs[nv]] + offs[:nv] + [offs[-1]], np.int64)
    at, extrema = 0, []
    for room in (7, 1, cap):
        m, lo, hi, k_lo, k_hi = native.parse_frames_packed(
            blob, at * rec.dtype.itemsize, nv, got.buf, lane_off,
            2 if key == "int64" else 1,
            native.PACKED_VALUE_KINDS[np.dtype(val)], got.n,
            min(room, got.room), ts_fixed)
        assert m == min(room, n - at)
        extrema.append((lo, hi))
        assert (lo, hi) == (int(tss[at:at + m].min()),
                            int(tss[at:at + m].max()))
        assert (k_lo, k_hi) == (int(rec["k"][at:at + m].min()),
                                int(rec["k"][at:at + m].max()))
        got.advance(m)
        at += m
    assert got.n == n and got.room == cap - n
    np.testing.assert_array_equal(got.finish(), want.finish())
    with pytest.raises(AssertionError):
        got.advance(cap - n + 1)
    assert native.frames_key_range(blob, nv) == (int(rec["k"].min()),
                                                    int(rec["k"].max()))


def test_builder_rejects_unpackable_dtypes():
    with pytest.raises(ValueError, match="unpackable"):
        PackedBatchBuilder(("float64",), 8, pool=StagingPool())


# ---------------------------------------------------------------------------
# steady-state reuse through a real graph
# ---------------------------------------------------------------------------

def _chained_graph(n_tuples, batch, config=None, got=None):
    got = got if got is not None else []
    # int payload: Python floats stack as float64, which is unpackable
    # (no cheap 64-bit device decode) and would bypass the pooled path
    src = (wf.Source_Builder(
            lambda: iter({"key": i % 8, "value": i}
                         for i in range(n_tuples)))
           .withOutputBatchSize(batch).build())
    m1 = wf.MapTPU_Builder(
        lambda t: {"key": t["key"], "value": t["value"] * 2.0}).build()
    f1 = wf.FilterTPU_Builder(lambda t: t["value"] >= 0).build()
    m2 = wf.MapTPU_Builder(
        lambda t: {"key": t["key"], "value": t["value"] + 1.0}).build()
    snk = wf.Sink_Builder(
        lambda r: got.append(float(r["value"])) if r is not None
        else None).build()
    g = wf.PipeGraph("staging_chain", wf.ExecutionMode.DEFAULT,
                     config=config)
    g.add_source(src).add(m1).add(f1).add(m2).add_sink(snk)
    return g, got


def test_steady_state_pool_hit_rate(fresh_pool):
    """Long chained-ops run: after warm-up the staging path must recycle
    buffers, not allocate — >= 90% pool hit rate (acceptance criterion),
    misses bounded by the pool warm-up, zero capacity drops."""
    g, got = _chained_graph(n_tuples=16384, batch=128)
    g.run()
    st = fresh_pool.stats()
    assert st["hits"] + st["misses"] >= 100     # the path actually ran
    assert st["hit_rate"] >= 0.90, st
    # warm-up misses only: bounded by pool depth + driver lookahead, not
    # proportional to the number of staged batches
    assert st["misses"] <= 8, st
    assert got and len(got) == 16384
    # the pool counters ride the monitoring stats dump
    top = g.stats()
    assert top["Staging_pool"]["hit_rate"] >= 0.90
    assert top["Stage_prefetch_depth"] == g.config.stage_prefetch_depth


def test_pool_survives_capacity_pressure_in_graph(fresh_pool):
    """A pool too small to retain anything must not deadlock or corrupt
    a run — staging falls back to allocation and the stream completes."""
    staging.set_default_pool(StagingPool(depth=1, max_bytes=1))
    g, got = _chained_graph(n_tuples=2048, batch=64)
    g.run()
    assert len(got) == 2048
    st = staging.default_pool().stats()
    assert st["hit_rate"] == 0.0 and st["drops_at_capacity"] > 0


# ---------------------------------------------------------------------------
# prefetch lookahead
# ---------------------------------------------------------------------------

def _prefetch_run(depth, n_tuples=4096, batch=64):
    cfg = wf.Config(stage_prefetch_depth=depth,
                    max_inflight_batches=2, max_inbox_messages=4)
    g, got = _chained_graph(n_tuples, batch, config=cfg)
    g.run()
    return got, g


def test_prefetch_ordering_under_backpressure(fresh_pool):
    """Lookahead packs batch N+1 while N's step runs; with tight
    in-transit caps forcing throttle cycles, the sink must still see
    every tuple exactly once, in order, for any prefetch depth."""
    expect, _ = _prefetch_run(0)
    assert len(expect) == 4096
    assert expect == sorted(expect)          # source order preserved
    for depth in (1, 3):
        got, g = _prefetch_run(depth)
        assert got == expect
        assert g.stats()["Stage_prefetch_ticks"] >= 0


def test_prefetch_respects_backpressure_caps(fresh_pool):
    """Prefetch passes re-check the in-transit caps: the high-water marks
    with lookahead enabled stay within one batch of the configured cap
    (lookahead must not overrun the throttle)."""
    _, g = _prefetch_run(3)
    cap = g.config.max_inbox_messages
    assert g.stats()["Max_inbox_depth_seen"] <= cap + 1


# ---------------------------------------------------------------------------
# multi-host staging metadata (ADVICE r5 medium)
# ---------------------------------------------------------------------------

def test_multihost_stage_attaches_no_ts_extrema(monkeypatch):
    """Multi-host `_stage_soa` computes ts extrema from the process-LOCAL
    tss slice; attaching them to the globally sharded batch let
    windows/ffat_tpu _regrow_for_span make divergent per-process ring
    growth decisions.  The sharded branch must attach None extrema (the
    SPMD-consistent eviction-cadence regrow is the growth path there)."""
    from windflow_tpu import batch as batch_mod
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("d",))
    sh = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    monkeypatch.setattr(jax, "process_count", lambda: 2)
    monkeypatch.setattr(
        jax, "make_array_from_process_local_data",
        lambda sharding, a, gshape: jnp.asarray(a))
    db = batch_mod._stage_soa({"v": np.arange(8, dtype=np.int32)},
                              np.arange(8, dtype=np.int64) * 1000,
                              n=8, capacity=16, watermark=7_000, device=sh)
    assert db.ts_min is None and db.ts_max is None
    assert db.watermark == 7_000
