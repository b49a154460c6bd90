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

def _layout_words(lanes, tss, cap):
    """The staged buffer of these columns, built from the layout's own
    statement and nothing of the package: a 4-byte lane is ``cap`` words,
    an 8-byte lane two planes of ``cap`` (the rows' low words, then their
    high words), the int64 ts lane last, unwritten rows zero, then n."""
    planes = []
    for col in list(lanes) + [np.asarray(tss, np.int64)]:
        if col.dtype.itemsize == 8:
            u = col.astype(np.uint64)
            planes += [u & np.uint64(0xFFFFFFFF), u >> np.uint64(32)]
        else:
            planes.append(col.view(np.uint32))
    out = np.zeros(len(planes) * cap + 1, np.uint32)
    for i, p in enumerate(planes):
        out[i * cap:i * cap + len(p)] = p
    out[-1] = len(tss)
    return out


def _lane_of(dt, n, rng):
    """``n`` values of ``dt`` that fill the width: negative and beyond
    2**32 where the dtype has them."""
    dt = np.dtype(dt)
    if dt == np.float32:
        return (rng.normal(size=n) * 1e6).astype(dt)
    if dt == np.uint64:
        return rng.integers(0, 1 << 63, n).astype(dt) * np.uint64(2) \
            + np.uint64(1)
    info = np.iinfo(dt)
    return rng.integers(info.min, info.max, n, dtype=dt, endpoint=True)


LANE_MIXES = {
    "every_width": ("int32", "float32", "int64", "uint64"),
    "int64_only": ("int64",),
    "uint64_then_int32": ("uint64", "int32"),
    "four_byte_lanes": ("float32", "int32"),
    "int64_between_int32": ("int32", "int64", "int32"),
}


@pytest.mark.parametrize("cap,n", [(1, 1), (32, 7), (32, 32)],
                         ids=["capacity_1", "partial", "full"])
@pytest.mark.parametrize("mix", sorted(LANE_MIXES))
def test_pack_then_unpack_body_round_trip(mix, cap, n):
    """``append`` writes the layout and ``unpack_body`` reads it: the
    words are the planes the layout states, and the device's columns are
    the host's bit for bit, int64 / uint64 values with their high words
    (negative, beyond 2**32) included, rows past the fill zero."""
    from windflow_tpu.batch import unpack_body
    dtypes = LANE_MIXES[mix]
    rng = np.random.default_rng(cap * 100 + n)
    lanes = [_lane_of(d, n, rng) for d in dtypes]
    tss = _lane_of("int64", n, rng)
    b = PackedBatchBuilder(dtypes, cap, pool=StagingPool())
    b.buf[:] = 0xFFFFFFFF
    # two appends where there are two rows to split
    for sl in (slice(0, n // 2), slice(n // 2, n)):
        b.append([l[sl] for l in lanes], tss[sl])
    buf = b.finish()
    np.testing.assert_array_equal(buf, _layout_words(lanes, tss, cap))
    cols, ts, valid, n_valid = jax.jit(unpack_body(dtypes, cap))(
        jnp.asarray(buf))
    assert int(n_valid) == n
    np.testing.assert_array_equal(np.asarray(valid), np.arange(cap) < n)
    for got, want in zip(list(cols) + [ts], lanes + [tss]):
        got = np.asarray(got)
        assert got.dtype == want.dtype
        assert got[:n].tobytes() == want.tobytes()
        assert not got[n:].view(np.uint8).any()


@pytest.mark.parametrize("dt", ["int64", "uint64"])
def test_rows_view_returns_the_values_written(dt):
    """``rows_view`` shows rows an in-place writer wrote as columns: a
    4-byte lane as a view of the buffer, an 8-byte lane (two planes, so no
    one typed view) as the combined column, from any row on."""
    cap, n = 16, 11
    rng = np.random.default_rng(3)
    wide, narrow = _lane_of(dt, n, rng), _lane_of("int32", n, rng)
    b = PackedBatchBuilder((dt, "int32"), cap, pool=StagingPool())
    b.append([wide[:4], narrow[:4]], np.zeros(4, np.int64))
    b.append([wide[4:], narrow[4:]], np.zeros(n - 4, np.int64))
    for lo, m in ((0, n), (4, n - 4), (10, 1), (3, 0)):
        got_wide, got_narrow = b.rows_view(lo, m)
        assert got_wide.dtype == wide.dtype
        np.testing.assert_array_equal(got_wide, wide[lo:lo + m])
        np.testing.assert_array_equal(got_narrow, narrow[lo:lo + m])
    assert np.shares_memory(b.rows_view(0, n)[1], b.buf)


def _strided_reads(fn, *shapes):
    """What makes the chip gather in ``fn``'s program: the ``gather`` and
    strided ``slice`` equations of its jaxpr (sub-jaxprs included), and
    the same two in the HLO XLA compiles from it."""
    import re
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            strides = eqn.params.get("strides")
            if eqn.primitive.name == "gather" or (
                    eqn.primitive.name == "slice" and strides is not None
                    and any(s != 1 for s in strides)):
                found.append(str(eqn))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)
    walk(jax.make_jaxpr(fn)(*shapes).jaxpr)
    hlo = jax.jit(fn).lower(*shapes).compile().as_text()
    found += re.findall(r"\bgather\(|slice=\{\[\d+:\d+:\d+\]", hlo)
    return found


@pytest.mark.parametrize("dtypes", [("int64",), ("int32", "uint64"),
                                    ("float32", "int64", "int32")],
                         ids=lambda d: "-".join(d))
def test_staged_unpack_program_holds_no_gather(dtypes):
    """An int64 lane is read back by contiguous slice: the
    ``staging.unpack`` program of an int64-bearing batch (the ts lane
    alone makes every batch one) holds no gather and no strided slice,
    in its jaxpr or in compiled HLO.  On the chip the stride-2 read of
    words interleaved a row was two gathers, ~4.2 ms a 262 144-row batch
    (PERF.md, PR 47): a CPU check sees the form, not the time."""
    from windflow_tpu.batch import _get_unpack
    cap = 64
    words = sum(staging.lane_words(d) for d in dtypes + ("int64",))
    shape = jax.ShapeDtypeStruct((words * cap + 1,), jnp.uint32)
    program = _get_unpack(None, dtypes, cap)
    assert program.op_name == "staging.unpack"
    assert _strided_reads(program._fn, shape) == []

    # the detector sees the interleaved form it stands guard against
    def interleaved(b):
        seg = b[:2 * cap]
        return seg[0::2], seg[1::2]
    seen = _strided_reads(interleaved, shape)
    assert len(seen) >= 2, seen


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
    leaves for the same rows as columns, word for word (an int64 lane's
    low and high words each in their plane, every plane's tail zeroed),
    in three slices that start mid-buffer, in a lane order that is not
    the wire's."""
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
            blob, at * rec.dtype.itemsize, nv, got.buf, lane_off, cap,
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
    np.testing.assert_array_equal(got.buf, _layout_words(
        [rec["v"][:, i].astype(val) for i in range(nv)]
        + [rec["k"].astype(key)], tss, cap))
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
