"""Native host-runtime + bulk IO tests: the C++ library (keyby partition,
frame/CSV parsers, buffer pool, SPSC ring, watermark fold) against numpy
fallbacks, and the FrameSource bulk-ingest path end-to-end through the graph
(native parse → columnar staging → TPU ops → sink)."""

import ctypes
import struct

import numpy as np
import pytest

import windflow_tpu as wf
from windflow_tpu import native
from windflow_tpu.io import FrameSource


def frames_bytes(records, nv=1):
    out = b""
    for k, ts, *vs in records:
        out += struct.pack("<qq" + "d" * nv, k, ts, *vs)
    return out


def test_native_builds_and_loads():
    assert native.is_available(), \
        "native library should build in this environment (g++ present)"


def test_hash_native_matches_numpy():
    L = native.lib()
    keys = np.array([0, 1, 2, -1, 123456789, 2 ** 62], np.int64)
    py = native.hash64(keys)
    for i, k in enumerate(keys):
        assert L.wf_hash64(int(k)) == int(py[i])


def test_keyby_partition_parity_and_counts():
    keys = np.random.default_rng(0).integers(-100, 100, 1000)
    for ndest in (1, 3, 8):
        dests, counts = native.keyby_partition(keys, ndest)
        exp = (native.hash64(keys.astype(np.int64)) %
               np.uint64(ndest)).astype(np.int32)
        np.testing.assert_array_equal(dests, exp)
        np.testing.assert_array_equal(
            counts, np.bincount(exp, minlength=ndest))


def test_parse_frames_roundtrip_and_carry():
    recs = [(i % 5, 1000 + i, float(i), float(-i)) for i in range(97)]
    buf = frames_bytes(recs, nv=2)
    # append a partial record: must be left unconsumed
    buf_partial = buf + b"\x01\x02\x03"
    keys, tss, vals, consumed = native.parse_frames(buf_partial, nv=2)
    assert consumed == len(buf)
    assert len(keys) == 97
    np.testing.assert_array_equal(keys, [r[0] for r in recs])
    np.testing.assert_array_equal(tss, [r[1] for r in recs])
    np.testing.assert_allclose(vals[:, 0], [r[2] for r in recs])
    np.testing.assert_allclose(vals[:, 1], [r[3] for r in recs])


def test_parse_csv_skips_malformed():
    buf = b"1,10,2.5\n2,20,3.5\nbogus line\n3,30,4.5\n4,40"  # last line partial
    keys, tss, vals, consumed = native.parse_csv(buf, nv=1)
    np.testing.assert_array_equal(keys, [1, 2, 3])
    np.testing.assert_array_equal(tss, [10, 20, 30])
    np.testing.assert_allclose(vals[:, 0], [2.5, 3.5, 4.5])
    assert buf[consumed:] == b"4,40"


def test_parse_csv_empty_field_does_not_steal_next_line():
    # "5,50,\n" has an empty value field: the whole line must be skipped
    # without consuming digits from the following line
    buf = b"5,50,\n6,60,7.5\n"
    keys, tss, vals, _ = native.parse_csv(buf, nv=1)
    np.testing.assert_array_equal(keys, [6])
    np.testing.assert_array_equal(tss, [60])
    np.testing.assert_allclose(vals[:, 0], [7.5])


def test_parse_csv_long_lines():
    # lines longer than any fixed scratch buffer must still parse (wide
    # records are normal for multi-field CSV)
    nv = 60
    fields = ",".join(f"{1.5:.10f}" for _ in range(nv))  # ~13 chars per field
    buf = (f"7,70,{fields}\n" * 3).encode()
    assert len(buf) > 3 * 512
    keys, tss, vals, consumed = native.parse_csv(buf, nv=nv)
    np.testing.assert_array_equal(keys, [7, 7, 7])
    np.testing.assert_array_equal(tss, [70, 70, 70])
    assert vals.shape == (3, nv)
    assert consumed == len(buf)


def test_parse_csv_empty_ts_skipped():
    # an empty ts field is malformed, not ts=0
    buf = b"1,,2.5\n2,20,3.5\n"
    keys, tss, vals, _ = native.parse_csv(buf, nv=1)
    np.testing.assert_array_equal(keys, [2])
    np.testing.assert_array_equal(tss, [20])


def test_frame_source_csv_without_trailing_newline():
    blob = b"1,10,2.5\n2,20,3.5"  # no trailing \n: last record still counts
    got = []
    src = FrameSource(lambda: iter([blob]), nv=1, fmt="csv",
                      output_batch_size=4)
    g = wf.PipeGraph("csv_tail", wf.ExecutionMode.DEFAULT,
                     wf.TimePolicy.EVENT)
    g.add_source(src).add_sink(wf.Sink_Builder(
        lambda t: got.append((t["key"], t["v0"])) if t else None).build())
    g.run()
    assert sorted(got) == [(1, 2.5), (2, 3.5)]


def test_min_watermark():
    WM = -1
    assert native.min_watermark(np.array([5, 3, 9], np.int64), WM) == 3
    assert native.min_watermark(np.array([5, WM, 9], np.int64), WM) == WM
    assert native.min_watermark(np.array([], np.int64), WM) == WM


@pytest.mark.parametrize("fmt", ["frames", "csv"])
def test_frame_source_to_tpu_pipeline(fmt):
    """bytes → FrameSource → MapTPU → keyed ReduceTPU → Sink vs oracle,
    with records split across chunk boundaries."""
    n, n_keys = 600, 7
    recs = [(i % n_keys, 1_000_000 + i, float(i)) for i in range(n)]
    if fmt == "frames":
        blob = frames_bytes(recs, nv=1)
    else:
        blob = b"".join(b"%d,%d,%f\n" % r for r in recs)

    def chunks():
        step = 997  # deliberately misaligned with the 24-byte record size
        for lo in range(0, len(blob), step):
            yield blob[lo:lo + step]

    sums = {}

    def sink_fn(t, ctx=None):
        if t is not None:
            sums[int(t["key"])] = sums.get(int(t["key"]), 0) + t["v0"]

    src = FrameSource(chunks, nv=1, fmt=fmt, output_batch_size=64)
    g = wf.PipeGraph("frames", wf.ExecutionMode.DEFAULT, wf.TimePolicy.EVENT)
    mp = g.add_source(src)
    mp.add(wf.MapTPU_Builder(
        lambda t: {"key": t["key"], "v0": t["v0"] * 2.0}).build())
    mp.add(wf.ReduceTPU_Builder(
        lambda a, b: {"key": a["key"], "v0": a["v0"] + b["v0"]})
        .withKeyBy(lambda t: t["key"]).build())
    mp.add_sink(wf.Sink_Builder(sink_fn).build())
    g.run()

    exp = {}
    for k, _, v in recs:
        exp[k] = exp.get(k, 0) + 2.0 * v
    assert set(sums) == set(exp)
    for k in exp:
        assert abs(sums[k] - exp[k]) < 1e-6


def test_frame_source_to_host_sink_fallback_path():
    """Columns explode to per-tuple records for host destinations, and the
    pure-Python parser path (native disabled) agrees."""
    n = 100
    recs = [(i % 3, 10 + i, float(i)) for i in range(n)]
    blob = frames_bytes(recs, nv=1)

    def run(disable_native):
        import windflow_tpu.native as nat
        saved = nat._lib, nat._load_attempted
        if disable_native:
            nat._lib, nat._load_attempted = None, True
        try:
            total = [0.0]
            src = FrameSource(lambda: iter([blob]), nv=1,
                              output_batch_size=16)
            g = wf.PipeGraph("fs_host", wf.ExecutionMode.DEFAULT,
                             wf.TimePolicy.EVENT)
            g.add_source(src).add_sink(wf.Sink_Builder(
                lambda t: total.__setitem__(0, total[0] + t["v0"])
                if t else None).build())
            g.run()
            return total[0]
        finally:
            nat._lib, nat._load_attempted = saved

    exp = sum(r[2] for r in recs)
    assert run(False) == exp
    assert run(True) == exp


def test_columnar_sink_end_to_end():
    """bytes → FrameSource → MapTPU → columnar Sink: the sink receives
    SinkColumns (SoA numpy + timestamp lane), no per-record dicts, and the
    totals match the record-sink run exactly."""
    n, n_keys = 500, 5
    recs = [(i % n_keys, 1_000_000 + i, float(i)) for i in range(n)]
    blob = frames_bytes(recs, nv=1)

    def run(columnar):
        got = {"sum": 0.0, "rows": 0, "batches": 0, "ts_sum": 0}

        def col_sink(c, ctx=None):
            if c is None:
                return
            assert isinstance(c, wf.SinkColumns)
            assert isinstance(c.cols["v0"], np.ndarray)
            got["sum"] += float(c.cols["v0"].sum())
            got["rows"] += len(c)
            got["batches"] += 1
            got["ts_sum"] += int(c.tss.sum())

        def rec_sink(t, ctx=None):
            if t is None:
                return
            got["sum"] += t["v0"]
            got["rows"] += 1
            got["ts_sum"] += 0

        src = FrameSource(lambda: iter([blob]), nv=1, fmt="frames",
                          output_batch_size=64)
        b = wf.Sink_Builder(col_sink if columnar else rec_sink)
        if columnar:
            b = b.withColumnarSink()
        g = wf.PipeGraph("colsink", wf.ExecutionMode.DEFAULT,
                         wf.TimePolicy.EVENT)
        g.add_source(src).add(wf.MapTPU_Builder(
            lambda t: {"key": t["key"], "v0": t["v0"] * 2.0}).build()) \
            .add_sink(b.build())
        g.run()
        return got

    col = run(True)
    rec = run(False)
    assert col["rows"] == rec["rows"] == n
    assert abs(col["sum"] - rec["sum"]) < 1e-6
    assert col["batches"] <= -(-n // 64) + 1
    assert col["ts_sum"] == sum(r[1] for r in recs)


def test_chunk_spanning_batches_do_not_fire_ahead():
    """One parse chunk spanning many staged batches (chunk >> batch cap):
    head batches must NOT carry the chunk's watermark — it covers tail rows
    still buffered in the emitter — or TB windows fire ahead of unplaced
    data and drop it as late.  Ordered stream => exact results, zero late."""
    n, n_keys = 1000, 4
    TWIN, TSLIDE = 16_000, 4_000
    recs = [(i % n_keys, i * 1000, float(i)) for i in range(n)]
    blob = frames_bytes(recs, nv=1)   # ONE chunk, staged as 64-row batches

    got = {}
    src = FrameSource(lambda: iter([blob]), nv=1, fmt="frames",
                      output_batch_size=64)
    op = (wf.Ffat_WindowsTPU_Builder(lambda t: t["v0"], lambda a, b: a + b)
          .withTBWindows(TWIN, TSLIDE).withKeyBy(lambda t: t["key"])
          .withMaxKeys(n_keys).build())
    snk = wf.Sink_Builder(
        lambda r: got.__setitem__((int(r["key"]), int(r["wid"])),
                                  float(r["value"]))
        if r is not None else None).build()
    g = wf.PipeGraph("chunk_span", wf.ExecutionMode.DEFAULT,
                     wf.TimePolicy.EVENT)
    g.add_source(src).add(op).add_sink(snk)
    g.run()

    exp = {}
    per_key = {}
    for k, ts, v in recs:
        per_key.setdefault(k, []).append((ts, v))
    for k, pts in per_key.items():
        wids = set()
        for ts, _ in pts:
            last = ts // TSLIDE
            first = max(0, -(-(ts - TWIN + 1) // TSLIDE))
            wids.update(range(first, last + 1))
        for w in wids:
            vals = [v for ts, v in pts
                    if w * TSLIDE <= ts < w * TSLIDE + TWIN]
            if vals:
                exp[(k, w)] = sum(vals)
    st = op.dump_stats()
    assert st["Late_tuples_dropped"] == 0
    assert st["Pane_cells_evicted"] == 0
    assert got == exp


def test_keyby_placement_agrees_across_paths():
    """The per-tuple, columnar-native, and on-device keyby paths must place
    every key on the same replica (a keyed operator can be fed by host and
    device edges at once)."""
    import jax.numpy as jnp
    from windflow_tpu import native
    from windflow_tpu.parallel.emitters import (_splitmix64_dev,
                                                splitmix64_int)

    rnd = np.random.default_rng(3)
    keys = rnd.integers(-2**31, 2**31, 257).astype(np.int64)
    for n in (2, 3, 7):
        native_dest, _ = native.keyby_partition(keys, n)
        py_dest = np.array([splitmix64_int(int(k)) % n for k in keys])
        dev_dest = np.asarray(
            _splitmix64_dev(jnp.asarray(keys, jnp.int32)) % jnp.uint64(n))
        assert np.array_equal(native_dest, py_dest)
        assert np.array_equal(native_dest, dev_dest.astype(np.int64))


# ---------------------------------------------------------------------------
# the one-pass route: frames parsed straight into the staged batch
# ---------------------------------------------------------------------------

def _stream(n, nv, keys):
    """``n`` frames whose event time rises unevenly and falls back now and
    then (the row frontier is a running max, not the last ts); ``keys``
    picks the key lane's story."""
    rng = np.random.default_rng(11)
    rec = np.zeros(n, dtype=[("k", "<i8"), ("t", "<i8"), ("v", "<f8", (nv,))])
    rec["t"] = np.cumsum(rng.integers(0, 50, n)) - rng.integers(0, 30, n)
    rec["v"] = rng.normal(size=(n, nv)) * 1e3 + 0.1
    k = rng.integers(0, 100, n)
    if keys == "leave_int32":
        # 64-bit ids in the middle fifth only: the chunks before and after
        # fit int32, the ones that hold any of these do not
        k = k.astype(np.int64)
        k[2 * n // 5:3 * n // 5] += 1 << 40
    elif keys == "negative":
        k = -1 - k
    elif keys == "both_signs":
        k = k - 50
    rec["k"] = k
    return rec


def _run_stream(monkeypatch, rec, *, chunk, cap, one_pass=True, nv=1,
                fmt="frames", policy=None, punct=0, mesh=None,
                value_dtype=np.float32):
    """The stream through FrameSource -> MapTPU -> Sink.  Returns every
    finished packed batch as the staging edge stamped it (the buffer word
    for word, n, the watermark stamp, the frontier, ts_min / ts_max, the
    lanes' dtypes), the rows the sink saw and the edge's counters."""
    import dataclasses

    from windflow_tpu.basic import default_config
    from windflow_tpu.io import frames
    from windflow_tpu.ops import source as source_mod
    from windflow_tpu.parallel import emitters

    packets = []

    class Recorded(emitters._StagedPacket):
        def __init__(self, buf, fmt_, wm, frontier, ts_min, ts_max, n,
                     seq, trace, logical, pool, treedef, dtypes, capacity):
            super().__init__(buf, fmt_, wm, frontier, ts_min, ts_max, n,
                             seq, trace, logical, pool, treedef, dtypes,
                             capacity)
            packets.append({"words": buf.copy(), "n": n, "wm": wm,
                            "frontier": frontier, "ts_min": ts_min,
                            "ts_max": ts_max, "dtypes": dtypes})

    monkeypatch.setattr(emitters, "_StagedPacket", Recorded)
    if not one_pass:
        monkeypatch.setattr(frames.FrameSourceReplica, "_ingest_in_place",
                            lambda self, buf, final: False)
    # ingress time: the source's clock ticks once a chunk on both routes,
    # the punctuation's stands still
    ticks = iter(range(10 ** 9, 10 ** 12, 1000))
    monkeypatch.setattr(frames, "current_time_usecs", lambda: next(ticks))
    monkeypatch.setattr(source_mod, "current_time_usecs", lambda: 10 ** 9)

    if fmt == "frames":
        blob = rec.tobytes()
    else:
        blob = b"".join(b"%d,%d,%s\n" % (
            r["k"], r["t"], b",".join(b"%r" % float(v) for v in r["v"]))
            for r in rec)

    def chunks():
        for lo in range(0, len(blob), chunk):
            yield blob[lo:lo + chunk]

    rows = []
    src = FrameSource(chunks, nv=nv, fmt=fmt, output_batch_size=cap,
                      value_dtype=value_dtype)
    cfg = dataclasses.replace(default_config, wire_compression="off",
                              punctuation_amount=punct,
                              punctuation_interval_usec=10 ** 15,
                              mesh=mesh)
    g = wf.PipeGraph("one_pass", wf.ExecutionMode.DEFAULT,
                     policy or wf.TimePolicy.EVENT, config=cfg)
    mp = g.add_source(src)
    mp.add(wf.MapTPU_Builder(
        lambda t: {"key": t["key"], "a": t["v0"],
                   "z": t[f"v{nv - 1}"]}).build())
    mp.add_sink(wf.Sink_Builder(
        lambda t: rows.append((int(t["key"]), t["a"], t["z"]))
        if t is not None else None).build())
    g.run()
    st = g.stats()["Staging"]
    return packets, rows, {k: v for k, v in st.items() if k != "Wire"}


ONE_PASS_CASES = {
    # 700 B chunks (29 frames and a piece) into batches of 64: every
    # third chunk splits a batch, every chunk ends inside a record
    "chunks_split_a_batch": dict(n=1500, chunk=700, cap=64),
    # chunks shorter than a frame: the carry grows over several chunks
    "record_over_three_chunks": dict(n=300, chunk=10, cap=64),
    # one chunk, many batches: each is stamped at ITS last row
    "one_chunk_many_batches": dict(n=1000, chunk=1 << 30, cap=64),
    # a punctuation every 150 tuples flushes the open batch short
    "partial_batches_by_punctuation": dict(n=1500, chunk=997, cap=256,
                                           punct=150),
    # the last batch is partial and only the end of the stream ships it
    "partial_last_batch": dict(n=1000, chunk=997, cap=256),
    # chunks that fit the open batch: written at the width of the chunk
    # before, checked against the keys read, written again where wrong
    "keys_leave_int32": dict(n=2000, chunk=997, cap=256,
                             keys="leave_int32"),
    # every chunk splits a batch: its keys are scanned before a row ships
    "keys_leave_int32_in_chunks_that_split": dict(
        n=2000, chunk=7000, cap=128, keys="leave_int32"),
    "negative_keys": dict(n=600, chunk=997, cap=64, keys="negative"),
    "keys_of_both_signs": dict(n=600, chunk=997, cap=64,
                               keys="both_signs"),
    "nv5": dict(n=1500, chunk=1000, cap=128, nv=5),
    "nv5_punctuated": dict(n=1500, chunk=4096, cap=512, nv=5, punct=700),
    "ingress_time": dict(n=1500, chunk=997, cap=64,
                         policy=wf.TimePolicy.INGRESS),
    "ingress_time_punctuated": dict(n=1500, chunk=997, cap=256, punct=150,
                                    policy=wf.TimePolicy.INGRESS),
    "int32_values": dict(n=800, chunk=997, cap=64, nv=2,
                         value_dtype=np.int32),
    "int64_values": dict(n=800, chunk=997, cap=64, nv=2,
                         value_dtype=np.int64),
}


@pytest.mark.parametrize("case", sorted(ONE_PASS_CASES))
def test_one_pass_stages_what_two_passes_stage(monkeypatch, case):
    """The same byte stream through the one-pass route (the native parse
    writes the staged words) and the two-pass route (columns, then
    ``emit_columns``): the finished staging buffers are equal word for
    word, and so are n, the watermark stamp, the frontier and ts_min /
    ts_max of every batch, the edge's counters and the sink's rows."""
    kw = dict(ONE_PASS_CASES[case])
    rec = _stream(kw.pop("n"), kw.get("nv", 1), kw.pop("keys", "narrow"))
    with monkeypatch.context() as m:
        one = _run_stream(m, rec, one_pass=True, **kw)
    with monkeypatch.context() as m:
        two = _run_stream(m, rec, one_pass=False, **kw)
    n = len(rec)
    assert one[2].pop("parsed_in_place_tuples") == n == one[2]["tuples"]
    assert two[2].pop("parsed_in_place_tuples") == 0
    assert one[2] == two[2]
    assert len(one[0]) == len(two[0]) == one[2]["batches"]
    for a, b in zip(one[0], two[0]):
        assert np.array_equal(a.pop("words"), b.pop("words"))
        assert a == b
    assert sum(p["n"] for p in one[0]) == n
    assert one[1] == two[1] and len(one[1]) == n
    if case.startswith("keys_leave_int32"):
        # the builder was finalized at each change of width and no key
        # lost its high word
        widths = [p["dtypes"][0] for p in one[0]]
        assert widths[0] == widths[-1] == "int32" and "int64" in widths
        assert sorted(r[0] for r in one[1]) == sorted(rec["k"].tolist())
    if "punct" in kw:
        assert one[2]["partial_batches"] >= n // kw["punct"] - 1
    if case == "keys_of_both_signs":
        # a chunk that straddles zero keeps the wire's width
        assert {p["dtypes"][0] for p in one[0]} == {"int64"}


def _no_native(monkeypatch):
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_load_attempted", True)


def _mesh_of_two():
    from windflow_tpu.parallel.mesh import make_mesh
    return make_mesh(2)


@pytest.mark.parametrize("fallback", ["csv", "no_native_library",
                                      "mesh_edge", "float64_values",
                                      "host_edge"])
def test_one_pass_is_not_taken_where_the_edge_or_the_input_cannot(
        monkeypatch, fallback):
    """CSV, the numpy twins, an unpackable value dtype, a mesh staging
    edge and a host edge keep the two-pass route: no row is parsed in
    place, and every row arrives."""
    rec = _stream(700, 2, "narrow")
    kw = dict(chunk=997, cap=64, nv=2)
    if fallback == "csv":
        kw["fmt"] = "csv"
    elif fallback == "no_native_library":
        _no_native(monkeypatch)
    elif fallback == "mesh_edge":
        kw["mesh"] = _mesh_of_two()
    elif fallback == "float64_values":
        kw["value_dtype"] = np.float64
    if fallback == "host_edge":
        got = []
        src = FrameSource(lambda: iter([rec.tobytes()]), nv=2,
                          output_batch_size=64)
        g = wf.PipeGraph("host_edge", wf.ExecutionMode.DEFAULT,
                         wf.TimePolicy.EVENT)
        g.add_source(src).add_sink(wf.Sink_Builder(
            lambda t: got.append((t["key"], t["v0"], t["v1"]))
            if t else None).build())
        g.run()
        rows, st = got, g.stats()["Staging"]
        assert st["tuples"] == 0
    else:
        packets, rows, st = _run_stream(monkeypatch, rec, **kw)
        assert st["tuples"] == len(rec)
        assert bool(packets) == (fallback in ("csv", "no_native_library"))
    assert st["parsed_in_place_tuples"] == 0
    vd = kw.get("value_dtype", np.float32)
    exp = [(int(r["k"]), vd(r["v"][0]), vd(r["v"][1])) for r in rec]
    assert sorted(rows) == sorted(exp)


@pytest.mark.parametrize("keys", ["narrow", "leave_int32"])
@pytest.mark.parametrize("one_pass", [True, False],
                         ids=["one_pass", "two_pass"])
def test_shard_probe_reads_the_rows_either_way(monkeypatch, one_pass, keys):
    """A staging edge that feeds a keyed device operator carries the shard
    plane's key probe.  On the one-pass route it reads the rows back
    from the staging buffer (``rows_view``): the sketch holds what it
    holds when the probe is handed columns, and the edge still parses in
    place.  A chunk of 64-bit keys lies in the buffer as two word planes:
    the probe is handed its keys whole."""
    from windflow_tpu.io import frames
    from windflow_tpu.monitoring.shard_ledger import HostKeyProbe
    if not one_pass:
        monkeypatch.setattr(frames.FrameSourceReplica, "_ingest_in_place",
                            lambda self, buf, final: False)
    handed, columns = [], HostKeyProbe.columns
    monkeypatch.setattr(
        HostKeyProbe, "columns", lambda self, cols, n:
        handed.append(np.array(cols["key"][:n])) or columns(self, cols, n))
    rec = _stream(3000, 2, keys)
    blob = rec.tobytes()
    sums = {}
    src = FrameSource(lambda: (blob[i:i + 997]
                               for i in range(0, len(blob), 997)),
                      nv=2, output_batch_size=256)
    red = (wf.ReduceTPU_Builder(
        lambda a, b: {"key": a["key"], "v0": a["v0"] + b["v0"],
                      "v1": a["v1"]})
        .withKeyBy(lambda t: t["key"]).build())
    g = wf.PipeGraph("probed", wf.ExecutionMode.DEFAULT,
                     wf.TimePolicy.EVENT)
    g.add_source(src).add(red).add_sink(wf.Sink_Builder(
        lambda t: sums.__setitem__(int(t["key"]), float(t["v0"]))
        if t is not None else None).build())
    g.run()
    em = src.replicas[0].emitter
    probe = em._shard_probe
    assert probe is not None and not probe.dead
    assert probe.sketch.total == 3000
    assert np.concatenate(handed).tolist() == rec["k"].tolist()
    if keys == "leave_int32":
        assert {h.dtype.name for h in handed} == {"int32", "int64"}
    keys, counts = np.unique(rec["k"], return_counts=True)
    if probe.sketch.hist is not None:
        assert probe.sketch.hist[keys].tolist() == counts.tolist()
    else:
        assert int(probe.sketch.cms[0].sum()) == 3000
    st = g.stats()["Staging"]
    assert st["tuples"] == 3000
    assert st["parsed_in_place_tuples"] == (3000 if one_pass else 0)
    assert set(sums) == set(keys.tolist())
