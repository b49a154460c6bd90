"""The four-chip keyby deployment under the benchmark's traffic, small, on
the CPU's virtual devices: chunks that do not divide the staging batch,
the cadence punctuation cutting half-filled batches, key shards that add
up to the uncut reference, and the mesh path's layer spans.  Nothing is
timed."""

import dataclasses
import json
import math
import os
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import windflow_tpu as wf  # noqa: E402
from benchmark import harness, reference as ref  # noqa: E402
from benchmark.generator import OpenLoop  # noqa: E402
from test_layer_spans import _Annotation  # noqa: E402  (the fake capture)
from windflow_tpu.basic import default_config  # noqa: E402
from windflow_tpu.io import FrameSource  # noqa: E402
from windflow_tpu.parallel import mesh as M  # noqa: E402

TINY = dict(batch=1024, n_keys=32, win=64, slide=16, ring_batches=4)
MIX = {"rate": "always_due", "event_rate": 100_000}
SEED = 2**31 + 29
N_TOTAL = 10 * TINY["batch"] + 300           # ends inside a batch


def _cell_config(name):
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        return harness.with_sizes(json.load(f), TINY)


def _drive(g, punctuate_every):
    """``g.run()`` with the cadence punctuation on a count of sweeps in
    place of the clock, so that it cuts the same batches every time."""
    g.start()
    sweeps = 0
    while not g.is_done():
        g.step()
        sweeps += 1
        if sweeps % punctuate_every == 0:
            for sr in g._source_replicas:
                if not sr.exhausted:
                    sr.punctuate()
    g.wait_end()


def _run_cell_graph(config_name, chunk_records, punctuate_every=3):
    """The benchmark's own graph of ``config_name`` over the first
    ``N_TOTAL`` (rounded up to whole chunks) tuples of the seeded ring."""
    cfg = _cell_config(config_name)
    mod = harness.load_module("configs", config_name)
    ring = mod.make_ring(SEED, cfg)
    gen = OpenLoop(ring["rec"], MIX, 0.0, chunk_records)

    def chunks():
        for buf in gen.chunks():
            yield buf
            if gen.pulled >= N_TOTAL:
                return

    sink = harness.SinkRecorder(harness._no_span)
    g = mod.build_graph(cfg, ring, chunks, sink)
    _drive(g, punctuate_every)
    got = {n: sink.column(n) for n in ("key", "wid", "value")}
    return cfg, mod, ring, gen.pulled, got, g.stats()


@pytest.mark.parametrize("chunk_records", [170, 333, 1000])
def test_mesh_graph_on_chunks_that_do_not_divide_the_batch(chunk_records):
    """(a) mesh graph == reference == one-chip graph on the same ring,
    with half-filled batches cut by the punctuation on the way."""
    cfg, mod, ring, n, got, st = _run_cell_graph("ffat_sum_mesh4",
                                                 chunk_records)
    stg = st["Staging"]
    assert stg["partial_batches"] >= 3 and stg["tuples"] == n
    assert stg["capacity"] > stg["tuples"]          # stubs ship padded
    exp = mod.expected(cfg, ring, n, MIX)
    checks = mod.compare(cfg, got, exp)
    assert ref.verdict(checks), checks
    assert st["Dropped_tuples"] == 0
    # the one-chip deployment on the same ring and chunks
    cfg1, mod1, _, n1, got1, _ = _run_cell_graph("ffat_sum", chunk_records)
    assert n1 == n and cfg1["graph"]["mesh"] == 0
    a = np.lexsort((got["wid"], got["key"]))
    b = np.lexsort((got1["wid"], got1["key"]))
    np.testing.assert_array_equal(got["key"][a], got1["key"][b])
    np.testing.assert_array_equal(got["wid"][a], got1["wid"][b])
    np.testing.assert_allclose(got["value"][a], got1["value"][b],
                               rtol=cfg["check"]["sum_rtol"])


@pytest.mark.parametrize("data,fills", [
    (1, (64, 64, 64, 64)),            # full batches
    (1, (64, 11, 64, 40, 1, 64)),     # stubs the punctuation would cut
    (2, (64, 23, 64, 9)),             # a (2, 2) mesh: shards replicated
])
def test_key_shards_add_up_to_the_uncut_reference(data, fills):
    """(b) every (key, wid) row comes out of exactly one key shard, whose
    key range holds it, and the union over the shards is what the plain
    reference gives for the whole stream."""
    cap, K, win, slide = 64, 16, 8, 4
    mesh = M.make_mesh(4, data=data)
    kk = mesh.shape[M.KEY_AXIS]
    K_local = K // kk
    Pn = math.gcd(win, slide)
    R, D = win // Pn, slide // Pn
    rng = np.random.default_rng(5)
    step = M.make_sharded_ffat_step(mesh, cap, K, Pn, R, D,
                                    lambda x: x["v"], lambda a, b: a + b,
                                    lambda x: x["k"])
    flush = M.make_sharded_ffat_flush(mesh, K, Pn, R, D, lambda a, b: a + b)
    state = M.make_sharded_ffat_state(jnp.zeros((), jnp.float32), K, R, mesh)
    sh = M.batch_sharding(mesh)
    all_k, all_v = [], []
    rows = {}                          # (key, wid) -> [(shard, value)]

    def collect(out, fired):
        blocks = {}
        for name, arr in (("key", out["key"]), ("wid", out["wid"]),
                          ("value", out["value"]), ("fired", fired)):
            for s in arr.addressable_shards:
                blocks.setdefault(s.index[0].start // (arr.shape[0] // kk),
                                  {})[name] = np.asarray(s.data)
        assert sorted(blocks) == list(range(kk))
        for shard, b in blocks.items():
            f = b["fired"]
            for k, w, v in zip(b["key"][f], b["wid"][f], b["value"][f]):
                assert shard * K_local <= k < (shard + 1) * K_local
                rows.setdefault((int(k), int(w)), []).append((shard, v))

    for i, n in enumerate(fills):
        keys = rng.integers(0, K, cap).astype(np.int32)
        vals = rng.random(cap).astype(np.float32)
        all_k.append(keys[:n])
        all_v.append(vals[:n])
        put = lambda a: jax.device_put(a, sh)        # noqa: E731
        state, out, fired, _ = step(
            state, {"k": put(keys), "v": put(vals)},
            put(np.arange(i * cap, (i + 1) * cap, dtype=np.int64)),
            put(np.arange(cap) < n))
        collect(out, fired)
    out, fired, _ = flush(state)
    collect(out, fired)

    assert all(len(v) == 1 for v in rows.values())
    keys, vals = np.concatenate(all_k), np.concatenate(all_v)
    exp = ref.cb_windows_of_ring(keys.astype(np.int64),
                                 vals.astype(np.float64),
                                 np.ones(len(keys), bool), len(keys),
                                 win, slide)
    assert sorted(rows) == sorted(zip(exp.key.tolist(), exp.wid.tolist()))
    got = np.array([rows[kw][0][1] for kw in zip(exp.key.tolist(),
                                                 exp.wid.tolist())])
    np.testing.assert_allclose(got, exp.value, rtol=win * 2.0 ** -23)
    # each shard fired something: the stream's keys cover every range
    assert {s for v in rows.values() for s, _ in v} == set(range(kk))


def _mesh_graph(mesh, prelude, n=5000, cap=1024, keys=8):
    """FrameSource (-> Map + Filter) -> keyed sliding sum -> columnar
    sink on ``mesh``, in 700-record chunks that never end on a batch."""
    rec = np.zeros(n, dtype=[("k", "<i8"), ("t", "<i8"), ("v0", "<f8")])
    rec["k"] = np.arange(n) % keys
    rec["t"] = np.arange(n)
    rec["v0"] = 1.0
    blob = rec.tobytes()
    step = rec.dtype.itemsize * 700

    def chunks():
        for i in range(0, len(blob), step):
            yield blob[i:i + step]

    src = FrameSource(chunks, nv=1, fmt="frames", output_batch_size=cap)
    src.record_spec = {"key": np.int32(0), "v0": np.float32(0.0)}
    win = (wf.Ffat_WindowsTPU_Builder(lambda t: t["v0"], lambda a, b: a + b)
           .withName("ffat").withCBWindows(16, 4)
           .withKeyBy(lambda t: t["key"]).withMaxKeys(keys).build())
    got = []
    snk = wf.Sink_Builder(got.append).withColumnarSink().build()
    g = wf.PipeGraph("mesh_spans", wf.ExecutionMode.DEFAULT,
                     wf.TimePolicy.EVENT,
                     config=dataclasses.replace(default_config, mesh=mesh))
    pipe = g.add_source(src)
    if prelude:
        pipe.add(wf.MapTPU_Builder(
            lambda t: {"key": t["key"], "v0": t["v0"] * 2.0})
            .withName("double").build())
        pipe.chain(wf.FilterTPU_Builder(lambda t: t["key"] >= 0)
                   .withName("keep").build())
    pipe.add(win).add_sink(snk)
    return g, got


@pytest.mark.parametrize("data,prelude,replication,dispatches", [
    (1, True, 4, {"double|keep", "ffat"}),    # the cell's mapping
    (2, True, 2, {"double|keep", "ffat"}),    # a (2, 2) mesh
    (1, False, 1, {"ffat"}),                  # host-fed: key-aligned ingest
])
def test_mesh_path_opens_the_layer_spans(monkeypatch, data, prelude,
                                         replication, dispatches):
    """(c) ``wf.pack`` and ``wf.h2d`` on the mesh staging, one
    ``wf.dispatch`` per program and batch with ``mesh=``, and
    ``Bytes_H2D_total`` is what the ``wf.h2d`` spans' ``bytes`` sum to."""
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Annotation)
    _Annotation.made = made = []
    g, got = _mesh_graph(M.make_mesh(4, data=data), prelude)
    g.run()
    assert sum(len(c) for c in got if c is not None) > 0
    st = g.stats()
    layers = st["Layers"]
    assert {"wf.pack", "wf.h2d", "wf.dispatch", "wf.compile"} <= set(layers)

    h2d = [a.counts for a in made if a.name == "wf.h2d"]
    n_batches = len(h2d)
    assert n_batches >= 5
    assert all(c["shards"] == 4 and c["logical"] > 0 for c in h2d)
    assert all(c["bytes"] == replication * c["logical"] for c in h2d)
    assert st["Bytes_H2D_total"] == sum(c["bytes"] for c in h2d)
    assert st["Bytes_H2D_logical_total"] == sum(c["logical"] for c in h2d)
    staged = [c["batch"] for c in h2d]
    assert staged == sorted(set(staged)) and staged[0] >= 1
    # the fill rides the transfer's span: chunks of 700 into 1024
    assert sum(c["n"] for c in h2d) == 5000
    assert all(c["cap"] == 1024 for c in h2d)
    assert any(c["n"] < c["cap"] for c in h2d)

    disp = [a.counts for a in made if a.name == "wf.dispatch"]
    assert {c["op"] for c in disp} == dispatches
    assert all(c["mesh"] == 4 for c in disp)
    for op in dispatches:
        assert [c["batch"] for c in disp if c["op"] == op] == staged
    compiled = {a.counts["op"] for a in made if a.name == "wf.compile"}
    assert "ffat.mesh" in compiled

    # the transfer nests in the pack, so the pack's self time leaves it out
    def parents(name):
        out = set()
        for a in made:
            if a.name != name:
                continue
            inside = [b for b in made if b.opened < a.opened
                      and b.closed >= a.closed and b is not a]
            out.add(max(inside, key=lambda b: b.opened).name)
        return out

    packs = [a for a in made if a.name == "wf.pack"]
    assert packs and parents("wf.h2d") <= {"wf.pack", "wf.source.tick"}
    assert "wf.pack" in parents("wf.h2d")
    table = g._recorder.layers(thread=threading.get_ident())
    assert sum(r["self_ns"] for r in table.values()) \
        == table["wf.sweep"]["total_ns"]
