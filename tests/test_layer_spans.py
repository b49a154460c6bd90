"""Layer spans (docs/OBSERVABILITY.md "Span tracing"): the one span
primitive of the flight recorder, at every layer boundary of a sweep.
Nothing here times anything: a fake ``TraceAnnotation`` records what a
profiler capture would hold, and the recorder's own table is compared with
itself."""

import dataclasses
import json
import os
import threading

import jax
import numpy as np
import pytest

import windflow_tpu as wf
from windflow_tpu import staging
from windflow_tpu.basic import default_config
from windflow_tpu.io import FrameSource
from windflow_tpu.monitoring import recorder as flightrec
from windflow_tpu.monitoring.openmetrics import (parse_exposition,
                                                 render_openmetrics)

#: every span name the FrameSource -> FFAT -> columnar sink graph reaches
#: per batch (``wf.pool.wait``, ``wf.megastep.drain`` have tests of their own)
REACHED = {"wf.sweep", "wf.source.tick", "wf.parse", "wf.pack",
           "wf.wire.encode", "wf.h2d", "wf.dispatch", "wf.compile",
           "wf.drain", "wf.sink.d2h", "wf.wait.d2h", "wf.sink.deliver"}


class _Annotation:
    """Stands where ``jax.profiler.TraceAnnotation`` does and keeps what a
    capture would: name, counts, the thread, and the open/close order."""

    made = []

    def __init__(self, name, **counts):
        self.name, self.counts = name, dict(counts)
        self.thread = threading.get_ident()
        self.opened = self.closed = None
        _Annotation.made.append(self)

    def __enter__(self):
        self.opened = len(_Annotation.made)
        return self

    def __exit__(self, *exc):
        self.closed = len(_Annotation.made)
        return False

    def set_metadata(self, **counts):
        self.counts.update(counts)


@pytest.fixture
def annotations(monkeypatch):
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Annotation)
    _Annotation.made = []
    return _Annotation.made


def _frame_graph(name, n=5000, cap=1024, **cfg_kw):
    """FrameSource -> keyed sliding count-window sum -> columnar sink,
    in 700-record chunks that never end on a batch."""
    rec = np.zeros(n, dtype=[("k", "<i8"), ("t", "<i8"), ("v0", "<f8")])
    rec["k"] = np.arange(n) % 8
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
           .withKeyBy(lambda t: t["key"]).withMaxKeys(8).build())
    got = []
    snk = wf.Sink_Builder(got.append).withColumnarSink().build()
    cfg = dataclasses.replace(default_config, **cfg_kw)
    g = wf.PipeGraph(name, wf.ExecutionMode.DEFAULT, wf.TimePolicy.EVENT,
                     config=cfg)
    g.add_source(src).add(win).add_sink(snk)
    return g, got


@pytest.fixture
def ran(annotations):
    g, got = _frame_graph("spans_on", trace_sample_every=2)
    g.run()
    assert sum(len(c) for c in got if c is not None) > 0
    return g, annotations


def test_every_reachable_span_is_recorded(ran):
    g, made = ran
    layers = g.stats()["Layers"]
    assert REACHED <= set(layers)
    assert REACHED <= {a.name for a in made}
    for name, row in layers.items():
        assert row["count"] == sum(a.name == name for a in made)
        assert 0 <= row["self_ns"] <= row["total_ns"]


def test_self_times_telescope_to_the_sweeps(ran):
    """A span's self time is its duration minus its children's, so on the
    driver thread the self times of all names add up to ``wf.sweep``'s
    total, to the nanosecond."""
    g, _ = ran
    table = g._recorder.layers(thread=threading.get_ident())
    assert sum(r["self_ns"] for r in table.values()) \
        == table["wf.sweep"]["total_ns"]
    # one thread recorded: the graph-wide table is the driver's
    assert g.stats()["Layers"] == table


def _parents(made):
    """span name -> the names of the spans its instances opened under."""
    def parent(a):
        inside = [b for b in made if b.opened < a.opened
                  and b.closed >= a.closed and b is not a]
        return max(inside, key=lambda b: b.opened).name if inside else None

    by_name = {}
    for a in made:
        by_name.setdefault(a.name, set()).add(parent(a))
    return by_name


def test_spans_nest_as_the_layers_do(ran):
    """parse and pack are siblings under the source's tick; the wire
    encode, the H2D and the unpack dispatch happen inside a pack; a
    compile inside the dispatch that met a new signature."""
    _, made = ran
    by_name = _parents(made)
    assert by_name["wf.sweep"] == {None}
    assert by_name["wf.source.tick"] == {"wf.sweep"}
    assert by_name["wf.parse"] == {"wf.source.tick"}
    assert by_name["wf.pack"] == {"wf.source.tick"}
    assert by_name["wf.drain"] == {"wf.sweep"}
    # (the window's flush at end of stream compiles under the drain, the
    # sink's egress pack under its transfer)
    assert "wf.dispatch" in by_name["wf.compile"] \
        <= {"wf.dispatch", "wf.drain", "wf.sink.d2h"}
    assert "wf.pack" in by_name["wf.wire.encode"]
    assert "wf.pack" in by_name["wf.h2d"]
    assert by_name["wf.sink.d2h"] <= {"wf.drain"}
    assert by_name["wf.sink.deliver"] <= {"wf.drain"}
    sweeps = [a.counts["sweep"] for a in made if a.name == "wf.sweep"]
    assert sweeps == list(range(1, len(sweeps) + 1))


def test_batch_number_is_shared_from_encode_to_sink(ran):
    g, made = ran
    staged = [a.counts["batch"] for a in made if a.name == "wf.wire.encode"]
    assert staged == sorted(set(staged)) and staged[0] >= 1
    for name, op in (("wf.h2d", None), ("wf.dispatch", "staging.unpack"),
                     ("wf.dispatch", "ffat")):
        seen = [a.counts["batch"] for a in made if a.name == name
                and (op is None or a.counts["op"] == op)]
        assert seen == staged, (name, op)
    # the sink delivers a batch a span, in receipt order (no ``batches``
    # count: it read 1 on every span since PR 37 and nothing read it)
    d2h = [a.counts for a in made if a.name == "wf.sink.d2h"]
    # (the window's flush at the end of the stream is born on the device:
    # it has no number)
    arrived = staged + [0]
    assert [c["batch"] for c in d2h] == arrived
    assert all(c["bytes"] > 0 and "batches" not in c for c in d2h)
    delivered = [a.counts["batch"] for a in made
                 if a.name == "wf.sink.deliver"]
    assert set(delivered) <= set(arrived)
    # a sampled batch's trace id is that number
    traced = {e["trace"] for e in g._recorder.events()}
    assert traced and traced <= set(staged)


def test_every_dispatch_has_a_span(ran):
    """Not one batch in 64: each staged batch shows its unpack and its
    operator step by ``op=`` and ``batch=``."""
    g, made = ran
    st = g.stats()
    disp = [a.counts for a in made if a.name == "wf.dispatch"]
    assert all("op" in c and "batch" in c for c in disp)
    n = st["Staging"]["batches"]
    assert sum(c["op"] == "staging.unpack" for c in disp) == n
    assert sum(c["op"] == "ffat" for c in disp) == n
    # (the unpack program is the process's: an earlier graph compiled it)
    compiled = {a.counts["op"] for a in made if a.name == "wf.compile"}
    assert "ffat" in compiled


def test_fill_is_counted_where_the_batch_is_cut(ran):
    """Chunks of 700 records into batches of 1024: what the punctuation
    and the end of stream flush short shows as ``n < cap`` on the wire
    encode and in the staging counters."""
    g, made = ran
    enc = [a.counts for a in made if a.name == "wf.wire.encode"]
    stg = g.stats()["Staging"]
    assert stg["batches"] == len(enc)
    assert stg["tuples"] == sum(c["n"] for c in enc) == 5000
    assert stg["capacity"] == sum(c["cap"] for c in enc) == 1024 * len(enc)
    assert stg["partial_batches"] == sum(c["n"] < c["cap"] for c in enc) >= 1
    assert all(c["bytes"] > 0 and c["logical"] >= c["n"] for c in enc)
    parsed = [a.counts for a in made if a.name == "wf.parse"]
    assert sum(c["n"] for c in parsed) == 5000
    assert sum(c["bytes"] for c in parsed) >= 5000 * 24
    assert sum(a.counts["n"] for a in made if a.name == "wf.pack") == 5000


def test_one_pass_edge_keeps_its_parse_and_pack_spans(ran):
    """The packed edge takes its rows in place (the native parse writes
    the staging buffer): every chunk still shows as ``wf.parse``, marked
    ``direct=1``, beside the ``wf.pack`` of the rows it wrote, a pair a
    slice (a chunk that splits a batch is two), and the edge counts
    every tuple as parsed in place."""
    g, made = ran
    parsed = [a.counts for a in made if a.name == "wf.parse"]
    packed = [a.counts for a in made if a.name == "wf.pack"]
    assert all(c.get("direct") == 1 for c in parsed)
    # 8 chunks of 700 records; 4 batch cuts fall inside a chunk
    assert len(parsed) == len(packed) == 8 + 4
    assert [c["n"] for c in parsed] == [c["n"] for c in packed]
    assert all(c["bytes"] == 24 * c["n"] for c in parsed)
    stg = g.stats()["Staging"]
    assert stg["parsed_in_place_tuples"] == stg["tuples"] == 5000


def test_two_pass_edge_marks_no_parse_direct(annotations, monkeypatch):
    """Without the native library the same edge takes columns: no span
    says ``direct`` and no tuple counts as parsed in place."""
    from windflow_tpu import native
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_load_attempted", True)
    g, got = _frame_graph("spans_two_pass", trace_sample_every=2)
    g.run()
    parsed = [a.counts for a in annotations if a.name == "wf.parse"]
    assert len(parsed) == 8 and not any("direct" in c for c in parsed)
    assert sum(c["n"] for c in parsed) == 5000
    stg = g.stats()["Staging"]
    assert stg["parsed_in_place_tuples"] == 0 and stg["tuples"] == 5000


def _session_graph(name, n=4000, cap=512, **cfg_kw):
    """Record source -> session windows (the shell every operator whose
    rows are bounded by the data shares) -> columnar sink."""
    src = (wf.Source_Builder(lambda: iter(
        {"key": np.int32(i % 8), "v": np.float32(1.0)} for i in range(n)))
        .withName("src").withOutputBatchSize(cap).build())
    win = (wf.Session_WindowsTPU_Builder(lambda t: t["v"],
                                         lambda a, b: a + b)
           .withName("sessions").withGap(50)
           .withKeyBy(lambda t: t["key"]).withMaxKeys(8).build())
    got = []
    snk = wf.Sink_Builder(got.append).withColumnarSink().build()
    cfg = dataclasses.replace(default_config, **cfg_kw)
    g = wf.PipeGraph(name, wf.ExecutionMode.DEFAULT, config=cfg)
    g.add_source(src).add(win).add_sink(snk)
    return g, got


def _stateful_graph(name, n=520, cap=64, **cfg_kw):
    """Record source -> stateful map that interns its keys on the host
    (no declared key space) -> record sink."""
    src = (wf.Source_Builder(lambda: iter(
        {"key": i % 6, "value": np.float32(i)} for i in range(n)))
        .withName("src").withOutputBatchSize(cap).build())
    m = (wf.MapTPU_Builder(
        lambda t, s: ({"key": t["key"], "value": s + t["value"]},
                      s + t["value"]))
        .withName("running").withKeyBy(lambda t: t["key"])
        .withInitialState(0.0).withNumKeySlots(64).build())
    got = []
    snk = wf.Sink_Builder(got.append).withName("snk").build()
    cfg = dataclasses.replace(default_config, **cfg_kw)
    g = wf.PipeGraph(name, wf.ExecutionMode.DEFAULT, config=cfg)
    g.add_source(src).add(m).add_sink(snk)
    return g, got


def _time_window_graph(name, n=4000, cap=256, **cfg_kw):
    """Record source -> keyed tumbling time windows over a pane ring the
    user sized, under the ``error`` overflow policy (its eviction count
    is read a step in 32 and before the flush) -> columnar sink."""
    src = (wf.Source_Builder(lambda: iter(
        {"key": np.int32(i % 8), "v": np.float32(1.0), "ts": i * 10}
        for i in range(n)))
        .withName("src").withTimestampExtractor(lambda t: t["ts"])
        .withOutputBatchSize(cap).build())
    win = (wf.Ffat_WindowsTPU_Builder(lambda t: t["v"], lambda a, b: a + b)
           .withName("tumbling").withTBWindows(1000, 1000)
           .withKeyBy(lambda t: t["key"]).withMaxKeys(8)
           .withPaneCapacity(64).withOverflowPolicy("error").build())
    got = []
    snk = wf.Sink_Builder(got.append).withColumnarSink().build()
    cfg = dataclasses.replace(default_config, **cfg_kw)
    g = wf.PipeGraph(name, wf.ExecutionMode.DEFAULT, wf.TimePolicy.EVENT,
                     config=cfg)
    g.add_source(src).add(win).add_sink(snk)
    return g, got


#: graph -> (the wait it reaches, the spans that wait opens under, how
#: the graph is configured to reach it, the tuples it stages)
WAIT_SITES = {
    # (the step's read under its dispatch; the last step's, read once
    # more before the flush, under the drain that ends the stream)
    "held": (_session_graph, "wf.wait.held", {"wf.dispatch", "wf.drain"},
             {}, 4000),
    # (a flush pass's reads at the end of the stream: a name of their
    # own, so that the one above stays the step's)
    "flush": (_session_graph, "wf.wait.flush", {"wf.drain"}, {}, 4000),
    "flush_tb": (_time_window_graph, "wf.wait.flush", {"wf.drain"}, {},
                 4000),
    "evicted": (_time_window_graph, "wf.wait.evicted", {"wf.drain"}, {},
                4000),
    "d2h": (_frame_graph, "wf.wait.d2h", {"wf.sink.d2h"}, {}, 5000),
    # (a device -> host edge reads the lanes in the producer's drain)
    "d2h_lanes": (_stateful_graph, "wf.wait.d2h", {"wf.drain"},
                  {"key_compaction": False}, 520),
    # (without the compaction plane the operator interns on the host)
    "keys": (_stateful_graph, "wf.wait.keys", {"wf.dispatch"},
             {"key_compaction": False}, 520),
    "sync": (_frame_graph, "wf.wait.sync", {"wf.drain"},
             {"trace_sample_every": 1, "trace_device_sync_every": 1}, 5000),
}


def _delivered(got):
    return sum(1 if isinstance(c, dict) else len(c)
               for c in got if c is not None)


@pytest.mark.parametrize("site", sorted(WAIT_SITES))
def test_recorder_off_constructs_nothing(annotations, site):
    build, _, _, cfg_kw, staged = WAIT_SITES[site]
    g, got = build(f"spans_off_{site}", flight_recorder=False, **cfg_kw)
    g.run()
    assert _delivered(got) > 0
    assert annotations == []
    assert g._recorder is None
    st = g.stats()
    assert st["Layers"] == {}
    # the counters are the program's, not the recorder's
    assert st["Staging"]["tuples"] == staged


@pytest.mark.parametrize("site", sorted(WAIT_SITES))
def test_a_wait_for_the_chip_is_a_span_of_its_own(annotations, site):
    """Each place the driver blocks on the device opens its wait under
    the span named in docs/OBSERVABILITY.md, innermost, so the parent's
    self time is the host's work: its total less the wait's (and less
    whatever else opened under it)."""
    build, wait, under, cfg_kw, _ = WAIT_SITES[site]
    g, got = build(f"spans_wait_{site}", **cfg_kw)
    g.run()
    assert _delivered(got) > 0
    by_name = _parents(annotations)
    assert by_name[wait] == under
    # innermost: nothing ever opens under a wait
    assert not [n for n, ps in by_name.items()
                if ps & set(flightrec.WAITS)]
    table = g._recorder.layers(thread=threading.get_ident())
    assert table[wait]["count"] == sum(a.name == wait for a in annotations)
    assert table[wait]["self_ns"] == table[wait]["total_ns"] > 0
    for parent in under:
        waits_under = sum(table[n]["total_ns"] for n in table
                          if n in flightrec.WAITS and by_name[n] == {parent})
        assert table[parent]["self_ns"] \
            <= table[parent]["total_ns"] - waits_under
    # the thread's blocked time is one derived number beside its sweeps
    sweep = g.stats()["Layers"]["wf.sweep"]
    assert sweep["wait_ns"] == sum(
        r["self_ns"] for n, r in table.items() if n in flightrec.WAITS)
    assert 0 < sweep["wait_ns"] < sweep["total_ns"]
    assert all("wait_ns" not in r for n, r in table.items()
               if n != "wf.sweep")


def test_held_wait_carries_the_batch_of_its_dispatch(annotations):
    g, _ = _session_graph("spans_held_batch")
    g.run()
    disp = [a for a in annotations if a.name == "wf.dispatch"
            and a.counts["op"] == "sessions"]
    held = [a for a in annotations if a.name == "wf.wait.held"]
    # the first step has no step before it to read; before the flush the
    # last step's count is read once more, numberless; the flush passes'
    # own reads go by another name
    in_step = [a.counts["batch"] for a in held if "batch" in a.counts]
    assert in_step == [a.counts["batch"] for a in disp[1:]]
    assert len(held) == len(in_step) + 1
    assert [a for a in annotations if a.name == "wf.wait.flush"]


def test_wait_refuses_a_name_the_table_lacks(annotations):
    with pytest.raises(ValueError, match="wf.wait.nonsense"):
        flightrec.wait("nonsense")
    rec = flightrec.FlightRecorder()
    with rec.span("wf.sweep", sweep=1):
        with pytest.raises(ValueError):
            flightrec.wait("pool.wait")     # not a wf.wait.* name
        with flightrec.wait("held", batch=3):
            pass
    assert [a.name for a in annotations] == ["wf.sweep", "wf.wait.held"]
    # outside a sweep a declared wait is as inert as any span
    assert flightrec.wait("d2h") is flightrec._NO_SPAN


def test_wait_ns_is_the_sweep_owning_threads_alone():
    """A pool thread (its root is a ``wf.drain``) that waits adds the
    wait's own row and nothing to ``wait_ns``: the share is of the
    sweeps' time."""
    rec = flightrec.FlightRecorder()
    with rec.span("wf.sweep", sweep=1):
        with flightrec.span("wf.pool.wait"):
            pass
        with flightrec.span("wf.dispatch", op="x"):
            with flightrec.wait("held"):
                pass

    def pool():
        with rec.span("wf.drain", op="hostmap"):
            with flightrec.wait("d2h"):
                pass

    t = threading.Thread(target=pool)
    t.start()
    t.join()
    table = rec.layers()
    # the span around a wait keeps its own work, to the nanosecond
    assert table["wf.dispatch"]["self_ns"] == table["wf.dispatch"]["total_ns"] \
        - table["wf.wait.held"]["total_ns"]
    assert table["wf.wait.d2h"]["count"] == 1
    assert table["wf.sweep"]["wait_ns"] == table["wf.pool.wait"]["self_ns"] \
        + table["wf.wait.held"]["self_ns"]
    assert "wait_ns" not in table["wf.drain"]


@pytest.mark.parametrize("name", sorted(flightrec.WAITS))
def test_every_wait_is_opened_somewhere_and_documented(name):
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    layer, blocks_for = flightrec.WAITS[name]
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        assert layer in {m["layer"] for m in json.load(f)["per_layer"]}
    assert blocks_for and "\n" not in blocks_for
    with open(os.path.join(root, "docs", "OBSERVABILITY.md")) as f:
        doc = f.read()
    section = doc[doc.index("## Span tracing"):doc.index("## Device phases")]
    assert section.count(f"`{name}`") >= 2      # its row, and the WAITS table
    opened = f'wait("{name[len(flightrec.WAIT_PREFIX):]}"' \
        if name.startswith(flightrec.WAIT_PREFIX) else f'span("{name}"'
    hits = []
    for top, _, files in os.walk(os.path.join(root, "windflow_tpu")):
        for fn in files:
            if fn.endswith(".py") and fn != "recorder.py":
                with open(os.path.join(top, fn)) as fh:
                    hits.append(opened in fh.read())
    assert any(hits), f"{name} is declared and opened nowhere"


def test_span_outside_a_sweep_is_inert(annotations):
    with flightrec.span("wf.h2d", batch=1, bytes=4) as sp:
        sp.note(bytes=8)
    assert annotations == []
    assert getattr(flightrec._open, "top", None) is None


def test_span_restores_the_stack_when_the_body_raises(annotations):
    rec = flightrec.FlightRecorder()
    with pytest.raises(ValueError):
        with rec.span("wf.sweep", sweep=1):
            with flightrec.span("wf.source.tick"):
                raise ValueError("user code")
    assert getattr(flightrec._open, "top", None) is None
    table = rec.layers()
    assert table["wf.sweep"]["count"] == table["wf.source.tick"]["count"] == 1
    assert table["wf.sweep"]["self_ns"] + table["wf.source.tick"]["self_ns"] \
        == table["wf.sweep"]["total_ns"]


def test_pool_wait_is_a_span_only_when_it_blocks(annotations):
    class Gate:
        def __init__(self, ready):
            self.ready = ready

        def is_ready(self):
            return self.ready

    rec = flightrec.FlightRecorder()
    pool = staging.StagingPool()
    with rec.span("wf.sweep", sweep=1):
        for ready in (True, False):
            pool.release(np.empty(256, np.uint32), gate=Gate(ready))
            pool.acquire(256)
    assert [a.name for a in annotations] == ["wf.sweep", "wf.pool.wait"]
    assert pool.gate_waits == 1


def test_megastep_group_is_spanned(annotations):
    """A K-group ships in one transfer, one dispatch and one blocking
    drain, each carrying the group's first batch and ``k``."""
    g, got = _frame_graph("spans_mega", n=16 * 1024, megastep_sweeps=2,
                          punctuation_interval_usec=10 ** 12,
                          wire_compression=False)
    g.run()
    edge = g.stats()["Megastep"]["edges"][0]
    assert edge["megasteps"] >= 1
    drains = [a.counts for a in annotations
              if a.name == "wf.megastep.drain"]
    mega = [a.counts for a in annotations if a.name == "wf.dispatch"
            and a.counts["op"].startswith("megastep.")]
    assert len(drains) == len(mega) == edge["megasteps"]
    assert all(c["k"] == 2 and c["batch"] >= 1 for c in drains + mega)
    assert sum(c["bytes"] > 2 * 1024 * 4 for c in
               (a.counts for a in annotations if a.name == "wf.h2d")) \
        >= edge["megasteps"]


def test_pool_threads_record_tables_of_their_own(annotations):
    """A host operator drained on the worker pool: its ``wf.drain`` is a
    root on the pool thread, and that thread's self times telescope to it
    as the driver's do to ``wf.sweep``."""
    n = 3000
    src = (wf.Source_Builder(
        lambda: iter({"key": i % 8, "v": float(i)} for i in range(n)))
        .withName("src").withOutputBatchSize(256).build())
    m = wf.Map_Builder(lambda t: {"key": t["key"], "v": t["v"] + 1.0}) \
        .withName("hostmap").build()
    seen = []
    snk = wf.Sink_Builder(lambda t, ctx=None: seen.append(t)) \
        .withName("snk").build()
    cfg = dataclasses.replace(default_config, host_worker_threads=2)
    g = wf.PipeGraph("spans_pool", wf.ExecutionMode.DEFAULT, config=cfg)
    g.add_source(src).add(m).add_sink(snk)
    g.run()
    assert len([t for t in seen if t is not None]) == n
    me = threading.get_ident()
    pool_threads = [t for t in g._recorder._layers if t != me]
    assert pool_threads
    for t in pool_threads:
        table = g._recorder.layers(thread=t)
        assert "wf.sweep" not in table
        assert sum(r["self_ns"] for r in table.values()) \
            == table["wf.drain"]["total_ns"]
    assert {a.thread for a in annotations if a.name == "wf.drain"} \
        >= set(pool_threads)
    total = g.stats()["Layers"]["wf.drain"]["count"]
    assert total == sum(a.name == "wf.drain" for a in annotations)


def test_layers_are_one_openmetrics_family(ran):
    g, _ = ran
    st = g.stats()
    fams = parse_exposition(render_openmetrics(st))
    fam = fams["wf_layer_span_total"]
    assert fam["type"] == "counter"
    got = {(lab["span"], lab["stat"]): v for _, lab, v in fam["samples"]}
    for name, row in st["Layers"].items():
        for stat in ("count", "total_ns", "self_ns"):
            assert got[(name, stat)] == row[stat]
    # the derived number rides the same family, on the sweep's row alone
    assert st["Layers"]["wf.sweep"]["wait_ns"] > 0
    assert got[("wf.sweep", "wait_ns")] == st["Layers"]["wf.sweep"]["wait_ns"]
    assert [k for k in got if k[1] == "wait_ns"] == [("wf.sweep", "wait_ns")]
    assert not any(n.startswith("wf_layer") and n != "wf_layer_span_total"
                   for n in fams)
