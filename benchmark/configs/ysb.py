"""``ysb``: the Yahoo Streaming Benchmark at its published shape (smoke
leg B's graph, with all seven fields on the wire) — graph builder, stream
schema and plain reference."""

from __future__ import annotations

import numpy as np

from benchmark import reference as ref
from benchmark.generator import frame_dtype

VIEW = 1                 # event types: 0 purchase, 1 view, 2 click
EVENT_TYPE = "v3"        # frame value lanes: user_id, page_id, ad_type,
N_FIELDS = 5             # event_type, ip
TABLE_SEED = 0x595342


def make_ring(seed: int, cfg: dict) -> dict:
    g, s = cfg["graph"], cfg["stream"]
    n = g["batch"] * s["ring_batches"]
    n_ads = g["campaigns"] * g["ads_per_campaign"]
    # the ad -> campaign table is the deployment's, not the stream's: the
    # same in every run, so the join's program (the table is a constant of
    # it) is found in the compilation cache whatever the seed
    table = np.random.default_rng(TABLE_SEED).permutation(
        np.repeat(np.arange(g["campaigns"]), g["ads_per_campaign"])) \
        .astype(np.int32)
    rng = np.random.default_rng(seed)
    rec = np.empty(n, dtype=frame_dtype(N_FIELDS))
    rec["k"] = rng.integers(0, n_ads, n)
    rec["t"] = 0
    # ids below 2**24 so the float32 lanes hold them exactly
    rec["v0"] = rng.integers(0, 1 << 20, n)          # user_id
    rec["v1"] = rng.integers(0, 1 << 16, n)          # page_id
    rec["v2"] = rng.integers(0, 5, n)                # ad_type
    rec[EVENT_TYPE] = rng.integers(0, 3, n)
    rec["v4"] = rng.integers(0, 1 << 24, n)          # ip
    return {"rec": rec, "table": table}


def build_graph(cfg: dict, ring: dict, chunks_fn, sink_fn):
    import jax.numpy as jnp

    import windflow_tpu as wf
    from windflow_tpu.io import FrameSource
    g = cfg["graph"]
    src = FrameSource(chunks_fn, nv=N_FIELDS, fmt="frames",
                      output_batch_size=g["batch"])
    src.record_spec = {"key": np.int32(0),
                       **{f"v{i}": np.float32(0.0) for i in range(N_FIELDS)}}
    table = jnp.asarray(ring["table"])
    flt = wf.FilterTPU_Builder(
        lambda e: e[EVENT_TYPE] == float(VIEW)).build()
    prj = wf.MapTPU_Builder(
        lambda e: {"campaign": table[e["key"]], "one": 1}).build()
    win = (wf.Ffat_WindowsTPU_Builder(lambda e: e["one"], lambda a, b: a + b)
           .withName("campaign_counts")
           .withTBWindows(g["window_usec"], g["window_usec"])
           .withKeyBy(lambda e: e["campaign"])
           .withMaxKeys(g["campaigns"]).withSumCombiner().build())
    snk = wf.Sink_Builder(sink_fn).withColumnarSink().build()
    graph = wf.PipeGraph("bench_" + cfg["name"], wf.ExecutionMode.DEFAULT,
                         wf.TimePolicy.EVENT, config=wf.Config())
    pipe = graph.add_source(src)
    pipe.add(flt)
    pipe.chain(prj)          # Filter + Map(join) fuse into one program
    pipe.add(win).add_sink(snk)
    return graph


def _campaign_of_views(ring: dict) -> np.ndarray:
    """Campaign of each ring record, -1 where the view filter drops it."""
    rec = ring["rec"]
    camp = ring["table"][rec["k"]].astype(np.int64)
    return np.where(rec[EVENT_TYPE] == VIEW, camp, -1)


def expected(cfg: dict, ring: dict, n_total: int, mix: dict) -> ref.Windows:
    g = cfg["graph"]
    return ref.tb_counts_of_ring(_campaign_of_views(ring), n_total,
                                 int(mix["event_rate"]), g["window_usec"],
                                 g["campaigns"])


def control(cfg: dict, ring: dict, n_total: int, mix: dict):
    """The configuration states no float precision (counts are exact);
    the control lowers the precision of the one lane the result depends
    on: event time rounded to the nearest millisecond, so the tuples of
    a window's last half millisecond fire with the next window."""
    g = cfg["graph"]
    w = ref.tb_counts_of_ring(_campaign_of_views(ring), n_total,
                              int(mix["event_rate"]), g["window_usec"],
                              g["campaigns"], stamp_offset_usec=500)
    return w.key, w.wid, w.value


def compare(cfg: dict, got: dict, exp: ref.Windows) -> list:
    return ref.compare_windows(got["key"].astype(np.int64),
                               got["wid"].astype(np.int64),
                               got["value"].astype(np.int64), exp,
                               cfg["check"]["count_mismatches"], exact=True)
