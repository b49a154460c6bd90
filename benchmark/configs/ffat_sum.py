"""``ffat_sum``: keyed sliding count-window sum (smoke leg A's graph) —
graph builder, stream schema and plain reference."""

from __future__ import annotations

import numpy as np

from benchmark import reference as ref
from benchmark.generator import frame_dtype


def make_ring(seed: int, cfg: dict) -> dict:
    """Uniform keys over ``n_keys``, U[0,1) values."""
    g, s = cfg["graph"], cfg["stream"]
    n = g["batch"] * s["ring_batches"]
    rng = np.random.default_rng(seed)
    rec = np.empty(n, dtype=frame_dtype(1))
    rec["k"] = rng.integers(0, g["n_keys"], n)
    rec["t"] = 0
    rec["v0"] = rng.random(n)
    return {"rec": rec}


def _config(cfg: dict):
    import windflow_tpu as wf
    n_mesh = cfg["graph"]["mesh"]
    if not n_mesh:
        return wf.Config()
    import dataclasses

    from windflow_tpu.parallel.mesh import make_mesh
    return dataclasses.replace(wf.Config(), mesh=make_mesh(n_mesh))


def build_graph(cfg: dict, ring: dict, chunks_fn, sink_fn):
    import windflow_tpu as wf
    from windflow_tpu.io import FrameSource
    g = cfg["graph"]
    src = FrameSource(chunks_fn, nv=1, fmt="frames",
                      output_batch_size=g["batch"])
    # the declared record spec is what lets the wire plane attach
    src.record_spec = {"key": np.int32(0), "v0": np.float32(0.0)}
    m = wf.MapTPU_Builder(
        lambda t: {"key": t["key"], "v0": t["v0"] * 1.5 + 1.0}).build()
    f = wf.FilterTPU_Builder(lambda t: (t["key"] & 7) != 7).build()
    w = (wf.Ffat_WindowsTPU_Builder(lambda t: t["v0"], lambda a, b: a + b)
         .withName("ffat").withCBWindows(g["win"], g["slide"])
         .withKeyBy(lambda t: t["key"]).withMaxKeys(g["n_keys"]).build())
    snk = wf.Sink_Builder(sink_fn).withColumnarSink().build()
    graph = wf.PipeGraph("bench_" + cfg["name"], wf.ExecutionMode.DEFAULT,
                         wf.TimePolicy.EVENT, config=_config(cfg))
    pipe = graph.add_source(src)
    pipe.add(m)
    pipe.chain(f)            # Map + Filter fuse into one XLA program
    pipe.add(w).add_sink(snk)
    return graph


def host_prelude(ring: dict, value_dtype=np.float32):
    """Host twin of the device prelude: ``v*1.5+1`` in the value lane's
    type, then drop keys whose low three bits are all set."""
    rec = ring["rec"]
    one = value_dtype(1)
    v = rec["v0"].astype(value_dtype) * value_dtype(1.5) + one
    return rec["k"], v.astype(np.float64), (rec["k"] & 7) != 7


def expected(cfg: dict, ring: dict, n_total: int, mix: dict) -> ref.Windows:
    keys, vals, keep = host_prelude(ring)
    g = cfg["graph"]
    return ref.cb_windows_of_ring(keys, vals, keep, n_total, g["win"],
                                  g["slide"])


def control(cfg: dict, ring: dict, n_total: int, mix: dict):
    """The reference in the precision below the configuration's: a
    bfloat16 value lane, summed in float32 as the program sums.  Returns
    rows in the program's place (key, wid, value)."""
    import ml_dtypes
    keys, vals, keep = host_prelude(ring, ml_dtypes.bfloat16)
    g = cfg["graph"]
    w = ref.cb_windows_of_ring(keys, vals, keep, n_total, g["win"],
                               g["slide"])
    return w.key, w.wid, w.value.astype(np.float32)


def compare(cfg: dict, got: dict, exp: ref.Windows) -> list:
    return ref.compare_windows(got["key"].astype(np.int64),
                               got["wid"].astype(np.int64), got["value"],
                               exp, cfg["check"]["sum_rtol"], exact=False)
