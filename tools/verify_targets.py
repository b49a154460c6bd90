#!/usr/bin/env python
"""Zero-arg graph factories for the wfverify CI stage.

``ci/run_tests.sh`` runs ``tools/wf_verify.py --strict`` over these
entrypoints — the bench e2e pipeline shape and one graph per chaos
family — so every kernel the repo itself ships stays clean under the
object-level verifier (``windflow_tpu/analysis/tracecheck.py``).  The
factories compose but never start their graphs: verification needs the
live callables, not a run.

The deliberately-violating determinism family (``wallclock``,
``durability/chaos.py``) is NOT listed here: it exists to be flagged
(WF612), which ``tests/test_tracecheck.py`` asserts — a strict CI pass
over it would always fail by design.
"""

from __future__ import annotations

import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def bench_e2e():
    """The representative pipeline shape (the `ffat_sum` graph of
    ``benchmark/`` and ``chip_smoke.py``, small): columnar source spec → MapTPU → chained FilterTPU → FFAT CB window →
    columnar sink."""
    import numpy as np

    import windflow_tpu as wf
    src = (wf.Source_Builder(lambda: iter(()))
           .withOutputBatchSize(4096)
           .withRecordSpec({"key": np.int32(0),
                            "v0": np.float32(0.0)}).build())
    m = wf.MapTPU_Builder(
        lambda t: {"key": t["key"], "v0": t["v0"] * 1.5 + 1.0}).build()
    f = wf.FilterTPU_Builder(lambda t: (t["key"] & 7) != 7).build()
    w = (wf.Ffat_WindowsTPU_Builder(lambda t: t["v0"], lambda a, b: a + b)
         .withCBWindows(64, 16)
         .withKeyBy(lambda t: t["key"]).withMaxKeys(64).build())
    g = wf.PipeGraph("verify_bench_e2e")
    pipe = g.add_source(src)
    pipe.add(m)
    pipe.chain(f)
    pipe.add(w).add_sink(
        wf.Sink_Builder(lambda r: None).withColumnarSink(defer=4).build())
    return g


def wire_ingest():
    """Compressed-ingest shape (windflow_tpu/wire.py): a declared-spec
    source staging wire-compressed batches — monotone ts/id lanes, a
    low-cardinality dict lane, a raw float lane — into a keyed reduce.
    Verifies the wire plane's decode-bearing graph composes clean under
    wfverify (the decode itself is framework code inside the unpack
    program; this pins the USER kernels around a compressed edge)."""
    import numpy as np

    import windflow_tpu as wf
    src = (wf.Source_Builder(lambda: iter(()))
           .withOutputBatchSize(4096)
           .withRecordSpec({"id": np.int64(0), "key": np.int32(0),
                            "v": np.float32(0.0)}).build())
    red = (wf.ReduceTPU_Builder(
        lambda a, b: {"id": jnp_max(a["id"], b["id"]),
                      "key": jnp_max(a["key"], b["key"]),
                      "v": jnp_max(a["v"], b["v"])})
        .withKeyBy(lambda t: t["key"]).withMaxKeys(64)
        .withMonoidCombiner("max").build())
    g = wf.PipeGraph("verify_wire_ingest")
    g.add_source(src).add(red).add_sink(
        wf.Sink_Builder(lambda r: None).build())
    return g


def jnp_max(a, b):
    import jax.numpy as jnp
    return jnp.maximum(a, b)


def pallas_window():
    """Pallas-kernel-enabled window shape (windflow_tpu/kernels): a
    declared-monoid CB window + a declared-dense reduce with the
    kernels FORCED on — the grouping, pane-combine, and segmented-
    reduce kernel bodies all trace into the verified programs, so
    wfverify pins the kernel-bearing builds trace-safe/deterministic
    exactly like the lax ones."""
    import numpy as np

    import windflow_tpu as wf
    src = (wf.Source_Builder(lambda: iter(()))
           .withOutputBatchSize(4096)
           .withRecordSpec({"key": np.int32(0),
                            "v0": np.float32(0.0)}).build())
    w = (wf.Ffat_WindowsTPU_Builder(lambda t: t["v0"],
                                    lambda a, b: a + b)
         .withCBWindows(64, 16)
         .withKeyBy(lambda t: t["key"]).withMaxKeys(64)
         .withSumCombiner().build())
    # the reduce combines WINDOW OUTPUT records ({key, value, wid}) —
    # wf_ir --drive actually runs this graph, so the combiner must match
    # the upstream record structure, not the source spec
    red = (wf.ReduceTPU_Builder(
            lambda a, b: {"key": jnp_max(a["key"], b["key"]),
                          "value": jnp_max(a["value"], b["value"]),
                          "wid": jnp_max(a["wid"], b["wid"])})
           .withKeyBy(lambda t: t["key"]).withMaxKeys(64)
           .withMonoidCombiner("max").build())
    g = wf.PipeGraph("verify_pallas_window",
                     config=wf.Config(pallas_kernels="1"))
    pipe = g.add_source(src)
    pipe.add(w)
    pipe.add(red)
    pipe.add_sink(wf.Sink_Builder(lambda r: None).build())
    return g


def megastep_latency():
    """Megastep + latency-ledger shape (windflow_tpu/megastep.py,
    monitoring/latency_ledger.py): K=4 staged sweeps folded into one
    compiled scan program feeding a CB window, with the per-batch
    latency ledger harvesting trace lanes — the two post-PR-10 hot
    paths (`MegastepEdge.offer`/`run`/drain, `LatencyLedger.harvest`)
    ride the verified/audited program set like every older plane."""
    import numpy as np

    import windflow_tpu as wf
    src = (wf.Source_Builder(lambda: iter(()))
           .withOutputBatchSize(4096)
           .withRecordSpec({"key": np.int32(0),
                            "v0": np.float32(0.0)}).build())
    m = wf.MapTPU_Builder(
        lambda t: {"key": t["key"], "v0": t["v0"] * 0.5}).build()
    w = (wf.Ffat_WindowsTPU_Builder(lambda t: t["v0"],
                                    lambda a, b: a + b)
         .withCBWindows(64, 16)
         .withKeyBy(lambda t: t["key"]).withMaxKeys(64)
         .withSumCombiner().build())
    g = wf.PipeGraph("verify_megastep_latency",
                     config=wf.Config(megastep_sweeps=4,
                                      latency_ledger=True))
    pipe = g.add_source(src)
    pipe.add(m)
    pipe.add(w)
    pipe.add_sink(wf.Sink_Builder(lambda r: None).build())
    return g


def _chaos(family: str):
    from windflow_tpu.durability.chaos import make_cell
    ckpt = tempfile.mkdtemp(prefix=f"wfverify_{family}_ck_")
    out = tempfile.mkdtemp(prefix=f"wfverify_{family}_out_") \
        if family in ("stateless_chain", "wallclock") else None
    cell = make_cell(family, ckpt, out_dir=out, n=64)
    return cell["factory"]()


def chaos_window_cb():
    return _chaos("window_cb")


def chaos_window_tb():
    return _chaos("window_tb")


def chaos_reduce():
    return _chaos("reduce")


def chaos_stateful():
    return _chaos("stateful")


def chaos_stateless_chain():
    return _chaos("stateless_chain")
