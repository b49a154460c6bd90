"""Cells whose files are in ``benchmark/`` but which ``BENCHMARK.json``
does not list yet (``PERF.md`` section 7 says why): the rehearsals keep
their configurations, readers and references working, and adding them
here is what a later PR does — entries only, no file edited."""

import atexit
import json
import os
import shutil
import tempfile

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))

QUEUED = ["ffat_sum.saturated", "ffat_sum_mesh4.saturated"]
_root = None


def root_with_queued() -> str:
    """A root whose ``BENCHMARK.json`` also lists the queued cells, the
    mesh configuration and the Pallas share; ``benchmark`` is the real
    directory."""
    global _root
    if _root is not None:
        return _root
    _root = tempfile.mkdtemp(prefix="bench_queued_")
    atexit.register(shutil.rmtree, _root, ignore_errors=True)
    os.symlink(os.path.join(ROOT, "benchmark"),
               os.path.join(_root, "benchmark"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "ffat_sum_mesh4.json")) as f:
        mesh = json.load(f)
    m["configs"].append({"name": mesh["name"], "source": mesh["source"],
                         "file": "benchmark/configs/ffat_sum_mesh4.json",
                         "reduced": sorted(mesh["reduced"]),
                         "why": "key-sharded window state on four chips"})
    m["workloads"] += [
        {"name": "ffat_sum.saturated", "config": "ffat_sum",
         "traffic": "saturated", "chips": 1, "why": "queued"},
        {"name": "ffat_sum_mesh4.saturated", "config": "ffat_sum_mesh4",
         "traffic": "saturated", "chips": 4, "why": "queued"}]
    m["per_layer"].append(
        {"name": "mosaic_dev_share.sat", "unit": "%", "better": "higher",
         "source": "device_trace", "layer": "Pallas kernels",
         "moves": "tuples_per_s", "workloads": []})
    for e in m["end_to_end"] + m["per_layer"]:
        if e["name"] == "tuples_per_s" or e.get("moves") == "tuples_per_s":
            e["workloads"] = e["workloads"] + QUEUED
    with open(os.path.join(_root, "BENCHMARK.json"), "w") as f:
        json.dump(m, f)
    return _root


def root_of(cell: str) -> str:
    return root_with_queued() if cell in QUEUED else ROOT
