"""Rehearsals of whole runs, in-process on the CPU backend at tiny sizes:
``harness.run_cell`` is what ``benchmark/run.py`` calls after it has
found its chips.  No device metric is printed or asserted here."""

import copy
import json
import os
import shutil
import sys

import numpy as np
import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax  # noqa: E402

from benchmark import harness, reference as ref  # noqa: E402
from queued_cells import root_of  # noqa: E402

TINY = dict(batch=1024, n_keys=16, win=64, slide=16, ring_batches=8,
            campaigns=10, ads_per_campaign=4,
            window_usec=100_000)
SEED = 2**31 + 17            # the driver's seeds pass 32 signed bits
#: stands for the compilation cache's directory (the CPU backend keeps
#: none); under out/, which git ignores
CACHE = os.path.join(ROOT, "benchmark", "out", "test_cache")


def tiny_cell(name, root=None, **mix):
    cell = harness.resolve_cell(name, root or root_of(name))
    mod = cell.pop("config_module")              # a module does not copy
    cell = dict(copy.deepcopy(cell), config_module=mod)
    if "event_rate" in cell["mix"]:
        cell["mix"]["event_rate"] = 100_000
    cell["mix"]["chunk_bytes"] = 4096
    cell["mix"].update(mix)
    return cell


def run(cell, seconds=0.5, seed=SEED, **sizes):
    import time
    # a cache that was primed already: the throw-away graph of a first
    # run has its own test, and every graph costs the suite CPU seconds
    os.makedirs(CACHE, exist_ok=True)
    open(os.path.join(CACHE, "entry"), "w").close()
    marker = os.path.join(cell["bench"], "out", "primed", cell["name"])
    os.makedirs(os.path.dirname(marker), exist_ok=True)
    with open(marker, "w") as f:
        f.write(CACHE)
    return harness.run_cell(cell, seed, seconds, False, time.monotonic(),
                            jax.devices()[:1], str(CACHE), sizes={**TINY, **sizes},
                            log=lambda msg: None)


@pytest.fixture(scope="module")
def windows():
    """One tiny run of each cell, the queued ones too."""
    out = {}
    for name in ("ffat_sum.saturated", "ysb.saturated",
                 "ffat_sum_mesh4.saturated"):
        out[name] = run(tiny_cell(name), n_keys=32 if "mesh" in name else 16)
    out["ffat_sum.steady"] = run(tiny_cell("ffat_sum.steady", rate=40_000),
                                 seconds=1.0)
    return out


CELLS = ["ffat_sum.saturated", "ffat_sum.steady", "ysb.saturated",
         "ffat_sum_mesh4.saturated"]


@pytest.mark.parametrize("name", CELLS)
def test_cell_runs_and_every_row_is_checked(windows, name):
    w = windows[name]
    assert w["correct"], w["checks"]
    assert {c["name"] for c in w["checks"]} >= {
        "rows_missing_or_extra", "key_wid_mismatches", "dropped_tuples"}
    assert w["rows"] > 0 and w["failed"] == 0 and w["attempted"] > 0
    # warm-up ran on the measured graph before the window opened
    assert w["open"]["pulled"] >= harness.WARMUP_MIN_BATCHES * 1024
    assert w["n_total"] == w["open"]["pulled"] + w["tuples_in_window"]
    assert w["t_open"] < w["t_stop"] <= w["t_last_delivery"]
    assert w["setup_s"] > 0


@pytest.mark.parametrize("name", CELLS)
def test_cell_reports_its_metrics(windows, name):
    cell = tiny_cell(name)
    w = windows[name]
    e2e = harness.read_metrics(cell, cell["end_to_end"], "end_to_end", None,
                               w)
    assert set(e2e) == {m["name"] for m in cell["end_to_end"]}
    assert all(v["value"] > 0 for v in e2e.values())
    units = {m["name"]: m["unit"] for m in cell["end_to_end"]}
    assert all(v["unit"] == units[k] for k, v in e2e.items())
    # untraced: the readers of the device trace find nothing to read and
    # are left out; the counters' readers report
    layer = harness.read_metrics(cell, cell["per_layer"], "layer_metrics",
                                 None, w)
    sources = {m["name"]: m["source"] for m in cell["per_layer"]}
    assert layer and all(sources[k] != "device_trace" for k in layer)
    json.dumps(layer)


def test_latency_is_delivery_minus_the_closing_tuples_creation(windows):
    w = windows["ffat_sum.steady"]
    lat = w["latencies_ms"]
    assert len(lat) > 100 and np.all(lat > 0)
    # a tuple waits at least for its chunk, at most for the whole run
    assert lat.max() < (w["t_last_delivery"] - w["t_open"]) * 1e3
    assert w["lags"].shape[1] == 2 and len(w["lags"]) > 10
    assert np.all(w["lags"][:, 1] >= w["lags"][:, 0])
    assert windows["ffat_sum.saturated"]["latencies_ms"] is None


def test_batch_span_reads_the_programs_staged_batches(monkeypatch):
    """``batch_span_ms.steady`` is the window over the program's own
    count of staged batches (``Staging.Wire``; the wire plane is on by
    default on a TPU and forced on here), not the generator's tuples
    over the batch size: the half-filled batches that the 100 ms
    punctuation flushes count, so the two differ on chunks that do not
    fill a batch."""
    from windflow_tpu import wire
    monkeypatch.setattr(wire, "wire_enabled", lambda cfg: True)
    w = run(tiny_cell("ffat_sum.steady", rate=40_000), seconds=1.0)
    assert w["correct"], w["checks"]
    stats = harness.delta(w["open"], w["close"])
    staged = stats["wire_batches"] + stats["wire_raw_batches"]
    assert staged > stats["pulled"] / w["batch"] > 0
    span = harness.load_module("layer_metrics", "batch_span_ms.steady") \
        .read(None, stats, w)
    assert span == pytest.approx(stats["t"] / staged * 1e3)
    assert span < w["batch"] / w["mix"]["rate"] * 1e3


def test_latency_mapping_on_a_hand_made_stream():
    """Window w of a key closes with that key's (w*slide+win)-th kept
    tuple; its creation time is the schedule's, not the program's."""
    keys = np.array([0, 1, 0, 0, 1, 0, 0, 1, 1, 0])     # ring of 10
    w = ref.cb_windows_of_ring(keys, np.ones(10), np.ones(10, bool), 25,
                               win=4, slide=2)
    k0 = [i for i in range(25) if keys[i % 10] == 0]
    by = {(int(k), int(i)): (bool(f), int(c)) for k, i, f, c
          in zip(w.key, w.wid, w.full, w.closer)}
    assert by[(0, 0)] == (True, k0[3]) and by[(0, 1)] == (True, k0[5])
    n0 = len(k0)
    assert by[(0, (n0 - 4) // 2)][0] and not by[(0, (n0 - 4) // 2 + 1)][0]
    from benchmark.generator import OpenLoop, frame_dtype
    gen = OpenLoop(np.zeros(10, frame_dtype(1)), {"rate": 100.0}, 1.0, 5,
                   clock=lambda: 50.0)
    gen.t_first = 50.0
    gen.open_window(50.0)
    assert gen.creation_times([k0[3]]) == pytest.approx([50.0 + k0[3] / 100])


def test_an_empty_cache_is_primed_once(tmp_path, monkeypatch):
    """The throw-away graph runs against an empty (or another) cache
    only, on the cell's own mix, over PRIME_BATCHES batches at least and
    until PRIME_S after its first delivery, to the end of its stream."""
    monkeypatch.setattr(harness, "PRIME_S", 0.05)
    cell = tiny_cell("ysb.saturated")
    cell["bench"] = str(tmp_path)
    cfg = harness.with_sizes(cell["config"], TINY)
    ring = cell["config_module"].make_ring(1, cfg)
    pulled = []

    class Stub:                      # stands for the configuration's .py
        @staticmethod
        def build_graph(cfg, ring, chunks_fn, sink_fn):
            class G:
                @staticmethod
                def run():
                    n = 0
                    for b in chunks_fn():
                        n += len(b)
                        sink_fn(object())
                    pulled.append(n)
            return G

    cache = tmp_path / "cache"
    cache.mkdir()
    args = (cell, Stub, cfg, ring, cell["mix"], 170, str(cache))
    assert harness.prime_cache(*args)            # empty: primed
    itemsize = ring["rec"].dtype.itemsize
    assert pulled[0] // itemsize >= harness.PRIME_BATCHES * 1024
    assert harness.prime_cache(*args)            # still empty: again
    (cache / "entry").write_text("x")            # what priming leaves
    assert not harness.prime_cache(*args)
    moved = tmp_path / "moved"
    moved.mkdir()
    (moved / "entry").write_text("x")
    assert harness.prime_cache(*args[:-1], str(moved))
    assert len(pulled) == 3


# -- a wrong answer makes the run incorrect -------------------------------------

@pytest.mark.parametrize("fault", ["value_off", "rows_lost"])
def test_broken_timed_path_is_not_correct(monkeypatch, fault):
    """The rest of a run with the timed path broken underneath: one
    delivered batch is altered where the program hands it to the sink."""
    from windflow_tpu import batch
    real = batch.device_to_columns_multi
    calls = {"n": 0}

    def broken(batches):
        out = list(real(batches))
        calls["n"] += 1
        if calls["n"] == 3 and out:
            cols, tss = out[0]
            if fault == "value_off":
                cols = dict(cols, value=np.asarray(cols["value"]) * 1.001)
            else:
                cols = {k: np.asarray(v)[:-1] for k, v in cols.items()}
                tss = tss[:-1]
            out[0] = (cols, tss)
        return out

    monkeypatch.setattr(batch, "device_to_columns_multi", broken)
    w = run(tiny_cell("ffat_sum.saturated"), seconds=0.3)
    assert calls["n"] >= 3 and not w["correct"]
    bad = {c["name"] for c in w["checks"] if not c["ok"]}
    assert bad == ({"sum_max_rel_err"} if fault == "value_off" else
                   {"rows_missing_or_extra", "key_wid_mismatches",
                    "sum_max_rel_err"})


@pytest.mark.parametrize("config,limit_key", [("ffat_sum", "sum_rtol"),
                                              ("ffat_sum_mesh4", "sum_rtol"),
                                              ("ysb", "count_mismatches")])
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_in_lower_precision_fails_the_check(config, limit_key, seed):
    """The control: the reference put in the program's place, in the
    precision below the configuration's (a bfloat16 value lane), or — for
    exact counts — with one stated guarantee broken."""
    cell = harness.resolve_cell(config + ".saturated",
                                root_of(config + ".saturated"))
    mod = cell["config_module"]
    cfg = harness.with_sizes(cell["config"], dict(TINY, n_keys=64, win=1024,
                                                  slide=128, batch=4096))
    mix = dict(cell["mix"], event_rate=100_000)
    ring = mod.make_ring(seed, cfg)
    n_total = 5 * len(ring["rec"]) + 777
    exp = mod.expected(cfg, ring, n_total, mix)
    k, w, v = mod.control(cfg, ring, n_total, mix)
    checks = mod.compare(cfg, {"key": k, "wid": w, "value": v}, exp)
    assert not ref.verdict(checks)
    worst = checks[-1]
    assert worst["limit"] == cfg["check"][limit_key]
    assert worst["value"] > 3 * worst["limit"]
    # and the reference itself, in the program's place, passes
    same = mod.compare(cfg, {"key": exp.key, "wid": exp.wid,
                             "value": exp.value}, exp)
    assert ref.verdict(same)


# -- driven by data ---------------------------------------------------------------

def test_a_cell_of_new_files_needs_no_edit(tmp_path):
    """A configuration, a traffic mix, a per-layer metric and a roofline
    count are added as new files plus one entry each."""
    root = str(tmp_path)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    bench = os.path.join(root, "benchmark")
    shutil.copytree(os.path.join(ROOT, "benchmark"), bench,
                    ignore=shutil.ignore_patterns("out", "__pycache__",
                                                  "testdata"))
    before = {p: open(os.path.join(dp, p)).read()
              for dp, _, fs in os.walk(bench) for p in fs}
    cfg = json.load(open(os.path.join(bench, "configs", "ffat_sum.json")))
    cfg.update(name="dummy", step_program="dummy_step")
    json.dump(cfg, open(os.path.join(bench, "configs", "dummy.json"), "w"))
    shutil.copy(os.path.join(bench, "configs", "ffat_sum.py"),
                os.path.join(bench, "configs", "dummy.py"))
    json.dump({"name": "dummy_mix", "rate": "always_due",
               "event_rate": 5000, "chunk_bytes": 4096},
              open(os.path.join(bench, "traffic", "dummy_mix.json"), "w"))
    with open(os.path.join(bench, "layer_metrics", "dummy_rows.py"),
              "w") as f:
        f.write("def read(trace, stats, window):\n"
                "    return window['rows']\n")
    with open(os.path.join(bench, "roofline", "dummy_step.py"), "w") as f:
        f.write("MODULES = r'dummy'\n\n"
                "def least_bytes(cfg):\n    return 1.0\n")
    m = json.load(open(os.path.join(root, "BENCHMARK.json")))
    m["configs"].append({"name": "dummy", "source": cfg["source"],
                         "file": "benchmark/configs/dummy.json",
                         "reduced": [], "why": "dummy"})
    m["workloads"].append({"name": "dummy.dummy_mix", "config": "dummy",
                           "traffic": "dummy_mix", "chips": 1,
                           "why": "dummy"})
    m["end_to_end"][0]["workloads"].append("dummy.dummy_mix")
    m["per_layer"].append({"name": "dummy_rows", "unit": "rows",
                           "better": "higher", "source": "program_counter",
                           "layer": "egress / sink", "moves": "tuples_per_s",
                           "workloads": ["dummy.dummy_mix"]})
    json.dump(m, open(os.path.join(root, "BENCHMARK.json"), "w"))

    cell = tiny_cell("dummy.dummy_mix", root, event_rate=5000)
    assert cell["mix"]["name"] == "dummy_mix"
    assert cell["config"]["name"] == "dummy"
    w = run(cell, seconds=0.3)
    assert w["correct"]
    layer = harness.read_metrics(cell, cell["per_layer"], "layer_metrics",
                                 None, w)
    assert layer["dummy_rows"] == {"value": float(w["rows"]), "unit": "rows"}
    prog = harness.load_module("roofline", "dummy_step", cell["bench"])
    assert prog.least_bytes(cell["config"]) == 1.0
    e2e = harness.read_metrics(cell, cell["end_to_end"], "end_to_end", None,
                               w)
    assert set(e2e) == {"tuples_per_s", "setup_s"}
    # no file that was there was edited
    for p, text in before.items():
        for dp, _, fs in os.walk(bench):
            if p in fs:
                assert open(os.path.join(dp, p)).read() == text
