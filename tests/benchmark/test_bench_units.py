"""The benchmark's yardstick, checked without a device: cell resolution,
the generator's schedule, the references against the smoke's oracles, the
comparison, the trace reduction on a recorded trace, the contract's
limits on ``BENCHMARK.json`` and the result line."""

import json
import os
import re
import sys

import numpy as np
import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness, reference as ref, trace_reduce  # noqa: E402
from benchmark.generator import ALWAYS_DUE, OpenLoop, frame_dtype  # noqa: E402
from queued_cells import QUEUED, root_of  # noqa: E402

MANIFEST = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELLS = [w["name"] for w in MANIFEST["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


# -- BENCHMARK.json against the contract ------------------------------------

def test_manifest_has_exactly_the_contract_keys():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= MANIFEST["run_seconds"] <= 51
    n = 24                      # the limit is what fits with 24 cells
    assert (2 + 14 * n) * (MANIFEST["run_seconds"] + 60) + n * 180 + 1200 \
        <= 43200
    assert len(json.dumps(MANIFEST)) < 64 * 1024


@pytest.mark.parametrize("group,keys", [
    ("configs", {"name", "source", "file", "reduced", "why"}),
    ("workloads", {"name", "config", "traffic", "chips", "why"}),
    ("end_to_end", {"name", "unit", "better", "bound", "source"}),
    ("per_layer", {"name", "unit", "better", "source", "layer", "moves"}),
])
def test_manifest_entries(group, keys):
    names = [e["name"] for e in MANIFEST[group]]
    assert len(names) == len(set(names))
    for e in MANIFEST[group]:
        assert set(e) - {"workloads"} == keys, e["name"]
        assert NAME.match(e["name"]), e["name"]
        for k in ("why", "layer", "source"):
            if k in e:
                assert 1 <= len(e[k]) <= 200 and "\n" not in e[k] \
                    and "\t" not in e[k], (e["name"], k)
        if "unit" in e:
            assert UNIT.match(e["unit"]) and e["better"] in ("lower",
                                                             "higher")
        for w in e.get("workloads", ()):
            assert w in CELLS


def test_manifest_cross_references():
    configs = {c["name"] for c in MANIFEST["configs"]}
    assert {w["config"] for w in MANIFEST["workloads"]} == configs
    four = [w for w in MANIFEST["workloads"] if w["chips"] == 4]
    assert all(w["chips"] in (1, 4) for w in MANIFEST["workloads"])
    assert len(four) <= max(1, len(CELLS) // 2)
    e2e = {m["name"]: m for m in MANIFEST["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in MANIFEST["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in MANIFEST["per_layer"]:
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        for w in m.get("workloads", ()):
            moved = e2e[m["moves"]]
            assert w in moved.get("workloads", CELLS), (m["name"], w)
    for c in MANIFEST["configs"]:
        assert c["file"].startswith(tuple(MANIFEST["paths"]))
        cfg = json.load(open(os.path.join(ROOT, c["file"])))
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert cfg["guarantees"] and cfg["assumed"] is not None


@pytest.mark.parametrize("cell", CELLS + QUEUED)
def test_cell_resolves_by_name(cell):
    c = harness.resolve_cell(cell, root_of(cell))
    with open(os.path.join(root_of(cell), "BENCHMARK.json")) as f:
        entry = next(w for w in json.load(f)["workloads"]
                     if w["name"] == cell)
    assert c["config"]["name"] == entry["config"]
    assert c["mix"]["name"] == entry["traffic"]
    assert c["chips"] == entry["chips"]
    e2e = {m["name"] for m in c["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c["per_layer"], "every cell reports a per-layer metric"
    # every metric the cell reports has its reader, found by name
    for kind, entries in (("end_to_end", c["end_to_end"]),
                          ("layer_metrics", c["per_layer"])):
        for m in entries:
            assert callable(harness.load_module(kind, m["name"]).read)
    prog = harness.load_module("roofline", c["config"]["step_program"])
    assert prog.least_bytes(c["config"]) > 0 and re.compile(prog.MODULES)
    for fn in ("make_ring", "build_graph", "expected", "control", "compare"):
        assert callable(getattr(c["config_module"], fn))


def test_unknown_names_are_errors():
    with pytest.raises(KeyError):
        harness.resolve_cell("no_such.cell")
    with pytest.raises(KeyError):
        harness.load_peaks("cpu")
    with pytest.raises(FileNotFoundError):
        harness.load_module("layer_metrics", "no_such_metric")
    assert harness.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


# -- the generator ------------------------------------------------------------

class Clock:
    def __init__(self):
        self.now = 100.0

    def __call__(self):
        return self.now


def _ring(n=1000, nv=1):
    rec = np.zeros(n, dtype=frame_dtype(nv))
    rec["k"] = np.arange(n)
    rec["v0"] = np.arange(n) / 7.0
    return rec


def _records(buf, nv=1):
    return np.frombuffer(buf, dtype=frame_dtype(nv))


def test_steady_schedule_and_lag_arithmetic():
    clk = Clock()
    gen = OpenLoop(_ring(), {"rate": 1000.0}, seconds=2.0, chunk_records=100,
                   clock=clk)
    it = gen.chunks()
    assert next(it) == b"" and gen.idle_yields == 1     # nothing due at t0
    clk.now = 100.0 + 0.099           # the chunk's last tuple is i = 99
    first = _records(next(it))
    assert len(first) == 100 and gen.pulled == 100
    assert list(first["k"][:3]) == [0, 1, 2]
    assert list(first["t"][:3]) == [0, 1000, 2000]      # usec since t0
    assert gen.lag_now(clk.now) == pytest.approx(-0.1)
    clk.now = 100.45                  # a stall: three chunks overdue
    assert gen.lag_now(clk.now) == pytest.approx(0.45 - 0.199)
    gen.reanchor(clk.now)             # warm-up forgives the backlog
    assert gen.lag_now(clk.now) == pytest.approx(-0.099)
    assert next(it) == b""
    gen.open_window(clk.now)          # tuple 100 is created NOW
    assert (gen.i_open, gen.t_open) == (100, 100.45)
    with pytest.raises(RuntimeError):
        gen.reanchor(clk.now)
    clk.now = 100.45 + 0.3            # chunks due at +.099, +.199, +.299
    a, b, c = (_records(next(it)) for _ in range(3))
    assert next(it) == b""
    lags = np.array(gen.lags)
    assert lags[:, 1] - lags[:, 0] == pytest.approx([0.201, 0.101, 0.001])
    # stamps are the creation times, and they never run backwards
    ts = np.concatenate([first["t"], a["t"], b["t"], c["t"]])
    assert np.all(np.diff(ts) >= 0) and a["t"][0] == 450000
    assert gen.creation_times([100, 200]) == pytest.approx([100.45, 100.55])
    # the window closes with 17 chunks overdue: they are owed, and
    # handed over before the stream ends
    clk.now = 100.45 + 2.0
    assert len([b for b in it if b]) == 17
    assert gen.i_stop == gen.i_open + 2000 == 2100
    assert gen.due_in_window() == 2000
    late = OpenLoop(_ring(), {"rate": 1000.0}, 1.0, 100, clock=clk)
    it = late.chunks()
    next(it)
    late.open_window(clk.now)
    clk.now += 1.0 + 1.5              # past the grace: the rest has failed
    assert list(it) == [] and late.i_stop == 0


def test_saturated_replays_the_ring_on_event_time():
    clk = Clock()
    gen = OpenLoop(_ring(250), {"rate": ALWAYS_DUE, "event_rate": 500},
                   seconds=1.0, chunk_records=100, clock=clk)
    it = gen.chunks()
    got = np.concatenate([_records(next(it)) for _ in range(6)])
    assert list(got["k"]) == [i % 250 for i in range(600)]   # wraps
    assert list(got["t"][:3]) == [0, 2000, 4000]             # i / 500 s
    assert gen.lag_now(clk.now) == 0.0 and gen.lags == []
    gen.open_window(clk.now)
    next(it)
    clk.now += 1.0
    assert list(it) == [] and gen.due_in_window() == 100
    with pytest.raises(ValueError):
        OpenLoop(_ring(), {"rate": ALWAYS_DUE}, 1.0, 10)


# -- references against the smoke's oracles ---------------------------------

@pytest.mark.parametrize("n_total", [700, 4096, 9999, 12288])
def test_cb_closed_form_equals_the_per_tuple_oracle(n_total):
    rng = np.random.default_rng(n_total)
    R, win, slide = 4096, 64, 16
    keys = rng.integers(0, 24, R)
    vals = rng.random(R)
    keep = (keys & 7) != 7
    w = ref.cb_windows_of_ring(keys, vals, keep, n_total, win, slide)
    reps = -(-n_total // R)
    sk = np.tile(keys, reps)[:n_total]
    sv = np.tile(vals, reps)[:n_total]
    kept = np.tile(keep, reps)[:n_total]
    ek, ew, ev = ref.oracle_cb_windows(sk[kept], sv[kept], win, slide)
    order = np.lexsort((ew, ek))
    assert np.array_equal(w.key, ek[order])
    assert np.array_equal(w.wid, ew[order])
    assert np.allclose(w.value, ev[order], rtol=1e-12, atol=0)
    # the closing tuple: the key's (wid*slide+win)-th kept tuple
    idx = np.flatnonzero(kept)
    for i in rng.choice(len(w.key), 50):
        mine = idx[sk[idx] == w.key[i]]
        last = w.wid[i] * slide + win
        assert w.full[i] == (last <= len(mine))
        assert w.closer[i] == (mine[last - 1] if w.full[i] else -1)


@pytest.mark.parametrize("n_total", [5000, 30000, 65536])
def test_tb_closed_form_equals_the_per_tuple_oracle(n_total):
    rng = np.random.default_rng(n_total)
    R, rate, W = 8192, 100_000, 50_000
    groups = np.where(rng.integers(0, 3, R) == 1, rng.integers(0, 10, R), -1)
    w = ref.tb_counts_of_ring(groups, n_total, rate, W, 10)
    reps = -(-n_total // R)
    sg = np.tile(groups, reps)[:n_total]
    ts = np.arange(n_total, dtype=np.int64) * 1_000_000 // rate
    ek, ew, ec = ref.oracle_tb_counts(sg[sg >= 0], ts[sg >= 0], W, 10)
    order = np.lexsort((ew, ek))
    assert np.array_equal(w.key, ek[order])
    assert np.array_equal(w.wid, ew[order])
    assert np.array_equal(w.value, ec[order])
    assert np.array_equal(w.full, w.wid < ts[-1] // W)


# -- the comparison -------------------------------------------------------------

def _exp():
    k = np.array([0, 0, 1, 2])
    w = np.array([0, 1, 0, 0])
    return ref.Windows(k, w, np.array([10.0, 20.0, 30.0, 40.0]),
                       np.ones(4, bool), np.arange(4))


def _ok(checks):
    return ref.verdict(checks)


def test_compare_accepts_the_same_rows_in_any_order():
    e = _exp()
    p = np.array([3, 0, 2, 1])
    assert _ok(ref.compare_windows(e.key[p], e.wid[p], e.value[p], e, 1e-6,
                                   exact=False))


@pytest.mark.parametrize("fault", ["value", "missing", "extra", "duplicate",
                                   "wrong_wid", "nan", "empty"])
def test_compare_rejects(fault):
    e = _exp()
    k, w, v = e.key.copy(), e.wid.copy(), e.value.copy()
    if fault == "value":
        v[2] *= 1.001
    elif fault == "missing":
        k, w, v = k[:3], w[:3], v[:3]
    elif fault == "extra":
        k, w, v = np.r_[k, 3], np.r_[w, 0], np.r_[v, 1.0]
    elif fault == "duplicate":
        k, w, v = np.r_[k[:3], 0], np.r_[w[:3], 0], np.r_[v[:3], 10.0]
    elif fault == "wrong_wid":
        w[3] = 1
    elif fault == "nan":
        v[0] = np.nan
    elif fault == "empty":
        k, w, v = k[:0], w[:0], v[:0]
    checks = ref.compare_windows(k, w, v, e, 1e-6, exact=False)
    assert not _ok(checks)
    assert all({"name", "value", "limit", "ok"} == set(c) for c in checks)


def test_exact_counts_have_the_limit_zero():
    e = _exp()._replace(value=np.array([1, 2, 3, 4]))
    assert _ok(ref.compare_windows(e.key, e.wid, e.value, e, 0, exact=True))
    off = e.value.copy()
    off[1] += 1
    checks = ref.compare_windows(e.key, e.wid, off, e, 0, exact=True)
    assert not _ok(checks) and checks[-1]["value"] == 1


# -- the trace reduction --------------------------------------------------------

def test_union_of_busy_intervals():
    iv = np.array([[0, 10], [5, 12], [20, 30], [25, 26], [40, 41]]) * 1e9
    secs, merged = trace_reduce.union_seconds(iv)
    assert secs == 12 + 10 + 1
    assert merged.tolist() == [[0, 12e9], [20e9, 30e9], [40e9, 41e9]]
    assert trace_reduce.union_seconds(np.empty((0, 2)))[0] == 0.0
    assert trace_reduce.module_name("jit_step(123456)") == "jit_step"


def test_reduce_planes_on_a_hand_made_trace():
    ms = 1e6
    mods = [("jit_ffat(7)", 0, 10 * ms, {}), ("jit_unpack(9)", 20 * ms,
                                              25 * ms, {})]
    # the trace names an operation by its whole HLO instruction
    ops = [("%while.16 = (u32[]{:T(128)}, /*index=1*/f32[8]{0}) while((u32[],"
            " f32[8]) %tuple.3), condition=%cond, body=%body", 0, 10 * ms, {}),
           ("%fusion.1 = f32[8]{0:T(1024)} fusion(f32[8]{0} %custom-call.7), "
            "kind=kLoop, calls=%fused_computation.1", 0, 4 * ms, {}),
           ("%tpu_custom_call.8 = (s32[1,8]{1,0:T(1,128)S(1)}, s32[1,8]{1,0})"
            " custom-call(s32[1,8]{1,0} %fusion.1), custom_call_target="
            "\"tpu_custom_call\"", 4 * ms, 10 * ms, {}),
           ("%copy.2 = f32[8]{0} copy(f32[8]{0} %p)", 20 * ms, 25 * ms, {})]
    host = [("source.pull", 11 * ms, 17 * ms, {}),
            ("generator.idle", 26 * ms, 29 * ms, {}),
            ("sink.callback", 30 * ms, 40 * ms, {})]
    planes = [("/device:TPU:0", [("XLA Modules", mods), ("XLA Ops", ops)]),
              ("/device:TPU:1", [("XLA Modules", mods[:1]),
                                 ("XLA Ops", ops[:3])]),
              ("/host:CPU", [("python", host)])]
    red = trace_reduce.reduce_planes(planes)
    assert red["window_s"] == pytest.approx(0.040)
    assert red["devices"][0]["busy_s"] == pytest.approx(0.015)
    assert red["devices"][1]["busy_s"] == pytest.approx(0.010)
    assert red["busy_s"] == pytest.approx(0.0125)
    assert red["modules"]["jit_ffat"] == pytest.approx(0.010)
    assert red["modules"]["jit_unpack"] == pytest.approx(0.0025)
    assert red["mosaic_s"] == pytest.approx(0.006)
    gaps = dict(red["breakdown"]["idle_gaps"])       # chip 0's
    assert gaps == {"source.pull": pytest.approx(0.010),     # 10..20 ms
                    "sink.callback": pytest.approx(0.015)}   # 25..40 ms
    only = trace_reduce.reduce_planes(planes[1:])    # chip 1: 10..40 ms
    assert dict(only["breakdown"]["idle_gaps"]) == {
        "step.other": pytest.approx(0.030)}
    # leaves only: the while that holds the two is not listed
    assert [n for n, _ in red["breakdown"]["device_ops"]] == [
        "tpu_custom_call.8 custom-call", "fusion.1 fusion", "copy.2 copy"]
    assert red["ops"]["copy.2 copy"] == pytest.approx(0.0025)   # mean of 2
    assert trace_reduce.reduce_planes([("/host:CPU", [])])["devices"] == {}


RECORDED = os.path.join(ROOT, "benchmark", "testdata", "small.xplane.pb")


def test_reduce_the_recorded_trace():
    """A short trace recorded on a v5e chip (``small.expect.json`` says
    how); the expected sums were checked by hand against the per-name
    totals of ``trace_reduce.describe``."""
    red = trace_reduce.reduce_planes(trace_reduce.read_planes(RECORDED))
    expect = json.load(open(RECORDED.replace(".xplane.pb", ".expect.json")))
    assert sorted(red["devices"]) == expect["devices"]
    assert red["busy_s"] == pytest.approx(expect["busy_s"], rel=1e-6)
    assert red["window_s"] == pytest.approx(expect["window_s"], rel=1e-6)
    assert 0 < red["busy_s"] <= red["window_s"]
    for name, secs in expect["modules"].items():
        assert red["modules"][name] == pytest.approx(secs, rel=1e-6)
    assert red["mosaic_s"] == pytest.approx(expect["mosaic_s"], rel=1e-6)
    assert len(red["breakdown"]["device_ops"]) <= 10
    assert len(red["breakdown"]["idle_gaps"]) <= 10


# -- the result line ------------------------------------------------------------

def test_last_line_holds_exactly_the_contract_keys(capsys):
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    try:
        import run
    finally:
        sys.path.pop(0)
    window = {"correct": np.True_, "attempted": np.int64(5), "failed": 0}
    metrics = {"setup_s": {"value": 1.5, "unit": "s"}}
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
              "memory_peak_bytes": 1}
    line = json.loads(json.dumps(run.result_line(window, metrics, device)))
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device"]
    assert line["correct"] is True and line["attempted"] == 5
    traced = run.result_line(window, metrics, device, {"device_ops": []})
    assert list(traced)[-1] == "breakdown" and len(traced) == 6
    # no TPU here: non-zero, and nothing on stdout
    assert run.main(["--workload", CELLS[0], "--seed", "1", "--seconds",
                     "1", "--trace", "0"]) != 0
    assert capsys.readouterr().out == ""
