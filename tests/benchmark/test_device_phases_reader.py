"""``benchmark/device_phases.py``: from the program's own names on the
chip's ``XLA Ops`` line to device time by (module, operator, phase), and
the per-layer metrics that read it.  A trace recorded on the chip from this
PR's program for the names, the older recorded traces (a program without
scopes) for the silence, hand-made planes for the arithmetic; no device,
and nothing is timed."""

import hashlib
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import device_phases as dp  # noqa: E402
from benchmark import harness, trace_reduce  # noqa: E402

#: outside ``benchmark/testdata``: ``trace_reduce.find_xplane`` takes "the
#: newest" trace under that directory, and accepted tests point it there
DATA = os.path.join(ROOT, "tests", "benchmark", "data", "phases")
RECORDED = os.path.join(DATA, "ysb.xplane.pb")
EXPECT = os.path.join(DATA, "ysb.expect.json")
NO_SCOPES = os.path.join(ROOT, "benchmark", "testdata")      # PR 23, PR 24

#: what this PR appends to ``per_layer``, in order
NEW = ["unpack_dev_ms_per_batch.sat", "operator_fn_dev_ms_per_batch.sat",
       "place_dev_ms_per_batch.sat", "ring_pass_dev_ms_per_batch.sat",
       "fire_dev_ms_per_batch.sat", "group_dev_ms_per_batch.sat",
       "group_dev_ms_per_batch.steady", "own_dev_ms_per_batch.sat",
       "sketch_dev_ms_per_batch.sat", "session_carry_dev_ms_per_batch.sat",
       "unscoped_dev_share.sat", "unscoped_dev_share.steady"]
#: sha256 of the parent's ``BENCHMARK.json`` (0ca8660, PR 33) as
#: ``json.dumps(..., sort_keys=True)``
PARENT_MANIFEST = \
    "f7376621fd407ed347b0220babab0f7f219abfc3ccce4033bf726b0ab905a045"
S = 1e9


def reader(name):
    return harness.load_module("layer_metrics", name)


def window(trace_dir, batches=4.0, batch=262144):
    """A traced run's window record, as far as the readers look at it."""
    return {"trace_dir": trace_dir, "batch": batch,
            "trace0": {"pulled": 0}, "trace1": {"pulled": batches * batch}}


# ---------------------------------------------------------------------------
# hand-made planes: the arithmetic
# ---------------------------------------------------------------------------

def op(name, opcode="fusion"):
    return f"%{name} = f32[8]{{0}} {opcode}(f32[8]{{0}} %p), kind=kLoop"


def plane(chip, modules, ops):
    """``modules``: ``[(name, start s, end s)]``; ``ops``: ``[(event name,
    op_name path or None, start s, end s, bytes)]``."""
    meta = {i: (name, path, moved)
            for i, (name, path, _, _, moved) in enumerate(ops)}
    return {"chip": chip,
            "modules": [(n, a * S, b * S) for n, a, b in modules],
            "ops": np.array([(i, a * S, b * S)
                             for i, (_, _, a, b, _) in enumerate(ops)],
                            np.float64).reshape(-1, 3),
            "meta": meta}


def one_step():
    """A step of 10 s: unpack 0-2 in its own module; in ``jit_step`` a
    place of 3 s, a ``while`` over 5-9 that holds a fire of 2 s and an
    operation of 1 s under no phase, and a copy XLA added (no name)."""
    return plane(0, [("jit_unpack_fn", 0, 2), ("jit_step", 2, 10)], [
        (op("fusion.1"), "jit(unpack_fn)/wf.unpack/gather:", 0, 2, 800),
        (op("fusion.2"), "jit(step)/wf.op.win/wf.place/scatter-add:", 2, 5,
         300),
        (op("while.3", "while"), "jit(step)/wf.op.win/while:", 5, 9, 0),
        (op("fusion.4"),
         "jit(step)/wf.op.win/wf.fire/while/body/jit(sort)/sort:", 5, 7, 40),
        (op("fusion.5"), "jit(step)/wf.op.win/while/body/add:", 7, 8, 8),
        (op("copy.6", "copy"), None, 9, 9.5, 16),
    ])


def test_rows_by_module_operator_and_innermost_phase():
    red = dp.reduce_planes([one_step()])
    assert red["chips"] == 1
    assert {k: tuple(v) for k, v in red["rows"].items()} == {
        ("jit_unpack_fn", None, "wf.unpack"): (2.0, 800.0),
        ("jit_step", "win", "wf.place"): (3.0, 300.0),
        ("jit_step", "win", "wf.fire"): (2.0, 40.0),
        ("jit_step", "win", dp.UNSCOPED): (1.0, 8.0),
        ("jit_step", None, dp.UNSCOPED): (0.5, 16.0),
    }
    # the while (4 s) holds the fire and the add: counted once, through them
    assert red["leaf_s"] == pytest.approx(2 + 3 + 2 + 1 + 0.5)
    assert red["busy_s"] == pytest.approx(10.0)
    assert red["unscoped_ops"] == pytest.approx(
        {"fusion.5 fusion": 1.0, "copy.6 copy": 0.5})
    assert dp.seconds(red, ("wf.place", "wf.fire")) == pytest.approx(5.0)
    assert dp.seconds(red, (dp.UNSCOPED,)) == pytest.approx(1.5)
    assert dp.has_phases(red)
    assert "wf.place" in dp.tables(red, 819e9)


def test_the_chips_are_averaged():
    """Four chips: the place runs 3 s on chip 0 and 1 s on the others; a
    chip without it still counts in the mean."""
    def chip(i, secs):
        return plane(i, [("jit_local", 0, 4)], [
            (op("fusion.2"), "jit(local)/wf.op.ffat/wf.place/scatter:", 0,
             secs, 100 * secs),
            (op("fusion.9"), None, secs, 4, 0)])
    red = dp.reduce_planes([chip(0, 3), chip(1, 1), chip(2, 1), chip(3, 1)])
    assert red["chips"] == 4
    assert red["rows"][("jit_local", "ffat", "wf.place")] \
        == pytest.approx([1.5, 150.0])
    assert red["busy_s"] == pytest.approx(4.0)
    assert red["leaf_s"] == pytest.approx(4.0)
    assert red["runs"][("jit_local", "ffat", "wf.place")] == 1.0


def test_a_later_window_stage_is_told_from_the_first_by_its_module():
    two = plane(0, [("jit_step", 0, 4), ("jit_step_w2", 4, 10)], [
        (op("fusion.1"), "jit(step)/wf.op.w1/wf.fire/gather:", 0, 4, 0),
        (op("fusion.1"), "jit(step_w2)/wf.op.w2/wf.fire/gather:", 4, 10, 0)])
    red = dp.reduce_planes([two])
    assert dp.seconds(red, ("wf.fire",)) == pytest.approx(10.0)
    assert dp.seconds(red, ("wf.fire",), dp.first_stage) \
        == pytest.approx(4.0)


def test_an_instruction_xla_made_takes_its_neighbours_phase():
    """``(name, op_name, operands, computation, calls)``: a scatter that
    XLA:TPU expanded (no metadata) between a named index fusion and the
    named merge; a 64-bit split on a parameter; a copy whose result only
    leaves the program; a while body whose instructions have no name at
    all; and one with no named neighbour anywhere."""
    place = "jit(step)/wf.op.win/wf.place/select_n"
    fire = "jit(step)/wf.op.win/wf.fire/while"
    got = dp.infer_scopes([
        ("p0", "state['cells']", [], "main", []),
        ("split.1", None, ["p0"], "main", []),            # user: ring
        ("roll.2", "jit(step)/wf.op.win/wf.ring/gather", ["split.1"],
         "main", []),
        ("idx.3", place, ["roll.2"], "main", []),
        ("scatter.4", None, ["idx.3"], "main", []),       # user: place
        ("relayout.5", None, ["scatter.4"], "main", []),  # ... through it
        ("merge.6", "jit(step)/wf.op.win/wf.place/add", ["relayout.5"],
         "main", []),
        ("while.7", fire, ["merge.6"], "main", ["body"]),
        ("copy.8", None, ["while.7"], "main", []),        # operand: fire
        ("b.p", None, [], "body", []),
        ("b.add", None, ["b.p"], "body", []),             # caller: fire
        ("lone.9", None, [], "main", []),
        # its own operator, no phase: the neighbour's pair replaces both
        ("rem.10", "jit(step)/wf.op.win/jit(floor_divide)/rem", ["p0"],
         "main", []),
        ("use.11", "jit(step)/wf.op.other/wf.group/sort", ["rem.10"],
         "main", []),
    ])
    assert got == {
        "p0": ("win", "wf.ring"), "split.1": ("win", "wf.ring"),
        "scatter.4": ("win", "wf.place"), "relayout.5": ("win", "wf.place"),
        "copy.8": ("win", "wf.fire"),
        "b.p": ("win", "wf.fire"), "b.add": ("win", "wf.fire"),
        "rem.10": ("other", "wf.group"),
    }


def test_inferred_time_counts_in_its_row_and_is_told_apart():
    """The reduction with a program whose HLO gives ``fusion.2`` (no
    ``op_name`` on the chip) the phase of its user."""
    p = plane(0, [("jit_step", 0, 6)], [
        (op("fusion.1"), "jit(step)/wf.op.win/wf.place/add:", 0, 1, 0),
        (op("fusion.2"), None, 1, 4, 0),
        (op("copy.3", "copy"), None, 4, 6, 0)])
    p["meta"] = {k: v + (77,) for k, v in p["meta"].items()}
    p["inferred"] = {77: {"fusion.2": ("win", "wf.place")}}
    red = dp.reduce_planes([p])
    assert {k: v[0] for k, v in red["rows"].items()} == {
        ("jit_step", "win", "wf.place"): 4.0,
        ("jit_step", None, dp.UNSCOPED): 2.0}
    assert red["unnamed_s"] == pytest.approx(5.0)     # 3 inferred + 2 left
    assert red["unscoped_ops"] == {"copy.3 copy": 2.0}


@pytest.mark.parametrize("path,expect", [
    ("jit(step)/wf.op.win/wf.place/scatter-add:", ("win", "wf.place")),
    ("jit(local)/shard_map/wf.op.ffat.mesh/wf.mesh.own/sort:",
     ("ffat.mesh", "wf.mesh.own")),
    ("jit(step)/wf.op.a/wf.fire/cond/branch_1_fun/jit(sort)/sort:",
     ("a", "wf.fire")),
    ("jit(unpack_fn)/wf.unpack/gather:", (None, "wf.unpack")),
    ("jit(step)/wf.op.campaign_counts/jit(floor_divide)/rem:",
     ("campaign_counts", None)),
    ("jit(step)/wf.session.carry:", (None, "wf.session.carry")),
    ("gather:", (None, None)), ("state['cells']:", (None, None)),
    (None, (None, None)),
])
def test_scope_of_a_path(path, expect):
    assert dp.scope_of(path) == expect


# ---------------------------------------------------------------------------
# the recorded traces
# ---------------------------------------------------------------------------

def test_the_walker_reads_what_profile_data_reads():
    """The protobuf walker against jax's own reader, event for event:
    names, starts and durations of the device lines."""
    from jax.profiler import ProfileData
    (mine,) = dp.read_xplane(RECORDED)
    theirs = {}
    for pl in ProfileData.from_file(RECORDED).planes:
        if trace_reduce.DEVICE_PLANE.match(pl.name):
            theirs = {ln.name: list(ln.events) for ln in pl.lines}
    ops = theirs[trace_reduce.OPS_LINE]
    assert len(ops) == len(mine["ops"]) > 100
    for e, (mid, s, t) in zip(ops, mine["ops"]):
        assert mine["meta"][int(mid)][0] == e.name
        assert s == pytest.approx(e.start_ns, abs=1.0)
        assert t - s == pytest.approx(e.duration_ns, abs=1.0)
    assert [trace_reduce.module_name(e.name)
            for e in theirs[trace_reduce.MODULES_LINE]] \
        == [m[0] for m in mine["modules"]]


def test_a_trace_of_this_program_gives_the_phase_table():
    with open(EXPECT) as f:
        expect = json.load(f)
    red = dp.reduce_planes(dp.read_xplane(RECORDED))
    rows = {" | ".join(str(x) for x in k): v[0]
            for k, v in red["rows"].items()}
    assert rows == pytest.approx(expect["rows_s"], rel=1e-9)
    assert red["busy_s"] == pytest.approx(expect["busy_s"])
    assert red["leaf_s"] == pytest.approx(expect["leaf_s"])
    # nothing counted twice and nothing lost: the rows are the line's leaf
    # operations, within 5 % of the modules' busy time
    assert sum(rows.values()) == pytest.approx(red["leaf_s"])
    assert abs(red["leaf_s"] / red["busy_s"] - 1) < 0.05
    phases = {k[2] for k in red["rows"]}
    assert {"wf.unpack", "wf.fn", "wf.place", "wf.ring", "wf.fire",
            "wf.egress.pack"} <= phases
    assert {k[1] for k in red["rows"] if k[1]} == set(expect["operators"])
    assert dp.seconds(red, (dp.UNSCOPED,)) / red["busy_s"] < 0.10
    # XLA's modeled bytes ride along
    assert red["rows"][("jit_unpack_fn", None, "wf.unpack")][1] > 0


@pytest.mark.parametrize("name,phase", [
    ("unpack_dev_ms_per_batch.sat", "wf.unpack"),
    ("operator_fn_dev_ms_per_batch.sat", "wf.fn"),
    ("place_dev_ms_per_batch.sat", "wf.place"),
    ("ring_pass_dev_ms_per_batch.sat", "wf.ring"),
    ("fire_dev_ms_per_batch.sat", "wf.fire"),
])
def test_metric_reads_its_phase_per_batch(name, phase):
    red = dp.reduce_planes(dp.read_xplane(RECORDED))
    got = reader(name).read({"any": "reduction"}, {}, window(DATA, 4.0))
    assert got == pytest.approx(dp.seconds(red, (phase,)) / 4.0 * 1e3)
    assert got > 0


def test_unscoped_share_of_the_recorded_trace():
    red = dp.reduce_planes(dp.read_xplane(RECORDED))
    for name in ("unscoped_dev_share.sat", "unscoped_dev_share.steady"):
        got = reader(name).read(None, {}, window(DATA))
        assert got == pytest.approx(
            100 * dp.seconds(red, (dp.UNSCOPED,)) / red["busy_s"])
        assert 0 < got < 10


@pytest.mark.parametrize("name", [
    # phases this program's trace does not hold: nothing to read
    "group_dev_ms_per_batch.sat", "group_dev_ms_per_batch.steady",
    "own_dev_ms_per_batch.sat", "sketch_dev_ms_per_batch.sat",
    "session_carry_dev_ms_per_batch.sat"])
def test_metric_of_an_absent_phase_reads_nothing(name):
    assert reader(name).read(None, {}, window(DATA)) is None


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_scopes_reads_nothing_and_raises_nothing(name):
    """Any parent commit: the traces recorded at PR 23 and PR 24."""
    assert reader(name).read({"any": "reduction"}, {},
                             window(NO_SCOPES)) is None
    # ... nor an untraced run, nor a run whose trace is missing
    assert reader(name).read(None, {}, {"trace_dir": None,
                                        "trace0": None}) is None
    assert reader(name).read(None, {}, window(
        os.path.join(ROOT, "benchmark", "configs"))) is None


def test_the_older_traces_hold_no_phase_but_add_up():
    for f in ("spans.xplane.pb", "small.xplane.pb"):
        red = dp.reduce_planes(dp.read_xplane(os.path.join(NO_SCOPES, f)))
        assert not dp.has_phases(red)
        assert {k[2] for k in red["rows"]} == {dp.UNSCOPED}
        assert abs(red["leaf_s"] / red["busy_s"] - 1) < 0.01
        both = trace_reduce.reduce_planes(trace_reduce.read_planes(
            os.path.join(NO_SCOPES, f)))
        assert red["busy_s"] == pytest.approx(both["busy_s"])
        assert red["leaf_s"] == pytest.approx(sum(both["ops"].values()),
                                              rel=1e-4)


# ---------------------------------------------------------------------------
# the manifest
# ---------------------------------------------------------------------------

def test_the_manifest_gains_these_entries_and_nothing_else():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    mine = [e for e in m["per_layer"] if e["name"] in NEW]
    assert [e["name"] for e in mine] == NEW
    # what is left when this PR's additions are taken away is the
    # parent's manifest, entry for entry and in order (a later PR may
    # append after these: they are not pinned as the last)
    parent = dict(m, per_layer=[e for e in m["per_layer"]
                                if e["name"] not in NEW])
    assert hashlib.sha256(json.dumps(parent, sort_keys=True).encode()) \
        .hexdigest() == PARENT_MANIFEST
    at = [e["name"] for e in m["per_layer"]].index(NEW[0])
    assert [e["name"] for e in m["per_layer"][at:at + len(NEW)]] == NEW
    cells = {w["name"] for w in m["workloads"]}
    layers = {e["layer"] for e in parent["per_layer"]}
    reports = {e["name"]: set(e.get("workloads", cells))
               for e in m["end_to_end"]}
    for e in mine:
        assert set(e) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert e["source"] == "device_trace" and e["better"] == "lower"
        assert e["layer"] in layers
        assert e["unit"] == ("%" if e["name"].startswith("unscoped")
                             else "ms")
        steady = e["name"].endswith(".steady")
        assert e["moves"] == ("latency_p95_ms" if steady else "tuples_per_s")
        assert e["workloads"] and set(e["workloads"]) <= reports[e["moves"]]
        assert os.path.isfile(os.path.join(
            ROOT, "benchmark", "layer_metrics", e["name"] + ".py"))
    for cell in cells:
        reported = {e["name"] for e in harness.resolve_cell(cell)["per_layer"]}
        assert any(n.startswith("unscoped_dev_share") for n in reported)


def test_every_phase_a_metric_reads_is_declared_by_the_program():
    from windflow_tpu.monitoring import recorder
    read = set()
    for name in NEW:
        with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                               name + ".py")) as f:
            import re
            read |= set(re.findall(r'"(wf\.[a-z_.]+)"', f.read()))
    assert read and read <= set(recorder.PHASES)
