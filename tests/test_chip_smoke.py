"""chip_smoke.py's legs at tiny sizes on the CPU backend: the graphs, the
sinks and the numpy oracles are the ones the chip run uses, so a leg
that breaks fails here first.  The TPU assertion lives in ``main()``
only."""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import chip_smoke as cs  # noqa: E402  (repo-root script under test)

TINY = dict(cap=256, n_batches=5, n_keys=16, win=64, slide=16)


def _assert_leg(out, tuples):
    assert out["correct"], out
    assert out["result_rows"] > 0
    assert out["warm_result_rows"] == out["result_rows"]
    assert out["tuples_in"] == tuples
    assert out["cold_programs"], "the jit registry saw no program"
    # on the CPU backend every default-ON path resolves off or interprets
    eng = out["engaged"]
    assert not eng["megastep"]["engaged"] and eng["megastep"]["reason"]
    assert not eng["wire"]["engaged"] and eng["wire"]["reason"]
    assert not eng["pallas"]["engaged"] and eng["pallas"]["interpret"]


def test_cb_window_oracle_against_a_loop():
    """The vectorized oracle is itself checked against the obvious
    per-key loop (partial windows flush at end of stream)."""
    rng = np.random.default_rng(3)
    keys = rng.integers(0, 5, 700)
    vals = rng.random(700)
    win, slide = 64, 16
    exp = {}
    for k in np.unique(keys):
        seq = vals[keys == k]
        w = 0
        while w * slide < len(seq):
            exp[(int(k), w)] = float(np.sum(seq[w * slide:w * slide + win]))
            w += 1
    gk, gw, gv = cs.oracle_cb_windows(keys, vals, win, slide)
    got = {(int(k), int(w)): float(v) for k, w, v in zip(gk, gw, gv)}
    assert got.keys() == exp.keys()
    assert all(abs(got[kw] - exp[kw]) < 1e-9 for kw in exp)


def test_compare_windows_rejects_wrong_answers():
    k = np.array([0, 0, 1])
    w = np.array([0, 1, 0])
    v = np.array([1.0, 2.0, 3.0])
    assert cs.compare_windows((k, w, v), (k, w, v), 1e-6)["correct"]
    assert not cs.compare_windows((k, w, v * 1.01), (k, w, v),
                                  1e-6)["correct"]
    assert not cs.compare_windows((k[:2], w[:2], v[:2]), (k, w, v),
                                  1e-6)["correct"]
    empty = (k[:0], w[:0], v[:0])
    assert not cs.compare_windows(empty, empty, 1e-6)["correct"]


@pytest.mark.parametrize("declared_sum", [False, True])
def test_leg_flagship(declared_sum):
    out = cs.leg_flagship(0, declared_sum=declared_sum, **TINY)
    _assert_leg(out, TINY["cap"] * TINY["n_batches"])
    assert out["window_set_exact"]
    # the generic combiner builds the grouping kernel; the declared one
    # adds the pane fold
    assert out["engaged"]["pallas"]["kernel_builds"] == 1 + declared_sum


def test_leg_ysb():
    out = cs.leg_ysb(1, cap=1024, n_batches=5)
    _assert_leg(out, 5 * 1024)
    assert out["windows"] == cs.YSB_WINDOWS and out["max_rel_err"] == 0.0


def test_leg_reduce():
    out = cs.leg_reduce(0, cap=256, n_batches=5, n_keys=16)
    _assert_leg(out, 5 * 256)
    assert out["counts_exact"]
    assert out["engaged"]["pallas"]["kernel_builds"] == 1   # dense table


def test_leg_mesh_shards_window_state():
    """Leg D on four of the suite's virtual CPU devices."""
    out = cs.leg_mesh(0, n_devices=4, **TINY)
    assert out["correct"], out
    assert out["state_devices"] == 4


def test_main_refuses_a_non_tpu_backend(monkeypatch, capsys):
    monkeypatch.setattr(sys, "argv", ["chip_smoke.py"])
    assert cs.main() == 2
    io = capsys.readouterr()
    assert io.out == "", "no result line without an accelerator"
    assert "'cpu'" in io.err


def test_last_stdout_line_is_the_verdict_and_nothing_else(capsys):
    """The driver reads the LAST stdout line and refuses any key beyond
    ``ok`` and ``device`` {platform, kind, count}; the report, with
    everything else, is the line before it."""
    import json

    import jax
    devs = jax.devices()
    cs.emit(True, devs, {"legs": {"A": {"correct": True}}})
    report, verdict = map(json.loads, capsys.readouterr().out.splitlines())
    assert verdict == {"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}
    assert type(verdict["device"]["count"]) is int
    assert report["legs"] and list(report)[-1] == "claim" \
        and report["claim"] is None
