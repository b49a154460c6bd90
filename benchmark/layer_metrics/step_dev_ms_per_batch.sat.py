"""fused operator program: device time of the configuration's step
program (its modules are named by ``roofline/<step_program>.py``) per
batch pulled in the traced span."""
import re

from benchmark.harness import load_module


def step_seconds(trace, window):
    prog = load_module("roofline", window["config"]["step_program"])
    pat = re.compile(prog.MODULES)
    return sum(s for n, s in trace["modules"].items() if pat.search(n))


def traced_batches(window):
    return (window["trace1"]["pulled"] - window["trace0"]["pulled"]) \
        / window["batch"]


def read(trace, stats, window):
    if trace is None or window["trace0"] is None:
        return None
    batches, secs = traced_batches(window), step_seconds(trace, window)
    if batches <= 0 or secs <= 0:
        return None
    return secs / batches * 1e3
