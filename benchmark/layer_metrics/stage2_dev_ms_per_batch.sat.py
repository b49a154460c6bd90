"""fused operator program: device time of the programs of the window
stages after the first (``jit_step_w2``, ``jit_step_w3``, ...: a window
operator fed by another window's fired rows names its program by its
stage) per batch pulled in the traced span.  A graph with one window
stage, or a program that does not tell its stages apart, gives nothing to
read."""
import re

from benchmark.harness import load_module

LATER_STAGES = re.compile(r"^jit_step_w\d+$")


def later_stage_seconds(trace):
    return sum(s for n, s in trace["modules"].items()
               if LATER_STAGES.search(n))


def read(trace, stats, window):
    if trace is None or window["trace0"] is None:
        return None
    batches = load_module("layer_metrics", "step_dev_ms_per_batch.sat") \
        .traced_batches(window)
    secs = later_stage_seconds(trace)
    if batches <= 0 or secs <= 0:
        return None
    return secs / batches * 1e3
