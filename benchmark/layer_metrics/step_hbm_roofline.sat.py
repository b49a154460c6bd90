"""fused operator program / Pallas kernels: the least time the chip's HBM
needs for the bytes the step must move by its shapes / the step's device
time, in the traced span.  Bound by bytes, not operations: a window step
does one add per tuple.  Over 100 % means the count is wrong."""
from benchmark.harness import load_module


def read(trace, stats, window):
    if trace is None or window["trace0"] is None:
        return None
    dev = load_module("layer_metrics", "step_dev_ms_per_batch.sat")
    batches, secs = dev.traced_batches(window), dev.step_seconds(trace,
                                                                 window)
    if batches <= 0 or secs <= 0:
        return None
    prog = load_module("roofline", window["config"]["step_program"])
    least = prog.least_bytes(window["config"]) * batches \
        / window["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / secs
