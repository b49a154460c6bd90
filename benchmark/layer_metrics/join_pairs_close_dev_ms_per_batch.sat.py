"""fused operator program: device time of the interval join's pair form
(module ``jit_step_join_pairs``) under the phase ``wf.join.close`` (the
pairs a step completed gathered into its output batch, through the
held-back lanes where they do not fit), ms per staging batch pulled in
the traced span."""
from benchmark import device_phases as dp
from benchmark import harness

pair_step = harness.load_module(
    "layer_metrics", "join_pairs_match_dev_ms_per_batch.sat").pair_step


def read(trace, stats, window):
    return dp.dev_ms_per_batch(window, ("wf.join.close",), pair_step)
