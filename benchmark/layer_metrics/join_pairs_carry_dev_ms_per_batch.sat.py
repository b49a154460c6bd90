"""fused operator program: device time of the interval join's pair form
(module ``jit_step_join_pairs``) under the phase ``wf.join.carry`` (the
probes that go on waiting for their build row, compacted into the next
step's pending lanes), ms per staging batch pulled in the traced span."""
from benchmark import device_phases as dp
from benchmark import harness

pair_step = harness.load_module(
    "layer_metrics", "join_pairs_match_dev_ms_per_batch.sat").pair_step


def read(trace, stats, window):
    return dp.dev_ms_per_batch(window, ("wf.join.carry",), pair_step)
