"""fused operator program: device time of the interval join's pair form
(module ``jit_step_join_pairs``) under the phases ``wf.join.sort`` (the
waiting probes and the batch brought into (key, event time) order, the
leaves the pair functions read riding) and ``wf.join.match`` (the build
row handed down its run, the interval and predicate tests, the stable
sort by class that brings pairs, waiting probes and build rows to the
front), ms per staging batch pulled in the traced span."""
from benchmark import device_phases as dp


def pair_step(module: str) -> bool:
    """The pair form's program, and not the fold form's ``jit_step_join``
    (the ``join_*_dev_ms_per_batch.sat`` of ``nexmark_q9.saturated``)."""
    return module == "jit_step_join_pairs"


def read(trace, stats, window):
    return dp.dev_ms_per_batch(window, ("wf.join.sort", "wf.join.match"),
                               pair_step)
