"""As ``latency_p50_ms``, 95th percentile: rows of one batch share a
delivery stamp, so the independent samples are the batches, a few hundred
a run, and p95 is the highest percentile with ten of them beyond it."""
from benchmark.harness import load_module


def read(trace, stats, window):
    return load_module("end_to_end", "latency_p50_ms").read(
        trace, stats, window, q=95)
