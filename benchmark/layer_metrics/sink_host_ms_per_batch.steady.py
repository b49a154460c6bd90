"""As ``sink_host_ms_per_batch.sat``, for the cells that report latency."""
from benchmark.harness import load_module

read = load_module("layer_metrics", "sink_host_ms_per_batch.sat").read
