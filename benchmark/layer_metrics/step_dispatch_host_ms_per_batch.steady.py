"""As ``step_dispatch_host_ms_per_batch.sat``, for the cells that report latency."""
from benchmark.harness import load_module

read = load_module("layer_metrics", "step_dispatch_host_ms_per_batch.sat").read
