"""As ``device_idle.sat``, for the cells that report latency."""
from benchmark.harness import load_module

read = load_module("layer_metrics", "device_idle.sat").read
