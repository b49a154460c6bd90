"""As ``compiles_in_window.sat``, for the cells that report latency."""
from benchmark.harness import load_module

read = load_module("layer_metrics", "compiles_in_window.sat").read
