"""As ``idle_unattributed_share.sat``, for the cells that report latency."""
from benchmark.harness import load_module

read = load_module("layer_metrics", "idle_unattributed_share.sat").read
