"""As ``unscoped_dev_share.sat``, for the cells that report latency."""
from benchmark.harness import load_module

read = load_module("layer_metrics", "unscoped_dev_share.sat").read
