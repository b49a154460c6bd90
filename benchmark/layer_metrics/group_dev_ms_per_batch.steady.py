"""As ``group_dev_ms_per_batch.sat``, for the cells that report latency:
this is where the Pallas grouping call shows."""
from benchmark.harness import load_module

read = load_module("layer_metrics", "group_dev_ms_per_batch.sat").read
