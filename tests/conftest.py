"""Test configuration: run everything on a virtual 8-device CPU mesh so
multi-chip sharding paths compile and execute without TPU hardware (the
driver's dryrun does the same; measurement on the chip lives in benchmark/)."""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

# The suite runs on the CPU backend whatever the machine has (the chip is
# chip_smoke.py's): pin it through the config API before any backend is
# initialized, so a plain `pytest` needs no JAX_PLATFORMS in its environment.
import jax

jax.config.update("jax_platforms", "cpu")

import random

import pytest


@pytest.fixture
def rng():
    return random.Random(1234)


def tb_window_sums(points, win_us, slide_us):
    """Shared TB-window oracle: per-key sums of every time window containing
    at least one tuple.  ``points`` maps key -> [(ts_us, value), ...]."""
    exp = {}
    for k, pts in points.items():
        wids = set()
        for ts, _ in pts:
            last = ts // slide_us
            first = max(0, -(-(ts - win_us + 1) // slide_us))
            wids.update(range(first, last + 1))
        for w in wids:
            vals = [v for ts, v in pts
                    if w * slide_us <= ts < w * slide_us + win_us]
            if vals:
                exp[(k, w)] = sum(vals)
    return exp
