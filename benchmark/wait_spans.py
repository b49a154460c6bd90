"""The driver thread's waits for the chip, apart from its work.

Since PR 51 the program opens an innermost span of its own wherever the
driver thread BLOCKS on the device (``windflow_tpu/monitoring/recorder.py``
``WAITS``: every ``wf.wait.*`` name, and the two wait spans that stood
before the table, ``wf.pool.wait`` and ``wf.megastep.drain``).  The self
time of the spans around them (``wf.dispatch``, ``wf.sink.d2h``,
``wf.drain``, ``wf.pack``) is then the host's own work, the thread's
blocked time a batch is a sum of named spans, and an idle gap of the chip
whose innermost covering span is a wait is the link's, not the host's.

Everything here goes through ``program_spans.load`` / ``analyse``.  On a
trace without a single ``wf.wait.*`` event (a program before PR 51, the
recorded trace in ``testdata/``) work and wait cannot be told apart and
every reader answers ``None``: a partial answer would be a wrong one.

The sums are over ``analyse``'s ``self_s``, so over every thread that
recorded; ``driver_self_s`` is the driver's alone, and ``analyse`` keeps
no thread's spans apart.  So ``host_work`` NEEDS the device path on the
driver thread (default ``Config()``, as every cell of the benchmark runs:
no host worker pool): there the two agree and ``chip_wait + host_work =
driver_self_s - generator.idle`` exactly; a pool thread that waited for
the chip would be taken off the driver's work and ``host_work`` would
under-read by that much.
"""

from __future__ import annotations

from typing import Callable, Optional

from benchmark import harness
from benchmark import program_spans as ps

WAIT_PREFIX = "wf.wait."
#: wait spans older than the vocabulary: they keep their names
OLDER_WAITS = ("wf.pool.wait", "wf.megastep.drain")
HELD = "wf.wait.held"
D2H = "wf.wait.d2h"


def is_wait(name: str) -> bool:
    return name.startswith(WAIT_PREFIX) or name in OLDER_WAITS


def load(window: dict) -> Optional[dict]:
    """``program_spans.load``, or None where the program that ran does
    not name its waits (no ``wf.wait.*`` event in the trace)."""
    sp = ps.load(window)
    if sp is None or not any(name.startswith(WAIT_PREFIX)
                             for name, _ in sp["count"]):
        return None
    return sp


def wait_seconds(sp: dict) -> float:
    """Blocked on the chip, wherever the wait landed."""
    return sum(s for (name, _), s in sp["self_s"].items() if is_wait(name))


def work_seconds(sp: dict) -> float:
    """The driver thread's self time under every span it holds but the
    waits and ``generator.idle``: the serial work that the chip's time a
    batch and the wall are held against."""
    return sp["driver_self_s"] - wait_seconds(sp) \
        - sp["self_s"].get((ps.WAITING, None), 0.0)


def ms_per_batch(window: dict,
                 seconds_of: Callable[[dict], float]) -> Optional[float]:
    """``seconds_of(analysis)`` per staging batch (262144 tuples) pulled
    in the traced span, the unit of every ``*_host_ms_per_batch``
    (``program_spans.host_ms_per_batch`` sums spans by name; the two
    readers here take a predicate and the driver line's total)."""
    sp = load(window)
    if sp is None:
        return None
    batches = harness.load_module(
        "layer_metrics", "step_dev_ms_per_batch.sat").traced_batches(window)
    if batches <= 0:
        return None
    return seconds_of(sp) / batches * 1e3


def idle_under_wait_share(window: dict) -> Optional[float]:
    """Idle seconds of chip 0 whose innermost covering span is a wait /
    all its idle seconds, %: the host waits and the chip computes
    nothing, so that part of the idle is the link (a copy on the wire,
    the round trip of a read), not the host's work."""
    sp = load(window)
    if sp is None or sp["idle_s"] <= 0:
        return None
    under = sum(s for (name, _), s in sp["gaps_s"].items() if is_wait(name))
    return 100.0 * under / sp["idle_s"]
