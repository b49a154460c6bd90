"""Where JAX's persistent compilation cache lives — THE one place that
decides (chip_smoke.py, benchmark/ and tools/wf_calibrate.py all call
:func:`setup_compile_cache`).

The cache directory is part of every entry's key, so it must not move
between runs: whoever launches the process may place it with
``JAX_COMPILATION_CACHE_DIR`` (JAX reads the variable itself, and then
this module sets no path); otherwise it is ``<checkout>/.jax_cache``, a
fixed git-ignored path next to the package.
"""

from __future__ import annotations

import os

import jax

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def setup_compile_cache() -> str:
    """Enable the persistent compilation cache and return its directory.
    Fresh operator objects re-trace in every graph build, so cross-run
    (and, within one process, cross-graph) reuse needs the disk cache."""
    # A Pallas kernel travels inside the HLO as serialized MLIR WITH its
    # locations, and XLA hashes that payload into the cache key.  Full
    # Python tracebacks as locations differ on every re-trace, so each
    # new graph's kernel-bearing programs would compile again (13-52 s
    # apiece on a v5e host); one frame per location keeps the key stable.
    # It stays a traceback, of ONE frame, and not a bare file location:
    # with jax_include_full_tracebacks_in_locations off, this jax (0.9.0)
    # names an operation by its primitive alone (op_name "gather") and
    # the name stack, jit(step)/wf.op.<operator>/wf.<phase>, never
    # reaches the HLO: a profiler capture could not tell the program's
    # device phases apart (monitoring/recorder.py; PERF.md, PR 34).
    jax.config.update("jax_traceback_in_locations_limit", 1)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    # the pipeline's programs are many and small: cache all of them
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
