#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The LAST line of stdout is one JSON object with exactly the keys
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (and
``breakdown`` with ``--trace 1``); earlier lines carry each number
compared beside its limit and the run's other observations.  Without a
TPU, with fewer chips than the cell asks for, or on a device kind that
``peaks.json`` does not list, it exits non-zero and prints nothing on
stdout.  There is no CPU path: the tests drive ``harness.run_cell``
in-process at tiny sizes and never print a device metric.
"""

from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def fail(msg: str, code: int = 2) -> int:
    print(f"benchmark: {msg}", file=sys.stderr)
    return code


def result_line(window: dict, metrics: dict, device: dict,
                breakdown=None) -> dict:
    """Exactly the contract's keys."""
    out = {"correct": bool(window["correct"]),
           "attempted": int(window["attempted"]),
           "failed": int(window["failed"]), "metrics": metrics,
           "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rate", type=float, default=None,
                    help="override a steady mix's rate: the knee sweep "
                         "of README.md, never the driver")
    args = ap.parse_args(argv)

    try:
        from benchmark import harness
        cell = harness.resolve_cell(args.workload)
    except (ImportError, OSError, KeyError, ValueError) as e:
        return fail(f"cannot resolve the cell: {type(e).__name__}: {e}")
    try:
        import windflow_tpu  # noqa: F401  (the system under test)
    except ImportError as e:
        return fail(f"the program is not in this checkout: {e}")

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        return fail(f"JAX found platform {devs[0].platform!r} "
                    f"({devs[0].device_kind}), not a TPU")
    if len(devs) < cell["chips"]:
        return fail(f"the cell needs {cell['chips']} chips, "
                    f"JAX sees {len(devs)}")
    try:
        peaks = harness.load_peaks(devs[0].device_kind, cell["bench"])
    except KeyError as e:
        return fail(str(e))
    devs = devs[:cell["chips"]]

    from windflow_tpu import native
    from windflow_tpu.compile_cache import setup_compile_cache
    cache_dir = setup_compile_cache()
    native.build()               # once per checkout, into native/_build/
    if not native.is_available():
        return fail("the native parser did not build or load", 3)

    if args.rate is not None:
        cell["mix"] = dict(cell["mix"], rate=args.rate)
    window = harness.run_cell(
        cell, args.seed, args.seconds, bool(args.trace), T_PROCESS, devs,
        cache_dir)
    window["peaks"] = peaks

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs),
              "memory_peak_bytes": window["memory_peak_bytes"]}
    breakdown = None
    if args.trace:
        from benchmark import trace_reduce
        red = trace_reduce.reduce_trace(window["trace_dir"])
        if red is None or not red["devices"] or red["busy_s"] <= 0:
            return fail("the traced span holds no device operation", 4)
        metrics = harness.read_metrics(cell, cell["per_layer"],
                                       "layer_metrics", red, window)
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        breakdown = red["breakdown"]
        print(json.dumps({"per_chip": red["devices"],
                          "modules": red["modules"]}))
    else:
        metrics = harness.read_metrics(cell, cell["end_to_end"],
                                       "end_to_end", None, window)
    print(json.dumps({"checks": window["checks"]}))
    print(json.dumps({"observed": {
        "cell": window["cell"], "seed": args.seed,
        "compile_cache_dir": cache_dir, "setup_s": window["setup_s"],
        "tuples_in_window": window["tuples_in_window"],
        "tuples_total": window["n_total"], "result_rows": window["rows"],
        "deliveries": len(window["delivery_stamps"]),
        "warmup_reanchors": window["warmup_reanchors"],
        "compiled_after_open": window["compiled_after_open"],
        "source_lag_ms": harness.lag_summary(window),
        "window_counts": harness.delta(window["open"], window["close"]),
    }}))
    print(json.dumps(result_line(window, metrics, device, breakdown)),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
