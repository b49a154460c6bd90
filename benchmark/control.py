#!/usr/bin/env python3
"""Read the control of a configuration at the cell's own size.

    python benchmark/control.py --workload <cell> --tuples <n> --seeds 1 2 3

The control is the plain reference put in the program's place and
computed in the precision below the one the configuration states (or,
where it states none, with one stated guarantee broken): see each
configuration's ``control``.  It needs no device and the benchmark's own
runs never run it; it is read when a limit is set, beside the largest
number that sound runs of the program give (``PERF.md`` section 2), and
``tests/benchmark`` keeps it at a size a test run can hold.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    from benchmark import harness
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--tuples", type=int, required=True,
                    help="stream length: what a run of the cell sends")
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = harness.resolve_cell(args.workload)
    mod, cfg, mix = cell["config_module"], cell["config"], cell["mix"]
    for seed in args.seeds:
        ring = mod.make_ring(seed, cfg)
        exp = mod.expected(cfg, ring, args.tuples, mix)
        k, w, v = mod.control(cfg, ring, args.tuples, mix)
        checks = mod.compare(cfg, {"key": k, "wid": w, "value": v}, exp)
        print(json.dumps({"control": args.workload, "seed": seed,
                          "rows": int(len(exp.key)), "checks": checks}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
