#!/usr/bin/env python
"""wf_tenant: rank tenants by budget pressure and emit a scheduler plan.

CLI face of the tenancy advisor (windflow_tpu/analysis/tenancy.py),
mirroring ``tools/wf_shard.py``: point it at a
stats dump carrying a ``Tenant`` section (a ``dump_stats`` JSON, a
postmortem ``stats.json`` / ``tenant.json``, or a bare section file)
and get every tenant in the process ranked by HBM budget pressure,
with the concrete ``throttle_admission``/``rescale_tenant``/
``drain_shards``/``rebalance_hot_tenant`` actions for an operator to
take: no executor in the package consumes ``plan(...)`` (``wf_shard.plan``
has one, the reshard executor).

Usage::

    python tools/wf_tenant.py --stats DUMP          # rank + plan
    python tools/wf_tenant.py ... --json            # machine-readable
    python tools/wf_tenant.py ... --top N           # worst N tenants
    python tools/wf_tenant.py --check --stats DUMP  # budget gate:
        # exit 1 while any tenant's latched OVER_BUDGET verdict is
        # active, or the attributed staged fraction is under
        # --min-fraction (default 0.9, the CI reconciliation floor)

This tool never imports jax (the ``wf_metrics``/``wf_doctor``
scrape-host stance — the advisor module is loaded file-direct, skipping
the package __init__).  Exit status: 0 when the plan has at least one
action (or --check passes), 1 when there is nothing to do (or --check
fails), 2 on usage/load failures.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _load_advisor():
    """File-direct import of analysis/tenancy.py (pure stdlib): skips
    the ``windflow_tpu`` package __init__, which imports jax."""
    path = os.path.join(REPO, "windflow_tpu", "analysis", "tenancy.py")
    spec = importlib.util.spec_from_file_location("_wf_tenancy", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def fail(msg: str) -> None:
    print(f"wf_tenant: FAIL: {msg}", file=sys.stderr)
    sys.exit(2)


def load_tenant_section(path: str) -> dict:
    """The ``Tenant`` section out of a stats dump / postmortem
    stats.json / bare tenant.json file."""
    try:
        with open(path) as f:
            obj = json.load(f)
    except (OSError, ValueError) as e:
        fail(f"cannot read stats dump '{path}': {e}")
    if isinstance(obj, dict) and "tenants" in obj:
        return obj                     # bare tenant.json section
    ten = (obj or {}).get("Tenant")
    if not isinstance(ten, dict) or not ten.get("enabled"):
        fail(f"'{path}' carries no enabled 'Tenant' section — run the "
             "graph with Config.tenant_ledger on and dump_stats first")
    return ten


def _bar(pressure, width: int = 20) -> str:
    """ASCII budget bar: filled to min(pressure, 1), '!' past 1."""
    if pressure is None:
        return "(no budget)"
    fill = min(1.0, pressure)
    n = int(round(fill * width))
    bar = "#" * n + "." * (width - n)
    tail = "!" * min(width, int((pressure - 1.0) * width)) \
        if pressure > 1.0 else ""
    return f"[{bar}]{tail} {pressure:.2f}x"


def render_text(p: dict) -> str:
    frac = (p.get("attributed") or {}).get("staged_fraction")
    head = (f"{p['tenants_total']} tenant(s), "
            f"{len(p['over_budget_tenants'])} over budget"
            + (f", attribution {frac:.0%} of process staged bytes"
               if frac is not None else ""))
    lines = [f"wf_tenant: {head}; {p['actionable']} tenant(s) with "
             f"actions"]
    for i, t in enumerate(p["tenants"], 1):
        lines.append(
            f"  #{i} {t['tenant']} "
            f"({', '.join(t['graphs']) or 'no live graphs'}): "
            f"{_bar(t['pressure'])} — {t['hbm_bytes']} B resident"
            + (f" / {t['budget_bytes']} B budget"
               if t["budget_bytes"] else "")
            + (f", heaviest op {t['heaviest_op']}"
               if t.get("heaviest_op") else ""))
        v = t.get("verdict")
        if v:
            tag = "ACTIVE" if t["over_budget"] else "last"
            lines.append(f"      verdict ({tag}): {v.get('message')}")
        for a in t["actions"]:
            if a["kind"] == "throttle_admission":
                lines.append(f"      PLAN throttle_admission x"
                             f"{a['factor']} — {a['note']}")
            elif a["kind"] == "rescale_tenant":
                lines.append(f"      PLAN rescale_tenant shed "
                             f"{a['shed_bytes']} B — {a['note']}")
            elif a["kind"] == "drain_shards":
                lines.append(f"      PLAN drain_shards op="
                             f"{a['op']} — {a['note']}")
            elif a["kind"] == "rebalance_hot_tenant":
                lines.append(f"      PLAN rebalance_hot_tenant — "
                             f"{a['note']}")
        if not t["actions"]:
            lines.append("      (no action)")
    if not p["tenants"]:
        lines.append("  (no tenants registered — is "
                     "Config.tenant_ledger on?)")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--stats", metavar="DUMP", required=True,
                    help="stats JSON with a Tenant section (dump_stats "
                         "output, postmortem stats.json, or a bare "
                         "tenant.json)")
    ap.add_argument("--json", action="store_true",
                    help="emit the ranked plan as JSON")
    ap.add_argument("--top", type=int, default=0,
                    help="emit only the worst N tenants")
    ap.add_argument("--check", action="store_true",
                    help="budget gate: exit 1 while any tenant's "
                         "latched OVER_BUDGET verdict is active or "
                         "attribution is under --min-fraction")
    ap.add_argument("--min-fraction", type=float, default=0.9,
                    help="minimum attributed staged fraction --check "
                         "accepts (default 0.9, the CI floor; only "
                         "enforced when the section reports one)")
    args = ap.parse_args(argv)

    ten = load_tenant_section(args.stats)
    adv = _load_advisor()
    p = adv.plan(ten, top=args.top)
    if args.check:
        if p["over_budget_tenants"]:
            worst = p["tenants"][0] if p["tenants"] else {}
            v = worst.get("verdict") or {}
            print(f"wf_tenant: OVER BUDGET — "
                  f"{', '.join(p['over_budget_tenants'])}: "
                  f"{v.get('message', '?')}")
            return 1
        frac = (p.get("attributed") or {}).get("staged_fraction")
        if frac is not None and frac < args.min_fraction:
            print(f"wf_tenant: ATTRIBUTION GAP — only {frac:.1%} of "
                  f"process staged bytes attributed to tenants "
                  f"(floor {args.min_fraction:.0%})")
            return 1
        print(f"wf_tenant: OK — {p['tenants_total']} tenant(s) within "
              f"budget"
              + (f", attribution {frac:.1%}" if frac is not None
                 else ""))
        return 0
    if args.json:
        print(json.dumps(p, indent=2))
    else:
        print(render_text(p))
    return 0 if p["actionable"] else 1


if __name__ == "__main__":
    sys.exit(main())
