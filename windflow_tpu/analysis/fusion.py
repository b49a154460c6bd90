"""Fusion advisor core: maximal fusible operator chains + projected
savings, the planning layer for whole-chain fusion (ROADMAP item 1).

Every operator hop in the PipeGraph sweep is its own jitted dispatch
that round-trips HBM; the sweep ledger (monitoring/sweep_ledger.py)
measures what each hop costs, and this module says which hops could
stop existing: it reuses the pre-flight graph walk
(analysis/preflight.py) to find **maximal fusible chains** — runs of
adjacent TPU operators whose routing and batch contracts let one XLA
program replace the whole run — and ranks them by projected bytes- and
dispatches-saved per batch.  ``ops/chained.py`` proves the pairwise
case today (``MultiPipe.chain`` fuses map/filter pairs into one
program); the chains found here generalize that to arbitrary runs,
window-lift/combine tails included, emitter/collector boundaries
permitting.

Two link strengths:

* ``chainable`` — both ends are stateless members (``Operator.
  chain_role``) and the edge is FORWARD at equal parallelism: today's
  ``chain()`` could already fuse them into one ``ChainedTPU``
  (``ops.chained.fuse``; a plan entry here is a missed call site).
* ``whole_chain`` — the edge needs the whole-chain-fusion refactor:
  a window/reduce/stateful tail, or a single-replica KEYBY edge whose
  key extraction already runs inside the compiled program (the keyby
  emitter is then a pure relay a fused program can absorb).

Entry point: :func:`plan` (used by ``tools/wf_advisor.py`` and the
tests); pass a ``stats()["Sweep"]`` section to rank by MEASURED per-hop
numbers instead of spec-based projections.
"""

from __future__ import annotations

from typing import Dict, List, Optional

from windflow_tpu.basic import RoutingMode


def _chain_boundary(a, b, fanout: Dict[int, int],
                    fanin: Dict[int, int]) -> Optional[str]:
    """Why the edge ``a -> b`` cannot join one fused program; ``None``
    when it can (the link reasons :func:`fusible_chains` records)."""
    if not a.is_tpu:
        return "upstream is not a TPU stage"
    if not b.is_tpu:
        return "downstream leaves the device (host stage / sink)"
    for end, op in (("upstream", a), ("downstream", b)):
        if op.chain_role is None:   # a device source; an operator that
            # declares no part (``Operator.chain_role``)
            return f"{end} takes no part in a fused chain"
    if fanout.get(id(a), 0) != 1:
        return "upstream fans out (split / multi-consumer)"
    if fanin.get(id(b), 0) != 1:
        return "downstream merges several inputs"
    if a.parallelism != b.parallelism:
        return "parallelism changes across the edge"
    if b.routing == RoutingMode.FORWARD:
        return None
    if b.routing == RoutingMode.KEYBY:
        if b.parallelism != 1:
            return "keyby edge re-partitions across replicas"
        if b.key_extractor is None:
            return "keyby edge without a device key extractor"
        return None     # single-replica keyby: the emitter is a relay
    return f"{b.routing.value} routing breaks the device chain"


def fusible_chains(graph) -> List[dict]:
    """Maximal fusible chains over a composed (built or unbuilt)
    PipeGraph: ``[{"ops": [op, ...], "links": [kind, ...],
    "tail_boundary": why-the-chain-ends}, ...]``, length >= 2 only."""
    edges = graph._edges()
    fanout: Dict[int, int] = {}
    fanin: Dict[int, int] = {}
    succ: Dict[int, object] = {}
    op_edges = []
    for edge in edges:
        if edge[0] == "op":
            _, a, b = edge
            op_edges.append((a, b))
            fanout[id(a)] = fanout.get(id(a), 0) + 1
            fanin[id(b)] = fanin.get(id(b), 0) + 1
        else:   # split point: the source op fans out by construction
            _, mp = edge
            src = mp.operators[-1]
            fanout[id(src)] = fanout.get(id(src), 0) + len(mp.split_children)
    links: Dict[int, tuple] = {}
    linked_in = set()
    for a, b in op_edges:
        boundary = _chain_boundary(a, b, fanout, fanin)
        # only a member is fused PAST: a tail's output is another stream
        if boundary is None and a.chain_role == "member":
            kind = ("chainable" if b.chain_role == "member"
                    and b.routing == RoutingMode.FORWARD else "whole_chain")
            links[id(a)] = (b, kind)
            linked_in.add(id(b))
    chains = []
    seen = set()
    for a, _ in op_edges:
        if id(a) in seen or id(a) in linked_in or id(a) not in links:
            continue
        ops = [a]
        kinds = []
        cur = a
        while id(cur) in links:
            nxt, kind = links[id(cur)]
            ops.append(nxt)
            kinds.append(kind)
            seen.add(id(cur))
            cur = nxt
        seen.add(id(cur))
        tail = None
        for b2 in (b for x, b in op_edges if x is cur):
            tail = _chain_boundary(cur, b2, fanout, fanin) \
                or ("chain tail is a window/reduce/stateful stage"
                    if cur.chain_role != "member" else None)
        chains.append({"ops": ops, "links": kinds, "tail_boundary": tail})
    return chains


def _batched_bytes(spec_bytes: Optional[int],
                   capacity: Optional[int]) -> Optional[int]:
    from windflow_tpu.monitoring.sweep_ledger import LANE_BYTES_PER_TUPLE
    if spec_bytes is None or not capacity:
        return None
    return (spec_bytes + LANE_BYTES_PER_TUPLE) * capacity


def plan(graph, sweep: Optional[dict] = None, top: int = 0) -> dict:
    """The concrete fusion plan: chains from :func:`fusible_chains`
    ranked by projected bytes-saved per batch (interior hop boundaries a
    fused program never materializes in HBM — write + re-read — plus
    the members' donation-miss copies), then by dispatches-saved.

    ``sweep`` — a live ``stats()["Sweep"]`` section — upgrades the
    projection to MEASURED dispatch counts and boundary bytes; without
    it, dispatches default to one per member and boundary bytes come
    from the pre-flight record specs."""
    from windflow_tpu.analysis.preflight import (_upstream_map,
                                                 _effective_caps,
                                                 propagate_specs,
                                                 record_nbytes)
    edges = graph._edges()
    upstreams = _upstream_map(edges)
    try:
        _, out_specs = propagate_specs(graph, edges=edges,
                                       upstreams=upstreams)
    except Exception:  # lint: broad-except-ok (advisor must still rank
        # by dispatch counts when a user kernel defeats abstract eval)
        out_specs = {}
    per_hop = (sweep or {}).get("per_hop") or {}
    out = []
    for chain in fusible_chains(graph):
        ops = chain["ops"]
        names = [op.name for op in ops]
        disp_now = 0.0
        bytes_saved = 0.0
        donation_bytes = 0.0
        measured = True
        for op in ops:
            h = per_hop.get(op.name) or {}
            d = h.get("dispatches_per_batch")
            if d is None:
                d = 1.0
                measured = False
            disp_now += d
            miss = (h.get("donation_miss") or {}).get("bytes_per_batch")
            if miss:
                donation_bytes += miss
        for op in ops[:-1]:     # interior boundaries only
            h = per_hop.get(op.name) or {}
            bb = h.get("fusion_fuel_bytes_per_batch")
            if bb is None:
                caps = sorted(c for c in _effective_caps(op, upstreams)
                              if c)
                bb = _batched_bytes(record_nbytes(out_specs.get(id(op))),
                                    caps[0] if caps else None)
                measured = False
            if bb:
                # the producing hop writes the boundary batch to HBM and
                # the consuming hop reads it back: both sides vanish
                # when the chain lowers into one program
                bytes_saved += 2 * bb
        out.append({
            "ops": names,
            "links": chain["links"],
            "provable_now": all(k == "chainable" for k in chain["links"]),
            "tail_boundary": chain["tail_boundary"],
            "dispatches_per_batch_now": round(disp_now, 3),
            "dispatches_saved_per_batch": round(disp_now - 1.0, 3),
            "projected_bytes_saved_per_batch": round(bytes_saved, 1),
            "donation_miss_bytes_per_batch": round(donation_bytes, 1),
            "basis": "measured" if (measured and per_hop) else "projected",
        })
    out.sort(key=lambda c: (c["projected_bytes_saved_per_batch"]
                            + c["donation_miss_bytes_per_batch"],
                            c["dispatches_saved_per_batch"]),
             reverse=True)
    if top:
        out = out[:top]
    return {"graph": graph.name, "chains": out}
