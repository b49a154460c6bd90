"""Sweep ledger: per-operator-hop dispatch & HBM-traffic attribution.

XLA's cost analysis counts several times more HBM traffic per tuple
than the declared record model, and the staged end-to-end rate sits
well below the raw kernel — this module says *which hop* pays it.
Every operator hop in the PipeGraph sweep is its own jitted dispatch
that round-trips HBM; whole-chain fusion (ROADMAP item 1) cannot be
planned, sized, or verified without per-hop accounting.

This module cashes in counters the earlier planes already maintain —
it adds **zero per-batch work of its own**:

* **dispatches per batch per hop** — the compile watcher
  (monitoring/jit_registry.py) bumps per-wrapper and per-name dispatch
  counters on every jitted call (two lock-free integer adds,
  ``@hot_path``-linted); the ledger baselines each wrapper at graph
  build and diffs at stats cadence, divided by the replicas'
  ``device_programs_launched`` batch counts.  Chained ops
  (ops/chained.py) therefore show their REAL dispatch count: one for
  the fused ``a|b`` hop where the unchained pair pays two.
* **per-hop HBM bytes** — XLA cost-analysis bytes-accessed per compiled
  op (captured at first compile) scaled by that op's dispatches, split
  into payload vs overhead against the declared record spec (the
  pre-flight spec walk, analysis/preflight.propagate_specs — the same
  shared walk the fusion advisor reuses).
* **donation misses** — compiled ops whose non-donated input buffers
  match an output buffer shape/dtype: each dispatch pays a whole-batch
  copy that ``donate_argnums`` would elide (audit captured by the
  compile watcher at first compile).
* **hop-boundary residency** — hops whose output batches stay on device
  and are immediately re-consumed by the next TPU hop: the bytes a
  fused program would never materialize in HBM (the advisor's "fusion
  fuel").

Surfaces: ``PipeGraph.stats()["Sweep"]``, the OpenMetrics exposition
(``wf_sweep_*`` families), ``dump_trace()`` metadata, the webui per-op
columns, and the postmortem bundle's ``sweep.json``
(``tools/wf_doctor.py`` renders it jax-free).  ``Config.sweep_ledger``
off leaves one ``is not None`` check at each read site — the per-batch
path is untouched either way (the dispatch counter belongs to the
compile watcher).
"""

from __future__ import annotations

from typing import Dict, Optional

#: bytes per tuple of the runtime lanes every device batch carries next
#: to the payload: int64 timestamp + bool validity mask
LANE_BYTES_PER_TUPLE = 9


def _op_wrappers(op):
    """Every :class:`~windflow_tpu.monitoring.jit_registry.WfJit` wrapper
    an operator instance (or one of its replicas) holds — directly
    (``_jit_step``, ``_extract``, a replica's ``_jit``) or in a
    per-capacity cache dict (``_jit_steps``, ``_steps``).  Wrappers are
    per instance, so this is what makes per-hop attribution graph-scoped
    where the registry aggregates per op name process-wide."""
    from windflow_tpu.monitoring.jit_registry import WfJit
    holders = [op] + list(getattr(op, "replicas", ()))
    for holder in holders:
        # list() snapshots: the monitor thread reads stats while the
        # driver may still be creating lazy per-capacity wrappers
        for v in list(vars(holder).values()):
            if isinstance(v, WfJit):
                yield v
            elif isinstance(v, dict):
                for w in list(v.values()):
                    if isinstance(w, WfJit):
                        yield w
            elif isinstance(getattr(v, "_jit", None), WfJit):
                # a fused stateless segment's chain program lives on the
                # host op's FusedStatelessExec (windflow_tpu/fusion) —
                # the fused hop's dispatches attribute here
                yield v._jit


class SweepLedger:
    """Per-graph view over the process-wide compile registry: built at
    ``PipeGraph._build`` (baseline dispatch snapshot), read at stats /
    trace / postmortem cadence — never on the per-batch path."""

    def __init__(self, graph) -> None:
        from windflow_tpu.monitoring.jit_registry import default_registry
        self._graph = graph
        # per-name registry baseline (for the non-hop infrastructure
        # programs) and per-wrapper baseline (for the hops: wrappers are
        # per operator instance, so two graphs reusing an op name never
        # cross-credit; wrappers built lazily after this start at zero)
        self._base = default_registry().dispatch_counts()
        self._wbase = {id(w): w.dispatches
                       for op in graph._operators
                       for w in _op_wrappers(op)}
        self._statics: Optional[dict] = None    # computed on first read

    # -- static graph facts (specs, capacities, residency) -------------------
    def _compute_statics(self) -> dict:
        """Record specs (shared pre-flight walk), effective batch
        capacities, and hop-boundary residency — all derivable from the
        built graph, cached after the first stats read."""
        from windflow_tpu.analysis.preflight import (_effective_caps,
                                                     _upstream_map,
                                                     propagate_specs,
                                                     record_nbytes)
        g = self._graph
        edges = g._edges()
        upstreams = _upstream_map(edges)
        try:
            in_specs, out_specs = propagate_specs(g, edges=edges,
                                                  upstreams=upstreams)
        except Exception:  # lint: broad-except-ok (the spec walk
            # abstractly evaluates arbitrary user kernels; a failure
            # degrades the payload/overhead split to "unknown", it must
            # never take a stats read down)
            in_specs, out_specs = {}, {}
        # downstream consumers per op over the plain op edges; a split
        # point fans out on the host, so its source op is never resident
        downs: Dict[int, list] = {}
        for edge in edges:
            if edge[0] == "op":
                _, a, b = edge
                downs.setdefault(id(a), []).append(b)
            else:
                _, mp = edge
                downs.setdefault(id(mp.operators[-1]), []).append(None)
        statics = {}
        for op in g._operators:
            caps = sorted(c for c in _effective_caps(op, upstreams) if c)
            cap = caps[0] if caps else getattr(op, "capacity", None)
            consumers = downs.get(id(op), [])
            resident = bool(consumers) and all(
                c is not None and c.is_tpu for c in consumers)
            statics[id(op)] = {
                "capacity": cap,
                "in_bytes_per_tuple": record_nbytes(in_specs.get(id(op))),
                "out_bytes_per_tuple": record_nbytes(out_specs.get(id(op))),
                "resident_output": resident,
            }
        return statics

    # -- the stats()["Sweep"] payload ----------------------------------------
    def section(self) -> dict:
        from windflow_tpu.ops.source import Source
        from windflow_tpu.monitoring.jit_registry import default_registry
        if self._statics is None:
            self._statics = self._compute_statics()
        reg = default_registry()
        snapshot = reg.snapshot()
        g = self._graph
        # ops sharing one name merge into ONE joint hop (their wrapper
        # sets and replica batch counts sum) — the surfaces key hops by
        # operator name, same per-name stance as the registry
        groups: Dict[str, list] = {}
        for op in g._operators:
            groups.setdefault(op.name, []).append(op)
        # whole-chain fusion (windflow_tpu/fusion): member hops are
        # marked fused_into and host hops carry the member list — the
        # "how fused hops appear" contract docs/OBSERVABILITY.md pins
        fused_member_of: Dict[str, str] = {}
        fused_hosts: Dict[str, dict] = {}
        for seg in getattr(g, "_fused_segments", ()):
            for n in seg["member_names"][:-1]:
                fused_member_of[n] = seg["name"]
            fused_hosts[seg["host_name"]] = seg
        per_hop: Dict[str, dict] = {}
        claimed = set()
        tot_bpt = 0.0
        tot_dpb = 0.0
        tot_miss = 0.0
        tot_disp = 0
        tot_attr_disp = 0
        for op in g._operators:
            key = op.name
            if key in per_hop:
                continue
            siblings = groups[key]
            wrappers = [w for sib in siblings for w in _op_wrappers(sib)]
            if not op.is_tpu and not wrappers:
                continue
            claimed.update(w.op_name for w in wrappers)
            batches = sum(r.stats.device_programs_launched
                          for sib in siblings for r in sib.replicas)
            # dispatch + byte tally from THIS graph's own wrappers
            # (per-instance counters and per-program cost tables,
            # baselined at build); donation audits are per op name
            disp = 0
            attr_disp = 0
            bytes_total = 0.0
            miss_bytes = 0.0
            miss_leaves = 0
            donated_any = False
            name_disp: Dict[str, int] = {}
            # the hop's dominant program (most dispatches): its bytes
            # are the steady-state per-dispatch cost, undiluted by
            # one-shot programs like the FFAT EOS flush
            primary_d = 0
            primary_ba = None
            for w in wrappers:
                d = w.dispatches - self._wbase.get(id(w), 0)
                if d <= 0:
                    continue
                disp += d
                name_disp[w.op_name] = name_disp.get(w.op_name, 0) + d
                cost = w.current_cost() \
                    or (snapshot.get(w.op_name) or {}).get("cost") or {}
                ba = cost.get("bytes_accessed")
                if isinstance(ba, (int, float)):
                    attr_disp += d
                    bytes_total += d * float(ba)
                    if d > primary_d:
                        primary_d = d
                        primary_ba = float(ba)
            # donation audits are per program name, weighted by every
            # dispatch that name saw in this graph
            for name, nd in name_disp.items():
                don = (snapshot.get(name) or {}).get("donation") or {}
                if don.get("donated_argnums"):
                    donated_any = True
                if don.get("candidate_leaves"):
                    miss_leaves += don["candidate_leaves"]
                    miss_bytes += nd * float(don.get("candidate_bytes", 0))
            st = self._statics.get(id(op), {})
            cap = st.get("capacity")
            hop = {
                "kind": type(op).__name__,
                "batches": batches,
                "dispatches": disp,
                "dispatches_per_batch":
                    round(disp / batches, 3) if batches else None,
                "capacity": cap,
                "resident_output": st.get("resident_output", False),
            }
            if key in fused_member_of and all(
                    sib._fused_into is not None for sib in siblings):
                # inert member of a fused segment: its execution (and
                # its dispatches/bytes) live in the fused hop below.
                # Guarded sibling-wise: hops aggregate per NAME, so an
                # unfused op sharing the name must keep its real
                # dispatch numbers unmasked (the per-wrapper attribution
                # stance — never cross-credit name collisions).
                hop["fused_into"] = fused_member_of[key]
            elif key in fused_hosts:
                seg = fused_hosts[key]
                hop["fused_program"] = seg["name"]
                hop["fused_members"] = seg["member_names"]
            if batches and attr_disp:
                bpb = bytes_total / batches
                hop["bytes_per_batch"] = round(bpb, 1)
                hop["bytes_per_tuple"] = round(bpb / cap, 2) if cap \
                    else None
                # XLA cost-table estimates, not byte counters — tagged
                # so downstream joins (roofline, tenant) name their
                # basis (monitoring/calibration.py vocabulary)
                hop["bytes_provenance"] = "modeled"
                if primary_ba is not None and cap:
                    # steady-state number: a short run's EOS flush or
                    # other one-shot programs dilute the amortized
                    # average above; this is what one more data batch
                    # would cost (the roofline comparison's domain)
                    hop["steady_bytes_per_tuple"] = \
                        round(primary_ba / cap, 2)
                if disp > attr_disp:
                    hop["unattributed_dispatches"] = disp - attr_disp
            payload = st.get("in_bytes_per_tuple")
            if payload is not None:
                model = payload + LANE_BYTES_PER_TUPLE
                hop["payload_bytes_per_tuple"] = model
                bpt = hop.get("bytes_per_tuple")
                if bpt is not None:
                    hop["overhead_bytes_per_tuple"] = round(bpt - model, 2)
                    hop["excess_vs_model"] = round(bpt / model, 2)
            if miss_leaves:
                hop["donation_miss"] = {
                    "candidate_leaves": miss_leaves,
                    "bytes_per_batch":
                        round(miss_bytes / batches, 1) if batches else None,
                    "donates_some_args": donated_any,
                }
            if st.get("resident_output") \
                    and st.get("out_bytes_per_tuple") is not None and cap:
                # what a fused chain would never materialize in HBM
                hop["fusion_fuel_bytes_per_batch"] = \
                    (st["out_bytes_per_tuple"] + LANE_BYTES_PER_TUPLE) * cap
            per_hop[key] = hop
            if hop.get("bytes_per_tuple") is not None:
                tot_bpt += hop["bytes_per_tuple"]
            if hop["dispatches_per_batch"] is not None \
                    and not isinstance(op, Source):
                tot_dpb += hop["dispatches_per_batch"]
            if miss_leaves and batches:
                tot_miss += miss_bytes / batches
            tot_disp += disp
            tot_attr_disp += attr_disp
        # infrastructure programs that dispatched but belong to no hop
        # (staging pack/unpack, emitter splits): reported so the bytes
        # accounting can reach 100% of the sweep's traffic
        non_hop = {}
        for name, e in snapshot.items():
            if name in claimed:
                continue
            d = e.get("dispatches", 0) - self._base.get(name, 0)
            if d <= 0:
                continue
            slot = {"dispatches": d}
            ba = (e.get("cost") or {}).get("bytes_accessed")
            if isinstance(ba, (int, float)):
                slot["bytes_per_dispatch"] = float(ba)
            non_hop[name] = slot
            tot_disp += d
        # fusion summary: realized dispatch savings (N member hops now
        # pay the host hop's single program) plus the projected interior
        # boundary bytes a fused chain never materializes — write + re-
        # read per boundary, the advisor's formula (analysis/fusion.plan)
        # evaluated over the segments that actually fused
        fusion_chains = []
        fusion_dsaved = 0.0
        fusion_bsaved = 0.0
        for seg in getattr(g, "_fused_segments", ()):
            n_members = len(seg["member_names"])
            host_hop = per_hop.get(seg["host_name"]) or {}
            dpb = host_hop.get("dispatches_per_batch")
            bsum = 0.0
            for mn in seg["member_names"][:-1]:
                fuel = (per_hop.get(mn) or {}) \
                    .get("fusion_fuel_bytes_per_batch")
                if fuel:
                    bsum += 2 * fuel
            entry = {
                "name": seg["name"],
                "members": seg["member_names"],
                "host": seg["host_name"],
                "donated_inputs": bool(seg.get("donate_inputs")),
                "dispatches_per_batch": dpb,
                "unfused_dispatches_per_batch": float(n_members),
                "bytes_saved_per_batch": round(bsum, 1),
            }
            if dpb is not None:
                entry["dispatches_saved_per_batch"] = \
                    round(n_members - dpb, 3)
                fusion_dsaved += n_members - dpb
            fusion_bsaved += bsum
            fusion_chains.append(entry)
        # wire plane (windflow_tpu/wire.py): THIS HOST's share of the
        # graph's staged traffic, wire vs logical — on a multi-host DCN
        # feed each process packs and stages only its local chips'
        # shard, and this is where that per-host attribution surfaces
        # (per-replica splits live in the replica stats' Bytes_H2D /
        # Bytes_H2D_logical pair)
        import jax as _jax
        wire_h2d = sum(r.stats.h2d_bytes for r in g._all_replicas)
        logical_h2d = sum(r.stats.h2d_logical_bytes
                          for r in g._all_replicas)
        wire_host = {
            "process_index": _jax.process_index(),
            "process_count": _jax.process_count(),
            "wire_bytes": wire_h2d,
            "logical_bytes": logical_h2d,
            "compression_ratio": round(logical_h2d / wire_h2d, 4)
            if wire_h2d else None,
            # real byte counters on the staged path, not a model
            "bytes_provenance": "measured",
        }
        return {
            "enabled": True,
            "per_hop": per_hop,
            "non_hop": non_hop,
            "wire": wire_host,
            "fusion": {
                "enabled": bool(fusion_chains),
                "fused_chains": [c["name"] for c in fusion_chains],
                "chains": fusion_chains,
                "dispatches_saved_per_batch": round(fusion_dsaved, 3),
                "bytes_saved_per_batch": round(fusion_bsaved, 1),
            },
            "totals": {
                "bytes_per_tuple": round(tot_bpt, 2),
                # the hop bytes are cost-table attributions (modeled);
                # the wire bytes above are real counters (measured)
                "bytes_provenance": "modeled",
                "dispatches_per_batch": round(tot_dpb, 3),
                "donation_miss_bytes_per_batch": round(tot_miss, 1),
                "dispatches": tot_disp,
                "cost_attributed_dispatch_fraction":
                    round(tot_attr_disp / tot_disp, 4) if tot_disp
                    else None,
            },
        }
