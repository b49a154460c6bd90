"""Pre-flight graph checker: abstract evaluation of a whole PipeGraph.

WindFlow rejects illegal pipeline compositions at C++ compile time through
template/concept checks; a Python/JAX graph has no compiler seam, so shape
and dtype mistakes historically surfaced only when a batch hit the device
mid-run (deep in ``ops/tpu.py`` or ``windows/ffat_tpu.py``) — and only the
FIRST one.  This module walks the *built-but-not-started* graph and reports
**every** violation it can prove, with zero device work:

* operator chains are abstractly evaluated with ``jax.eval_shape`` on the
  user kernels (DrJAX idiom: abstract evaluation type-checks the dataflow
  without touching an accelerator) — dtype/shape mismatches, non-boolean
  filter predicates, combiner contract drift, non-integer key extractors;
* window specs are checked for length/slide/lateness consistency;
* keyby routing, mesh shard-divisibility (``parallel/mesh.py`` contracts)
  and fixed-capacity merge consistency are validated structurally;
* watermark modes are folded across merge/split points
  (``graph/multipipe.py``): a branch that can never produce watermarks
  stalls every time window downstream of the merge.

Entry point: :func:`check_graph`, surfaced as ``PipeGraph.check()`` and
auto-run at ``start()`` under ``Config.preflight`` ("error" | "warn" |
"off").  Abstract record specs flow from sources: declared via
``Source_Builder.withRecordSpec(example)`` or inferred from a
``DeviceSource``'s traced generator; chains fed by undeclared sources skip
the kernel passes (structure/spec checks still run).
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from windflow_tpu.analysis.diagnostics import Diagnostic
from windflow_tpu.basic import (RoutingMode, TimePolicy, WindFlowError,
                                WinType)

#: sentinel for "record structure unknown at this point of the chain"
_UNKNOWN = None


# ---------------------------------------------------------------------------
# record specs
# ---------------------------------------------------------------------------

def _as_struct(example):
    """An example record (pytree of scalars/arrays) or a pytree of
    ``jax.ShapeDtypeStruct`` -> per-record abstract spec.  Host numpy
    only — never touches a device."""
    import jax

    def leaf(x):
        if isinstance(x, jax.ShapeDtypeStruct):
            return x
        a = np.asarray(x)
        return jax.ShapeDtypeStruct(a.shape, a.dtype)

    return jax.tree.map(leaf, example)


def _batched(spec, capacity: int):
    """Per-record spec -> batch spec (leading dim = capacity)."""
    import jax
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((capacity,) + tuple(s.shape),
                                       s.dtype), spec)


def _same_struct(a, b) -> bool:
    import jax
    return jax.tree.structure(a) == jax.tree.structure(b)


def _leaf_mismatch(want, got) -> Optional[str]:
    """First leaf whose shape/dtype drifts between two same-structure
    specs, rendered for the message; None when they agree."""
    import jax
    in_leaves, _ = jax.tree_util.tree_flatten_with_path(want)
    out_leaves = jax.tree.leaves(got)
    for (path, a), b in zip(in_leaves, out_leaves):
        if tuple(a.shape) != tuple(b.shape) or a.dtype != b.dtype:
            return (f"field {jax.tree_util.keystr(path) or '.'} is "
                    f"{tuple(a.shape)}/{a.dtype} in the records but came "
                    f"back {tuple(b.shape)}/{b.dtype}")
    return None


# ---------------------------------------------------------------------------
# graph structure helpers (shared with PipeGraph._build)
# ---------------------------------------------------------------------------

def _upstream_map(edges) -> Dict[int, Tuple[Any, list]]:
    """id(op) -> (op, [upstream ops]) over every graph edge, including
    split fan-outs (same traversal as ``PipeGraph._check_fixed_capacity_ops``
    used before it moved here)."""
    upstreams: Dict[int, Tuple[Any, list]] = {}
    for edge in edges:
        if edge[0] == "op":
            _, a, b = edge
            upstreams.setdefault(id(b), (b, []))[1].append(a)
        else:  # split: each child's head is fed by the split source
            _, mp = edge
            src_op = mp.operators[-1]
            for child in mp.split_children:
                if child.operators:
                    head = child.operators[0]
                    upstreams.setdefault(id(head), (head, []))[1].append(
                        src_op)
    return upstreams


def _effective_caps(op, upstreams, seen=None) -> set:
    """Batch capacities a device batch can arrive with at ``op``: host
    operators stamp their ``output_batch_size``; TPU operators pass their
    input capacity through."""
    seen = seen or set()
    if id(op) in seen:
        return set()
    seen.add(id(op))
    if not op.is_tpu:
        return {op.output_batch_size}
    caps = set()
    for up in upstreams.get(id(op), (None, []))[1]:
        caps |= _effective_caps(up, upstreams, seen)
    return caps


def capacity_conflicts(graph, upstreams=None) -> List[Tuple[Any, str, set]]:
    """Fixed-capacity device operators fed by upstream paths delivering
    unequal batch capacities: ``[(op, label, caps), ...]``.  Shared by the
    pre-flight pass (code WF403) and ``PipeGraph._build``'s
    ``preflight="off"`` backstop; ``upstreams`` lets check_graph reuse
    the map it already built."""
    if upstreams is None:
        upstreams = _upstream_map(graph._edges())
    out = []
    for _, (op, ups) in upstreams.items():
        label = op.fixed_capacity_label
        if label is not None:
            caps = set()
            for up in ups:
                caps |= _effective_caps(up, upstreams)
            if len(caps) > 1:
                out.append((op, label, caps))
    return out


def _all_ops(graph) -> list:
    seen, out = set(), []
    for mp in graph._all_pipes():
        for op in mp.operators:
            if id(op) not in seen:
                seen.add(id(op))
                out.append(op)
    return out


# ---------------------------------------------------------------------------
# the passes
# ---------------------------------------------------------------------------

def check_graph(graph) -> List[Diagnostic]:
    """Run every pre-flight pass over an unstarted PipeGraph and return
    the full list of diagnostics (errors AND warnings — never just the
    first).  Performs no device work: the kernel pass is pure
    ``jax.eval_shape`` abstract evaluation."""
    diags: List[Diagnostic] = []
    try:
        edges = graph._edges()
    except WindFlowError as e:
        diags.append(Diagnostic("WF304", str(e)))
        return diags
    except IndexError:
        # merged MultiPipe with no operators yet: _edges() indexes
        # merged.operators[0] — report it instead of crashing the
        # diagnostic API that exists to explain malformed compositions
        diags.append(Diagnostic(
            "WF304",
            "a merged MultiPipe has no operators — add an operator (and "
            "a sink) to the merge result before running"))
        return diags
    ops = _all_ops(graph)
    upstreams = _upstream_map(edges)

    _structural_pass(graph, ops, edges, diags)
    _window_spec_pass(ops, diags)
    _count_order_pass(ops, upstreams, diags)
    _capacity_pass(graph, upstreams, diags)
    _mesh_pass(graph, ops, edges, diags)
    _compaction_pass(graph, ops, diags)
    _watermark_pass(graph, ops, upstreams, diags)
    _durability_pass(graph, ops, diags)
    _kernel_pass(graph, ops, edges, upstreams, diags)
    _wire_pass(graph, ops, edges, upstreams, diags)
    _pallas_pass(graph, ops, diags)
    _megastep_pass(graph, ops, edges, upstreams, diags)
    _tracecheck_pass(graph, diags)
    _ir_audit_pass(graph, diags)
    return diags


def _pallas_pass(graph, ops, diags) -> None:
    """WF607: forced Pallas kernels (``WF_TPU_PALLAS=1``) name their
    downgrades instead of taking them silently — the WF606 contract
    applied to the kernel plane.  Two cases:

    * the runtime backend has no kernel lowering (neither TPU Mosaic
      nor the CPU interpreter): the whole plane downgrades to lax;
    * a MESH graph: the sharded program factories (parallel/mesh.py)
      compose their steps inside shard_map, which keeps the lax bodies
      this round — forcing the kernels there does nothing;
    * an FFAT window with a GENERIC traced combiner (no declared
      sum/max/min monoid): the MXU pane-combine path only exists for
      declared monoids, so the sliding fold keeps the lax body (the
      grouping kernel still applies).

    ``auto`` mode picks per backend silently and never warns."""
    from windflow_tpu.kernels import pallas_forced, resolve_pallas
    if not pallas_forced(graph.config):
        return
    if graph.config.mesh is not None:
        diags.append(Diagnostic(
            "WF607",
            "WF_TPU_PALLAS=1 forced on a mesh graph: sharded programs "
            "(shard_map step factories) keep the lax bodies this "
            "round, so no kernels build",
            hint="single-chip graphs take the kernels; kernels inside "
                 "shard_map are not built"))
        return
    mode = resolve_pallas(graph.config)
    if mode is None:
        import jax as _jax
        diags.append(Diagnostic(
            "WF607",
            "WF_TPU_PALLAS=1 forced but backend "
            f"'{_jax.default_backend()}' has no kernel lowering "
            "(TPU compiles Mosaic, CPU runs interpret=True): the lax "
            "path runs instead",
            hint="unset WF_TPU_PALLAS (auto picks per backend) or run "
                 "on a TPU/CPU backend"))
        return
    from windflow_tpu.windows.ffat_tpu import FfatWindowsTPU
    for op in ops:
        if isinstance(op, FfatWindowsTPU) and op.monoid is None:
            diags.append(Diagnostic(
                "WF607",
                f"window '{op.name}' has a generic traced combiner: "
                "the MXU pane-combine kernel only exists for declared "
                "sum/max/min monoids, so its sliding fold keeps the "
                "lax body (the grouping kernel still applies)",
                node=op.name,
                hint="declare the combiner with withMonoidCombiner/"
                     "withSumCombiner if it is a leafwise monoid"))


def _megastep_pass(graph, ops, edges, upstreams, diags) -> None:
    """WF608: a FORCED megastep width (``WF_TPU_MEGASTEP=K`` /
    ``Config.megastep_sweeps > 1``) names its downgrades instead of
    taking them silently — the WF606/WF607 contract applied to the
    megastep plane.  The fold only exists for a single-destination
    host→device staging edge whose post-fusion tail steps entirely on
    device (windflow_tpu/megastep.py ``tail_kind`` — the same
    classifier ``attach_plane`` consults at build time, so preflight
    and runtime can never disagree about a reason).  Named cases:

    * a MESH graph (aligned per-shard ingest, collectives per batch);
    * a multi-destination staging edge (keyed/round-robin fan-out);
    * a host operator, host-interning stateful, compacted key space,
      or parallel tail — ``tail_kind``'s reason verbatim;
    * a spec-less source: packed signatures drift batch to batch, so
      a K-group never assembles (declare withRecordSpec).

    ``auto`` mode picks per backend silently and never warns; every
    case above runs correctly at the per-batch (K=1) cadence."""
    from windflow_tpu.io.device_source import DeviceSource
    from windflow_tpu.megastep import megastep_forced, tail_kind
    from windflow_tpu.ops.sink import Sink

    k = megastep_forced(graph.config)
    if not k:
        return
    if graph.config.mesh is not None:
        diags.append(Diagnostic(
            "WF608",
            f"WF_TPU_MEGASTEP={k} forced on a mesh graph: staging is "
            "per-shard aligned ingest with collectives every batch, so "
            "every edge keeps the per-batch (K=1) cadence",
            hint="single-chip graphs take the fold; scanning sharded "
                 "programs is not built"))
        return

    down: Dict[int, list] = {}
    roots = []
    for edge in edges:
        if edge[0] == "op":
            _, a, b = edge
            down.setdefault(id(a), []).append(b)
        else:
            _, mp = edge
            src = mp.operators[-1]
            for child in mp.split_children:
                if child.operators:
                    down.setdefault(id(src), []).append(
                        child.operators[0])
    for op in ops:
        ups = upstreams.get(id(op))
        if (ups is None or not ups[1]) and down.get(id(op)):
            roots.append(op)

    def warn(src, reason: str, node=None) -> None:
        diags.append(Diagnostic(
            "WF608",
            f"WF_TPU_MEGASTEP={k} forced but the staging edge from "
            f"'{src.name}' keeps per-batch dispatch: {reason}",
            node=node,
            hint="the downgrade is correctness-neutral (the per-batch "
                 "path is the reference semantics); unset "
                 "WF_TPU_MEGASTEP or restructure the edge to a "
                 "single-destination device tail"))

    for src in roots:
        if getattr(src, "record_spec", None) is None and not (
                isinstance(src, DeviceSource)
                and src.batch_fn is not None):
            warn(src, "the source declares/infers no record spec, so "
                      "packed batch signatures can drift and a K-group "
                      "never assembles (declare withRecordSpec)",
                 node=src.name)
            continue
        tail = src
        while True:
            dests = down.get(id(tail), [])
            if len(dests) != 1:
                warn(src, "multi-destination staging edge "
                          "(keyed/round-robin fan-out ships per batch)",
                     node=tail.name)
                tail = None
                break
            tail = dests[0]
            if tail.chain_role != "member":
                break
        if tail is None or isinstance(tail, Sink):
            # an all-stateless run ending at the sink has no stateful
            # step to carry — tail_kind's fused-segment reason applies,
            # but only once the chain actually fused; stay quiet here
            continue
        if getattr(tail, "parallelism", 1) != 1 \
                and not isinstance(tail, _ffat_type()):
            warn(src, "parallel tail (per-replica state shards the "
                      "scan carry)", node=tail.name)
            continue
        if _will_compact(graph.config, tail):
            # the compactor only attaches at build time (parallel/
            # compaction.attach_compaction), so tail_kind cannot see it
            # on an unstarted graph — predict it from the same criteria
            warn(src, "compacted key space (host admission runs per "
                      "batch; Config.key_compaction=False folds this "
                      "edge)", node=tail.name)
            continue
        kind, reason = tail_kind(tail)
        if kind is None:
            warn(src, reason, node=tail.name)


def _ffat_type():
    from windflow_tpu.windows.ffat_tpu import FfatWindowsTPU
    return FfatWindowsTPU


def _will_compact(config, op) -> bool:
    """Predict whether ``attach_compaction`` will hang a KeyCompactor on
    ``op`` at build time — the single-chip criteria of
    ``parallel/compaction.attach_compaction`` restated over the
    unstarted graph (mesh graphs never reach here: the megastep pass
    returns on them first)."""
    if not getattr(config, "key_compaction", True):
        return False
    from windflow_tpu.ops.tpu import ReduceTPU
    if isinstance(op, ReduceTPU):
        return op.key_extractor is not None and op.monoid is not None
    if isinstance(op, _ffat_type()):
        return op.key_extractor is not None and op.max_keys is None
    return False


def _wire_pass(graph, ops, edges, upstreams, diags) -> None:
    """WF606: wire compression (windflow_tpu/wire.py) engages only on
    staging edges whose record spec is declared/inferred — codec choice
    needs the lane semantics.  With ``Config.wire_compression`` on, a
    spec-less host→TPU edge gets a NAMED warning and the documented
    raw-passthrough downgrade instead of a silent one.  Mesh graphs are
    exempt: their staging is per-shard assembly, never the packed wire
    path."""
    from windflow_tpu.wire import wire_enabled
    if not wire_enabled(graph.config) or graph.config.mesh is not None:
        return
    try:
        in_specs, _ = propagate_specs(graph, ops=ops, edges=edges,
                                      upstreams=upstreams)
    except Exception:  # noqa: BLE001 - lint: broad-except-ok (abstract
        # eval of arbitrary user kernels; an internal failure must not
        # add spurious WF606s on top of the kernel pass's real findings)
        return
    seen = set()

    def specless_source_upstream(op, visited) -> bool:
        """True when some SOURCE feeding ``op`` declares/infers no
        record spec — the WF606 case.  A spec that is merely ambiguous
        (merge structure drift) is WF106's finding, not a new one."""
        if id(op) in visited:
            return False
        visited.add(id(op))
        ups = upstreams.get(id(op))
        if ups is None or not ups[1]:   # a root: source-like
            return source_spec(op) is _UNKNOWN
        return any(specless_source_upstream(u, visited) for u in ups[1])

    def source_spec(op):
        if getattr(op, "record_spec", None) is not None:
            return object()     # declared (well-formedness is WF101's)
        from windflow_tpu.io.device_source import DeviceSource
        if isinstance(op, DeviceSource) and op.batch_fn is not None:
            return object()     # inferred from batch_fn
        return _UNKNOWN

    def note(a, b) -> None:
        spec = in_specs.get(id(b))
        if spec is not None and spec is not _UNKNOWN:
            return
        if not specless_source_upstream(b, set()):
            return
        if (id(a), id(b)) in seen:
            return
        seen.add((id(a), id(b)))
        diags.append(Diagnostic(
            "WF606",
            f"staging edge '{a.name}' → '{b.name}' has no "
            "declared/inferred record spec: wire compression "
            "(Config.wire_compression) downgrades to raw passthrough "
            "on this edge",
            node=b.name,
            hint="declare the stream's record shape with "
                 "Source_Builder.withRecordSpec(example); DeviceSource "
                 "infers its spec from batch_fn"))

    for edge in edges:
        if edge[0] == "op":
            _, a, b = edge
            if b.is_tpu and not a.is_tpu:
                note(a, b)
        else:
            _, mp = edge
            src = mp.operators[-1]
            for child in mp.split_children:
                if child.operators and child.operators[0].is_tpu \
                        and not src.is_tpu:
                    note(src, child.operators[0])


def _tracecheck_pass(graph, diags) -> None:
    """wfverify (analysis/tracecheck.py): object-level trace-safety /
    recompile / donation / determinism verification of the live kernel
    objects.  Guarded: a verifier bug must degrade to 'unchecked', never
    block a run the runtime itself would have accepted."""
    try:
        from windflow_tpu.analysis.tracecheck import verify_graph
        report = verify_graph(graph)
        graph._tracecheck_report = report
        diags.extend(report.diagnostics)
    except Exception as e:  # noqa: BLE001 - lint: broad-except-ok (the
        # verifier inspects arbitrary user sources; any internal failure
        # degrades to a note instead of masking the preflight result)
        diags.append(Diagnostic(
            "WF800", f"wfverify pass failed internally and was skipped "
                     f"— {type(e).__name__}: {e}"[:300],
            severity="warning"))


def _ir_audit_pass(graph, diags) -> None:
    """wfir (analysis/ir_audit.py): WF9xx audit of the lowered StableHLO
    of every program — captured lowerings from the compile watcher's
    store plus a dry lower of the user kernels over the record specs
    when the graph has not compiled yet.  Guarded like wfverify: an
    auditor bug degrades to WF900 'unchecked', never blocks a run."""
    try:
        from windflow_tpu.analysis import ir_audit
        if not ir_audit.enabled(getattr(graph, "config", None)):
            return
        report = ir_audit.audit_graph(graph)
        graph._ir_audit_report = report
        diags.extend(report.diagnostics)
    except Exception as e:  # noqa: BLE001 - lint: broad-except-ok (the
        # auditor parses backend-emitted IR text; any internal failure
        # degrades to a note instead of masking the preflight result)
        diags.append(Diagnostic(
            "WF900", f"ir-audit pass failed internally and was skipped "
                     f"— {type(e).__name__}: {e}"[:300],
            severity="warning"))


def _durability_pass(graph, ops, diags) -> None:
    """WF6xx: with checkpointing enabled (Config.durability names a
    directory), warn about graph elements that undermine the restore
    contract — sources whose replay is not deterministic (WF601: a
    generator restarts from scratch; an INGRESS device source re-stamps
    wall-clock time) and operators whose cross-batch state the plane
    cannot snapshot yet (WF603: host window engines, persistent-DB
    suites).  docs/DURABILITY.md spells out the contract each warning
    points at."""
    if not getattr(graph.config, "durability", ""):
        return
    from windflow_tpu.io.device_source import DeviceSource
    from windflow_tpu.kafka.kafka_source import KafkaSource
    from windflow_tpu.ops.source import Source
    on_mesh = graph.config.mesh is not None
    # on a mesh the same gaps also block rescale-on-restore: state the
    # checkpoint never captured (or a replay that diverges) cannot be
    # re-bucketed onto a different shard shape either
    mesh_tail = (" — on a mesh this also makes the operator "
                 "rescale-incompatible (restore on N±1 shards replays "
                 "through the checkpoint)") if on_mesh else ""
    for op in ops:
        if isinstance(op, Source):
            if isinstance(op, KafkaSource):
                continue    # offset-addressed: the replayable case
            if isinstance(op, DeviceSource) and op.ts_fn is not None:
                continue    # EVENT-time device source: pure fn of the
                #             batch index, replays bit-identically
            diags.append(Diagnostic(
                "WF601",
                f"source '{op.name}' cannot replay deterministically "
                "after a restore (no offsets to seek, "
                "wall-clock/ingress timestamps re-stamp on replay) — "
                "restored runs will diverge from the checkpointed "
                "stream position" + mesh_tail,
                node=op.name,
                hint="feed checkpointed graphs from a Kafka source or "
                     "an EVENT-time DeviceSource (withTimestampFn / "
                     "withTimestampBounds)"))
        elif op.checkpoint_opaque:
            diags.append(Diagnostic(
                "WF603",
                f"operator '{op.name}' ({type(op).__name__}) holds "
                "cross-batch state the checkpoint cannot capture — a "
                "restore silently resets it" + mesh_tail,
                node=op.name,
                hint="use the TPU window/stateful operators "
                     "(FfatWindowsTPU, StatefulMapTPU, Reduce) for "
                     "checkpointed graphs"))
        elif on_mesh and op.key_extractor is not None \
                and _checkpoints_unrebucketable_state(op):
            # rescale-on-restore re-buckets keyed state through the
            # known state kinds (durability/rebucket.py: dense key
            # spaces, compaction remaps, shared slot tables); a keyed
            # operator checkpointing state of an unknown kind offers no
            # re-bucketing rule, so a shape-changing restore will
            # refuse with WF605
            diags.append(Diagnostic(
                "WF604",
                f"keyed operator '{op.name}' ({type(op).__name__}) on "
                "a mesh checkpoints state with no re-bucketing rule "
                "(no declared key space or compaction remap) — a "
                "restore onto a different mesh shape will refuse with "
                "WF605",
                node=op.name,
                hint="use the built-in keyed operators (FfatWindowsTPU, "
                     "StatefulMapTPU, ReduceTPU, Reduce) for rescalable "
                     "checkpoints, or keep the mesh shape fixed"))


def _checkpoints_unrebucketable_state(op) -> bool:
    """True when the operator overrides ``snapshot_state`` (it
    checkpoints something) but states no ``snapshot_kind`` that
    ``durability/rebucket.py`` can re-bucket."""
    from windflow_tpu.durability.rebucket import _has_rule
    from windflow_tpu.ops.base import Operator
    cls = type(op)
    if cls.snapshot_state is Operator.snapshot_state:
        return False    # stateless: nothing to re-bucket
    if not _has_rule(op, cls.snapshot_kind):
        return True
    # identity on the IMPLEMENTATION, not the class: a kind is stated
    # for the ``snapshot_state`` it was written beside (or above: the
    # sessions and the joins share one).  A subclass that overrides
    # snapshot_state BELOW that statement checkpoints a kind the
    # re-bucketer has never seen, however familiar its base class is
    stated = next(c for c in cls.__mro__ if "snapshot_kind" in vars(c))
    written = next(c for c in cls.__mro__ if "snapshot_state" in vars(c))
    return not issubclass(stated, written)


def manifest_conflicts(graph, manifest,
                       allow_rescale: bool = False) -> List[Diagnostic]:
    """WF602: named diff between a composed (possibly unbuilt) graph and
    a checkpoint manifest's topology signature — the gate
    ``PipeGraph.restore()`` runs before touching any state.  Empty list
    means the restore may proceed.

    ``allow_rescale`` (the ``manifest_rescale_plan`` path) exempts the
    two supported shape changes from WF602: a parallelism difference on
    a KEYED non-terminal, non-source operator (restore on N±1 replica
    shards) and the mesh shape recorded in the manifest (restore on
    N±1 chips) — both re-bucket state through
    ``durability/rebucket.py`` instead of refusing."""
    from windflow_tpu.durability.checkpoint import topology_signature
    from windflow_tpu.ops.source import Source
    diags: List[Diagnostic] = []
    want = manifest.get("topology") or []
    ops = graph._topo_operators()
    have = topology_signature(ops)
    if len(want) != len(have):
        diags.append(Diagnostic(
            "WF602",
            f"checkpoint has {len(want)} operator(s), graph has "
            f"{len(have)} — "
            f"checkpoint: {[w['name'] for w in want]}, "
            f"graph: {[h['name'] for h in have]}"))
        return diags
    for i, (w, h) in enumerate(zip(want, have)):
        for field in ("name", "type", "parallelism", "routing",
                      "is_tpu", "record_spec"):
            if w.get(field) == h.get(field):
                continue
            op = ops[i]
            if allow_rescale and field == "parallelism" \
                    and op.key_extractor is not None \
                    and not op.is_terminal \
                    and not isinstance(op, Source):
                continue    # keyed replica rescale: re-bucketable
            hint = ("restore needs the same composition that wrote "
                    "the checkpoint (names, types, parallelism, "
                    "record specs)")
            if field == "parallelism":
                hint += ("; only keyed non-terminal operators may "
                         "change parallelism on a rescale restore")
            diags.append(Diagnostic(
                "WF602",
                f"operator #{i} {field} differs: checkpoint has "
                f"{w.get(field)!r} ('{w.get('name')}'), graph has "
                f"{h.get(field)!r} ('{h.get('name')}')",
                node=h.get("name"), hint=hint))
    return diags


def manifest_rescale_plan(graph, manifest):
    """Restore-time validation with rescale awareness: returns
    ``(diagnostics, rescaled)``.  Blocking diagnostics are WF602
    (genuine topology mismatch) and WF605 (a shape change the state
    cannot re-bucket: an operator of unknown state kind — the static
    half; dynamic refusals like disagreeing TB ring clocks raise
    :class:`~windflow_tpu.durability.rebucket.RescaleError` when the
    blobs are applied).  ``rescaled`` is True when any supported shape
    change (keyed parallelism or mesh shape) is in effect."""
    from windflow_tpu.durability.rebucket import mesh_shape
    diags = manifest_conflicts(graph, manifest, allow_rescale=True)
    want = manifest.get("topology") or []
    ops = graph._topo_operators()
    rescaled = False
    if len(want) == len(ops):
        for i, (w, op) in enumerate(zip(want, ops)):
            if w.get("parallelism") == op.parallelism:
                continue
            rescaled = True
            if _checkpoints_unrebucketable_state(op):
                diags.append(Diagnostic(
                    "WF605",
                    f"operator '{op.name}' ({type(op).__name__}) "
                    f"changes parallelism "
                    f"{w.get('parallelism')} → {op.parallelism} but "
                    "checkpoints state with no re-bucketing rule",
                    node=op.name,
                    hint="restore on the checkpointed shard shape, or "
                         "use the built-in keyed operators"))
    old_mesh = manifest.get("mesh")
    new_mesh = mesh_shape(graph.config.mesh)
    if old_mesh != new_mesh:
        rescaled = True
        for op in ops:
            if op.key_extractor is not None \
                    and _checkpoints_unrebucketable_state(op):
                diags.append(Diagnostic(
                    "WF605",
                    f"mesh shape changes {old_mesh} → {new_mesh} but "
                    f"keyed operator '{op.name}' "
                    f"({type(op).__name__}) checkpoints state with no "
                    "re-bucketing rule",
                    node=op.name,
                    hint="restore on the checkpointed mesh shape, or "
                         "use the built-in keyed operators"))
    return diags, rescaled


def _structural_pass(graph, ops, edges, diags) -> None:
    has_downstream = set()
    for edge in edges:
        if edge[0] == "op":
            _, a, b = edge
            has_downstream.add(id(a))
            if a.is_terminal:
                diags.append(Diagnostic(
                    "WF301",
                    f"operator '{b.name}' is composed downstream of sink "
                    f"'{a.name}' — a sink terminates its pipeline and "
                    "forwards nothing",
                    node=b.name,
                    hint="route the data before the sink (split the pipe) "
                         "or drop the trailing operators"))
        else:
            _, mp = edge
            has_downstream.add(id(mp.operators[-1]))
    for op in ops:
        if not op.is_terminal and id(op) not in has_downstream:
            diags.append(Diagnostic(
                "WF302",
                f"operator '{op.name}' has no downstream consumer — "
                "every MultiPipe must end in a Sink",
                node=op.name, hint="append add_sink(...) to the pipeline"))
        if op.routing == RoutingMode.KEYBY and op.key_extractor is None:
            diags.append(Diagnostic(
                "WF303",
                f"operator '{op.name}' uses KEYBY routing but declares no "
                "key extractor",
                node=op.name, hint="pass withKeyBy(fn) on the builder"))


def _count_order_pass(ops, upstreams, diags) -> None:
    """WF609: a count window that counts in ARRIVAL order
    (``Operator.count_order``) and is fed, through whatever operators,
    by a device operator whose rows follow the data
    (``Operator.rows_follow_data``: the interval join, the session
    window).  Such an operator hands a step's closed rows over compacted
    in the order of its own sort, by key, and holds some back a step:
    the count window's windows are then over that order, not over event
    time, and no counter says so.  ``withEventTimeOrder`` is the cure;
    a count window built with it is named in the producer's favour by
    nothing (its ``CB_order`` stat says ``event_time``)."""
    def producer(op, seen):
        for up in upstreams.get(id(op), (None, []))[1]:
            if id(up) in seen:
                continue
            seen.add(id(up))
            if up.rows_follow_data:
                return up
            if up.count_order == "event_time":
                continue        # hands its rows on in time order by key
            found = producer(up, seen)
            if found is not None:
                return found
        return None

    for op in ops:
        if op.count_order != "arrival":
            continue
        up = producer(op, set())
        if up is not None:
            diags.append(Diagnostic(
                "WF609",
                f"count window '{op.name}' counts a key's rows in the "
                f"order they ARRIVE, and '{up.name}' "
                f"({type(up).__name__}) hands a step's rows over in the "
                "order of its own sort, by key, some a step later: its "
                "windows are over that order, not over event time",
                node=op.name,
                hint="build the window with withEventTimeOrder(tie) "
                     "(rows wait for the watermark and are counted by "
                     "event time), or use a time window, which places "
                     "a row by its timestamp"))


def _window_spec_pass(ops, diags) -> None:
    from windflow_tpu.windows.engine import WindowSpec
    for op in ops:
        spec = getattr(op, "spec", None)
        if not isinstance(spec, WindowSpec):
            continue
        if spec.win_len <= 0 or spec.slide <= 0:
            diags.append(Diagnostic(
                "WF201",
                f"operator '{op.name}': window length {spec.win_len} / "
                f"slide {spec.slide} must both be positive",
                node=op.name))
            continue   # the remaining spec arithmetic assumes positives
        if spec.slide > spec.win_len:
            diags.append(Diagnostic(
                "WF202",
                f"operator '{op.name}': slide {spec.slide} exceeds window "
                f"length {spec.win_len} — tuples landing in the "
                f"{spec.slide - spec.win_len}-wide gaps belong to no "
                "window (hopping-with-gaps is supported, but a swapped "
                "(length, slide) pair silently drops data)",
                node=op.name,
                hint="use slide <= length unless the gaps are intended"))
        if spec.lateness < 0:
            diags.append(Diagnostic(
                "WF204",
                f"operator '{op.name}': lateness {spec.lateness} is "
                "negative", node=op.name))
        elif spec.lateness > 0 and spec.win_type == WinType.CB:
            diags.append(Diagnostic(
                "WF203",
                f"operator '{op.name}': lateness "
                f"{spec.lateness} declared on a count-based window — "
                "lateness gates time-based windows only and is ignored "
                "here", node=op.name,
                hint="drop withLateness or switch to withTBWindows"))


def _capacity_pass(graph, upstreams, diags) -> None:
    for op, label, caps in capacity_conflicts(graph, upstreams):
        diags.append(Diagnostic(
            "WF403",
            f"'{op.name}' ({label}) compiles for one fixed batch capacity "
            f"but its upstream paths deliver {sorted(caps)}; give the "
            "merged branches equal withOutputBatchSize",
            node=op.name))


def _mesh_pass(graph, ops, edges, diags) -> None:
    mesh = graph.config.mesh
    if mesh is None:
        return
    total = int(math.prod(mesh.devices.shape))
    extents = dict(zip(mesh.axis_names, mesh.devices.shape))
    key_extent = int(extents.get("key", 1))
    # host -> TPU staging edges: the staged batch lays out data-sharded
    # over the whole mesh (DeviceStageEmitter contract)
    for edge in edges:
        if edge[0] != "op":
            continue
        _, a, b = edge
        if b.is_tpu and not a.is_tpu and a.output_batch_size > 0 \
                and a.output_batch_size % total:
            diags.append(Diagnostic(
                "WF401",
                f"staging edge '{a.name}' -> '{b.name}': output batch "
                f"size {a.output_batch_size} not divisible by the mesh's "
                f"{total} devices",
                node=b.name,
                hint=f"pick a withOutputBatchSize that is a multiple of "
                     f"{total}"))
    # key-sharded state spaces (parallel/mesh.py raises the same at
    # compile time; reported here for the whole graph at once)
    from windflow_tpu.ops.tpu_stateful import _StatefulTPUBase
    from windflow_tpu.windows.ffat_tpu import FfatWindowsTPU
    for op in ops:
        if isinstance(op, FfatWindowsTPU) and op.max_keys is None:
            # compacted key space (withCompactedKeys): the remap table
            # is single-chip device state — there is no per-shard slot
            # ownership to shard the pane rings by (the graph build
            # raises the same; reported here before any build work)
            diags.append(Diagnostic(
                "WF402",
                f"operator '{op.name}': compacted key space "
                "(withCompactedKeys) is single-chip; mesh execution "
                "needs a declared dense key space",
                node=op.name,
                hint=f"declare withMaxKeys (a multiple of the key axis "
                     f"{key_extent})"))
        elif isinstance(op, FfatWindowsTPU) and op.max_keys % key_extent:
            diags.append(Diagnostic(
                "WF402",
                f"operator '{op.name}': max_keys {op.max_keys} not "
                f"divisible by key axis {key_extent}",
                node=op.name))
        elif isinstance(op, _StatefulTPUBase) \
                and op.num_key_slots % key_extent:
            diags.append(Diagnostic(
                "WF402",
                f"operator '{op.name}': num_key_slots {op.num_key_slots} "
                f"not divisible by key axis {key_extent}",
                node=op.name))


_MONOID_PRIMS = {"add": "sum", "add_any": "sum", "max": "max", "min": "min"}


def _monoid_comb_mismatches(comb, key_fn, monoid, spec) -> list:
    """Leaves where the user combiner PROVABLY diverges from the declared
    monoid (WF405), found structurally on the comb's jaxpr — abstract
    tracing only, no device work.  Two classes, both zero-false-positive:
    an output leaf passed through from ONE input unchanged (legal only
    for the segment-constant key leaf under an idempotent max/min —
    the blessed ``{"key": a["key"], ...}`` idiom; under "sum" the dense
    scatter ADDS the equal keys), and a leaf combined by a recognized
    monoid primitive of the WRONG kind.  Anything else is inconclusive
    and stays silent — equivalence in general is the user's contract."""
    import jax
    closed = jax.make_jaxpr(comb)(spec, spec)
    jaxpr = closed.jaxpr
    leaves, _ = jax.tree_util.tree_flatten_with_path(spec)
    n = len(leaves)
    if len(jaxpr.invars) != 2 * n or len(jaxpr.outvars) != n:
        return []
    pos = {id(v): i for i, v in enumerate(jaxpr.invars)}
    key_leaf = None
    if key_fn is not None:
        kj = jax.make_jaxpr(key_fn)(spec).jaxpr
        if len(kj.outvars) == 1:
            kpos = {id(v): i for i, v in enumerate(kj.invars)}
            key_leaf = kpos.get(id(kj.outvars[0]))
    made_by = {}
    for eq in jaxpr.eqns:
        for ov in eq.outvars:
            made_by[id(ov)] = eq
    out = []
    for i, (path, _) in enumerate(leaves):
        name = jax.tree_util.keystr(path) or "."
        ov = jaxpr.outvars[i]
        j = pos.get(id(ov))
        if j is not None:
            # passthrough is legal only at the segment-constant key
            # LEAF ITSELF (output i IS the key leaf, copied from the
            # same leaf of either input) under an idempotent kind — a
            # key copied into a VALUE leaf diverges just the same
            if monoid == "sum" or key_leaf is None \
                    or i != key_leaf or j % n != i:
                out.append((name, f"returns input {'ab'[j // n]}'s leaf "
                                  "unchanged"))
            continue
        eq = made_by.get(id(ov))
        if eq is None:
            continue
        kind = _MONOID_PRIMS.get(eq.primitive.name)
        if kind is None or kind == monoid:
            continue
        operands = {pos.get(id(v)) for v in eq.invars}
        if operands == {i, n + i}:
            out.append((name, f"computes leafwise '{kind}'"))
    return out


def _compaction_pass(graph, ops, diags) -> None:
    """Key-compaction advice (parallel/compaction.py, WF404): a keyed
    reduce that DECLARED its key space bounded (``withMaxKeys``) but no
    monoid still runs the sorted segmented path — the dense
    scatter-combine table (and the compacted remap riding it) needs the
    declared-monoid contract.  Declared dense beats compaction: the
    user is one ``withMonoidCombiner`` away from the fast path, so say
    so instead of silently sorting.

    Also WF405: on every specialized stage the declared kind REPLACES
    the combiner (docs/API.md "declared-monoid contract"), so a
    combiner that provably diverges from it leafwise silently changes
    results exactly where the declaration kicks in — newly urgent now
    that key compaction routes UNDECLARED key spaces onto the monoid
    path by default."""
    from windflow_tpu.ops.tpu import ReduceTPU
    in_specs = None
    for op in ops:
        if isinstance(op, ReduceTPU) and op.monoid in _MONOID_PRIMS.values():
            if in_specs is None:
                in_specs = propagate_specs(graph, ops=ops)[0]
            spec = in_specs.get(id(op))
            if spec is None:
                continue
            try:
                bad = _monoid_comb_mismatches(
                    op.comb, op.key_extractor, op.monoid, spec)
            except Exception:  # noqa: BLE001 - lint: broad-except-ok (the
                # probe must never block a run the runtime would accept;
                # exotic-but-correct combiners simply go unchecked)
                bad = []
            for leaf, why in bad:
                diags.append(Diagnostic(
                    "WF405",
                    f"operator '{op.name}': declared "
                    f"withMonoidCombiner(\"{op.monoid}\") but the "
                    f"combiner {why} at record leaf {leaf} — the dense/"
                    "compacted/mesh stages compute the DECLARED "
                    f"'{op.monoid}' there instead, silently diverging "
                    "from the sorted path",
                    node=op.name,
                    hint="make the combiner leafwise "
                         f"'{op.monoid}' on every field (a key leaf may "
                         "pass through under idempotent max/min), or "
                         "drop the declaration to keep the sorted "
                         "path's semantics"))
    for op in ops:
        # mesh reduces are exempt: the sharded step's non-monoid variant
        # runs the dense per-chip partial + gather fold, never the
        # single-chip sorted path this warning prices
        if isinstance(op, ReduceTPU) and op.key_extractor is not None \
                and op.max_keys is not None and op.monoid is None \
                and op.mesh is None:
            diags.append(Diagnostic(
                "WF404",
                f"operator '{op.name}': withMaxKeys({op.max_keys}) "
                "declares a bounded key space but no monoid combiner — "
                "the reduce takes the sorted arbitrary-key path, an "
                "argsort and a whole-record scan where the dense "
                "table is one scatter-combine pass",
                node=op.name,
                hint="declare withMonoidCombiner/withSumCombiner for "
                     "the dense fast path; an undeclared key space "
                     "with a monoid still compacts (Config."
                     "key_compaction)"))


def _source_wm_mode(op, time_policy, diags) -> str:
    """Classify how a source advances watermarks: "ingress" (wall clock),
    "event" (data timestamps) or "none" (cannot advance — the stalling
    mode the merge pass hunts).  Unknown Source subclasses (Kafka, user
    sources with custom replicas) are assumed to manage time themselves."""
    from windflow_tpu.io.device_source import DeviceSource
    from windflow_tpu.ops.source import Source, SourceReplica
    if isinstance(op, DeviceSource):
        if time_policy == TimePolicy.EVENT:
            if op.ts_fn is None or op.wm_fn is None:
                diags.append(Diagnostic(
                    "WF501",
                    f"device source '{op.name}': EVENT time policy needs "
                    "both ts_fn (device lane) and wm_fn (host frontier)",
                    node=op.name, hint="use withTimestampFn(ts_fn, wm_fn)"))
                return "none"
            return "event"
        if op.ts_fn is not None:
            diags.append(Diagnostic(
                "WF501",
                f"device source '{op.name}': withTimestampFn requires the "
                "EVENT time policy (INGRESS stamps arrival time itself)",
                node=op.name))
        return "ingress"
    if type(op) is Source or op.replica_class is SourceReplica:
        if time_policy == TimePolicy.EVENT:
            if op.ts_extractor is None:
                diags.append(Diagnostic(
                    "WF501",
                    f"source '{op.name}': EVENT time policy requires a "
                    "timestamp extractor",
                    node=op.name,
                    hint="use withTimestampExtractor(fn) on the builder"))
                return "none"
            return "event"
        return "ingress"
    return "event" if time_policy == TimePolicy.EVENT else "ingress"


def _watermark_pass(graph, ops, upstreams, diags) -> None:
    from windflow_tpu.ops.source import Source
    from windflow_tpu.windows.engine import WindowSpec
    # demand-driven fold over the upstream map (merge-connection edges
    # sort last in _edges(), so a forward sweep would leave everything
    # past a merged pipe's head without modes — same ordering hazard the
    # kernel pass avoids the same way)
    memo: Dict[int, set] = {}

    def modes_of(op, stack=frozenset()):
        if id(op) in memo:
            return memo[id(op)]
        if id(op) in stack:         # defensive: compositions cannot cycle
            return set()
        if isinstance(op, Source):
            m = {_source_wm_mode(op, graph.time_policy, diags)}
        else:
            m = set()
            for up in upstreams.get(id(op), (None, []))[1]:
                m |= modes_of(up, stack | {id(op)})
        memo[id(op)] = m
        return m

    for op in ops:
        modes_of(op)    # classifies every source (WF501) exactly once
    # merge points: the WatermarkCollector min-folds channel watermarks, so
    # one watermark-less parent pins the merged frontier at WM_NONE forever
    for merged in graph._merges:
        if not merged.operators:
            continue
        head = merged.operators[0]
        got = memo.get(id(head), set())
        if len(got) > 1:
            diags.append(Diagnostic(
                "WF502",
                f"merge into '{head.name}' joins branches with mixed "
                f"watermark modes {sorted(got)} — the merged watermark "
                "min-folds over channels, so the least-advancing branch "
                "gates every time window downstream",
                node=head.name,
                hint="give every merged branch the same timestamping "
                     "(all event-timestamped, or all ingress)"))
    # TB windows downstream of a watermark-less branch never fire mid-run
    for op in ops:
        got = memo.get(id(op), set())
        if "none" not in got:
            continue
        spec = getattr(op, "spec", None)
        if isinstance(spec, WindowSpec) and spec.win_type == WinType.TB:
            diags.append(Diagnostic(
                "WF503",
                f"time-based window operator '{op.name}' is fed by a "
                "branch that never advances watermarks — its windows "
                "fire only at end-of-stream",
                node=op.name))


# ---------------------------------------------------------------------------
# abstract kernel evaluation
# ---------------------------------------------------------------------------

def _eval(fn, *specs):
    """``jax.eval_shape`` with the exception surfaced as a string (the
    diagnostic payload); no device work either way."""
    import jax
    try:
        return jax.eval_shape(fn, *specs), None
    except Exception as e:  # noqa: BLE001 - lint: broad-except-ok (user
        # kernels raise arbitrary exception types under abstract eval; the
        # whole point of this pass is to turn ANY of them into a WF101)
        return None, f"{type(e).__name__}: {e}"


def _check_key_extractor(op, spec, diags) -> None:
    if op.key_extractor is None:
        return
    out, err = _eval(op.key_extractor, spec)
    if err is not None:
        diags.append(Diagnostic(
            "WF104",
            f"operator '{op.name}': key extractor failed abstract "
            f"evaluation over the record spec — {err}",
            node=op.name))
        return
    shape = tuple(getattr(out, "shape", ())) if out is not None else ()
    dtype = getattr(out, "dtype", None)
    if shape != () or dtype is None \
            or not np.issubdtype(np.dtype(dtype), np.integer):
        diags.append(Diagnostic(
            "WF104",
            f"operator '{op.name}': key extractor must return an integer "
            f"scalar, got shape {shape} dtype {dtype} — keys are "
            "extracted inside the compiled program and index dense key "
            "tables",
            node=op.name,
            hint="return an int field (cast with .astype(jnp.int32))"))


def _check_comb(op, one, code, what, diags) -> bool:
    """Combiner must map (rec, rec) -> rec with structure, shapes and
    dtypes preserved — the associativity contract every fold path
    (sort/scan, dense tables, mesh collectives) compiles against."""
    import jax
    out, err = _eval(op.comb, one, one)
    if err is not None:
        diags.append(Diagnostic(
            code,
            f"operator '{op.name}': {what} combiner failed abstract "
            f"evaluation — {err}", node=op.name))
        return False
    if not _same_struct(one, out):
        want = jax.tree.structure(one)
        got = jax.tree.structure(out)
        diags.append(Diagnostic(
            code,
            f"operator '{op.name}': {what} combiner must return the same "
            f"record structure as its inputs (records have {want}, "
            f"combiner returned {got}); carry every field through the "
            "combine", node=op.name))
        return False
    drift = _leaf_mismatch(one, out)
    if drift is not None:
        diags.append(Diagnostic(
            code,
            f"operator '{op.name}': {what} combiner must preserve each "
            f"field's shape and dtype: {drift}", node=op.name))
        return False
    return True


def record_nbytes(spec) -> Optional[int]:
    """Payload bytes of ONE record under an abstract spec (summed leaf
    ``shape x itemsize``) — the declared-record byte model the sweep
    ledger (monitoring/sweep_ledger.py) splits measured HBM traffic
    against.  ``None`` when the spec is unknown."""
    if spec is _UNKNOWN:
        return None
    import jax
    total = 0
    for leaf in jax.tree.leaves(spec):
        n = 1
        for d in getattr(leaf, "shape", ()):
            n *= int(d)
        total += n * np.dtype(leaf.dtype).itemsize
    return total


def _kernel_pass(graph, ops, edges, upstreams, diags) -> None:
    """Diagnostic face of :func:`propagate_specs` (the WF1xx codes)."""
    propagate_specs(graph, ops=ops, edges=edges, upstreams=upstreams,
                    diags=diags)


def propagate_specs(graph, ops=None, edges=None, upstreams=None,
                    diags=None) -> Tuple[Dict[int, Any], Dict[int, Any]]:
    """Propagate abstract record specs from the sources through every
    chain, eval-shaping each user kernel where a spec is known.  Returns
    ``(in_specs, out_specs)``, both keyed by ``id(op)`` with ``None``
    marking "unknown at this point of the chain".

    This is THE shared graph walk: the pre-flight kernel pass appends
    its WF1xx diagnostics through ``diags``; the sweep ledger and the
    fusion advisor (analysis/fusion.py) call it with ``diags`` defaulted
    to a throwaway list just for the per-op record specs."""
    if diags is None:
        diags = []
    if edges is None:
        edges = graph._edges()
    if ops is None:
        ops = _all_ops(graph)
    if upstreams is None:
        upstreams = _upstream_map(edges)
    import jax
    from windflow_tpu.io.device_source import DeviceSource
    from windflow_tpu.ops.chained import ChainedTPU
    from windflow_tpu.ops.filter_op import Filter
    from windflow_tpu.ops.source import Source
    from windflow_tpu.ops.tpu import FilterTPU, MapTPU, ReduceTPU
    from windflow_tpu.ops.tpu_stateful import (StatefulFilterTPU,
                                               StatefulMapTPU)
    from windflow_tpu.windows.ffat_tpu import FfatWindowsTPU
    from windflow_tpu.windows.rolling_tpu import RollingAggregateTPU

    in_spec: Dict[int, Any] = {}

    def cap_of(op) -> int:
        caps = sorted(c for c in _effective_caps(op, upstreams) if c)
        return caps[0] if caps else (graph.config.default_batch_size or 1)

    def source_spec(op):
        if getattr(op, "record_spec", None) is not None:
            try:
                return _as_struct(op.record_spec)
            except Exception as e:  # noqa: BLE001 - lint: broad-except-ok
                # (withRecordSpec takes arbitrary user pytrees; a bad one
                # must degrade to "unknown", never crash the checker)
                diags.append(Diagnostic(
                    "WF101",
                    f"source '{op.name}': withRecordSpec example could "
                    f"not be abstracted — {type(e).__name__}: {e}",
                    node=op.name))
                return _UNKNOWN
        if isinstance(op, DeviceSource) and op.batch_fn is not None:
            out, err = _eval(op.batch_fn,
                             jax.ShapeDtypeStruct((), np.int32))
            if err is None and out is not None:
                # per-record view of the [capacity] batch leaves
                return jax.tree.map(
                    lambda s: jax.ShapeDtypeStruct(tuple(s.shape)[1:],
                                                   s.dtype), out)
        return _UNKNOWN

    def out_spec(op, spec):
        """Abstract output record spec of ``op`` given input ``spec``
        (which may be _UNKNOWN), appending diagnostics for provable
        kernel violations.  Device kernels MUST trace (WF101); host
        functions are best-effort (arbitrary Python degrades to
        unknown, never to an error)."""
        if spec is not _UNKNOWN and op.is_keyed:
            # device-traced integer extractors only: ReduceTPU and FFAT
            # extract keys INSIDE the compiled program; dense-key stateful
            # ops index slot tables directly.  (Interned stateful keys and
            # host keyby extractors may return any hashable — no check.)
            if isinstance(op, (ReduceTPU, FfatWindowsTPU)) \
                    or (isinstance(op, (StatefulMapTPU, StatefulFilterTPU))
                        and op.dense_keys):
                _check_key_extractor(op, spec, diags)
        if isinstance(op, MapTPU):
            if spec is _UNKNOWN:
                return _UNKNOWN
            if op.batch_fn:
                cap = cap_of(op)
                out, err = _eval(op.fn, _batched(spec, cap),
                                 jax.ShapeDtypeStruct((cap,), np.bool_))
                if err is not None:
                    diags.append(Diagnostic(
                        "WF101",
                        f"operator '{op.name}': batch kernel failed "
                        f"abstract evaluation over the incoming record "
                        f"spec — {err}", node=op.name))
                    return _UNKNOWN
                return jax.tree.map(
                    lambda s: jax.ShapeDtypeStruct(tuple(s.shape)[1:],
                                                   s.dtype), out)
            out, err = _eval(op.fn, spec)
            if err is not None:
                diags.append(Diagnostic(
                    "WF101",
                    f"operator '{op.name}': kernel failed abstract "
                    f"evaluation over the incoming record spec — {err}",
                    node=op.name,
                    hint="the record fields/dtypes reaching this operator "
                         "do not match what the kernel expects"))
                return _UNKNOWN
            return out
        if isinstance(op, FilterTPU):
            if spec is _UNKNOWN:
                return _UNKNOWN
            out, err = _eval(op.fn, spec)
            if err is not None:
                diags.append(Diagnostic(
                    "WF101",
                    f"operator '{op.name}': predicate failed abstract "
                    f"evaluation — {err}", node=op.name))
            else:
                shape = tuple(getattr(out, "shape", (-1,)))
                dtype = getattr(out, "dtype", None)
                if shape != () or dtype is None \
                        or np.dtype(dtype) != np.dtype(np.bool_):
                    diags.append(Diagnostic(
                        "WF102",
                        f"operator '{op.name}': predicate must return a "
                        f"boolean scalar, got shape {shape} dtype "
                        f"{dtype} — the validity-mask intersection needs "
                        "a bool lane", node=op.name))
            return spec
        if isinstance(op, ChainedTPU):
            cur = spec
            for kind, fn, _ in op.specs:
                if cur is _UNKNOWN:
                    return _UNKNOWN
                if kind == "map":
                    out, err = _eval(fn, cur)
                    if err is not None:
                        diags.append(Diagnostic(
                            "WF101",
                            f"operator '{op.name}': fused map stage "
                            f"failed abstract evaluation — {err}",
                            node=op.name))
                        return _UNKNOWN
                    cur = out
                elif kind == "batch_map":
                    cap = cap_of(op)
                    out, err = _eval(
                        fn, _batched(cur, cap),
                        jax.ShapeDtypeStruct((cap,), np.bool_))
                    if err is not None:
                        diags.append(Diagnostic(
                            "WF101",
                            f"operator '{op.name}': fused batch-map "
                            f"stage failed abstract evaluation — {err}",
                            node=op.name))
                        return _UNKNOWN
                    cur = jax.tree.map(
                        lambda s: jax.ShapeDtypeStruct(
                            tuple(s.shape)[1:], s.dtype), out)
                else:   # filter
                    out, err = _eval(fn, cur)
                    if err is not None:
                        diags.append(Diagnostic(
                            "WF101",
                            f"operator '{op.name}': fused predicate "
                            f"failed abstract evaluation — {err}",
                            node=op.name))
                    elif tuple(getattr(out, "shape", (-1,))) != () \
                            or np.dtype(out.dtype) != np.dtype(np.bool_):
                        diags.append(Diagnostic(
                            "WF102",
                            f"operator '{op.name}': fused predicate must "
                            "return a boolean scalar, got shape "
                            f"{tuple(getattr(out, 'shape', ()))} dtype "
                            f"{getattr(out, 'dtype', None)}",
                            node=op.name))
            return cur
        if isinstance(op, ReduceTPU):
            if spec is not _UNKNOWN:
                _check_comb(op, spec, "WF103", "reduce", diags)
            return spec
        if isinstance(op, FfatWindowsTPU):
            if spec is not _UNKNOWN:
                agg, err = _eval(op.lift, spec)
                if err is not None:
                    diags.append(Diagnostic(
                        "WF101",
                        f"operator '{op.name}': lift failed abstract "
                        f"evaluation over the incoming record spec — "
                        f"{err}", node=op.name))
                else:
                    _check_ffat_comb(op, agg, diags)
            return _UNKNOWN   # emits window results, not input records
        if isinstance(op, RollingAggregateTPU):
            if spec is _UNKNOWN:
                return _UNKNOWN
            _check_key_extractor(op, spec, diags)
            try:
                row, err = op.row_spec(spec), None
            except Exception as e:  # noqa: BLE001 - lint: broad-except-ok
                # (a user lift raises anything under abstract evaluation)
                err = f"{type(e).__name__}: {e}"
            if err is not None:
                diags.append(Diagnostic(
                    "WF101",
                    f"operator '{op.name}': lift failed abstract "
                    f"evaluation over the incoming record spec — {err}",
                    node=op.name,
                    hint="lift(record, ts) gives {leaf: value} for "
                         "exactly the declared leaves (withSum / withMin "
                         "/ withMax / withDistinct)"))
                return _UNKNOWN
            return row      # one upsert row: downstream maps are checked
        if isinstance(op, (StatefulMapTPU, StatefulFilterTPU)):
            if spec is not _UNKNOWN and op.assoc is None:
                state = jax.tree.map(
                    lambda a: jax.ShapeDtypeStruct(
                        tuple(np.shape(a))[1:],
                        np.asarray(a).dtype if not hasattr(a, "dtype")
                        else a.dtype), op._state)
                out, err = _eval(op.fn, spec, state)
                if err is not None:
                    diags.append(Diagnostic(
                        "WF101",
                        f"operator '{op.name}': stateful kernel failed "
                        f"abstract evaluation — {err}", node=op.name))
                    return _UNKNOWN
                if isinstance(op, StatefulMapTPU):
                    try:
                        return out[0]
                    except (TypeError, IndexError):
                        return _UNKNOWN
                return spec
            return spec if isinstance(op, StatefulFilterTPU) else _UNKNOWN
        if isinstance(op, Filter):
            # the predicate is not invoked (host functions may be
            # side-effectful); records pass through unchanged either way
            return spec
        # host Map/FlatMap/Reduce, window engines, sinks, unknown types:
        # arbitrary Python the runtime never traces — calling it here
        # (even abstractly) could fire side effects before the stream
        # runs, so the spec goes unknown instead.  Device kernels above
        # are different: jit traces them at the first batch anyway, so
        # abstract evaluation adds no new execution contract.
        return _UNKNOWN

    # Demand-driven propagation over the upstream map (which already
    # includes merge and split fan-in edges): order-independent, so a
    # merged pipe's internal chain sees the specs its parents deliver
    # even though the merge-connection edges sort last in _edges().
    out_cache: Dict[int, Any] = {}
    visiting: set = set()

    def in_of(op):
        if id(op) in in_spec:
            return in_spec[id(op)]
        spec = _UNKNOWN
        first = True
        for up in upstreams.get(id(op), (None, []))[1]:
            s = out_of(up)
            if first:
                spec, first = s, False
            elif spec is _UNKNOWN or s is _UNKNOWN:
                spec = _UNKNOWN
            else:
                # structure AND leaf shapes/dtypes must agree: a merge of
                # {"v": int32} with {"v": float32} would otherwise be
                # checked against only the first branch
                drift = (f"record structures {jax.tree.structure(spec)} "
                         f"vs {jax.tree.structure(s)}"
                         if not _same_struct(spec, s)
                         else _leaf_mismatch(spec, s))
                if drift is not None:
                    diags.append(Diagnostic(
                        "WF106",
                        f"operator '{op.name}': merged branches deliver "
                        f"different records ({drift}) — downstream "
                        "kernels were checked against neither",
                        node=op.name))
                    spec = _UNKNOWN
        in_spec[id(op)] = spec
        return spec

    def out_of(op):
        if id(op) in out_cache:
            return out_cache[id(op)]
        if id(op) in visiting:      # defensive: compositions cannot cycle
            return _UNKNOWN
        visiting.add(id(op))
        if isinstance(op, Source):
            spec = source_spec(op)
        else:
            spec = out_spec(op, in_of(op))
        visiting.discard(id(op))
        out_cache[id(op)] = spec
        return spec

    for op in ops:
        out_of(op)      # force every operator's kernel checks
        in_of(op)       # ... and materialize every input spec
    return in_spec, out_cache


def _check_ffat_comb(op, agg, diags) -> None:
    """FFAT comb folds *lifted aggregates*: (agg, agg) -> agg with the
    lift's structure preserved (WF105)."""
    import jax
    out, err = _eval(op.comb, agg, agg)
    if err is not None:
        diags.append(Diagnostic(
            "WF105",
            f"operator '{op.name}': window combiner failed abstract "
            f"evaluation over the lifted aggregate — {err}",
            node=op.name))
        return
    if not _same_struct(agg, out):
        diags.append(Diagnostic(
            "WF105",
            f"operator '{op.name}': window combiner must return the "
            f"lift's aggregate structure ({jax.tree.structure(agg)}), "
            f"got {jax.tree.structure(out)}", node=op.name))
        return
    drift = _leaf_mismatch(agg, out)
    if drift is not None:
        diags.append(Diagnostic(
            "WF105",
            f"operator '{op.name}': window combiner must preserve the "
            f"aggregate's shapes and dtypes: {drift}", node=op.name))
