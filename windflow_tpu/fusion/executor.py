"""Fusion executor: lower whole operator chains into one XLA dispatch.

PR 6's sweep ledger attributed the roofline's ~8x bytes/tuple excess to
per-hop HBM round-trips, and the fusion advisor
(``analysis/fusion.plan``) named the chains one program could replace.
This module is the executor that plan is the contract for: at
``PipeGraph._build`` every executable chain — a run of stateless TPU
stages (map / filter / chained pairs) optionally ending in a window
lift/combine, keyed reduce, or dense-key stateful tail — is routed as
ONE hop whose program threads payload/valid/ts/keys/state end to end
with no hop-boundary materialization, generalizing ``ops/chained.py``
from pairwise map/filter specs to arbitrary chains with stateful tails
(the ``whole_chain`` link kind the advisor records, single-replica
KEYBY relays included: key extraction already runs inside the compiled
program, so the relay edge simply disappears).

Mechanism (three cooperating pieces):

* **Prelude** — :func:`build_prelude` folds the stateless members'
  record transforms into one traced ``(payload, valid) -> (payload,
  valid)`` body.  Stateful tails inline it at program-build time
  (``windows/ffat_tpu._build_step``, ``ops/tpu.ReduceTPU._get_step`` /
  ``_get_dense_step``, ``ops/tpu_stateful._get_step`` consult
  ``op._fused_prelude``), so the tail's existing host machinery — TB
  ring regrow/rebase, EOS flush, overflow policy, donation of the state
  buffers — keeps working unchanged with the prelude fused in.
* **Stateless host** — an all-stateless chain has no tail program to
  extend; :class:`FusedStatelessExec` compiles the combined spec run
  (plus in-program key extraction for a downstream KEYBY consumer) and
  the last member's replicas dispatch it via the
  ``_TPUReplica._op_step`` hook (one attribute check per batch).
* **Graph rewiring** — ``PipeGraph._build`` wires edges INTO a fused
  segment's head to the segment host instead (keeping the head edge's
  routing contract), skips the interior edges entirely, and marks the
  member replicas inert.  Member operators stay in ``_operators``:
  preflight (which runs pre-build), the health watchdog, gauges, and
  ``stats()`` keep their shapes, with member numbers attributed from
  the fused hop by :func:`attribute_member_stats`.

Safety gates: fusion is skipped on a mesh (the sharded program
factories compose differently), for host-interning stateful tails (the
key intern needs a host round-trip mid-chain), and input-buffer
donation is only enabled when every producer of the head's batches is a
staging edge or a FORWARD DeviceSource — the only cases where the
arrays are provably unshared (split/broadcast/keyby device edges alias
one payload across destinations).

``Config.whole_chain_fusion`` / ``WF_TPU_FUSE=0`` is the kill switch;
tier-1 exercises both paths on CPU (tests/test_fusion.py A/B families).
"""

from __future__ import annotations

from typing import Callable, List, Optional

import jax
import jax.numpy as jnp

from windflow_tpu.basic import RoutingMode
from windflow_tpu.batch import DeviceBatch
from windflow_tpu.monitoring.jit_registry import wf_jit
from windflow_tpu.monitoring.recorder import operator_scope, phase


def fused_name(members) -> str:
    """Display/program name of a fused segment — the chained-pair
    ``a|b`` convention (ops/chained.fuse) extended to the whole run."""
    return "|".join(op.name for op in members)


def build_prelude(members):
    """One traced ``(payload, valid) -> (payload, valid)`` body applying
    every stateless member's record transform in chain order — the
    generalization of ``ChainedTPU``'s spec loop that stateful tails
    inline ahead of their own step.  Returns ``(prelude, has_filter)``.

    Wire-compressed staging (windflow_tpu/wire.py) composes AHEAD of
    this prelude at zero dispatch cost: ``batch.stage_packed`` inlines
    the traced ``wire.build_wire_decode`` stage into the unpack program
    the staged path already dispatches, so by the time a fused segment's
    program (prelude + tail) sees the batch, its lanes are decoded —
    the per-batch dispatch sequence stays exactly ``unpack → fused
    program``, compressed or not (pinned by tests/test_wire.py).

    Pallas kernels (windflow_tpu/kernels) compose BEHIND it the same
    way: the tail builders that inline this prelude
    (``ffat_tpu._build_step``, ``ReduceTPU._get_dense_step`` /
    ``_get_compacted_step``) resolve ``Config.pallas_kernels`` at
    program-build time, so a fused chain's single program carries
    prelude + Pallas kernel bodies + tail state machine in ONE
    dispatch — the kill switch (``WF_TPU_PALLAS=0``) swaps the kernel
    regions back to lax without touching the fusion plan (pinned by
    tests/test_pallas_kernels.py's zero-dispatch-delta test)."""
    from windflow_tpu.ops.chained import _tpu_specs
    specs = []
    for op in members:
        specs.extend(_tpu_specs(op))
    has_filter = any(kind == "filter" for kind, _, _ in specs)

    def prelude(payload, valid):
        # each member's part of the one program under its own name
        # (device phases, monitoring/recorder.py): a tail opens its own
        # operator scope AFTER this call, never around it
        for kind, fn, owner in specs:
            with operator_scope(owner), phase("wf.fn"):
                if kind == "map":
                    payload = jax.vmap(fn)(payload)
                elif kind == "batch_map":
                    payload = fn(payload, valid)
                else:
                    valid = valid & jax.vmap(fn)(payload)
        return payload, valid

    return prelude, has_filter


def prelude_out_spec(prelude: Callable, payload, valid):
    """Abstract post-prelude payload (``jax.eval_shape`` — zero device
    work): what the tail's record-structure checks and state layouts
    must be sized against when a prelude rewrites the records."""
    return jax.eval_shape(lambda p, v: prelude(p, v)[0], payload, valid)


def donation_aliases_cleanly(fn, *args) -> bool:
    """True when every input leaf of ``args`` finds a DISTINCT same-
    shape/dtype output leaf of ``fn(*args)`` — the condition under which
    ``donate_argnums`` elides whole-buffer copies instead of tripping
    XLA's "donated buffers were not usable" warning.  A chain whose map
    rewrites a field's dtype (int64 counter -> float value) leaves the
    old buffer unaliased, so donation is decided per program at the
    first batch (``jax.eval_shape`` — zero device work), not assumed."""
    try:
        out = jax.eval_shape(fn, *args)
    except Exception:  # lint: broad-except-ok (abstract eval of an
        # arbitrary user chain — ANY failure just means "don't donate";
        # the real dispatch will surface a genuine error on its own)
        return False
    pool: dict = {}
    for leaf in jax.tree_util.tree_leaves(out):
        sig = (tuple(leaf.shape), str(leaf.dtype))
        pool[sig] = pool.get(sig, 0) + 1
    for leaf in jax.tree_util.tree_leaves(args):
        sig = (tuple(getattr(leaf, "shape", ())),
               str(getattr(leaf, "dtype", None)))
        if pool.get(sig, 0) <= 0:
            return False
        pool[sig] -= 1
    return True


class FusedStatelessExec:
    """Executor for an all-stateless fused segment: ONE ``wf_jit``
    program for the member chain, installed on the LAST member (the
    segment host) and dispatched through ``_TPUReplica._op_step``.
    Mirrors ``ChainedTPU._step``'s batch contract — size is unknown
    after any fused filter, watermark/frontier/ts extrema relay — and
    adds the two whole-chain upgrades: in-program key extraction for a
    downstream KEYBY consumer (the keys lane rides the output batch so
    the consumer never re-extracts) and input-buffer donation when the
    graph proves the staged inputs unshared."""

    def __init__(self, name: str, members,
                 donate_inputs: bool = False) -> None:
        self.name = name
        self._prelude, self._has_filter = build_prelude(members)
        self._key_extractor: Optional[Callable] = None
        # donation is two-phase: the graph walk proves the inputs
        # UNSHARED at build (donate_inputs); whether they actually ALIAS
        # the chain's outputs is only knowable against the first batch's
        # concrete specs (donation_aliases_cleanly)
        self._donate_pending = donate_inputs
        self._donate = False
        # shard plane (monitoring/shard_ledger.py): when the ledger
        # attaches a sketch, the downstream key extraction this program
        # already performs also updates an on-device count-min/candidate
        # state threaded through as one donated operand — zero extra
        # dispatches; None leaves one check per batch in step()
        self._sketch = None
        self._sk_n = 1
        self._sk_state = None
        self._raw_step = None
        self._jit = None
        self._build()

    def set_downstream_key_extractor(self, key_fn: Callable) -> None:
        """Fuse the downstream KEYBY consumer's key extraction into the
        chain program: keys are computed on the chain's OUTPUT records
        (exactly what the consumer's own in-program extraction would
        see) and attached to the output batch's keys lane."""
        self._key_extractor = key_fn
        self._build()

    def enable_input_donation(self) -> None:
        """Arm the two-phase input donation (see ``__init__``): the
        caller proved the inputs unshared; the aliasing half is checked
        against the first batch.  ``PipeGraph._build`` calls this for
        unfused ``ChainedTPU`` hops, which share this machinery."""
        self._donate_pending = True

    def attach_shard_sketch(self, sketch, n_shards: int) -> None:
        """Fold the shard-plane sketch update into this chain program:
        the keys computed for the downstream KEYBY consumer feed the
        on-device count-min/candidate state inside the SAME dispatch.
        Called by the shard ledger at graph build (before any compile);
        ``n_shards`` is the consumer's replica count, so the sketch's
        per-shard counts use the exact splitmix placement the keyby
        routing applies downstream."""
        self._sketch = sketch
        self._sk_n = max(1, n_shards)
        sketch.register_device_state(lambda: self._sk_state)
        self._build()

    def _build(self) -> None:
        prelude = self._prelude
        kx = self._key_extractor
        sketched = self._sketch is not None and kx is not None
        n_sh = self._sk_n

        def raw(payload, valid):
            payload, valid = prelude(payload, valid)
            keys = None
            if kx is not None:
                # the downstream consumer's extractor: no operator of
                # this chain
                with phase("wf.fn"):
                    keys = jax.vmap(kx)(payload).astype(jnp.int32)
            return payload, valid, keys

        # the donation aliasing probe always evaluates the sketch-free
        # two-arg form: the sketch state trivially aliases itself and
        # must not mask a payload lane that fails to alias
        self._raw_step = raw
        if sketched:
            from windflow_tpu.monitoring.shard_ledger import \
                device_sketch_update

            def step(payload, valid, sk):
                payload, valid, keys = raw(payload, valid)
                return payload, valid, keys, device_sketch_update(
                    sk, keys, valid, n_sh)

            donate = ((0, 1) if self._donate else ()) + (2,)
        else:
            step = raw
            donate = (0, 1) if self._donate else ()
        self._jit = wf_jit(step, op_name=self.name, donate_argnums=donate)

    def step(self, batch: DeviceBatch) -> DeviceBatch:
        if self._donate_pending:
            self._donate_pending = False
            if donation_aliases_cleanly(self._raw_step, batch.payload,
                                        batch.valid):
                self._donate = True
                self._build()
        if self._sketch is not None and self._key_extractor is not None:
            if self._sk_state is None:
                from windflow_tpu.monitoring.shard_ledger import \
                    device_sketch_init
                self._sk_state = device_sketch_init(self._sk_n)
            payload, valid, keys, self._sk_state = self._jit(
                batch.payload, batch.valid, self._sk_state)
        else:
            payload, valid, keys = self._jit(batch.payload, batch.valid)
        size = None if self._has_filter else batch.known_size
        return DeviceBatch(payload, batch.ts, valid, keys=keys,
                           watermark=batch.watermark, size=size,
                           frontier=batch.frontier, ts_max=batch.ts_max,
                           ts_min=batch.ts_min)


# ---------------------------------------------------------------------------
# Segment planning: the advisor's chains, trimmed to what executes today
# ---------------------------------------------------------------------------

def plan_segments(graph) -> List[dict]:
    """Executable fused segments of a composed graph: each advisor chain
    (``analysis/fusion.fusible_chains`` — the shared walk, so executor
    and advisor can never disagree about linkability) trimmed to its
    executable run — the stateless prefix plus at most one supported
    stateful tail.  Segments of fewer than two members are dropped."""
    from windflow_tpu.analysis.fusion import fusible_chains
    segments = []
    for chain in fusible_chains(graph):
        run = []
        for op in chain["ops"]:
            if op.chain_role == "member":
                run.append(op)
                continue
            if run and op.inlines_prelude():
                run.append(op)
            break
        if len(run) < 2:
            continue
        segments.append({
            "name": fused_name(run),
            "members": run,
            "member_names": [op.name for op in run],
            "host_name": run[-1].name,
        })
    return segments


def _upstream_edges(graph) -> dict:
    """id(op) -> [(upstream op, arrived_via_split)] over every graph
    edge — the donation-safety walk (split fan-outs alias device
    buffers across branches, so they matter here where the preflight
    upstream map folds them away)."""
    ups: dict = {}
    for edge in graph._edges():
        if edge[0] == "op":
            _, a, b = edge
            ups.setdefault(id(b), []).append((a, False))
        else:
            _, mp = edge
            src_op = mp.operators[-1]
            for child in mp.split_children:
                if child.operators:
                    ups.setdefault(id(child.operators[0]), []).append(
                        (src_op, True))
    return ups


def input_donation_safe(head, upstreams: dict) -> bool:
    """True when every producer of ``head``'s input batches stages
    FRESH, unshared device arrays per batch, so the fused program may
    take them with ``donate_argnums`` (eliding the whole-buffer copies
    the sweep ledger's donation-miss tripwire counts):

    * a host→device staging edge materializes new arrays from host
      records every batch (the pool recycles HOST buffers only, gated
      on the unpack output — batch.stage_packed);
    * a FORWARD DeviceSource emits its program's fresh outputs to one
      destination per tick.

    Everything else — device keyby splits, broadcast, device splits —
    aliases ONE payload across several destinations' masks, where a
    donation by any consumer would invalidate its siblings' views."""
    from windflow_tpu.io.device_source import DeviceSource
    ups = upstreams.get(id(head))
    if not ups:
        return False
    for up_op, via_split in ups:
        if not up_op.is_tpu:
            continue
        if isinstance(up_op, DeviceSource) and not via_split \
                and head.routing == RoutingMode.FORWARD:
            continue
        return False
    return True


def apply_fusion(graph) -> List[dict]:
    """Install the fused segments on a graph being built (called by
    ``PipeGraph._build`` after replica construction, before edge
    wiring).  Marks members, installs the prelude/exec on each segment
    host, decides input donation, and chains member closers onto the
    host so per-replica shutdown callbacks still run once.  Returns the
    segment list ``PipeGraph._fused_segments`` keeps for the wiring
    redirect, the sweep ledger, and stats attribution."""
    segments = plan_segments(graph)
    if not segments:
        return []
    upstreams = _upstream_edges(graph)
    for seg in segments:
        members = seg["members"]
        host = members[-1]
        donate = input_donation_safe(members[0], upstreams)
        seg["donate_inputs"] = donate
        for m in members[:-1]:
            m._fused_into = seg["name"]
        host._fused_name = seg["name"]
        if host.chain_role == "member":
            host._fusion_exec = FusedStatelessExec(
                seg["name"], members, donate_inputs=donate)
        else:
            prelude, _ = build_prelude(members[:-1])
            host._fused_prelude = prelude
            host._fused_donate_inputs = donate
        _chain_closers(members, host)
    return segments


def _chain_closers(members, host) -> None:
    """Member closing_funcs run at HOST termination (the fused replica
    is the only one that terminates through the normal EOS path) — the
    ops/chained.fuse stance generalized to the whole segment."""
    closers = [m.closing_func for m in members if m.closing_func is not None]
    if not closers or closers == [host.closing_func]:
        return
    from windflow_tpu.meta import adapt
    adapted = [adapt(f, 0) for f in closers]

    def closing(ctx):
        for f in adapted:
            f(ctx)

    host.closing_func = closing


def attribute_member_stats(graph) -> None:
    """Per-op stats for fused members, attributed from the fused hop at
    stats-read cadence: the members' replicas never dispatch, so their
    input/output counters mirror the host hop's input count (records
    thread through the fused program; per-member survivor counts after
    interior filters are only observable with a device sync the hot
    path must never pay).  Replica 0 carries the whole-hop number."""
    for seg in graph._fused_segments:
        host = seg["members"][-1]
        inputs = sum(r.stats.inputs_received for r in host.replicas)
        for m in seg["members"][:-1]:
            for i, rep in enumerate(m.replicas):
                rep.stats.inputs_received = inputs if i == 0 else 0
                rep.stats.outputs_sent = inputs if i == 0 else 0
