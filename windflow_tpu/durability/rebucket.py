"""Re-bucketing of checkpointed keyed state for shape-changing restores.

``PipeGraph.restore()`` onto a *different* shard shape — a keyed
operator's parallelism changed, or the graph moved to a different mesh
(N±1 chips, single-chip ↔ mesh) — is the production ops story: chip
failure, rolling upgrade, capacity change under live traffic.  The
epoch protocol makes it cheap: every checkpoint snapshot is taken at a
quiesced aligned barrier with the state pulled to host numpy, so a
rescale is pure host-side array surgery between ``load_checkpoint`` and
``restore_state`` — re-bucket each keyed row/entry to the shard the NEW
placement assigns it, then let the operator re-place the result on the
new mesh.

Placement mirrors the routing plane exactly (the state must land where
the keys will):

* host ``KeyByEmitter`` edges (host Reduce): ``stable_hash(key) % n``;
* keyed staging / device keyby edges (FFAT, stateful):
  ``splitmix64(k32) % n``;
* compacted key spaces (parallel/compaction.py): ``slot % n`` — the
  remap table itself rides the operator blob, so slots survive the
  restore and hot keys stay balanced on the new shard count;
* executor placement overrides (windflow_tpu/serving): moves applied by
  a live reshard are recorded in the checkpoint and re-applied before
  the hash, exactly as the advisor's ``move_keys`` contract routes.

What cannot re-bucket raises :class:`RescaleError` (surfaced as WF605):
state of an unknown kind, a key space that does not divide the new mesh
key axis, or TB pane rings whose per-shard clocks disagree at the
barrier (each shard's ring base/window frontier is shard-local state; a
merge across disagreeing clocks would re-fire or skip windows — restore
once on the checkpointed shape to reconcile, then rescale).
"""

from __future__ import annotations

from typing import Dict, Optional

import numpy as np

from windflow_tpu.basic import WindFlowError, int32_key, stable_hash
from windflow_tpu.windows.ffat_kernels import (TB_ALIGNED, TB_COUNTERS,
                                               TB_SCALARS)

#: the one shard-shaped lane of a count-based state, on a mesh alone: its
#: many-round steps, one lane a key shard (parallel/mesh.CB_WIDE_STEPS;
#: a blob from before it, or from one chip, lacks it and restores as 0)
CB_COUNTER = "n_wide"


class RescaleError(WindFlowError):
    """A shape-changing restore that cannot re-bucket (WF605)."""

    def __init__(self, op_name: str, why: str) -> None:
        super().__init__(
            f"WF605 restore: operator '{op_name}' cannot re-bucket its "
            f"checkpointed state onto the new shard shape — {why}")


def mesh_shape(mesh) -> Optional[dict]:
    """JSON-able shape record the manifest pins for a mesh graph."""
    if mesh is None:
        return None
    from windflow_tpu.parallel.mesh import DATA_AXIS, KEY_AXIS
    return {"devices": int(np.prod(list(mesh.devices.shape))),
            "data": int(mesh.shape[DATA_AXIS]),
            "key": int(mesh.shape[KEY_AXIS])}


def _owner_fn(kind: str, n: int, override: Optional[dict]):
    """Shard owner of a key/row under one placement — bit-identical to
    the emitter the edge routes through (parallel/emitters.py).  The
    override map must be keyed in the SAME domain the owner is asked
    about (user keys for hash placements, ring rows for ``slot_mod`` —
    see ``_slot_override``)."""
    from windflow_tpu.parallel.emitters import splitmix64_int
    ov = override or {}

    def owner(key) -> int:
        d = ov.get(key)
        if isinstance(d, int) and 0 <= d < n:
            return d
        if kind == "slot_mod":
            return int(key) % n
        if kind == "stable_hash":
            return stable_hash(key) % n
        return splitmix64_int(int32_key(key)) % n

    return owner


def _slot_override(blob: dict, override: Optional[dict]
                   ) -> Optional[dict]:
    """Translate an executor key→shard override (USER keys — the domain
    the emitters route by) into the ROW/slot domain a compacted ring's
    state is indexed by, through the compactor's checkpointed key→slot
    map.  Without this, an overridden hot key's tuples would route to
    one shard while its pane rows re-bucket to ``slot % n`` on
    another."""
    if not override:
        return None
    key_slot = (blob.get("compactor") or {}).get("key_slot") or {}
    ks = {int32_key(k): int(v) for k, v in key_slot.items()}
    out = {}
    for k, dst in override.items():
        slot = ks.get(int32_key(k))
        if slot is not None:
            out[slot] = dst
    return out or None


# ---------------------------------------------------------------------------
# per-kind re-bucketing
# ---------------------------------------------------------------------------

def _rebucket_reduce_host(op, blob, old_p, new_p, old_kk, new_kk,
                          override: Optional[dict], on_mesh) -> dict:
    """Host Reduce per-replica per-key dicts: merge, re-split by the
    host keyby placement (``stable_hash(key) % n`` with overrides
    first) — each key's rolling state lands on the replica its tuples
    will now reach."""
    merged = {}
    for d in blob.get("replicas") or []:
        merged.update(d)
    owner = _owner_fn("stable_hash", new_p, override)
    reps = [dict() for _ in range(new_p)]
    for k, v in merged.items():
        reps[owner(k)][k] = v
    return {"kind": "reduce_host", "replicas": reps}


def _tb_scalar(v) -> np.ndarray:
    """Normalize a TB clock scalar to a 1-D lane array (single-chip
    checkpoints carry shape ())."""
    a = np.asarray(v)
    return a.reshape(1) if a.ndim == 0 else a


def _check_aligned(op, states: dict, names=TB_ALIGNED) -> dict:
    """All contributing TB states/lanes must agree on the ring
    alignment scalars; returns the agreed value per name."""
    agreed = {}
    for name in names:
        vals = set()
        for st in states.values():
            for x in _tb_scalar(st[name]).tolist():
                vals.add(int(x))
        if len(vals) > 1:
            raise RescaleError(
                op.name,
                f"TB pane-ring clocks disagree across shards at the "
                f"checkpoint barrier ({name} in {sorted(vals)}); "
                "restore once on the checkpointed shape to reconcile "
                "the rings, then rescale")
        agreed[name] = vals.pop() if vals else 0
    return agreed


def _tree_map(fn, tree):
    import jax
    return jax.tree.map(fn, tree)


def _rebucket_ffat(op, blob, old_p: int, new_p: int,
                   old_kk: int, new_kk: int,
                   override: Optional[dict], on_mesh: bool) -> dict:
    """FFAT pane rings.  CB state is purely per-key (one shared table,
    per-key clock lanes) — shape-independent but for the mesh step's
    counter lanes (``CB_COUNTER``); only the mesh key-axis divisibility
    needs a check.  TB state carries ring clocks: one
    scalar lane per mesh key shard, or one full state per replica when
    keyed at parallelism > 1 — both re-bucket only when the clocks
    agree at the barrier (see :class:`RescaleError`)."""
    K = int(op.max_keys)
    if new_kk > 1 and K % new_kk:
        raise RescaleError(
            op.name, f"max_keys {K} not divisible by the new mesh key "
                     f"axis {new_kk}")
    states: Dict[int, dict] = blob["states"]
    is_tb = bool(getattr(op, "is_tb", False))
    kind = "slot_mod" if blob.get("compactor") is not None else "splitmix"
    old_per_rep = is_tb and op.key_extractor is not None and old_p > 1
    new_per_rep = is_tb and op.key_extractor is not None and new_p > 1

    if not is_tb:
        # per-key state only, but for the mesh step's counter lanes: their
        # sum moves to lane 0 of the new key axis (off a mesh it goes)
        out = dict(blob)
        out["states"] = {s: _relane_cb_counter(st, new_kk, on_mesh)
                         for s, st in states.items()}
        return out
    if not old_per_rep and not new_per_rep:
        if old_kk == new_kk or not states:
            return blob     # per-key state only: nothing shard-local
        # TB scalar lanes re-shaped old_kk -> new_kk (1 == single chip)
        st = dict(states[0])
        agreed = _check_aligned(op, {0: st})
        lanes = max(1, new_kk)

        def lane(name, fill):
            a = np.full((lanes,), fill,
                        _tb_scalar(st[name]).dtype)
            return a if new_kk > 1 else a.reshape(())

        for name in TB_ALIGNED:
            st[name] = lane(name, agreed[name])
        st["max_seen"] = lane("max_seen",
                              int(_tb_scalar(st["max_seen"]).max()))
        for name in TB_COUNTERS:
            if name not in st:
                continue
            total = int(_tb_scalar(st[name]).sum())
            a = np.zeros((lanes,), _tb_scalar(st[name]).dtype)
            a[0] = total
            st[name] = a if new_kk > 1 else a.reshape(())
        out = dict(blob)
        out["states"] = {0: st}
        return out

    # keyed TB across replica counts: gather each key row from its old
    # owner state into its new owner state; ring clocks must agree
    live = {s: st for s, st in states.items() if st}
    if not live:
        return blob
    agreed = _check_aligned(op, live)
    max_seen = max(int(_tb_scalar(st["max_seen"]).max())
                   for st in live.values())
    counters = {name: sum(int(_tb_scalar(st[name]).sum())
                          for st in live.values() if name in st)
                for name in TB_COUNTERS}
    if kind == "slot_mod":
        # compacted rings index rows by SLOT; executor overrides are
        # keyed by USER key — translate through the checkpointed remap
        override = _slot_override(blob, override)
    owner_old = _owner_fn(kind, max(1, old_p), override if old_per_rep
                          else None)
    owner_new = _owner_fn(kind, max(1, new_p), override)
    o_old = np.array([owner_old(r) for r in range(K)])
    o_new = np.array([owner_new(r) for r in range(K)])
    template = next(iter(live.values()))
    n_new_states = new_p if new_per_rep else 1

    def build(j: int) -> dict:
        out = {}
        rows_j = o_new == j if new_per_rep else np.ones(K, bool)
        for name, val in template.items():
            if name in TB_SCALARS:
                if name in TB_ALIGNED:
                    out[name] = np.asarray(agreed[name],
                                           _tb_scalar(val).dtype)
                elif name == "max_seen":
                    out[name] = np.asarray(max_seen,
                                           _tb_scalar(val).dtype)
                else:
                    out[name] = np.asarray(counters[name] if j == 0
                                           else 0,
                                           _tb_scalar(val).dtype)
                out[name] = out[name].reshape(())
                continue
            # per-key leaves (cells/cell_valid/horizon): axis 0 is K —
            # map over the pytree so nested aggregate structures work
            out[name] = _tree_map(
                lambda leaf, _n=name: _gather_rows(live, o_old, rows_j,
                                                   _n, leaf, template),
                val)
        return out

    new_states = {j: build(j) for j in range(n_new_states)}
    out = dict(blob)
    out["states"] = new_states
    return out


def _relane_cb_counter(st: dict, new_kk: int, on_mesh: bool) -> dict:
    """A count-based state with its mesh counter lanes re-shaped for the
    new key axis: the total in lane 0, or gone off a mesh."""
    if CB_COUNTER not in st:
        return st
    st = dict(st)
    old = _tb_scalar(st.pop(CB_COUNTER))
    if on_mesh:
        lanes = np.zeros((new_kk,), old.dtype)
        lanes[0] = old.sum()
        st[CB_COUNTER] = lanes
    return st


def _gather_rows(live, o_old, rows_j, name, leaf, template):
    """One per-key leaf gathered row-wise from the old owner states.
    ``leaf`` is the template's leaf; matching leaves in every old state
    share its position in the pytree, found by flattened index."""
    import jax
    t_leaves, treedef = jax.tree_util.tree_flatten(template[name])
    idx = next(i for i, l in enumerate(t_leaves) if l is leaf)
    acc = np.zeros_like(np.asarray(leaf))
    for s, st in live.items():
        m = rows_j & (o_old == s)
        if m.any():
            src = jax.tree_util.tree_flatten(st[name])[0][idx]
            acc[m] = np.asarray(src)[m]
    return acc


def _rebucket_stateful(op, blob, old_p, new_p, old_kk, new_kk: int,
                       override, on_mesh) -> dict:
    """Dense/interned stateful tables are ONE shared table across
    replicas (per-key arrival order comes from keyed routing, not state
    ownership) — shape-independent; only mesh divisibility can block."""
    S = int(getattr(op, "num_key_slots", 0) or 0)
    if new_kk > 1 and S and S % new_kk:
        raise RescaleError(
            op.name, f"num_key_slots {S} not divisible by the new mesh "
                     f"key axis {new_kk}")
    return blob


#: the kinds whose state HAS a shard shape, each with its rule
#: ``(op, blob, old_p, new_p, old_kk, new_kk, override, on_mesh)``: the
#: module's own knowledge of three state layouts, keyed by the string the
#: operator states as ``snapshot_kind``.  A kind without one re-buckets
#: only where the operator declares ``snapshot_shapeless``.
_RULES = {
    "reduce_host": _rebucket_reduce_host,
    "ffat_tpu": _rebucket_ffat,
    "stateful_tpu": _rebucket_stateful,
}


def _has_rule(op, kind) -> bool:
    """True where a blob of ``kind``, checkpointed by ``op``, can move
    onto another shard shape: the kind has a rule here, or it is the
    operator's own and its state has no shard shape."""
    if kind is None:
        return False
    return kind in _RULES or (
        kind == getattr(op, "snapshot_kind", None)
        and getattr(op, "snapshot_shapeless", False))


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------

def rebucket_blob(op, blob: dict, old_p: int, new_p: int,
                  old_mesh: Optional[dict], new_mesh: Optional[dict],
                  override: Optional[dict] = None) -> dict:
    """Re-bucket one operator's checkpoint blob from the shape it was
    written under (``old_p`` replicas on ``old_mesh``) onto the shape
    the restoring graph builds (``new_p`` / ``new_mesh``).  Blobs whose
    state is shape-independent pass through unchanged; unknown kinds
    under a genuine shape change raise :class:`RescaleError`."""
    old_kk = (old_mesh or {}).get("key", 1) or 1
    new_kk = (new_mesh or {}).get("key", 1) or 1
    unchanged = old_p == new_p and old_kk == new_kk \
        and (old_mesh is None) == (new_mesh is None)
    if unchanged:
        return blob
    kind = blob.get("kind") if isinstance(blob, dict) else None
    rule = _RULES.get(kind)
    if rule is not None:
        return rule(op, blob, old_p, new_p, old_kk, new_kk, override,
                    new_mesh is not None)
    if _has_rule(op, kind):
        return blob     # the operator says: no shard shape to change
    raise RescaleError(
        op.name,
        f"state of kind {kind!r} has no re-bucketing rule (the operator "
        "declares neither a dense key space nor a compaction remap)")
