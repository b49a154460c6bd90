"""Reshard/failover executor: apply the advisor's plans to a live graph.

PR 9's reshard advisor (analysis/resharding.py) emits ``move_keys`` /
``split_hot_key`` plans; PR 8's epoch protocol proves the graph can
quiesce to an aligned barrier with every operator's keyed state
host-visible.  This module is the missing verb: a reshard IS
"quiesce, re-place the key→shard map, resume" — the checkpoint
machinery minus the manifest.  Concretely, at executor-tick cadence
(``Config.reshard_check_sweeps`` driver sweeps — never per batch):

* **Triggers.**  A health-plane ``BACKPRESSURED``/``STALLED`` verdict on
  a keyed operator, or measured imbalance above
  ``Config.reshard_imbalance_threshold`` (the advisor's own
  actionability bound), sustained for ``reshard_trigger_ticks``.
* **move_keys.**  The graph quiesces (durability/checkpoint.quiesce —
  the same aligned barrier, so no record is in flight), the plan's
  key→shard overrides install on every keyed emitter feeding the
  operator (routing), the moved keys' STATE moves with them (host
  Reduce per-key dicts re-home; per-replica TB pane-ring rows re-home
  when the ring clocks agree; shared-table operators — dense/interned
  stateful, CB FFAT — need no state move at all: per-key rows are
  replica-independent), and the driver resumes.  No restart, no dropped
  or duplicated record: the barrier guarantees the moved key's tuples
  before the move were fully processed at the old shard and every tuple
  after it routes to the new one.
* **split_hot_key.**  Routing cannot balance a key hotter than a whole
  shard's fair share; the executor turns the split action into a
  PRE-AGGREGATING partial combine at the keyed staging boundary
  (parallel/emitters.KeyedDeviceStageEmitter.set_preagg): the hot key's
  tuples fold through the consumer's associative combiner before they
  ship, cutting its downstream load by the fold factor.  Applied only
  to consumers exposing an associative record combiner with a declared
  monoid (the WF405 contract class — ReduceTPU); per-batch partials
  coarsen, the final per-key aggregate is unchanged.
* **Admission control.**  When no plan can help (nothing actionable, or
  an applied plan did not recover), the executor degrades gracefully AT
  THE SOURCE: the per-sweep tick chunk scales down (halving to a 1/16
  floor) so inboxes stop growing, and recovers (doubling back to 1.0)
  once the graph holds OK.
* **Scale-down.**  Sustained OK for ``reshard_scale_down_ticks`` ticks
  (0 = record candidates only) drains the least-loaded shard's known
  keys onto its siblings through the same move path — the capacity-
  shrink half of elastic serving; the actual replica-count change is a
  rescale restore (docs/DURABILITY.md "rescale-on-restore").

Every action lands in ``stats()["Reshard"]`` (plans_applied,
keys_moved, quiesce_ms, recovery_ms, admission_factor, a bounded
timeline), the ``wf_reshard_*`` OpenMetrics families, and the
postmortem bundle's ``reshard.json`` (wf_doctor renders the timeline).
"""

from __future__ import annotations

import time
from collections import deque
from typing import Dict, Optional

from windflow_tpu.basic import current_time_usecs
from windflow_tpu.monitoring.health import HISTORY

#: per-operator executor states (stats()["Reshard"].ops[..].state)
E_OK = "OK"
E_TRIGGERED = "TRIGGERED"
E_RECOVERING = "RECOVERING"
E_DEGRADED = "DEGRADED"

#: admission-control floor: the source tick chunk never throttles below
#: this fraction — the graph keeps draining even fully degraded
_MIN_ADMISSION = 1.0 / 16.0


class _OpTrack:
    __slots__ = ("name", "state", "bad_ticks", "ok_ticks", "t_applied",
                 "last_action", "rounds")

    def __init__(self, name: str) -> None:
        self.name = name
        self.state = E_OK
        self.bad_ticks = 0
        self.ok_ticks = 0
        self.t_applied: Optional[float] = None
        self.last_action: Optional[str] = None
        #: plan applications in the current degradation episode — a
        #: reshard often takes several rounds (move the next-hottest
        #: keys off the still-hot shard) before admission control is
        #: the honest answer
        self.rounds = 0


class ReshardExecutor:
    """Graph-scoped executor (built by ``PipeGraph._build`` when
    ``Config.reshard_executor`` is on).  All work happens at tick
    cadence on the driver thread — ``on_sweep`` is one counter compare
    per sweep, and a tick that finds nothing bad reads two cached
    telemetry sections and returns."""

    def __init__(self, graph) -> None:
        self.graph = graph
        cfg = graph.config
        self.check_sweeps = max(1, int(cfg.reshard_check_sweeps))
        self.trigger_ticks = max(1, int(cfg.reshard_trigger_ticks))
        self.ok_ticks_needed = max(1, int(cfg.reshard_ok_ticks))
        self.threshold = float(cfg.reshard_imbalance_threshold)
        self.scale_down_ticks = max(0, int(cfg.reshard_scale_down_ticks))
        self._sweeps = 0
        # keyed targets: operators reached through override-capable
        # keyed emitters at parallelism > 1, plus split candidates
        # (monoid reduce consumers) at any parallelism
        from windflow_tpu.durability.checkpoint import keyed_emitters_into
        self._targets: Dict[str, dict] = {}
        for op in graph._operators:
            if op.key_extractor is None:
                continue
            ems = keyed_emitters_into(graph, op)
            if not ems:
                continue
            self._targets[op.name] = {"op": op, "emitters": ems}
        self._tracks = {name: _OpTrack(name) for name in self._targets}
        # counters (stats()["Reshard"] / wf_reshard_* / reshard.json)
        self.plans_applied = 0
        self.keys_moved = 0
        self.splits_applied = 0
        self.moves_skipped = 0
        self.admission_throttles = 0
        self.scale_down_events = 0
        self.last_quiesce_ms: Optional[float] = None
        self.quiesce_ms_total = 0.0
        self.last_recovery_ms: Optional[float] = None
        self.ticks = 0
        self._admission = 1.0
        self._all_ok_ticks = 0
        # per-op shard loads at the previous tick: the imbalance TRIGGER
        # judges the delta window (loads since last tick), because the
        # ledger's loads are cumulative — a successful move can never
        # repair the historical ratio, only the current one
        self._prev_loads: Dict[str, list] = {}
        self._last_delta: Dict[str, float] = {}
        self._last_window: Dict[str, list] = {}
        #: minimum delta-window tuples before the ratio means anything
        #: (idle graphs and end-of-stream must read as no-signal); the
        #: window ACCUMULATES across ticks until it is judgeable, so
        #: bursty per-shard flush cadences average out
        self._min_window = 256
        self.timeline: deque = deque(maxlen=HISTORY)

    # -- sweep hook (the whole per-sweep cost) -------------------------------
    def on_sweep(self) -> None:
        self._sweeps += 1
        if self._sweeps % self.check_sweeps == 0:
            self.tick()

    # -- admission control ---------------------------------------------------
    def admit_chunk(self, chunk: int) -> int:
        """Scale the source tick chunk by the admission factor — the
        graceful-degradation valve ``PipeGraph._tick_chunk`` applies."""
        if self._admission >= 1.0:
            return chunk
        return max(1, int(chunk * self._admission))

    # -- the tick ------------------------------------------------------------
    def tick(self) -> None:
        """One executor evaluation: read health verdicts + the shard
        plan, advance each target's state machine, apply what fires."""
        self.ticks += 1
        verdicts = self._health_verdicts()
        pl = self._plan()
        by_op = {e["op"]: e for e in (pl.get("ops") or [])}
        all_ok = True
        for name, tr in self._tracks.items():
            entry = by_op.get(name) or {}
            bad = self._is_bad(name, entry, verdicts)
            self._advance(tr, bad, entry)
            if tr.state != E_OK or self._admission < 1.0:
                all_ok = False
        if all_ok:
            self._all_ok_ticks += 1
            if self.scale_down_ticks \
                    and self._all_ok_ticks >= self.scale_down_ticks:
                self._all_ok_ticks = 0
                self._scale_down(by_op)
        else:
            self._all_ok_ticks = 0

    def _is_bad(self, name: str, entry: dict,
                verdicts: dict) -> Optional[bool]:
        """Tri-state verdict: True (degraded), False (healthy), None
        (no information this tick — a delta window too small to judge;
        the state machine holds position instead of flapping)."""
        v = (verdicts.get(name) or {}).get("state")
        if v in ("BACKPRESSURED", "STALLED"):
            return True
        r = self._delta_imbalance(name, entry.get("loads") or [])
        if r is None:
            return None
        return r > self.threshold

    def _delta_imbalance(self, name: str, loads: list) -> Optional[float]:
        """Imbalance ratio of the CURRENT window: per-shard load growth
        since the previous tick.  The ledger's loads are cumulative —
        judging them directly would latch any historical skew forever;
        the delta is what an applied plan can actually repair."""
        prev = self._prev_loads.get(name)
        if prev is None or len(prev) != len(loads) or len(loads) < 2:
            self._prev_loads[name] = list(loads)
            return None
        delta = [max(0, b - a) for a, b in zip(prev, loads)]
        total = sum(delta)
        if total < self._min_window:
            # window too small to judge: keep the origin so the next
            # tick's window includes this one (no signal is discarded)
            return None
        self._prev_loads[name] = list(loads)
        self._last_window[name] = delta
        r = max(delta) / (total / len(delta))
        self._last_delta[name] = round(r, 4)
        return r

    def _advance(self, tr: _OpTrack, bad: Optional[bool],
                 entry: dict) -> None:
        if bad is None:
            return      # no signal this tick: hold position
        if tr.state == E_OK:
            if bad:
                tr.state = E_TRIGGERED
                tr.bad_ticks = 1
                self._note(tr.name, "triggered",
                           f"window imbalance="
                           f"{self._last_delta.get(tr.name)} "
                           f"(cumulative "
                           f"{entry.get('imbalance_ratio')})")
            return
        if tr.state == E_TRIGGERED:
            if not bad:
                # symmetric hysteresis: one balanced window must not
                # reset a building trigger — bursty per-shard flush
                # cadences make single-window ratios noisy
                tr.ok_ticks += 1
                if tr.ok_ticks >= self.ok_ticks_needed:
                    tr.state = E_OK
                    tr.bad_ticks = tr.ok_ticks = 0
                return
            tr.ok_ticks = 0
            tr.bad_ticks += 1
            if tr.bad_ticks >= self.trigger_ticks:
                self._fire(tr, entry)
            return
        if tr.state == E_RECOVERING:
            if not bad:
                if tr.ok_ticks == 0 and tr.t_applied is not None:
                    self.last_recovery_ms = round(
                        (time.perf_counter() - tr.t_applied) * 1e3, 3)
                tr.ok_ticks += 1
                if tr.ok_ticks >= self.ok_ticks_needed:
                    tr.state = E_OK
                    tr.bad_ticks = tr.ok_ticks = tr.rounds = 0
                    self._note(tr.name, "recovered",
                               f"after {tr.last_action}, "
                               f"{self.last_recovery_ms}ms to first OK")
                return
            tr.ok_ticks = 0
            tr.bad_ticks += 1
            if tr.bad_ticks >= 2 * self.trigger_ticks:
                if tr.rounds < 3:
                    # still degraded after the move: re-enter the
                    # trigger path — the advisor plans the NEXT move
                    # round (the next-hottest keys) before admission
                    # control becomes the honest answer
                    tr.state = E_TRIGGERED
                    tr.bad_ticks = self.trigger_ticks
                    return
                self._degrade(tr)
            return
        if tr.state == E_DEGRADED:
            if bad:
                self._throttle(tr)
                return
            tr.ok_ticks += 1
            if tr.ok_ticks >= self.ok_ticks_needed:
                tr.ok_ticks = 0
                self._admission = min(1.0, self._admission * 2.0)
                self._note(tr.name, "admission",
                           f"recovering to {self._admission:.3f}")
                if self._admission >= 1.0:
                    tr.state = E_OK
                    tr.bad_ticks = 0

    def _fire(self, tr: _OpTrack, entry: dict) -> None:
        """A trigger confirmed: apply the best available action."""
        actions = entry.get("actions") or []
        if not actions and entry.get("loads"):
            # the delta-window trigger can fire while the CUMULATIVE
            # ratio still looks balanced (a fresh skew on a long
            # history — the Zipf-shift case): synthesize the plan from
            # the WINDOW loads, with the hot-key estimates scaled to
            # the window so the greedy placement arithmetic stays in
            # one unit
            try:
                from windflow_tpu.analysis.resharding import \
                    rebalance_actions
                row = dict(entry)
                win = self._last_window.get(tr.name)
                if win and sum(win) > 0:
                    scale = sum(win) / max(1, sum(entry["loads"]))
                    row["loads"] = win
                    row["hot_keys"] = [
                        dict(h, est_tuples=max(1, int(
                            h.get("est_tuples", 0) * scale)))
                        for h in (entry.get("hot_keys") or [])]
                actions = rebalance_actions(row, self.threshold)
            except Exception:  # lint: broad-except-ok (plan synthesis
                # over telemetry rows — a failure degrades to the
                # admission path, never the pipeline)
                actions = []
        moves = [a for a in actions if a.get("kind") == "move_keys"]
        splits = [a for a in actions if a.get("kind") == "split_hot_key"]
        if moves and self._apply_moves(tr, moves[0]):
            return
        if splits and self._apply_split(tr, splits):
            return
        self._degrade(tr)

    def _degrade(self, tr: _OpTrack) -> None:
        tr.state = E_DEGRADED
        tr.bad_ticks = tr.ok_ticks = 0
        self._throttle(tr)

    def _throttle(self, tr: _OpTrack) -> None:
        if self._admission > _MIN_ADMISSION:
            self._admission = max(_MIN_ADMISSION, self._admission / 2.0)
            self.admission_throttles += 1
            self._note(tr.name, "admission",
                       f"no plan helps — throttled to "
                       f"{self._admission:.3f}")

    # -- actions -------------------------------------------------------------
    def _apply_moves(self, tr: _OpTrack, action: dict) -> bool:
        """move_keys: quiesce → re-place → move state → resume."""
        target = self._targets[tr.name]
        op = target["op"]
        moves = [m for m in (action.get("moves") or [])
                 if isinstance(m.get("to_shard"), int)
                 and 0 <= m["to_shard"] < op.parallelism]
        if not moves:
            return False
        from windflow_tpu.durability.checkpoint import quiesce
        t0 = time.perf_counter()
        quiesce(self.graph)
        moved = self._move_state(op, moves)
        # routing: merge the new moves over any earlier override
        for em in target["emitters"]:
            cur = dict(getattr(em, "_override", None) or {})
            cur.update({m["key"]: m["to_shard"] for m in moves})
            em.set_override(cur)
            sk = getattr(em, "_sketch", None)
            if sk is not None:
                # keep the ledger's derived-placement attribution honest
                try:
                    sk.override = dict(cur)
                except Exception:  # lint: broad-except-ok (telemetry
                    # attribution only — an exotic sketch must never
                    # fail the reshard itself)
                    pass
        ms = round((time.perf_counter() - t0) * 1e3, 3)
        self.last_quiesce_ms = ms
        self.quiesce_ms_total += ms
        self.plans_applied += 1
        self.keys_moved += len(moves)
        tr.state = E_RECOVERING
        tr.bad_ticks = tr.ok_ticks = 0
        tr.rounds += 1
        tr.t_applied = time.perf_counter()
        tr.last_action = "move_keys"
        self._note(tr.name, "move_keys",
                   f"{len(moves)} key(s) re-placed, {moved} state "
                   f"row(s) moved, quiesce {ms}ms")
        return True

    def _apply_split(self, tr: _OpTrack, splits: list) -> bool:
        """split_hot_key → pre-aggregating partial combine (only for
        consumers with an associative combiner and a declared monoid —
        the contract class where replacing m tuples by their fold is
        provably output-preserving)."""
        target = self._targets[tr.name]
        op = target["op"]
        comb = getattr(op, "comb", None)
        if comb is None or getattr(op, "monoid", None) is None:
            return False
        ems = [em for em in target["emitters"]
               if hasattr(em, "set_preagg")]
        if not ems:
            return False
        keys = [s["key"] for s in splits if s.get("key") is not None]
        if not keys:
            return False
        for em in ems:
            cur = set()
            pa = getattr(em, "_preagg", None)
            if pa:
                cur = set(pa["keys"])
            em.set_preagg(cur | set(keys), comb)
        self.splits_applied += 1
        self.plans_applied += 1
        tr.state = E_RECOVERING
        tr.bad_ticks = tr.ok_ticks = 0
        tr.rounds += 1
        tr.t_applied = time.perf_counter()
        tr.last_action = "split_hot_key"
        self._note(tr.name, "split_hot_key",
                   f"pre-aggregating {len(keys)} hot key(s) at the "
                   "staging boundary")
        return True

    def _scale_down(self, by_op: dict) -> None:
        """Sustained OK: drain the least-loaded shard's KNOWN keys onto
        its siblings (the ledger only knows the hot-key table; an
        honest scale-down reports what it could not find)."""
        for name, target in self._targets.items():
            op = target["op"]
            if op.parallelism < 2:
                continue
            entry = by_op.get(name) or {}
            loads = entry.get("loads") or []
            hot = entry.get("hot_keys") or []
            if len(loads) < 2:
                continue
            victim = min(range(len(loads)), key=lambda i: loads[i])
            keys_on = [h for h in hot if h.get("shard") == victim
                       and h.get("key") is not None]
            self.scale_down_events += 1
            if not keys_on:
                self._note(name, "scale_down",
                           f"shard {victim} is the drain candidate "
                           "(no known keys to move — rescale-restore "
                           "onto fewer shards to realize it)")
                continue
            others = [i for i in range(len(loads)) if i != victim]
            moves = [{"key": h["key"],
                      "to_shard": others[i % len(others)],
                      "from_shard": victim,
                      "est_tuples": h.get("est_tuples", 0)}
                     for i, h in enumerate(keys_on)]
            tr = self._tracks[name]
            self._apply_moves(tr, {"moves": moves})
            self._note(name, "scale_down",
                       f"drained {len(moves)} known key(s) off shard "
                       f"{victim}")
            return      # one consolidation per sustained-OK window

    # -- keyed state movement ------------------------------------------------
    def _move_state(self, op, moves: list) -> int:
        """Move the keyed state rows/entries of ``moves`` to their new
        shards.  Shared-table operators need nothing (every replica
        reads the same table); host Reduce re-homes dict entries;
        per-replica TB FFAT re-homes pane-ring rows when the ring
        clocks agree (skipped and counted otherwise — the keys still
        re-route, and the advisor re-plans if the imbalance returns)."""
        from windflow_tpu.ops.reduce_op import Reduce
        if isinstance(op, Reduce):
            moved = 0
            reps = op.replicas
            for m in moves:
                key, dst = m["key"], m["to_shard"]
                for r in reps:
                    if r.index != dst and key in r._states:
                        reps[dst]._states[key] = r._states.pop(key)
                        moved += 1
                        break
            return moved
        from windflow_tpu.windows.ffat_tpu import FfatWindowsTPU
        if isinstance(op, FfatWindowsTPU) and op._per_replica_state:
            return self._move_ffat_rows(op, moves)
        return 0    # shared state table: routing move is the whole move

    def _move_ffat_rows(self, op, moves: list) -> int:
        import jax.numpy as jnp
        import numpy as np
        comp = getattr(op, "_compactor", None)
        moved = 0
        from windflow_tpu.basic import int32_key
        for m in moves:
            try:
                k32 = int32_key(m["key"])
            except (TypeError, ValueError):
                self.moves_skipped += 1
                continue
            row = comp.slot_of(k32) if comp is not None else k32
            dst = m["to_shard"]
            src_i = m.get("from_shard")
            if row is None or not (0 <= row < op.max_keys) \
                    or src_i not in op._states \
                    or dst not in op._states:
                # a destination replica that never stepped has no state
                # to merge into — the re-route alone is still safe (its
                # first batch initializes a ring and the key's windows
                # restart from the barrier), but we refuse to move the
                # rows and say so
                self.moves_skipped += 1
                self._note(op.name, "move_skipped",
                           f"key {m['key']}: no ring state at shard "
                           f"{src_i}→{dst} (or no slot)")
                continue
            src, dstst = op._states[src_i], op._states[dst]
            if int(np.asarray(src["base"])) \
                    != int(np.asarray(dstst["base"])) \
                    or int(np.asarray(src["win_next"])) \
                    != int(np.asarray(dstst["win_next"])):
                self.moves_skipped += 1
                self._note(op.name, "move_skipped",
                           f"key {m['key']}: ring clocks disagree "
                           f"between shards {src_i} and {dst}")
                continue
            import jax
            for name in ("cells", "cell_valid", "horizon"):
                s_v, d_v = src[name], dstst[name]
                if name == "cells":
                    dstst[name] = jax.tree.map(
                        lambda d, s: d.at[row].set(s[row]), d_v, s_v)
                    src[name] = jax.tree.map(
                        lambda s: s.at[row].set(jnp.zeros_like(s[row])),
                        s_v)
                elif name == "cell_valid":
                    dstst[name] = d_v.at[row].set(s_v[row])
                    src[name] = s_v.at[row].set(False)
                else:   # horizon: per-key overflow taint travels along
                    dstst[name] = d_v.at[row].set(s_v[row])
                    src[name] = s_v.at[row].set(
                        jnp.int64(-(1 << 60)))
            moved += 1
        return moved

    # -- reporting -----------------------------------------------------------
    def _health_verdicts(self) -> dict:
        h = self.graph._health
        if h is None:
            return {}
        try:
            return h.sample()
        except Exception:  # lint: broad-except-ok (telemetry read — a
            # watchdog bug degrades the trigger to imbalance-only, it
            # must never take the executor or the pipeline down)
            return {}

    def _plan(self) -> dict:
        led = self.graph._shard
        if led is None:
            return {"ops": []}
        try:
            from windflow_tpu.analysis.resharding import plan
            return plan(led.section(), graph_name=self.graph.name,
                        threshold=self.threshold)
        except Exception:  # lint: broad-except-ok (planning reads the
            # shard ledger's merged sketches — telemetry; a failure
            # skips this tick's actions, never the pipeline)
            return {"ops": []}

    def _note(self, op: str, event: str, detail: str) -> None:
        self.timeline.append({"t_usec": current_time_usecs(),
                              "op": op, "event": event,
                              "detail": detail})

    def preagg_folds(self) -> int:
        total = 0
        for t in self._targets.values():
            for em in t["emitters"]:
                total += getattr(em, "preagg_folds", 0)
        return total

    def section(self) -> dict:
        """stats()["Reshard"] / OpenMetrics / postmortem payload."""
        return {
            "enabled": True,
            "ticks": self.ticks,
            "plans_applied": self.plans_applied,
            "keys_moved": self.keys_moved,
            "splits_applied": self.splits_applied,
            "moves_skipped": self.moves_skipped,
            "preagg_folds": self.preagg_folds(),
            "admission_factor": self._admission,
            "admission_throttles": self.admission_throttles,
            "scale_down_events": self.scale_down_events,
            "quiesce_ms": self.last_quiesce_ms,
            "quiesce_ms_total": round(self.quiesce_ms_total, 3),
            "recovery_ms": self.last_recovery_ms,
            "ops": {name: {"state": tr.state,
                           "last_action": tr.last_action,
                           "window_imbalance":
                               self._last_delta.get(name)}
                    for name, tr in self._tracks.items()},
            "timeline": list(self.timeline),
        }
