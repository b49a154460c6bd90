"""Diagnostic records shared by every static-analysis pass.

One record type surfaces everything the analysis subsystem finds: the
pre-flight graph checker (``analysis/preflight.py``), the hot-path AST
lint (``tools/wf_lint.py``), and the debug-mode race detector
(``analysis/debug_concurrency.py``).  WindFlow gets the same guarantees
from C++ template/concept errors at compile time; a Python/JAX framework
has no compiler seam, so the seam is built here: stable ``WFxxx`` codes,
a severity, the graph node or file:line the finding anchors to, and a fix
hint — machine-consumable (``to_json``) and human-readable (``__str__``)
from the same record.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional

from windflow_tpu.basic import WindFlowError

#: code -> (default severity, one-line description).  The table is the
#: contract: tests assert codes, docs/ANALYSIS.md renders it, and
#: tools/wf_check.py --json ships it.  Codes are append-only — a released
#: code never changes meaning.
CODES = {
    # -- abstract evaluation of operator chains (WF1xx) ----------------------
    "WF101": ("error", "operator kernel failed abstract evaluation "
                       "(dtype/shape mismatch in the chain)"),
    "WF102": ("error", "filter predicate must return a boolean scalar"),
    "WF103": ("error", "reduce combiner must preserve the record "
                       "structure, shapes and dtypes"),
    "WF104": ("error", "key extractor of a keyed device operator must "
                       "return an integer scalar"),
    "WF105": ("error", "window combiner must preserve the lifted "
                       "aggregate structure"),
    "WF106": ("warning", "merged branches deliver different record "
                         "structures"),
    # -- window specifications (WF2xx) ---------------------------------------
    "WF201": ("error", "window length and slide must be positive"),
    # warning, not error: hopping windows WITH gaps are a supported
    # semantic (the FFAT spec sweep exercises them against an oracle) —
    # but an accidental swap of (length, slide) silently drops gap
    # tuples, so it is surfaced loudly
    "WF202": ("warning", "window slide exceeds window length: tuples in "
                         "the gaps belong to no window"),
    "WF203": ("warning", "lateness on a count-based window is ignored"),
    "WF204": ("error", "window lateness must be non-negative"),
    # -- graph composition / routing (WF3xx) ---------------------------------
    "WF301": ("error", "operator follows a terminal (sink) operator"),
    "WF302": ("error", "pipeline does not end in a sink"),
    "WF303": ("error", "KEYBY routing requires a key extractor"),
    "WF304": ("error", "malformed graph composition"),
    # -- mesh / sharding (WF4xx) ---------------------------------------------
    "WF401": ("error", "staged batch capacity not divisible across the "
                       "mesh devices"),
    "WF402": ("error", "keyed state space not divisible by the mesh key "
                       "axis"),
    "WF403": ("error", "merged upstream paths deliver unequal fixed "
                       "batch capacities"),
    # key compaction (parallel/compaction.py):
    # a declared-bounded reduce without a monoid runs the SORTED path —
    # declared dense beats both sorting and the compacted remap
    "WF404": ("warning", "bounded key space declared but no monoid "
                         "combiner: the reduce takes the sorted path"),
    # the declared kind REPLACES the combiner on every specialized stage
    # (dense table, compacted remap, mesh collective) — a combiner that
    # provably diverges from it leafwise silently changes results there
    "WF405": ("warning", "declared monoid combiner diverges from the "
                         "user combiner on at least one record leaf"),
    # -- watermarks / time (WF5xx) -------------------------------------------
    "WF501": ("error", "EVENT time policy requires a timestamp "
                       "extractor on every source"),
    "WF502": ("error", "merge joins branches with mixed watermark modes"),
    "WF503": ("warning", "time-based windows fed by a watermark-less "
                         "source fire only at end-of-stream"),
    # -- durability / checkpoint-restore (WF6xx) -----------------------------
    "WF601": ("warning", "checkpointing enabled with a source that "
                         "cannot replay deterministically"),
    "WF602": ("error", "restore target graph mismatches the checkpoint "
                       "manifest topology"),
    "WF603": ("warning", "operator holds cross-batch state the "
                         "checkpoint cannot capture"),
    # rescale-on-restore (durability/rebucket.py, docs/DURABILITY.md
    # "Multi-chip checkpoints & rescale-on-restore"): a restore onto a
    # different mesh shape / shard count re-buckets keyed state through
    # the operator's declared key space or compaction remap — operators
    # providing neither refuse the shape change
    "WF604": ("warning", "keyed operator on a mesh checkpoints state "
                         "with no declared key space or compaction "
                         "remap: a shape-changing restore cannot "
                         "re-bucket it"),
    "WF605": ("error", "restore manifest shard shape cannot be "
                       "re-bucketed onto the target graph"),
    # wire plane (windflow_tpu/wire.py, docs/OBSERVABILITY.md "Wire
    # plane"): codec choice needs the lane semantics only a
    # declared/inferred record spec provides — a spec-less staging edge
    # under Config.wire_compression downgrades to raw passthrough, and
    # that downgrade is NAMED here instead of happening silently
    "WF606": ("warning", "wire compression downgraded to raw "
                         "passthrough: the staging edge has no "
                         "declared/inferred record spec"),
    # Pallas kernels (windflow_tpu/kernels):
    # ``WF_TPU_PALLAS=1`` forces the hand-written FFAT kernels on, but
    # three downgrades are built in — a backend with no lowering
    # (neither TPU Mosaic nor the CPU interpreter) keeps the lax path,
    # a MESH graph keeps it too (the shard_map step factories compose
    # lax bodies this round), and a window whose combiner is a GENERIC
    # traced function (no declared sum/max/min monoid) keeps the lax
    # sliding fold (only declared monoids ride the MXU pane combine).
    # Forcing makes those downgrades NAMED instead of silent, mirroring
    # WF606's raw-passthrough contract; "auto" picks silently.
    "WF607": ("warning", "Pallas kernels forced on but downgraded to "
                         "the lax path (unsupported backend, mesh "
                         "graph, or a generic combiner on the MXU "
                         "pane-combine path)"),
    # Megastep executor (windflow_tpu/megastep.py):
    # ``WF_TPU_MEGASTEP=K`` forces K staged sweeps folded into one
    # compiled scan program, but the fold only exists for a
    # single-dest device staging edge whose tail steps entirely on
    # device — a host operator, a mesh-sharded or host-interning
    # stateful tail, a compacted key space (host admission runs per
    # batch), or a spec-less source keeps the per-batch cadence.
    # Forcing makes that downgrade NAMED instead of silent — the
    # WF606/WF607 contract applied to the megastep plane.  "auto"
    # picks silently.
    "WF608": ("warning", "megastep forced on but the edge downgraded "
                         "to per-batch dispatch (host operator, mesh "
                         "or host-interning tail, compacted key "
                         "space, or spec-less source)"),
    # A count window counts a key's rows in the order they ARRIVE unless
    # it is built with withEventTimeOrder.  Behind a device operator
    # whose rows close where the data says (interval join, session
    # window) arrival order is the order of that operator's own sort, by
    # key, with held-back rows a step later: windows over it are not the
    # windows over event time, and nothing else says so.
    "WF609": ("warning", "count window in arrival order fed by a "
                         "device operator whose rows follow the data "
                         "(interval join / session window): its "
                         "windows are over that operator's hand-over "
                         "order, not event time"),
    # -- determinism for replay (WF61x, wfverify — analysis/tracecheck.py):
    #    kernels and callbacks of a durability-enabled graph must
    #    regenerate the committed prefix identically on replay
    #    (docs/DURABILITY.md "Determinism requirements") -------------------
    "WF611": ("warning", "RNG without an explicitly threaded key in a "
                         "kernel/callback of a checkpointed graph"),
    "WF612": ("warning", "wall-clock read in a kernel/callback of a "
                         "checkpointed graph"),
    "WF613": ("warning", "id()/hash() identity dependence in a "
                         "kernel/callback of a checkpointed graph"),
    "WF614": ("warning", "set iteration-order dependence in a "
                         "kernel/callback of a checkpointed graph"),
    # -- hot-path lint (WF7xx, emitted by tools/wf_lint.py) ------------------
    "WF701": ("error", "allocation inside a @hot_path function"),
    "WF702": ("error", "host synchronization inside a @hot_path function"),
    "WF703": ("error", "lock acquisition inside a @hot_path function"),
    "WF711": ("error", "bare except"),
    "WF712": ("error", "broad 'except Exception' without an allowlist "
                       "justification"),
    "WF721": ("error", "lock-guarded attribute accessed outside its "
                       "declared lock"),
    # -- wfverify: object-level static verification of the actual
    #    function objects handed to device operators plus the
    #    framework's wf_jit wrapper bodies (analysis/tracecheck.py) --------
    "WF800": ("warning", "wfverify pass failed internally and was "
                         "skipped (analysis degraded, graph unchecked "
                         "by the object-level verifier)"),
    # trace-safety (WF80x)
    "WF801": ("error", "host materialization of a traced value inside a "
                       "jit-traced kernel"),
    "WF802": ("error", "Python control flow on a traced value inside a "
                       "jit-traced kernel"),
    "WF803": ("warning", "mutation of closure/global/default-arg state "
                         "inside a jit-traced kernel (trace-time side "
                         "effect)"),
    "WF804": ("warning", "print() inside a jit-traced kernel (runs at "
                         "trace time only; use jax.debug.print)"),
    # recompile hazards (WF81x) — the static twin of the wf_jit
    # recompile-storm tripwire (monitoring/jit_registry.py)
    "WF811": ("warning", "trace-time value that can vary per call baked "
                         "into a jit-traced kernel (stale constant / "
                         "recompile driver)"),
    "WF812": ("warning", "data-dependent output shape inside a "
                         "jit-traced kernel (fails to trace or "
                         "recompiles per batch)"),
    # donation safety (WF82x) — the static twin of the sweep ledger's
    # donation-miss audit (monitoring/sweep_ledger.py)
    "WF821": ("error", "donated operand read after dispatch (the buffer "
                       "is dead once the compiled program owns it)"),
    # -- wfir: IR-level audit of the LOWERED StableHLO of every wf_jit
    #    program (analysis/ir_audit.py, tools/wf_ir.py).  The preflight
    #    checker reasons about the composed graph and wfverify about the
    #    Python source; this family is proved on the module XLA actually
    #    compiles — captured from the registry's existing first-compile
    #    lowering, zero extra compiles (docs/ANALYSIS.md "wfir") -----------
    "WF900": ("warning", "ir-audit pass failed internally and was "
                         "skipped (analysis degraded, lowered programs "
                         "unchecked)"),
    "WF901": ("error", "cross-chip collective in a program on an edge "
                       "the aligned-ingest plan promised (or would "
                       "make) collective-free"),
    "WF902": ("error", "host callback / infeed-outfeed custom call "
                       "inside a hot-path program"),
    # a warning (as docs/ANALYSIS.md has always listed it): the
    # framework's own timestamp lanes are int64, so on a TPU every
    # ts-carrying program trips it — as an error it failed the preflight
    # of a process's second mesh graph on the first real 4-chip run
    "WF903": ("warning", "f64/i64 values survived into a TPU-targeted "
                         "program past the compiled-dtype gates"),
    "WF904": ("warning", "dynamic-shape op in the lowered module (IR "
                         "twin of the WF812 recompile hazard)"),
    "WF905": ("error", "donation miss at IR level: donated operands "
                       "with no input-output aliasing in the lowered "
                       "module"),
    "WF906": ("warning", "mid-program device<->host transfer (scalar "
                         "D2H sync) in the lowered module"),
    "WF907": ("warning", "Pallas kernel lowered without a Mosaic "
                         "custom call on a compiled backend "
                         "(interpret/lax fallback — the WF607 "
                         "downgrade, proven on the IR)"),
}


@dataclasses.dataclass
class Diagnostic:
    """One analysis finding.

    ``node`` names the graph operator (pre-flight passes) and ``location``
    carries ``file:line`` (lint passes); either may be None — the two
    anchor styles share the record so ``wf_check --json`` and
    ``wf_lint --json`` emit the same schema.
    """

    code: str
    message: str
    node: Optional[str] = None
    location: Optional[str] = None
    hint: Optional[str] = None
    severity: str = ""

    def __post_init__(self) -> None:
        if not self.severity:
            self.severity = CODES.get(self.code, ("error",))[0]

    def to_json(self) -> dict:
        return {
            "code": self.code,
            "severity": self.severity,
            "message": self.message,
            "node": self.node,
            "location": self.location,
            "hint": self.hint,
        }

    def __str__(self) -> str:
        where = self.location or (f"node '{self.node}'" if self.node
                                  else "graph")
        s = f"{self.code} [{self.severity}] {where}: {self.message}"
        if self.hint:
            s += f" (hint: {self.hint})"
        return s


class PreflightWarning(UserWarning):
    """Carrier for warning-severity pre-flight diagnostics (and for
    error-severity ones under ``Config.preflight = "warn"``)."""


class PreflightError(WindFlowError):
    """Raised by ``PipeGraph.start()`` under ``Config.preflight="error"``
    when the checker finds error-severity diagnostics.  Carries ALL of
    them — the message lists every violation, not just the first."""

    def __init__(self, diagnostics: List[Diagnostic]) -> None:
        self.diagnostics = list(diagnostics)
        n = len(self.diagnostics)
        lines = "\n  ".join(str(d) for d in self.diagnostics)
        super().__init__(
            f"pre-flight check found {n} error(s) "
            f"(Config.preflight='warn'/'off' to bypass):\n  {lines}")
