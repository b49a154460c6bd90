"""wfir: static audit of the LOWERED StableHLO of every wf_jit program.

The preflight checker (analysis/preflight.py) reasons about the composed
graph abstractly and wfverify (analysis/tracecheck.py) walks the Python
AST of the user kernels — neither ever inspects the module XLA actually
compiles.  The contracts that live *below* the source level — "the
aligned-ingest all_gather disappears", "no host callback hides in a
hot-path program", "the donated carry really aliases its output" — were
enforced only by runtime counters and structural models.  wfir closes
that gap: the compile watcher (monitoring/jit_registry.py) already calls
``Lowered = jit.lower(...)`` once per (op name, signature) for its cost
tables, and this module parses that SAME lowering's StableHLO text —
zero extra compiles, cold path only — into per-program **facts**
(collectives, callback custom calls, wide dtypes, dynamic shapes,
host transfers, aliased outputs, Mosaic custom calls), then interprets
the facts under graph context into the WF9xx diagnostics family
(analysis/diagnostics.py):

* **WF901** cross-chip collective on an edge the aligned-ingest plan
  promised (or would make) collective-free — the static twin of the
  shard ledger's modeled ICI drop;
* **WF902** host callback / infeed-outfeed inside a hot-path program;
* **WF903** f64/i64 surviving into a TPU-targeted program;
* **WF904** dynamic-shape ops (IR twin of wfverify's WF812);
* **WF905** donation miss at IR level: donated operands with zero
  input-output aliasing in the lowered module — cross-validated against
  the sweep ledger's runtime donation-miss counters;
* **WF906** mid-program device<->host transfer (scalar D2H sync);
* **WF907** a Pallas program that lost its Mosaic custom call on a
  compiled backend (the WF607 downgrade, proven on the IR).

Wired three ways like its sibling planes: ``stats()["IR_audit"]`` +
postmortem ``ir_audit.json`` (tools/wf_doctor.py renders it jax-free),
``PipeGraph.check()`` folds :func:`audit_graph` — including a dry-lower
of the user kernels over the preflight record specs — into the
preflight table, and ``tools/wf_ir.py --strict`` audits every shipped
graph in CI.  Kill switch ``Config.ir_audit`` / ``WF_TPU_IR_AUDIT=0``
leaves one flag check on the (already cold) first-compile path; capture
rides the cost-analysis lowering (``jit_registry.COST_MODE``).  Suppression shares wfverify's inline syntax: a
``# wfverify: ok (reason)`` on (or two lines above) the kernel's
``def`` line suppresses that operator's wfir findings, counted in the
report like tracecheck's.

Detectors match on STABLE mnemonics (``stablehlo.all_gather``,
``custom_call @xla_python_cpu_callback``, ``tf.aliasing_output``,
``tpu_custom_call``) with golden-substring fixtures in
``tests/test_ir_audit.py`` pinning them against jaxlib text drift.
"""

from __future__ import annotations

import json
import os
import re
import threading
import time
from typing import Dict, List, Optional

from windflow_tpu.analysis.diagnostics import Diagnostic

#: process-wide kill switch (the registry hook's one flag check);
#: Config.ir_audit gates the per-graph reporting planes on top
ENABLED = os.environ.get("WF_TPU_IR_AUDIT", "1").lower() \
    not in ("0", "", "false", "off")


def enabled(config=None) -> bool:
    """The audit gate: the process switch AND (when a config is given)
    the graph's ``Config.ir_audit``."""
    if not ENABLED:
        return False
    if config is None:
        return True
    return bool(getattr(config, "ir_audit", True))


# ---------------------------------------------------------------------------
# fact extraction from StableHLO text
# ---------------------------------------------------------------------------

#: cross-chip collective mnemonics (stablehlo dialect)
_COLLECTIVES = ("all_gather", "all_reduce", "all_to_all",
                "collective_permute", "reduce_scatter",
                "collective_broadcast")
_COLLECTIVE_RE = re.compile(
    r"stablehlo\.(" + "|".join(_COLLECTIVES) + r")\b")
#: custom_call target spellings (pretty @name form and the explicit
#: call_target_name attribute older/verbose printers emit)
_CUSTOM_CALL_RE = re.compile(
    r'custom_call\s*@(\w+)|call_target_name\s*=\s*"([^"]+)"')
#: a custom_call target that re-enters the host runtime
_CALLBACK_MARKERS = ("callback", "py_func", "host_func")
#: a custom_call target that is a Mosaic (Pallas TPU) kernel
_MOSAIC_MARKERS = ("tpu_custom_call", "mosaic")
#: ops that move data between device and host mid-program
_TRANSFER_RE = re.compile(r"stablehlo\.(send|recv)\b")
_INFEED_RE = re.compile(r"stablehlo\.(infeed|outfeed)\b")
#: dynamic-shape ops + unranked/dynamic dims in tensor types
_DYNAMIC_OP_RE = re.compile(
    r"stablehlo\.(dynamic_reshape|real_dynamic_slice|dynamic_pad|"
    r"dynamic_broadcast_in_dim|dynamic_gather|dynamic_iota|"
    r"dynamic_conv)\b")
_DYNAMIC_DIM_RE = re.compile(r"tensor<\?")
#: wide ELEMENT types of a tensor in a VALUE position: the type
#: signature after the last " : " of an op line (attribute tensors like
#: ``dense<0> : tensor<1xi64>`` live inside attr dicts mid-line, and
#: region-opening lines end "({" with only attribute types in tail)
_WIDE_RE = re.compile(r"tensor<[0-9x?]*?(f64|i64|ui64|c128)>")
#: input-output aliasing attributes jax emits for donated operands
_ALIAS_MARKERS = ("tf.aliasing_output", "jax.buffer_donor")
#: per-collective detail: which devices talk (replica_groups) and how
#: much data moves (the operand tensor) — WF901 classifies with these
_GROUPS_RE = re.compile(r"replica_groups\s*=\s*dense<(\[\[.*?\]\])>")
_TENSOR_RE = re.compile(r"tensor<([0-9x?]*)[a-z]")


def _wide_dtypes(text: str) -> List[str]:
    found = set()
    for line in text.splitlines():
        head = line.lstrip()
        if head.startswith("func.func"):
            sig = line  # arg/result types are inline annotations
        elif line.rstrip().endswith("({"):
            continue  # region op: its type lives on the matching "})"
        elif head.startswith(("%", "return", "})")):
            # the op's own type signature follows the last " : ";
            # attribute tensors (dense<...> : tensor<1xi64>) stay in
            # the attr dict this slices away
            tail = line.rsplit(" : ", 1)
            sig = tail[1] if len(tail) == 2 else ""
        else:
            continue
        for m in _WIDE_RE.finditer(sig):
            found.add(m.group(1))
    return sorted(found)


def _collective_ops(text: str) -> List[dict]:
    """One entry per collective-bearing line: the mnemonic, the parsed
    replica groups (None when unprintable), and the operand element
    count (None when dynamic/unparseable) — the detail
    :func:`cross_key_collectives` classifies WF901 with."""
    out = []
    lines = text.splitlines()
    for i, line in enumerate(lines):
        m = _COLLECTIVE_RE.search(line)
        if not m:
            continue
        entry = {"op": m.group(1), "groups": None, "numel": None}
        gm = _GROUPS_RE.search(line)
        if gm:
            try:
                entry["groups"] = json.loads(gm.group(1).replace(" ", ""))
            except ValueError:
                pass
        sig_line = line
        if line.rstrip().endswith("({"):
            # region-bearing collective (all_reduce / reduce_scatter
            # carry their combiner as a region): the op's own type
            # signature follows the region's closing "})" line — the
            # last " : " of the OPENING line is the replica_groups
            # attribute tensor, not the operand
            for j in range(i + 1, min(i + 64, len(lines))):
                if lines[j].lstrip().startswith("})"):
                    sig_line = lines[j]
                    break
            else:
                sig_line = ""
        sig = sig_line.rsplit(" : ", 1)
        if len(sig) == 2:
            tm = _TENSOR_RE.search(sig[1])
            if tm:
                dims = [d for d in tm.group(1).split("x") if d]
                if "?" not in dims:
                    n = 1
                    for d in dims:
                        n *= int(d)
                    entry["numel"] = n
        out.append(entry)
    return out


def cross_key_collectives(facts: dict, mesh=None) -> List[str]:
    """The collective mnemonics in ``facts`` that move NON-scalar data
    across ``mesh``'s key axis — the traffic aligned ingest eliminates,
    and the only collectives WF901 charges.  Excluded by design: scalar
    counter reduces (the drop-count psum telemetry every layout keeps)
    and within-column data-axis gathers (replica groups whose devices
    all share one key coordinate — aligned ingest shrinks them, never
    removes them).  Unparseable groups/operands classify conservatively
    as crossing."""
    ops = facts.get("collective_ops")
    if ops is None:
        return list(facts.get("collectives") or [])
    key_of = None
    if mesh is not None:
        try:
            import numpy as np
            from windflow_tpu.parallel.mesh import KEY_AXIS
            axis = mesh.axis_names.index(KEY_AXIS)
            key_of = {}
            for idx in np.ndindex(mesh.devices.shape):
                key_of[int(mesh.devices[idx].id)] = idx[axis]
        except Exception:  # lint: broad-except-ok (mesh introspection
            # over arbitrary Mesh objects; an unmappable mesh falls back
            # to the conservative no-coordinate classification)
            key_of = None
    out = set()
    for e in ops:
        numel = e.get("numel")
        if numel is not None and numel <= 1:
            continue
        groups = e.get("groups")
        if key_of is None or groups is None:
            out.add(e["op"])
            continue
        for grp in groups:
            if len({key_of.get(int(d)) for d in grp}) > 1:
                out.add(e["op"])
                break
    return sorted(out)


def extract_facts(text: str, donated_leaves: int = 0,
                  backend: Optional[str] = None) -> dict:
    """Parse one lowered module's StableHLO text into the context-free
    fact record every WF9xx interpretation reads.  Pure string work —
    no jax objects, so the same function runs over golden fixtures."""
    collectives = sorted({m.group(1)
                          for m in _COLLECTIVE_RE.finditer(text)})
    callbacks: List[str] = []
    mosaic_calls = 0
    for m in _CUSTOM_CALL_RE.finditer(text):
        target = (m.group(1) or m.group(2) or "").strip()
        low = target.lower()
        if any(s in low for s in _MOSAIC_MARKERS):
            mosaic_calls += 1
        elif any(s in low for s in _CALLBACK_MARKERS):
            if target not in callbacks:
                callbacks.append(target)
    infeed = sorted({m.group(1) for m in _INFEED_RE.finditer(text)})
    transfers = sorted({m.group(1) for m in _TRANSFER_RE.finditer(text)})
    dynamic = sorted({m.group(1) for m in _DYNAMIC_OP_RE.finditer(text)})
    if _DYNAMIC_DIM_RE.search(text):
        dynamic.append("dynamic_dimension")
    aliased = sum(text.count(marker) for marker in _ALIAS_MARKERS)
    return {
        "backend": backend,
        "collectives": collectives,
        "collective_ops": _collective_ops(text) if collectives else [],
        "callbacks": callbacks + infeed,
        "transfers": transfers,
        "wide_dtypes": _wide_dtypes(text),
        "dynamic": dynamic,
        "mosaic_calls": mosaic_calls,
        "aliased_outputs": aliased,
        "donated_leaves": int(donated_leaves),
    }


# ---------------------------------------------------------------------------
# the process-wide program store (fed by the registry's compile capture)
# ---------------------------------------------------------------------------

#: per-op cap on distinct recorded signatures — a recompile storm must
#: not grow the store unboundedly (the storm has its own tripwire)
MAX_SIGS_PER_OP = 16

_store: Dict[str, Dict[object, dict]] = {}
_store_lock = threading.Lock()


def record_lowered(op_name: str, sig, lowered) -> None:
    """Registry hook (``WfJit._capture_cost``): extract and store the
    facts of one just-lowered program.  Reuses the cost capture's
    ``Lowered`` — calling ``as_text()`` serializes the already-built
    module; nothing here compiles.  Raises propagate to the caller's
    guarded capture path (which warns once per op name)."""
    if not ENABLED:
        return
    import jax
    donated = 0
    try:
        for leaf in jax.tree_util.tree_leaves(lowered.args_info):
            if getattr(leaf, "donated", False):
                donated += 1
    except Exception:  # lint: broad-except-ok (args_info is a stages-API
        # detail that has drifted across jax versions; losing the donated
        # count only disarms WF905 for this program, never the capture)
        donated = 0
    facts = extract_facts(lowered.as_text(), donated_leaves=donated,
                          backend=jax.default_backend())
    with _store_lock:
        progs = _store.setdefault(op_name, {})
        if sig in progs or len(progs) < MAX_SIGS_PER_OP:
            progs[sig] = facts


def store_snapshot() -> Dict[str, List[dict]]:
    """op name -> recorded program facts (copy; tests and the process
    report read this)."""
    with _store_lock:
        return {name: list(progs.values())
                for name, progs in _store.items()}


def reset_store() -> None:
    """Drop every recorded program (tests)."""
    with _store_lock:
        _store.clear()


# ---------------------------------------------------------------------------
# fact -> diagnostic interpretation
# ---------------------------------------------------------------------------

def program_findings(op_name: str, facts: dict, *,
                     promised_collective_free: bool = False,
                     alignable_unaligned: bool = False,
                     expect_mosaic: bool = False,
                     cross_key: Optional[List[str]] = None
                     ) -> List[Diagnostic]:
    """WF9xx diagnostics for ONE program's facts under graph context.
    Context-free checks (WF902-WF906) always run; WF901/WF907 need the
    caller to say what the graph promised.  ``cross_key`` (from
    :func:`cross_key_collectives`) narrows WF901 to the collectives
    that actually cross the key axis; None falls back to every
    collective in the program."""
    out: List[Diagnostic] = []
    backend = facts.get("backend")
    coll = facts.get("collectives") if cross_key is None else cross_key
    if coll and (promised_collective_free or alignable_unaligned):
        what = ", ".join(coll)
        if promised_collective_free:
            msg = (f"program '{op_name}' lowered with cross-chip "
                   f"collective(s) [{what}] on an edge the aligned-"
                   "ingest plan promised collective-free")
            hint = ("the aligned sharded step regressed — the modeled "
                    "ICI drop (shard ledger) no longer holds on the "
                    "compiled IR")
        else:
            msg = (f"program '{op_name}' pays cross-chip collective(s) "
                   f"[{what}] on an edge aligned ingest would make "
                   "collective-free")
            hint = ("enable Config.key_aligned_ingest "
                    "(WF_TPU_KEY_ALIGNED=1) so the consumer takes "
                    "pre-placed lanes instead of the in-program gather")
        out.append(Diagnostic("WF901", msg, node=op_name, hint=hint))
    if facts.get("callbacks"):
        what = ", ".join(facts["callbacks"])
        out.append(Diagnostic(
            "WF902",
            f"program '{op_name}' re-enters the host mid-program: "
            f"callback/infeed custom call(s) [{what}] in the lowered "
            "module",
            node=op_name,
            hint="hot-path programs must stay on device; move the "
                 "callback to a sink/host operator or a sampled "
                 "diagnostic site"))
    if facts.get("wide_dtypes") and backend == "tpu":
        what = ", ".join(facts["wide_dtypes"])
        out.append(Diagnostic(
            "WF903",
            f"program '{op_name}' carries 64-bit values [{what}] on a "
            "TPU backend — past the compiled-dtype gates, these run "
            "emulated or force layout padding",
            node=op_name,
            hint="cast to f32/i32 before staging (the wire plane's "
                 "compiled-dtype gates do this for declared specs)"))
    if facts.get("dynamic"):
        what = ", ".join(facts["dynamic"])
        out.append(Diagnostic(
            "WF904",
            f"program '{op_name}' lowered dynamic-shape op(s) [{what}] "
            "— the compiled twin of a WF812 recompile hazard",
            node=op_name,
            hint="pad to fixed capacity; data-dependent shapes recompile "
                 "per batch or fail to trace on TPU"))
    if facts.get("donated_leaves", 0) > 0 \
            and facts.get("aliased_outputs", 0) == 0:
        out.append(Diagnostic(
            "WF905",
            f"program '{op_name}' declares {facts['donated_leaves']} "
            "donated operand leaf/leaves but the lowered module aliases "
            "none of them to an output — every donated buffer is "
            "copied, not reused",
            node=op_name,
            hint="donation needs matching shape/dtype between the "
                 "donated input and an output; the sweep ledger's "
                 "donation_miss counters show the bytes paid per batch"))
    if facts.get("transfers"):
        what = ", ".join(facts["transfers"])
        out.append(Diagnostic(
            "WF906",
            f"program '{op_name}' contains mid-program device<->host "
            f"transfer op(s) [{what}] — a scalar D2H sync serializes "
            "the dispatch pipeline",
            node=op_name,
            hint="return the scalar with the batch outputs and read it "
                 "at drain time instead"))
    if expect_mosaic and backend == "tpu" \
            and facts.get("mosaic_calls", 0) == 0:
        out.append(Diagnostic(
            "WF907",
            f"program '{op_name}' was built with Pallas kernels "
            "resolved ON but its lowered module contains no Mosaic "
            "custom call — the kernel fell back to interpret/lax on a "
            "compiled backend",
            node=op_name,
            hint="the WF607 downgrade, proven on the IR: check "
                 "Config.pallas_kernels and the kernel support gates "
                 "(windflow_tpu/kernels)"))
    return out


# ---------------------------------------------------------------------------
# graph-level report
# ---------------------------------------------------------------------------

class IRAuditReport:
    """One audit's result: programs audited, WF9xx diagnostics, the
    operators whose programs are not lowered yet, and the pass cost."""

    def __init__(self) -> None:
        self.programs_audited = 0
        self.dry_lowered = 0
        self.findings: List[Diagnostic] = []
        self.suppressed = 0
        self.pending: List[str] = []
        self.check_ms = 0.0
        #: every wf_jit op name claimed by this graph's wrappers —
        #: wf_ir's orphan sweep audits the store entries NO graph claims
        #: (framework programs: staging pack/unpack etc.)
        self.op_names: set = set()

    @property
    def diagnostics(self) -> List[Diagnostic]:
        return self.findings

    def to_json(self) -> dict:
        return {
            "programs_audited": self.programs_audited,
            "dry_lowered": self.dry_lowered,
            "findings": [d.to_json() for d in self.findings],
            "suppressed": self.suppressed,
            "pending": sorted(self.pending),
            "check_ms": round(self.check_ms, 3),
        }


def _graph_ops(graph) -> list:
    seen, out = set(), []
    for mp in graph._all_pipes():
        for op in mp.operators:
            if id(op) not in seen:
                seen.add(id(op))
                out.append(op)
    return out


def _collective_context(graph, op) -> tuple:
    """(promised, alignable_unaligned) for WF901: ``promised`` when the
    aligned-ingest plan stamped this consumer collective-free,
    ``alignable_unaligned`` when the consumer QUALIFIES for aligned
    ingest but runs without it (kill switch / downgrade) — the case
    where a collective in the IR is provably avoidable."""
    if getattr(graph.config, "mesh", None) is None:
        return False, False
    if getattr(op, "_ingest_mode", None) == "aligned":
        return True, False
    try:
        from windflow_tpu.basic import RoutingMode
        from windflow_tpu.parallel.mesh import _aligned_slot_bound
        alignable = (getattr(op, "is_tpu", False)
                     and _aligned_slot_bound(op) is not None
                     and op.routing == RoutingMode.KEYBY
                     and op.parallelism == 1)
    except Exception:  # lint: broad-except-ok (eligibility probes
        # arbitrary operator attrs; an unknown op kind is simply not
        # alignable, never an audit crash)
        alignable = False
    return False, alignable


def _expect_mosaic(op) -> bool:
    """True when this operator's step programs were built with compiled
    (non-interpret) Pallas kernels resolved on — the WF907 expectation.
    Conservative: only the kernel-bearing operator families, and only
    when the resolved mode is Mosaic (never the CPU interpreter)."""
    try:
        from windflow_tpu.kernels import resolve_pallas_for
        from windflow_tpu.ops.tpu import ReduceTPU
        from windflow_tpu.windows.ffat_tpu import FfatWindowsTPU
        if not isinstance(op, (FfatWindowsTPU, ReduceTPU)):
            return False
        mode = resolve_pallas_for(op)
        return mode is not None and not mode.interpret
    except Exception:  # lint: broad-except-ok (kernel-plane probe over
        # arbitrary operators; no expectation beats a crashed audit)
        return False


def _suppression_anchor(op):
    """(path, lineno) of the operator's primary user callable, or None —
    the site a ``# wfverify: ok (reason)`` suppresses wfir findings at
    (shared syntax with tracecheck)."""
    import inspect
    for attr in ("fn", "comb", "lift", "key_extractor", "gen_fn"):
        fn = getattr(op, attr, None)
        if not callable(fn):
            continue
        try:
            path = inspect.getsourcefile(fn)
            _, lineno = inspect.getsourcelines(fn)
        except (OSError, TypeError):
            continue
        if path:
            return path, lineno
    return None


def _apply_suppression(op, findings: List[Diagnostic],
                       report: IRAuditReport) -> List[Diagnostic]:
    if not findings:
        return findings
    anchor = _suppression_anchor(op)
    if anchor is None:
        return findings
    try:
        from windflow_tpu.analysis.tracecheck import suppression_at
        state = suppression_at(*anchor)
    except Exception:  # lint: broad-except-ok (suppression lookup reads
        # user source files; unreadable source means no suppression)
        state = None
    if state == "ok":
        report.suppressed += len(findings)
        return []
    return findings


def _op_program_rows(op):
    """(op_name, facts) rows for every program this operator's live
    wrappers have had captured — the sweep ledger's wrapper walk keyed
    into the process store."""
    from windflow_tpu.monitoring.sweep_ledger import _op_wrappers
    rows, missing, names = [], [], set()
    for w in _op_wrappers(op):
        names.add(w.op_name)
        with _store_lock:
            progs = _store.get(w.op_name)
            facts_list = list(progs.values()) if progs else []
        if facts_list:
            for facts in facts_list:
                rows.append((w.op_name, facts))
        elif getattr(w, "dispatches", 0) > 0:
            # this wrapper RAN but the store has no record: its capture
            # failed or was skipped — unaudited, not clean (the registry
            # warned once).  A zero-dispatch wrapper was merely fused
            # away / never exercised and is not pending.
            missing.append(w.op_name)
    return rows, missing, names


def _dry_lower_kernel(op, in_spec, cap: int):
    """Best-effort dry lower of the operator's USER kernel over the
    preflight record spec: ``jax.jit(jax.vmap(fn)).lower(abstract)`` —
    ShapeDtypeStruct args, client-side lowering only, nothing compiles
    and the registry is never touched.  Returns StableHLO text or
    None."""
    import jax
    fn = getattr(op, "fn", None)
    if fn is None or getattr(op, "batch_fn", False):
        return None
    batched = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((cap,) + tuple(s.shape), s.dtype),
        in_spec)
    try:
        return jax.jit(jax.vmap(fn)).lower(batched).as_text()
    except Exception:  # lint: broad-except-ok (the kernel pass already
        # reported un-evaluable kernels as WF101; the dry lower is an
        # extra lens, not a second reporter of the same failure)
        return None


def audit_graph(graph, dry_lower: bool = True) -> IRAuditReport:
    """Audit every program of ``graph``'s operators: captured lowerings
    from the process store (programs the registry compiled for these
    operators' wrappers), plus — for operators whose step programs are
    not built yet — a dry lower of the user kernels over the preflight
    record specs.  Cold path: call at check()/stats/postmortem cadence."""
    t0 = time.perf_counter()
    report = IRAuditReport()
    if not enabled(getattr(graph, "config", None)):
        report.check_ms = (time.perf_counter() - t0) * 1e3
        return report
    import jax
    backend = jax.default_backend()
    mesh = getattr(graph.config, "mesh", None)
    in_specs = None
    for op in _graph_ops(graph):
        promised, alignable = _collective_context(graph, op)
        expect = _expect_mosaic(op)
        rows, missing, names = _op_program_rows(op)
        report.op_names |= names
        findings: List[Diagnostic] = []
        for op_name, facts in rows:
            report.programs_audited += 1
            findings.extend(program_findings(
                op_name, facts, promised_collective_free=promised,
                alignable_unaligned=alignable, expect_mosaic=expect,
                cross_key=cross_key_collectives(facts, mesh)))
        if not rows and getattr(op, "is_tpu", False) and dry_lower:
            # composed-but-unstarted graph: lower the user kernel over
            # the record spec so check() still sees IR before any run
            if in_specs is None:
                from windflow_tpu.analysis.preflight import (_UNKNOWN,
                                                             propagate_specs)
                in_specs, _ = propagate_specs(graph)
                unknown = _UNKNOWN
            spec = in_specs.get(id(op), unknown)
            if spec is not unknown:
                cap = graph.config.default_batch_size or 1
                for up in _graph_ops(graph):
                    if getattr(up, "output_batch_size", 0):
                        cap = up.output_batch_size
                        break
                text = _dry_lower_kernel(op, spec, cap)
                if text is not None:
                    report.dry_lowered += 1
                    report.programs_audited += 1
                    facts = extract_facts(text, backend=backend)
                    findings.extend(program_findings(
                        f"{op.name} (dry-lowered kernel)", facts,
                        promised_collective_free=promised,
                        alignable_unaligned=alignable,
                        cross_key=cross_key_collectives(facts, mesh)))
        if missing and not rows:
            report.pending.append(op.name)
        report.findings.extend(
            _apply_suppression(op, findings, report))
    report.check_ms = (time.perf_counter() - t0) * 1e3
    return report


def audit_orphans(claimed) -> IRAuditReport:
    """Context-free audit of the store entries NO audited graph's
    wrappers claimed — the framework's own programs (staging pack /
    unpack, flush paths of operators fused away).  ``claimed`` is the
    union of :attr:`IRAuditReport.op_names` over the graphs already
    audited; wf_ir runs this sweep last so every program the process
    compiled is covered exactly once."""
    t0 = time.perf_counter()
    report = IRAuditReport()
    if not ENABLED:
        report.check_ms = (time.perf_counter() - t0) * 1e3
        return report
    claimed = set(claimed)
    for op_name, facts_list in sorted(store_snapshot().items()):
        if op_name in claimed:
            continue
        report.op_names.add(op_name)
        for facts in facts_list:
            report.programs_audited += 1
            report.findings.extend(program_findings(op_name, facts))
    report.check_ms = (time.perf_counter() - t0) * 1e3
    return report


def process_report() -> IRAuditReport:
    """Context-free audit of EVERY program captured in this process
    (WF902-WF906 only;
    WF901/WF907 need graph context the process store does not keep)."""
    t0 = time.perf_counter()
    report = IRAuditReport()
    if not ENABLED:
        report.check_ms = (time.perf_counter() - t0) * 1e3
        return report
    for op_name, facts_list in sorted(store_snapshot().items()):
        for facts in facts_list:
            report.programs_audited += 1
            report.findings.extend(program_findings(op_name, facts))
    report.check_ms = (time.perf_counter() - t0) * 1e3
    return report
