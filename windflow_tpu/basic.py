"""Basic definitions: enums, defaults, and small shared helpers.

TPU-native re-design of the reference's basic definitions
(``/root/reference/wf/basic.hpp:78-87`` execution/time/window/routing enums,
``:189-206`` default knobs).  Where the reference configures everything through
compile-time macros, this framework uses a runtime :class:`Config` layer
(SURVEY.md §5.6 calls this out as a required replacement).
"""

from __future__ import annotations

import dataclasses
import enum
import os
import time
import zlib


class ExecutionMode(enum.Enum):
    """How replicas treat out-of-order inputs (reference ``basic.hpp:78``).

    * DEFAULT        – out-of-order processing gated by watermarks.
    * DETERMINISTIC  – inputs re-ordered by id/timestamp before processing, so
                       every run produces the same sequence of outputs.
    * PROBABILISTIC  – approximate ordering with an adaptive K-slack buffer;
                       tuples later than the slack are dropped (and counted).
    """

    DEFAULT = "default"
    DETERMINISTIC = "deterministic"
    PROBABILISTIC = "probabilistic"


class TimePolicy(enum.Enum):
    """Timestamping policy (reference ``basic.hpp:84``).

    * INGRESS – timestamps/watermarks assigned by the source shipper at entry.
    * EVENT   – timestamps supplied by the user (``push_with_timestamp``);
                watermarks are still monotonized by the shipper.
    """

    INGRESS = "ingress"
    EVENT = "event"


class WinType(enum.Enum):
    """Window domain (reference ``basic.hpp:80``): count-based or time-based."""

    CB = "count"
    TB = "time"


class RoutingMode(enum.Enum):
    """How an emitter distributes outputs (reference ``basic.hpp:87``)."""

    NONE = "none"
    FORWARD = "forward"
    KEYBY = "keyby"
    BROADCAST = "broadcast"
    REBALANCING = "rebalancing"


class WindowRole(enum.Enum):
    """Role of a window stage inside compound window operators
    (reference ``basic.hpp:219``): plain sequential, pane-level query,
    window-level query, map stage, reduce stage."""

    SEQ = "seq"
    PLQ = "plq"
    WLQ = "wlq"
    MAP = "map"
    REDUCE = "reduce"


class WindowEvent(enum.Enum):
    """Classification of a tuple w.r.t. one window
    (reference ``window_structure.hpp:49-115`` triggerer outcomes)."""

    OLD = "old"
    IN = "in"
    FIRED = "fired"


@dataclasses.dataclass
class Config:
    """Runtime configuration.  Replaces the reference's compile-time macro set
    (``WF_DEFAULT_VECTOR_CAPACITY``, ``WF_DEFAULT_WM_INTERVAL_USEC``,
    ``WF_DEFAULT_WM_AMOUNT``, ``WF_GPU_*`` — SURVEY.md §5.6) with values that
    can be set per-process or per-graph.
    """

    # Default device batch capacity (tuples per compiled step).  The TPU
    # analogue of the reference's GPU batch size: large enough to keep the
    # VPU/MXU busy, small enough to bound latency.
    default_batch_size: int = 4096
    # Punctuation (watermark flush) cadence for idle emitters, microseconds
    # (reference default 100 ms, basic.hpp:195).
    punctuation_interval_usec: int = 100_000
    # Punctuation cadence in number of inputs (reference default 1000,
    # basic.hpp:195).  0 disables the count trigger: a punctuation flushes
    # open/staged batches (the watermark must never overtake buffered data),
    # and unlike the reference — whose batches are at most a few hundred
    # tuples — TPU staging batches run to 10^5+ lanes, where a count cadence
    # below the batch capacity would chronically ship padded batches.  The
    # interval cadence above is what keeps idle streams firing.
    punctuation_amount: int = 0
    # Cap on outstanding device batches per operator before the host driver
    # throttles source ticks (reference: in-transit counter +
    # WF_GPU_FREE_MEMORY_LIMIT, recycling_gpu.hpp:88-126).  Each queued
    # DeviceBatch pins ~capacity x payload-width bytes of HBM, so this bounds
    # device memory the way the reference's FullGPUMemoryException retry does.
    max_inflight_batches: int = 8
    # Cap on total queued messages per replica inbox (host batches included)
    # before source throttling — the runtime analogue of the reference's
    # FF_BOUNDED_BUFFER bounded queues (README.md:36-39).
    max_inbox_messages: int = 8192
    # Tuples pulled from each live source per scheduler sweep; 0 means
    # "one staged batch worth" (the source's output_batch_size, or 256).
    source_tick_chunk: int = 0
    # Messages one replica may process per scheduler sweep; bounding this
    # interleaves sibling replicas fairly (the cooperative-loop analogue of
    # the reference's thread-parallel arrival order, which matters for the
    # KSlack collector's adaptive slack).
    sweep_drain_limit: int = 16
    # Directory where per-operator stats JSON logs are dumped at wait_end
    # (reference WF_LOG_DIR, basic_operator.hpp:297-303).
    log_dir: str = os.environ.get("WF_TPU_LOG_DIR", "log")
    # Dashboard endpoint (reference WF_DASHBOARD_MACHINE/PORT,
    # monitoring.hpp:184-196).
    dashboard_host: str = os.environ.get("WF_TPU_DASHBOARD_HOST", "localhost")
    dashboard_port: int = int(os.environ.get("WF_TPU_DASHBOARD_PORT", "20207"))
    # Enable runtime tracing (reference compile-time -DWF_TRACING_ENABLED).
    tracing_enabled: bool = bool(int(os.environ.get("WF_TPU_TRACING", "0")))
    # Flight recorder (monitoring/recorder.py): per-batch span tracing into
    # preallocated per-replica ring buffers + staged→sunk latency
    # histograms.  Default ON at 1-in-`trace_sample_every` batch sampling
    # with a documented <2% overhead budget (docs/OBSERVABILITY.md;
    # tests/test_observability.py asserts it); switching it off removes
    # every hook but a single `is not None` check per batch.
    flight_recorder: bool = bool(int(os.environ.get(
        "WF_TPU_FLIGHT_RECORDER", "1")))
    # 1-in-N batch sampling rate for span traces (N=1 traces everything —
    # tests/debugging only; the overhead budget assumes the default).
    trace_sample_every: int = int(os.environ.get("WF_TPU_TRACE_SAMPLE",
                                                 "64"))
    # Every M-th TRACED batch additionally records `device_done` by calling
    # block_until_ready on the operator's output — a real device sync, so
    # it runs 1 in (trace_sample_every * M) batches.  0 disables the sync
    # (spans then end at `dispatched`/`collected`).
    trace_device_sync_every: int = int(os.environ.get(
        "WF_TPU_TRACE_DEVICE_SYNC", "8"))
    # Host-side worker threads draining host-operator replicas in parallel
    # (reference: one OS thread per replica via FastFlow,
    # basic_operator.hpp:54-235, so a CPU-operator pipeline scales across
    # cores).  0 = the single cooperative dispatch loop (device-heavy
    # pipelines need nothing more — XLA dispatch is already async).  N > 0
    # = an N-thread pool drains host replicas each sweep; TPU replicas and
    # sources stay on the driver thread (stateful device ops share operator
    # state serialized by construction).  Host operators whose hot work is
    # numpy/native (GIL-releasing) scale near-linearly; pure-Python
    # per-tuple functions are GIL-bound, as in any CPython thread pool.
    host_worker_threads: int = int(os.environ.get("WF_TPU_HOST_WORKERS",
                                                  "0"))
    # Staging-plane lookahead (windflow_tpu/staging): extra source-tick
    # passes per scheduler sweep AFTER the drain phase, so batch N+1 is
    # packed into a (pooled) host staging buffer while batch N's
    # asynchronously dispatched XLA step still runs — the driver-loop form
    # of the reference's 2-deep pinned double buffering
    # (forward_emitter_gpu.hpp:254-300).  Each pass re-checks backpressure
    # first, so the in-transit caps above still bound lookahead depth.
    # 0 disables (sources tick once per sweep, pre-r6 behavior).
    stage_prefetch_depth: int = int(os.environ.get("WF_TPU_STAGE_PREFETCH",
                                                   "1"))
    # Profiler bridge (monitoring/device_metrics, docs/OBSERVABILITY.md):
    # directory PipeGraph.profile(duration_ms) writes its jax.profiler
    # capture into ("" = "{log_dir}/{name}_xprof").  With the flight
    # recorder on the capture holds the layer spans (wf.sweep, wf.parse,
    # wf.dispatch with op= and batch=, ...) on the device lines' clock.
    profiler_dir: str = os.environ.get("WF_TPU_PROFILER_DIR", "")
    # Pre-flight static analysis (windflow_tpu/analysis): PipeGraph.start()
    # runs PipeGraph.check() — abstract evaluation of the whole graph, zero
    # device work — and "error" fails fast with the FULL list of
    # error-severity diagnostics (warnings are warned), "warn" downgrades
    # everything to warnings, "off" skips the pass entirely.
    preflight: str = os.environ.get("WF_TPU_PREFLIGHT", "error")
    # Health plane (monitoring/health.py, docs/OBSERVABILITY.md): a
    # watchdog evaluated at monitor cadence (never per batch) derives a
    # per-operator OK/BACKPRESSURED/STALLED/FAILED state from the sampled
    # gauges, attributes stalls to a root-cause operator, and feeds the
    # postmortem bundle.  Off removes the plane entirely — every call
    # site keeps one `is not None` check.
    health_watchdog: bool = bool(int(os.environ.get("WF_TPU_HEALTH", "1")))
    # An operator with pending input whose progress counters (inputs
    # received, watermark frontier) have not moved for this long is
    # STALLED (microseconds).
    health_stall_grace_usec: int = int(os.environ.get(
        "WF_TPU_HEALTH_STALL_GRACE", "5000000"))
    # Summed replica inbox depth at/above which an operator that is still
    # making progress is BACKPRESSURED.  0 = derive from the in-transit
    # cap (max_inbox_messages // 2).
    health_backpressure_depth: int = int(os.environ.get(
        "WF_TPU_HEALTH_BP_DEPTH", "0"))
    # Compile-watcher recompiles per op name at/above which the operator
    # is flagged as in a recompilation storm (BACKPRESSURED verdict).
    health_recompile_storm: int = int(os.environ.get(
        "WF_TPU_HEALTH_RECOMPILE_STORM", "4"))
    # Black-box postmortem bundle directory written by
    # PipeGraph.dump_postmortem — best-effort on the wait_end crash path
    # and on watchdog-confirmed stalls ("" = "{log_dir}/{name}_postmortem";
    # tools/wf_doctor.py renders/validates a bundle offline).
    health_postmortem_dir: str = os.environ.get(
        "WF_TPU_HEALTH_POSTMORTEM_DIR", "")
    # Write the postmortem bundle automatically when wait_end crashes or
    # the watchdog confirms a stall (the bundle is exactly the telemetry
    # a crash used to discard).  dump_postmortem() stays callable either
    # way.
    health_postmortem_on_crash: bool = bool(int(os.environ.get(
        "WF_TPU_HEALTH_POSTMORTEM", "1")))
    # Latency ledger (monitoring/latency_ledger.py, docs/OBSERVABILITY.md
    # "Latency plane & SLO"): per-batch critical-path decomposition of the
    # flight recorder's span lane — each sampled batch's staged→emitted,
    # emitted→dispatched (the megastep K-wait), dispatched→device_done,
    # device_done→collected and collected→sunk segments land in
    # per-operator per-segment log2 histograms, plus window-freshness
    # gauges and the megastep freshness floor.  Harvested from the
    # existing rings only at monitor/stats cadence — zero new hot-path
    # work; off removes the plane entirely and every call site keeps one
    # `is not None` check (micro-asserted by tests/test_latency_plane.py).
    # Requires the flight recorder (off recorder -> no ledger).
    latency_ledger: bool = bool(int(os.environ.get("WF_TPU_LATENCY", "1")))
    # Declarative end-to-end latency target in milliseconds (0 = no SLO).
    # When set, the ledger evaluates the recent staged→sunk p99 against
    # the budget at watchdog cadence and the health plane raises an
    # SLO_VIOLATED verdict attributed to the dominant segment of the
    # dominant operator.
    latency_slo_ms: float = float(os.environ.get("WF_TPU_LATENCY_SLO_MS",
                                                 "0"))
    # Tenant plane (monitoring/tenant_ledger.py, docs/OBSERVABILITY.md
    # "Tenant plane"): the tenant label this graph's telemetry is
    # attributed under when N PipeGraphs share one process/mesh (ROADMAP
    # item 2 — the multi-tenant serving shape).  "" (the default)
    # resolves to the graph's own app name at build, so single-app
    # deployments need no configuration; several graphs sharing one
    # label pool their attribution under one tenant row.
    tenant: str = os.environ.get("WF_TPU_TENANT", "")
    # Kill switch for the tenant plane.  On, every graph registers into
    # the process-level tenant registry at build and the shared ledger
    # attributes HBM bytes, dispatches/compile wall-ms, H2D/D2H wire
    # bytes, modeled ICI bytes and latency budget share per tenant — all
    # read from telemetry the other planes already maintain, only at
    # monitor/stats cadence (zero per-batch hot-path work).  Off removes
    # the plane entirely and every call site keeps one `is not None`
    # check (micro-asserted by tests/test_tenant_plane.py).
    tenant_ledger: bool = bool(int(os.environ.get("WF_TPU_TENANT_LEDGER",
                                                  "1")))
    # Per-tenant HBM budget in bytes (0 = no budget declared).  When
    # set, the tenant ledger evaluates the tenant's attributed device
    # bytes against the budget at watchdog cadence; sustained overage
    # enters a latched OVER_BUDGET health verdict attributed to the
    # tenant's heaviest op (the SLO_VIOLATED contract applied to
    # memory), and analysis/tenancy.py + tools/wf_tenant.py turn the
    # measured pressure into a drain/rescale/throttle plan for a person
    # to read: nothing in the package executes it.
    hbm_budget_bytes: int = int(os.environ.get(
        "WF_TPU_HBM_BUDGET_BYTES", "0"))
    # Sweep ledger (monitoring/sweep_ledger.py, docs/OBSERVABILITY.md):
    # per-operator-hop attribution of jitted dispatches and XLA
    # cost-analysis HBM bytes per staged batch, donation-miss tripwires,
    # and hop-boundary residency (fusion fuel for tools/wf_advisor.py).
    # Evaluated only at stats/postmortem cadence from counters the
    # compile watcher already maintains — the per-batch cost is the
    # watcher's one integer add per dispatch, and switching the ledger
    # off leaves one `is not None` check at each read site.
    sweep_ledger: bool = bool(int(os.environ.get("WF_TPU_SWEEP_LEDGER",
                                                 "1")))
    # Shard plane (monitoring/shard_ledger.py, docs/OBSERVABILITY.md
    # "Shard plane"): per-shard/per-replica attribution of the gauges the
    # earlier planes only report per OPERATOR — queue depth, watermark
    # frontier/lag, service latency, HBM bytes — plus key-skew sketches
    # on the keyed edges (count-min + hot-key tables computed in-program
    # on the existing keys lane: folded into the keyby split / fused
    # chain programs, zero extra dispatches, merged to host only at
    # monitor cadence) and a reshard advisor
    # (analysis/resharding.py, tools/wf_shard.py).  Off removes the
    # plane entirely: no sketches attach and every call site keeps one
    # `is not None` check (micro-asserted by tests/test_shard_plane.py).
    shard_ledger: bool = bool(int(os.environ.get("WF_TPU_SHARD_LEDGER",
                                                 "1")))
    # Device-side key compaction (parallel/compaction.py):
    # keyed consumers over UNDECLARED int32 key spaces get a
    # device-resident key→dense-slot remap table — hot keys run the
    # dense scatter-combine / dense-slot stateful path, the cold tail
    # falls back to the sorted lane inside the SAME program (zero extra
    # dispatches), and the table is seeded from the shard plane's
    # count-min/hot-key sketches plus an in-program miss-candidate
    # ring.  Off removes the plane entirely: no compactor attaches and
    # every step keeps one `is not None` check (micro-asserted by
    # tests/test_key_compaction.py, same stance as the other planes).
    key_compaction: bool = bool(int(os.environ.get(
        "WF_TPU_KEY_COMPACTION", "1")))
    # Dense slots per compacted consumer (the remap table capacity):
    # hot keys get stable slots here; the cold tail overflows to the
    # sorted lane.  Stateful/FFAT consumers use their own slot bound
    # (num_key_slots / the compacted key space) instead.
    key_compaction_slots: int = int(os.environ.get(
        "WF_TPU_KEY_COMPACTION_SLOTS", "1024"))
    # Remap reseed cadence in consumer batches: every N-th batch the
    # compactor folds the sketch's hot candidates and the in-program
    # miss ring into the table (evicting the coldest slots on a full
    # table — the only churn source).  The only device sync the plane
    # pays, at this cadence.
    key_compaction_reseed: int = int(os.environ.get(
        "WF_TPU_KEY_COMPACTION_RESEED", "64"))
    # Wire compression (windflow_tpu/wire.py,
    # docs/OBSERVABILITY.md "Wire plane"): staged batches' packed
    # buffers are re-encoded lane by lane (delta/delta-of-delta for
    # monotone ts/id lanes, dictionary for low-cardinality int lanes,
    # constant collapse, bit-packing; raw passthrough fallback) before
    # the ONE fused host→device transfer, and the inverse decode is
    # traced INTO the existing unpack program — zero extra dispatches.
    # Engages only on edges with a declared/inferred record spec
    # (Source_Builder.withRecordSpec / DeviceSource inference); a
    # spec-less source downgrades to raw passthrough with a WF606
    # preflight warning.  Per-lane codec choice re-evaluates on the
    # key_compaction_reseed cadence and surfaces in
    # stats()["Staging"]["Wire"].  Default "auto": the plane attaches
    # whenever the default backend is a real accelerator, and each
    # staging edge then DECIDES BY MEASUREMENT whether it encodes: it
    # times its own link once (staging.probe_h2d) and the steady encode
    # pass of its first batch, and keeps the codec only if the link
    # time of the bytes saved exceeds the codec time — a host-attached
    # chip ships raw, a slow tunnel keeps the codec
    # (wire.WireEncoder).  Nothing attaches on the CPU fallback, where
    # host and "device" share memory and there is no link.
    # WF_TPU_WIRE=1 forces the codec anywhere, whatever the link (the
    # A/B tests do), =0 is the kill switch: no
    # encoder attaches and each staged batch keeps one flag check.
    # Typed loosely: True/False/"auto"/"1"/"0" all work
    # (wire.wire_enabled resolves it).
    wire_compression: object = os.environ.get("WF_TPU_WIRE", "auto")
    # Pallas TPU kernels for the FFAT hot loop (windflow_tpu/kernels):
    # hand-written kernels for segmented
    # grouping, the pane-level sliding fold, and the dense segmented
    # reduce drop into the hottest regions of the SAME wf_jit programs
    # the lax compositions occupied — zero dispatch-count change,
    # record-for-record identical output.  Default "auto": compiled
    # Mosaic kernels on TPU backends, interpret=True on the CPU
    # fallback so tier-1 executes the real kernel bodies (the
    # interpreter emulation is a correctness vehicle, not a perf path).
    # =1 forces (downgrades get a WF607 preflight
    # warning: non-TPU/CPU backends have no lowering, and windows with
    # GENERIC traced combiners keep the lax fold — only declared
    # sum/max/min monoids ride the MXU pane combine); =0 is the kill
    # switch restoring the lax path verbatim (no kernel builds, one
    # resolve per program build).
    pallas_kernels: object = os.environ.get("WF_TPU_PALLAS", "auto")
    # Device-resident sweep megastep (windflow_tpu/megastep.py):
    # fold K consecutive batch sweeps of a
    # host→TPU staged edge into ONE wf_jit program — a lax.scan over a
    # super-batch of K packed wire buffers whose body is the existing
    # fused per-sweep program (unpack decode + prelude + tail step), so
    # the host pacer pays one dispatch, one H2D stack, and one D2H
    # drain per K batches instead of per batch.  The fusion executor's
    # move lifted one level: per-sweep → per-K-sweeps.  Only edges whose
    # staging emitter feeds a single megastep-capable tail qualify
    # (FFAT windows, keyed/dense reduce, dense-key stateful — all
    # non-mesh, non-compacted); everything else keeps the per-batch
    # cadence.  Default "auto": K=8 on real accelerator backends, K=1
    # on the CPU fallback (tier-1 cadence unchanged).  An explicit
    # integer forces that K anywhere (tests set it directly);
    # graphs that cannot honor a forced K>1 downgrade to per-batch with
    # a WF608 preflight warning.  =1 is the kill switch: no plane
    # attaches and the per-batch path runs verbatim.  Durability epochs
    # round UP to a multiple of K (quiesce lands only on megastep
    # boundaries, keeping the chaos A/B diff meaningful).
    megastep_sweeps: object = os.environ.get("WF_TPU_MEGASTEP", "auto")
    # Key-aligned mesh ingest (parallel/emitters.AlignedMeshStageEmitter
    # + mesh.py ingest="aligned", docs/OBSERVABILITY.md "Wire plane"):
    # host-fed key-sharded FFAT consumers take their batches PRE-PLACED
    # on the owning key shard (the dense-range owner the sharded step
    # compiles; executor key moves deliberately do not apply — mesh
    # reshard routes through rescale-on-restore), killing the data-axis
    # all_gather the ICI model names dominant.  Off
    # (WF_TPU_KEY_ALIGNED=0) keeps the data-sharded ingest +
    # in-program gather everywhere.
    key_aligned_ingest: bool = bool(int(os.environ.get(
        "WF_TPU_KEY_ALIGNED", "1")))
    # IR-level program audit (analysis/ir_audit.py, tools/wf_ir.py,
    # docs/ANALYSIS.md "wfir"): parse the StableHLO text of every wf_jit
    # program off the compile watcher's EXISTING first-compile lowering
    # (the cost-table capture — zero extra compiles, cold path only) and
    # flag the WF9xx family: collectives on promised-collective-free
    # aligned-ingest edges (WF901), host callbacks/infeed (WF902),
    # f64/i64 on TPU (WF903), dynamic shapes (WF904), donation misses at
    # IR level (WF905), mid-program host transfers (WF906), and Pallas
    # programs that lost their Mosaic lowering (WF907).  Findings land in
    # stats()["IR_audit"], the postmortem's ir_audit.json, and the
    # preflight table; =0 is the kill switch — no capture, no parsing,
    # one flag check on the (already cold) first-compile path.
    ir_audit: bool = bool(int(os.environ.get("WF_TPU_IR_AUDIT", "1")))
    # Whole-chain fusion (windflow_tpu/fusion):
    # at graph build, maximal fusible runs of adjacent TPU operators
    # (the fusion advisor's plan — analysis/fusion.py) lower into ONE
    # wf_jit program per batch sweep: the stateless members' record
    # transforms are inlined ahead of the tail's program (map/filter
    # prelude before a window lift/combine, keyed reduce, or dense-key
    # stateful step), so the interior hop boundaries never materialize
    # in HBM and the chain pays one dispatch where it paid N.  Member
    # operators stay in the graph (stats/health/preflight contracts
    # unchanged; their numbers are attributed from the fused hop).
    # Fusion is skipped on a mesh (sharded program factories compose
    # differently) and for stateful tails that intern keys on the host.
    # Kill switch: WF_TPU_FUSE=0 restores one-dispatch-per-hop sweeps.
    whole_chain_fusion: bool = bool(int(os.environ.get("WF_TPU_FUSE",
                                                       "1")))
    # Durable state (windflow_tpu/durability, docs/DURABILITY.md): the
    # directory holding the graph's epoch-versioned checkpoint store.
    # Non-empty enables watermark-aligned checkpointing — at every
    # `durability_epoch_sweeps`-th scheduler sweep the driver quiesces the
    # graph (flush + drain to an aligned barrier), commits exactly-once
    # sink epochs (fenced Kafka commit / atomic file rename), snapshots
    # all operator state (FFAT rings, stateful tables, reduce states,
    # Kafka offsets, watermark frontiers) into the persistent LogKV, and
    # writes the epoch manifest as the commit point.  A stopped/crashed
    # graph rebuilds at the last complete epoch via PipeGraph.restore().
    # "" (the default) is the kill switch: the plane is never built and
    # the sweep loop keeps exactly one `is None` check (micro-asserted by
    # tests/test_durability.py, same stance as the health/ledger planes).
    durability: str = os.environ.get("WF_TPU_DURABILITY", "")
    # Checkpoint cadence in scheduler sweeps.  Sweep-counted (not
    # wall-clock) so two runs of the same graph over the same data place
    # their barriers at the same stream positions — what makes the chaos
    # harness's record-for-record A/B diff meaningful.
    durability_epoch_sweeps: int = int(os.environ.get(
        "WF_TPU_DURABILITY_EPOCH_SWEEPS", "64"))
    # Complete epochs retained in the checkpoint store; older epochs are
    # tombstoned (LogKV auto-compaction reclaims the log space).
    durability_keep: int = int(os.environ.get(
        "WF_TPU_DURABILITY_KEEP", "2"))
    # Reshard/failover executor (windflow_tpu/serving, docs/OBSERVABILITY.md
    # "Reshard executor"): closes the shard-plane loop — health-plane
    # BACKPRESSURED verdicts / sustained imbalance drive the reshard
    # advisor's move_keys plans live (quiesce → re-place the key→shard
    # override → resume, keyed state moved with the keys), split_hot_key
    # becomes a pre-aggregating partial combine at the keyed staging
    # boundary, and when no plan can help, admission control throttles the
    # sources instead of letting inboxes grow without bound.  Default OFF:
    # unlike the observe-only planes, the executor MUTATES routing —
    # opt in per deployment (WF_TPU_RESHARD=1).  Off leaves one
    # `is not None` check per sweep (micro-asserted).
    reshard_executor: bool = bool(int(os.environ.get(
        "WF_TPU_RESHARD", "0")))
    # Executor tick cadence in scheduler sweeps (each tick reads the
    # health verdicts + shard section — cadence-rate work, never per
    # batch) and the state-machine thresholds: consecutive bad ticks
    # before a plan applies, consecutive good ticks before an applied
    # plan counts as recovered (and admission control backs off).
    reshard_check_sweeps: int = int(os.environ.get(
        "WF_TPU_RESHARD_CHECK_SWEEPS", "32"))
    reshard_trigger_ticks: int = int(os.environ.get(
        "WF_TPU_RESHARD_TRIGGER_TICKS", "2"))
    reshard_ok_ticks: int = int(os.environ.get(
        "WF_TPU_RESHARD_OK_TICKS", "4"))
    # Imbalance ratio (max shard load / mean) above which the executor
    # treats an operator as degraded even without a health verdict —
    # the advisor's own actionability threshold.
    reshard_imbalance_threshold: float = float(os.environ.get(
        "WF_TPU_RESHARD_IMBALANCE", "1.25"))
    # Sustained-OK ticks before the executor consolidates keys off the
    # least-loaded shard (scale-down via the same quiesce→re-place
    # path).  0 (default) records scale-down candidates without acting.
    reshard_scale_down_ticks: int = int(os.environ.get(
        "WF_TPU_RESHARD_SCALE_DOWN_TICKS", "0"))
    # Calibration store (monitoring/calibration.py, tools/wf_calibrate.py,
    # docs/OBSERVABILITY.md "Calibration plane"): path of a versioned
    # calibration.json (probe-measured values for the modeled constants:
    # ICI B/s, H2D B/s, HBM B/s, dispatch overhead, sampled-sync
    # cost, kernel step time) keyed by device kind + jax version.  When
    # set, the shard ledger's ICI model, the tenant ledger and the live
    # roofline compute from the calibrated
    # constants and their provenance tags flip `modeled` →
    # `calibrated(<age>)`; stale past calibration.TTL_S (7
    # days) or a device-kind mismatch degrades back to `modeled` with
    # a one-time warning.  "" (default) runs uncalibrated;
    # WF_TPU_CALIBRATION=0 is the kill switch — no store loads anywhere
    # and every read site keeps one `is not None` check (micro-asserted
    # by tests/test_calibration.py).
    calibration: str = os.environ.get("WF_TPU_CALIBRATION", "")
    # Live roofline plane (monitoring/calibration.RooflineLedger): a
    # roofline decomposition as a monitor-cadence gauge —
    # per-hop achieved tup/s (deltas over counters the replicas already
    # keep; zero per-batch work) joined with the sweep ledger's
    # bytes/tuple and the calibrated bandwidth into stats()["Roofline"]
    # + wf_roofline_* OpenMetrics families, plus a latched advisory
    # ROOFLINE_DEGRADED health verdict when the dominant hop's
    # throughput collapses vs its own trailing baseline (the SLO
    # plane's enter/latch/clear hysteresis).  Requires the sweep ledger
    # for the bytes join (rates-only without it).  WF_TPU_ROOFLINE=0
    # removes the plane: no ledger attaches and each call site keeps
    # one `is not None` check (micro-asserted).
    roofline_plane: bool = bool(int(os.environ.get("WF_TPU_ROOFLINE",
                                                   "1")))
    # Multi-chip execution: a jax.sharding.Mesh with ("data", "key") axes
    # (see windflow_tpu.parallel.mesh.make_mesh).  When set, staging emitters
    # lay batches out data-sharded across the mesh and mesh-aware TPU
    # operators (FfatWindowsTPU, ReduceTPU) compile their sharded variants —
    # the mesh takes the role the reference fills with operator replication
    # over threads (SURVEY.md §2.6 item 10).  Requires output_batch_size
    # divisible by the data-axis extent and max_keys divisible by the
    # key-axis extent.  Typed Any so importing this module never imports jax.
    mesh: object = None


#: Process-wide default configuration; graphs copy it at construction so later
#: mutation does not affect running graphs.
default_config = Config()


def stable_hash(key) -> int:
    """Deterministic key hash (reference uses ``std::hash`` —
    ``keyby_emitter.hpp:216``).  Python's ``hash`` is salted for str/bytes,
    so use crc32 there to keep keyby placement (and Kafka partition
    placement, ``kafka/client.py``) reproducible across processes."""
    if isinstance(key, int):
        return key
    if isinstance(key, str):
        return zlib.crc32(key.encode())
    if isinstance(key, bytes):
        return zlib.crc32(key)
    return hash(key)


def int32_key(k) -> int:
    """Wrap a numeric key to the int32 value the device state collapses
    to (keyed device extractors cast to int32 on chip).  THE canonical
    copy: keyed routing (parallel/emitters.py), compaction admission,
    the reshard executor's state moves, and rescale re-bucketing
    (durability/rebucket.py) must all collapse exactly the same keys,
    or one logical key would straddle shards."""
    i = int(k) & 0xFFFFFFFF
    return i - (1 << 32) if i >= (1 << 31) else i


def current_time_usecs() -> int:
    """Monotonic-ish wall clock in microseconds (reference
    ``basic.hpp`` ``current_time_usecs``)."""
    return time.time_ns() // 1_000


#: Sentinel key used by non-keyed stateful operators
#: (reference ``empty_key_t``, basic.hpp:306-318).
EMPTY_KEY = 0


class WindFlowError(RuntimeError):
    """Raised for user/API misuse.  The reference aborts the process with a
    colored message (``basic_operator.hpp:269-272``); a library should raise."""
