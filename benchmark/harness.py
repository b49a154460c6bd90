"""One run of one cell: set-up, warm-up on the measured graph, the window,
the drain, the check against the reference, and the metrics.

Everything that belongs to one configuration, traffic mix or metric is a
file found by the name ``BENCHMARK.json`` gives it (see README.md); this
module holds only what every cell shares.
"""

from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import shutil
import sys
import time
from typing import Callable, Dict, List, Optional

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)

WARMUP_MIN_BATCHES = 16       # two megastep groups of K = 8
WARMUP_QUIET_BATCHES = 8      # no compile while this many were pulled
WARMUP_LIMIT_S = 900.0
STALL_S = 0.5                 # warm-up backlog forgiven beyond this
TRACE_S = 4.0

_modules: Dict[str, object] = {}


def load_module(kind: str, name: str, bench: str = BENCH):
    """``<bench>/<kind>/<name>.py`` as a module, found by name."""
    path = os.path.join(bench, kind, name + ".py")
    if path not in _modules:
        if not os.path.isfile(path):
            raise FileNotFoundError(f"no {kind} file for {name!r}: {path}")
        spec = importlib.util.spec_from_file_location(
            f"benchmark_{kind}_{name}".replace(".", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _modules[path] = mod
    return _modules[path]


def load_json(kind: str, name: str, bench: str = BENCH) -> dict:
    with open(os.path.join(bench, kind, name + ".json")) as f:
        return json.load(f)


def _applies(metric: dict, cell: str, reported: set) -> bool:
    """A metric with a ``workloads`` key is reported by those cells; an
    end-to-end metric without one by every cell, a per-layer metric
    without one by every cell that reports the metric it ``moves``."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return "moves" not in metric or metric["moves"] in reported


def resolve_cell(workload: str, root: str = ROOT) -> dict:
    """Everything ``BENCHMARK.json`` and the files it names say about one
    cell: its configuration, its traffic mix and the metrics it reports."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(has: {', '.join(sorted(cells))})")
    cell = cells[workload]
    bench = os.path.join(root, manifest["paths"][0])
    configs = {c["name"]: c for c in manifest["configs"]}
    cfg_path = os.path.join(root, configs[cell["config"]]["file"])
    with open(cfg_path) as f:
        cfg = json.load(f)
    e2e = [m for m in manifest["end_to_end"]
           if _applies(m, workload, set())]
    reported = {m["name"] for m in e2e}
    layer = [m for m in manifest["per_layer"]
             if _applies(m, workload, reported)]
    return {"name": workload, "chips": cell["chips"], "bench": bench,
            "config": cfg, "config_module":
                load_module("configs", cell["config"], bench),
            "mix": load_json("traffic", cell["traffic"], bench),
            "end_to_end": e2e, "per_layer": layer,
            "run_seconds": manifest["run_seconds"]}


def load_peaks(device_kind: str, bench: str = BENCH) -> dict:
    """Published peaks of the device; an unknown kind is an error."""
    with open(os.path.join(bench, "peaks.json")) as f:
        peaks = json.load(f)
    if device_kind not in peaks:
        raise KeyError(f"device kind {device_kind!r} is not in peaks.json")
    return peaks[device_kind]


def with_sizes(cfg: dict, sizes: Optional[dict]) -> dict:
    """The configuration with its ``graph``/``stream`` sizes overridden —
    for the rehearsal tests only; the command never passes any."""
    if not sizes:
        return cfg
    out = dict(cfg)
    for group in ("graph", "stream"):
        out[group] = {**cfg[group], **{k: v for k, v in sizes.items()
                                        if k in cfg[group]}}
    return out


class SinkRecorder:
    """The benchmark's columnar sink callback: stamps each delivered batch
    with the host's monotonic clock and keeps its columns."""

    def __init__(self, span: Callable[[str], object]) -> None:
        self.stamps: List[float] = []
        self.cols: List[dict] = []
        self._span = span

    def __call__(self, c) -> None:
        if c is None:
            return
        with self._span("sink.callback"):
            self.stamps.append(time.monotonic())
            self.cols.append(c.cols)

    def column(self, name: str) -> np.ndarray:
        return np.concatenate([np.asarray(b[name]) for b in self.cols])

    def rows_per_batch(self) -> np.ndarray:
        return np.array([len(b["key"]) for b in self.cols], dtype=np.int64)


def compiles_so_far() -> int:
    """Programs the jit registry has seen compile in this process."""
    from windflow_tpu.monitoring.jit_registry import default_registry
    return default_registry().totals()["compiles"]


def marks(gen, sweeps: int) -> dict:
    """What is cheap to read at an instant inside the window."""
    return {"t": time.monotonic(), "pulled": gen.pulled, "sweeps": sweeps,
            "compiles": compiles_so_far()}


def compiled_programs() -> Dict[str, int]:
    """op name -> programs compiled so far in this process."""
    from windflow_tpu.monitoring.jit_registry import default_registry
    return {n: e["compiles"]
            for n, e in default_registry().snapshot().items()}


def counters(g) -> dict:
    """The program's counts (``g.stats()``).  Read outside the window —
    before it opens and after the graph has drained — because the call
    costs milliseconds to tenths of a second."""
    st = g.stats()
    edges = st["Megastep"]["edges"]
    return {"throttle_events": st["Backpressure_throttle_events"],
            "h2d_bytes": st["Bytes_H2D_total"],
            "d2h_bytes": st["Bytes_D2H_total"],
            "dropped": st["Dropped_tuples"],
            "wire_batches": st["Staging"]["Wire"].get("batches", 0),
            "megastep_scanned": sum(e["batches"] for e in edges),
            "megastep_per_batch": sum(e["fallback_batches"]
                                      + e["warmup_batches"] for e in edges),
            "wire_reseeds": st["Staging"]["Wire"].get("reseeds", 0),
            "wire_raw_batches": st["Staging"]["Wire"].get("raw_batches", 0),
            "wire_fallback_lanes":
                st["Staging"]["Wire"].get("fallback_lanes", 0)}


def delta(a: dict, b: dict) -> dict:
    """Counts over the window: ``t`` and ``compiles`` end where the
    generator stopped, the others where the graph had drained."""
    return {k: b[k] - a[k] for k in a}


PRIME_BATCHES = 24            # three megastep groups ...
PRIME_S = 8.0                 # ... and so long after the first delivery


def prime_cache(cell: dict, mod, cfg: dict, ring: dict, mix: dict,
                chunk_records: int, cache_dir: str) -> bool:
    """First run of a cell against an empty compilation cache only: run a
    throw-away graph of the configuration on the cell's own traffic to
    an end of stream, so that the programs the warm-up phase cannot be
    sure to reach are compiled before the measured graph needs them: the
    window operator's flush at end of stream (it lies inside what
    ``tuples_per_s`` times), and the unpack variants of the half-filled
    batches that the program's punctuation flushes (one first met inside
    the window would stall it for the seconds a cold compile takes).  A
    marker under ``out/`` names the cache that was primed; a cache that
    was emptied or moved is primed again.  Returns whether it ran."""
    from benchmark.generator import OpenLoop
    marker = os.path.join(cell["bench"], "out", "primed", cell["name"])
    try:
        with open(marker) as f:
            if f.read() == cache_dir and os.path.isdir(cache_dir) \
                    and os.listdir(cache_dir):
                return False
    except OSError:
        pass
    n = PRIME_BATCHES * cfg["graph"]["batch"]
    gen = OpenLoop(ring["rec"], mix, 0.0, chunk_records)
    first_delivery: List[float] = []

    def sink(c):
        if c is not None and not first_delivery:
            first_delivery.append(time.monotonic())

    def chunks():
        for buf in gen.chunks():
            yield buf
            if gen.pulled >= n and first_delivery \
                    and time.monotonic() >= first_delivery[0] + PRIME_S:
                return

    mod.build_graph(cfg, ring, chunks, sink).run()
    os.makedirs(os.path.dirname(marker), exist_ok=True)
    with open(marker, "w") as f:
        f.write(cache_dir)
    return True


def _no_span(_name: str):
    return contextlib.nullcontext()


def run_cell(cell: dict, seed: int, seconds: float, trace: bool,
             t_process: float, devices, cache_dir: str,
             sizes: Optional[dict] = None, log=None) -> dict:
    """Run the cell once.  Returns the window record: times, counts, the
    checks with their limits, and what the metric readers need."""
    from benchmark.generator import OpenLoop
    log = log or (lambda msg: print(f"benchmark: {msg}", file=sys.stderr,
                                    flush=True))
    cfg = with_sizes(cell["config"], sizes)
    mod, mix = cell["config_module"], cell["mix"]
    batch = cfg["graph"]["batch"]

    span = _no_span
    if trace:
        import jax
        span = jax.profiler.TraceAnnotation

    t_phase = time.monotonic()

    def phase(what: str) -> None:
        nonlocal t_phase
        now = time.monotonic()
        log(f"set-up: {what} {now - t_phase:.2f}s")
        t_phase = now

    # the same seed gives the same inputs; numpy takes 32 bits and more
    ring = mod.make_ring(int(seed), cfg)
    phase("ring")
    rec = ring["rec"]
    # a mix hands the source chunks of so many bytes (whole records)
    chunk_records = max(1, mix["chunk_bytes"] // rec.dtype.itemsize)
    gen = OpenLoop(rec, mix, seconds, chunk_records, span=span)
    sink = SinkRecorder(span)
    if prime_cache(cell, mod, cfg, ring, mix, chunk_records, cache_dir):
        phase("empty compilation cache primed (throw-away graph)")
    g = mod.build_graph(cfg, ring, gen.chunks, sink)

    g.start()
    sweeps = 0
    t_start = time.monotonic()
    seen, mark_pulled, mark_deliv = compiles_so_far(), 0, 0
    while True:
        g.step()
        sweeps += 1
        now = time.monotonic()
        c = compiles_so_far()
        if c != seen:
            seen, mark_pulled, mark_deliv = c, gen.pulled, len(sink.stamps)
        lag = gen.lag_now(now)
        if lag > STALL_S:
            gen.reanchor(now)
        elif gen.pulled >= WARMUP_MIN_BATCHES * batch \
                and gen.pulled - mark_pulled >= WARMUP_QUIET_BATCHES * batch \
                and len(sink.stamps) > mark_deliv and lag <= 0.0:
            break
        if now - t_start > WARMUP_LIMIT_S:
            raise RuntimeError("warm-up did not settle: "
                               f"{seen} compiles, {gen.pulled} tuples pulled")
    at_open = counters(g)
    programs_at_open = compiled_programs()
    phase(f"warm-up on the measured graph ({gen.pulled} tuples, {seen} "
          f"compiles, {gen.reanchors} re-anchors)")
    gen.open_window(time.monotonic())
    at_open.update(marks(gen, sweeps))

    t_trace0 = gen.t_close - TRACE_S if trace else None
    at_trace0 = at_trace1 = None
    trace_dir = None
    while gen.t_stop is None:
        if trace and at_trace0 is None and time.monotonic() >= t_trace0:
            trace_dir = os.path.join(cell["bench"], "out", "trace",
                                     cell["name"])
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            at_trace0 = marks(gen, sweeps)
        # the sweep in which the generator stops also runs the end of
        # stream; the window's marks are those before it
        at_close = marks(gen, sweeps)
        g.step()
        sweeps += 1
    at_close["t"] = gen.t_stop
    compiled_in_run = {n: c - programs_at_open.get(n, 0)
                       for n, c in compiled_programs().items()
                       if c > programs_at_open.get(n, 0)}
    if at_trace0 is not None:
        at_trace1 = dict(at_close)
        jax.profiler.stop_trace()
    # the drain: the window's tuples still in flight are the window's
    # work, so its sweeps, bytes and throttle events count with it
    while not g.is_done():
        g.step()
        at_close["sweeps"] += 1
    g.wait_end()
    t_drained = time.monotonic()
    at_close.update(counters(g))
    dropped = at_close["dropped"]
    log(f"window: {gen.i_stop - gen.i_open} tuples pulled in "
        f"{gen.t_stop - gen.t_open:.2f}s, drained "
        f"{t_drained - gen.t_stop:.2f}s later, {len(sink.stamps)} deliveries")

    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    del g

    # -- the check, outside the window -----------------------------------
    t0 = time.monotonic()
    got = {name: sink.column(name) for name in ("key", "wid", "value")} \
        if sink.cols else {n: np.empty(0) for n in ("key", "wid", "value")}
    exp = mod.expected(cfg, ring, gen.pulled, mix)
    from benchmark import reference as ref
    checks = mod.compare(cfg, got, exp)
    checks.append(ref.check("dropped_tuples", dropped, 0))
    correct = ref.verdict(checks)

    stamps = np.asarray(sink.stamps)
    latencies_ms = None
    if correct and not gen.always_due:
        order = np.lexsort((got["wid"], got["key"]))
        row_stamp = np.repeat(stamps, sink.rows_per_batch())[order]
        i_end = gen.i_open + gen.due_in_window()
        sel = exp.full & (exp.closer >= gen.i_open) & (exp.closer < i_end)
        latencies_ms = (row_stamp[sel]
                        - gen.creation_times(exp.closer[sel])) * 1e3
    log(f"check: {time.monotonic() - t0:.2f}s, {len(exp.key)} rows")

    attempted = gen.due_in_window()
    failed = int(dropped)
    if not gen.always_due:
        failed += max(0, attempted - (gen.i_stop - gen.i_open))
    return {
        "cell": cell["name"], "config": cfg, "mix": mix, "batch": batch,
        "seconds": seconds, "correct": correct, "checks": checks,
        "attempted": int(attempted), "failed": failed,
        "setup_s": gen.t_open - t_process,
        "t_open": gen.t_open, "t_stop": gen.t_stop,
        "t_last_delivery": float(stamps[-1]) if len(stamps) else None,
        "tuples_in_window": gen.i_stop - gen.i_open,
        "n_total": gen.pulled, "rows": int(len(exp.key)),
        "lags": np.asarray(gen.lags, dtype=np.float64).reshape(-1, 2),
        "quiet_until": t_trace0 if trace else gen.t_stop,
        "latencies_ms": latencies_ms, "delivery_stamps": stamps,
        "open": at_open, "close": at_close,
        "trace0": at_trace0, "trace1": at_trace1, "trace_dir": trace_dir,
        "memory_peak_bytes": int(peak), "warmup_reanchors": gen.reanchors,
        # programs first compiled after the window opened (the sweep
        # that ends the stream included)
        "compiled_after_open": compiled_in_run,
    }


def lag_summary(window: dict) -> Optional[dict]:
    """How late the source pulled, by quarter of the window: the knee
    sweep reads it (README.md).  None for an always-due mix."""
    lags = window["lags"]
    if not len(lags):
        return None
    late = (lags[:, 1] - lags[:, 0]) * 1e3
    q = np.minimum((lags[:, 1] - window["t_open"]) // (window["seconds"] / 4),
                   3).astype(int)
    return {"median_by_quarter": [float(np.median(late[q == i]))
                                  if np.any(q == i) else None
                                  for i in range(4)],
            "p95": float(np.percentile(late, 95)), "max": float(late.max()),
            "batch_span": window["batch"] / window["mix"]["rate"] * 1e3}


def read_metrics(cell: dict, entries: List[dict], kind: str, trace,
                 window: dict) -> Dict[str, dict]:
    """Each metric's reader, found by the metric's name under ``kind``
    (``end_to_end`` or ``layer_metrics``).  A reader that finds nothing to
    read returns None and the metric is left out."""
    stats = delta(window["open"], window["close"])
    out = {}
    for m in entries:
        value = load_module(kind, m["name"], cell["bench"]) \
            .read(trace, stats, window)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
