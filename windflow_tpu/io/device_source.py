"""DeviceSource: batches generated ON DEVICE by a jitted program.

The reference's GPU sources still materialize tuples in host memory and
copy them in (``Batch_GPU_t`` staging, ``batch_gpu_t.hpp:51-229``); a TPU
source has a cheaper option the reference lacks: run the generator itself
as an XLA program so the batch is BORN in HBM and the host link never
carries the hot path.  Uses:

* synthetic feeds: a run fed this way pays framework dispatch only,
  decoupled from host→device link bandwidth;
* replay of device-resident datasets (arrays already in HBM);
* load generators for soak tests.

Device-born batches never touch the wire plane (windflow_tpu/wire.py):
there is no host→device transfer to compress.  ``batch_fn`` still matters to the wire plane indirectly: the
preflight spec walk infers this source's record spec from it
(``analysis/preflight.propagate_specs``), so a DeviceSource feeding a
host stage that later re-stages to a device edge keeps that edge
spec-known (no WF606 downgrade).

Contract: ``batch_fn(i)`` is JAX-traceable, maps the int32 batch index to
a payload pytree whose leaves have leading dimension ``capacity``; it is
jitted once and executed per tick.  Timestamps: INGRESS stamps the whole
batch with one monotone host arrival stamp (broadcast on device); EVENT
requires ``ts_fn(i) -> int64[capacity]`` (traced, fused into the same
program) plus ``wm_fn(i) -> int`` giving the batch's watermark frontier
on the host — the host never reads device lanes back to learn time.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from windflow_tpu.basic import RoutingMode, TimePolicy, WindFlowError, \
    current_time_usecs
from windflow_tpu.batch import DeviceBatch
from windflow_tpu.monitoring import recorder as flightrec
from windflow_tpu.monitoring.jit_registry import wf_jit
from windflow_tpu.ops.base import Operator
from windflow_tpu.ops.source import BaseSourceReplica, Source


class DeviceSourceReplica(BaseSourceReplica):
    def __init__(self, op: "DeviceSource", index: int) -> None:
        super().__init__(op, index)
        self._i = index              # replicas stride the batch index space
        self._jit = None

    def start(self) -> None:
        if self.time_policy == TimePolicy.EVENT \
                and (self.op.ts_fn is None or self.op.wm_fn is None):
            raise WindFlowError(
                f"device source '{self.op.name}': EVENT time policy needs "
                "both ts_fn (device lane) and wm_fn (host frontier)")
        if self.time_policy != TimePolicy.EVENT and self.op.ts_fn is not None:
            # event-time lanes under an INGRESS wall-clock watermark would
            # put every tuple eons behind the frontier — windows would
            # silently drop everything as late
            raise WindFlowError(
                f"device source '{self.op.name}': withTimestampFn requires "
                "the EVENT time policy (INGRESS stamps arrival time itself)")
        cap = self.op.capacity

        @flightrec.operator_scope(self.op.name)
        @flightrec.phase("wf.fn")
        def program(i, base_ts):
            payload = self.op.batch_fn(i)
            ts = (self.op.ts_fn(i).astype(jnp.int64)
                  if self.op.ts_fn is not None
                  else jnp.full((cap,), base_ts, jnp.int64))
            return payload, ts, jnp.ones((cap,), bool)

        self._jit = wf_jit(program, op_name=self.op.name)

    def tick(self, max_items: int) -> bool:
        """One device batch per tick (``max_items`` is a host-tuple notion;
        a device source's natural quantum is its compiled batch)."""
        if self._exhausted:
            return False
        if self._i >= self.op.n_batches:
            self._exhausted = True
            self._terminate()
            return True
        if self.time_policy == TimePolicy.INGRESS:
            base = max(current_time_usecs(), self._last_ts + 1)
            wm = base
            # every lane carries the same arrival stamp, so the data-ts
            # extrema are host-known for free — device-born batches then
            # feed the same preemptive TB ring sizing as staged batches
            # (DeviceBatch.ts_min/ts_max, windows/ffat_tpu
            # _regrow_for_span) without any device sync
            ts_lo = ts_hi = base
        else:
            base = 0
            wm = int(self.op.wm_fn(self._i))
            if self.op.ts_bounds_fn is not None:
                lo, hi = self.op.ts_bounds_fn(self._i)
                ts_lo, ts_hi = int(lo), int(hi)
            else:
                ts_lo = ts_hi = None    # unknown: eviction backstop only
        payload, ts, valid = self._jit(jnp.int32(self._i), jnp.int64(base))
        self._last_ts = max(self._last_ts, wm)
        self._advance_wm(self._last_ts)
        self.stats.outputs_sent += self.op.capacity
        self.stats.device_programs_launched += 1
        # device-born batches join the flight recorder's trace lane at
        # birth ("emitted" — nothing was staged over the host link)
        seq = self.emitter._new_seq()
        self.emitter.emit_device_batch(
            DeviceBatch(payload, ts, valid, watermark=self.current_wm,
                        size=self.op.capacity, ts_min=ts_lo, ts_max=ts_hi,
                        trace=self.emitter._trace_of(seq), seq=seq))
        self._i += self.op.parallelism
        self._count_toward_punctuation(self.op.capacity)
        return True


class DeviceSource(Source):
    """Source whose batches are generated on device (see module doc).

    ``n_batches`` bounds the stream; replicas stride the index space
    (replica r generates batches r, r+parallelism, ...)."""

    replica_class = DeviceSourceReplica

    def __init__(self, batch_fn: Callable, capacity: int, n_batches: int,
                 name: str = "device_source", parallelism: int = 1,
                 ts_fn: Optional[Callable] = None,
                 wm_fn: Optional[Callable[[int], int]] = None,
                 ts_bounds_fn: Optional[Callable] = None) -> None:
        if capacity <= 0 or n_batches < 0:
            raise WindFlowError(
                "device source needs capacity > 0 and n_batches >= 0")
        Operator.__init__(self, name, parallelism, routing=RoutingMode.NONE,
                          output_batch_size=capacity, is_tpu=True)
        self.batch_fn = batch_fn
        self.capacity = capacity
        self.n_batches = n_batches
        self.ts_fn = ts_fn
        self.wm_fn = wm_fn
        #: optional HOST fn ``i -> (ts_min, ts_max)`` bounding the event-
        #: time lane of batch ``i``: attaches the data-ts extrema that let
        #: downstream TB window rings size themselves preemptively
        #: (batch.py DeviceBatch.ts_min/ts_max) — without it, device-born
        #: EVENT batches rely on the eviction-cadence backstop
        self.ts_bounds_fn = ts_bounds_fn
        self.ts_extractor = None
