"""The one traffic generator: a seeded replay ring of binary frames, handed
to the program as chunks on an open-loop schedule.

A mix file (``traffic/<mix>.json``) holds the parameters; nothing here
knows a cell by name.

``rate`` is tuples per second of wall time, or ``"always_due"``:

* a number (the ``steady`` kind): tuple *i* is created at
  ``anchor + (i - anchor_i) / rate`` of wall time, that time is its event
  timestamp, and a chunk is yielded once its LAST tuple's time has come —
  ``b""`` otherwise, so the driver sweep is never blocked and the schedule
  never slows with the system.  How late each chunk was pulled is
  recorded (``lags``).
* ``"always_due"`` (the ``saturated`` kind): the replay of a retained
  log.  A chunk is always due and tuple *i*'s timestamp is
  ``i / event_rate`` seconds of event time, so event time runs as fast as
  the system consumes.

The stream is the ring repeated: tuple *i* is ring record ``i % R`` with
its ts field overwritten as the chunk is handed over.
"""

from __future__ import annotations

import time
from typing import Callable, Iterator, List, Optional, Tuple

import numpy as np

ALWAYS_DUE = "always_due"
GRACE_S = 1.0


def frame_dtype(nv: int) -> np.dtype:
    """The frames wire format: int64 key, int64 ts, nv float64 values."""
    return np.dtype([("k", "<i8"), ("t", "<i8")]
                    + [(f"v{i}", "<f8") for i in range(nv)])


class OpenLoop:
    """Hands the ring to a ``FrameSource`` chunk by chunk.

    Phases: *warm-up* from the first pull (the schedule may be
    re-anchored after a stall, :meth:`reanchor`), then the *window* from
    :meth:`open_window` for ``seconds`` (never re-anchored), then the
    generator stops and the source sees end of stream (a steady mix
    first hands over the chunks that fell due before the close)."""

    def __init__(self, ring: np.ndarray, mix: dict, seconds: float,
                 chunk_records: int,
                 clock: Callable[[], float] = time.monotonic,
                 span: Optional[Callable[[str], object]] = None) -> None:
        self.ring = ring
        self.R = len(ring)
        self.C = int(chunk_records)
        self.seconds = float(seconds)
        self.clock = clock
        rate = mix["rate"]
        self.always_due = rate == ALWAYS_DUE
        self.rate = None if self.always_due else float(rate)
        self.event_rate = float(mix.get("event_rate", 0) or 0)
        if self.always_due and self.event_rate <= 0:
            raise ValueError("an always_due mix needs an event_rate")
        if not self.always_due and self.rate <= 0:
            raise ValueError("rate must be positive or 'always_due'")
        #: context manager factory naming what the host does in a pull
        #: (the traced run's idle-gap labels); None = no spans
        self._span = span
        self.pulled = 0                 # tuples handed over so far
        self.t_first: Optional[float] = None   # first pull (event-time 0)
        self._anchor_t = 0.0            # wall time of tuple _anchor_i
        self._anchor_i = 0
        self.reanchors = 0
        self.t_open: Optional[float] = None    # window start
        self.i_open: Optional[int] = None
        self.t_stop: Optional[float] = None    # generator stopped
        self.i_stop: Optional[int] = None
        #: (due, pulled_at) per chunk pulled inside the window
        self.lags: List[Tuple[float, float]] = []
        self.idle_yields = 0

    # -- schedule ---------------------------------------------------------
    def due_time(self, i: int) -> float:
        """Wall time at which tuple ``i`` is created (steady kind)."""
        return self._anchor_t + (i - self._anchor_i) / self.rate

    def creation_times(self, idx: np.ndarray) -> np.ndarray:
        """Creation wall time of the window's tuples ``idx`` (global
        stream indices >= ``i_open``); valid once the window is open,
        because the schedule is not re-anchored after that."""
        return self._anchor_t + (np.asarray(idx, np.float64)
                                 - self._anchor_i) / self.rate

    def reanchor(self, now: float) -> None:
        """Warm-up only: forgive the backlog a stall (a compile) left, so
        that the next tuple is due now."""
        if self.t_open is not None:
            raise RuntimeError("the window's schedule is never re-anchored")
        self._anchor_t, self._anchor_i = now, self.pulled
        self.reanchors += 1

    def lag_now(self, now: float) -> float:
        """Seconds by which the next chunk's last tuple is overdue (< 0:
        not due yet).  0 for an always-due mix."""
        if self.always_due:
            return 0.0
        return now - self.due_time(self.pulled + self.C - 1)

    def open_window(self, now: float) -> None:
        if not self.always_due:
            self._anchor_t, self._anchor_i = now, self.pulled
        self.t_open, self.i_open = now, self.pulled

    @property
    def t_close(self) -> float:
        return self.t_open + self.seconds

    def _owes(self, now: float) -> bool:
        """At the window's close: is a chunk that fell due inside the
        window still to be handed over?  A source that runs a few
        milliseconds late is owed its last chunks, for at most
        ``GRACE_S``; what is still owed then counts as failed."""
        return not self.always_due and now < self.t_close + GRACE_S \
            and self.due_time(self.pulled + self.C - 1) < self.t_close

    def due_in_window(self) -> int:
        """Tuples due inside the window: those pulled, when a chunk is
        always due; else those of the chunks whose last tuple was created
        inside it (the source is handed whole chunks)."""
        if self.always_due:
            return self.i_stop - self.i_open
        return int(self.seconds * self.rate) // self.C * self.C

    # -- the chunks_fn ----------------------------------------------------
    def _ts_usec(self, lo: int, n: int) -> np.ndarray:
        i = np.arange(lo, lo + n, dtype=np.int64)
        if self.always_due:
            return (i * 1_000_000) // int(self.event_rate)
        base = (self._anchor_t - self.t_first) * 1e6
        return (base + (i - self._anchor_i) * (1e6 / self.rate)) \
            .astype(np.int64)

    def _chunk(self, lo: int) -> bytes:
        a = lo % self.R
        if a + self.C <= self.R:
            out = self.ring[a:a + self.C].copy()
        else:
            out = np.concatenate([self.ring[a:], self.ring[:a + self.C
                                                           - self.R]])
        out["t"] = self._ts_usec(lo, self.C)
        return out.tobytes()

    def chunks(self) -> Iterator[bytes]:
        self.t_first = self._anchor_t = self.clock()
        idle = None
        while True:
            now = self.clock()
            if self.t_open is not None and now >= self.t_close \
                    and not self._owes(now):
                if idle is not None:
                    idle.__exit__(None, None, None)
                self.t_stop, self.i_stop = now, self.pulled
                return
            if not self.always_due:
                due = self.due_time(self.pulled + self.C - 1)
                if now < due:
                    self.idle_yields += 1
                    # one span over the whole stretch of sweeps in which
                    # nothing was due: it closes when a chunk is
                    if idle is None and self._span is not None:
                        idle = self._span("generator.idle")
                        idle.__enter__()
                    yield b""
                    continue
                if idle is not None:
                    idle.__exit__(None, None, None)
                    idle = None
                if self.t_open is not None:
                    self.lags.append((due, now))
            if self._span is None:
                buf = self._chunk(self.pulled)
            else:
                with self._span("source.pull"):
                    buf = self._chunk(self.pulled)
            self.pulled += self.C
            yield buf
