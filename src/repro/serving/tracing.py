"""Host spans and the tick log of the paged serving engine.

Every phase of ``PagedServingEngine.tick`` runs inside ``TickLog.phase``,
which feeds two sinks from the same boundaries:

  * a ``jax.profiler.TraceAnnotation`` named after the phase, with the
    phase's counters as its arguments, on the profiler's clock (so a
    device trace shows what the engine's thread was doing beside each
    device op); off the profiler it costs about a microsecond;
  * the current row of a fixed-size ring of the last ``size`` ticks: the
    milliseconds each phase took on the engine's injected clock, and the
    tick's counters.

Counters are host ints only: a device value in a span argument would
force a device->host sync. ``summary()`` is the operator view
(``stats()["tick_phases_ms"]``); ``rows()`` copies the ring out.
``latest()`` is the log made last in the process, which outlives its
engine: the ticks can still be read after the engine and its page pool
are freed.
"""
from __future__ import annotations

import contextlib
from typing import Callable, Dict, Iterator, List, Optional

import numpy as np
from jax.profiler import TraceAnnotation

TICK = "engine.tick"
#: every phase a row records, in the order a tick runs them; a tick's
#: phases after ``engine.tick`` nest inside it and do not overlap
PHASES = (TICK, "engine.expire", "engine.admit", "engine.prefill",
          "engine.decode.prep", "engine.decode.launch",
          "engine.decode.sync", "engine.decode.commit", "engine.audit")
#: the tick's counters; ``packed_bucket`` is 0 on a masked (full-width)
#: decode and on a tick without decode
COUNTERS = ("tick", "decode_rows", "packed_bucket", "prefill_tokens",
            "pages_grown", "preemptions")


class _Phase:
    """One open phase: a profiler span plus its share of the current row."""
    __slots__ = ("_log", "_col", "_span", "_t0")

    def __init__(self, log: "TickLog", col: int, span: TraceAnnotation):
        self._log, self._col, self._span = log, col, span

    def __enter__(self) -> "_Phase":
        self._span.__enter__()
        self._t0 = self._log._clock()
        return self

    def __exit__(self, *exc) -> None:
        log = self._log
        log._ms[self._col] += 1e3 * (log._clock() - self._t0)
        self._span.__exit__(*exc)


class TickLog:
    """Ring of the last ``size`` ticks: per tick the milliseconds of each
    phase and the tick's counters. The row of tick ``i`` is
    ``i % size``; the open tick's row is kept in plain lists and written
    to the ring when the tick closes."""

    def __init__(self, clock: Callable[[], float], size: int = 4096):
        self._clock = clock
        self.size = size
        self.ms = np.zeros((size, len(PHASES)), np.float64)
        self.counts = np.zeros((size, len(COUNTERS)), np.int64)
        self.n = 0                 # ticks closed
        self._col = {p: i for i, p in enumerate(PHASES)}
        self._cnt = {c: i for i, c in enumerate(COUNTERS)}
        self._ms = [0.0] * len(PHASES)        # the open tick's row
        self._counts = [0] * len(COUNTERS)
        global _LATEST
        _LATEST = self

    def phase(self, name: str, **counters: int) -> _Phase:
        """Time one phase into the open tick's row, inside a profiler span
        ``name`` whose arguments are ``counters``."""
        return _Phase(self, self._col[name], TraceAnnotation(name, **counters))

    @contextlib.contextmanager
    def tick(self, index: int) -> Iterator[None]:
        """Open the row of tick ``index`` inside an ``engine.tick`` span;
        the span's arguments are the row's counters as the tick left
        them."""
        self._ms = [0.0] * len(PHASES)
        self._counts = [0] * len(COUNTERS)
        self._counts[0] = index
        with TraceAnnotation(TICK) as span:
            t0 = self._clock()
            try:
                yield
            finally:
                self._ms[0] = 1e3 * (self._clock() - t0)
                row = self.n % self.size
                self.ms[row] = self._ms
                self.counts[row] = self._counts
                self.n += 1
                span.set_metadata(**dict(zip(COUNTERS, self._counts)))

    def count(self, **counters: int) -> None:
        """Add to the open tick's counters."""
        for k, v in counters.items():
            self._counts[self._cnt[k]] += int(v)

    def _live(self) -> np.ndarray:
        """Row indices of the ticks still in the ring, oldest first."""
        n = min(self.n, self.size)
        return (np.arange(self.n - n, self.n) % self.size)

    def rows(self, since: int = 0) -> List[Dict[str, object]]:
        """The ring's ticks with index >= ``since``, oldest first, each as
        its counters plus ``ms`` (phase -> milliseconds)."""
        out = []
        for r in self._live():
            c = self.counts[r]
            if c[0] < since:
                continue
            row: Dict[str, object] = {k: int(v) for k, v in zip(COUNTERS, c)}
            row["ms"] = {p: float(m) for p, m in zip(PHASES, self.ms[r])}
            out.append(row)
        return out

    def summary(self) -> Dict[str, Dict[str, float]]:
        """Per phase: mean, 95th percentile and max milliseconds per tick
        over the ring (a phase a tick did not run counts 0 there)."""
        ms = self.ms[self._live()]
        if not len(ms):
            return {}
        return {p: {"mean": float(ms[:, i].mean()),
                    "p95": float(np.percentile(ms[:, i], 95)),
                    "max": float(ms[:, i].max())}
                for i, p in enumerate(PHASES)}


_LATEST: Optional[TickLog] = None


def latest() -> Optional[TickLog]:
    """The tick log made last in this process, or None before the first."""
    return _LATEST
