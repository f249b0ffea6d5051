"""Drive the engine through the measured window, and record what a client
would see: the time of every token of every request, on the harness's
clock after each ``tick()`` returns. Each tick runs inside the profiler
span ``bench.tick``, the harness's reading of the requests inside
``bench.poll``.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List

import numpy as np

from jax.profiler import TraceAnnotation


@dataclasses.dataclass
class Seen:
    """What the harness saw of one request."""
    req: object                 # the engine's Request
    tokens: List[float] = dataclasses.field(default_factory=list)
    n_out: int = 0              # tokens seen so far


@dataclasses.dataclass
class Window:
    """One measured window: its bounds, what each request saw, the context
    lengths each decode step served (for the per-layer readers), and per
    tick when it ended, the wall and thread CPU seconds inside
    ``tick()`` and the process's CPU seconds over the whole tick."""
    t0: float
    t1: float = 0.0
    seen: List[Seen] = dataclasses.field(default_factory=list)
    ticks: int = 0
    decode_steps: List[List[int]] = dataclasses.field(default_factory=list)
    tick_ends: List[float] = dataclasses.field(default_factory=list)
    tick_calls: List[tuple] = dataclasses.field(default_factory=list)

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


def _poll(seen: List[Seen], now: float) -> None:
    with TraceAnnotation("bench.poll"):
        for s in seen:
            n = len(s.req.out)
            if n > s.n_out:
                s.tokens.extend([now] * (n - s.n_out))
                s.n_out = n


def _contexts(before: Dict[int, int], seen: List[Seen]) -> List[int]:
    """Context length of every request a tick decoded: one that gained
    its k-th token was decoded at prompt + k - 1 (the new token
    included)."""
    return [len(s.req.prompt) + len(s.req.out) - 1 for s in seen
            if len(s.req.out) > before[id(s.req)]]


def run_ticks_until(eng, win: Window, seen: List[Seen], until: float,
                    clock: Callable[[], float]) -> None:
    """Tick until ``until``; the window closes after the tick that
    crosses it."""
    while clock() < until:
        before = {id(s.req): len(s.req.out) for s in seen}
        c0, p0 = time.thread_time(), time.process_time()
        a = clock()
        with TraceAnnotation("bench.tick"):
            eng.tick()
        b = clock()
        cpu = time.thread_time() - c0
        ctx = _contexts(before, seen)
        if ctx:
            win.decode_steps.append(ctx)
        _poll(seen, clock())
        win.ticks += 1
        win.tick_ends.append(clock())
        win.tick_calls.append((b - a, cpu, time.process_time() - p0))
    win.t1 = clock()


def longest_ticks(win: Window, n: int = 5) -> List[List[float]]:
    """The ``n`` longest intervals between consecutive tick ends (and from
    the window's start to the first), each as [ms, seconds into the
    window, ms inside ``tick()``, of which the engine's thread spent on
    the CPU, the process's CPU ms over the tick]."""
    ends = [win.t0] + win.tick_ends
    gaps = [[1e3 * (b - a), a - win.t0, 1e3 * t, 1e3 * c, 1e3 * p]
            for a, b, (t, c, p) in zip(ends, ends[1:], win.tick_calls)]
    return sorted(gaps, key=lambda g: -g[0])[:n]


def tail(values, q: float) -> float:
    """The q-th percentile (0-100) of ``values``, linear between ranks."""
    return float(np.percentile(np.asarray(values, np.float64), q))


def itls(win: Window) -> List[float]:
    """Every gap between consecutive tokens of a request, both inside the
    window."""
    out: List[float] = []
    for s in win.seen:
        ts = [t for t in s.tokens if win.t0 <= t <= win.t1]
        out.extend(b - a for a, b in zip(ts, ts[1:]))
    return out


def tokens_in(win: Window) -> int:
    return sum(sum(1 for t in s.tokens if win.t0 <= t <= win.t1)
               for s in win.seen)


def statuses(win: Window) -> Dict[str, int]:
    out: Dict[str, int] = {}
    for s in win.seen:
        out[str(s.req.status)] = out.get(str(s.req.status), 0) + 1
    return out
