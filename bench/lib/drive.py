"""Drive the engine through the measured window, and record what a client
would see: the time of every token of every request, on the harness's
clock after each ``tick()`` returns. Each tick runs inside the profiler
span ``bench.tick``, the harness's reading of the requests inside
``bench.poll``. In an open loop (``run_open_until``) requests are
submitted between ticks once they are due, inside ``bench.submit``.
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
    due: float = 0.0            # open loop: when it was due, and when
    submitted: float = 0.0      # submitted (harness's clock)


@dataclasses.dataclass
class Window:
    """One measured window: its bounds, what each request saw, the context
    lengths each decode step served (for the per-layer readers), per
    tick when it ended, the wall and thread CPU seconds inside
    ``tick()`` and the process's CPU seconds over the whole tick, and in
    an open loop the due times of requests due before the window closed
    that were never submitted."""
    t0: float
    t1: float = 0.0
    seen: List[Seen] = dataclasses.field(default_factory=list)
    ticks: int = 0
    decode_steps: List[List[int]] = dataclasses.field(default_factory=list)
    tick_ends: List[float] = dataclasses.field(default_factory=list)
    tick_calls: List[tuple] = dataclasses.field(default_factory=list)
    unsent: List[float] = dataclasses.field(default_factory=list)

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
        _tick(eng, win, seen, clock)
    win.t1 = clock()


def _tick(eng, win: Window, seen: List[Seen],
          clock: Callable[[], float]) -> None:
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


class Arrivals:
    """An open loop's requests in order of their due times (absolute, on
    the harness's clock), submitted to the engine once due. ``terminal``
    holds the statuses of an ended request, ``waiting`` that of one not
    yet admitted."""

    def __init__(self, reqs, dues, clock: Callable[[], float], terminal,
                 waiting):
        self.clock = clock
        self.terminal, self.waiting = terminal, waiting
        self.todo = sorted(zip(dues, range(len(reqs)), reqs))[::-1]
        self.active: List[Seen] = []      # submitted, not yet ended
        self.queued: List[int] = []       # after each tick: how many wait

    @property
    def next_due(self) -> float:
        return self.todo[-1][0] if self.todo else float("inf")

    def submit_due(self, eng, seen: List[Seen]) -> None:
        """Submit every request that is due; each joins ``seen``."""
        if self.next_due > self.clock():
            return
        with TraceAnnotation("bench.submit"):
            while self.next_due <= self.clock():
                due, _, req = self.todo.pop()
                s = Seen(req, due=due)
                eng.submit(req)
                s.submitted = self.clock()
                seen.append(s)
                self.active.append(s)


def run_open_until(eng, win: Window, seen: List[Seen], until: float,
                   clock: Callable[[], float], arrivals: Arrivals) -> None:
    """The open loop until ``until``: submit what is due, tick while a
    submitted request has not ended, else wait for the next due time. The
    window closes after the tick that crosses ``until``."""
    while clock() < until:
        arrivals.submit_due(eng, seen)
        arrivals.active = [s for s in arrivals.active
                           if s.req.status not in arrivals.terminal]
        if arrivals.active:
            _tick(eng, win, seen, clock)
            arrivals.queued.append(sum(
                1 for s in arrivals.active
                if s.req.status is arrivals.waiting))
        else:
            time.sleep(max(0.0, min(arrivals.next_due, until) - clock()))
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


def ttfts(win: Window) -> List[float]:
    """Open loop: for every request due inside the window, seconds from
    its due time to its first token, or to the window's end where none
    came by then."""
    out = [win.t1 - due for due in win.unsent if due >= win.t0]
    for s in win.seen:
        if win.t0 <= s.due < win.t1:
            first = s.tokens[0] if s.tokens else win.t1
            out.append(min(first, win.t1) - s.due)
    return out


def arrival_stats(win: Window, queued: List[int]) -> Dict[str, object]:
    """Open loop: requests due in the window, how late the harness
    submitted them (ms after due), and how many requests waited for a
    slot after each of the window's ticks (its first and last quarters'
    means tell a growing queue)."""
    late = [1e3 * (s.submitted - s.due) for s in win.seen
            if win.t0 <= s.due < win.t1]
    q = np.asarray(queued or [0], np.float64)
    k = max(len(q) // 4, 1)
    return {"due": len(late) + len(win.unsent),
            "unsent": len(win.unsent),
            "late_ms": {"mean": float(np.mean(late)) if late else None,
                        "max": max(late) if late else None},
            "waiting": {"first_quarter": float(q[:k].mean()),
                        "last_quarter": float(q[-k:].mean()),
                        "max": int(q.max()), "end": int(q[-1])}}


def tokens_in(win: Window) -> int:
    return sum(sum(1 for t in s.tokens if win.t0 <= t <= win.t1)
               for s in win.seen)


def statuses(win: Window) -> Dict[str, int]:
    out: Dict[str, int] = {}
    for s in win.seen:
        out[str(s.req.status)] = out.get(str(s.req.status), 0) + 1
    return out
