"""Reduction of a profiler trace to the numbers the per-layer metrics read.

``load_xplane`` turns the profiler's ``.xplane.pb`` into plain event lists
(so the reduction can be tested on a small recorded trace), and
``reduce_events`` computes, inside the host span ``bench.window``:

  * device busy time: the union of the intervals in which an operation ran
    (``XLA Ops`` line of each device plane), averaged over the devices;
  * device time per program call (``XLA Modules`` line);
  * device time per operation: only innermost operations count, so a
    layer loop (``while``) does not count its body twice. An operation is
    keyed ``<program>:<op>`` (``jit_decode_step:loki_decode_fused.9``);
  * the longest idle gaps, each labelled by what the host's main thread
    was doing at the gap's midpoint (the innermost ``bench.*`` span and
    the innermost other host event inside it).

On a TPU the ``XLA Ops`` events are named by their whole HLO instruction
(``%copy.34 = bf16[...] copy(...)``); the op's name is the part between
``%`` and `` = ``, and the whole text is kept for matching a kernel.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
import re
from typing import Dict, List, Tuple

WINDOW_SPAN = "bench.window"
_CALL_SUFFIX = re.compile(r"\(\d+\)$")
_HLO_NAME = re.compile(r"^%?([^\s=]+)")


def latest_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load_xplane(path: str) -> dict:
    """Events of an xplane file as plain lists:

    ``{"device": {plane: [[line, name, start_ns, end_ns]]},
       "host": [[name, start_ns, end_ns]]}``

    ``device`` keeps the ``XLA Modules`` and ``XLA Ops`` lines of every
    device plane; ``host`` the events of the host thread that carries the
    ``bench.window`` span."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    device: Dict[str, list] = {}
    host: List[list] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            rows = [[line.name, ev.name, float(ev.start_ns),
                     float(ev.start_ns) + float(ev.duration_ns)]
                    for line in plane.lines
                    if line.name in ("XLA Ops", "XLA Modules")
                    for ev in line.events]
            if rows:
                device[plane.name] = rows
        elif plane.name.startswith("/host:") and not host:
            for line in plane.lines:
                evs = [[ev.name, float(ev.start_ns),
                        float(ev.start_ns) + float(ev.duration_ns)]
                       for ev in line.events]
                if any(e[0] == WINDOW_SPAN for e in evs):
                    host = evs
                    break
    return {"device": device, "host": host}


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _leaves(ops: List[list]) -> List[list]:
    """The innermost operations: those that contain no later operation."""
    ops = sorted(ops, key=lambda r: (r[2], -r[3]))
    return [r for r, nxt in zip(ops, ops[1:] + [None])
            if nxt is None or nxt[2] >= r[3]]


def op_name(event_name: str) -> str:
    m = _HLO_NAME.match(event_name)
    return m.group(1) if m else event_name


def _host_label(host: List[list], t: float) -> str:
    """What the host thread was doing at time ``t``."""
    bench, other = None, None
    for name, a, b in host:
        if a <= t < b and name != WINDOW_SPAN:
            if name.startswith("bench."):
                if bench is None or a >= bench[1]:
                    bench = (name, a)
            elif other is None or a >= other[1]:
                other = (name, a)
    parts = [bench[0] if bench else "outside bench spans"]
    if other is not None and (bench is None or other[1] >= bench[1]):
        parts.append(other[0])
    return " > ".join(parts)


def reduce_events(events: dict, top: int = 10) -> dict:
    """Reduce ``load_xplane`` output to the traced window's numbers.
    Times in seconds. Raises ValueError when the window or the device
    events are missing: a trace that shows nothing must not read as an
    idle device."""
    spans = [e for e in events["host"] if e[0] == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"trace has no host span {WINDOW_SPAN!r}")
    t0, t1 = spans[0][1], spans[0][2]
    busy = []
    modules: Dict[str, List[float]] = collections.defaultdict(list)
    ops: Dict[str, float] = collections.defaultdict(float)
    op_calls: Dict[str, int] = collections.defaultdict(int)
    labels: Dict[str, str] = {}
    gaps: List[Tuple[float, float]] = []
    for _, rows in sorted(events["device"].items()):
        mods = sorted((a, b, _CALL_SUFFIX.sub("", name))
                      for line, name, a, b in rows if line == "XLA Modules")
        starts = [m[0] for m in mods]
        for a, b, name in mods:
            if t0 <= a < t1:
                modules[name].append((b - a) * 1e-9)
        op_rows = [r for r in rows if r[0] == "XLA Ops"] or \
            [r for r in rows if r[0] == "XLA Modules"]
        merged = _union([(max(a, t0), min(b, t1)) for _, _, a, b in op_rows
                         if b > t0 and a < t1])
        busy.append(sum(b - a for a, b in merged))
        for _, name, a, b in _leaves(op_rows):
            if not (t0 <= a < t1):
                continue
            i = bisect.bisect_right(starts, a) - 1
            prog = mods[i][2] if i >= 0 and a < mods[i][1] else "?"
            key = f"{prog}:{op_name(name)}"
            ops[key] += (min(b, t1) - a) * 1e-9
            op_calls[key] += 1
            labels.setdefault(key, name)
        edges = [t0] + [x for iv in merged for x in iv] + [t1]
        gaps += [((b - a) * 1e-9, (a + b) / 2)
                 for a, b in zip(edges[::2], edges[1::2]) if b > a]
    if not any(busy):
        raise ValueError("trace holds no device operation in the window")
    gaps.sort(key=lambda g: -g[0])
    return {
        "window_s": (t1 - t0) * 1e-9,
        "busy_s": sum(busy) / len(busy) * 1e-9,
        "modules": dict(modules),
        "ops": dict(ops),
        "op_calls": dict(op_calls),
        "op_labels": labels,
        "device_ops": sorted(([k, v] for k, v in ops.items()),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": [[_host_label(events["host"], mid), s]
                      for s, mid in gaps[:top]],
    }


def module_calls(red: dict, prefix: str) -> List[float]:
    """Device seconds of every call of the programs whose name starts
    with ``prefix`` (``jit_`` + the engine's program name)."""
    return [d for name, ds in red["modules"].items()
            if name.startswith(prefix) for d in ds]


def op_seconds(red: dict, pattern: str) -> Tuple[float, int]:
    """Summed device seconds and calls of the innermost operations whose
    key or HLO text matches ``pattern`` (a regular expression)."""
    rx = re.compile(pattern)
    secs, calls = 0.0, 0
    for key, s in red["ops"].items():
        if rx.search(key) or rx.search(red["op_labels"].get(key, "")):
            secs += s
            calls += red["op_calls"][key]
    return secs, calls
