"""A second reading of the profiler trace, in the program's own names.

``load`` reads the ``.xplane.pb`` again and keeps what ``trace.load_xplane``
drops: each device op's scope, and the engine's host spans with their
arguments. ``reduce`` computes, inside the host span ``bench.window``:

  * device time per call of the decode program, split by the device
    scope (``jax.named_scope``) of each innermost op: ``pool``, ``attn``,
    ``mlp``, ``head``, else ``unscoped`` (an op XLA inserted with no
    metadata, or a program that names no scopes);
  * the device's idle time, integrated over each idle interval and
    attributed to the innermost host span the engine's thread was in
    (an ``engine.*`` span, else a ``bench.*`` span, else none), and the
    part of it outside ``engine.decode.sync``;
  * the arguments of the engine's ``engine.admit`` spans (``rid``,
    ``queued_ms``) and ``engine.prefill`` spans (``rid``, ``tokens``),
    in the order they began.

An op's scope is in its ``op_name`` metadata (the JAX name stack,
``jit(decode_step)/pool/while/body/attn/...``). A TPU ``XLA Ops`` event
carries no such stat; the profiler keeps each program's optimized HLO in
the ``/host:metadata`` plane (stat ``Hlo Proto``), and ``hlo_op_names``
reads the ``op_name`` of every instruction from it (a minimal protobuf
reader: the field numbers below are those of ``xplane.proto`` and
``hlo.proto``). An op is matched to its program's instruction by name
(``%copy.34 = ...`` is ``copy.34``), never by shape or position. The CPU
profiler has no device plane, so on the CPU the readers find nothing.
"""
from __future__ import annotations

import bisect
import collections
import functools
import glob
import importlib
import os
import time
from typing import Dict, List, Optional, Tuple

from bench.lib import spec
from bench.lib.trace import (_CALL_SUFFIX, WINDOW_SPAN, _leaves, _union,
                             op_name)

SCOPES = ("pool", "attn", "mlp", "head")
UNSCOPED = "unscoped"
HLO_PROTO_STAT = "Hlo Proto"
METADATA_PLANE = "/host:metadata"
SYNC_SPAN = "engine.decode.sync"
DECODE_PROGRAM = "jit_decode_step"
OUTSIDE = "outside spans"


def scope_of(op_name: str) -> str:
    """The innermost of ``SCOPES`` in an op's name stack. An op XLA
    inserts on a program argument (a layout copy) carries the argument's
    name and path instead (``pool['layers']['attn']['k']``): the engine
    names its cache argument ``pool``."""
    inner = [p for p in (c.split("[", 1)[0] for c in op_name.split("/"))
             if p in SCOPES]
    return inner[-1] if inner else UNSCOPED


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes, span: Tuple[int, int]):
    """(field number, value) of each field of the protobuf message at
    ``buf[span[0]:span[1]]``: an int, or the (start, end) of a
    length-delimited value; fixed-width values are skipped (None)."""
    i, end = span
    while i < end:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif kind in (1, 5):
            value, i = None, i + (8 if kind == 1 else 4)
        else:
            raise ValueError(f"protobuf wire type {kind} at byte {i}")
        yield key >> 3, value


def _text(buf: bytes, span: Tuple[int, int]) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _hlo_names(buf: bytes, proto: Tuple[int, int]) -> Dict[str, str]:
    """Instruction name -> ``op_name`` of an ``HloProto``: hlo_module 1;
    HloModuleProto computations 3; HloComputationProto instructions 2;
    HloInstructionProto name 1, metadata 7; OpMetadata op_name 2."""
    out: Dict[str, str] = {}
    for f, module in _fields(buf, proto):
        if f != 1:
            continue
        for g, comp in _fields(buf, module):
            if g != 3:
                continue
            for h, ins in _fields(buf, comp):
                if h != 2:
                    continue
                name, opn = "", ""
                for k, v in _fields(buf, ins):
                    if k == 1:
                        name = _text(buf, v)
                    elif k == 7:
                        opn = next((_text(buf, w) for m, w in
                                    _fields(buf, v) if m == 2), "")
                out[name] = opn
    return out


def hlo_op_names(buf: bytes) -> Dict[str, Dict[str, str]]:
    """Program (``jit_decode_step(<id>)``, as ``XLA Modules`` names it) ->
    instruction name -> ``op_name``, from the ``Hlo Proto`` stats of an
    XSpace's metadata plane. XSpace planes 1; XPlane name 2,
    event_metadata 4 and stat_metadata 5 (map entries: key 1, value 2);
    XEventMetadata name 2, stats 5; XStatMetadata id 1, name 2; XStat
    metadata_id 1, bytes_value 6."""
    out: Dict[str, Dict[str, str]] = {}
    for f, plane in _fields(buf, (0, len(buf))):
        fields = list(_fields(buf, plane)) if f == 1 else []
        if not any(g == 2 and _text(buf, v) == METADATA_PLANE
                   for g, v in fields):
            continue
        stat_names = {}
        for g, entry in fields:
            for h, meta in (_fields(buf, entry) if g == 5 else ()):
                if h == 2:
                    sm = dict(_fields(buf, meta))
                    stat_names[sm.get(1, 0)] = _text(buf, sm.get(2, (0, 0)))
        for g, entry in fields:
            for h, meta in (_fields(buf, entry) if g == 4 else ()):
                if h != 2:
                    continue
                name, proto = "", None
                for k, v in _fields(buf, meta):
                    if k == 2:
                        name = _text(buf, v)
                    elif k == 5:
                        st = dict(_fields(buf, v))
                        if stat_names.get(st.get(1)) == HLO_PROTO_STAT:
                            proto = st.get(6)
                if proto is not None:
                    out[name] = _hlo_names(buf, proto)
    return out


def load(path: str) -> dict:
    """Events of an xplane file as plain lists:

    ``{"device": {plane: [[line, name, start_ns, end_ns, scope]]},
       "host": [[name, start_ns, end_ns, {arg: value}]]}``

    ``device`` keeps the ``XLA Modules`` and ``XLA Ops`` lines of every
    device plane (a module's scope is ``""``); ``host`` the ``bench.*``
    and ``engine.*`` events of the host thread that carries
    ``bench.window``."""
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        names = hlo_op_names(f.read())
    pd = ProfileData.from_file(path)
    device: Dict[str, list] = {}
    host: List[list] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            rows = [[line.name, ev.name, float(ev.start_ns),
                     float(ev.start_ns) + float(ev.duration_ns), ""]
                    for line in plane.lines
                    if line.name in ("XLA Ops", "XLA Modules")
                    for ev in line.events]
            # each op takes the op_name its program gives it
            mods = sorted((r[2], r[3], r[1]) for r in rows
                          if r[0] == "XLA Modules")
            starts = [m[0] for m in mods]
            for r in rows:
                if r[0] != "XLA Ops":
                    continue
                i = bisect.bisect_right(starts, r[2]) - 1
                prog = mods[i][2] if i >= 0 and r[2] < mods[i][1] else ""
                r[4] = scope_of(names.get(prog, {}).get(op_name(r[1]), ""))
            if rows:
                device[plane.name] = rows
        elif plane.name.startswith("/host:") and not host:
            for line in plane.lines:
                evs = [[ev.name, float(ev.start_ns),
                        float(ev.start_ns) + float(ev.duration_ns),
                        dict(ev.stats)]
                       for ev in line.events
                       if ev.name.startswith(("bench.", "engine."))]
                if any(e[0] == WINDOW_SPAN for e in evs):
                    host = evs
                    break
    return {"device": device, "host": host}


def _labels(host: List[list], t0: float, t1: float
            ) -> List[Tuple[float, float, str, bool]]:
    """The window cut at every host span boundary: each piece with the
    innermost span covering it (an ``engine.*`` span before a
    ``bench.*`` one) and whether ``SYNC_SPAN`` covers it."""
    spans = [(a, b, name) for name, a, b, *_ in host
             if name != WINDOW_SPAN and b > t0 and a < t1]
    cuts = sorted({t0, t1} | {x for a, b, _ in spans for x in (a, b)
                               if t0 < x < t1})
    out = []
    for a, b in zip(cuts, cuts[1:]):
        mid = (a + b) / 2
        over = [s for s in spans if s[0] <= mid < s[1]]
        eng = [s for s in over if s[2].startswith("engine.")]
        pick = max(eng or over, key=lambda s: (s[0], -s[1]), default=None)
        out.append((a, b, pick[2] if pick else OUTSIDE,
                    any(s[2] == SYNC_SPAN for s in over)))
    return out


def reduce(events: dict) -> dict:
    """Reduce ``load`` output to the traced window's numbers (seconds):
    ``decode_calls`` and ``decode_scopes_s`` (device seconds per scope
    over those calls), ``idle_by_span`` and ``idle_outside_sync_s``
    (averaged over the ``devices``), ``window_s``, ``scoped`` (whether any op
    carried a scope) and ``engine_spans`` (how many ``engine.*`` spans
    the window holds). Raises ValueError without the window span."""
    spans = [e for e in events["host"] if e[0] == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"trace has no host span {WINDOW_SPAN!r}")
    t0, t1 = spans[0][1], spans[0][2]
    pieces = _labels(events["host"], t0, t1)
    scopes: Dict[str, float] = collections.defaultdict(float)
    idle: Dict[str, float] = collections.defaultdict(float)
    outside_sync, calls, scoped = 0.0, 0, False
    devices = sorted(events["device"].items())
    for _, rows in devices:
        mods = sorted((a, b) for line, name, a, b, _ in rows
                      if line == "XLA Modules" and t0 <= a < t1
                      and _CALL_SUFFIX.sub("", name) == DECODE_PROGRAM)
        calls += len(mods)
        starts = [m[0] for m in mods]
        ops = [r for r in rows if r[0] == "XLA Ops"]
        for _, _, a, b, scope in _leaves(ops):
            scoped |= scope != UNSCOPED
            i = bisect.bisect_right(starts, a) - 1
            if i >= 0 and a < mods[i][1]:
                scopes[scope] += (b - a) * 1e-9
        busy = _union([(max(a, t0), min(b, t1)) for _, _, a, b, _ in ops
                       if b > t0 and a < t1])
        edges = [t0] + [x for iv in busy for x in iv] + [t1]
        i = 0
        for g0, g1 in zip(edges[::2], edges[1::2]):
            # both lists are sorted and the pieces tile the window
            while i < len(pieces) and pieces[i][1] <= g0:
                i += 1
            j = i
            while j < len(pieces) and pieces[j][0] < g1:
                a, b, label, in_sync = pieces[j]
                secs = (min(b, g1) - max(a, g0)) * 1e-9 / len(devices)
                if secs > 0:
                    idle[label] += secs
                    if not in_sync:
                        outside_sync += secs
                j += 1
    return {
        "window_s": (t1 - t0) * 1e-9,
        "devices": len(devices),
        "decode_calls": calls,
        "decode_scopes_s": dict(scopes),
        "scoped": scoped,
        "idle_by_span": dict(sorted(idle.items(), key=lambda kv: -kv[1])),
        "idle_outside_sync_s": outside_sync,
        "engine_spans": sum(1 for e in events["host"]
                            if e[0].startswith("engine.")
                            and e[2] > t0 and e[1] < t1),
        "admits": _args(events["host"], "engine.admit", t0, t1,
                        ("rid", "queued_ms")),
        "prefills": _args(events["host"], "engine.prefill", t0, t1,
                          ("rid", "tokens")),
    }


def _args(host: List[list], name: str, t0: float, t1: float,
          keys: Tuple[str, ...]) -> List[List[int]]:
    """The arguments ``keys`` of every span ``name`` that begins inside
    [t0, t1), in the order they began."""
    return [[int(float(e[3][k])) for k in keys]
            for e in sorted(host, key=lambda e: e[1])
            if e[0] == name and t0 <= e[1] < t1
            and all(k in e[3] for k in keys)]


@functools.lru_cache(maxsize=1)
def read(path: str) -> dict:
    """``reduce(load(path))``, once per trace file for all its readers."""
    return reduce(load(path))


def trace_file(run) -> Optional[str]:
    """The xplane file of a run's traced part, or None for an untraced
    run: the newest under the harness's trace directories
    (``bench/.out/trace-<cell>``) written after the traced part began
    (``run.traced.t0``, on the harness's ``time.perf_counter``)."""
    if getattr(run, "traced", None) is None:
        return None
    began = time.time() - (time.perf_counter() - run.traced.t0)
    paths = [p for p in glob.glob(os.path.join(
        spec.BENCH, ".out", "trace-*", "plugins", "profile", "*",
        "*.xplane.pb")) if os.path.getmtime(p) >= began]
    return max(paths, key=os.path.getmtime) if paths else None


def of_run(run) -> Optional[dict]:
    """The reduction of a run's trace, or None for an untraced run."""
    path = trace_file(run)
    return read(path) if path else None


def tick_rows(run) -> Optional[Tuple[List[dict], int]]:
    """The window's ticks in the program's tick log
    (``repro.serving.tracing``), oldest first, each as its counters plus
    ``ms`` (phase -> milliseconds), and how many of the last of them ran
    traced; None where the program keeps no tick log. The window ends on
    the engine's last tick, so its ticks are the log's last
    ``run.window.ticks`` (fewer where the ring has dropped the oldest)."""
    try:
        tracing = importlib.import_module("repro.serving.tracing")
    except ImportError:
        return None
    log = tracing.latest()
    if log is None or not run.window.ticks:
        return None
    rows = log.rows()[-run.window.ticks:]
    traced = run.traced.ticks if run.traced is not None else 0
    return rows, min(traced, len(rows))


def scopes_ms(red: dict) -> Optional[Dict[str, float]]:
    """Device ms per decode call by scope (``breakdown.decode_scopes_ms``),
    or None when no op carried a scope or no decode call ran."""
    if not red["scoped"] or not red["decode_calls"]:
        return None
    return {k: 1e3 * v / red["decode_calls"]
            for k, v in sorted(red["decode_scopes_s"].items(),
                               key=lambda kv: -kv[1])}
