"""The second trace reduction (``bench/lib/spans.py``) against a small
recorded trace that keeps each op's scope and the engine's spans (30 ms
around the end of a decode call of ``qwen2.5-3b.longctx_decode`` on one
TPU v5 lite) and against a hand-made trace whose every number can be
counted by hand; and the readers it feeds, on the CPU, where they find
nothing to read."""
import glob
import json
import os
import sys
import time
import types

import numpy as np
import pytest

import tiny
from bench.lib import spans, spec

HERE = os.path.dirname(os.path.abspath(__file__))
READERS = ("decode_pool_ms", "engine_host_ms", "device_idle.outside_sync")


@pytest.fixture(scope="module")
def events():
    with open(os.path.join(HERE, "data",
                           "trace_longctx_scoped_30ms.json")) as f:
        return json.load(f)


def _window(events):
    return [e for e in events["host"] if e[0] == "bench.window"][0][1:3]


def test_scopes_split_the_decode_call(events):
    red = spans.reduce(events)
    t0, t1 = _window(events)
    assert red["window_s"] == pytest.approx(0.030, rel=1e-9)
    assert red["scoped"] and red["decode_calls"] == 1
    rows = events["device"]["/device:TPU:0"]
    # the end of one decode call and the start of the next: every scope
    assert {r[4] for r in rows if r[0] == "XLA Ops"} >= set(spans.SCOPES)
    # brute force: every op of the call that starts in the window and
    # holds no other op
    (m0, m1), = [(a, b) for line, name, a, b, _ in rows
                 if line == "XLA Modules" and t0 <= a < t1
                 and name.startswith("jit_decode_step(")]
    ops = [r for r in rows if r[0] == "XLA Ops" and m0 <= r[2] < m1]
    want = {}
    for line, name, a, b, scope in ops:
        if not any(o[2] >= a and o[3] <= b and (o[2], o[3]) != (a, b)
                   for o in ops):
            want[scope] = want.get(scope, 0.0) + (b - a) * 1e-9
    got = red["decode_scopes_s"]
    assert set(got) == set(want)
    for k in want:
        assert got[k] == pytest.approx(want[k], rel=1e-9)
    # the call opens with the copies of the whole pool
    assert spans.scopes_ms(red)["pool"] > 5.0


def test_idle_is_integrated_by_host_span(events):
    red = spans.reduce(events)
    t0, t1 = _window(events)
    n = int((t1 - t0) // 100)                             # 100 ns bins
    mid = t0 + 100 * (np.arange(n) + 0.5)
    busy = np.zeros(n, bool)
    for rows in events["device"].values():
        for line, _, a, b, _ in rows:
            if line == "XLA Ops":
                busy |= (mid >= a) & (mid < b)
    host = [e for e in events["host"] if e[0] != "bench.window"]
    label = np.full(n, spans.OUTSIDE, object)
    depth = np.full(n, -np.inf)
    sync = np.zeros(n, bool)
    for name, a, b, _ in sorted(host, key=lambda e: (e[1], -e[2])):
        inside = (mid >= a) & (mid < b)
        rank = a + (1e18 if name.startswith("engine.") else 0)
        take = inside & (rank >= depth)
        label[take], depth[take] = name, rank
        if name == spans.SYNC_SPAN:
            sync |= inside
    idle = ~busy
    for name, secs in red["idle_by_span"].items():
        assert secs == pytest.approx(
            (idle & (label == name)).sum() * 1e-7, abs=3e-7), name
    assert sum(red["idle_by_span"].values()) == pytest.approx(
        idle.sum() * 1e-7, abs=3e-7)
    assert red["idle_outside_sync_s"] == pytest.approx(
        (idle & ~sync).sum() * 1e-7, abs=3e-7)
    # the gap between the two calls: the host waits in the tick's sync
    assert max(red["idle_by_span"], key=red["idle_by_span"].get) \
        == spans.SYNC_SPAN
    assert red["engine_spans"] > 0


US = 1e3
# a decode call [10, 60) us with three ops, a second program's op at
# [80, 90); the host in ``engine.decode.launch``, then
# ``engine.decode.sync`` [20, 70), then ``bench.poll`` [72, 76)
HAND_MADE = {
    "device": {"/device:TPU:0": [
        ["XLA Modules", "jit_decode_step(1)", 10 * US, 60 * US, ""],
        ["XLA Ops", "%copy.1 = bf16[2]", 10 * US, 30 * US, "pool"],
        ["XLA Ops", "%while.1 = (s32[])", 30 * US, 55 * US, "pool"],
        ["XLA Ops", "%fused_loki_decode.2 = f32[2]", 32 * US, 50 * US,
         "attn"],
        ["XLA Modules", "jit_prefill_chunk(2)", 80 * US, 90 * US, ""],
        ["XLA Ops", "%fusion.3 = bf16[2]", 80 * US, 90 * US, "mlp"]]},
    "host": [["bench.window", 0.0, 100 * US, {}],
             ["bench.tick", 5 * US, 71 * US, {}],
             ["engine.tick", 6 * US, 70 * US, {"tick": 1}],
             ["engine.decode.launch", 8 * US, 20 * US, {}],
             ["engine.decode.sync", 20 * US, 70 * US, {}],
             ["bench.poll", 72 * US, 76 * US, {}]]}


def test_reduce_on_a_hand_made_trace():
    red = spans.reduce(HAND_MADE)
    assert red["decode_calls"] == 1 and red["scoped"]
    # the loop counts once, through the op it holds
    assert red["decode_scopes_s"] == pytest.approx(
        {"pool": 20e-6, "attn": 18e-6})
    assert spans.scopes_ms(red) == pytest.approx({"pool": 0.020,
                                                  "attn": 0.018})
    # idle [0, 10): outside 5, bench.tick 1, engine.tick 2, launch 2;
    # [55, 80): sync 15, bench.tick 1, outside 1, poll 4, outside 4;
    # [90, 100): outside 10
    assert red["idle_by_span"] == pytest.approx({
        spans.OUTSIDE: 20e-6, "bench.tick": 2e-6, "engine.tick": 2e-6,
        "engine.decode.launch": 2e-6, "engine.decode.sync": 15e-6,
        "bench.poll": 4e-6})
    assert red["idle_outside_sync_s"] == pytest.approx(45e-6 - 15e-6)
    assert red["engine_spans"] == 3 and red["window_s"] == 1e-4


def test_scope_of_reads_name_stacks_and_arguments():
    assert spans.scope_of(
        "jit(decode_step)/pool/while/body/closed_call/attn/pool/scatter") \
        == "pool"
    assert spans.scope_of("jit(decode_step)/pool/while/body/closed_call/"
                          "attn/jit(loki_decode_fused)/fused_loki_decode/"
                          "pallas_call") == "attn"
    assert spans.scope_of("pool['layers']['attn']['k']") == "pool"
    assert spans.scope_of("p['layers']['mlp']['w_in']") == "unscoped"
    assert spans.scope_of("jit(decode_step)/while/body/add") == "unscoped"
    assert spans.scope_of("") == "unscoped"


def test_a_trace_without_its_window_is_refused():
    with pytest.raises(ValueError, match="bench.window"):
        spans.reduce({**HAND_MADE, "host": HAND_MADE["host"][1:]})


def _read(name, run):
    return spec.reader(name)(run)


def test_cpu_trace_keeps_op_names_but_no_device(tmp_path, monkeypatch):
    """The trace keeps each program's HLO, whose ``op_name`` carries the
    scopes; the CPU profiler has no device plane, so no reader finds a
    scope or an idle share."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    def step(x):
        with jax.named_scope("pool"):
            y = jnp.sin(x) * 2
        with jax.named_scope("attn"):
            return y @ y.T
    f = jax.jit(step)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with TraceAnnotation("bench.window"):
        with TraceAnnotation(spans.SYNC_SPAN):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    with open(path, "rb") as fh:
        names = spans.hlo_op_names(fh.read())
    prog, = [k for k in names if k.startswith("jit_step(")]
    assert {spans.scope_of(v) for v in names[prog].values()} \
        >= {"pool", "attn"}
    red = spans.reduce(spans.load(path))
    assert red["devices"] == 0 and not red["scoped"]
    assert red["engine_spans"] == 1 and spans.scopes_ms(red) is None
    monkeypatch.setattr(spans, "trace_file", lambda run: path)
    monkeypatch.setattr(spans, "tick_rows", lambda run: None)
    for name in READERS:
        assert _read(name, None) is None, name


def test_trace_file_is_the_newest_written_after_the_traced_part(
        tmp_path, monkeypatch):
    """The harness's trace directories by cell; a file older than the
    traced part is another run's."""
    monkeypatch.setattr(spec, "BENCH", str(tmp_path))
    now = time.perf_counter()
    run = types.SimpleNamespace(traced=types.SimpleNamespace(t0=now - 5))
    assert spans.trace_file(run) is None
    paths = []
    for cell, age in (("a", 60), ("b", 2), ("c", 1)):
        d = tmp_path / ".out" / f"trace-{cell}" / "plugins/profile/x"
        d.mkdir(parents=True)
        paths.append(str(d / "h.xplane.pb"))
        open(paths[-1], "wb").close()
        os.utime(paths[-1], (time.time() - age,) * 2)
    assert spans.trace_file(run) == paths[2]
    os.utime(paths[2], (time.time() - 60,) * 2)
    assert spans.trace_file(run) == paths[1]
    assert spans.trace_file(types.SimpleNamespace(traced=None)) is None


def test_readers_on_a_cpu_run(monkeypatch):
    """An untraced CPU run: no trace to read; the tick log's last ticks
    are the window's and read host time, and a program that keeps no
    tick log reads nothing."""
    keep = {}
    tiny.run("longctx_decode", 2 ** 31 + 5, keep=keep)
    run = keep["run"]
    assert spans.trace_file(run) is None
    assert _read("decode_pool_ms", run) is None
    assert _read("device_idle.outside_sync", run) is None
    rows, n_traced = spans.tick_rows(run)
    assert n_traced == 0 and len(rows) == run.window.ticks
    ticks = [r["tick"] for r in rows]
    assert ticks == list(range(ticks[0], ticks[0] + len(ticks)))
    got = _read("engine_host_ms", run)
    assert got["value"] > 0
    longest = got["longest_tick_phases"]
    assert len(longest) == min(5, len(rows))
    assert not any(t["traced"] for t in longest)
    assert longest[0]["ms"]["engine.tick"] == max(
        r["ms"]["engine.tick"] for r in rows)
    monkeypatch.setitem(sys.modules, "repro.serving.tracing", None)
    assert spans.tick_rows(run) is None
    assert _read("engine_host_ms", run) is None
