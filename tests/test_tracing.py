"""The program's own measurement: the engine's tick log and host spans
(serving/tracing.py), the device scopes of the model's programs, the
engine's program names and the names of the Pallas kernels.

* under a ManualClock advanced only inside known phases, every tick's
  phases sum exactly to the tick, and the log's counters agree with
  ``stats()``;
* a CPU profiler trace of three ticks holds ``engine.tick`` spans with
  their phases nested in order and their arguments;
* the lowered ``decode_step`` and ``prefill_chunk`` carry the scopes
  ``pool``, ``attn``, ``mlp`` and ``head`` in their op metadata, and
  the engine names its programs without a trace guard;
* every registered kernel entry passes its own name to ``pallas_call``.
"""
import ast
import glob
import inspect
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.kernels import registry
from repro.models import lm
from repro.serving import faults as FI
from repro.serving import scheduler as SCH
from repro.serving.engine import Request
from repro.serving.lifecycle import ManualClock
from repro.serving.scheduler import PagedServingEngine
from repro.serving.tracing import COUNTERS, PHASES, TICK

SCOPES = ("pool", "attn", "mlp", "head")
# clock steps that are exact in binary, so sums of phase times are exact
STEP = 2.0 ** -10


@pytest.fixture(scope="module")
def qwen():
    cfg = get_smoke_config("qwen2.5-3b").with_policy(
        "loki_block", k_f=0.5, d_f=0.5, block_size=8, local_window=4,
        min_k=4)
    return lm.init(jax.random.PRNGKey(0), cfg), cfg


def _requests(cfg, n, max_new, base=10):
    return [Request(rid=i, prompt=(np.arange(base + 2 * i) * 3 + i)
                    % cfg.vocab, max_new=max_new) for i in range(n)]


def _advancing(monkeypatch, obj, name, clock, steps):
    """Make ``obj.name`` advance ``clock`` by ``steps`` STEPs per call."""
    orig = getattr(obj, name)

    def wrapped(*a, **kw):
        clock.advance(steps * STEP)
        return orig(*a, **kw)
    monkeypatch.setattr(obj, name, wrapped)


def test_tick_log_phases_sum_to_tick(qwen, monkeypatch):
    params, cfg = qwen
    clock = ManualClock(100.0)
    # 9 pages of 8 tokens for three requests growing to ~4 pages each:
    # the pool runs short and the scheduler preempts
    eng = PagedServingEngine(params, cfg, n_slots=3, smax=64, page_size=8,
                             n_pages=9, prefill_chunk=8, clock=clock,
                             audit=True)
    steps = {"engine.expire": 1, "engine.admit": 2, "engine.prefill": 3,
             "engine.decode.prep": 4, "engine.decode.launch": 5,
             "engine.decode.sync": 6, "engine.decode.commit": 7,
             "engine.audit": 8}
    _advancing(monkeypatch, eng, "_expire_deadlines", clock, 1)
    _advancing(monkeypatch, eng, "_admit_into", clock, 2)
    _advancing(monkeypatch, eng, "_prefill_slot_chunk", clock, 3)
    _advancing(monkeypatch, eng, "_prep_decode", clock, 4)
    _advancing(monkeypatch, eng, "_run_decode", clock, 5)
    _advancing(monkeypatch, eng, "_run_decode_packed", clock, 5)
    _advancing(monkeypatch, SCH.jax, "device_get", clock, 6)
    _advancing(monkeypatch, eng, "_commit_decode", clock, 7)
    _advancing(monkeypatch, FI, "audit_engine", clock, 8)
    reqs = _requests(cfg, 3, max_new=20)
    for r in reqs:
        eng.submit(r)
    eng.run_until_done(400)
    assert all(r.done for r in reqs), [r.status for r in reqs]
    st = eng.stats()
    assert st["n_preempted"] > 0, "the test pool must run short"

    rows = eng.tick_log.rows()
    assert [r["tick"] for r in rows] == list(range(eng.ticks))
    for r in rows:
        ms = r["ms"]
        assert set(ms) == set(PHASES)
        # nothing but the phases moved the clock: they cover the tick
        assert sum(v for k, v in ms.items() if k != TICK) == ms[TICK], r
        for phase, k in steps.items():
            per_call = 1e3 * k * STEP
            assert ms[phase] / per_call == int(ms[phase] / per_call), r
        assert ms["engine.expire"] == 1e3 * STEP
        assert ms["engine.audit"] == 1e3 * 8 * STEP
        if r["decode_rows"]:
            assert ms["engine.decode.sync"] == 1e3 * 6 * STEP, r

    def total(key):
        return sum(r[key] for r in rows)
    assert total("prefill_tokens") == st["n_prefill_computed_tokens"]
    assert total("pages_grown") == st["n_pages_grown"]
    assert total("preemptions") == st["n_preempted"]
    assert total("decode_rows") == sum(len(r.out) for r in reqs)
    packed = [r for r in rows if r["packed_bucket"]]
    masked = [r for r in rows if r["decode_rows"] and not r["packed_bucket"]]
    assert len(packed) == st["packed"]["n_packed_ticks"] > 0
    assert len(masked) == st["packed"]["n_masked_ticks"]
    assert all(r["decode_rows"] <= r["packed_bucket"] for r in packed)

    summary = st["tick_phases_ms"]
    assert set(summary) == set(PHASES)
    ticks_ms = [r["ms"][TICK] for r in rows]
    assert summary[TICK]["max"] == max(ticks_ms)
    assert summary[TICK]["mean"] == pytest.approx(np.mean(ticks_ms))


def test_tick_log_ring_keeps_the_last_ticks():
    from repro.serving.tracing import TickLog
    clock = ManualClock()
    log = TickLog(clock, size=4)
    for i in range(10):
        with log.tick(i):
            with log.phase("engine.prefill", rid=i, tokens=3):
                clock.advance(i * STEP)
            log.count(prefill_tokens=3)
    rows = log.rows()
    assert [r["tick"] for r in rows] == [6, 7, 8, 9]
    assert [r["ms"]["engine.prefill"] for r in rows] == \
        [1e3 * i * STEP for i in (6, 7, 8, 9)]
    assert [r["tick"] for r in log.rows(since=8)] == [8, 9]
    assert all(r["prefill_tokens"] == 3 for r in rows)
    assert set(rows[0]) == set(COUNTERS) | {"ms"}
    assert log.summary()[TICK]["max"] == 1e3 * 9 * STEP


def test_latest_tick_log_outlives_its_engine(qwen):
    """The log made last stays readable after its engine is freed."""
    import gc
    import weakref
    from repro.serving import tracing
    params, cfg = qwen
    eng = PagedServingEngine(params, cfg, n_slots=2, smax=64, page_size=8,
                             prefill_chunk=16, clock=ManualClock())
    eng.submit(Request(rid=0, prompt=np.arange(1, 9, dtype=np.int32),
                       max_new=3))
    for _ in range(3):
        eng.tick()
    log, gone = eng.tick_log, weakref.ref(eng)
    del eng
    gc.collect()
    assert gone() is None and tracing.latest() is log
    assert [r["tick"] for r in log.rows()] == [0, 1, 2]


def _host_events(trace_dir):
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                    dict(ev.stats)) for ev in line.events]
            if any(e[0] == TICK for e in evs):
                return evs
    raise AssertionError("no host thread carries engine.tick")


def test_profiler_trace_holds_engine_spans(qwen, tmp_path):
    params, cfg = qwen
    eng = PagedServingEngine(params, cfg, n_slots=2, smax=64, page_size=8,
                             prefill_chunk=8)
    # compile every program first: the traced ticks only run them
    warm = _requests(cfg, 2, max_new=2)
    for r in warm:
        eng.submit(r)
    eng.run_until_done(100)
    first = eng.ticks
    reqs = [Request(rid=10 + i, prompt=(np.arange(6) + i) % cfg.vocab,
                    max_new=8) for i in range(2)]
    for r in reqs:
        eng.submit(r)
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(3):
            eng.tick()
    finally:
        jax.profiler.stop_trace()
    evs = _host_events(str(tmp_path))
    ticks = sorted((e for e in evs if e[0] == TICK), key=lambda e: e[1])
    assert [t[3]["tick"] for t in ticks] == [first, first + 1, first + 2]
    order = {p: i for i, p in enumerate(PHASES)}
    for n, (_, a, b, args) in enumerate(ticks):
        assert set(args) == set(COUNTERS)
        assert args["decode_rows"] == 2
        kids = sorted((e for e in evs if e[0].startswith("engine.")
                       and e[0] != TICK and a <= e[1] and e[2] <= b),
                      key=lambda e: e[1])
        names = [k[0] for k in kids]
        # phases run in the order PHASES lists them, without overlap
        assert [order[x] for x in names] == sorted(order[x] for x in names)
        assert all(x[2] <= y[1] for x, y in zip(kids, kids[1:]))
        assert names[0] == "engine.expire"
        assert names[-4:] == ["engine.decode.prep", "engine.decode.launch",
                              "engine.decode.sync", "engine.decode.commit"]
        if n == 0:
            # both requests were admitted and prefilled in the first tick
            admits = [k[3] for k in kids if k[0] == "engine.admit"]
            assert [x["rid"] for x in admits] == [10, 11]
            assert all(x["queued_ms"] >= 0 for x in admits)
            chunks = [k[3] for k in kids if k[0] == "engine.prefill"]
            assert [x["rid"] for x in chunks] == [10, 11]
            # each prompt's first token may come from the prefix cache
            assert sum(x["tokens"] for x in chunks) \
                == args["prefill_tokens"] > 0
        else:
            assert "engine.admit" not in names
            assert "engine.prefill" not in names


def _scopes(hlo_text):
    """The innermost of SCOPES in each op's ``op_name`` metadata (an
    argument's metadata is its name and pytree path)."""
    import re
    found = set()
    for name in re.findall(r'op_name="([^"]*)"', hlo_text):
        inner = [p for p in (c.split("[", 1)[0] for c in name.split("/"))
                 if p in SCOPES]
        if inner:
            found.add(inner[-1])
    return found


def _lowered(eng, program):
    pt = jnp.asarray(eng.page_table)
    if program == "decode_step":
        return eng._decode.lower(eng.params, eng.cache, eng.last_tok,
                                 eng.pos, pt, None)
    if program == "decode_step_packed_b1":
        return eng._packed_program(1).lower(
            eng.params, eng.cache, eng.last_tok[:1], eng.pos[:1], pt[:1],
            None, jnp.zeros((1,), jnp.int32))
    chunk = jnp.zeros((1, eng.prefill_chunk), jnp.int32)
    return eng._chunk.lower(eng.params, eng.cache, chunk, jnp.int32(0),
                            jnp.int32(4), eng.page_table[0].copy(),
                            jnp.int32(0))


@pytest.mark.parametrize("program", ["decode_step", "decode_step_packed_b1",
                                     "prefill_chunk"])
def test_programs_carry_device_scopes_and_names(qwen, program):
    params, cfg = qwen
    eng = PagedServingEngine(params, cfg, n_slots=2, smax=64, page_size=8,
                             prefill_chunk=8)
    lowered = _lowered(eng, program)
    # no trace guard: the engine names the program itself
    assert f"@jit_{program}" in lowered.as_text()
    compiled = lowered.compile().as_text()
    assert _scopes(compiled) == set(SCOPES)
    # the cache argument is named ``pool``: ops XLA inserts on it (layout
    # copies) carry that name
    assert 'op_name="pool[' in compiled


def _pallas_calls(fn):
    tree = ast.parse(inspect.getsource(inspect.getmodule(fn)))
    for node in ast.walk(tree):
        if isinstance(node, ast.FunctionDef) and node.name == fn.__name__:
            return [c for c in ast.walk(node) if isinstance(c, ast.Call)
                    and getattr(c.func, "attr", "") == "pallas_call"]
    raise AssertionError(f"{fn.__name__} not found in its module")


@pytest.mark.parametrize("entry", sorted(registry.load_all()))
def test_kernel_entry_names_its_pallas_call(entry):
    """A kernel's name is what a device trace shows for it: each entry
    passes its own name, so a reduction finds it after a refactor."""
    calls = _pallas_calls(registry.REGISTRY[entry].fn)
    assert calls, f"{entry} makes no pallas_call"
    for call in calls:
        names = [k.value.value for k in call.keywords if k.arg == "name"]
        assert names == [entry], (entry, names)
