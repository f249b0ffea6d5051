"""One run of one cell: set-up, the measured window, the output check and
the metrics. ``bench/run.py`` is the command; tests call ``run`` with
``require_chip=False`` and small sizes.

Order of a run:
  1. find the cell's files; JAX's compile cache inside the checkout;
     the chip, or stop;
  2. weights drawn on the device from the configuration's seed; the PCA
     projections, calibrated once per checkout with the reference and
     kept under ``bench/.cache``;
  3. the engine, with every program the cell's traffic reaches warmed
     (and the cell's sessions prefilled); the trace guard is sealed; an
     open mix's arrivals start, and run ``warm_seconds`` before the window;
  4. the window (``--seconds``), timed on the harness's clock; with
     ``--trace 1`` its last ``trace_seconds`` under the profiler; an open
     mix's requests are submitted as they come due, between ticks;
  5. the device's memory peak; the engine freed; the reference run over a
     seeded sample of the sessions the window served (the longest among
     them); the metrics.
"""
from __future__ import annotations

import dataclasses
import gc
import hashlib
import json
import os
import shutil
import sys
import time
from typing import Optional

import numpy as np

from bench.lib import drive, flops, spec
from bench.lib import traffic as traffic_gen

CHECK = "max_logit_gap"


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Run:
    """What the metric readers read (``bench/metrics/*.py``)."""
    cfg: dict
    traffic: dict
    dims: flops.Dims
    peaks: Optional[dict]
    setup_s: float
    window: drive.Window                   # the measured window
    traced: Optional[drive.Window] = None  # its traced part
    trace: Optional[dict] = None           # trace.reduce_events output


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _merge(base: dict, over: Optional[dict]) -> dict:
    out = dict(base)
    for k, v in (over or {}).items():
        out[k] = (_merge(out[k], v) if isinstance(v, dict)
                  and isinstance(out.get(k), dict) else v)
    return out


def program_norm_eps(pc) -> float:
    """The RMSNorm epsilon the program serves with: its model config's,
    where it has one, else the default of its norm."""
    eps = getattr(pc, "norm_eps", None)
    if eps is None:
        import inspect
        from repro.models import layers
        eps = inspect.signature(layers.norm_apply).parameters["eps"].default
    return float(eps)


def program_config(cfg: dict, traffic: dict, check_registry: bool,
                   reduced=()):
    """The program's ModelConfig for a configuration file, through the
    launcher's ``ServeConfig`` (policy, k_f/d_f, page layout), with the
    fields that the configuration's reference module says the file sets
    (``program_fields``). Stops when the program's own record of the
    architecture disagrees with the file on a key not in ``reduced``, or
    where the reference finds the program departing from the file
    (``departures``)."""
    from repro.configs import get_config
    from repro.launch import serve
    ref = spec.reference(cfg)
    sv, eng = cfg["serving"], traffic["engine"]
    sc = serve.ServeConfig(
        engine=serve.EngineSection(
            arch=cfg["arch"], smoke=False, policy=sv["policy"],
            k_f=sv["k_f"], d_f=sv["d_f"], backend=sv["backend"],
            n_slots=eng["n_slots"], smax=eng["smax"]),
        pool=serve.PoolSection(page_size=sv["page_size"],
                               prefill_chunk=sv["prefill_chunk"]),
        layout=serve.LayoutSection(spec=sv["pool_layout"]))
    resolved = sc.resolve_model()
    fields = ref.program_fields(cfg, resolved)
    if check_registry:
        pub = get_config(cfg["arch"])
        for k, (key, v) in fields.items():
            if key not in reduced and getattr(pub, k) != v:
                raise ValueError(f"program's {cfg['arch']} has {k}="
                                 f"{getattr(pub, k)}, the file {v}")
    pc = resolved.replace(**{k: v for k, (_, v) in fields.items()})
    why = ref.departures(pc, cfg, program_norm_eps(pc))
    if pc.loki.local_window != sv["local_window"] \
            or pc.loki.transform != cfg["calibration"]["transform"]:
        why.append(f"Loki serving {pc.loki}")
    if why:
        raise ValueError(f"program config departs from the file: {why}")
    return sc, pc


def warm_programs(eng, guard, seed: int, vocab: int) -> None:
    """Trace and compile every program an open mix can reach, through
    the engine's public calls: for each decode batch width the engine
    serves (each packed bucket below ``n_slots``, then ``n_slots``, the
    full-width step) that many short requests, one prefill chunk each,
    which stay until all of them decode together, then finish; then a
    request that opens with a live one's first tokens, whose first write
    copies the page they share (a prompt that opens with the first token
    of a live request's first page does so too). Stops where a program
    was not traced."""
    from repro.serving.engine import Request
    from repro.serving.lifecycle import TERMINAL, Status
    n, chunk = eng.n_slots, eng.prefill_chunk
    widths = [1 << i for i in range(n.bit_length()) if 1 << i < n] + [n]
    rng = np.random.Generator(np.random.PCG64([seed, 2]))

    def prompt(head=()):
        return np.concatenate([head, rng.integers(
            0, vocab, chunk + 1 - len(head))]).astype(np.int32)

    def serve(warm, until):
        for _ in range(10_000):
            if all(r.status in until for r in warm):
                return
            if any(r.status in TERMINAL - until for r in warm):
                break
            eng.tick()
        raise RuntimeError("a warm-up request did not get on: "
                           + str([str(r.status) for r in warm]))

    done = frozenset({Status.DONE})
    for b in widths:
        warm = [Request(rid=-1 - i, prompt=prompt(), max_new=b + 2)
                for i in range(b)]
        for r in warm:
            eng.submit(r)
        serve(warm, done)
    live = Request(rid=-1, prompt=prompt(), max_new=4)
    eng.submit(live)
    serve([live], frozenset({Status.DECODE}))
    twin = Request(rid=-2, prompt=prompt(live.prompt[:8]), max_new=2)
    eng.submit(twin)
    serve([live, twin], done)
    want = ["prefill_chunk", "decode_step", "copy_cache_page"] + [
        f"decode_step_packed[b{b}]" for b in widths[:-1]]
    if not all(guard.traces.get(w) for w in want):
        raise RuntimeError(f"warm-up left programs untraced: {guard.traces}")


def projections(params, cfg: dict, smax: int) -> np.ndarray:
    """PCA projections of the configuration, calibrated once with its
    reference and kept in the checkout, keyed by the file's content."""
    key = hashlib.sha256(json.dumps(cfg, sort_keys=True).encode()
                         ).hexdigest()[:16]
    path = os.path.join(spec.cache_dir(), f"pca-{cfg['name']}-{key}.npy")
    if os.path.exists(path):
        return np.load(path)
    proj = spec.reference(cfg).calibrate(params, cfg, smax)
    os.makedirs(spec.cache_dir(), exist_ok=True)
    tmp = path + ".tmp.npy"
    np.save(tmp, proj)
    os.replace(tmp, path)
    return proj


def _device_info(jax, devices) -> dict:
    peak = 0
    for d in devices:
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(jax.devices()), "memory_peak_bytes": peak}


def check_outputs(cfg, params, proj, smax, reqs, prompts,
                  control: bool = False) -> dict:
    """The reference over each request's prompt and served tokens:
    the widest gap by which a served token's logit lies below the
    reference's best at its position. With ``control``, also the same gap
    for the token that the reference in float8 puts first at each of
    those positions (the control, ``bench/tests/test_control.py``)."""
    ref = spec.reference(cfg)
    out = {"value": 0.0, "tokens": 0, "control": 0.0}
    for req in reqs:
        served = np.asarray(req.out, np.int32)
        reads = served[:, None]
        if control:
            _, lower, _ = ref.logit_reads(params, proj, cfg, smax,
                                          prompts[req.rid], served,
                                          prec="fp8")
            reads = np.stack([served, lower], 1)
        best, _, got = ref.logit_reads(params, proj, cfg, smax,
                                       prompts[req.rid], served, reads)
        gaps = best[:, None] - got
        out["value"] = max(out["value"], float(gaps[:, 0].max()))
        if control:
            out["control"] = max(out["control"], float(gaps[:, 1].max()))
        out["tokens"] += len(served)
    return out


def pick(reqs, seed: int, k: int):
    """A seeded sample of ``k`` requests that served tokens, the longest
    (prompt and served tokens) among them."""
    cands = sorted((r for r in reqs if r.out),
                   key=lambda r: (len(r.prompt) + len(r.out), r.rid))
    if not cands:
        return []
    longest, rest = cands[-1], cands[:-1]
    rng = np.random.Generator(np.random.PCG64([seed, 1]))
    chosen = rng.choice(len(rest), size=min(k - 1, len(rest)),
                        replace=False) if rest and k > 1 else []
    return [longest] + [rest[i] for i in sorted(chosen)]


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, require_chip: bool = True,
        overrides: Optional[dict] = None, keep: Optional[dict] = None,
        control: bool = False) -> dict:
    """One run; returns the result line's object. ``overrides`` merge into
    the configuration and traffic files (tests); ``keep``, when
    given, receives the ``Run`` the metrics were read from and the output
    check's readings; ``control`` adds the control's reading to them."""
    cell = spec.load_cell(workload)
    cfg = _merge(cell.config, (overrides or {}).get("config"))
    tr = _merge(cell.traffic, (overrides or {}).get("traffic"))
    src = os.path.join(spec.ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise FileNotFoundError(f"the program is not in {src}")
    sys.path.insert(0, src)

    import jax
    if require_chip:
        # a fixed directory inside the checkout, also where the environment
        # names a shared one: two checkouts measured side by side share
        # no cache
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(spec.ROOT, ".jax_cache"))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = jax.devices()[:cell.chips]
    if require_chip and (devices[0].platform != "tpu"
                         or len(devices) < cell.chips):
        raise NoChip(f"cell {workload} needs {cell.chips} TPU chip(s); "
                     f"JAX found {len(jax.devices())} "
                     f"{devices[0].platform} device(s)")
    from bench.lib.peaks import peaks_for
    peaks = peaks_for(devices[0].device_kind) if require_chip else None

    from jax.profiler import TraceAnnotation
    from repro.serving.engine import Request
    from repro.serving.lifecycle import TERMINAL, Status
    from repro.serving.scheduler import PagedServingEngine
    from bench.lib import weights
    from bench.lib.guard import NamedGuard

    eng_cfg = tr["engine"]
    smax = eng_cfg["smax"]
    sc, pcfg = program_config(cfg, tr, check_registry="config" not in (
        overrides or {}), reduced=cell.reduced)
    params = weights.make_params(cfg, pcfg)
    proj = projections(params, cfg, smax)
    params = weights.with_projections(params, proj)
    jax.block_until_ready(params)
    log(f"weights and projections ready at "
        f"{time.time() - t_start:.2f} s")

    guard = NamedGuard()
    eng = PagedServingEngine(
        params, pcfg, n_slots=eng_cfg["n_slots"], smax=smax,
        page_size=sc.pool.page_size, prefill_chunk=sc.pool.prefill_chunk,
        backend=sc.engine.backend,
        prefill_budget=eng_cfg.get("prefill_budget"), trace_guard=guard)
    terminal = frozenset(TERMINAL)
    open_loop = tr["kind"] == "open"
    plan = traffic_gen.plan(tr, seed, cfg["vocab_size"],
                            seconds if open_loop else None)
    prompts = {p.rid: p.prompt for p in plan}
    clock = time.perf_counter

    reqs = [Request(rid=p.rid, prompt=p.prompt.copy(), max_new=p.max_new)
            for p in plan]
    if open_loop:
        warm_programs(eng, guard, seed, cfg["vocab_size"])
        jax.block_until_ready(eng.cache)
        t_sched = clock()
        seen = []
        arrivals = drive.Arrivals(reqs, [t_sched + p.due for p in plan],
                                  clock, terminal, Status.QUEUED)
        guard.seal()
        traces0 = dict(guard.traces)
        setup_s = time.time() - t_start
        # the window opens when its block of arrivals begins, after the
        # warm stretch
        t0 = t_sched + tr["warm_seconds"]
        drive.run_open_until(eng, drive.Window(t0=t_sched, seen=seen),
                             seen, t0, clock, arrivals)
        ticks_before = len(arrivals.queued)
    else:
        for r in reqs:
            eng.submit(r)
        ticks = 0
        while not all(r.status is Status.DECODE for r in reqs):
            if any(r.status in terminal for r in reqs) or ticks > 10_000:
                raise RuntimeError("a session ended during set-up: "
                                   + str([str(r.status) for r in reqs]))
            eng.tick()
            ticks += 1
        for _ in range(tr.get("warm_ticks", 2)):
            eng.tick()
        jax.block_until_ready(eng.cache)
        t0 = clock()
        seen = [drive.Seen(r, n_out=len(r.out)) for r in reqs]
        guard.seal()
        traces0 = dict(guard.traces)
        setup_s = time.time() - t_start
    fallbacks0 = eng.n_packed_fallbacks + eng.n_backend_fallbacks

    win = drive.Window(t0=t0, seen=seen)
    until = t0 + seconds
    gc_pauses, gc_start, compiles = [], [], []

    def gc_watch(phase, info):
        # the host's garbage collector pauses the engine's thread too
        if phase == "start":
            gc_start[:] = [clock()]
        elif gc_start:
            ms = 1e3 * (clock() - gc_start[0])
            if ms > 20:
                gc_pauses.append([ms, info["generation"], gc_start[0] - t0])

    def compile_watch(event, secs, **kw):
        # JAX's own record of tracing, lowering, compiling and reading the
        # compile cache: the trace guard sees only the first
        compiles.append([event, str(kw.get("fun_name", "")), secs,
                         clock() - t0])

    def drive_until(w, t):
        if open_loop:
            drive.run_open_until(eng, w, seen, t, clock, arrivals)
        else:
            drive.run_ticks_until(eng, w, seen, t, clock)
    gc.callbacks.append(gc_watch)
    jax.monitoring.register_event_duration_secs_listener(compile_watch)
    traced, red = None, None
    trace_dir = os.path.join(spec.BENCH, ".out", f"trace-{workload}")
    if trace:
        split = max(until - tr["trace_seconds"], t0)
        drive_until(win, split)
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
        traced = drive.Window(t0=clock(), seen=seen)
        with TraceAnnotation("bench.window"):
            drive_until(traced, until)
        jax.profiler.stop_trace()
        win.ticks += traced.ticks
        win.tick_ends += traced.tick_ends
        win.tick_calls += traced.tick_calls
        win.t1 = traced.t1
    else:
        drive_until(win, until)
    jax.monitoring.unregister_event_duration_listener(compile_watch)
    gc.callbacks.remove(gc_watch)
    if guard.traces != traces0:
        raise RuntimeError(f"programs traced in the window: {traces0} -> "
                           f"{guard.traces}")
    fallbacks = eng.n_packed_fallbacks + eng.n_backend_fallbacks - fallbacks0
    if open_loop:
        win.unsent = [due for due, _, _ in arrivals.todo if due < win.t1]

    device = _device_info(jax, devices)
    sent = [x.req for x in seen]
    failed = sum(1 for r in sent if r.status in terminal
                 and r.status is not Status.DONE)
    log(f"window {win.seconds:.3f} s, {win.ticks} ticks, "
        f"{drive.tokens_in(win)} tokens, statuses {drive.statuses(win)}, "
        f"fallbacks {fallbacks}, set-up {setup_s:.2f} s")
    if trace:
        from bench.lib.trace import latest_xplane, load_xplane, reduce_events
        red = reduce_events(load_xplane(latest_xplane(trace_dir)))
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]

    # free the engine's pool before the reference runs beside the weights
    served = pick([x.req for x in seen if x.req.status is Status.DONE
                   and x.tokens and win.t0 <= x.tokens[-1] <= win.t1]
                  if open_loop else reqs, seed, tr["check"]["requests"])
    del eng
    gc.collect()
    t_check = time.time()
    check = (check_outputs(cfg, params, proj, smax, served, prompts,
                           control)
             if served else {"value": None, "tokens": 0})
    limit = float(cell.limits[CHECK]["limit"])

    run_data = Run(cfg=cfg, traffic=tr, dims=flops.dims(cfg, smax),
                   peaks=peaks, setup_s=setup_s, window=win, traced=traced,
                   trace=red)
    metrics = spec.read_metrics(cell, run_data, trace)
    result = {
        # nothing served to compare is not a pass
        "correct": check["value"] is not None and check["value"] <= limit,
        "attempted": len(sent),
        "failed": failed,
        "metrics": metrics,
        "device": device,
    }
    if red is not None:
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
    result["window"] = {"seconds": win.seconds, "ticks": win.ticks,
                        "tokens": drive.tokens_in(win),
                        "statuses": drive.statuses(win),
                        "packed_fallbacks": fallbacks,
                        "longest_ticks_ms": drive.longest_ticks(win),
                        "gc_pauses_ms": gc_pauses,
                        "compile_events": compiles}
    if open_loop:
        result["window"]["arrivals"] = drive.arrival_stats(
            win, arrivals.queued[ticks_before:])
    if keep is not None:
        keep.update(run=run_data, check=check)
    log(f"compared {len(served)} requests, {check['tokens']} served tokens "
        f"in {time.time() - t_check:.1f} s")
    log(f"check {CHECK}: {check['value']!r} (limit {limit!r})")
    result["checks"] = {CHECK: {"value": check["value"], "limit": limit}}
    return result
