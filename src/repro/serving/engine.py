"""Slot-based batched serving engine.

The paper (§6.4) finds >80% of HuggingFace decode time is KV-cache *append*
(concatenation re-allocates the cache every token). This engine removes the
append entirely: the cache is preallocated (B_slots, Smax, ...) ring storage
and decode writes in place — the design the paper defers to "a more advanced
inference system like vLLM".

Continuous batching (lite): requests join free slots; every engine tick runs
one batched decode step over all active slots; finished requests free their
slot. Per-slot positions make ragged batches exact.
"""
from __future__ import annotations

import dataclasses
import time
from typing import (Any, Callable, Dict, List, Optional, Protocol,
                    runtime_checkable)

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import lm
from repro.serving import cache_spec as CS
from repro.serving import lifecycle as LC
from repro.serving.lifecycle import Deadline, Status


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray            # (S_p,) int32
    max_new: int
    out: List[int] = dataclasses.field(default_factory=list)
    done: bool = False            # finished *normally* (== status DONE)
    # scheduling class: higher is more urgent. FIFO ignores it; the paged
    # engine's priority policy admits (and, for strictly higher classes,
    # preempts) by it. Ties fall back to arrival order.
    priority: int = 0
    t_submit: float = 0.0         # set by submit(); for latency reporting
    t_first: float = 0.0          # first generated token (TTFT reporting)
    t_done: float = 0.0           # set at any terminal status
    # encoder-decoder (whisper): precomputed frame embeddings (enc_seq,
    # d_model); the engine runs the encoder once at admission
    frames: Optional[np.ndarray] = None
    # lifecycle (serving/lifecycle.py): where the request is, why it
    # ended (terminal detail), and its wall budgets on the engine clock
    status: Status = Status.QUEUED
    detail: str = ""
    deadline: Optional[Deadline] = None
    # SHED only: the scheduler's estimate (in ticks) of when resubmitting
    # is worth trying — the backlog it shed this request to clear
    retry_after: float = 0.0
    # times this request lost its slot to preemption (scheduler-stamped;
    # feeds the shed policy's churn tie-break)
    n_preempts: int = 0


def context_cap(smax: int, gen_tokens: int) -> int:
    """Prompt rows a fresh admission may occupy: reserve headroom for the
    generation, capped at half the context so an outsized max_new degrades
    to a capacity-capped run instead of eating the whole prompt (full
    max_new is guaranteed for max_new <= smax//2). Shared by both engines
    so their admitted context — and therefore greedy outputs — agree."""
    return max(smax - min(gen_tokens, smax // 2), 1)


@runtime_checkable
class Engine(Protocol):
    """What a serving engine looks like to harnesses (benchmarks, serve
    CLI, tests): submit requests, advance ticks, cancel mid-flight, drain
    to completion, and report counters — one surface across the dense and
    paged engines, so callers never branch on the engine kind."""

    def submit(self, req: "Request") -> None: ...

    def tick(self, rng: Optional[jax.Array] = None) -> None: ...

    def cancel(self, rid: int, detail: str = "client cancel") -> bool: ...

    def drain(self, max_ticks: int = 10_000,
              rng: Optional[jax.Array] = None) -> None: ...

    def stats(self) -> Dict[str, Any]: ...


def oversized_reason(prompt_len: int, max_new: int,
                     smax: int) -> Optional[str]:
    """Why a request can never be held whole in an ``smax``-row context,
    or None if it fits. Shared by both engines' strict admission so a
    doomed request FAILs at ``submit()`` with a clear reason instead of
    being silently truncated (prompt) or capped (generation) deep inside
    admission (a request with ``prompt + max_new == smax`` exactly fills
    the context: its last token lands at row smax - 1)."""
    if prompt_len < 1:
        return "empty prompt"
    if max_new < 1:
        return f"max_new={max_new} < 1"
    if prompt_len + max_new > smax:
        return (f"prompt ({prompt_len}) + max_new ({max_new}) exceeds "
                f"context capacity {smax}; shorten one or raise smax")
    return None


def sample_next(logits, *, greedy: bool, rng, ticks: int):
    """Shared next-token rule for both engines: greedy argmax, or
    categorical with the caller's key (falling back to PRNGKey(tick) —
    thread a real rng via run_until_done for independent draws)."""
    with jax.named_scope("head"):
        if greedy:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        rng = rng if rng is not None else jax.random.PRNGKey(ticks)
        return jax.random.categorical(rng, logits).astype(jnp.int32)


class ServingEngine:
    """Dense slot engine.

    admission  'strict' (default) FAILs requests whose prompt + max_new
               can never fit the smax-row context at ``submit()``;
               'lenient' keeps the legacy degraded modes (prompt
               truncated to the most recent context, generation capped
               at capacity)
    clock      zero-arg wall clock (default time.time) stamping
               t_submit/t_first/t_done and driving Request.deadline
               expiry — inject lifecycle.ManualClock for determinism
    """

    def __init__(self, params, cfg: ModelConfig, *, n_slots: int = 4,
                 smax: int = 512, eos_id: Optional[int] = None,
                 greedy: bool = True, backend: Optional[str] = None,
                 admission: str = "strict", clock=None,
                 trace_guard=None):
        if backend is not None:
            # route the decode hot path through the chosen kernel backend
            # (core/dispatch.py): "pallas" | "xla" | "auto"
            cfg = cfg.replace(
                loki=dataclasses.replace(cfg.loki, backend=backend))
        if admission not in ("strict", "lenient"):
            raise ValueError(f"admission={admission!r}; "
                             "use 'strict' or 'lenient'")
        self.params, self.cfg = params, cfg
        self.n_slots, self.smax = n_slots, smax
        self.eos_id, self.greedy = eos_id, greedy
        self.admission = admission
        self._clock = clock or time.time
        self.lifecycle_counts: Dict[str, int] = {}
        self.n_stalled = 0
        self.stalled_rids: List[int] = []
        self.cache = lm.init_cache(cfg, n_slots, smax, jnp.float32)
        # recurrent-state families only: batch-1 init values so an
        # admission that skips prefill (1-token prompt) can reset its
        # slot's state — a previous occupant's mamba/xlstm state must not
        # leak into the new request. Attention-only families need nothing:
        # stale K/V rows beyond the slot's position are unreachable.
        self._fresh_state = CS.fresh_state_tree(cfg, jnp.float32,
                                                include_cross=False)
        # positions / last tokens live on the HOST: per-slot bookkeeping
        # writes stay cheap in-place numpy ops and cross to the device
        # once per jitted call, never the other way around
        self.pos = np.zeros((n_slots,), np.int32)
        self.live = np.zeros((n_slots,), bool)
        self.slot_req: List[Optional[Request]] = [None] * n_slots
        self.last_tok = np.zeros((n_slots,), np.int32)
        wrap = trace_guard.wrap if trace_guard is not None \
            else (lambda _n, f: f)
        # the cache is donated: tick always replaces self.cache with the
        # result, so the old buffer is dead on return (no-op on CPU)
        self._decode = jax.jit(
            wrap("decode_step",
                 lambda p, c, t, pl: lm.decode_step(p, cfg, c, t, pl)),
            donate_argnums=(1,))
        # admission-path prefill, compiled; jit's cache retraces only per
        # distinct prompt length. It *creates* the returned cache, so
        # there is nothing to donate.
        self._prefill = jax.jit(
            wrap("prefill",
                 lambda p, t, fr: lm.prefill(p, cfg, t, smax, frames=fr,
                                             cache_dtype=jnp.float32)))
        self._queue: List[Request] = []
        self.ticks = 0

    # -------------------------------------------------------- lifecycle

    def _terminal(self, req: Request, status: Status,
                  detail: str = "") -> None:
        """Move a request to a terminal status with the shared stamps."""
        # lifecycle: live -> terminal
        LC.transition(req, status, detail)
        req.t_done = self._clock()
        self.lifecycle_counts[str(status)] = \
            self.lifecycle_counts.get(str(status), 0) + 1

    def _evict_slot(self, slot: int) -> None:
        """Drop a slot's occupant without a DONE transition (cancel /
        timeout): the stale cache rows beyond a future occupant's
        position are unreachable, so clearing the bookkeeping is enough."""
        self.live[slot] = False
        self.slot_req[slot] = None

    def cancel(self, rid: int, detail: str = "client cancel") -> bool:
        """Terminate a request by id, queued or mid-generation. Returns
        False when no live request has this rid (already terminal ids
        are not resurrected)."""
        for i, req in enumerate(self._queue):
            if req.rid == rid:
                del self._queue[i]
                self._terminal(req, Status.CANCELLED, detail)
                return True
        for slot in range(self.n_slots):
            req = self.slot_req[slot]
            if req is not None and req.rid == rid:
                self._terminal(req, Status.CANCELLED, detail)
                self._evict_slot(slot)
                return True
        return False

    def _expire_deadlines(self) -> None:
        now = self._clock()
        for req in [r for r in self._queue
                    if LC.breach(r.deadline, now, r.t_submit, bool(r.out))]:
            why = LC.breach(req.deadline, now, req.t_submit, bool(req.out))
            self._queue.remove(req)
            self._terminal(req, Status.TIMED_OUT, why)
        for slot in range(self.n_slots):
            req = self.slot_req[slot]
            if req is None:
                continue
            why = LC.breach(req.deadline, now, req.t_submit, bool(req.out))
            if why:
                self._terminal(req, Status.TIMED_OUT, why)
                self._evict_slot(slot)

    # ------------------------------------------------------------ admin

    def submit(self, req: Request) -> None:
        req.t_submit = self._clock()
        if self.admission == "strict":
            why = oversized_reason(len(req.prompt), req.max_new, self.smax)
            if why:
                self._terminal(req, Status.FAILED, f"oversized: {why}")
                return
        self._queue.append(req)

    def _admit(self) -> None:
        for slot in range(self.n_slots):
            if self.live[slot] or not self._queue:
                continue
            req = self._queue.pop(0)
            self._prefill_slot(slot, req)

    def _prefill_slot(self, slot: int, req: Request) -> None:
        """Single-request batched prefill into one slot.

        One causal-attention pass over the whole prompt, scattered into the
        slot's cache rows only — live slots are untouched. (The previous
        token-by-token fill ran a full batched decode step per prompt token,
        rewriting every live slot's cache at its current position.)"""
        # lifecycle: QUEUED -> PREFILL
        LC.transition(req, Status.PREFILL)
        toks = req.prompt.astype(np.int32)
        # cache can hold smax rows; keep the most recent context AND leave
        # generation headroom — truncating to smax itself left pos at
        # smax-1, so the finish guard ended the request after a single
        # generated token
        cap = context_cap(self.smax, req.max_new)
        if len(toks) > cap:
            toks = toks[-cap:]
        self.pos[slot] = 0
        fr = None
        if self.cfg.is_encoder_decoder:
            if req.frames is None:
                raise ValueError("encoder-decoder serving needs "
                                 "Request.frames (enc_seq, d_model)")
            fr = jnp.asarray(req.frames)[None]
        if len(toks) > 1:
            _, filled, _ = self._prefill(self.params,
                                         jnp.asarray(toks[None, :-1]), fr)
            self._write_slot(slot, filled)
            self.pos[slot] = len(toks) - 1
        elif self.cfg.is_encoder_decoder:
            # 1-token prompt: nothing to cache, but the slot still needs
            # its cross K/V — prefill the single token and keep pos=0 (the
            # decode step rewrites the same cache row with identical
            # values, so the continuation is unchanged)
            _, filled, _ = self._prefill(self.params, jnp.asarray(toks[None]),
                                         fr)
            self._write_slot(slot, filled)
        elif self._fresh_state is not None:
            self.cache = {"layers": CS.reset_slot_state(
                self.cache["layers"], self._fresh_state, slot,
                lm.uses_scan(self.cfg))}
        self.last_tok[slot] = int(toks[-1])
        self.slot_req[slot] = req
        self.live[slot] = True
        # lifecycle: PREFILL -> DECODE
        LC.transition(req, Status.DECODE)

    def _write_slot(self, slot: int, one) -> None:
        """Overwrite one slot's cache slice with a (batch-1) cache tree."""
        axis = 1 if lm.uses_scan(self.cfg) else 0      # skip the layer axis
        self.cache = jax.tree.map(
            lambda full, single: jax.lax.dynamic_update_slice_in_dim(
                full, single.astype(full.dtype), slot, axis=axis),
            self.cache, one)

    # ------------------------------------------------------------- tick

    def tick(self, rng: Optional[jax.Array] = None) -> None:
        self._expire_deadlines()
        self._admit()
        if not self.live.any():
            return
        # snapshots, not the live host arrays: the call returns before the
        # step runs, and the CPU backend reads a numpy argument in place
        # (no copy), so the ``pos`` update below would race the step
        logits, self.cache = self._decode(
            self.params, self.cache, self.last_tok.copy(), self.pos.copy())
        self.pos += self.live.astype(np.int32)
        nxt = sample_next(logits, greedy=self.greedy, rng=rng,
                          ticks=self.ticks)
        # host-sync: the one batched device->host sync of the tick — the
        # sampled tokens must reach Python to drive per-request lifecycle
        nxt_np = jax.device_get(nxt)
        for slot in range(self.n_slots):
            req = self.slot_req[slot]
            if req is None or not self.live[slot]:
                continue
            tok = int(nxt_np[slot])
            req.out.append(tok)
            if len(req.out) == 1:
                req.t_first = self._clock()
            finished = (len(req.out) >= req.max_new
                        or (self.eos_id is not None and tok == self.eos_id)
                        or int(self.pos[slot]) >= self.smax - 1)
            if finished:
                self._terminal(req, Status.DONE)
                self._evict_slot(slot)
            else:
                self.last_tok[slot] = tok
        self.ticks += 1

    def run_until_done(self, max_ticks: int = 10_000,
                       rng: Optional[jax.Array] = None) -> None:
        """Drive ticks to completion. ``rng`` (non-greedy sampling): split a
        fresh subkey per tick — without it every run re-derives
        PRNGKey(tick) and two engines sampling the same tick draw identical
        tokens.

        Hitting ``max_ticks`` with work still pending is a *stall*, and it
        is reported instead of silently returned from: every still-queued
        or still-running request is marked TIMED_OUT and counted in
        ``stats()['n_stalled']`` so hangs show up in tests and benches."""
        for _ in range(max_ticks):
            if not self._queue and not self.live.any():
                return
            sub = None
            if rng is not None:
                rng, sub = jax.random.split(rng)
            self.tick(sub)
        self._report_stall()

    def _report_stall(self) -> None:
        detail = "stalled: drain hit max_ticks"
        for req in list(self._queue):
            self._queue.remove(req)
            self._terminal(req, Status.TIMED_OUT, detail)
            self.n_stalled += 1
            self.stalled_rids.append(req.rid)
        for slot in range(self.n_slots):
            req = self.slot_req[slot]
            if req is None:
                continue
            self._terminal(req, Status.TIMED_OUT, detail)
            self._evict_slot(slot)
            self.n_stalled += 1
            self.stalled_rids.append(req.rid)

    # ------------------------------------------- Engine protocol surface

    def drain(self, max_ticks: int = 10_000,
              rng: Optional[jax.Array] = None) -> None:
        """Engine protocol: run ticks until no request is queued or live."""
        self.run_until_done(max_ticks, rng)

    def stats(self) -> Dict[str, Any]:
        """Engine protocol: serving counters. The dense engine has no pool,
        so pool-specific keys are simply absent — shared keys match the
        paged engine's."""
        return {"engine": "dense", "ticks": self.ticks,
                "lifecycle": dict(self.lifecycle_counts),
                "n_stalled": self.n_stalled,
                "stalled_rids": list(self.stalled_rids)}
