"""Paged serving engine: policy-driven scheduling over a refcounted pool.

Replaces the dense engine's ``(n_slots, Smax, ...)`` preallocation with the
shared page pool of serving/paged_cache.py and a tick split into three
**policy-driven phases** (serving/policy.py):

  admission  waiting requests take free slots in ``SchedulerPolicy`` order
             (FIFO or priority classes); under the priority policy a
             strictly-more-urgent waiter may preempt the least-urgent
             running request for its slot
  prefill    mid-prefill slots advance by fixed-size chunks until the
             per-tick **prefill token budget** is spent — several small
             chunks, or several waiting prompts, share one tick
  decode     one batched ``lm.decode_step`` over the selected live slots
             (at most the **decode token budget**; selection round-robins
             within a policy class so a tight budget never starves a
             stream), with per-slot positions and page tables keeping
             ragged batches exact

What a slot *holds* is declared by the per-layer CacheSpec table
(serving/cache_spec.py) — PagedAttn / WindowPagedAttn (recycled) /
StateSlot / CrossAttnStatic — so every family in configs/ serves here
(DESIGN.md §8).

**Prefix caching** (DESIGN.md §9): for configs whose components are all
``shareable`` (state-free, full-attention families), full prompt pages are
registered in the pool's content-hash index as prefill writes them. A
later request whose prompt starts with the same tokens *acquires* those
pages (refcount++) and starts its query stream at the first uncached
token — chunks fully covered by cached pages are never computed. Cached
pages hold storage-basis keys, so Loki scoring over them is exact (Lemma
4.1). When the match ends mid-page the tail page is shared read-only and
**copy-on-write** duplicates it the moment this request must write its
own rows. Unreferenced cached pages form an LRU that ``alloc`` reclaims
*before* the scheduler ever preempts a live request.

Under memory pressure the scheduler *preempts* the least-urgent request
by the policy's order (vLLM's recompute policy — under FIFO an older
request is never evicted for a younger one): its references are released
— never force-freed, shared pages survive for their other readers — and
it is requeued with its generated tokens folded into the prompt.
StateSlot layers are handled by recompute, except pure-state families
(no pages to rebuild), whose tiny recurrent state is **snapshotted to
host** at preemption and restored at re-admission so the folded prompt is
not re-run. ``n_pages - 1 >=`` the per-request page bound is enforced at
construction, so a lone request can always run to its length cap and
preemption cannot livelock.

Decode numerics are the dense engine's: the jnp policies read the gathered
logical view (bit-compatible with a dense cache of the same logical
length), the ``loki_block`` Pallas path indexes the pool directly through
the page table (DESIGN.md §7, §8).

**Request lifecycle + fault tolerance** (DESIGN.md §11): every request
walks the serving/lifecycle.py status machine (QUEUED -> PREFILL ->
DECODE -> DONE | CANCELLED | TIMED_OUT | FAILED | SHED), with per-request
deadlines on the engine's injected clock, a ``cancel(rid)`` that frees
refcounted pages / COW tails / state snapshots mid-generation without
disturbing shared-prefix readers, and a degradation ladder under faults
(serving/faults.py): NaN-poisoned slots are quarantined and FAILed
individually instead of poisoning the batch; a fused-Pallas decode
failure disables the backend (core/dispatch.py) and re-runs the tick on
the XLA path; sustained pool pressure sheds the least-urgent request
(terminal SHED + retry-after hint) once it has churned through
``shed_after`` preemptions, instead of livelocking on recompute churn.
An optional per-tick invariant auditor (``audit=True``) cross-checks the
pool's refcounts, the slots' page lists and the device page table after
every tick, turning silent corruption into a loud ``AuditError``.
"""
from __future__ import annotations

import collections
import dataclasses
import re
import time
from typing import Any, Deque, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import dispatch
from repro.kernels import layout
from repro.models import lm
from repro.serving import cache_spec as CS
from repro.serving import faults as FI
from repro.serving import lifecycle as LC
from repro.serving import paged_cache as PC
from repro.serving.engine import (Request, context_cap, oversized_reason,
                                  sample_next)
from repro.serving.lifecycle import Status
from repro.serving.paged_cache import PagePool
from repro.serving.policy import SchedulerPolicy, TickBudget, make_policy
from repro.serving.tracing import TickLog

PAGED_POLICIES = ("full", "exact_topk", "loki", "loki_block")
# paged policies whose decode runs the Pallas kernels under backend pallas
_KERNEL_POLICIES = ("full", "exact_topk", "loki_block")

# miss-repair bound for the tiered decode: run 1 discovers the first
# off-device winners, run 2 can still shift deeper layers' selections
# (their run-1 scores attended trash rows), run 3 is fully resident in
# every observed trace — 4 leaves one run of slack before declaring
# promotion/selection ping-pong
_TIERED_MAX_RUNS = 4


def _named(name: str, fn):
    """Give a program's traced function ``name`` (``decode_step``,
    ``decode_step_packed_b8``), so its compiled program is
    ``jit_<name>`` in the profiler trace instead of ``jit__lambda_``."""
    fn.__name__ = fn.__qualname__ = re.sub(r"\W+", "_", name).strip("_")
    return fn


def _dus(full, one, slot, axis):
    return jax.lax.dynamic_update_slice_in_dim(
        full, one.astype(full.dtype), slot, axis=axis)


class PagedServingEngine:
    """Continuous-batching engine over a paged KV-cache (all families).

    n_slots        decode batch width (concurrent *running* requests)
    smax           logical context cap per request (rounded up to pages)
    page_size      tokens per page; defaults to ``cfg.loki.block_size`` so
                   pages coincide with the fused kernel's DMA blocks
    n_pages        physical pool size incl. the reserved trash page;
                   defaults to fitting every slot at its spec-table page
                   bound (pass less to exercise pressure / preemption)
    prefill_chunk  prompt tokens processed per chunk (fixed-size, padded)
    policy         'fifo' | 'priority' | a SchedulerPolicy instance
    prefill_budget prompt tokens computed per tick (default: one chunk)
    decode_budget  live slots decoded per tick (default: all of them)
    prefix_cache   share identical prompt-prefix pages across requests
                   (auto-bypassed for configs with unshareable components)
    admission      'strict' (default) FAILs requests whose prompt +
                   max_new can never fit smax at submit(); 'lenient'
                   keeps the legacy truncate/cap degraded modes
    clock          zero-arg wall clock (default time.time) stamping
                   request times and driving deadline expiry — inject
                   lifecycle.ManualClock for deterministic tests
    shed_after     preemptions a request survives before the scheduler
                   sheds it (terminal SHED + retry-after hint) instead of
                   requeueing — anti-churn under sustained pool pressure;
                   None (default) never sheds
    faults         serving/faults.py FaultPlan consulted by the pool,
                   this scheduler and the decode dispatch; None = off
    audit          run the serving/faults.py invariant auditor after
                   every tick (raises AuditError on violation)
    nan_guard      quarantine slots whose decode logits go non-finite
                   (FAIL that request alone, keep the batch serving)
    device_pages   tiered KV pool (DESIGN.md §13): only this many pages
                   (incl. the trash frame) keep full-D K/V rows in HBM;
                   the rest live in host buffers, always scoreable
                   through the resident latent-K sidecar, and are
                   promoted back on demand when Loki's selection attends
                   them. Requires a Loki policy over a non-quantized
                   layout. None (default) = single-tier, all-resident.
    max_inflight   outstanding async host->HBM fetches the tiered pool's
                   fetch queue may hold (default 2: double-buffered)
    packed         gather-packed decode (DESIGN.md §14): compact the
                   tick's live slots into a dense batch padded to a
                   power-of-two bucket, so decode FLOPs scale with
                   occupancy instead of ``n_slots``. Bucket programs jit
                   lazily; under a sealed TraceGuard an unwarmed bucket
                   falls back to the full-width masked program instead of
                   recompiling in the hot path. False = always masked
                   full-width (the A/B benchmarking baseline).
    """

    def __init__(self, params, cfg: ModelConfig, *, n_slots: int = 4,
                 smax: int = 512, page_size: Optional[int] = None,
                 n_pages: Optional[int] = None, prefill_chunk: int = 32,
                 eos_id: Optional[int] = None, greedy: bool = True,
                 backend: Optional[str] = None,
                 policy="fifo", prefill_budget: Optional[int] = None,
                 decode_budget: Optional[int] = None,
                 prefix_cache: bool = True, admission: str = "strict",
                 clock=None, shed_after: Optional[int] = None,
                 faults: Optional[FI.FaultPlan] = None,
                 audit: bool = False, nan_guard: bool = True,
                 trace_guard=None, donate: bool = True,
                 device_pages: Optional[int] = None,
                 max_inflight: int = 2, packed: bool = True):
        if backend is not None:
            cfg = cfg.replace(
                loki=dataclasses.replace(cfg.loki, backend=backend))
        CS.assert_pageable(cfg)
        self.specs = CS.layer_specs(cfg)
        self.has_pages = CS.has_paged_attn(cfg)
        self.has_state = CS.has_state_slots(cfg)
        self.is_encdec = cfg.is_encoder_decoder
        if self.has_pages and cfg.attn_policy() not in PAGED_POLICIES:
            raise ValueError(
                f"policy {cfg.attn_policy()!r} cannot serve from a paged "
                f"cache (supported: {PAGED_POLICIES}); use ServingEngine")
        self.params, self.cfg = params, cfg
        self.page_size = page_size or cfg.loki.block_size
        self.max_pages = -(-smax // self.page_size)
        self.smax = self.max_pages * self.page_size      # logical cap
        self.n_slots = n_slots
        self.prefill_chunk = prefill_chunk
        self.eos_id, self.greedy = eos_id, greedy
        self.policy: SchedulerPolicy = make_policy(policy)
        self.budget = TickBudget(
            prefill_tokens=prefill_budget or prefill_chunk,
            decode_tokens=decode_budget or n_slots)
        shareable, why = CS.prefix_shareable(cfg)
        self.prefix_caching = bool(prefix_cache and shareable)
        self.prefix_cache_reason = (
            "" if not prefix_cache else why)     # bypass reason, if any

        # page accounting from the spec table: ``req_budget`` is the
        # decode-phase bound per request (summed over its page-table
        # groups); ``_group_pages_hard`` additionally covers a mid-prefill
        # chunk, whose pages can't be recycled until the chunk's earliest
        # query has moved past them. Layers whose windows differ keep
        # separate page tables (DESIGN.md §14): group 0 owns the primary
        # table and every existing mechanism (prefix cache, COW,
        # snapshots); groups 1.. are aux window groups that grow and
        # recycle in lockstep with it but at their own window
        self.window = CS.recycle_window(cfg)
        self.group_windows = CS.group_windows(cfg)
        self.n_groups = max(len(self.group_windows), 1)
        self.req_budget = CS.request_page_budget(cfg, self.smax,
                                                 self.page_size)

        def hard(w: int) -> int:
            if w:
                return min(self.max_pages, CS.window_page_budget(
                    w + self.prefill_chunk - 1, self.page_size))
            return self.max_pages
        if self.group_windows:
            self._group_pages_hard = [hard(w) for w in self.group_windows]
        else:
            self._group_pages_hard = [hard(self.window) if self.window
                                      else self.req_budget]
        self._req_pages_hard = sum(self._group_pages_hard)
        if n_pages is None:
            n_pages = 1 + max(n_slots * self._req_pages_hard, 1)
        if self.has_pages and n_pages - 1 < self._req_pages_hard:
            raise ValueError(
                f"pool of {n_pages} pages cannot hold one full request "
                f"({self._req_pages_hard} pages); raise n_pages or lower "
                "smax")

        self.tiered = device_pages is not None
        if self.tiered:
            pol = cfg.attn_policy()
            if pol not in ("loki", "loki_block"):
                raise ValueError(
                    "tiered KV pool needs a Loki policy (its latent "
                    f"sidecar drives the score pass), not {pol!r}")
            if cfg.page_layout.quantized:
                raise ValueError(
                    "tiered KV pool requires a non-quantized page layout: "
                    "quantized row writes re-derive per-page scales, so "
                    "the miss-repair replay would not be bit-idempotent")
            if not (self.has_pages and lm.uses_scan(cfg)):
                raise ValueError("tiered KV pool needs paged attention "
                                 "layers in a scan family")
            if self.n_groups > 1:
                raise ValueError(
                    "tiered KV pool does not compose with per-layer "
                    "page-table groups (cfg.window_layers): the frame "
                    "table and pin ledger are single-table")
            if device_pages - 1 < self._req_pages_hard:
                raise ValueError(
                    f"device pool of {device_pages} frames cannot hold "
                    f"one full request ({self._req_pages_hard} pages); "
                    "raise device_pages or lower smax")

        if admission not in ("strict", "lenient"):
            raise ValueError(f"admission={admission!r}; "
                             "use 'strict' or 'lenient'")
        self.admission = admission
        self._clock = clock or time.time
        # per tick: each phase's milliseconds and the tick's counters
        # (serving/tracing.py), inside profiler spans of the same names
        self.tick_log = TickLog(self._clock)
        self.shed_after = shed_after
        self._faults = faults
        self.audit = audit
        self.nan_guard = nan_guard
        self.lifecycle_counts: Dict[str, int] = {}
        self.n_stalled = 0
        self.stalled_rids: List[int] = []
        self.n_quarantined = 0
        self.n_shed = 0
        self.n_backend_fallbacks = 0

        if (self.has_pages and cfg.attn_policy() in _KERNEL_POLICIES
                and dispatch.resolve_backend(cfg.loki.backend) == "pallas"):
            dispatch.check_tpu_pool(PC.STORAGE_DTYPE[cfg.page_layout.dtype])

        self.pool = PagePool(n_pages, self.page_size,
                             device_pages=device_pages,
                             max_inflight=max_inflight)
        if faults is not None:
            self.pool.set_faults(faults)
        self.cache = lm.init_paged_cache(cfg, n_pages, self.page_size,
                                         jnp.float32, n_slots=n_slots,
                                         device_pages=device_pages)
        self._fresh_state = CS.fresh_state_tree(cfg, jnp.float32)
        # page table / positions / last tokens live on the HOST: every
        # per-slot update between ticks is a cheap in-place numpy write,
        # and the arrays cross to the device once per jitted call instead
        # of forcing a device round-trip per bookkeeping touch
        self.page_table = np.zeros((n_slots, self.max_pages), np.int32)
        self.pos = np.zeros((n_slots,), np.int32)
        self.last_tok = np.zeros((n_slots,), np.int32)
        self.live = np.zeros((n_slots,), bool)
        self.slot_req: List[Optional[Request]] = [None] * n_slots
        # logical page index -> physical page id, or None once recycled
        # (window slide); ``len`` is the logical coverage, the number of
        # non-None entries is what the slot actually holds
        self.slot_pages: List[List[Optional[int]]] = [
            [] for _ in range(n_slots)]
        # aux page-table groups 1..n-1 mirror the primary table's shape;
        # their pages are never prefix-shared (any multi-group config has
        # a WindowPagedAttn component, which bypasses prefix caching), so
        # every aux page is sole-owned and COW/registration never apply
        self.aux_tables: List[np.ndarray] = [
            np.zeros((n_slots, self.max_pages), np.int32)
            for _ in range(self.n_groups - 1)]
        self.aux_pages: List[List[List[Optional[int]]]] = [
            [[] for _ in range(n_slots)] for _ in range(self.n_groups - 1)]
        # slot -> logical index of a shared tail page this request must
        # copy-on-write before its first write lands in it (full-page
        # prefix hits need no COW: the slot never writes below its first
        # uncached token, so only the partial tail can collide)
        self._cow_pending: Dict[int, int] = {}
        # prefix-cache registration cursor per slot: next full prompt page
        # to publish, and the chain hash of everything before it
        self._reg_next: Dict[int, int] = {}
        self._reg_parent: Dict[int, bytes] = {}
        # slots mid-prefill: slot -> index of the next prompt token to feed
        self._prefill_at: Dict[int, int] = {}
        # admission order, oldest first — used for phase iteration; the
        # *policy* key decides urgency and preemption victims
        self._admit_order: List[int] = []
        self._queue: Deque[Request] = collections.deque()
        # generated tokens already folded back into req.prompt by earlier
        # preemptions (keyed by object id; a second preemption must only
        # fold the tokens generated since the last one)
        self._folded: Dict[int, int] = {}
        # original submission order (survives preemption/re-admission) —
        # the tie-break inside a policy class, so FIFO's "an older request
        # is never evicted for a younger one" guarantee holds per class
        self._arrival: Dict[int, int] = {}
        self._arrival_seq = 0
        # host snapshots of preempted StateSlot state: id(req) ->
        # (tokens consumed, batch-1 state tree). Pure-state families
        # restore unconditionally; hybrids (state + paged K/V, e.g. hymba)
        # additionally park their own K/V pages as private pool entries
        # (``_page_snap``) and restore only when the *whole* retained set
        # survived the interim — recompute stays the fallback
        self._state_snap: Dict[int, Tuple[int, Any]] = {}
        self._page_snap: Dict[
            int, Tuple[List[Optional[int]], List[bytes]]] = {}
        self._snap_eligible = self.has_state
        self._last_decoded = np.zeros((n_slots,), np.int64)
        self.ticks = 0
        self.n_preempted = 0
        self.n_recycled_pages = 0
        self.peak_slot_pages = 0       # max pages any slot held at once
        self.n_prefill_computed_tokens = 0
        self.n_pages_grown = 0
        self.n_cow_copies = 0
        self.n_state_restores = 0
        # tiered-pool engine state (DESIGN.md §13): host byte buffers for
        # demoted pages, the per-slot pinned write-target, a last-use tick
        # per page driving the cold-resident demotion order, and the
        # bounded async fetch queue
        self._host_kv: Dict[int, Tuple[np.ndarray, np.ndarray]] = {}
        self._pinned_tail: Dict[int, int] = {}
        self._page_last_use: Dict[int, int] = {}
        self.n_prefetch_hits = 0
        self.n_prefetch_misses = 0
        self.n_sync_fetches = 0
        self.n_decode_reruns = 0
        self._fetch = (PC.FetchQueue(self.pool, self._promote_copy,
                                     faults=faults)
                       if self.tiered else None)
        self._trace_guard = trace_guard
        self._donate = donate       # False only for A/B benchmarking
        # gather-packed decode: tiered decode already packs its work by
        # re-running only missing slots, and its winner-mask bookkeeping
        # is slot-indexed — keep it on the full-width masked program
        self.packed = bool(packed) and not self.tiered
        self.n_packed_ticks = 0
        self.n_masked_ticks = 0
        self.n_packed_rows_saved = 0   # (n_slots - bucket) summed
        self.n_packed_fallbacks = 0    # sealed-guard unwarmed buckets

        self._build_programs()

    def _build_programs(self) -> None:
        """(Re-)jit the engine's compiled closures. Called once at
        construction and again by the backend-fallback path: after
        ``dispatch.disable_backend('pallas')`` a fresh jit retraces, and
        the retrace resolves to the XLA path."""
        cfg, ps = self.cfg, self.page_size
        guard = self._trace_guard
        if guard is not None:
            guard.rebuild()     # legitimate retrace window re-opens
        wrap = self._wrap
        # the cache argument is donated on every cache-updating program:
        # the caller always replaces ``self.cache`` with the result, so
        # the old buffer is dead on return and XLA may update in place
        # (CPU silently ignores donation; the kernel-fallback re-run in
        # ``_run_decode`` is safe because the injected failure raises
        # before dispatch ever consumes the buffer)
        self._decode = jax.jit(
            wrap("decode_step",
                 lambda p, pool, t, pl, pt, lv: lm.decode_step(
                     p, cfg, pool, t, pl, page_table=pt, page_size=ps,
                     live=lv)),
            donate_argnums=(1,) if self._donate else ())
        # per-bucket packed decode programs jit lazily (_packed_program);
        # a rebuild invalidates them all so the retrace resolves to the
        # surviving backend exactly like the programs above
        self._decode_packed: Dict[int, Any] = {}
        self._chunk = jax.jit(
            wrap("prefill_chunk",
                 lambda p, pool, toks, start, nv, row, sl:
                 lm.prefill_chunk(p, cfg, pool, toks, start, nv, row, ps,
                                  slot=sl)),
            donate_argnums=(1,) if self._donate else ())
        self._copy_page = jax.jit(
            wrap("copy_cache_page",
                 lambda pool, s, d: lm.copy_cache_page(cfg, pool, s, d,
                                                       ps)),
            donate_argnums=(0,) if self._donate else ())
        if self.tiered:
            self._decode_t = jax.jit(
                wrap("decode_step_tiered",
                     lambda p, pool, t, pl, pt, ft, lv: lm.decode_step(
                         p, cfg, pool, t, pl, page_table=pt, page_size=ps,
                         live=lv, frame_table=ft)),
                donate_argnums=(1,) if self._donate else ())
            self._chunk_t = jax.jit(
                wrap("prefill_chunk_tiered",
                     lambda p, pool, toks, start, nv, row, fr, sl:
                     lm.prefill_chunk(p, cfg, pool, toks, start, nv, row,
                                      ps, slot=sl, frame_row=fr)),
                donate_argnums=(1,) if self._donate else ())
            self._copy_page_t = jax.jit(
                wrap("copy_cache_page_tiered",
                     lambda pool, s, d, sf, df: lm.copy_cache_page(
                         cfg, pool, s, d, ps, src_frame=sf, dst_frame=df)),
                donate_argnums=(0,) if self._donate else ())
            self._promote_write = jax.jit(
                wrap("promote_page_rows",
                     lambda pool, k, v, f: lm.promote_page_rows(
                         cfg, pool, k, v, f, ps)),
                donate_argnums=(0,) if self._donate else ())
            if self._fresh_state is not None:
                # batched rewind for the miss-repair re-run: one masked
                # restore over every stale slot at once (tiered requires a
                # scan family, so the slot axis of every state leaf is 1)
                def rewind(sub, snap, stale):
                    def mask_one(cur, sv):
                        m = stale.reshape((1, -1) + (1,) * (cur.ndim - 2))
                        return jnp.where(m, sv, cur)
                    return jax.tree.map(mask_one, sub, snap)
                self._rewind = jax.jit(
                    wrap("tiered_rewind", rewind),
                    donate_argnums=(0,) if self._donate else ())
        if self.is_encdec:
            self._encode_cross = jax.jit(
                lambda p, fr: lm.encode_cross_kv(p, cfg, fr))

    def _wrap(self, name: str, fn):
        """A program's traced function: counted by the trace guard, if
        any, and named ``name``. Its arguments keep ``fn``'s names, so
        the ops XLA inserts on the cache argument (layout copies) carry
        ``pool`` in their metadata, like the ops under the device scope
        ``pool`` (models/lm.py)."""
        guard = self._trace_guard
        if guard is None:
            return _named(name, fn)
        traced = guard.wrap(name, fn)
        traced.__wrapped__ = fn
        return _named(name, traced)

    def _packed_program(self, bucket: int):
        """The packed decode program for one bucket width, jitted on
        first use — or None when the trace guard is sealed and this
        bucket was never warmed, in which case the caller runs the
        full-width masked program instead of recompiling mid-hot-path."""
        prog = self._decode_packed.get(bucket)
        if prog is not None:
            return prog
        guard = self._trace_guard
        name = f"decode_step_packed[b{bucket}]"
        if guard is not None and guard.sealed \
                and not guard.traces.get(name):
            return None
        cfg, ps, wrap = self.cfg, self.page_size, self._wrap
        prog = jax.jit(
            wrap(name,
                 lambda p, pool, t, pl, pt, lv, si: lm.decode_step(
                     p, cfg, pool, t, pl, page_table=pt, page_size=ps,
                     live=lv, slot_idx=si)),
            donate_argnums=(1,) if self._donate else ())
        self._decode_packed[bucket] = prog
        return prog

    # --------------------------------------------------- per-slot state

    def _group_tables(self) -> List[np.ndarray]:
        """Every group's host page table, primary (group 0) first."""
        return [self.page_table] + self.aux_tables

    def _group_pages(self, g: int) -> List[List[Optional[int]]]:
        """Group ``g``'s per-slot logical page lists."""
        return self.slot_pages if g == 0 else self.aux_pages[g - 1]

    def _key(self, req: Request):
        """The policy's urgency key (smaller = more urgent)."""
        return self.policy.sort_key(req, self._arrival[id(req)])

    def _reset_slot_state(self, slot: int) -> None:
        """(Re-)admission: zero the slot's recurrent state so a previous
        occupant cannot leak into this request — preemption recovery is
        recompute, and recompute must start from the fresh state."""
        if self._fresh_state is None:
            return
        self.cache = {"layers": CS.reset_slot_state(
            self.cache["layers"], self._fresh_state, slot,
            lm.uses_scan(self.cfg))}

    def _drop_page_snap(self, psnap) -> None:
        """Discard a retained-page set: reclaim whatever private entries
        still exist and return their pages to the free list."""
        if psnap is None:
            return
        pages = self.pool.reclaim_private(psnap[1])
        if pages:
            self.pool.release(pages)
            if self.tiered:
                self._prune_host()

    def _try_restore_state(self, slot: int, req: Request,
                           n_pre: int) -> Optional[int]:
        """Snapshot-on-preemption restore: write the host snapshot back
        into the slot and return the number of prompt tokens it already
        folded in, or None when recompute must run. Pure-state families
        need only the snapshot; hybrids also reclaim their retained K/V
        pages — all-or-nothing, since a state snapshot over a partial K/V
        prefix would attend garbage."""
        snap = self._state_snap.get(id(req))
        psnap = self._page_snap.pop(id(req), None)
        if snap is None or not self._snap_eligible:
            self._drop_page_snap(psnap)
            return None
        consumed, tree = snap
        if not 1 <= consumed <= n_pre:
            self._drop_page_snap(psnap)
            return None
        if self.has_pages:
            if psnap is None:
                return None
            pages_list, keys = psnap
            if self.pool.reclaim_private(keys) is None:
                # pool pressure evicted part of the retained set while we
                # were queued: the snapshot is unusable, recompute instead
                return None
            self.slot_pages[slot] = list(pages_list)
            row = np.zeros((self.max_pages,), np.int32)
            for i, pg in enumerate(pages_list):
                if pg is not None:
                    row[i] = pg
            self.page_table[slot] = row
            self.peak_slot_pages = max(
                self.peak_slot_pages,
                sum(p is not None for p in pages_list))
        self.cache = {"layers": CS.reset_slot_state(
            self.cache["layers"], jax.tree.map(jnp.asarray, tree), slot,
            lm.uses_scan(self.cfg))}
        self.n_state_restores += 1
        return consumed

    def _install_cross(self, slot: int, frames: np.ndarray) -> None:
        """CrossAttnStatic lifecycle: run the encoder once at admission and
        write this request's cross K/V into its slot."""
        ck, cv = self._encode_cross(self.params,
                                    jnp.asarray(frames)[None])
        layers = self.cache["layers"]
        upd = {}
        if "cross_k_scale" in layers:
            # quantized CrossAttnStatic: one scale per (layer, slot),
            # written once here — the slot is never rewritten, so no RMW
            qmax = self.cfg.page_layout.qmax

            def quantize(x, dst):
                amax = jnp.max(jnp.abs(x),
                               axis=tuple(range(1, x.ndim)))      # (L,)
                s = jnp.maximum(amax, PC.QUANT_EPS) / qmax
                codes = PC.quantize_rows(
                    x, s.reshape((-1,) + (1,) * (x.ndim - 1)),
                    dst.dtype, qmax)
                return codes, s

            ck, ks = quantize(ck, layers["cross_k"])
            cv, vs = quantize(cv, layers["cross_v"])
            upd["cross_k_scale"] = _dus(layers["cross_k_scale"],
                                        ks[:, None], slot, 1)
            upd["cross_v_scale"] = _dus(layers["cross_v_scale"],
                                        vs[:, None], slot, 1)
        self.cache = {"layers": {
            **layers,
            "cross_k": _dus(layers["cross_k"], ck, slot, 1),
            "cross_v": _dus(layers["cross_v"], cv, slot, 1), **upd}}

    # -------------------------------------------------------- lifecycle

    def _terminal(self, req: Request, status: Status, detail: str = "",
                  retry_after: float = 0.0) -> None:
        """Move a request to a terminal status and drop every piece of
        engine state keyed to it — fold bookkeeping, arrival order, host
        state snapshots and privately-retained pages — so a terminated
        request leaks nothing no matter how it ended."""
        # lifecycle: live -> terminal
        LC.transition(req, status, detail)
        req.t_done = self._clock()
        req.retry_after = retry_after
        self.lifecycle_counts[str(status)] = \
            self.lifecycle_counts.get(str(status), 0) + 1
        self._folded.pop(id(req), None)
        self._arrival.pop(id(req), None)
        self._state_snap.pop(id(req), None)
        self._drop_page_snap(self._page_snap.pop(id(req), None))

    def _retry_after_hint(self) -> float:
        """SHED hint: ticks to drain the current backlog at the decode
        budget — roughly when resubmitting stops being hopeless."""
        live = [r for r in self.slot_req if r is not None]
        rem = sum(max(r.max_new - len(r.out), 1)
                  for r in list(self._queue) + live)
        return float(-(-rem // max(self.budget.decode_tokens, 1)))

    def cancel(self, rid: int, detail: str = "client cancel") -> bool:
        """Terminate a request by id — queued, mid-prefill, or
        mid-decode. A running request's references are released exactly
        like a finished one's: shared prefix pages survive for their
        other readers, sole-owned pages (incl. a COW'd tail) return to
        the pool, and any preemption snapshot is dropped. Returns False
        when no live request carries this rid."""
        for i, req in enumerate(self._queue):
            if req.rid == rid:
                del self._queue[i]
                self._terminal(req, Status.CANCELLED, detail)
                return True
        for slot in range(self.n_slots):
            req = self.slot_req[slot]
            if req is not None and req.rid == rid:
                self._terminal(req, Status.CANCELLED, detail)
                self._release_slot(slot)
                return True
        return False

    def _expire_deadlines(self) -> None:
        """Tick phase 0: expire breached deadlines, queued or running."""
        now = self._clock()
        for req in list(self._queue):
            why = LC.breach(req.deadline, now, req.t_submit, bool(req.out))
            if why:
                self._queue.remove(req)
                self._terminal(req, Status.TIMED_OUT, why)
        for slot in range(self.n_slots):
            req = self.slot_req[slot]
            if req is None:
                continue
            why = LC.breach(req.deadline, now, req.t_submit, bool(req.out))
            if why:
                self._terminal(req, Status.TIMED_OUT, why)
                self._release_slot(slot)

    # ------------------------------------------------------------ admin

    def submit(self, req: Request) -> None:
        if self.is_encdec and req.frames is None:
            raise ValueError("encoder-decoder serving needs Request.frames "
                             "(enc_seq, d_model)")
        req.t_submit = self._clock()
        if self.admission == "strict":
            why = oversized_reason(len(req.prompt), req.max_new, self.smax)
            if why:
                self._terminal(req, Status.FAILED, f"oversized: {why}")
                return
        self._arrival[id(req)] = self._arrival_seq
        self._arrival_seq += 1
        self._queue.append(req)

    def _pop_next(self) -> Request:
        """Most urgent waiting request by the policy key. Re-admissions
        keep their original arrival, so under FIFO a preempted request
        resumes ahead of everything that arrived after it."""
        qi = min(range(len(self._queue)),
                 key=lambda i: self._key(self._queue[i]))
        req = self._queue[qi]
        del self._queue[qi]
        return req

    def _admit_into(self, slot: int, req: Request) -> None:
        # lifecycle: QUEUED -> PREFILL
        LC.transition(req, Status.PREFILL)
        toks = req.prompt.astype(np.int32)
        if not req.out:
            cap = context_cap(self.smax, req.max_new)
            if len(toks) > cap:
                toks = toks[-cap:]
        # else: re-admission after a mid-decode preemption. Everything
        # in the folded prompt was legitimately cached at preemption
        # (pos_after < smax-1, so len <= smax-1): re-truncating here
        # would drop context the unpreempted run kept and make greedy
        # output depend on preemption timing.
        req.prompt = toks
        self.slot_req[slot] = req
        self.slot_pages[slot] = []
        for g in range(1, self.n_groups):
            self.aux_pages[g - 1][slot] = []
            self.aux_tables[g - 1][slot] = 0
        self._cow_pending.pop(slot, None)
        self._admit_order.append(slot)
        self.pos[slot] = 0
        n_pre = len(toks) - 1
        restored = self._try_restore_state(slot, req, n_pre)
        if restored is None:
            self._reset_slot_state(slot)
        if self.is_encdec:
            self._install_cross(slot, req.frames)
        start = 0
        self._reg_next[slot] = 0
        self._reg_parent[slot] = PC.ROOT_KEY
        if restored is not None:
            start = restored
        elif self.prefix_caching and n_pre > 0:
            pages, cov, tail, parent = self.pool.match_prefix(toks, n_pre)
            if pages:
                self.page_table[slot, :len(pages)] = pages
                self.slot_pages[slot] = list(pages)
                if tail:
                    # shared partial tail: read-only until the first write
                    # into it forces a copy (COW)
                    self._cow_pending[slot] = len(pages) - 1
                n_full = len(pages) - (1 if tail else 0)
                self._reg_next[slot] = n_full
                self._reg_parent[slot] = parent
                self.peak_slot_pages = max(self.peak_slot_pages,
                                           len(pages))
                start = cov
        if n_pre > start:
            self._prefill_at[slot] = start
        else:
            self._ready(slot)

    def _ready(self, slot: int) -> None:
        """Prefill finished: the slot joins the decode batch."""
        req = self.slot_req[slot]
        # lifecycle: PREFILL -> DECODE
        LC.transition(req, Status.DECODE)
        toks = req.prompt
        self._prefill_at.pop(slot, None)
        self.pos[slot] = len(toks) - 1
        self.last_tok[slot] = int(toks[-1])
        self.live[slot] = True
        if self.tiered and any(p is not None
                               for p in self.slot_pages[slot]):
            # pin the decode write-target now if a frame allows it; the
            # decode phase re-ensures residency before every batched step,
            # so failing here only costs a sync fetch later
            tail = [p for p in self.slot_pages[slot] if p is not None][-1]
            if self._ensure_resident([tail]):
                self._repin_tail(slot)

    def _release_slot(self, slot: int) -> None:
        """Return a slot to the pool — pure page/slot bookkeeping, no
        request-status side effects (callers pair this with ``_terminal``
        or a requeue, which own the status transition)."""
        if self.tiered:
            old = self._pinned_tail.pop(slot, None)
            if old is not None:
                self.pool.unpin(old)
        # recycled (None) entries were released the moment they slid out
        # of the window; everything else drops one reference — a shared
        # page another request (or the prefix index) still needs survives,
        # a sole-owned one returns to the free list / LRU
        self.pool.release(
            [p for p in self.slot_pages[slot] if p is not None])
        for g in range(1, self.n_groups):
            self.pool.release(
                [p for p in self.aux_pages[g - 1][slot] if p is not None])
            self.aux_pages[g - 1][slot] = []
            self.aux_tables[g - 1][slot] = 0
        if self.tiered:
            self._prune_host()
        self.slot_pages[slot] = []
        self._cow_pending.pop(slot, None)
        self._reg_next.pop(slot, None)
        self._reg_parent.pop(slot, None)
        # retarget the freed slot at the trash page so the batched decode
        # step's unconditional write cannot touch reallocated pages
        self.page_table[slot] = 0
        self.pos[slot] = 0
        self.live[slot] = False
        self.slot_req[slot] = None
        self._prefill_at.pop(slot, None)
        self._admit_order.remove(slot)

    def _retain_slot_pages(self, slot: int, req: Request) -> None:
        """Hybrid preemption (StateSlot + paged K/V, e.g. hymba): park the
        slot's own K/V pages as *private* pool entries so re-admission can
        apply the state snapshot instead of recomputing the folded prompt.
        Private entries are unreachable from prefix matching; once the
        slot releases its references they sit unreferenced, so under
        pressure the pool evicts them like any cached page and the restore
        falls back to recompute (``_try_restore_state`` is all-or-nothing:
        a partial K/V prefix is useless to the snapshot)."""
        keys, ok = [], True
        for p in self.slot_pages[slot]:
            if p is None:
                continue
            try:
                keys.append(self.pool.register_private(p))
            except ValueError:
                ok = False      # page already published (shared): the
                break           # retained set cannot be made whole
        if ok and keys:
            self._page_snap[id(req)] = (list(self.slot_pages[slot]), keys)
        elif keys:
            self._drop_page_snap(([], keys))

    def _preempt(self, slot: int) -> None:
        """Recompute-preemption: fold generated tokens into the prompt and
        requeue; greedy decoding reproduces the rest. A preempted request
        *releases* its references — shared pages are never freed out from
        under their other readers. State-carrying families additionally
        snapshot the slot's recurrent state to host so re-admission can
        skip re-running the folded prompt; hybrids park their K/V pages
        beside the snapshot (pure-paged families keep recompute).

        With ``shed_after`` set, a request that has already churned
        through that many preemptions is **shed** instead of requeued:
        terminal SHED with a retry-after hint, its pages released. Under
        sustained pressure this converts recompute livelock into an
        explicit, client-visible admission-control signal."""
        req = self.slot_req[slot]
        req.n_preempts += 1
        if (self.shed_after is not None
                and req.n_preempts >= self.shed_after):
            self.n_preempted += 1
            self.n_shed += 1
            self._terminal(
                req, Status.SHED,
                f"pool pressure: preempted {req.n_preempts}x",
                retry_after=self._retry_after_hint())
            self._release_slot(slot)
            return
        consumed = self._prefill_at.get(slot)
        folded = self._folded.get(id(req), 0)
        fresh = req.out[folded:]
        if fresh:
            req.prompt = np.concatenate(
                [req.prompt, np.asarray(fresh, np.int32)])
            self._folded[id(req)] = len(req.out)
        if consumed is None:
            # live mid-decode: the state has folded in every token of the
            # (just-folded) prompt except the last, which re-admission
            # feeds through the first decode step
            consumed = len(req.prompt) - 1 if self.live[slot] else 0
        if self._snap_eligible and consumed >= 1:
            snap = CS.snapshot_slot_state(
                self.cache["layers"], self._fresh_state, slot,
                lm.uses_scan(self.cfg))
            # host-sync: preemption snapshot copy-out — rare, off the
            # steady-state decode path by construction
            self._state_snap[id(req)] = (consumed, jax.device_get(snap))
            if self.has_pages and self.n_groups == 1:
                # multi-group hybrids recompute: retention parks only the
                # primary table's pages, and a restore over missing aux
                # pages would attend garbage (_try_restore_state is
                # all-or-nothing, so no psnap -> recompute)
                self._retain_slot_pages(slot, req)
        # lifecycle: PREFILL|DECODE -> QUEUED
        LC.transition(req, Status.QUEUED, "preempted")
        self._release_slot(slot)
        self._queue.appendleft(req)
        self.n_preempted += 1

    def _make_room(self, need: int, protect: int) -> bool:
        """Free pages by preempting strictly-less-urgent requests (largest
        policy key first) — under FIFO that is exactly "newest arrival
        first; an older request is never evicted for a younger one", so
        head-of-line requests always finish. Unreferenced cached pages do
        NOT require preemption: they count as available and ``alloc``
        reclaims them LRU-first, so eviction always precedes preemption.
        Only slots actually holding pages are victims (a just-admitted
        slot with none would be churned for nothing). True iff ``need``
        pages are now available."""
        while self.pool.available_pages < need:
            mine = self._key(self.slot_req[protect])
            candidates = [s for s in self._admit_order
                          if s != protect
                          and any(p is not None for p in self.slot_pages[s])
                          and self._key(self.slot_req[s]) > mine]
            if not candidates:
                return False
            # under sharing, releasing a page only reclaims it when this
            # slot is its last holder: prefer victims whose preemption
            # actually gains pages; fall back to shared-only holders only
            # when nothing gainful exists (their release drops refcounts,
            # which is what turns a co-holder into a gainful victim next
            # iteration — so the loop still makes progress)
            gainful = [s for s in candidates
                       if any(p is not None and self.pool.refcount(p) == 1
                              for p in self.slot_pages[s])]
            # victim order: the policy's shed key — least urgent first,
            # ties toward the most-churned request, which is also the one
            # shed_after retires when pressure is sustained
            self._preempt(max(
                gainful or candidates,
                key=lambda s: self.policy.shed_key(
                    self.slot_req[s],
                    self._arrival[id(self.slot_req[s])],
                    self.slot_req[s].n_preempts)))
        return True

    def _grow_to(self, slot: int, n_tokens: int) -> bool:
        """Ensure every group's table covers logical positions
        [0, n_tokens). Groups grow in lockstep — each group's layers write
        the same token row, so logical coverage is identical across
        tables; only recycling (per-group window) makes them diverge."""
        if not self.has_pages:
            return True                    # StateSlot-only model (xlstm)
        want = PagePool.pages_for(n_tokens, self.page_size)
        needs = [max(want - len(self._group_pages(g)[slot]), 0)
                 for g in range(self.n_groups)]
        total = sum(needs)
        if total <= 0:
            return True
        if not self._make_room(total, protect=slot):
            return False
        # tiered: fresh pages are born RESIDENT, so claim frames first —
        # by demotion, never by preempting (demote-before-preempt: the
        # _make_room above handles *logical* page shortage, which frames
        # cannot fix; frame shortage is always demotion's job)
        if self.tiered and not self._demote_for_frames(
                total, protect=frozenset(
                    p for p in self.slot_pages[slot] if p is not None)):
            return False
        for g, (need, table) in enumerate(zip(needs,
                                              self._group_tables())):
            if not need:
                continue
            pages = self.pool.alloc(need)
            if pages is None:
                # injected alloc_fail: contended this tick. Groups grown
                # so far keep their (consistent) pages; the retry only
                # re-requests what is still missing
                return False
            plist = self._group_pages(g)[slot]
            base = len(plist)
            table[slot, base:base + need] = pages
            plist.extend(pages)
            self.n_pages_grown += need
        self.peak_slot_pages = max(
            self.peak_slot_pages,
            sum(p is not None for p in self.slot_pages[slot]))
        return True

    def _resolve_cow(self, slot: int) -> bool:
        """Copy-on-write of a shared tail page, run lazily right before
        this slot's first write could land in it. If the slot is the
        page's only reader it takes ownership in place — the index entry
        is dropped (this write is about to overwrite the cached content)
        and no copy is paid; only a page another request still reads is
        actually copied, the table entry repointed, and the original left
        serving its other readers. False when the pool cannot produce the
        copy's page (caller retries or preempts)."""
        idx = self._cow_pending.get(slot)
        if idx is None:
            return True
        old = self.slot_pages[slot][idx]
        if self.pool.refcount(old) == 1:
            self.pool.deregister(old)
            self._cow_pending.pop(slot)
            return True
        if not self._make_room(1, protect=slot):
            return False
        if self.pool.refcount(old) == 1:
            # _make_room preempted the co-holder: sole reader after all —
            # take ownership instead of paying the copy at peak pressure
            self.pool.deregister(old)
            self._cow_pending.pop(slot)
            return True
        if self.tiered:
            # the copy reads the source frame and writes a fresh one:
            # both ends must be on device before the kernel runs (promote
            # the source first — its promotion may consume a free frame,
            # the destination's frame is claimed after)
            prot = frozenset(
                p for p in self.slot_pages[slot] if p is not None)
            if not (self._ensure_resident([old], prot)
                    and self._demote_for_frames(1, prot | {old})):
                return False
        got = self.pool.alloc(1)
        if got is None:
            return False        # injected alloc_fail: contended this tick
        new = got[0]
        if self.tiered:
            self.cache = self._copy_page_t(
                self.cache, old, new,
                jnp.int32(self.pool.frame_of(old)),
                jnp.int32(self.pool.frame_of(new)))
        else:
            self.cache = self._copy_page(self.cache, old, new)
        self.page_table[slot, idx] = new
        self.slot_pages[slot][idx] = new
        if self.tiered:
            # the old page may have been this slot's pinned tail: move
            # the pin to the copy BEFORE dropping the reference
            self._repin_tail(slot)
        self.pool.release([old])
        if self.tiered:
            self._prune_host()
        self._cow_pending.pop(slot)
        self.n_cow_copies += 1
        return True

    def _register_ready_pages(self, slot: int) -> None:
        """Publish full prompt pages the prefill has completely written.
        Only pages fully covered by *prefilled* prompt tokens register —
        the page receiving decode writes never does, so registered pages
        are immutable and safe to alias."""
        if not self.prefix_caching:
            return
        req = self.slot_req[slot]
        toks = req.prompt
        written = self._prefill_at.get(slot, len(toks) - 1)
        ps = self.page_size
        i = self._reg_next[slot]
        while (i + 1) * ps <= written:
            self._reg_parent[slot] = self.pool.register(
                self.slot_pages[slot][i], self._reg_parent[slot],
                toks[i * ps:(i + 1) * ps])
            i += 1
        self._reg_next[slot] = i

    def _recycle_window(self, slot: int, next_q: int) -> None:
        """WindowPagedAttn lifecycle: pages every future query's window has
        slid past are dead — free them and point their table entries at the
        trash page (reads of recycled rows are masked by the sliding-window
        mask exactly like the dense cache's dead rows). ``next_q`` is the
        earliest position any future query of this slot can have; it
        attends kv >= next_q - window + 1."""
        windows = self.group_windows or ((self.window,)
                                         if self.window else ())
        for g, w in enumerate(windows):
            if not w:
                continue         # full-attention group: pages pin forever
            first_live = max(0, next_q - w + 1) // self.page_size
            pages = self._group_pages(g)[slot]
            freed = [p for p in pages[:first_live] if p is not None]
            if not freed:
                continue
            pages[:first_live] = [None] * min(first_live, len(pages))
            self.pool.release(freed)
            if self.tiered:
                self._prune_host()
            self.n_recycled_pages += len(freed)
            self._group_tables()[g][slot, :first_live] = 0
            live = sum(p is not None for p in pages)
            if live > self._group_pages_hard[g]:
                raise RuntimeError(
                    f"slot {slot} group {g} holds {live} pages after "
                    "recycling, above the spec-table bound "
                    f"{self._group_pages_hard[g]}")

    # ------------------------------------------- tiered KV pool (§13)

    def _frame_table(self, pt: np.ndarray) -> np.ndarray:
        """Resolve a logical page table to device frames. RESIDENT pages
        map to their frame; HOST pages (and staging frames still in
        flight) map to the trash frame 0 — rows read through a trash
        entry are finite garbage that the selection's validity mask turns
        into an exactly-zero attention contribution, and the winner mask
        is what reports the page for promotion."""
        lut = np.zeros((self.pool.n_pages,), np.int32)
        for p, f in self.pool.frame_map().items():
            lut[p] = f
        for p in self.pool.inflight_page_ids():
            lut[p] = 0
        return lut[pt]

    def _prune_host(self) -> None:
        """Drop host byte buffers no off-device page needs anymore: only
        HOST / IN_FLIGHT pages can ever be promoted from host bytes."""
        keep = set(self.pool.host_page_ids()) \
            | set(self.pool.inflight_page_ids())
        if len(self._host_kv) != len(keep):
            self._host_kv = {p: v for p, v in self._host_kv.items()
                             if p in keep}

    def _promote_copy(self, page: int, frame: int) -> None:
        """FetchQueue copy_fn: host bytes -> the claimed staging frame.
        ``jnp.asarray`` starts the host->device transfer and the jitted
        row update is dispatched asynchronously, so the copy overlaps
        whatever the host enqueues next (the repair run's score pass)."""
        k_np, v_np = self._host_kv[page]
        self.cache = self._promote_write(
            self.cache, jnp.asarray(k_np), jnp.asarray(v_np),
            jnp.int32(frame))

    def _demote_page(self, page: int) -> None:
        """Copy-then-demote: pull the page's full-D rows out of its frame
        into host memory, then surrender the frame. The latent sidecar
        row stays on device, so the page keeps scoring in the approximate
        pass; only exact attention needs it back."""
        frame = self.pool.frame_of(page)
        attn = self.cache["layers"]["attn"]
        start = frame * self.page_size
        # host-sync: demotion copy-out — runs under frame pressure, never
        # on the steady-state all-resident decode path
        k_np, v_np = jax.device_get(
            (layout.page_slice(attn["k"], start, self.page_size),
             layout.page_slice(attn["v"], start, self.page_size)))
        self._host_kv[page] = (k_np, v_np)
        self.pool.demote(page)

    def _demote_for_frames(self, need: int, protect=frozenset()) -> bool:
        """Free device frames by demoting victims in the policy's
        ``demote_key`` order — cached-but-unreferenced pages first (their
        frames serve nobody; their bytes keep prefix value on host), then
        cold residents by last-use tick. Demotion always precedes
        preemption or shedding: losing a frame costs one prefetch, losing
        a slot costs a re-prefill. Pinned tails and ``protect`` pages are
        never victims. True iff ``need`` frames are now free."""
        if not self.tiered:
            return True
        if self.pool.free_frames >= need:
            return True
        lru_pos = {p: i for i, p in enumerate(self.pool.lru_page_ids())}
        cands = [p for p in self.pool.resident_page_ids()
                 if p not in protect and not self.pool.is_pinned(p)]
        cands.sort(key=lambda p: self.policy.demote_key(
            p, p in lru_pos, lru_pos.get(p, 0),
            self._page_last_use.get(p, -1)))
        for p in cands:
            if self.pool.free_frames >= need:
                break
            self._demote_page(p)
        return self.pool.free_frames >= need

    def _promote_sync(self, page: int, protect=frozenset()) -> bool:
        """Synchronous promote, counted — the miss-repair fallback and
        the path for reads with no trash-masking to hide behind (prefill
        prefix gathers, COW sources, decode write targets). Claims a
        frame (demoting a victim if none is free), copies, completes.
        False when no frame could be claimed this tick (injected
        hbm_oom_on_promote, or every frame pinned/protected): the caller
        defers its slot to the next tick — bit-safe under greedy
        decoding, since nothing of that stream advanced."""
        state = self.pool.tier_of(page)
        if state == PC.IN_FLIGHT:
            self._fetch.drain()
            self._prune_host()
            state = self.pool.tier_of(page)
        if state == PC.RESIDENT:
            return True
        frame = self.pool.promote_begin(page)
        if frame is None:
            self._demote_for_frames(1, protect | {page})
            frame = self.pool.promote_begin(page)
        if frame is None:
            return False
        self._promote_copy(page, frame)
        self.pool.promote_complete(page)
        self.n_sync_fetches += 1
        self._prune_host()
        return True

    def _ensure_resident(self, pages, protect=frozenset()) -> bool:
        """Promote every off-device page in ``pages`` synchronously."""
        if not self.tiered:
            return True
        todo = [p for p in pages if p is not None]
        prot = frozenset(protect) | set(todo)
        return all(self._promote_sync(p, prot) for p in todo)

    def _repin_tail(self, slot: int) -> None:
        """Pin the slot's current write-target (tail) page, unpinning the
        previous one once the tail moves. The batched decode writes K/V
        rows through the frame table; a pinned tail cannot be demoted, so
        a write is never silently diverted to the trash frame."""
        live = [p for p in self.slot_pages[slot] if p is not None]
        tail = live[-1] if live else None
        old = self._pinned_tail.get(slot)
        if old == tail:
            return
        if old is not None:
            self.pool.unpin(old)
            self._pinned_tail.pop(slot, None)
        if tail is not None:
            self.pool.pin(tail)
            self._pinned_tail[slot] = tail

    def _frame_starved(self, slot: int) -> bool:
        """True when this slot's decode-prep growth failed for *frames*
        rather than logical pages: the pool could satisfy the growth (and
        a pending COW copy) out of free or cached pages, so only the
        device tier is short. Frame shortage is demotion's and deferral's
        job; it must never preempt (DESIGN.md §13)."""
        need = PagePool.pages_for(int(self.pos[slot]) + 1, self.page_size) \
            - len(self.slot_pages[slot])
        if slot in self._cow_pending:
            need += 1
        return self.pool.available_pages >= max(need, 0)

    def _unpin_tails(self, keep) -> None:
        """Drop the best-effort tail pins of every slot not in ``keep``.
        Safe at any point after the pinned slot's last write landed: a
        demotion copies the frame's rows to the host first, so unpinning
        never loses data — it only lets the demotion policy consider
        those frames again. Unpinned slots re-ensure and re-pin in their
        own prep (or defer if they cannot)."""
        for t in [t for t in self._pinned_tail if t not in keep]:
            self.pool.unpin(self._pinned_tail.pop(t))

    def _winner_pages(self, pt: np.ndarray, win: np.ndarray,
                      sel: np.ndarray):
        """slot -> set of logical pages this run's selection attended."""
        out: Dict[int, set] = {}
        for s in np.flatnonzero(sel):
            out[int(s)] = {int(p) for p in pt[s][win[s]] if p != 0}
        return out

    def _repair_misses(self, miss: Dict[int, List[int]],
                       winners: Dict[int, set],
                       todo: np.ndarray) -> None:
        """Promote the missed pages of as many slots as the device pool
        allows, most urgent first; slots whose misses cannot all fit
        *defer* (dropped from ``todo``; their streams re-run identically
        next tick). Frames are granted incrementally: each repaired
        slot's full winner set joins the protected set, so a later slot
        can never demote an earlier one's pages and re-runs make strict
        progress. When even the head-of-line slot cannot fit, every
        other stream defers and unpins so it can claim the whole pool —
        the ctor guarantees one request always fits on device."""
        order = sorted(miss, key=lambda s: self.policy.decode_key(
            self.slot_req[s], self._arrival[id(self.slot_req[s])],
            int(self._last_decoded[s])))

        def claim(pages, trial):
            for p in pages:
                if self.pool.tier_of(p) != PC.HOST:
                    continue    # already in flight / just promoted
                if not self._fetch.request(p):
                    self._demote_for_frames(1, frozenset(trial))
                    if not self._fetch.request(p):
                        return False
            return True

        protect = set(self._pinned_tail.values())
        head_took_all = False
        for i, s in enumerate(order):
            if head_took_all:
                todo[s] = False
                continue
            trial = protect | winners[s]
            if claim(miss[s], trial):
                protect = trial
                continue
            if i == 0:
                # head-of-line starvation: everything else defers, its
                # pins lift (a deferred stream commits nothing this tick;
                # next tick's prep re-promotes and re-pins its tail)
                self._unpin_tails(keep={s})
                head_took_all = True
                trial = winners[s] | {self._pinned_tail.get(s)} - {None}
                if claim(miss[s], trial):
                    continue
            todo[s] = False                 # defer this stream

    def _decode_tiered(self, sel: np.ndarray, rng):
        """Two-phase tiered decode (DESIGN.md §13): one optimistic jitted
        run whose score pass reads only the always-resident latent
        sidecar, then exact attention through the frame table. Slots
        whose every attended (winner) page was resident **commit** their
        token immediately — their run was exact. Slots that attended an
        off-device page saw trash-frame garbage: their misses are
        promoted through the bounded fetch queue and only *they* re-run.
        Replay is exact because a slot's K/V row write depends only on
        its input token and position (never on what attention read), the
        recurrent state of re-run slots is restored from a pre-run device
        snapshot, and positions only advance after the phase. A slot
        whose misses cannot be promoted this tick is deferred whole.

        Returns (nxt, finite, committed) over the full slot axis, with
        ``committed`` <= the ``sel`` passed in."""
        log = self.tick_log
        todo = sel.copy()
        done = np.zeros_like(sel)
        nxt_out = np.zeros((self.n_slots,), np.int64)
        fin_out = np.ones((self.n_slots,), bool) if self.nan_guard \
            else None
        # one pre-phase snapshot of the recurrent-state leaves: every
        # re-run restores its slots to this, so each stream's state
        # advances exactly once no matter how many runs it took
        snap = None
        if self._fresh_state is not None:
            with log.phase("engine.decode.prep"):
                layers = self.cache["layers"]
                snap = {k: jax.tree.map(jnp.copy, layers[k])
                        for k in self._fresh_state}
        for attempt in range(_TIERED_MAX_RUNS):
            with log.phase("engine.decode.prep"):
                ran = todo.copy()
                sel_dev = jnp.asarray(todo)
                pt = self.page_table * todo.astype(np.int32)[:, None]
                ft = self._frame_table(pt)
            with log.phase("engine.decode.launch"):
                logits, win, self.cache = self._run_decode_t(pt, ft,
                                                             sel_dev)
                slots = np.flatnonzero(todo)
                nxt, finite_dev = self._sample(logits, slots, slots, rng)
            with log.phase("engine.decode.sync"):
                # host-sync: the ONE batched device->host sync of the
                # common (all-hit) tiered tick — sampled tokens, the
                # nan-guard mask and the winner mask cross together
                nxt_np, finite, win_np = jax.device_get(
                    (nxt, finite_dev, win))
            with log.phase("engine.decode.commit"):
                miss, winners = self._commit_tiered(
                    attempt, pt, win_np, todo, done, nxt_np, finite,
                    nxt_out, fin_out)
            with log.phase("engine.decode.prep"):
                self._prep_rerun(miss, winners, todo, ran & ~done, snap)
            if not todo.any():
                return nxt_out, fin_out, done
        raise RuntimeError(
            f"tiered decode did not converge in {_TIERED_MAX_RUNS} runs "
            "(promotion/selection ping-pong; raise device_pages)")

    def _commit_tiered(self, attempt, pt, win_np, todo, done, nxt_np,
                       finite, nxt_out, fin_out):
        """Commit every slot of one tiered run whose attended pages were
        all resident (in place on ``todo``/``done``/``nxt_out``/
        ``fin_out``); returns (misses, winners) of the rest."""
        winners = self._winner_pages(pt, np.asarray(win_np), todo)
        miss = {s: [p for p in sorted(pages)
                    if self.pool.tier_of(p) != PC.RESIDENT]
                for s, pages in winners.items()}
        miss = {s: ps_ for s, ps_ in miss.items() if ps_}
        if attempt == 0:
            uniq = set().union(*winners.values()) if winners else set()
            n_miss = sum(self.pool.tier_of(p) != PC.RESIDENT
                         for p in uniq)
            self.n_prefetch_misses += n_miss
            self.n_prefetch_hits += len(uniq) - n_miss
        # commit every fully-resident slot: its token is exact, its
        # K/V row write is input-only (valid even beside garbage
        # reads), and its advanced state must NOT be restored
        for s in winners:
            if s in miss:
                continue
            done[s] = True
            todo[s] = False
            nxt_out[s] = nxt_np[s]
            if fin_out is not None:
                fin_out[s] = bool(finite[s])
            for p in winners[s]:
                self._page_last_use[p] = self.ticks
        return miss, winners

    def _prep_rerun(self, miss, winners, todo, stale, snap) -> None:
        """Before a tiered re-run: promote the misses of the slots still
        to do, and rewind the recurrent state of every slot that ran
        without committing."""
        if todo.any():
            self.n_decode_reruns += 1
            self._repair_misses(miss, winners, todo)
            self._fetch.drain()
            self._prune_host()
        # restore every slot that ran this attempt without committing —
        # both the re-running and the just-deferred: their recurrent
        # state advanced on garbage attention inputs and must rewind to
        # the snapshot (committed slots keep theirs, so each stream's
        # state advances exactly once)
        if snap is not None and stale.any():
            # one jitted masked restore over every stale slot at once
            # (was a per-slot snapshot/reset Python loop: a chain of
            # eagerly-dispatched slice updates per re-run)
            layers = self.cache["layers"]
            sub = {k: layers[k] for k in snap}
            sub = self._rewind(sub, snap, jnp.asarray(stale))
            self.cache = {"layers": {**layers, **sub}}

    def _run_decode_t(self, pt, ft, sel_dev):
        """Tiered twin of ``_run_decode`` (frame-table decode program)."""
        lv = sel_dev if self.has_state else None
        return self._degrade(lambda: self._decode_t(
            self.params, self.cache, self.last_tok.copy(), self.pos.copy(),
            pt, jnp.asarray(ft), lv))

    # ------------------------------------------------------------ phases

    def _admission_phase(self) -> None:
        """Fill free slots in policy order; then, if the policy allows it,
        let a strictly-more-urgent waiter preempt the least-urgent running
        request for its slot (the running key multiset strictly decreases
        at every swap, so this terminates and the most urgent request
        always makes progress)."""
        while self._queue:
            free = [s for s in range(self.n_slots)
                    if self.slot_req[s] is None]
            if not free:
                break
            req = self._pop_next()
            with self._admit_span(req):
                self._admit_into(free[0], req)
        if not self.policy.preempt_for_admission:
            return
        while self._queue:
            qi = min(range(len(self._queue)),
                     key=lambda i: self._key(self._queue[i]))
            cand = self._queue[qi]
            worse = [s for s in self._admit_order
                     if self._key(self.slot_req[s]) > self._key(cand)]
            if not worse:
                return
            del self._queue[qi]
            with self._admit_span(cand):
                self._preempt(max(
                    worse, key=lambda s: self._key(self.slot_req[s])))
                slot = next(s for s in range(self.n_slots)
                            if self.slot_req[s] is None)
                self._admit_into(slot, cand)

    def _admit_span(self, req: Request):
        """The ``engine.admit`` phase of one request, with how long it
        has waited since ``submit`` on the engine's clock."""
        return self.tick_log.phase(
            "engine.admit", rid=req.rid,
            queued_ms=int(1e3 * (self._clock() - req.t_submit)))

    def _prefill_phase(self) -> None:
        """Advance mid-prefill slots, most urgent first, spending at most
        ``budget.prefill_tokens`` real prompt tokens across any number of
        chunks and slots this tick."""
        budget = self.budget.prefill_tokens
        slots = sorted([s for s in self._admit_order
                        if s in self._prefill_at],
                       key=lambda s: self._key(self.slot_req[s]))
        for slot in slots:
            while budget > 0 and slot in self._prefill_at:
                req = self.slot_req[slot]
                with self.tick_log.phase(
                        "engine.prefill", rid=req.rid,
                        tokens=min(self.prefill_chunk,
                                   len(req.prompt) - 1
                                   - self._prefill_at[slot])):
                    n = self._prefill_slot_chunk(slot)
                if n < 0:
                    break              # this slot is pool-contended; a
                budget -= max(n, 1)    # later slot may still fit (e.g. a
            if budget <= 0:            # chunk into pages it already holds)
                return

    def _prefill_slot_chunk(self, slot: int) -> int:
        """One fixed-size chunk of one slot's prompt. Returns the number
        of real tokens computed, or -1 when the pool is contended."""
        req = self.slot_req[slot]
        toks = req.prompt
        n_pre = len(toks) - 1              # last token goes through decode
        start = self._prefill_at[slot]
        c = self.prefill_chunk
        n_valid = min(c, n_pre - start)
        # recycle before growing: the chunk's earliest query is at
        # ``start``, so pages its window has passed free up first and the
        # per-request bound holds at every instant
        self._recycle_window(slot, start)
        # a shared tail page must be copied before this chunk's first
        # write lands in it (start == the first uncached token)
        if not self._resolve_cow(slot):
            return -1
        if not self._grow_to(slot, start + n_valid):
            return -1
        chunk = np.zeros((1, c), np.int32)
        chunk[0, :n_valid] = toks[start:start + n_valid]
        if self.tiered:
            # prefill reads the *whole* prefix exactly (no trash-masking
            # selection to hide behind) and writes the chunk's pages:
            # everything this slot holds must be resident, synchronously
            held = [p for p in self.slot_pages[slot] if p is not None]
            if not self._ensure_resident(held):
                return -1        # frame-starved this tick: retry later
            self._repin_tail(slot)
            fr = self._frame_table(self.page_table[slot])
            # table rows go in as copies: the chunk runs after this call
            # returns, and the CPU backend reads numpy arguments in place,
            # so a later in-place table edit would race it
            _, self.cache = self._chunk_t(
                self.params, self.cache, jnp.asarray(chunk),
                jnp.int32(start), jnp.int32(n_valid),
                self.page_table[slot].copy(), jnp.asarray(fr),
                jnp.int32(slot))
        else:
            row = self.page_table[slot].copy() if self.n_groups == 1 \
                else np.stack([t[slot] for t in self._group_tables()])
            _, self.cache = self._chunk(
                self.params, self.cache, jnp.asarray(chunk),
                jnp.int32(start), jnp.int32(n_valid),
                row, jnp.int32(slot))
        self._prefill_at[slot] = start + n_valid
        self.n_prefill_computed_tokens += n_valid
        self._register_ready_pages(slot)
        if start + n_valid >= n_pre:
            self._ready(slot)
        return n_valid

    def _decode_phase(self, rng: Optional[jax.Array]) -> bool:
        if not self.live.any():
            return False
        log = self.tick_log
        with log.phase("engine.decode.prep"):
            sel = self._prep_decode()
        if not sel.any():
            return False
        # the batched step writes a token for *every* slot; unselected
        # slots (idle, mid-prefill, live-but-over-budget) must land in the
        # trash page, not at their current position — and their StateSlot
        # components must not advance (``live`` mask)
        if self.tiered:
            nxt_np, finite, sel = self._decode_tiered(sel, rng)
            if not sel.any():
                return False    # every stream deferred to the next tick
            log.count(decode_rows=int(sel.sum()))
        else:
            nxt_np, finite = self._decode_batch(sel, rng)
        with log.phase("engine.decode.commit"):
            self._commit_decode(sel, nxt_np, finite)
        return True

    def _prep_decode(self) -> np.ndarray:
        """Pick this tick's decode batch and make every chosen slot's
        target page exist and be privately writable. Returns the selected
        slot mask (a slot may drop out: preempted or deferred)."""
        # decode-budget selection: when more slots are live than the
        # budget covers, the policy's decode key picks this tick's batch
        # (strict priority classes, round-robin inside a class)
        chosen = [int(s) for s in np.flatnonzero(self.live)]
        if len(chosen) > self.budget.decode_tokens:
            chosen.sort(key=lambda s: self.policy.decode_key(
                self.slot_req[s], self._arrival[id(self.slot_req[s])],
                int(self._last_decoded[s])))
            chosen = chosen[: self.budget.decode_tokens]
        sel = np.zeros((self.n_slots,), bool)
        sel[chosen] = True
        # every selected slot writes its new token this step: make sure
        # the target page exists and is privately writable (COW first),
        # recycling window-dead pages so SWA slots stay within their
        # spec-table page bound
        prepped: set = set()
        for slot in chosen:
            if not self.live[slot]:
                continue                   # preempted by an earlier grow
            self._recycle_window(slot, int(self.pos[slot]))
            if not (self._resolve_cow(slot)
                    and self._grow_to(slot, int(self.pos[slot]) + 1)):
                if self.tiered and self._frame_starved(slot):
                    # demote-before-preempt (§13): the pool has logical
                    # capacity and only device frames are short — a frame
                    # shortage never costs a slot its pages. Pins are
                    # best-effort and re-taken each tick, so drop the
                    # tails pinned by slots that have not completed this
                    # tick's prep (they re-ensure in their own iteration
                    # or defer) and retry; if frames are still short,
                    # defer the slot one tick instead of preempting.
                    self._unpin_tails(keep=prepped | {slot})
                    if not (self._resolve_cow(slot) and self._grow_to(
                            slot, int(self.pos[slot]) + 1)):
                        sel[slot] = False
                        continue
                else:
                    # this slot's request is the least urgent under memory
                    # pressure: vLLM's recompute policy preempts the
                    # requester itself rather than evicting a more urgent
                    # request
                    self._preempt(slot)
                    continue
            if self.tiered:
                # this step writes a K/V row into the tail page: promote
                # it if demoted, pin it so no repair-loop demotion diverts
                # the write to the trash frame. Frame-starved -> defer the
                # slot one tick (bit-safe: nothing of its stream advances)
                held = [p for p in self.slot_pages[slot] if p is not None]
                if held:
                    if not self._ensure_resident([held[-1]],
                                                 frozenset(held)):
                        sel[slot] = False
                        continue
                    self._repin_tail(slot)
                    self._page_last_use[held[-1]] = self.ticks
                prepped.add(slot)
        return sel & self.live

    def _sample(self, logits, rows, slots, rng):
        """Sampled tokens and the nan-guard mask of one decode run, still
        on the device. ``rows`` are the logits rows of the live ``slots``
        (the ``nan_logits`` fault site poisons a row by its slot)."""
        if self._faults is not None:
            bad = [r for r, s in zip(rows, slots)
                   if self._faults.hit("nan_logits", int(s))]
            if bad:
                logits = logits.at[jnp.asarray(bad, jnp.int32)].set(jnp.nan)
        finite_dev = jnp.isfinite(logits).all(axis=-1) \
            if self.nan_guard else None
        nxt = sample_next(logits, greedy=self.greedy, rng=rng,
                          ticks=self.ticks)
        return nxt, finite_dev

    def _decode_batch(self, sel: np.ndarray, rng):
        """One single-tier decode step over the selected slots: packed
        when a warmed bucket narrower than ``n_slots`` fits, else masked
        full-width. Returns (tokens, finite) over the full slot axis."""
        log = self.tick_log
        with log.phase("engine.decode.prep"):
            order = self._packed_order(sel)
            if order is not None:
                # gather-packed step: the batch is the live slots plus
                # distinct idle pad rows up to the bucket width — pad
                # rows write to the trash page (zeroed table rows) and
                # their state is live-masked, so only result unpacking
                # differs from the masked path below
                sidx, plive = order
                n_live = int(plive.sum())
                self.n_packed_ticks += 1
                self.n_packed_rows_saved += self.n_slots - len(sidx)
                keep, batch = plive.astype(np.int32), sidx
                rows, slots = range(n_live), sidx[:n_live]
                log.count(decode_rows=n_live, packed_bucket=len(sidx))
            else:
                self.n_masked_ticks += 1
                sel_dev = jnp.asarray(sel)
                keep, batch = sel.astype(np.int32), slice(None)
                rows = slots = np.flatnonzero(sel)
                log.count(decode_rows=len(rows))
            tables = [t[batch] for t in self._group_tables()]
            pt = (np.stack(tables, axis=1) * keep[:, None, None]
                  if self.n_groups > 1 else tables[0] * keep[:, None])
        with log.phase("engine.decode.launch"):
            if order is not None:
                logits, self.cache = self._run_decode_packed(
                    len(sidx), sidx, pt, plive)
            else:
                logits, self.cache = self._run_decode(pt, sel_dev)
            nxt, finite_dev = self._sample(logits, rows, slots, rng)
        with log.phase("engine.decode.sync"):
            # host-sync: the ONE batched device->host sync of the decode
            # tick — sampled tokens (and the nan-guard mask) must reach
            # Python to drive per-request lifecycle; everything else
            # stays host-side
            nxt_np, finite = jax.device_get((nxt, finite_dev))
        if order is None:
            return nxt_np, finite
        with log.phase("engine.decode.commit"):
            # unpack the packed rows to their slots
            full = np.zeros((self.n_slots,), nxt_np.dtype)
            full[slots] = nxt_np[:n_live]
            fin = None
            if finite is not None:
                fin = np.ones((self.n_slots,), bool)
                fin[slots] = finite[:n_live]
        return full, fin

    def _commit_decode(self, sel: np.ndarray, nxt_np: np.ndarray,
                       finite: Optional[np.ndarray]) -> None:
        """Advance the decoded slots: append each sampled token, stamp
        first-token times, finish or quarantine requests."""
        self.pos += sel.astype(np.int32)
        self._last_decoded[sel] = self.ticks
        for slot in range(self.n_slots):
            req = self.slot_req[slot]
            if req is None or not sel[slot]:
                continue
            if finite is not None and not finite[slot]:
                # numerically-failed slot: quarantine this request alone
                # (its pages go back to the pool; the rest of the batch
                # saw its own rows only and keeps serving untouched)
                self.n_quarantined += 1
                self._terminal(req, Status.FAILED,
                               "non-finite logits (slot quarantined)")
                self._release_slot(slot)
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
                self._release_slot(slot)
            else:
                self.last_tok[slot] = tok

    def _degrade(self, run):
        """Run one decode program through the degradation ladder. Only
        the ``kernel_fail`` injection site (``FI.FaultInjected``) takes
        it: disable Pallas process-wide, re-jit so the retrace resolves
        to XLA, and re-run the *same* step — the tick completes on the
        reference path and every later step stays there. ``run`` reads
        the engine's programs when called, so the re-run picks up the
        rebuilt ones. A real kernel error (a compile refusal, a runtime
        fault) propagates: the ladder never hides the device path."""
        on_pallas = dispatch.resolve_backend(
            self.cfg.loki.backend) == "pallas"
        try:
            if (on_pallas and self._faults is not None
                    and self._faults.hit("kernel_fail")):
                raise FI.FaultInjected("injected fused-kernel abort")
            return run()
        except FI.FaultInjected as e:
            dispatch.disable_backend("pallas", f"decode step failed: {e}")
            self._build_programs()
            self.n_backend_fallbacks += 1
            return run()

    def _run_decode(self, pt, sel_dev):
        """One batched full-width decode step (``_degrade`` ladder)."""
        lv = sel_dev if self.has_state else None
        return self._degrade(lambda: self._decode(
            self.params, self.cache, self.last_tok.copy(), self.pos.copy(),
            pt, lv))

    def _packed_order(self, sel: np.ndarray):
        """Plan this tick's gather-packed batch: (slot order, packed live
        mask), or None when the tick should run masked
        full-width — packing disabled, the bucket would not be narrower
        than ``n_slots``, or the trace guard is sealed and this bucket
        was never warmed."""
        if not self.packed:
            return None
        live_idx = np.flatnonzero(sel)
        n_live = int(live_idx.size)
        # bucketed padding keeps the set of program shapes small and
        # stable (log2(n_slots) buckets), so a warmed engine never
        # retraces as occupancy wanders
        bucket = 1 << max(n_live - 1, 0).bit_length()
        if bucket >= self.n_slots:
            return None
        prog = self._packed_program(bucket)
        if prog is None:
            self.n_packed_fallbacks += 1
            return None
        # pad with DISTINCT non-selected slot ids: the packed cache
        # scatter requires unique rows, and uniqueness is what lets pad
        # rows reuse the live-masking/trash-page machinery untouched
        pad = np.setdiff1d(np.arange(self.n_slots, dtype=np.int64),
                           live_idx)[:bucket - n_live]
        sidx = np.concatenate([live_idx, pad]).astype(np.int32)
        plive = np.zeros((bucket,), bool)
        plive[:n_live] = True
        return sidx, plive

    def _run_decode_packed(self, bucket: int, sidx: np.ndarray,
                           pt: np.ndarray, plive: np.ndarray):
        """Packed twin of ``_run_decode``, with token/position rows
        gathered to the packed order on the host. The program is looked
        up per call: a ladder rebuild drops the bucket cache."""
        lv = jnp.asarray(plive) if self.has_state else None
        tok, pos = self.last_tok[sidx], self.pos[sidx]
        return self._degrade(lambda: self._packed_program(bucket)(
            self.params, self.cache, tok, pos, pt, lv, jnp.asarray(sidx)))

    def _inject_corruption(self) -> None:
        """``slot_corrupt`` site: silently repoint one live slot's tail
        page entry at a page some *other* slot holds — the kind of
        bookkeeping bug that would alias two requests' caches. Nothing
        fails here by design; the per-tick auditor is what must catch
        it (invariant B/E)."""
        if self._faults is None:
            return
        for slot in range(self.n_slots):
            pages = self.slot_pages[slot]
            tail = [i for i, p in enumerate(pages) if p is not None]
            if (self.slot_req[slot] is None or not tail
                    or not self._faults.hit("slot_corrupt", slot)):
                continue
            mine = {p for p in pages if p is not None}
            foreign = sorted(
                {p for s in range(self.n_slots) if s != slot
                 for p in self.slot_pages[s]
                 if p is not None and p not in mine})
            pages[tail[-1]] = foreign[0] if foreign else 0

    # ------------------------------------------------------------- tick

    def tick(self, rng: Optional[jax.Array] = None) -> None:
        log = self.tick_log
        before = (self.n_prefill_computed_tokens, self.n_pages_grown,
                  self.n_preempted)
        with log.tick(self.ticks):
            with log.phase("engine.expire"):
                if self._faults is not None:
                    self._faults.advance(self.ticks)
                self._expire_deadlines()
            self._admission_phase()
            self._prefill_phase()
            self._decode_phase(rng)
            self._inject_corruption()
            self.ticks += 1
            log.count(
                prefill_tokens=self.n_prefill_computed_tokens - before[0],
                pages_grown=self.n_pages_grown - before[1],
                preemptions=self.n_preempted - before[2])
            if self.audit:
                with log.phase("engine.audit"):
                    FI.audit_engine(self)

    @property
    def n_prefix_hit_tokens(self) -> int:
        """Prompt tokens served from cached pages (every match goes
        through pool.match_prefix, so the pool's counter is the truth)."""
        return self.pool.n_hit_tokens

    def prefix_hit_rate(self) -> float:
        """Fraction of prefill-eligible prompt tokens served from cached
        pages instead of being computed."""
        total = self.n_prefix_hit_tokens + self.n_prefill_computed_tokens
        return self.n_prefix_hit_tokens / total if total else 0.0

    def run_until_done(self, max_ticks: int = 10_000,
                       rng: Optional[jax.Array] = None) -> None:
        for _ in range(max_ticks):
            if not self._queue and not self._admit_order:
                return
            sub = None
            if rng is not None:
                rng, sub = jax.random.split(rng)
            self.tick(sub)
        self._report_stall(max_ticks)

    def _report_stall(self, max_ticks: int) -> None:
        """Drain exhausted its tick budget with requests still live: a
        stall is an *answer*, not a silent return. Every remaining
        request is marked TIMED_OUT (its pages released, pool back to
        baseline) and recorded in ``stalled_rids`` / ``stats()`` so
        harnesses and operators see exactly who starved."""
        detail = f"stalled: drain hit max_ticks={max_ticks}"
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
            self._release_slot(slot)
            self.n_stalled += 1
            self.stalled_rids.append(req.rid)

    # ------------------------------------------- Engine protocol surface

    def drain(self, max_ticks: int = 10_000,
              rng: Optional[jax.Array] = None) -> None:
        """Engine protocol: run ticks until no request is queued or live."""
        self.run_until_done(max_ticks, rng)

    def stats(self) -> Dict[str, Any]:
        """Engine protocol: one flat dict of serving counters, keyed the
        same across engine kinds so harnesses never branch on the type."""
        out = {
            "engine": "paged",
            "ticks": self.ticks,
            "layout": self.cfg.page_layout.describe(),
            "n_preempted": self.n_preempted,
            "n_recycled_pages": self.n_recycled_pages,
            "n_cow_copies": self.n_cow_copies,
            "n_state_restores": self.n_state_restores,
            "peak_slot_pages": self.peak_slot_pages,
            "n_prefill_computed_tokens": self.n_prefill_computed_tokens,
            "n_pages_grown": self.n_pages_grown,
            "prefix_hit_rate": self.prefix_hit_rate(),
            "lifecycle": dict(self.lifecycle_counts),
            "n_stalled": self.n_stalled,
            "stalled_rids": list(self.stalled_rids),
            "n_shed": self.n_shed,
            "n_quarantined": self.n_quarantined,
            "n_backend_fallbacks": self.n_backend_fallbacks,
            "packed": {
                "enabled": self.packed,
                "n_packed_ticks": self.n_packed_ticks,
                "n_masked_ticks": self.n_masked_ticks,
                "n_rows_saved": self.n_packed_rows_saved,
                "n_sealed_fallbacks": self.n_packed_fallbacks,
            },
            # per phase, mean / p95 / max ms per tick over the tick log
            "tick_phases_ms": self.tick_log.summary(),
        }
        if self.n_groups > 1:
            out["table_groups"] = {
                "n_groups": self.n_groups,
                "group_windows": list(self.group_windows),
                "group_pages_hard": list(self._group_pages_hard),
            }
        if self.tiered:
            looked = self.n_prefetch_hits + self.n_prefetch_misses
            out["tiered"] = {
                "device_pages": self.pool.device_pages,
                "n_demoted": self.pool.n_demoted,
                "n_promoted": self.pool.n_promoted,
                "n_prefetch_hits": self.n_prefetch_hits,
                "n_prefetch_misses": self.n_prefetch_misses,
                "prefetch_hit_rate": (self.n_prefetch_hits / looked
                                      if looked else 1.0),
                "n_sync_fetches": self.n_sync_fetches,
                "n_fetches_issued": self._fetch.n_issued,
                "n_sync_fallbacks": self._fetch.n_sync_fallback,
                "n_decode_reruns": self.n_decode_reruns,
            }
        if self._faults is not None:
            out["faults"] = dict(self._faults.counts)
        return out
