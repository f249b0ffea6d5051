"""Paged KV-cache: block-granular cache storage for the serving engine.

The dense engine preallocates one ``(n_slots, Smax, Hkv, D)`` cache per
layer, so total context is hard-capped at ``n_slots * smax`` and every slot
pays for its worst case. Here the cache is a shared **page pool**:

  pool      (Hkv, W, n_pages * page_size)   per layer, no batch dim
  page table(n_slots, max_pages) int32      logical page -> physical page

A request's logical position ``p`` lives at pool row (the minor axis)
``table[slot, p // page_size] * page_size + p % page_size``. The pool's
axis order and why it is the one the TPU kernels compile for live in
kernels/layout.py; the jnp helpers below read and write it and hand
callers the model-native logical view ``(B, S, Hkv, W)``. Pages are
handed out on demand as a request's context grows and **released** — not
destroyed — the moment it finishes (or is preempted), so memory scales with
the *live* token count, not with ``n_slots * smax``.

``page_size`` defaults to ``LokiConfig.block_size``: the fused Loki decode
kernel already treats the cache as fixed-size blocks, so a page is exactly
the kernel's DMA unit and paged decode is pure index indirection
(DESIGN.md §7).

Physical page 0 is reserved as a trash page: freed slots point their whole
table at it, so the batched decode step's unconditional cache write lands
in the trash instead of corrupting pages that have been reallocated to
other requests.

Refcounts + prefix cache (DESIGN.md §9): every held page carries a
refcount, and full prompt pages can be *registered* in a content-hash
index (a chain hash over the page's tokens and everything before them, so
two prompts share a physical page iff their token prefixes are identical).
A later request whose prompt starts with the same pages **acquires** them
(refcount++) instead of recomputing their K/V. Releasing a page whose
refcount drops to zero sends it to

  * the free list, if it was never registered, or
  * an LRU of *cached-but-unreferenced* pages, if it is in the index —
    still servable as prefix hits, reclaimed (LRU-first, index entry
    dropped) only when the free list runs dry. Eviction of unreferenced
    cached pages therefore always happens *before* the scheduler has to
    preempt a live request.

Tiered pool (DESIGN.md §13): with ``device_pages`` set, the pool splits
logical pages from device **frames**. Page ids stay the unit of the page
tables, refcounts and the prefix index; only ``device_pages`` frames of
full-D K/V rows exist in HBM. Every logical page additionally owns an
always-resident rank-r latent-K sidecar row range (allocated by
``init_paged_cache``), which is all the Loki score pass reads. A page is
in exactly one tier state:

  RESIDENT   full-D rows live in a device frame (``frame_of(page)``)
  HOST       full-D rows live in the engine's pinned host buffers
  IN_FLIGHT  a host->HBM fetch owns a frame but has not landed yet

``demote``/``promote_begin``/``promote_complete`` move pages between the
states with double-free-style guards (demoting a HOST page or promoting a
RESIDENT page raises). ``FetchQueue`` wraps the promote pair into a
bounded async queue with double-buffered staging frames.

This module is deliberately two-layered:
  * pure-jnp array helpers (``gather_logical``, ``write_token_rows``,
    ``write_chunk_rows``, ``copy_page_rows``) used inside jit,
  * the host-side ``PagePool`` allocator driven by the scheduler.
"""
from __future__ import annotations

import collections
import dataclasses
import hashlib
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dispatch
from repro.kernels.layout import page_slice, put_rows
from repro.kernels.pool_write import pool_write_rows
from repro.kernels.tuning import LANE

TRASH_PAGE = 0

# tier states of a logical page in a tiered pool (DESIGN.md §13)
RESIDENT = "resident"
HOST = "host"
IN_FLIGHT = "in_flight"

_UINT_OF = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}

#: PageLayout.dtype -> jnp storage dtype of the physical pool
STORAGE_DTYPE = {"fp32": jnp.float32, "fp16": jnp.float16,
                 "bf16": jnp.bfloat16, "int8": jnp.int8,
                 "fp8": jnp.float8_e4m3fn}

# chain-hash root: the "prefix" before a prompt's first page
ROOT_KEY = b""


# ------------------------------------------------------------ jnp helpers
# (those that write or read pool rows run under the device scope ``pool``)

def logical_rows(page_table, page_size: int):
    """(B, max_pages) int32 -> (B, max_pages * page_size) pool row ids."""
    b, n = page_table.shape
    rows = page_table[:, :, None] * page_size + jnp.arange(page_size)
    return rows.reshape(b, n * page_size)


def gather_logical(pool, page_table, page_size: int):
    """Materialize the logical per-slot view of a pooled cache.

    pool (Hkv, W, R); page_table (B, max_pages)
    -> (B, max_pages * page_size, Hkv, W).

    This is the jnp-oracle read path: every dense-cache decode/attention
    routine runs unchanged on the gathered view (rows past ``cur_len`` are
    garbage from unallocated/trash pages and are masked by the caller's
    length mask exactly like the dense cache's unwritten rows). The
    gather is page-granular: whole ``(W, page_size)`` tiles move."""
    h, w, r = pool.shape
    b, n = page_table.shape
    pages = pool.reshape(h, w, r // page_size, page_size)[:, :, page_table]
    # (Hkv, W, B, max_pages, ps) -> (B, max_pages, ps, Hkv, W)
    return pages.transpose(2, 3, 4, 0, 1).reshape(b, n * page_size, h, w)


def _scatter_rows(pool, rows, new, layer=None):
    """pool (Hkv, W, R) <- new (N, Hkv, W) at row ids (N,); with ``layer``,
    into that layer of stacked pools (L, Hkv, W, R). The jnp route: the
    scatter runs on the bits as uint, because XLA:CPU legalizes a
    low-precision float scatter through f32 and would rewrite the whole
    pool with converts on every write (§Perf L3)."""
    dt = pool.dtype
    uint = _UINT_OF.get(jnp.dtype(dt).itemsize) if jnp.issubdtype(
        dt, jnp.floating) else None
    p_view = jax.lax.bitcast_convert_type(pool, uint) if uint else pool
    n_view = jax.lax.bitcast_convert_type(new.astype(dt), uint) if uint \
        else new.astype(dt)
    if layer is None:
        out = p_view.at[:, :, rows].set(n_view.transpose(1, 2, 0),
                                        mode="drop")
    else:
        out = p_view.at[layer, :, :, rows].set(n_view, mode="drop")
    return jax.lax.bitcast_convert_type(out, dt) if uint else out


def token_rows(page_table, pos, page_size: int):
    """Pool rows for one token per slot. page_table (B, max_pages),
    pos (B,) logical positions -> (B,) physical rows."""
    page = (pos // page_size).astype(jnp.int32)
    pid = jnp.take_along_axis(page_table, page[:, None], axis=1)[:, 0]
    return pid * page_size + (pos % page_size).astype(jnp.int32)


@jax.named_scope("pool")
def write_token_rows(pool, new, page_table, pos, page_size: int, *,
                     layer=None, backend: str = "xla"):
    """Decode-step write: new (B, Hkv, W) at logical positions pos (B,).

    ``layer`` (traced int32): ``pool`` is every layer's pools stacked
    (L, Hkv, W, R) and the rows land in this layer. On the Pallas
    ``backend`` that is the aliased row-write kernel
    (kernels/pool_write.py), in place in the stack; otherwise, and for a
    pool with no whole 128-row tiles on TPU, a jnp scatter into it."""
    rows = token_rows(page_table, pos, page_size)
    if layer is not None and dispatch.resolve_backend(backend) == "pallas":
        interpret = dispatch._interpret(None)
        if interpret or pool.shape[-1] % LANE == 0:
            return pool_write_rows(pool, new, layer, rows,
                                   interpret=interpret)
    return _scatter_rows(pool, rows, new, layer)


@jax.named_scope("pool")
def write_chunk_rows(pool, new, table_row, pos_start, page_size: int, *,
                     n_valid=None):
    """Chunked-prefill write: new (C, Hkv, W) at logical positions
    ``pos_start + [0, C)`` of a single request. table_row (max_pages,).

    ``n_valid``: rows at or past it (the zero-padding of a fixed-size final
    chunk) are diverted to the trash page so a padded chunk never needs
    pages beyond the real tokens and never clobbers live rows."""
    c = new.shape[0]
    pos = pos_start + jnp.arange(c)
    page = (pos // page_size).astype(jnp.int32)
    rows = table_row[page] * page_size + (pos % page_size).astype(jnp.int32)
    if n_valid is not None:
        rows = jnp.where(jnp.arange(c) < n_valid, rows,
                         TRASH_PAGE * page_size)
    return _scatter_rows(pool, rows, new)


# ------------------------------------------------- quantized page helpers
#
# Quantized PageLayouts store pool rows in int8/fp8 with one f32 amax scale
# per physical page (kept in a (n_pages,) sidecar next to the page table,
# one per pool — K and V scales are independent). Serving writes are
# strictly sequential per request, so a page's valid rows are always a
# prefix [0, n_valid): every write re-derives the page scale from exactly
# that prefix. A rewrite at an unchanged scale is bit-exact (the amax row
# quantizes to +-qmax, every other row reproduces its code), so the
# read-modify-write below is idempotent and only loses precision when the
# page's amax actually grows.

QUANT_EPS = 1e-8      # scale floor: all-zero (fresh) pages divide safely


def quantize_rows(x, scale, dtype, qmax: float):
    """f32 rows -> quantized codes at a given (scalar) page scale."""
    y = x / scale
    if jnp.issubdtype(jnp.dtype(dtype), jnp.integer):
        y = jnp.clip(jnp.round(y), -qmax, qmax)
    return y.astype(dtype)


def _page_scale(page_f32, n_valid, qmax: float):
    """amax/qmax over the valid prefix of one page's dequantized rows
    (page (Hkv, W, ps): rows are the minor axis)."""
    m = jnp.arange(page_f32.shape[-1]) < n_valid
    amax = jnp.max(jnp.abs(page_f32) * m)
    return jnp.maximum(amax, QUANT_EPS) / qmax


def gather_scales(scales, page_table, page_size: int):
    """Per logical-row dequant scale. scales (n_pages,) f32;
    page_table (B, max_pages) -> (B, max_pages * page_size)."""
    s = scales[page_table]                       # (B, max_pages)
    return jnp.repeat(s, page_size, axis=1)


@jax.named_scope("pool")
def gather_logical_dq(pool, scales, page_table, page_size: int):
    """``gather_logical`` + dequantization: the f32 logical view of a
    quantized pool (``scales=None`` falls through to the plain gather, so
    callers hold one code path per layout)."""
    rows = gather_logical(pool, page_table, page_size)
    if scales is None:
        return rows
    s = gather_scales(scales, page_table, page_size)
    return rows.astype(jnp.float32) * s[:, :, None, None]


@jax.named_scope("pool")
def write_token_rows_q(pool, scales, new, page_table, pos, page_size: int,
                       *, qmax: float):
    """Quantized decode-step write: RMW of each slot's current page.

    pool (H, W, R) int8/fp8; scales (n_pages,) f32; new (B, H, W);
    pos (B,) logical positions. Each slot's touched page is dequantized at
    its old scale, the new row overlaid, the scale re-derived over the
    valid prefix [0, pos%ps + 1) and the page re-quantized. Slots of dead
    requests point at the trash page (page 0) and harmlessly RMW it."""
    ps = page_size

    def body(i, carry):
        pool, scales = carry
        page = page_table[i, pos[i] // ps]
        start = page * ps
        dq = page_slice(pool, start, ps).astype(jnp.float32) * scales[page]
        off = pos[i] % ps
        dq = put_rows(dq, new[i][:, :, None], off)
        scale = _page_scale(dq, off + 1, qmax)
        q = quantize_rows(dq, scale, pool.dtype, qmax)
        pool = put_rows(pool, q, start)
        return pool, scales.at[page].set(scale)

    return jax.lax.fori_loop(0, new.shape[0], body, (pool, scales))


@jax.named_scope("pool")
def write_chunk_rows_q(pool, scales, new, table_row, pos_start,
                       page_size: int, *, n_valid=None, qmax: float):
    """Quantized chunked-prefill write (one request): RMW of every page
    the chunk touches. new (C, H, W) at logical ``pos_start + [0, C)``;
    rows at or past ``n_valid`` (final-chunk padding) are never written.
    A spanned page that receives no valid row is diverted to the trash
    page so live pages are never re-quantized gratuitously."""
    ps = page_size
    c = new.shape[0]
    nv = c if n_valid is None else n_valid
    max_pages = table_row.shape[0]
    span = (c + ps - 1) // ps + 1                # static page-span bound

    def body(j, carry):
        pool, scales = carry
        lpage = pos_start // ps + j
        in_range = lpage < max_pages
        page = jnp.where(
            in_range, table_row[jnp.minimum(lpage, max_pages - 1)],
            TRASH_PAGE)
        g0 = lpage * ps                          # page's logical start
        ci = g0 + jnp.arange(ps) - pos_start     # page row -> chunk row
        take = (ci >= 0) & (ci < nv)
        page = jnp.where(take.any() & in_range, page, TRASH_PAGE)
        start = page * ps
        dq = page_slice(pool, start, ps).astype(jnp.float32) * scales[page]
        rows = new[jnp.clip(ci, 0, c - 1)].astype(jnp.float32)
        dq = jnp.where(take, rows.transpose(1, 2, 0), dq)
        nv_page = jnp.clip(pos_start + nv - g0, 0, ps)
        scale = _page_scale(dq, nv_page, qmax)
        q = quantize_rows(dq, scale, pool.dtype, qmax)
        pool = put_rows(pool, q, start)
        return pool, scales.at[page].set(scale)

    return jax.lax.fori_loop(0, span, body, (pool, scales))


@jax.named_scope("pool")
def copy_page_rows(pool, src_page, dst_page, page_size: int):
    """Copy-on-write: duplicate one physical page's rows inside a pool.

    pool (Hkv, W, R); src_page/dst_page traced int32 scalars. Used when a
    request sharing a cached tail page must diverge from it: the rows it
    read so far are copied to a freshly-allocated page, and only then does
    the request write its own tokens (the shared original stays intact for
    its other readers / the cache index)."""
    return put_rows(pool, page_slice(pool, src_page * page_size, page_size),
                    dst_page * page_size)


def copy_page_scale(scales, src_page, dst_page):
    """COW of a quantized page's sidecar scale: codes are copied verbatim
    by ``copy_page_rows``, so the copy only stays a faithful dequant of
    the donor if its scale rides along."""
    return scales.at[dst_page].set(scales[src_page])


# --------------------------------------------------------- host allocator

def page_key(parent: bytes, tokens) -> bytes:
    """Chain hash identifying a full page of prompt tokens *in context*:
    ``parent`` is the preceding pages' key (ROOT_KEY for page 0), so equal
    keys imply equal token prefixes end to end — position-dependent K/V
    (rope, Loki's storage basis) can be shared safely."""
    h = hashlib.blake2b(digest_size=16)
    h.update(parent)
    h.update(np.ascontiguousarray(tokens, np.int32).tobytes())
    return h.digest()


@dataclasses.dataclass
class CacheEntry:
    """One registered (immutable, full) prompt page."""
    page: int
    key: bytes                 # chain hash incl. this page's tokens
    parent: bytes              # chain hash of the preceding pages
    tokens: np.ndarray         # this page's page_size token ids


class PagePool:
    """Host-side refcounted allocator over ``n_pages`` physical pages.

    Page 0 is reserved (trash page for freed slots' writes), so the usable
    capacity is ``n_pages - 1`` pages. Lifecycle of a usable page:

      free -> alloc() -> held (ref 1) -> acquire()/release() ref +-1
        release to ref 0:  unregistered -> free
                           registered   -> cached (LRU, evictable)
      cached -> match_prefix() hit -> held again (ref 1)
      cached -> eviction (free list empty) -> free

    ``free_pages`` counts only truly-free pages; ``cached_pages`` the
    registered-but-unreferenced LRU; ``available_pages`` their sum — the
    number ``alloc`` can actually produce. ``used_pages`` counts pages some
    request currently holds a reference to.
    """

    def __init__(self, n_pages: int, page_size: int,
                 device_pages: Optional[int] = None,
                 max_inflight: int = 2):
        if n_pages < 2:
            raise ValueError("need >= 2 pages (page 0 is reserved)")
        if device_pages is not None and not 2 <= device_pages <= n_pages:
            raise ValueError(
                f"device_pages must be in [2, n_pages={n_pages}], "
                f"got {device_pages}")
        self.n_pages = n_pages
        self.page_size = page_size
        # seeded fault plan (serving/faults.py) this pool consults at its
        # injection sites; None = no faults (production default)
        self._faults = None
        self._free: List[int] = list(range(1, n_pages))
        # ---- tier state (None device_pages = single-tier: every page is
        # its own frame and the tier machinery degenerates to identity)
        self.device_pages = device_pages
        self.max_inflight = max_inflight
        self._free_frames: List[int] = (
            list(range(1, device_pages)) if device_pages else [])
        self._frame_of: Dict[int, int] = {}   # RESIDENT | IN_FLIGHT pages
        self._tier: Dict[int, str] = {}       # allocated/cached pages only
        self._pinned: Dict[int, int] = {}     # page -> pin count
        self._inflight: Dict[int, int] = {}   # page -> staging frame
        self.n_demoted = 0
        self.n_promoted = 0
        self._ref: Dict[int, int] = {}
        # prefix-cache index over *full* prompt pages
        self._index: Dict[bytes, CacheEntry] = {}
        self._children: Dict[bytes, List[CacheEntry]] = {}
        self._by_page: Dict[int, CacheEntry] = {}
        # registered pages with refcount 0, oldest-released first
        self._lru: "collections.OrderedDict[int, None]" = \
            collections.OrderedDict()
        # counters (benchmarks / hit-rate assertions)
        self.n_lookups = 0
        self.n_hits = 0
        self.n_hit_tokens = 0
        self.n_evicted = 0
        self._priv_ctr = 0          # unique private-entry keys

    # --------------------------------------------------- fault injection

    def set_faults(self, plan) -> None:
        """Attach a serving/faults.py FaultPlan; the pool consults it at
        ``alloc`` (alloc_fail) and ``available_pages`` (pool_exhaustion).
        The engine owns advancing the plan's tick."""
        self._faults = plan

    def _fault(self, site: str, unit: int = 0) -> bool:
        return self._faults is not None and self._faults.hit(site, unit)

    # ------------------------------------------------------- accounting

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def cached_pages(self) -> int:
        """Registered pages no request references — servable as prefix
        hits, reclaimable by ``alloc`` without preempting anyone."""
        return len(self._lru)

    @property
    def available_pages(self) -> int:
        """What ``alloc`` can produce: free plus evictable cached pages.
        An injected ``pool_exhaustion`` fault reads as 0 for the whole
        tick — callers see a full pool and exercise their pressure
        paths — without touching any real accounting."""
        if self._fault("pool_exhaustion"):
            return 0
        return len(self._free) + len(self._lru)

    @property
    def used_pages(self) -> int:
        """Pages some request currently holds a reference to (cached-but-
        unreferenced pages are *not* used — they are reclaimable)."""
        return (self.n_pages - 1) - len(self._free) - len(self._lru)

    def refcount(self, page: int) -> int:
        return self._ref.get(page, 0)

    def is_registered(self, page: int) -> bool:
        return page in self._by_page

    def is_private(self, page: int) -> bool:
        """Is this page a ``register_private`` retained entry (never
        shareable — the auditor's invariant D checks no slot pair ever
        aliases one)?"""
        e = self._by_page.get(page)
        return e is not None and e.key.startswith(b"priv:")

    # read-only views for the invariant auditor (serving/faults.py): the
    # auditor re-derives accounting from these instead of groping private
    # state, so the pool can change representation without breaking it
    def free_page_ids(self) -> List[int]:
        return list(self._free)

    def lru_page_ids(self) -> List[int]:
        return list(self._lru)

    def holders(self) -> Dict[int, int]:
        """page -> refcount for every currently-held page (a copy)."""
        return dict(self._ref)

    # ------------------------------------------------------- tiered state
    #
    # The pool is pure bookkeeping: the *engine* owns the device pools and
    # the host byte buffers and performs the actual copies. The contract
    # is copy-then-demote (full-D rows must be on host before the frame is
    # surrendered) and promote_begin-copy-promote_complete (the frame is
    # owned by the fetch from begin to complete).

    @property
    def tiered(self) -> bool:
        return self.device_pages is not None

    @property
    def free_frames(self) -> int:
        return len(self._free_frames)

    def tier_of(self, page: int) -> str:
        """Tier state of an allocated/cached page (single-tier pools and
        the trash page are RESIDENT by definition)."""
        if not self.tiered or page == TRASH_PAGE:
            return RESIDENT
        state = self._tier.get(page)
        if state is None:
            raise ValueError(f"tier_of() of free page {page}")
        return state

    def frame_of(self, page: int) -> Optional[int]:
        """Device frame holding a page's full-D rows: the page id itself
        in a single-tier pool, the mapped frame for RESIDENT/IN_FLIGHT
        pages of a tiered pool, None for HOST pages."""
        if not self.tiered:
            return page
        if page == TRASH_PAGE:
            return TRASH_PAGE
        return self._frame_of.get(page)

    def pin(self, page: int) -> None:
        """Pin a RESIDENT page against demotion (tail pages receiving
        decode writes, pages of a slot mid-prefill)."""
        if not self.tiered or page == TRASH_PAGE:
            return
        if self._tier.get(page) != RESIDENT:
            raise ValueError(f"pin of non-resident page {page}")
        self._pinned[page] = self._pinned.get(page, 0) + 1

    def unpin(self, page: int) -> None:
        if not self.tiered or page == TRASH_PAGE:
            return
        if self._pinned.get(page, 0) <= 0:
            raise ValueError(f"unpin of unpinned page {page}")
        self._pinned[page] -= 1
        if self._pinned[page] == 0:
            del self._pinned[page]

    def is_pinned(self, page: int) -> bool:
        return self._pinned.get(page, 0) > 0

    def demote(self, page: int) -> int:
        """Surrender a RESIDENT page's frame (its full-D rows must already
        be in the host buffers — the engine copies first). Returns the
        freed frame. Raises like a double-free on a page that is already
        HOST, mid-fetch, pinned, or the trash page."""
        if not self.tiered:
            raise ValueError("demote() on a single-tier pool")
        if page == TRASH_PAGE:
            raise ValueError("demote of the reserved trash page")
        state = self._tier.get(page)
        if state == HOST:
            raise ValueError(f"double-demote of page {page}")
        if state != RESIDENT:
            raise ValueError(f"demote of {state or 'free'} page {page}")
        if self._pinned.get(page, 0):
            raise ValueError(f"demote of pinned page {page}")
        frame = self._frame_of.pop(page)
        self._free_frames.append(frame)
        self._tier[page] = HOST
        self.n_demoted += 1
        return frame

    def promote_begin(self, page: int, faultable: bool = True
                      ) -> Optional[int]:
        """Claim a staging frame for a HOST page's host->HBM fetch.

        Returns the frame (page becomes IN_FLIGHT; the engine copies, then
        ``promote_complete``), or None when no frame is free, the bounded
        in-flight budget is exhausted, or an ``hbm_oom_on_promote`` fault
        fires — callers run their demote/retry/preempt ladder. Promoting a
        RESIDENT or IN_FLIGHT page raises like a double-free."""
        if not self.tiered:
            raise ValueError("promote_begin() on a single-tier pool")
        state = self._tier.get(page)
        if state in (RESIDENT, IN_FLIGHT):
            raise ValueError(f"promote of {state} page {page}")
        if state != HOST:
            raise ValueError(f"promote of free page {page}")
        if faultable and self._fault("hbm_oom_on_promote", page):
            return None
        if not self._free_frames or len(self._inflight) >= self.max_inflight:
            return None
        frame = self._free_frames.pop()
        self._frame_of[page] = frame
        self._tier[page] = IN_FLIGHT
        self._inflight[page] = frame
        return frame

    def promote_complete(self, page: int) -> int:
        """The fetch landed: IN_FLIGHT -> RESIDENT. Returns the frame."""
        if self._tier.get(page) != IN_FLIGHT:
            raise ValueError(
                f"promote_complete of page {page} with no fetch in flight")
        del self._inflight[page]
        self._tier[page] = RESIDENT
        self.n_promoted += 1
        return self._frame_of[page]

    def promote_abort(self, page: int) -> None:
        """A fetch that never landed (dma_timeout): give the staging frame
        back and return the page to HOST so a synchronous retry can claim
        a fresh fetch."""
        if self._tier.get(page) != IN_FLIGHT:
            raise ValueError(
                f"promote_abort of page {page} with no fetch in flight")
        del self._inflight[page]
        self._free_frames.append(self._frame_of.pop(page))
        self._tier[page] = HOST

    def _tier_free(self, page: int) -> None:
        """Clear a page's tier state as it returns to the free list.
        The in-flight check comes before any mutation: a refused free
        must leave the tier partition untouched (the fetch still owns
        its staging frame)."""
        if not self.tiered:
            return
        state = self._tier.get(page)
        if state == IN_FLIGHT:
            raise ValueError(f"free of in-flight page {page}")
        self._tier.pop(page, None)
        if state == RESIDENT:
            self._free_frames.append(self._frame_of.pop(page))
        self._pinned.pop(page, None)

    # auditor views over the tier partition (serving/faults.py invariants
    # G/H/I re-derive the accounting from these copies)
    def resident_page_ids(self) -> List[int]:
        return [p for p, s in self._tier.items() if s == RESIDENT]

    def host_page_ids(self) -> List[int]:
        return [p for p, s in self._tier.items() if s == HOST]

    def inflight_page_ids(self) -> List[int]:
        return list(self._inflight)

    def free_frame_ids(self) -> List[int]:
        return list(self._free_frames)

    def pinned_page_ids(self) -> List[int]:
        return [p for p, n in self._pinned.items() if n > 0]

    def frame_map(self) -> Dict[int, int]:
        """page -> frame for every RESIDENT/IN_FLIGHT page (a copy)."""
        return dict(self._frame_of)

    def deregister(self, page: int) -> None:
        """Drop a *held* page's index entry (no-op if unregistered). The
        sole-reader arm of copy-on-write uses this to take ownership in
        place: the caller is about to overwrite rows, so the cached
        content ceases to exist and a copy would preserve data nobody
        else references. Unreferenced cached pages are reclaimed through
        ``_evict_one`` instead."""
        e = self._by_page.get(page)
        if e is None:
            return
        if self._ref.get(page, 0) <= 0:
            raise ValueError(f"deregister of unheld page {page}")
        self._drop_entry(e)

    def _drop_entry(self, e: CacheEntry) -> None:
        """Remove an entry from all three index views (page stays as-is)."""
        del self._by_page[e.page]
        del self._index[e.key]
        sibs = self._children[e.parent]
        sibs.remove(e)
        if not sibs:
            del self._children[e.parent]

    # ------------------------------------------------------- alloc/free

    def alloc(self, n: int) -> Optional[List[int]]:
        """Grab n fresh pages (refcount 1), or None (and no allocation /
        eviction) if the pool can't. Eviction of cached-but-unreferenced
        pages (LRU first) backs the free list, so a full cache never
        forces a preemption while reclaimable pages exist. ``alloc(0)``
        returns ``[]`` without touching the free list."""
        if n == 0:
            return []
        if self._fault("alloc_fail", n):
            return None       # injected: as if the free list ran dry
        if n > self.available_pages:
            return None
        if self.tiered:
            # fresh pages receive writes, so each needs a device frame;
            # evictable cached pages may carry reclaimable frames, but if
            # even those can't cover the request the caller must demote
            # cold resident pages (policy hook) before retrying
            lru_frames = sum(1 for p in self._lru
                             if self._tier.get(p) == RESIDENT)
            if n > len(self._free_frames) + lru_frames:
                return None
            while len(self._free_frames) < n:
                self._evict_one()
        while len(self._free) < n:
            self._evict_one()
        taken, self._free = self._free[:n], self._free[n:]
        for p in taken:
            self._ref[p] = 1
            if self.tiered:
                self._tier[p] = RESIDENT
                self._frame_of[p] = self._free_frames.pop()
        return taken

    def acquire(self, pages: List[int]) -> List[int]:
        """Take an additional reference on already-held or cached pages
        (sharing). ``acquire([])`` returns ``[]`` without touching any
        state. Raises on a page nobody holds and the index doesn't know —
        that would be acquiring a free page out of thin air."""
        for p in pages:
            if p == TRASH_PAGE:
                raise ValueError("acquire of the reserved trash page")
            if self._ref.get(p, 0) == 0 and p not in self._by_page:
                raise ValueError(f"acquire of unheld page {p}")
        for p in pages:
            self._acquire_one(p)
        return pages

    def _acquire_one(self, page: int) -> None:
        self._ref[page] = self._ref.get(page, 0) + 1
        self._lru.pop(page, None)

    def release(self, pages: List[int]) -> None:
        """Drop one reference per page. At refcount zero the page returns
        to the free list — or, if registered in the prefix index, to the
        cached-unreferenced LRU (still hittable, evicted on demand).

        Raises (rather than asserts, so ``python -O`` keeps the guard) on
        a refcount underflow — the refcounted equivalent of a double-free
        — or an attempt to release the reserved trash page."""
        seen: Dict[int, int] = {}
        for p in pages:
            if p == TRASH_PAGE:
                raise ValueError("free() of the reserved trash page")
            seen[p] = seen.get(p, 0) + 1
            if self._ref.get(p, 0) < seen[p]:
                raise ValueError(
                    f"double-free of page {p} (refcount underflow)")
        for p, c in seen.items():
            # all-or-nothing: a free that would drop an IN_FLIGHT page to
            # the free list must refuse before any refcount moves (the
            # fetch still owns the page's staging frame)
            if self.tiered and self._ref.get(p, 0) == c \
                    and p not in self._by_page \
                    and self._tier.get(p) == IN_FLIGHT:
                raise ValueError(f"free of in-flight page {p}")
        for p in pages:
            self._ref[p] -= 1
            if self._ref[p] == 0:
                del self._ref[p]
                if p in self._by_page:
                    self._lru[p] = None          # MRU end of the LRU
                else:
                    self._tier_free(p)
                    self._free.append(p)

    # released pages historically went through ``free``; release IS free
    # under refcounts (ref 1 -> 0), so keep the old name as an alias
    free = release

    def _evict_one(self) -> None:
        """Reclaim the least-recently-released cached page: drop its index
        entry and hand the physical page to the free list."""
        page, _ = self._lru.popitem(last=False)
        self._drop_entry(self._by_page[page])
        self._tier_free(page)
        self._free.append(page)
        self.n_evicted += 1

    # ------------------------------------------------------ prefix cache

    def register(self, page: int, parent: bytes, tokens) -> bytes:
        """Publish a held, fully-written prompt page under its chain hash.
        Returns the page's key (the next page's ``parent``). A key that is
        already indexed keeps its existing physical page (first writer
        wins); the caller's copy stays private. Registered pages are
        immutable: the engine never writes a row of a registered page
        again (COW duplicates first)."""
        toks = np.ascontiguousarray(tokens, np.int32)
        if toks.shape[0] != self.page_size:
            raise ValueError("register() needs exactly one full page of "
                             f"tokens ({self.page_size}), got {toks.shape}")
        key = page_key(parent, toks)
        if key in self._index or page in self._by_page:
            return key
        if self._ref.get(page, 0) <= 0:
            raise ValueError(f"register of unheld page {page}")
        e = CacheEntry(page, key, parent, toks)
        self._index[key] = e
        self._children.setdefault(parent, []).append(e)
        self._by_page[page] = e
        return key

    def match_prefix(self, tokens, max_tokens: int
                     ) -> Tuple[List[int], int, bool, bytes]:
        """Longest cached prefix of ``tokens[:max_tokens]``, acquired.

        Walks the chain hash over full pages; after the last full-page hit
        it additionally tries a *partial tail*: a registered sibling page
        whose first rows match the remaining tokens (the classic shared-
        system-prompt case where the split falls mid-page). Matched pages
        come back with a reference taken (caller releases them like any
        other page).

        Returns (pages, n_matched_tokens, tail_is_partial, parent_key)
        where ``parent_key`` is the chain hash after the *full* matches —
        the key the caller threads into ``register`` for the pages it goes
        on to compute itself."""
        ps = self.page_size
        toks = np.ascontiguousarray(tokens, np.int32)
        pages: List[int] = []
        n, parent = 0, ROOT_KEY
        while n + ps <= max_tokens:
            key = page_key(parent, toks[n:n + ps])
            e = self._index.get(key)
            if e is None:
                break
            self._acquire_one(e.page)
            pages.append(e.page)
            parent = key
            n += ps
        tail = False
        rem = min(max_tokens - n, ps)   # rem == ps: full lookup missed but
        if rem > 0:                     # a shorter overlap may still exist
            best, best_j = None, 0
            for e in self._children.get(parent, ()):  # longest overlap wins
                j = int((e.tokens[:rem] == toks[n:n + rem]).cumprod().sum())
                if j > best_j:
                    best, best_j = e, j
            if best is not None:
                self._acquire_one(best.page)
                pages.append(best.page)
                n += best_j
                tail = True
        self.n_lookups += 1
        if pages:
            self.n_hits += 1
        self.n_hit_tokens += n
        return pages, n, tail, parent

    # ---------------------------------------------------- private entries

    def register_private(self, page: int) -> bytes:
        """Index a *held* page under a unique private key.

        Private entries give a page the cached-page lifecycle (release ->
        LRU, evictable under pressure, reclaimable by key) without ever
        being shareable: the key is a counter tag, so it can never collide
        with a chain hash and ``match_prefix`` can never walk into it.
        Preemption uses this to retain a hybrid request's own K/V pages —
        whose content depends on that request's recurrent state, not just
        its tokens — so a state snapshot plus reclaimed pages can resume
        it without recompute."""
        if self._ref.get(page, 0) <= 0:
            raise ValueError(f"register_private of unheld page {page}")
        if page in self._by_page:
            raise ValueError(f"page {page} is already registered")
        self._priv_ctr += 1
        key = b"priv:%d" % self._priv_ctr
        e = CacheEntry(page, key, key, np.empty(0, np.int32))
        self._index[key] = e
        self._children.setdefault(key, []).append(e)
        self._by_page[page] = e
        return key

    def reclaim_private(self, keys) -> Optional[List[int]]:
        """All-or-nothing reclaim of ``register_private`` entries.

        If every key survived eviction: re-acquire each page (ref 0 -> 1,
        out of the LRU), drop the private index entries (the pages go back
        to plain held pages) and return them in key order. If *any* page
        was evicted the retained set is useless — the snapshot's state
        covers exactly the full prefix — so the survivors are dropped from
        the index and freed immediately; returns None (caller recomputes)."""
        if any(k not in self._index for k in keys):
            for k in keys:
                e = self._index.get(k)
                if e is None:
                    continue
                self._drop_entry(e)
                if e.page in self._lru:
                    self._lru.pop(e.page)
                    self._tier_free(e.page)
                    self._free.append(e.page)
            return None
        pages = []
        for k in keys:
            e = self._index[k]
            self._acquire_one(e.page)
            self._drop_entry(e)
            pages.append(e.page)
        return pages

    @staticmethod
    def pages_for(n_tokens: int, page_size: int) -> int:
        """Pages needed to hold n_tokens."""
        return -(-max(n_tokens, 0) // page_size)


# ------------------------------------------------------- async fetch queue

class FetchQueue:
    """Bounded async host->HBM promotion queue over a tiered PagePool.

    ``request(page)`` claims a staging frame (``promote_begin``), dispatches
    the engine-supplied copy (jax dispatch is async, so the DMA overlaps
    whatever the host enqueues next — the next layer's score pass in the
    tiered decode pipeline) and tracks the fetch as IN_FLIGHT. The queue
    holds at most ``pool.max_inflight`` outstanding fetches (default 2:
    double-buffered staging); requesting past the budget completes the
    oldest fetch first, so issue order is also landing order.

    ``drain()`` is the barrier before the sparse-attention pass reads the
    frame table: every outstanding fetch is completed (or, under an
    injected ``dma_timeout``, aborted and re-copied synchronously — the
    counted fallback path).
    """

    def __init__(self, pool: PagePool, copy_fn, faults=None):
        self.pool = pool
        self._copy = copy_fn            # copy_fn(page, frame) -> None
        self._faults = faults
        self._pending: "collections.deque[int]" = collections.deque()
        self.n_issued = 0
        self.n_sync_fallback = 0

    def request(self, page: int) -> bool:
        """Start fetching a HOST page; False if no staging frame could be
        claimed (frame pressure or an hbm_oom_on_promote fault) — the
        caller runs its demote/retry/preempt ladder and may re-request."""
        if self._pending and len(self._pending) >= self.pool.max_inflight:
            self._complete(self._pending.popleft())
        frame = self.pool.promote_begin(page)
        if frame is None:
            return False
        self._copy(page, frame)
        self._pending.append(page)
        self.n_issued += 1
        return True

    def _complete(self, page: int) -> None:
        if self._faults is not None and self._faults.hit("dma_timeout",
                                                         page):
            # the async fetch never landed: give the staging frame back,
            # then fall back to a synchronous claim+copy (not faultable —
            # this *is* the fallback) and count it
            self.pool.promote_abort(page)
            frame = self.pool.promote_begin(page, faultable=False)
            if frame is None:       # budget freed by the abort above
                raise RuntimeError(
                    f"sync fallback could not claim a frame for {page}")
            self._copy(page, frame)
            self.n_sync_fallback += 1
        self.pool.promote_complete(page)

    def drain(self) -> None:
        """Complete every outstanding fetch (barrier before the frame
        table is rebuilt for the sparse-attention pass)."""
        while self._pending:
            self._complete(self._pending.popleft())

    @property
    def in_flight(self) -> int:
        return len(self._pending)
