"""Backend selection for the Loki decode hot path (DESIGN.md §5).

One chokepoint decides, per decode step, which implementation of block-
granular Loki runs:

  backend="xla"    — the pure-jnp reference (``loki.loki_decode_block``),
                     paper-faithful per-head selection; lowers everywhere.
  backend="pallas" — the fused GQA-batched kernels (group-shared selection,
                     DESIGN.md §4), with ``kernels/tuning.py`` picking the
                     single-pass vs two-kernel variant and block size. Off
                     TPU the kernels run in interpret mode (how CI validates
                     them); on TPU they compile through Mosaic.
  backend="auto"   — "pallas" on TPU, "xla" elsewhere.

Off TPU (interpret mode), shapes no kernel plan covers fall back to jnp
*with the kernel's group-shared selection semantics*, so a given backend
choice is numerically consistent across shapes. On TPU a Pallas route
with no plan raises ``NoKernelPlan``: a served path must never leave the
kernels it was configured for without saying so.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import LokiConfig
from repro.core import attention as attn
from repro.core import baselines, loki
from repro.kernels import layout, ops, tuning
from repro.kernels.tuning import LANE, SUBLANE

BACKENDS = ("auto", "pallas", "xla")

# Backends disabled at runtime after a failure (graceful degradation,
# DESIGN.md §11): when a fused-Pallas decode aborts mid-serving, the
# engine reports it here and every subsequent ``resolve_backend`` routes
# to the XLA path instead — the process keeps serving on the slow-but-
# sound implementation rather than dying or flapping. Process-wide on
# purpose: a kernel that aborted once on this host will abort again.
_DISABLED: dict = {}          # backend -> reason


def disable_backend(backend: str, reason: str = "") -> None:
    """Mark a backend failed; resolve_backend avoids it from now on."""
    if backend not in BACKENDS or backend == "auto":
        raise ValueError(f"cannot disable backend {backend!r}")
    _DISABLED[backend] = reason or "runtime failure"


def enable_backend(backend: str) -> None:
    """Clear a failure mark (tests, or operator-driven recovery)."""
    _DISABLED.pop(backend, None)


def backend_disabled(backend: str) -> Optional[str]:
    """The failure reason if ``backend`` is disabled, else None."""
    return _DISABLED.get(backend)


class NoKernelPlan(ValueError):
    """A Pallas decode route found no kernel plan for its shape on TPU."""


def _interpret(interpret: Optional[bool]) -> bool:
    """Kernels run in interpret mode everywhere but on a TPU."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def _tpu_plan(plan, what: str, *, smax: int, page_size: int,
              widths, itemsize: int, interpret: bool):
    """The plan a compiled (TPU) Pallas route may use, or raise.

    Paged pools are feature-major (serving/paged_cache.py): a kernel block
    is a lane slice of the page, so it must be whole 128-lane tiles, and
    every feature slice it DMAs (the Loki score width d, the stored key
    width, the value width) a whole sublane granule of the pool's dtype.
    Interpret mode has no tiling, so off TPU the plan passes through and a
    missing plan keeps its jnp fallback."""
    if interpret:
        return plan
    sub = SUBLANE.get(itemsize, 8)
    why = None
    if plan is None:
        why = "no tiling fits"
    elif page_size and plan.block_size % LANE:
        why = f"block_size {plan.block_size} is not a multiple of {LANE}"
    elif any(w % sub for w in widths):
        why = f"feature widths {tuple(widths)} are not multiples of {sub}"
    if why:
        raise NoKernelPlan(
            f"{what}: no Pallas kernel plan on TPU for smax={smax} "
            f"page_size={page_size} widths={tuple(widths)} "
            f"itemsize={itemsize} ({why}); use backend='xla' for this shape")
    return plan


#: page-pool storage dtypes the TPU compiler refuses for the paged Pallas
#: kernels (v5e has no fp16 vector loads: "Invalid vector type for load");
#: fp32, bf16, int8 and fp8 pools compile (tests/test_tpu_compile.py)
TPU_REFUSED_POOL_DTYPES = (jnp.float16,)


def check_tpu_pool(dtype, platform: Optional[str] = None) -> None:
    """Raise at engine construction, not deep inside Mosaic, when a
    Pallas-served paged pool has a dtype the TPU kernels cannot read."""
    platform = platform or jax.default_backend()
    if platform == "tpu" and jnp.dtype(dtype) in TPU_REFUSED_POOL_DTYPES:
        raise NoKernelPlan(
            f"{jnp.dtype(dtype).name} page pools do not compile for the "
            "paged Pallas kernels on TPU (no fp16 vector loads); use a "
            "bf16, fp32, int8 or fp8 layout, or backend='xla'")


def resolve_backend(backend: str, platform: Optional[str] = None) -> str:
    """'auto' | 'pallas' | 'xla' -> the concrete backend for this host,
    skipping backends disabled by an earlier runtime failure (the XLA
    reference path is never disabled — it is the floor of the
    degradation ladder)."""
    if backend not in BACKENDS:
        raise ValueError(f"unknown loki backend {backend!r}; have {BACKENDS}")
    if backend == "auto":
        platform = platform or jax.default_backend()
        backend = "pallas" if platform == "tpu" else "xla"
    if backend == "pallas" and "pallas" in _DISABLED:
        return "xla"
    return backend


def _token_fallback(q_rope, k_hat_cache, v_cache, cur_len, proj, cfg,
                    *, sliding_window, logit_scale, page_table, page_size,
                    k_scale=None, v_scale=None):
    """Token-granular jnp path; gathers the logical view first when paged
    (dequantizing through the per-page scale sidecars when present)."""
    if page_table is not None:
        from repro.serving.paged_cache import gather_logical_dq
        k_hat_cache = gather_logical_dq(k_hat_cache, k_scale,
                                        page_table, page_size)
        v_cache = gather_logical_dq(v_cache, v_scale, page_table, page_size)
    return loki.loki_decode(q_rope, k_hat_cache, v_cache, cur_len, proj,
                            cfg, sliding_window=sliding_window,
                            logit_scale=logit_scale)


def loki_block_decode(q_rope, k_hat_cache, v_cache, cur_len, proj,
                      cfg: LokiConfig, *, sliding_window: int = 0,
                      logit_scale=None, page_table=None, page_size: int = 0,
                      k_scale=None, v_scale=None, layer=None,
                      interpret: Optional[bool] = None):
    """Block-granular Loki decode through the configured backend.

    q_rope (B,H,D); k_hat_cache (B,Smax,Hkv,W) with W <= D the stored
    latent key width (rank-r PageLayout truncation; W = D full basis);
    v_cache (B,Smax,Hkv,D); cur_len (B,) or scalar; proj (Hkv,D,D).
    Returns (B,H,D).

    ``sliding_window`` and ``cfg.local_window`` are honored identically on
    every backend (the token path's semantics). With ``page_table``/
    ``page_size`` the caches are the serving engine's shared page pools
    (Hkv,·,R): the Pallas kernels index their block DMAs through the table,
    the jnp paths gather the logical view through the same table. Quantized
    layouts pass the pools' per-page f32 ``k_scale``/``v_scale`` sidecars;
    every path dequantizes behind its DMA/gather, never in HBM.

    ``layer`` (traced int32): the pools are every layer's, stacked
    (L,Hkv,·,R), and this layer is read. The fused kernel addresses it
    inside the stack; every other route reads the layer's own pools
    (``layout.layer_pool``), which only the route taken keeps."""
    backend = resolve_backend(cfg.backend)
    paged = page_table is not None
    k_stack, v_stack = k_hat_cache, v_cache
    k_hat_cache = layout.layer_pool(k_hat_cache, layer)
    v_cache = layout.layer_pool(v_cache, layer)
    b, h = q_rope.shape[0], q_rope.shape[1]
    smax, n_kv, kd, dim = layout.cache_dims(k_hat_cache, v_cache,
                                            page_table, page_size)
    g = h // n_kv
    if logit_scale is None and kd < dim:
        # rank-r keys: the softmax temperature is set by the true head_dim,
        # not the truncated key width — pin it before any backend's default
        logit_scale = dim ** -0.5
    d = min(max(int(cfg.d_f * dim), 8), kd)
    itemsize = jnp.dtype(k_hat_cache.dtype).itemsize
    plan = tuning.plan_decode(smax, dim, g, d, cfg.block_size,
                              itemsize=itemsize)
    if paged and plan is not None and page_size % plan.block_size:
        # kernel DMA blocks must tile pages exactly; otherwise a block could
        # straddle two (non-adjacent) physical pages
        plan = None
    pargs = dict(page_table=page_table, page_size=page_size)
    qargs = dict(k_scale=k_scale, v_scale=v_scale)
    fb_args = dict(sliding_window=sliding_window, logit_scale=logit_scale,
                   page_table=page_table, page_size=page_size, **qargs)

    if backend == "xla":
        if smax % cfg.block_size:
            # short caches (smax < block_size etc.): adopt the planner's
            # dividing block size rather than tripping the reference assert
            if plan is None:
                return _token_fallback(q_rope, k_hat_cache, v_cache,
                                       cur_len, proj, cfg, **fb_args)
            cfg = dataclasses.replace(cfg, block_size=plan.block_size)
        return loki.loki_decode_block(q_rope, k_hat_cache, v_cache, cur_len,
                                      proj, cfg, logit_scale=logit_scale,
                                      sliding_window=sliding_window,
                                      **pargs, **qargs)
    interpret = _interpret(interpret)
    plan = _tpu_plan(plan, "loki_block decode", smax=smax,
                     page_size=page_size, widths=(d, kd, dim),
                     itemsize=itemsize, interpret=interpret)
    if plan is None:
        # no viable tiling (interpret mode only): jnp fallback, keeping the
        # kernel's group-shared selection when the block decomposition
        # exists at all
        if smax % cfg.block_size == 0 and (
                not paged or page_size % cfg.block_size == 0):
            return loki.loki_decode_block(q_rope, k_hat_cache, v_cache,
                                          cur_len, proj, cfg,
                                          logit_scale=logit_scale,
                                          sliding_window=sliding_window,
                                          group_select=True,
                                          **pargs, **qargs)
        return _token_fallback(q_rope, k_hat_cache, v_cache, cur_len, proj,
                               cfg, **fb_args)

    nb = smax // plan.block_size
    k_blocks = max(int(cfg.k_f * nb), 1)
    if sliding_window:
        # a sliding window overlaps at most ceil(w/bs)+1 blocks; selection
        # slots beyond that can only fill with -1 sentinels, so clamping
        # trims dead attention-pass iterations (the kernel's score stream
        # already skips blocks older than the window entirely)
        k_blocks = min(k_blocks,
                       -(-sliding_window // plan.block_size) + 1)
    q_hat = loki.grouped_queries(q_rope, proj, kd)
    cur = jnp.broadcast_to(jnp.asarray(cur_len, jnp.int32), (b,))
    kargs = dict(d=d, k_blocks=k_blocks, block_size=plan.block_size,
                 scale=logit_scale, local_window=cfg.local_window,
                 sliding_window=sliding_window, interpret=interpret,
                 **pargs, **qargs)
    if plan.variant == "fused":
        out = ops.loki_decode_fused(q_hat, k_stack, v_stack, cur,
                                    layer=layer, **kargs)
    else:
        out = ops.loki_decode_two_kernel(q_hat, k_hat_cache, v_cache, cur,
                                         **kargs)
    return out.reshape(b, h, dim)


def _gathered(k_cache, v_cache, page_table, page_size, k_scale, v_scale):
    """Logical (B,Smax,Hkv,·) views of possibly-pooled caches."""
    if page_table is None:
        return k_cache, v_cache
    from repro.serving.paged_cache import gather_logical_dq
    return (gather_logical_dq(k_cache, k_scale, page_table, page_size),
            gather_logical_dq(v_cache, v_scale, page_table, page_size))


def full_paged_decode(q, k_cache, v_cache, cur_len, *, backend: str = "auto",
                      block_size: int = 128, sliding_window: int = 0,
                      logit_scale=None, page_table=None, page_size: int = 0,
                      k_scale=None, v_scale=None,
                      interpret: Optional[bool] = None):
    """Full-attention decode through the configured backend.

    q (B,H,W) queries already in the storage basis (W <= D the stored key
    width); k_cache (B,Smax,Hkv,W) or pooled (Hkv,W,R) with
    ``page_table``; v_cache likewise at width D. Returns (B,H,D).

    backend="xla" is the bit-preserved reference (gather the logical view,
    ``attention.decode_full``); "pallas" streams live blocks through the
    page table (gather_attention.paged_full_decode) — same math, online
    softmax, so parity is within float tolerance. Off TPU, shapes with no
    viable tiling fall back to the jnp path; on TPU they raise."""
    backend = resolve_backend(backend)
    paged = page_table is not None
    b, h = q.shape[0], q.shape[1]
    smax, n_kv, kd, dim = layout.cache_dims(k_cache, v_cache, page_table,
                                            page_size)
    g = h // n_kv
    if logit_scale is None and kd < dim:
        logit_scale = dim ** -0.5

    plan = None
    if backend == "pallas":
        itemsize = jnp.dtype(k_cache.dtype).itemsize
        plan = tuning.plan_full_decode(smax, dim, g, kd, block_size,
                                       itemsize=itemsize)
        if plan is not None and paged and page_size % plan.block_size:
            plan = None
        interpret = _interpret(interpret)
        plan = _tpu_plan(plan, "full decode", smax=smax,
                         page_size=page_size, widths=(kd, dim),
                         itemsize=itemsize, interpret=interpret)
    if plan is None:
        kc, vc = _gathered(k_cache, v_cache, page_table, page_size,
                           k_scale, v_scale)
        return attn.decode_full(q, kc, vc, cur_len,
                                sliding_window=sliding_window,
                                logit_scale=logit_scale)
    qg = q.reshape(b, n_kv, g, kd)
    cur = jnp.broadcast_to(jnp.asarray(cur_len, jnp.int32), (b,))
    out = ops.full_decode(qg, k_cache, v_cache, cur,
                          block_size=plan.block_size, scale=logit_scale,
                          sliding_window=sliding_window,
                          page_table=page_table, page_size=page_size,
                          k_scale=k_scale, v_scale=v_scale,
                          interpret=interpret)
    return out.reshape(b, h, dim)


def exact_topk_paged_decode(q, k_cache, v_cache, cur_len, cfg: LokiConfig,
                            *, logit_scale=None, page_table=None,
                            page_size: int = 0, k_scale=None, v_scale=None,
                            interpret: Optional[bool] = None):
    """Exact-top-k decode through the configured backend.

    backend="xla" is the bit-preserved token-granular reference
    (``baselines.exact_topk_decode`` over the gathered logical view);
    "pallas" fuses the exact score pass with block top-k the same way the
    Loki kernel fuses its approximate pass (score width = full stored key
    width, group-shared selection — ``baselines.exact_topk_decode_block``
    is the jnp oracle and the fallback for kernel-shaped configurations
    no plan covers)."""
    backend = resolve_backend(cfg.backend)
    paged = page_table is not None
    b, h = q.shape[0], q.shape[1]
    smax, n_kv, kd, dim = layout.cache_dims(k_cache, v_cache, page_table,
                                            page_size)
    g = h // n_kv
    if logit_scale is None and kd < dim:
        logit_scale = dim ** -0.5
    pargs = dict(page_table=page_table, page_size=page_size,
                 k_scale=k_scale, v_scale=v_scale)

    if backend == "xla":
        kc, vc = _gathered(k_cache, v_cache, page_table, page_size,
                           k_scale, v_scale)
        return baselines.exact_topk_decode(q, kc, vc, cur_len, cfg,
                                           logit_scale=logit_scale)
    # the exact score pass reads the full stored width: plan with d = kd
    itemsize = jnp.dtype(k_cache.dtype).itemsize
    plan = tuning.plan_decode(smax, dim, g, kd, cfg.block_size,
                              itemsize=itemsize)
    if plan is not None and paged and page_size % plan.block_size:
        plan = None
    interpret = _interpret(interpret)
    plan = _tpu_plan(plan, "exact_topk decode", smax=smax,
                     page_size=page_size, widths=(kd, dim),
                     itemsize=itemsize, interpret=interpret)
    if plan is None:
        if smax % cfg.block_size == 0 and (
                not paged or page_size % cfg.block_size == 0):
            # kernel-shaped fallback: keep the block/group-shared semantics
            return baselines.exact_topk_decode_block(
                q, k_cache, v_cache, cur_len, cfg, logit_scale=logit_scale,
                group_select=True, **pargs)
        kc, vc = _gathered(k_cache, v_cache, page_table, page_size,
                           k_scale, v_scale)
        return baselines.exact_topk_decode(q, kc, vc, cur_len, cfg,
                                           logit_scale=logit_scale)

    nb = smax // plan.block_size
    k_blocks = max(int(cfg.k_f * nb), 1)
    qg = q.reshape(b, n_kv, g, kd)
    cur = jnp.broadcast_to(jnp.asarray(cur_len, jnp.int32), (b,))
    if plan.variant == "fused":
        out = ops.exact_topk_decode_fused(
            qg, k_cache, v_cache, cur, k_blocks=k_blocks,
            block_size=plan.block_size, scale=logit_scale,
            interpret=interpret, **pargs)
    else:
        # the two-kernel pair at d = kd scores exactly — select_blocks'
        # "approximate" stream reads the whole key, so this is the same
        # selection as the fused variant
        out = ops.loki_decode_two_kernel(
            qg, k_cache, v_cache, cur, d=kd, k_blocks=k_blocks,
            block_size=plan.block_size, scale=logit_scale,
            local_window=0, sliding_window=0, interpret=interpret, **pargs)
    return out.reshape(b, h, dim)


def loki_tiered_decode(q_rope, k_pool, v_pool, lat_pool, cur_len, proj,
                       cfg: LokiConfig, *, page_table, frame_table,
                       page_size: int, sliding_window: int = 0,
                       logit_scale=None, token_granular: bool = False,
                       interpret: Optional[bool] = None):
    """Tiered Loki decode (DESIGN.md §13) through the configured backend.

    The score/top-k pass reads only the always-resident latent-K sidecar
    ``lat_pool (Hkv, d, R_log)`` through the *logical* ``page_table``;
    exact attention reads winner rows from the frame-sized ``k_pool``/
    ``v_pool (Hkv, ·, R_dev)`` through ``frame_table``. Returns
    (out (B,H,D), winners (B, max_pages) bool).

    Routing mirrors ``loki_block_decode`` decision-for-decision (backend
    resolution, planner adoption of a dividing block size, group-shared
    selection on kernel-shaped fallbacks, token fallback otherwise) so a
    tiered engine selects exactly the pages its single-tier twin attends.
    On the Pallas path the two-kernel composition is used as-is: the
    select kernel's block DMAs index the sidecar via the logical table and
    the attention kernel's via the frame table — no kernel-body changes.
    The single-pass fused variant cannot split its score/attend reads
    across two pools, so tiered always runs the two-kernel pair: bit-
    identical to a single-tier two-kernel run, within float tolerance
    (accumulation order) of a fused one."""
    paged_common = dict(page_table=page_table, frame_table=frame_table,
                        page_size=page_size, sliding_window=sliding_window,
                        logit_scale=logit_scale)
    b, h = q_rope.shape[0], q_rope.shape[1]
    smax, n_kv, kd, dim = layout.cache_dims(k_pool, v_pool, page_table,
                                            page_size)
    g = h // n_kv
    if logit_scale is None and kd < dim:
        logit_scale = dim ** -0.5
        paged_common["logit_scale"] = logit_scale
    if token_granular:
        # the "loki" policy's paper-faithful token top-k (loki_decode)
        return loki.loki_decode_tiered(q_rope, k_pool, v_pool, lat_pool,
                                       cur_len, proj, cfg,
                                       token_granular=True, **paged_common)
    backend = resolve_backend(cfg.backend)
    d = min(max(int(cfg.d_f * dim), 8), kd)
    itemsize = jnp.dtype(k_pool.dtype).itemsize
    plan = tuning.plan_decode(smax, dim, g, d, cfg.block_size,
                              itemsize=itemsize)
    if plan is not None and page_size % plan.block_size:
        plan = None
    if backend == "xla":
        if smax % cfg.block_size:
            if plan is None:
                return loki.loki_decode_tiered(
                    q_rope, k_pool, v_pool, lat_pool, cur_len, proj, cfg,
                    token_granular=True, **paged_common)
            cfg = dataclasses.replace(cfg, block_size=plan.block_size)
        return loki.loki_decode_tiered(q_rope, k_pool, v_pool, lat_pool,
                                       cur_len, proj, cfg, **paged_common)
    interpret = _interpret(interpret)
    plan = _tpu_plan(plan, "tiered loki decode", smax=smax,
                     page_size=page_size, widths=(d, kd, dim),
                     itemsize=itemsize, interpret=interpret)
    if plan is None:
        if smax % cfg.block_size == 0 and page_size % cfg.block_size == 0:
            return loki.loki_decode_tiered(q_rope, k_pool, v_pool, lat_pool,
                                           cur_len, proj, cfg,
                                           group_select=True, **paged_common)
        return loki.loki_decode_tiered(q_rope, k_pool, v_pool, lat_pool,
                                       cur_len, proj, cfg,
                                       token_granular=True, **paged_common)

    bs = plan.block_size
    nb = smax // bs
    k_blocks = max(int(cfg.k_f * nb), 1)
    if sliding_window:
        k_blocks = min(k_blocks, -(-sliding_window // bs) + 1)
    q_hat = loki.grouped_queries(q_rope, proj, kd)
    cur = jnp.broadcast_to(jnp.asarray(cur_len, jnp.int32), (b,))
    # Two-kernel composition, one table per tier: the select kernel's
    # score DMAs walk the latent sidecar through the logical page table;
    # the attention kernel re-resolves the winning (logical) blocks
    # through the frame table, reading full-width rows from HBM frames.
    blk_idx = ops.select_blocks(q_hat[..., :d], lat_pool, cur, d=d,
                                k_blocks=k_blocks, block_size=bs,
                                scale=logit_scale,
                                local_window=cfg.local_window,
                                sliding_window=sliding_window,
                                page_table=page_table, page_size=page_size,
                                k_scale=None, interpret=interpret)
    out = ops.block_sparse_attention_grouped(
        q_hat, k_pool, v_pool, blk_idx, cur, block_size=bs,
        scale=logit_scale, sliding_window=sliding_window,
        page_table=frame_table, page_size=page_size,
        k_scale=None, v_scale=None, interpret=interpret)
    valid = blk_idx.reshape(b, -1) >= 0
    pages = jnp.where(valid, blk_idx.reshape(b, -1) * bs // page_size, 0)
    winners = jnp.zeros((b, page_table.shape[1]), bool)
    winners = winners.at[jnp.arange(b)[:, None], pages].max(valid)
    return out.reshape(b, h, dim), winners
