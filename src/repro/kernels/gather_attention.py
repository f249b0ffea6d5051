"""Pallas-TPU kernel: block-sparse top-k attention (Loki lines 8-9).

Given the indices of the selected KV blocks (from the approx-score block
top-k), run exact flash-style attention over ONLY those blocks. The sparse
HBM read happens in the grid itself: the BlockSpec ``index_map`` looks up the
prefetched block index, so the selected K̂/V blocks stream from HBM directly
into VMEM — no dense gather copy is ever materialized (the paper's Triton
kernels achieve this with register-level indexing; scalar-prefetched index
maps are the TPU-native equivalent, DESIGN.md §3).

Grid: (BH, n_sel). The n_sel axis is sequential per row — the online-softmax
accumulator lives in VMEM scratch across grid steps.

  q_hat    (BH, D)        PCA-basis query (full D -> exact, Lemma 4.1)
  k_hat    (BH, S, D)     PCA-basis key cache
  v        (BH, S, D)
  blk_idx  (BH, n_sel)    selected block indices (scalar-prefetched)
  cur_len  (BH,)          valid prefix length
Output:
  out      (BH, D)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.layout import block_shape, cache_width, pv, qk
from repro.kernels.registry import kernel_entry

NEG_INF = -1e30


def _kernel(blk_idx_ref, len_ref, q_ref, k_ref, v_ref, out_ref,
            m_ref, l_ref, acc_ref, *, bs: int, scale: float, n_sel: int):
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[0] = NEG_INF
        l_ref[0] = 0.0
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)                       # (D,)
    k = k_ref[0].astype(jnp.float32)                       # (bs, D)
    s = jnp.dot(k, q, preferred_element_type=jnp.float32) * scale

    blk = blk_idx_ref[i, j]
    pos = blk * bs + jax.lax.broadcasted_iota(jnp.int32, (bs,), 0)
    live = pos < len_ref[i]
    s = jnp.where(live, s, NEG_INF)

    m_prev = m_ref[0]
    m_new = jnp.maximum(m_prev, jnp.max(s))
    # guard: all-masked block with empty accumulator
    m_safe = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
    alpha = jnp.exp(jnp.minimum(m_prev - m_safe, 0.0)) * (m_prev > NEG_INF / 2)
    p = jnp.exp(s - m_safe) * live                         # (bs,)
    v_blk = v_ref[0].astype(jnp.float32)                   # (bs, D)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p, v_blk, preferred_element_type=jnp.float32)
    l_ref[0] = l_ref[0] * alpha + jnp.sum(p)
    m_ref[0] = m_new

    @pl.when(j == n_sel - 1)
    def _fini():
        out_ref[0] = (acc_ref[...] /
                      jnp.maximum(l_ref[0], 1e-30)).astype(out_ref.dtype)


@kernel_entry(scalar_prefetch=("blk_idx", "cur_len"), grid="(BH, n_sel)")
def block_sparse_attention(q_hat, k_hat, v, blk_idx, cur_len, *,
                           block_size: int = 128, scale=None,
                           interpret: bool = False):
    bh, dim = q_hat.shape
    s_len = k_hat.shape[1]
    bs = block_size
    n_sel = blk_idx.shape[1]
    assert s_len % bs == 0
    scale = float(scale if scale is not None else dim ** -0.5)

    kernel = functools.partial(_kernel, bs=bs, scale=scale, n_sel=n_sel)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(bh, n_sel),
            in_specs=[
                pl.BlockSpec((1, dim), lambda i, j, bi, ln: (i, 0)),
                # the sparse read: block index comes from the prefetched
                # selection, so only chosen blocks leave HBM
                pl.BlockSpec((1, bs, dim),
                             lambda i, j, bi, ln: (i, bi[i, j], 0)),
                pl.BlockSpec((1, bs, dim),
                             lambda i, j, bi, ln: (i, bi[i, j], 0)),
            ],
            out_specs=pl.BlockSpec((1, dim), lambda i, j, bi, ln: (i, 0)),
            scratch_shapes=[
                pltpu.VMEM((1,), jnp.float32),   # running max
                pltpu.VMEM((1,), jnp.float32),   # running denom
                pltpu.VMEM((dim,), jnp.float32), # accumulator
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((bh, dim), q_hat.dtype),
        name="block_sparse_attention",
        interpret=interpret,
    )(blk_idx.astype(jnp.int32), cur_len.astype(jnp.int32), q_hat, k_hat, v)
    return out


# ------------------------------------------- streaming full-decode variant

def _full_kernel(*args, paged: bool, quant: bool, ps: int, bs: int,
                 scale: float, g: int, kdim: int, dim: int,
                 sliding_window: int):
    if quant:
        (len_ref, pt_ref, q_ref, k_ref, v_ref, ksc_ref, vsc_ref, out_ref,
         kbuf, vbuf, sem_k, sem_v) = args
    elif paged:
        (len_ref, pt_ref, q_ref, k_ref, v_ref, out_ref,
         kbuf, vbuf, sem_k, sem_v) = args
    else:
        (len_ref, q_ref, k_ref, v_ref, out_ref,
         kbuf, vbuf, sem_k, sem_v) = args
    b = pl.program_id(0)
    h = pl.program_id(1)
    ln = len_ref[b]
    q = q_ref[0, 0].astype(jnp.float32) * scale            # (G, W)

    def k_slice(ref, blk, width):
        """HBM source for (logical) block ``blk``: direct for contiguous
        caches, through the page table for pooled ones."""
        tok = blk * bs
        if paged:
            row = pt_ref[b, tok // ps] * ps + tok % ps
            return ref.at[h, pl.ds(0, width), pl.ds(row, bs)]
        return ref.at[b, pl.ds(tok, bs), h, pl.ds(0, width)]

    def page_of(blk):
        return pt_ref[b, (blk * bs) // ps]

    def copies(j, slot):
        ck = pltpu.make_async_copy(k_slice(k_ref, j, kdim), kbuf.at[slot],
                                   sem_k.at[slot])
        cv = pltpu.make_async_copy(k_slice(v_ref, j, dim), vbuf.at[slot],
                                   sem_v.at[slot])
        return ck, cv

    if sliding_window:
        # only the window's blocks are live: under window page recycling
        # the older table entries point at trash anyway, so their DMAs
        # would be pure waste — start at the first overlapping block
        lo = jnp.maximum(ln - sliding_window, 0) // bs
    else:
        lo = jnp.int32(0)
    # stream live blocks only: the trip count follows cur_len, not smax —
    # this is the whole point versus gathering the logical view (decode
    # reads scale with the live prefix / window, never the table capacity)
    hi = (ln + bs - 1) // bs
    ck0, cv0 = copies(lo, jax.lax.rem(lo, 2))
    ck0.start()
    cv0.start()

    def att_blk(j, carry):
        m_prev, l_prev, acc = carry
        slot = jax.lax.rem(j, 2)

        @pl.when(j + 1 < hi)
        def _prefetch():
            ck, cv = copies(j + 1, 1 - slot)
            ck.start()
            cv.start()

        ck, cv = copies(j, slot)
        ck.wait()
        cv.wait()
        kb = kbuf[slot].astype(jnp.float32)
        if quant:
            # per-page scale from SMEM, applied in the DMA epilogue —
            # HBM only ever moves the narrow codes (DESIGN.md §10)
            kb = kb * ksc_ref[page_of(j), 0]
        s = qk(q, kb, paged)
        pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
        live = pos < ln                                    # (1, bs)
        if sliding_window:
            live &= pos >= ln - sliding_window
        s = jnp.where(live, s, NEG_INF)                    # (G, bs)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        # guard: an all-masked block with an empty accumulator
        m_safe = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
        alpha = jnp.exp(jnp.minimum(m_prev - m_safe, 0.0)) \
            * (m_prev > NEG_INF / 2)
        p = jnp.exp(s - m_safe[:, None]) * live            # (G, bs)
        vb = vbuf[slot].astype(jnp.float32)
        if quant:
            vb = vb * vsc_ref[page_of(j), 0]
        acc = acc * alpha[:, None] + pv(p, vb, paged)
        return m_new, l_prev * alpha + jnp.sum(p, axis=1), acc

    m0 = jnp.full((g,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((g,), jnp.float32)
    a0 = jnp.zeros((g, dim), jnp.float32)
    _, l, acc = jax.lax.fori_loop(lo, hi, att_blk, (m0, l0, a0))
    out_ref[0, 0] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(
        out_ref.dtype)


@kernel_entry(scalar_prefetch=("cur_len", "page_table"),
              smem_sidecars=("k_scale", "v_scale"),
              paged_operand="page_table", grid="(B, Hkv)")
def paged_full_decode(q_hat, k_hat, v, cur_len, *, block_size: int = 128,
                      scale=None, sliding_window: int = 0,
                      page_table=None, page_size: int = 0,
                      k_scale=None, v_scale=None,
                      interpret: bool = False):
    """Streaming full-attention decode over live blocks only.

    The ``full`` policy's paged fast path: instead of gathering the whole
    logical KV view per layer (the jnp route), one grid step per
    (batch, kv-head) double-buffer DMAs K/V block-by-block through the
    scalar-prefetched page table and folds each block into a (G,)-wide
    online softmax. The block loop runs ``ceil(cur_len/bs)`` iterations
    (from the window's first block under ``sliding_window``), so HBM
    traffic follows the *live* prefix, never the table capacity.

      q_hat    (B, Hkv, G, W)  grouped queries, already in the storage
                               basis (W <= D: rank-r latent keys)
      k_hat    (B, S, Hkv, W)  or pooled (Hkv, W, R) with ``page_table``
      v        (B, S, Hkv, D)  or pooled (Hkv, D, R)
      cur_len  (B,)
    Output:    (B, Hkv, G, D)

    Requires cur_len >= 1 per row (the decode invariant). Quantized
    layouts pass the pools' (n_pages,) f32 ``k_scale``/``v_scale``
    sidecars (paged only); dequantization happens in the DMA epilogue."""
    b, n_kv, g, kdim = q_hat.shape
    bs = block_size
    paged = page_table is not None
    dim = cache_width(v, paged)
    assert cache_width(k_hat, paged) == kdim, "q_hat/k_hat widths must match"
    if paged:
        assert page_size > 0 and page_size % bs == 0, \
            "kernel blocks must tile pages exactly (page_size % bs == 0)"
        assert k_hat.ndim == 3, "paged caches are pooled (Hkv, W, R)"
        s_len = page_table.shape[1] * page_size
        prefetch = (cur_len.astype(jnp.int32), page_table.astype(jnp.int32))
    else:
        s_len = k_hat.shape[1]
        prefetch = (cur_len.astype(jnp.int32),)
    quant = k_scale is not None
    assert not quant or (paged and v_scale is not None), \
        "per-page scales require paged caches"
    assert s_len % bs == 0, "cache length must be a multiple of block_size"
    scale = float(scale if scale is not None else dim ** -0.5)

    kernel = functools.partial(
        _full_kernel, paged=paged, quant=quant, ps=page_size, bs=bs,
        scale=scale, g=g, kdim=kdim, dim=dim, sliding_window=sliding_window)
    if paged:
        io_map = lambda i, j, ln, pt: (i, j, 0, 0)
    else:
        io_map = lambda i, j, ln: (i, j, 0, 0)
    in_specs = [
        pl.BlockSpec((1, 1, g, kdim), io_map),
        # caches stay in HBM; the kernel DMAs live blocks itself
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    inputs = [q_hat, k_hat, v]
    if quant:
        # (n_pages, 1) f32 sidecars land whole in SMEM (scalar prefetch
        # itself is int32-only)
        in_specs += [pl.BlockSpec(memory_space=pltpu.SMEM),
                     pl.BlockSpec(memory_space=pltpu.SMEM)]
        inputs += [k_scale.astype(jnp.float32).reshape(-1, 1),
                   v_scale.astype(jnp.float32).reshape(-1, 1)]
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(b, n_kv),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, 1, g, dim), io_map),
            scratch_shapes=[
                # K and V stream buffers
                pltpu.VMEM((2, *block_shape(kdim, bs, paged)), k_hat.dtype),
                pltpu.VMEM((2, *block_shape(dim, bs, paged)), v.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, n_kv, g, dim), q_hat.dtype),
        name="paged_full_decode",
        interpret=interpret,
    )(*prefetch, *inputs)
    return out


# ------------------------------------------------- GQA-batched variant

def _gkernel(*args, paged: bool, quant: bool, bs: int, bpp: int,
             scale: float, n_sel: int, sliding_window: int):
    if quant:
        (blk_idx_ref, len_ref, pt_ref, q_ref, k_ref, v_ref,
         ksc_ref, vsc_ref, out_ref, m_ref, l_ref, acc_ref) = args
    elif paged:
        (blk_idx_ref, len_ref, pt_ref, q_ref, k_ref, v_ref, out_ref,
         m_ref, l_ref, acc_ref) = args
    else:
        (blk_idx_ref, len_ref, q_ref, k_ref, v_ref, out_ref,
         m_ref, l_ref, acc_ref) = args
    b = pl.program_id(0)
    h = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32) * scale            # (G, W)
    # paged pools are feature-major with no batch dim: the k/v block
    # arrives as (1, W, bs)
    k = (k_ref[0] if paged else k_ref[0, :, 0]).astype(jnp.float32)
    if quant:
        # one physical page per staged block (bs divides page_size): its
        # SMEM-resident scale dequantizes the codes right after the DMA
        page = pt_ref[b, jnp.maximum(blk_idx_ref[b, h, j], 0) // bpp]
        k = k * ksc_ref[page, 0]
    s = qk(q, k, paged)                                    # (G, bs)

    blk = blk_idx_ref[b, h, j]
    pos = jnp.maximum(blk, 0) * bs + jax.lax.broadcasted_iota(
        jnp.int32, (1, bs), 1)
    # blk == -1: selection exhausted (fewer live blocks than n_sel) — the
    # staged block is a clamped re-read and must contribute nothing
    live = (pos < len_ref[b]) & (blk >= 0)                 # (1, bs)
    if sliding_window:
        live &= pos >= len_ref[b] - sliding_window
    s = jnp.where(live, s, NEG_INF)

    m_prev = m_ref[...]                                    # (G,)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    m_safe = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
    alpha = jnp.exp(jnp.minimum(m_prev - m_safe, 0.0)) * (m_prev > NEG_INF / 2)
    p = jnp.exp(s - m_safe[:, None]) * live                # (G, bs)
    v_blk = (v_ref[0] if paged else v_ref[0, :, 0]).astype(jnp.float32)
    if quant:
        v_blk = v_blk * vsc_ref[page, 0]
    acc_ref[...] = acc_ref[...] * alpha[:, None] + pv(p, v_blk, paged)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)
    m_ref[...] = m_new

    @pl.when(j == n_sel - 1)
    def _fini():
        out_ref[0, 0] = (acc_ref[...] /
                         jnp.maximum(l_ref[...], 1e-30)[:, None]).astype(
            out_ref.dtype)


@kernel_entry(scalar_prefetch=("blk_idx", "cur_len", "page_table"),
              smem_sidecars=("k_scale", "v_scale"),
              paged_operand="page_table", grid="(B, Hkv, n_sel)")
def block_sparse_attention_grouped(q_hat, k_hat, v, blk_idx, cur_len, *,
                                   block_size: int = 128, scale=None,
                                   sliding_window: int = 0,
                                   page_table=None, page_size: int = 0,
                                   k_scale=None, v_scale=None,
                                   interpret: bool = False):
    """GQA-batched sparse attention over a *group-shared* block selection.

    All G query heads of a KV group ride one grid row, so each selected
    K̂/V block is streamed from HBM once per group and the score/value
    products are (G, D) @ (D, bs) / (G, bs) @ (bs, D) MXU tiles instead of
    G matrix-vector products (DESIGN.md §4). Operates on the model-native
    cache layout — no transpose copies.

      q_hat    (B, Hkv, G, D)    PCA-basis grouped queries
      k_hat    (B, S, Hkv, D)    PCA-basis key cache
      v        (B, S, Hkv, D)
      blk_idx  (B, Hkv, n_sel)   group-shared selected blocks (prefetched)
      cur_len  (B,)
    Output:    (B, Hkv, G, D)

    With ``page_table``/``page_size`` the caches are pooled
    (Hkv, D, n_pages * page_size) and the selected *logical* block indices
    resolve to physical blocks inside the BlockSpec index map — the sparse
    paged read costs exactly one extra SMEM lookup per block (DESIGN.md §7).
    """
    b, n_kv, g, kdim = q_hat.shape
    bs = block_size
    n_sel = blk_idx.shape[-1]
    paged = page_table is not None
    dim = cache_width(v, paged)
    assert cache_width(k_hat, paged) == kdim, \
        "q_hat/k_hat latent widths must match"
    quant = k_scale is not None
    assert not quant or (paged and v_scale is not None), \
        "per-page scales require paged caches"
    bpp = 0
    if paged:
        assert page_size > 0 and page_size % bs == 0, \
            "kernel blocks must tile pages exactly"
        assert k_hat.ndim == 3, "paged caches are pooled (Hkv, W, R)"
        bpp = page_size // bs                 # blocks per page
        assert (page_table.shape[1] * page_size) % bs == 0
    else:
        assert k_hat.shape[1] % bs == 0
    scale = float(scale if scale is not None else dim ** -0.5)

    kernel = functools.partial(_gkernel, paged=paged, quant=quant, bs=bs,
                               bpp=bpp, scale=scale, n_sel=n_sel,
                               sliding_window=sliding_window)
    if paged:
        def kv_map(i, h, j, bi, ln, pt):
            # clamp the -1 "exhausted" sentinel, then translate the logical
            # block to its physical home: page_table picks the page, the
            # block's offset inside the page is preserved
            blk = jnp.maximum(bi[i, h, j], 0)
            return (h, 0, pt[i, blk // bpp] * bpp + blk % bpp)
        in_specs = [
            pl.BlockSpec((1, 1, g, kdim),
                         lambda i, h, j, bi, ln, pt: (i, h, 0, 0)),
            pl.BlockSpec((1, kdim, bs), kv_map),
            pl.BlockSpec((1, dim, bs), kv_map),
        ]
        o_map = lambda i, h, j, bi, ln, pt: (i, h, 0, 0)
        prefetch = (blk_idx.astype(jnp.int32), cur_len.astype(jnp.int32),
                    page_table.astype(jnp.int32))
    else:
        def kv_map(i, h, j, bi, ln):
            # clamp the -1 "exhausted" sentinel to a safe block address;
            # the kernel masks its contribution to zero
            return (i, jnp.maximum(bi[i, h, j], 0), h, 0)
        in_specs = [
            pl.BlockSpec((1, 1, g, kdim),
                         lambda i, h, j, bi, ln: (i, h, 0, 0)),
            pl.BlockSpec((1, bs, 1, kdim), kv_map),
            pl.BlockSpec((1, bs, 1, dim), kv_map),
        ]
        o_map = lambda i, h, j, bi, ln: (i, h, 0, 0)
        prefetch = (blk_idx.astype(jnp.int32), cur_len.astype(jnp.int32))
    inputs = [q_hat, k_hat, v]
    if quant:
        # per-page f32 scale sidecars live whole in SMEM beside the table
        in_specs += [pl.BlockSpec(memory_space=pltpu.SMEM),
                     pl.BlockSpec(memory_space=pltpu.SMEM)]
        inputs += [k_scale.astype(jnp.float32).reshape(-1, 1),
                   v_scale.astype(jnp.float32).reshape(-1, 1)]
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(b, n_kv, n_sel),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, 1, g, dim), o_map),
            scratch_shapes=[
                pltpu.VMEM((g,), jnp.float32),       # running max per head
                pltpu.VMEM((g,), jnp.float32),       # running denom per head
                pltpu.VMEM((g, dim), jnp.float32),   # accumulator
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, n_kv, g, dim), q_hat.dtype),
        name="block_sparse_attention_grouped",
        interpret=interpret,
    )(*prefetch, *inputs)
    return out
