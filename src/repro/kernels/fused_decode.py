"""Pallas-TPU kernel: fused GQA-batched Loki decode (DESIGN.md §4, §7).

One grid step per (batch, kv-head) pair runs the *entire* Loki decode for
that KV group — approximate scoring, block top-k selection and exact sparse
attention — without any intermediate tensor ever returning to HBM:

  1. score stream: the leading-``d`` feature slice of each K̂ block is
     double-buffer DMA'd from HBM and hit with a (G, d) @ (d, bs) MXU tile —
     all G query heads of the GQA group score the block at once. Only the
     per-group block maximum survives, merged into a (1, nb) vector that
     the score loop carries (Mosaic cannot store a scalar into VMEM).
  2. selection: ``k_blocks`` iterations of argmax-and-suppress over that
     VMEM row (equivalent to ``lax.top_k`` incl. lower-index tie-breaking);
     winners land in SMEM. The (B·Hkv, S)-sized score tensor and the block
     maxima that the two-pass path materializes in HBM never exist here.
  3. exact pass: each winning K̂/V block is DMA'd once *per group* (not per
     head) and folded into a (G,)-wide online softmax; the (G, bs) @ (bs, D)
     value product again batches the group onto the MXU.

Window semantics match the token-granular reference (core/loki.py):
``local_window`` inflates the recency window's approximate scores by 1e4 so
those blocks always win selection; ``sliding_window`` masks positions older
than the window out of both the selection and the exact pass.

Inputs are the model-native layouts — no transposes or flattening copies:

  q_hat    (B, Hkv, G, D)   PCA-basis post-RoPE queries, grouped
  k_hat    (B, S, Hkv, D)   key cache in PCA basis (full D, Lemma 4.1)
  v        (B, S, Hkv, D)
  cur_len  (B,)             valid prefix length per slot (scalar-prefetched)
Output:
  out      (B, Hkv, G, D)

**Paged mode** (DESIGN.md §7): pass ``page_table (B, max_pages)`` and
``page_size``; the caches are then the serving engine's shared pools
``(Hkv, D, n_pages * page_size)`` (head-major, feature-major: a block is
a ``(W, bs)`` tile, see kernels/layout.py) with no batch dim, and
every block DMA resolves its HBM address through the scalar-prefetched
table —
``row = table[b, tok // page_size] * page_size + tok % page_size``. Pages
are a whole number of kernel blocks (``page_size % block_size == 0``), so
a block never straddles two pages and the kernel math is untouched: paged
decode is pure index indirection on the DMA source.

``select_blocks`` exposes phases 1-2 as a standalone kernel (scores still
never leave VMEM; only the tiny index rows do) for the two-kernel fallback
that feeds ``gather_attention.block_sparse_attention_grouped``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.layout import block_shape, cache_width, pv, qk
from repro.kernels.registry import kernel_entry
from repro.kernels.tuning import pad_lanes

NEG_INF = -1e30


def _score_and_select(ln, q_hat, kd_src, kd_buf, sem_kd, write_sel=None,
                      *, paged: bool, d: int, bs: int, nb: int, nb_pad: int,
                      k_blocks: int, scale: float, local_window: int = 0,
                      sliding_window: int = 0, k_scale_at=None):
    """Phases 1-2: stream d-slices, keep block maxima in a vector carried
    through the score loop, emit top-k.

    ``kd_src(j)`` returns the HBM ref slice holding block j's leading-d
    feature columns (contiguous caches address it directly; paged caches
    resolve it through the page table). Winners come out in lax.top_k
    order (descending score, ties to the lower index); an entry is ``-1``
    once the finite maxima are exhausted (fewer live blocks than
    k_blocks): argmax over an all-NEG_INF row would otherwise re-emit
    index 0 and double-count a live block in the attention pass.
    ``write_sel(t, idx)``, when given, receives each winner as an SMEM
    scalar; the return value is the same selection as a (1, k_blocks)
    int32 row.

    Mosaic cannot store a scalar into VMEM, so the block maxima never
    live in a scratch ref: each block's maximum is merged into the row by
    a lane select, and selection is max / min-of-matching-lanes reductions
    over that row."""
    qd = q_hat[:, :d] * scale                              # (G, d) f32

    def kd_copy(j, slot):
        return pltpu.make_async_copy(kd_src(j), kd_buf.at[slot],
                                     sem_kd.at[slot])

    if sliding_window:
        # window decode only streams the live window's blocks: positions
        # older than ln - sliding_window are masked out of selection anyway
        # (and under window page recycling their pages point at trash), so
        # their score DMAs are pure waste — start at the first block that
        # overlaps the window. Blocks never selected are never DMA'd in the
        # attention pass either, so a windowed decode touches
        # ceil(window/bs)+1 blocks of HBM, not smax/bs.
        lo = jnp.maximum(ln - sliding_window, 0) // bs
    else:
        lo = jnp.int32(0)
    kd_copy(lo, jax.lax.rem(lo, 2)).start()
    lanes = jax.lax.broadcasted_iota(jnp.int32, (1, nb_pad), 1)

    def score_blk(j, row):
        slot = jax.lax.rem(j, 2)

        @pl.when(j + 1 < nb)
        def _prefetch():
            kd_copy(j + 1, 1 - slot).start()

        kd_copy(j, slot).wait()
        kd = kd_buf[slot].astype(jnp.float32)       # (d, bs) | (bs, d)
        if k_scale_at is not None:
            # quantized layout: per-page scale rides in SMEM; the multiply
            # happens here, inside the DMA epilogue — HBM only ever moves
            # the narrow codes (DESIGN.md §10)
            kd = kd * k_scale_at(j)
        s = qk(qd, kd, paged)
        pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
        live = pos < ln
        if sliding_window:
            live &= pos >= ln - sliding_window
        s = jnp.where(live, s, NEG_INF)                    # (G, bs)
        if local_window:
            # recency inflation: force the local window into the selection
            recent = live & (pos >= ln - local_window)
            s = jnp.where(recent, s + jnp.float32(1e4), s)
        return jnp.where(lanes == j, jnp.max(s), row)

    row = jax.lax.fori_loop(lo, nb, score_blk,
                            jnp.full((1, nb_pad), NEG_INF, jnp.float32))
    sel_lanes = jax.lax.broadcasted_iota(jnp.int32, (1, k_blocks), 1)

    def pick(t, carry):
        row, sel = carry
        m = jnp.max(row)
        idx = jnp.min(jnp.where(row == m, lanes, nb_pad))
        idx = jnp.where(m > NEG_INF / 2, idx, -1)
        if write_sel is not None:
            write_sel(t, idx)
        return (jnp.where(lanes == idx, NEG_INF, row),
                jnp.where(sel_lanes == t, idx, sel))

    _, sel = jax.lax.fori_loop(
        0, k_blocks, pick, (row, jnp.full((1, k_blocks), -1, jnp.int32)))
    return sel


def _fused_kernel(*args, paged: bool, stacked: bool, quant: bool, ps: int,
                  d: int, bs: int, nb: int, nb_pad: int, k_blocks: int,
                  scale: float, g: int, kdim: int, dim: int,
                  local_window: int, sliding_window: int):
    # scalar prefetch: cur_len [, page_table [, layer]]
    n_pre = 1 + paged + stacked
    len_ref, pt_ref = args[0], args[1] if paged else None
    lyr = args[2][0] if stacked else None
    if quant:
        (q_ref, k_ref, v_ref, ksc_ref, vsc_ref, out_ref,
         kd_buf, kbuf, vbuf, sel, sem_kd, sem_kv) = args[n_pre:]
    else:
        (q_ref, k_ref, v_ref, out_ref,
         kd_buf, kbuf, vbuf, sel, sem_kd, sem_kv) = args[n_pre:]
    b = pl.program_id(0)
    h = pl.program_id(1)
    ln = len_ref[b]
    q = q_ref[0, 0].astype(jnp.float32)                    # (G, W)

    def k_slice(ref, blk, width):
        """HBM source for (logical) block ``blk``: direct for contiguous
        caches, through the page table for pooled ones (the paged
        index-indirection — blocks tile pages exactly), in layer ``lyr``
        of stacked pools."""
        tok = blk * bs
        if paged:
            row = pt_ref[b, tok // ps] * ps + tok % ps
            idx = (h, pl.ds(0, width), pl.ds(row, bs))
            return ref.at[(lyr,) + idx if stacked else idx]
        return ref.at[b, pl.ds(tok, bs), h, pl.ds(0, width)]

    def page_of(blk):
        # blocks tile pages exactly (ps % bs == 0), so one physical page —
        # hence one quantization scale — covers the whole DMA'd block
        return pt_ref[b, (blk * bs) // ps]

    def write_sel(t, idx):
        sel[t] = idx

    _score_and_select(ln, q, lambda j: k_slice(k_ref, j, d), kd_buf,
                      sem_kd, write_sel, paged=paged, d=d, bs=bs, nb=nb,
                      nb_pad=nb_pad,
                      k_blocks=k_blocks, scale=scale,
                      local_window=local_window,
                      sliding_window=sliding_window,
                      k_scale_at=(lambda j: ksc_ref[page_of(j), 0])
                      if quant else None)

    qs = q * scale                                         # (G, W)

    def att_blk(t, carry):
        m_prev, l_prev, acc = carry
        blk = sel[t]
        safe = jnp.maximum(blk, 0)

        @pl.when(blk >= 0)
        def _fetch():
            # -1 sentinel (exhausted selection): skip the DMA; the stale
            # buffer contents are fully masked below
            ck = pltpu.make_async_copy(k_slice(k_ref, safe, kdim), kbuf,
                                       sem_kv.at[0])
            cv = pltpu.make_async_copy(k_slice(v_ref, safe, dim), vbuf,
                                       sem_kv.at[1])
            ck.start()
            cv.start()
            ck.wait()
            cv.wait()

        kb = kbuf[...].astype(jnp.float32)
        if quant:
            kb = kb * ksc_ref[page_of(safe), 0]
        s = qk(qs, kb, paged)
        pos = safe * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
        live = (pos < ln) & (blk >= 0)                     # (1, bs)
        if sliding_window:
            live &= pos >= ln - sliding_window
        s = jnp.where(live, s, NEG_INF)                    # (G, bs)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        # guard: selected-but-dead block with an empty accumulator
        m_safe = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
        alpha = jnp.exp(jnp.minimum(m_prev - m_safe, 0.0)) \
            * (m_prev > NEG_INF / 2)
        p = jnp.exp(s - m_safe[:, None]) * live            # (G, bs)
        vb = vbuf[...].astype(jnp.float32)
        if quant:
            vb = vb * vsc_ref[page_of(safe), 0]
        acc = acc * alpha[:, None] + pv(p, vb, paged)
        return m_new, l_prev * alpha + jnp.sum(p, axis=1), acc

    m0 = jnp.full((g,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((g,), jnp.float32)
    a0 = jnp.zeros((g, dim), jnp.float32)
    _, l, acc = jax.lax.fori_loop(0, k_blocks, att_blk, (m0, l0, a0))
    out_ref[0, 0] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(
        out_ref.dtype)


def _paged_args(q_hat, k_hat, cur_len, page_table, page_size, block_size,
                layer=None):
    """Validate/resolve the (paged?, logical length) of a kernel call.
    ``layer``: the pools are stacked (L, Hkv, W, R) and the kernel reads
    this layer of them (one more scalar-prefetch operand)."""
    paged = page_table is not None
    if paged:
        assert page_size > 0 and page_size % block_size == 0, \
            "kernel blocks must tile pages exactly (page_size % bs == 0)"
        assert k_hat.ndim == 3 + (layer is not None), \
            "paged caches are pooled (Hkv, W, R), stacked (L, Hkv, W, R) " \
            "with a layer"
        s_len = page_table.shape[1] * page_size
        prefetch = (cur_len.astype(jnp.int32),
                    page_table.astype(jnp.int32))
        if layer is not None:
            prefetch += (jnp.reshape(layer, (1,)).astype(jnp.int32),)
    else:
        assert layer is None, "only paged pools stack layers"
        s_len = k_hat.shape[1]
        prefetch = (cur_len.astype(jnp.int32),)
    return paged, s_len, prefetch


@kernel_entry(scalar_prefetch=("cur_len", "page_table", "layer"),
              smem_sidecars=("k_scale", "v_scale"),
              paged_operand="page_table", grid="(B, Hkv)")
def fused_loki_decode(q_hat, k_hat, v, cur_len, *, d: int, k_blocks: int,
                      block_size: int = 128, scale=None,
                      local_window: int = 0, sliding_window: int = 0,
                      page_table=None, page_size: int = 0,
                      k_scale=None, v_scale=None, layer=None,
                      interpret: bool = False):
    """Single-pass Loki decode. (B,Hkv,G,W),(B,S,Hkv,W),(B,S,Hkv,D),(B,)
    -> (B,Hkv,G,D). Requires cur_len >= 1 per row (the decode invariant:
    the new token is already in the cache). With ``page_table``/``page_size``
    the caches are pooled (Hkv,W,R) and block DMAs resolve through the
    table; with ``layer`` too (a traced int32 scalar) they are the stacked
    (L,Hkv,W,R) pools of every layer and each DMA reads that layer, so no
    layer is ever sliced out of the stack. ``W <= D`` is the stored latent
    key width (rank-r PageLayout);
    queries arrive already projected/truncated to W, values stay full D.
    Quantized layouts pass ``k_scale``/``v_scale`` (n_pages,) f32 per-page
    scales (paged only); the kernel multiplies them in right after each
    block's DMA lands — dequantization never touches HBM."""
    b, n_kv, g, kdim = q_hat.shape
    bs = block_size
    paged, s_len, prefetch = _paged_args(q_hat, k_hat, cur_len, page_table,
                                         page_size, bs, layer)
    dim = cache_width(v, paged)
    assert cache_width(k_hat, paged) == kdim, \
        "q_hat/k_hat latent widths must match"
    quant = k_scale is not None
    assert not quant or (paged and v_scale is not None), \
        "per-page scales require paged caches"
    assert s_len % bs == 0, "cache length must be a multiple of block_size"
    nb = s_len // bs
    nb_pad = pad_lanes(nb)
    k_blocks = min(k_blocks, nb)
    scale = float(scale if scale is not None else dim ** -0.5)

    kernel = functools.partial(
        _fused_kernel, paged=paged, stacked=layer is not None, quant=quant,
        ps=page_size, d=d, bs=bs, nb=nb, nb_pad=nb_pad, k_blocks=k_blocks,
        scale=scale, g=g, kdim=kdim, dim=dim, local_window=local_window,
        sliding_window=sliding_window)
    io_map = lambda i, j, *prefetched: (i, j, 0, 0)
    in_specs = [
        pl.BlockSpec((1, 1, g, kdim), io_map),
        # the caches stay in HBM; the kernel DMAs d-slices and the
        # winning blocks itself
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    inputs = [q_hat, k_hat, v]
    if quant:
        # (n_pages, 1) f32 sidecars land whole in SMEM: one scalar read per
        # block resolves the page's scale (scalar prefetch is int32-only)
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
                # score-stream buffers, winner K̂ block, winner V block
                pltpu.VMEM((2, *block_shape(d, bs, paged)), k_hat.dtype),
                pltpu.VMEM(block_shape(kdim, bs, paged), k_hat.dtype),
                pltpu.VMEM(block_shape(dim, bs, paged), v.dtype),
                pltpu.SMEM((k_blocks,), jnp.int32),     # selected blocks
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, n_kv, g, dim), q_hat.dtype),
        name="fused_loki_decode",
        interpret=interpret,
    )(*prefetch, *inputs)
    return out


@kernel_entry(scalar_prefetch=("cur_len", "page_table"),
              smem_sidecars=("k_scale", "v_scale"),
              paged_operand="page_table", grid="(B, Hkv)")
def fused_exact_topk_decode(q_hat, k_hat, v, cur_len, *, k_blocks: int,
                            block_size: int = 128, scale=None,
                            sliding_window: int = 0,
                            page_table=None, page_size: int = 0,
                            k_scale=None, v_scale=None,
                            interpret: bool = False):
    """Single-pass exact-top-k decode: the ``exact_topk`` baseline's score
    pass and block top-k fused the same way the Loki kernel's approximate
    pass is — but the score stream reads the *full* stored key width, so
    selection is over exact scores (the quality-upper-bound baseline,
    Section 5). No recency inflation: the baseline has none.

    Shapes/paging/quantization follow ``fused_loki_decode`` exactly:
    (B,Hkv,G,W),(B,S,Hkv,W),(B,S,Hkv,D),(B,) -> (B,Hkv,G,D), pooled
    (Hkv,·,R) caches with ``page_table``/``page_size``, per-page f32
    scale sidecars for quantized layouts, cur_len >= 1 per row."""
    b, n_kv, g, kdim = q_hat.shape
    bs = block_size
    paged, s_len, prefetch = _paged_args(q_hat, k_hat, cur_len, page_table,
                                         page_size, bs)
    dim = cache_width(v, paged)
    assert cache_width(k_hat, paged) == kdim, \
        "q_hat/k_hat widths must match"
    quant = k_scale is not None
    assert not quant or (paged and v_scale is not None), \
        "per-page scales require paged caches"
    assert s_len % bs == 0, "cache length must be a multiple of block_size"
    nb = s_len // bs
    nb_pad = pad_lanes(nb)
    k_blocks = min(k_blocks, nb)
    scale = float(scale if scale is not None else dim ** -0.5)

    # d = kdim: the "approximate" stream IS the exact score pass
    kernel = functools.partial(
        _fused_kernel, paged=paged, stacked=False, quant=quant,
        ps=page_size, d=kdim, bs=bs, nb=nb, nb_pad=nb_pad,
        k_blocks=k_blocks, scale=scale, g=g, kdim=kdim, dim=dim,
        local_window=0, sliding_window=sliding_window)
    if paged:
        io_map = lambda i, j, ln, pt: (i, j, 0, 0)
    else:
        io_map = lambda i, j, ln: (i, j, 0, 0)
    in_specs = [
        pl.BlockSpec((1, 1, g, kdim), io_map),
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    inputs = [q_hat, k_hat, v]
    if quant:
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
                # full-width stream, winner K block, winner V block
                pltpu.VMEM((2, *block_shape(kdim, bs, paged)), k_hat.dtype),
                pltpu.VMEM(block_shape(kdim, bs, paged), k_hat.dtype),
                pltpu.VMEM(block_shape(dim, bs, paged), v.dtype),
                pltpu.SMEM((k_blocks,), jnp.int32),      # selected blocks
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, n_kv, g, dim), q_hat.dtype),
        name="fused_exact_topk_decode",
        interpret=interpret,
    )(*prefetch, *inputs)
    return out


def _select_kernel(*args, paged: bool, quant: bool, ps: int, d: int,
                   bs: int, nb: int, nb_pad: int, k_blocks: int,
                   scale: float, local_window: int, sliding_window: int):
    if quant:
        (len_ref, pt_ref, q_ref, k_ref, ksc_ref, out_ref,
         kd_buf, sem_kd) = args
    elif paged:
        (len_ref, pt_ref, q_ref, k_ref, out_ref, kd_buf, sem_kd) = args
    else:
        len_ref, q_ref, k_ref, out_ref, kd_buf, sem_kd = args
    b = pl.program_id(0)
    h = pl.program_id(1)
    ln = len_ref[b]
    q = q_ref[0, 0].astype(jnp.float32)                    # (G, W)

    def kd_src(j):
        tok = j * bs
        if paged:
            row = pt_ref[b, tok // ps] * ps + tok % ps
            return k_ref.at[h, pl.ds(0, d), pl.ds(row, bs)]
        return k_ref.at[b, pl.ds(tok, bs), h, pl.ds(0, d)]

    out_ref[0, 0] = _score_and_select(
        ln, q, kd_src, kd_buf, sem_kd, paged=paged,
                      d=d, bs=bs, nb=nb, nb_pad=nb_pad, k_blocks=k_blocks,
                      scale=scale, local_window=local_window,
                      sliding_window=sliding_window,
                      k_scale_at=(lambda j: ksc_ref[
                          pt_ref[b, (j * bs) // ps], 0]) if quant else None)


@kernel_entry(scalar_prefetch=("cur_len", "page_table"),
              smem_sidecars=("k_scale",),
              paged_operand="page_table", grid="(B, Hkv)")
def select_blocks(q_hat, k_hat, cur_len, *, d: int, k_blocks: int,
                  block_size: int = 128, scale=None, local_window: int = 0,
                  sliding_window: int = 0, page_table=None,
                  page_size: int = 0, k_scale=None,
                  interpret: bool = False):
    """Fused score+select: (B,Hkv,G,W),(B,S,Hkv,W),(B,) -> (B,Hkv,kb) int32
    block indices, group-shared; ``-1`` marks exhausted entries (fewer live
    blocks than kb). Scores live only in VMEM scratch. Paged caches resolve
    block reads through ``page_table`` exactly like ``fused_loki_decode``;
    quantized layouts pass the K pool's (n_pages,) ``k_scale`` sidecar."""
    b, n_kv, g, kdim = q_hat.shape
    bs = block_size
    paged, s_len, prefetch = _paged_args(q_hat, k_hat, cur_len, page_table,
                                         page_size, bs)
    quant = k_scale is not None
    assert not quant or paged, "per-page scales require paged caches"
    assert s_len % bs == 0, "cache length must be a multiple of block_size"
    nb = s_len // bs
    nb_pad = pad_lanes(nb)
    k_blocks = min(k_blocks, nb)
    scale = float(scale if scale is not None else kdim ** -0.5)

    kernel = functools.partial(
        _select_kernel, paged=paged, quant=quant, ps=page_size, d=d, bs=bs,
        nb=nb, nb_pad=nb_pad, k_blocks=k_blocks, scale=scale,
        local_window=local_window, sliding_window=sliding_window)
    if paged:
        q_map = lambda i, j, ln, pt: (i, j, 0, 0)
        o_map = lambda i, j, ln, pt: (i, j, 0, 0)
    else:
        q_map = lambda i, j, ln: (i, j, 0, 0)
        o_map = lambda i, j, ln: (i, j, 0, 0)
    in_specs = [
        pl.BlockSpec((1, 1, g, kdim), q_map),
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    inputs = [q_hat, k_hat]
    if quant:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        inputs.append(k_scale.astype(jnp.float32).reshape(-1, 1))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(b, n_kv),
            in_specs=in_specs,
            # one (1, k_blocks) row per (b, h): a singleton sublane dim
            # keeps the block's last two dims equal to the array's
            out_specs=pl.BlockSpec((1, 1, 1, k_blocks), o_map),
            scratch_shapes=[
                pltpu.VMEM((2, *block_shape(d, bs, paged)), k_hat.dtype),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, n_kv, 1, k_blocks), jnp.int32),
        name="select_blocks",
        interpret=interpret,
    )(*prefetch, *inputs)
    return out.reshape(b, n_kv, k_blocks)
