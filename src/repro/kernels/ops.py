"""Jit'd public wrappers around the Pallas kernels.

``loki_decode_attention`` is the full TPU decode pipeline of the paper:

  1. block_max_scores kernel      — approx scores from d PCA dims, reading
                                    only d/D of the cache bytes
  2. lax.top_k over block maxima  — S/bs-long selection (128× cheaper than
                                    the token-level torch.topk the paper
                                    identifies as a bottleneck, §6.4)
  3. block_sparse_attention kernel — exact attention over selected blocks,
                                    streamed via scalar-prefetch index maps

``interpret=True`` runs the kernel bodies in Python on CPU (how this repo
validates them); on TPU hardware the same calls compile through Mosaic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.approx_scores import block_max_scores
from repro.kernels.approx_scores_fm import block_max_scores_fm
from repro.kernels.flash_attention import flash_attention
from repro.kernels.fused_decode import (fused_exact_topk_decode,
                                        fused_loki_decode, select_blocks)
from repro.kernels.gather_attention import (block_sparse_attention,
                                            block_sparse_attention_grouped,
                                            paged_full_decode)


@functools.partial(jax.jit, static_argnames=("d", "k_blocks", "block_size",
                                             "interpret"))
def loki_decode_attention(q_hat, k_hat, v, cur_len, *, d: int,
                          k_blocks: int, block_size: int = 128,
                          interpret: bool = False):
    """Full Loki decode step over flattened (BH) rows.

    q_hat (BH,D) PCA-basis post-RoPE query; k_hat (BH,S,D) PCA-basis cache;
    v (BH,S,D); cur_len (BH,). Returns (BH,D).
    """
    dim = q_hat.shape[-1]
    scale = dim ** -0.5
    blk_max = block_max_scores(q_hat, k_hat, cur_len, d=d,
                               block_size=block_size, scale=scale,
                               interpret=interpret)
    _, blk_idx = jax.lax.top_k(blk_max, k_blocks)
    return block_sparse_attention(q_hat, k_hat, v, blk_idx, cur_len,
                                  block_size=block_size, scale=scale,
                                  interpret=interpret)


@functools.partial(jax.jit, static_argnames=("d", "k_blocks", "block_size",
                                             "interpret"))
def loki_decode_attention_fm(q_hat, k_hat_T, v, cur_len, *, d: int,
                             k_blocks: int, block_size: int = 128,
                             interpret: bool = False):
    """Feature-major scoring variant: the cache's K half is stored (BH,D,S)
    so the d-slice is sublane-aligned (DESIGN.md §3.1). The exact pass takes
    the token-major view (transpose is free for the gathered blocks)."""
    dim = q_hat.shape[-1]
    scale = dim ** -0.5
    blk_max = block_max_scores_fm(q_hat, k_hat_T, cur_len, d=d,
                                  block_size=block_size, scale=scale,
                                  interpret=interpret)
    _, blk_idx = jax.lax.top_k(blk_max, k_blocks)
    k_hat = jnp.swapaxes(k_hat_T, 1, 2)
    return block_sparse_attention(q_hat, k_hat, v, blk_idx, cur_len,
                                  block_size=block_size, scale=scale,
                                  interpret=interpret)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret"))
def flash(q, k, v, *, causal=True, block_q=128, block_k=128,
          interpret=False):
    return flash_attention(q, k, v, causal=causal, block_q=block_q,
                           block_k=block_k, interpret=interpret)


# ------------------------------------------------ GQA-batched decode paths

@functools.partial(jax.jit, static_argnames=("d", "k_blocks", "block_size",
                                             "scale", "local_window",
                                             "sliding_window", "page_size",
                                             "interpret"))
def loki_decode_fused(q_hat, k_hat, v, cur_len, *, d: int, k_blocks: int,
                      block_size: int = 128, scale=None,
                      local_window: int = 0, sliding_window: int = 0,
                      page_table=None, page_size: int = 0,
                      k_scale=None, v_scale=None, layer=None,
                      interpret: bool = False):
    """Single-pass fused decode (DESIGN.md §4): score, select and attend in
    one kernel; no score/selection tensor ever reaches HBM.

    q_hat (B,Hkv,G,W) grouped PCA-basis queries (W = stored latent K width,
    <= D); k_hat (B,S,Hkv,W) / v (B,S,Hkv,D) model-native caches (or pooled
    (Hkv,·,R) with ``page_table``, or every layer's pools stacked
    (L,Hkv,·,R) with ``page_table`` and ``layer``); cur_len (B,). Quantized
    PageLayouts pass the pools' (n_pages,) f32 ``k_scale``/``v_scale``
    sidecars (paged only). Returns (B,Hkv,G,D)."""
    return fused_loki_decode(q_hat, k_hat, v, cur_len, d=d,
                             k_blocks=k_blocks, block_size=block_size,
                             scale=scale, local_window=local_window,
                             sliding_window=sliding_window,
                             page_table=page_table, page_size=page_size,
                             k_scale=k_scale, v_scale=v_scale, layer=layer,
                             interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block_size", "scale",
                                             "sliding_window", "page_size",
                                             "interpret"))
def full_decode(q_hat, k_hat, v, cur_len, *, block_size: int = 128,
                scale=None, sliding_window: int = 0,
                page_table=None, page_size: int = 0,
                k_scale=None, v_scale=None, interpret: bool = False):
    """Streaming full-attention decode (the ``full`` policy's paged fast
    path): K/V stream block-by-block through the page table into a
    (G,)-wide online softmax, reading only the live prefix (or window).
    Shapes and scale sidecars follow ``loki_decode_fused``."""
    return paged_full_decode(q_hat, k_hat, v, cur_len,
                             block_size=block_size, scale=scale,
                             sliding_window=sliding_window,
                             page_table=page_table, page_size=page_size,
                             k_scale=k_scale, v_scale=v_scale,
                             interpret=interpret)


@functools.partial(jax.jit, static_argnames=("k_blocks", "block_size",
                                             "scale", "sliding_window",
                                             "page_size", "interpret"))
def exact_topk_decode_fused(q_hat, k_hat, v, cur_len, *, k_blocks: int,
                            block_size: int = 128, scale=None,
                            sliding_window: int = 0,
                            page_table=None, page_size: int = 0,
                            k_scale=None, v_scale=None,
                            interpret: bool = False):
    """Single-pass exact-top-k decode: full-width exact scores, block
    top-k and sparse attention in one kernel — ``exact_topk``'s analogue
    of ``loki_decode_fused`` (whose paging/quantization rules it shares)."""
    return fused_exact_topk_decode(q_hat, k_hat, v, cur_len,
                                   k_blocks=k_blocks, block_size=block_size,
                                   scale=scale,
                                   sliding_window=sliding_window,
                                   page_table=page_table,
                                   page_size=page_size,
                                   k_scale=k_scale, v_scale=v_scale,
                                   interpret=interpret)


@functools.partial(jax.jit, static_argnames=("d", "k_blocks", "block_size",
                                             "scale", "local_window",
                                             "sliding_window", "page_size",
                                             "interpret"))
def loki_decode_two_kernel(q_hat, k_hat, v, cur_len, *, d: int,
                           k_blocks: int, block_size: int = 128, scale=None,
                           local_window: int = 0, sliding_window: int = 0,
                           page_table=None, page_size: int = 0,
                           k_scale=None, v_scale=None,
                           interpret: bool = False):
    """Two-kernel fallback for shapes the single-pass kernel can't tile:
    fused score+select (scores stay in VMEM, only the (B,Hkv,kb) index rows
    cross HBM) feeding the GQA-batched sparse-attention kernel. Latent
    widths and per-page scale sidecars follow ``loki_decode_fused``."""
    blk_idx = select_blocks(q_hat, k_hat, cur_len, d=d, k_blocks=k_blocks,
                            block_size=block_size, scale=scale,
                            local_window=local_window,
                            sliding_window=sliding_window,
                            page_table=page_table, page_size=page_size,
                            k_scale=k_scale, interpret=interpret)
    return block_sparse_attention_grouped(q_hat, k_hat, v, blk_idx, cur_len,
                                          block_size=block_size, scale=scale,
                                          sliding_window=sliding_window,
                                          page_table=page_table,
                                          page_size=page_size,
                                          k_scale=k_scale, v_scale=v_scale,
                                          interpret=interpret)
