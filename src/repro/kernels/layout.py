"""Layout of the serving engine's paged K/V pools: the one module that
knows their axis order.

  pool  (Hkv, W, R)   per layer, no batch dim; R = n_pages * page_size

Pools are head-major and feature-major, rows (tokens) minor: one head's
page is the ``(W, page_size)`` tile ``pool[h, :, page * ps:(page + 1) * ps]``,
the leading-d Loki score slice of it is a sublane slice and a kernel block
of 128 tokens a lane-aligned slice. The TPU compiler refuses DMAs of one
head out of a head-minor ``(R, Hkv, W)`` pool in narrow dtypes and of a
d < 128 lane slice of a token-major row, so this is the layout in which
every paged kernel compiles (tests/test_tpu_compile.py). Stacked per-layer
pools ``(L, Hkv, W, R)`` keep the same three minor axes, so every helper
here also accepts them.

Contiguous caches of the dense engine keep the model-native token-major
``(B, S, Hkv, W)``; helpers taking ``paged`` cover both.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def pool_shape(n_kv: int, width: int, n_rows: int) -> Tuple[int, int, int]:
    """Shape of one layer's pool."""
    return (n_kv, width, n_rows)


def pool_width(pool) -> int:
    """Feature width W of a pool (stored key width, or D for values)."""
    return pool.shape[-2]


def layer_pool(pool, layer):
    """Layer ``layer`` (traced) of stacked pools, or ``pool`` itself where
    ``layer`` is None. Routes that cannot address the stack read this."""
    if layer is None:
        return pool
    return jax.lax.dynamic_index_in_dim(pool, layer, 0, keepdims=False)


def page_slice(pool, start, n: int):
    """Rows ``[start, start + n)`` of every head and feature: one page's
    tiles when ``start`` is a page boundary. ``start`` may be traced."""
    return jax.lax.dynamic_slice_in_dim(pool, start, n, axis=-1)


def put_rows(pool, rows, start):
    """Write ``rows`` (a ``page_slice`` of any length) at row ``start``."""
    return jax.lax.dynamic_update_slice_in_dim(
        pool, rows.astype(pool.dtype), start, axis=-1)


def take_rows(pool, heads, rows):
    """Rows of a pool, head by head: ``heads`` and ``rows`` broadcast to
    one shape S; returns ``S + (W,)``."""
    return pool[heads, :, rows]


def cache_width(cache, paged: bool) -> int:
    """Feature width of a pooled (``paged``) or contiguous cache."""
    return pool_width(cache) if paged else cache.shape[-1]


def cache_dims(k_cache, v_cache, page_table, page_size: int):
    """(smax, n_kv, kd, dim) of contiguous caches or of pools (one layer's
    or stacked) read through ``page_table``: logical length, KV heads, key
    and value widths."""
    if page_table is not None:
        return (page_table.shape[1] * page_size, k_cache.shape[-3],
                pool_width(k_cache), pool_width(v_cache))
    _, smax, n_kv, kd = k_cache.shape
    return smax, n_kv, kd, v_cache.shape[-1]


def block_shape(width: int, bs: int, paged: bool):
    """VMEM shape of one staged K/V block: ``(W, bs)`` out of a pool (a
    sublane slice of features by a 128-lane slice of tokens, both
    tile-aligned DMAs), ``(bs, W)`` rows out of a contiguous cache."""
    return (width, bs) if paged else (bs, width)


def qk(q, kb, paged: bool):
    """(G, W) queries against a staged K block -> (G, bs) f32 scores.

    Full float32 contraction (``HIGHEST``): Mosaic's default rounds f32
    operands to bf16, and block selection is a top-k over these scores,
    so it must not hang on that rounding (chip_smoke.py checks the paged
    Loki kernel against a float64 reference). The extra MXU passes cover
    one (G, W) x (W, bs) tile per streamed block."""
    dims = (((1,), (0,)), ((), ())) if paged else (((1,), (1,)), ((), ()))
    return jax.lax.dot_general(q, kb, dims, precision=HIGHEST,
                               preferred_element_type=jnp.float32)


def pv(p, vb, paged: bool):
    """(G, bs) softmax weights against a staged V block -> (G, D) f32."""
    dims = (((1,), (1,)), ((), ())) if paged else (((1,), (0,)), ((), ()))
    return jax.lax.dot_general(p, vb, dims, precision=HIGHEST,
                               preferred_element_type=jnp.float32)
