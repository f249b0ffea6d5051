"""Pallas-TPU kernel: Loki approximate scores -> per-block maxima.

Computes, for each (batch×head) row and each sequence block of the KV cache,
``max_{s in block} q̂[:d] · K̂[s,:d]`` — the statistic the block top-k
selection ranks on. Only the **leading d feature columns** of the cache ever
leave HBM: the BlockSpec's index_map pins the feature-dim block index to 0
with block width d, which is the TPU realization of the paper's "contiguous
PCA slice beats SparQ's scattered column gather" insight (DESIGN.md §3).

Also emits the masked score block itself when ``return_scores`` (used by the
token-granular variant and tests).

Inputs (already flattened over batch and query heads; GQA dedup upstream):
  q_hat   (BH, D)      query in PCA basis (post-RoPE, rotated)
  k_hat   (BH, S, D)   key cache in PCA basis
  cur_len (BH,)        valid prefix length per row (scalar-prefetched)
Outputs:
  block_max (BH, S/bs) f32
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.registry import kernel_entry

NEG_INF = -1e30


def _kernel(len_ref, q_ref, k_ref, out_ref, *, d: int, bs: int,
            scale: float):
    i = pl.program_id(0)
    j = pl.program_id(1)
    # blocks are (1, d) / (1, bs, d): only the first d feature columns of
    # the cache are ever staged into VMEM
    q = q_ref[0].astype(jnp.float32)                      # (d,)
    k = k_ref[0].astype(jnp.float32)                      # (bs, d)
    s = jnp.dot(k, q, preferred_element_type=jnp.float32) * scale
    pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (bs,), 0)
    live = pos < len_ref[i]
    s = jnp.where(live, s, NEG_INF)
    out_ref[0, 0] = jnp.max(s)


@kernel_entry(scalar_prefetch=("cur_len",), grid="(BH, n_blocks)")
def block_max_scores(q_hat, k_hat, cur_len, *, d: int, block_size: int = 128,
                     scale=None, interpret: bool = False):
    """(BH,D),(BH,S,D),(BH,) -> (BH, S/bs) block maxima of approx scores."""
    bh, dim = q_hat.shape
    s_len = k_hat.shape[1]
    bs = block_size
    assert s_len % bs == 0, "cache length must be a multiple of block_size"
    nb = s_len // bs
    scale = float(scale if scale is not None else dim ** -0.5)

    grid = (bh, nb)
    out = pl.pallas_call(
        functools.partial(_kernel, d=d, bs=bs, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, d), lambda i, j, ln: (i, 0)),
                pl.BlockSpec((1, bs, d), lambda i, j, ln: (i, j, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1), lambda i, j, ln: (i, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((bh, nb), jnp.float32),
        name="block_max_scores",
        interpret=interpret,
    )(cur_len.astype(jnp.int32), q_hat, k_hat)
    return out
