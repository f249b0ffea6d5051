"""Pallas-TPU kernel: feature-major approximate scores (DESIGN.md §3.1).

The token-major kernel (approx_scores.py) stages (bs, d) cache blocks into
VMEM; at small d (16/32) the d lanes of each (8,128) VMEM tile are mostly
empty — the slice wastes up to 7/8 of every tile's lane dimension.

This variant keeps the cache **feature-major**: K̂ᵀ with shape (D, S). The
d-slice is then a *sublane* slice (d ∈ {8..64} is a multiple of the 8-row
sublane granule) while the lane dimension stays a full ``bs``-token run —
every staged tile is dense. The dot becomes q̂[:d] · K̂ᵀ[:d, block], an
(1×d)·(d×bs) MXU matmul with hardware-aligned lanes.

The layout transform itself is free at cache-write time (the decode cache is
written one token-column at a time either way); ``ops.py`` exposes both
layouts and ``ref.py``'s oracle validates them against each other.

Inputs:
  q_hat    (BH, D)      query in PCA basis
  k_hat_T  (BH, D, S)   key cache in PCA basis, feature-major
  cur_len  (BH,)        valid prefix length per row (scalar-prefetched)
Outputs:
  block_max (BH, S/bs) f32 — identical semantics to the token-major kernel
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.registry import kernel_entry

NEG_INF = -1e30


def _kernel(len_ref, q_ref, kT_ref, out_ref, *, d: int, bs: int,
            scale: float):
    i = pl.program_id(0)
    j = pl.program_id(1)
    # staged blocks: q (1, d); kT (1, d, bs) — a sublane slice of the
    # feature-major cache; the bs-token lane dimension is fully dense
    q = q_ref[0].astype(jnp.float32)                      # (d,)
    kT = kT_ref[0].astype(jnp.float32)                    # (d, bs)
    s = jnp.dot(q, kT, preferred_element_type=jnp.float32) * scale  # (bs,)
    pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (bs,), 0)
    live = pos < len_ref[i]
    s = jnp.where(live, s, NEG_INF)
    out_ref[0, 0] = jnp.max(s)


@kernel_entry(scalar_prefetch=("cur_len",), grid="(BH, n_blocks)")
def block_max_scores_fm(q_hat, k_hat_T, cur_len, *, d: int,
                        block_size: int = 128, scale=None,
                        interpret: bool = False):
    """(BH,D),(BH,D,S),(BH,) -> (BH, S/bs) block maxima, feature-major."""
    bh, dim = q_hat.shape
    s_len = k_hat_T.shape[2]
    bs = block_size
    assert s_len % bs == 0, "cache length must be a multiple of block_size"
    assert d % 8 == 0, "feature-major slice must be sublane-aligned (8)"
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
                # sublane slice: feature-block index pinned to 0, width d
                pl.BlockSpec((1, d, bs), lambda i, j, ln: (i, 0, j)),
            ],
            out_specs=pl.BlockSpec((1, 1), lambda i, j, ln: (i, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((bh, nb), jnp.float32),
        name="block_max_scores_fm",
        interpret=interpret,
    )(cur_len.astype(jnp.int32), q_hat, k_hat_T)
    return out
