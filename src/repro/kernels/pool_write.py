"""Pallas-TPU kernel: the decode step's K/V row writes into a stacked page
pool, in place.

  pool  (L, Hkv, W, R)   every layer's pool, stacked (kernels/layout.py)
  new   (B, Hkv, W)      one token row per slot
  rows  (B,)             its physical pool row (scalar-prefetched)
  layer ()               the layer written (scalar-prefetched)

The pool stays in HBM (``pl.ANY``) and is aliased input to output, so
under a donated cache the write touches only the rows it changes: per
slot, the 128-row tile that holds its row is DMA'd into VMEM, the row's
lane is overwritten and the tile DMA'd back. Because the Pallas operand
pins the pool's feature-major layout, XLA has no reason to relayout,
copy or restack the pool around the decode step's layer loop.

Slots run one after another (the grid is sequential): idle slots all
point at the trash page, so two slots may write the same row, and the
last one wins, as in the jnp scatter this replaces. Rows outside
``[0, R)`` are skipped (the scatter's ``mode="drop"``).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.registry import kernel_entry
from repro.kernels.tuning import LANE


def _write_kernel(layer_ref, rows_ref, new_ref, _pool_in, pool_ref, buf, sem,
                  *, tile: int, n_rows: int):
    i = pl.program_id(0)
    row = rows_ref[i]

    @pl.when((row >= 0) & (row < n_rows))
    def _write():
        start = pl.multiple_of((row // tile) * tile, tile)
        dst = pool_ref.at[layer_ref[0], :, :, pl.ds(start, tile)]
        cp = pltpu.make_async_copy(dst, buf, sem.at[0])
        cp.start()
        cp.wait()
        # slot i's row, moved from lane i of the (Hkv, W, B) operand to a
        # (Hkv, W, 1) column: a max against -inf everywhere else returns
        # any value (signed zeros, inf, nan) as it was
        new = new_ref[...].astype(jnp.float32)
        slot = jax.lax.broadcasted_iota(jnp.int32, new.shape, 2)
        col = jnp.max(jnp.where(slot == i, new, -jnp.inf), axis=2,
                      keepdims=True)
        lane = jax.lax.broadcasted_iota(jnp.int32, buf.shape, 2)
        buf[...] = jnp.where(lane == row - start, col,
                             buf[...].astype(jnp.float32)).astype(buf.dtype)
        cp = pltpu.make_async_copy(buf, dst, sem.at[0])
        cp.start()
        cp.wait()


@kernel_entry(scalar_prefetch=("layer", "rows"), grid="(B,)")
def pool_write_rows(pool, new, layer, rows, *, interpret: bool = False):
    """(L,Hkv,W,R) pool, (B,Hkv,W) rows, layer (), rows (B,) -> the pool
    with ``new[b]`` at row ``rows[b]`` of layer ``layer``, written in place
    (the pool is aliased to the output). Rows outside ``[0, R)`` are
    dropped. A float pool's rows land bit for bit."""
    n_kv, width, n_rows = pool.shape[1:]
    b = new.shape[0]
    # the tile every row write moves: one 128-lane tile of the pool (a
    # pool of whole pages of 128 rows always has them); interpret mode
    # also takes the smaller pools of the CPU tests
    tile = math.gcd(n_rows, LANE)
    kernel = functools.partial(_write_kernel, tile=tile, n_rows=n_rows)
    # slots ride the lane axis, so that the kernel moves one slot's row
    # into a column of the feature-major tile with a lane reduction
    new_t = jnp.transpose(new.astype(pool.dtype), (1, 2, 0))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b,),
            in_specs=[pl.BlockSpec((n_kv, width, b),
                                   lambda i, lyr, rw: (0, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.VMEM((n_kv, width, tile), pool.dtype),
                            pltpu.SemaphoreType.DMA((1,))],
        ),
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        # operands: layer, rows, new_t, pool -> the pool is output 0
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="pool_write_rows",
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), rows.astype(jnp.int32),
      new_t, pool)
