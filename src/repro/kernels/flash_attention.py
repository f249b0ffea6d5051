"""Pallas-TPU kernel: causal flash attention (prefill / train).

Standard online-softmax tiling: grid (BH, n_q_blocks, n_kv_blocks) with the
kv axis sequential and the accumulator in VMEM scratch. Fully-masked
(non-causal) kv blocks are skipped arithmetically (alpha=1, p=0) — on real
hardware the j > i blocks are pruned by the grid's causal upper bound per i,
which we express by masking; Mosaic hoists the no-op blocks.

The paper defers FlashAttention integration to future work (§7 Limitations);
this kernel plus gather_attention.py is that integration: prefill uses dense
flash, decode uses block-sparse flash over Loki's selection.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.registry import kernel_entry

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, out_ref, m_ref, l_ref, acc_ref, *,
            bq: int, bk: int, scale: float, n_kv: int, causal: bool):
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32) * scale               # (bq, D)
    k = k_ref[0].astype(jnp.float32)                       # (bk, D)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # (bq, bk)

    if causal:
        qpos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(qpos >= kpos, s, NEG_INF)

    m_prev = m_ref[...]                                    # (bq,)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    m_safe = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
    alpha = jnp.exp(jnp.minimum(m_prev - m_safe, 0.0)) * (m_prev > NEG_INF / 2)
    p = jnp.exp(s - m_safe[:, None])
    p = jnp.where(s <= NEG_INF / 2, 0.0, p)                # (bq, bk)
    v_blk = v_ref[0].astype(jnp.float32)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jnp.dot(
        p, v_blk, preferred_element_type=jnp.float32)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)
    m_ref[...] = m_new

    @pl.when(j == n_kv - 1)
    def _fini():
        out_ref[0] = (acc_ref[...] /
                      jnp.maximum(l_ref[...], 1e-30)[:, None]
                      ).astype(out_ref.dtype)


@kernel_entry(grid="(BH, n_q, n_kv)")
def flash_attention(q, k, v, *, block_q: int = 128, block_k: int = 128,
                    causal: bool = True, scale=None,
                    interpret: bool = False):
    """q (BH, Sq, D); k, v (BH, Sk, D) -> (BH, Sq, D)."""
    bh, sq, dim = q.shape
    sk = k.shape[1]
    bq, bk = min(block_q, sq), min(block_k, sk)
    assert sq % bq == 0 and sk % bk == 0
    scale = float(scale if scale is not None else dim ** -0.5)
    nq, nk = sq // bq, sk // bk

    kernel = functools.partial(_kernel, bq=bq, bk=bk, scale=scale,
                               n_kv=nk, causal=causal)
    return pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, dim), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, dim), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, dim), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, dim), lambda b, i, j: (b, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, dim), jnp.float32),
        ],
        out_shape=jax.ShapeDtypeStruct((bh, sq, dim), q.dtype),
        name="flash_attention",
        interpret=interpret,
    )(q, k, v)
