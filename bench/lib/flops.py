"""Operations and bytes that the served algorithm needs, computed from
shapes. These are the numerators of every utilization and roofline share
the benchmark reports; they count the work the algorithm asks for, never
what an implementation happens to do (padding, dead blocks, recompute).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Iterable, Tuple

POOL_ITEMSIZE = {"fp32": 4, "bf16": 2, "fp16": 2, "int8": 1, "fp8": 1}


@dataclasses.dataclass(frozen=True)
class Dims:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    block: int          # Loki block = page, in tokens
    d_slice: int        # leading PCA dims scored by the approximate pass
    k_blocks: int       # blocks attended exactly per (slot, kv head)
    pool_itemsize: int  # bytes per stored K/V element

    @property
    def group(self) -> int:
        return self.heads // self.kv_heads


def dims(cfg: dict, smax: int) -> Dims:
    """Dims of a configuration file (Hugging Face keys plus ``serving``)
    served with a logical context of ``smax`` tokens."""
    sv = cfg["serving"]
    heads = cfg["num_attention_heads"]
    head_dim = cfg["hidden_size"] // heads
    block = sv["page_size"]
    return Dims(
        layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        heads=heads, kv_heads=cfg["num_key_value_heads"],
        head_dim=head_dim, d_ff=cfg["intermediate_size"],
        vocab=cfg["vocab_size"], block=block,
        d_slice=max(int(sv["d_f"] * head_dim), 8),
        k_blocks=max(int(sv["k_f"] * (smax // block)), 1),
        pool_itemsize=POOL_ITEMSIZE[sv["pool_layout"].split(":")[0]])


def layer_matmul_params(m: Dims) -> int:
    """Weights one token multiplies in one layer: q, k, v, o and the
    gated MLP (gate and up fused, then down)."""
    qo = 2 * m.d_model * m.heads * m.head_dim
    kv = 2 * m.d_model * m.kv_heads * m.head_dim
    mlp = 3 * m.d_model * m.d_ff
    return qo + kv + mlp


def selected_tokens(m: Dims, ctx: int) -> int:
    """Tokens Loki's exact pass attends at context ``ctx``: every live
    token while the live blocks fit the budget, else the partial last
    block (always selected through the local window) and k-1 full ones."""
    n_live = -(-ctx // m.block)
    if n_live <= m.k_blocks:
        return ctx
    return (m.k_blocks - 1) * m.block + (ctx - (n_live - 1) * m.block)


def decode_step_flops(m: Dims, contexts: Iterable[int]) -> float:
    """Model FLOPs of one decode step over slots at the given context
    lengths (tokens in the cache, the new one included): the matmuls,
    the rotation of the query and the new key into the PCA basis, the
    approximate scores over every live token and exact attention over
    the selected ones, and the vocabulary head."""
    ctx = list(contexts)
    b = len(ctx)
    mm = 2 * m.layers * layer_matmul_params(m) * b + 2 * m.d_model * m.vocab * b
    pca = 2 * m.layers * b * (m.heads + m.kv_heads) * m.head_dim ** 2
    attn = sum(2 * m.heads * m.d_slice * c
               + 4 * m.heads * m.head_dim * selected_tokens(m, c)
               for c in ctx) * m.layers
    return float(mm + pca + attn)


def prefill_chunk_flops(m: Dims, start: int, n: int) -> float:
    """Model FLOPs of one prefill chunk of ``n`` prompt tokens at
    positions ``start`` .. ``start + n - 1``: the matmuls, the rotation
    of the queries and keys into the PCA basis, and exact causal
    attention of each token over every earlier position and itself. The
    chunk's logits, which the engine does not use, are not counted."""
    mm = 2 * n * m.layers * layer_matmul_params(m)
    pca = 2 * m.layers * n * (m.heads + m.kv_heads) * m.head_dim ** 2
    keys = n * start + n * (n + 1) // 2         # sum of each token's keys
    attn = 4 * m.layers * m.heads * m.head_dim * keys
    return float(mm + pca + attn)


def loki_kernel_cost(m: Dims, contexts: Iterable[int]) -> Tuple[float, float]:
    """(FLOPs, HBM bytes) the fused Loki decode kernel needs for one
    decode step, summed over slots, layers and KV heads. Per (slot, KV
    head, layer): the leading ``d_slice`` rows of K over every live
    block, then K and V at full width for the selected blocks, the
    group's float32 queries in and float32 outputs out."""
    flops = bytes_ = 0
    g, dim, bs, it = m.group, m.head_dim, m.block, m.pool_itemsize
    for c in contexts:
        n_live = -(-c // bs)
        sel = min(m.k_blocks, n_live)
        bytes_ += (n_live * bs * m.d_slice * it + 2 * sel * bs * dim * it
                   + 2 * g * dim * 4)
        flops += 2 * g * m.d_slice * n_live * bs + 4 * g * dim * sel * bs
    scale = m.layers * m.kv_heads
    return float(flops * scale), float(bytes_ * scale)


def roofline_share(flops: float, bytes_: float, seconds: float,
                   peak_flops: float, peak_bw: float) -> Tuple[float, str]:
    """Percent of the roofline reached in ``seconds``, and which roof
    bounds it ("compute" or "memory")."""
    t_c, t_m = flops / peak_flops, bytes_ / peak_bw
    bound = "memory" if t_m >= t_c else "compute"
    if not seconds > 0 or not math.isfinite(seconds):
        raise ValueError(f"kernel time {seconds!r} s")
    return 100.0 * max(t_c, t_m) / seconds, bound
