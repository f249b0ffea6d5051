"""Transformer / SSM blocks: init + train-forward + decode-step for each
block family. All blocks share a uniform interface so the LM can lax.scan
over stacked per-layer params:

  init_block(key, cfg)                        -> params (one layer)
  block_train(p, x, positions, cfg)           -> (y, aux_loss)
  block_decode(p, cache, x, pos_len, cfg)     -> (y, new_cache)
  init_cache(cfg, batch, smax, dtype)         -> per-layer cache pytree

``pos_len`` is the number of tokens already in the cache (B,) — the new token
lands at that index and RoPE uses it as the position.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import attention as A
from repro.core import baselines, dispatch, loki
from repro.kernels import layout
from repro.models import layers as L
from repro.sharding.rules import constrain


# =====================================================================
# Attention block
# =====================================================================

def init_attention(key, cfg: ModelConfig):
    d, hd = cfg.d_model, cfg.resolved_head_dim
    ks = jax.random.split(key, 5)
    p = {
        "wq": L.param(ks[0], (d, cfg.q_dim), cfg),
        "wk": L.param(ks[1], (d, cfg.kv_dim), cfg),
        "wv": L.param(ks[2], (d, cfg.kv_dim), cfg),
        "wo": L.param(ks[3], (cfg.q_dim, d), cfg),
        # PCA basis per kv head (identity until calibrated). Held in params so
        # it checkpoints/shards like everything else; excluded from the
        # optimizer by name (see optim.adamw).
        "pca": jnp.broadcast_to(jnp.eye(hd, dtype=jnp.float32),
                                (cfg.n_kv_heads, hd, hd)).copy(),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.q_dim,), jnp.float32)
        p["bk"] = jnp.zeros((cfg.kv_dim,), jnp.float32)
        p["bv"] = jnp.zeros((cfg.kv_dim,), jnp.float32)
    return p


def _qkv(p, x, cfg: ModelConfig):
    """x (B,S,E) -> q (B,S,H,D), k/v (B,S,Hkv,D)."""
    hd = cfg.resolved_head_dim
    dt = x.dtype
    q = L.dot(x, p["wq"].astype(dt))
    k = L.dot(x, p["wk"].astype(dt))
    v = L.dot(x, p["wv"].astype(dt))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(dt)
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    b, s = x.shape[:2]
    q = q.reshape(b, s, cfg.n_heads, hd)
    k = k.reshape(b, s, cfg.n_kv_heads, hd)
    v = v.reshape(b, s, cfg.n_kv_heads, hd)
    q = constrain(q, ("batch", "seq", "heads", "head_dim"))
    k = constrain(k, ("batch", "seq", "kv_heads", "head_dim"))
    v = constrain(v, ("batch", "seq", "kv_heads", "head_dim"))
    return q, k, v


def attn_train(p, x, positions, cfg: ModelConfig, *, capture=None):
    """Full causal attention (train / perplexity eval).

    ``capture``: optional dict that receives pre/post-rotary keys for PCA
    calibration runs."""
    q, k, v = _qkv(p, x, cfg)
    if capture is not None:
        capture["pre"] = k
    if cfg.rope:
        q = L.apply_rope(q, positions, cfg.rope_theta)
        k = L.apply_rope(k, positions, cfg.rope_theta)
    if capture is not None:
        capture["post"] = k
        capture["q"] = q
    out = A.causal_attention(q, k, v, causal=True,
                             sliding_window=cfg.sliding_window)
    b, s = x.shape[:2]
    out = out.reshape(b, s, cfg.q_dim)
    return L.dot(out, p["wo"].astype(x.dtype))


def encoder_attn_train(p, x, positions, cfg: ModelConfig):
    q, k, v = _qkv(p, x, cfg)
    out = A.causal_attention(q, k, v, causal=False)
    b, s = x.shape[:2]
    return L.dot(out.reshape(b, s, cfg.q_dim), p["wo"].astype(x.dtype))


def init_attn_cache(cfg: ModelConfig, batch: int, smax: int, dtype):
    hd = cfg.resolved_head_dim
    pol = cfg.loki
    if cfg.attn_policy() == "pcaattn":
        d = max(int(pol.d_f * hd), 8)
        k_shape = (batch, smax, cfg.n_kv_heads, d)
    elif cfg.attn_policy() == "h2o":
        budget = loki.static_k(pol, smax)
        st = baselines.h2o_init(batch, budget, cfg.n_kv_heads, hd, dtype)
        return {"k": st.k, "v": st.v, "pos": st.pos, "acc": st.acc,
                "fill": st.fill}
    else:
        k_shape = (batch, smax, cfg.n_kv_heads, hd)
    return {
        "k": jnp.zeros(k_shape, dtype),
        "v": jnp.zeros((batch, smax, cfg.n_kv_heads, hd), dtype),
    }


_UINT_OF = {2: jnp.uint16, 4: jnp.uint32, 1: jnp.uint8}


@jax.named_scope("pool")
def _write_cache(cache_arr, new, pos_len):
    """Insert new (B,Hkv,D) rows at per-slot positions pos_len (B,).

    The vmapped DUS lowers to a scatter. XLA:CPU legalizes a bf16 scatter
    through f32 and would rewrite the whole buffer with converts every
    step (§Perf L3), so the raw bit pattern is scattered as an unsigned
    int. (On TPU such a bitcast of a whole cache is a copy; this
    contiguous cache serves the dense engine, the CPU reference.)"""
    b = new.shape[0]
    dt = cache_arr.dtype
    uint = _UINT_OF.get(jnp.dtype(dt).itemsize) if jnp.issubdtype(
        dt, jnp.floating) else None
    c_view = jax.lax.bitcast_convert_type(cache_arr, uint) if uint \
        else cache_arr
    n_view = jax.lax.bitcast_convert_type(new.astype(dt), uint) if uint \
        else new.astype(dt)

    def one(c, n, i):
        return jax.lax.dynamic_update_slice_in_dim(c, n[None], i, axis=0)

    out = jax.vmap(one)(c_view, n_view,
                        jnp.broadcast_to(pos_len, (b,)).astype(jnp.int32))
    return jax.lax.bitcast_convert_type(out, dt) if uint else out


def attn_decode(p, cache, x, pos_len, cfg: ModelConfig, *,
                page_table=None, page_size: int = 0, frame_table=None,
                rank=None, sliding_window=None, layer=None):
    """One-token decode with the configured attention policy.

    x (B,E); pos_len (B,) tokens already cached. Returns (y (B,E), cache).

    ``sliding_window`` (static): this layer's attention window, overriding
    the config-global ``cfg.sliding_window`` — models mixing SWA and
    full-attention layers (``cfg.window_layers``) pass each layer's own
    window through the unrolled decode path (0 = full attention).

    With ``page_table (B, max_pages)``/``page_size`` the cache arrays are
    the serving engine's shared page pools (Hkv,D,R): the new token's K/V
    scatter through the table to their physical rows, and reads either
    gather the logical per-slot view (jnp policies) or hand the pool plus
    table straight to the paged Pallas kernels (loki_block).

    ``frame_table (B, max_pages)`` (tiered pools, DESIGN.md §13): K/V rows
    live at device *frames* while the always-resident ``k_lat`` sidecar is
    indexed by logical page. The approximate score pass reads only the
    sidecar; exact attention gathers winner rows through the frame table
    (HOST pages resolve to the trash frame — finite garbage masked to an
    exact zero by the selection validity mask). Returns (y, cache,
    winners) where ``winners (B, max_pages)`` flags logical pages the
    selection attended.

    ``rank`` (traced scalar): this layer's latent-K rank under per-layer
    ``cfg.page_ranks`` — tail columns of the stored keys are zero-masked,
    which is self-consistent truncation (zeroed dims contribute nothing
    to q̂·k̂).

    ``layer`` (traced int32; one-tier float pools only): ``cache`` holds
    every layer's pools stacked (L,Hkv,·,R) and this layer is decoded in
    place in the stack: the new rows go in through
    ``PC.write_token_rows``'s row-write kernel and the Loki kernel reads
    the layer inside the stack, so no pool is copied or sliced on the
    Pallas route. The cache returned is the whole stack."""
    hd = cfg.resolved_head_dim
    b = x.shape[0]
    q, k, v = _qkv(p, x[:, None, :], cfg)
    q, k, v = q[:, 0], k[:, 0], v[:, 0]          # (B,H,D)/(B,Hkv,D)
    positions = jnp.broadcast_to(pos_len, (b,))
    if cfg.rope:
        q = L.apply_rope(q[:, None], positions[:, None],
                         cfg.rope_theta)[:, 0]
        k = L.apply_rope(k[:, None], positions[:, None],
                         cfg.rope_theta)[:, 0]

    policy = cfg.attn_policy()
    proj = p["pca"]
    cur_len = positions + 1                       # cache incl. new token
    paged = page_table is not None
    sw = cfg.sliding_window if sliding_window is None else sliding_window

    if policy == "h2o":
        if paged:
            raise ValueError("h2o keeps its own budgeted cache; "
                             "serve it through the dense engine")
        st = baselines.H2OState(cache["k"], cache["v"], cache["pos"],
                                cache["acc"], cache["fill"])
        out, st = baselines.h2o_decode(q, k, v, st, positions)
        new_cache = {"k": st.k, "v": st.v, "pos": st.pos, "acc": st.acc,
                     "fill": st.fill}
        y = L.dot(out.reshape(b, cfg.q_dim), p["wo"].astype(x.dtype))
        return y, new_cache

    lay = cfg.page_layout
    if policy in ("loki", "loki_block"):
        # cache keys live in the PCA basis (paper line 3-4)
        _, k_store = loki.project_qk(q, k, proj)
    elif policy == "pcaattn":
        d = cache["k"].shape[-1]
        k_store = jnp.einsum("bhd,hde->bhe", k, proj[..., :d].astype(k.dtype))
    elif paged and lay.basis == "pca":
        # latent-basis pages for non-Loki policies: store k̂ = k·P, rotate
        # q at read time — exact at full rank (Lemma 4.1), back-projection
        # folds into the epilogue (softmax weights are basis-free)
        k_store = jnp.einsum("bhd,hde->bhe", k, proj.astype(k.dtype))
    else:
        k_store = k
    if paged:
        from repro.serving import paged_cache as PC
        # the pool's allocated width is authoritative: per-layer ranks
        # stack every layer at the max width (narrower layers zero-mask)
        kw = layout.pool_width(cache["k"])
        if kw < k_store.shape[-1] and policy != "pcaattn":
            k_store = k_store[..., :kw]           # latent rank-r truncation
        if rank is not None and policy != "pcaattn":
            k_store = k_store * (jnp.arange(kw) < rank).astype(k_store.dtype)
        if frame_table is not None:
            if policy not in ("loki", "loki_block"):
                raise ValueError("tiered pools serve Loki policies only "
                                 f"(got {policy!r})")
            if cfg.loki.n_chunks:
                raise ValueError("tiered pools do not support chunked "
                                 "(distributed) Loki selection")
            dl = layout.pool_width(cache["k_lat"])
            cache = {"k": PC.write_token_rows(cache["k"], k_store,
                                              frame_table, positions,
                                              page_size),
                     "v": PC.write_token_rows(cache["v"], v, frame_table,
                                              positions, page_size),
                     "k_lat": PC.write_token_rows(cache["k_lat"],
                                                  k_store[..., :dl],
                                                  page_table, positions,
                                                  page_size)}
            out, win = dispatch.loki_tiered_decode(
                q, cache["k"], cache["v"], cache["k_lat"], cur_len, proj,
                cfg.loki, sliding_window=sw,
                page_table=page_table, frame_table=frame_table,
                page_size=page_size, token_granular=(policy == "loki"))
            y = L.dot(out.reshape(b, cfg.q_dim).astype(x.dtype),
                      p["wo"].astype(x.dtype))
            return y, cache, win
        if lay.quantized:
            kp, ks = PC.write_token_rows_q(
                cache["k"], cache["k_scale"], k_store, page_table,
                positions, page_size, qmax=lay.qmax)
            vp, vs = PC.write_token_rows_q(
                cache["v"], cache["v_scale"], v, page_table,
                positions, page_size, qmax=lay.qmax)
            cache = {"k": kp, "v": vp, "k_scale": ks, "v_scale": vs}
        else:
            wargs = dict(layer=layer, backend=cfg.loki.backend)
            cache = {"k": PC.write_token_rows(cache["k"], k_store,
                                              page_table, positions,
                                              page_size, **wargs),
                     "v": PC.write_token_rows(cache["v"], v, page_table,
                                              positions, page_size,
                                              **wargs)}
        # this layer's pools, for the routes that cannot address the stack
        read = {n: layout.layer_pool(a, layer) for n, a in cache.items()}

        def view(name):
            return PC.gather_logical_dq(read[name],
                                        cache.get(name + "_scale"),
                                        page_table, page_size)
    else:
        cache = {"k": _write_cache(cache["k"], k_store, pos_len),
                 "v": _write_cache(cache["v"], v, pos_len)}
        read = cache

        def view(name):
            return cache[name]

    # queries follow the storage basis; hd**-0.5 stays the logit scale even
    # when the stored K width is the latent rank r < hd
    q_read = q
    if paged and lay.basis == "pca" and policy in ("full", "exact_topk"):
        qg_r = q.reshape(b, cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads,
                         hd)
        qh = jnp.einsum("bhgd,hde->bhge", qg_r, proj.astype(q.dtype))
        q_read = qh[..., :lay.k_width(hd)].reshape(b, cfg.n_heads, -1)

    if policy == "full":
        # backend-dispatched like loki_block: on the Pallas path the paged
        # streaming kernel reads live blocks through the table; the XLA
        # path is the bit-preserved gather + decode_full reference
        out = dispatch.full_paged_decode(q_read, read["k"], read["v"],
                                         cur_len, backend=cfg.loki.backend,
                                         block_size=cfg.loki.block_size,
                                         sliding_window=sw,
                                         logit_scale=hd ** -0.5,
                                         page_table=page_table,
                                         page_size=page_size,
                                         k_scale=cache.get("k_scale"),
                                         v_scale=cache.get("v_scale"))
    elif policy == "exact_topk":
        # exact scores + block top-k fused the same way loki_block's
        # approximate pass is; XLA keeps the token-granular reference
        out = dispatch.exact_topk_paged_decode(q_read, read["k"],
                                               read["v"], cur_len,
                                               cfg.loki,
                                               logit_scale=hd ** -0.5,
                                               page_table=page_table,
                                               page_size=page_size,
                                               k_scale=cache.get("k_scale"),
                                               v_scale=cache.get("v_scale"))
    elif policy == "loki":
        if cfg.loki.n_chunks:
            out = loki.loki_decode_chunked(
                q, view("k"), view("v"), cur_len, proj,
                cfg.loki, sliding_window=sw)
        else:
            out = loki.loki_decode(q, view("k"), view("v"),
                                   cur_len, proj, cfg.loki,
                                   sliding_window=sw)
    elif policy == "loki_block":
        # backend-dispatched: fused Pallas kernels on TPU (or when forced),
        # the jnp reference otherwise (core/dispatch.py). Paged caches pass
        # through untouched — the kernels index the pool via the table and
        # dequantize quantized layouts in their DMA epilogue.
        out = dispatch.loki_block_decode(q, cache["k"], cache["v"], cur_len,
                                         proj, cfg.loki,
                                         sliding_window=sw,
                                         page_table=page_table,
                                         page_size=page_size,
                                         k_scale=cache.get("k_scale"),
                                         v_scale=cache.get("v_scale"),
                                         layer=layer)
    elif policy == "pcaattn":
        out = baselines.pcaattn_decode(q, view("k"), view("v"),
                                       cur_len, proj, cfg.loki)
    else:
        raise ValueError(f"unknown attention policy {policy!r}")
    # the jnp routes attend in float32: back to the activation dtype, or a
    # bf16 model's layer scan would change its carry type
    y = L.dot(out.reshape(b, cfg.q_dim).astype(x.dtype),
              p["wo"].astype(x.dtype))
    return y, cache


def attn_prefill(p, cache, x, positions, cfg: ModelConfig):
    """Process a whole prompt, filling cache slots [0, S). Returns (y, cache).

    The cache stores keys in the policy's basis so subsequent decode steps
    are pure Algorithm-1."""
    q, k, v = _qkv(p, x, cfg)
    if cfg.rope:
        q = L.apply_rope(q, positions, cfg.rope_theta)
        k = L.apply_rope(k, positions, cfg.rope_theta)
    out = A.causal_attention(q, k, v, causal=True,
                             sliding_window=cfg.sliding_window)
    b, s = x.shape[:2]
    y = L.dot(out.reshape(b, s, cfg.q_dim), p["wo"].astype(x.dtype))

    policy = cfg.attn_policy()
    proj = p["pca"]
    if policy in ("loki", "loki_block"):
        k_store = jnp.einsum("bshd,hde->bshe", k, proj.astype(k.dtype))
    elif policy == "pcaattn":
        d = cache["k"].shape[-1]
        k_store = jnp.einsum("bshd,hde->bshe", k,
                             proj[..., :d].astype(k.dtype))
    else:
        k_store = k
    if policy == "h2o":
        # budget cache: keep the most recent `budget` prompt tokens
        budget = cache["k"].shape[1]
        take = min(budget, s)
        kk = k[:, s - take:]
        vv = v[:, s - take:]
        pad = budget - take
        cache = dict(cache)
        cache["k"] = jnp.pad(kk, ((0, 0), (0, pad), (0, 0), (0, 0))).astype(
            cache["k"].dtype)
        cache["v"] = jnp.pad(vv, ((0, 0), (0, pad), (0, 0), (0, 0))).astype(
            cache["v"].dtype)
        cache["pos"] = jnp.pad(
            jnp.broadcast_to(jnp.arange(s - take, s), (b, take)),
            ((0, 0), (0, pad)), constant_values=-1).astype(jnp.int32)
        cache["acc"] = jnp.zeros_like(cache["acc"])
        cache["fill"] = jnp.full((b,), take, jnp.int32)
        return y, cache
    smax = cache["k"].shape[1]
    cache = {
        "k": jax.lax.dynamic_update_slice(
            cache["k"], k_store.astype(cache["k"].dtype), (0, 0, 0, 0)),
        "v": jax.lax.dynamic_update_slice(
            cache["v"], v.astype(cache["v"].dtype), (0, 0, 0, 0)),
    }
    return y, cache


def attn_prefill_chunk(p, cache, x, pos_start, n_valid, cfg: ModelConfig, *,
                       table_row, page_size: int, frame_row=None,
                       rank=None, sliding_window=None):
    """One chunk of a paged, chunked prefill for a single request.

    ``sliding_window`` overrides ``cfg.sliding_window`` for this layer
    (per-layer windows, ``cfg.window_layers``; 0 = full attention).

    x (1,C,E) holds the chunk's token embeddings at logical positions
    ``pos_start .. pos_start+C-1``; only the first ``n_valid`` are real
    (the scheduler zero-pads the final chunk to keep the jit signature
    fixed). The chunk's K/V scatter through ``table_row (max_pages,)``
    into the shared pool (pad rows go to the trash page), then the chunk
    attends causally over [0, pos_start+C) via the logical view.

    Exactness across chunks: the cached prefix holds keys in the policy's
    storage basis, so prefix scores are taken in that basis — for Loki
    policies that is q̂·k̂ which equals q·k exactly for orthogonal P
    (Lemma 4.1). The chunk's own columns use the fresh original-basis
    keys, so a single-chunk prefill reproduces the one-shot prefill's
    score matrix term for term."""
    from repro.serving import paged_cache as PC
    b, c = x.shape[:2]
    q, k, v = _qkv(p, x, cfg)
    positions = pos_start + jnp.arange(c)[None]            # (1, C)
    if cfg.rope:
        q = L.apply_rope(q, positions, cfg.rope_theta)
        k = L.apply_rope(k, positions, cfg.rope_theta)

    policy = cfg.attn_policy()
    proj = p["pca"]
    lay = cfg.page_layout
    hd = cfg.resolved_head_dim
    if policy not in ("full", "exact_topk", "loki", "loki_block"):
        raise ValueError(f"policy {policy!r} cannot reconstruct exact "
                         "prefix attention from its cache; use the dense "
                         "engine's one-shot prefill")
    pca_store = policy in ("loki", "loki_block") or lay.basis == "pca"
    k_store = (jnp.einsum("bshd,hde->bshe", k, proj.astype(k.dtype))
               if pca_store else k)
    kw = layout.pool_width(cache["k"])   # allocated width is authoritative
    if kw < hd:
        k_store = k_store[..., :kw]                # latent rank-r storage
    if rank is not None:
        k_store = k_store * (jnp.arange(kw) < rank).astype(k_store.dtype)
    if frame_row is not None:
        # tiered pool (DESIGN.md §13): full-D rows at device frames, the
        # latent sidecar by logical page. Prefill is exact attention, so
        # the scheduler has promoted every page of this slot already.
        dl = layout.pool_width(cache["k_lat"])
        cache = {"k": PC.write_chunk_rows(cache["k"], k_store[0], frame_row,
                                          pos_start, page_size,
                                          n_valid=n_valid),
                 "v": PC.write_chunk_rows(cache["v"], v[0], frame_row,
                                          pos_start, page_size,
                                          n_valid=n_valid),
                 "k_lat": PC.write_chunk_rows(cache["k_lat"],
                                              k_store[0][..., :dl],
                                              table_row, pos_start,
                                              page_size, n_valid=n_valid)}
    elif lay.quantized:
        kp, ks = PC.write_chunk_rows_q(
            cache["k"], cache["k_scale"], k_store[0], table_row, pos_start,
            page_size, n_valid=n_valid, qmax=lay.qmax)
        vp, vs = PC.write_chunk_rows_q(
            cache["v"], cache["v_scale"], v[0], table_row, pos_start,
            page_size, n_valid=n_valid, qmax=lay.qmax)
        cache = {"k": kp, "v": vp, "k_scale": ks, "v_scale": vs}
    else:
        cache = {"k": PC.write_chunk_rows(cache["k"], k_store[0], table_row,
                                          pos_start, page_size,
                                          n_valid=n_valid),
                 "v": PC.write_chunk_rows(cache["v"], v[0], table_row,
                                          pos_start, page_size,
                                          n_valid=n_valid)}

    read_row = frame_row if frame_row is not None else table_row
    klog = PC.gather_logical_dq(cache["k"], cache.get("k_scale"),
                                read_row[None], page_size)
    vlog = PC.gather_logical_dq(cache["v"], cache.get("v_scale"),
                                read_row[None], page_size)
    sl = klog.shape[1]
    n_kv = cfg.n_kv_heads
    scale = hd ** -0.5
    qg = A._group(q, n_kv)                                 # (1,C,Hkv,G,D)
    if pca_store:
        q_pref = jnp.einsum("bchgd,hde->bchge", qg, proj.astype(q.dtype))
    else:
        q_pref = qg
    if kw < hd:
        q_pref = q_pref[..., :kw]       # scores against rank-r cached keys
    # prefix scores against the cached (storage-basis) keys ...
    scores = jnp.einsum("bchgd,bshd->bhgcs", q_pref * scale, klog,
                        preferred_element_type=jnp.float32)
    # ... the chunk's own columns overwritten with fresh original-basis
    # scores (bit-parity with the one-shot prefill for these terms).
    # Scatter, not dynamic_update_slice: when the padded chunk overhangs
    # the logical length (pos_start + C > Sl, pad columns only) a DUS
    # would clamp the start and land the whole block at shifted columns;
    # drop-mode scatter discards exactly the overhanging pads instead.
    s_chunk = jnp.einsum("bchgd,bshd->bhgcs", qg * scale, k,
                         preferred_element_type=jnp.float32)
    chunk_cols = pos_start + jnp.arange(c)
    scores = scores.at[:, :, :, :, chunk_cols].set(s_chunk, mode="drop")

    sw = cfg.sliding_window if sliding_window is None else sliding_window
    kv_pos = jnp.arange(sl)
    mask = kv_pos[None, :] <= positions[0][:, None]        # causal (C, Sl)
    if sw:
        mask &= positions[0][:, None] - kv_pos[None, :] < sw
    scores = jnp.where(mask[None, None, None], scores, A.NEG_INF)
    w = jax.nn.softmax(scores, axis=-1).astype(vlog.dtype)
    o = jnp.einsum("bhgcs,bshd->bchgd", w, vlog)
    y = L.dot(o.reshape(b, c, cfg.q_dim).astype(x.dtype),
              p["wo"].astype(x.dtype))
    return y, cache


# =====================================================================
# MoE block (GShard-style capacity dispatch; FLOPs track active experts)
# =====================================================================

def init_moe(key, cfg: ModelConfig):
    m = cfg.moe
    d, f = cfg.d_model, m.d_ff_expert
    ks = jax.random.split(key, 3)
    gated = cfg.mlp in ("swiglu", "geglu")
    return {
        "router": L.param(ks[0], (d, m.n_experts), cfg),
        "w_in": L.param(ks[1], (m.n_experts, d, 2 * f if gated else f),
                        cfg),
        "w_out": L.param(ks[2], (m.n_experts, f, d), cfg),
    }


MOE_GROUP = 256  # tokens per dispatch group (keeps dispatch tensors small)


def moe_apply(p, x, cfg: ModelConfig):
    """Returns (y, aux_loss). Capacity routing with sort-based gather/scatter
    dispatch (§Perf M1).

    The GShard one-hot formulation materializes (G,g,K,E,C) dispatch/combine
    tensors — ~50 GB/layer at train_4k scale for 40 experts. Here tokens are
    argsorted by expert id (stable sort keeps GShard's drop-in-token-order
    semantics exactly), each expert's capacity window gathers its tokens, and
    the combine is a scatter-add — O(E·C) index tensors instead of
    O(g·K·E·C) one-hots. Compute shards over the expert dim when divisible,
    else over the capacity dim (``expert_capacity`` rule)."""
    m = cfg.moe
    b, s, d = x.shape
    n_tok = b * s
    g = min(MOE_GROUP, n_tok)
    n_groups = n_tok // g
    xt = x.reshape(n_groups, g, d)
    xt = constrain(xt, ("moe_group", None, "act_embed"))
    K, E = m.top_k, m.n_experts

    logits = L.dot(xt.astype(jnp.float32), p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)                 # (G,g,E)
    gate_w, eidx = jax.lax.top_k(probs, K)                  # (G,g,K)
    gate_w = gate_w / jnp.maximum(gate_w.sum(-1, keepdims=True), 1e-9)

    cap = int(math.ceil(g * K / E * m.capacity_factor))
    cap = max(4, -(-cap // 4) * 4)                          # round up to 4

    # ---- sort-based dispatch ------------------------------------------
    flat_e = eidx.reshape(n_groups, g * K)                  # (G,gK)
    flat_w = gate_w.reshape(n_groups, g * K)
    order = jnp.argsort(flat_e, axis=-1, stable=True)       # tokens by expert
    sorted_e = jnp.take_along_axis(flat_e, order, -1)
    erange = jnp.arange(E)
    starts = jax.vmap(
        lambda se: jnp.searchsorted(se, erange, side="left"))(sorted_e)
    ends = jax.vmap(
        lambda se: jnp.searchsorted(se, erange, side="right"))(sorted_e)
    slot = starts[:, :, None] + jnp.arange(cap)[None, None]   # (G,E,C)
    valid = slot < ends[:, :, None]                           # capacity drop
    slot = jnp.minimum(slot, g * K - 1)
    sel = jnp.take_along_axis(order, slot.reshape(n_groups, -1), -1)
    tok = sel // K                                            # (G,E*C)
    tok = constrain(tok, ("moe_group", None))
    w_sel = jnp.take_along_axis(flat_w, sel, -1)
    w_sel = jnp.where(valid.reshape(n_groups, -1), w_sel, 0.0)

    dt = x.dtype
    x_sel = jnp.take_along_axis(xt, tok[..., None], axis=1)   # (G,E*C,D)
    x_sel = constrain(x_sel, ("moe_group", None, "act_embed"))
    expert_in = x_sel.reshape(n_groups, E, cap, d)
    expert_in = constrain(
        expert_in, ("moe_group", "expert", "expert_capacity", "act_embed"))
    f = m.d_ff_expert
    h = jnp.einsum("gecd,edf->gecf", expert_in, p["w_in"].astype(dt))
    h = constrain(h, ("moe_group", "expert", "expert_capacity", "mlp"))
    if cfg.mlp in ("swiglu", "geglu"):
        gate, up = h[..., :f], h[..., f:]
        act = jax.nn.silu(gate) if cfg.mlp == "swiglu" else jax.nn.gelu(gate)
        h = act * up
    elif cfg.mlp == "sq_relu":
        h = jnp.square(jax.nn.relu(h))
    else:
        h = jax.nn.gelu(h)
    expert_out = jnp.einsum("gecf,efd->gecd", h, p["w_out"].astype(dt))
    expert_out = constrain(
        expert_out, ("moe_group", "expert", "expert_capacity", "act_embed"))

    # ---- combine: weighted scatter-add back to token order ------------
    contrib = (expert_out.reshape(n_groups, E * cap, d)
               * w_sel[..., None].astype(dt))
    contrib = constrain(contrib, ("moe_group", None, "act_embed"))
    y = jnp.zeros((n_groups, g, d), dt)
    y = y.at[jnp.arange(n_groups)[:, None], tok].add(contrib)
    y = constrain(y, ("moe_group", None, "act_embed"))
    y = y.reshape(b, s, d)

    # aux: load-balance (Switch) + router z-loss
    first = jax.nn.one_hot(eidx[:, :, 0], E, dtype=jnp.float32)
    frac_tokens = jnp.mean(first, axis=1)                   # first choice
    frac_probs = jnp.mean(probs, axis=1)
    lb = E * jnp.mean(jnp.sum(frac_tokens * frac_probs, -1))
    zl = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    aux = lb + m.router_z_loss * zl
    return y, aux


def moe_decode(p, x, cfg: ModelConfig):
    """Single-token MoE: gather the top-k expert weights per token.

    x (B,E). At decode, per-token expert weight gathers beat dispatch einsums
    (k·d·f bytes vs n_tok·E·C flops)."""
    m = cfg.moe
    b, d = x.shape
    logits = L.dot(x.astype(jnp.float32), p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, -1)
    gate_w, eidx = jax.lax.top_k(probs, m.top_k)            # (B,K)
    gate_w = gate_w / jnp.maximum(gate_w.sum(-1, keepdims=True), 1e-9)
    w_in = jnp.take(p["w_in"], eidx, axis=0).astype(x.dtype)   # (B,K,d,f')
    w_out = jnp.take(p["w_out"], eidx, axis=0).astype(x.dtype)
    f = m.d_ff_expert
    h = jnp.einsum("bd,bkdf->bkf", x, w_in)
    if cfg.mlp in ("swiglu", "geglu"):
        gate, up = h[..., :f], h[..., f:]
        act = jax.nn.silu(gate) if cfg.mlp == "swiglu" else jax.nn.gelu(gate)
        h = act * up
    elif cfg.mlp == "sq_relu":
        h = jnp.square(jax.nn.relu(h))
    else:
        h = jax.nn.gelu(h)
    y = jnp.einsum("bkf,bkfd->bkd", h, w_out)
    return jnp.einsum("bk,bkd->bd", gate_w.astype(x.dtype), y)


# =====================================================================
# Mamba (S6) block — hymba's parallel-SSM path
# =====================================================================

def init_mamba(key, cfg: ModelConfig):
    s = cfg.ssm
    d = cfg.d_model
    d_in = s.expand * d
    dt_rank = max(d // 16, 1)
    ks = jax.random.split(key, 6)
    # S4D-real init, as a host constant: inside the jitted init a traced
    # log can round differently from the op-by-op value
    a_log = np.broadcast_to(
        np.log(np.arange(1, s.state_dim + 1, dtype=np.float32)),
        (d_in, s.state_dim))
    return {
        "in_proj": L.param(ks[0], (d, 2 * d_in), cfg),
        "conv_w": L.param(ks[1], (s.conv_width, d_in), cfg, scale=0.5),
        "x_proj": L.param(ks[2], (d_in, dt_rank + 2 * s.state_dim),
                          cfg),
        "dt_proj": L.param(ks[3], (dt_rank, d_in), cfg),
        "dt_bias": jnp.zeros((d_in,), jnp.float32),
        "a_log": jnp.asarray(a_log),
        "d_skip": jnp.ones((d_in,), jnp.float32),
        "out_proj": L.param(ks[4], (d_in, d), cfg),
    }


def _mamba_scan(p, xz, conv_state, ssm_state, cfg: ModelConfig,
                n_valid=None):
    """Shared S6 recurrence. xz (B,S,2*d_in) from in_proj.

    conv_state (B,cw-1,d_in), ssm_state (B,d_in,N).
    Returns (y (B,S,d_in->d projected later), states).

    ``n_valid`` (traced scalar): positions at or past it are zero padding
    (a fixed-size prefill chunk's tail). Their ``dt`` is forced to 0 so the
    SSM state passes through unchanged (exp(0·A)=1, zero input), and the
    carried conv window ends at the last *valid* token — running chunks
    back-to-back reproduces the unchunked recurrence exactly."""
    s = cfg.ssm
    d_in = xz.shape[-1] // 2
    x, z = xz[..., :d_in], xz[..., d_in:]
    # causal depthwise conv with carried state
    cw = s.conv_width
    xpad = jnp.concatenate([conv_state.astype(x.dtype), x], axis=1)
    if cw <= 1:
        new_conv = conv_state
    elif n_valid is None:
        new_conv = xpad[:, -(cw - 1):]
    else:
        # last cw-1 inputs *ending at the n_valid-th real token* (rows
        # [n_valid, n_valid + cw - 1) of xpad; reaches back into the old
        # conv state when the chunk has fewer than cw-1 valid tokens)
        new_conv = jax.lax.dynamic_slice_in_dim(xpad, n_valid, cw - 1,
                                                axis=1)
    conv = sum(xpad[:, i:i + x.shape[1]] * p["conv_w"][i].astype(x.dtype)
               for i in range(cw))
    x = jax.nn.silu(conv)

    dt_rank = p["dt_proj"].shape[0]
    proj = L.dot(x, p["x_proj"].astype(x.dtype))
    dt = jax.nn.softplus(
        L.dot(proj[..., :dt_rank], p["dt_proj"].astype(x.dtype))
        + p["dt_bias"].astype(x.dtype))                        # (B,S,d_in)
    if n_valid is not None:
        dt = dt * (jnp.arange(x.shape[1]) < n_valid)[None, :, None]
    bmat = proj[..., dt_rank:dt_rank + s.state_dim]            # (B,S,N)
    cmat = proj[..., dt_rank + s.state_dim:]                   # (B,S,N)
    a = -jnp.exp(p["a_log"]).astype(jnp.float32)               # (d_in,N)

    def step(h, inp):
        x_t, dt_t, b_t, c_t = inp                              # (B,d_in)...
        da = jnp.exp(dt_t[..., None] * a)                      # (B,d_in,N)
        h = da * h + (dt_t * x_t)[..., None] * b_t[:, None, :]
        y = jnp.einsum("bdn,bn->bd", h, c_t)
        return h, y

    xs = (jnp.moveaxis(x.astype(jnp.float32), 1, 0),
          jnp.moveaxis(dt.astype(jnp.float32), 1, 0),
          jnp.moveaxis(bmat.astype(jnp.float32), 1, 0),
          jnp.moveaxis(cmat.astype(jnp.float32), 1, 0))
    new_ssm, ys = jax.lax.scan(step, ssm_state.astype(jnp.float32), xs)
    y = jnp.moveaxis(ys, 0, 1).astype(x.dtype)
    y = y + x * p["d_skip"].astype(x.dtype)
    y = y * jax.nn.silu(z)
    return y, new_conv, new_ssm


def mamba_train(p, x, cfg: ModelConfig):
    s = cfg.ssm
    b = x.shape[0]
    d_in = s.expand * cfg.d_model
    xz = L.dot(x, p["in_proj"].astype(x.dtype))
    conv0 = jnp.zeros((b, s.conv_width - 1, d_in), x.dtype)
    ssm0 = jnp.zeros((b, d_in, s.state_dim), jnp.float32)
    y, _, _ = _mamba_scan(p, xz, conv0, ssm0, cfg)
    return L.dot(y, p["out_proj"].astype(x.dtype))


def init_mamba_cache(cfg: ModelConfig, batch: int, dtype):
    s = cfg.ssm
    d_in = s.expand * cfg.d_model
    return {
        "conv": jnp.zeros((batch, s.conv_width - 1, d_in), dtype),
        "ssm": jnp.zeros((batch, d_in, s.state_dim), jnp.float32),
    }


def mamba_decode(p, cache, x, cfg: ModelConfig):
    xz = L.dot(x[:, None, :], p["in_proj"].astype(x.dtype))
    y, conv, ssm = _mamba_scan(p, xz, cache["conv"], cache["ssm"], cfg)
    y = L.dot(y[:, 0], p["out_proj"].astype(x.dtype))
    return y, {"conv": conv.astype(cache["conv"].dtype), "ssm": ssm}


def mamba_prefill_chunk(p, state, x, n_valid, cfg: ModelConfig):
    """One chunk of a chunked prefill through the S6 recurrence.

    x (1,C,E) chunk hidden states, only the first ``n_valid`` real; state
    is the slot's carried {conv, ssm}. Pad tokens leave the state untouched
    (see ``_mamba_scan``), so consecutive chunks reproduce the one-shot
    ``_mamba_prefill`` state exactly. Returns (y (1,C,E), new_state)."""
    xz = L.dot(x, p["in_proj"].astype(x.dtype))
    y, conv, ssm = _mamba_scan(p, xz, state["conv"], state["ssm"], cfg,
                               n_valid=n_valid)
    y = L.dot(y, p["out_proj"].astype(x.dtype))
    return y, {"conv": conv.astype(state["conv"].dtype), "ssm": ssm}


# =====================================================================
# xLSTM blocks — mLSTM (chunkwise-parallel) and sLSTM (recurrent)
# =====================================================================

def init_mlstm(key, cfg: ModelConfig):
    d = cfg.d_model
    nh = cfg.ssm.n_heads
    dh = d // nh
    ks = jax.random.split(key, 6)
    return {
        "wq": L.param(ks[0], (d, d), cfg),
        "wk": L.param(ks[1], (d, d), cfg),
        "wv": L.param(ks[2], (d, d), cfg),
        "w_if": L.param(ks[3], (d, 2 * nh), cfg, scale=0.01),
        "b_if": jnp.concatenate([jnp.zeros((nh,)), 3.0 * jnp.ones((nh,))]),
        "wo_gate": L.param(ks[4], (d, d), cfg),
        "w_out": L.param(ks[5], (d, d), cfg),
    }


MLSTM_CHUNK = 256


def mlstm_train(p, x, cfg: ModelConfig, *, return_state: bool = False,
                initial_state=None, n_valid=None):
    """Chunkwise-parallel mLSTM (exponential-gated linear attention with
    matrix memory). O(S·c·d + S·d²/c) — sub-quadratic, the long_500k path.

    ``return_state``: also return the final (C, n, m) recurrent state — the
    scan's own carry — so prefill gets its cache for free instead of
    re-scanning the whole prompt token-by-token (§Perf X2).
    ``initial_state``: resume the recurrence from a carried {C, n, m} (the
    paged engine's chunked prefill). ``n_valid``: positions at or past it
    are padding — their input gate is forced to -inf and forget gate to 0
    (identity), so they contribute nothing to the carry."""
    b, s, d = x.shape
    nh = cfg.ssm.n_heads
    dh = d // nh
    dt = x.dtype
    q = L.dot(x, p["wq"].astype(dt)).reshape(b, s, nh, dh) * dh ** -0.5
    k = L.dot(x, p["wk"].astype(dt)).reshape(b, s, nh, dh) * dh ** -0.5
    v = L.dot(x, p["wv"].astype(dt)).reshape(b, s, nh, dh)
    # gate pre-activations: bf16 matmul, f32 accumulation (§Perf X3 — an
    # f32 upcast here forces f32 partial-sum all-reduces under FSDP)
    if_g = jnp.matmul(x, p["w_if"].astype(dt),
                      preferred_element_type=jnp.float32) + p["b_if"]
    ig, fg = if_g[..., :nh], if_g[..., nh:]                 # (B,S,H)
    logf = jax.nn.log_sigmoid(fg)
    if n_valid is not None:
        vm = (jnp.arange(s) < n_valid)[None, :, None]
        ig = jnp.where(vm, ig, -1e30)                       # i -> 0
        logf = jnp.where(vm, logf, 0.0)                     # f -> 1

    c = min(MLSTM_CHUNK, s)
    if s % c:
        c = s
    n_chunks = s // c

    def reshape_c(t):
        return jnp.moveaxis(t.reshape(b, n_chunks, c, *t.shape[2:]), 1, 0)

    qc, kc, vc = reshape_c(q), reshape_c(k), reshape_c(v)
    ic, fc = reshape_c(ig), reshape_c(logf)                 # (n,B,c,H)

    def chunk_step(carry, inp):
        C, n, m = carry          # (B,H,dh,dh), (B,H,dh), (B,H)
        qq, kk, vv, ii, ff = inp
        csum = jnp.cumsum(ff, axis=1)                       # (B,c,H)
        total = csum[:, -1]                                 # (B,H)
        # log decay from chunk start to position t (inclusive)
        d_in = csum                                          # sum_{j<=t} logf
        # intra-chunk log weights: a[t,s] = csum_t - csum_s + i_s  (s<=t)
        log_a = (d_in[:, :, None, :] - d_in[:, None, :, :]
                 + ii[:, None, :, :])                       # (B,t,s,H)
        tmask = jnp.tril(jnp.ones((c, c), bool))
        log_a = jnp.where(tmask[None, :, :, None], log_a, -jnp.inf)
        # inter-chunk: carried state decayed to position t
        log_b = d_in + m[:, None, :]                        # (B,t,H)
        m_new = jnp.maximum(jnp.max(log_a, axis=2), log_b)  # (B,t,H)
        a = jnp.exp(log_a - m_new[:, :, None, :])
        bw = jnp.exp(log_b - m_new)                         # (B,t,H)
        # numerator / denominator (fp32 accumulation)
        scores = jnp.einsum("bthd,bshd->bhts", qq, kk,
                            preferred_element_type=jnp.float32)
        scores = scores * jnp.moveaxis(a, 3, 1)             # (B,H,t,s)
        num_intra = jnp.einsum("bhts,bshd->bthd", scores.astype(dt), vv)
        num_inter = jnp.einsum("bthd,bhde->bthe", qq,
                               C.astype(dt)) * bw[..., None].astype(dt)
        den = (jnp.einsum("bthd,bhd->bth", qq.astype(jnp.float32), n) * bw
               + jnp.sum(scores, axis=3).transpose(0, 2, 1))
        h = (num_intra + num_inter).astype(jnp.float32) / jnp.maximum(
            jnp.abs(den), jnp.exp(-m_new))[..., None]
        h = h.astype(dt)
        # carry update: C' = exp(total + m - m') C + sum_s exp(csum_T - csum_s + i_s - m') k v^T
        m_next = jnp.maximum(total + m, jnp.max(
            total[:, None] - d_in + ii, axis=1))            # (B,H)
        decay_c = jnp.exp(total + m - m_next)               # (B,H)
        w_s = jnp.exp(total[:, None] - d_in + ii - m_next[:, None])
        C = (C * decay_c[..., None, None]
             + jnp.einsum("bsh,bshd,bshe->bhde",
                          w_s, kk.astype(jnp.float32),
                          vv.astype(jnp.float32)))
        n = (n * decay_c[..., None]
             + jnp.einsum("bsh,bshd->bhd", w_s, kk.astype(jnp.float32)))
        return (C, n, m_next), h

    if initial_state is not None:
        C0 = initial_state["C"].astype(jnp.float32)
        n0 = initial_state["n"].astype(jnp.float32)
        m0 = initial_state["m"].astype(jnp.float32)
    else:
        C0 = jnp.zeros((b, nh, dh, dh), jnp.float32)
        n0 = jnp.zeros((b, nh, dh), jnp.float32)
        m0 = jnp.full((b, nh), -1e30, jnp.float32)
    (C_f, n_f, m_f), hs = jax.lax.scan(chunk_step, (C0, n0, m0),
                                       (qc, kc, vc, ic, fc))
    h = jnp.moveaxis(hs, 0, 1).reshape(b, s, d)
    o = jax.nn.sigmoid(L.dot(x, p["wo_gate"].astype(dt)))
    y = L.dot(h * o, p["w_out"].astype(dt))
    if return_state:
        return y, {"C": C_f, "n": n_f, "m": m_f}
    return y


def init_mlstm_cache(cfg: ModelConfig, batch: int):
    nh = cfg.ssm.n_heads
    dh = cfg.d_model // nh
    return {
        "C": jnp.zeros((batch, nh, dh, dh), jnp.float32),
        "n": jnp.zeros((batch, nh, dh), jnp.float32),
        "m": jnp.full((batch, nh), -1e30, jnp.float32),
    }


def mlstm_decode(p, cache, x, cfg: ModelConfig):
    b, d = x.shape
    nh = cfg.ssm.n_heads
    dh = d // nh
    dt = x.dtype
    q = L.dot(x, p["wq"].astype(dt)).reshape(b, nh, dh) * dh ** -0.5
    k = L.dot(x, p["wk"].astype(dt)).reshape(b, nh, dh) * dh ** -0.5
    v = L.dot(x, p["wv"].astype(dt)).reshape(b, nh, dh)
    if_g = (L.dot(x.astype(jnp.float32), p["w_if"].astype(jnp.float32))
            + p["b_if"])
    ii, ff = if_g[..., :nh], jax.nn.log_sigmoid(if_g[..., nh:])
    C, n, m = cache["C"], cache["n"], cache["m"]
    m_new = jnp.maximum(ff + m, ii)
    fw = jnp.exp(ff + m - m_new)[..., None]
    iw = jnp.exp(ii - m_new)[..., None]
    kf, vf = k.astype(jnp.float32), v.astype(jnp.float32)
    C = C * fw[..., None] + iw[..., None] * kf[..., None] * vf[:, :, None, :]
    n = n * fw + iw * kf
    num = jnp.einsum("bhd,bhde->bhe", q.astype(jnp.float32), C)
    den = jnp.maximum(jnp.abs(jnp.einsum("bhd,bhd->bh",
                                         q.astype(jnp.float32), n)),
                      jnp.exp(-m_new))
    h = (num / den[..., None]).astype(dt).reshape(b, d)
    o = jax.nn.sigmoid(L.dot(x, p["wo_gate"].astype(dt)))
    y = L.dot(h * o, p["w_out"].astype(dt))
    return y, {"C": C, "n": n, "m": m_new}


def init_slstm(key, cfg: ModelConfig):
    d = cfg.d_model
    nh = cfg.ssm.n_heads
    dh = d // nh
    ks = jax.random.split(key, 3)
    return {
        "w_gates": L.param(ks[0], (d, 4 * d), cfg),   # z,i,f,o pre-acts
        "r_gates": L.param(ks[1], (nh, dh, 4 * dh), cfg, scale=0.1),
        "b_gates": jnp.zeros((4 * d,), jnp.float32),
        "w_out": L.param(ks[2], (d, d), cfg),
    }


def _slstm_cell(p, wx_t, state, nh, dh):
    """One sLSTM step. wx_t (B,4d) precomputed input part."""
    c, n, h, m = state
    b = wx_t.shape[0]
    rh = jnp.einsum("bhd,hde->bhe", h.reshape(b, nh, dh),
                    p["r_gates"]).reshape(b, 4 * nh * dh)
    pre = (wx_t + rh + p["b_gates"]).astype(jnp.float32)
    d = nh * dh
    z, i_p, f_p, o_p = pre[:, :d], pre[:, d:2*d], pre[:, 2*d:3*d], pre[:, 3*d:]
    z = jnp.tanh(z)
    o = jax.nn.sigmoid(o_p)
    logf = jax.nn.log_sigmoid(f_p)
    m_new = jnp.maximum(logf + m, i_p)
    i_w = jnp.exp(i_p - m_new)
    f_w = jnp.exp(logf + m - m_new)
    c = f_w * c + i_w * z
    n = f_w * n + i_w
    h = o * (c / jnp.maximum(n, 1.0))
    return (c, n, h, m_new)


def slstm_train(p, x, cfg: ModelConfig, *, return_state: bool = False,
                initial_state=None, n_valid=None):
    """``initial_state``/``n_valid``: resume from a carried {c,n,h,m} and
    skip state updates for pad positions (the paged engine's chunked
    prefill) — the recurrence is stepwise, so masking is exact."""
    b, s, d = x.shape
    nh = cfg.ssm.n_heads
    dh = d // nh
    wx = jnp.matmul(x, p["w_gates"].astype(x.dtype),
                    preferred_element_type=jnp.float32)
    # §Perf X1: the sLSTM recurrence is sequential with dense per-head
    # coupling — tensor-parallel state would need a collective every token
    # (32768 tiny all-to-alls per layer at prefill_32k). Replicate the gate
    # activations across the model axis ONCE, outside the scan; the cell is
    # then collective-free and the model axis idles through this (tiny) op.
    wx = constrain(wx, ("batch", "seq", None))
    if initial_state is not None:
        state0 = (initial_state["c"].astype(jnp.float32),
                  initial_state["n"].astype(jnp.float32),
                  initial_state["h"].astype(jnp.float32),
                  initial_state["m"].astype(jnp.float32))
    else:
        zeros = jnp.zeros((b, d), jnp.float32)
        state0 = (zeros, zeros, zeros, jnp.full((b, d), -1e30))
    state0 = jax.tree.map(lambda a: constrain(a, ("batch", None)), state0)
    valid = (jnp.arange(s) < n_valid) if n_valid is not None \
        else jnp.ones((s,), bool)

    def step(st, inp):
        wx_t, ok = inp
        new = _slstm_cell(p, wx_t, st, nh, dh)
        st = jax.tree.map(lambda nw, od: jnp.where(ok, nw, od), new, st)
        return st, st[2]

    st_f, hs = jax.lax.scan(step, state0, (jnp.moveaxis(wx, 1, 0), valid))
    h = jnp.moveaxis(hs, 0, 1).astype(x.dtype)
    y = L.dot(h, p["w_out"].astype(x.dtype))
    if return_state:
        c, n, hst, m = st_f
        return y, {"c": c, "n": n, "h": hst, "m": m}
    return y


def init_slstm_cache(cfg: ModelConfig, batch: int):
    d = cfg.d_model
    z = jnp.zeros((batch, d), jnp.float32)
    return {"c": z, "n": z.copy(), "h": z.copy(),
            "m": jnp.full((batch, d), -1e30, jnp.float32)}


def slstm_decode(p, cache, x, cfg: ModelConfig):
    nh = cfg.ssm.n_heads
    dh = cfg.d_model // nh
    wx = jnp.matmul(x, p["w_gates"].astype(x.dtype),
                    preferred_element_type=jnp.float32)
    st = (cache["c"], cache["n"], cache["h"], cache["m"])
    c, n, h, m = _slstm_cell(p, wx, st, nh, dh)
    y = L.dot(h.astype(x.dtype), p["w_out"].astype(x.dtype))
    return y, {"c": c, "n": n, "h": h, "m": m}
