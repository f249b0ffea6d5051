"""Language-model assembly for every assigned architecture family.

One parameter tree, three entry points:

  init(key, cfg)                                   -> params
  forward(params, tokens, cfg, ...)                -> logits       (train/eval)
  prefill(params, cfg, inputs)                     -> (logits, cache)
  decode_step(params, cfg, cache, token, pos_len)  -> (logits, cache)

Layers are stacked along a leading L axis and driven with ``lax.scan`` so the
lowered HLO stays compact regardless of depth (critical for the 512-device
dry-run compiles). Architectures whose layers are heterogeneous (xLSTM's
mLSTM/sLSTM mix) use a Python loop over per-layer param trees instead
(cfg-driven; these models are shallow).

Block composition per family:
  dense   : [attn, mlp]
  moe     : [attn, moe]
  hybrid  : [attn ∥ mamba, mlp]          (hymba: parallel heads, mean-fused)
  ssm     : [mlstm] or [slstm]           (xlstm; no attention at all)
  encdec  : encoder [attn, mlp] + decoder [attn, cross-attn, mlp]  (whisper)
  vlm     : dense backbone; vision patch embeddings prepended (llava)
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import layout
from repro.models import blocks as B
from repro.models import layers as L
from repro.serving import cache_spec as CS
# canonical layer-kind logic lives in the CacheSpec registry so the spec
# table and the model assembly can never disagree; re-exported here for the
# rest of the codebase (engine.py etc. call lm.uses_scan)
from repro.serving.cache_spec import layer_kind, uses_scan
from repro.sharding.rules import constrain


# --------------------------------------------------------------- init

def _is_slstm(cfg: ModelConfig, i: int) -> bool:
    return CS.is_slstm(cfg, i)


def init_layer(key, cfg: ModelConfig, kind: str):
    """kind: dense|moe|hybrid|mlstm|slstm|enc|dec"""
    ks = jax.random.split(key, 8)
    p: Dict[str, Any] = {}
    if kind in ("dense", "moe", "hybrid", "enc", "dec"):
        p["ln1"] = L.init_norm(cfg)
        p["attn"] = B.init_attention(ks[0], cfg)
        p["ln2"] = L.init_norm(cfg)
    if kind in ("dense", "hybrid", "enc", "dec"):
        p["mlp"] = L.init_mlp(ks[1], cfg)
    if kind == "moe":
        p["moe"] = B.init_moe(ks[1], cfg)
    if kind == "hybrid":
        p["ssm"] = B.init_mamba(ks[2], cfg)
        p["ln_ssm"] = L.init_norm(cfg)
    if kind == "mlstm":
        p["ln1"] = L.init_norm(cfg)
        p["ssm"] = B.init_mlstm(ks[0], cfg)
        p["ln2"] = L.init_norm(cfg)
        p["mlp"] = L.init_mlp(ks[1], cfg, d_ff=2 * cfg.d_model)
    if kind == "slstm":
        p["ln1"] = L.init_norm(cfg)
        p["ssm"] = B.init_slstm(ks[0], cfg)
        p["ln2"] = L.init_norm(cfg)
        p["mlp"] = L.init_mlp(ks[1], cfg, d_ff=2 * cfg.d_model)
    if kind == "dec" and cfg.is_encoder_decoder:
        p["ln_x"] = L.init_norm(cfg)
        p["xattn"] = B.init_attention(ks[3], cfg)
    return p


@functools.partial(jax.jit, static_argnums=1)
def init(key, cfg: ModelConfig):
    """Seeded random parameters. One jitted program: at full width the
    weights are drawn straight into ``cfg.param_dtype`` on the device,
    with no eager per-leaf float32 temporaries."""
    k_emb, k_layers, k_enc, k_out = jax.random.split(key, 4)
    params: Dict[str, Any] = {"embed": L.init_embed(k_emb, cfg)}
    if uses_scan(cfg):
        kind = layer_kind(cfg, 0)
        keys = jax.random.split(k_layers, cfg.n_layers)
        params["layers"] = jax.vmap(
            lambda k: init_layer(k, cfg, kind))(keys)
    else:
        keys = jax.random.split(k_layers, cfg.n_layers)
        params["layers"] = [init_layer(keys[i], cfg, layer_kind(cfg, i))
                            for i in range(cfg.n_layers)]
    if cfg.is_encoder_decoder:
        ekeys = jax.random.split(k_enc, cfg.enc_layers)
        params["enc_layers"] = jax.vmap(
            lambda k: init_layer(k, cfg, "enc"))(ekeys)
        params["enc_norm"] = L.init_norm(cfg)
    if cfg.vision_tokens:
        # stub frontend: a single linear adapter over precomputed patch
        # embeddings (anyres tiling & the ViT tower are out of scope — the
        # dry-run feeds ShapeDtypeStructs for the patch embeddings).
        params["vision_adapter"] = L.param(
            k_out, (cfg.d_model, cfg.d_model), cfg)
    params["final_norm"] = L.init_norm(cfg)
    return params


# ----------------------------------------------------- layer train fns

def _block_train(p, x, positions, cfg: ModelConfig, kind: str,
                 enc_out=None, capture=None):
    aux = jnp.float32(0.0)
    if kind in ("dense", "moe", "hybrid", "dec"):
        h = L.norm_apply(p["ln1"], x)
        a = B.attn_train(p["attn"], h, positions, cfg, capture=capture)
        if kind == "hybrid":
            s = B.mamba_train(p["ssm"], h, cfg)
            a = 0.5 * (L.norm_apply(p["ln_ssm"], a) +
                       L.norm_apply(p["ln_ssm"], s))
        x = x + a
        if kind == "dec" and cfg.is_encoder_decoder:
            h = L.norm_apply(p["ln_x"], x)
            q, _, _ = B._qkv(p["xattn"], h, cfg)
            from repro.core.attention import cross_attention
            ek, ev = enc_out
            o = cross_attention(q, ek, ev)
            b, s_ = h.shape[:2]
            x = x + L.dot(o.reshape(b, s_, cfg.q_dim),
                          p["xattn"]["wo"].astype(h.dtype))
        h = L.norm_apply(p["ln2"], x)
        if kind == "moe":
            y, aux = B.moe_apply(p["moe"], h, cfg)
        else:
            y = L.mlp_apply(p["mlp"], h, cfg)
        x = x + y
    elif kind in ("mlstm", "slstm"):
        h = L.norm_apply(p["ln1"], x)
        y = (B.mlstm_train(p["ssm"], h, cfg) if kind == "mlstm"
             else B.slstm_train(p["ssm"], h, cfg))
        x = x + y
        h = L.norm_apply(p["ln2"], x)
        x = x + L.mlp_apply(p["mlp"], h, cfg)
    return x, aux


def _encode(params, frames, cfg: ModelConfig):
    """Whisper-style encoder over precomputed frame embeddings.

    Returns per-layer-agnostic encoder output projected to (k, v) per decoder
    layer lazily (we return the hidden states; cross-attn projects)."""
    x = frames.astype(jnp.dtype(cfg.dtype))
    pos = jnp.arange(frames.shape[1])[None]
    x = x + _sinusoidal(frames.shape[1], cfg.d_model).astype(x.dtype)

    def body(x, p):
        h = L.norm_apply(p["ln1"], x)
        a = B.encoder_attn_train(p["attn"], h, pos, cfg)
        x = x + a
        h = L.norm_apply(p["ln2"], x)
        return x + L.mlp_apply(p["mlp"], h, cfg), None

    x, _ = jax.lax.scan(body, x, params["enc_layers"])
    return L.norm_apply(params["enc_norm"], x)


def _sinusoidal(s: int, d: int):
    import numpy as np
    pos = np.arange(s)[:, None]
    i = np.arange(d // 2)[None]
    ang = pos / np.power(10000.0, 2 * i / d)
    return jnp.asarray(
        np.concatenate([np.sin(ang), np.cos(ang)], axis=-1), jnp.float32)[None]


def _enc_kv(p_layer, enc_x, cfg: ModelConfig):
    """Project encoder hidden states to this decoder layer's cross (k, v)."""
    _, k, v = B._qkv(p_layer["xattn"], enc_x, cfg)
    return k, v


# --------------------------------------------------------------- forward

def forward(params, tokens, cfg: ModelConfig, *, frames=None, patches=None,
            remat: str = "none", capture_keys: bool = False):
    """Teacher-forced forward -> logits (B,S,V).

    frames: (B,enc_seq,d_model) whisper stub input.
    patches: (B,vision_tokens,d_model) llava stub input (prepended).
    capture_keys: also return (pre, post) rotary keys per layer for PCA
    calibration — (L,B,S,Hkv,D) each.
    """
    x = L.embed_apply(params["embed"], tokens, cfg)
    b, s = tokens.shape
    positions = jnp.arange(s)[None]
    if cfg.vision_tokens and patches is not None:
        vis = L.dot(patches.astype(x.dtype),
                    params["vision_adapter"].astype(x.dtype))
        x = jnp.concatenate([vis, x[:, : s - cfg.vision_tokens]], axis=1)
    if not cfg.rope and not cfg.is_encoder_decoder and cfg.family != "ssm":
        x = x + _sinusoidal(s, cfg.d_model).astype(x.dtype)
    if cfg.is_encoder_decoder:
        x = x + _sinusoidal(s, cfg.d_model).astype(x.dtype)

    enc_x = _encode(params, frames, cfg) if cfg.is_encoder_decoder else None

    captures = [] if capture_keys else None

    if uses_scan(cfg) and not capture_keys:
        kind = layer_kind(cfg, 0)

        def body(carry, p):
            x, aux = carry
            enc_out = _enc_kv(p, enc_x, cfg) if cfg.is_encoder_decoder else None
            x, a = _block_train(p, x, positions, cfg, kind, enc_out=enc_out)
            return (x, aux + a), None

        if remat != "none":
            policy = (jax.checkpoint_policies.nothing_saveable
                      if remat == "full"
                      else jax.checkpoint_policies.checkpoint_dots)
            body = jax.checkpoint(body, policy=policy)
        (x, aux), _ = jax.lax.scan(body, (x, jnp.float32(0.0)),
                                   params["layers"])
    else:
        aux = jnp.float32(0.0)
        layers = params["layers"]
        n = cfg.n_layers
        for i in range(n):
            if uses_scan(cfg):
                p = jax.tree.map(lambda a: a[i], layers)
                kind = layer_kind(cfg, 0)
            else:
                p = layers[i]
                kind = layer_kind(cfg, i)
            cap = {} if capture_keys and "attn" in p else None
            enc_out = _enc_kv(p, enc_x, cfg) if cfg.is_encoder_decoder else None
            x, a = _block_train(p, x, positions, cfg, kind,
                                enc_out=enc_out, capture=cap)
            aux = aux + a
            if cap is not None:
                captures.append(cap)

    x = L.norm_apply(params["final_norm"], x)
    logits = L.unembed_apply(params["embed"], x, cfg)
    if capture_keys:
        pre = jnp.stack([c["pre"] for c in captures]) if captures else None
        post = jnp.stack([c["post"] for c in captures]) if captures else None
        qs = jnp.stack([c["q"] for c in captures]) if captures else None
        return logits, aux, (pre, post, qs)
    return logits, aux


# --------------------------------------------------------------- caches

def init_cache(cfg: ModelConfig, batch: int, smax: int,
               dtype=jnp.bfloat16) -> Dict[str, Any]:
    """Stacked (L, ...) decode cache for the whole model."""
    def one(kind):
        c = {}
        if kind in ("dense", "moe", "hybrid", "dec"):
            c["attn"] = B.init_attn_cache(cfg, batch, smax, dtype)
        if kind == "hybrid":
            c["ssm"] = B.init_mamba_cache(cfg, batch, dtype)
        if kind == "mlstm":
            c["ssm"] = B.init_mlstm_cache(cfg, batch)
        if kind == "slstm":
            c["ssm"] = B.init_slstm_cache(cfg, batch)
        if kind == "dec" and cfg.is_encoder_decoder:
            hd = cfg.resolved_head_dim
            c["cross_k"] = jnp.zeros(
                (batch, cfg.enc_seq, cfg.n_kv_heads, hd), dtype)
            c["cross_v"] = jnp.zeros_like(c["cross_k"])
        return c

    if uses_scan(cfg):
        kind = layer_kind(cfg, 0)
        layer = one(kind)
        return {"layers": jax.tree.map(
            lambda a: jnp.broadcast_to(
                a, (cfg.n_layers,) + a.shape).copy(), layer)}
    return {"layers": [one(layer_kind(cfg, i)) for i in range(cfg.n_layers)]}


def init_paged_cache(cfg: ModelConfig, n_pages: int, page_size: int,
                     dtype=jnp.float32, n_slots: int = 1,
                     device_pages: Optional[int] = None) -> Dict[str, Any]:
    """Spec-driven paged decode cache for *every* family.

    Each layer's components come from the CacheSpec registry
    (serving/cache_spec.py):

      PagedAttn / WindowPagedAttn -> shared page pool (Hkv, W,
          n_pages * page_size) per layer, no batch dim (head-major,
          feature-major: paged_cache.py); requests map logical positions
          to pool rows through per-slot page tables.
      StateSlot -> per-slot recurrent state (n_slots, ...) carried across
          prefill chunks / decode steps; O(1) in request length.
      CrossAttnStatic -> per-slot encoder K/V (n_slots, enc_seq, Hkv, D)
          written once at admission.

    Pool memory scales with the page budget, not n_slots × smax.

    Physical layout is the component's ``PageLayout``: storage dtype, K
    feature width (latent rank under basis="pca") and — for quantized
    dtypes — per-page f32 ``k_scale``/``v_scale`` sidecars (one slot per
    physical page) living next to the pools. CrossAttnStatic carries one
    scale per *slot* (written once at admission). The ``dtype`` argument
    keeps its historical meaning for StateSlot components and for the
    default layout, so existing callers are bit-identical.

    ``device_pages`` (DESIGN.md §13) turns the pool tiered: the full-D
    K/V pools shrink to ``device_pages`` *frames* while an always-resident
    latent-K sidecar ``k_lat`` keeps the leading
    ``cache_spec.latent_score_width`` columns of every *logical* page's
    (PCA-rotated) keys, so Loki's approximate score pass never touches the
    host tier. Quantized layouts are rejected: their RMW store path
    re-derives per-page scales, which is not replay-idempotent under the
    tiered engine's optimistic-run/repair decode."""
    from repro.serving import paged_cache as PC
    CS.assert_pageable(cfg)
    specs = CS.layer_specs(cfg)
    r = n_pages * page_size
    rkv = (device_pages if device_pages is not None else n_pages) * page_size
    if device_pages is not None:
        if not (2 <= device_pages <= n_pages):
            raise ValueError(f"device_pages {device_pages} must be in "
                             f"[2, n_pages={n_pages}]")
        if cfg.page_layout.quantized:
            raise ValueError("tiered pools require a non-quantized "
                             "PageLayout (per-page scale RMW is not "
                             "replay-idempotent)")

    def pool_dtype(lay):
        # the default layout defers to the caller's dtype argument
        if lay == CS.PageLayout():
            return dtype
        return PC.STORAGE_DTYPE[lay.dtype]

    def one(spec: CS.LayerSpec) -> Dict[str, Any]:
        c: Dict[str, Any] = {}
        for name, comp in spec.components:
            if isinstance(comp, (CS.PagedAttn, CS.WindowPagedAttn)):
                lay = comp.layout
                pdt = pool_dtype(lay)
                # per-layer ranks: scan families stack every layer's pool
                # in one array, so allocate at the max width — narrower
                # layers zero-mask their tail dims at write time
                kw = (CS.max_k_width(cfg) if cfg.page_ranks is not None
                      else comp.k_width)
                hkv = comp.n_kv_heads
                c["attn"] = {
                    "k": jnp.zeros(layout.pool_shape(hkv, kw, rkv), pdt),
                    "v": jnp.zeros(layout.pool_shape(hkv, comp.head_dim, rkv),
                                   pdt)}
                if device_pages is not None:
                    c["attn"]["k_lat"] = jnp.zeros(
                        layout.pool_shape(hkv, CS.latent_score_width(cfg), r),
                        pdt)
                if lay.quantized:
                    c["attn"]["k_scale"] = jnp.zeros((n_pages,),
                                                     jnp.float32)
                    c["attn"]["v_scale"] = jnp.zeros((n_pages,),
                                                     jnp.float32)
            elif isinstance(comp, CS.StateSlot):
                c["ssm"] = CS.state_slot_init(cfg, comp, n_slots, dtype)
            elif isinstance(comp, CS.CrossAttnStatic):
                lay = comp.layout
                c["cross_k"] = jnp.zeros(
                    (n_slots, comp.enc_seq, comp.n_kv_heads,
                     comp.head_dim), pool_dtype(lay))
                c["cross_v"] = jnp.zeros_like(c["cross_k"])
                if lay.quantized:
                    c["cross_k_scale"] = jnp.zeros((n_slots,),
                                                   jnp.float32)
                    c["cross_v_scale"] = jnp.zeros((n_slots,),
                                                   jnp.float32)
        return c

    if uses_scan(cfg):
        layer = one(specs[0])
        return {"layers": jax.tree.map(
            lambda a: jnp.broadcast_to(
                a, (cfg.n_layers,) + a.shape).copy(), layer)}
    return {"layers": [one(s) for s in specs]}


# --------------------------------------------------------------- decode

@jax.named_scope("pool")
def _at_layer(tree, layer):
    return jax.tree.map(lambda a: layout.layer_pool(a, layer), tree)


@jax.named_scope("pool")
def _put_layer(tree, one, layer):
    return jax.tree.map(
        lambda a, o: jax.lax.dynamic_update_index_in_dim(
            a, o.astype(a.dtype), layer, 0), tree, one)


def _layer_decode(p, c, x, pos_len, cfg: ModelConfig, kind: str, *,
                  page_table=None, page_size: int = 0, live=None,
                  frame_table=None, rank=None, sliding_window=None,
                  layer=None):
    """One layer's decode; ``layer`` (traced int32) hands ``c["attn"]`` as
    every layer's stacked pools, decoded in place (``B.attn_decode``)."""
    def keep_live(new, old):
        """StateSlot protection for the batched paged tick: slots that are
        idle or mid-prefill must not have their carried recurrent state
        advanced by the unconditional batched decode (their K/V writes
        already land in the trash page; state has no trash row)."""
        if live is None:
            return new
        return jax.tree.map(
            lambda nw, od: jnp.where(
                live.reshape((-1,) + (1,) * (nw.ndim - 1)), nw, od),
            new, old)

    win = None
    if kind in ("dense", "moe", "hybrid", "dec"):
        with jax.named_scope("attn"):
            h = L.norm_apply(p["ln1"], x)
            if frame_table is not None:
                a, new_attn, win = B.attn_decode(p["attn"], c["attn"], h,
                                                 pos_len, cfg,
                                                 page_table=page_table,
                                                 page_size=page_size,
                                                 frame_table=frame_table,
                                                 rank=rank)
            else:
                a, new_attn = B.attn_decode(p["attn"], c["attn"], h,
                                            pos_len, cfg,
                                            page_table=page_table,
                                            page_size=page_size, rank=rank,
                                            sliding_window=sliding_window,
                                            layer=layer)
            c = dict(c)
            c["attn"] = new_attn
            if kind == "hybrid":
                s, new_ssm = B.mamba_decode(p["ssm"], c["ssm"], h, cfg)
                c["ssm"] = keep_live(new_ssm, c["ssm"])
                a = 0.5 * (L.norm_apply(p["ln_ssm"], a) +
                           L.norm_apply(p["ln_ssm"], s))
            x = x + a
            if kind == "dec" and cfg.is_encoder_decoder:
                h = L.norm_apply(p["ln_x"], x)
                from repro.core.attention import decode_full
                q, _, _ = B._qkv(p["xattn"], h[:, None], cfg)
                ck, cv = c["cross_k"], c["cross_v"]
                if "cross_k_scale" in c:  # quantized CrossAttnStatic pages
                    ck = ck.astype(jnp.float32) \
                        * c["cross_k_scale"][:, None, None, None]
                    cv = cv.astype(jnp.float32) \
                        * c["cross_v_scale"][:, None, None, None]
                o = decode_full(q[:, 0], ck, cv, jnp.int32(ck.shape[1]))
                x = x + L.dot(o.reshape(x.shape[0], cfg.q_dim),
                              p["xattn"]["wo"].astype(x.dtype))
        with jax.named_scope("mlp"):
            h = L.norm_apply(p["ln2"], x)
            y = (B.moe_decode(p["moe"], h, cfg) if kind == "moe"
                 else L.mlp_apply(p["mlp"], h, cfg))
            x = x + y
    else:
        with jax.named_scope("attn"):
            h = L.norm_apply(p["ln1"], x)
            fn = B.mlstm_decode if kind == "mlstm" else B.slstm_decode
            y, new_ssm = fn(p["ssm"], c["ssm"], h, cfg)
            c = dict(c)
            c["ssm"] = keep_live(new_ssm, c["ssm"])
            x = x + y
        with jax.named_scope("mlp"):
            h = L.norm_apply(p["ln2"], x)
            x = x + L.mlp_apply(p["mlp"], h, cfg)
    return x, c, win


# cache keys whose leading (post-L) axis is the *slot* axis — everything
# else in a paged cache is pooled (no batch dim) and shared by all slots
_SLOT_KEYS = ("ssm", "cross_k", "cross_v", "cross_k_scale", "cross_v_scale")


@jax.named_scope("pool")
def _slot_gather(layers, sidx, scan: bool):
    """Compact the per-slot cache components to the packed batch: leaf
    [n_slots] rows -> [n_live] rows at ``sidx``. Pooled attn leaves pass
    through untouched (they carry no slot axis)."""
    ax = 1 if scan else 0

    def g(tree):
        return jax.tree.map(lambda a: jnp.take(a, sidx, axis=ax), tree)

    if scan:
        return {k: (g(v) if k in _SLOT_KEYS else v)
                for k, v in layers.items()}
    return [{k: (g(v) if k in _SLOT_KEYS else v) for k, v in lc.items()}
            for lc in layers]


@jax.named_scope("pool")
def _slot_scatter(full_layers, packed_layers, sidx, scan: bool):
    """Merge a packed decode's cache back into the full-width cache.

    Recurrent state (``ssm``) scatters to its slots — sound because the
    packed batch holds *distinct* slot ids. Cross K/V is read-only during
    decode, so the original leaves are kept (no copy). Pooled attn leaves
    come from the packed run verbatim: page-table indirection already
    landed their writes at the right physical rows."""
    def sc(full, pk, ax):
        idx = (slice(None), sidx) if ax else sidx
        return jax.tree.map(
            lambda f, p: f.at[idx].set(p.astype(f.dtype)), full, pk)

    def merge(full_lc, packed_lc, ax):
        out = dict(packed_lc)
        for k in _SLOT_KEYS:
            if k not in full_lc:
                continue
            out[k] = (sc(full_lc[k], packed_lc[k], ax) if k == "ssm"
                      else full_lc[k])
        return out

    if scan:
        return merge(full_layers, packed_layers, 1)
    return [merge(f, p, 0) for f, p in zip(full_layers, packed_layers)]


def decode_step(params, cfg: ModelConfig, cache, token, pos_len, *,
                page_table=None, page_size: int = 0, live=None,
                frame_table=None, slot_idx=None):
    """One generation step. token (B,) int32; pos_len (B,) tokens cached.

    Returns (logits (B,V), new_cache). With ``page_table (B, max_pages)``/
    ``page_size`` the cache is the pooled layout of ``init_paged_cache``
    and every layer's attention reads/writes resolve through the table.
    ``live (B,)`` bool: slots marked dead keep their StateSlot components
    (recurrent state / cross K/V are per-slot, with no trash row to divert
    writes to).

    ``slot_idx (B,)`` int32 — gather-packed decode: the batch rows are a
    *compaction* of the cache's slot axis (distinct slot ids; token /
    pos_len / live / page_table rows arrive pre-packed by the scheduler).
    Per-slot components are gathered to the packed batch before the layer
    stack and the advanced recurrent state is scattered back after, so
    decode FLOPs scale with live slots instead of engine capacity while
    the cache keeps its full-width layout.

    With ``cfg.window_layers`` (per-layer SWA/full mixes) the layer stack
    unrolls so each layer gets its *static* window, and a rank-3
    ``page_table (B, n_groups, max_pages)`` carries one table row per
    page-table group (cache_spec.layer_group_ids picks each layer's row).

    ``frame_table (B, max_pages)`` (tiered pools, DESIGN.md §13) maps each
    logical table entry to its device frame (0 = trash frame for HOST
    pages). The return becomes (logits, winners, new_cache) where
    ``winners (B, max_pages)`` bool is the union over layers of logical
    pages the Loki selection attended — the scheduler promotes HOST
    winners and replays.

    The scan families carry the stacked cache through the layer loop
    (the weights are its ``xs``): each layer's K/V rows are written into
    the stack and the attention reads its layer there, so a paged float
    pool is never copied, relaid out or restacked on the Pallas route.

    Device scopes (``jax.named_scope``, in the ops' metadata): ``pool``
    moves the page pool (the layer loop, whose own ops are the weights'
    per-layer slices, the packed slot gather/scatter, K/V row writes,
    per-layer slices of the components not written in place, and logical
    gathers),
    ``attn`` the token mixer (norm, QKV, rope, PCA projection, the
    attention kernel or its jnp route, output projection), ``mlp`` the
    feed-forward, ``head`` the embedding lookup, final norm and LM head."""
    with jax.named_scope("head"):
        x = L.embed_apply(params["embed"], token[:, None], cfg)[:, 0]
        if not cfg.rope and cfg.family != "ssm":
            # sinusoidal decoders: add position encoding for the current
            # slot
            d = cfg.d_model
            x = x + _sinusoidal_at(pos_len, d).astype(x.dtype)

    tiered = frame_table is not None
    ranks = None
    if cfg.page_ranks is not None and page_table is not None:
        ranks = jnp.asarray(cfg.page_ranks, jnp.int32)

    scan = uses_scan(cfg)
    hetero = cfg.window_layers is not None and scan
    if tiered and hetero:
        raise ValueError("tiered pools do not compose with per-layer "
                         "window groups (window_layers)")
    packed = slot_idx is not None
    layers_in = cache["layers"]
    if packed:
        sidx = jnp.asarray(slot_idx, jnp.int32)
        layers_in = _slot_gather(layers_in, sidx, scan)

    if scan and not hetero:
        kind = layer_kind(cfg, 0)
        xs = (params["layers"], jnp.arange(cfg.n_layers, dtype=jnp.int32))
        if ranks is not None:
            xs = xs + (ranks,)
        # one-tier float page pools are decoded in place in the stack;
        # every other component (recurrent state, cross K/V, quantized or
        # tiered pools, contiguous caches) has its layer sliced out and
        # put back into the carry
        in_place = (page_table is not None and not tiered
                    and "attn" in layers_in
                    and "k_scale" not in layers_in["attn"])
        own = {k: in_place and k == "attn" for k in layers_in}

        def body(carry, pc):
            x, layers, win = carry
            lyr = pc[1]
            c = {k: v if own[k] else _at_layer(v, lyr)
                 for k, v in layers.items()}
            x, c, w = _layer_decode(pc[0], c, x, pos_len, cfg, kind,
                                    page_table=page_table,
                                    page_size=page_size, live=live,
                                    frame_table=frame_table,
                                    rank=pc[2] if len(pc) > 2 else None,
                                    layer=lyr if in_place else None)
            layers = {k: v if own[k] else _put_layer(layers[k], v, lyr)
                      for k, v in c.items()}
            return (x, layers, win | w if tiered else None), None

        # the loop's own ops (the weights' per-layer slices) are the
        # pool's; the body's ops carry their inner scopes
        with jax.named_scope("pool"):
            win0 = jnp.zeros(page_table.shape, bool) if tiered else None
            (x, layers, win), _ = jax.lax.scan(body, (x, layers_in, win0),
                                               xs)
        new_cache = {"layers": layers}
    elif hetero:
        # per-layer static windows: unroll over the stacked leaves so each
        # layer's mask/kernel window and page-table group row are compile-
        # time constants (these models are shallow; the scan families'
        # compact-HLO concern doesn't bite)
        win = None
        gids = CS.layer_group_ids(cfg)
        kind = layer_kind(cfg, 0)
        new_layers = layers_in
        for i in range(cfg.n_layers):
            p = jax.tree.map(lambda a: a[i], params["layers"])
            c = _at_layer(new_layers, i)
            pt_i = page_table
            if page_table is not None and page_table.ndim == 3:
                pt_i = page_table[:, gids[i]]
            x, c, _ = _layer_decode(
                p, c, x, pos_len, cfg, kind,
                page_table=pt_i, page_size=page_size, live=live,
                rank=None if ranks is None else ranks[i],
                sliding_window=cfg.layer_window(i))
            new_layers = _put_layer(new_layers, c, i)
        new_cache = {"layers": new_layers}
    else:
        # non-scan families (xlstm) have no paged attention: no tiering
        win = None
        new_list = []
        x_cur = x
        for i in range(cfg.n_layers):
            x_cur, c, _ = _layer_decode(params["layers"][i],
                                        layers_in[i],
                                        x_cur, pos_len, cfg,
                                        layer_kind(cfg, i),
                                        page_table=page_table,
                                        page_size=page_size, live=live)
            new_list.append(c)
        x = x_cur
        new_cache = {"layers": new_list}

    if packed:
        new_cache = {"layers": _slot_scatter(cache["layers"],
                                             new_cache["layers"],
                                             sidx, scan)}
    with jax.named_scope("head"):
        x = L.norm_apply(params["final_norm"], x)
        logits = L.unembed_apply(params["embed"], x[:, None], cfg)[:, 0]
    if tiered:
        return logits, win, new_cache
    return logits, new_cache


def _sinusoidal_at(pos, d):
    import numpy as np
    i = jnp.arange(d // 2)[None]
    ang = pos[:, None].astype(jnp.float32) / jnp.power(
        10000.0, 2 * i / d)
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def prefill(params, cfg: ModelConfig, tokens, smax: int, *, frames=None,
            patches=None, cache_dtype=jnp.bfloat16):
    """Process a prompt, returning (logits_last (B,V), cache, pos_len)."""
    b, s = tokens.shape
    cache = init_cache(cfg, b, smax, cache_dtype)
    x = L.embed_apply(params["embed"], tokens, cfg)
    positions = jnp.arange(s)[None]
    if cfg.vision_tokens and patches is not None:
        vis = L.dot(patches.astype(x.dtype),
                    params["vision_adapter"].astype(x.dtype))
        x = jnp.concatenate([vis, x[:, : s - cfg.vision_tokens]], axis=1)
    if (not cfg.rope or cfg.is_encoder_decoder) and cfg.family != "ssm":
        x = x + _sinusoidal(s, cfg.d_model).astype(x.dtype)
    enc_x = _encode(params, frames, cfg) if cfg.is_encoder_decoder else None

    if uses_scan(cfg):
        kind = layer_kind(cfg, 0)

        def body(carry, pc):
            x = carry
            p, c = pc
            h = L.norm_apply(p["ln1"], x)
            if kind in ("dense", "moe", "hybrid", "dec"):
                a, new_attn = B.attn_prefill(p["attn"], c["attn"], h,
                                             positions, cfg)
                c = dict(c)
                c["attn"] = new_attn
                if kind == "hybrid":
                    sy, xz_states = _mamba_prefill(p["ssm"], h, cfg)
                    c["ssm"] = xz_states
                    a = 0.5 * (L.norm_apply(p["ln_ssm"], a) +
                               L.norm_apply(p["ln_ssm"], sy))
                x = x + a
                if kind == "dec" and cfg.is_encoder_decoder:
                    ek, ev = _enc_kv(p, enc_x, cfg)
                    c["cross_k"] = ek.astype(c["cross_k"].dtype)
                    c["cross_v"] = ev.astype(c["cross_v"].dtype)
                    hx = L.norm_apply(p["ln_x"], x)
                    q, _, _ = B._qkv(p["xattn"], hx, cfg)
                    from repro.core.attention import cross_attention
                    o = cross_attention(q, ek, ev)
                    x = x + L.dot(o.reshape(b, s, cfg.q_dim),
                                  p["xattn"]["wo"].astype(x.dtype))
                h = L.norm_apply(p["ln2"], x)
                if kind == "moe":
                    y, _ = B.moe_apply(p["moe"], h, cfg)
                else:
                    y = L.mlp_apply(p["mlp"], h, cfg)
                x = x + y
            return x, c

        x, new_layers = jax.lax.scan(body, x,
                                     (params["layers"], cache["layers"]))
        cache = {"layers": new_layers}
    else:
        # ssm family: prefill == run the recurrence, keep final states.
        # The train-path scans already carry exactly the decode state, so we
        # take their final carry instead of re-scanning the prompt through
        # the decode cell token-by-token (§Perf X2: removes a 32768-step
        # while loop and its per-step collectives per layer).
        for i in range(cfg.n_layers):
            kind = layer_kind(cfg, i)
            p = params["layers"][i]
            h = L.norm_apply(p["ln1"], x)
            fn = B.mlstm_train if kind == "mlstm" else B.slstm_train
            y, st = fn(p["ssm"], h, cfg, return_state=True)
            cache["layers"][i]["ssm"] = st
            x = x + y
            h2 = L.norm_apply(p["ln2"], x)
            x = x + L.mlp_apply(p["mlp"], h2, cfg)

    x = L.norm_apply(params["final_norm"], x[:, -1:])
    logits = L.unembed_apply(params["embed"], x, cfg)[:, 0]
    pos_len = jnp.full((b,), s, jnp.int32)
    return logits, cache, pos_len


def prefill_chunk(params, cfg: ModelConfig, cache, tokens, pos_start,
                  n_valid, page_table, page_size: int, *, slot=None,
                  frame_row=None):
    """One step of a paged, chunked prefill for a single request — driven
    by the CacheSpec table, so every family serves through it.

    tokens (1, C) — a fixed-size chunk whose first ``n_valid`` entries are
    real prompt tokens at logical positions ``pos_start .. pos_start+C-1``
    (the rest is zero padding, written to the trash page). Per component:

      PagedAttn/WindowPagedAttn — the chunk's K/V scatter through
          ``page_table`` ((1, max_pages) or (max_pages,)) into the shared
          pool; attention runs causally over the cached prefix plus the
          chunk (blocks.attn_prefill_chunk, exact via Lemma 4.1).
      StateSlot — the slot's recurrent state (mamba / mLSTM / sLSTM) is
          carried across chunks: pad tokens leave it untouched, so chunked
          prefill reproduces the one-shot recurrence exactly.
      CrossAttnStatic — read-only (written at admission); the chunk's
          cross-attention queries attend the slot's full encoder K/V.

    Returns (logits (1, V) for token ``n_valid - 1`` of the chunk,
    new_cache). ``pos_start``/``n_valid``/``slot`` are traced scalars —
    one trace serves every chunk of every request in any slot.

    Prefix caching (state-free families): chunks fully covered by cached
    pages are *skipped entirely* — the scheduler starts the query stream
    at the first uncached token, so the first call may have ``pos_start``
    anywhere in the prompt over a table whose earlier entries are shared
    physical pages. This composes with chunking because cached pages
    already hold storage-basis keys: the prefix scores below are taken in
    that basis regardless of who wrote the rows (Lemma 4.1 — scoring is
    unaffected), so a cache-hit run is exact, not approximate.

    ``frame_row (max_pages,)`` (tiered pools): device frame of each table
    entry. Prefill is exact attention over the whole prefix, so the
    scheduler promotes *all* of the slot's pages before each chunk; here
    the frame row simply redirects the K/V writes and gathers while the
    latent sidecar is written through the logical ``table_row``.

    Device scopes as in ``decode_step`` (``pool`` also takes the slot's
    recurrent-state slice and update)."""
    CS.assert_pageable(cfg)
    if cfg.window_layers is not None:
        # per-layer table groups: the table is (n_groups, max_pages) (or
        # batch-1 of it); each layer slices its group's row below
        table_row = page_table[0] if page_table.ndim == 3 else page_table
    else:
        table_row = page_table[0] if page_table.ndim == 2 else page_table
    if frame_row is not None and frame_row.ndim == 2:
        frame_row = frame_row[0]
    ranks = (jnp.asarray(cfg.page_ranks, jnp.int32)
             if cfg.page_ranks is not None else None)
    slot = jnp.int32(0) if slot is None else jnp.asarray(slot, jnp.int32)
    b, c = tokens.shape
    positions = pos_start + jnp.arange(c)
    with jax.named_scope("head"):
        x = L.embed_apply(params["embed"], tokens, cfg)
        if (not cfg.rope or cfg.is_encoder_decoder) \
                and cfg.family != "ssm":
            x = x + _sinusoidal_at(positions, cfg.d_model)[None].astype(
                x.dtype)

    @jax.named_scope("pool")
    def slot_take(a):
        return jax.lax.dynamic_slice_in_dim(a, slot, 1, axis=0)

    @jax.named_scope("pool")
    def slot_put(full, one):
        return jax.lax.dynamic_update_slice_in_dim(
            full, one.astype(full.dtype), slot, axis=0)

    if uses_scan(cfg):
        kind = layer_kind(cfg, 0)

        def body_at(x, p, cc, rk, trow, sw):
            cc = dict(cc)
            with jax.named_scope("attn"):
                h = L.norm_apply(p["ln1"], x)
                a, new_attn = B.attn_prefill_chunk(
                    p["attn"], cc["attn"], h, pos_start, n_valid, cfg,
                    table_row=trow, page_size=page_size,
                    frame_row=frame_row, rank=rk, sliding_window=sw)
                cc["attn"] = new_attn
                if kind == "hybrid":
                    st = jax.tree.map(slot_take, cc["ssm"])
                    sy, new_st = B.mamba_prefill_chunk(p["ssm"], st, h,
                                                       n_valid, cfg)
                    cc["ssm"] = jax.tree.map(slot_put, cc["ssm"], new_st)
                    a = 0.5 * (L.norm_apply(p["ln_ssm"], a) +
                               L.norm_apply(p["ln_ssm"], sy))
                x = x + a
                if kind == "dec" and cfg.is_encoder_decoder:
                    ek = slot_take(cc["cross_k"])
                    ev = slot_take(cc["cross_v"])
                    if "cross_k_scale" in cc:
                        ek = ek.astype(jnp.float32) * slot_take(
                            cc["cross_k_scale"])[:, None, None, None]
                        ev = ev.astype(jnp.float32) * slot_take(
                            cc["cross_v_scale"])[:, None, None, None]
                    ek, ev = ek.astype(x.dtype), ev.astype(x.dtype)
                    hx = L.norm_apply(p["ln_x"], x)
                    q, _, _ = B._qkv(p["xattn"], hx, cfg)
                    from repro.core.attention import cross_attention
                    o = cross_attention(q, ek, ev)
                    x = x + L.dot(o.reshape(b, c, cfg.q_dim),
                                  p["xattn"]["wo"].astype(x.dtype))
            with jax.named_scope("mlp"):
                h = L.norm_apply(p["ln2"], x)
                if kind == "moe":
                    y, _ = B.moe_apply(p["moe"], h, cfg)
                else:
                    y = L.mlp_apply(p["mlp"], h, cfg)
                y = x + y
            return y, cc

        if cfg.window_layers is not None:
            # unrolled: each layer's window is static and its K/V scatter
            # goes through its page-table group's row
            gids = CS.layer_group_ids(cfg)
            new_layers = cache["layers"]
            for i in range(cfg.n_layers):
                p = jax.tree.map(lambda a: a[i], params["layers"])
                with jax.named_scope("pool"):
                    cc = jax.tree.map(lambda a: a[i], new_layers)
                trow = (table_row[gids[i]] if table_row.ndim == 2
                        else table_row)
                x, cc = body_at(x, p, cc,
                                None if ranks is None else ranks[i],
                                trow, cfg.layer_window(i))
                with jax.named_scope("pool"):
                    new_layers = jax.tree.map(
                        lambda full, one:
                        jax.lax.dynamic_update_index_in_dim(
                            full, one.astype(full.dtype), i, 0),
                        new_layers, cc)
            new_cache = {"layers": new_layers}
        else:
            xs = (params["layers"], cache["layers"])
            if ranks is not None:
                xs = xs + (ranks,)

            def body(x, pc):
                rk = pc[2] if len(pc) > 2 else None
                return body_at(x, pc[0], pc[1], rk, table_row, None)

            # the scan's per-layer cache slicing and restacking
            with jax.named_scope("pool"):
                x, new_layers = jax.lax.scan(body, x, xs)
            new_cache = {"layers": new_layers}
    else:
        # ssm family (xlstm): no pages at all — the chunk runs the
        # recurrences from the slot's carried state, masking pad tokens
        new_list = []
        for i in range(cfg.n_layers):
            kind = layer_kind(cfg, i)
            p = params["layers"][i]
            cc = dict(cache["layers"][i])
            st = jax.tree.map(slot_take, cc["ssm"])
            with jax.named_scope("attn"):
                h = L.norm_apply(p["ln1"], x)
                fn = B.mlstm_train if kind == "mlstm" else B.slstm_train
                y, new_st = fn(p["ssm"], h, cfg, return_state=True,
                               initial_state=st, n_valid=n_valid)
                x = x + y
            cc["ssm"] = jax.tree.map(slot_put, cc["ssm"], new_st)
            new_list.append(cc)
            with jax.named_scope("mlp"):
                h2 = L.norm_apply(p["ln2"], x)
                x = x + L.mlp_apply(p["mlp"], h2, cfg)
        new_cache = {"layers": new_list}

    with jax.named_scope("head"):
        x_last = jax.lax.dynamic_slice_in_dim(x, n_valid - 1, 1, axis=1)
        x_last = L.norm_apply(params["final_norm"], x_last)
        logits = L.unembed_apply(params["embed"], x_last, cfg)[:, 0]
    return logits, new_cache


def copy_cache_page(cfg: ModelConfig, cache, src_page, dst_page,
                    page_size: int, src_frame=None, dst_frame=None):
    """Copy-on-write over a paged cache: duplicate physical page ``src``'s
    rows into ``dst`` in every paged-attention layer's K and V pool.

    The scheduler calls this when a request sharing a cached tail page
    must diverge (its next token lands mid-page in rows another request /
    the prefix index still reads): the rows read so far move to a private
    page, the table entry is repointed, and only then does the request
    write. ``src_page``/``dst_page`` are traced scalars — one trace serves
    every COW.

    Tiered pools: the full-D K/V rows live at ``src_frame``/``dst_frame``
    (both pages must be RESIDENT) while the latent sidecar copies by
    logical page id."""
    from repro.serving import paged_cache as PC
    src = jnp.asarray(src_page, jnp.int32)
    dst = jnp.asarray(dst_page, jnp.int32)

    def cp(attn):
        if src_frame is not None:
            sf = jnp.asarray(src_frame, jnp.int32)
            df = jnp.asarray(dst_frame, jnp.int32)
            return {"k": PC.copy_page_rows(attn["k"], sf, df, page_size),
                    "v": PC.copy_page_rows(attn["v"], sf, df, page_size),
                    "k_lat": PC.copy_page_rows(attn["k_lat"], src, dst,
                                               page_size)}
        out = {"k": PC.copy_page_rows(attn["k"], src, dst, page_size),
               "v": PC.copy_page_rows(attn["v"], src, dst, page_size)}
        if "k_scale" in attn:   # quantized layout: the codes only stay a
            # faithful dequant of the donor if the scale rides along
            out["k_scale"] = PC.copy_page_scale(attn["k_scale"], src, dst)
            out["v_scale"] = PC.copy_page_scale(attn["v_scale"], src, dst)
        return out

    if uses_scan(cfg):
        layers = dict(cache["layers"])
        if "attn" in layers:
            # (L, Hkv, W, R): vmap the row copy over the stacked layer axis
            layers["attn"] = jax.vmap(cp)(layers["attn"])
        return {"layers": layers}
    out = []
    for lc in cache["layers"]:
        if "attn" in lc:
            lc = {**lc, "attn": cp(lc["attn"])}
        out.append(lc)
    return {"layers": out}


def promote_page_rows(cfg: ModelConfig, cache, k_rows, v_rows, frame,
                      page_size: int):
    """Land a promoted page's host-tier full-D rows in its staging frame
    (tiered pools, DESIGN.md §13). ``k_rows (L, Hkv, kw, page_size)`` /
    ``v_rows (L, Hkv, D, page_size)`` are the bytes captured at demotion;
    ``frame`` is the frame ``PagePool.promote_begin`` handed out. The
    latent sidecar is untouched — it never left the device."""
    layers = dict(cache["layers"])
    attn = dict(layers["attn"])
    row = jnp.asarray(frame, jnp.int32) * page_size

    attn["k"] = layout.put_rows(attn["k"], k_rows, row)
    attn["v"] = layout.put_rows(attn["v"], v_rows, row)
    layers["attn"] = attn
    return {"layers": layers}


def encode_cross_kv(params, cfg: ModelConfig, frames):
    """Encoder K/V for every decoder layer (the CrossAttnStatic component).

    Runs the encoder once over ``frames (B, enc_seq, d_model)`` and
    projects the hidden states with each decoder layer's cross-attention
    weights. Returns (k, v), each (L, B, enc_seq, Hkv, D) — written into a
    request's slot once at admission by the paged engine."""
    enc_x = _encode(params, frames, cfg)

    def body(carry, p):
        k, v = _enc_kv(p, enc_x, cfg)
        return carry, (k, v)

    _, (ks, vs) = jax.lax.scan(body, 0, params["layers"])
    return ks, vs


def _mamba_prefill(p, x, cfg):
    s = cfg.ssm
    b = x.shape[0]
    d_in = s.expand * cfg.d_model
    xz = L.dot(x, p["in_proj"].astype(x.dtype))
    conv0 = jnp.zeros((b, s.conv_width - 1, d_in), x.dtype)
    ssm0 = jnp.zeros((b, d_in, s.state_dim), jnp.float32)
    y, conv, ssm = B._mamba_scan(p, xz, conv0, ssm0, cfg)
    y = L.dot(y, p["out_proj"].astype(x.dtype))
    return y, {"conv": conv, "ssm": ssm}

