"""Plain reference of the Qwen2 architecture served with block-granular Loki
decode attention, in float32 jax.numpy at highest matmul precision.

It imports nothing of the program. It reads the weights the benchmark
made (``bench/lib/weights.py``) by their leaf names, and the PCA
projections it calibrates itself (``calibrate``). It also tells the
harness what a Qwen2 configuration file asks of the program: the widths
it sets (``program_fields``), where the program would depart from it
(``departures``) and how each weight is drawn (``LEAVES``).

The published architecture (Qwen2, ``config.json``): token embedding;
per layer RMSNorm, q/k/v projections with bias, rotary position embedding
(theta ``rope_theta``, half-split rotation), grouped-query attention,
output projection, residual; RMSNorm, SiLU-gated MLP, residual; final
RMSNorm and the vocabulary head (the embedding table, tied).

Served attention: a prompt's tokens but the last attend causally over
the whole prefix (the engine's exact chunked prefill). Every later
position is a decode step with Loki (paper Algorithm 1) at block
granularity, as the engine's ``loki_block`` policy serves it:

  * queries and keys rotate into the PCA basis P of their KV head;
  * approximate scores q̂[:d]·k̂[:d] / sqrt(D) over the live positions,
    the last ``local_window`` of them raised by 1e4 so they always win;
  * block maxima over ``page_size`` positions and over the query heads
    of the KV group; the top ``max(int(k_f * smax / page_size), 1)``
    blocks (ties to the lower index) are selected for the whole group;
  * exact softmax attention over the live positions of those blocks.

The whole sequence (prompt and served tokens) runs teacher-forced, layer
by layer, with attention in blocks of rows, so it fits beside the weights
on one chip. ``prec="fp8"`` is the benchmark's control: the same
computation with every matmul operand rounded to float8 e4m3 under a
per-tensor (weights) or per-row (activations) scale.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib import weights as W

HIGHEST = jax.lax.Precision.HIGHEST
NEG_INF = -1e30
ROWS = 256          # query rows per attention block
PAD = 1024          # sequences pad to a multiple of this (fewer programs)
VOCAB_CHUNKS = 8    # the head reads its table in this many slices


# how each leaf of the program's parameter tree is drawn, by its name
LEAVES = {
    "table": W.fan_in(-1),                        # (vocab, d_model)
    **{m: W.fan_in(-2) for m in                   # (L, fan_in, fan_out)
       ("wq", "wk", "wv", "wo", "w_in", "w_out")},
    **{b: W.bias for b in ("bq", "bk", "bv")},
    "scale": W.norm_scale,                        # RMSNorm gains
    "pca": W.identity,                            # until calibrated
}


def program_fields(cfg: dict, resolved) -> Dict[str, Tuple[str, object]]:
    """The fields of the program's model config that the file sets, each
    as (the file's key, value). ``resolved`` is the program's own config
    of the architecture; an RMSNorm epsilon is set where it has one."""
    out = {"n_layers": ("num_hidden_layers", cfg["num_hidden_layers"]),
           "d_model": ("hidden_size", cfg["hidden_size"]),
           "n_heads": ("num_attention_heads", cfg["num_attention_heads"]),
           "n_kv_heads": ("num_key_value_heads",
                          cfg["num_key_value_heads"]),
           "d_ff": ("intermediate_size", cfg["intermediate_size"]),
           "vocab": ("vocab_size", cfg["vocab_size"]),
           "rope_theta": ("rope_theta", float(cfg["rope_theta"]))}
    if hasattr(resolved, "norm_eps"):
        out["norm_eps"] = ("rms_norm_eps", float(cfg["rms_norm_eps"]))
    return out


def departures(pc, cfg: dict, norm_eps: float) -> List[str]:
    """Where the program's model config ``pc``, serving with RMSNorm
    epsilon ``norm_eps``, is not the Qwen2 of the file."""
    out = []
    if not (pc.qkv_bias and pc.mlp == "swiglu" and pc.norm == "rms"
            and pc.rope):
        out.append("not Qwen2's block (qkv bias, SwiGLU, RMSNorm, rope)")
    if pc.resolved_head_dim * pc.n_heads != pc.d_model:
        out.append(f"head_dim {pc.resolved_head_dim} x {pc.n_heads} "
                   f"heads is not d_model {pc.d_model}")
    if norm_eps != float(cfg["rms_norm_eps"]):
        out.append(f"RMSNorm epsilon {norm_eps}, the file "
                   f"{cfg['rms_norm_eps']}")
    if not cfg["tie_word_embeddings"]:
        out.append("the program's embedding table is its head; the file "
                   "unties them")
    return out


def _fp8(x, axis):
    """Round to float8 e4m3 under a scale that maps the largest magnitude
    along ``axis`` (None: the whole tensor) to the format's maximum."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    s = jnp.maximum(amax, 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(prec, a, w):
    """a (..., k) @ w (k, n) in float32; operands rounded first under fp8."""
    a = a.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if prec == "fp8":
        a, w = _fp8(a, -1), _fp8(w, None)
    return jnp.matmul(a, w, precision=HIGHEST)


def _ein(prec, spec, a, b):
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if prec == "fp8":
        a, b = _fp8(a, -1), _fp8(b, -1)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _rms(x, scale, eps):
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + eps) * scale.astype(jnp.float32)


def _rope(x, pos, theta):
    """x (S, heads, D) rotated at positions pos (S,): the first and
    second halves of D form the pairs (Qwen2's rotate_half)."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (np.arange(half, dtype=np.float64) * 2
                           / x.shape[-1]))
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(inv, jnp.float32)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "eps", "theta", "bs", "n_blocks", "k_blocks",
    "d_slice", "local_window", "prec"))
def _layer(x, lp, proj, n_pre, n_real, *, heads, kv_heads, eps, theta, bs,
           n_blocks, k_blocks, d_slice, local_window, prec):
    """One layer over the whole padded sequence x (S, d): rows below
    ``n_pre`` attend causally, rows in [n_pre, n_real) are Loki decode
    steps over the ``n_blocks`` blocks of the context cap. Returns the new
    x and the layer's keys before rotation (for calibration)."""
    s_pad, dm = x.shape
    dim = dm // heads
    g = heads // kv_heads
    scale = dim ** -0.5
    pos = jnp.arange(s_pad)
    nbp = s_pad // bs
    h = _rms(x, lp["ln1"]["scale"], eps)
    a = lp["attn"]
    q = (_mm(prec, h, a["wq"]) + a["bq"]).reshape(s_pad, heads, dim)
    k_pre = (_mm(prec, h, a["wk"]) + a["bk"]).reshape(s_pad, kv_heads, dim)
    v = (_mm(prec, h, a["wv"]) + a["bv"]).reshape(s_pad, kv_heads, dim)
    q = _rope(q, pos, theta).reshape(s_pad, kv_heads, g, dim)
    k = _rope(k_pre, pos, theta)
    k_hat = _ein(prec, "shd,hde->she", k, proj)[..., :d_slice]

    def rows(blk):
        i = blk * ROWS + jnp.arange(ROWS)
        qb = jax.lax.dynamic_slice_in_dim(q, blk * ROWS, ROWS, 0)
        causal = (pos[None, :] <= i[:, None]) & (pos[None, :] < n_real)
        # Loki's selection (used by the decode rows only)
        qh = _ein(prec, "rhgd,hde->rhge", qb, proj)[..., :d_slice]
        ap = _ein(prec, "rhgc,shc->rhgs", qh, k_hat) * scale
        live = causal[:, None, None, :]
        recent = live & (pos >= (i + 1 - local_window)[:, None])[
            :, None, None, :]
        ap = jnp.where(recent, ap + 1e4, ap)
        ap = jnp.where(live, ap, NEG_INF)
        blk_max = ap.reshape(ROWS, kv_heads, g, nbp, bs).max(axis=(2, 4))
        if nbp < n_blocks:
            blk_max = jnp.pad(blk_max, ((0, 0), (0, 0), (0, n_blocks - nbp)),
                              constant_values=NEG_INF)
        top, idx = jax.lax.top_k(blk_max[..., :n_blocks], k_blocks)
        picked = ((idx[..., None] == jnp.arange(nbp))
                  & (top > NEG_INF / 2)[..., None]).any(axis=-2)
        tok = jnp.repeat(picked, bs, axis=-1)                  # (R,Hkv,S)
        dec = (i >= n_pre)[:, None, None]
        mask = jnp.where(dec, causal[:, None, :] & tok, causal[:, None, :])
        s = _ein(prec, "rhgd,shd->rhgs", qb, k) * scale
        s = jnp.where(mask[:, :, None, :], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        return _ein(prec, "rhgs,shd->rhgd", p, v)

    o = jax.lax.map(rows, jnp.arange(s_pad // ROWS))
    x = x + _mm(prec, o.reshape(s_pad, heads * dim), a["wo"])
    h = _rms(x, lp["ln2"]["scale"], eps)
    up = _mm(prec, h, lp["mlp"]["w_in"])
    f = up.shape[-1] // 2
    x = x + _mm(prec, jax.nn.silu(up[:, :f]) * up[:, f:], lp["mlp"]["w_out"])
    return x, k_pre


@functools.partial(jax.jit, static_argnames=("eps", "prec"))
def _head(x, norm, table, reads, *, eps, prec):
    """Logits of rows x (R, d): their maximum, their argmax and the values
    at the token ids ``reads`` (R, n)."""
    xn = _rms(x, norm, eps)
    v = table.shape[0]
    step = -(-v // VOCAB_CHUNKS)
    best = jnp.full(x.shape[:1], -jnp.inf)
    arg = jnp.zeros(x.shape[:1], jnp.int32)
    got = jnp.zeros(reads.shape, jnp.float32)
    for c in range(0, v, step):
        lg = _mm(prec, xn, table[c:c + step].T)
        m, am = lg.max(-1), jnp.argmax(lg, -1).astype(jnp.int32) + c
        arg = jnp.where(m > best, am, arg)
        best = jnp.maximum(best, m)
        inside = (reads >= c) & (reads < c + lg.shape[-1])
        val = jnp.take_along_axis(lg, jnp.clip(reads - c, 0,
                                               lg.shape[-1] - 1), axis=-1)
        got = jnp.where(inside, val, got)
    return best, arg, got


def _settings(cfg: dict, smax: int) -> dict:
    sv = cfg["serving"]
    heads = cfg["num_attention_heads"]
    dim = cfg["hidden_size"] // heads
    n_blocks = smax // sv["page_size"]
    return dict(heads=heads, kv_heads=cfg["num_key_value_heads"],
                eps=float(cfg["rms_norm_eps"]),
                theta=float(cfg["rope_theta"]), bs=sv["page_size"],
                n_blocks=n_blocks,
                k_blocks=max(int(sv["k_f"] * n_blocks), 1),
                d_slice=max(int(sv["d_f"] * dim), 8),
                local_window=sv["local_window"])


def _run_layers(params, proj, tokens, n_pre, cfg, smax, prec,
                capture=False):
    """The residual stream after every layer for ``tokens``; with
    ``capture`` also each layer's keys before rotation."""
    n = len(tokens)
    s_pad = min(-(-n // PAD) * PAD, -(-smax // ROWS) * ROWS)
    if n > s_pad:
        raise ValueError(f"sequence of {n} exceeds the context cap {smax}")
    ids = np.zeros((s_pad,), np.int32)
    ids[:n] = tokens
    x = jnp.take(params["embed"]["table"], jnp.asarray(ids),
                 axis=0).astype(jnp.float32)
    kw = _settings(cfg, smax)
    keys = []
    for layer in range(cfg["num_hidden_layers"]):
        lp = jax.tree.map(lambda a: a[layer], params["layers"])
        x, k_pre = _layer(x, lp, jnp.asarray(proj[layer]), jnp.int32(n_pre),
                          jnp.int32(n), prec=prec, **kw)
        if capture:
            keys.append(np.asarray(k_pre[:n], np.float64))
    return x, keys


def calibrate(params, cfg: dict, smax: int) -> np.ndarray:
    """PCA projections (L, Hkv, D, D), components as columns in descending
    variance, of the keys before rotation (``transform: pre``) over the
    configuration's calibration batches of random tokens."""
    cal = cfg["calibration"]
    if cal["transform"] != "pre":
        raise ValueError("only the pre-rotary transform is calibrated")
    heads = cfg["num_attention_heads"]
    dim = cfg["hidden_size"] // heads
    hkv = cfg["num_key_value_heads"]
    n_layers = cfg["num_hidden_layers"]
    eye = np.broadcast_to(np.eye(dim, dtype=np.float32),
                          (n_layers, hkv, dim, dim))
    rng = np.random.Generator(np.random.PCG64(cal["seed"]))
    outer = np.zeros((n_layers, hkv, dim, dim))
    total = np.zeros((n_layers, hkv, dim))
    count = 0
    for _ in range(cal["batches"] * cal["batch"]):
        toks = rng.integers(0, cfg["vocab_size"], cal["length"])
        _, keys = _run_layers(params, eye, toks, len(toks), cfg,
                              max(smax, cal["length"]), "f32", capture=True)
        for layer, k in enumerate(keys):                 # (S, Hkv, D)
            outer[layer] += np.einsum("shd,she->hde", k, k)
            total[layer] += k.sum(0)
        count += len(toks)
    mu = total / count
    cov = outer / count - np.einsum("lhd,lhe->lhde", mu, mu)
    _, vec = np.linalg.eigh(cov)                           # ascending
    return np.ascontiguousarray(vec[..., ::-1]).astype(np.float32)


def logit_reads(params, proj, cfg: dict, smax: int, prompt, served,
                reads=None, prec: str = "f32") -> Tuple[np.ndarray, ...]:
    """Teacher-forced logits at every position where a token was served
    after ``prompt``. Returns, per served token, the largest logit, the
    argmax, and the logits at ``reads`` (n_served, m) token ids (default:
    the served tokens themselves, m = 1)."""
    served = np.asarray(served, np.int32)
    prompt = np.asarray(prompt, np.int32)
    tokens = np.concatenate([prompt, served[:-1]])
    n_pre = len(prompt) - 1
    n_dec = len(served)
    if reads is None:
        reads = served[:, None]
    x, _ = _run_layers(params, proj, tokens, n_pre, cfg, smax, prec)
    x = jnp.pad(x, ((0, ROWS), (0, 0)))
    best, arg, got = [], [], []
    eps = float(cfg["rms_norm_eps"])
    for r0 in range(0, n_dec, ROWS):
        rows = jax.lax.dynamic_slice_in_dim(x, n_pre + r0, ROWS, 0)
        rd = np.zeros((ROWS, reads.shape[1]), np.int32)
        rd[:min(ROWS, n_dec - r0)] = reads[r0:r0 + ROWS]
        b, a, g = _head(rows, params["final_norm"]["scale"],
                        params["embed"]["table"], jnp.asarray(rd), eps=eps,
                        prec=prec)
        m = min(ROWS, n_dec - r0)
        best.append(np.asarray(b)[:m])
        arg.append(np.asarray(a)[:m])
        got.append(np.asarray(g)[:m])
    return np.concatenate(best), np.concatenate(arg), np.concatenate(got)
