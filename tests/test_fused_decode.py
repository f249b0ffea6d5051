"""Fused GQA-batched decode kernel: parity vs the jnp oracle, dispatch
routing, and the two-kernel fallback — all in interpret mode so CI runs on
CPU (on TPU the identical pallas_calls compile through Mosaic)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import LokiConfig
from repro.core import dispatch
from repro.core.loki import loki_decode_block
from repro.kernels import tuning
from repro.kernels.fused_decode import fused_loki_decode, select_blocks
from repro.kernels.gather_attention import block_sparse_attention_grouped
from repro.kernels.ops import loki_decode_two_kernel


def _setup(b, hkv, g, s, dim, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (b, hkv * g, dim), dtype)
    k = jax.random.normal(ks[1], (b, s, hkv, dim), dtype)
    v = jax.random.normal(ks[2], (b, s, hkv, dim), dtype)
    return q, k, v


def _orthogonal(hkv, dim, seed=0):
    rng = np.random.RandomState(seed)
    mats = [np.linalg.qr(rng.randn(dim, dim))[0] for _ in range(hkv)]
    return jnp.asarray(np.stack(mats), jnp.float32)


def _grouped_q(q, proj, hkv):
    b, h, dim = q.shape
    qg = q.reshape(b, hkv, h // hkv, dim)
    return jnp.einsum("bhgd,hde->bhge", qg, proj.astype(q.dtype))


def _oracle(q, k_hat, v, cur, proj, cfg):
    want = loki_decode_block(q, k_hat, v, cur, proj, cfg, group_select=True)
    b, h, dim = q.shape
    hkv = proj.shape[0]
    return want.reshape(b, hkv, h // hkv, dim)


# ------------------------------------------------------------ fused kernel

@pytest.mark.parametrize("g", [1, 4, 8])
@pytest.mark.parametrize("b,hkv,s,dim,bs", [
    (2, 2, 256, 64, 32),
    (1, 2, 512, 128, 128),
    (3, 1, 384, 64, 64),          # non-pow2 batch, single kv head
])
def test_fused_matches_grouped_oracle(b, hkv, g, s, dim, bs):
    q, k, v = _setup(b, hkv, g, s, dim, seed=g + s)
    proj = _orthogonal(hkv, dim, seed=g)
    k_hat = jnp.einsum("bshd,hde->bshe", k, proj)
    cur = jax.random.randint(jax.random.PRNGKey(7), (b,), 1, s + 1)
    cfg = LokiConfig(enabled=True, d_f=0.25, k_f=0.25, block_size=bs,
                     local_window=0)
    want = _oracle(q, k_hat, v, cur, proj, cfg)
    nb = s // bs
    got = fused_loki_decode(
        _grouped_q(q, proj, hkv), k_hat, v, cur,
        d=max(int(cfg.d_f * dim), 8), k_blocks=max(int(cfg.k_f * nb), 1),
        block_size=bs, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_fused_equals_per_head_oracle_when_g1():
    """At G=1, group-shared selection IS per-head selection: the fused
    kernel must match the unmodified loki_decode_block."""
    b, hkv, s, dim, bs = 2, 3, 256, 64, 32
    q, k, v = _setup(b, hkv, 1, s, dim, seed=11)
    proj = _orthogonal(hkv, dim, seed=3)
    k_hat = jnp.einsum("bshd,hde->bshe", k, proj)
    cur = jnp.array([s, s // 3])
    cfg = LokiConfig(enabled=True, d_f=0.5, k_f=0.25, block_size=bs,
                     local_window=0)
    want = loki_decode_block(q, k_hat, v, cur, proj, cfg)
    got = fused_loki_decode(
        _grouped_q(q, proj, hkv), k_hat, v, cur,
        d=max(int(cfg.d_f * dim), 8),
        k_blocks=max(int(cfg.k_f * (s // bs)), 1),
        block_size=bs, interpret=True)
    np.testing.assert_allclose(np.asarray(got).reshape(b, hkv, dim),
                               np.asarray(want), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("cur_lens", [(1, 1), (1, 300), (17, 33)])
def test_fused_all_masked_blocks(cur_lens):
    """cur_len smaller than one block: most selected blocks are fully dead
    and must contribute exactly nothing (and never NaN)."""
    b, hkv, g, s, dim, bs = 2, 2, 4, 512, 64, 64
    q, k, v = _setup(b, hkv, g, s, dim, seed=5)
    proj = _orthogonal(hkv, dim, seed=5)
    k_hat = jnp.einsum("bshd,hde->bshe", k, proj)
    cur = jnp.array(cur_lens, jnp.int32)
    cfg = LokiConfig(enabled=True, d_f=0.25, k_f=0.5, block_size=bs,
                     local_window=0)
    want = _oracle(q, k_hat, v, cur, proj, cfg)
    got = fused_loki_decode(
        _grouped_q(q, proj, hkv), k_hat, v, cur,
        d=16, k_blocks=max(int(0.5 * (s // bs)), 1),
        block_size=bs, interpret=True)
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_fused_selection_exhausted_no_double_count():
    """Fewer live blocks than k_blocks (2 live, k_blocks=4): exhausted
    selection rounds must contribute nothing — not re-select block 0 and
    double-count it in the online softmax (regression)."""
    b, hkv, g, s, dim, bs = 2, 2, 4, 512, 64, 64
    q, k, v = _setup(b, hkv, g, s, dim, seed=13)
    proj = _orthogonal(hkv, dim, seed=13)
    k_hat = jnp.einsum("bshd,hde->bshe", k, proj)
    cur = jnp.array([100, 90], jnp.int32)       # 2 of 8 blocks live
    cfg = LokiConfig(enabled=True, d_f=0.25, k_f=0.5, block_size=bs,
                     local_window=0)
    kb = max(int(cfg.k_f * (s // bs)), 1)
    assert kb == 4
    want = _oracle(q, k_hat, v, cur, proj, cfg)
    q_hat = _grouped_q(q, proj, hkv)
    kw = dict(d=16, k_blocks=kb, block_size=bs, interpret=True)
    fused = fused_loki_decode(q_hat, k_hat, v, cur, **kw)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    two = loki_decode_two_kernel(q_hat, k_hat, v, cur, **kw)
    np.testing.assert_allclose(np.asarray(two), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    # and the selection marks the exhausted tail with -1
    sel = select_blocks(q_hat, k_hat, cur, d=16, k_blocks=kb,
                        block_size=bs, interpret=True)
    assert int((np.asarray(sel) == -1).sum()) == b * hkv * 2


def test_fused_bf16_inputs():
    b, hkv, g, s, dim, bs = 1, 2, 4, 256, 64, 64
    q, k, v = _setup(b, hkv, g, s, dim, seed=9, dtype=jnp.bfloat16)
    proj = _orthogonal(hkv, dim, seed=9)
    k_hat = jnp.einsum("bshd,hde->bshe", k.astype(jnp.float32),
                       proj).astype(jnp.bfloat16)
    cur = jnp.array([s], jnp.int32)
    cfg = LokiConfig(enabled=True, d_f=0.25, k_f=0.5, block_size=bs,
                     local_window=0)
    want = _oracle(q, k_hat, v, cur, proj, cfg)
    got = fused_loki_decode(
        _grouped_q(q, proj.astype(jnp.bfloat16), hkv), k_hat, v, cur,
        d=16, k_blocks=max(int(0.5 * (s // bs)), 1),
        block_size=bs, interpret=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=5e-2, atol=5e-2)


# -------------------------------------------------- two-kernel fallback

@pytest.mark.parametrize("g", [1, 4])
def test_two_pass_matches_fused(g):
    b, hkv, s, dim, bs = 2, 2, 384, 64, 32
    q, k, v = _setup(b, hkv, g, s, dim, seed=21)
    proj = _orthogonal(hkv, dim, seed=2)
    k_hat = jnp.einsum("bshd,hde->bshe", k, proj)
    cur = jnp.array([s, s // 2])
    q_hat = _grouped_q(q, proj, hkv)
    kw = dict(d=16, k_blocks=3, block_size=bs, interpret=True)
    fused = fused_loki_decode(q_hat, k_hat, v, cur, **kw)
    two = loki_decode_two_kernel(q_hat, k_hat, v, cur, **kw)
    np.testing.assert_allclose(np.asarray(two), np.asarray(fused),
                               rtol=2e-5, atol=2e-5)


def test_select_blocks_matches_topk():
    """The in-kernel argmax-and-suppress selection equals lax.top_k over the
    jnp group block maxima (including tie/order semantics)."""
    b, hkv, g, s, dim, bs = 2, 2, 4, 512, 64, 64
    q, k, v = _setup(b, hkv, g, s, dim, seed=31)
    cur = jnp.array([s, 200])
    proj = jnp.broadcast_to(jnp.eye(dim), (hkv, dim, dim))
    d, kb = 16, 3
    q_hat = _grouped_q(q, proj, hkv)
    got = select_blocks(q_hat, k, cur, d=d, k_blocks=kb, block_size=bs,
                        interpret=True)
    # jnp reference selection
    scale = dim ** -0.5
    approx = jnp.einsum("bhgd,bshd->bhgs", q_hat[..., :d] * scale,
                        k[..., :d], preferred_element_type=jnp.float32)
    approx = jnp.where(jnp.arange(s)[None, None, None] < cur[:, None, None,
                                                             None],
                       approx, -1e30)
    blk = approx.reshape(b, hkv, g, s // bs, bs).max(-1).max(2)
    _, want = jax.lax.top_k(blk, kb)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_grouped_gather_matches_per_head_kernel():
    """block_sparse_attention_grouped == per-head block_sparse_attention run
    row by row with the shared selection."""
    from repro.kernels.gather_attention import block_sparse_attention
    b, hkv, g, s, dim, bs = 1, 2, 2, 256, 64, 32
    q, k, v = _setup(b, hkv, g, s, dim, seed=41)
    proj = jnp.broadcast_to(jnp.eye(dim), (hkv, dim, dim))
    q_hat = _grouped_q(q, proj, hkv)
    cur = jnp.array([s - 40])
    nb = s // bs
    blk_idx = jnp.stack([jnp.array([0, 3, 5]), jnp.array([1, 2, 7])])[None]
    got = block_sparse_attention_grouped(q_hat, k, v, blk_idx, cur,
                                         block_size=bs, interpret=True)
    for h in range(hkv):
        for gi in range(g):
            row = block_sparse_attention(
                q_hat[:, h, gi], jnp.swapaxes(k, 1, 2)[:, h],
                jnp.swapaxes(v, 1, 2)[:, h], blk_idx[:, h], cur,
                block_size=bs, interpret=True)
            np.testing.assert_allclose(np.asarray(got[:, h, gi]),
                                       np.asarray(row), rtol=2e-5,
                                       atol=2e-5)


# ------------------------------------------------- window semantics

@pytest.mark.parametrize("g", [1, 4])
@pytest.mark.parametrize("local_window,sliding_window", [
    (16, 0), (0, 96), (16, 96)])
def test_window_parity_across_backends(g, local_window, sliding_window):
    """Regression: the block paths used to silently ignore
    cfg.local_window and sliding_window that the token path honors. All
    three implementations (block reference, fused kernel, two-kernel
    fallback) must now agree with local_window/sliding_window set."""
    b, hkv, s, dim, bs = 2, 2, 256, 64, 32
    q, k, v = _setup(b, hkv, g, s, dim, seed=g + local_window)
    proj = _orthogonal(hkv, dim, seed=g)
    k_hat = jnp.einsum("bshd,hde->bshe", k, proj)
    cur = jnp.array([s, 130], jnp.int32)
    cfg = LokiConfig(enabled=True, d_f=0.25, k_f=0.25, block_size=bs,
                     local_window=local_window)
    want = loki_decode_block(q, k_hat, v, cur, proj, cfg,
                             sliding_window=sliding_window,
                             group_select=True)
    want = want.reshape(b, hkv, g, dim)
    nb = s // bs
    kw = dict(d=max(int(cfg.d_f * dim), 8),
              k_blocks=max(int(cfg.k_f * nb), 1), block_size=bs,
              local_window=local_window, sliding_window=sliding_window,
              interpret=True)
    q_hat = _grouped_q(q, proj, hkv)
    fused = fused_loki_decode(q_hat, k_hat, v, cur, **kw)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    two = loki_decode_two_kernel(q_hat, k_hat, v, cur, **kw)
    np.testing.assert_allclose(np.asarray(two), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_block_full_budget_windows_match_token_path():
    """At full budget (k_f=1: every block selected) the block path with
    windows must equal the token-granular loki_decode — the semantic
    anchor tying the block windows to the paper's formulation."""
    from repro.core.loki import loki_decode
    b, hkv, g, s, dim, bs = 2, 2, 2, 128, 64, 32
    q, k, v = _setup(b, hkv, g, s, dim, seed=77)
    proj = _orthogonal(hkv, dim, seed=77)
    k_hat = jnp.einsum("bshd,hde->bshe", k, proj)
    cur = jnp.array([s, 70], jnp.int32)
    cfg = LokiConfig(enabled=True, d_f=1.0, k_f=1.0, min_k=1,
                     block_size=bs, local_window=16)
    want = loki_decode(q, k_hat, v, cur, proj, cfg, sliding_window=48)
    got = loki_decode_block(q, k_hat, v, cur, proj, cfg, sliding_window=48)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# ------------------------------------------------- paged (page-table) mode

def _paged_pool(k_hat, v, bs, ps, seed=0):
    """Scatter contiguous (B,S,Hkv,D) caches into a shuffled page pool
    in the serving layout (Hkv, D, R).

    Returns (pool_k, pool_v, page_table) with page 0 left as trash."""
    b, s, hkv, dim = k_hat.shape
    mp = s // ps
    rng = np.random.RandomState(seed)
    perm = rng.permutation(b * mp) + 1              # physical pages, 1-based
    table = perm.reshape(b, mp).astype(np.int32)
    n_pages = b * mp + 1
    pool_k = np.zeros((hkv, dim, n_pages * ps), np.asarray(k_hat).dtype)
    pool_v = np.zeros_like(pool_k)
    kn, vn = np.asarray(k_hat), np.asarray(v)
    for i in range(b):
        for p in range(mp):
            rows = slice(table[i, p] * ps, table[i, p] * ps + ps)
            pool_k[..., rows] = kn[i, p * ps:(p + 1) * ps].transpose(1, 2, 0)
            pool_v[..., rows] = vn[i, p * ps:(p + 1) * ps].transpose(1, 2, 0)
    return (jnp.asarray(pool_k), jnp.asarray(pool_v),
            jnp.asarray(table))


@pytest.mark.parametrize("g", [1, 4])
@pytest.mark.parametrize("bs,ps", [(32, 32), (16, 32), (32, 64)])
def test_fused_paged_matches_contiguous(g, bs, ps):
    """The paged kernel (block DMA through the page table) must reproduce
    the contiguous kernel bit-for-bit on a shuffled pool, including ragged
    lengths and windows."""
    b, hkv, s, dim = 2, 2, 256, 64
    q, k, v = _setup(b, hkv, g, s, dim, seed=g + bs)
    proj = _orthogonal(hkv, dim, seed=bs)
    k_hat = jnp.einsum("bshd,hde->bshe", k, proj)
    cur = jnp.array([s, 100], jnp.int32)
    pool_k, pool_v, table = _paged_pool(k_hat, v, bs, ps, seed=g)
    q_hat = _grouped_q(q, proj, hkv)
    kw = dict(d=16, k_blocks=3, block_size=bs, local_window=8,
              interpret=True)
    want = fused_loki_decode(q_hat, k_hat, v, cur, **kw)
    got = fused_loki_decode(q_hat, pool_k, pool_v, cur,
                            page_table=table, page_size=ps, **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    two = loki_decode_two_kernel(q_hat, pool_k, pool_v, cur,
                                 page_table=table, page_size=ps, **kw)
    np.testing.assert_allclose(np.asarray(two), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("layer", ["first", "last"])
def test_fused_reads_its_layer_of_stacked_pools(layer):
    """With the layers' pools stacked (L, Hkv, W, R) and a layer index,
    the kernel returns exactly what it returns on that layer's own pools:
    the index only moves every DMA's source into that layer."""
    b, hkv, g, s, dim, bs, n_layers = 2, 2, 4, 256, 64, 32, 3
    q, _, _ = _setup(b, hkv, g, s, dim, seed=5)
    proj = _orthogonal(hkv, dim, seed=5)
    pools = []
    for i in range(n_layers):
        _, k, v = _setup(b, hkv, g, s, dim, seed=10 + i)
        k_hat = jnp.einsum("bshd,hde->bshe", k, proj)
        pools.append(_paged_pool(k_hat, v, bs, 64, seed=1))
    table = pools[0][2]
    k_stack = jnp.stack([p[0] for p in pools])
    v_stack = jnp.stack([p[1] for p in pools])
    lyr = 0 if layer == "first" else n_layers - 1
    cur = jnp.array([s, 100], jnp.int32)
    kw = dict(d=16, k_blocks=3, block_size=bs, local_window=8,
              page_table=table, page_size=64, interpret=True)
    q_hat = _grouped_q(q, proj, hkv)
    want = fused_loki_decode(q_hat, pools[lyr][0], pools[lyr][1], cur, **kw)
    got = fused_loki_decode(q_hat, k_stack, v_stack, cur,
                            layer=jnp.int32(lyr), **kw)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    other = fused_loki_decode(q_hat, k_stack, v_stack, cur,
                              layer=jnp.int32(1), **kw)
    assert not np.array_equal(np.asarray(other), np.asarray(want))


def test_dispatch_paged_pallas_matches_oracle():
    """End-to-end dispatch with a page table: backend='pallas' (paged
    kernels) equals the group-shared jnp oracle gathering through the same
    table, and backend='xla' through the table equals the dense-cache
    reference (per-head selection)."""
    b, hkv, g, s, dim, bs = 2, 2, 4, 256, 64, 32
    q, k, v = _setup(b, hkv, g, s, dim, seed=91)
    proj = _orthogonal(hkv, dim, seed=91)
    k_hat = jnp.einsum("bshd,hde->bshe", k, proj)
    cur = jnp.array([s, 77], jnp.int32)
    pool_k, pool_v, table = _paged_pool(k_hat, v, bs, bs, seed=3)
    cfg = LokiConfig(enabled=True, d_f=0.25, k_f=0.25, block_size=bs,
                     local_window=16)
    got = dispatch.loki_block_decode(
        q, pool_k, pool_v, cur, proj,
        dataclasses.replace(cfg, backend="pallas"),
        page_table=table, page_size=bs)
    want = loki_decode_block(q, pool_k, pool_v, cur, proj, cfg,
                             group_select=True, page_table=table,
                             page_size=bs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    # xla dispatch through the table == dense-cache reference
    via_table = dispatch.loki_block_decode(
        q, pool_k, pool_v, cur, proj,
        dataclasses.replace(cfg, backend="xla"),
        page_table=table, page_size=bs)
    dense = loki_decode_block(q, k_hat, v, cur, proj, cfg)
    np.testing.assert_allclose(np.asarray(via_table), np.asarray(dense),
                               rtol=2e-5, atol=2e-5)


# ------------------------------------------------------------- dispatch

def test_resolve_backend():
    assert dispatch.resolve_backend("auto", "cpu") == "xla"
    assert dispatch.resolve_backend("auto", "tpu") == "pallas"
    assert dispatch.resolve_backend("pallas", "cpu") == "pallas"
    assert dispatch.resolve_backend("xla", "tpu") == "xla"
    with pytest.raises(ValueError):
        dispatch.resolve_backend("triton")


@pytest.mark.parametrize("g", [1, 4])
def test_dispatch_pallas_matches_xla_grouped(g):
    """End-to-end dispatch: backend='pallas' (interpret on CPU) equals the
    grouped jnp oracle across ragged lengths."""
    b, hkv, s, dim, bs = 2, 2, 256, 64, 32
    q, k, v = _setup(b, hkv, g, s, dim, seed=51)
    proj = _orthogonal(hkv, dim, seed=51)
    k_hat = jnp.einsum("bshd,hde->bshe", k, proj)
    cur = jnp.array([s, 77])
    cfg = LokiConfig(enabled=True, d_f=0.25, k_f=0.25, block_size=bs,
                     local_window=0, backend="pallas")
    got = dispatch.loki_block_decode(q, k_hat, v, cur, proj, cfg)
    want = loki_decode_block(q, k_hat, v, cur, proj, cfg,
                             group_select=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_dispatch_xla_is_reference():
    b, hkv, g, s, dim, bs = 1, 2, 2, 128, 64, 32
    q, k, v = _setup(b, hkv, g, s, dim, seed=61)
    proj = _orthogonal(hkv, dim, seed=61)
    k_hat = jnp.einsum("bshd,hde->bshe", k, proj)
    cur = jnp.array([s])
    cfg = LokiConfig(enabled=True, d_f=0.5, k_f=0.5, block_size=bs,
                     local_window=0, backend="xla")
    got = dispatch.loki_block_decode(q, k_hat, v, cur, proj, cfg)
    want = loki_decode_block(q, k_hat, v, cur, proj, cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want))


def test_dispatch_unplannable_shape_falls_back():
    """A cache length no candidate block size divides still decodes off
    TPU — the dispatcher falls back to the jnp path instead of asserting.
    With compiled kernels (``interpret=False``, as on TPU) every Pallas
    route raises ``NoKernelPlan`` instead of leaving its kernels."""
    b, hkv, g, dim = 1, 2, 2, 64
    s = 105  # 3*5*7: neither the hint nor any pow2 candidate divides
    cfg = LokiConfig(enabled=True, d_f=0.5, k_f=0.5, block_size=8,
                     local_window=0, backend="pallas")
    assert tuning.plan_decode(s, dim, g, 32, 8) is None
    q, k, v = _setup(b, hkv, g, s, dim, seed=71)
    proj = _orthogonal(hkv, dim, seed=71)
    k_hat = jnp.einsum("bshd,hde->bshe", k, proj)
    cur = jnp.array([s])
    out = dispatch.loki_block_decode(q, k_hat, v, cur, proj, cfg)
    assert bool(jnp.isfinite(out).all())
    with pytest.raises(dispatch.NoKernelPlan):
        dispatch.loki_block_decode(q, k_hat, v, cur, proj, cfg,
                                   interpret=False)
    with pytest.raises(dispatch.NoKernelPlan):
        dispatch.exact_topk_paged_decode(q, k_hat, v, cur, cfg,
                                         interpret=False)
    with pytest.raises(dispatch.NoKernelPlan):
        dispatch.full_paged_decode(q, k_hat, v, cur, backend="pallas",
                                   block_size=8, interpret=False)


def test_plan_decode_table_and_heuristic():
    p = tuning.plan_decode(32_768, 128, 8, 32, 128)
    assert p is not None and 32_768 % p.block_size == 0
    assert tuning.plan_decode(4096, 128, 4, 32, 128).variant == "fused"
    # indivisible cache length -> no plan
    assert tuning.plan_decode(300, 64, 2, 16, 128) is None
    # absurd scratch demand -> two-pass or refusal, never "fused"
    big = tuning.plan_decode(2 ** 21, 8192, 64, 2048, 128, itemsize=4)
    assert big is None or big.variant == "two_kernel"


def test_engine_backend_knob():
    """ServingEngine(backend=...) threads through to cfg.loki.backend."""
    from repro.configs import get_smoke_config
    from repro.models import lm
    from repro.serving.engine import ServingEngine
    cfg = get_smoke_config("qwen2.5-3b").with_policy(
        "loki_block", d_f=0.5, k_f=0.5, block_size=8, local_window=0)
    params = lm.init(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(params, cfg, n_slots=1, smax=32, backend="xla")
    assert eng.cfg.loki.backend == "xla"
    assert cfg.loki.backend == "auto"  # caller's config untouched
