"""The decode step's in-place K/V row writes into stacked page pools.

``pool_write_rows`` (kernels/pool_write.py) runs here in interpret mode
against a numpy loop and against the jnp scatter it replaces on the
Pallas route (``paged_cache._scatter_rows``): the pools must come out
bit for bit the same. A tiny scan-family model then decodes a few steps
through ``lm.decode_step`` with the kernel and with the jnp scatter in
its place: logits and pools must not move.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.kernels.pool_write import pool_write_rows
from repro.models import lm
from repro.serving import paged_cache as PC

N_LAYERS, HKV, W, PS, N_PAGES = 3, 2, 16, 128, 4
R = N_PAGES * PS
DTYPES = {"bf16": jnp.bfloat16, "fp32": jnp.float32}


def _bits(a):
    a = np.asarray(a)
    return a.view({2: np.uint16, 4: np.uint32}[a.dtype.itemsize])


def _numpy_write(pool, new, layer, rows):
    out = np.array(pool)
    for b, r in enumerate(rows):
        if 0 <= r < out.shape[-1]:
            out[layer, :, :, r] = np.asarray(new)[b]
    return out


@pytest.mark.parametrize("dtype", sorted(DTYPES))
@pytest.mark.parametrize("layer", [0, N_LAYERS - 1])
def test_row_write_matches_scatter_bit_for_bit(dtype, layer):
    """Rows 0 and 127 of a page, two slots writing one trash row (the
    later wins), a row past R dropped; signed zeros and infinities kept."""
    dt = DTYPES[dtype]
    pool = jax.random.normal(jax.random.PRNGKey(0),
                             (N_LAYERS, HKV, W, R)).astype(dt)
    rows = jnp.array([2 * PS, 2 * PS + 127, 5, 5, R + 3, 3 * PS + 64],
                     jnp.int32)
    new = jax.random.normal(jax.random.PRNGKey(1),
                            (rows.shape[0], HKV, W)).astype(dt)
    new = new.at[0, 0, :3].set(jnp.array([-0.0, jnp.inf, -jnp.inf], dt))
    got = pool_write_rows(pool, new, jnp.int32(layer), rows, interpret=True)
    want = _numpy_write(pool, new, layer, np.asarray(rows))
    scattered = PC._scatter_rows(pool, rows, new, jnp.int32(layer))
    assert got.dtype == pool.dtype and got.shape == pool.shape
    np.testing.assert_array_equal(_bits(got), _bits(want))
    np.testing.assert_array_equal(_bits(scattered), _bits(want))
    # the trash row holds the later slot's values; nothing else moved
    np.testing.assert_array_equal(_bits(got[layer, :, :, 5]),
                                  _bits(new[3]))
    changed = np.any(_bits(got) != _bits(pool), axis=(1, 2))
    assert set(np.argwhere(changed)[:, 0]) <= {layer}


def test_write_token_rows_routes_by_backend():
    """The Pallas backend writes a stacked pool through the kernel, the
    xla backend through the jnp scatter; both give the same pool."""
    pool = jnp.zeros((N_LAYERS, HKV, W, R), jnp.float32)
    new = jnp.arange(2 * HKV * W, dtype=jnp.float32).reshape(2, HKV, W)
    table = jnp.array([[1, 2], [3, 0]], jnp.int32)
    pos = jnp.array([130, 7], jnp.int32)
    outs = {}
    for backend in ("pallas", "xla"):
        fn = lambda p, n: PC.write_token_rows(
            p, n, table, pos, PS, layer=jnp.int32(1), backend=backend)
        calls = str(jax.make_jaxpr(fn)(pool, new))
        assert ("pool_write_rows" in calls) == (backend == "pallas")
        outs[backend] = np.asarray(fn(pool, new))
    np.testing.assert_array_equal(outs["pallas"], outs["xla"])
    np.testing.assert_array_equal(outs["xla"][1, :, :, 2 * PS + 2], new[0])
    np.testing.assert_array_equal(outs["xla"][1, :, :, 3 * PS + 7], new[1])


def _decode_steps(monkeypatch, dtype, scatter: bool, n_steps=3):
    """Logits and pools of ``n_steps`` decode steps of a tiny Qwen2-shaped
    model on the Pallas route (interpret mode), its row writes through
    the kernel or, with ``scatter``, through the jnp scatter."""
    cfg = get_smoke_config("qwen2.5-3b").with_policy(
        "loki_block", d_f=0.5, k_f=0.5, block_size=16, local_window=0)
    cfg = cfg.replace(loki=dataclasses.replace(cfg.loki, backend="pallas"))
    params = lm.init(jax.random.PRNGKey(0), cfg)
    ps, n_slots, max_pages = 16, 3, 4
    n_pages = 1 + n_slots * max_pages
    cache = lm.init_paged_cache(cfg, n_pages, ps, dtype, n_slots=n_slots)
    cache = jax.tree.map(
        lambda a: jax.random.normal(jax.random.PRNGKey(7), a.shape)
        .astype(a.dtype), cache)
    # slots 0 and 1 hold pages; slot 2 is idle and points at the trash
    table = jnp.array([[1, 2, 3, 4], [5, 6, 7, 8], [0, 0, 0, 0]],
                      jnp.int32)
    pos = jnp.array([40, 17, 0], jnp.int32)
    tok = jnp.array([5, 9, 0], jnp.int32)
    if scatter:
        monkeypatch.setattr(
            PC, "pool_write_rows",
            lambda pool, new, layer, rows, interpret: PC._scatter_rows(
                pool, rows, new, layer))
    step = jax.jit(lambda p, c, t, n: lm.decode_step(
        p, cfg, c, t, n, page_table=table, page_size=ps))
    calls = str(jax.make_jaxpr(step)(params, cache, tok, pos))
    assert ("pool_write_rows" in calls) == (not scatter)
    assert "fused_loki_decode" in calls
    logits = []
    for _ in range(n_steps):
        out, cache = step(params, cache, tok, pos)
        logits.append(np.asarray(out))
        tok = jnp.argmax(out, axis=-1).astype(jnp.int32)
        pos = pos + jnp.array([1, 1, 0], jnp.int32)
    return np.stack(logits), cache


@pytest.mark.parametrize("dtype", sorted(DTYPES))
def test_decode_steps_unchanged_by_the_row_write_kernel(monkeypatch, dtype):
    got, got_cache = _decode_steps(monkeypatch, DTYPES[dtype], False)
    want, want_cache = _decode_steps(monkeypatch, DTYPES[dtype], True)
    np.testing.assert_array_equal(got, want)
    assert np.isfinite(got).all()
    for a, b in zip(jax.tree.leaves(got_cache), jax.tree.leaves(want_cache)):
        np.testing.assert_array_equal(_bits(a), _bits(b))
