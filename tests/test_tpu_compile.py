"""Compile the paged decode kernels for a described TPU v5e, no chip needed.

Interpret mode (every other kernel test) has no tiling, so it cannot see
what the TPU compiler refuses: scalar stores to VMEM, blocks that break
the (8, 128) rule, DMAs of a slice that is not tile-aligned. These tests
lower and compile each main-path kernel at Qwen2.5-3B's decode widths
(D=128, Hkv=2, G=8, page_size 128) for one chip of a described
``v5e:2x2`` topology and check that a Mosaic kernel came out.

The topology is described inside a fixture, never at import time: only
one process at a time may load the TPU library, and pytest-xdist workers
all import this file.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.configs.base import PageLayout
from repro.core import dispatch
from repro.kernels import fused_decode as FD
from repro.kernels import gather_attention as GA
from repro.kernels import layout
from repro.models import lm

B, HKV, G, D, PS, SMAX = 4, 2, 8, 128, 128, 2048
MAX_PAGES = SMAX // PS
N_PAGES = 1 + B * MAX_PAGES
ROWS = N_PAGES * PS
D_SCORE = 32                       # Loki's d at d_f = 0.25
K_BLOCKS = 4


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile written to the persistent cache cannot be read back
    # without a chip; keep this module's compiles out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _operands(one_chip, dtype, quant, kw=D):
    """Operands of one paged decode; ``kw`` < D is a rank-r key pool."""
    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    ops = dict(q=sds((B, HKV, G, kw), jnp.float32),
               k=sds(layout.pool_shape(HKV, kw, ROWS), dtype),
               v=sds(layout.pool_shape(HKV, D, ROWS), dtype),
               cur=sds((B,), jnp.int32),
               table=sds((B, MAX_PAGES), jnp.int32))
    scales = {}
    if quant:
        scales = dict(k_scale=sds((N_PAGES,), jnp.float32),
                      v_scale=sds((N_PAGES,), jnp.float32))
    return ops, scales


def _loki(q, k, v, cur, table, **sc):
    return FD.fused_loki_decode(q, k, v, cur, d=D_SCORE, k_blocks=K_BLOCKS,
                                page_table=table, page_size=PS, **sc)


def _exact_topk(q, k, v, cur, table, **sc):
    return FD.fused_exact_topk_decode(q, k, v, cur, k_blocks=K_BLOCKS,
                                      page_table=table, page_size=PS, **sc)


def _full(q, k, v, cur, table, **sc):
    return GA.paged_full_decode(q, k, v, cur, page_table=table,
                                page_size=PS, **sc)


def _two_kernel(q, k, v, cur, table, **sc):
    idx = FD.select_blocks(q, k, cur, d=D_SCORE, k_blocks=K_BLOCKS,
                           page_table=table, page_size=PS,
                           k_scale=sc.get("k_scale"))
    return GA.block_sparse_attention_grouped(q, k, v, idx, cur,
                                             page_table=table, page_size=PS,
                                             **sc)


KERNELS = {"loki_block": _loki, "exact_topk": _exact_topk, "full": _full,
           "two_kernel": _two_kernel}
POOLS = {"fp32": (jnp.float32, False), "bf16": (jnp.bfloat16, False),
         "int8": (jnp.int8, True), "fp8": (jnp.float8_e4m3fn, True)}


def _compile(one_chip, kernel, pool, kw=D):
    dtype, quant = POOLS[pool]
    ops, scales = _operands(one_chip, dtype, quant, kw)
    fn = KERNELS[kernel]
    lowered = jax.jit(fn).lower(ops["q"], ops["k"], ops["v"], ops["cur"],
                                ops["table"], **scales)
    return lowered.compile(), ops


@pytest.mark.parametrize("pool", sorted(POOLS))
@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_paged_kernel_compiles_for_v5e(one_chip, kernel, pool):
    compiled, ops = _compile(one_chip, kernel, pool)
    assert "tpu_custom_call" in compiled.as_text()
    # the feature-major pools carry no tile padding: the program's
    # arguments are the pools' own bytes plus a few small operands
    pool_bytes = 2 * HKV * D * ROWS * jnp.dtype(ops["k"].dtype).itemsize
    args = compiled.memory_analysis().argument_size_in_bytes
    assert pool_bytes <= args <= pool_bytes + 64 * 1024, (args, pool_bytes)


@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_rank_r_pool_compiles_for_v5e(one_chip, kernel):
    """Rank-r latent keys (W = D/2) in a bf16 pool: the key block and the
    queries are half width, values stay full D."""
    compiled, _ = _compile(one_chip, kernel, "bf16", kw=D // 2)
    assert "tpu_custom_call" in compiled.as_text()


def test_fp16_pool_is_refused_and_guarded(one_chip):
    """v5e has no fp16 vector loads: the compiler refuses an fp16 pool,
    which is why a Pallas-served engine checks its layout up front."""
    ops, _ = _operands(one_chip, jnp.float16, False)
    with pytest.raises(Exception, match="Invalid vector type"):
        jax.jit(_full).lower(ops["q"], ops["k"], ops["v"], ops["cur"],
                             ops["table"]).compile()
    with pytest.raises(dispatch.NoKernelPlan):
        dispatch.check_tpu_pool(jnp.float16, platform="tpu")
    dispatch.check_tpu_pool(jnp.float16, platform="cpu")
    for dt in (jnp.float32, jnp.bfloat16, jnp.int8, jnp.float8_e4m3fn):
        dispatch.check_tpu_pool(dt, platform="tpu")


# ------------------------------------------------ the whole decode step

STEP_SLOTS, STEP_SMAX = 4, 11264
STEP_ROWS = (1 + STEP_SLOTS * (STEP_SMAX // PS)) * PS
#: ops that only hand the stacked pool along: the program's arguments,
#: the layer loop and its carry
_PLUMBING = {"parameter", "get-tuple-element", "tuple", "while"}
#: the kernels that address the pool in place
_POOL_KERNELS = ("pool_write_rows", "fused_loki_decode")


def _pool_ops(hlo_text, n_rows):
    """(name, opcode) of every instruction whose result has a dimension of
    ``n_rows``: the ops that hold, or copy, a whole pool or layer."""
    dim = re.compile(r"[\[,]%d[\],]" % n_rows)
    found = []
    for line in hlo_text.splitlines():
        m = re.match(r"\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)", line)
        if not m:
            continue
        op = re.search(r"\s([a-z][a-z0-9\-]*)\(", " " + m.group(2))
        if op and dim.search(m.group(2)[:op.start()]):
            found.append((m.group(1), op.group(1)))
    return found


@pytest.mark.parametrize("program", ["masked", "packed"])
def test_decode_step_writes_the_stacked_pool_in_place(one_chip, monkeypatch,
                                                      program):
    """Qwen2.5-3B's decode step at 2 layers (bf16 pool, Hkv 2, D 128, page
    128) compiled for v5e, with its Pallas kernels compiled for Mosaic:
    the stacked pool rides the layer loop's carry, and no op but the
    aliased row-write kernel and the Loki kernel makes anything of its
    size (no layout copy, bitcast, layer slice or restack). Temporaries
    stay under one layer's pool."""
    monkeypatch.setattr(dispatch, "_interpret", lambda interpret: False)
    cfg = get_config("qwen2.5-3b").replace(
        n_layers=2, param_dtype="bfloat16").with_policy(
        "loki_block", k_f=0.25, d_f=0.25).with_layout(PageLayout("bf16"))
    cfg = cfg.replace(loki=dataclasses.replace(cfg.loki, backend="pallas",
                                               local_window=16))

    def sds(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    n_pages = STEP_ROWS // PS
    params = sds(jax.eval_shape(lambda k: lm.init(k, cfg),
                                jax.random.PRNGKey(0)))
    cache = sds(jax.eval_shape(lambda: lm.init_paged_cache(
        cfg, n_pages, PS, jnp.float32, n_slots=STEP_SLOTS)))
    assert cache["layers"]["attn"]["k"].shape == (2, HKV, D, STEP_ROWS)
    b = STEP_SLOTS if program == "masked" else STEP_SLOTS // 2
    pack = {} if program == "masked" else {"slot_idx": i32(b)}
    fn = lambda p, c, t, n, pt, **kw: lm.decode_step(
        p, cfg, c, t, n, page_table=pt, page_size=PS, **kw)
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        params, cache, i32(b), i32(b), i32(b, STEP_SMAX // PS),
        **pack).compile()
    text = compiled.as_text()
    ops = _pool_ops(text, STEP_ROWS)
    assert any(op == "while" for _, op in ops), "the pool left the carry"
    kernels = [n for n, op in ops if op == "custom-call"]
    assert kernels and all(n.startswith(_POOL_KERNELS) for n in kernels), \
        kernels
    stray = [(n, op) for n, op in ops
             if op not in _PLUMBING and op != "custom-call"]
    assert stray == [], stray
    layer_pool = HKV * D * STEP_ROWS * 2
    ma = compiled.memory_analysis()
    assert ma.temp_size_in_bytes < layer_pool, (ma.temp_size_in_bytes,
                                                layer_pool)
    assert ma.alias_size_in_bytes >= 2 * 2 * layer_pool
