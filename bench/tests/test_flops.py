"""The FLOP and byte functions against counts made by hand at the shapes
of ``qwen2.5-3b.longctx_decode`` (D 128, 2 KV heads of groups of 8, d 32,
blocks of 128, 22 of 88 blocks selected, bf16 pool)."""
import json
import os

from bench.lib import flops

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def dims():
    with open(os.path.join(ROOT, "bench/configs/qwen2.5-3b.json")) as f:
        return flops.dims(json.load(f), smax=11264)


def test_dims_of_cell_one():
    m = dims()
    assert (m.layers, m.heads, m.kv_heads, m.head_dim, m.group) == \
        (36, 16, 2, 128, 8)
    assert (m.d_slice, m.k_blocks, m.block, m.pool_itemsize) == \
        (32, 22, 128, 2)


def test_loki_kernel_bytes_by_hand():
    # context 6000: 47 live blocks, 22 selected. Per (slot, KV head,
    # layer): d-slice 47*128*32*2 = 385,024 B; K and V of the selected
    # blocks 2*22*128*128*2 = 1,441,792 B; queries in and outputs out,
    # float32, 2*8*128*4 = 8,192 B. Total 1,835,008 B, times 2 heads and
    # 36 layers.
    _, b = flops.loki_kernel_cost(dims(), [6000])
    assert b == 1_835_008 * 2 * 36
    # a short context (300 tokens: 3 live blocks, all selected)
    _, b = flops.loki_kernel_cost(dims(), [300])
    assert b == (3 * 128 * 32 * 2 + 2 * 3 * 128 * 128 * 2 + 8192) * 72
    # flops at 6000: scores 2*8*32*47*128 + exact 4*8*128*22*128
    f, _ = flops.loki_kernel_cost(dims(), [6000])
    assert f == (2 * 8 * 32 * 47 * 128 + 4 * 8 * 128 * 22 * 128) * 72


def test_selected_tokens():
    m = dims()
    assert flops.selected_tokens(m, 300) == 300
    assert flops.selected_tokens(m, 22 * 128) == 22 * 128
    # 6000 = 46 full blocks and 112 tokens: 21 full blocks + the tail
    assert flops.selected_tokens(m, 6000) == 21 * 128 + 112


def test_decode_step_flops_by_hand():
    m = dims()
    layer = (2 * 2048 * 2048 + 2 * 2048 * 256 + 3 * 2048 * 11008)
    want = (2 * 36 * layer + 2 * 2048 * 151936
            + 2 * 36 * (16 + 2) * 128 * 128
            + 36 * (2 * 16 * 32 * 6000 + 4 * 16 * 128 * (21 * 128 + 112)))
    assert flops.decode_step_flops(m, [6000]) == want
    assert flops.decode_step_flops(m, [6000, 6000]) == 2 * want


def test_roofline_share_names_the_bound():
    share, bound = flops.roofline_share(1e9, 819e6, 2e-3, 197e12, 819e9)
    assert bound == "memory" and abs(share - 50.0) < 1e-9
    share, bound = flops.roofline_share(197e9, 1.0, 4e-3, 197e12, 819e9)
    assert bound == "compute" and abs(share - 25.0) < 1e-9


def test_prefill_chunk_flops_by_hand():
    # qwen2.5-14b-pp4: 12 layers, 40 query and 8 KV heads of 128, d_ff
    # 13824; per layer a token multiplies 275,251,200 weights
    with open(os.path.join(ROOT, "bench/configs/qwen2.5-14b-pp4.json")) as f:
        m = flops.dims(json.load(f), smax=3584)
    assert flops.layer_matmul_params(m) == 275_251_200
    # a full chunk of 256 tokens at positions 512..767: each attends to
    # 513..768 keys, 256 * 512 + 256 * 257 / 2 = 163,968 in all
    want = (2 * 256 * 12 * 275_251_200 + 2 * 12 * 256 * 48 * 128 * 128
            + 4 * 12 * 40 * 128 * 163_968)
    assert flops.prefill_chunk_flops(m, 512, 256) == want
    # the last, partial chunk of a prompt: 3 tokens from position 0
    assert flops.prefill_chunk_flops(m, 0, 3) == (
        2 * 3 * 12 * 275_251_200 + 2 * 12 * 3 * 48 * 128 * 128
        + 4 * 12 * 40 * 128 * 6)
