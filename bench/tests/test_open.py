"""The open loop and what the harness takes from a configuration's
reference module: the ``open`` plan, the TTFT and chat readers on hand-made
windows and spans, a whole chat run at a tiny size on the CPU, the weights
of ``qwen2.5-3b`` drawn as before, and an architecture that brings its own
reference module and gets through ``program_config`` and ``make_params``
with nothing in ``bench/lib`` edited."""
import dataclasses
import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny
from bench.lib import drive, flops, harness, spec, traffic, weights

CHAT = "qwen2.5-14b-pp4.chat_poisson"


def _mix():
    with open(os.path.join(spec.BENCH, "traffic", "chat_poisson.json")) as f:
        return json.load(f)


def test_open_plan_same_schedule_any_seed():
    tr = _mix()
    warm = tr["warm_seconds"]
    a = traffic.plan(tr, 7, 152064, 51)
    b = traffic.plan(tr, 2 ** 31 + 12345, 152064, 51)
    # the same sizes due at the same times; only the token ids differ
    assert [(len(p.prompt), p.max_new, p.due) for p in a] == \
        [(len(p.prompt), p.max_new, p.due) for p in b]
    assert not any(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    # each block is its own multiset at the distributions' quantiles, its
    # gaps exponential quantiles summing to its length
    for t0, t1 in ((0.0, warm), (warm, warm + 51)):
        block = [p for p in a if t0 <= p.due < t1]
        n = round(tr["rate"] * (t1 - t0))
        assert len(block) == n and block[0].due == t0
        assert sorted(len(p.prompt) for p in block) == \
            list(traffic.quantiles(tr["prompt_len"], n))
        assert sorted(p.max_new for p in block) == \
            list(traffic.quantiles(tr["max_new"], n))
        g = np.diff([p.due for p in block] + [t1])
        want = traffic.gaps(tr["rate"], n)
        np.testing.assert_allclose(np.sort(g), want * (t1 - t0) / want.sum(),
                                   rtol=1e-9)
    assert all(128 <= len(p.prompt) <= 3072 and 16 <= p.max_new <= 384
               for p in a)
    win = [len(p.prompt) for p in a if p.due >= warm]
    assert np.median(win) == pytest.approx(1024, rel=0.05)
    # another schedule seed orders the same sizes otherwise
    c = traffic.plan({**tr, "schedule_seed": 1}, 7, 152064, 51)
    assert sorted(len(p.prompt) for p in c) == sorted(len(p.prompt) for p in a)
    assert [len(p.prompt) for p in c] != [len(p.prompt) for p in a]


def _seen(rid, due, tokens, prompt=8, submitted=None):
    req = types.SimpleNamespace(rid=rid, prompt=np.zeros(prompt, np.int32),
                                out=[0] * len(tokens))
    return drive.Seen(req, tokens=list(tokens), n_out=len(tokens), due=due,
                      submitted=due if submitted is None else submitted)


def test_ttft_reader_on_a_hand_made_window():
    win = drive.Window(t0=10.0, t1=20.0, seen=[
        _seen(0, 9.0, [9.5]),              # due before the window: out
        _seen(1, 11.0, [11.2, 11.3]),      # 0.2 s
        _seen(2, 12.0, []),                # never starts: to the end, 8 s
        _seen(3, 15.0, [15.4]),            # 0.4 s
        _seen(4, 19.0, [20.5]),            # first token after the end: 1 s
        _seen(5, 20.0, [])])               # due at the close: out
    win.unsent = [19.9]                    # due, never submitted: 0.1 s
    assert sorted(drive.ttfts(win)) == pytest.approx([0.1, 0.2, 0.4, 1.0,
                                                      8.0])
    run = types.SimpleNamespace(window=win)
    assert spec.reader("ttft_p50_ms")(run) == pytest.approx(400.0)
    # linear between ranks: 1.0 + 0.6 * (8.0 - 1.0) s
    assert drive.tail(drive.ttfts(win), 90) == pytest.approx(5.2)
    assert spec.reader("ttft_p50_ms")(types.SimpleNamespace(
        window=drive.Window(t0=0.0, t1=1.0))) is None


def _chat_run(monkeypatch, red):
    """A run of the chat cell's shapes whose trace reduction is ``red``:
    request 1 (600-token prompt) admitted in the window, request 2
    (1000 tokens) admitted before it and first token inside it, request
    3 (3000 tokens) mid-prefill at both ends."""
    cell = spec.load_cell(CHAT)
    seen = [_seen(1, 100.0, [101.0], 600, submitted=100.25),
            _seen(2, 90.0, [102.0], 1000, submitted=90.5),
            _seen(3, 95.0, [], 3000, submitted=95.0)]
    run = harness.Run(
        cfg=cell.config, traffic=cell.traffic,
        dims=flops.dims(cell.config, 3584),
        peaks={"bf16_flops": 197e12}, setup_s=1.0,
        window=drive.Window(t0=99.0, t1=104.0, seen=seen),
        traced=drive.Window(t0=100.0, t1=104.0, seen=seen),
        trace={"modules": {"jit_prefill_chunk": [0.1, 0.3]},
               "busy_s": 3.0, "window_s": 4.0})
    monkeypatch.setattr("bench.lib.spans.of_run", lambda r: red)
    return run


def test_chat_readers_on_hand_made_spans(monkeypatch):
    red = {"admits": [[1, 40], [1, 70]],
           "prefills": [[3, 256], [1, 256], [2, 256], [1, 256], [2, 231],
                        [1, 87], [3, 256]]}
    run = _chat_run(monkeypatch, red)
    # due 100.0, submitted 0.25 s late, then 40 ms in the queue (the
    # second admission of the same request is not a first one)
    assert spec.reader("queue_wait_ms")(run) == pytest.approx(290.0)
    assert spec.reader("prefill_chunk_ms")(run) == pytest.approx(200.0)
    assert spec.reader("device_idle.chat")(run) == pytest.approx(25.0)
    # request 1 from its start: 0, 256, 512; request 2 back from its
    # end (999 prompt tokens before the one decode takes: 4 chunks):
    # 512, 768; request 3 left out
    m = run.dims
    work = [flops.prefill_chunk_flops(m, s, n) for s, n in
            ((0, 256), (256, 256), (512, 87), (512, 256), (768, 231))]
    want = 100 * np.mean(work) / 0.2 / 197e12
    assert spec.reader("prefill_mfu")(run) == pytest.approx(want, rel=1e-12)


def test_chat_readers_without_spans_read_nothing(monkeypatch):
    run = _chat_run(monkeypatch, None)
    for name in ("queue_wait_ms", "prefill_mfu"):
        assert spec.reader(name)(run) is None
    run = _chat_run(monkeypatch, {"admits": [], "prefills": []})
    assert spec.reader("queue_wait_ms")(run) is None
    assert spec.reader("prefill_mfu")(run) is None


def test_chat_run_submits_when_due_and_compiles_nothing_in_the_window():
    keep = {}
    res = tiny.run("chat_poisson", 2 ** 31 + 23, 3.0,
                   config={"serving": {"k_f": 1.0}}, keep=keep)
    assert res["correct"] and res["failed"] == 0, res["checks"]
    want = {m["name"] for m in spec.load_cell(CHAT).metrics(False)}
    assert set(res["metrics"]) == want >= {"ttft_p50_ms", "itl_p95_ms"}
    win = keep["run"].window
    due = [s for s in win.seen if win.t0 <= s.due < win.t1]
    assert len(due) >= 5
    # never before its due time, and at most about a tick after it (the
    # first may wait for the warm stretch's last tick)
    longest = max(t for t, _, _ in win.tick_calls)
    assert all(0 <= s.submitted - s.due <= 2 * longest + 0.1 for s in due)
    assert res["window"]["compile_events"] == []
    arr = res["window"]["arrivals"]
    assert arr["due"] == len(due) + arr["unsent"]
    assert res["attempted"] >= len(due)


def _parent_draw(key, specs, bias_std, scale_std):
    """The weights as ``bench/lib/weights.py`` drew them before a
    configuration's reference module gave the rules (kept frozen)."""
    leaves = []
    for i, (names, shape, dtype) in enumerate(specs):
        k = jax.random.fold_in(key, i)
        name = names[-1]
        dt = jnp.dtype(dtype)
        if name == "table":
            w = jax.random.normal(k, shape, dt) * jnp.asarray(
                shape[-1] ** -0.5, dt)
        elif name in ("wq", "wk", "wv", "wo", "w_in", "w_out"):
            w = jax.random.normal(k, shape, dt) * jnp.asarray(
                shape[-2] ** -0.5, dt)
        elif name in ("bq", "bk", "bv"):
            w = (bias_std * jax.random.normal(k, shape)).astype(dt)
        elif name == "scale":
            w = (1.0 + scale_std * jax.random.normal(k, shape)).astype(dt)
        elif name == "pca":
            w = jnp.broadcast_to(jnp.eye(shape[-1], dtype=dt), shape)
        else:
            raise ValueError(names)
        leaves.append(w)
    return leaves


def test_qwen25_3b_weights_drawn_bit_for_bit_as_before():
    cell = spec.load_cell(tiny.CELL)
    cfg = harness._merge(cell.config, tiny.CONFIG)
    tr = harness._merge(cell.traffic, tiny.SIZES["longctx_decode"])
    _, pc = harness.program_config(cfg, tr, False)
    got = weights.make_params(cfg, pc)
    from repro.models import lm
    shapes = jax.eval_shape(lambda k: lm.init(k, pc), jax.random.PRNGKey(0))
    specs = weights._leaf_specs(shapes)
    w = cfg["weights"]
    old = jax.jit(_parent_draw, static_argnums=(1, 2, 3))(
        jax.random.PRNGKey(np.uint32(w["seed"])), specs,
        float(w["bias_std"]), float(w["norm_scale_std"]))
    new = jax.tree_util.tree_leaves(got)
    assert len(new) == len(old) == len(specs)
    for a, b, s in zip(new, old, specs):
        assert a.dtype == b.dtype and a.shape == b.shape, s
        assert np.array_equal(np.asarray(a).view(np.uint8),
                              np.asarray(b).view(np.uint8)), s


def test_new_architecture_brings_its_rules_as_files(monkeypatch):
    """A mixture of experts (the program's mixtral-8x22b) at a tiny size,
    through a reference module that declares its widths, its structure
    and a rule for a leaf Qwen2 does not have (the router)."""
    from bench.reference import qwen2

    def router(k, shape, dt, w):
        return jnp.full(shape, w["router_init"], dt)

    def program_fields(cfg, resolved):
        moe = dataclasses.replace(
            resolved.moe, n_experts=cfg["num_local_experts"],
            top_k=cfg["num_experts_per_tok"],
            d_ff_expert=cfg["intermediate_size"])
        return {"n_layers": ("num_hidden_layers", cfg["num_hidden_layers"]),
                "d_model": ("hidden_size", cfg["hidden_size"]),
                "n_heads": ("num_attention_heads", 2),
                "n_kv_heads": ("num_key_value_heads", 2),
                "d_ff": ("intermediate_size", cfg["intermediate_size"]),
                "vocab": ("vocab_size", cfg["vocab_size"]),
                "moe": ("num_local_experts", moe)}

    stub = types.SimpleNamespace(
        program_fields=program_fields,
        departures=lambda pc, cfg, eps: [] if pc.moe and not pc.qkv_bias
        else ["not a mixture of experts"],
        LEAVES={**qwen2.LEAVES, "router": router})
    monkeypatch.setattr(spec, "reference", lambda cfg: stub)
    cfg = {"arch": "mixtral-8x22b", "reference": "stub",
           "num_hidden_layers": 2, "hidden_size": 256,
           "intermediate_size": 512, "vocab_size": 1024,
           "num_local_experts": 4, "num_experts_per_tok": 2,
           "weights": {"seed": 5, "bias_std": 0.1, "norm_scale_std": 0.1,
                       "router_init": 0.5},
           "calibration": {"transform": "pre"},
           "serving": {"policy": "loki_block", "backend": "xla",
                       "k_f": 0.25, "d_f": 0.25, "local_window": 16,
                       "page_size": 128, "prefill_chunk": 128,
                       "pool_layout": "bf16"}}
    tr = {"engine": {"n_slots": 2, "smax": 512}}
    cut = ["num_hidden_layers", "hidden_size", "num_attention_heads",
           "num_key_value_heads", "intermediate_size", "vocab_size",
           "num_local_experts"]
    with pytest.raises(ValueError, match="has"):
        harness.program_config(cfg, tr, True, reduced=cut[:-1])
    _, pc = harness.program_config(cfg, tr, True, reduced=cut)
    assert (pc.moe.n_experts, pc.moe.top_k, pc.n_layers) == (4, 2, 2)
    params = weights.make_params(cfg, pc)
    moe = params["layers"]["moe"]
    assert moe["router"].shape == (2, 256, 4)
    assert np.all(np.asarray(moe["router"], np.float32) == 0.5)
    assert moe["w_in"].shape[:2] == (2, 4)
    # the reference's verdict stops the run
    stub.departures = lambda pc, cfg, eps: ["not this model"]
    with pytest.raises(ValueError, match="not this model"):
        harness.program_config(cfg, tr, False)


def test_program_norm_epsilon_must_be_the_files():
    cell = spec.load_cell(CHAT)
    cfg = harness._merge(cell.config, {"rms_norm_eps": 1e-5})
    with pytest.raises(ValueError, match="epsilon"):
        harness.program_config(cfg, cell.traffic, True, cell.reduced)
    _, pc = harness.program_config(cell.config, cell.traffic, True,
                                   cell.reduced)
    assert harness.program_norm_eps(pc) == cell.config["rms_norm_eps"]
    assert (pc.n_layers, pc.d_model, pc.n_heads, pc.n_kv_heads) == \
        (12, 5120, 40, 8)
