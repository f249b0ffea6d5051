"""Smoke test of the serving path on one TPU chip.

    python3 chip_smoke.py

Serves Qwen2.5-3B at its published widths (36 layers, d_model 2048,
vocab 151936) with seeded random bfloat16 weights through the entry
points a user calls: ``ServeConfig.resolve_model`` and ``build_engine``
of ``repro.launch.serve``, the paged engine with its chunked prefill, and
the Pallas decode kernels (policy ``loki_block``, backend ``auto``). Then
it checks the kernels and the decode logits, not tokens:

  * the paged Loki kernel alone, at the model's decode widths, against a
    float64 numpy reference;
  * policy ``loki_block`` on the Pallas kernel against the jnp oracle of
    the kernel's semantics (group-shared block selection) on one cache:
    one token is decoded following the kernel and again following the
    oracle, and every layer hands its inputs to both, which compare the
    blocks they pick and their outputs. With float32 activations the two
    decodes' logits must agree too; at the precision the engine serves
    (bfloat16 activations) the two may part at a near-tied block, which
    is reported with its score gap;
  * policy ``full`` on the paged Pallas kernel against a float32
    ``lm.forward`` over the same tokens at highest matmul precision.

Without a TPU it exits non-zero and prints no result. Run alone, outside
a checkout, it fails at import. The last line of standard output is one
JSON object: ``{"ok": true, "device": {...}}``. Timings on earlier lines
are host-clock smoke figures after ``block_until_ready``, not a benchmark.
One process, no children.
"""
import contextlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SEED = 0
ARCH = "qwen2.5-3b"
N_SLOTS, SMAX, PAGE_SIZE, PREFILL_CHUNK = 4, 2048, 128, 256
PROMPT_LENS = (384, 768, 1152, 1536)
MAX_NEW = 32
CHECK_LEN = 1536             # tokens of the logit checks (12 of 16 blocks)

# Tolerances, as max |a - b| / max |b|:
#  * the paged Loki kernel alone vs float64: its dots run at HIGHEST
#    precision in float32, so what differs is float32 rounding, about
#    1e-6; Mosaic's default precision (bf16 operands) would give about
#    5e-3, as would a kernel that drops a selected block's rows.
KERNEL_VS_F64_TOL = 1e-4
#  * Pallas loki_block vs its jnp oracle on one cache, float32
#    activations and highest matmul precision on both sides: identical
#    math and selection rule, so what differs is float32 accumulation
#    order (online softmax over blocks vs one softmax), about 1e-6
#    relative per layer. The tolerance is several hundred times that and
#    far below the effect of one differently selected block (1e-1).
PALLAS_VS_ORACLE_TOL = 1e-3
#  * layer by layer on the same inputs, either activation dtype: both
#    sides project the query into float32, then score and attend in
#    float32 at full precision over the float32 pool. Where they select
#    the same blocks, their attention outputs differ by float32 noise.
#    Blocks may differ only where the oracle scores them within NEAR_TIE
#    (in logits) of the selection boundary: float32 score noise is far
#    below that.
LAYER_TOL = 1e-4
NEAR_TIE = 1e-3
#  * bf16 activations, the two decodes: each layer's output is rounded to
#    bf16, so one-ulp differences reach the next layer, and a block tied
#    within that drift can swap (on a TPU v5e the logits then end 5e-2
#    to 2e-1 apart). Only decodes that pick the same blocks in every
#    layer are held to a limit, FULL_VS_F32_TOL below.
#  * full vs float32 forward: the served path keeps the same (bf16-exact)
#    weights but rounds activations to bf16 (unit roundoff 2^-9) at every
#    matmul input and residual add, against a reference that keeps them in
#    float32. This drift does not grow much with depth: the same check on
#    the CPU at these widths cut to 2, 4, 8 and 16 layers gave 1.05e-2,
#    1.30e-2, 1.67e-2 and 1.48e-2. The faults the check is there for land
#    far above the tolerance: on the CPU at 2 and 4 layers, one wrong
#    page-table entry gave 0.54 to 0.59 and zeroed attention output 1.34
#    and 1.45.
FULL_VS_F32_TOL = 5e-2


def log(msg: str) -> None:
    print(msg, flush=True)


def fail(msg: str) -> None:
    raise SystemExit(f"chip_smoke FAILED: {msg}")


def rel_err(got, want) -> float:
    import numpy as np
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


@contextlib.contextmanager
def loki_probe(follow: str, records: list):
    """While tracing, hand every layer's loki_block decode to both the
    Pallas route and its group-select oracle on the same inputs, and go
    on with ``follow``'s output ("pallas" or "oracle"). Each layer appends
    to ``records``, in layer order: the kernel's selected blocks (from the
    select kernel, which runs the fused kernel's score-and-select code on
    the same queries), the oracle's, the oracle's group block maxima
    (B, Hkv, nb) and the relative error between the attention outputs."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import dispatch, loki
    from repro.kernels import fused_decode as FD
    from repro.kernels import layout
    from repro.serving import paged_cache as PC
    pallas = dispatch.loki_block_decode

    def probe(q, k, v, cur_len, proj, cfg, *, sliding_window=0,
              logit_scale=None, page_table=None, page_size=0, k_scale=None,
              v_scale=None, layer=None, interpret=None):
        kw = dict(sliding_window=sliding_window, logit_scale=logit_scale,
                  page_table=page_table, page_size=page_size,
                  k_scale=k_scale, v_scale=v_scale)
        got = pallas(q, k, v, cur_len, proj, cfg, layer=layer, **kw)
        # the oracle and the select kernel read the layer's own pools
        k, v = layout.layer_pool(k, layer), layout.layer_pool(v, layer)
        b, dim = q.shape[0], q.shape[-1]
        kd = layout.pool_width(k)
        q_hat = loki.grouped_queries(q, proj, kd)    # the kernel's queries
        want = loki.loki_decode_block(q, k, v, cur_len, proj, cfg,
                                      group_select=True, **kw)
        view = PC.gather_logical_dq(k, k_scale, page_table, page_size)
        _, _, blk, scale = loki.block_maxima(
            q, view, cur_len, proj, cfg, sliding_window=sliding_window,
            logit_scale=logit_scale)
        k_blocks = max(int(cfg.k_f * blk.shape[-1]), 1)
        o_sel = loki.top_blocks(blk, k_blocks, True)[0][:, :, 0]
        k_sel = FD.select_blocks(
            q_hat, k, jnp.broadcast_to(cur_len, (b,)).astype(jnp.int32),
            d=min(max(int(cfg.d_f * dim), 8), kd), k_blocks=k_blocks,
            block_size=cfg.block_size, scale=scale,
            local_window=cfg.local_window, sliding_window=sliding_window,
            page_table=page_table, page_size=page_size, k_scale=k_scale,
            interpret=jax.default_backend() != "tpu")
        err = jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want))
        jax.debug.callback(
            lambda *a: records.append([np.asarray(x) for x in a]),
            k_sel, o_sel, blk.max(axis=2), err, ordered=True)
        return got if follow == "pallas" else want.astype(got.dtype)

    dispatch.loki_block_decode = probe
    try:
        yield
    finally:
        dispatch.loki_block_decode = pallas


def selection_check(records):
    """Per layer on the same inputs: blocks only one side selected, how
    far (in logits) the farthest of them sits from the oracle's selection
    boundary, and the attention output error. Returns (rows, faults)."""
    import numpy as np
    rows, faults = [], []
    for layer, (k_sel, o_sel, blk, err) in enumerate(records):
        k_blocks = k_sel.shape[-1]
        n_diff, tie = 0, 0.0
        for bh in np.ndindex(k_sel.shape[:-1]):
            ks, os_ = set(k_sel[bh]) - {-1}, set(o_sel[bh]) - {-1}
            edge = np.sort(blk[bh])[::-1][k_blocks - 1]
            for j in ks ^ os_:
                tie = max(tie, float(abs(blk[bh][j] - edge)))
            n_diff += len(ks - os_)
        err = float(err)
        rows.append({"layer": layer, "blocks_differ": n_diff,
                     "tie": tie, "err": err})
        if n_diff and tie > NEAR_TIE:
            faults.append(f"layer {layer}: {n_diff} blocks differ, "
                          f"{tie:.3e} from the boundary")
        if not n_diff and not err <= LAYER_TOL:
            faults.append(f"layer {layer}: same blocks, error {err:.3e}")
    return rows, faults


def first_divergence(rec_a, rec_b):
    """First layer whose oracle selection differs between two decode
    trajectories, with the blocks swapped, how far apart they score in
    trajectory a, and how far the live block scores drifted from a to b
    (a swap needs the gap to be at most twice the drift)."""
    import numpy as np
    for layer, (ra, rb) in enumerate(zip(rec_a, rec_b)):
        sa, sb = ra[1], rb[1]
        if np.array_equal(np.sort(sa, -1), np.sort(sb, -1)):
            continue
        live = ra[2] > -1e29
        drift = float(np.max(np.abs(ra[2] - rb[2])[live]))
        swapped, gap = [], 0.0
        for bh in np.ndindex(sa.shape[:-1]):
            d = sorted(set(sa[bh]) ^ set(sb[bh]))
            if d:
                swapped.append((bh, d))
                s = ra[2][bh][d]
                gap = max(gap, float(s.max() - s.min()))
        return {"layer": layer, "swapped": str(swapped), "gap": gap,
                "drift": drift}
    return None


def kernel_vs_float64(rng):
    """The paged fused Loki kernel alone at the model's decode widths
    (Hkv 2, G 8, D 128, d 32, 16 pages of 128, 4 of 16 blocks) over a
    shuffled page table, against a float64 numpy reference of the same
    selection and attention. Returns (max rel err, selections agree)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import fused_decode as FD
    from repro.kernels import layout
    hkv, g, dim, d, ps, n_blk, kb, lw = 2, 8, 128, 32, 128, 16, 4, 16
    n_pages = 1 + n_blk
    cur = 1500
    args = dict(d=d, k_blocks=kb, block_size=ps, local_window=lw,
                page_size=ps, interpret=jax.default_backend() != "tpu")
    q = rng.randn(1, hkv, g, dim).astype(np.float32)
    k = rng.randn(*layout.pool_shape(hkv, dim, n_pages * ps)).astype(
        np.float32)
    v = rng.randn(*layout.pool_shape(hkv, dim, n_pages * ps)).astype(
        np.float32)
    table = (1 + rng.permutation(n_blk)).astype(np.int32)[None]
    ops = [jnp.asarray(x) for x in (q, k, v, [cur], table)]
    got = np.asarray(FD.fused_loki_decode(*ops[:4], page_table=ops[4],
                                          **args), np.float64)
    sel_got = np.asarray(FD.select_blocks(ops[0], ops[1], ops[3],
                                          page_table=ops[4], **args))
    rows = (table[0][:, None] * ps + np.arange(ps)).reshape(-1)
    kl = k.astype(np.float64)[:, :, rows]          # (Hkv, D, S) logical
    vl = v.astype(np.float64)[:, :, rows]
    q64 = q.astype(np.float64)[0] * dim ** -0.5    # (Hkv, G, D)
    want, agree = np.zeros_like(got), True
    for h in range(hkv):
        s = q64[h, :, :d] @ kl[h, :d]              # (G, S)
        s[:, cur:] = -np.inf
        s[:, cur - lw:cur] += 1e4                  # the local window
        blk = s.max(0).reshape(n_blk, ps).max(1)
        sel = np.argsort(-blk, kind="stable")[:kb]
        tok = (sel[:, None] * ps + np.arange(ps)).reshape(-1)
        tok = tok[tok < cur]
        e = q64[h] @ kl[h][:, tok]
        p = np.exp(e - e.max(1, keepdims=True))
        want[0, h] = (p / p.sum(1, keepdims=True)) @ vl[h][:, tok].T
        agree &= set(sel_got[0, h].tolist()) == set(sel.tolist())
    return rel_err(got, want), agree


def main() -> None:
    import jax
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    log(f"device: {device}")
    if dev.platform != "tpu":
        fail(f"needs a TPU; JAX found platform {dev.platform!r}")
    run(dev)
    print(json.dumps({"ok": True, "device": device}), flush=True)


def run(dev) -> None:
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import dispatch
    from repro.launch import serve
    from repro.models import lm
    from repro.serving.engine import Request
    from repro.serving.lifecycle import Status

    log(f"compile cache: {serve.use_compile_cache(ROOT)}")
    rng = np.random.RandomState(SEED)

    # ------------------------------------------------ the kernel alone
    err_kernel, agree = kernel_vs_float64(rng)
    log(f"paged loki kernel vs float64: max rel err {err_kernel:.3e} "
        f"(tol {KERNEL_VS_F64_TOL}), same blocks selected {agree}")
    if not agree or not err_kernel <= KERNEL_VS_F64_TOL:
        fail(f"paged loki kernel vs float64: {err_kernel}, blocks {agree}")

    # ---------------------------------------------------------- build
    sc = serve.ServeConfig(
        engine=serve.EngineSection(arch=ARCH, smoke=False,
                                   policy="loki_block", backend="auto",
                                   n_slots=N_SLOTS, smax=SMAX),
        pool=serve.PoolSection(page_size=PAGE_SIZE,
                               prefill_chunk=PREFILL_CHUNK))
    cfg = sc.resolve_model()
    if (cfg.n_layers, cfg.d_model, cfg.vocab) != (36, 2048, 151936):
        fail(f"not the published widths: {cfg}")
    if dispatch.resolve_backend(cfg.loki.backend) != "pallas":
        fail("backend auto did not resolve to pallas")
    t = time.perf_counter()
    params = jax.block_until_ready(lm.init(jax.random.PRNGKey(SEED), cfg))
    n_params = sum(int(x.size) for x in jax.tree.leaves(params))
    log(f"init: {n_params} params ({cfg.param_dtype}) in "
        f"{time.perf_counter() - t:.1f} s")
    t = time.perf_counter()
    batches = [jnp.asarray(rng.randint(0, cfg.vocab, (2, 512)), jnp.int32)
               for _ in range(2)]
    params = jax.block_until_ready(serve.calibrate_pca(params, cfg, batches))
    log(f"pca calibration: {time.perf_counter() - t:.1f} s")
    eng, paged = sc.build_engine(params, cfg)
    if not paged:
        fail("the paged engine was not built")

    # ---------------------------------------------------------- serve
    def wave(base):
        reqs = [Request(rid=base + i,
                        prompt=rng.randint(0, cfg.vocab, n).astype(np.int32),
                        max_new=MAX_NEW)
                for i, n in enumerate(PROMPT_LENS)]
        for r in reqs:
            eng.submit(r)
        ticks0, t0 = eng.ticks, time.perf_counter()
        eng.drain(max_ticks=2000)
        jax.block_until_ready(eng.cache)
        for r in reqs:
            if r.status != Status.DONE or len(r.out) != MAX_NEW:
                fail(f"request {r.rid}: {r.status} {r.detail!r} "
                     f"after {len(r.out)} tokens")
        return time.perf_counter() - t0, eng.ticks - ticks0

    sec, ticks = wave(0)
    log(f"serve wave 1 (compiles the engine programs): {ticks} ticks in "
        f"{sec:.1f} s")
    sec, ticks = wave(100)
    log(f"serve wave 2: {ticks} ticks in {sec:.2f} s = "
        f"{1e3 * sec / ticks:.1f} ms/tick (host clock; smoke figure, "
        "not a benchmark)")
    st = eng.stats()
    if eng.n_backend_fallbacks or eng.n_packed_fallbacks:
        fail(f"fallbacks fired: backend {eng.n_backend_fallbacks}, "
             f"sealed {eng.n_packed_fallbacks}")
    if dispatch.backend_disabled("pallas") is not None:
        fail(f"pallas disabled: {dispatch.backend_disabled('pallas')}")
    log(f"engine: {eng.n_packed_ticks} packed + {eng.n_masked_ticks} "
        f"masked decode ticks, lifecycle {st.get('lifecycle', '')}")

    # one live slot at the decode position after a prefill of n_pre tokens
    max_pages = eng.max_pages
    tokens = rng.randint(0, cfg.vocab, CHECK_LEN).astype(np.int32)
    n_pre = CHECK_LEN - 1          # prefilled; the last token is decoded
    row = np.arange(1, 1 + max_pages, dtype=np.int32)
    tok = tokens[n_pre:n_pre + 1]
    pos = np.asarray([n_pre], np.int32)
    pt = row[None]

    decode = eng._packed_program(1)
    if "tpu_custom_call" not in decode.lower(
            params, eng.cache, tok, pos, pt, None,
            jnp.asarray([0], jnp.int32)).as_text():
        fail("the engine's decode program holds no Pallas kernel")
    del eng, decode

    def decode_program(c):
        """``lm.decode_step`` jitted for ``c`` on the one-slot cache."""
        return jax.jit(lambda p, ca, t, pl, pt_: lm.decode_step(
            p, c, ca, t, pl, page_table=pt_, page_size=PAGE_SIZE))

    def prefill(c):
        """A one-slot cache of 1 + max_pages pages holding the first n_pre
        tokens, written by ``lm.prefill_chunk`` in the engine's chunks."""
        chunk_fn = jax.jit(lambda p, ca, t, s, nv, r: lm.prefill_chunk(
            p, c, ca, t, s, nv, r, PAGE_SIZE))
        cache = lm.init_paged_cache(c, 1 + max_pages, PAGE_SIZE,
                                    jnp.float32, n_slots=1)
        for start in range(0, n_pre, PREFILL_CHUNK):
            nv = min(PREFILL_CHUNK, n_pre - start)
            chunk = np.zeros((1, PREFILL_CHUNK), np.int32)
            chunk[0, :nv] = tokens[start:start + nv]
            _, cache = chunk_fn(params, cache, jnp.asarray(chunk),
                                jnp.int32(start), jnp.int32(nv), row)
        return cache

    # --------------------------- decode logits: loki_block vs its oracle
    def kernel_vs_oracle(c):
        """Decode the last token once following the kernel and once
        following the oracle, every layer probed. Returns the logits'
        max rel err, the first layer whose selection parts the two
        trajectories, and the layers where kernel and oracle disagree on
        the same inputs."""
        cache = prefill(c)
        out, rec, faults = {}, {}, []
        for follow in ("pallas", "oracle"):
            rec[follow] = []
            with loki_probe(follow, rec[follow]):
                logits, _ = decode_program(c)(params, cache, tok, pos, pt)
            out[follow] = np.asarray(jax.block_until_ready(logits)[0])
            jax.effects_barrier()
            if len(rec[follow]) != c.n_layers:
                fail(f"probe saw {len(rec[follow])} of {c.n_layers} layers")
            rows, bad = selection_check(rec[follow])
            faults += bad
            same = [r["err"] for r in rows if not r["blocks_differ"]]
            log(f"{c.dtype} activations, following {follow}: kernel and "
                f"oracle pick different blocks in "
                f"{sum(r['blocks_differ'] for r in rows)} (layer, head) "
                f"selections, at most {max(r['tie'] for r in rows):.3e} "
                f"from the boundary (limit {NEAR_TIE}); outputs at most "
                f"{max(same, default=0.0):.3e} apart where they agree "
                f"(tol {LAYER_TOL})")
        err = rel_err(out["pallas"], out["oracle"])
        div = first_divergence(rec["pallas"], rec["oracle"])
        agree = int(np.argmax(out["pallas"])) == int(np.argmax(out["oracle"]))
        log(f"{c.dtype} activations, pallas vs oracle trajectory: logits max "
            f"rel err {err:.3e}, argmax agree {agree}; first layer selecting "
            f"differently: {div}")
        return err, div, faults

    with jax.default_matmul_precision("highest"):
        err32, _, faults = kernel_vs_oracle(cfg.replace(dtype="float32"))
    if not np.isfinite(err32) or err32 > PALLAS_VS_ORACLE_TOL:
        fail(f"loki_block pallas vs oracle error {err32} (float32)")
    err16, div, bad = kernel_vs_oracle(cfg)
    faults += bad
    if faults:
        fail("kernel vs oracle on the same inputs: " + "; ".join(faults))
    if not np.isfinite(err16) or (div is None and err16 > FULL_VS_F32_TOL):
        fail(f"loki_block pallas vs oracle error {err16} (bf16)")

    # ------------------------------------------- decode logits: full
    cfg_full = cfg.with_policy("full")
    dec_full = decode_program(cfg_full)
    cache = prefill(cfg_full)
    if "tpu_custom_call" not in dec_full.lower(
            params, cache, tok, pos, pt).as_text():
        fail("the full-policy decode program holds no Pallas kernel")
    got, _ = dec_full(params, cache, tok, pos, pt)
    del cache
    cfg_ref = cfg_full.replace(dtype="float32")
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p, t: lm.forward(p, t, cfg_ref)[0][0, -1])(
            params, jnp.asarray(tokens)[None])
    err_full = rel_err(got[0], want)
    agree = int(np.argmax(got[0])) == int(np.argmax(want))
    log(f"full pallas vs float32 forward: max rel err {err_full:.3e} "
        f"(tol {FULL_VS_F32_TOL}), argmax agree {agree}")

    stats = dev.memory_stats() or {}
    log(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use', 'n/a')}")
    if not np.isfinite(err_full) or err_full > FULL_VS_F32_TOL:
        fail(f"full vs float32 reference error {err_full}")


if __name__ == "__main__":
    main()
