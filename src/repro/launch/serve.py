"""Production serving launcher.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b --smoke \
        --policy loki --requests 6 --max-new 16

``--no-smoke`` serves the architecture at its published widths with
seeded random bfloat16 weights (no warm-up training: AdamW state would not
fit beside the weights); ``chip_smoke.py`` at the repository root drives
that path on a TPU.

Builds the serving engine with the selected attention policy
(full | loki | loki_block | exact_topk | h2o | pcaattn), calibrates PCA
transforms on the fly for Loki policies, and reports per-tick latency and
throughput over a synthetic request stream.

Every knob lives in :class:`ServeConfig`, a frozen dataclass with four
sections — ``engine`` (arch / policy / backend / slots), ``pool`` (page
size, pool size, prefill chunk), ``scheduler`` (policy, per-tick token
budgets, prefix cache) and ``layout`` (the per-component PageLayout spec,
e.g. ``int8:pca:r=32``) — consumed by both engine kinds and printed in
full by ``--dryrun``. The argparse flags are thin aliases over its fields.

``--engine paged`` (default) serves from the paged KV-cache with the
chunked-prefill scheduler (serving/scheduler.py). The allowed set is
derived from the per-layer CacheSpec registry (serving/cache_spec.py), so
*every* family serves paged — hybrid (hymba) and ssm (xlstm) carry their
recurrent state in per-slot StateSlots, whisper's encoder K/V is written
once at admission, and mixtral's sliding-window layers recycle pages that
slide out of the window. Only policies whose caches cannot rebuild exact
prefix attention (h2o, pcaattn) fall back to the dense slot engine.

``--layout`` selects the physical page layout (DESIGN.md §10): storage
dtype (fp32 | fp16 | bf16 | int8 | fp8), storage basis (native | pca —
keys written to pages already projected to the PCA basis, exact at full
rank by Lemma 4.1), and an optional latent rank ``r=N`` truncating the
stored key width. Quantized dtypes carry one f32 scale per physical page
beside the page table; the decode kernels dequantize in their DMA
epilogue.

``--dryrun`` prints the per-layer CacheSpec table for the chosen arch and
policy (what state each layer holds, page budgets, recycle window, bytes
per page under the layout), the full ServeConfig, and exits without
touching the accelerator.
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import time
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.configs.base import ModelConfig, PageLayout, TrainConfig
from repro.core import pca as PCA
from repro.data.synthetic import DataConfig, SyntheticLM, jax_batch
from repro.models import lm
from repro.optim import adamw
from repro.serving import cache_spec as CS
from repro.serving import faults as FI
from repro.serving.engine import Engine, Request, ServingEngine
from repro.serving.lifecycle import Deadline, summarize
from repro.serving.scheduler import PAGED_POLICIES, PagedServingEngine
from repro.training.step import TrainState, make_train_step


# ------------------------------------------------------------ ServeConfig

@dataclasses.dataclass(frozen=True)
class EngineSection:
    """What runs: model, attention policy, kernel backend, batch shape."""
    arch: str = "qwen2.5-3b"
    smoke: bool = True
    kind: str = "paged"            # paged | dense
    policy: str = "loki"
    k_f: float = 0.25
    d_f: float = 0.25
    backend: str = "auto"          # auto | pallas | xla
    n_slots: int = 4
    smax: int = 128


@dataclasses.dataclass(frozen=True)
class PoolSection:
    """Paged-engine pool shape (0 = derive from the spec table)."""
    page_size: int = 0             # tokens per page (0 = loki block_size)
    n_pages: int = 0               # pool size (0 = fit all slots)
    prefill_chunk: int = 32
    device_pages: int = 0          # tiered pool (§13): HBM frames; 0 = off
    max_inflight: int = 2          # bounded async fetch queue depth


@dataclasses.dataclass(frozen=True)
class SchedulerSection:
    """Tick policy: admission order, per-tick token budgets, sharing."""
    policy: str = "fifo"           # fifo | priority
    prefill_budget: int = 0        # prompt tok/tick (0 = one chunk)
    decode_budget: int = 0         # live slots decoded/tick (0 = all)
    prefix_cache: bool = True


@dataclasses.dataclass(frozen=True)
class LifecycleSection:
    """Request-lifecycle hardening knobs (DESIGN.md §11)."""
    admission: str = "strict"      # strict | lenient (oversized requests)
    faults: str = ""               # FaultPlan.parse spec; '' = off
    audit: bool = False            # per-tick invariant auditor
    shed_after: int = 0            # preemptions before SHED (0 = never)
    ttft_deadline: float = 0.0     # s to first token (0 = none)
    total_deadline: float = 0.0    # s to completion (0 = none)

    def fault_plan(self) -> Optional[FI.FaultPlan]:
        return FI.FaultPlan.parse(self.faults) if self.faults else None

    def request_deadline(self) -> Optional[Deadline]:
        if not (self.ttft_deadline or self.total_deadline):
            return None
        return Deadline(ttft=self.ttft_deadline or None,
                        total=self.total_deadline or None)


@dataclasses.dataclass(frozen=True)
class LayoutSection:
    """Physical page layout spec, ``PageLayout.parse`` syntax
    (e.g. ``fp16``, ``fp32:pca``, ``int8:pca:r=32``); '' = default."""
    spec: str = ""

    def page_layout(self) -> PageLayout:
        return PageLayout.parse(self.spec) if self.spec else PageLayout()


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """One object holding every serving knob; the CLI flags are aliases.

    ``resolve_model()`` folds the policy and layout into a ModelConfig and
    ``build_engine()`` constructs whichever engine the spec table allows —
    the rest of the launcher (and any harness) only talks to the
    :class:`~repro.serving.engine.Engine` protocol it returns."""
    engine: EngineSection = dataclasses.field(default_factory=EngineSection)
    pool: PoolSection = dataclasses.field(default_factory=PoolSection)
    scheduler: SchedulerSection = dataclasses.field(
        default_factory=SchedulerSection)
    layout: LayoutSection = dataclasses.field(default_factory=LayoutSection)
    lifecycle: LifecycleSection = dataclasses.field(
        default_factory=LifecycleSection)
    requests: int = 6
    max_new: int = 16
    warm_steps: int = 60

    @classmethod
    def from_args(cls, a: argparse.Namespace) -> "ServeConfig":
        return cls(
            engine=EngineSection(
                arch=a.arch, smoke=a.smoke, kind=a.engine, policy=a.policy,
                k_f=a.k_f, d_f=a.d_f, backend=a.backend,
                n_slots=a.n_slots, smax=a.smax),
            pool=PoolSection(page_size=a.page_size, n_pages=a.n_pages,
                             prefill_chunk=a.prefill_chunk,
                             device_pages=a.device_pages,
                             max_inflight=a.max_inflight),
            scheduler=SchedulerSection(
                policy=a.sched_policy, prefill_budget=a.prefill_budget,
                decode_budget=a.decode_budget,
                prefix_cache=a.prefix_cache == "on"),
            layout=LayoutSection(spec=a.layout),
            lifecycle=LifecycleSection(
                admission=a.admission, faults=a.faults, audit=a.audit,
                shed_after=a.shed_after, ttft_deadline=a.ttft_deadline,
                total_deadline=a.total_deadline),
            requests=a.requests, max_new=a.max_new,
            warm_steps=a.warm_steps)

    def resolve_model(self) -> ModelConfig:
        """The served ModelConfig: smoke presets keep float32 weights;
        published widths serve bfloat16 weights (training stays f32)."""
        if self.engine.smoke:
            cfg = get_smoke_config(self.engine.arch)
        else:
            cfg = get_config(self.engine.arch).replace(
                param_dtype="bfloat16")
        policy = self.engine.policy
        if cfg.family == "ssm" and policy != "full":
            print(f"note: {self.engine.arch} has no KV cache; policy "
                  "forced to full")
            policy = "full"
        if policy != "full":
            cfg = cfg.with_policy(policy, k_f=self.engine.k_f,
                                  d_f=self.engine.d_f)
        lay = self.layout.page_layout()
        if lay != PageLayout():
            cfg = cfg.with_layout(lay)
        return cfg

    def build_engine(self, params, cfg: ModelConfig) -> Tuple[Engine, bool]:
        """Construct the engine the spec table allows; (engine, paged?)."""
        pageable, why = CS.pageable(cfg)
        paged = self.engine.kind == "paged" and pageable
        if self.engine.kind == "paged" and not paged:
            print(f"note: {why}; falling back to the dense engine")
        lc = self.lifecycle
        if paged:
            eng = PagedServingEngine(
                params, cfg, n_slots=self.engine.n_slots,
                smax=self.engine.smax,
                page_size=self.pool.page_size or None,
                n_pages=self.pool.n_pages or None,
                prefill_chunk=self.pool.prefill_chunk,
                backend=self.engine.backend,
                policy=self.scheduler.policy,
                prefill_budget=self.scheduler.prefill_budget or None,
                decode_budget=self.scheduler.decode_budget or None,
                prefix_cache=self.scheduler.prefix_cache,
                admission=lc.admission,
                shed_after=lc.shed_after or None,
                faults=lc.fault_plan(), audit=lc.audit,
                device_pages=self.pool.device_pages or None,
                max_inflight=self.pool.max_inflight)
        else:
            eng = ServingEngine(params, cfg, n_slots=self.engine.n_slots,
                                smax=self.engine.smax,
                                backend=self.engine.backend,
                                admission=lc.admission)
        return eng, paged

    def describe(self, cfg: ModelConfig) -> str:
        """The --dryrun report: every section, plus derived quantities."""
        lay = cfg.page_layout
        ps = self.pool.page_size or cfg.loki.block_size
        lines = [CS.format_spec_table(cfg, self.engine.smax, ps)]
        ok, why = CS.pageable(cfg)
        lines.append("engine: paged" if ok and self.engine.kind == "paged"
                     else "engine: dense" if self.engine.kind == "dense"
                     else f"engine: dense fallback — {why}")
        lines.append(
            f"scheduler: policy={self.scheduler.policy} prefill_budget="
            f"{self.scheduler.prefill_budget or self.pool.prefill_chunk} "
            f"tok/tick decode_budget="
            f"{self.scheduler.decode_budget or self.engine.n_slots} "
            "tok/tick")
        can_share, share_why = CS.prefix_shareable(cfg)
        if not self.scheduler.prefix_cache:
            lines.append("prefix-cache: off (by flag)")
        elif can_share:
            lines.append("prefix-cache: on (page-granular, COW tail, LRU "
                         "eviction before preemption)")
        else:
            lines.append(f"prefix-cache: bypassed — {share_why}")
        bpr = lay.bytes_per_page_row(cfg.resolved_head_dim, cfg.n_kv_heads)
        lines.append(
            f"layout: {lay.describe()} — {bpr * ps} B/page/layer"
            + (" (per-page f32 scales beside the table)"
               if lay.quantized else ""))
        if self.pool.device_pages:
            d = CS.latent_score_width(cfg)
            lines.append(
                f"tiered pool: {self.pool.device_pages} device frames, "
                f"host offload beyond, rank-{d} latent sidecar resident, "
                f"<= {self.pool.max_inflight} fetches in flight "
                "(demote-before-preempt)")
        lc = self.lifecycle
        plan = lc.fault_plan()
        lines.append(
            f"lifecycle: admission={lc.admission}"
            + (f" shed_after={lc.shed_after}" if lc.shed_after else "")
            + (f" ttft_deadline={lc.ttft_deadline}s" if lc.ttft_deadline
               else "")
            + (f" total_deadline={lc.total_deadline}s" if lc.total_deadline
               else "")
            + (f" faults=[{plan.describe()}]" if plan is not None else "")
            + (" audit=per-tick" if lc.audit else ""))
        lines.append("paged-servable archs (default policy): "
                     + ", ".join(CS.servable_archs()))
        return "\n".join(lines)


def _frames(cfg, seed: int, batch: int = 1):
    """Deterministic stand-in encoder frames (offline container: no audio
    frontend; the conv stem is stubbed, see configs/whisper_small.py)."""
    return jax.random.normal(jax.random.PRNGKey(seed),
                             (batch, cfg.enc_seq, cfg.d_model),
                             jnp.float32)


def build_parser() -> argparse.ArgumentParser:
    """Thin aliases over ServeConfig's fields (see ServeConfig.from_args)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b")
    ap.add_argument("--smoke", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="smoke preset (default) or, with --no-smoke, the "
                         "published widths with bf16 weights")
    ap.add_argument("--policy", default="loki",
                    choices=["full", "loki", "loki_block", "exact_topk",
                             "h2o", "pcaattn"])
    ap.add_argument("--k-f", type=float, default=0.25)
    ap.add_argument("--d-f", type=float, default=0.25)
    ap.add_argument("--backend", default="auto",
                    choices=["auto", "pallas", "xla"],
                    help="decode kernel backend for loki_block "
                         "(core/dispatch.py; auto = Pallas on TPU)")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--n-slots", type=int, default=4)
    ap.add_argument("--smax", type=int, default=128)
    ap.add_argument("--engine", default="paged", choices=["paged", "dense"],
                    help="paged = page-pool cache + chunked-prefill "
                         "scheduler (serving/scheduler.py); dense = the "
                         "preallocated slot cache")
    ap.add_argument("--page-size", type=int, default=0,
                    help="tokens per KV page (0 = loki block_size)")
    ap.add_argument("--n-pages", type=int, default=0,
                    help="page pool size (0 = fit all slots at their "
                         "spec-table page bound)")
    ap.add_argument("--prefill-chunk", type=int, default=32,
                    help="prompt tokens prefetched per tick (paged engine)")
    ap.add_argument("--device-pages", type=int, default=0,
                    help="tiered KV pool (DESIGN.md §13): full-D K/V "
                         "frames kept in device memory; pages beyond "
                         "spill to pinned host buffers and promote back "
                         "through the Loki-guided fetch queue (0 = "
                         "single-tier; needs a loki policy)")
    ap.add_argument("--max-inflight", type=int, default=2,
                    help="outstanding async host->device fetches of the "
                         "tiered pool (bounded staging budget)")
    ap.add_argument("--sched-policy", default="fifo",
                    choices=["fifo", "priority"],
                    help="paged-engine SchedulerPolicy (serving/policy.py);"
                         " priority admits by Request.priority and may "
                         "preempt a lower class for a slot")
    ap.add_argument("--prefill-budget", type=int, default=0,
                    help="prompt tokens computed per tick across chunks/"
                         "slots (0 = one chunk per tick)")
    ap.add_argument("--decode-budget", type=int, default=0,
                    help="live slots decoded per tick (0 = all)")
    ap.add_argument("--prefix-cache", default="on", choices=["on", "off"],
                    help="share identical prompt-prefix pages across "
                         "requests (auto-bypassed for configs whose spec "
                         "table marks components unshareable)")
    ap.add_argument("--layout", default="",
                    help="PageLayout spec 'dtype[:basis][:r=N]' — dtype "
                         "fp32|fp16|bf16|int8|fp8, basis native|pca, "
                         "latent rank r (pca only); e.g. 'int8:pca:r=32'. "
                         "Empty = fp32 native (bit-identical to PR 5)")
    ap.add_argument("--admission", default="strict",
                    choices=["strict", "lenient"],
                    help="strict FAILs requests whose prompt + max_new "
                         "can never fit smax at submit(); lenient keeps "
                         "the legacy truncate/cap degraded modes")
    ap.add_argument("--faults", default="",
                    help="deterministic fault-injection spec "
                         "(serving/faults.py), e.g. "
                         "'seed=3,nan_logits=0.05,kernel_fail@7'; sites: "
                         + ", ".join(FI.FaultPlan.SITES))
    ap.add_argument("--audit", action="store_true",
                    help="run the pool/slot/table invariant auditor after "
                         "every tick (raises AuditError on violation)")
    ap.add_argument("--shed-after", type=int, default=0,
                    help="preemptions a request survives before being "
                         "shed (terminal SHED + retry-after hint); "
                         "0 = never shed")
    ap.add_argument("--ttft-deadline", type=float, default=0.0,
                    help="per-request seconds-to-first-token budget "
                         "(0 = none)")
    ap.add_argument("--total-deadline", type=float, default=0.0,
                    help="per-request total wall budget in seconds "
                         "(0 = none)")
    ap.add_argument("--warm-steps", type=int, default=60,
                    help="brief training so generation has signal "
                         "(smoke presets only)")
    ap.add_argument("--dryrun", action="store_true",
                    help="print the per-layer CacheSpec table and the "
                         "full ServeConfig, then exit")
    return ap


def use_compile_cache(root: str) -> str:
    """Keep JAX's persistent compilation cache at a fixed path so repeat
    runs skip compiling. ``JAX_COMPILATION_CACHE_DIR``, when set, is left
    to JAX; otherwise ``<root>/.jax_cache`` (the path is part of the
    cache key, so it never depends on a temp name, PID or time). For
    entry points only: library code that tests import sets nothing."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(os.path.abspath(root), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def calibrate_pca(params, cfg: ModelConfig, batches, frames=None):
    """Install calibrated PCA projections when the policy or the page
    layout needs the PCA basis; otherwise return ``params`` unchanged."""
    needs_pca = (cfg.attn_policy() in ("loki", "loki_block", "pcaattn")
                 or cfg.page_layout.basis == "pca")
    if not needs_pca:
        return params
    calib = PCA.calibrate_model(params, cfg, batches, frames=frames)
    return PCA.install_projections(params, calib, cfg.loki.transform)


def main():
    args = build_parser().parse_args()
    sc = ServeConfig.from_args(args)
    cfg = sc.resolve_model()

    if args.dryrun:
        print(sc.describe(cfg))
        return
    use_compile_cache(os.path.join(os.path.dirname(__file__), "..", "..",
                                   ".."))

    dcfg = DataConfig(vocab=cfg.vocab, seq_len=96, global_batch=8, seed=7,
                      n_states=32, temperature=0.22)
    data = SyntheticLM(dcfg)

    def batch_with_extras(i):
        batch = jax_batch(data.batch_at(i))
        if cfg.is_encoder_decoder:
            batch["frames"] = _frames(cfg, i, batch["tokens"].shape[0])
        return batch

    params = lm.init(jax.random.PRNGKey(0), cfg)
    if sc.warm_steps and sc.engine.smoke:
        tcfg = TrainConfig(lr=3e-3, warmup_steps=5,
                           total_steps=sc.warm_steps)
        state = TrainState(params, adamw.init_state(params))
        step = jax.jit(make_train_step(cfg, tcfg))
        for i in range(sc.warm_steps):
            state, m = step(state, batch_with_extras(i))
        params = state.params
        print(f"warmed {sc.warm_steps} steps, loss "
              f"{float(m['loss']):.3f}")

    batches = [jnp.asarray(data.batch_at(1000 + i)["tokens"])
               for i in range(2)]
    frames = (_frames(cfg, 0, batches[0].shape[0])
              if cfg.is_encoder_decoder else None)
    params = calibrate_pca(params, cfg, batches, frames=frames)

    eng, paged = sc.build_engine(params, cfg)
    if paged:
        extra = (f" window={eng.window} (recycling)" if eng.window else "")
        share = ("on" if eng.prefix_caching else
                 f"bypassed ({eng.prefix_cache_reason})"
                 if sc.scheduler.prefix_cache else "off")
        print(f"paged engine: page_size={eng.page_size} "
              f"pool={eng.pool.n_pages} pages "
              f"(budget {eng.req_budget}/request){extra} "
              f"layout={cfg.page_layout.describe()} "
              f"policy={eng.policy.name} "
              f"budgets={eng.budget.prefill_tokens}p/"
              f"{eng.budget.decode_tokens}d tok/tick "
              f"prefix-cache={share}")
    # the priority policy needs classes to tell apart: spread the demo
    # stream over two of them (even rids are urgent)
    deadline = sc.lifecycle.request_deadline()
    reqs = [Request(rid=i,
                    prompt=data.batch_at(4000 + i)["tokens"][0, :24 + 4 * i],
                    max_new=sc.max_new,
                    priority=(i + 1) % 2
                    if sc.scheduler.policy == "priority" else 0,
                    deadline=deadline,
                    frames=(np.asarray(_frames(cfg, 4000 + i)[0])
                            if cfg.is_encoder_decoder else None))
            for i in range(sc.requests)]
    for r in reqs:
        eng.submit(r)
    t0 = time.time()
    eng.drain()
    dt = time.time() - t0
    toks = sum(len(r.out) for r in reqs)
    print(f"policy={cfg.attn_policy()} served {len(reqs)} requests "
          f"({toks} tokens) in {eng.ticks} ticks, {dt:.1f}s "
          f"-> {toks/dt:.1f} tok/s, {1e3*dt/max(eng.ticks,1):.0f} ms/tick")
    st = eng.stats()
    line = f"lifecycle: {summarize(reqs)}"
    for k in ("n_stalled", "n_shed", "n_quarantined",
              "n_backend_fallbacks"):
        if st.get(k):
            line += f" {k}={st[k]}"
    if st.get("faults"):
        line += f" faults={st['faults']}"
    print(line)
    if st.get("tick_phases_ms"):
        print("tick phases (ms per tick, mean/p95/max): " + ", ".join(
            f"{k.removeprefix('engine.')} {v['mean']:.2f}/{v['p95']:.2f}/"
            f"{v['max']:.2f}" for k, v in st["tick_phases_ms"].items()))
    for r in reqs:
        if str(r.status) not in ("done",):
            print(f"  req{r.rid}: {r.status} — {r.detail}")
    if paged and eng.prefix_caching:
        print(f"prefix cache: {eng.n_prefix_hit_tokens} hit tokens, "
              f"{eng.n_prefill_computed_tokens} computed "
              f"(hit rate {eng.prefix_hit_rate():.2f}), "
              f"{eng.n_cow_copies} COW copies, "
              f"{eng.pool.n_evicted} evictions")
    if st.get("tiered"):
        ti = st["tiered"]
        print(f"tiered pool: {ti['device_pages']} device frames, "
              f"{ti['n_demoted']} demoted / {ti['n_promoted']} promoted, "
              f"prefetch hit rate {ti['prefetch_hit_rate']:.2f}, "
              f"{ti['n_sync_fetches']} sync fetches, "
              f"{ti['n_decode_reruns']} decode reruns")
    for r in reqs[:2]:
        print(f"  req{r.rid}: {np.asarray(r.out)[:10]}")
    print("done")


if __name__ == "__main__":
    main()
