"""Serving launcher: continuous batching with a paged KV cache.

The default path is :class:`repro.serving.engine.PagedServingEngine`:

* block-table paged KV pool — slots of different lengths share one
  preallocated pool; finished requests free their pages immediately,
* admission queue + continuous batching — queued requests join the
  running batch mid-flight (no wave barrier, no dummy padding),
* dynamic page growth + preemption — admission reserves prompt-sized
  pages, decode pages are granted on demand, and under pool pressure
  (``--pool-blocks``) the youngest request is swapped out to host memory
  (``--preempt-mode swap``) or re-prefilled (``recompute``);
  ``--no-preempt`` restores the conservative full-reservation baseline,
* chunked prefill for long prompts,
* bf16 or PMQ-compressed weights (§3.2 bit buckets; ``--pmq`` compresses
  the demo model in-process); OTP masks at decode time (deterministic
  argmax — the τ→0 limit, paper §3.4),
* host-offloaded expert buckets (``--resident-experts N``, implies
  ``--pmq``): cold PMQ rows live in host memory, a router-stats EMA
  prefetches the hot set, misses upload synchronously and replay
  (:mod:`repro.serving.offload`),
* async expert streaming (``--async-offload``): planner-driven uploads
  stage into shadow device buffers while the current megastep computes
  and commit at the next boundary — outputs stay bit-identical to the
  synchronous path; ``--offload-dir DIR`` extends the store to a third
  tier (mmap'd CRC-checked packed buckets on disk behind a
  byte-budgeted pinned host cache, ``--host-expert-bytes B``)
  (docs/serving_offload.md),
* TTFT / per-token latency / queue depth / expert-activation metrics
  (:mod:`repro.serving.metrics`),
* request-lifecycle tracing (``--trace-out trace.json`` writes a
  Perfetto-viewable Chrome trace + JSONL event log; ``--trace-level``
  picks the detail) and expert-routing telemetry incl. the
  bit-misallocation report (:mod:`repro.serving.trace`,
  docs/observability.md),
* multi-tenant scheduling policy (``--policy fcfs|priority|fair``,
  ``--tenant-weights a=2,b=1`` for weighted-deficit token fairness,
  ``--ttft-budget-ms`` for SLO load shedding) executed through the
  declarative resource controller — every admit/preempt/grow/shed/
  expert-upload is a reconciliation plan step
  (:mod:`repro.serving.controller`, docs/serving_scheduling.md),
* the fail-closed fault plane (``--chaos-seed N`` attaches a seeded
  deterministic FaultPlan, ``--deadline-steps N`` bounds every request):
  injected faults recover bit-exact (retry / re-fetch / degrade) or
  terminate typed — never wrong tokens (:mod:`repro.serving.faults`,
  docs/serving_robustness.md).

:class:`BatchedServer` is the legacy static *wave* batcher kept for
comparison (``--legacy``): it pads every wave with dummy requests and
re-prefills per wave — the baseline the paged engine exists to beat.

By default it serves the arch's reduced CPU-sized twin
(examples/serve_batched.py). ``--published`` serves the registry config
at its published widths, with ``--layers N`` cutting only the depth —
the same engine construction ``chip_smoke.py`` drives on a TPU. Weights,
calibration tokens and prompts all come from ``--seed``.
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.registry import ARCH_IDS, get_config
from ..models.registry import get_model
from ..serving import EngineConfig, PagedServingEngine
from ..serving import Request as PagedRequest
from .compile_cache import enable_compile_cache

__all__ = [
    "BatchedServer", "Request", "build_engine", "build_model", "main",
    "parser", "serving_config",
]


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray  # [S] int32
    max_new: int = 16
    out: Optional[List[int]] = None


class BatchedServer:
    """Static-batch wave server over a fixed slot count (legacy baseline).

    Kept for A/B comparison against the paged engine: it admits in waves,
    pads short waves with dummy requests, and holds every slot until the
    wave's longest request finishes.
    """

    def __init__(self, cfg, params, max_slots: int = 4, prompt_len: int = 32):
        self.cfg = cfg
        self.bundle = get_model(cfg)
        self.params = params
        self.max_slots = max_slots
        self.prompt_len = prompt_len
        self._decode = jax.jit(self.bundle.decode_step, donate_argnums=(1,))
        self._prefill = jax.jit(self.bundle.prefill)
        self.stats = {"prefill_s": [], "decode_s": [], "active": []}

    def _pad_prompts(self, reqs: List[Request]) -> jnp.ndarray:
        toks = np.zeros((len(reqs), self.prompt_len), np.int32)
        for i, r in enumerate(reqs):
            p = r.prompt[-self.prompt_len :]
            toks[i, -len(p) :] = p
        return jnp.asarray(toks)

    def serve(self, reqs: List[Request]) -> Dict[int, List[int]]:
        """Serve a wave of requests (grouped into slot-sized batches)."""
        results: Dict[int, List[int]] = {}
        for i in range(0, len(reqs), self.max_slots):
            wave = reqs[i : i + self.max_slots]
            while len(wave) < self.max_slots:  # pad wave with a dummy
                # max_new=0: a dummy must never extend the wave's decode
                # loop nor count toward latency/throughput stats
                wave = wave + [Request(rid=-1, prompt=wave[0].prompt, max_new=0)]
            tokens = self._pad_prompts(wave)
            max_new = max(r.max_new for r in wave)
            t0 = time.time()
            cache, logits = self._prefill(self.params, {"tokens": tokens})
            jax.block_until_ready(logits)
            self.stats["prefill_s"].append(time.time() - t0)
            # the prefill cache covers exactly the prompt; extend it so
            # decode steps have somewhere to write their K/V
            pad = ((0, 0), (0, 0), (0, max_new), (0, 0), (0, 0))
            cache = dict(
                cache, k=jnp.pad(cache["k"], pad), v=jnp.pad(cache["v"], pad)
            )
            cur = jnp.argmax(logits[:, -1, :], axis=-1)[:, None].astype(jnp.int32)
            outs = [[] for _ in wave]
            for j, r in enumerate(wave):
                if r.rid >= 0 and r.max_new > 0:
                    outs[j].append(int(cur[j, 0]))
            for step in range(max_new - 1):
                # each decode step writes at the next cache position —
                # never clamp to prompt_len-1 (that overwrote one slot
                # forever and decoded against a stale cache)
                pos = jnp.int32(self.prompt_len + step)
                t0 = time.time()
                cache, logits = self._decode(self.params, cache, cur, pos)
                jax.block_until_ready(logits)
                self.stats["decode_s"].append(time.time() - t0)
                self.stats["active"].append(
                    sum(1 for r in wave if r.rid >= 0 and step + 1 < r.max_new)
                )
                cur = jnp.argmax(logits[:, -1, :], axis=-1)[:, None].astype(jnp.int32)
                for j, r in enumerate(wave):
                    if r.rid >= 0 and step + 1 < r.max_new:
                        outs[j].append(int(cur[j, 0]))
            for j, r in enumerate(wave):
                if r.rid >= 0:
                    results[r.rid] = outs[j]
        return results

    def summary(self) -> Dict[str, float]:
        d = np.asarray(self.stats["decode_s"])
        # throughput counts only real (non-dummy, still-decoding) slots
        gen = float(np.sum(self.stats["active"]))
        return {
            "prefill_mean_s": float(np.mean(self.stats["prefill_s"])),
            "decode_mean_s": float(np.mean(d)) if d.size else 0.0,
            "decode_p95_s": float(np.percentile(d, 95)) if d.size else 0.0,
            "tokens_per_s": gen / float(d.sum()) if d.size else 0.0,
        }


def _compress_for_serving(cfg, params, seed: int = 0):
    """PMQ-compress the demo model on synthetic calibration tokens (the
    layer-uniform stacked layout from repro.core.pipeline — the same
    layout benchmarks/serving_latency.py serves)."""
    from ..core import pipeline

    rng = np.random.default_rng(seed)
    tokens = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (4, 64)).astype(np.int32)
    )
    calib = pipeline.calibrate(params, tokens, cfg)
    params_c, _ = pipeline.compress_for_serving(params, calib, cfg)
    return params_c


def _parse_tenant_weights(spec: str):
    """Parse ``"name=w,name=w"`` into the hashable pair tuple
    EngineConfig carries; refuses empty names, repeats, and w <= 0."""
    pairs = []
    seen = set()
    for item in spec.split(","):
        name, eq, w = item.partition("=")
        try:
            weight = float(w)
        except ValueError:
            weight = -1.0
        if not name or not eq or weight <= 0 or name in seen:
            raise SystemExit(
                "--tenant-weights expects 'name=w,name=w' with unique "
                f"names and w > 0 (got {spec!r})"
            )
        seen.add(name)
        pairs.append((name, weight))
    return tuple(pairs)


def parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--arch", choices=ARCH_IDS, default="moonshot-v1-16b-a3b")
    p.add_argument("--published", action="store_true",
                   help="serve the registry config at its published widths "
                        "(default: its reduced CPU-sized twin)")
    p.add_argument("--layers", type=int, default=None, metavar="N",
                   help="cut the depth to N layers (widths untouched)")
    p.add_argument("--seed", type=int, default=0,
                   help="seed of the weights, calibration tokens and prompts")
    p.add_argument("--requests", type=int, default=8)
    p.add_argument("--prompt-len", type=int, default=24)
    p.add_argument("--max-new", type=int, default=16)
    p.add_argument("--slots", type=int, default=4)
    p.add_argument("--block-size", type=int, default=16)
    p.add_argument("--pmq", action="store_true",
                   help="serve PMQ-compressed experts (§3.2 bit buckets) "
                        "instead of full-precision weights")
    p.add_argument("--resident-experts", type=int, default=None,
                   metavar="N",
                   help="per-layer device budget in expert slots; cold "
                        "PMQ rows are offloaded to host memory and "
                        "prefetched by router stats (implies --pmq)")
    p.add_argument("--async-offload", action="store_true",
                   help="double-buffer planner-driven expert uploads: "
                        "residency targets stage into shadow device "
                        "buffers while the current megastep computes and "
                        "commit at the next boundary; outputs stay bit-"
                        "identical (requires --resident-experts; see "
                        "docs/serving_offload.md)")
    p.add_argument("--offload-dir", type=str, default=None, metavar="DIR",
                   help="spill the expert store to mmap'd packed buckets "
                        "under DIR (three-tier disk <- host <- device "
                        "residency; every row fetch is CRC-checked; "
                        "requires --resident-experts)")
    p.add_argument("--host-expert-bytes", type=int, default=None,
                   metavar="B",
                   help="byte budget for the pinned host row cache in "
                        "front of --offload-dir (default: unbounded)")
    p.add_argument("--pool-blocks", type=int, default=None,
                   help="KV pool size in pages; undersize it to exercise "
                        "growth + preemption (default: worst-case demand)")
    p.add_argument("--preempt-mode", choices=["swap", "recompute"],
                   default="swap",
                   help="restore preempted requests from the host swap "
                        "store or by re-prefilling their context")
    p.add_argument("--no-preempt", action="store_true",
                   help="reserve prompt+max_new pages at admission "
                        "(PR-1 baseline: no growth, no preemption)")
    p.add_argument("--ffn-backend", choices=["grouped", "scan", "ref"],
                   default=None,
                   help="compressed expert-FFN implementation: grouped "
                        "GEMM (default; Pallas moe_gmm on TPU), the "
                        "legacy per-expert scan, or the forced jnp "
                        "reference — reproducible A/B legs from the CLI")
    p.add_argument("--decode-horizon", type=int, default=None, metavar="H",
                   help="fused decode megastep length: one jitted "
                        "program advances every slot up to H tokens with "
                        "on-device sampling — one dispatch + one host "
                        "sync per megastep (default: "
                        "REPRO_DECODE_HORIZON or 8; 1 = the per-token "
                        "baseline program)")
    p.add_argument("--temperature", type=float, default=0.0,
                   help="on-device sampling temperature inside the "
                        "horizon scan (0 = greedy argmax, the "
                        "bit-reproducible default)")
    p.add_argument("--sample-seed", type=int, default=0,
                   help="seed for temperature sampling; one subkey per "
                        "megastep, so runs replay deterministically")
    p.add_argument("--prefix-cache", action="store_true",
                   help="enable shared-prefix KV reuse: prompts sharing a "
                        "cached prefix admit onto its pages copy-on-write "
                        "and skip the prefill of the shared tokens "
                        "(outputs stay bit-identical; see "
                        "docs/serving_kv.md)")
    p.add_argument("--kv-bits", type=int, choices=[8], default=None,
                   metavar="B",
                   help="quantize the KV pool to B-bit codes with per-row "
                        "f32 scales (~2.7x KV tokens per device byte at "
                        "head_dim 16; greedy outputs stay batch-"
                        "composition independent — see docs/serving_kv.md)")
    p.add_argument("--policy", choices=["fcfs", "priority", "fair"],
                   default=None,
                   help="admission-order policy: arrival order, strict "
                        "priority classes, or weighted-deficit token "
                        "fairness across tenants (WDRR; see "
                        "docs/serving_scheduling.md); outputs are bit-"
                        "identical across policies")
    p.add_argument("--tenant-weights", type=str, default=None,
                   metavar="T=W,...",
                   help="per-tenant fairness weights for --policy fair, "
                        "e.g. 'batch=1,interactive=4'; demo requests are "
                        "assigned round-robin over the named tenants")
    p.add_argument("--ttft-budget-ms", type=float, default=None,
                   metavar="MS",
                   help="SLO admission budget: shed (reject with empty "
                        "output) any never-admitted request that has "
                        "waited longer than MS for its first token")
    p.add_argument("--chaos-seed", type=int, default=None, metavar="SEED",
                   help="attach a seeded deterministic FaultPlan (expert-"
                        "upload / KV-swap / pool / logits faults) to the "
                        "engine and print the fault-plane counters after "
                        "the run — every request still finishes bit-exact "
                        "or with a typed error (docs/serving_robustness.md)")
    p.add_argument("--deadline-steps", type=int, default=None, metavar="N",
                   help="per-request deadline in engine steps; requests "
                        "not finished within N steps of submission "
                        "terminate typed with DeadlineExceeded")
    p.add_argument("--legacy", action="store_true",
                   help="run the static wave batcher instead of the paged engine")
    p.add_argument("--trace-out", type=str, default=None, metavar="PATH",
                   help="write a Chrome trace-event JSON (open in "
                        "ui.perfetto.dev) to PATH and the raw event log "
                        "to PATH + '.jsonl' after serving")
    p.add_argument("--trace-level", choices=["off", "spans", "full"],
                   default=None,
                   help="span tracing detail (default: 'full' when "
                        "--trace-out is given, else 'off'); lifecycle "
                        "metrics are identical at every level")
    return p


def _check_args(args) -> None:
    """Refuse flag combinations the chosen server cannot honour."""
    if args.layers is not None and args.layers < 1:
        raise SystemExit("--layers must be >= 1")
    if args.legacy and (args.prefix_cache or args.kv_bits is not None):
        # both features live in the paged KV pool — the wave batcher has
        # neither pages nor a prefix index
        raise SystemExit("--prefix-cache/--kv-bits require the paged "
                         "engine (drop --legacy)")
    if args.legacy and (args.trace_out or args.trace_level not in (None, "off")):
        # the wave batcher predates the tracer — refuse rather than
        # silently emit an empty trace
        raise SystemExit("--trace-out/--trace-level require the paged "
                         "engine (drop --legacy)")
    if args.legacy and (args.chaos_seed is not None
                        or args.deadline_steps is not None):
        # the fault plane and request deadlines live in the paged
        # engine's step loop — the wave batcher has neither
        raise SystemExit("--chaos-seed/--deadline-steps require the "
                         "paged engine (drop --legacy)")
    if args.deadline_steps is not None and args.deadline_steps < 1:
        raise SystemExit("--deadline-steps must be >= 1")
    if args.legacy and (args.policy or args.tenant_weights
                        or args.ttft_budget_ms is not None):
        # scheduling policy lives in the controller loop the wave
        # batcher doesn't run
        raise SystemExit("--policy/--tenant-weights/--ttft-budget-ms "
                         "require the paged engine (drop --legacy)")
    if args.tenant_weights and (args.policy or "fcfs") != "fair":
        raise SystemExit("--tenant-weights only applies to --policy fair")
    if args.ttft_budget_ms is not None and args.ttft_budget_ms < 0:
        raise SystemExit("--ttft-budget-ms must be >= 0")
    if args.resident_experts is not None and args.legacy:
        # the wave batcher has no offload path — refuse rather than
        # silently serve everything device-resident
        raise SystemExit("--resident-experts requires the paged engine "
                         "(drop --legacy)")
    if ((args.async_offload or args.offload_dir is not None)
            and args.resident_experts is None):
        # both ride the offload manager's residency plan — nothing to
        # overlap or tier without a device budget
        raise SystemExit("--async-offload/--offload-dir require "
                         "--resident-experts")
    if args.host_expert_bytes is not None and args.offload_dir is None:
        raise SystemExit("--host-expert-bytes requires --offload-dir")


def serving_config(args):
    """The arch's config at reduced or published widths, with the depth
    cut to ``--layers`` when given."""
    cfg = get_config(args.arch)
    if not args.published:
        cfg = cfg.reduced()
    if args.layers is not None:
        cfg = dataclasses.replace(cfg, num_layers=args.layers)
    return cfg


def build_model(args):
    """``(cfg, params, prompts)`` for a parsed command line: the
    :func:`serving_config`, weights from ``--seed`` — PMQ-compressed when
    ``--pmq`` or ``--resident-experts`` asks — and ``--requests`` seeded
    prompts."""
    _check_args(args)
    if args.ffn_backend:
        # process default too, so the --legacy wave batcher (no engine
        # config, plain decode_step) honors the same A/B knob
        import os

        os.environ["REPRO_FFN_BACKEND"] = args.ffn_backend
    cfg = serving_config(args)
    params = get_model(cfg).init(jax.random.PRNGKey(args.seed))
    if args.pmq or args.resident_experts is not None:
        if not cfg.is_moe:
            flag = "--pmq" if args.pmq else "--resident-experts"
            raise SystemExit(f"{flag} requires an MoE arch")
        print("compressing demo model (PMQ, layer-uniform plan)…")
        params = _compress_for_serving(cfg, params, args.seed)
    rng = np.random.default_rng(args.seed)
    prompts = [
        rng.integers(0, cfg.vocab_size, size=args.prompt_len).astype(np.int32)
        for _ in range(args.requests)
    ]
    return cfg, params, prompts


def build_engine(args, cfg, params, *, keep_logits: bool = False
                 ) -> PagedServingEngine:
    """The paged engine a parsed command line describes, over ``params``;
    the pool holds every request's worst case unless ``--pool-blocks``
    undersizes it. ``keep_logits`` records every served token's logits
    (``EngineConfig.keep_logits``)."""
    tenant_weights = (
        _parse_tenant_weights(args.tenant_weights)
        if args.tenant_weights else None
    )
    trace_level = args.trace_level or ("full" if args.trace_out else "off")
    blocks_per_req = (args.prompt_len + args.max_new) // args.block_size + 2
    plan = None
    if args.chaos_seed is not None:
        from ..serving import FaultPlan

        sites = ("swap_out", "swap_in", "pool", "logits")
        if args.resident_experts is not None:
            sites = ("upload",) + sites
        plan = FaultPlan.generate(
            args.chaos_seed, n_faults=8, max_step=4 * args.max_new,
            sites=sites, rids=list(range(args.requests)),
        )
    engine = PagedServingEngine(
        cfg, params,
        EngineConfig(
            max_slots=args.slots,
            block_size=args.block_size,
            num_blocks=args.pool_blocks or args.slots * blocks_per_req,
            max_blocks_per_slot=blocks_per_req,
            preempt_mode=args.preempt_mode,
            reserve_full=args.no_preempt,
            resident_experts=args.resident_experts,
            async_offload=args.async_offload,
            offload_dir=args.offload_dir,
            host_expert_bytes=args.host_expert_bytes,
            ffn_backend=args.ffn_backend,
            temperature=args.temperature,
            sample_seed=args.sample_seed,
            trace_level=trace_level,
            prefix_cache=args.prefix_cache,
            kv_bits=args.kv_bits,
            keep_logits=keep_logits,
            policy=args.policy or "fcfs",
            tenant_weights=tenant_weights,
            ttft_budget_s=(
                args.ttft_budget_ms / 1000.0
                if args.ttft_budget_ms is not None else None
            ),
            **({"decode_horizon": args.decode_horizon}
               if args.decode_horizon is not None else {}),
        ),
        faults=plan,
    )
    return engine


def main(argv=None) -> None:
    enable_compile_cache()
    args = parser().parse_args(argv)
    cfg, params, prompts = build_model(args)
    if args.legacy:
        server = BatchedServer(cfg, params, max_slots=args.slots,
                               prompt_len=args.prompt_len)
        reqs = [
            Request(rid=i, prompt=prompts[i], max_new=args.max_new)
            for i in range(args.requests)
        ]
        out = server.serve(reqs)
        print(f"served {len(out)} requests; stats: {server.summary()}")
        return
    engine = build_engine(args, cfg, params)
    # the engine's tree holds any resident partition + host store;
    # dropping the caller's reference releases the full-resident device
    # buckets — the memory an offload budget exists to reclaim
    del params
    tenant_weights = engine.ecfg.tenant_weights
    tenant_names = (
        [t for t, _ in tenant_weights] if tenant_weights else ["default"]
    )
    out = engine.serve(
        [
            PagedRequest(rid=i, prompt=prompts[i], max_new=args.max_new,
                         tenant=tenant_names[i % len(tenant_names)],
                         deadline_steps=args.deadline_steps)
            for i in range(args.requests)
        ]
    )
    m = engine.metrics.summary()
    print(f"served {len(out)} requests; metrics: {engine.metrics.to_json()}")
    print(f"pool pressure: {m['preemptions']} preemptions, "
          f"{m['swap_bytes']} swap bytes, "
          f"page util p95 {m['page_util_p95']:.2f}")
    print(f"scheduling: policy {engine.ecfg.policy}; {m['sheds']} sheds, "
          f"{m['preemptions']} preemptions, {m['readmissions']} "
          f"readmissions, {m['plans']} plans {m['plan_actions']}; "
          f"tenant tokens {m['tenant_tokens']}")
    if args.prefix_cache:
        print(f"prefix cache: {m['prefix_hits']} hits "
              f"({m['prefix_full_hits']} full), "
              f"{m['prefix_tokens_saved']} prompt tokens reused, "
              f"{m['cow_copies']} COW copies")
    if engine.offload is not None:
        print(
            f"expert offload: budget {engine.offload.budgets} "
            f"(resident {engine.offload.resident_bytes} B of "
            f"{engine.offload.host_bytes} B host), "
            f"hit rate {m['expert_hit_rate']:.2f}, "
            f"{m['expert_prefetch_uploads']} prefetch + "
            f"{m['expert_miss_uploads']} miss uploads "
            f"({m['expert_upload_bytes']} B), "
            f"{engine.offload.grows} budget grows"
        )
        if args.async_offload:
            print(
                f"async offload: {m['uploads_overlapped']} overlapped "
                f"({m['uploads_committed']} committed, "
                f"{m['uploads_dropped_stale']} dropped stale), "
                f"stall {m['upload_stall_s']:.4f} s, "
                f"hidden {m['upload_hidden_s']:.4f} s"
            )
        if args.offload_dir is not None:
            print(
                f"expert tiers: {m['tier_host_hits']} host hits, "
                f"{m['tier_disk_hits']} disk fetches "
                f"({m['tier_disk_bytes']} B, CRC-checked)"
            )
    if engine.faults is not None or args.deadline_steps is not None:
        ctr = engine.metrics.counters()
        print(
            f"fault plane: {ctr['fault_injected']} injected "
            f"{dict(ctr['faults_by_site'])}; "
            f"{ctr['upload_retries']} upload retries, "
            f"{ctr['degraded_serves']} degraded serves, "
            f"{ctr['swap_fallbacks']} swap fallbacks, "
            f"{ctr['cancelled']} cancelled, "
            f"{ctr['deadline_exceeded']} deadline-exceeded, "
            f"{ctr['poisoned']} poisoned"
        )
        if engine.errors:
            print("typed errors: " + ", ".join(
                f"rid {r}: {type(e).__name__}"
                for r, e in sorted(engine.errors.items())
            ))
    report = engine.routing_report()
    if report is not None:
        corr = report["mean_freq_bits_corr"]
        hot = sum(len(l["hot_low_bit"]) for l in report["layers"])
        cold = sum(len(l["cold_high_bit"]) for l in report["layers"])
        print(
            f"routing telemetry: {report['steps']} steps over "
            f"{report['num_layers']}×{report['num_slots']} (layer, slot) "
            f"cells; freq↔bits corr "
            f"{'n/a' if corr is None else f'{corr:+.2f}'}, "
            f"{hot} hot-low-bit + {cold} cold-high-bit candidates"
        )
    if args.trace_out:
        extra = {"routing_report": report} if report is not None else None
        engine.tracer.write_chrome(args.trace_out, extra=extra)
        engine.tracer.write_jsonl(args.trace_out + ".jsonl")
        print(f"trace: {len(engine.tracer.events)} events → "
              f"{args.trace_out} (+ .jsonl); open in https://ui.perfetto.dev")


if __name__ == "__main__":
    main()
