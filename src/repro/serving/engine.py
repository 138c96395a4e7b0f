"""Continuous-batching decode engine over the paged KV pool.

The engine owns two jitted programs, both with static shapes so they
compile exactly once each:

* **prefill chunk** — one request's prompt streams through
  :func:`repro.models.transformer.paged_prefill_chunk` in fixed-size
  chunks, writing K/V straight into the request's pages (no dense
  [L,B,S,…] cache, no per-wave re-prefill). The final chunk's logits give
  the first generated token — the TTFT event.
* **decode megastep** — all ``max_slots`` slots advance up to
  ``decode_horizon`` tokens through one
  :func:`repro.models.transformer.paged_decode_horizon` program: an
  on-device ``lax.scan`` over H single-step bodies with on-device
  sampling (greedy argmax by default, categorical at
  ``temperature > 0``) feeding each step's token into the next, and
  per-slot stop logic (emission budget exhausted, EOS emitted, slot
  inactive) folded into the carried ``active`` mask. The pool arrays are
  donated, so the multi-GB cache is updated in place.

**What syncs when.** The host orchestration cost — one jitted dispatch,
one ``device→host`` fetch, one Python bookkeeping pass — is paid once
per *megastep*, not once per token: the engine fetches the ``[H, slots]``
emitted-token matrix plus its emit mask, per-step activation, and
per-step dispatch counts in a single sync, then applies up to
``H · slots`` tokens host-side. ``H = 1`` reproduces the historical
per-token program exactly (the A/B baseline); any ``H`` emits greedy
tokens bit-identical to ``dense_greedy_reference`` because each scan
step runs the same traced body as ``paged_decode_step``.
:class:`repro.serving.metrics.ServingMetrics` reconstructs per-logical-
step records from each megastep (emit counts, activation and pool gauges
are exact per step — admissions, queue depth and page utilization are
genuinely constant within a megastep since all scheduling happens at its
boundary) and counts dispatches/syncs per token, the horizon's
deterministic witness.

Between megasteps the (host-side)
:class:`repro.serving.scheduler.Scheduler` admits queued requests into
freed slots — continuous batching with no wave barrier and no dummy
padding, FCFS at megastep granularity. The model path is the standard
bundle tree, including PMQ-compressed experts (``moe_ce`` buckets, paper
§3.2) and OTP deterministic decode masks (§3.4 τ→0 argmax) when present.

**Dynamic page growth + preemption.** Admission reserves pages for the
prompt plus the first megastep's writes; before each megastep the engine
grows every active slot's block table **horizon-ahead** — enough pages
for all ``min(H, budget)`` KV writes the fused program will perform
(oldest admission first), so no write inside the scan can land on an
unallocated page. When the pool runs dry, the youngest-admitted /
least-progress request is preempted — its pages are swapped to a host
backing store (``preempt_mode="swap"``) or dropped (``"recompute"``) —
and it rejoins the FCFS queue at the head. On re-admission the engine
swap-restores the pages or re-prefills ``prompt + out[:-1]``; greedy
outputs are bit-identical either way for any pool that admits the
largest single request (fuzzed in ``tests/test_serving_sim.py``). Block
tables keep their static ``[max_slots, max_blocks_per_slot]`` shape
throughout — growth only fills in rows between jitted programs, so
nothing recompiles.

**Host-offloaded expert buckets + replay semantics.** With
``resident_experts`` set (PMQ params only), cold expert rows live in
host memory (:class:`repro.serving.offload.ExpertOffloadManager`) and
the jitted programs read a budget-shaped resident partition. Between
megasteps the controller plan uploads the router-stats-EMA-hottest
experts (an ``upload_experts`` convergence action computed from
``offload.residency_targets()``); because routing happens inside the jitted
program, a **miss** is only observable afterwards — from the reported
``[H, L, slots]`` dispatch counts, whose step-major flattening is the
horizon-union working set in computation order. The engine then uploads
the missing experts synchronously and **replays the whole megastep**:
KV writes land at position-determined destinations and the token
sequence is deterministic (greedy, or categorical under the megastep's
fixed key), so a replay simply overwrites every write with identical
values — the same authentic-prefix induction as the single-step case,
now bounded by ``H · num_layers`` replays. Greedy outputs are therefore
bit-identical to the all-resident engine for any budget that holds the
megastep working set (fuzzed in ``tests/test_offload.py``). The
megastep timer reports **compute** (first run) and **offload overhead**
(uploads + replays) as separate metrics — ``decode_step_s`` and
``tokens_per_s`` stay honest end-to-end wall-clock, and the new split
makes the replay share separately attributable instead of silently
folded in.
"""
from __future__ import annotations

import dataclasses
import functools
import os
import time
from typing import Dict, Iterable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..models import transformer as tf
from .controller import PlanAction, ResourceController
from .faults import (
    DeadlineExceeded,
    ExpertUploadFailed,
    FaultPlan,
    LivelockDetected,
    PoisonedRequest,
    RequestCancelled,
    ServingFault,
    SwapFault,
    WatchdogTimeout,
)
from .kvcache import PagedKVCache, PoolExhausted
from .metrics import ServingMetrics
from .scheduler import Request, Scheduler, VALID_POLICIES
from .trace import ExpertRoutingTelemetry, MetricsConsumer, SpanTracer

__all__ = [
    "EngineConfig", "PagedServingEngine", "dense_greedy_reference",
    "quantized_greedy_reference",
]


def dense_greedy_reference(cfg, params, prompt: np.ndarray, max_new: int):
    """Greedy decode through the *dense* cache — the equivalence oracle
    for the paged engine (tests and examples assert paged == dense).

    Returns ``(tokens, per_step_logits)`` where ``per_step_logits[i]`` is
    the last-token logits [V] that produced ``tokens[i]``. Run it with the
    engine's ``model_cfg`` so both sides use drop-free expert capacity.
    """
    from ..models.registry import get_model

    bundle = get_model(cfg)
    cache, logits = bundle.prefill(params, {"tokens": jnp.asarray(prompt[None])})
    # the prefill cache covers exactly the prompt; extend for decode
    pad = ((0, 0), (0, 0), (0, max_new), (0, 0), (0, 0))
    cache = dict(cache, k=jnp.pad(cache["k"], pad), v=jnp.pad(cache["v"], pad))
    toks, steps = [], [np.asarray(logits[0, -1])]
    cur = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
    toks.append(int(cur[0, 0]))
    for step in range(max_new - 1):
        cache, logits = bundle.decode_step(
            params, cache, cur, jnp.int32(len(prompt) + step)
        )
        steps.append(np.asarray(logits[0, -1]))
        cur = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
        toks.append(int(cur[0, 0]))
    return toks, steps


def quantized_greedy_reference(cfg, params, prompt: np.ndarray, max_new: int,
                               *, kv_bits: int = 8, block_size: int = 16,
                               use_otp: bool = True,
                               ffn_backend: Optional[str] = None) -> List[int]:
    """Greedy decode oracle for **int8-KV** engines: a fresh
    single-request, single-slot, ``H = 1``, prefix-cache-off paged
    engine with the same ``kv_bits``.

    Quantized greedy outputs cannot be compared against
    :func:`dense_greedy_reference` — the dense cache attends to
    unquantized rows, so its logits differ by design. The invariant the
    quantized engine *does* keep is batch-composition independence:
    per-row quantization depends only on the row values, so a request's
    codes (hence its tokens) are identical whether it runs alone here or
    co-scheduled/preempted/prefix-shared in a loaded engine — that
    equality is what the fuzz harness asserts, and page geometry does
    not enter the math (any ``block_size`` gives the same tokens).
    """
    prompt = np.ascontiguousarray(prompt, np.int32)
    pages = -(-(len(prompt) + max_new) // block_size)
    eng = PagedServingEngine(cfg, params, EngineConfig(
        max_slots=1, block_size=block_size, num_blocks=pages,
        max_blocks_per_slot=pages, prefill_chunk=block_size,
        decode_horizon=1, reserve_full=True, use_otp=use_otp,
        ffn_backend=ffn_backend, kv_bits=kv_bits, prefix_cache=False,
        trace_level="off",
    ))
    out = eng.serve([Request(rid=0, prompt=prompt, max_new=max_new)])
    return out[0]


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    max_slots: int = 4
    block_size: int = 16
    num_blocks: int = 64
    max_blocks_per_slot: int = 8
    prefill_chunk: int = 16
    use_otp: bool = True  # OTP decode masks when the model carries them
    # Preempted-request restore path: "swap" moves victim KV pages to a
    # host backing store and uploads them back at re-admission (bit-exact,
    # costs PCIe/host bandwidth); "recompute" drops the pages and
    # re-prefills prompt + generated-so-far (costs FLOPs, no host memory).
    preempt_mode: str = "swap"
    # True restores the PR-1 admission policy: reserve prompt + max_new
    # pages up front so growth/preemption never trigger — the baseline leg
    # of the --pool-blocks pressure sweeps.
    reserve_full: bool = False
    # Serving must be batch-composition independent: a request's tokens
    # cannot change because of who it was co-scheduled with (continuous
    # batching reshuffles neighbors every step) nor how its prompt was
    # chunked. Expert capacity is therefore raised to the drop-free bound
    # (cap ≥ tokens·top_k ⇔ capacity_factor ≥ num_experts) inside the
    # engine's jitted steps.
    drop_free_capacity: bool = True
    # Per-layer device budget (in permuted expert slots) for PMQ buckets;
    # None keeps every bucket fully resident. Requires compressed params
    # ("moe_ce" in the stacked block tree). Cold rows live in host memory
    # and are prefetched by a router-stats EMA; misses replay the step.
    resident_experts: Optional[int] = None
    # EMA decay of the per-(layer, slot) dispatch counts driving prefetch.
    prefetch_ema: float = 0.8
    # Async expert streaming (docs/serving_offload.md): the controller's
    # prefetch plan is *issued* right after each megastep's program
    # dispatch (double-buffered — built against immutable jax arrays
    # while the megastep computes on the live ones) and *committed* at
    # the next boundary; stale batches (a miss/grow landed mid-flight)
    # are dropped and re-planned. Outputs are bit-identical with this on
    # or off (fuzzed in tests/test_serving_sim.py); only the timing —
    # decode_offload_frac — changes. False keeps the synchronous PR-3
    # path: apply_residency blocks the boundary.
    async_offload: bool = False
    # Three-tier expert store (repro.serving.tierstore): a directory to
    # spill the packed PMQ buckets into as mmap'd .npy images (CRC
    # manifest, verified on every read). The full in-memory host copies
    # are dropped after the spill — cold rows are then served
    # disk → host cache → device. None keeps the two-tier host store.
    offload_dir: Optional[str] = None
    # Byte budget of the warm host row cache between the disk images and
    # the device partitions (only with offload_dir). None = unbounded;
    # 0 = every fetch reads (and CRC-verifies) the mmap.
    host_expert_bytes: Optional[int] = None
    # Compressed expert-FFN implementation inside the jitted programs:
    # "grouped" (default — bucket-at-a-time grouped GEMM, Pallas moe_gmm
    # on TPU / jnp oracle on CPU), "scan" (legacy per-expert scan, the
    # A/B baseline), "ref"/"interpret" (grouped layout, forced kernel
    # backend). Trace-time static: changing it costs one retrace, using
    # it never retraces. None = repro.core.compressed_moe default.
    ffn_backend: Optional[str] = None
    # Fused decode horizon H: one jitted megastep advances every slot up
    # to H tokens with on-device sampling, paying one dispatch + one
    # host sync per megastep instead of per token. H = 1 reproduces the
    # historical per-token program (the A/B baseline); greedy outputs
    # are bit-identical across H. Trace-time static.
    decode_horizon: int = dataclasses.field(
        default_factory=lambda: int(os.environ.get(
            "REPRO_DECODE_HORIZON", "8"))
    )
    # On-device sampling inside the horizon scan: 0 (default) compiles
    # greedy argmax — the path every bit-identity invariant runs; > 0
    # compiles categorical sampling from logits/T, seeded per megastep
    # from sample_seed so runs (and offload replays) are deterministic.
    temperature: float = 0.0
    sample_seed: int = 0
    # Keep the float32 logits behind every emitted token in
    # ``engine.token_logits[rid]``: the prefill's first-token row, then
    # one row per decode step — what a check of served logits against a
    # reference reads. Trace-time static: the decode megastep then also
    # returns its [H, slots, V] logits, one more device-to-host copy per
    # megastep. Off for serving.
    keep_logits: bool = False
    # Shared-prefix KV reuse: admission probes a prefix → physical-page-
    # run cache (exact token keys, LRU) and shares matching page-aligned
    # pages copy-on-write instead of re-prefilling them; fresh prompts
    # register their page-boundary prefixes after prefill. Greedy outputs
    # are bit-identical with the cache on or off (fuzzed in
    # tests/test_serving_sim.py) — cached pages hold exactly the KV the
    # skipped prefill would have written.
    prefix_cache: bool = False
    # int8 KV quantization: 8 stores the pools as uint8 per-row affine
    # codes with per-(layer, page, row, kv-head) scale/zero tables (see
    # repro.core.quantizers.quantize_kv_rows), halving-plus KV bytes per
    # token at fixed pool geometry; None keeps fp pools (today's path,
    # byte-for-byte untouched). Quantized greedy outputs are batch-
    # composition independent (per-row params depend only on the row) and
    # equal quantized_greedy_reference bit-for-bit, but differ from the
    # dense fp oracle by design.
    kv_bits: Optional[int] = None
    # Request-lifecycle tracing (repro.serving.trace): "off" records no
    # events (lifecycle facts still reach the metrics consumer, so
    # counters() are invariant to this knob), "spans" records
    # span/instant/flow events, "full" adds per-step gauges + the
    # expert-routing telemetry. Host-side only — never traced into jit.
    trace_level: str = dataclasses.field(
        default_factory=lambda: os.environ.get("REPRO_TRACE_LEVEL", "off")
    )
    # Multi-tenant scheduling policy (docs/serving_scheduling.md):
    # "fcfs" (historical single-tenant behavior), "priority" (classes
    # first, FCFS within), "fair" (priority + weighted deficit round-
    # robin over per-tenant decode-token grants). Policies reorder
    # *when* requests run, never *what* they emit — outputs stay
    # batch-composition independent under every policy.
    policy: str = "fcfs"
    # Per-tenant WDRR weights for policy="fair", as a hashable tuple of
    # (tenant, weight) pairs (EngineConfig is frozen/hashable); unlisted
    # tenants weigh 1.0. None ⇒ all tenants weigh 1.0.
    tenant_weights: Optional[Tuple[Tuple[str, float], ...]] = None
    # SLO-aware admission: a fresh request that cannot admit at a
    # boundary after waiting more than this many logical decode steps
    # (deterministic — the sim/bench budget) or this many wall-clock
    # seconds (launch/serve's --ttft-budget-ms) is *shed*: removed from
    # the queue with an empty output and a "shed" lifecycle event,
    # instead of queueing unboundedly. None disables shedding.
    ttft_budget_steps: Optional[int] = None
    ttft_budget_s: Optional[float] = None
    # ---- fault plane (docs/serving_robustness.md) ----
    # Precision-ladder degradation: when an expert row's target-bit
    # upload persistently fails (past upload_max_retries), serve a
    # lower-bit copy of that row (codes snapped to the next ladder rung,
    # scale/zero kept) instead of failing closed. Off by default — the
    # bit-exact contract then holds unconditionally: recovery either
    # reproduces the fault-free run or raises ExpertUploadFailed.
    degrade_experts: bool = False
    # Bounded miss-path retries per expert row before degrade/fail.
    upload_max_retries: int = 3
    # Wall-clock megastep watchdog: a megastep slower than this fails
    # the engine closed with WatchdogTimeout (None = off; tests drive it
    # through the engine's injectable ``_clock``).
    watchdog_timeout_s: Optional[float] = None
    # No-progress livelock guard: this many consecutive megastep
    # boundaries with work but zero emitted tokens / finished requests
    # fail closed with LivelockDetected. Logical steps — deterministic.
    livelock_steps: int = 4096


@functools.lru_cache(maxsize=None)
def _jitted_steps(model_cfg, use_otp: bool, ffn_backend: Optional[str] = None,
                  horizon: int = 1, temperature: float = 0.0,
                  keep_logits: bool = False):
    """Compiled decode-megastep/prefill builders, shared across engines
    with the same (hashable, frozen) model config and the same static
    horizon/sampling knobs — jit caching then dedupes by array shapes
    *and pytree structure* (fp and int8 engines trace different
    programs off the same builder), so two engines differing only in
    pool geometry cost one trace each, not one per instance.

    Both programs take and return the ``quant`` scale/zero tables right
    after the pools (``None`` on fp engines — an empty pytree that
    donates and returns as nothing): the tables are pool metadata and
    must travel through every donated round-trip with the codes they
    dequantize. ``keep_logits`` adds the megastep's ``[H, B, V]`` logits
    to the decode outputs, just before the dispatch counts.
    """
    hooks = {"use_otp": use_otp, "ffn_backend": ffn_backend}

    def decode_fn(params, k, v, quant, token, positions, tables, active,
                  budgets, eos_ids, key):
        cache = {"k": k, "v": v, "block_tables": tables, "active": active}
        if quant is not None:
            cache["kv_quant"] = quant
        new_cache, toks, emits, info = tf.paged_decode_horizon(
            params, cache, token, positions, model_cfg, horizon=horizon,
            budgets=budgets, eos_ids=eos_ids, moe_hooks=hooks,
            temperature=temperature, rng_key=key, keep_logits=keep_logits,
        )
        return (
            new_cache["k"], new_cache["v"], new_cache.get("kv_quant"),
            toks, emits, info["expert_activation"],
            *([info["logits"]] if keep_logits else []), info["slot_counts"],
        )

    def prefill_fn(params, k, v, quant, tokens, start, valid_len, table_row):
        cache = {"k": k, "v": v, "block_tables": table_row}
        if quant is not None:
            cache["kv_quant"] = quant
        new_cache, logits, info = tf.paged_prefill_chunk(
            params, cache, tokens, start, valid_len, model_cfg, moe_hooks=hooks
        )
        return (
            new_cache["k"], new_cache["v"], new_cache.get("kv_quant"),
            logits, info["slot_counts"],
        )

    return (
        jax.jit(decode_fn, donate_argnums=(1, 2, 3)),
        jax.jit(prefill_fn, donate_argnums=(1, 2, 3)),
    )


class PagedServingEngine:
    """Serve requests against a transformer-family model bundle tree."""

    def __init__(self, cfg, params, engine_cfg: Optional[EngineConfig] = None,
                 faults: Optional[FaultPlan] = None):
        if cfg.family not in ("dense", "moe", "vlm"):
            raise ValueError(
                f"paged serving supports transformer families, got {cfg.family}"
            )
        self.cfg = cfg
        self.ecfg = engine_cfg or EngineConfig()
        self.model_cfg = cfg
        if cfg.is_moe and self.ecfg.drop_free_capacity:
            self.model_cfg = dataclasses.replace(
                cfg,
                moe_capacity_factor=float(
                    max(cfg.moe_capacity_factor, cfg.num_experts)
                ),
            )
        if self.ecfg.preempt_mode not in ("swap", "recompute"):
            raise ValueError(
                f"preempt_mode must be 'swap' or 'recompute', "
                f"got {self.ecfg.preempt_mode!r}"
            )
        if self.ecfg.decode_horizon < 1:
            raise ValueError(
                f"decode_horizon must be ≥ 1, got {self.ecfg.decode_horizon}"
            )
        if self.ecfg.temperature < 0.0:
            raise ValueError(
                f"temperature must be ≥ 0, got {self.ecfg.temperature}"
            )
        if self.ecfg.policy not in VALID_POLICIES:
            raise ValueError(
                f"policy must be one of {VALID_POLICIES}, "
                f"got {self.ecfg.policy!r}"
            )
        if (
            self.ecfg.ttft_budget_steps is not None
            and self.ecfg.ttft_budget_steps < 0
        ):
            raise ValueError(
                f"ttft_budget_steps must be ≥ 0, "
                f"got {self.ecfg.ttft_budget_steps}"
            )
        if self.ecfg.ttft_budget_s is not None and self.ecfg.ttft_budget_s < 0:
            raise ValueError(
                f"ttft_budget_s must be ≥ 0, got {self.ecfg.ttft_budget_s}"
            )
        if self.ecfg.livelock_steps < 1:
            raise ValueError(
                f"livelock_steps must be ≥ 1, got {self.ecfg.livelock_steps}"
            )
        # fault plane: the plan is mutable/unhashable, so it rides next
        # to the frozen EngineConfig rather than inside it
        self.faults = faults
        cfg = self.model_cfg
        # metrics + tracer come first: every downstream component
        # (offload, cache, scheduler) records through the tracer, and the
        # metrics consume its lifecycle stream. The consumer holds a
        # *getter* so callers that reset ``engine.metrics`` (benchmark
        # warmups) keep feeding the live instance.
        self.metrics = ServingMetrics()
        self.tracer = SpanTracer(
            self.ecfg.trace_level,
            consumers=(MetricsConsumer(lambda: self.metrics),),
        )
        self.offload = None
        if self.ecfg.resident_experts is not None:
            blocks = params.get("blocks") if isinstance(params, dict) else None
            if not isinstance(blocks, dict) or "moe_ce" not in blocks:
                raise ValueError(
                    "resident_experts requires PMQ-compressed params "
                    "(a stacked 'moe_ce' entry in params['blocks'])"
                )
            from .offload import ExpertOffloadManager

            self.offload = ExpertOffloadManager(
                blocks["moe_ce"],
                resident_slots=self.ecfg.resident_experts,
                ema_decay=self.ecfg.prefetch_ema,
                tracer=self.tracer,
                faults=faults,
                degrade=self.ecfg.degrade_experts,
                max_retries=self.ecfg.upload_max_retries,
                offload_dir=self.ecfg.offload_dir,
                host_budget_bytes=self.ecfg.host_expert_bytes,
            )
            params = dict(params, blocks=dict(blocks, moe_ce=self.offload.ce))
        elif self.ecfg.async_offload or self.ecfg.offload_dir is not None:
            raise ValueError(
                "async_offload/offload_dir require resident_experts "
                "(there is no expert streaming to overlap or tier)"
            )
        # async expert streaming: upload_experts plan targets deferred
        # from the boundary to right after the next program dispatch
        self._pending_expert_targets: Tuple = ()
        self.params = params
        self.cache = PagedKVCache.create(
            cfg,
            num_blocks=self.ecfg.num_blocks,
            block_size=self.ecfg.block_size,
            max_slots=self.ecfg.max_slots,
            max_blocks_per_slot=self.ecfg.max_blocks_per_slot,
            kv_bits=self.ecfg.kv_bits,
            prefix_cache=self.ecfg.prefix_cache,
        )
        self.cache.set_tracer(self.tracer)
        self.cache.faults = faults
        self.scheduler = Scheduler(
            self.cache, reserve_full=self.ecfg.reserve_full,
            horizon=self.ecfg.decode_horizon, tracer=self.tracer,
            policy=self.ecfg.policy,
            tenant_weights=(
                dict(self.ecfg.tenant_weights)
                if self.ecfg.tenant_weights is not None else None
            ),
        )
        # one declarative controller owns slots, pages, and resident
        # experts: each boundary it observes, reconciles against the
        # policy's target state, and emits the plan _execute_plan runs
        self.controller = ResourceController(
            self.scheduler, offload=self.offload, tracer=self.tracer,
            ttft_budget_steps=self.ecfg.ttft_budget_steps,
            ttft_budget_s=self.ecfg.ttft_budget_s,
            faults=faults,
        )
        self.results: Dict[int, List[int]] = {}
        # rid → the typed ServingFault a request terminated with; its
        # results[rid] entry holds whatever tokens it emitted before
        self.errors: Dict[int, ServingFault] = {}
        self._cancel_requests: set = set()
        self._no_progress = 0
        # injectable wall clock (watchdog tests swap in a fake); the
        # watchdog itself is a HeartbeatTable over the single "megastep"
        # host, beaten at each megastep's start and checked at its end
        self._clock = time.time
        self._watchdog = None
        if self.ecfg.watchdog_timeout_s is not None:
            from ..runtime.fault_tolerance import HeartbeatTable

            self._watchdog = HeartbeatTable(
                ["megastep"], timeout=float(self.ecfg.watchdog_timeout_s),
            )
        self._step_idx = 0  # logical decode steps completed
        self._megastep_idx = 0  # fused megasteps run (sampling-key index)
        # two independent key streams off sample_seed: decode megasteps
        # fold in the megastep index, prefill first-token draws fold in
        # the request id (admission-order independent, replay stable)
        base = jax.random.PRNGKey(self.ecfg.sample_seed)
        self._sample_key = jax.random.fold_in(base, 0)
        self._prefill_key = jax.random.fold_in(base, 1)
        self._last_run_stats: Dict[str, float] = {}
        # PMQ trees report per-slot dispatch counts; the capacity gauge
        # needs the slot total to turn them into a utilization fraction
        blocks = params.get("blocks") if isinstance(params, dict) else None
        self._num_slots = (
            blocks["moe_ce"].num_slots
            if isinstance(blocks, dict) and "moe_ce" in blocks else None
        )
        # expert-routing telemetry: per-(layer, slot) dispatch histograms
        # + drift/Gini gauges + the bit-misallocation report, fed from
        # the slot_counts every jitted program already reports. PMQ trees
        # only (slot_counts has trailing dim 0 otherwise), and only when
        # tracing is on — disabled tracing must cost nothing.
        self._ce_meta = (
            blocks["moe_ce"].meta
            if isinstance(blocks, dict) and "moe_ce" in blocks else None
        )
        self.routing = (
            ExpertRoutingTelemetry()
            if self.tracer.enabled and self._num_slots else None
        )
        self._decode, self._prefill = _jitted_steps(
            self.model_cfg, self.ecfg.use_otp, self.ecfg.ffn_backend,
            self.ecfg.decode_horizon, float(self.ecfg.temperature),
            self.ecfg.keep_logits,
        )
        # rid → [V] float32 logits per emitted token (keep_logits only)
        self.token_logits: Dict[int, List[np.ndarray]] = {}

    # ----------------------------------------------------- observability
    def routing_report(self) -> Optional[Dict]:
        """Bit-misallocation report: observed per-(layer, expert-slot)
        dispatch frequency joined against the PMQ bit assignment (see
        :meth:`repro.serving.trace.ExpertRoutingTelemetry
        .bit_misallocation_report`). ``None`` unless the model is
        PMQ-compressed and tracing collected routing traffic."""
        if self.routing is None or self._ce_meta is None:
            return None
        degraded = None
        if self.offload is not None and self.offload.degraded:
            degraded = {
                k: to_bits for k, (_, to_bits) in self.offload.degraded.items()
            }
        return self.routing.bit_misallocation_report(
            self._ce_meta, degraded=degraded
        )

    # ------------------------------------------------------------ intake
    def submit(self, req: Request) -> None:
        req.arrival_s = time.time()
        self.scheduler.submit(req, self._step_idx)

    def cancel(self, rid: int) -> bool:
        """Request cancellation of a live request. Marked immediately;
        applied at the next safe point — the next megastep boundary, or
        between prefill chunks if the request is mid-prefill — where its
        slot, pages, and prefix-cache refs are released atomically and
        ``errors[rid]`` records a :class:`RequestCancelled`. Returns
        whether ``rid`` was live (waiting or active) when called."""
        live = {r.rid for r in self.scheduler.waiting}
        live.update(r.rid for r in self.scheduler.active.values())
        if rid not in live:
            return False
        self._cancel_requests.add(rid)
        return True

    def serve(self, requests: Iterable[Request]) -> Dict[int, List[int]]:
        """Submit + run; returns outputs for *this* batch only (``run``'s
        ``results`` keep accumulating across calls on a live engine)."""
        reqs = list(requests)
        for r in reqs:
            self.submit(r)
        self.run()
        return {r.rid: self.results[r.rid] for r in reqs}

    # -------------------------------------------------------------- loop
    def run(self) -> Dict[int, List[int]]:
        """Drive admission + growth + decode until queue and slots drain."""
        while self.step():
            pass
        return dict(self.results)

    def step(self) -> bool:
        """One engine round (megastep boundary): reconcile resources —
        the controller observes the pools, computes the target state,
        and emits the convergence plan this engine executes (grow /
        preempt page tables horizon-ahead, admit or shed waiters,
        upload experts) — then advance every active slot up to
        ``decode_horizon`` tokens in one fused jitted program. Returns
        whether work remains — the simulation harness drives this
        directly to interleave arrivals with decode.

        The fault plane hooks in here: the boundary advances the
        :class:`FaultPlan`'s logical step, applies pending cancellations
        and expired deadlines (typed per-request termination with an
        atomic release), and runs the watchdog + livelock guards that
        fail the whole engine closed (:meth:`_fail_closed`) rather than
        hang or serve silently corrupted state.
        """
        if self.faults is not None:
            self.faults.at_step(self._step_idx)
        self._apply_cancellations()
        self._apply_deadlines()
        if not self.scheduler.has_work():
            return False
        progress0 = (
            self._step_idx,
            sum(len(v) for v in self.results.values()),
        )
        try:
            self._converge()
            if not self.scheduler.active:
                if not self.scheduler.waiting:
                    return False
                if self.controller.last_pool_penalty <= 0:
                    held = (
                        self.cache.prefix.pages_held
                        if self.cache.prefix is not None else frozenset()
                    )
                    if not held:
                        # unreachable for pools that admit the largest
                        # request (submit guards that); kept as a thrash
                        # circuit-breaker
                        head = self.scheduler.waiting[0]
                        raise PoolExhausted(
                            f"request {head.rid} needs "
                            f"{self.cache.blocks_needed(head.context_tokens)} "
                            f"blocks but cannot be admitted "
                            f"({self.cache.allocator.num_free} free)"
                        )
                    # blocked head on an otherwise idle pool: the prefix
                    # cache is pure optimization, and the hit-entry
                    # protect set can pin pages the eviction walk will
                    # never reclaim — drop the cache and retry admission
                    # next boundary instead of declaring exhaustion
                    self.cache.clear_prefix_cache()
                # no megastep this boundary (transient pool pressure or a
                # just-cleared cache), but fall through to the no-progress
                # accounting — a *persistent* stall must eventually fail
                # closed as a livelock, not spin forever
            else:
                t_start = self._clock()
                if self._watchdog is not None:
                    self._watchdog.beat("megastep", now=t_start)
                self._decode_megastep()
                if self._watchdog is not None and self._watchdog.failed(
                    now=self._clock()
                ):
                    raise WatchdogTimeout(
                        f"megastep exceeded the "
                        f"{self.ecfg.watchdog_timeout_s}s watchdog budget"
                    )
        except (ExpertUploadFailed, WatchdogTimeout) as exc:
            self._fail_closed(exc)
        progress1 = (
            self._step_idx,
            sum(len(v) for v in self.results.values()),
        )
        if self.scheduler.has_work() and progress1 == progress0:
            self._no_progress += 1
            if self._no_progress >= self.ecfg.livelock_steps:
                self._fail_closed(LivelockDetected(
                    f"{self._no_progress} consecutive megastep boundaries "
                    f"with work but no progress"
                ))
        else:
            self._no_progress = 0
        return self.scheduler.has_work()

    # --------------------------------------------------- typed termination
    def _terminate(self, req: Request, exc: ServingFault, kind: str) -> None:
        """Terminate one request with a typed error: release every
        resource it holds atomically (slot, pages, prefix-cache refs,
        swap image), record its partial output and the error, and emit
        the lifecycle event. The released pool passes check_consistency
        — a terminated request can never leak pages or refcounts."""
        track = f"slot{req.slot}" if req.slot >= 0 else "queue"
        self.scheduler.cancel_release(req)
        self._cancel_requests.discard(req.rid)
        self.errors[req.rid] = exc
        self.results[req.rid] = req.out
        self.tracer.lifecycle(
            kind, track=track, rid=req.rid, step=self._step_idx,
            tokens=len(req.out),
        )
        self.tracer.flow("f", req.rid, track=track)

    def _find_live(self, rid: int) -> Optional[Request]:
        for r in self.scheduler.active.values():
            if r.rid == rid:
                return r
        return self._find_waiting(rid)

    def _apply_cancellations(self) -> None:
        for rid in sorted(self._cancel_requests):
            req = self._find_live(rid)
            if req is None:
                self._cancel_requests.discard(rid)
                continue
            self._terminate(
                req, RequestCancelled(f"request {rid} cancelled", rid=rid),
                "cancel",
            )

    def _apply_deadlines(self) -> None:
        live = list(self.scheduler.active.values())
        live.extend(self.scheduler.waiting)
        for req in live:
            if req.deadline_steps is None:
                continue
            if self._step_idx - req.submit_step >= req.deadline_steps:
                self._terminate(
                    req,
                    DeadlineExceeded(
                        f"request {req.rid} missed its "
                        f"{req.deadline_steps}-step deadline",
                        rid=req.rid,
                    ),
                    "deadline",
                )

    def _fail_closed(self, exc: ServingFault) -> None:
        """Engine-level fatal: terminate *every* live request with the
        typed error, releasing all slots, pages, and prefix refs so the
        pool drains clean (check_consistency passes, zero leaks), then
        re-raise. Never hang, never serve silent corruption."""
        live = list(self.scheduler.active.values())
        live.extend(self.scheduler.waiting)
        for req in live:
            self.scheduler.cancel_release(req)
            self.errors[req.rid] = exc
            self.results[req.rid] = req.out
        self._cancel_requests.clear()
        self.tracer.lifecycle(
            "fail_closed", track="engine", step=self._step_idx,
            error=type(exc).__name__, requests=len(live),
        )
        raise exc

    # ----------------------------------------------------- reconciliation
    def _converge(self) -> None:
        """One reconciliation pass at a megastep boundary: the controller
        observes the pools, diffs against the policy's target state, and
        this engine executes the convergence plan in order. All
        admit/preempt/grow/evict/upload decisions live in the plan; the
        executors below only carry them out (and emit the lifecycle
        events every action must flow through)."""
        if self.offload is not None and self.ecfg.async_offload:
            # flip the double buffer first: staged expert uploads from
            # the megastep that just ran either commit (buffers, tables
            # and maps swap together) or drop as stale — before the
            # controller observes residency to plan this boundary
            committed, dropped, nbytes, wait_s = self.offload.commit_async()
            if committed or dropped:
                self.metrics.record_async_commit(
                    committed, dropped, nbytes, wait_s
                )
        plan = self.controller.plan_boundary(self._step_idx, time.time())
        self._execute_plan(plan)

    def _execute_plan(self, plan: List[PlanAction]) -> None:
        for action in plan:
            kind = action.kind
            if kind == "admit":
                self._execute_admit(action)
            elif kind == "preempt":
                self._execute_preempt(action)
            elif kind == "grow":
                self._execute_grow(action)
            elif kind == "evict_prefix":
                if self.cache.prefix is not None:
                    self.cache.prefix.evict_for(
                        action.pages, frozenset(action.protect)
                    )
            elif kind == "shed":
                self._execute_shed(action)
            elif kind == "upload_experts":
                if self.ecfg.async_offload:
                    # defer: issued right after the next program
                    # dispatch (overlapped with its compute), committed
                    # at the next boundary — one-boundary-stale targets,
                    # which placement-invariance makes safe
                    self._pending_expert_targets = action.targets
                else:
                    t0 = time.time()
                    uploads, nbytes = self.offload.apply_residency(
                        action.targets
                    )
                    if uploads:
                        self.metrics.record_expert_prefetch(uploads, nbytes)
                        # the boundary blocked on this upload — the
                        # stall async streaming exists to hide
                        self.metrics.record_upload_stall(time.time() - t0)
            else:
                raise ValueError(f"unknown plan action kind {kind!r}")

    def _find_waiting(self, rid: int) -> Optional[Request]:
        for r in self.scheduler.waiting:
            if r.rid == rid:
                return r
        return None

    # --------------------------------------------------------- admission
    def _execute_admit(self, action: PlanAction) -> None:
        req = self._find_waiting(action.rid)
        if req is None:
            return  # defensive: the planner plans each waiter once
        active_before = len(self.scheduler.active)
        # sample the depth before admit_planned removes the request, so
        # the recorded value counts the request being admitted (the
        # depth the admission decision actually saw)
        depth_before = self.scheduler.queue_depth
        wait_steps = self._step_idx - req.submit_step
        req = self.scheduler.admit_planned(req, self._step_idx)
        if req is None:
            return  # plan/pool divergence: drop the step, stay queued
        track = f"slot{req.slot}"
        # lifecycle events feed the metrics consumer *and* (when
        # tracing is on) the event log; the flow hop stitches the
        # request's journey from the queue track onto its slot track
        self.tracer.lifecycle(
            "admit", track=track, rid=req.rid, slot=req.slot,
            step=self._step_idx, active_before=active_before,
            queue_depth=depth_before, resumed=req.preempt_count > 0,
            tenant=req.tenant, priority=req.priority,
            wait_steps=wait_steps,
        )
        self.tracer.flow("t", req.rid, track=track)
        if self.cache.prefix is not None and req.preempt_count == 0:
            # every fresh admission is a cache probe: hit/miss + the
            # prefill tokens the shared pages saved (full hits also
            # skip the first-token logits dispatch entirely)
            if req.cached_tokens > 0:
                self.tracer.lifecycle(
                    "prefix_hit", track=track, rid=req.rid,
                    tokens_saved=req.cached_tokens,
                    full=req.cached_logits is not None,
                )
            else:
                self.tracer.lifecycle(
                    "prefix_miss", track=track, rid=req.rid,
                )
        try:
            if req.swapped is not None:  # swap-restore a preempted slot
                try:
                    nbytes = self.cache.swap_in(
                        req.slot, req.swapped, rid=req.rid
                    )
                except SwapFault:
                    # corrupted/failed swap payload: discard it and fall
                    # back to recompute re-prefill — bit-exact, so the
                    # recovery is invisible to outputs
                    self.tracer.lifecycle(
                        "swap_fallback", track=track, rid=req.rid,
                        site="swap_in",
                    )
                    req.swapped = None
                    self._prefill_request(req, resume=True)
                else:
                    self.tracer.lifecycle(
                        "swap_in", track=track, rid=req.rid, slot=req.slot,
                        nbytes=nbytes,
                    )
                    req.swapped = None
            elif req.pos > 0:  # recompute-restore: re-prefill the context
                self._prefill_request(req, resume=True)
            else:
                t0 = time.time()
                self._prefill_request(req)
                now = time.time()
                self.metrics.record_ttft(
                    now - req.arrival_s, now - t0, tenant=req.tenant
                )
                self.results[req.rid] = req.out
        except (RequestCancelled, PoisonedRequest) as exc:
            # per-request faults mid-prefill terminate exactly this
            # request; any KV it wrote dies with its released pages
            self._terminate(
                req, exc,
                "cancel" if isinstance(exc, RequestCancelled) else "poisoned",
            )
            return
        if req.done:  # max_new == 1: first token is the only token
            slot = req.slot
            self.scheduler.finish(slot)
            self.tracer.lifecycle(
                "release", track=track, rid=req.rid, slot=slot,
                step=self._step_idx,
            )
            self.tracer.flow("f", req.rid, track=track)

    def _execute_shed(self, action: PlanAction) -> None:
        req = self._find_waiting(action.rid)
        if req is None:
            return
        self.scheduler.shed(req, self._step_idx)
        self.results[req.rid] = []  # served nothing, honestly
        self.tracer.lifecycle(
            "shed", track="queue", rid=req.rid, step=self._step_idx,
            tenant=req.tenant, priority=req.priority,
            wait_steps=action.waited_steps,
        )
        # the request's journey ends on the queue track — it never
        # reached a slot
        self.tracer.flow("f", req.rid, track="queue")

    def _prefill_request(self, req: Request, resume: bool = False) -> None:
        """Stream a context through chunked prefill into the slot's pages.

        Fresh requests prefill the prompt and emit the first token
        (TTFT). ``resume=True`` rebuilds a recompute-mode preempted slot:
        the context is ``prompt + out[:-1]`` (everything already written
        to KV before eviction) and the final chunk's logits are discarded
        — they re-predict the already-known ``out[-1]``.

        **Shared-prefix fast path.** A fresh request admitted through a
        prefix-cache hit starts prefill at ``req.cached_tokens`` — the
        shared/COW pages already hold that prefix's KV, bit-identical to
        what the skipped chunks would have written. A *full*-prompt hit
        carries the registration-time final-token logits
        (``req.cached_logits``) and dispatches **zero** prefill programs.
        Afterwards the freshly prefilled prompt registers its own
        page-boundary prefixes (+ final logits) back into the cache.
        """
        if resume:
            seq = np.concatenate(
                [req.prompt, np.asarray(req.out[:-1], np.int32)]
            )
            assert len(seq) == req.pos, (len(seq), req.pos)
        else:
            seq = req.prompt
        p_len = len(seq)
        c = self.ecfg.prefill_chunk
        track = f"slot{req.slot}"
        off0 = 0 if resume else min(req.cached_tokens, p_len)
        if not resume and req.cached_logits is not None and off0 >= p_len:
            last = np.asarray(req.cached_logits)
        else:
            assert off0 < p_len, (off0, p_len)  # scheduler demotes no-logits full hits
            table_row = jnp.asarray(
                self.cache.block_tables[req.slot : req.slot + 1]
            )
            logits = None
            for off in range(off0, p_len, c):
                if req.rid in self._cancel_requests:
                    # mid-prefill cancellation: stop streaming chunks
                    # now; the caller releases the slot (and any KV
                    # already written dies with the pages)
                    raise RequestCancelled(
                        f"request {req.rid} cancelled mid-prefill",
                        rid=req.rid,
                    )
                n = min(c, p_len - off)
                chunk = np.zeros((1, c), np.int32)
                chunk[0, :n] = seq[off : off + n]
                args = (
                    jnp.asarray(chunk), jnp.int32(off), jnp.int32(n),
                    table_row,
                )
                t0 = self.tracer.now_us()
                logits, counts = self._run_offloaded(
                    self._prefill, args, kind="prefill", track=track
                )
                self.metrics.record_prefill_runs(self._last_run_stats["runs"])
                self.tracer.complete(
                    "prefill_chunk", track=track, cat="prefill", start_us=t0,
                    args={"rid": req.rid, "offset": off, "tokens": n,
                          "resume": resume,
                          "runs": int(self._last_run_stats["runs"])},
                )
                self._record_capacity_util(counts, c)
            if resume:
                return
            jax.block_until_ready(logits)
            last = np.asarray(logits)[0, -1]
        if self.faults is not None:
            spec = self.faults.fire("logits", req.rid)
            if spec is not None:
                self.tracer.lifecycle(
                    "fault", track=track, site="logits", mode=spec.mode,
                    rid=req.rid,
                )
                last = np.array(last, copy=True)
                last[0] = np.nan
        # finite guard: non-finite first-token logits (a poisoned
        # request) must never reach sampling or the prefix cache — the
        # request terminates with a typed error and a clean release
        if not np.all(np.isfinite(last)):
            raise PoisonedRequest(
                f"request {req.rid}: non-finite prefill logits",
                rid=req.rid,
            )
        self.cache.register_prefix(req.prompt, req.slot, last_logits=last)
        if self.ecfg.keep_logits:
            self.token_logits[req.rid] = [np.asarray(last, np.float32)]
        if self.ecfg.temperature > 0.0:
            # the TTFT token is sampled too — same categorical draw the
            # horizon scan applies to every later token
            tok = int(jax.random.categorical(
                jax.random.fold_in(self._prefill_key, req.rid),
                jnp.asarray(last) / jnp.float32(self.ecfg.temperature),
            ))
        else:
            tok = int(np.argmax(last))
        req.out.append(tok)
        req.pos = p_len
        # the TTFT token is tenant output too — without this the
        # per-tenant ledger undercounts every request by exactly one
        self.metrics.record_tenant_tokens(req.tenant, 1)
        self.tracer.instant(
            "first_token", track=track, cat="prefill", rid=req.rid, token=tok
        )

    # --------------------------------------------------- expert residency
    def _run_offloaded(self, program, args, kind: str = "decode",
                       track: str = "engine"):
        """Run one jitted program (prefill chunk or decode megastep)
        under the expert-residency contract: re-run after a synchronous
        upload until every expert the program actually dispatched to was
        resident *during* the run — only then are its outputs (and KV
        writes, which land at position-determined destinations and carry
        a deterministic token sequence, so a replay simply overwrites
        them with identical values) identical to the all-resident
        engine. Returns ``(*payload, counts)`` — everything the program
        emitted after the donated pools, with the trailing dispatch
        counts already fetched to host numpy (this fetch is the
        megastep's one host sync). ``self._last_run_stats`` records the
        run count and the compute/offload wall-time split: the first run
        is pure decode/prefill math, everything after it (uploads +
        replays) is offload overhead that used to conflate into the
        latency metric.
        """
        if self.offload is not None:
            self.offload.begin_step()
        missed = False
        runs = 0
        compute_s = 0.0
        offload_s = 0.0
        while True:
            t0 = time.time()
            t0_us = self.tracer.now_us()
            out = program(
                self.params, self.cache.k, self.cache.v, self.cache.quant,
                *args,
            )
            self.cache.k, self.cache.v = out[0], out[1]
            if out[2] is not None:  # quantized pools: scale/zero tables
                self.cache.quant = out[2]
            payload = out[3:-1]
            if runs == 0 and self._pending_expert_targets:
                # async expert streaming: the program is dispatched but
                # its counts not yet fetched — stage the boundary's
                # prefetch uploads now so the copies land while it
                # computes; the flip happens at the next boundary
                targets = self._pending_expert_targets
                self._pending_expert_targets = ()
                ti = time.time()
                ups, _ = self.offload.issue_async(targets)
                if ups:
                    self.metrics.record_async_issue(ups, time.time() - ti)
            # the one host sync: dispatch counts ([L, num_slots] for a
            # prefill chunk, [H, L, num_slots] for a decode megastep;
            # trailing dim 0 outside PMQ) — fetched for the offload miss
            # check and the capacity-utilization gauge
            counts = np.asarray(out[-1])
            runs += 1
            dt = time.time() - t0
            # run 1 is the program's real math; every later run is a
            # miss replay — the compute-vs-offload split, visible per run
            self.tracer.complete(
                "compute" if runs == 1 else "replay", track=track,
                cat=kind, start_us=t0_us, args={"run": runs},
            )
            if runs == 1:
                compute_s = dt
            else:
                offload_s += dt
            if self.offload is None:
                self._last_run_stats = {
                    "runs": runs, "compute_s": compute_s,
                    "offload_s": offload_s,
                }
                return payload + (counts,)
            t1 = time.time()
            # ensure_resident normalizes [L,S] and [H,L,S] itself
            uploads, nbytes = self.offload.ensure_resident(counts)
            if uploads == 0:
                if missed:
                    self.metrics.record_expert_miss_step()
                else:
                    self.metrics.record_expert_hit()
                self.offload.update_stats(counts)
                self._last_run_stats = {
                    "runs": runs, "compute_s": compute_s,
                    "offload_s": offload_s + (time.time() - t1),
                }
                return payload + (counts,)
            missed = True
            offload_s += time.time() - t1
            self.metrics.record_expert_miss(uploads, nbytes)

    def _record_capacity_util(self, counts: np.ndarray, t: int) -> None:
        """Feed the MoE capacity-padding gauge from one logical step's
        reported ``slot_counts`` ([L, num_slots]): routed (token, choice)
        pairs over the dispatch buffer's total capacity rows
        (``L · num_slots · cap`` for the ``t`` tokens the program ran).
        The complement is the dead-padding compute the grouped FFN path
        skips (see serving.metrics)."""
        if self._num_slots is None or counts is None or counts.size == 0:
            return
        from ..models.moe import dispatch_capacity

        cap = dispatch_capacity(self.model_cfg, t)
        denom = counts.shape[0] * self._num_slots * cap
        # slot_counts are pre-clip dispatch counts; clamp to cap so pairs
        # dropped by capacity (possible with drop_free_capacity=False)
        # don't push the occupied-row gauge past 1.0
        occupied = np.minimum(counts, cap).sum()
        self.metrics.record_capacity_utilization(
            float(occupied) / float(denom)
        )
        if self.routing is not None:
            gauges = self.routing.update(counts)
            if gauges:
                self.tracer.counter("routing", track="engine", **gauges)

    # ---------------------------------------------------- growth/preempt
    def _note_preempt(self, vreq: Request, vslot: int, *, for_rid: int,
                      for_tenant: str) -> None:
        """Lifecycle bookkeeping for one executed preemption."""
        vtrack = f"slot{vslot}"
        self.tracer.lifecycle(
            "preempt", track=vtrack, rid=vreq.rid, slot=vslot,
            step=self._step_idx, mode=self.ecfg.preempt_mode,
            swap_bytes=vreq.swapped.nbytes if vreq.swapped else 0,
            tenant=vreq.tenant, for_rid=for_rid, for_tenant=for_tenant,
        )
        self.tracer.flow("t", vreq.rid, track=vtrack)

    def _execute_preempt(self, action: PlanAction) -> None:
        vreq = self.scheduler.active.get(action.slot)
        if vreq is None or vreq.rid != action.rid:
            return  # defensive: plan victims are live actives
        swap = self.ecfg.preempt_mode == "swap"
        vreq = self.scheduler.preempt(action.slot, swap=swap)
        self._note_preempt(
            vreq, action.slot, for_rid=action.for_rid,
            for_tenant=action.for_tenant,
        )

    def _execute_grow(self, action: PlanAction) -> None:
        """Grow one active slot **horizon-ahead**: enough pages to
        cover all ``min(H, budget)`` KV writes of the coming megastep,
        so no write inside the fused scan can land on an unallocated
        page — growth, like every pool-pressure decision, happens only
        at megastep boundaries.

        The controller's page ledger simulates allocator + prefix-cache
        state exactly, so by the time a grow executes its pages are
        available (planned preemptions and prefix evictions ran
        earlier in the plan). The reactive loop below is a safety net
        for ledger/pool divergence only — it falls back to the
        historical policy-ordered preemption rather than crashing.
        """
        slot = action.slot
        req = self.scheduler.active.get(slot)
        if req is None or req.rid != action.rid:
            return  # the grower itself was victimized earlier in the plan
        need = self.cache.slot_deficit(
            slot, req.pos + req.next_decode_writes(self.ecfg.decode_horizon)
        )
        if need <= 0:
            return
        swap = self.ecfg.preempt_mode == "swap"
        # LRU-evictable prefix-cache pages count as available —
        # cache.grow evicts entries before preemption ever triggers
        while (
            self.cache.available_pages() < need
            and slot in self.scheduler.active
        ):
            vslot = self.scheduler.pick_victim()
            vreq = self.scheduler.preempt(vslot, swap=swap)
            self._note_preempt(
                vreq, vslot, for_rid=req.rid, for_tenant=req.tenant
            )
        if slot in self.scheduler.active:
            self.cache.grow(slot, need)

    # ------------------------------------------------------------ decode
    def lower_decode(self):
        """The decode megastep the server runs, lowered at this engine's
        shapes without running it: ``.compile().as_text()`` is the device
        program (on TPU its Pallas kernels appear as ``tpu_custom_call``)
        and ``.compile().memory_analysis()`` its buffer sizes."""
        args, _ = self._decode_args()
        return self._decode.lower(
            self.params, self.cache.k, self.cache.v, self.cache.quant, *args
        )

    def _decode_args(self):
        """Host-built inputs of one decode megastep (everything after the
        pools) and the ``[slots]`` active mask."""
        b = self.ecfg.max_slots
        tokens = np.zeros((b, 1), np.int32)
        positions = np.zeros((b,), np.int32)
        active = np.zeros((b,), bool)
        budgets = np.zeros((b,), np.int32)
        eos_ids = np.full((b,), -1, np.int32)
        for slot, req in self.scheduler.active.items():
            tokens[slot, 0] = req.out[-1]
            positions[slot] = req.pos
            active[slot] = True
            budgets[slot] = req.max_new - len(req.out)
            eos_ids[slot] = req.eos_id
        # one key per megastep (unused under greedy): offload replays of
        # the same megastep reuse it, so sampled runs replay bit-identically
        key = None
        if self.ecfg.temperature > 0.0:
            key = jax.random.fold_in(self._sample_key, self._megastep_idx)
        args = (jnp.asarray(tokens), jnp.asarray(positions),
                self.cache.tables_device(), jnp.asarray(active),
                jnp.asarray(budgets), jnp.asarray(eos_ids), key)
        return args, active

    def _decode_megastep(self) -> None:
        """Advance every active slot up to ``decode_horizon`` tokens in
        one fused jitted program, then apply the fetched ``[H, slots]``
        token matrix host-side: one dispatch, one host sync, one Python
        pass per megastep. Per-logical-step metrics are reconstructed
        from the emit mask (exact) and the megastep wall time (spread
        evenly — see serving.metrics)."""
        b = self.ecfg.max_slots
        h = self.ecfg.decode_horizon
        args, active = self._decode_args()
        t0 = time.time()
        t0_us = self.tracer.now_us()
        toks, emits, acts, *kept, counts = self._run_offloaded(
            self._decode, args
        )
        toks = np.asarray(toks)          # [H, B] (-1 where not emitted)
        emits = np.asarray(emits)        # [H, B] bool
        acts = np.asarray(acts)          # [H]
        logits = np.asarray(kept[0]) if kept else None  # [H, B, V]
        dt = time.time() - t0
        stats = self._last_run_stats
        # logical steps that emitted ≥ 1 token; trailing all-stopped scan
        # steps computed garbage and recorded nothing
        emitting = np.flatnonzero(emits.any(axis=1))
        steps_run = len(emitting)
        self.metrics.record_megastep(
            steps_run, stats["compute_s"], stats["offload_s"],
            stats["runs"], stats["runs"],
        )
        # the megastep span (engine track) plus one decode span per
        # active slot, all sharing the megastep's extent — the per-slot
        # view shows who actually emitted inside the fused program
        self.tracer.complete(
            "megastep", track="engine", cat="decode", start_us=t0_us,
            args={"megastep": self._megastep_idx, "horizon": h,
                  "active": int(active.sum()), "steps": steps_run,
                  "runs": int(stats["runs"])},
        )
        for slot, req in self.scheduler.active.items():
            self.tracer.complete(
                "decode", track=f"slot{slot}", cat="decode", start_us=t0_us,
                args={"rid": req.rid, "tokens": int(emits[:, slot].sum())},
            )
        self.tracer.counter(
            "pool", track="engine",
            page_util=self.cache.utilization,
            queue_depth=self.scheduler.queue_depth,
            active=int(active.sum()),
        )
        per_step_s = dt / max(steps_run, 1)
        for s in emitting:
            # queue depth / page utilization are genuinely constant
            # within a megastep (all scheduling happens at the boundary)
            self.metrics.record_decode_step(
                per_step_s, int(emits[s].sum()), float(acts[s]),
                self.scheduler.queue_depth,
                page_utilization=self.cache.utilization,
            )
            self._record_capacity_util(counts[s], b)
        if self.offload is not None:
            self.metrics.record_expert_residency(self.offload.resident_bytes)
        for slot, req in list(self.scheduler.active.items()):
            last_s = 0
            emitted = 0
            for s in range(h):
                if emits[s, slot]:
                    req.out.append(int(toks[s, slot]))
                    req.pos += 1
                    if logits is not None:
                        self.token_logits[req.rid].append(logits[s, slot])
                    last_s = s
                    emitted += 1
            # fairness accounting: debit the tenant's WDRR grant and
            # record the per-tenant token counters (policy witnesses)
            self.scheduler.note_tokens(req.tenant, emitted)
            self.metrics.record_tenant_tokens(req.tenant, emitted)
            if req.done:
                self.scheduler.finish(slot)
                track = f"slot{slot}"
                self.tracer.lifecycle(
                    "release", track=track, rid=req.rid, slot=slot,
                    step=self._step_idx + last_s,
                )
                self.tracer.flow("f", req.rid, track=track)
        self._step_idx += steps_run
        self._megastep_idx += 1
