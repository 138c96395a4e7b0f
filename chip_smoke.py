"""Bring-up smoke on one TPU: PMQ-compressed moonshot-v1-16b-a3b served at
its published widths by the paged engine, checked against float32
references.

    python chip_smoke.py [--seed S]

One process, one chip. Phases, in order; any failure exits non-zero and
prints no result line:

1. device — the first JAX device must be a TPU, and ``REPRO_FFN_BACKEND``
   must not pin a CPU path (``ref``, ``interpret``, ``scan``).
2. build — the registry config at its published widths (d_model 2048,
   16 heads x 128, 64 experts of 1408, top-6, 2 shared, vocab 163840)
   with only the depth cut to ``LAYERS``; weights, calibration tokens
   and prompts from ``--seed``; PMQ compression by
   ``repro.core.pipeline.compress_for_serving``. All of it through
   ``repro.launch.serve``'s own builders.
3. program — the decode megastep, compiled, holds ``tpu_custom_call``.
4. kernels — each served Pallas kernel against its ``ref`` oracle on the
   same bf16 inputs, at moonshot widths, on shapes that cross the
   kernels' block boundaries: paged attention at KV lengths around page
   edges, the grouped expert GEMMs on layer 0's PMQ buckets with expert
   row counts around row-block edges, 4-bit ``quant_matmul`` on layer
   0's attention and shared-expert weights. Every row (or expert group)
   must lie within ``KERNEL_TOL``.
5. serve — 8 requests (prompt 128, 32 new tokens) through
   ``PagedServingEngine`` with the default engine settings (horizon 8,
   OTP on, drop-free capacity, grouped expert FFN), keeping the logits
   behind every served token; every request must finish with all its
   tokens and no typed error.
6. reference — a plain float32 jnp forward of the same compressed
   weights (dequantized by the ``ref`` kernels, matmuls at float32
   precision), teacher-forced on each prompt plus its served tokens, and
   the same forward in bf16 as a witness of what rounding alone does.
   The served logits of every token — the first from prefill, the rest
   from decode megasteps — are compared with the float32 one (see the
   tolerances below).

Lines before the last are smoke output, not measurements. The last line
is ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time
import traceback
import warnings
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

ARCH = "moonshot-v1-16b-a3b"
# 4 of 48 layers: the bf16 init and its PMQ copy must fit one v5e together
LAYERS, REQUESTS, PROMPT_LEN, MAX_NEW, SLOTS = 4, 8, 128, 32, 8
# Served logits come from bf16 activations and bf16-dequantized weight
# tiles; the reference keeps float32 throughout. A row's error is
# ||served - ref||_2 / ||ref||_2. Rounding alone keeps it near 0.02, so
# the median over first tokens, and over decode tokens, must stay under
# LOGITS_TOL: a kernel fault on every step, or on every token one expert
# block serves, moves the median. Where rounding swaps one of a row's
# top-k experts in some layer, that row alone reaches 0.1-0.26 — a
# kernel-free bf16 run of the reference does the same, and is printed
# beside each served error as the witness. Each row must stay under
# LOGITS_WORST; rarer data-dependent faults are the kernel phase's job.
LOGITS_TOL, LOGITS_WORST = 0.05, 0.5
# Pallas against ref on identical bf16 inputs differ by accumulation
# order and bf16 output rounding, ~3e-3; a mis-sliced head, a mask off
# by one at a page edge or a wrong expert block moves a row by > 0.05.
KERNEL_TOL = 2e-2
CPU_FFN_PATHS = ("ref", "interpret", "scan")


def say(msg: str) -> None:
    print(f"smoke: {msg}", flush=True)


def check_device():
    """The chip this run must use; refuses any other platform."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"chip_smoke needs a TPU; JAX's first device is {dev.platform!r}"
        )
    backend = os.environ.get("REPRO_FFN_BACKEND", "").strip().lower()
    if backend in CPU_FFN_PATHS:
        raise SystemExit(
            f"REPRO_FFN_BACKEND={backend!r} would bypass the Pallas expert "
            f"kernels; unset it"
        )
    return dev


def peak_bytes(dev):
    return (dev.memory_stats() or {}).get("peak_bytes_in_use", "n/a")


def rel_err(got, want) -> float:
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30))


def build(seed: int, dev):
    """``(cfg, engine, prompts)``: the published-width model, cut to
    ``LAYERS``, PMQ-compressed and behind a logits-keeping engine."""
    from repro.configs.registry import get_config
    from repro.launch import serve

    t0 = time.time()
    sargs = serve.parser().parse_args([
        "--arch", ARCH, "--published", "--layers", str(LAYERS), "--pmq",
        "--seed", str(seed), "--requests", str(REQUESTS),
        "--prompt-len", str(PROMPT_LEN), "--max-new", str(MAX_NEW),
        "--slots", str(SLOTS),
    ])
    cfg, params, prompts = serve.build_model(sargs)
    engine = serve.build_engine(sargs, cfg, params, keep_logits=True)
    del params
    full = get_config(ARCH)
    say(f"{cfg.name} at published widths: d_model {cfg.d_model}, "
        f"{cfg.num_heads}x{cfg.head_dim} q / {cfg.num_kv_heads} kv heads, "
        f"{cfg.num_experts} experts x d_ff {cfg.d_ff_expert}, top-{cfg.top_k}, "
        f"{cfg.num_shared_experts} shared, vocab {cfg.vocab_size}; layers cut "
        f"{full.num_layers} -> {cfg.num_layers}")
    say(f"build + PMQ compression {time.time() - t0:.1f} s, peak device "
        f"bytes {peak_bytes(dev)}; engine horizon "
        f"{engine.ecfg.decode_horizon}, otp {engine.ecfg.use_otp}, "
        f"drop-free {engine.ecfg.drop_free_capacity}, ffn "
        f"{engine.ecfg.ffn_backend or 'grouped'}")
    return cfg, engine, prompts


def check_decode_program(engine) -> None:
    """Compile the engine's decode megastep; its HLO must call kernels."""
    t0 = time.time()
    hlo = engine.lower_decode().compile().as_text()
    n_kernels = hlo.count("tpu_custom_call")
    say(f"decode megastep compile {time.time() - t0:.1f} s; "
        f"{n_kernels} tpu_custom_call sites")
    if n_kernels == 0:
        raise RuntimeError("decode megastep holds no tpu_custom_call")


# -------------------------------------------------------------- kernels
def _attention_errors(engine, cfg, key):
    """Per-sequence error of Pallas paged attention against ``ref`` on a
    random pool of the engine's geometry, one sequence per KV length
    around the page edges."""
    from repro.kernels import ops

    _, nb, bs, hkv, dh = engine.cache.k.shape
    mb = engine.ecfg.max_blocks_per_slot
    lengths = np.array([1, 2, bs - 1, bs, bs + 1, 2 * bs + 1, mb * bs - 1,
                        mb * bs], np.int32)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(
        kq, (len(lengths), hkv, cfg.num_heads // hkv, dh), jnp.bfloat16)
    k_pool = jax.random.normal(kk, (nb, bs, hkv, dh), jnp.bfloat16)
    v_pool = jax.random.normal(kv, (nb, bs, hkv, dh), jnp.bfloat16)
    tables = np.random.default_rng(0).integers(0, nb, (len(lengths), mb))
    args = (q, k_pool, v_pool, jnp.asarray(tables, jnp.int32),
            jnp.asarray(lengths))
    got = np.asarray(ops.paged_attention(*args).astype(jnp.float32))
    want = np.asarray(ops.paged_attention(*args, backend="ref")
                      .astype(jnp.float32))
    return {f"len {n}": rel_err(got[i], want[i]) for i, n in enumerate(lengths)}


def _expert_errors(engine, key):
    """Per-expert error of each PMQ bucket's grouped SwiGLU (Pallas
    ``moe_gmm_swiglu`` + ``moe_gmm``) against ``ref``, on layer 0's
    packed weights at decode capacity, with expert row counts cycling
    through the row-block edges. Unfilled capacity rows must be zero."""
    from repro.core.compressed_moe import gmm_block_rows, grouped_bucket_ffn

    ce = engine.params["blocks"]["moe_ce"]
    cap = SLOTS * engine.cfg.top_k  # drop-free decode capacity
    bm = gmm_block_rows(cap)
    edges = [0, 1, bm - 1, bm, bm + 1, 2 * bm + 1, cap - 1, cap]
    errs = {}
    for i, m in enumerate(ce.meta):
        w = jax.tree.map(lambda a: a[0], ce.arrays[f"b{i}"])
        fill = np.resize(np.array(edges, np.int32), m.count)
        x = jax.random.normal(jax.random.fold_in(key, i),
                              (m.count * cap, ce.d_model), jnp.bfloat16)
        run = functools.partial(
            grouped_bucket_ffn, x, w, bits=m.bits, group=ce.group,
            count=m.count, cap=cap, fill=jnp.asarray(fill),
        )
        got = np.asarray(run().astype(jnp.float32)).reshape(m.count, cap, -1)
        want = np.asarray(run(kernel_backend="ref").astype(jnp.float32)
                          ).reshape(m.count, cap, -1)
        for e, n in enumerate(fill):
            err = rel_err(got[e, :n], want[e, :n]) if n else 0.0
            if np.any(got[e, n:]):
                err = float("inf")
            errs[f"{m.bits}-bit expert {e} rows {n}"] = err
    return errs


def _quant_matmul_errors(engine, key):
    """Per-row error of 4-bit Pallas ``quant_matmul`` against ``ref`` on
    layer 0's attention query and shared-expert down projections, at a
    decode batch and at a row count spanning two row blocks."""
    from repro.kernels import ops

    blocks = engine.params["blocks"]
    errs = {}
    for name, pt in (("wq", blocks["attn"]["wq"]["w"]),
                     ("shared w_down", blocks["moe"]["shared"]["w_down"]["w"])):
        pt = jax.tree.map(lambda a: a[0], pt)
        for rows in (SLOTS, 300):
            x = jax.random.normal(jax.random.fold_in(key, rows),
                                  (rows, pt.shape[0]), jnp.bfloat16)
            got, want = (
                np.asarray(ops.quant_matmul(x, pt, backend=b).astype(jnp.float32))
                for b in (None, "ref")
            )
            errs[f"{name} x{rows}"] = max(
                rel_err(got[r], want[r]) for r in range(rows))
    return errs


def check_kernels(engine, cfg, seed: int) -> None:
    """Phase 4: every served kernel within ``KERNEL_TOL`` of its oracle."""
    t0 = time.time()
    key = jax.random.PRNGKey(seed)
    bad = []
    for name, errs in (
        ("paged attention", _attention_errors(engine, cfg, key)),
        ("grouped expert SwiGLU", _expert_errors(engine, key)),
        ("4-bit quant_matmul", _quant_matmul_errors(engine, key)),
    ):
        worst = max(errs, key=errs.get)
        say(f"kernel {name}: {len(errs)} cases, worst rel L2 err "
            f"{errs[worst]:.3e} ({worst}) (tol {KERNEL_TOL})")
        bad += [f"{name} {c}: {e:.3e}" for c, e in errs.items()
                if not e <= KERNEL_TOL]
    say(f"kernels checked in {time.time() - t0:.1f} s")
    if bad:
        raise RuntimeError(f"kernels disagree with ref: {bad}")


# --------------------------------------------------------------- serving
def serve_requests(engine, prompts):
    """Phase 5: serve every prompt; all must finish, none with an error."""
    from repro.serving import Request

    t0 = time.time()
    out = engine.serve(
        [Request(rid=i, prompt=p, max_new=MAX_NEW)
         for i, p in enumerate(prompts)]
    )
    say(f"served {len(out)} requests x {MAX_NEW} tokens in "
        f"{time.time() - t0:.1f} s wall (prefill compile included)")
    if engine.errors:
        raise RuntimeError(f"typed errors: {engine.errors}")
    short = {r: len(o) for r, o in out.items() if len(o) != MAX_NEW}
    if short or len(out) != REQUESTS:
        raise RuntimeError(f"requests did not finish: {short or out.keys()}")
    say("all requests finished with no typed error")
    return out


# ------------------------------------------------------------ reference
def _dense(pt):
    """A 4-bit ``PackedTensor`` (attention, shared experts) in float32."""
    from repro.kernels import ref

    return ref.dequant_ref(pt.data, pt.scale, pt.zero, pt.bits, pt.shape[0],
                           pt.group)


def _expert(w, bits: int, k: int, group: int):
    """One expert's ``{data|hi,lo, scale, zero}`` matrix in float32."""
    from repro.kernels import ref

    planes = (w["hi"], w["lo"]) if bits == 3 else w["data"]
    return ref.dequant_ref(planes, w["scale"], w["zero"], bits, k, group)


def _rms(x, w, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (
        1.0 + w.astype(jnp.float32)
    )


def _rope(x, theta):  # x [T, H, dh], positions 0..T-1
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnums=2)
def _embed(embed, tokens, dt):
    return embed[tokens].astype(dt)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _reference_layer(x, p, cfg, dt):
    """One decoder layer on the compressed weights, with activations and
    matmul operands in ``dt`` (softmax, norms and routing in float32):
    causal attention, top-k routing with every expert computed densely
    and weighted by its gate, shared experts. ``x [T, D]``."""
    f32 = jnp.float32

    def rounded(a):  # what the served path keeps in dt, back in f32
        return a.astype(dt).astype(f32)

    def mm(a, w):
        return jnp.dot(a.astype(dt), w.astype(dt),
                       preferred_element_type=f32).astype(dt)

    def swiglu(h, wg, wu, wd):
        return mm(jax.nn.silu(mm(h, wg).astype(f32)) * mm(h, wu), wd)

    t = x.shape[0]
    hq, hkv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    a = p["attn"]
    h = _rms(x, p["ln1"], cfg.norm_eps).astype(dt)
    q = rounded(_rope(mm(h, _dense(a["wq"]["w"])).reshape(t, hq, dh)
                      .astype(f32), cfg.rope_theta))
    k = rounded(_rope(mm(h, _dense(a["wk"]["w"])).reshape(t, hkv, dh)
                      .astype(f32), cfg.rope_theta))
    v = rounded(mm(h, _dense(a["wv"]["w"])).reshape(t, hkv, dh))
    k = jnp.repeat(k, hq // hkv, axis=1)
    v = jnp.repeat(v, hq // hkv, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) * dh**-0.5
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)
    x = (x + mm(o.reshape(t, hq * dh), _dense(a["wo"]["w"]))).astype(dt)

    h = _rms(x, p["ln2"], cfg.norm_eps).astype(dt)
    probs = jax.nn.softmax(h.astype(f32) @ p["moe"]["router"]["w"], -1)
    gates, idx = jax.lax.top_k(probs, cfg.top_k)
    gates = gates / gates.sum(-1, keepdims=True)
    ce = p["moe_ce"]
    weight = jnp.zeros((t, ce.num_slots), f32).at[
        jnp.arange(t)[:, None], ce.slot_of_expert[idx]
    ].add(gates)  # [T, slot]: each permuted expert's routing weight
    y = jnp.zeros(x.shape, f32)
    for i, m in enumerate(ce.meta):

        def expert(acc, xs, bits=m.bits):
            w, gate = xs
            out = swiglu(
                h,
                _expert(w["w_gate"], bits, ce.d_model, ce.group),
                _expert(w["w_up"], bits, ce.d_model, ce.group),
                _expert(w["w_down"], bits, ce.d_ff, ce.group),
            )
            return acc + gate[:, None] * out.astype(f32), None

        y, _ = jax.lax.scan(
            expert, y,
            (ce.arrays[f"b{i}"], weight[:, m.start : m.start + m.count].T),
        )
    sh = p["moe"]["shared"]
    y = y + swiglu(h, *(_dense(sh[n]["w"]) for n in
                        ("w_gate", "w_up", "w_down"))).astype(f32)
    return (x + y.astype(dt)).astype(dt)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _reference_head(x, norm, unembed, eps, dt):
    h = _rms(x, norm, eps).astype(dt).astype(jnp.float32)
    return h @ unembed.astype(jnp.float32).T


def reference_logits(params, cfg, tokens: np.ndarray, dt=jnp.float32):
    """``[T, V]`` float32 logits of ``tokens`` under the compressed model,
    computed in ``dt``."""
    if (cfg.qk_norm or cfg.attn_bias or cfg.local_window or not cfg.is_moe
            or "otp" in params["blocks"]):
        raise NotImplementedError("the reference covers moonshot's blocks")
    with jax.default_matmul_precision(
            "float32" if dt == jnp.float32 else "default"):
        x = _embed(params["embed"], jnp.asarray(tokens), dt)
        for l in range(cfg.num_layers):
            x = _reference_layer(
                x, jax.tree.map(lambda a: a[l], params["blocks"]), cfg, dt
            )
        unembed = params.get("unembed", params["embed"])
        return np.asarray(_reference_head(
            x, params["final_norm"], unembed, cfg.norm_eps, dt))


def compare_with_reference(engine, cfg, prompts, out) -> None:
    """Phase 6: every served token's logits against the float32 reference,
    teacher-forced on the served tokens, each error printed beside the
    bf16 reference's."""
    t0 = time.time()
    rows = {"first": [], "decode": []}
    bad = []
    for rid, prompt in enumerate(prompts):
        served = np.stack(engine.token_logits[rid])  # [MAX_NEW, V]
        seq = np.concatenate([prompt, np.asarray(out[rid][:-1], np.int32)])
        ref = reference_logits(engine.params, cfg, seq)[PROMPT_LEN - 1 :]
        ref16 = reference_logits(engine.params, cfg, seq, jnp.bfloat16)[
            PROMPT_LEN - 1 :]
        if not (np.all(np.isfinite(ref)) and np.all(np.isfinite(served))):
            raise RuntimeError(f"rid {rid}: non-finite logits")
        err = np.array([rel_err(served[i], ref[i]) for i in range(MAX_NEW)])
        err16 = np.array([rel_err(ref16[i], ref[i]) for i in range(MAX_NEW)])
        for i in np.flatnonzero(~(err <= LOGITS_WORST)):
            bad.append(f"rid {rid} token {i}: {err[i]:.3e}")
        rows["first"].append(err[0])
        rows["decode"] += err[1:].tolist()
        say(f"rid {rid}: first-token rel L2 err {err[0]:.3e} (bf16 "
            f"reference {err16[0]:.3e}); decode median "
            f"{np.median(err[1:]):.3e} max {err[1:].max():.3e} (bf16 "
            f"reference median {np.median(err16[1:]):.3e} max "
            f"{err16[1:].max():.3e})")
    for kind, errs in rows.items():
        med = float(np.median(errs))
        say(f"{kind}-token logits rel L2 err median {med:.3e} (tol "
            f"{LOGITS_TOL}), worst {max(errs):.3e} over {len(errs)} tokens")
        if not med <= LOGITS_TOL:
            bad.append(f"{kind}-token median {med:.3e} > {LOGITS_TOL}")
    say(f"reference {time.time() - t0:.1f} s")
    if bad:
        raise RuntimeError(f"served logits disagree with the reference: {bad}")


# ----------------------------------------------------------------- main
def run(seed: int) -> dict:
    dev = check_device()
    say(f"device {dev.platform} {dev.device_kind} x{len(jax.devices())}")
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.launch.compile_cache import enable_compile_cache

    say(f"compile cache {enable_compile_cache()}")
    warnings.filterwarnings(
        "error", category=RuntimeWarning, message="compressed_expert_ffn"
    )
    cfg, engine, prompts = build(seed, dev)
    check_decode_program(engine)
    check_kernels(engine, cfg, seed)
    out = serve_requests(engine, prompts)
    compare_with_reference(engine, cfg, prompts, out)
    say(f"peak device bytes {peak_bytes(dev)}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)
    try:
        device = run(args.seed)
    except Exception as e:  # any failed phase: no result line
        traceback.print_exc()
        print(f"smoke: FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
