"""The served Pallas kernels, and the decode megastep that calls them,
compile for a TPU v5e at published widths.

Interpret mode (tests/test_kernels.py, tests/test_moe_grouped.py) checks
the kernels' numerics but not the TPU compiler's rules: block tiling,
supported casts, VMEM size. Here each kernel on the serving path is
compiled, not run, for a described v5e chip at moonshot-v1-16b-a3b's
widths (d_model 2048, 16 KV heads x 128, 64 experts of d_ff 1408, group
128; shared experts 2 x 1408), through the same ``repro.kernels.ops``
wrappers and block choices the server uses.

The topology is described inside a module fixture, never at import, so
every xdist worker collects the same tests and only the one that runs
this file loads the TPU compiler. Keep these tests in this one file.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.registry import get_config
from repro.core.compressed_moe import BucketMeta, CompressedExperts
from repro.core.packing import PackedTensor
from repro.kernels import ops

D_MODEL, D_FF, HEAD_DIM, GROUP = 2048, 1408, 128, 128
SLOTS = 8  # decode batch
EXPERT_ROWS = 16  # one PMQ bucket's experts
CAP = SLOTS * 6  # drop-free decode capacity: tokens x top-k


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            yield topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, one_chip, *shapes):
    """Compile ``fn`` for the described chip from ``(shape, dtype)``
    leaves and return the optimized HLO text."""
    args = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s[0], s[1], sharding=one_chip),
        shapes,
        is_leaf=lambda s: isinstance(s, tuple) and len(s) == 2
        and all(isinstance(d, int) for d in s[0]),
    )
    return jax.jit(fn).lower(*args).compile().as_text()


def _packed(e, k, n, bits):
    if bits == 3:
        return ((e, k // 4, n), jnp.uint8), ((e, k // 8, n), jnp.uint8)
    return ((e, k // (8 // bits), n), jnp.uint8)


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("hkv,g", [(16, 1), (8, 8)], ids=["mha16", "gqa8x8"])
def test_paged_attention_compiles(one_chip, hkv, g, quant):
    nb, bs, mb = 80, 16, 10
    kv_dtype = jnp.uint8 if quant else jnp.bfloat16
    shapes = [
        ((SLOTS, hkv, g, HEAD_DIM), jnp.bfloat16),
        ((nb, bs, hkv, HEAD_DIM), kv_dtype),
        ((nb, bs, hkv, HEAD_DIM), kv_dtype),
        ((SLOTS, mb), jnp.int32),
        ((SLOTS,), jnp.int32),
    ]
    if quant:
        shapes.append([((nb, bs, hkv), jnp.float32)] * 4)

        def fn(q, k, v, tables, lengths, scales):
            return ops.paged_attention(
                q, k, v, tables, lengths, backend="pallas", quant=scales
            )
    else:
        def fn(q, k, v, tables, lengths):
            return ops.paged_attention(q, k, v, tables, lengths, backend="pallas")

    assert "tpu_custom_call" in _compiled_text(fn, one_chip, *shapes)


@pytest.mark.parametrize("bits", [1, 2, 3])
@pytest.mark.parametrize(
    "k,n", [(D_MODEL, D_FF), (D_FF, D_MODEL)], ids=["gate_up", "down"]
)
@pytest.mark.parametrize("swiglu", [False, True], ids=["gmm", "swiglu"])
def test_moe_gmm_compiles(one_chip, swiglu, k, n, bits):
    m = EXPERT_ROWS * CAP
    bm = 16
    w = _packed(EXPERT_ROWS, k, n, bits)
    sz = ((EXPERT_ROWS, k // GROUP, n), jnp.float32)
    x = ((m, k), jnp.bfloat16)
    be, na = ((m // bm,), jnp.int32), ((1,), jnp.int32)
    if swiglu:
        def fn(x, wg, wu, gs, gz, us, uz, be, na):
            return ops.moe_gmm_swiglu(
                x, wg, wu, gs, gz, us, uz, be, na,
                bits=bits, group=GROUP, backend="pallas", bm=bm,
            )
        shapes = (x, w, w, sz, sz, sz, sz, be, na)
    else:
        def fn(x, w, s, z, be, na):
            return ops.moe_gmm(
                x, w, s, z, be, na, bits=bits, group=GROUP,
                backend="pallas", bm=bm,
            )
        shapes = (x, w, sz, sz, be, na)
    assert "tpu_custom_call" in _compiled_text(fn, one_chip, *shapes)


@pytest.mark.parametrize(
    "k,n",
    [(D_MODEL, D_MODEL), (D_MODEL, 2 * D_FF), (2 * D_FF, D_MODEL)],
    ids=["attn", "shared_gate_up", "shared_down"],
)
def test_quant_matmul_4bit_compiles(one_chip, k, n):
    def fn(x, w, s, z):
        pt = PackedTensor(data=w, scale=s, zero=z, bits=4, shape=(k, n),
                          group=GROUP)
        return ops.quant_matmul(x, pt, backend="pallas")

    shapes = (
        ((SLOTS, k), jnp.bfloat16),
        ((k // 2, n), jnp.uint8),
        ((k // GROUP, n), jnp.float32),
        ((k // GROUP, n), jnp.float32),
    )
    assert "tpu_custom_call" in _compiled_text(fn, one_chip, *shapes)


def _served_param_specs(cfg, buckets, sharding=None):
    """Shapes of ``compress_for_serving``'s stacked output for ``cfg``,
    with PMQ buckets of ``(bits, experts)``: 4-bit attention and shared
    experts, an f32 router, f32 scale/zero tables."""
    l, d, f, g = cfg.num_layers, cfg.d_model, cfg.d_ff_expert, cfg.quant.group
    fp = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    def packed(k, n, bits=cfg.quant.attn_bits, lead=(l,)):
        data = [((*lead, k // (8 // bits), n))] if bits != 3 else [
            (*lead, k // 4, n), (*lead, k // 8, n)]
        return [sds(s, jnp.uint8) for s in data], [
            sds((*lead, -(-k // g), n), jnp.float32) for _ in "sz"]

    def dense(k, n):
        (data, *planes), (scale, zero) = packed(k, n)
        return {"w": PackedTensor(data=(data, *planes) if planes else data,
                                  scale=scale, zero=zero,
                                  bits=cfg.quant.attn_bits, shape=(k, n),
                                  group=g)}

    arrays, meta, start = {}, [], 0
    for bits, count in buckets:
        bucket = {}
        for name, (k, n) in (("w_gate", (d, f)), ("w_up", (d, f)),
                             ("w_down", (f, d))):
            data, (scale, zero) = packed(k, n, bits, lead=(l, count))
            keys = ("hi", "lo") if bits == 3 else ("data",)
            bucket[name] = {**dict(zip(keys, data)), "scale": scale,
                            "zero": zero}
        arrays[f"b{len(meta)}"] = bucket
        meta.append(BucketMeta(bits=bits, start=start, count=count))
        start += count
    hq, hkv, dh = cfg.num_heads * cfg.head_dim, cfg.num_kv_heads * cfg.head_dim, f
    fs = f * cfg.num_shared_experts
    return {
        "embed": sds((cfg.vocab_size, d), fp),
        "final_norm": sds((d,), fp),
        "unembed": sds((cfg.vocab_size, d), fp),
        "blocks": {
            "ln1": sds((l, d), fp),
            "attn": {"wq": dense(d, hq), "wk": dense(d, hkv),
                     "wv": dense(d, hkv), "wo": dense(hq, d)},
            "ln2": sds((l, d), fp),
            "moe": {
                "router": {"w": sds((l, d, cfg.num_experts), jnp.float32)},
                "shared": {"w_gate": dense(d, fs), "w_up": dense(d, fs),
                           "w_down": dense(fs, d)},
            },
            "moe_ce": CompressedExperts(
                meta=tuple(meta),
                slot_of_expert=sds((l, cfg.num_experts), jnp.int32),
                arrays=arrays, num_slots=start, group=g, d_model=d, d_ff=f,
            ),
        },
    }


def test_served_param_specs_match_compression():
    """The specs the megastep compile below uses are exactly what PMQ
    compression produces (checked at the reduced size, in bf16)."""
    from repro.core import pipeline
    from repro.models.registry import get_model

    cfg = dataclasses.replace(
        get_config("moonshot-v1-16b-a3b").reduced(), dtype="bfloat16"
    )
    params = get_model(cfg).init(jax.random.PRNGKey(0))
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 32)),
        jnp.int32,
    )
    served, _ = pipeline.compress_for_serving(
        params, pipeline.calibrate(params, tokens, cfg), cfg
    )
    ce = served["blocks"]["moe_ce"]
    spec = _served_param_specs(cfg, [(m.bits, m.count) for m in ce.meta])
    assert jax.tree.structure(served) == jax.tree.structure(spec)
    shapes = lambda t: [(a.shape, a.dtype) for a in jax.tree.leaves(t)]
    assert shapes(served) == shapes(spec)


def test_decode_megastep_compiles(one_chip, monkeypatch):
    """The engine's horizon-8 decode program at moonshot widths (2 of 48
    layers, 8 slots, drop-free capacity) calls the Pallas kernels."""
    from repro.serving.engine import _jitted_steps

    # the code asks jax for the platform and sees the CPU; the described
    # chip takes the Pallas branch
    monkeypatch.setattr(ops, "default_backend", lambda: "pallas")
    cfg = dataclasses.replace(
        get_config("moonshot-v1-16b-a3b"), num_layers=2,
        moe_capacity_factor=64.0,
    )
    params = _served_param_specs(cfg, [(1, 16), (2, 29), (3, 19)], one_chip)
    blocks, bs = 12, 16
    pool = ((2, SLOTS * blocks, bs, cfg.num_kv_heads, HEAD_DIM), jnp.bfloat16)
    decode, _ = _jitted_steps(cfg, True, None, 8, 0.0)
    shapes = (pool, pool, ((SLOTS, 1), jnp.int32), ((SLOTS,), jnp.int32),
              ((SLOTS, blocks), jnp.int32), ((SLOTS,), jnp.bool_),
              ((SLOTS,), jnp.int32), ((SLOTS,), jnp.int32))

    def fn(params, k, v, token, pos, tables, active, budgets, eos):
        return decode(params, k, v, None, token, pos, tables, active,
                      budgets, eos, None)

    args = [params] + [
        jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes
    ]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
