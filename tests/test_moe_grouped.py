"""Grouped expert-GEMM dispatch (the compressed-MoE hot path).

Contract: the grouped path — ragged compaction + ``ops.moe_gmm`` /
``ops.moe_gmm_swiglu`` with ``num_active`` block skipping — computes the
same thing as the legacy per-expert scan for every routing pattern:
bit-bucket mixes, OTP masks, capacity clipping, empty experts, resident
partitions, and expert-parallel reshapes. The single-device layer builds
that layout straight from the routed pairs and gives the capacity
layout's outputs bit for bit, in a buffer sized by the pairs
(``grouped_extent``), which holds every routing. Plus: the Pallas
kernels match their jnp oracles in interpret mode, and the serving
engine's greedy outputs are unchanged under the default (grouped)
backend.
"""
import dataclasses
import itertools
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import compressed_moe as cm
from repro.core import otp as otp_mod
from repro.core.quantizers import quantize_to_packed
from repro.kernels import ops, ref
from repro.models.moe import (
    capacity_dispatch,
    combine,
    dispatch_capacity,
    route_topk,
    slot_fill_counts,
)


def _experts(e, d, f, seed=0):
    rng = np.random.default_rng(seed)
    return {
        "w_gate": rng.normal(size=(e, d, f)).astype(np.float32),
        "w_up": rng.normal(size=(e, d, f)).astype(np.float32),
        "w_down": rng.normal(size=(e, f, d)).astype(np.float32),
    }


def _routed(ce, t, k, cap, seed, mask_p=0.0):
    """Random routing → (xp, slot_fill, dest, valid)."""
    rng = np.random.default_rng(seed)
    x2 = jnp.asarray(rng.normal(size=(t, ce.d_model)), jnp.float32)
    slots = jnp.asarray(rng.integers(0, ce.num_slots, size=(t, k)), jnp.int32)
    gates = jax.nn.softmax(jnp.asarray(rng.normal(size=(t, k)), jnp.float32))
    mask = None
    if mask_p > 0:
        mask = jnp.asarray(
            (rng.random((t, k)) > mask_p).astype(np.float32)
        )
    xp, dest, valid, _ = capacity_dispatch(
        x2, slots, gates, ce.num_slots, cap, mask
    )
    fill = slot_fill_counts(dest, valid, ce.num_slots, cap)
    return xp, fill, dest, valid


# ------------------------------------------------- grouped == scan (fuzzed)
@given(
    bits_seed=st.integers(0, 1000),
    t=st.integers(6, 28),
    k=st.integers(1, 3),
    cap=st.sampled_from([8, 16, 24]),
    mask_p=st.sampled_from([0.0, 0.4]),
)
@settings(max_examples=10, deadline=None)
def test_grouped_matches_scan_fuzzed(bits_seed, t, k, cap, mask_p):
    rng = np.random.default_rng(bits_seed)
    e = int(rng.integers(3, 7))
    bits = [int(b) for b in rng.choice([1, 2, 3, 4], size=e)]
    ce = cm.build_compressed_experts(
        _experts(e, 32, 48, seed=bits_seed), bits, group=16, ep=1,
        refine=False,
    )
    xp, fill, dest, valid = _routed(ce, t, k, cap, bits_seed, mask_p)
    y_scan = np.asarray(cm.compressed_expert_ffn(ce, xp, cap, backend="scan"))
    y_ref = np.asarray(
        cm.compressed_expert_ffn(
            ce, xp, cap, backend="ref", slot_fill=fill, pairs=t * k
        )
    )
    y_int = np.asarray(
        cm.compressed_expert_ffn(
            ce, xp, cap, backend="interpret", slot_fill=fill, pairs=t * k
        )
    )
    np.testing.assert_allclose(y_ref, y_scan, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(y_int, y_ref, rtol=2e-4, atol=2e-4)
    # uncompacted grouped layout (no slot_fill) agrees too
    y_nofill = np.asarray(
        cm.compressed_expert_ffn(ce, xp, cap, backend="ref")
    )
    np.testing.assert_allclose(y_nofill, y_ref, rtol=2e-4, atol=2e-4)


def test_empty_expert_contributes_nothing():
    """An expert with zero routed rows must produce exactly-zero output
    rows and zero grouped blocks — the ragged frontier skips it."""
    e = 4
    ce = cm.build_compressed_experts(
        _experts(e, 32, 32, seed=1), [2, 2, 4, 4], group=16, ep=1,
        refine=False,
    )
    cap = 16
    t, k = 10, 2
    rng = np.random.default_rng(2)
    x2 = jnp.asarray(rng.normal(size=(t, 32)), jnp.float32)
    # route everything to slot 1: slots 0, 2, 3 stay empty
    slots = jnp.ones((t, k), jnp.int32)
    gates = jnp.full((t, k), 0.5, jnp.float32)
    xp, dest, valid, _ = capacity_dispatch(x2, slots, gates, ce.num_slots, cap)
    fill = slot_fill_counts(dest, valid, ce.num_slots, cap)
    assert list(np.asarray(fill)) == [0, 16, 0, 0]  # cap-clipped to 16
    y = np.asarray(
        cm.compressed_expert_ffn(ce, xp, cap, backend="ref", slot_fill=fill)
    )
    y_scan = np.asarray(cm.compressed_expert_ffn(ce, xp, cap, backend="scan"))
    np.testing.assert_allclose(y, y_scan, rtol=2e-4, atol=2e-4)
    for s in (0, 2, 3):
        assert np.all(y[s * cap : (s + 1) * cap] == 0.0)


def test_grouped_resident_map_bitwise_identical():
    """Resident indirection rides the scalar block_expert table: same
    bits in, same floats out as the all-resident grouped path."""
    ce = cm.build_compressed_experts(
        _experts(4, 32, 48, seed=3), [1, 2, 2, 3], group=16, ep=1,
        refine=False,
    )
    cap = 8
    xp, fill, _, _ = _routed(ce, 12, 2, cap, seed=4)
    y_full = np.asarray(
        cm.compressed_expert_ffn(ce, xp, cap, backend="ref", slot_fill=fill)
    )
    # permuted resident rows: bucket b1 (count 2) stored reversed
    arrays = dict(ce.arrays)
    arrays["b1"] = jax.tree.map(lambda a: a[::-1], ce.arrays["b1"])
    rmap = {
        f"b{i}": jnp.arange(m.count, dtype=jnp.int32)
        for i, m in enumerate(ce.meta)
    }
    rmap["b1"] = jnp.asarray([1, 0], jnp.int32)
    ce_perm = dataclasses.replace(
        ce, arrays=arrays, resident_map=rmap,
        resident_rows=tuple(m.count for m in ce.meta),
    )
    y_res = np.asarray(
        cm.compressed_expert_ffn(
            ce_perm, xp, cap, backend="ref", slot_fill=fill
        )
    )
    np.testing.assert_array_equal(y_res, y_full)


def test_grouped_ep_reshape_equivalent(monkeypatch):
    """ep > 1 splits each bucket across the model axis; the vmapped
    grouped path must agree with the ep=1 result (same math, reshaped)."""
    ce = cm.build_compressed_experts(
        _experts(4, 32, 32, seed=5), [2, 2, 2, 2], group=16, ep=2,
        refine=False,
    )
    cap = 16
    xp, fill, _, _ = _routed(ce, 14, 2, cap, seed=6)
    y1 = np.asarray(
        cm.compressed_expert_ffn(ce, xp, cap, backend="ref", slot_fill=fill)
    )
    monkeypatch.setattr(cm, "model_axis_size", lambda: 2)
    y2 = np.asarray(
        cm.compressed_expert_ffn(ce, xp, cap, backend="ref", slot_fill=fill)
    )
    np.testing.assert_allclose(y2, y1, rtol=2e-5, atol=2e-5)


# ------------------------------------ ragged dispatch == capacity layout
def _tiny_layer(e, d, k, seed):
    """Router, OTP router and PMQ experts of a tiny compressed layer."""
    ce = cm.build_compressed_experts(
        _experts(e, d, 48, seed=seed), [1, 2, 2, 3, 3, 3, 2, 1][:e],
        group=16, ep=1, refine=False,
    )
    keys = jax.random.split(jax.random.PRNGKey(seed), 2)
    p = {"router": {"w": jax.random.normal(keys[0], (d, e)) / d**0.5}}
    otp = otp_mod.init_otp_router(keys[1], d, k)
    return p, ce, otp


def _with_resident_map(ce):
    """Every bucket stored in reversed row order behind a resident map."""
    arrays = {b: jax.tree.map(lambda a: a[::-1], w) for b, w in ce.arrays.items()}
    rmap = {
        f"b{i}": jnp.arange(m.count, dtype=jnp.int32)[::-1]
        for i, m in enumerate(ce.meta)
    }
    return dataclasses.replace(
        ce, arrays=arrays, resident_map=rmap,
        resident_rows=tuple(m.count for m in ce.meta),
    )


def _capacity_layer(p, ce, x2, cfg, cf, otp, backend):
    """The layer through the ``[num_slots·cap, D]`` capacity layout."""
    t, k = x2.shape[0], cfg.top_k
    _, idx, gates = route_topk(p["router"], x2, k)
    mask = None if otp is None else otp_mod.otp_mask(otp, x2, idx, gates)
    slots = ce.slot_of_expert[idx]
    cap = dispatch_capacity(cfg, t, cf)
    xp, dest, valid, gflat = capacity_dispatch(
        x2, slots, gates, ce.num_slots, cap, mask
    )
    fill = slot_fill_counts(dest, valid, ce.num_slots, cap)
    yp = cm.compressed_expert_ffn(ce, xp, cap, backend=backend, slot_fill=fill)
    yp_ragged = cm.compressed_expert_ffn(
        ce, xp, cap, backend=backend, slot_fill=fill, pairs=t * k
    )
    np.testing.assert_array_equal(np.asarray(yp_ragged), np.asarray(yp))
    eff = slots.reshape(-1)
    if mask is not None:
        eff = jnp.where(mask.reshape(-1) > 0, eff, ce.num_slots)
    y = combine(yp, dest, valid, gflat, t, k)
    return y, fill, eff, cap, valid


@pytest.mark.parametrize("resident", [False, True], ids=["all", "resident"])
@pytest.mark.parametrize("use_otp", [False, True], ids=["dense", "otp"])
@pytest.mark.parametrize("cf", [None, 1.0], ids=["drop_free", "tight"])
@pytest.mark.parametrize("backend", ["ref", "interpret"])
def test_ragged_layer_bitwise_equals_capacity_path(backend, cf, use_otp,
                                                   resident):
    """The single-device layer (pairs → compacted rows) gives the capacity
    path's outputs bit for bit, with the same per-slot fill and
    ``slot_counts``, whether capacity drops pairs or not."""
    e, d, k, t = 8, 32, 3, 24
    p, ce, otp = _tiny_layer(e, d, k, seed=40)
    if resident:
        ce = _with_resident_map(ce)
    cfg = SimpleNamespace(num_experts=e, top_k=k, moe_capacity_factor=float(e))
    rng = np.random.default_rng(41)
    # a shared offset skews the router, so tight capacity must drop pairs
    x2 = jnp.asarray(rng.normal(size=(t, d)) + 2.0 * rng.normal(size=(1, d)),
                     jnp.float32)
    weight = jnp.asarray(np.arange(t) % 5 != 0)  # some padded tokens
    y, info = cm.compressed_moe_layer(
        p, ce, x2.reshape(2, t // 2, d), cfg, otp_params=otp if use_otp else None,
        capacity_factor=cf, count_weight=weight, ffn_backend=backend,
    )
    y_cap, fill, eff, cap, valid = _capacity_layer(
        p, ce, x2, cfg, cf, otp if use_otp else None, backend
    )
    np.testing.assert_array_equal(np.asarray(y).reshape(t, d), np.asarray(y_cap))
    if cf is not None:
        assert not np.all(np.asarray(valid)[np.asarray(eff) < ce.num_slots])
    else:
        assert np.all(np.asarray(valid)[np.asarray(eff) < ce.num_slots])
    yg, row, valid_r, fill_r = cm.ragged_expert_ffn(
        ce, x2, eff, cap, kernel_backend=backend
    )
    np.testing.assert_array_equal(np.asarray(fill_r), np.asarray(fill))
    np.testing.assert_array_equal(np.asarray(valid_r), np.asarray(valid))
    counted = np.where(np.repeat(np.asarray(weight), k), np.asarray(eff),
                       ce.num_slots)
    np.testing.assert_array_equal(
        np.asarray(info["slot_counts"]),
        np.bincount(counted, minlength=ce.num_slots + 1)[:-1],
    )
    bm = cm.gmm_block_rows(cap)
    assert yg.shape[0] == sum(
        cm.grouped_extent(m.count, cap, t * k, bm) for m in ce.meta
    )


def _worst_fill(count, pairs, bm):
    """A routing that reaches the bound: one pair on every slot, then the
    rest in whole blocks, slot after slot."""
    fill = np.ones(count, np.int64) if pairs >= count else (
        np.arange(count) < pairs).astype(np.int64)
    rest = pairs - int(fill.sum())
    for s in itertools.cycle(range(count)):
        if rest < bm:
            break
        fill[s] += bm
        rest -= bm
    fill[0] += rest  # inside a block slot 0 has already opened
    return fill


@pytest.mark.parametrize("t", [16, 64])
@pytest.mark.parametrize(
    "routing", ["one_expert", "one_per_expert", "one_bucket", "worst_bucket"]
)
def test_grouped_extent_holds_every_routing(routing, t):
    """At 64 experts, top-6, in three bit buckets and drop-free capacity,
    each bucket's compacted rows fit :func:`grouped_extent` for
    adversarial routings and never pass ``count·cap``; at the decode
    shape (64 tokens) the extent is at most an eighth of ``count·cap``."""
    k, e = 6, 64
    bits = [1] * 16 + [2] * 29 + [3] * 19
    ce = cm.build_compressed_experts(
        _experts(e, 16, 16, seed=50), bits, group=16, ep=1, refine=False
    )
    cfg = SimpleNamespace(num_experts=e, top_k=k, moe_capacity_factor=float(e))
    cap, n = dispatch_capacity(cfg, t), t * k
    bm = cm.gmm_block_rows(cap)
    big = max(ce.meta, key=lambda m: m.count)
    if routing == "one_expert":
        eids = np.zeros(n, np.int64)
    elif routing == "one_per_expert":
        eids = np.arange(n) % e
    elif routing == "one_bucket":
        eids = big.start + np.arange(n) % big.count
    else:
        fill = _worst_fill(big.count, n, bm)
        eids = big.start + np.repeat(np.arange(big.count), fill)
    x2 = jnp.asarray(np.random.default_rng(51).normal(size=(t, 16)), jnp.float32)
    yg, row, valid, fill = cm.ragged_expert_ffn(ce, x2, jnp.asarray(eids), cap)
    assert np.all(np.asarray(valid))  # drop-free: every pair keeps its row
    rows = np.asarray(row)
    assert len(set(rows.tolist())) == n and rows.max() < yg.shape[0]
    fill = np.asarray(fill)
    base = 0
    for m in ce.meta:
        extent = cm.grouped_extent(m.count, cap, n, bm)
        f = fill[m.start:m.start + m.count]
        used = int((-(-f // bm) * bm).sum())
        assert used <= extent <= m.count * cap
        mine = (rows >= base) & (rows < base + extent)
        assert mine.sum() == f.sum()  # the bucket's rows stay in its buffer
        if routing == "worst_bucket" and m is big:
            assert used == extent  # the bound is reached
        if t == 64:
            assert 8 * extent <= m.count * cap
        base += extent
    assert yg.shape[0] == base


@pytest.mark.parametrize("count,cap", [(1, 16), (2, 8), (2, 24), (3, 16)])
def test_grouped_extent_is_the_worst_case(count, cap):
    """For every pair count, :func:`grouped_extent` equals the most rows
    any fill of ``count`` slots (each ≤ ``cap``) takes in bm-aligned
    groups: it holds every routing, and no smaller bound does."""
    bm = cm.gmm_block_rows(cap)
    fills = np.asarray(list(itertools.product(range(cap + 1), repeat=count)))
    used = (-(-fills // bm) * bm).sum(axis=1)
    total = fills.sum(axis=1)
    for pairs in range(1, count * cap + 1):
        assert cm.grouped_extent(count, cap, pairs, bm) == used[
            total <= pairs
        ].max()


def test_bad_backend_rejected():
    ce = cm.build_compressed_experts(
        _experts(2, 32, 32, seed=7), [2, 2], group=16, ep=1, refine=False
    )
    xp = jnp.zeros((ce.num_slots * 8, 32), jnp.float32)
    with pytest.raises(ValueError, match="not in"):
        cm.compressed_expert_ffn(ce, xp, 8, backend="nope")


def test_gmm_block_rows_divides_cap():
    for cap in (8, 16, 24, 32, 64, 128, 256, 1000 * 8):
        bm = cm.gmm_block_rows(cap)
        assert cap % bm == 0 and bm % 8 == 0


# ------------------------------------------------------ kernel-level ragged
def _packed_bucket(e, k, n, bits, group, seed):
    rng = np.random.default_rng(seed)
    ws = [jnp.asarray(rng.normal(size=(k, n)), jnp.float32) for _ in range(e)]
    pts = [quantize_to_packed(w, bits, group=group, refine=False) for w in ws]
    if bits == 3:
        packed = (
            jnp.stack([p.data[0] for p in pts]),
            jnp.stack([p.data[1] for p in pts]),
        )
    else:
        packed = jnp.stack([p.data for p in pts])
    scale = jnp.stack([p.scale for p in pts])
    zero = jnp.stack([p.zero for p in pts])
    return packed, scale, zero


@pytest.mark.parametrize("bits", [1, 2, 3, 4])
def test_moe_gmm_num_active_skips_blocks(bits):
    e, k, n, bm = 3, 128, 128, 8
    packed, scale, zero = _packed_bucket(e, k, n, bits, 128, seed=bits)
    rng = np.random.default_rng(bits + 1)
    m = 6 * bm
    x = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    be = jnp.asarray([0, 0, 1, 2, 2, 2], jnp.int32)
    na = jnp.asarray([4], jnp.int32)
    y_ref = ref.moe_gmm_ref(
        x, packed, scale, zero, be, na, bits=bits, group=128, bm=bm
    )
    y = ops.moe_gmm(
        x, packed, scale, zero, be, na,
        bits=bits, group=128, backend="interpret", bm=bm,
    )
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(y_ref), rtol=2e-5, atol=2e-5
    )
    # blocks past the frontier are exactly zero; blocks before it match
    # the unmasked GEMM
    y_all = ref.moe_gmm_ref(
        x, packed, scale, zero, be, bits=bits, group=128, bm=bm
    )
    np.testing.assert_array_equal(np.asarray(y)[4 * bm :], 0.0)
    np.testing.assert_allclose(
        np.asarray(y)[: 4 * bm], np.asarray(y_all)[: 4 * bm],
        rtol=2e-5, atol=2e-5,
    )


def _f64_projection(x, packed, scale, zero, be, bits, bm):
    """``x @ W`` per row block's expert in float64 over the oracle's
    dequantised weights, and the f32 rounding bound of a ``k``-term dot
    product against it, ``sqrt(k)·u·(|x| @ |W|)`` (u = 2^-24)."""
    k = x.shape[1]
    planes = list(zip(*packed)) if bits == 3 else list(packed)
    w = np.stack([
        np.asarray(ref.dequant_ref(p, s_, z_, bits, k), np.float64)
        for p, s_, z_ in zip(planes, scale, zero)
    ])[np.repeat(np.asarray(be), bm)]
    xf = np.asarray(x, np.float64)
    bound = np.sqrt(k) * 2.0 ** -24 * np.einsum("mk,mkn->mn", np.abs(xf), np.abs(w))
    return np.einsum("mk,mkn->mn", xf, w), bound


@pytest.mark.parametrize("bits", [2, 3])
def test_moe_gmm_swiglu_matches_oracle(bits):
    e, k, n, bm = 3, 128, 128, 8
    gp, gs, gz = _packed_bucket(e, k, n, bits, 128, seed=10 + bits)
    up, us, uz = _packed_bucket(e, k, n, bits, 128, seed=20 + bits)
    rng = np.random.default_rng(30 + bits)
    m = 4 * bm
    x = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    be = jnp.asarray([0, 1, 1, 2], jnp.int32)
    na = jnp.asarray([3], jnp.int32)
    y_ref = ref.moe_gmm_swiglu_ref(
        x, gp, up, gs, gz, us, uz, be, na, bits=bits, group=128, bm=bm
    )
    # oracle == composition of the two plain grouped GEMMs
    comp = jax.nn.silu(
        ref.moe_gmm_ref(x, gp, gs, gz, be, na, bits=bits, group=128, bm=bm)
    ) * ref.moe_gmm_ref(x, up, us, uz, be, na, bits=bits, group=128, bm=bm)
    np.testing.assert_allclose(
        np.asarray(y_ref), np.asarray(comp), rtol=2e-5, atol=2e-5
    )
    y = ops.moe_gmm_swiglu(
        x, gp, up, gs, gz, us, uz, be, na,
        bits=bits, group=128, backend="interpret", bm=bm,
    )
    # each projection, by the plain kernel, is the oracle's at 2e-5
    g64, eg = _f64_projection(x, gp, gs, gz, be, bits, bm)
    u64, eu = _f64_projection(x, up, us, uz, be, bits, bm)
    kw = dict(bits=bits, group=128, bm=bm)
    for w, s_, z_, p64, e_p in ((gp, gs, gz, g64, eg), (up, us, uz, u64, eu)):
        p32 = np.asarray(ref.moe_gmm_ref(x, w, s_, z_, be, na, **kw))
        np.testing.assert_allclose(
            np.asarray(ops.moe_gmm(x, w, s_, z_, be, na, backend="interpret",
                                   **kw)),
            p32, rtol=2e-5, atol=2e-5,
        )
        np.testing.assert_array_less(np.abs(p32 - p64)[: 3 * bm],
                                     e_p[: 3 * bm])
    # y against the oracle, within what f32 rounding can move each. The
    # kernel scales each group's MXU product and the oracle each weight,
    # so they round differently, and silu(g)·u carries a rounding in u
    # times |g| (up to ~50 here): against float64 maths the oracle itself
    # is off by up to 4x the 2e-5 of its composition check above. So each
    # projection's rounding, sqrt(k)·u·(|x| @ |W|) against a float64
    # evaluation of the dequantised weights (checked on the oracle just
    # above), is carried through silu(g)·u for each side, on top of 2e-5.
    silu_g = g64 / (1.0 + np.exp(-g64))
    carry = 1.1 * eg * np.abs(u64) + np.abs(silu_g) * eu + 1.1 * eg * eu
    y_ref = np.asarray(y_ref)
    np.testing.assert_array_less(
        np.abs(np.asarray(y) - y_ref),
        2e-5 + 2e-5 * np.abs(y_ref) + 2.0 * carry,
    )
    # and y against float64 maths, within its own side's rounding alone
    y64 = silu_g * u64
    err = np.abs(np.asarray(y) - y64)[: 3 * bm]
    assert np.all(err <= (carry + 2.0 ** -23 * np.abs(y64))[: 3 * bm])
    np.testing.assert_array_equal(np.asarray(y)[3 * bm :], 0.0)


# ------------------------------------------------- serving greedy unchanged
def test_engine_greedy_outputs_unchanged_by_backend():
    """The default (grouped) engine serves the exact same greedy tokens
    as a scan-backend engine over the same trace — the kernel-path
    swap is invisible to served traffic."""
    from test_offload import TINY_MOE, compress_for_serving, make_requests
    from repro.models.registry import get_model
    from repro.serving import EngineConfig, PagedServingEngine, Request

    bundle = get_model(TINY_MOE)
    params = bundle.init(jax.random.PRNGKey(0))
    params_c = compress_for_serving(TINY_MOE, params)
    ecfg = EngineConfig(
        max_slots=2, block_size=4, num_blocks=16, max_blocks_per_slot=6,
        prefill_chunk=4,
    )
    outs = {}
    for backend in (None, "scan"):
        engine = PagedServingEngine(
            TINY_MOE, params_c,
            dataclasses.replace(ecfg, ffn_backend=backend),
        )
        reqs = make_requests(TINY_MOE, 3, seed=11, max_new=4)
        outs[backend] = engine.serve(reqs)
        if backend is None:
            # PMQ engines must report the capacity-padding gauge
            util = engine.metrics.capacity_utilization
            assert util and all(0.0 < u <= 1.0 for u in util)
    assert outs[None] == outs["scan"]
