"""Serving subsystem tests: paged-KV allocator invariants, paged-vs-dense
decode equivalence, and continuous-batching scheduler behavior."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.kernels import ref
from repro.kernels.paged_attention import paged_attention_pallas
from repro.models import transformer as tf
from repro.models.registry import get_model
from repro.serving import (
    BlockAllocator,
    EngineConfig,
    PagedKVCache,
    PagedServingEngine,
    PoolExhausted,
    PrefixCache,
    Request,
)
from repro.serving.engine import dense_greedy_reference

from _hypothesis_compat import HAS_HYPOTHESIS, given, settings, st

TINY_MOE = ModelConfig(
    name="tiny-serving-moe",
    family="moe",
    num_layers=2,
    d_model=64,
    num_heads=4,
    num_kv_heads=2,
    head_dim=16,
    d_ff=128,
    d_ff_expert=64,
    vocab_size=128,
    num_experts=4,
    top_k=2,
    num_shared_experts=1,
    dtype="float32",
    remat="none",
    logits_chunk=32,
    attn_q_chunk=16,
    attn_kv_chunk=16,
)

# decode_horizon=1 pins the historical per-token program — the baseline
# whose pressure dynamics (growth/preemption counts, admission steps)
# these tests assert exactly; fused-horizon behavior is covered by the
# dedicated horizon tests below and the randomized harness
ECFG = EngineConfig(
    max_slots=2, block_size=4, num_blocks=16, max_blocks_per_slot=6,
    prefill_chunk=4, decode_horizon=1,
)


@pytest.fixture(scope="module")
def model():
    bundle = get_model(TINY_MOE)
    params = bundle.init(jax.random.PRNGKey(0))
    return TINY_MOE, params


# ------------------------------------------------------- block allocator
def test_allocator_alloc_free_roundtrip():
    a = BlockAllocator(8)
    blocks = a.alloc(5)
    assert len(set(blocks)) == 5 and a.num_free == 3
    a.free(blocks)
    assert a.num_free == 8


def test_allocator_alloc_zero_is_stateless():
    a = BlockAllocator(4)
    held = a.alloc(2)
    assert a.alloc(0) == []
    assert a.num_free == 2
    assert a.allocated == frozenset(held)


def test_allocator_exhaustion_raises_and_leaves_state():
    a = BlockAllocator(4)
    a.alloc(3)
    with pytest.raises(PoolExhausted):
        a.alloc(2)
    assert a.num_free == 1  # failed alloc took nothing


def test_allocator_double_free_raises():
    a = BlockAllocator(4)
    blocks = a.alloc(2)
    a.free(blocks)
    with pytest.raises(ValueError):
        a.free([blocks[0]])
    with pytest.raises(ValueError):
        a.free([99])  # never allocated


def test_allocator_free_is_atomic():
    """A free list containing one bad block must leave the allocator
    untouched (documented invariant) — not half-free the good prefix."""
    a = BlockAllocator(8)
    good = a.alloc(3)
    free_before = a.num_free
    with pytest.raises(ValueError):
        a.free([good[0], good[1], 99])  # 99 was never allocated
    assert a.num_free == free_before
    assert a.allocated == frozenset(good)  # nothing partially freed
    with pytest.raises(ValueError):
        a.free([good[0], good[0]])  # duplicate within one call
    assert a.num_free == free_before
    assert a.allocated == frozenset(good)
    a.free(good)  # the valid list still frees in full
    assert a.num_free == 8 and a.allocated == frozenset()


def test_allocator_recycles_blocks():
    a = BlockAllocator(4)
    first = a.alloc(4)
    a.free(first)
    second = a.alloc(4)
    assert sorted(second) == sorted(first)  # same physical pages reused


def test_kvcache_slot_lifecycle():
    cache = PagedKVCache.create(
        TINY_MOE, num_blocks=8, block_size=4, max_slots=2,
        max_blocks_per_slot=4,
    )
    slot = cache.acquire_slot(10)  # 3 blocks
    assert cache.allocator.num_free == 5
    assert (cache.block_tables[slot, :3] >= 0).all()
    with pytest.raises(PoolExhausted):
        cache.acquire_slot(17)  # 5 blocks > max_blocks_per_slot
    cache.release_slot(slot)
    assert cache.allocator.num_free == 8
    assert slot in cache.free_slots


# ----------------------------------------------- COW refcount invariants
def test_allocator_incref_shares_and_defers_free():
    a = BlockAllocator(4)
    blocks = a.alloc(2)
    a.incref(blocks)  # a second holder (e.g. a prefix-cache entry)
    assert all(a.refcount(b) == 2 for b in blocks)
    a.free(blocks)  # first holder releases: pages stay allocated
    assert a.num_free == 2 and a.allocated == frozenset(blocks)
    a.free(blocks)  # last holder releases: pages recycle
    assert a.num_free == 4 and a.allocated == frozenset()
    with pytest.raises(ValueError):
        a.free([blocks[0]])  # refcount-0 page: double free
    with pytest.raises(ValueError):
        a.incref([blocks[0]])  # cannot share a free page


def test_allocator_incref_is_atomic():
    a = BlockAllocator(4)
    good = a.alloc(2)
    with pytest.raises(ValueError):
        a.incref([good[0], 99])  # one bad page: nothing increments
    assert all(a.refcount(b) == 1 for b in good)


if HAS_HYPOTHESIS:
    def _op_seqs():
        return st.lists(
            st.tuples(
                st.sampled_from(["alloc", "incref", "free"]),
                st.integers(0, 2**16),
            ),
            max_size=40,
        )
else:  # decoration-time stand-in; the test collects as skipped
    def _op_seqs():
        return None


@given(ops=_op_seqs())
@settings()
def test_property_cow_refcounts_never_corrupt(ops):
    """Hypothesis: under ANY interleaving of alloc / incref / free the
    allocator never double-frees, never frees a page whose refcount is
    still positive, mirrors an exact shadow refcount map, and conserves
    ``free + allocated == num_blocks`` — then drains back to fully
    free."""
    a = BlockAllocator(12)
    shadow: dict = {}
    for kind, seed in ops:
        rng = np.random.default_rng(seed)
        live = sorted(shadow)
        if kind == "alloc":
            n = int(rng.integers(0, 5))
            if n > a.num_free:
                with pytest.raises(PoolExhausted):
                    a.alloc(n)
            else:
                got = a.alloc(n)
                assert len(set(got)) == n
                assert not set(got) & set(shadow), "handed out a live page"
                for b in got:
                    shadow[b] = 1
        elif kind == "incref" and live:
            picks = [b for b in live if rng.integers(0, 2)]
            a.incref(picks)
            for b in picks:
                shadow[b] += 1
        elif kind == "free" and live:
            picks = [b for b in live if rng.integers(0, 2)]
            a.free(picks)
            for b in picks:
                shadow[b] -= 1
                if shadow[b] == 0:
                    del shadow[b]
        assert a.num_free + len(a.allocated) == a.num_blocks
        assert a.allocated == frozenset(shadow)
        assert len(set(a.free_pages)) == len(a.free_pages)
        assert not set(a.free_pages) & set(shadow), "page free AND held"
        for b, rc in shadow.items():
            assert a.refcount(b) == rc
    while shadow:  # drain every remaining hold, one per page per call
        live = sorted(shadow)
        a.free(live)
        for b in live:
            shadow[b] -= 1
            if shadow[b] == 0:
                del shadow[b]
    assert a.num_free == a.num_blocks


# ----------------------------------------------------- prefix page cache
def test_prefix_cache_register_lookup_roundtrip():
    a = BlockAllocator(16)
    pc = PrefixCache(a, 4)
    prompt = np.arange(10, dtype=np.int32)  # 2 full pages + 2-token tail
    blocks = a.alloc(3)
    assert pc.register(prompt, blocks, last_logits=np.ones(7)) == 3
    ent = pc.lookup(prompt)  # full-prompt entry carries the logits
    assert ent.n_tokens == 10 and ent.last_logits is not None
    longer = np.concatenate([prompt[:8], np.asarray([99, 98], np.int32)])
    ent2 = pc.lookup(longer)  # diverging suffix → longest boundary entry
    assert ent2.n_tokens == 8 and ent2.last_logits is None
    assert pc.lookup(np.asarray([50] * 6, np.int32)) is None
    # page 0 is held by the slot + all three entries; re-registration
    # must not leak holds
    assert a.refcount(blocks[0]) == 4
    assert pc.register(prompt, blocks) == 0
    assert a.refcount(blocks[0]) == 4
    pc.check_consistency()


def test_prefix_cache_lru_eviction_and_protect():
    a = BlockAllocator(6)
    pc = PrefixCache(a, 4)
    b1 = a.alloc(1)
    pc.register(np.arange(4, dtype=np.int32), b1, last_logits=np.zeros(2))
    b2 = a.alloc(1)
    pc.register(np.arange(10, 14, dtype=np.int32), b2,
                last_logits=np.zeros(2))
    a.free(b1)
    a.free(b2)  # slots done: pages are cache-held only
    assert a.num_free == 4
    pc.lookup(np.arange(4, dtype=np.int32))  # refresh entry 1 → 2 is LRU
    pc.evict_for(5)
    assert a.num_free == 5
    assert pc.lookup(np.arange(10, 14, dtype=np.int32)) is None
    assert pc.lookup(np.arange(4, dtype=np.int32)) is not None
    # the survivor's pages are protected: eviction must leave it alone
    pc.evict_for(6, protect=frozenset(b1))
    assert a.num_free == 5 and pc.n_entries == 1  # boundary == full entry
    pc.check_consistency()


def test_prefix_cache_reclaimable_is_exact():
    a = BlockAllocator(8)
    pc = PrefixCache(a, 4)
    b = a.alloc(2)
    pc.register(np.arange(8, dtype=np.int32), b, last_logits=np.zeros(2))
    # the owning slot is still live: eviction would drop holds but free
    # no page — reclaimable must say 0, not 2
    assert pc.reclaimable() == 0
    a.free(b)
    assert pc.reclaimable() == 2
    # protecting the entry's pages removes them from the count entirely
    assert pc.reclaimable(frozenset({b[0]})) == 0


def test_acquire_slot_shared_prefix_cow():
    cache = PagedKVCache.create(
        TINY_MOE, num_blocks=8, block_size=4, max_slots=2,
        max_blocks_per_slot=8, prefix_cache=True,
    )
    prompt = np.arange(6, dtype=np.int32)  # 1 full page + 2-token tail
    slot = cache.acquire_slot(6)
    blocks0 = list(cache.slot_blocks[slot])
    cache.register_prefix(prompt, slot, last_logits=np.zeros(3))
    ent = cache.prefix_lookup(prompt)
    assert ent is not None and ent.n_tokens == 6
    slot2 = cache.acquire_slot(8, prefix_entry=ent, rid=7)
    blocks2 = cache.slot_blocks[slot2]
    assert blocks2[0] == blocks0[0], "aligned page must be shared"
    assert blocks2[1] != blocks0[1], "tail page must be a private copy"
    # page 0: slot1 + slot2 + two cache entries (boundary at 4, full at 6)
    assert cache.allocator.refcount(blocks0[0]) == 4
    cache.check_consistency()
    cache.release_slot(slot)
    cache.release_slot(slot2)
    cache.check_consistency()  # cache holds keep the pages alive
    assert cache.allocator.num_free < 8
    cache.clear_prefix_cache()
    assert cache.allocator.num_free == 8


def test_kvcache_kv_bits_validation_and_quant_swap_guard():
    with pytest.raises(ValueError):
        PagedKVCache.create(
            TINY_MOE, num_blocks=4, block_size=4, max_slots=1,
            max_blocks_per_slot=4, kv_bits=4,
        )
    cache = PagedKVCache.create(
        TINY_MOE, num_blocks=4, block_size=4, max_slots=2,
        max_blocks_per_slot=2, kv_bits=8,
    )
    assert cache.k.dtype == jnp.uint8
    assert set(cache.quant) == {"k_scale", "k_zero", "v_scale", "v_zero"}
    slot = cache.acquire_slot(4)
    sw = cache.swap_out(slot, 4)
    assert sw.quant is not None  # scales travel with the codes
    slot2 = cache.acquire_slot(4)
    with pytest.raises(ValueError):
        cache.swap_in(slot2, dataclasses.replace(sw, quant=None))
    cache.swap_in(slot2, sw)  # the genuine payload restores fine
    cache.release_slot(slot2)


# ------------------------------------------------- paged attention kernel
@pytest.mark.parametrize("window", [None, 7])
def test_paged_attention_pallas_matches_ref(window):
    rng = np.random.default_rng(0)
    b, hkv, g, dh, nb, bs, mb = 3, 2, 2, 32, 16, 8, 4
    q = jnp.asarray(rng.normal(size=(b, hkv, g, dh)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(nb, bs, hkv, dh)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(nb, bs, hkv, dh)), jnp.float32)
    bt = jnp.asarray(rng.permutation(nb)[: b * mb].reshape(b, mb), jnp.int32)
    lengths = jnp.asarray([5, 17, 32], jnp.int32)
    o_ref = ref.paged_attention_ref(q, kp, vp, bt, lengths, window=window)
    win = jnp.asarray([window if window else mb * bs + 1], jnp.int32)
    o_pal = paged_attention_pallas(q, kp, vp, bt, lengths, win, interpret=True)
    np.testing.assert_allclose(
        np.asarray(o_ref), np.asarray(o_pal), rtol=1e-5, atol=1e-5
    )


# ------------------------------------------------ paged == dense decoding
def test_paged_matches_dense_logits(model):
    """Chunked paged prefill + paged decode reproduce the dense path's
    logits step for step (the engine runs at drop-free expert capacity,
    so the reference does too)."""
    cfg, params = model
    eng = PagedServingEngine(cfg, params, ECFG)
    mcfg = eng.model_cfg
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, cfg.vocab_size, size=9).astype(np.int32)
    max_new = 4
    ref_toks, ref_logits = dense_greedy_reference(mcfg, params, prompt, max_new)

    # drive the jitted steps directly to observe per-step logits
    cache = eng.cache
    slot = cache.acquire_slot(len(prompt) + max_new)
    table_row = jnp.asarray(cache.block_tables[slot : slot + 1])
    c = ECFG.prefill_chunk
    for off in range(0, len(prompt), c):
        n = min(c, len(prompt) - off)
        chunk = np.zeros((1, c), np.int32)
        chunk[0, :n] = prompt[off : off + n]
        cache.k, cache.v, _, logits, _ = eng._prefill(
            params, cache.k, cache.v, cache.quant, jnp.asarray(chunk),
            jnp.int32(off), jnp.int32(n), table_row,
        )
    np.testing.assert_allclose(
        np.asarray(logits)[0, -1], ref_logits[0], rtol=1e-4, atol=1e-4
    )
    toks = [int(np.argmax(np.asarray(logits)[0, -1]))]
    pos = len(prompt)
    b = ECFG.max_slots

    @jax.jit
    def decode_fn(k, v, token, positions, active):
        pc = {"k": k, "v": v, "block_tables": cache.tables_device(),
              "active": active}
        nc, logits, _ = tf.paged_decode_step(params, pc, token, positions, mcfg)
        return nc["k"], nc["v"], logits

    for step in range(max_new - 1):
        token = np.zeros((b, 1), np.int32)
        token[slot] = toks[-1]
        positions = np.zeros((b,), np.int32)
        positions[slot] = pos
        active = np.zeros((b,), bool)
        active[slot] = True
        cache.k, cache.v, logits = decode_fn(
            cache.k, cache.v, jnp.asarray(token),
            jnp.asarray(positions), jnp.asarray(active),
        )
        np.testing.assert_allclose(
            np.asarray(logits)[slot, -1], ref_logits[step + 1],
            rtol=1e-4, atol=1e-4,
        )
        toks.append(int(np.argmax(np.asarray(logits)[slot, -1])))
        pos += 1
    assert toks == ref_toks
    cache.release_slot(slot)


def test_engine_serve_matches_dense_greedy_reference(model):
    cfg, params = model
    eng = PagedServingEngine(cfg, params, ECFG)
    rng = np.random.default_rng(2)
    prompt = rng.integers(0, cfg.vocab_size, size=7).astype(np.int32)
    ref_toks, _ = dense_greedy_reference(eng.model_cfg, params, prompt, 5)
    out = eng.serve([Request(rid=0, prompt=prompt, max_new=5)])
    assert out[0] == ref_toks


@pytest.mark.parametrize("horizon", [1, 4])
def test_engine_keep_logits_matches_dense_reference(model, horizon):
    """``keep_logits`` records the logits behind every served token — the
    prefill's first, then each decode step's — equal to the dense
    reference's, and leaves the tokens unchanged."""
    cfg, params = model
    eng = PagedServingEngine(cfg, params, dataclasses.replace(
        ECFG, decode_horizon=horizon, keep_logits=True))
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
               for n in (7, 5)]
    out = eng.serve([Request(rid=i, prompt=p, max_new=6)
                     for i, p in enumerate(prompts)])
    for rid, prompt in enumerate(prompts):
        ref_toks, ref_logits = dense_greedy_reference(
            eng.model_cfg, params, prompt, 6
        )
        assert out[rid] == ref_toks
        kept = np.stack(eng.token_logits[rid])
        assert kept.shape == (6, cfg.vocab_size)
        np.testing.assert_allclose(kept, np.stack(ref_logits[:6]),
                                   rtol=1e-4, atol=1e-4)


# -------------------------------------------------- continuous batching
def test_scheduler_mid_flight_admission(model):
    """With 2 slots and 3 requests, the third must join once a short
    request finishes — no wave barrier, pages recycled."""
    cfg, params = model
    eng = PagedServingEngine(cfg, params, ECFG)
    rng = np.random.default_rng(3)
    reqs = [
        Request(rid=0, prompt=rng.integers(0, cfg.vocab_size, size=6).astype(np.int32), max_new=2),
        Request(rid=1, prompt=rng.integers(0, cfg.vocab_size, size=6).astype(np.int32), max_new=8),
        Request(rid=2, prompt=rng.integers(0, cfg.vocab_size, size=6).astype(np.int32), max_new=3),
    ]
    out = eng.serve(reqs)
    for r in reqs:
        assert len(out[r.rid]) == r.max_new  # independent completion
    m = eng.metrics.summary()
    assert m["mid_flight_admissions"] >= 1
    assert m["slot_releases"] == 3
    # request 2 was admitted strictly after decoding started
    admit_steps = {a["rid"]: a["step"] for a in eng.metrics.admissions}
    assert admit_steps[2] > 0
    # all pages returned to the pool
    assert eng.cache.allocator.num_free == ECFG.num_blocks
    assert len(eng.cache.free_slots) == ECFG.max_slots


def test_admission_depth_counts_admitted_request(model):
    """record_admission logs the queue depth the admission decision saw —
    including the request being admitted (regression: the engine used to
    read queue_depth after try_admit popped the head, off by one)."""
    cfg, params = model
    eng = PagedServingEngine(cfg, params, ECFG)  # 2 slots
    rng = np.random.default_rng(11)
    reqs = [
        Request(rid=i,
                prompt=rng.integers(0, cfg.vocab_size, size=5).astype(np.int32),
                max_new=3)
        for i in range(3)
    ]
    eng.serve(reqs)
    depths = [a["queue_depth"] for a in eng.metrics.admissions]
    # step 0: three waiting, two slots -> depths 3 then 2; the third is
    # admitted alone once a slot frees -> depth 1 (itself)
    assert depths == [3, 2, 1]
    assert all(d >= 1 for d in depths)  # an admitted request counts itself


def test_expert_activation_ignores_inactive_slots(model):
    """Regression for OTP activation dilution: the per-step activation
    metric must be computed over *active* slots only — the garbage token
    an empty slot decodes must not move it (paged_decode_step used to
    average the mask over all slots)."""
    cfg, _ = model
    from test_offload import compress_for_serving

    from repro.core.otp import init_otp_router

    bundle = get_model(cfg)
    params_c = compress_for_serving(cfg, bundle.init(jax.random.PRNGKey(0)))
    otps = [
        init_otp_router(jax.random.PRNGKey(100 + l), cfg.d_model, cfg.top_k)
        for l in range(cfg.num_layers)
    ]
    params_c["blocks"]["otp"] = jax.tree.map(lambda *xs: jnp.stack(xs), *otps)
    cache = PagedKVCache.create(
        cfg, num_blocks=8, block_size=4, max_slots=2, max_blocks_per_slot=2
    )
    cache.acquire_slot(2)
    cache.acquire_slot(2)
    tables = jnp.asarray(cache.block_tables)
    positions = jnp.zeros((2,), jnp.int32)

    @jax.jit
    def act_of(tokens, active):
        pc = {"k": cache.k, "v": cache.v, "block_tables": tables,
              "active": active}
        _, _, info = tf.paged_decode_step(params_c, pc, tokens, positions, cfg)
        return info["expert_activation"]

    masked, unmasked = [], []
    for garbage in range(10):
        tokens = jnp.asarray([[7], [garbage]], jnp.int32)
        masked.append(float(act_of(tokens, jnp.asarray([True, False]))))
        unmasked.append(float(act_of(tokens, jnp.asarray([True, True]))))
    # sanity: slot 1's token genuinely moves the metric when it counts
    assert len({round(u, 6) for u in unmasked}) > 1
    # regression: with slot 1 inactive its token must not move the metric
    assert len({round(m, 6) for m in masked}) == 1


def test_model_api_paged_dispatch(model):
    """The bundle-level API accepts the paged cache layout: decode_step
    dispatches on ``"block_tables" in cache`` and prefill on ``paged=``,
    both matching the direct paged functions."""
    cfg, params = model
    rng = np.random.default_rng(4)
    prompt = rng.integers(0, cfg.vocab_size, size=5).astype(np.int32)
    cache = PagedKVCache.create(
        cfg, num_blocks=8, block_size=4, max_slots=2, max_blocks_per_slot=4
    )
    slot = cache.acquire_slot(len(prompt) + 2)
    table_row = jnp.asarray(cache.block_tables[slot : slot + 1])
    pc = {"k": cache.k, "v": cache.v, "block_tables": table_row}
    # prefill via the dispatch kwarg == direct paged_prefill_chunk
    pc1, logits1 = tf.prefill(
        params, {"tokens": jnp.asarray(prompt[None])}, cfg,
        paged={"cache": pc},
    )
    pc2, logits2, _ = tf.paged_prefill_chunk(
        params, pc, jnp.asarray(prompt[None]), jnp.int32(0),
        jnp.int32(len(prompt)), cfg,
    )
    np.testing.assert_allclose(np.asarray(logits1), np.asarray(logits2))
    # decode via decode_step dispatch == direct paged_decode_step
    tables = jnp.zeros((2, 4), jnp.int32).at[0].set(table_row[0])
    dcache = {
        "k": pc1["k"], "v": pc1["v"], "block_tables": tables,
        "active": jnp.asarray([True, False]),
    }
    token = jnp.asarray([[int(np.argmax(np.asarray(logits1)[0, -1]))], [0]],
                        jnp.int32)
    positions = jnp.asarray([len(prompt), 0], jnp.int32)
    out1, lg1 = tf.decode_step(params, dcache, token, positions, cfg)
    out2, lg2, info = tf.paged_decode_step(params, dcache, token, positions, cfg)
    np.testing.assert_allclose(np.asarray(lg1), np.asarray(lg2))
    assert float(info["expert_activation"]) == 1.0  # no OTP params here
    assert "block_tables" in out1


def test_empty_prompt_rejected(model):
    cfg, params = model
    eng = PagedServingEngine(cfg, params, ECFG)
    with pytest.raises(ValueError):
        eng.submit(Request(rid=0, prompt=np.zeros(0, np.int32), max_new=4))
    with pytest.raises(ValueError):
        eng.submit(Request(rid=1, prompt=np.zeros(4, np.int32), max_new=0))


def test_oversized_request_rejected(model):
    cfg, params = model
    eng = PagedServingEngine(cfg, params, ECFG)
    prompt = np.zeros(ECFG.max_blocks_per_slot * ECFG.block_size, np.int32)
    with pytest.raises(ValueError):
        eng.submit(Request(rid=0, prompt=prompt, max_new=4))


def test_pool_too_small_raises(model):
    cfg, params = model
    ecfg = dataclasses.replace(ECFG, num_blocks=2, max_blocks_per_slot=6)
    eng = PagedServingEngine(cfg, params, ecfg)
    prompt = np.zeros(12, np.int32)  # needs 4 blocks, pool has 2
    with pytest.raises(PoolExhausted):
        eng.serve([Request(rid=0, prompt=prompt, max_new=4)])


# ------------------------------------------- dynamic growth + preemption
def test_kvcache_grow_extends_table():
    cache = PagedKVCache.create(
        TINY_MOE, num_blocks=8, block_size=4, max_slots=2,
        max_blocks_per_slot=4,
    )
    slot = cache.acquire_slot(5)  # 2 blocks
    assert cache.grow(slot, 0) == []
    new = cache.grow(slot, 1)
    assert len(new) == 1 and cache.allocator.num_free == 5
    assert list(cache.block_tables[slot, :3]) == cache.slot_blocks[slot]
    with pytest.raises(PoolExhausted):
        cache.grow(slot, 2)  # 3 + 2 > max_blocks_per_slot
    assert cache.allocator.num_free == 5  # failed grow took nothing
    cache.check_consistency()
    cache.release_slot(slot)


def test_swap_roundtrip_preserves_kv_bits():
    """swap_out → pages recycled by another tenant → swap_in restores the
    preempted slot's KV bit-for-bit into fresh pages."""
    rng = np.random.default_rng(0)
    cache = PagedKVCache.create(
        TINY_MOE, num_blocks=6, block_size=4, max_slots=2,
        max_blocks_per_slot=3,
    )
    slot = cache.acquire_slot(10)  # 3 blocks
    blocks = list(cache.slot_blocks[slot])
    fill = rng.normal(size=(TINY_MOE.num_layers, 3, 4, 2, 16)).astype(np.float32)
    cache.k = cache.k.at[:, np.asarray(blocks)].set(jnp.asarray(fill))
    cache.v = cache.v.at[:, np.asarray(blocks)].set(jnp.asarray(2 * fill))
    swapped = cache.swap_out(slot, 10)
    assert swapped.n_pages == 3 and swapped.n_tokens == 10
    assert cache.allocator.num_free == 6  # device pages freed immediately
    # another tenant scribbles over the recycled pages
    other = cache.acquire_slot(12)
    cache.k = cache.k.at[:, np.asarray(cache.slot_blocks[other])].set(-1.0)
    cache.release_slot(other)
    slot2 = cache.acquire_slot(10)
    nbytes = cache.swap_in(slot2, swapped)
    assert nbytes == swapped.nbytes
    got = np.asarray(cache.k[:, np.asarray(cache.slot_blocks[slot2])])
    np.testing.assert_array_equal(got, fill)
    got_v = np.asarray(cache.v[:, np.asarray(cache.slot_blocks[slot2])])
    np.testing.assert_array_equal(got_v, 2 * fill)


def test_grow_on_exhaustion_preempts_instead_of_raising(model):
    """A pool far below Σ(prompt+max_new) — which PR-1 admission would
    have rejected mid-run — now finishes every request by preempting on
    page exhaustion instead of raising PoolExhausted."""
    cfg, params = model
    reqs = [
        Request(rid=i, prompt=np.full(3, 5 + i, np.int32), max_new=12)
        for i in range(3)
    ]
    demand = sum(-(-(3 + 12) // ECFG.block_size) for _ in reqs)  # 12 blocks
    ecfg = dataclasses.replace(
        ECFG, max_slots=3, num_blocks=demand // 2, max_blocks_per_slot=4,
        preempt_mode="recompute",
    )
    eng = PagedServingEngine(cfg, params, ecfg)
    out = eng.serve(reqs)
    m = eng.metrics.summary()
    assert m["preemptions"] >= 1
    assert all(len(out[r.rid]) == r.max_new for r in reqs)
    assert eng.cache.allocator.num_free == ecfg.num_blocks


@pytest.mark.parametrize("preempt_mode", ["swap", "recompute"])
def test_preempted_resume_matches_never_preempted(model, preempt_mode):
    """A preempted-then-resumed request re-reads KV identical to a run
    that was never preempted: greedy tokens agree request by request."""
    cfg, params = model
    def mk():
        return [
            Request(rid=i, prompt=np.asarray([7 + i, 3, 11 + i], np.int32),
                    max_new=10)
            for i in range(3)
        ]
    roomy = PagedServingEngine(
        cfg, params,
        dataclasses.replace(ECFG, max_slots=3, num_blocks=16,
                            max_blocks_per_slot=4),
    )
    baseline = roomy.serve(mk())
    assert roomy.metrics.summary()["preemptions"] == 0
    tight = PagedServingEngine(
        cfg, params,
        dataclasses.replace(ECFG, max_slots=3, num_blocks=6,
                            max_blocks_per_slot=4, preempt_mode=preempt_mode),
    )
    pressured = tight.serve(mk())
    assert tight.metrics.summary()["preemptions"] >= 1
    assert pressured == baseline


def test_reserve_full_never_preempts(model):
    """The PR-1 baseline policy: full up-front reservation serializes
    under a tight pool but never grows, swaps, or preempts."""
    cfg, params = model
    reqs = [
        Request(rid=i, prompt=np.full(4, 2 + i, np.int32), max_new=8)
        for i in range(3)
    ]
    ecfg = dataclasses.replace(
        ECFG, max_slots=3, num_blocks=6, max_blocks_per_slot=4,
        reserve_full=True,
    )
    eng = PagedServingEngine(cfg, params, ecfg)
    out = eng.serve(reqs)
    m = eng.metrics.summary()
    assert m["preemptions"] == 0 and m["swap_bytes"] == 0
    assert all(len(out[r.rid]) == r.max_new for r in reqs)


# ------------------------------------------------- fused decode horizon
def _prefilled_slot(mcfg, params, prompt, max_new):
    """Fresh paged cache with one prefilled slot; returns
    (cache, slot, first_token)."""
    cache = PagedKVCache.create(
        mcfg, num_blocks=16, block_size=4, max_slots=2, max_blocks_per_slot=6
    )
    slot = cache.acquire_slot(len(prompt) + max_new)
    row = jnp.asarray(cache.block_tables[slot : slot + 1])
    pc = {"k": cache.k, "v": cache.v, "block_tables": row}
    pc, logits, _ = tf.paged_prefill_chunk(
        params, pc, jnp.asarray(prompt[None]), jnp.int32(0),
        jnp.int32(len(prompt)), mcfg,
    )
    cache.k, cache.v = pc["k"], pc["v"]
    return cache, slot, int(np.argmax(np.asarray(logits)[0, -1]))


@pytest.mark.parametrize("horizon", [2, 4, 6])
def test_horizon_program_matches_manual_steps(model, horizon):
    """paged_decode_horizon emits exactly the tokens of ``budget`` manual
    paged_decode_step calls with host-side argmax — including a horizon
    larger than the remaining budget (trailing scan steps emit nothing)
    — and leaves a bit-identical KV pool behind."""
    cfg, params = model
    mcfg = dataclasses.replace(
        cfg, moe_capacity_factor=float(cfg.num_experts)
    )
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, cfg.vocab_size, size=5).astype(np.int32)
    budget = 4
    cache, slot, tok0 = _prefilled_slot(mcfg, params, prompt, budget + 1)
    tables = cache.tables_device()
    b = 2
    # ---- manual single-step loop (the H = 1 reference semantics)
    k, v = cache.k, cache.v
    toks_ref, cur, pos = [], tok0, len(prompt)
    for _ in range(budget):
        token = np.zeros((b, 1), np.int32)
        token[slot] = cur
        positions = np.zeros((b,), np.int32)
        positions[slot] = pos
        active = np.zeros((b,), bool)
        active[slot] = True
        pc = {"k": k, "v": v, "block_tables": tables,
              "active": jnp.asarray(active)}
        pc, logits, _ = tf.paged_decode_step(
            params, pc, jnp.asarray(token), jnp.asarray(positions), mcfg
        )
        k, v = pc["k"], pc["v"]
        cur = int(np.argmax(np.asarray(logits)[slot, -1]))
        toks_ref.append(cur)
        pos += 1
    # ---- one fused horizon program from the same starting state
    token = np.zeros((b, 1), np.int32)
    token[slot] = tok0
    positions = np.zeros((b,), np.int32)
    positions[slot] = len(prompt)
    active = np.zeros((b,), bool)
    active[slot] = True
    budgets = np.zeros((b,), np.int32)
    budgets[slot] = budget
    hc = {"k": cache.k, "v": cache.v, "block_tables": tables,
          "active": jnp.asarray(active)}
    hc, toks, emits, info = tf.paged_decode_horizon(
        params, hc, jnp.asarray(token), jnp.asarray(positions), mcfg,
        horizon=horizon, budgets=jnp.asarray(budgets),
        eos_ids=jnp.full((b,), -1, np.int32),
    )
    toks, emits = np.asarray(toks), np.asarray(emits)
    n_emit = min(horizon, budget)
    assert list(emits[:, slot]) == [True] * n_emit + [False] * (horizon - n_emit)
    assert not emits[:, 1 - slot].any()  # inactive slot never emits
    assert list(toks[:n_emit, slot]) == toks_ref[:n_emit]
    assert (toks[n_emit:, slot] == -1).all()
    assert np.asarray(info["slot_counts"]).shape[0] == horizon
    if horizon >= budget:  # same writes happened ⇒ same pool bits
        np.testing.assert_array_equal(np.asarray(hc["k"]), np.asarray(k))
        np.testing.assert_array_equal(np.asarray(hc["v"]), np.asarray(v))


def test_horizon_eos_stops_mid_horizon(model):
    """A slot that emits its per-request EOS mid-horizon keeps the EOS
    token and emits nothing after it."""
    cfg, params = model
    mcfg = dataclasses.replace(
        cfg, moe_capacity_factor=float(cfg.num_experts)
    )
    rng = np.random.default_rng(6)
    prompt = rng.integers(0, cfg.vocab_size, size=5).astype(np.int32)
    ref_toks, _ = dense_greedy_reference(mcfg, params, prompt, 6)
    eos = ref_toks[2]  # greedy emits this at decode step 2 of the horizon
    cache, slot, tok0 = _prefilled_slot(mcfg, params, prompt, 7)
    assert tok0 == ref_toks[0]
    b = 2
    token = np.zeros((b, 1), np.int32)
    token[slot] = tok0
    positions = np.zeros((b,), np.int32)
    positions[slot] = len(prompt)
    active = np.zeros((b,), bool)
    active[slot] = True
    budgets = np.zeros((b,), np.int32)
    budgets[slot] = 5
    eos_ids = np.full((b,), -1, np.int32)
    eos_ids[slot] = eos
    hc = {"k": cache.k, "v": cache.v, "block_tables": cache.tables_device(),
          "active": jnp.asarray(active)}
    _, toks, emits, _ = tf.paged_decode_horizon(
        params, hc, jnp.asarray(token), jnp.asarray(positions), mcfg,
        horizon=5, budgets=jnp.asarray(budgets),
        eos_ids=jnp.asarray(eos_ids),
    )
    toks, emits = np.asarray(toks), np.asarray(emits)
    emitted = [int(t) for t in toks[emits[:, slot], slot]]
    assert emitted == ref_toks[1:3]  # ... up to and including the EOS
    assert emitted[-1] == eos
    assert not emits[2:, slot].any()  # nothing after the stop


def test_engine_eos_request_matches_truncated_reference(model):
    """Engine-level EOS: the request finishes the step it emits its stop
    token, its output is the dense reference truncated at the EOS, and
    its slot frees at the right logical step."""
    cfg, params = model
    eng = PagedServingEngine(
        cfg, params, dataclasses.replace(ECFG, decode_horizon=4)
    )
    rng = np.random.default_rng(8)
    prompt = rng.integers(0, cfg.vocab_size, size=6).astype(np.int32)
    ref_toks, _ = dense_greedy_reference(eng.model_cfg, params, prompt, 8)
    eos = ref_toks[3]
    assert eos not in ref_toks[:3]  # the cut lands where we think it does
    out = eng.serve([Request(rid=0, prompt=prompt, max_new=8, eos_id=eos)])
    assert out[0] == ref_toks[:4]  # truncated at (and including) the EOS
    # released at logical step 2: tokens 1..3 decode at steps 0..2
    assert eng.metrics.slot_releases[0]["step"] == 2
    assert eng.cache.allocator.num_free == ECFG.num_blocks


def test_engine_temperature_sampling_deterministic(model):
    """Sampled runs replay bit-identically under the same seed, and the
    knob leaves greedy untouched at temperature 0."""
    cfg, params = model
    rng = np.random.default_rng(9)
    prompts = [rng.integers(0, cfg.vocab_size, size=5).astype(np.int32)
               for _ in range(3)]

    def serve(temp, seed, horizon=4):
        eng = PagedServingEngine(
            cfg, params,
            dataclasses.replace(ECFG, decode_horizon=horizon,
                                temperature=temp, sample_seed=seed),
        )
        return eng.serve(
            [Request(rid=i, prompt=prompts[i], max_new=6) for i in range(3)]
        )

    a = serve(2.0, seed=0)
    b = serve(2.0, seed=0)
    assert a == b  # explicit per-megastep keys ⇒ deterministic replay
    assert all(
        0 <= t < cfg.vocab_size for toks in a.values() for t in toks
    )
    # the TTFT token is sampled too (per-rid keys): identical prompts
    # under high temperature must not all open with the greedy argmax
    eng = PagedServingEngine(
        cfg, params,
        dataclasses.replace(ECFG, max_slots=2, decode_horizon=2,
                            temperature=5.0, sample_seed=3),
    )
    same = eng.serve([
        Request(rid=i, prompt=prompts[0], max_new=2) for i in range(6)
    ])
    assert len({toks[0] for toks in same.values()}) > 1
    greedy = serve(0.0, seed=0)
    ref = {
        i: dense_greedy_reference(
            PagedServingEngine(cfg, params, ECFG).model_cfg,
            params, prompts[i], 6,
        )[0]
        for i in range(3)
    }
    assert greedy == ref  # temperature 0 is exactly the greedy path


def test_decode_horizon_env_default(monkeypatch):
    """REPRO_DECODE_HORIZON sets the config default; explicit values and
    validation still win."""
    monkeypatch.setenv("REPRO_DECODE_HORIZON", "3")
    assert EngineConfig().decode_horizon == 3
    assert EngineConfig(decode_horizon=2).decode_horizon == 2
    monkeypatch.delenv("REPRO_DECODE_HORIZON")
    assert EngineConfig().decode_horizon == 8
    with pytest.raises(ValueError):
        PagedServingEngine(
            TINY_MOE, {}, dataclasses.replace(ECFG, decode_horizon=0)
        )
    with pytest.raises(ValueError):
        PagedServingEngine(
            TINY_MOE, {}, dataclasses.replace(ECFG, temperature=-1.0)
        )


# ---------------------------------------------------------- metrics unit
def test_metrics_megastep_split_and_dispatch_rates():
    from repro.serving import ServingMetrics

    m = ServingMetrics()
    # two megasteps of 4 logical steps each, 2 active slots throughout;
    # the second needed one offload replay
    for steps, runs, offload_s in ((4, 1, 0.0), (4, 2, 0.03)):
        m.record_megastep(steps, 0.01, offload_s, runs, runs)
        for _ in range(steps):
            m.record_decode_step(0.0025, 2, 1.0, 0, page_utilization=0.5)
    m.record_prefill_runs(1)
    s = m.summary()
    assert s["megasteps"] == 2
    assert s["decode_dispatches"] == 3 and s["decode_replays"] == 1
    assert s["decode_host_syncs"] == 3
    assert s["prefill_dispatches"] == 1 and s["prefill_replays"] == 0
    # compute vs offload split: replays no longer inflate compute time
    assert s["decode_compute_mean_s"] == pytest.approx(0.01)
    assert s["decode_offload_mean_s"] == pytest.approx(0.015)
    assert s["decode_offload_frac"] == pytest.approx(0.03 / 0.05)
    # 3 dispatches over 8 logical steps / 16 batch tokens
    assert s["dispatches_per_step"] == pytest.approx(3 / 8)
    assert s["dispatches_per_token"] == pytest.approx(3 / 16)
    assert s["syncs_per_token"] == pytest.approx(3 / 16)
    c = m.counters()
    assert c["megasteps"] == 2
    assert c["megastep_logical_steps"] == [4, 4]
    assert c["decode_dispatches"] == 3 and c["decode_replays"] == 1
    # the deterministic counters slice holds counts only — never seconds
    assert not any("_s" == k[-2:] for k in c)


def test_metrics_new_counters_and_json_roundtrip():
    import json

    from repro.serving import ServingMetrics

    m = ServingMetrics()
    m.record_admission(0, 0, 0, 0, 0)
    # a resumed re-admission mid-decode is a pressure artifact, not a
    # continuous-batching admission
    m.record_admission(1, 1, 3, 1, 0, resumed=True)
    assert m.mid_flight_admissions == 0
    m.record_ttft(0.5, 0.4)
    m.record_decode_step(0.01, 2, 1.0, 1, page_utilization=0.5)
    m.record_decode_step(0.01, 1, 1.0, 0, page_utilization=1.0)
    m.record_preemption(0, 0, 1, "swap", swap_bytes=1024)
    m.record_swap_in(1024)
    m.record_release(0, 0, 2)
    s = m.summary()
    assert s["preemptions"] == 1
    assert s["swap_out_bytes"] == 1024 and s["swap_in_bytes"] == 1024
    assert s["swap_bytes"] == 2048
    assert s["page_util_mean"] == pytest.approx(0.75)
    assert s["page_util_p95"] == pytest.approx(np.percentile([0.5, 1.0], 95))
    assert json.loads(m.to_json()) == s  # round-trip: every value JSON-safe
    # recompute-mode preemptions move no bytes
    m2 = ServingMetrics()
    m2.record_preemption(1, 1, 0, "recompute", swap_bytes=0)
    assert m2.summary()["swap_bytes"] == 0
    assert m2.counters()["preemptions"][0]["mode"] == "recompute"


def test_metrics_decode_step_without_page_utilization():
    from repro.serving import ServingMetrics

    m = ServingMetrics()
    # callers with no pool attached omit the gauge entirely — no sample
    # recorded, not a fake 0.0 dragging the mean down
    m.record_decode_step(0.01, 2, 1.0, 0)
    m.record_decode_step(0.01, 2, 1.0, 0, page_utilization=None)
    assert m.page_utilization == []
    m.record_decode_step(0.01, 2, 1.0, 0, page_utilization=0.5)
    assert m.page_utilization == [0.5]
    assert m.summary()["page_util_mean"] == pytest.approx(0.5)


def test_metrics_empty_summary_ratios_are_none():
    from repro.serving import ServingMetrics

    s = ServingMetrics().summary()
    # no generated tokens / no megasteps → undefined ratios stay None
    # instead of dividing by zero or reporting a misleading 0.0
    assert s["tokens_per_s"] is None
    assert s["dispatches_per_token"] is None
    assert s["syncs_per_token"] is None
    assert s["dispatches_per_step"] is None
    assert s["requests"] == 0  # plain counts still report zeros


def test_metrics_to_json_include_counters():
    import json

    from repro.serving import ServingMetrics

    m = ServingMetrics()
    m.record_admission(0, 0, 0, 0, 1)
    m.record_decode_step(0.01, 1, 1.0, 0, page_utilization=0.25)
    m.record_release(0, 0, 3)
    # default shape is unchanged: the flat summary dict
    assert json.loads(m.to_json()) == json.loads(json.dumps(m.summary()))
    doc = json.loads(m.to_json(include_counters=True))
    assert set(doc) == {"summary", "counters"}
    assert doc["summary"] == json.loads(json.dumps(m.summary()))
    assert doc["counters"] == json.loads(json.dumps(m.counters()))
    assert doc["counters"]["slot_releases"] == [{"rid": 0, "slot": 0, "step": 3}]
