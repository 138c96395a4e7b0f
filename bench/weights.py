"""The benchmark's own weights, made on the device from a seed.

The benchmark makes every weight of a configuration itself, so that the
plain reference (``bench/reference.py``) can make the same ones again
without taking anything the program produced. Each leaf has its own key,
``fold_in(PRNGKey(seed), crc32(path))``, and each layer of a stacked leaf
``fold_in(leaf_key, layer)``: one leaf or one layer can be made alone and
comes out bit for bit as it does inside the whole tree.

Every matrix that PMQ packs (attention, routed and shared experts) is
``c * sign`` with ``c = 105 * 2**k`` chosen near ``1/sqrt(fan_in)``. A
group-affine code of 1, 2, 3 or 4 bits holds such a matrix exactly: its
group min and max are ``-c`` and ``c``, the scales ``2c/(2**b - 1)`` are
``70, 30, 14 * 2**k`` and the zero points ``1.5, 3.5, 7.5`` are exact in
float32, and 1-bit sign codes with the column's mean ``|w|`` give ``c``
back. So compressing changes no weight, and any gap between the served
tokens and the reference comes from how the program computes. Norm
weights are ``k/64`` (``1 + w`` exact in bf16), the router is float32
``normal / sqrt(d)``, embeddings ``0.02 * normal`` in bf16. Large leaves
are made in pieces (a layer, or 4096 vocabulary rows, at a time) so that
making them needs little memory beyond the leaf itself.

This module imports nothing of the program.
"""
from __future__ import annotations

import functools
import math
import zlib

import jax
import jax.numpy as jnp

__all__ = ["leaf_specs", "make_tree", "make_layer", "make_leaf", "sign_scale"]

# (path, kind) — the program's parameter tree for a transformer
# MoE (``repro.models.transformer.init_lm``); bench/model.py checks that
# the shapes below match ``get_model(cfg).init`` before any run.
_BLOCK_LEAVES = (
    (("ln1",), "norm"),
    (("attn", "wq", "w"), "sign"),
    (("attn", "wk", "w"), "sign"),
    (("attn", "wv", "w"), "sign"),
    (("attn", "wo", "w"), "sign"),
    (("ln2",), "norm"),
    (("moe", "router", "w"), "router"),
    (("moe", "experts", "w_gate"), "sign"),
    (("moe", "experts", "w_up"), "sign"),
    (("moe", "experts", "w_down"), "sign"),
    (("moe", "shared", "w_gate", "w"), "sign"),
    (("moe", "shared", "w_up", "w"), "sign"),
    (("moe", "shared", "w_down", "w"), "sign"),
)


def sign_scale(fan_in: int) -> float:
    """``105 * 2**k`` nearest to ``1/sqrt(fan_in)`` on a log scale."""
    return 105.0 * 2.0 ** round(math.log2(1.0 / (105.0 * math.sqrt(fan_in))))


def _block_shapes(m: dict) -> dict:
    d, hq, hkv, dh = m["d_model"], m["num_heads"], m["num_kv_heads"], m["head_dim"]
    e, f = m["num_experts"], m["d_ff_expert"]
    fs = f * m["num_shared_experts"]
    shapes = {
        ("ln1",): (d,),
        ("attn", "wq", "w"): (d, hq * dh),
        ("attn", "wk", "w"): (d, hkv * dh),
        ("attn", "wv", "w"): (d, hkv * dh),
        ("attn", "wo", "w"): (hq * dh, d),
        ("ln2",): (d,),
        ("moe", "router", "w"): (d, e),
        ("moe", "experts", "w_gate"): (e, d, f),
        ("moe", "experts", "w_up"): (e, d, f),
        ("moe", "experts", "w_down"): (e, f, d),
    }
    if fs:
        shapes[("moe", "shared", "w_gate", "w")] = (d, fs)
        shapes[("moe", "shared", "w_up", "w")] = (d, fs)
        shapes[("moe", "shared", "w_down", "w")] = (fs, d)
    return shapes


def leaf_specs(m: dict):
    """``[(path, kind, shape, layered)]`` for the model fields ``m`` (the
    ``program`` group of a configuration file)."""
    d, v = m["d_model"], m["vocab_size"]
    specs = [(("embed",), "embed", (v, d), False)]
    shapes = _block_shapes(m)
    for path, kind in _BLOCK_LEAVES:
        if path in shapes:
            specs.append((("blocks",) + path, kind, shapes[path], True))
    specs.append((("final_norm",), "norm", (d,), False))
    if not m.get("tie_embeddings", False):
        specs.append((("unembed",), "embed", (v, d), False))
    return specs


def _leaf_key(seed: int, path) -> jax.Array:
    tag = zlib.crc32("/".join(path).encode()) & 0x7FFFFFFF
    return jax.random.fold_in(jax.random.PRNGKey(seed), tag)


def make_leaf(key, kind: str, shape, dtype=jnp.bfloat16) -> jax.Array:
    """One leaf (one layer of a stacked leaf) from its key."""
    if kind == "sign":  # 32 signs from each random word
        *lead, n = shape
        words = jax.random.bits(key, (*lead, -(-n // 32)), jnp.uint32)
        bits = (words[..., None] >> jnp.arange(32, dtype=jnp.uint32)) & 1
        c = sign_scale(shape[-2])
        return jnp.where(bits.reshape(*lead, -1)[..., :n] == 1, c, -c).astype(dtype)
    if kind == "norm":
        return (jax.random.randint(key, shape, -8, 9) / 64.0).astype(dtype)
    if kind == "router":
        return jax.random.normal(key, shape, jnp.float32) / math.sqrt(shape[0])
    if kind == "embed":  # made 4096 rows or fewer at a time: small temporaries
        v = shape[0]
        rows = max(r for r in range(1, min(v, 4096) + 1) if v % r == 0)
        keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(jnp.arange(v // rows))
        out = jax.lax.map(lambda k: (0.02 * jax.random.normal(
            k, (rows, *shape[1:]), jnp.float32)).astype(dtype), keys)
        return out.reshape(shape)
    raise ValueError(f"unknown leaf kind {kind!r}")


def _set(tree: dict, path, value) -> None:
    for p in path[:-1]:
        tree = tree.setdefault(p, {})
    tree[path[-1]] = value


@functools.partial(jax.jit, static_argnums=(1,))
def _make_top(seed, m_items):
    m = dict(m_items)
    dt = jnp.bfloat16 if m["dtype"] == "bfloat16" else jnp.float32
    return {path[0]: make_leaf(_leaf_key(seed, path), kind, shape, dt)
            for path, kind, shape, layered in leaf_specs(m) if not layered}


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5))
def _make_stacked(seed, path, kind, shape, layers, dtype):
    key = _leaf_key(seed, path)
    keys = jax.vmap(lambda l: jax.random.fold_in(key, l))(jnp.arange(layers))
    # one layer at a time, so temporaries stay one layer's size
    return jax.lax.map(lambda k: make_leaf(k, kind, shape, dtype), keys)


def make_tree(m: dict, seed: int, *, top_only: bool = False) -> dict:
    """The parameter tree on the default device, in the configuration's
    ``dtype`` (the router in float32). The leaves outside ``blocks`` come
    from one jitted call; ``top_only`` makes only those. Each stacked
    leaf of ``blocks`` is one more call, so that the temporaries of
    making a multi-GB tree stay those of one leaf."""
    m_items = tuple(sorted(m.items()))
    tree = dict(_make_top(seed, m_items))
    if top_only:
        return tree
    dt = jnp.bfloat16 if m["dtype"] == "bfloat16" else jnp.float32
    for path, kind, shape, layered in leaf_specs(m):
        if layered:
            _set(tree, path, _make_stacked(seed, path, kind, shape,
                                           m["num_layers"], dt))
    return tree


@functools.partial(jax.jit, static_argnums=(1, 3))
def _make_layer(seed, m_items, layer, dtype):
    m = dict(m_items)
    out: dict = {}
    for path, kind, shape, layered in leaf_specs(m):
        if layered:
            key = jax.random.fold_in(_leaf_key(seed, path), layer)
            _set(out, path[1:], make_leaf(key, kind, shape).astype(
                jnp.float32 if kind == "router" else dtype))
    return out


def make_layer(m: dict, seed: int, layer: int, dtype=jnp.float32) -> dict:
    """Layer ``layer`` of ``blocks`` alone, cast to ``dtype`` (the router
    stays float32)."""
    return _make_layer(seed, tuple(sorted(m.items())), layer, dtype)
