"""Sharding rules: logical activation names + path-based param specs.

DP over (pod, data), TP/EP over model, SP for long-context KV
(DESIGN.md §7). Models call :func:`shard` with a logical name; inside a
:func:`sharding_rules` context this becomes ``with_sharding_constraint``
(skipped when a dim doesn't divide — GSPMD then decides), outside it is
identity so smoke tests/CPU runs are unaffected.
"""
from __future__ import annotations

import contextlib
import contextvars
import re
from typing import Dict, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "shard",
    "sharding_rules",
    "batch_axes",
    "activation_rules",
    "param_spec_for_path",
    "make_param_shardings",
    "cache_pspec",
]


_CTX: contextvars.ContextVar = contextvars.ContextVar("sharding_ctx", default=None)


def batch_axes(mesh: Mesh):
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def activation_rules(mesh: Mesh, sequence_parallel: bool = True) -> Dict[str, P]:
    """Megatron-SP style: the residual stream between blocks is sharded
    over (batch → data, seq → model). The per-layer remat stash and the
    TP boundary collectives then scale 1/model (AG+RS instead of AR)."""
    ba = batch_axes(mesh)
    seq = "model" if sequence_parallel else None
    return {
        "act_btd": P(ba, seq, None),  # [B, S, D] residual stream (SP)
        "act_full": P(ba, None, None),  # gathered entry to attn/mlp regions
        "moe_ed": P("model", None),  # [E*cap, D] expert-major rows
        "moe_ecd": P("model", "data", None),  # [E, cap, D]: EP × cap-DP
        "moe_elcd": P("model", None, "data", None),  # [ep, local, cap, D]
        "moe_tke": P(ba, None),  # [T*k, E] routing one-hot/rank buffers
        "logits_btv": P(ba, None, "model"),
    }


@contextlib.contextmanager
def sharding_rules(mesh: Mesh, rules: Optional[Dict[str, P]] = None):
    token = _CTX.set({"mesh": mesh, "rules": rules or activation_rules(mesh)})
    try:
        yield
    finally:
        _CTX.reset(token)


@contextlib.contextmanager
def manual_region():
    """Inside shard_map bodies: all axes are manual → ``shard`` = identity
    (with_sharding_constraint on manual axes is an error)."""
    token = _CTX.set(None)
    try:
        yield
    finally:
        _CTX.reset(token)


def _divides(shape, spec: P, mesh: Mesh) -> bool:
    for dim, names in zip(shape, spec):
        if names is None:
            continue
        names = names if isinstance(names, tuple) else (names,)
        size = int(np.prod([mesh.shape[n] for n in names]))
        if dim % size != 0:
            return False
    return True


def model_axis_size() -> int:
    """Model-axis extent of the active sharding context (1 outside)."""
    ctx = _CTX.get()
    if ctx is None:
        return 1
    return int(ctx["mesh"].shape.get("model", 1))


def current_mesh() -> Optional[Mesh]:
    ctx = _CTX.get()
    return None if ctx is None else ctx["mesh"]


def shard(x, name: str):
    ctx = _CTX.get()
    if ctx is None:
        return x
    spec = ctx["rules"].get(name)
    if spec is None:
        return x
    mesh = ctx["mesh"]
    if len(spec) > x.ndim or not _divides(x.shape, spec, mesh):
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


# ------------------------------------------------------------ param specs
# Path-pattern → PartitionSpec builder. Patterns match the "/"-joined tree
# path. PackedTensor leaves append child indices ("attn/wq/w/0" = packed
# data) — the `(?:/\d+)*$` tail matches them; packed rows inherit the
# logical weight's row/col parallelism (divisibility checked downstream).
_IDX = r"(?:/\d+)*$"
_PARAM_RULES = [
    # attention projections: column-parallel qkv, row-parallel o
    (r"attn/w[qkv]/w" + _IDX, P(None, "model")),
    (r"attn/wo/w" + _IDX, P("model", None)),
    (r"cross/w[qkv]/w" + _IDX, P(None, "model")),
    (r"cross/wo/w" + _IDX, P("model", None)),
    # dense / shared-expert SwiGLU: column-parallel in, row-parallel out
    (r"(mlp|shared|ffn)/w_(gate|up)/w" + _IDX, P(None, "model")),
    (r"(mlp|shared|ffn)/w_down/w" + _IDX, P("model", None)),
    # MoE experts (bf16 stacked): expert-parallel over model axis
    (r"experts/w_(gate|up|down)$", P("model", None, None)),
    # PMQ-compressed expert buckets [cnt, K?, N]: EP on the bucket dim
    (r"moe_ce/.*", P("model", None, None)),
    (r"router/w$", P(None, None)),
    # embeddings: vocab-parallel
    (r"^(embed|unembed)$", P("model", None)),
    # recurrent / xlstm / whisper-style block projections
    (r"(proj_in|gate_in|wq|wk|wv|wa|wx|w_z|w_o|w_if)/w" + _IDX, P(None, "model")),
    (r"(proj_out|out)/w" + _IDX, P("model", None)),
    (r"ffn/proj_in/w" + _IDX, P(None, "model")),
    (r"ffn/proj_out/w" + _IDX, P("model", None)),
    # everything else (norms, biases, small params): replicated
]


def param_spec_for_path(path: str, ndim: int, stacked: bool) -> P:
    for pat, spec in _PARAM_RULES:
        if re.search(pat, path):
            parts = list(spec)
            if stacked:
                parts = [None] + parts
            # pad/truncate to ndim
            while len(parts) < ndim:
                parts.append(None)
            return P(*parts[:ndim])
    return P(*([None] * ndim))


def _path_str(path) -> str:
    out = []
    for p in path:
        if hasattr(p, "key"):
            out.append(str(p.key))
        elif hasattr(p, "idx"):
            out.append(str(p.idx))
        else:
            out.append(str(p))
    return "/".join(out)


STACKED_PREFIXES = ("blocks", "groups", "enc_blocks", "dec_blocks")


def make_param_shardings(mesh: Mesh, params, stacked_prefixes=STACKED_PREFIXES):
    """Pytree of NamedShardings for a param tree (stacked layer dims aware).

    Falls back to replication when a spec doesn't divide the dim — this is
    what lets 40-head attention ride a 16-way model axis (output dim 5120
    divides even though head count doesn't; embeddings of odd vocab sizes
    replicate instead of crashing).
    """

    def one(path, leaf):
        ps = _path_str(path)
        stacked = any(ps.startswith(pref) for pref in stacked_prefixes)
        nd = getattr(leaf, "ndim", 0)
        spec = param_spec_for_path(ps, nd, stacked)
        if not _divides(leaf.shape, spec, mesh):
            spec = P(*([None] * nd))
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(one, params)


def cache_pspec(mesh: Mesh, cache_shape, prefer: str = "batch") -> P:
    """KV cache [L, B, S, Hkv, dh] — 2-D sharded.

    Preference order: (batch→data, heads→model); heads that don't divide
    fall back to (batch→data, seq→model); long-context (batch=1):
    (seq→data, heads→model), else seq over both axes.
    """
    ba = batch_axes(mesh)
    bsz = int(np.prod([mesh.shape[a] for a in ba]))
    model = mesh.shape.get("model", 1)
    data = mesh.shape.get("data", 1)
    l, b, s, h = cache_shape[:4]
    if prefer == "batch" and b % bsz == 0:
        if h % model == 0:
            return P(None, ba, None, "model", None)
        if s % model == 0:
            return P(None, ba, "model", None, None)
        return P(None, ba, None, None, None)
    # long-context: sequence first
    if s % data == 0 and h % model == 0:
        return P(None, None, "data", "model", None)
    if s % (data * model) == 0:
        return P(None, None, ("data", "model"), None, None)
    if s % data == 0:
        return P(None, None, "data", None, None)
    return P(*([None] * len(cache_shape)))
