"""PMQ-compressed MoE experts: bit-bucketed storage + grouped-GEMM compute.

After :func:`repro.core.pmq.allocate_model` assigns per-expert bit-widths,
experts are **permuted so equal-width experts are contiguous** and stacked
into ≤3 *buckets* (one per bit-width). Each bucket is padded to a multiple
of the expert-parallel shard count (DESIGN.md §5.4).

**Compute path (default: ``grouped``).** Each bucket's SwiGLU runs as
grouped GEMMs via :func:`repro.kernels.ops` (one fused gate/up call with
the SwiGLU epilogue + one down call) over a *compacted* row layout: one
row per routed (token, choice) pair, pairs sorted by permuted slot, each
slot's group padded to ``bm`` rows and the groups packed back to back,
with a scalar-prefetched ``block_expert`` table naming each row block's
expert. The buffer, and so the kernels' grid, is sized by a static bound
on the routed pairs (:func:`grouped_extent`: about ``count + T·k/bm``
blocks), not by the bucket's ``count·cap`` capacity rows, which under
drop-free serving (``cf = num_experts``) are ``num_experts`` times the
pairs. Blocks past the routed frontier (``num_active``) are skipped
inside the kernel. On a single device :func:`ragged_expert_ffn` builds
the layout straight from the pairs; the expert-parallel paths keep the
capacity layout (slot ``s`` owns rows ``[s·cap, (s+1)·cap)``, occupied
rows a prefix) and :func:`grouped_bucket_ffn` compacts it into the same
rows. Either way each output row depends only on its own input row and
its expert's weights, so both give the same bits. On TPU
``ops.moe_gmm`` lowers to the Pallas kernel in
:mod:`repro.kernels.moe_gmm`; on CPU it runs the jnp oracle
(``moe_gmm_ref``), and tests opt into ``interpret``.

**Backend knob.** ``backend=`` / ``ffn_backend=`` selects per call:
``"grouped"`` (platform-default kernel — Pallas on TPU, oracle on CPU),
``"interpret"`` / ``"ref"`` (grouped layout, forced kernel backend), or
``"scan"`` (the legacy per-expert scan, kept as the A/B baseline and
numeric reference; its dequant-matmul now routes through
``ops.quant_matmul_parts`` so even the scan gets the Pallas
dequant-GEMM on TPU). ``REPRO_FFN_BACKEND`` overrides the default
process-wide — it is read at trace time, so a jitted serving engine
keeps whichever backend it was traced with.

The router remap (original expert id → permuted slot) rides the routing
top-k output, so the rest of the MoE layer (capacity semantics, OTP
masking, combine) is unchanged.

**Host-offloaded residency** (serving): a bucket may be split into a
*resident* device partition of ``resident_rows[i]`` expert rows plus a
host backing store (:mod:`repro.serving.offload`). ``resident_map[bᵢ]``
maps every bucket slot to a row of the resident buffer. The grouped path
never materializes the gathered bucket: the indirection is folded into
the scalar ``block_expert`` table once per bucket
(``block_expert = resident_map[block_expert]``), so the kernel fetches
resident rows directly — bit-identical to the all-resident path for
every slot whose resident row holds its true weights. The pytree
structure is a function of the *budget* only (array shapes + map shape),
never of *which* experts are resident, so uploads between steps never
retrace the jitted serving programs.
"""
from __future__ import annotations

import dataclasses
import math
import os
import warnings
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels import ops
from ..models.moe import (
    capacity_dispatch,
    combine,
    dispatch_capacity,
    route_topk,
    slot_fill_counts,
)
from ..models.layers import mlp
from ..parallel.sharding import model_axis_size, shard
from . import otp as otp_mod
from .packing import packed_nbytes
from .quantizers import quantize_to_packed

__all__ = [
    "BucketMeta",
    "CompressedExperts",
    "FFN_BACKENDS",
    "build_compressed_experts",
    "compressed_expert_ffn",
    "compressed_moe_layer",
    "default_ffn_backend",
    "gmm_block_rows",
    "grouped_bucket_ffn",
    "grouped_extent",
    "ragged_expert_ffn",
]

FFN_BACKENDS = ("grouped", "scan", "ref", "interpret")


def default_ffn_backend() -> str:
    """Process-wide expert-FFN path: ``REPRO_FFN_BACKEND`` or ``grouped``."""
    b = os.environ.get("REPRO_FFN_BACKEND", "").strip().lower() or "grouped"
    if b not in FFN_BACKENDS:
        raise ValueError(
            f"REPRO_FFN_BACKEND={b!r} not in {FFN_BACKENDS}"
        )
    return b


def _resolve_backend(backend: Optional[str]) -> Tuple[str, Optional[str]]:
    """``backend`` → ``(path, kernel_backend)``.

    ``path`` is ``"grouped"`` or ``"scan"``; ``kernel_backend`` feeds the
    :mod:`repro.kernels.ops` platform selection (None = platform default).
    """
    b = backend or default_ffn_backend()
    if b == "scan":
        return "scan", None
    if b == "grouped":
        return "grouped", None
    if b in ("ref", "interpret"):
        return "grouped", b
    raise ValueError(f"ffn backend {b!r} not in {FFN_BACKENDS}")


@dataclasses.dataclass(frozen=True)
class BucketMeta:
    bits: int
    start: int  # first permuted slot
    count: int  # padded expert count (multiple of ep)


@dataclasses.dataclass
class CompressedExperts:
    """Static metadata + array pytree for one layer's quantized experts.

    All-resident (the default): ``arrays[bᵢ]`` leaves span the bucket's
    full ``[count, ...]`` expert dim and ``resident_map is None``.

    Host-offloaded (serving): ``arrays[bᵢ]`` leaves span only
    ``resident_rows[i]`` device rows and ``resident_map[bᵢ]`` ([count]
    int32, or [L, count] stacked) maps each bucket slot to its resident
    row — non-resident slots point at row 0 and must not receive routed
    tokens (the serving engine's miss/replay loop guarantees that).
    """

    meta: Tuple[BucketMeta, ...]  # static
    slot_of_expert: jnp.ndarray  # [E] original id -> permuted slot
    arrays: Dict  # {bucket_i: {w_gate/w_up/w_down: {data|hi|lo, scale, zero}}}
    num_slots: int  # total padded slots
    group: int
    d_model: int
    d_ff: int
    resident_map: Optional[Dict] = None  # {bucket_i: [count] int32 -> row}
    resident_rows: Optional[Tuple[int, ...]] = None  # static, per bucket

    @property
    def weight_bytes(self) -> int:
        """Device-resident quantized bytes (= total bytes when all-resident)."""
        tot = 0
        for i, m in enumerate(self.meta):
            for w in ("w_gate", "w_up", "w_down"):
                a = self.arrays[f"b{i}"][w]
                for key in ("data", "hi", "lo", "scale", "zero"):
                    if key in a:
                        arr = a[key]
                        tot += arr.size * arr.dtype.itemsize
        return tot


def _flatten(xs):
    return [x for x in xs]


jax.tree_util.register_pytree_node(
    CompressedExperts,
    lambda ce: (
        (ce.slot_of_expert, ce.arrays, ce.resident_map),
        (ce.meta, ce.num_slots, ce.group, ce.d_model, ce.d_ff,
         ce.resident_rows),
    ),
    lambda aux, ch: CompressedExperts(
        meta=aux[0], slot_of_expert=ch[0], arrays=ch[1], num_slots=aux[1],
        group=aux[2], d_model=aux[3], d_ff=aux[4],
        resident_map=ch[2], resident_rows=aux[5],
    ),
)


def _pack_stack(ws, ids: List[int], bits: int, group: int,
                gptq=None, refine: bool = True) -> Dict:
    """Stack the packed experts ``ws[i]`` for ``i`` in ``ids`` (one bucket,
    one bit-width). Each expert is cast to float32 and packed on its
    device, which is waited for before the next expert is dispatched, so
    that only one expert's float32 copy and temporaries are ever live;
    ``gptq[i]`` (optional) gives expert ``i``'s GPTQ codes/scales."""
    pts = []
    for i in ids:
        kw = {}
        if gptq is not None:
            kw = {
                "codes": jnp.asarray(gptq[i].codes),
                "scale": jnp.asarray(gptq[i].scale),
                "zero": jnp.asarray(gptq[i].zero),
            }
        w = jnp.asarray(ws[i], jnp.float32)
        pts.append(jax.block_until_ready(
            quantize_to_packed(w, bits, group=group, refine=refine, **kw)
        ))
        del w
    out: Dict = {
        "scale": jnp.stack([p.scale for p in pts]),
        "zero": jnp.stack([p.zero for p in pts]),
    }
    if bits == 3:
        out["hi"] = jnp.stack([p.data[0] for p in pts])
        out["lo"] = jnp.stack([p.data[1] for p in pts])
    else:
        out["data"] = jnp.stack([p.data for p in pts])
    return out


def build_compressed_experts(
    experts: Dict,
    bits_per_expert: Sequence[int],
    *,
    group: int = 128,
    ep: int = 1,
    gptq_results: Optional[Dict] = None,
    refine: bool = True,
) -> CompressedExperts:
    """Quantize + bucket one layer's experts.

    ``experts`` = {"w_gate": [E, D, F], "w_up": [E, D, F], "w_down": [E, F, D]}
    (host or device arrays, or anything whose ``[i]`` is expert ``i`` and
    whose ``shape`` is theirs); each expert is packed on the device.
    ``gptq_results[(expert, name)]`` optionally carries GPTQ codes/scales
    (:class:`repro.core.gptq.GPTQResult`) — otherwise RTN/HQQ packing.
    ``ep`` = expert-parallel shard count (buckets padded to multiples).
    """
    e = len(bits_per_expert)
    bits_arr = np.asarray(bits_per_expert)
    order = np.argsort(bits_arr, kind="stable")  # ascending bit groups
    meta: List[BucketMeta] = []
    arrays: Dict = {}
    slot_of_expert = np.full(e, -1, np.int64)
    d, f = experts["w_gate"].shape[1:]
    slot = 0
    for bits in sorted(set(bits_arr.tolist())):
        ids = [int(i) for i in order if bits_arr[i] == bits]
        for j, eid in enumerate(ids):
            slot_of_expert[eid] = slot + j
        count = len(ids)
        pad = (-count) % ep
        padded = count + pad
        pick = ids + [ids[-1]] * pad  # dummy slots clone the last expert
        bdict = {}
        for name in ("w_gate", "w_up", "w_down"):
            gptq = None
            if gptq_results is not None:
                gptq = {i: gptq_results[(i, name)] for i in set(pick)}
            bdict[name] = _pack_stack(experts[name], pick, bits, group,
                                      gptq, refine=refine)
        arrays[f"b{len(meta)}"] = bdict
        meta.append(BucketMeta(bits=bits, start=slot, count=padded))
        slot += padded
    return CompressedExperts(
        meta=tuple(meta),
        slot_of_expert=jnp.asarray(slot_of_expert, jnp.int32),
        arrays=arrays,
        num_slots=slot,
        group=group,
        d_model=d,
        d_ff=f,
    )


def _bmm_ep(x3, wd, bits: int, group: int, kernel_backend: Optional[str] = None):
    """Dequant-matmul vmapped over the (model-sharded) ep axis.

    ``x3 [ep, cap, K]``, ``wd`` packed arrays sliced to one local expert:
    [ep, K/per, N] (+ scale/zero [ep, ngroups, N]). Routed through the
    :func:`repro.kernels.ops.quant_matmul_parts` backend selection, so
    TPU shards run the fused dequant-GEMM Pallas kernel.
    """
    if bits == 3:
        fn = lambda x2, hi, lo, s, z: ops.quant_matmul_parts(
            x2, (hi, lo), s, z, bits=bits, group=group,
            backend=kernel_backend,
        )
        return jax.vmap(fn)(x3, wd["hi"], wd["lo"], wd["scale"], wd["zero"])
    fn = lambda x2, pk, s, z: ops.quant_matmul_parts(
        x2, pk, s, z, bits=bits, group=group, backend=kernel_backend
    )
    return jax.vmap(fn)(x3, wd["data"], wd["scale"], wd["zero"])


def _ep_fallback(count: int, ep: int) -> None:
    """A bucket whose padded expert count does not divide the runtime
    model-axis extent silently loses expert parallelism (the compute runs
    every expert on every shard). That only happens when the bucket was
    built with a different ``ep`` than the mesh it runs under — loud by
    default, fatal under ``REPRO_STRICT_EP=1``.
    """
    msg = (
        f"compressed_expert_ffn: bucket of {count} padded experts is not "
        f"divisible by the model-axis size {ep}; falling back to ep=1 "
        f"(expert parallelism disabled for this bucket). Rebuild the "
        f"buckets with build_compressed_experts(..., ep={ep}) to restore "
        f"EP, or set REPRO_STRICT_EP=1 to make this fatal."
    )
    strict = os.environ.get("REPRO_STRICT_EP", "0").strip().lower()
    if strict not in ("", "0", "false", "off", "no"):
        raise AssertionError(msg)
    warnings.warn(msg, RuntimeWarning, stacklevel=3)


def _gmm_parts(w: Dict, bits: int):
    pk = (w["hi"], w["lo"]) if bits == 3 else w["data"]
    return pk, w["scale"], w["zero"]


def gmm_block_rows(cap: int) -> int:
    """Row-block size ``bm`` for the grouped path at capacity ``cap``.

    ``bm`` must divide ``cap`` (so a slot's group never outgrows its
    capacity in whole blocks) and trades MXU tile height against padding:
    each nonempty expert pads its group to a multiple of ``bm``, so with
    ``P`` routed pairs over ``c`` touched experts the grouped GEMMs walk
    about ``c + P/bm`` row blocks (:func:`grouped_extent`). Few rows per
    expert — decode, or short prefill chunks spread over many experts —
    favour small blocks; default target 16, override with
    ``REPRO_GMM_BM`` (e.g. 128 for long-prefill TPU runs). Always a
    multiple of 8 because ``cap`` is.
    """
    target = int(os.environ.get("REPRO_GMM_BM", "0") or 0) or 16
    target = max(8, ((target + 7) // 8) * 8)  # sublane-align the target
    return math.gcd(cap, target)


def grouped_extent(count: int, cap: int, pairs: int, bm: int) -> int:
    """Rows of one bucket's compacted grouped-GEMM buffer (static).

    The bucket receives at most ``p = min(pairs, count·cap)`` routed rows.
    Its groups take ``Σ_s bm·ceil(fill_s/bm)`` rows; over ``n`` nonempty
    slots that is at most ``bm·(n + floor((p − n)/bm))``, which grows with
    ``n``, so ``n = min(count, p)`` bounds every routing. Where capacity
    is tight the bound reaches ``count·cap``, today's full layout.
    """
    p = min(pairs, count * cap)
    c = min(count, p)
    return bm * min(count * cap // bm, c + (p - c) // bm)


def _block_table(fill, count: int, rows: int, bm: int, rmap=None):
    """``fill [count]`` → each slot's first compacted row ``[count]``, the
    expert of every row block ``[rows/bm]`` and the live block count
    ``num_active [1]``: slot groups packed back to back at ``bm``
    boundaries, in slot order."""
    padded = ((fill + bm - 1) // bm) * bm
    nblk = padded // bm
    block_expert = jnp.repeat(
        jnp.arange(count, dtype=jnp.int32), nblk,
        total_repeat_length=rows // bm,
    )  # trailing pad entries repeat a valid id; num_active masks them
    if rmap is not None:
        block_expert = rmap[block_expert].astype(jnp.int32)
    num_active = jnp.sum(nblk).astype(jnp.int32).reshape(1)
    return jnp.cumsum(padded) - padded, block_expert, num_active


def _bucket_gemms(xg, wdict, block_expert, num_active, *, bits, group, bm,
                  kernel_backend):
    """One bucket's SwiGLU as two grouped GEMMs over the compacted rows."""
    gp, gs, gz = _gmm_parts(wdict["w_gate"], bits)
    up, us, uz = _gmm_parts(wdict["w_up"], bits)
    dp, ds, dz = _gmm_parts(wdict["w_down"], bits)
    h = ops.moe_gmm_swiglu(
        xg, gp, up, gs, gz, us, uz, block_expert, num_active,
        bits=bits, group=group, backend=kernel_backend, bm=bm,
    )
    return ops.moe_gmm(
        h, dp, ds, dz, block_expert, num_active,
        bits=bits, group=group, backend=kernel_backend, bm=bm,
    )


def grouped_bucket_ffn(
    xb: jnp.ndarray,
    wdict: Dict,
    *,
    bits: int,
    group: int,
    count: int,
    cap: int,
    kernel_backend: Optional[str] = None,
    fill: Optional[jnp.ndarray] = None,
    rmap: Optional[jnp.ndarray] = None,
    pairs: Optional[int] = None,
) -> jnp.ndarray:
    """One bucket's SwiGLU over its capacity slice as grouped GEMMs.

    ``xb [count·cap, D]`` is the bucket's expert-major capacity slice;
    ``wdict`` its packed gate/up/down arrays (leading dim = ``count``,
    or the resident row count when ``rmap`` indirects). Returns
    ``[count·cap, D]`` in the same layout.

    ``fill [count]`` (optional) gives each slot's occupied-row count —
    occupancy is a *prefix* per slot (capacity dispatch ranks within the
    expert), so compaction is a pure index shuffle: slot ``s`` row ``j``
    (``j < fill[s]``) moves to ``offsets[s] + j`` where groups are packed
    back-to-back at ``bm`` boundaries. The compacted buffer, and with it
    the kernels' grid, has :func:`grouped_extent` rows for ``pairs`` (the
    most routed pairs the caller can send, static; default ``count·cap``)
    and ``num_active`` skips the blocks past the routed-row frontier.
    Results are scattered back so unoccupied capacity rows are exactly
    zero — identical to what the scan path computes for them. Without
    ``fill`` every capacity row is treated as live (the layout is already
    bm-aligned and expert-major, so no shuffle is needed).

    ``rmap [count]`` folds host-offload residency into the scalar
    ``block_expert`` table instead of gathering the packed bucket.
    """
    m = count * cap
    d = xb.shape[-1]
    bm = gmm_block_rows(cap)
    kw = dict(bits=bits, group=group, bm=bm, kernel_backend=kernel_backend)
    if fill is None:
        block_expert = jnp.repeat(jnp.arange(count, dtype=jnp.int32), cap // bm)
        if rmap is not None:
            block_expert = rmap[block_expert].astype(jnp.int32)
        return _bucket_gemms(xb, wdict, block_expert, None, **kw)
    rows = grouped_extent(count, cap, m if pairs is None else pairs, bm)
    fill = jnp.minimum(fill.astype(jnp.int32), cap)
    offsets, block_expert, num_active = _block_table(fill, count, rows, bm, rmap)
    s_of = jnp.arange(m, dtype=jnp.int32) // cap
    j_of = jnp.arange(m, dtype=jnp.int32) % cap
    # capacity row (s, j) → compacted row; dropped/empty rows → rows
    gdest = jnp.where(j_of < fill[s_of], offsets[s_of] + j_of, rows)
    inv = jnp.zeros((rows + 1,), jnp.int32)
    inv = inv.at[gdest].set(jnp.arange(m, dtype=jnp.int32) + 1)[:rows]
    src = jnp.where(inv > 0, inv - 1, m)  # m = appended zero row
    x_pad = jnp.concatenate([xb, jnp.zeros((1, d), xb.dtype)], axis=0)
    yg = _bucket_gemms(x_pad[src], wdict, block_expert, num_active, **kw)
    y_pad = jnp.concatenate([yg, jnp.zeros((1, d), yg.dtype)], axis=0)
    return y_pad[gdest]


def ragged_expert_ffn(
    ce: CompressedExperts,
    x2: jnp.ndarray,
    eids: jnp.ndarray,
    cap: int,
    *,
    kernel_backend: Optional[str] = None,
):
    """Grouped SwiGLU straight from the routed pairs (single device).

    ``x2 [T, D]`` tokens; ``eids [T·k]`` the permuted slot of each
    (token, choice) pair in (t, k) order, ``ce.num_slots`` for a pair
    that OTP pruned. The pairs are stably sorted by slot; a pair whose
    rank in its slot is ``≥ cap`` is dropped, as
    :func:`repro.models.moe.capacity_dispatch` drops it. Each bucket's
    kept pairs form ``bm``-aligned slot groups in a buffer of
    :func:`grouped_extent` rows for ``T·k`` pairs, and the buffers of all
    buckets stand back to back. Every row sits where
    :func:`grouped_bucket_ffn` would compact it, so the outputs are the
    capacity path's, bit for bit, without its ``[num_slots·cap, D]``
    buffer.

    Returns ``(y [R, D], row [T·k], valid [T·k], fill [num_slots])``:
    the grouped outputs, each pair's row of ``y`` (``R`` when dropped),
    whether it holds one, and each slot's kept-pair count (what
    :func:`repro.models.moe.slot_fill_counts` gives for the capacity
    layout). Feed ``y, row, valid`` to :func:`repro.models.moe.combine`.
    """
    t, d = x2.shape
    n = eids.shape[0]
    k = n // t
    bm = gmm_block_rows(cap)
    eids = eids.astype(jnp.int32)
    counts = jnp.zeros((ce.num_slots + 1,), jnp.int32).at[eids].add(1)
    first = jnp.cumsum(counts) - counts
    order = jnp.argsort(eids, stable=True)
    rank = jnp.zeros((n,), jnp.int32).at[order].set(
        jnp.arange(n, dtype=jnp.int32) - first[eids[order]]
    )
    fill = jnp.minimum(counts[:-1], cap)
    valid = (rank < cap) & (eids < ce.num_slots)

    rows = [grouped_extent(m.count, cap, n, bm) for m in ce.meta]
    total = sum(rows)
    starts, tables = [], []
    base = 0
    for i, (m, r) in enumerate(zip(ce.meta, rows)):
        rmap = None if ce.resident_map is None else ce.resident_map[f"b{i}"]
        offsets, block_expert, num_active = _block_table(
            jax.lax.slice_in_dim(fill, m.start, m.start + m.count),
            m.count, r, bm, rmap,
        )
        starts.append(base + offsets)
        tables.append((block_expert, num_active))
        base += r
    slot_start = jnp.concatenate(starts + [jnp.full((1,), total, jnp.int32)])
    row = jnp.where(valid, slot_start[eids] + rank, total)
    inv = jnp.zeros((total + 1,), jnp.int32)
    inv = inv.at[row].set(jnp.arange(n, dtype=jnp.int32) + 1)[:total]
    src = jnp.where(inv > 0, (inv - 1) // k, t)  # t = appended zero row
    xg = jnp.concatenate([x2, jnp.zeros((1, d), x2.dtype)], axis=0)[src]

    ys = []
    base = 0
    for i, (m, r, (block_expert, num_active)) in enumerate(
        zip(ce.meta, rows, tables)
    ):
        ys.append(_bucket_gemms(
            jax.lax.slice_in_dim(xg, base, base + r), ce.arrays[f"b{i}"],
            block_expert, num_active, bits=m.bits, group=ce.group, bm=bm,
            kernel_backend=kernel_backend,
        ))
        base += r
    return jnp.concatenate(ys, axis=0), row, valid, fill


def compressed_expert_ffn(
    ce: CompressedExperts, xp: jnp.ndarray, cap: int,
    *,
    backend: Optional[str] = None,
    slot_fill: Optional[jnp.ndarray] = None,
    pairs: Optional[int] = None,
) -> jnp.ndarray:
    """SwiGLU over permuted capacity layout ``xp [num_slots*cap, D]``.

    Default (``backend="grouped"``): each bucket runs as two grouped
    GEMM calls — fused gate/up with the SwiGLU epilogue, then down —
    through :func:`grouped_bucket_ffn` (see its docstring for the
    compacted ragged layout driven by ``slot_fill``, the per-permuted-
    slot occupied-row counts from capacity dispatch, and sized by
    ``pairs``, the number of routed pairs dispatched). With a resident
    partition (``ce.resident_map``) the indirection is folded into the
    scalar ``block_expert`` table once per bucket, before the GEMM —
    never a per-step weight gather (non-resident slots read row 0, which
    is only sound because they carry no routed tokens).

    ``backend="scan"`` keeps the legacy expert-parallel scan (DESIGN.md
    §5.4): each bucket reshaped ``[count·cap, D] → [ep, local, cap, D]``
    (ep = model-axis extent, baked into bucket padding at build time),
    a ``lax.scan`` over the local expert index, one dequantized [K, N]
    tile per shard per step, dequant-matmul via
    ``ops.quant_matmul_parts``. It gathers resident rows back to the
    full bucket layout instead of remapping ``block_expert``.

    Under ``ep > 1`` the grouped path vmaps :func:`grouped_bucket_ffn`
    over the shard axis (the ``moe_elcd`` capacity sharding is kept); the
    production multi-host EP route is the shard_map region in
    :mod:`repro.parallel.ep_shardmap`, which calls the same primitive
    device-locally.
    """
    d = ce.d_model
    path, kb = _resolve_backend(backend)
    ys = []
    for i, m in enumerate(ce.meta):
        b = ce.arrays[f"b{i}"]
        rmap = None
        if ce.resident_map is not None:
            rmap = ce.resident_map[f"b{i}"]
        ep = model_axis_size()
        if m.count % ep:
            _ep_fallback(m.count, ep)
            ep = 1
        local = m.count // ep
        xb = jax.lax.slice_in_dim(xp, m.start * cap, (m.start + m.count) * cap)
        fill = None
        if slot_fill is not None:
            fill = jax.lax.slice_in_dim(
                slot_fill, m.start, m.start + m.count
            )

        if path == "scan":
            if rmap is not None:
                b = jax.tree.map(lambda a: jnp.take(a, rmap, axis=0), b)
            x4 = xb.reshape(ep, local, cap, d)
            x4 = shard(x4, "moe_elcd")
            w4 = jax.tree.map(
                lambda a: jnp.moveaxis(a.reshape(ep, local, *a.shape[1:]), 1, 0),
                b,
            )  # leaves [local, ep, ...]

            def step(_, inp, bits=m.bits):
                x3, wg, wu, wd_ = inp
                h = jax.nn.silu(
                    _bmm_ep(x3, wg, bits, ce.group, kb)
                ) * _bmm_ep(x3, wu, bits, ce.group, kb)
                return None, _bmm_ep(h, wd_, bits, ce.group, kb)

            _, y = jax.lax.scan(
                step,
                None,
                (jnp.moveaxis(x4, 1, 0), w4["w_gate"], w4["w_up"], w4["w_down"]),
            )  # y [local, ep, cap, D]
            ys.append(jnp.moveaxis(y, 0, 1).reshape(m.count * cap, d))
            continue

        if ep == 1:
            y = grouped_bucket_ffn(
                xb, b, bits=m.bits, group=ce.group, count=m.count, cap=cap,
                kernel_backend=kb, fill=fill, rmap=rmap, pairs=pairs,
            )
        else:
            if rmap is not None:
                # resident buffers are not ep-structured; materialize the
                # bucket gather once, then shard as usual
                b = jax.tree.map(lambda a: jnp.take(a, rmap, axis=0), b)
            x4 = xb.reshape(ep, local, cap, d)
            x4 = shard(x4, "moe_elcd")
            x3 = x4.reshape(ep, local * cap, d)
            w3 = jax.tree.map(lambda a: a.reshape(ep, local, *a.shape[1:]), b)

            def gfn(xe, we, fe, bits=m.bits):
                return grouped_bucket_ffn(
                    xe, we, bits=bits, group=ce.group, count=local, cap=cap,
                    kernel_backend=kb, fill=fe, pairs=pairs,
                )

            if fill is None:
                y = jax.vmap(lambda xe, we: gfn(xe, we, None))(x3, w3)
            else:
                y = jax.vmap(gfn)(x3, w3, fill.reshape(ep, local))
            y = y.reshape(m.count * cap, d)
        ys.append(y)
    return jnp.concatenate(ys, axis=0)


def compressed_moe_layer(
    p: Dict,
    ce: CompressedExperts,
    x: jnp.ndarray,
    cfg,
    *,
    otp_params: Optional[Dict] = None,
    otp_rng=None,
    otp_tau: float = 1.0,
    capacity_factor: Optional[float] = None,
    count_weight: Optional[jnp.ndarray] = None,
    ffn_backend: Optional[str] = None,
) -> Tuple[jnp.ndarray, Dict]:
    """MoE block with PMQ experts (+ optional OTP pruning).

    ``p`` carries the (full-precision or 4-bit) router and shared experts.
    Returns ``(y [B,S,D], info)`` where info holds the OTP mask & router
    outputs (for distillation / calibration). ``info["mask_l1"]`` is the
    Eq. 14 ℓ1 statistic in both code paths. ``info["slot_counts"]`` is
    the per-permuted-slot count of dispatched (token, choice) pairs after
    OTP masking — the router statistic the serving offload prefetcher
    consumes; ``count_weight`` ([T], optional) zeroes the contribution of
    padding/inactive tokens so the counts reflect real traffic only.
    ``ffn_backend`` selects the expert-FFN implementation (see
    :data:`FFN_BACKENDS`; default ``grouped``).

    Inside a mesh context the routed region runs the shard_map EP path
    (zero all-to-all — see :mod:`repro.parallel.ep_shardmap`); a
    host-offloaded ``ce`` (``resident_map`` set) always takes the local
    path, which folds the resident-row indirection into the grouped
    dispatch tables. On a single device the grouped path dispatches
    straight from the routed pairs (:func:`ragged_expert_ffn`); the scan
    path and an ``ep > 1`` model axis go through the capacity layout.
    """
    from ..models.moe import ep_shardmap_ok
    from ..parallel.sharding import current_mesh

    mesh = current_mesh()
    if (
        mesh is not None
        and ce.resident_map is None
        and ep_shardmap_ok(cfg, mesh, x, ce.num_slots)
        and all(m.count % mesh.shape["model"] == 0 for m in ce.meta)
    ):
        from ..parallel.ep_shardmap import compressed_moe_region_sharded

        y, mask_l1 = compressed_moe_region_sharded(
            p, ce, x, cfg, mesh,
            otp_params=otp_params, otp_rng=otp_rng, otp_tau=otp_tau,
            capacity_factor=capacity_factor, ffn_backend=ffn_backend,
        )
        if "shared" in p:
            b, s, d = x.shape
            y = y + mlp(p["shared"], x.reshape(b * s, d)).reshape(b, s, d)
        info = {
            "probs": None, "idx": None, "gates": None, "mask": None,
            "mask_l1": mask_l1 if otp_params is not None else None,
            "slot_counts": None,
        }
        return y, info
    b, s, d = x.shape
    t = b * s
    x2 = x.reshape(t, d)
    e, k = cfg.num_experts, cfg.top_k
    probs, idx, gates = route_topk(p["router"], x2, k)
    mask = None
    if otp_params is not None:
        mask = otp_mod.otp_mask(
            otp_params, x2, idx, gates, rng=otp_rng, tau=otp_tau
        )
    # remap original expert ids -> permuted slots (dummy pads never hit)
    slots = ce.slot_of_expert[idx]
    # per-slot dispatch counts (post-mask, padding-weighted): the serving
    # offload manager's router statistic. The drop bucket (row num_slots)
    # absorbs masked / padded picks and is discarded.
    routed = slots.reshape(-1)
    if mask is not None:
        routed = jnp.where(mask.reshape(-1) > 0, routed, ce.num_slots)
    counted = routed  # padded tokens still take rows, but are not counted
    if count_weight is not None:
        cw = jnp.repeat(count_weight.reshape(-1).astype(bool), k)
        counted = jnp.where(cw, routed, ce.num_slots)
    slot_counts = (
        jnp.zeros((ce.num_slots + 1,), jnp.int32).at[counted].add(1)[:-1]
    )
    cap = dispatch_capacity(cfg, t, capacity_factor)
    path, kb = _resolve_backend(ffn_backend)
    if path == "grouped" and model_axis_size() == 1:
        gflat = gates.reshape(-1)
        if mask is not None:
            gflat = gflat * mask.reshape(-1)
        yg, row, valid, _ = ragged_expert_ffn(
            ce, x2, routed, cap, kernel_backend=kb
        )
        y = combine(yg, row, valid, gflat, t, k)
    else:
        xp, dest, valid, gflat = capacity_dispatch(
            x2, slots, gates, ce.num_slots, cap, mask
        )
        # occupied-row counts after capacity clipping: occupancy is a
        # prefix per slot, so these drive the grouped path's compaction
        slot_fill = slot_fill_counts(dest, valid, ce.num_slots, cap)
        xp = shard(xp, "moe_ed")
        yp = compressed_expert_ffn(
            ce, xp, cap, backend=ffn_backend, slot_fill=slot_fill,
            pairs=t * k,
        )
        y = combine(yp, dest, valid, gflat, t, k)
    if "shared" in p:
        y = y + mlp(p["shared"], x2)
    info = {
        "probs": probs, "idx": idx, "gates": gates, "mask": mask,
        "mask_l1": mask.mean() if mask is not None else None,
        "slot_counts": slot_counts,
    }
    return y.reshape(b, s, d), info
