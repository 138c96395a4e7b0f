"""shard_map expert parallelism — the collective-minimal MoE region.

Key observation (DESIGN.md §7): in the sequence-parallel block layout the
MoE region's input is already *replicated over the model axis* within
each data shard (``act_full``). Expert parallelism therefore needs **no
all-to-all at all**: every (data d, model m) device

1. routes its data-shard's tokens (duplicated across m — routing is
   ~0.1 % of expert FLOPs),
2. keeps only the (token, k)-slots whose expert lives on model-shard m,
3. runs the *local* capacity dispatch + expert FFN (bf16 batched einsum
   or the PMQ bucket path — everything device-local),
4. contributes its partial combine; one ``psum`` over ``model`` per layer
   merges expert outputs — the same wire cost as a dense TP block.

This replaces the pjit/GSPMD global-dispatch path, which replicated the
[E·cap, D] buffer per device (measured: kimi-k2 prefill_32k collective
term 414 s/step → see EXPERIMENTS.md §Perf).

Gradients flow through ``shard_map``; OTP masks are computed
token-locally so they are identical on every model shard (the DM router
rides ``in_specs=P(None, None)`` so it is differentiable end-to-end).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from .sharding import batch_axes, manual_region

__all__ = ["moe_region_sharded", "compressed_moe_region_sharded"]


def moe_region_sharded(p: Dict, x: jnp.ndarray, cfg, mesh,
                       gate_mask_fn=None):
    """bf16 expert path. ``x [B, S, D]`` (batch on data, seq gathered)."""
    from ..models import moe as moe_mod

    ba = batch_axes(mesh)
    model = mesh.shape["model"]
    e, k = cfg.num_experts, cfg.top_k
    eploc = e // model

    def body(xl, wr, wg, wu, wd):
        with manual_region():
            return _body(xl, wr, wg, wu, wd)

    def _body(xl, wr, wg, wu, wd):
        b, s, d = xl.shape
        x2 = xl.reshape(b * s, d)
        t = x2.shape[0]
        midx = jax.lax.axis_index("model")
        probs, idx, gates = moe_mod.route_topk({"w": wr}, x2, k)
        mask = gate_mask_fn(x2, idx, gates) if gate_mask_fn else None
        lo = midx * eploc
        sel = ((idx >= lo) & (idx < lo + eploc)).astype(gates.dtype)
        if mask is not None:
            sel = sel * mask
        local_idx = jnp.clip(idx - lo, 0, eploc - 1)
        cap = moe_mod.dispatch_capacity(cfg, t)
        xp, dest, valid, gflat = moe_mod.capacity_dispatch(
            x2, local_idx, gates, eploc, cap, gate_mask=sel
        )
        x3 = xp.reshape(eploc, cap, d)
        h = jax.nn.silu(
            jnp.einsum("ecd,edf->ecf", x3, wg.astype(x3.dtype))
        ) * jnp.einsum("ecd,edf->ecf", x3, wu.astype(x3.dtype))
        yp = jnp.einsum("ecf,efd->ecd", h, wd.astype(x3.dtype)).reshape(
            eploc * cap, d
        )
        y_partial = moe_mod.combine(yp, dest, valid, gflat, t, k)
        y = jax.lax.psum(y_partial, "model")
        aux = jax.lax.pmean(moe_mod.load_balance_loss(probs, idx, e), ba)
        return y.reshape(b, s, d), aux

    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(ba, None, None),
            P(None, None),
            P("model", None, None),
            P("model", None, None),
            P("model", None, None),
        ),
        out_specs=(P(ba, None, None), P()),
        check_vma=False,
    )
    ex = p["experts"]
    return fn(x, p["router"]["w"], ex["w_gate"], ex["w_up"], ex["w_down"])


def _slot_tables(meta, model: int) -> Tuple[np.ndarray, np.ndarray]:
    """Static maps: global permuted slot → (model shard, local slot).

    Bucket rows shard contiguously *within each bucket* (P("model") on the
    bucket dim), so shard m's local layout is the concat of its share of
    every bucket, preserving bucket order.
    """
    num_slots = sum(m.count for m in meta)
    shard_of = np.zeros(num_slots, np.int32)
    local_of = np.zeros(num_slots, np.int32)
    for m in meta:
        cnt_loc = m.count // model
        off = np.arange(m.count)
        shard_of[m.start : m.start + m.count] = off // cnt_loc
        local_of[m.start : m.start + m.count] = m.start // model + off % cnt_loc
    return shard_of, local_of


def compressed_moe_region_sharded(
    p: Dict, ce, x: jnp.ndarray, cfg, mesh,
    otp_params: Optional[Dict] = None, otp_rng=None, otp_tau: float = 1.0,
    capacity_factor: Optional[float] = None,
    ffn_backend: Optional[str] = None,
):
    """PMQ-compressed expert path (bit-bucketed, device-local dequant).

    Bucket counts are multiples of the model extent (builder guarantee);
    each shard runs its local share of every bucket through the same
    grouped-GEMM primitive as the local path
    (:func:`repro.core.compressed_moe.grouped_bucket_ffn`): occupied rows
    compact into bm-aligned ragged groups, one fused gate/up + one down
    ``ops.moe_gmm`` call per bucket, dead capacity blocks skipped via
    ``num_active``. ``ffn_backend="scan"`` keeps the legacy one-expert-
    at-a-time scan (dequant-matmul through ``ops.quant_matmul_parts``,
    so TPU shards still get the Pallas dequant-GEMM).
    """
    from ..core import compressed_moe as cmoe
    from ..core import otp as otp_mod
    from ..kernels import ops
    from ..models import moe as moe_mod

    path, kb = cmoe._resolve_backend(ffn_backend)
    ba = batch_axes(mesh)
    model = mesh.shape["model"]
    data = mesh.shape.get("data", 1)
    e, k = cfg.num_experts, cfg.top_k
    eploc = ce.num_slots // model
    cf = capacity_factor if capacity_factor is not None else cfg.moe_capacity_factor
    shard_of_np, local_of_np = _slot_tables(ce.meta, model)
    shard_of = jnp.asarray(shard_of_np)
    local_of = jnp.asarray(local_of_np)

    # 2-D expert sharding (EP over model × expert-TP over data): kimi-scale
    # packed experts (~322 GB at 2.25 b) must use *every* chip for storage.
    # gate/up go column-parallel on F, down row-parallel on F (+ one psum
    # over data per layer). Requires quant groups to align with F shards.
    f = ce.d_ff
    etp = (
        data > 1
        and f % data == 0
        and (f // data) % ce.group == 0
        and (f // ce.group) % data == 0
    )
    # ETP correctness requires the F-contraction partials of a token to be
    # summable across the data axis — valid only if tokens are REPLICATED
    # over data. Small T (decode): replicate tokens (per-device weight
    # reads stay at the 1/(model·data) storage share — the decode-roofline
    # optimum). Large T (prefill/train): keep tokens data-sharded and
    # all-gather each layer's F-shards instead (ZeRO-3-style; transient =
    # one layer's model-share).
    import os

    t_global = x.shape[0] * x.shape[1]
    etp_mode = None
    if etp:
        thresh = int(os.environ.get("REPRO_ETP_REPLICATE_MAX", 32768))
        etp_mode = "replicate_tokens" if t_global <= thresh else "gather_weights"

    def _wspec(wname: str, ndim: int) -> P:
        if not etp:
            return P("model", *([None] * (ndim - 1)))
        if wname in ("w_gate", "w_up"):
            # [cnt, D(/per|/group), F]: F column-parallel over data
            return P("model", *([None] * (ndim - 2)), "data")
        # w_down [cnt, F(/per|/group), D]: F row-parallel over data
        return P("model", "data", *([None] * (ndim - 2)))

    # flatten CE arrays (+ optional OTP params) into positional args
    bucket_names = [f"b{i}" for i in range(len(ce.meta))]
    arr_list, spec_list = [], []
    for bn in bucket_names:
        for wname in ("w_gate", "w_up", "w_down"):
            entry = ce.arrays[bn][wname]
            for key in ("data", "hi", "lo", "scale", "zero"):
                if key in entry:
                    a = entry[key]
                    arr_list.append(a)
                    spec_list.append(_wspec(wname, a.ndim))
    has_otp = otp_params is not None
    otp_args, otp_specs = (), ()
    if has_otp:
        otp_args = (otp_params["fc1"], otp_params["fc2"])
        otp_specs = (P(None, None), P(None, None))

    slot_map = ce.slot_of_expert
    if slot_map.ndim > 1:
        slot_map = slot_map[0]

    def rebuild(local_arrays):
        it = iter(local_arrays)
        out = {}
        for bn in bucket_names:
            out[bn] = {}
            for wname in ("w_gate", "w_up", "w_down"):
                entry = ce.arrays[bn][wname]
                out[bn][wname] = {
                    key: next(it)
                    for key in ("data", "hi", "lo", "scale", "zero")
                    if key in entry
                }
        return out

    def body(xl, wr, *rest):
        with manual_region():
            return _body(xl, wr, *rest)

    def _body(xl, wr, *rest):
        if has_otp:
            fc1, fc2 = rest[:2]
            local_arrays = rest[2:]
        else:
            fc1 = fc2 = None
            local_arrays = rest
        local = rebuild(local_arrays)
        if etp_mode == "gather_weights":
            # rebuild full-F weights from the data-axis shards
            def _gather(wname, key, a):
                if wname in ("w_gate", "w_up"):
                    return jax.lax.all_gather(a, "data", axis=a.ndim - 1, tiled=True)
                return jax.lax.all_gather(a, "data", axis=1, tiled=True)

            local = {
                bn: {
                    wname: {
                        key: _gather(wname, key, arr)
                        for key, arr in entry.items()
                    }
                    for wname, entry in bucket.items()
                }
                for bn, bucket in local.items()
            }
        b, s, d = xl.shape
        x2 = xl.reshape(b * s, d)
        t = x2.shape[0]
        midx = jax.lax.axis_index("model")
        probs, idx, gates = moe_mod.route_topk({"w": wr}, x2, k)
        mask = None
        if has_otp:
            mask = otp_mod.otp_mask(
                {"fc1": fc1, "fc2": fc2}, x2, idx, gates,
                rng=otp_rng, tau=otp_tau,
            )
        sidx = slot_map[idx]  # original expert id → permuted slot
        sel = (shard_of[sidx] == midx).astype(gates.dtype)
        if mask is not None:
            sel = sel * mask
        local_idx = local_of[sidx]
        cap = moe_mod.dispatch_capacity(cfg, t, cf)
        xp, dest, valid, gflat = moe_mod.capacity_dispatch(
            x2, local_idx, gates, eploc, cap, gate_mask=sel
        )
        # occupied-row counts per local slot (prefix occupancy — see
        # grouped_bucket_ffn): the ragged frontier of the grouped GEMMs
        local_fill = moe_mod.slot_fill_counts(dest, valid, eploc, cap)

        ys = []
        for i, m in enumerate(ce.meta):
            cnt_loc = m.count // model
            st_loc = m.start // model
            xb = jax.lax.slice_in_dim(xp, st_loc * cap, (st_loc + cnt_loc) * cap)
            wdict = local[f"b{i}"]

            if path == "scan":
                x3 = xb.reshape(cnt_loc, cap, d)

                def step(_, inp, bits=m.bits):
                    x2_, wg, wu, wd_ = inp

                    def mm(xx, wd2):
                        pk = (wd2["hi"], wd2["lo"]) if bits == 3 else wd2["data"]
                        return ops.quant_matmul_parts(
                            xx, pk, wd2["scale"], wd2["zero"],
                            bits=bits, group=ce.group, backend=kb,
                        )

                    h = jax.nn.silu(mm(x2_, wg)) * mm(x2_, wu)
                    return None, mm(h, wd_)

                _, y = jax.lax.scan(
                    step, None,
                    (x3, wdict["w_gate"], wdict["w_up"], wdict["w_down"]),
                )
                ys.append(y.reshape(cnt_loc * cap, d))
                continue

            fill = jax.lax.slice_in_dim(local_fill, st_loc, st_loc + cnt_loc)
            y = cmoe.grouped_bucket_ffn(
                xb, wdict, bits=m.bits, group=ce.group, count=cnt_loc,
                cap=cap, kernel_backend=kb, fill=fill,
            )
            ys.append(y)
        yp = jnp.concatenate(ys, axis=0)
        if etp_mode == "replicate_tokens":
            # tokens replicated over data: F-partials sum across data, and
            # expert partials across model — one fused psum
            yp = jax.lax.psum(yp, "data")
        y_partial = moe_mod.combine(yp, dest, valid, gflat, t, k)
        y = jax.lax.psum(y_partial, "model")
        m_l1 = mask.mean() if mask is not None else jnp.float32(0)
        return y.reshape(b, s, d), m_l1

    x_spec = (
        P(None, None, None) if etp_mode == "replicate_tokens" else P(ba, None, None)
    )
    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(x_spec, P(None, None), *otp_specs, *spec_list),
        out_specs=(x_spec, P()),
        check_vma=False,
    )
    y, m_l1 = fn(x, p["router"]["w"], *otp_args, *arr_list)
    return y, m_l1
