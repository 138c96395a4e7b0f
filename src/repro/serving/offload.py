"""Host-offloaded PMQ expert buckets with router-stats prefetch.

MC#'s PMQ buckets (§3.2) shrink expert *storage*; this module shrinks
expert *device residency*: a device that holds only the hot slice of
each bit-bucket (plus the paged KV pool) can serve models whose full
expert set never fits. The pattern mirrors the serving swap store
(:class:`repro.serving.kvcache.SwappedKV`): cold rows live in a
host-memory backing store and move across the host↔device boundary in
whole quantized-expert rows (packed codes + scales/zeros — a fraction
of the bf16 bytes, which is exactly why PMQ makes offload cheap).

Residency is managed per ``(layer, bucket, expert slot)``:

* **Device**: per bucket, a ``[L, R_i, ...]`` resident buffer for each
  packed leaf plus a ``[L, count_i]`` int32 map from bucket slot to
  resident row. Both have *budget-determined* shapes, so changing which
  experts are resident never changes the pytree — the jitted serving
  programs compile once per budget, not per residency state.
* **Host**: full numpy copies of every bucket leaf (``[L, count_i, ...]``).
* **Prefetch**: an EMA over the per-(layer, slot) dispatch counts that
  every decode/prefill program reports (EAC-MoE-style expert-selection
  awareness, PAPERS.md) picks the top-``R_i`` slots per bucket; uploads
  happen between engine steps, alongside KV page growth.
* **Miss**: routing happens *inside* the jitted program, so the true
  working set is only known after the program ran. The engine replays
  the program after a synchronous upload of the missing experts
  (:meth:`ensure_resident`); KV writes land at position-determined
  destinations and the fused decode horizon's token sequence is
  deterministic per megastep, so a replay simply overwrites them with
  the correct values — residency is invisible to correctness for any
  budget that holds the per-program working set. Only usage up to the
  first missed row of the reported counts — layer-major within a step,
  step-major across a fused horizon — is trusted (later rows routed on
  garbage activations); authentic slots are **pinned** until the
  program is accepted, each replay extends the correct prefix, and the
  loop accepts within ``rows`` (``num_layers``, or ``H·num_layers``
  for a decode megastep) replays.
* **Overflow**: if a single step's working set exceeds a bucket's
  budget, the manager grows that bucket's resident buffer to fit (a
  one-time retrace) rather than serving wrong tokens — ``grows`` counts
  how often the configured budget was too small to be honored.
* **Async overlap** (:meth:`issue_async` / :meth:`commit_async`): with
  ``EngineConfig(async_offload=True)`` the controller's prefetch plan is
  *issued* right after the megastep's program dispatch — the post-upload
  device buffers are built against immutable jax arrays while the
  megastep computes on the live ones — and *committed* (buffers, tables
  and device maps flipped together) at the next megastep boundary.
  Content versions invalidate stale batches: any miss upload or budget
  grow between issue and commit bumps the touched bucket's version and
  the commit drops the batch instead of installing stale buffers.
  Placement is output-invariant and the miss backstop is untouched, so
  outputs stay bit-identical with overlap on or off.
* **Tiers** (:mod:`repro.serving.tierstore`): with ``offload_dir`` set
  the backing store generalizes to disk → host → device — packed
  buckets spilled once to mmap'd ``.npy`` images (CRC manifest, verified
  on every read) with a byte-budgeted EMA-heat host row cache between
  them, so host RAM no longer scales with total expert bytes.
* **Faults** (:mod:`repro.serving.faults`): with a :class:`FaultPlan`
  attached, every upload runs the recovery ladder of
  docs/serving_robustness.md — each staged payload is CRC-checked
  against the host row's checksum and re-fetched on mismatch; transient
  I/O failures retry (immediately and bounded on the miss path, with
  deterministic logical-step backoff on the prefetch path); a row whose
  target-bit upload persistently fails is **degraded**: its codes are
  snapped to the next lower rung of the PMQ precision ladder
  (:func:`degrade_expert_row` — same packed container, strictly fewer
  levels, scale/zero kept) and served from there permanently, emitting
  a ``degrade`` lifecycle event, or the manager fails closed with
  :class:`~repro.serving.faults.ExpertUploadFailed` when degradation is
  disabled or impossible (1-bit floor).
"""
from __future__ import annotations

import dataclasses
import time
import warnings
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.compressed_moe import CompressedExperts
from .faults import (
    ExpertUploadFailed,
    FaultPlan,
    checksum_tree,
    corrupt_tree,
)
from .tierstore import TieredExpertStore

__all__ = ["ExpertOffloadManager", "degrade_expert_row"]


def degrade_expert_row(row: Dict, bits: int, to_bits: int) -> Dict:
    """Snap one packed expert row's codes onto the ``2^to_bits`` grid,
    re-encoded in the same ``bits``-wide container (shapes unchanged, so
    the degraded payload drops into the resident buffer like any other
    upload). Scale/zero tables are kept — the row keeps its calibrated
    dynamic range but only ``2^to_bits`` distinct levels survive, i.e.
    the next rung down the PMQ precision ladder. ``row`` is the
    ``{w_gate/w_up/w_down: {data|hi+lo, scale, zero}}`` sub-tree of one
    ``(layer, slot)`` host row (packed axis 0)."""
    from ..core.packing import pack_bits, unpack_bits

    if not 1 <= to_bits < bits:
        raise ValueError(f"cannot degrade {bits}-bit codes to {to_bits}")
    maxq = (1 << bits) - 1
    maxt = (1 << to_bits) - 1

    def snap(q):
        q = np.asarray(q, np.float64)
        q2 = np.rint(q * maxt / maxq)
        return np.rint(q2 * maxq / maxt).astype(np.uint8)

    out: Dict = {}
    for wname, parts in row.items():
        new = dict(parts)
        if bits == 3:
            q = np.asarray(unpack_bits(
                (jnp.asarray(parts["hi"]), jnp.asarray(parts["lo"])),
                3, axis=0,
            ))
            hi, lo = pack_bits(jnp.asarray(snap(q)), 3, axis=0)
            new["hi"], new["lo"] = np.asarray(hi), np.asarray(lo)
        elif bits == 8:
            new["data"] = snap(parts["data"])
        else:
            q = np.asarray(unpack_bits(jnp.asarray(parts["data"]), bits,
                                       axis=0))
            new["data"] = np.asarray(
                pack_bits(jnp.asarray(snap(q)), bits, axis=0)
            )
        out[wname] = new
    return out


class ExpertOffloadManager:
    """Residency manager for one model's layer-stacked PMQ buckets.

    ``ce`` must be the serving layout: every bucket leaf stacked to
    ``[L, count, ...]`` (see ``repro.models.transformer.restack_blocks``).
    ``resident_slots`` is the per-layer device budget in expert slots,
    split across buckets proportionally to their padded counts (every
    bucket keeps ≥ 1 resident row). The manager owns :attr:`ce` — a new
    :class:`CompressedExperts` whose arrays are the resident partitions;
    callers splice it into their parameter tree and never touch the
    original full-resident arrays again.
    """

    def __init__(self, ce: CompressedExperts, *, resident_slots: int,
                 ema_decay: float = 0.8, tracer=None,
                 faults: Optional[FaultPlan] = None, degrade: bool = False,
                 max_retries: int = 3, offload_dir: Optional[str] = None,
                 host_budget_bytes: Optional[int] = None):
        if ce.resident_map is not None:
            raise ValueError("CompressedExperts is already host-offloaded")
        if tracer is None:
            from .trace import NULL_TRACER

            tracer = NULL_TRACER
        self.tracer = tracer
        # fault plane (docs/serving_robustness.md): with a FaultPlan
        # attached every upload is checksum-verified and runs the
        # retry -> re-fetch -> degrade -> fail-closed recovery ladder
        self.faults = faults
        self.degrade_enabled = bool(degrade)
        self.max_retries = int(max_retries)
        self._host_crc: Dict[Tuple[str, int, int], int] = {}
        self._degraded_rows: Dict[Tuple[str, int, int], Dict] = {}
        # (layer, global slot) -> (from_bits, to_bits), engine-lifetime
        self.degraded: Dict[Tuple[int, int], Tuple[int, int]] = {}
        self._attempts: Dict[Tuple[str, int, int], int] = {}
        # prefetch backoff: key -> logical step before which no re-attempt
        self._retry_after: Dict[Tuple[str, int, int], int] = {}
        self.meta = ce.meta
        self.num_slots = ce.num_slots
        self.ema_decay = float(ema_decay)
        self._bkeys = [f"b{i}" for i in range(len(ce.meta))]
        # full host backing store (numpy copies of every packed leaf)
        self.host: Dict[str, Dict] = {
            bk: jax.tree.map(np.asarray, ce.arrays[bk]) for bk in self._bkeys
        }
        first = jax.tree.leaves(self.host[self._bkeys[0]])[0]
        if first.ndim < 3 or first.shape[1] != ce.meta[0].count:
            raise ValueError(
                "expert offload expects layer-stacked buckets "
                f"[L, count, ...]; got leaf shape {first.shape} for "
                f"bucket count {ce.meta[0].count}"
            )
        self.num_layers = int(first.shape[0])
        self._budgets = self._split_budget(int(resident_slots))
        # residency tables (host side): slot -> row (-1 absent), row -> slot
        self.slot_row: Dict[str, np.ndarray] = {}
        self.row_slot: Dict[str, np.ndarray] = {}
        self.ema = np.zeros((self.num_layers, self.num_slots), np.float64)
        # upload counts/bytes are returned to the caller per call and
        # aggregated by ServingMetrics — the manager only tracks what the
        # metrics cannot derive: budget growths (deterministic per trace)
        self.grows = 0
        self._pinned: List[Dict[str, set]] = []
        # double-buffered async prefetch (issue_async/commit_async): the
        # one staged upload batch in flight, validated against these
        # per-bucket content versions at commit time — any mutation of a
        # bucket's device buffer between issue and commit (miss upload,
        # budget grow) bumps its version and invalidates the batch
        self._bucket_version: Dict[str, int] = {}
        self._inflight: Optional[Dict] = None
        self.begin_step()

        dev_arrays: Dict[str, Dict] = {}
        maps: Dict[str, jnp.ndarray] = {}
        for i, bk in enumerate(self._bkeys):
            r, cnt = self._budgets[i], self.meta[i].count
            # seed residency with the first r slots of each bucket — the
            # EMA prefetcher re-ranks them after the first real traffic
            sr = np.full((self.num_layers, cnt), -1, np.int32)
            sr[:, :r] = np.arange(r, dtype=np.int32)[None, :]
            self.slot_row[bk] = sr
            rs = np.full((self.num_layers, r), -1, np.int32)
            rs[:, :] = np.arange(r, dtype=np.int32)[None, :]
            self.row_slot[bk] = rs
            dev_arrays[bk] = jax.tree.map(
                lambda a: jnp.asarray(a[:, :r]), self.host[bk]
            )
            maps[bk] = jnp.asarray(np.maximum(sr, 0))
            self._bucket_version[bk] = 0
        self.ce = dataclasses.replace(
            ce, arrays=dev_arrays, resident_map=maps,
            resident_rows=tuple(self._budgets),
        )
        # three-tier mode (docs/serving_offload.md): spill the packed
        # buckets to mmap'd disk images and drop the full host copies —
        # cold rows are then served disk → byte-budgeted host cache →
        # device, and the process stops paying RAM for the whole model
        self.store: Optional[TieredExpertStore] = None
        if offload_dir is not None:
            self.store = TieredExpertStore(
                self.host, offload_dir=offload_dir,
                host_budget_bytes=host_budget_bytes, tracer=tracer,
            )
            self.host = None

    # ---------------------------------------------------------- budgeting
    def _split_budget(self, resident_slots: int) -> List[int]:
        counts = [m.count for m in self.meta]
        nb = len(counts)
        total = min(self.num_slots, max(nb, resident_slots))
        if total != resident_slots:
            warnings.warn(
                f"resident_slots={resident_slots} clamped to {total} "
                f"(floor: one row per bucket = {nb}; ceiling: "
                f"num_slots = {self.num_slots})",
                RuntimeWarning, stacklevel=3,
            )
        r = [
            max(1, min(c, int(round(resident_slots * c / self.num_slots))))
            for c in counts
        ]
        while sum(r) > total:
            i = max(range(nb), key=lambda j: r[j])
            if r[i] <= 1:
                break
            r[i] -= 1
        while sum(r) < total:
            cands = [j for j in range(nb) if r[j] < counts[j]]
            if not cands:
                break
            i = max(cands, key=lambda j: counts[j] - r[j])
            r[i] += 1
        return r

    @property
    def budgets(self) -> Tuple[int, ...]:
        return tuple(self._budgets)

    @property
    def resident_bytes(self) -> int:
        tot = 0
        for bk in self._bkeys:
            for a in jax.tree.leaves(self.ce.arrays[bk]):
                tot += a.size * a.dtype.itemsize
        return tot

    @property
    def host_bytes(self) -> int:
        """Bytes of the full backing store — the in-memory host copies,
        or the mmap'd disk images when tiered (the host then holds only
        the byte-budgeted warm cache)."""
        if self.store is not None:
            return self.store.disk_bytes
        return sum(
            a.nbytes for bk in self._bkeys
            for a in jax.tree.leaves(self.host[bk])
        )

    def resident_slots_of(self, layer: int) -> Dict[str, set]:
        """Bucket-local resident slot sets of one layer (for tests)."""
        return {
            bk: {int(s) for s in np.nonzero(self.slot_row[bk][layer] >= 0)[0]}
            for bk in self._bkeys
        }

    # ----------------------------------------------------------- plumbing
    def _row_tree(self, bk: str, layer: int, slot: int) -> Dict:
        """The pristine host payload of one (layer, bucket-local slot)
        row: the ``{w_gate/w_up/w_down: {...}}`` sub-tree sliced from the
        ``[L, count, ...]`` backing-store leaves (numpy views), or — in
        three-tier mode — fetched through the disk → host-cache ladder
        at the row's current routing heat (disk reads CRC-verify and
        promote; see :mod:`repro.serving.tierstore`)."""
        if self.store is not None:
            i = self._bkeys.index(bk)
            gslot = self.meta[i].start + int(slot)
            return self.store.row(
                bk, layer, slot, heat=float(self.ema[int(layer), gslot])
            )
        return jax.tree.map(lambda a: a[layer, slot], self.host[bk])

    def _row_crc(self, bk: str, layer: int, slot: int) -> int:
        """Lazily computed/cached checksum of the pristine host row —
        what every staged upload payload is verified against. Tiered
        stores carry the spill-time CRC manifest instead."""
        if self.store is not None:
            return self.store.crc(bk, layer, slot)
        key = (bk, int(layer), int(slot))
        crc = self._host_crc.get(key)
        if crc is None:
            crc = checksum_tree(self._row_tree(bk, layer, slot))
            self._host_crc[key] = crc
        return crc

    def _degrade_target_bits(self, i: int) -> Optional[int]:
        """to_bits for bucket ``i``: the next lower rung of the mixed-
        precision ladder (the largest smaller bucket width, else half
        this bucket's width). ``None`` means no rung below (1-bit floor)."""
        bits = self.meta[i].bits
        lower = [m.bits for m in self.meta if m.bits < bits]
        if lower:
            return max(lower)
        return bits // 2 if bits // 2 >= 1 else None

    def _degrade_or_raise(self, i: int, layer: int, slot: int) -> Dict:
        """A row's target-bit upload failed past the retry budget: build
        (and permanently cache) its precision-degraded payload, or fail
        closed with :class:`ExpertUploadFailed` when degradation is
        disabled or the row is already at the 1-bit floor."""
        bk = self._bkeys[i]
        m = self.meta[i]
        gslot = int(m.start + slot)
        to_bits = self._degrade_target_bits(i) if self.degrade_enabled else None
        if to_bits is None:
            raise ExpertUploadFailed(
                f"expert row (layer {layer}, slot {gslot}) upload failed "
                f"past {self.max_retries} retries and degradation is "
                + ("impossible at the 1-bit floor" if self.degrade_enabled
                   else "disabled")
            )
        key = (bk, int(layer), int(slot))
        if key not in self._degraded_rows:
            self._degraded_rows[key] = degrade_expert_row(
                self._row_tree(bk, layer, slot), m.bits, to_bits
            )
            self.degraded[(int(layer), gslot)] = (int(m.bits), int(to_bits))
        return self._degraded_rows[key]

    def _clear_for_upload(self, i: int, layer: int, slots, kind: str):
        """Run the recovery ladder over bucket-local ``slots`` of one
        layer before placement. Returns ``(cleared, payloads)`` —
        ``payloads`` is ``None`` on the fault-free fast path (the caller
        batch-gathers from the backing store), else one verified host
        row per cleared slot. On the ``miss`` path every slot is cleared
        (bounded immediate retries, then degrade-or-raise: the megastep
        cannot proceed without the row); on the ``prefetch`` path a
        transiently failing slot is deferred with deterministic
        logical-step backoff and simply dropped from this boundary's
        placement (a later boundary, or a miss, re-attempts)."""
        bk = self._bkeys[i]
        m = self.meta[i]
        if self.faults is None and not self._degraded_rows \
                and self.store is None:
            # fast path: the caller batch-gathers straight from the
            # in-memory backing store (tiered stores always hand back
            # per-row payloads — the gather goes through the ladder)
            return list(slots), None
        cleared: List[int] = []
        payloads: List[Dict] = []
        for s in slots:
            s = int(s)
            key = (bk, int(layer), s)
            gslot = int(m.start + s)
            degraded = self._degraded_rows.get(key)
            if degraded is not None:
                # permanently degraded: serve the lower-bit copy. The
                # fault models the *target-bit* payload's transport; the
                # degraded substitute is a different payload and bypasses
                # injection.
                fb, tb = self.degraded[(int(layer), gslot)]
                self.tracer.lifecycle(
                    "degrade", track="experts", layer=int(layer),
                    slot=gslot, from_bits=fb, to_bits=tb,
                )
                cleared.append(s)
                payloads.append(degraded)
                continue
            if self.faults is None:
                cleared.append(s)
                payloads.append(self._row_tree(bk, layer, s))
                continue
            was_deferred = key in self._retry_after
            if kind == "prefetch" and was_deferred:
                if self.faults.step < self._retry_after[key]:
                    continue  # still backing off — skip this boundary
                del self._retry_after[key]
                self.tracer.lifecycle(
                    "retry", track="experts", path="prefetch",
                    layer=int(layer), slot=gslot,
                    attempt=int(self._attempts.get(key, 0)),
                )
            attempts = int(self._attempts.get(key, 0))
            while True:
                spec = self.faults.fire("upload", (int(layer), gslot))
                if spec is not None:
                    self.tracer.lifecycle(
                        "fault", track="experts", site="upload",
                        mode=spec.mode, layer=int(layer), slot=gslot,
                        path=kind,
                    )
                if spec is None or spec.mode == "corrupt":
                    row = self._row_tree(bk, layer, s)
                    if spec is not None:
                        row = corrupt_tree(row)
                    if checksum_tree(row) != self._row_crc(bk, layer, s):
                        # integrity check caught the damage: re-fetch the
                        # pristine host payload (one recovered retry)
                        self.tracer.lifecycle(
                            "retry", track="experts", path="refetch",
                            layer=int(layer), slot=gslot,
                            attempt=attempts + 1,
                        )
                        row = self._row_tree(bk, layer, s)
                    cleared.append(s)
                    payloads.append(row)
                    self._attempts.pop(key, None)
                    break
                # mode == "fail": transient/persistent I/O error
                attempts += 1
                self._attempts[key] = attempts
                if attempts > self.max_retries:
                    # persistent: degrade to the next ladder rung (or
                    # fail closed). The degraded payload bypasses
                    # injection — see above.
                    row = self._degrade_or_raise(i, layer, s)
                    fb, tb = self.degraded[(int(layer), gslot)]
                    self.tracer.lifecycle(
                        "degrade", track="experts", layer=int(layer),
                        slot=gslot, from_bits=fb, to_bits=tb,
                    )
                    cleared.append(s)
                    payloads.append(row)
                    break
                if kind == "prefetch":
                    # deterministic backoff in logical steps, never
                    # seconds — replay-identical across runs
                    self._retry_after[key] = self.faults.step + (1 << attempts)
                    break  # deferred; a later boundary re-attempts
                # miss path: bounded immediate retries
                self.tracer.lifecycle(
                    "retry", track="experts", path="miss",
                    layer=int(layer), slot=gslot, attempt=attempts,
                )
        return cleared, payloads

    def _build_upload(self, bk: str, triples, payloads=None):
        """Build the post-upload device buffers for ``(layer, row,
        slot)`` placements — one batched scatter per packed leaf per
        bucket, regardless of how many layers the placements span (a
        per-layer ``.set`` would rebuild the whole [L, R, ...] buffer
        once per layer). Pure with respect to the manager: jax arrays
        are immutable, so ``.at[].set`` returns *new* buffers and the
        live ones keep serving until the caller swaps them in — exactly
        the double-buffering :meth:`issue_async` rides on. ``payloads``
        (one verified host-row tree per triple, from
        :meth:`_clear_for_upload`) replaces the backing-store gather on
        the fault/tiered paths. Returns ``(new_arrays, nbytes)``."""
        l_idx = np.asarray([t[0] for t in triples], np.int32)
        r_idx = np.asarray([t[1] for t in triples], np.int32)
        s_idx = np.asarray([t[2] for t in triples], np.int32)
        nbytes = 0

        if payloads is None:
            def up(dev, host):
                nonlocal nbytes
                src = host[l_idx, s_idx]  # [n, ...]
                nbytes += src.nbytes
                return dev.at[l_idx, r_idx].set(jnp.asarray(src))

            return jax.tree.map(
                up, self.ce.arrays[bk], self.host[bk]
            ), nbytes

        stacked = jax.tree.map(lambda *rows: np.stack(rows), *payloads)

        def up_rows(dev, src):
            nonlocal nbytes
            nbytes += src.nbytes
            return dev.at[l_idx, r_idx].set(jnp.asarray(src))

        return jax.tree.map(
            up_rows, self.ce.arrays[bk], stacked
        ), nbytes

    def _upload_batch(self, bk: str, triples, payloads=None) -> int:
        """Synchronous host→device copy: build the new buffers and swap
        them in immediately, invalidating any in-flight async batch for
        this bucket (its staged buffers no longer contain these rows)."""
        if not triples:
            return 0
        new_arrays, nbytes = self._build_upload(bk, triples, payloads)
        self.ce.arrays[bk] = new_arrays
        self._bucket_version[bk] += 1
        return nbytes

    def _refresh_map(self, bk: str) -> None:
        self.ce.resident_map[bk] = jnp.asarray(
            np.maximum(self.slot_row[bk], 0).astype(np.int32)
        )

    def _grow(self, i: int, need: int) -> None:
        """Enlarge bucket i's resident buffer to ``need`` rows (all
        layers). Changes leaf shapes — the jitted programs re-specialize
        once — and is only taken when a step's working set cannot fit the
        configured budget (correctness beats the budget)."""
        bk = self._bkeys[i]
        old = self._budgets[i]
        new_r = min(self.meta[i].count, int(need))
        if new_r <= old:
            return
        pad = new_r - old
        self.row_slot[bk] = np.concatenate(
            [self.row_slot[bk],
             np.full((self.num_layers, pad), -1, np.int32)], axis=1,
        )
        self.ce.arrays[bk] = jax.tree.map(
            lambda a: jnp.concatenate(
                [a, jnp.zeros((a.shape[0], pad) + a.shape[2:], a.dtype)],
                axis=1,
            ),
            self.ce.arrays[bk],
        )
        self._budgets[i] = new_r
        self.ce.resident_rows = tuple(self._budgets)
        self._bucket_version[bk] += 1  # staged async buffers now stale
        self.grows += 1
        self.tracer.instant(
            "expert_budget_grow", track="experts", cat="offload",
            bucket=i, rows_before=old, rows_after=new_r,
        )

    def _place(self, i: int, layer: int, want, protected, score_fn):
        """Install bucket-local slots ``want`` into bucket ``i``'s rows of
        one layer, filling free rows first and then evicting the
        lowest-``score_fn`` rows whose slot is not in ``protected``.
        Updates the host-side tables and returns the ``(layer, row,
        slot)`` placements; the caller batch-uploads them
        (:meth:`_upload_batch`) and refreshes the device map.
        """
        bk = self._bkeys[i]
        sr = self.slot_row[bk]
        rows = self.row_slot[bk]
        r_i = self._budgets[i]
        free = [j for j in range(r_i) if rows[layer, j] < 0]
        evictable = sorted(
            (j for j in range(r_i)
             if rows[layer, j] >= 0 and int(rows[layer, j]) not in protected),
            key=lambda j: (score_fn(int(rows[layer, j])),
                           int(rows[layer, j])),
        )
        targets = (free + evictable)[: len(want)]
        placed = []
        for s, j in zip(want, targets):
            old = int(rows[layer, j])
            if old >= 0:
                sr[layer, old] = -1
            rows[layer, j] = s
            sr[layer, s] = j
            placed.append((layer, j, s))
        return placed

    # ------------------------------------------------------ step protocol
    def begin_step(self) -> None:
        """Reset the per-step pin sets. The engine calls this before each
        jitted-program replay loop; every slot reported used during the
        loop stays pinned (never evicted) until the loop accepts."""
        self._pinned = [
            {bk: set() for bk in self._bkeys} for _ in range(self.num_layers)
        ]

    def ensure_resident(self, counts: np.ndarray) -> Tuple[int, int]:
        """Make the last program run's *authentic* working set resident.

        ``counts`` is the run's ``slot_counts`` output with rows in
        **computation order**: ``[L, num_slots]`` for a single-step
        program, or ``[H·L, num_slots]`` (step-major: row ``k`` is layer
        ``k % L`` of horizon step ``k // L``) for a fused decode
        megastep — whose union over steps is the horizon working set.
        Returns ``(uploads, bytes)`` — ``uploads == 0`` means the run's
        whole working set was already resident (the run is *accepted*:
        its outputs are bit-identical to the all-resident engine).
        Otherwise the caller must replay the whole program after this
        synchronous upload (KV writes are position-addressed and the
        token sequence is deterministic per megastep key, so a megastep
        replay is idempotent).

        Usage is only trusted up to the **first row with a miss**: rows
        before it computed with correct expert rows, so their routing —
        and the missed row's own routing — is authentic; later rows
        (deeper layers, and with a horizon every subsequent fused step,
        whose input token depends on the full previous step) routed on
        garbage activations and are ignored until a replay reaches them
        with correct inputs. Every pinned slot is therefore part of the
        true working set — phantom usage can never inflate uploads or
        trigger a budget grow — and each replay extends the correct
        prefix by ≥ 1 row, so the loop accepts within ``rows`` (≤ H·L)
        replays. Evicts only unpinned rows, coldest EMA first.
        """
        rows = counts.reshape(-1, self.num_slots)
        # fast path (the common all-hit case): nothing dispatched-to is
        # non-resident, so the run is accepted without touching the pin
        # sets — pins only matter across replays, and slots pinned by an
        # earlier iteration are already resident (eviction protects them)
        resident = np.concatenate(
            [self.slot_row[bk] >= 0 for bk in self._bkeys], axis=1
        )
        layer_of = np.arange(rows.shape[0]) % self.num_layers
        if not np.any((rows > 0) & ~resident[layer_of]):
            return 0, 0
        with self.tracer.span("expert_upload", track="experts",
                              cat="offload", kind="miss") as span:
            ups, nbytes = self._upload_missing(rows, layer_of)
            span.args.update(uploads=ups, bytes=nbytes)
            span.record = ups > 0  # kept only once rows were uploaded
        return ups, nbytes

    def _upload_missing(self, rows, layer_of) -> Tuple[int, int]:
        ups = 0
        nbytes = 0
        pending = {bk: [] for bk in self._bkeys}
        pend_rows = {bk: [] for bk in self._bkeys}
        for k in range(rows.shape[0]):
            l = int(layer_of[k])
            row_missed = False
            for i, bk in enumerate(self._bkeys):
                m = self.meta[i]
                used = np.nonzero(rows[k, m.start:m.start + m.count] > 0)[0]
                pin = self._pinned[l][bk]
                pin.update(int(u) for u in used)
                missing = [s for s in sorted(pin) if self.slot_row[bk][l, s] < 0]
                if not missing:
                    continue
                row_missed = True
                if len(pin) > self._budgets[i]:
                    self._grow(i, len(pin))
                # recovery ladder first: on the miss path every slot is
                # cleared (retried, degraded) or a typed fault is raised
                missing, rows_pay = self._clear_for_upload(
                    i, l, missing, "miss"
                )
                # pin ≤ budget now, so every missing slot finds a row
                placed = self._place(
                    i, l, missing, pin,
                    lambda s, l=l, m=m: self.ema[l, m.start + s],
                )
                assert len(placed) == len(missing), "pin set exceeds budget"
                pending[bk].extend(placed)
                if rows_pay is not None:
                    pend_rows[bk].extend(rows_pay)
                ups += len(placed)
            if row_missed:
                break  # later rows routed on garbage — replay first
        for bk in self._bkeys:  # one batched upload + map per bucket
            if pending[bk]:
                nbytes += self._upload_batch(
                    bk, pending[bk], pend_rows[bk] or None
                )
                self._refresh_map(bk)
        return ups, nbytes

    def update_stats(self, counts: np.ndarray) -> None:
        """Fold an accepted program's dispatch counts into the routing
        EMA. Accepts ``[L, num_slots]`` or a fused megastep's
        ``[H·L, num_slots]`` / ``[H, L, num_slots]`` — horizon steps are
        summed, so one EMA update per accepted megastep sees the whole
        horizon's traffic (a smoother, more predictive prefetch signal
        than per-token updates)."""
        counts = counts.reshape(-1, self.num_layers, self.num_slots).sum(0)
        d = self.ema_decay
        self.ema = d * self.ema + (1.0 - d) * counts.astype(np.float64)

    def residency_targets(self) -> Tuple[Tuple[int, int, Tuple[int, ...]], ...]:
        """Pure target-set computation: the declarative half of prefetch.

        Per (layer, bucket): the top-``R_i`` slots by EMA score are the
        *desired* resident set. Stable ranking (score desc, slot asc)
        keeps the selection deterministic and churn-free on ties.
        Returns one ``(bucket_idx, layer, desired_slots)`` triple for
        every (layer, bucket) whose desired set is not fully resident —
        an empty tuple means residency already matches the target.
        Reads routing EMA and residency maps; mutates **nothing** (the
        controller calls this at planning time; convergence happens in
        :meth:`apply_residency`).
        """
        targets = []
        for l in range(self.num_layers):
            for i, bk in enumerate(self._bkeys):
                m = self.meta[i]
                r_i = self._budgets[i]
                if r_i >= m.count:
                    continue
                scores = self.ema[l, m.start:m.start + m.count]
                desired = tuple(
                    int(s) for s in np.argsort(-scores, kind="stable")[:r_i]
                )
                if any(self.slot_row[bk][l, s] < 0 for s in desired):
                    targets.append((i, l, desired))
        return tuple(targets)

    def apply_residency(
        self, targets: Tuple[Tuple[int, int, Tuple[int, ...]], ...]
    ) -> Tuple[int, int]:
        """Converge residency toward :meth:`residency_targets` output:
        missing desired slots are uploaded over the coldest undesired
        residents (one batched upload + device-map refresh per bucket).
        Returns ``(uploads, bytes)``.
        """
        if not targets:
            return 0, 0
        with self.tracer.span("expert_upload", track="experts",
                              cat="offload", kind="prefetch") as span:
            ups, nbytes = self._upload_targets(targets)
            span.args.update(uploads=ups, bytes=nbytes)
            span.record = ups > 0  # kept only once rows were uploaded
        return ups, nbytes

    def _upload_targets(self, targets) -> Tuple[int, int]:
        ups = 0
        nbytes = 0
        pending = {bk: [] for bk in self._bkeys}
        pend_rows = {bk: [] for bk in self._bkeys}
        for i, l, desired in targets:
            bk = self._bkeys[i]
            m = self.meta[i]
            scores = self.ema[l, m.start:m.start + m.count]
            want = sorted(
                s for s in desired if self.slot_row[bk][l, s] < 0
            )
            if not want:
                continue
            # recovery ladder: transiently failing prefetch uploads are
            # deferred with logical-step backoff (dropped from this
            # boundary's placement); the rest arrive verified
            want, rows_pay = self._clear_for_upload(i, l, want, "prefetch")
            if not want:
                continue
            placed = self._place(i, l, want, set(desired),
                                 lambda s, scores=scores: scores[s])
            pending[bk].extend(placed)
            if rows_pay is not None:
                pend_rows[bk].extend(rows_pay)
            ups += len(placed)
        for bk in self._bkeys:  # one batched upload + map per bucket
            if pending[bk]:
                nbytes += self._upload_batch(
                    bk, pending[bk], pend_rows[bk] or None
                )
                self._refresh_map(bk)
        return ups, nbytes

    def prefetch(self) -> Tuple[int, int]:
        """Upload the EMA-hottest slots ahead of need (between steps):
        :meth:`residency_targets` (pure) followed by
        :meth:`apply_residency` (converge). Kept as the one-call form
        for direct drivers and tests; the engine goes through the
        resource controller, which folds the target set into its
        boundary plan as an ``upload_experts`` action.
        """
        return self.apply_residency(self.residency_targets())

    # ------------------------------------------- async double-buffering
    def issue_async(self, targets) -> Tuple[int, int]:
        """Stage one boundary's prefetch uploads *without touching the
        live residency state* — the overlap half of async expert
        streaming (docs/serving_offload.md).

        The engine calls this right after dispatching a megastep: the
        recovery ladder runs immediately (an in-flight transfer failure
        is a prefetch failure — deferred with the same deterministic
        backoff), payload rows are gathered through the tier ladder, and
        the post-upload device buffers are *built* (``.at[].set`` on
        immutable jax arrays returns new buffers, so the dispatch is
        enqueued and the copy proceeds while the megastep computes) but
        **not** swapped in. Placement runs on copies of the residency
        tables; the live tables — and the live buffers the running
        megastep (and any miss replay) uses — are untouched until
        :meth:`commit_async` flips them at the next boundary. At most
        one batch is in flight; a second issue before commit is a no-op.
        Returns ``(uploads, bytes)`` staged.
        """
        if not targets or self._inflight is not None:
            return 0, 0
        t0_us = self.tracer.now_us()
        live_sr, live_rs = self.slot_row, self.row_slot
        # placement mutates the snapshot tables only: the in-flight
        # megastep keeps a consistent (tables, buffers, map) view
        self.slot_row = {bk: a.copy() for bk, a in live_sr.items()}
        self.row_slot = {bk: a.copy() for bk, a in live_rs.items()}
        versions = dict(self._bucket_version)
        budgets = tuple(self._budgets)
        pending = {bk: [] for bk in self._bkeys}
        pend_rows = {bk: [] for bk in self._bkeys}
        ups = 0
        nbytes = 0
        staged_arrays: Dict[str, Dict] = {}
        try:
            for i, l, desired in targets:
                bk = self._bkeys[i]
                m = self.meta[i]
                scores = self.ema[l, m.start:m.start + m.count]
                want = sorted(
                    s for s in desired if self.slot_row[bk][l, s] < 0
                )
                if not want:
                    continue
                want, rows_pay = self._clear_for_upload(
                    i, l, want, "prefetch"
                )
                if not want:
                    continue
                placed = self._place(i, l, want, set(desired),
                                     lambda s, scores=scores: scores[s])
                pending[bk].extend(placed)
                if rows_pay is not None:
                    pend_rows[bk].extend(rows_pay)
                ups += len(placed)
            for bk in self._bkeys:
                if pending[bk]:
                    staged_arrays[bk], nb = self._build_upload(
                        bk, pending[bk], pend_rows[bk] or None
                    )
                    nbytes += nb
        finally:
            staged_sr, staged_rs = self.slot_row, self.row_slot
            self.slot_row, self.row_slot = live_sr, live_rs
        if ups == 0:
            return 0, 0
        self._inflight = {
            "arrays": staged_arrays,
            "slot_row": staged_sr,
            "row_slot": staged_rs,
            "versions": versions,
            "budgets": budgets,
            "uploads": ups,
            "nbytes": nbytes,
            "t0_us": t0_us,
        }
        return ups, nbytes

    def commit_async(self) -> Tuple[int, int, int, float]:
        """Flip the double buffer at a megastep boundary: swap the
        staged device buffers, residency tables, and device maps in —
        unless any bucket's content version moved since issue (a miss
        upload or budget grow landed mid-flight), in which case the
        whole staged batch is **dropped** (the stale buffers are missing
        those rows; the next boundary re-plans from fresh targets).
        Dropping can never corrupt outputs — residency placement is
        output-invariant and the miss-replay backstop is unchanged.
        Returns ``(committed_uploads, dropped_uploads, bytes, wait_s)``
        where ``wait_s`` is the residual wall time spent waiting for
        staged transfers that had not finished landing (the un-hidden
        remainder; ~0 when the megastep fully covered the copy).
        """
        inf, self._inflight = self._inflight, None
        if inf is None:
            return 0, 0, 0, 0.0
        if tuple(self._budgets) != inf["budgets"] or any(
            self._bucket_version[bk] != v
            for bk, v in inf["versions"].items()
        ):
            self.tracer.instant(
                "expert_upload_dropped", track="experts", cat="offload",
                uploads=inf["uploads"],
            )
            return 0, inf["uploads"], 0, 0.0
        t0 = time.time()
        for arrs in inf["arrays"].values():
            jax.block_until_ready(jax.tree.leaves(arrs))
        wait_s = time.time() - t0
        for bk, arrs in inf["arrays"].items():
            self.ce.arrays[bk] = arrs
            self._bucket_version[bk] += 1
        self.slot_row = inf["slot_row"]
        self.row_slot = inf["row_slot"]
        for bk in inf["arrays"]:
            self._refresh_map(bk)
        self.tracer.complete(
            "expert_upload", track="experts", cat="offload",
            start_us=inf["t0_us"],
            args={"kind": "async", "uploads": inf["uploads"],
                  "bytes": inf["nbytes"]},
        )
        return inf["uploads"], 0, inf["nbytes"], wait_s

    # -------------------------------------------------------- housekeeping
    def prune_backoff(self) -> int:
        """Drop prefetch-backoff entries that can never be consumed
        again: rows that were permanently **degraded** (their target-bit
        upload is never re-attempted — ``_clear_for_upload`` serves the
        cached lower-rung copy first) and rows that became **resident**
        through another path (a miss upload landed them, proving the
        transport; the deferral is moot). The controller calls this at
        every plan boundary, so ``_retry_after`` stays bounded by the
        set of live, non-resident, still-failing rows instead of
        accumulating one entry per fault ever fired. Returns the number
        of entries pruned."""
        stale = [
            key for key in self._retry_after
            if key in self._degraded_rows
            or self.slot_row[key[0]][key[1], key[2]] >= 0
        ]
        for key in stale:
            del self._retry_after[key]
            if key in self._degraded_rows:
                self._attempts.pop(key, None)
        return len(stale)
