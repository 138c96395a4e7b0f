"""Block-table paged KV cache (vLLM-style) for the serving engine.

One preallocated pool ``[L, num_blocks, block_size, Hkv, dh]`` per K and
V replaces the dense ``[L, B, S, Hkv, dh]`` cache: a slot's logical
position ``p`` lives at physical page ``block_tables[slot, p // bs]``,
offset ``p % bs``. Slots of different lengths therefore share the pool —
a finished request's pages return to the free list immediately and the
next queued request reuses them, so pool sizing follows the *sum* of
live context lengths instead of ``max_slots × max_len``.

Slots grow **on demand**: admission reserves pages for the prompt only
and :meth:`PagedKVCache.grow` appends decode pages between jitted
programs. With a fused decode horizon the engine reserves **horizon
ahead** — before each megastep every active slot is grown to cover all
``min(H, budget)`` KV writes the fused program will perform
(:meth:`slot_deficit` computes the gap), so growth, preemption and every
other pool-pressure decision happen at megastep boundaries only; the
pool can still be sized well below the worst-case ``prompt + max_new``
sum. Under pressure a victim slot's pages move to a host-memory backing
store (:meth:`swap_out` → :class:`SwappedKV` → :meth:`swap_in`) — the
device pages are freed immediately and the bit-exact KV is restored when
the preempted request is re-admitted.

Host-side bookkeeping (:class:`BlockAllocator`, slot tables) is plain
python/numpy — it runs between jitted steps. Device-side gathers go
through :func:`repro.kernels.ops.paged_attention`; writes compute a flat
destination ``page * bs + offset`` per new token inside the jitted step
(:func:`repro.models.transformer.paged_decode_step`).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import jax.numpy as jnp
import numpy as np

from .faults import SwapFault, checksum_tree

__all__ = [
    "BlockAllocator", "PagedKVCache", "PoolExhausted", "SwappedKV",
    "PrefixCache", "PrefixEntry",
]


class PoolExhausted(RuntimeError):
    """Raised when an allocation asks for more pages than are free."""


class BlockAllocator:
    """Refcounted free-list allocator over ``num_blocks`` fixed-size
    pages — copy-on-write sharing for the prefix cache.

    :meth:`alloc` hands out pages at refcount 1; :meth:`incref` adds a
    holder (a prefix-cache entry, or a second slot sharing a cached
    prefix page); :meth:`free` *releases one hold* — the page returns to
    the free list only when its refcount hits zero, so releasing a slot
    whose prefix pages are still cached (or shared with a live
    neighbor) never corrupts the other holders.

    Invariants (tested): an allocation either returns exactly ``n``
    distinct free pages or raises :class:`PoolExhausted` leaving state
    untouched; freeing/increfing a page not currently allocated raises
    ``ValueError`` (double-free guard); a freed page becomes allocatable
    again only at refcount 0 (recycling); ``num_free +
    len(allocated) == num_blocks`` always.
    """

    def __init__(self, num_blocks: int):
        if num_blocks <= 0:
            raise ValueError(f"num_blocks must be positive, got {num_blocks}")
        self.num_blocks = num_blocks
        self._free: List[int] = list(range(num_blocks - 1, -1, -1))
        self._refcount: Dict[int, int] = {}

    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def allocated(self) -> frozenset:
        """Pages with refcount ≥ 1."""
        return frozenset(self._refcount)

    @property
    def free_pages(self) -> tuple:
        """Snapshot of the free list (for invariant checks)."""
        return tuple(self._free)

    def refcount(self, block: int) -> int:
        """Current holders of ``block`` (0 = free)."""
        return self._refcount.get(block, 0)

    def alloc(self, n: int) -> List[int]:
        """Return ``n`` distinct free pages at refcount 1;
        ``alloc(0) == []`` and is a guaranteed no-op on allocator
        state."""
        if n < 0:
            raise ValueError(f"cannot allocate {n} blocks")
        if n == 0:
            return []
        if n > len(self._free):
            raise PoolExhausted(
                f"requested {n} blocks, {len(self._free)} free "
                f"of {self.num_blocks}"
            )
        blocks = [self._free.pop() for _ in range(n)]
        for b in blocks:
            self._refcount[b] = 1
        return blocks

    def incref(self, blocks: List[int]) -> None:
        """Add one hold to each page — atomically: every page is
        validated live before any count moves (an unknown page raises
        ``ValueError`` with state untouched). Duplicates in ``blocks``
        are allowed and each add a hold (a slot sharing the same page
        twice cannot happen, but two entries of the prefix cache may)."""
        for b in blocks:
            if b not in self._refcount:
                raise ValueError(f"incref of unallocated block {b}")
        for b in blocks:
            self._refcount[b] += 1

    def free(self, blocks: List[int]) -> None:
        """Release one hold per page — atomically: the whole list is
        validated (allocated, no duplicates) before any count moves, so
        a bad entry raises ``ValueError`` with allocator state untouched
        instead of half-freeing the good prefix. Pages reaching
        refcount 0 return to the free list; shared pages simply drop a
        holder."""
        seen: set = set()
        for b in blocks:
            if b not in self._refcount or b in seen:
                raise ValueError(f"double free / unknown block {b}")
            seen.add(b)
        for b in blocks:
            self._refcount[b] -= 1
            if self._refcount[b] == 0:
                del self._refcount[b]
                self._free.append(b)


@dataclasses.dataclass
class SwappedKV:
    """Host-memory backing store of one preempted slot's KV pages.

    Whole pages are saved (the partial tail page included), so
    :meth:`PagedKVCache.swap_in` restores a bit-exact cache — a resumed
    request's re-read KV is indistinguishable from never having been
    preempted. Quantized pools additionally save the per-row scale/zero
    tables (``quant``), so codes and their dequant parameters travel
    together and restore bit-exactly too.
    """

    k: np.ndarray  # [L, n_pages, BS, Hkv, dh]
    v: np.ndarray
    n_tokens: int  # valid kv entries covered by the saved pages
    quant: Optional[Dict[str, np.ndarray]] = None  # [L, n_pages, BS, Hkv] × 4
    # CRC of the pristine payload at swap-out time; swap-in verifies it
    # and raises SwapFault on mismatch (engine recovers by recompute
    # re-prefill — docs/serving_robustness.md)
    checksum: Optional[int] = None

    def payload_checksum(self) -> int:
        tree = {"k": self.k, "v": self.v}
        if self.quant is not None:
            tree["quant"] = self.quant
        return checksum_tree(tree)

    @property
    def n_pages(self) -> int:
        return self.k.shape[1]

    @property
    def nbytes(self) -> int:
        n = self.k.nbytes + self.v.nbytes
        if self.quant is not None:
            n += sum(a.nbytes for a in self.quant.values())
        return n


@dataclasses.dataclass
class PrefixEntry:
    """One cached prefix: an exact token string → the physical pages
    holding its KV. ``pages`` covers tokens ``[0, n_tokens)`` in order;
    every page carries one allocator hold owned by this entry.
    ``last_logits`` is set on **full-prompt** entries only — the
    prompt's final-token logits, letting a full hit skip prefill
    entirely (the first sampled token is derived from the identical
    array the non-cached path would have computed)."""

    key: bytes  # prompt[:n_tokens].tobytes() — exact, collision-free
    pages: List[int]
    n_tokens: int
    last_logits: Optional[np.ndarray] = None
    hits: int = 0


class PrefixCache:
    """LRU prefix → physical-page-run cache layered on the block-table
    indirection (tentpole (a) of ROADMAP item 2).

    **Key granularity.** Keys are the *exact token bytes* of the prefix
    (no lossy hashing — a hash collision would silently serve wrong KV).
    A fresh prompt registers one entry per full-page boundary
    (``prompt[:j·BS]`` for ``j = 1..P//BS``) plus a full-prompt entry
    (which may end mid-page and carries ``last_logits``), so a later
    prompt sharing any page-aligned prefix — a system-prompt template —
    matches the longest cached boundary even when its suffix diverges.

    **Sharing rules.** Page-aligned entry pages are *immutable* (fully
    covered by prompt tokens; the owner never writes them again) and are
    shared directly via :meth:`BlockAllocator.incref`. The full-prompt
    entry's partial tail page is the one page the owning slot keeps
    writing (its decode tokens land at rows ≥ ``P % BS``), so a sharer
    receives a private **copy-on-write** duplicate at admission — the
    first divergent write is its first decode token, so the copy is
    made eagerly (``cow_copy`` trace event) rather than trapped.

    **Eviction.** Entries are LRU (lookup refreshes recency); evicting
    an entry releases one hold per page — pages held *only* by the cache
    return to the free list, pages shared with live slots stay until
    the slots finish. :meth:`reclaimable` counts the pages eviction
    could actually free right now, which admission/growth add to the
    allocator's free count before resorting to preemption.
    """

    def __init__(self, allocator: BlockAllocator, block_size: int,
                 tracer=None):
        from collections import OrderedDict

        if tracer is None:
            from .trace import NULL_TRACER

            tracer = NULL_TRACER
        self.allocator = allocator
        self.block_size = block_size
        self.tracer = tracer
        self._entries: "OrderedDict[bytes, PrefixEntry]" = OrderedDict()
        # page → number of cache entries holding it (≤ allocator refcount)
        self.holds: Dict[int, int] = {}

    # ------------------------------------------------------------- state
    @property
    def n_entries(self) -> int:
        return len(self._entries)

    @property
    def pages_held(self) -> frozenset:
        return frozenset(self.holds)

    def snapshot(self) -> List[PrefixEntry]:
        """Entries in LRU order (oldest first), **without** touching
        recency or hit counts — the controller's planning-time view.
        Callers must treat the entries as read-only; actual lookups
        (which refresh LRU state) happen at plan execution via
        :meth:`lookup`."""
        return list(self._entries.values())

    def reclaimable(self, protect: frozenset = frozenset()) -> int:
        """Pages :meth:`evict_for` could actually free right now: count
        the holds dropped if every entry *not touching* ``protect``
        (pages an in-flight admission is about to share — their entries
        are skipped by eviction) were evicted; a page frees iff that
        covers its whole allocator refcount (no live-slot reference, no
        protected-entry hold)."""
        drop: Dict[int, int] = {}
        for ent in self._entries.values():
            if protect and not protect.isdisjoint(ent.pages):
                continue
            for pg in ent.pages:
                drop[pg] = drop.get(pg, 0) + 1
        return sum(
            1 for pg, d in drop.items()
            if d == self.allocator.refcount(pg)
        )

    # ------------------------------------------------------------ lookup
    def lookup(self, prompt: np.ndarray) -> Optional[PrefixEntry]:
        """Longest cached prefix of ``prompt``: the full prompt first,
        then page boundaries descending. A hit moves the entry to the
        LRU tail (most recent)."""
        prompt = np.ascontiguousarray(prompt, np.int32)
        p = len(prompt)
        bs = self.block_size
        probes = [p] + [j * bs for j in range(p // bs, 0, -1)
                        if j * bs != p]
        for n in probes:
            ent = self._entries.get(prompt[:n].tobytes())
            if ent is not None:
                self._entries.move_to_end(ent.key)
                ent.hits += 1
                return ent
        return None

    # ---------------------------------------------------------- register
    def register(self, prompt: np.ndarray, blocks: List[int],
                 last_logits: Optional[np.ndarray] = None) -> int:
        """Cache every page-boundary prefix of ``prompt`` plus the full
        prompt (with its final-token logits), mapping onto the slot's
        ``blocks``. Existing keys are left untouched (their pages
        already hold identical KV — registering the same bytes twice
        must not leak holds). Returns the number of new entries."""
        prompt = np.ascontiguousarray(prompt, np.int32)
        p = len(prompt)
        bs = self.block_size
        added = 0
        bounds = [j * bs for j in range(1, p // bs + 1)]
        if p % bs or not bounds:
            bounds.append(p)  # full-prompt entry ends mid-page
        for n in bounds:
            key = prompt[:n].tobytes()
            npages = -(-n // bs)
            logits = last_logits if n == p else None
            ent = self._entries.get(key)
            if ent is not None:
                # same bytes ⇒ same KV content; keep the incumbent pages
                # but attach logits if this registration has them and the
                # incumbent (a boundary entry of a longer prompt) doesn't
                if logits is not None and ent.last_logits is None:
                    ent.last_logits = np.asarray(logits)
                continue
            pages = list(blocks[:npages])
            self.allocator.incref(pages)
            for pg in pages:
                self.holds[pg] = self.holds.get(pg, 0) + 1
            ent = PrefixEntry(
                key=key, pages=pages, n_tokens=n,
                last_logits=(
                    np.asarray(logits) if logits is not None else None
                ),
            )
            self._entries[key] = ent
            added += 1
        return added

    # ----------------------------------------------------------- evict
    def _release(self, ent: PrefixEntry) -> None:
        self.allocator.free(ent.pages)
        for pg in ent.pages:
            self.holds[pg] -= 1
            if self.holds[pg] == 0:
                del self.holds[pg]
        del self._entries[ent.key]

    def evict_for(self, n_pages: int,
                  protect: frozenset = frozenset()) -> int:
        """Evict LRU entries until ``n_pages`` pages are free (or no
        evictable entry remains). Entries touching ``protect`` — pages
        an in-flight admission is sharing — are skipped. Returns the
        number of entries evicted."""
        evicted = 0
        while self.allocator.num_free < n_pages:
            victim = None
            for ent in self._entries.values():  # LRU order
                if not protect or protect.isdisjoint(ent.pages):
                    victim = ent
                    break
            if victim is None:
                break
            self._release(victim)
            evicted += 1
            self.tracer.instant(
                "prefix_evict", track="pool", cat="kv",
                tokens=victim.n_tokens, pages=len(victim.pages),
                free=self.allocator.num_free,
            )
        return evicted

    def clear(self) -> None:
        """Drop every entry (releases all cache holds) — drain-time
        teardown and the sim harness's pool-accounting hook."""
        for ent in list(self._entries.values()):
            self._release(ent)

    # ------------------------------------------------------- invariants
    def check_consistency(self) -> None:
        """Cache-side invariants: holds mirror entries exactly; every
        held page is live in the allocator with refcount ≥ holds; entry
        page counts match their token counts."""
        recount: Dict[int, int] = {}
        for ent in self._entries.values():
            if len(ent.pages) != -(-ent.n_tokens // self.block_size):
                raise AssertionError(
                    f"prefix entry {ent.n_tokens} tokens / "
                    f"{len(ent.pages)} pages mismatch"
                )
            for pg in ent.pages:
                recount[pg] = recount.get(pg, 0) + 1
        if recount != self.holds:
            raise AssertionError("prefix cache holds out of sync")
        for pg, h in self.holds.items():
            if self.allocator.refcount(pg) < h:
                raise AssertionError(
                    f"page {pg}: allocator refcount "
                    f"{self.allocator.refcount(pg)} < cache holds {h}"
                )


@dataclasses.dataclass
class PagedKVCache:
    """Pool arrays + per-slot block tables for ``max_slots`` sequences.

    The jnp pool arrays ``k``/``v`` are *donated* through the jitted
    decode/prefill steps — the engine reassigns them after every call.
    Everything else is host state.
    """

    k: jnp.ndarray  # [L, NB, BS, Hkv, dh] — uint8 codes when kv_bits set
    v: jnp.ndarray
    block_size: int
    max_slots: int
    max_blocks_per_slot: int
    allocator: BlockAllocator
    block_tables: np.ndarray  # [max_slots, MB] int32, 0-padded
    slot_blocks: Dict[int, List[int]]
    free_slots: List[int]
    # int8 per-page KV quantization (tentpole (b) of ROADMAP item 2):
    # kv_bits selects the code width (None = fp pools, today's path
    # untouched); ``quant`` holds the per-row affine dequant tables
    # {k_scale, k_zero, v_scale, v_zero}, each [L, NB, BS, Hkv] f32 —
    # page-granular metadata living alongside the pool exactly like the
    # block tables, donated through the jitted steps with the pools.
    kv_bits: Optional[int] = None
    quant: Optional[Dict[str, jnp.ndarray]] = None
    # shared-prefix page cache (None = disabled); admission shares its
    # page runs copy-on-write via the refcounted allocator
    prefix: Optional[PrefixCache] = None
    # optional FaultPlan (repro.serving.faults): swap_out / swap_in
    # consult it to inject payload corruption and I/O errors
    faults: object = None
    # device copy of block_tables, rebuilt only after admission/release —
    # the per-token decode loop must not pay a host→device upload
    _tables_device: object = None
    # span tracer (repro.serving.trace.SpanTracer); the engine installs
    # its own, standalone caches keep the shared no-op singleton
    tracer: object = None

    def __post_init__(self):
        if self.tracer is None:
            from .trace import NULL_TRACER

            self.tracer = NULL_TRACER

    def set_tracer(self, tracer) -> None:
        self.tracer = tracer
        if self.prefix is not None:
            self.prefix.tracer = tracer

    @classmethod
    def create(
        cls,
        cfg,
        *,
        num_blocks: int,
        block_size: int,
        max_slots: int,
        max_blocks_per_slot: int,
        dtype=None,
        kv_bits: Optional[int] = None,
        prefix_cache: bool = False,
    ) -> "PagedKVCache":
        if kv_bits is not None and kv_bits != 8:
            raise ValueError(
                f"kv_bits supports 8 (int8 codes) or None (fp pools), "
                f"got {kv_bits}"
            )
        dt = dtype or (jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32)
        shape = (
            cfg.num_layers, num_blocks, block_size,
            cfg.num_kv_heads, cfg.head_dim,
        )
        quant = None
        if kv_bits is not None:
            dt = jnp.uint8
            qshape = shape[:-1]  # [L, NB, BS, Hkv]: one pair per KV row
            quant = {
                name: jnp.zeros(qshape, jnp.float32)
                for name in ("k_scale", "k_zero", "v_scale", "v_zero")
            }
        allocator = BlockAllocator(num_blocks)
        return cls(
            k=jnp.zeros(shape, dt),
            v=jnp.zeros(shape, dt),
            block_size=block_size,
            max_slots=max_slots,
            max_blocks_per_slot=max_blocks_per_slot,
            allocator=allocator,
            block_tables=np.zeros((max_slots, max_blocks_per_slot), np.int32),
            slot_blocks={},
            free_slots=list(range(max_slots - 1, -1, -1)),
            kv_bits=kv_bits,
            quant=quant,
            prefix=(
                PrefixCache(allocator, block_size) if prefix_cache else None
            ),
        )

    # ------------------------------------------------------------- slots
    def blocks_needed(self, total_tokens: int) -> int:
        return -(-total_tokens // self.block_size)

    def max_slot_tokens(self) -> int:
        return self.max_blocks_per_slot * self.block_size

    def slot_deficit(self, slot: int, total_tokens: int) -> int:
        """Pages a live slot still needs to cover ``total_tokens`` kv
        entries — the engine grows by this before each megastep so every
        write of the fused decode program lands on an allocated page."""
        return max(
            0,
            self.blocks_needed(total_tokens) - len(self.slot_blocks[slot]),
        )

    def shared_prefix_pages(self, entry: Optional[PrefixEntry]) -> int:
        """Directly shareable pages of a prefix match: its page-aligned
        full pages. A partial tail page (full-prompt entries) is not
        shared — the sharer gets a private copy-on-write duplicate, so
        it still costs one fresh page."""
        if entry is None:
            return 0
        return entry.n_tokens // self.block_size

    def available_pages(self, protect: frozenset = frozenset()) -> int:
        """Free pages plus what prefix-cache eviction could free — the
        number growth/admission may count on before preempting."""
        n = self.allocator.num_free
        if self.prefix is not None:
            n += self.prefix.reclaimable(protect)
        return n

    def can_admit(self, total_tokens: int, headroom: int = 0,
                  prefix_entry: Optional[PrefixEntry] = None) -> bool:
        """``headroom`` pages are spoken for (pending growth of already
        active slots) — admission may only use what's left above them.
        A prefix match shrinks the bill to the *fresh* (non-shared)
        pages, and LRU-evictable cache pages count as available (the
        match's own pages are protected from that eviction)."""
        n = self.blocks_needed(total_tokens)
        fresh = n - self.shared_prefix_pages(prefix_entry)
        protect = (
            frozenset(prefix_entry.pages) if prefix_entry is not None
            else frozenset()
        )
        return (
            bool(self.free_slots)
            and fresh <= self.available_pages(protect) - headroom
            and n <= self.max_blocks_per_slot
        )

    def _copy_page(self, src: int, dst: int, rid: int = -1) -> None:
        """Copy-on-write page duplication (device-side): K/V rows and,
        on quantized pools, their scale/zero rows move together so the
        copy dequantizes bit-identically to the original."""
        with self.tracer.span("cow_copy_span", track="pool", cat="kv",
                              src=src, dst=dst):
            self.k = self.k.at[:, dst].set(self.k[:, src])
            self.v = self.v.at[:, dst].set(self.v[:, src])
            if self.quant is not None:
                self.quant = {
                    name: a.at[:, dst].set(a[:, src])
                    for name, a in self.quant.items()
                }
            self.tracer.lifecycle(
                "cow_copy", track="pool", rid=rid, src_page=src,
                dst_page=dst,
            )

    def acquire_slot(self, total_tokens: int,
                     prefix_entry: Optional[PrefixEntry] = None,
                     rid: int = -1) -> int:
        """Reserve a slot + enough pages for ``total_tokens`` kv entries.

        With a ``prefix_entry`` (from :meth:`prefix_lookup`) the match's
        page-aligned pages are **shared** (incref, no allocation, no
        prefill needed for those tokens) and only the suffix is freshly
        allocated; a full-prompt match ending mid-page additionally
        copies its partial tail page into the first fresh page (COW —
        the sharer's decode writes land there and must not corrupt the
        other holders). LRU cache entries are evicted as needed to make
        room, never touching the match's own pages."""
        n = self.blocks_needed(total_tokens)
        if n > self.max_blocks_per_slot:
            raise PoolExhausted(
                f"{total_tokens} tokens need {n} blocks > "
                f"max_blocks_per_slot={self.max_blocks_per_slot}"
            )
        if not self.free_slots:
            raise PoolExhausted("no free slots")
        if prefix_entry is None:
            if self.prefix is not None:
                self.prefix.evict_for(n)
            blocks = self.allocator.alloc(n)  # raises before slot consumed
        else:
            full = self.shared_prefix_pages(prefix_entry)
            tail = 1 if prefix_entry.n_tokens % self.block_size else 0
            fresh_needed = n - full
            if fresh_needed < tail:
                raise ValueError(
                    f"prefix match of {prefix_entry.n_tokens} tokens "
                    f"cannot seed a {total_tokens}-token slot"
                )
            protect = frozenset(prefix_entry.pages)
            self.prefix.evict_for(fresh_needed, protect)
            fresh = self.allocator.alloc(fresh_needed)  # raises first
            shared = list(prefix_entry.pages[:full])
            self.allocator.incref(shared)
            blocks = shared + fresh
            if tail:
                self._copy_page(prefix_entry.pages[full], fresh[0], rid=rid)
        slot = self.free_slots.pop()
        self.slot_blocks[slot] = blocks
        self.block_tables[slot] = 0
        self.block_tables[slot, : len(blocks)] = blocks
        self._tables_device = None
        return slot

    # ----------------------------------------------------------- prefix
    def prefix_lookup(self, prompt: np.ndarray) -> Optional[PrefixEntry]:
        """Longest cached prefix of ``prompt`` (None when the prefix
        cache is disabled or misses)."""
        if self.prefix is None:
            return None
        return self.prefix.lookup(prompt)

    def register_prefix(self, prompt: np.ndarray, slot: int,
                        last_logits: Optional[np.ndarray] = None) -> int:
        """Cache the freshly prefilled prompt's page-boundary prefixes +
        the full prompt (with its final-token logits) from a live slot's
        pages. No-op when the prefix cache is disabled."""
        if self.prefix is None:
            return 0
        return self.prefix.register(
            prompt, self.slot_blocks[slot], last_logits
        )

    def clear_prefix_cache(self) -> None:
        if self.prefix is not None:
            self.prefix.clear()

    def grow(self, slot: int, n: int) -> List[int]:
        """Append ``n`` pages to a live slot (on-demand growth).

        LRU prefix-cache entries are evicted first when the free list is
        short (cached prefixes are a best-effort accelerator; a running
        request's pages are not). Raises :class:`PoolExhausted` — leaving
        the slot untouched — when the pool is still out of pages (the
        scheduler preempts a victim and retries) or the slot would
        exceed ``max_blocks_per_slot``.
        """
        have = len(self.slot_blocks[slot])
        if have + n > self.max_blocks_per_slot:
            raise PoolExhausted(
                f"slot {slot}: growing {have}+{n} blocks exceeds "
                f"max_blocks_per_slot={self.max_blocks_per_slot}"
            )
        if self.prefix is not None:
            self.prefix.evict_for(n)
        blocks = self.allocator.alloc(n)  # raises with state untouched
        if not blocks:
            return blocks
        self.slot_blocks[slot].extend(blocks)
        self.block_tables[slot, have : have + len(blocks)] = blocks
        self._tables_device = None
        self.tracer.instant(
            "page_grow", track="pool", cat="kv", slot=slot, pages=len(blocks),
            slot_pages=len(self.slot_blocks[slot]),
            free=self.allocator.num_free,
        )
        return blocks

    # ------------------------------------------------------------- swap
    def swap_out(self, slot: int, n_tokens: int, rid: int = -1) -> SwappedKV:
        """Move a victim slot's pages to host memory and free the slot.

        Device→host copy of the slot's whole pages, then the pages and
        the slot return to the free lists — the caller re-queues the
        request and restores via :meth:`swap_in` at re-admission. The
        payload carries a CRC of its pristine bytes. An injected
        ``swap_out``/``fail`` fault raises :class:`SwapFault` *before*
        any state moves (the engine falls back to recompute-mode
        preemption); ``corrupt`` damages the host payload after the CRC
        is taken, so swap-in's verification catches it.
        """
        spec = self.faults.fire("swap_out", rid) if self.faults else None
        if spec is not None:
            self.tracer.lifecycle(
                "fault", track="pool", site="swap_out", mode=spec.mode,
                rid=int(rid), slot=int(slot),
            )
            if spec.mode == "fail":
                raise SwapFault(
                    f"injected swap-out I/O failure (slot {slot})",
                    rid=(int(rid) if rid >= 0 else None),
                )
        blocks = self.slot_blocks[slot]
        idx = np.asarray(blocks, np.int32)
        with self.tracer.span("kv_swap_out", track="pool", cat="kv",
                              slot=slot) as span:
            swapped = SwappedKV(
                k=np.array(self.k[:, idx]),
                v=np.asarray(self.v[:, idx]),
                n_tokens=n_tokens,
                quant=(
                    {n: np.asarray(a[:, idx]) for n, a in self.quant.items()}
                    if self.quant is not None else None
                ),
            )
            swapped.checksum = swapped.payload_checksum()
            if spec is not None and spec.mode == "corrupt":
                # in-transit damage: the checksum above describes the
                # pristine payload, so swap-in's verification must trip
                swapped.k.view(np.uint8).reshape(-1)[0] ^= 0xFF
            self.release_slot(slot)
            span.args.update(pages=swapped.n_pages, bytes=swapped.nbytes)
        return swapped

    def swap_in(self, slot: int, swapped: SwappedKV, rid: int = -1) -> int:
        """Restore swapped pages into a freshly acquired slot.

        The slot must already hold at least ``swapped.n_pages`` pages
        (admission sizes it from the request's context length). Returns
        the bytes uploaded (host→device) for the swap-traffic metric.
        The payload's CRC is verified before any device state moves; a
        mismatch (real corruption, or an injected ``swap_in`` fault)
        raises :class:`SwapFault` and leaves the slot untouched — the
        engine discards the swap and recovers by recompute re-prefill.
        """
        blocks = self.slot_blocks[slot][: swapped.n_pages]
        if len(blocks) < swapped.n_pages:
            raise ValueError(
                f"slot {slot} holds {len(self.slot_blocks[slot])} pages, "
                f"swap-in needs {swapped.n_pages}"
            )
        if self.quant is not None and swapped.quant is None:
            raise ValueError("quantized pool restored from fp swap")
        spec = self.faults.fire("swap_in", rid) if self.faults else None
        if spec is not None:
            self.tracer.lifecycle(
                "fault", track="pool", site="swap_in", mode=spec.mode,
                rid=int(rid), slot=int(slot),
            )
            if spec.mode == "fail":
                raise SwapFault(
                    f"injected swap-in I/O failure (slot {slot})",
                    rid=(int(rid) if rid >= 0 else None),
                )
            # corrupt: damage the host payload right before the verify
            swapped.k = np.array(swapped.k, copy=True)
            swapped.k.view(np.uint8).reshape(-1)[0] ^= 0xFF
        if (swapped.checksum is not None
                and swapped.payload_checksum() != swapped.checksum):
            raise SwapFault(
                f"swap payload failed checksum for slot {slot}",
                rid=(int(rid) if rid >= 0 else None),
            )
        idx = jnp.asarray(np.asarray(blocks, np.int32))
        with self.tracer.span("kv_swap_in", track="pool", cat="kv",
                              slot=slot, pages=swapped.n_pages,
                              bytes=swapped.nbytes):
            self.k = self.k.at[:, idx].set(
                jnp.asarray(swapped.k, self.k.dtype))
            self.v = self.v.at[:, idx].set(
                jnp.asarray(swapped.v, self.v.dtype))
            if self.quant is not None:
                self.quant = {
                    n: a.at[:, idx].set(jnp.asarray(swapped.quant[n]))
                    for n, a in self.quant.items()
                }
        return swapped.nbytes

    def release_slot(self, slot: int) -> None:
        self.allocator.free(self.slot_blocks.pop(slot))
        self.block_tables[slot] = 0
        self.free_slots.append(slot)
        self._tables_device = None

    # -------------------------------------------------------- observability
    @property
    def utilization(self) -> float:
        """Fraction of pool pages currently held by live slots."""
        return 1.0 - self.allocator.num_free / self.allocator.num_blocks

    def check_consistency(self) -> None:
        """Assert the allocator/table invariants the simulation harness
        fuzzes after every step. With copy-on-write refcounts, "no page
        owned by two live slots" generalizes to exact refcount
        accounting: every allocated page's refcount equals the number of
        live-slot references plus prefix-cache holds (≥ 1 — every
        refcounted page is reachable from a block table or the cache),
        no page is both free and referenced, page conservation holds
        over the union, block tables mirror ``slot_blocks``, and the
        slot free-list is disjoint from live slots. Cheap (host-only).
        """
        slot_refs: Dict[int, int] = {}
        for bl in self.slot_blocks.values():
            for b in bl:
                slot_refs[b] = slot_refs.get(b, 0) + 1
        holds = self.prefix.holds if self.prefix is not None else {}
        referenced = set(slot_refs) | set(holds)
        if referenced != set(self.allocator.allocated):
            raise AssertionError(
                "referenced pages out of sync with allocator (unreachable "
                "refcounted page or untracked reference)"
            )
        free = self.allocator.free_pages
        if len(free) != len(set(free)):
            raise AssertionError("duplicate page in the free list")
        if set(free) & referenced:
            raise AssertionError("page both free and referenced")
        if len(free) + len(referenced) != self.allocator.num_blocks:
            raise AssertionError(
                f"page conservation violated: {len(free)} free + "
                f"{len(referenced)} referenced != {self.allocator.num_blocks}"
            )
        for b in referenced:
            want = slot_refs.get(b, 0) + holds.get(b, 0)
            got = self.allocator.refcount(b)
            if got != want:
                raise AssertionError(
                    f"page {b}: refcount {got} != {slot_refs.get(b, 0)} "
                    f"slot refs + {holds.get(b, 0)} cache holds"
                )
        for slot, bl in self.slot_blocks.items():
            if slot in self.free_slots:
                raise AssertionError(f"live slot {slot} also in free_slots")
            if len(bl) != len(set(bl)):
                raise AssertionError(f"slot {slot} lists a page twice")
            if len(bl) > self.max_blocks_per_slot:
                raise AssertionError(f"slot {slot} over max_blocks_per_slot")
            if list(self.block_tables[slot, : len(bl)]) != bl:
                raise AssertionError(f"block table row {slot} != slot_blocks")
        if self.prefix is not None:
            self.prefix.check_consistency()

    def tables_device(self) -> jnp.ndarray:
        if self._tables_device is None:
            self._tables_device = jnp.asarray(self.block_tables)
        return self._tables_device
