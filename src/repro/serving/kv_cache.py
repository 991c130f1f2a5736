"""Paged KV-cache subsystem: page pool + per-slot block-table indirection.

The dense serving layout reserves ``slots × max_len`` cache rows up front,
so resident memory is fixed by the worst-case sequence.  This module makes
resident bytes track *live tokens* instead — the off-chip analogue of the
paper's M-independent on-chip buffering:

    layer storage (device, one per layer)     block table (host-mirrored,
    [num_pages, Hkv, page_size, dh]           one per capacity class,
                                              shared by all its layers)
    ┌────────┐                                 slot 0: [ 3, 7, 1, ·]
    │ page 0 │◄───────┐                        slot 1: [ 0, 4, ·, ·]
    │ page 1 │◄─────┐ │                        slot 2: [ 6, 2, 5, 8]
    │ page 2 │      │ │
    │  ...   │      │ └─ token at position p lives at
    └────────┘      │    (table[slot, l // page_size], l % page_size)
                    │    with logical index l = p % capacity
                    └─ pages allocate from a free list as sequences grow
                       and return to it on completion / preemption

Capacity classes subsume the three dense cache kinds with one mechanism:

* **full** layers (global GQA, MLA latents): capacity = ``max_len``;
  a slot's table grows one page at a time as its sequence lengthens.
* **ring / window** layers: capacity = ``window`` — the logical index
  wraps, so a windowed layer cycles through a fixed
  ``ceil(window / page_size)``-page working set no matter how long the
  sequence runs.  Eviction *is* the page-addressing policy; there is no
  special-cased rotation code left in the model.

Automatic prefix caching (``prefix_caching=True``, the paper's
redundant-pass argument applied at request scope): pages are *refcounted*
and a token-hash index (chained per-page hashes, full pages only) maps
prompt prefixes to resident pages.  ``admit`` matches an incoming prompt
against the index and maps the hit pages straight into the slot's block
table — only the uncached tail is prefilled; ``release`` demotes a
completed slot's full pages into the index (an extra index-held reference)
instead of freeing them, and the pool reclaims index-only pages LRU when
it runs short.  A shared page is never written: the admission path
copy-on-writes the one page a tail prefill could touch (the
prompt-exactly-page-aligned case), and released rows are reset to an
out-of-range *sentinel* page id so any write-mask slip is dropped by the
scatter instead of corrupting a live sequence.  Prefix caching requires
every cache class to be position-addressed from zero and every layer to
be position-local, so it auto-disables for configs with windowed
attention or SSM layers (their state at the prefix boundary is not
reconstructible from retained pages) and for MoE configs (expert
capacity depends on the prefilled chunk length).

Device sharding (``shard=``, a
:class:`repro.distributed.sharding.KVShard`): every page array is
partitioned along its kv-head (GQA) / latent-rank (MLA) axis over one
mesh axis while the page dimension stays complete on each device.  Page
ids are global, so *everything host-side is replicated and unchanged* —
free lists, block tables, the prefix index, refcounts, COW scheduling,
sentinel semantics — and per-device resident bytes are exactly
``total / tp``.  The sharded compute lives in the attention layer
(``rt.kv_shard`` → ``shard_map`` head-parallel paths); this class only
places the arrays and validates divisibility.

``PagedKVCache`` owns the device page arrays (built by
``transformer.init_paged_cache`` with the same run/stack tree shape as the
dense caches, so scan/donation work unchanged), the host free lists
(:class:`PagePool`, one per class), and the block tables.  The engine asks
it to ``admit`` a request / ``grow`` a slot before every dispatch and
``release`` on completion or preemption; ``memory_stats`` reports resident
(live-page) bytes versus reusable-prefix and physical pool bytes for the
serving benchmark.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.distributed import sharding as shd
from repro.model import transformer as tf
from repro.model.attention import kv_quant_dtype, paged_cache_key


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


class PagePool:
    """Host-side refcounting free-list allocator over a fixed page count.

    Allocation and reclaim are O(n) list operations; freed pages are
    recycled LIFO so a steady-state workload keeps touching the same
    (cache-warm) pages.  Every allocated page carries a reference count
    (1 at ``alloc``); ``ref``/``unref`` let several owners — block-table
    rows of different slots, the prefix index — share one physical page,
    and the page returns to the free list only when the last reference
    drops.  Freeing a page that is not allocated (double free) or still
    shared raises instead of silently corrupting the free list.
    ``peak_in_use`` feeds the serving benchmark's memory accounting.
    """

    def __init__(self, num_pages: int):
        if num_pages < 1:
            raise ValueError(f"need at least one page, got {num_pages}")
        self.num_pages = num_pages
        self._free: List[int] = list(range(num_pages - 1, -1, -1))
        self._refcount: Dict[int, int] = {}
        self.peak_in_use = 0

    @property
    def pages_in_use(self) -> int:
        return self.num_pages - len(self._free)

    @property
    def free_pages(self) -> int:
        return len(self._free)

    def alloc(self, n: int) -> Optional[List[int]]:
        """Pop ``n`` pages (refcount 1), or None (and no change) if the
        pool can't."""
        if n > len(self._free):
            return None
        got = [self._free.pop() for _ in range(n)]
        for p in got:
            self._refcount[p] = 1
        self.peak_in_use = max(self.peak_in_use, self.pages_in_use)
        return got

    def free(self, pages: List[int]) -> None:
        """Return pages to the free list.  Raises on a double free (page
        not currently allocated) or on freeing a still-shared page —
        either would alias one physical page to two owners later."""
        for p in pages:
            rc = self._refcount.get(p)
            if rc is None:
                raise RuntimeError(
                    f"double free: page {p} is not allocated")
            if rc > 1:
                raise RuntimeError(
                    f"freeing shared page {p} (refcount {rc}); "
                    f"drop references with unref() instead")
            del self._refcount[p]
            self._free.append(p)

    def ref(self, page: int) -> None:
        """Add a reference to an allocated page."""
        if page not in self._refcount:
            raise RuntimeError(f"ref of unallocated page {page}")
        self._refcount[page] += 1

    def unref(self, page: int) -> bool:
        """Drop one reference; the page is freed when the count reaches
        zero.  Returns True if the page was freed."""
        rc = self._refcount.get(page)
        if rc is None:
            raise RuntimeError(f"unref of unallocated page {page}")
        if rc <= 1:
            self.free([page])
            return True
        self._refcount[page] = rc - 1
        return False

    def refcount(self, page: int) -> int:
        return self._refcount.get(page, 0)


@dataclasses.dataclass
class _CacheClass:
    """One capacity class: its pool, block table, and accounting."""
    capacity: int                    # logical tokens before wrap
    table_width: int                 # pages per slot
    pool: PagePool
    table: np.ndarray                # [slots, table_width] int32 page ids
    owned: List[List[int]]           # per-slot pages, logical order
    bytes_per_page: int              # across every layer of the class
    peak_live_pages: int = 0         # distinct pages referenced by slots
    # per-slot speculative scratch tail pages: mapped into the table rows
    # after ``owned`` while a draft is in flight, promoted into ``owned``
    # by commit_draft or unref'd by drop_draft/release — never registered
    # in the prefix index, never counted as resident
    scratch: List[List[int]] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class _PrefixEntry:
    """One full page of the prefix index.  ``key`` is the chained hash of
    every token up to and including this page, so matching a prompt is a
    walk from the root; ``parent`` is the previous page's chain hash
    (None at depth 0).  The index holds its own pool reference on
    ``page`` — the page outlives the slot that wrote it.

    With the host swap tier, an entry may be *demoted*: ``page == -1``
    and ``host`` holds the page's content (one numpy array per full-class
    layer leaf, in deterministic leaf order) in host RAM.  Demoted
    entries stay matchable through the index; a prefix hit promotes them
    back into freshly allocated pool pages (a DMA instead of a
    recompute)."""
    page: int
    parent: Optional[int]
    last_used: int
    host: Optional[list] = None


class PagedKVCache:
    """Page-pool KV cache for the serving engine (``cache_layout="paged"``).

    One instance replaces the dense ``init_cache`` allocation: ``caches``
    is the device tree the jit'd prefill/decode programs thread through
    (page arrays for attention, per-slot dense rows for SSM state), and
    ``tables()`` materializes the block tables for a dispatch.

    ``num_pages`` sizes the *full* class pool (the unbounded one); windowed
    classes are bounded by construction and default to their maximum
    working set.  The default full pool equals the dense layout's capacity
    (``slots × max_len / page_size`` pages) — shrink it to serve mixed
    traffic in less memory, at the cost of admission back-pressure and
    (worst case) preemption.
    """

    def __init__(self, cfg: ModelConfig, slots: int, max_len: int, dtype,
                 *, page_size: int = 16,
                 num_pages: Optional[int] = None,
                 prefix_caching: bool = True,
                 shard: Optional[shd.KVShard] = None,
                 kv_dtype: Optional[str] = None,
                 pool_bytes: Optional[int] = None,
                 host_swap_bytes: int = 0):
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        if max_len % page_size:
            raise ValueError(
                f"max_len={max_len} must be a multiple of "
                f"page_size={page_size}")
        self.cfg = cfg
        self.slots = slots
        self.max_len = max_len
        self.page_size = page_size
        # device sharding of the pool along the kv-head / latent-rank axis
        # (repro.distributed.sharding.KVShard).  Validated up front: an
        # axis the mesh does not divide must fail loudly, never replicate.
        self.shard = shard if shard is not None and shard.size > 1 else None
        if self.shard is not None:
            shd.validate_kv_shard(cfg, self.shard.size)

        # capacity classes present in this architecture; scale elems are
        # the parallel fp16 scale-pool entries of a quantized pool (one
        # scalar per token per kv head for GQA, one per latent vector and
        # one per rope vector for MLA)
        caps: Dict[str, int] = {}
        per_layer_page_elems: Dict[str, int] = {}
        per_layer_scale_elems: Dict[str, int] = {}
        has_ssm = has_moe = False
        for spec in cfg.layer_specs():
            if spec.mlp == "moe":
                has_moe = True
            if spec.attn == "gqa":
                key = paged_cache_key(spec)
                caps[key] = spec.window if spec.window is not None \
                    else max_len
                per_layer_page_elems[key] = per_layer_page_elems.get(key, 0) \
                    + 2 * page_size * cfg.n_kv_heads * cfg.dh
                per_layer_scale_elems[key] = \
                    per_layer_scale_elems.get(key, 0) \
                    + 2 * page_size * cfg.n_kv_heads
            elif spec.attn == "mla":
                caps["full"] = max_len
                per_layer_page_elems["full"] = \
                    per_layer_page_elems.get("full", 0) + page_size * (
                        cfg.mla.kv_lora_rank + cfg.mla.rope_dim)
                per_layer_scale_elems["full"] = \
                    per_layer_scale_elems.get("full", 0) + 2 * page_size
            if spec.ssm is not None:
                has_ssm = True

        qdt = kv_quant_dtype(kv_dtype)
        self.kv_dtype = kv_dtype
        itemsize = jnp.dtype(dtype).itemsize if qdt is None \
            else jnp.dtype(qdt).itemsize
        self.classes: Dict[str, _CacheClass] = {}
        pool_sizes: Dict[str, int] = {}
        for key, cap in caps.items():
            width = _ceil_div(cap, page_size)
            # honest per-page bytes: quantized data plus its fp16 scales
            bpp = per_layer_page_elems[key] * itemsize
            if qdt is not None:
                bpp += per_layer_scale_elems[key] * 2
            if key == "full" and pool_bytes is not None:
                # byte-budget sizing: a quantized pool gets ~4× the pages
                # of a fp32 pool from the same budget
                n = max(1, pool_bytes // bpp)
            elif key == "full" and num_pages is not None:
                n = num_pages
            else:
                n = slots * width            # dense-equivalent capacity
            pool_sizes[key] = n
            self.classes[key] = _CacheClass(
                capacity=cap,
                table_width=width,
                pool=PagePool(n),
                # sentinel-filled: an out-of-range id on every row that is
                # not backed by an owned page (reads clamp + are masked by
                # kv_len; writes drop via scatter mode="drop")
                table=np.full((slots, width), n, np.int32),
                owned=[[] for _ in range(slots)],
                bytes_per_page=bpp,
                scratch=[[] for _ in range(slots)],
            )

        # prefix reuse needs every class to address positions from zero and
        # all per-position state to live in pages: windowed rings hold only
        # each sequence's own trailing window, and SSM state is a running
        # summary — neither reconstructs another request's prefix boundary.
        # MoE layers gate off too: expert capacity is a function of the
        # prefilled chunk length (ceil(S·k/E·factor)), so a tail-only
        # prefill would route tokens differently than the full prompt —
        # greedy streams would no longer be identical with the cache off.
        self.prefix_supported = (not has_ssm) and (not has_moe) \
            and set(caps) <= {"full"}
        self.prefix_enabled = bool(prefix_caching) and self.prefix_supported
        self._prefix: Dict[int, _PrefixEntry] = {}
        self._prefix_tick = 0
        self._cow_fns: Dict[str, object] = {}
        self.stats = {"prefix_evictions": 0, "demotions": 0,
                      "promotions": 0, "host_drops": 0, "reregistered": 0}

        # host swap tier: under pool pressure, index-only prefix pages are
        # demoted to host RAM (up to ``host_swap_bytes``) instead of
        # dropped, and promoted back on a prefix hit.  ``cache_source``
        # must be wired by the owner (the engine points it at its live
        # cache tree) before demotion can snapshot page contents; without
        # it eviction falls back to the plain LRU drop.
        self.host_swap_bytes = int(host_swap_bytes)
        self.swap_enabled = self.host_swap_bytes > 0 and self.prefix_enabled
        self._host_bytes = 0
        self.cache_source = None
        self._promote_jit = None

        self.caches = tf.init_paged_cache(cfg, slots, pool_sizes, page_size,
                                          dtype, kv_dtype)
        self._shardings = None
        if self.shard is not None:
            # pages split along the kv-head (GQA) / latent-rank (MLA) axis;
            # the page dimension stays complete per device, so page ids —
            # and with them every host-side structure above — are global
            self._shardings = shd.paged_cache_shardings(self.caches,
                                                        self.shard)
            self.caches = jax.device_put(self.caches, self._shardings)
        self._physical_page_bytes = sum(
            c.pool.num_pages * c.bytes_per_page
            for c in self.classes.values())
        self._state_bytes = sum(
            x.nbytes for x in jax.tree.leaves(self.caches)
        ) - self._physical_page_bytes

    # -- allocation ---------------------------------------------------------

    def _sentinel(self, c: _CacheClass) -> int:
        return c.pool.num_pages

    def pages_needed(self, key: str, kv_target: int) -> int:
        c = self.classes[key]
        return _ceil_div(min(kv_target, c.capacity), self.page_size)

    def validate_request(self, total_tokens: int) -> None:
        """Reject a request no pool could ever hold alone — the engine's
        progress guarantee (preempt-youngest) needs any single request to
        fit an otherwise-empty pool."""
        for key, c in self.classes.items():
            need = self.pages_needed(key, min(total_tokens, self.max_len))
            if need > c.pool.num_pages:
                raise ValueError(
                    f"request needs {need} '{key}' pages but the pool has "
                    f"only {c.pool.num_pages}; raise num_pages or shorten "
                    f"the request")

    def _evictable_pages(self, key: str, c: _CacheClass) -> int:
        if key != "full" or not self.prefix_enabled:
            return 0
        return sum(1 for e in self._prefix.values()
                   if e.page >= 0 and c.pool.refcount(e.page) == 1)

    def can_grow(self, slot: int, kv_target: int) -> bool:
        return all(
            self.pages_needed(k, kv_target) - len(c.owned[slot])
            <= c.pool.free_pages + self._evictable_pages(k, c)
            for k, c in self.classes.items())

    def grow(self, slot: int, kv_target: int) -> bool:
        """Extend ``slot``'s tables to cover ``kv_target`` tokens in every
        class.  All-or-nothing: returns False (state unchanged) when any
        pool is short even after evicting reusable-prefix pages."""
        if any(c.scratch[slot] for c in self.classes.values()):
            raise RuntimeError(
                f"grow of slot {slot} with a staged draft: commit or drop "
                f"the draft first (its table rows overlap the growth)")
        if not self.can_grow(slot, kv_target):
            return False
        for key, c in self.classes.items():
            need = self.pages_needed(key, kv_target)
            have = len(c.owned[slot])
            if need > have:
                if need - have > c.pool.free_pages:
                    self._evict_prefix(c, need - have)
                got = c.pool.alloc(need - have)
                c.table[slot, have:need] = got
                c.owned[slot].extend(got)
        self._touch_peaks()
        return True

    def release(self, slot: int,
                tokens: Optional[np.ndarray] = None) -> None:
        """Drop every page reference the slot owns and reset its table
        rows to the out-of-range sentinel (reads through stale rows are
        masked by kv_len and clamped; writes drop).  With ``tokens`` (the
        slot's full token stream, completion path) the slot's full pages
        are first demoted into the reusable-prefix index — the index takes
        its own reference, so those pages survive the release until reused
        or evicted.  Any staged draft is drained first (the preemption
        contract: in-flight scratch pages are fully unref'd before the
        request requeues, and they never reach the prefix index)."""
        self.drop_draft(slot)
        if tokens is not None and self.prefix_enabled:
            c = self.classes["full"]
            if c.owned[slot]:
                hashes = self._chain_hashes(tokens)
                if len(tokens) % self.page_size == 0 and hashes:
                    # the stream's final position L-1 sits in the last full
                    # page, and the fused decode loop keeps issuing masked
                    # steps for a slot whose budget is spent while others
                    # decode — those steps rewrite position L-1 with the
                    # dummy token's K/V, so that page's content can no
                    # longer be trusted to match the token hash: never
                    # demote it (a partial final page is skipped anyway)
                    hashes = hashes[:-1]
                self._register(hashes[:len(c.owned[slot])], c.owned[slot])
        for c in self.classes.values():
            for p in c.owned[slot]:
                c.pool.unref(p)
            c.owned[slot] = []
            c.table[slot] = self._sentinel(c)

    def tables(self) -> Dict[str, jnp.ndarray]:
        """Device block tables for one dispatch (tiny int32 uploads).
        Asserts the sentinel invariant: a live table row (owned page or
        staged draft scratch) never holds the sentinel — only unbacked
        rows do."""
        for k, c in self.classes.items():
            for slot, owned in enumerate(c.owned):
                live = len(owned) + len(c.scratch[slot])
                if live and int(c.table[slot, :live].max()) \
                        >= c.pool.num_pages:
                    raise AssertionError(
                        f"class '{k}' slot {slot}: live block-table row "
                        f"holds the sentinel page")
        return {k: jnp.asarray(c.table) for k, c in self.classes.items()}

    # -- speculative drafts (scratch tail pages) ----------------------------

    def reserve_draft(self, slot: int, kv_len: int,
                      kv_target: int) -> Optional[List[Tuple[str, int, int]]]:
        """Stage scratch pages so chain positions ``[kv_len, kv_target)``
        are writable: the draft's K/V lands in tail pages mapped into the
        slot's table rows *after* its owned pages, so a rejected draft
        rolls back by dropping references — no K/V copies.

        Owned boundary pages the draft would write (the partially-filled
        last page, when shared with the prefix index or another slot) are
        copy-on-write'd exactly like :meth:`admit`'s page-aligned case:
        the returned pairs must go through :meth:`apply_cow` before the
        verify dispatch.  All-or-nothing: returns None (state unchanged)
        when any pool is short even after LRU prefix eviction.  Scratch
        pages never enter the prefix index until :meth:`commit_draft`
        promotes them into ``owned``."""
        if any(c.scratch[slot] for c in self.classes.values()):
            raise RuntimeError(f"slot {slot} already has a staged draft")
        ps = self.page_size
        plan: Dict[str, Tuple[int, List[int]]] = {}
        for key, c in self.classes.items():
            need = self.pages_needed(key, kv_target)
            have = len(c.owned[slot])
            n_scratch = max(0, need - have)
            first = min(kv_len, c.capacity) // ps
            cow_idx = [i for i in range(first, have)
                       if c.pool.refcount(c.owned[slot][i]) > 1]
            plan[key] = (n_scratch, cow_idx)
            fresh = n_scratch + len(cow_idx)
            if fresh > c.pool.free_pages + self._evictable_pages(key, c):
                return None
        pairs: List[Tuple[str, int, int]] = []
        for key, c in self.classes.items():
            n_scratch, cow_idx = plan[key]
            fresh = n_scratch + len(cow_idx)
            if fresh > c.pool.free_pages:
                self._evict_prefix(c, fresh)
            for i in cow_idx:
                src = c.owned[slot][i]
                dst = c.pool.alloc(1)[0]
                # the slot's reference on src transfers to the pair
                # (apply_cow unrefs it); the slot owns the copy target
                pairs.append((key, src, dst))
                c.owned[slot][i] = dst
                c.table[slot, i] = dst
            got = c.pool.alloc(n_scratch)
            have = len(c.owned[slot])
            c.table[slot, have:have + n_scratch] = got
            c.scratch[slot] = got
        return pairs

    def commit_draft(self, slot: int, kv_len_new: int) -> None:
        """Accept a draft prefix by block-table surgery: the scratch pages
        covering ``kv_len_new`` tokens are promoted into ``owned`` (their
        single reference transfers — no copy), the rejected tail's pages
        drop their references, and rows beyond the new extent reset to
        the sentinel."""
        for c in self.classes.values():
            need = _ceil_div(min(kv_len_new, c.capacity), self.page_size)
            keep = max(0, need - len(c.owned[slot]))
            if keep > len(c.scratch[slot]):
                raise RuntimeError(
                    f"commit of {kv_len_new} tokens needs {keep} scratch "
                    f"pages but slot {slot} staged "
                    f"{len(c.scratch[slot])}")
            kept, dropped = c.scratch[slot][:keep], c.scratch[slot][keep:]
            c.owned[slot].extend(kept)
            for p in dropped:
                c.pool.unref(p)
            c.scratch[slot] = []
            c.table[slot, len(c.owned[slot]):] = self._sentinel(c)
        self._touch_peaks()

    def drop_draft(self, slot: int) -> None:
        """Roll back a staged draft entirely: unref every scratch page and
        reset its table rows (rejection with zero kept pages, and the
        preemption path via :meth:`release`).  Idempotent."""
        for c in self.classes.values():
            if not c.scratch[slot]:
                continue
            for p in c.scratch[slot]:
                c.pool.unref(p)
            c.scratch[slot] = []
            c.table[slot, len(c.owned[slot]):] = self._sentinel(c)

    # -- prefix cache -------------------------------------------------------

    def _tick(self) -> int:
        self._prefix_tick += 1
        return self._prefix_tick

    def _chain_hashes(self, tokens) -> List[int]:
        """Chained hashes over the *full* pages of a token stream: entry i
        hashes (parent chain, page i's tokens), so equal chain hash ⇒
        equal token prefix (modulo 64-bit hash collisions, the standard
        prefix-cache trade)."""
        ps = self.page_size
        hashes: List[int] = []
        parent: Optional[int] = None
        for i in range(len(tokens) // ps):
            h = hash((parent,
                      tuple(int(t) for t in tokens[i * ps:(i + 1) * ps])))
            hashes.append(h)
            parent = h
        return hashes

    def _register(self, hashes: List[int], row: List[int]) -> None:
        """Insert chain entries for pages not yet indexed; the index takes
        a reference on each inserted page.  Existing entries win (their
        content is hash-equal), so duplicate prefills dedupe here."""
        for i, h in enumerate(hashes):
            e = self._prefix.get(h)
            if e is not None:
                if e.page < 0 and i < len(row):
                    # a fresh prefill just rebuilt this demoted page's
                    # content on device: re-point the entry at the
                    # resident copy and drop the host blob (a free
                    # promotion — no DMA, the recompute already happened)
                    self.classes["full"].pool.ref(row[i])
                    e.page = row[i]
                    e.host = None
                    self._host_bytes -= \
                        self.classes["full"].bytes_per_page
                    self.stats["reregistered"] += 1
                e.last_used = self._tick()
                continue
            self.classes["full"].pool.ref(row[i])
            self._prefix[h] = _PrefixEntry(
                page=row[i], parent=hashes[i - 1] if i else None,
                last_used=self._tick())

    def _page_blobs(self, page: int) -> list:
        """device_get one page's content across every full-class layer
        leaf (data pools and, when quantized, scale pools) in the engine's
        live cache tree — deterministic leaf order (run/position order,
        sorted leaf names) shared with :meth:`_promote_fn`."""
        caches = self.cache_source()
        blobs = []
        for (pattern, reps), cache_run in zip(self.cfg.runs(), caches):
            for spec, c1 in zip(pattern, cache_run):
                full = (spec.attn == "mla") or (
                    spec.attn == "gqa"
                    and paged_cache_key(spec) == "full")
                if not full or "attn" not in c1:
                    continue
                for name in sorted(c1["attn"]):
                    a = c1["attn"][name]
                    blobs.append(jax.device_get(
                        a[:, page] if reps > 1 else a[page]))
        return blobs

    def _drop_subtree(self, c: _CacheClass, root: int) -> None:
        """Drop an index entry and every descendant (they are matchable
        only through it): resident pages drop their index reference, host
        blobs release their swap-tier bytes."""
        stack = [root]
        while stack:
            h = stack.pop()
            e = self._prefix.pop(h, None)
            if e is None:
                continue
            stack.extend(h2 for h2, e2 in self._prefix.items()
                         if e2.parent == h)
            if e.page >= 0:
                c.pool.unref(e.page)
                self.stats["prefix_evictions"] += 1
            else:
                self._host_bytes -= c.bytes_per_page
                self.stats["host_drops"] += 1

    def _host_make_room(self, c: _CacheClass, bytes_needed: int,
                        exclude: frozenset) -> bool:
        """Last rung of the HBM → host → drop eviction ordering: drop LRU
        demoted chains until ``bytes_needed`` more bytes fit under the
        host byte cap."""
        while self._host_bytes + bytes_needed > self.host_swap_bytes:
            victim = None
            for h, e in self._prefix.items():
                if h in exclude or e.page >= 0:
                    continue
                if victim is None or \
                        e.last_used < self._prefix[victim].last_used:
                    victim = h
            if victim is None:
                return False
            self._drop_subtree(c, victim)
        return True

    def _evict_prefix(self, c: _CacheClass, need: int,
                      protect: frozenset = frozenset()) -> bool:
        """Free index-only pages (LRU) until ``need`` pages are free.
        Evicting an entry takes its whole subtree along — descendants are
        only matchable through it; their pages survive if a live slot
        still references them.  Entries in ``protect`` (e.g. the chain an
        in-flight admission just matched but has not ref'd yet) are never
        chosen as victims; since every ancestor of a protected entry is
        itself protected (chains are matched from the root), no protected
        entry can fall inside an evicted subtree either.

        With the host swap tier active the subtree is *demoted* — page
        contents device_get into host blobs, entries kept in the index
        with ``page = -1`` — so a later prefix hit turns into a DMA
        promotion instead of a recompute.  When the subtree does not fit
        under the host cap even after dropping LRU demoted chains, it
        falls back to the plain drop (eviction ordering HBM → host →
        drop)."""
        while c.pool.free_pages < need:
            victim = None
            for h, e in self._prefix.items():
                if h in protect or e.page < 0:
                    continue
                if c.pool.refcount(e.page) == 1 and (
                        victim is None
                        or e.last_used < self._prefix[victim].last_used):
                    victim = h
            if victim is None:
                return False
            stack, subtree = [victim], []
            while stack:
                h = stack.pop()
                if h not in self._prefix or h in subtree:
                    continue
                subtree.append(h)
                stack.extend(h2 for h2, e2 in self._prefix.items()
                             if e2.parent == h)
            resident = [h for h in subtree if self._prefix[h].page >= 0]
            demote = (self.swap_enabled and self.cache_source is not None
                      and self._host_make_room(
                          c, len(resident) * c.bytes_per_page,
                          exclude=protect | frozenset(subtree)))
            if demote:
                for h in resident:
                    e = self._prefix[h]
                    e.host = self._page_blobs(e.page)
                    c.pool.unref(e.page)
                    e.page = -1
                    self._host_bytes += c.bytes_per_page
                    self.stats["demotions"] += 1
            else:
                self._drop_subtree(c, victim)
        return True

    def clear_prefix(self) -> int:
        """Drop every index entry (e.g. after engine warmup, or to drain
        the pool).  Drains the host swap tier too — demoted entries must
        not survive a clear (warmup must never leave demoted warmup pages
        resident in host RAM).  Returns the number of entries dropped."""
        n = len(self._prefix)
        c = self.classes.get("full")
        for e in self._prefix.values():
            if e.page >= 0:
                c.pool.unref(e.page)
        self._prefix.clear()
        self._host_bytes = 0
        return n

    def _match(self, hashes: List[int]) -> int:
        m = 0
        for h in hashes:
            if h not in self._prefix:
                break
            m += 1
        return m

    def match_prefix(self, tokens) -> int:
        """Longest indexed prefix of ``tokens``, in full pages."""
        return self._match(self._chain_hashes(tokens))

    def register_progress(self, slot: int, tokens, upto: int) -> None:
        """Index the slot's prompt pages that are fully *written* —
        positions [0, upto) have been prefilled.  The async engine's
        interleaved prefill admits with ``register=False`` and calls this
        after every quantum dispatch: pages enter the index only once
        their writer has dispatched, so a concurrent admission can never
        map (and read) a page whose prefill has not happened yet.  Device
        dispatch order then guarantees writer-before-reader for free.
        Idempotent — already-indexed pages dedupe in :meth:`_register`."""
        if not self.prefix_enabled:
            return
        c = self.classes["full"]
        n = min(int(upto), len(tokens)) // self.page_size
        if n <= 0 or n > len(c.owned[slot]):
            return
        hashes = self._chain_hashes(tokens[:n * self.page_size])
        self._register(hashes, c.owned[slot][:n])

    def admit(self, slot: int, tokens, kv_target: int,
              register: bool = True) -> Optional[dict]:
        """Build ``slot``'s block table for a request: map the longest
        indexed prefix (shared pages, one reference each), schedule a COW
        copy of the single page a tail prefill could write into (only when
        the prompt is exactly page-aligned with the hit — at least one
        token is always re-prefilled so decode has last-token logits),
        allocate fresh pages for the rest, and pre-register the prompt's
        full pages so admissions later in the same batch can share them
        (the engine dispatches cold groups first, so writers always
        precede readers).

        The COW copy is *deferred*: the source page may be written by a
        colder group of the same admission batch, so the engine must call
        :meth:`apply_cow` with the returned ``cow_pairs`` after every
        earlier group has dispatched and before this slot's own prefill
        (the pair holds a pool reference on the source page until then).

        When the matched chain ends in host-demoted entries (swap tier),
        those pages are promoted: a fresh pool page is allocated per
        demoted entry and a ``(dst_page, host_blobs)`` instruction is
        returned under ``"promotes"`` — the engine must apply them via
        :meth:`apply_promote` *before* :meth:`apply_cow` and before this
        slot's prefill.  If the pool cannot hold the promotions even
        after eviction, the match falls back to the resident prefix and
        the demoted tail stays on the host tier.

        ``register=False`` defers the pre-registration entirely: the
        caller indexes pages progressively via :meth:`register_progress`
        as its prefill quanta dispatch (the async engine's interleaved
        prefill — where the prompt's later pages stay unwritten for many
        scheduler turns and must not be matchable in between).

        All-or-nothing: returns None (state unchanged) when the pool is
        short even after LRU eviction; otherwise
        ``{"cached_len", "reused", "cow_pairs", "promotes"}``."""
        if not self.prefix_enabled:
            if not self.grow(slot, kv_target):
                return None
            return {"cached_len": 0, "reused": 0, "cow_pairs": [],
                    "promotes": []}

        c = self.classes["full"]
        if c.owned[slot] or c.scratch[slot]:
            raise RuntimeError(f"admit into non-empty slot {slot}")
        n_tok = len(tokens)
        hashes = self._chain_hashes(tokens)
        m = self._match(hashes)
        # demotion is subtree-wise, so the demoted part of the matched
        # chain is a contiguous tail after the resident prefix
        n_res = 0
        while n_res < m and self._prefix[hashes[n_res]].page >= 0:
            n_res += 1
        n_dem = 0
        while n_res + n_dem < m and \
                self._prefix[hashes[n_res + n_dem]].page < 0:
            n_dem += 1
        m = n_res + n_dem
        need_width = self.pages_needed("full", kv_target)
        while True:
            cow = m > 0 and m * self.page_size == n_tok
            cached_len = n_tok - 1 if cow else m * self.page_size
            fresh = need_width - m + (1 if cow else 0)
            if fresh + n_dem <= c.pool.free_pages or self._evict_prefix(
                    c, fresh + n_dem, protect=frozenset(hashes[:m])):
                break
            if n_dem:
                # not enough pages to promote the demoted tail: fall back
                # to the resident prefix (the tail stays on the host tier)
                m, n_dem = n_res, 0
                continue
            return None
        prom = c.pool.alloc(n_dem) if n_dem else []
        got = c.pool.alloc(fresh)
        if got is None or prom is None:      # pragma: no cover - guarded
            return None
        promotes = []
        for j, h in enumerate(hashes[n_res:m]):
            e = self._prefix[h]
            e.page = prom[j]                 # alloc's reference becomes
            promotes.append((prom[j], e.host))   # the index's own
            e.host = None
            self._host_bytes -= c.bytes_per_page
            self.stats["promotions"] += 1
        shared = []
        for h in hashes[:m]:
            e = self._prefix[h]
            e.last_used = self._tick()
            c.pool.ref(e.page)
            shared.append(e.page)
        cow_pairs = []
        if cow:
            # the slot owns the copy target; the matched source page keeps
            # the reference taken above until apply_cow() releases it
            cow_pairs.append(("full", shared[-1], got[0]))
            shared[-1] = got[0]
            row = shared + got[1:]
        else:
            row = shared + got
        c.table[slot, :len(row)] = row
        c.table[slot, len(row):] = self._sentinel(c)
        c.owned[slot] = list(row)
        if register:
            self._register(hashes, row)
        self._touch_peaks()
        return {"cached_len": cached_len,
                "reused": cached_len if m else 0,
                "cow_pairs": cow_pairs,
                "promotes": promotes}

    def _cow_fn(self, key: str):
        """Jit'd ``pages[dst] = pages[src]`` over every layer of a class,
        with the cache tree donated so the copy updates the pool
        in place instead of materializing a second full allocation per
        layer.  ``src``/``dst`` are device operands — one executable
        serves every COW of the class."""
        fn = self._cow_fns.get(key)
        if fn is None:

            def run(caches, src, dst):
                out = tf.copy_cache_pages(self.cfg, caches, key, src, dst)
                if self._shardings is not None:
                    # pin the pool's head/rank sharding through the copy —
                    # the page-axis update is shard-local either way, but
                    # an unconstrained output could let GSPMD replicate
                    out = jax.tree.map(
                        jax.lax.with_sharding_constraint, out,
                        self._shardings)
                return out

            fn = jax.jit(run, donate_argnums=(0,))
            self._cow_fns[key] = fn
        return fn

    def apply_cow(self, caches, cow_pairs: List[Tuple[str, int, int]]):
        """Materialize deferred COW copies (``pages[dst] = pages[src]``
        per class) and release the source-page references
        :meth:`admit` held for them.  Returns the rebuilt cache tree."""
        for key, src, dst in cow_pairs:
            caches = self._cow_fn(key)(
                caches, jnp.asarray(src, jnp.int32),
                jnp.asarray(dst, jnp.int32))
            self.classes[key].pool.unref(src)
        return caches

    def _promote_fn(self):
        """Jit'd ``pages[dst] = host_blob`` over every full-class layer
        leaf, donated + sharding-pinned like :meth:`_cow_fn` so a
        promotion is an in-place page DMA, not a pool reallocation."""
        fn = self._promote_jit
        if fn is None:

            def run(caches, blobs, dst):
                i = 0
                out = []
                for (pattern, reps), cache_run in zip(self.cfg.runs(),
                                                      caches):
                    pos = []
                    for spec, c1 in zip(pattern, cache_run):
                        full = (spec.attn == "mla") or (
                            spec.attn == "gqa"
                            and paged_cache_key(spec) == "full")
                        if not full or "attn" not in c1:
                            pos.append(c1)
                            continue
                        attn = dict(c1["attn"])
                        for name in sorted(attn):
                            a = attn[name]
                            attn[name] = (a.at[:, dst].set(blobs[i])
                                          if reps > 1
                                          else a.at[dst].set(blobs[i]))
                            i += 1
                        c2 = dict(c1)
                        c2["attn"] = attn
                        pos.append(c2)
                    out.append(pos)
                if self._shardings is not None:
                    out = jax.tree.map(
                        jax.lax.with_sharding_constraint, out,
                        self._shardings)
                return out

            fn = jax.jit(run, donate_argnums=(0,))
            self._promote_jit = fn
        return fn

    def start_promote(self, promotes: List[Tuple[int, list]]
                      ) -> List[Tuple[int, list]]:
        """Launch the host→HBM transfers for promotion blobs *without*
        applying the page scatters: each blob is handed to
        ``jax.device_put`` immediately, which begins an async DMA the
        caller can overlap with host-side admission work (hashing, COW
        planning, further admissions) and with unrelated device
        dispatches.  Returns the promote list with device-resident blobs
        — feed it to :meth:`apply_promote` (whose ``jnp.asarray`` is then
        a no-op) before anything reads the destination pages."""
        return [(dst, [jax.device_put(b) for b in blobs])
                for dst, blobs in promotes]

    def apply_promote(self, caches,
                      promotes: List[Tuple[int, list]]):
        """Materialize host→device promotions scheduled by :meth:`admit`
        (``pages[dst] = host blob`` for every full-class layer leaf, in
        the :meth:`_page_blobs` leaf order).  Must run *before*
        :meth:`apply_cow` for the same admission batch — a COW source may
        itself be a just-promoted page.  Returns the rebuilt tree."""
        for dst, blobs in promotes:
            caches = self._promote_fn()(
                caches, [jnp.asarray(b) for b in blobs],
                jnp.asarray(dst, jnp.int32))
        return caches

    # -- invariants ---------------------------------------------------------

    def check_invariants(self) -> None:
        """Full-state consistency audit; raises AssertionError on the
        first violation.  Intended for tests (called at quiescent points —
        admission batches with deferred COW pairs in flight hold transient
        source references that intentionally fail the exact-refcount
        check):

        * free list: in range, duplicate-free, disjoint from the
          refcounted set, and together they account for every page;
        * refcounts: every page's count equals exactly its multiplicity
          across slot ``owned`` rows + staged draft ``scratch`` rows +
          (full class) one reference per resident prefix-index entry;
        * block tables: row ``[: live]`` mirrors ``owned + scratch`` in
          order, no live row holds the sentinel, every row past the live
          extent *is* the sentinel;
        * prefix index: entries point at in-range pages, parent chains
          are closed under the index (no orphaned descendants), resident
          entries carry no host blob and demoted entries carry one;
        * host tier: ``_host_bytes`` equals demoted pages × page bytes;
        * quantized pools: every data leaf's parallel ``*_scale`` leaf
          covers the identical page set (same page-axis extent).
        """
        for key, c in self.classes.items():
            pool = c.pool
            free = pool._free
            assert len(set(free)) == len(free), \
                f"class '{key}': duplicate pages in the free list"
            assert all(0 <= p < pool.num_pages for p in free), \
                f"class '{key}': free-list page out of range"
            refed = set(pool._refcount)
            assert not (set(free) & refed), \
                f"class '{key}': page both free and allocated"
            assert len(free) + len(refed) == pool.num_pages, \
                f"class '{key}': {pool.num_pages - len(free) - len(refed)}" \
                f" page(s) leaked (neither free nor allocated)"
            assert all(rc > 0 for rc in pool._refcount.values()), \
                f"class '{key}': allocated page with refcount <= 0"

            expected: Dict[int, int] = {}
            for rows in (c.owned, c.scratch):
                for row in rows:
                    for p in row:
                        expected[p] = expected.get(p, 0) + 1
            if key == "full":
                for e in self._prefix.values():
                    if e.page >= 0:
                        expected[e.page] = expected.get(e.page, 0) + 1
            assert expected == pool._refcount, \
                f"class '{key}': refcounts {pool._refcount} != expected " \
                f"{expected} from slot rows + prefix index"

            sent = self._sentinel(c)
            for slot in range(self.slots):
                live = c.owned[slot] + c.scratch[slot]
                row = c.table[slot]
                assert all(p < sent for p in live), \
                    f"class '{key}' slot {slot}: live row holds sentinel"
                assert list(row[:len(live)]) == live, \
                    f"class '{key}' slot {slot}: table row " \
                    f"{list(row[:len(live)])} != owned+scratch {live}"
                assert all(int(p) == sent for p in row[len(live):]), \
                    f"class '{key}' slot {slot}: unbacked row not sentinel"

        full = self.classes.get("full")
        demoted = 0
        for h, e in self._prefix.items():
            assert e.page < full.pool.num_pages, \
                f"prefix entry {h}: page {e.page} out of range"
            assert e.parent is None or e.parent in self._prefix, \
                f"prefix entry {h}: orphaned (parent evicted from index)"
            if e.page >= 0:
                assert e.host is None, \
                    f"prefix entry {h}: resident but still holds host blob"
            else:
                demoted += 1
                assert e.host is not None, \
                    f"prefix entry {h}: demoted without host blob"
        host_bytes = 0 if full is None else demoted * full.bytes_per_page
        assert self._host_bytes == host_bytes, \
            f"host tier accounts {self._host_bytes} bytes, " \
            f"{demoted} demoted page(s) imply {host_bytes}"

        if self.kv_dtype is not None:
            for (pattern, reps), cache_run in zip(self.cfg.runs(),
                                                  self.caches):
                for spec, c1 in zip(pattern, cache_run):
                    if "attn" not in c1:
                        continue
                    axis = 1 if reps > 1 else 0
                    for name, a in c1["attn"].items():
                        scale = c1["attn"].get(f"{name}_scale")
                        if name.endswith("_scale") or scale is None:
                            continue
                        assert scale.shape[axis] == a.shape[axis], \
                            f"'{name}': scale pool covers " \
                            f"{scale.shape[axis]} pages, data pool " \
                            f"{a.shape[axis]}"

    # -- accounting ---------------------------------------------------------

    def _live_pages(self, c: _CacheClass) -> int:
        live = set()
        for owned in c.owned:
            live.update(owned)
        return len(live)

    def _touch_peaks(self) -> None:
        for c in self.classes.values():
            c.peak_live_pages = max(c.peak_live_pages, self._live_pages(c))

    def reset_peaks(self) -> None:
        for c in self.classes.values():
            c.pool.peak_in_use = 0
            c.peak_live_pages = 0

    @property
    def pages_in_use(self) -> Dict[str, int]:
        return {k: c.pool.pages_in_use for k, c in self.classes.items()}

    def memory_stats(self) -> dict:
        """Resident = distinct pages referenced by live slots (shared
        prefix pages count once); reusable-prefix pages held only by the
        index are reported separately — they are reclaimable on demand.
        Physical = the whole pool allocation (device arrays are static).
        In-flight speculative scratch pages are *not* resident — they are
        transient (promoted or dropped within the step) and counting them
        would double-book the accept path (the same bytes reappear as
        owned pages on commit); they report separately as ``draft_pages``.
        SSM slot state is counted separately — it is O(slots), independent
        of sequence length.  With a device-sharded pool the head/rank axis
        of every page splits evenly over ``tp`` devices (validated at
        construction), so per-device bytes are exactly total/tp — reported
        under ``sharding.per_device``."""
        live = {k: self._live_pages(c) for k, c in self.classes.items()}
        resident = sum(live[k] * c.bytes_per_page
                       for k, c in self.classes.items())
        peak = sum(c.peak_live_pages * c.bytes_per_page
                   for c in self.classes.values())
        full = self.classes.get("full")
        prefix_pages = len(self._prefix)
        prefix_only = 0 if full is None else \
            self._evictable_pages("full", full)
        demoted = sum(1 for e in self._prefix.values() if e.page < 0)
        sharding = None
        if self.shard is not None:
            tp = self.shard.size
            sharding = {
                "tp": tp,
                "axis": self.shard.axis,
                "per_device": {
                    "resident_cache_bytes": resident // tp,
                    "peak_resident_cache_bytes": peak // tp,
                    "physical_cache_bytes":
                        self._physical_page_bytes // tp,
                },
            }
        return {
            "page_size": self.page_size,
            "kv_dtype": self.kv_dtype,
            "num_pages": {k: c.pool.num_pages
                          for k, c in self.classes.items()},
            "pages_in_use": self.pages_in_use,
            "live_pages": live,
            "peak_pages_in_use": {k: c.pool.peak_in_use
                                  for k, c in self.classes.items()},
            "peak_live_pages": {k: c.peak_live_pages
                                for k, c in self.classes.items()},
            "resident_cache_bytes": resident,
            "peak_resident_cache_bytes": peak,
            "draft_pages": {k: sum(len(s) for s in c.scratch)
                            for k, c in self.classes.items()},
            "physical_cache_bytes": self._physical_page_bytes,
            "ssm_state_bytes": self._state_bytes,
            "sharding": sharding,
            "prefix_cache": {
                "enabled": self.prefix_enabled,
                "entries": prefix_pages,
                "evictable_pages": prefix_only,
                "reusable_prefix_bytes": 0 if full is None else
                    prefix_only * full.bytes_per_page,
                "evictions": self.stats["prefix_evictions"],
            },
            "host_tier": {
                "enabled": self.swap_enabled,
                "capacity_bytes": self.host_swap_bytes,
                "demoted_pages": demoted,
                "demoted_bytes": self._host_bytes,
                "demotions": self.stats["demotions"],
                "promotions": self.stats["promotions"],
                "host_drops": self.stats["host_drops"],
                "reregistered": self.stats["reregistered"],
                "promote_hit_rate": self.stats["promotions"]
                    / max(1, self.stats["demotions"]),
            },
        }
