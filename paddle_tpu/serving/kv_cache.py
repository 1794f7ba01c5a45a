"""Paged KV cache: fixed-size pages in a preallocated pool.

The decode-side analog of virtual memory (vLLM's PagedAttention, applied
to the TPU static-shape discipline): instead of one contiguous
``[B, Hkv, T, D]`` buffer per request — whose batch and length dimensions
change every time a request joins, leaves, or grows, forcing a retrace —
the KV cache is ONE preallocated pool of fixed-size pages

    k_pool / v_pool: [num_layers, num_pages, num_kv_heads, page_size, D]

plus a per-request **page table** (``[max_pages]`` int32, physical page id
per logical page). Every tensor the decode program touches has a static
shape: requests joining/leaving the batch only change *values* in the
page-table and position arrays, and sequences growing across a page
boundary only append a page id — the compiled decode program NEVER
retraces after warmup (the acceptance contract `bench.py serve` proves).

Page 0 is reserved as the **trash page**: unallocated page-table slots
point at it, and in-trace writes that must go nowhere (prompt padding,
inactive batch slots) are steered into it. Attention masks by position,
so trash contents are never read into a real output.

Device-side helpers (pure jnp, called inside traced programs):

* :func:`write_token` — scatter one new (k, v) per batch row into its
  page/slot (the decode-step write).
* :func:`write_token_rows` — the same write for the paged decode path,
  shaped so that the pool keeps its layout for the kernel that reads it.
* :func:`write_prefill` — scatter a whole prompt's (k, v) rows, padding
  positions steered to the trash page (the prefill write).
* :func:`paged_attention` — the decode program's attention, per-row
  positions. Where the kernels dispatch and the page shape fits their
  tiles it is ONE Pallas kernel (``ops/kernels/mmha_pallas.py:
  paged_mmha_decode``) that takes the whole pool, the layer, the page
  tables and the positions and fetches each row's live pages itself,
  block by block: no gathered view exists, and a step reads what is
  live, not ``max_batch x max_seq_len``. Elsewhere (the CPU tests, page
  shapes the tiles do not admit) it is the composite below over
  :func:`gather_layer`.
* :func:`gather_layer` — page-table gather producing the contiguous
  ``[B, Hkv, T, D]`` view: what :func:`chunk_attention` (chunked prefill,
  speculative verify) reads, the decode composite, and the oracle the
  paged kernel is tested against.
* :func:`reference_paged_attention` — the composite: the same
  grouped-einsum math as ``models/generation.py:cached_attention``.

Host-side :class:`PagePool` owns the pool tensors and the accounting.
Since the prefix cache landed, a non-trash page is in exactly ONE of
three states:

* **free** — on the LIFO free list, contents meaningless;
* **used** — refcount >= 1: one ref per request page-table that maps it.
  Pages become *shared* (refcount >= 2) when the scheduler maps a cached
  prefix page into a second request; a shared page is immutable — the
  scheduler copy-on-writes before any write would land in it;
* **cached** — refcount 0 but retained because a
  :class:`~.prefix_cache.PrefixCache` key still names its contents.
  Cached pages are the prefix cache's working set AND allocation
  headroom: ``alloc`` reclaims them LRU-first when the free list runs
  dry (dropping the cache entry via the reclaim hook), so admission
  accounting over :attr:`available_pages` stays truthful.

``free`` is a *decref*: a page returns to the free list (or the cached
state, when keyed) only at refcount 0. Double-free detection
distinguishes a **second decref** (:class:`PageDoubleFree` — the page is
already free/cached) from true corruption (a foreign id that was never
this pool's to free). ``leaked()`` counts refcount>=1 pages only — the
chaos gate's "leak zero KV pages" check — and ``lost()`` proves the
three states partition the pool exactly.
"""

from __future__ import annotations

import math
from collections import OrderedDict

import jax
import jax.numpy as jnp

from ..analysis.concurrency import tsan as _tsan
from ..core.tensor import Tensor
from ..observability import gauge as _obs_gauge, counter as _obs_counter

__all__ = [
    "PagePool", "PagePoolError", "PagePoolExhausted", "PageDoubleFree",
    "TRASH_PAGE",
    "write_token", "write_token_rows", "write_prefill", "gather_layer",
    "paged_attention",
    "chunk_attention", "window_pages", "window_first_page",
]

#: physical page id reserved as the write sink for padding / inactive rows
TRASH_PAGE = 0

#: :func:`paged_attention_path`'s name for the paged kernel (the other is
#: "composite"); what ``program_stats()`` shows under path/attention
PAGED_PATH = "paged_mmha_decode"

_PAGES = _obs_gauge("paddle_tpu_serving_kv_pages",
                    "KV-cache pages by state (free/used/cached/total)")
_SHARED = _obs_gauge("paddle_tpu_serving_shared_pages",
                     "KV pages mapped by more than one request "
                     "(refcount >= 2)")
_ALLOC_FAIL = _obs_counter(
    "paddle_tpu_serving_page_alloc_failures_total",
    "page allocations that failed because the pool was exhausted")


class PagePoolError(RuntimeError):
    """Pool accounting violation (double free, freeing an unowned page)."""


class PagePoolExhausted(PagePoolError):
    """No free pages left for an allocation."""


class PageDoubleFree(PagePoolError):
    """A second decref of a page whose refcount already reached zero —
    distinct from freeing a foreign id (true corruption): the page IS one
    of this pool's, but nobody holds a reference to give back."""


class PagePool:
    """Preallocated paged KV pool + thread-safe refcounted accounting.

    ``k``/``v`` are framework Tensors shaped
    ``[num_layers, num_pages, num_kv_heads, page_size, head_dim]`` —
    read and written inside the engine's compiled programs, so they
    thread through ``to_static`` as state. Page ids are handed out from
    a LIFO free list (recently-freed pages are warm); page ``0``
    (:data:`TRASH_PAGE`) is never handed out. Each allocated page
    carries a refcount; the prefix cache shares pages across requests by
    claiming extra references, and keyed pages linger in a reclaimable
    LRU **cached** state at refcount 0 instead of returning to the free
    list.
    """

    def __init__(self, num_layers: int, num_pages: int, num_kv_heads: int,
                 page_size: int, head_dim: int, dtype: str = "float32"):
        if num_pages < 2:
            raise ValueError("num_pages must be >= 2 (page 0 is the "
                             "reserved trash page)")
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        self.num_layers = int(num_layers)
        self.num_pages = int(num_pages)
        self.num_kv_heads = int(num_kv_heads)
        self.page_size = int(page_size)
        self.head_dim = int(head_dim)
        shape = (self.num_layers, self.num_pages, self.num_kv_heads,
                 self.page_size, self.head_dim)
        self.k = Tensor(jnp.zeros(shape, jnp.dtype(dtype)))
        self.v = Tensor(jnp.zeros(shape, jnp.dtype(dtype)))
        self._lock = _tsan.lock("serving.PagePool")
        # LIFO: recently-freed (warm) pages are reused first
        self._free = list(range(self.num_pages - 1, TRASH_PAGE, -1))
        self._ref: dict[int, int] = {}          # page -> refcount (>= 1)
        self._shared = 0        # pages at refcount >= 2, kept on the
        #                         1<->2 transitions (O(P) rescans would
        #                         serialize into every page op)
        self._cached: OrderedDict = OrderedDict()   # page -> key, LRU order
        self._keys: dict[int, bytes] = {}       # page -> retained cache key
        # prefix-cache hook, called (page, key) with the POOL lock held
        # whenever a cached page is reclaimed (its contents die)
        self._reclaim_cb = None
        self._export()

    def set_reclaim_hook(self, cb) -> None:
        """``cb(page, key)`` fires (pool lock held) when a cached page is
        reclaimed for reuse — the prefix cache drops its map entry."""
        with self._lock:
            self._reclaim_cb = cb

    # -- accounting ----------------------------------------------------------

    def _export(self):
        _PAGES.set(len(self._free), state="free")
        _PAGES.set(len(self._ref), state="used")
        _PAGES.set(len(self._cached), state="cached")
        _PAGES.set(self.allocatable, state="total")
        _SHARED.set(self._shared)

    @property
    def allocatable(self) -> int:
        """Total pages that can ever be handed out (pool minus trash)."""
        return self.num_pages - 1

    @property
    def free_pages(self) -> int:
        with self._lock:
            return len(self._free)

    @property
    def used_pages(self) -> int:
        """Pages with refcount >= 1."""
        with self._lock:
            return len(self._ref)

    @property
    def cached_pages(self) -> int:
        """Refcount-0 pages retained for the prefix cache (reclaimable)."""
        with self._lock:
            return len(self._cached)

    @property
    def available_pages(self) -> int:
        """Pages an ``alloc`` can satisfy right now: free + reclaimable
        cached — the truthful admission-headroom number."""
        with self._lock:
            return len(self._free) + len(self._cached)

    @property
    def shared_pages(self) -> int:
        """Pages mapped by more than one request (refcount >= 2)."""
        with self._lock:
            return self._shared

    def refcount(self, page: int) -> int:
        with self._lock:
            return self._ref.get(int(page), 0)

    def pages_for(self, length: int) -> int:
        """Pages needed to hold ``length`` token positions."""
        return max(0, math.ceil(int(length) / self.page_size))

    def alloc(self, n: int = 1) -> list[int]:
        """Allocate ``n`` pages at refcount 1; raises
        :class:`PagePoolExhausted` (and allocates nothing) when fewer
        than ``n`` are available. The free list is preferred; when it
        runs dry, refcount-0 **cached** pages are reclaimed LRU-first
        (their prefix-cache entries dropped via the reclaim hook) —
        refcount>=1 pages are NEVER taken."""
        with self._lock:
            if n > len(self._free) + len(self._cached):
                _ALLOC_FAIL.inc()
                raise PagePoolExhausted(
                    f"need {n} page(s), {len(self._free)} free + "
                    f"{len(self._cached)} cached (pool {self.allocatable})")
            pages = []
            for _ in range(n):
                if self._free:
                    p = self._free.pop()
                else:
                    p = self._reclaim_lru_locked()
                self._ref[p] = 1
                pages.append(p)
            if _tsan.active():
                _tsan.note_write(self, "_free", self._lock)
            self._export()
            return pages

    def _reclaim_lru_locked(self) -> int:
        """Pop the least-recently-cached refcount-0 page; its key dies."""
        page, key = self._cached.popitem(last=False)
        self._keys.pop(page, None)
        cb = self._reclaim_cb
        if cb is not None:
            cb(page, key)
        return page

    def incref(self, pages) -> None:
        """Take an extra reference on already-content-valid pages: live
        (refcount >= 1) pages gain a sharer; cached (refcount 0) pages
        revive to refcount 1. Unknown/free ids raise."""
        pages = [int(p) for p in pages]
        with self._lock:
            bad = [p for p in pages
                   if p not in self._ref and p not in self._cached]
            if bad:
                raise PagePoolError(
                    f"incref of page(s) {bad} that are neither live nor "
                    f"cached")
            for p in pages:
                if p in self._cached:
                    del self._cached[p]
                    self._ref[p] = 1
                else:
                    self._ref[p] += 1
                    if self._ref[p] == 2:
                        self._shared += 1
            self._export()

    def claim_prefix(self, pairs) -> list:
        """Claim the longest verified prefix of ``pairs`` (``(page,
        key)`` in chain order): each page must still carry exactly that
        retained key — a page reclaimed-and-reused between the cache
        lookup and this claim fails verification and ends the chain.
        Claimed pages gain a reference (cached ones revive). Returns the
        claimed page ids."""
        claimed = []
        with self._lock:
            for page, key in pairs:
                page = int(page)
                if self._keys.get(page) != key:
                    break
                if page in self._cached:
                    del self._cached[page]
                    self._ref[page] = 1
                elif page in self._ref:
                    self._ref[page] += 1
                    if self._ref[page] == 2:
                        self._shared += 1
                else:       # keyed but neither live nor cached: corrupt
                    break
                claimed.append(page)
            if claimed:
                self._export()
        return claimed

    def retain_keys(self, pairs) -> None:
        """Mark live pages cacheable: ``(page, key)`` pairs record the
        content key under which a page should linger (cached state)
        instead of returning to the free list at refcount 0."""
        with self._lock:
            for page, key in pairs:
                page = int(page)
                if page in self._ref:
                    self._keys[page] = key

    def free(self, pages) -> None:
        """Release one reference per page (decref). A page reaching
        refcount 0 returns to the free list — or to the **cached** state
        when a prefix-cache key is retained for it. Errors distinguish a
        second decref (:class:`PageDoubleFree`: the page is already
        free/cached) from true corruption (foreign id). A duplicate id
        WITHIN one call is one request double-counting its own mapping —
        it raises before any mutation."""
        pages = [int(p) for p in pages]
        with self._lock:
            if len(set(pages)) != len(pages):
                dups = sorted({p for p in pages if pages.count(p) > 1})
                raise PagePoolError(
                    f"page(s) {dups} appear more than once in one free() "
                    f"call (double free); pool left untouched")
            zero = [p for p in pages
                    if p not in self._ref
                    and (p in self._cached or p in self._free)]
            if zero:
                raise PageDoubleFree(
                    f"second decref of page(s) {zero}: refcount already "
                    f"zero (page is free/cached); pool left untouched")
            foreign = [p for p in pages if p not in self._ref]
            if foreign:
                raise PagePoolError(
                    f"freeing page(s) {foreign} this pool never "
                    f"allocated (foreign id or trash page); pool left "
                    f"untouched")
            for p in pages:
                self._ref[p] -= 1
                if self._ref[p] == 1:
                    self._shared -= 1
                if self._ref[p] > 0:
                    continue            # still shared: page stays live
                del self._ref[p]
                key = self._keys.get(p)
                if key is not None:
                    self._cached[p] = key       # MRU end of the LRU
                else:
                    self._free.append(p)
            if _tsan.active():
                _tsan.note_write(self, "_free", self._lock)
            self._export()

    def copy_page(self, src: int, dst: int) -> None:
        """Copy one physical page's K and V across every layer (the
        copy-on-write data move). Caller holds references on both pages;
        runs eagerly on the engine thread, outside the compiled
        programs."""
        src, dst = int(src), int(dst)
        self.k._data = self.k._data.at[:, dst].set(self.k._data[:, src])
        self.v._data = self.v._data.at[:, dst].set(self.v._data[:, src])

    def leaked(self) -> int:
        """Pages still referenced — 0 after every request completed/
        failed (asserted by the chaos serving profile and engine
        shutdown). Cached (refcount-0) pages are NOT leaks: they are
        reclaimable headroom."""
        return self.used_pages

    def lost(self) -> int:
        """Pages in NO state (free/used/cached) — always 0; a nonzero
        value means the accounting dropped a page on the floor (the
        refcount-aware complement of :meth:`leaked`)."""
        with self._lock:
            return self.allocatable - len(self._free) - len(self._ref) \
                - len(self._cached)

    def reset(self) -> None:
        """Drop all allocations AND cached contents (does not zero page
        data — stale data is masked by position everywhere it could be
        read). The reclaim hook fires for every cached page so a prefix
        cache stays consistent."""
        with self._lock:
            cb = self._reclaim_cb
            if cb is not None:
                for page, key in list(self._cached.items()):
                    cb(page, key)
            self._free = list(range(self.num_pages - 1, TRASH_PAGE, -1))
            self._ref.clear()
            self._cached.clear()
            self._keys.clear()
            self._shared = 0
            self._export()


# -- device-side helpers (pure jnp; run inside traced programs) -------------

def write_token(pool, layer: int, page_ids, slots, vals):
    """Scatter one new token's k or v rows into the pool.

    pool ``[L, P, Hkv, ps, D]``; ``page_ids``/``slots`` ``[B]`` int32
    (physical page and in-page slot per batch row — inactive rows point
    at the trash page); vals ``[B, Hkv, D]``. Returns the updated pool.
    """
    with jax.named_scope("kv_write"):
        return pool.at[layer, page_ids, :, slots, :].set(
            vals.astype(pool.dtype))


def write_token_rows(pool, layer: int, page_ids, slots, vals):
    """:func:`write_token` for the paged decode path: the same rows land
    in the same places, scattered as one ``[D]`` row per (batch row, KV
    head). With ``[Hkv, D]`` windows the compiler lays the whole pool out
    for the scatter (``Hkv`` next to ``D``) and copies it back to its own
    layout in front of every kernel that reads it in place; with no
    window but the last dimension the pool keeps its layout from the
    program's entry to its exit.
    """
    with jax.named_scope("kv_write"):
        heads = jnp.arange(pool.shape[2], dtype=jnp.int32)
        return pool.at[layer, page_ids[:, None], heads[None, :],
                       slots[:, None], :].set(vals.astype(pool.dtype))


def write_prefill(pool, layer: int, table_row, prompt_len, vals,
                  page_size: int):
    """Scatter a prompt's k or v rows; positions >= ``prompt_len``
    (bucket padding) are steered into the trash page.

    pool ``[L, P, Hkv, ps, D]``; ``table_row`` ``[max_pages]`` int32;
    ``prompt_len`` traced scalar; vals ``[L_bucket, Hkv, D]``.
    """
    with jax.named_scope("kv_write"):
        n = vals.shape[0]
        t = jnp.arange(n, dtype=jnp.int32)
        page = jnp.where(t < prompt_len, table_row[t // page_size],
                         jnp.int32(TRASH_PAGE))
        return pool.at[layer, page, :, t % page_size, :].set(
            vals.astype(pool.dtype))


def gather_layer(pool, layer: int, tables):
    """Page-table gather: one layer's pages assembled into the contiguous
    ``[B, Hkv, max_pages * ps, D]`` view the decode-attention math reads
    (unallocated table slots gather the trash page; masked by position).

    pool ``[L, P, Hkv, ps, D]``; tables ``[B, max_pages]`` int32.
    """
    with jax.named_scope("kv_gather"):
        kp = pool[layer][tables]              # [B, Pmax, Hkv, ps, D]
        kp = jnp.moveaxis(kp, 2, 1)           # [B, Hkv, Pmax, ps, D]
        b, h, pmax, ps, d = kp.shape
        return kp.reshape(b, h, pmax * ps, d)


def chunk_attention(q, k_cache, v_cache, start):
    """Causal attention of a query BLOCK against the gathered paged view
    — the chunked-prefill/speculative-verify analog of
    :func:`reference_paged_attention` (same grouped-einsum math, a block
    of queries instead of one row).

    q ``[B, C, H, D]`` (queries at absolute positions
    ``start + [0..C)``); k/v_cache ``[B, Hkv, T, D]`` gathered from the
    page table AFTER this block's KV writes (so the block sees itself);
    ``start`` traced int32 — a scalar (chunked prefill, B=1) or a
    per-row ``[B]`` vector (the speculative verify program, one base
    position per batch slot). Key position ``j`` is visible to query
    ``i`` iff ``j <= start + i`` — earlier chunks, cached prefix pages,
    in-flight draft tokens, and the in-block causal triangle in one
    rule; positions past the context (trash/stale pages) are always
    masked. Padding lanes (``i`` beyond the block's valid length)
    produce garbage outputs that nothing reads, and their KV went to
    the trash page, so they can never contaminate a real lane. Returns
    ``[B, C, H, D]``.
    """
    b, s, h, d = q.shape
    h_kv, t = k_cache.shape[1], k_cache.shape[2]
    rep = h // h_kv
    qg = q.reshape(b, s, h_kv, rep, d).astype(jnp.float32)
    logits = jnp.einsum("bsgrd,bgtd->bgrst", qg,
                        k_cache.astype(jnp.float32)) / math.sqrt(d)
    qpos = jnp.asarray(start, jnp.int32).reshape(-1, 1) + \
        jnp.arange(s, dtype=jnp.int32)[None, :]            # [B or 1, C]
    mask = jnp.arange(t, dtype=jnp.int32)[None, None, :] <= \
        qpos[:, :, None]                                   # [B|1, C, T]
    logits = jnp.where(mask[:, None, None], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bgrst,bgtd->bsgrd", probs,
                     v_cache.astype(jnp.float32))
    return out.reshape(b, s, h, d).astype(q.dtype)


def reference_paged_attention(q, k_cache, v_cache, pos, lo=None):
    """Composite decode attention with PER-ROW positions over the
    gathered paged view: delegates to
    ``ops/kernels/mmha_pallas.py:reference_mmha`` (which accepts vector
    positions), so the serving composite is LITERALLY the decode math
    the training path's ``cached_attention`` runs — one implementation,
    no way to diverge.

    q ``[B, 1, H, D]``; k/v_cache ``[B, Hkv, T, D]``; pos ``[B]`` int32,
    last valid cache index per row; lo ``[B]`` int32 or None, the first
    (a window layer's lower bound). Returns ``[B, 1, H, D]``.
    """
    from ..ops.kernels import mmha_pallas
    return mmha_pallas.reference_mmha(q, k_cache, v_cache,
                                      jnp.asarray(pos, jnp.int32), lo)


def paged_attention_path(q_shape, pool_shape, pool_dtype) -> str:
    """Which path :func:`paged_attention` takes, from what the code can
    observe: kernels dispatching, one new token, a head width and page
    size the paged kernel's tiles admit, the pool's dtype."""
    from ..ops.kernels import mmha_pallas
    return PAGED_PATH if mmha_pallas.use_paged_kernel(
        q_shape, pool_shape, pool_dtype) else "composite"


def window_pages(window: int, page_size: int) -> int:
    """Pages of a window group that one row can hold at a time: the pages
    `window` consecutive positions can touch."""
    return -(-int(window) // int(page_size)) + 1


def window_first_page(length: int, window: int, page_size: int) -> int:
    """First logical page of a window layer that a row holding `length`
    positions still needs: the page of the first key that the query at the
    row's last position, and so any later one, can see."""
    return max(0, int(length) - int(window)) // int(page_size)


def paged_block_positions(path: str, page_size, max_pages) -> int:
    """Positions in one block of pages that a decode call on `path` reads
    at a time: a row's read is its live positions rounded up to this. 0 on
    the composite, whose gather reads every slot of every table row."""
    if path != PAGED_PATH:
        return 0
    from ..ops.kernels import mmha_pallas
    return mmha_pallas.paged_block_positions(page_size, max_pages)


def paged_attention(q, k_pool, v_pool, layer, tables, pos, interpret=None,
                    window=None, live=None):
    """Decode attention of one layer over the paged pool, per-row
    positions. `window` (a window layer, whose pool and tables are its
    group's): the row's query sees the last `window` positions up to `pos`
    alone, and the kernel starts at the first block that holds one. `live`
    ``[B]`` bool says which rows hold anything (default: those whose table
    does not start with the trash page, which a window group's may).

    q ``[B, 1, H, D]``; k_pool/v_pool ``[L, P, Hkv, ps, D]`` (the row's
    new token already written); ``layer`` int; tables ``[B, max_pages]``
    int32; pos ``[B]`` int32, last valid position per row. The paged
    Pallas kernel when :func:`paged_attention_path` admits the shapes,
    else :func:`reference_paged_attention` over :func:`gather_layer`.
    ``interpret=True`` forces the kernel in interpret mode (the parity
    tests' path); ``interpret=False`` forces the composite. The kernel
    call carries the scope ``kv_gather``: the fetch through the page
    table happens inside it.
    """
    from ..ops.kernels import _common as kern
    from ..ops.kernels import mmha_pallas

    pos = jnp.asarray(pos, jnp.int32)
    lo = None if window is None else jnp.maximum(pos - (int(window) - 1), 0)
    if interpret is True or (interpret is None and paged_attention_path(
            q.shape, k_pool.shape, k_pool.dtype) == PAGED_PATH):
        with jax.named_scope("kv_gather"):
            # an inactive slot (position 0, an all-trash table) has nothing
            # live: the kernel scores nothing for it
            if live is None:
                live = tables[:, 0] != TRASH_PAGE
            pos = jnp.where(live, pos, jnp.int32(-1))
            return mmha_pallas.paged_mmha_decode(
                q, k_pool, v_pool, jnp.int32(layer), tables, pos, lo,
                interpret=interpret is True or kern.interpret_mode())
    return reference_paged_attention(
        q, gather_layer(k_pool, layer, tables),
        gather_layer(v_pool, layer, tables), pos, lo)
