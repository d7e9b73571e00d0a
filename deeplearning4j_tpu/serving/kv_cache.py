"""Paged KV-cache arena: preallocated block pools + a page allocator.

The serving-side memory manager behind continuous-batching decode
(PAPERS: vLLM/SOSP'23). Instead of a monolithic ``[b, max_t, f]`` cache
per sequence — whose worst-case length must be reserved up front and
whose slots idle whenever a sequence is shorter — K/V live in per-layer
``[num_pages, page_size, heads * head_dim]`` block pools shared by every
in-flight sequence. Each sequence owns an ordered page table of physical
page ids; pages are handed out lazily as decode advances and returned to
the free list the moment the sequence retires, so HBM holds exactly the
tokens that exist, not the tokens that might.

Two-level accounting:

- **reservation** (admission control): a sequence reserves its worst-case
  page count when admitted — ``ceil((prompt + max_new_tokens) /
  page_size)`` capped at ``pages_per_seq`` — so a RUNNING sequence can
  never deadlock waiting for a page another running sequence holds.
  Reservations are counts, not physical pages.
- **draw** (lazy allocation): physical pages leave the free list one at a
  time, against the reservation, as the sequence actually grows.

Cross-request prefix caching (PAPERS: RadixAttention/SGLang) extends the
allocator with REFERENCE COUNTS: a page is live while any owner — a lane
or the :class:`PrefixIndex` — holds a reference, and returns to the free
list only at refcount 0. The index maps full-page-aligned token prefixes
to page chains; an admission whose prefix is resident retains the shared
pages into its table and skips their prefill. Cached-but-unpinned chains
count as *reclaimable*: the reservation invariant becomes ``reserved <=
free + reclaimable`` and ``draw()`` evicts the LRU unpinned chain leaf
when the free list runs dry. Writes never target shared pages (sharing
is full-page only; tails re-prefill from the page boundary), so
copy-on-write degenerates to a metadata detach: a window-evicting lane
releases its reference on a shared page and draws a private tail instead
of recycling in place (``kv_pages_cow_total``).

Sliding-window overflow is PAGE EVICTION: once a sequence holds
``pages_per_seq`` pages, its oldest page is recycled as the new tail
(the page table rotates, the view base advances by ``page_size``) —
the decode-arena analog of the dense cache's per-token eviction in
``SelfAttentionLayer._apply_streaming``, accounted in
``kv_pages_evicted_total``.

The lanes' host page state and the one policy that fills it live here too
(:class:`LaneTables`): the serving engine keeps only its free lanes.

Thread-safety: the allocator locks itself (submit threads reserve while
the decode loop draws); the prefix index shares the allocator's RLock so
lookup→admit and draw→reclaim compose atomically. The pools are owned by
the decode engine, which mutates them only under the scheduler's
dispatch lock.
"""

from __future__ import annotations

import hashlib
import threading
import weakref
from collections import deque
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from ..util import metrics as _metrics
from ..util.tracing import region

__all__ = ["PageAllocator", "PagedEntry", "PagedKVArena", "PrefixIndex",
           "LaneTables"]

# kv_page_refcount histogram buckets: refcounts are small integers
# (1 = private, 2+ = shared); powers of two cover fan-out up to a
# 64-way-shared system prompt without per-value series blowup.
_REFCOUNT_BUCKETS = (1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0)


class PageAllocator:
    """Refcounted free-list allocator over ``num_pages`` physical pages
    with reservation accounting (see module docstring).

    ``draw()`` hands a page out at refcount 1; ``retain()`` adds a
    reference (a prefix-cache hit mapping a shared page, or the index
    itself caching a chain); ``free()`` releases references and returns
    a page to the free list only when the last one drops. With a
    :class:`PrefixIndex` attached, cached-but-unpinned pages are
    *reclaimable* and extend admission capacity: ``reserved <= free +
    reclaimable`` is the invariant that keeps ``draw()`` infallible.
    """

    def __init__(self, num_pages: int,
                 registry: Optional[_metrics.MetricsRegistry] = None):
        if num_pages < 1:
            raise ValueError(f"num_pages must be >= 1, got {num_pages}")
        self.num_pages = int(num_pages)
        self._free = deque(range(self.num_pages))
        self._refcount = [0] * self.num_pages
        self._shared = 0          # pages with refcount >= 2
        self._reserved = 0
        self._index: Optional["PrefixIndex"] = None
        # RLock: PrefixIndex methods run under this lock and call back
        # into the unlocked _retain/_release internals; the engine may
        # also hold it across lookup+admit to make a hit-admission atomic
        self._lock = threading.RLock()
        reg = registry if registry is not None else _metrics.REGISTRY
        self._m_evicted = reg.counter(
            "kv_pages_evicted_total",
            "KV pages recycled by sliding-window eviction")
        self._m_cow = reg.counter(
            "kv_pages_cow_total",
            "Shared KV pages detached copy-on-write at window eviction "
            "(reference released, private tail drawn instead)")
        self._m_refcount = reg.histogram(
            "kv_page_refcount",
            "Page reference count observed at each retain()",
            buckets=_REFCOUNT_BUCKETS)
        # weakly bound callbacks: on a SHARED registry the newest arena's
        # gauges win (per-server registries are the default, as with the
        # serving gauges), and a retired allocator is collectable — a
        # dead ref raises, which drops the series at exposition
        ref = weakref.ref(self)

        def _sample(attr):
            def fn():
                alloc = ref()
                if alloc is None:
                    raise LookupError("allocator retired")
                return float(getattr(alloc, attr))
            return fn

        reg.gauge(
            "kv_pages_in_use",
            "KV arena pages currently owned by live sequences"
        ).set_function(_sample("pages_in_use"))
        reg.gauge(
            "kv_pages_reserved",
            "KV arena pages reserved by admitted sequences but not yet "
            "drawn").set_function(_sample("reserved"))
        reg.gauge(
            "kv_pages_shared",
            "KV pages referenced by more than one owner (lanes and/or "
            "the prefix index)").set_function(_sample("shared_pages"))

    def attach_index(self, index: "PrefixIndex") -> None:
        self._index = index

    # -- unlocked internals (caller holds self._lock) ------------------

    def _reclaimable_locked(self) -> int:
        return self._index.reclaimable if self._index is not None else 0

    def _retain_locked(self, page: int) -> None:
        if not (0 <= page < self.num_pages):
            raise ValueError(f"retain() of unknown page {page}")
        rc = self._refcount[page]
        if rc < 1:
            raise ValueError(f"retain() of free page {page}")
        self._refcount[page] = rc + 1
        if rc == 1:
            self._shared += 1
            if self._index is not None:
                self._index._on_pin(page)
        self._m_refcount.observe(float(rc + 1))

    def _release_locked(self, pages: Sequence[int]) -> None:
        for p in pages:
            if not (0 <= p < self.num_pages):
                raise ValueError(f"free() of unknown page {p}")
            rc = self._refcount[p] - 1
            if rc < 0:
                raise ValueError(f"free() of unreferenced page {p}")
            self._refcount[p] = rc
            if rc == 0:
                self._free.append(p)
            elif rc == 1:
                self._shared -= 1
                if self._index is not None:
                    self._index._on_unpin(p)

    # -- public API ----------------------------------------------------

    @property
    def pages_in_use(self) -> int:
        with self._lock:
            return self.num_pages - len(self._free)

    @property
    def reserved(self) -> int:
        with self._lock:
            return self._reserved

    @property
    def shared_pages(self) -> int:
        with self._lock:
            return self._shared

    def refcount(self, page: int) -> int:
        with self._lock:
            return self._refcount[page]

    def available(self) -> int:
        """Pages an admission could still reserve (reclaimable cached
        chains count — draw() evicts them on demand)."""
        with self._lock:
            return (len(self._free) + self._reclaimable_locked()
                    - self._reserved)

    def reserve(self, n: int) -> bool:
        """Reserve ``n`` pages for a sequence about to be admitted.
        False (and no state change) when the arena cannot guarantee
        them."""
        with self._lock:
            if n > (len(self._free) + self._reclaimable_locked()
                    - self._reserved):
                return False
            self._reserved += n
            return True

    def admit(self, need: int, retain_pages: Sequence[int] = ()) -> bool:
        """Atomic prefix-hit admission: retain ``retain_pages`` (the
        covered prefix chain) AND reserve ``need`` uncovered pages, or do
        neither. The check runs AFTER the retains because pinning a
        cached chain removes it from the reclaimable pool — an admission
        that covers its whole prompt (``need == 0``) can still fail when
        pinning would break ``reserved <= free + reclaimable``."""
        with self._lock:
            taken: List[int] = []
            try:
                for p in retain_pages:
                    self._retain_locked(p)
                    taken.append(p)
            except ValueError:
                self._release_locked(taken)
                return False
            if need > (len(self._free) + self._reclaimable_locked()
                       - self._reserved):
                self._release_locked(taken)
                return False
            self._reserved += need
            return True

    def retain(self, page: int) -> None:
        """Add a reference to a live page (prefix-cache sharing)."""
        with self._lock:
            self._retain_locked(page)

    def unreserve(self, n: int) -> None:
        """Return ``n`` unused reservations (early retirement: EOS before
        max_new_tokens, or a capped window that never grew that far)."""
        with self._lock:
            if n > self._reserved:
                raise ValueError(
                    f"unreserve({n}) exceeds outstanding reservation "
                    f"{self._reserved}")
            self._reserved -= n

    def draw(self) -> int:
        """Hand out one physical page against an existing reservation."""
        with self._lock:
            if self._reserved < 1:
                raise RuntimeError(
                    "draw() without a reservation — admission control "
                    "must reserve before the sequence grows")
            self._reserved -= 1
            if not self._free:
                # reserved <= free + reclaimable: the shortfall is
                # covered by unpinned cached chains — evict LRU leaves
                # until a page frees up
                while not self._free:
                    if (self._index is None
                            or not self._index._reclaim_one_locked()):
                        raise RuntimeError(
                            "allocator invariant breached: reservation "
                            "outstanding but no free or reclaimable page")
            page = self._free.popleft()
            self._refcount[page] = 1
            return page

    def free(self, pages: Sequence[int]) -> None:
        """Release references (sequence retired / CoW detach). A page
        returns to the free list when its LAST reference drops."""
        with self._lock:
            self._release_locked(pages)

    def recycle(self, pages: Sequence[int]) -> None:
        """Give an owner's pages back to the free list and keep as many
        reserved for it: an EVA lane's finished window, whose next window
        draws its pages against the same reservation (``reserved <= free +
        reclaimable`` holds: both grow by the same count)."""
        with self._lock:
            self._release_locked(pages)
            self._reserved += len(pages)

    def note_eviction(self, n: int = 1) -> None:
        self._m_evicted.inc(n)

    def note_cow(self, n: int = 1) -> None:
        self._m_cow.inc(n)


class _PrefixEntry:
    __slots__ = ("key", "parent", "page", "tokens", "children",
                 "pinned_desc", "last_use")

    def __init__(self, key, parent, page, tokens, last_use):
        self.key = key
        self.parent = parent          # parent entry's key, or None (root)
        self.page = page              # physical page id (index holds 1 ref)
        self.tokens = tokens          # this page's token ids (verification)
        self.children = 0             # resident child entries
        self.pinned_desc = 0          # self-pin + children with pinned_desc>0
        self.last_use = last_use


class PrefixIndex:
    """Hash-consed chain over full-page-aligned token prefixes.

    Each entry caches ONE page keyed by ``blake2s(parent_key ||
    page_tokens)`` — a radix tree flattened to a dict, with the page's
    own tokens stored for collision-proof verification (the parent
    digest binds everything before it). The index holds one allocator
    reference per cached page, so a cached page can never be recycled
    under a reader.

    Pinning: an entry is *self-pinned* while its page has references
    beyond the index's own (a lane mapped it). ``pinned_desc`` counts
    self-pin plus pinned descendants, propagated incrementally on the
    allocator's 1<->2 refcount transitions; an entry with
    ``pinned_desc == 0`` is reclaimable and a reclaimable LEAF may be
    evicted (LRU by ``last_use``) when ``draw()`` runs dry. Eviction is
    therefore exactly refcount-aware: shared pages are refused by
    construction.

    All methods run under the owning allocator's RLock.
    """

    def __init__(self, allocator: PageAllocator, page_size: int):
        self.allocator = allocator
        self.page_size = int(page_size)
        self._entries: Dict[bytes, _PrefixEntry] = {}
        self._bypage: Dict[int, bytes] = {}
        self._reclaimable = 0
        self._stamp = 0
        allocator.attach_index(self)

    # -- stats ---------------------------------------------------------

    @property
    def reclaimable(self) -> int:
        return self._reclaimable

    @property
    def cached_pages(self) -> int:
        with self.allocator._lock:
            return len(self._entries)

    # -- key derivation ------------------------------------------------

    def _key(self, parent_key: Optional[bytes], tokens) -> bytes:
        h = hashlib.blake2s(digest_size=16)
        if parent_key is not None:
            h.update(parent_key)
        h.update(np.asarray(tokens, np.int64).tobytes())
        return h.digest()

    # -- allocator callbacks (lock held) -------------------------------

    def _adjust(self, entry: Optional[_PrefixEntry], delta: int) -> None:
        """Propagate a pin/unpin up the ancestor chain: each 0<->positive
        transition of ``pinned_desc`` contributes one unit to the parent
        (walk depth is bounded by pages_per_seq)."""
        while entry is not None:
            was = entry.pinned_desc > 0
            entry.pinned_desc += delta
            now = entry.pinned_desc > 0
            if was == now:
                break
            self._reclaimable += -1 if now else 1
            delta = 1 if now else -1
            entry = (self._entries.get(entry.parent)
                     if entry.parent is not None else None)

    def _on_pin(self, page: int) -> None:
        key = self._bypage.get(page)
        if key is not None:
            self._adjust(self._entries[key], +1)

    def _on_unpin(self, page: int) -> None:
        key = self._bypage.get(page)
        if key is not None:
            self._adjust(self._entries[key], -1)

    # -- lookup / register / reclaim -----------------------------------

    def lookup(self, prompt_ids, max_pages: int) -> List[int]:
        """Longest resident full-page prefix of ``prompt_ids`` → its page
        chain (LRU-stamped). Returns physical page ids WITHOUT retaining
        them — pair with ``allocator.admit(need, pages)`` under the
        allocator lock (the engine's admission path does)."""
        ps = self.page_size
        full = min(len(prompt_ids) // ps, int(max_pages))
        pages: List[int] = []
        with self.allocator._lock:
            self._stamp += 1
            parent: Optional[bytes] = None
            for i in range(full):
                toks = tuple(int(t) for t in prompt_ids[i * ps:(i + 1) * ps])
                key = self._key(parent, toks)
                e = self._entries.get(key)
                if e is None or e.tokens != toks:
                    break
                e.last_use = self._stamp
                pages.append(e.page)
                parent = key
            return pages

    def register(self, prompt_ids, pages: Sequence[int]) -> int:
        """Publish a freshly prefilled lane's full-page prefix chain.
        ``pages`` are the lane's held pages for ``prompt_ids``'s full
        pages, in order. Existing keys are kept (only LRU-stamped): the
        cached page holds identical K/V by construction — K/V content is
        a deterministic function of the token prefix. Returns the number
        of NEW entries."""
        ps = self.page_size
        new = 0
        with self.allocator._lock:
            self._stamp += 1
            parent: Optional[bytes] = None
            for i, page in enumerate(pages):
                toks = tuple(int(t)
                             for t in prompt_ids[i * ps:(i + 1) * ps])
                key = self._key(parent, toks)
                e = self._entries.get(key)
                if e is not None:
                    e.last_use = self._stamp
                    parent = key
                    continue
                # index takes its own reference; the lane's reference
                # makes the page immediately self-pinned
                self.allocator._retain_locked(page)
                e = _PrefixEntry(key, parent, page, toks, self._stamp)
                self._entries[key] = e
                self._bypage[page] = key
                if parent is not None:
                    self._entries[parent].children += 1
                if self.allocator._refcount[page] > 1:
                    # seed self-pin, then propagate to ancestors
                    e.pinned_desc = 1
                    pe = (self._entries.get(parent)
                          if parent is not None else None)
                    self._adjust(pe, +1)
                else:
                    self._reclaimable += 1
                parent = key
                new += 1
            return new

    def _reclaim_one_locked(self) -> bool:
        """Evict the LRU reclaimable LEAF, freeing its page. Called by
        ``draw()`` under the allocator lock when the free list is dry.
        O(entries) scan — entries are bounded by num_pages."""
        best: Optional[_PrefixEntry] = None
        for e in self._entries.values():
            if e.pinned_desc == 0 and e.children == 0:
                if best is None or e.last_use < best.last_use:
                    best = e
        if best is None:
            return False
        self._remove_locked(best)
        return True

    def _remove_locked(self, e: _PrefixEntry) -> None:
        del self._entries[e.key]
        del self._bypage[e.page]
        if e.parent is not None:
            pe = self._entries.get(e.parent)
            if pe is not None:
                pe.children -= 1
        self._reclaimable -= 1
        # drops the index's reference: refcount 1 -> 0 -> free list
        self.allocator._release_locked([e.page])

    def flush(self) -> int:
        """Drop every cached chain (pool reset or model swap — the
        cached K/V no longer matches what a hit would read). Pages still
        referenced by live lanes survive until those lanes retire.
        Returns the number of entries dropped."""
        with self.allocator._lock:
            n = len(self._entries)
            for e in self._entries.values():
                self.allocator._release_locked([e.page])
            self._entries.clear()
            self._bypage.clear()
            self._reclaimable = 0
            return n


class PagedEntry(NamedTuple):
    """What the arena holds for one stateful vertex, as its layer's
    ``paged_entry(lanes)`` declares it. ``rows``: the width of a token's
    row in each of its paged pools, two (a K and a V pool) or one (a latent
    vertex's), with ``heads`` the heads a row holds (an int8 pool keeps a
    scale a (page, head); None: a row of no heads, never quantized).
    ``lane``: instead, the shapes of its per-lane state, one or two arrays
    of a row a lane, float32, no pages. ``window``: ``(window, chunk)``
    where a lane's table is a window of pages and then summary pages (EVA,
    :class:`LaneTables`)."""

    rows: Tuple[int, ...] = ()
    heads: Optional[int] = None
    lane: Tuple[Tuple[int, ...], ...] = ()
    window: Optional[Tuple[int, int]] = None


class PagedKVArena:
    """What the stateful vertices of a decode graph own, in the order the
    walker visits them, + the shared page allocator.

    ``entries`` maps vertex name → its :class:`PagedEntry`. Each of a
    vertex's ``rows`` is a block pool ``[num_pages, page_size, width]``
    (``k_pools[i]``, and ``v_pools[i]`` for a second), paged: ``SENTINEL``
    (= num_pages, one past the pool) marks page-table holes: gathers fill
    zeros there, scatters drop. A vertex of one pool (latent attention,
    ``nn/conf/mla``) has None in ``v_pools[i]`` (an empty pytree: nothing is
    donated, returned or counted for it). A vertex with per-lane state gets
    its ``lane`` arrays in the same two slots: a convolution tail ``[lanes,
    K-1, C]`` and an SSM state ``[lanes, H, P, N]`` (``nn/conf/ssm``) or a
    delta-rule state ``[lanes, H, D, D]`` (``nn/conf/kda``); fixed-size
    whatever the sequence's length, so it takes no page; a lane's row is
    zeroed inside the first prefill program of the sequence it is given to,
    never by the arena. Both kinds ride the engine's donated-pytree
    dispatch protocol alike and are rebuilt together after a failed
    dispatch (:meth:`reset_pools`).

    A pool is stored ``[num_pages, page_size, h*d]`` (a token's heads in
    one row), for every layer, dtype and arena alike: a decode program's
    entry parameter has the default layout, and for this shape that is
    the one the compiler keeps for the scatter and the gather, so a
    donated pool is updated in place. Stored ``[..., h, d]`` every
    program relaid every pool at its entry and before its result
    (PERF.md, PR 32). No program reshapes a whole pool; the heads are
    split off the gathered pages (``ops/paged_attention``, "Layout
    conventions").

    ``kv_dtype="int8"`` swaps each pool for a ``(q_int8, scales)`` tuple
    — ``q_int8`` is ``[num_pages, page_size, h*d]`` int8, ``scales`` is
    ``[num_pages, h]`` f32 per-(page, head) — quantized on write and
    dequantized in ``ops/paged_attention.paged_gather``. Tuples ride the
    engine's donated-pytree dispatch protocol unchanged. Recurrent state
    is never quantized.
    """

    def __init__(self, entries: Dict[str, PagedEntry], *,
                 num_pages: int, page_size: int, dtype=jnp.float32,
                 registry: Optional[_metrics.MetricsRegistry] = None,
                 with_allocator: bool = True,
                 kv_dtype: Optional[str] = None,
                 prefix_cache: bool = False):
        """``with_allocator=False`` builds a POOLS-ONLY shadow arena —
        the speculative-decoding draft model's K/V lives in one of
        these, indexed by the page tables the TARGET's allocator owns
        (one admission/eviction decision covers both models). A shadow
        arena must never allocate (``allocator`` is None) nor register
        page gauges (they would shadow the owning arena's series on a
        shared registry). ``prefix_cache=True`` attaches a
        :class:`PrefixIndex` to the allocator."""
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        if not entries:
            raise ValueError("arena needs at least one stateful layer")
        if kv_dtype not in (None, "int8"):
            raise ValueError(f"kv_dtype must be None or 'int8', "
                             f"got {kv_dtype!r}")
        self.page_size = int(page_size)
        self.num_pages = int(num_pages)
        self.sentinel = self.num_pages
        self.dtype = dtype
        self.kv_dtype = kv_dtype
        self.entries = dict(entries)
        if kv_dtype == "int8" and any(e.rows and e.heads is None
                                      for e in self.entries.values()):
            raise ValueError(
                "kv_dtype='int8' with a latent attention vertex: the int8 "
                "pools keep one scale a (page, head), and a latent row has "
                "no heads: its normalised latent and its rotary key differ "
                "in scale by columns — serve this net with float pools")
        self.k_pools: List = []
        self.v_pools: List = []
        self.reset_pools()
        self.allocator = (PageAllocator(num_pages, registry=registry)
                          if with_allocator else None)
        self.prefix_index = (
            PrefixIndex(self.allocator, self.page_size)
            if (prefix_cache and self.allocator is not None) else None)

    def reset_pools(self) -> None:
        """Fresh zero pools. Used at construction AND after a failed
        dispatch: the engine donates the pools into every step, so an
        error mid-dispatch may have consumed the old buffers — rebuilding
        is the only safe recovery (retiring sequences freed the pages;
        zeros are indistinguishable from a fresh arena). NOTE: callers
        recovering a live engine must also ``prefix_index.flush()`` —
        zeroed pools would serve stale prefix hits."""
        self.k_pools = []
        self.v_pools = []
        for e in self.entries.values():
            pools = ([jnp.zeros(shape, jnp.float32) for shape in e.lane]
                     or [self._pool(width, e.heads) for width in e.rows])
            self.k_pools.append(pools[0])
            self.v_pools.append(pools[1] if len(pools) > 1 else None)

    def _pool(self, width: int, heads: Optional[int]):
        shape = (self.num_pages, self.page_size, width)
        if self.kv_dtype == "int8":
            return (jnp.zeros(shape, jnp.int8),
                    jnp.zeros((self.num_pages, heads), jnp.float32))
        return jnp.zeros(shape, self.dtype)

    def pages_for(self, n_tokens: int) -> int:
        """Pages needed to hold ``n_tokens`` (ceil)."""
        return -(-int(n_tokens) // self.page_size)

    def _owned(self, lane: bool):
        """The pools of the vertices with (``lane``) or without per-lane
        state, None left out."""
        return [p for e, k, v in zip(self.entries.values(), self.k_pools,
                                     self.v_pools)
                if bool(e.lane) == lane for p in (k, v) if p is not None]

    def state_nbytes(self) -> int:
        """Bytes of the per-lane recurrent state (no pool among them)."""
        return sum(int(p.nbytes) for p in self._owned(lane=True))

    def token_nbytes(self) -> int:
        """Bytes ONE cached token takes over all the paged pools (a
        quantized pool's per-page scales and the per-lane recurrent state,
        which no token adds to, left out)."""
        return sum(codes.shape[-1] * codes.dtype.itemsize
                   for codes in (p[0] if isinstance(p, tuple) else p
                                 for p in self._owned(lane=False)))

    def nbytes(self) -> int:
        return sum(int(x.nbytes) for p in self._owned(True)
                   + self._owned(False)
                   for x in (p if isinstance(p, tuple) else (p,)))


class LaneTables:
    """The lanes' host page state and the one policy that fills it: each
    lane's page table (``tables [lanes, width]``, the row a dispatch hands
    the paged reads), fed positions (``pos``), evicted positions
    (``base``), held pages (``held``; an EVA lane's summary pages in
    ``summaries``), unused reservation (``reserve_left``) and prefix-cache
    cover (``covered``), through admission (:meth:`admit`), paging
    (:meth:`ensure`) and release (:meth:`release`); ``shadow`` is a draft
    arena the same tables index.

    A lane's table is its window of ``pages_per_seq`` pages, which slides a
    page at a time. Where the arena's vertices declare a window
    (``PagedEntry.window``: EVA) it is that window's pages (and one more,
    which a fused block that crosses an edge draws before the last window's
    pages go back), then the summary pages of the longest sequence
    (``page_size`` chunks a page); one table serves every such vertex, and
    no other paged vertex may share it."""

    def __init__(self, arena: PagedKVArena, lanes: int, pages_per_seq: int,
                 *, prefill_chunk: int, block_len: int,
                 registry: _metrics.MetricsRegistry,
                 shadow: Optional[PagedKVArena] = None):
        self.arena, self.shadow = arena, shadow
        self.lanes, self.page_size = int(lanes), arena.page_size
        self.pages_per_seq = int(pages_per_seq)
        self.window = self.page_size * self.pages_per_seq
        self.width = self.pages_per_seq
        self.eva = any(e.window for e in arena.entries.values())
        if self.eva:
            self._eva_geometry(prefill_chunk, block_len, registry)
        self.tables = np.full((self.lanes, self.width), arena.sentinel,
                              np.int32)
        self.pos = np.zeros(self.lanes, np.int64)     # global fed positions
        self.base = np.zeros(self.lanes, np.int64)    # evicted positions
        self.held: List[List[int]] = [[] for _ in range(self.lanes)]
        self.summaries: List[List[int]] = [[] for _ in range(self.lanes)]
        self.reserve_left = np.zeros(self.lanes, np.int64)
        self.covered = np.zeros(self.lanes, np.int64)  # prefix-hit tokens

    def _eva_geometry(self, prefill_chunk: int, block_len: int,
                      registry: _metrics.MetricsRegistry) -> None:
        entries = self.arena.entries
        others = sorted(n for n, e in entries.items()
                        if e.rows and not e.window)
        if others:
            raise ValueError(
                f"EVA vertices beside other attention vertices {others[:2]}: "
                "an EVA lane's table holds one window and its summaries, "
                "where a full attention vertex needs every page of the "
                "sequence (no tables per kind of layer yet)")
        geometry = {e.window for e in entries.values() if e.window}
        if len(geometry) > 1:
            raise ValueError(f"EVA vertices with different windows or chunks "
                             f"{sorted(geometry)}: one table serves them all")
        (window, chunk), = geometry
        ps = self.page_size
        if ps != chunk:
            raise ValueError(
                f"page_size={ps} with EVA chunks of {chunk}: a page holds "
                "one chunk, which is pooled into its summary row when it "
                "fills — set page_size to the chunk size")
        if window % prefill_chunk:
            raise ValueError(
                f"prefill_chunk={prefill_chunk} does not divide the EVA "
                f"window of {window}: a prefill chunk must not cross a "
                "window edge (the lane's table holds one window and the "
                "next window's first page)")
        if block_len > ps:
            raise ValueError(
                f"block_len={block_len} exceeds the page of {ps}: a "
                "fused block crosses an EVA window edge into one page of "
                "the next window at most")
        self.eva_window, self.eva_chunk = window, chunk
        self.win_pages = window // ps + 1
        self.sum_pages = -(-(self.window // chunk) // ps)
        self.width = self.win_pages + self.sum_pages
        if self.width > self.arena.num_pages:
            raise ValueError(
                f"an EVA lane may hold {self.width} pages, more than "
                f"the arena's {self.arena.num_pages}: one sequence could "
                "never run")
        self._m_retired = registry.counter(
            "eva_window_pages_retired_total",
            "Window pages EVA lanes gave back to the allocator at a window "
            "edge")
        ref = weakref.ref(self)

        def held_per_token():
            t = ref()
            if t is None:
                raise LookupError("lane tables retired")
            live = [i for i in range(t.lanes) if t.pos[i] > 0]
            tokens = sum(int(t.pos[i]) for i in live)
            return (sum(t.held_pages(i) for i in live) * t.page_size / tokens
                    if tokens else 0.0)
        registry.gauge(
            "eva_held_rows_per_context_token",
            "Rows of the pages the live EVA lanes hold over the positions "
            "they have fed (1 and more if windows were never given back)"
        ).set_function(held_per_token)

    # -- admission and release ------------------------------------------

    def admit(self, lane: int, total_tokens: int,
              prompt=None) -> Optional[int]:
        """The page half of admitting a sequence of ``total_tokens`` into
        ``lane``: a worst-case page reservation, or None when the arena
        cannot guarantee it. With the prefix cache and ``prompt`` given,
        the longest resident full-page prefix is mapped (retained) into the
        table, those pages skip prefill, and only the UNCOVERED pages are
        reserved (``pages_per_seq`` for a sequence that will outgrow the
        window: every shared page may detach copy-on-write, drawing a
        private one). Returns the number of pages mapped."""
        alloc, index = self.arena.allocator, self.arena.prefix_index
        worst = self.arena.pages_for(total_tokens)
        covered: List[int] = []
        if index is not None and prompt is not None:
            # lookup + admit under one lock: a page the lookup returned
            # cannot be reclaimed before admit() pins it
            with alloc._lock:
                covered = index.lookup(prompt, self.pages_per_seq)
                if worst > self.pages_per_seq:
                    need = self.pages_per_seq      # CoW detaches may draw
                else:
                    need = worst - len(covered)
                if not alloc.admit(need, covered):
                    return None
        else:
            need = (self._eva_pages(total_tokens) if self.eva
                    else min(self.pages_per_seq, worst))
            if not alloc.reserve(need):
                return None
        cov = len(covered)
        self.base[lane] = 0
        self.reserve_left[lane] = need
        self.held[lane] = list(covered)
        self.tables[lane, :] = self.arena.sentinel
        self.tables[lane, :cov] = covered
        self.covered[lane] = cov * self.page_size
        # feed resumes after the covered prefix; a full cover re-feeds
        # the last prompt token (write dropped) for its distribution
        full = prompt is not None and self.covered[lane] >= len(prompt)
        self.pos[lane] = len(prompt) - 1 if full else self.covered[lane]
        return cov

    def register_prefix(self, lane: int, prompt_ids) -> int:
        """Publish a freshly prefilled lane's full-page prompt prefix to
        the index (no-op without one, or if the lane's window already
        slid — its leading pages no longer hold the prompt's start).
        Called the moment prefill completes, while the lane still holds
        its pages."""
        index = self.arena.prefix_index
        if index is None or self.base[lane] != 0:
            return 0
        full = min(len(prompt_ids) // self.page_size, self.pages_per_seq)
        if full <= 0:
            return 0
        return index.register(prompt_ids, self.held[lane][:full])

    def release(self, lane: int) -> None:
        """The lane's page references released (a page returns to the
        free list at refcount 0 — prefix-cached pages stay resident under
        the index's reference) and its unused reservation returned."""
        alloc = self.arena.allocator
        alloc.free(self.held[lane] + self.summaries[lane])
        if self.reserve_left[lane]:
            alloc.unreserve(int(self.reserve_left[lane]))
        self.held[lane], self.summaries[lane] = [], []
        self.reserve_left[lane] = self.covered[lane] = 0
        self.pos[lane] = self.base[lane] = 0
        self.tables[lane, :] = self.arena.sentinel

    # -- paging -----------------------------------------------------------

    def ensure(self, lane: int, n_new: int) -> None:
        """Pre-dispatch host bookkeeping: make the lane's view hold slots
        for ``n_new`` tokens at positions ``pos .. pos+n_new-1`` — first
        sliding the window a page at a time when the view is full (an EVA
        lane: giving back the finished window at its edge), then lazily
        drawing reserved pages as the sequence grows (an EVA lane: its
        summary pages too)."""
        if n_new > self.window:
            raise ValueError(f"chunk of {n_new} exceeds the "
                             f"window ({self.window})")
        last = int(self.pos[lane]) + n_new - 1
        fresh = self._retire(lane) if self.eva else self._slide(lane, last)
        held, ps = self.held[lane], self.page_size
        while len(held) <= (last - self.base[lane]) // ps:
            page = self._draw(lane)
            self.tables[lane, len(held)] = page
            held.append(page)
            fresh.append(page)
        sums = self.summaries[lane]
        while self.eva and len(sums) * ps < (last + 1) // self.eva_chunk:
            page = self._draw(lane)
            self.tables[lane, self.win_pages + len(sums)] = page
            sums.append(page)
        if fresh:
            self._reset_page_scales(fresh)

    def _slide(self, lane: int, last: int) -> List[int]:
        """Evict the oldest pages until position ``last`` is in the view;
        returns the pages whose rows are now stale."""
        base, held = int(self.base[lane]), self.held[lane]
        alloc = self.arena.allocator
        fresh: List[int] = []
        while last - base >= self.window:
            # sliding window at page granularity: the oldest page is
            # recycled as the LAST LIVE table entry. Only the live
            # prefix [0, len(held)) shifts — rotating the full row when
            # the table still has sentinel holes would smear a hole into
            # the middle and drop the chunk's writes. The recycled
            # page's stale slots are either overwritten by this chunk
            # or sit beyond the causal mask until they are.
            oldest = held.pop(0)
            if alloc.refcount(oldest) > 1:
                # COPY-ON-WRITE detach: the oldest page is shared (the
                # prefix index and/or another lane still reads it) —
                # recycling it in place would overwrite their K/V.
                # Sharing is full-page only and tails re-prefill from
                # the page boundary, so no content copy is ever needed:
                # release our reference and draw a private tail instead
                # (admission reserved pages_per_seq for window-sliding
                # sequences precisely so these draws cannot fail).
                alloc.free([oldest])
                replacement = self._draw(lane)
                alloc.note_cow()
            else:
                replacement = oldest   # its rows are all pre-window now
            fresh.append(replacement)
            held.append(replacement)
            n = len(held)
            self.tables[lane, :n - 1] = self.tables[lane, 1:n]
            self.tables[lane, n - 1] = replacement
            base += self.page_size
            alloc.note_eviction()
        self.base[lane] = base
        return fresh

    def _retire(self, lane: int) -> List[int]:
        """An EVA lane whose next position lies past a window edge gives
        the finished window's pages back to the allocator (their chunks
        are summary rows by now) and keeps them reserved for its next
        window; ``base`` is the window's first position, so view-relative
        slots index the window pages as they index a plain lane's view."""
        held, start = self.held[lane], (int(self.pos[lane]) // self.eva_window
                                        * self.eva_window)
        if start > self.base[lane]:
            with region("sched.eva_retire"):
                n = min(len(held), (start - int(self.base[lane]))
                        // self.page_size)
                done, held[:] = held[:n], held[n:]
                self.arena.allocator.recycle(done)
                self.reserve_left[lane] += len(done)
                self.tables[lane, :self.win_pages] = self.arena.sentinel
                self.tables[lane, :len(held)] = held
                self._m_retired.inc(len(done))
                self.base[lane] = start
        return []

    def _eva_pages(self, total_tokens: int) -> int:
        """Pages an EVA lane of ``total_tokens`` may hold at once: its
        window's, one more while a block crosses an edge, and the summary
        pages of every chunk it can complete."""
        return (min(self.win_pages, self.arena.pages_for(total_tokens))
                + -(-(total_tokens // self.eva_chunk) // self.page_size))

    def _draw(self, lane: int) -> int:
        """One page for ``lane``, against its reservation."""
        self.reserve_left[lane] -= 1
        return self.arena.allocator.draw()

    def _reset_page_scales(self, pages: List[int]) -> None:
        """int8 arenas: zero the quantization scales of freshly drawn
        pages. A recycled page's scale is a max over its PREVIOUS
        owner's rows — folding new writes into it would quantize them
        needlessly coarsely, and stale codes × zero scale dequantize to
        exact zeros (fp pools get the same hygiene from the causal
        mask). Host-side eager updates on the small ``[num_pages, h]``
        scale arrays, between dispatches, under the scheduler's tick."""
        idx = np.asarray(pages, np.int32)
        for arena in (self.arena, self.shadow):
            if arena is None or arena.kv_dtype != "int8":
                continue
            for pools in (arena.k_pools, arena.v_pools):
                for i, pool in enumerate(pools):
                    if isinstance(pool, tuple):     # not recurrent state
                        q, s = pool
                        pools[i] = (q, s.at[idx].set(0.0))

    # -- what a dispatch reads --------------------------------------------

    def advance(self, lane: int, n: int) -> None:
        """Account ``n`` tokens written by the dispatch that just ran."""
        self.pos[lane] += int(n)

    def rel_pos(self, lane: int) -> int:
        """View-relative position of the lane's next token."""
        return int(self.pos[lane] - self.base[lane])

    def absolute(self, lanes: np.ndarray, rel) -> np.ndarray:
        """``rel`` of ``lanes`` plus what their windows evicted (a lane id
        past the last, a bucket's padded slot, evicted nothing)."""
        base = np.append(self.base, 0)[np.minimum(lanes, self.lanes)]
        return np.asarray(rel, np.int64) + base

    def held_pages(self, lane: int) -> int:
        """Pages the lane holds, window and summary pages alike."""
        return len(self.held[lane]) + len(self.summaries[lane])
