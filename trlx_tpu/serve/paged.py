"""Host side of the paged KV cache: free-list page allocator + radix-tree
prefix cache.

The device half (trlx_tpu.models.generation / transformer ``block_apply``
paged mode) is shape-static and dumb on purpose: it scatters/gathers
through whatever per-slot page tables it is handed. ALL policy lives
here, in plain-python structures the scheduler thread owns exclusively:

- :class:`PageAllocator` — a free list over ``num_pages`` fixed-size KV
  pages plus per-page refcounts (number of live slots whose table maps
  the page). ``alloc`` never blocks and never raises on pressure: it
  returns ``None``, and the scheduler leaves the request QUEUED (the
  exhaustion -> queue-not-crash contract). Refcounts are guarded — a
  release below zero is a real bookkeeping bug and raises.
- :class:`RadixCache` — vLLM's block pool crossed with SGLang's
  RadixAttention (Zheng et al., 2023), rebuilt block-granular: a trie
  over ``page_size``-token blocks of COMMITTED prompts, each node owning
  the physical page that holds that block's KV. Admission walks the
  prompt's full blocks through the trie; every hit page is refcounted
  and mapped copy-free into the new slot's page table, and only the
  unmatched suffix is prefilled. Matches are capped one token short of
  the prompt (``(len - 1) // page_size`` blocks) so at least one suffix
  token always runs — the first-step logits must come from a real
  forward. Pages whose refcount is 0 but that the trie still owns are
  *cached*, not free: when ``alloc`` runs dry it evicts refcount-0 LEAF
  nodes in LRU order (evicting an interior node would orphan its
  descendants' prefixes) until the request fits or nothing evictable
  remains.

Commit happens at ADMISSION, not harvest: the pages of the suffix a
request is about to prefill enter the trie immediately, so later
requests in the very same admission batch (and every batch after) hit
them. That is sound because the device program scatters each layer's
fresh K/V *before* the attention gather reads it — a same-batch sharer's
gather sees the owner row's writes — and because committed-but-pending
pages always carry refcount >= 1 (the owner slot), so they cannot be
evicted before their content lands. A failed prefill rolls the inserted
nodes back (:meth:`RadixCache.rollback`).

A model with window layers keeps TWO CLASSES of page (docs "serving",
"Two classes of page"): every full layer is addressed through the
full-class tables above, every window layer through a window-class table
over a second allocator. A committed block owns a full-class page and,
while it is kept, a window-class page; a slot maps only the window-class
pages its window still reaches and releases each at the step that passes
it. A prefix match is usable only as far as the window-class pages of the
``window / page_size`` blocks before its end are still there, and the
window-class pages of blocks deep inside a cached prefix (no prompt and no
match has ended within a window below them) are the first evicted.

Everything here is nanosecond-scale dict/list work on the scheduler
thread — no jax, no device syncs. The allocator's free list and
refcounts carry their own mutex (the reload/drain paths reach them from
off-worker threads); the radix trie itself stays worker-confined.
"""

import heapq
import threading
from typing import Dict, List, Optional, Sequence, Tuple

from trlx_tpu import telemetry


class PageAllocator:
    """Free-list allocator + refcounts for a fixed pool of KV pages."""

    def __init__(self, num_pages: int):
        if num_pages <= 0:
            raise ValueError(f"num_pages={num_pages} must be >= 1")
        self.num_pages = num_pages
        self._lock = threading.Lock()
        # LIFO free list: recently-freed pages are reused first (their
        # HBM is warm, and reuse order is deterministic for tests)
        self._free: List[int] = list(range(num_pages - 1, -1, -1))  # guarded-by: _lock
        self._ref: List[int] = [0] * num_pages  # guarded-by: _lock

    def free_count(self) -> int:
        # read under the lock: /healthz and the drain path call this
        # from off-worker threads while alloc/free resize the list
        with self._lock:
            return len(self._free)

    def refcount(self, page: int) -> int:
        return self._ref[page]

    def alloc(self, n: int) -> Optional[List[int]]:
        """``n`` fresh pages at refcount 1, or ``None`` when the free
        list cannot cover them (caller decides whether to evict/queue —
        never partial, never raising)."""
        if n < 0:
            raise ValueError(f"alloc({n})")
        with self._lock:
            if n > len(self._free):
                return None
            pages = [self._free.pop() for _ in range(n)]
            for p in pages:
                self._ref[p] = 1
        return pages

    def retain(self, page: int) -> None:
        with self._lock:
            self._ref[page] += 1

    def release(self, page: int) -> int:
        """Drop one reference; returns the new refcount. A page at
        refcount 0 is NOT auto-freed — the radix cache may still own it
        (cached, evictable); :meth:`free_page` returns it to the list."""
        with self._lock:
            ref = self._ref[page] - 1
            if ref < 0:
                raise RuntimeError(
                    f"page {page} released below refcount 0 — allocator "
                    f"bookkeeping bug (double free)"
                )
            self._ref[page] = ref
        return ref

    def free_page(self, page: int) -> None:
        with self._lock:
            if self._ref[page] != 0:
                raise RuntimeError(
                    f"page {page} freed at refcount {self._ref[page]} "
                    f"(> 0)"
                )
            self._free.append(page)


class _Node:
    """One committed token block: ``key`` (the block's tokens) under its
    parent, owning physical ``page``."""

    __slots__ = ("key", "page", "wpage", "parent", "children", "last_used",
                 "end")

    def __init__(self, key, page, parent):
        self.key: Tuple[int, ...] = key
        self.page: int = page
        self.wpage: int = -1  # its window-class page, while that is kept
        self.end = False  # a committed prompt, or a match, ended on it
        self.parent: Optional["_Node"] = parent
        self.children: Dict[Tuple[int, ...], "_Node"] = {}
        self.last_used = 0


class RadixCache:
    """Block-granular radix tree over committed prompt pages + the
    allocator they live in. The scheduler's one-stop paged-KV broker:
    ``match`` -> ``alloc`` -> ``commit`` at admission, ``release_all`` at
    harvest, ``evict`` under pressure (called by ``alloc`` itself)."""

    def __init__(self, num_pages: int, page_size: int,
                 window_pages: int = 0, window: int = 0):
        if page_size <= 0:
            raise ValueError(f"page_size={page_size} must be >= 1")
        self.allocator = PageAllocator(num_pages)
        self.page_size = page_size
        self._root = _Node((), -1, None)
        self._node_of_page: Dict[int, _Node] = {}
        self._clock = 0  # LRU tick (monotonic per-operation counter)
        self.evicted_pages = 0  # lifetime counter (telemetry mirrors it)
        # -- the window class (a model with window layers) ---------------
        #: blocks before a match's end whose window-class pages it needs
        self.window_blocks = -(-window // page_size) if window_pages else 0
        self.window_allocator: Optional[PageAllocator] = (
            PageAllocator(window_pages) if window_pages else None
        )
        self._node_of_wpage: Dict[int, _Node] = {}
        #: window-class pages live slots may still take (their quota less
        #: what they hold): free + evictable never falls below it
        self.window_reserved = 0
        self.window_pages_freed = 0  # released behind a window, lifetime
        #: pages evicted per pass once a free list runs dry. Two classes
        #: keep long prefixes cached, so a pass walks thousands of blocks:
        #: it frees a batch (a sixteenth of the class, at most 64 pages; an
        #: eighth of the window class, whose pass also asks of every
        #: candidate whether it is deep), and the allocations that follow
        #: walk nothing
        self._evict_batch = max(min(64, num_pages // 16), 1) \
            if window_pages else 1
        self._evict_batch_window = max(window_pages // 8, 1)

    # -- introspection ---------------------------------------------------

    def cached_pages(self) -> int:
        """Pages the trie owns (committed blocks, hit-able)."""
        return len(self._node_of_page)

    def evictable_pages(self) -> int:
        return sum(
            1 for p in self._node_of_page
            if self.allocator.refcount(p) == 0
        )

    def free_pages(self) -> int:
        return self.allocator.free_count()

    def available_pages(self) -> int:
        """Free now + evictable under pressure — what admission can
        actually obtain for a new request."""
        return self.free_pages() + self.evictable_pages()

    # -- prefix match ----------------------------------------------------

    def match(self, tokens: Sequence[int]) -> List[int]:
        """Longest committed prefix of ``tokens`` in whole blocks, capped
        at ``(len(tokens) - 1) // page_size`` so >= 1 suffix token always
        remains to prefill. Every returned page is RETAINED for the
        caller (release via :meth:`release_all` at harvest) and
        LRU-touched."""
        self._clock += 1
        pages: List[int] = []
        for node in self._walk(tokens):
            node.last_used = self._clock
            self.allocator.retain(node.page)
            pages.append(node.page)
        return pages

    def _walk(self, tokens: Sequence[int]) -> List[_Node]:
        """The committed nodes along ``tokens``' whole blocks, capped one
        token short of the prompt."""
        ps = self.page_size
        node, out = self._root, []
        for i in range(max(len(tokens) - 1, 0) // ps):
            node = node.children.get(tuple(tokens[i * ps:(i + 1) * ps]))
            if node is None:
                break
            out.append(node)
        return out

    def match_classes(self, tokens: Sequence[int]):
        """:meth:`match` for two classes of page: ``(pages, wpages)``, the
        full-class pages of the usable prefix and ``{block: window-class
        page}`` of the ``window_blocks`` blocks before its end, all
        RETAINED. A match of ``m`` blocks is usable only if those blocks'
        window-class pages are still kept (a window layer's first suffix
        token reads them); else it is cut back to the longest prefix for
        which they are, or to nothing."""
        nodes = self._walk(tokens)
        wb = self.window_blocks
        m = len(nodes)
        while m > 0 and any(n.wpage < 0 for n in nodes[max(m - wb, 0):m]):
            m -= 1
        self._clock += 1
        if m:
            nodes[m - 1].end = True  # where this prompt left the cached ones
        pages, wpages = [], {}
        for i, node in enumerate(nodes[:m]):
            node.last_used = self._clock
            self.allocator.retain(node.page)
            pages.append(node.page)
            if i >= m - wb:
                self.window_allocator.retain(node.wpage)
                wpages[i] = node.wpage
        return pages, wpages

    def peek_continuation(self, tokens: Sequence[int], k: int) -> List[int]:
        """Read-only speculation probe: up to ``k`` tokens that committed
        prompts continued ``tokens`` with. Walks the trie by whole
        blocks, finishes a partial tail block from a prefix-matching
        child, then follows child chains. Touches NOTHING — no
        refcounts, no LRU clock — so a wrong guess costs only the
        verify pass that rejects it."""
        ps = self.page_size
        node = self._root
        blocks = len(tokens) // ps
        for i in range(blocks):
            child = node.children.get(tuple(tokens[i * ps:(i + 1) * ps]))
            if child is None:
                return []
            node = child
        out: List[int] = []
        tail = tuple(tokens[blocks * ps:])
        if tail:
            nxt = None
            for key, child in node.children.items():
                if key[:len(tail)] == tail:
                    nxt = child
                    break
            if nxt is None:
                return []
            out.extend(nxt.key[len(tail):])
            node = nxt
        while len(out) < k and node.children:
            node = next(iter(node.children.values()))
            out.extend(node.key)
        return out[:k]

    # -- allocation + eviction -------------------------------------------

    def alloc(self, n: int) -> Optional[List[int]]:
        """``n`` pages at refcount 1, evicting LRU refcount-0 cached
        leaves as needed; ``None`` (nothing allocated, nothing evicted
        beyond what was already needed) when even full eviction cannot
        cover the request."""
        short = n - self.allocator.free_count()
        if short > 0 and self.evict(max(short, self._evict_batch)) < short:
            return None
        return self.allocator.alloc(n)

    def evict(self, n: int) -> int:
        """Evict up to ``n`` refcount-0 LEAF nodes, least-recently-used
        first, returning their pages to the free list. Returns how many
        were actually evicted. Interior nodes become leaves as their
        children go, so repeated passes walk chains root-ward."""
        # one pass collects the evictable leaves; a parent joins the heap
        # when its last child goes (the order is the one a fresh scan per
        # victim gives: the least recently used evictable leaf each time)
        heap = [
            (node.last_used, page) for page, node in self._node_of_page.items()
            if not node.children and self.allocator.refcount(page) == 0
        ]
        heapq.heapify(heap)
        evicted = 0
        while evicted < n and heap:
            victim = self._node_of_page[heapq.heappop(heap)[1]]
            parent = victim.parent
            self._remove_node(victim)
            self.allocator.free_page(victim.page)
            evicted += 1
            if parent is not self._root and not parent.children \
                    and self.allocator.refcount(parent.page) == 0:
                heapq.heappush(heap, (parent.last_used, parent.page))
        if evicted:
            self.evicted_pages += evicted
            telemetry.inc("serve/evicted_pages", evicted)
        return evicted

    def _remove_node(self, node: _Node) -> None:
        del node.parent.children[node.key]
        del self._node_of_page[node.page]
        if node.wpage >= 0:  # the block goes, and its window-class page
            self._drop_wpage(node)

    # -- the window class --------------------------------------------------

    def _drop_wpage(self, node: _Node) -> None:
        """Take a node's window-class page from the trie; free it unless a
        live slot still maps it (it then frees at that slot's release)."""
        wpage, node.wpage = node.wpage, -1
        del self._node_of_wpage[wpage]
        if self.window_allocator.refcount(wpage) == 0:
            self.window_allocator.free_page(wpage)

    def window_free_pages(self) -> int:
        return self.window_allocator.free_count()

    def window_evictable_pages(self) -> int:
        return sum(1 for p in self._node_of_wpage
                   if self.window_allocator.refcount(p) == 0)

    def window_available_pages(self) -> int:
        """What a NEW request can still obtain of the window class: free
        + evictable, less what live slots have reserved."""
        return (self.window_free_pages() + self.window_evictable_pages()
                - self.window_reserved)

    def evict_window(self, n: int) -> int:
        """Evict up to ``n`` cached window-class pages no slot maps. The
        pages of blocks DEEP inside a cached prefix go first, then least
        recently used. A block is deep when no prompt end lies within
        ``window_blocks`` blocks below it: a match reads the window-class
        pages of the ``window_blocks`` blocks before its end, and matches
        end where earlier prompts ended or left the cached ones (a
        document's last block, asked about again and again) — not merely
        on a leaf: the questions committed below a document must not turn
        its last pages into deep ones. The block keeps its full-class
        page; a later match is cut back where its window-class page is
        gone."""
        wb = self.window_blocks

        def deep(node: _Node) -> bool:
            """No prompt end within ``wb`` blocks at or below ``node``
            (breadth first down its subtree; a chain is a straight walk)."""
            frontier = [node]
            for _ in range(wb):
                if any(x.end or not x.children for x in frontier):
                    return False
                frontier = [c for x in frontier for c in x.children.values()]
            return True

        victims = sorted(
            (n_ for p, n_ in self._node_of_wpage.items()
             if self.window_allocator.refcount(p) == 0),
            key=lambda n_: (not deep(n_), n_.last_used),
        )[:n]
        for node in victims:
            self._drop_wpage(node)
        if victims:
            telemetry.inc("serve/evicted_pages", len(victims),
                          labels={"class": "window"})
        return len(victims)

    def alloc_window(self, n: int, reserve: int = 0,
                     reserved: bool = False) -> Optional[List[int]]:
        """``n`` window-class pages at refcount 1 (evicting cached ones as
        needed), or ``None``. A NEW request also sets ``reserve`` pages
        aside for its later growth and is refused unless both fit beside
        what live slots have reserved; ``reserved=True`` takes ``n`` pages
        OUT of the caller's reservation, which cannot fail."""
        if reserved:
            self.window_reserved -= n
        elif self.window_available_pages() < n + reserve:
            return None
        short = n - self.window_free_pages()
        if short > 0 and self.evict_window(
            max(short, self._evict_batch_window)
        ) < short:
            if reserved:
                raise RuntimeError(
                    "window-class reservation broken: a reserved page "
                    "could not be obtained (allocator bookkeeping bug)"
                )
            return None
        self.window_reserved += reserve
        return self.window_allocator.alloc(n)

    def release_window(self, wpages: Sequence[int], behind: bool = False,
                       back_to_reserve: bool = False) -> None:
        """Drop one reference per window-class page; a page at refcount 0
        frees unless the trie still owns it (then it stays cached).
        ``behind`` counts them as released behind a window (not at
        harvest); ``back_to_reserve`` returns the places to the releasing
        slot's reservation (it lives on and may take them again)."""
        for wpage in wpages:
            if self.window_allocator.release(wpage) == 0 \
                    and wpage not in self._node_of_wpage:
                self.window_allocator.free_page(wpage)
        if behind:
            self.window_pages_freed += len(wpages)
        if back_to_reserve:
            self.window_reserved += len(wpages)

    def attach_window(self, page: int, wpage: int) -> bool:
        """Give the committed block that owns full-class ``page`` the
        window-class page ``wpage`` (a slot just wrote it), unless the
        block has one or the page is not the trie's. The slot keeps its
        own reference either way."""
        node = self._node_of_page.get(page)
        if node is None or node.wpage >= 0:
            return False
        node.wpage = wpage
        self._node_of_wpage[wpage] = node
        return True

    # -- commit / rollback / release -------------------------------------

    def commit(self, tokens: Sequence[int],
               pages: Sequence[int]) -> List[int]:
        """Insert ``tokens``' full blocks (``len // page_size``) into the
        trie, block i living on ``pages[i]`` (the slot's page table:
        matched prefix pages first, then the fresh suffix pages). Blocks
        already present keep their existing page — a racing duplicate
        page simply never enters the trie and frees at harvest. Returns
        the newly inserted pages (the rollback handle for a failed
        prefill)."""
        ps = self.page_size
        self._clock += 1
        node = self._root
        inserted: List[int] = []
        for i in range(len(tokens) // ps):
            key = tuple(tokens[i * ps:(i + 1) * ps])
            child = node.children.get(key)
            if child is None:
                child = _Node(key, pages[i], node)
                node.children[key] = child
                self._node_of_page[pages[i]] = child
                inserted.append(pages[i])
            child.last_used = self._clock
            node = child
        if node is not self._root:
            node.end = True  # the prompt's last whole block
        return inserted

    def rollback(self, inserted: Sequence[int]) -> None:
        """Un-commit pages a failed prefill never filled (deepest first,
        so parents are leaves by the time they go). Refcounts are the
        caller's to release — this only detaches the trie nodes."""
        for page in reversed(list(inserted)):
            node = self._node_of_page.get(page)
            if node is not None and not node.children:
                self._remove_node(node)

    def release_all(self, pages: Sequence[int]) -> None:
        """Harvest path: drop one reference per page; pages at refcount 0
        return to the free list unless the trie still owns them (then
        they stay cached/evictable)."""
        for page in pages:
            if self.allocator.release(page) == 0 \
                    and page not in self._node_of_page:
                self.allocator.free_page(page)
