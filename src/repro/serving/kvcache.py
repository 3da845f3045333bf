"""Slot-arena KV/state cache.

TPU-friendly dense layout: one preallocated arena per layer-pattern
position with a leading slot dimension —

  attention:  k/v  (G, slots, S_max, Hkv, D)
  mamba:      ssm  (G, slots, NH, HD, DS) fp32, conv (G, slots, W-1, C)

Sessions own slots; a prefill batch is assembled by gathering its slot
rows and written back by scatter.  Statically shaped throughout (S_max
fixed), so every bucketized step compiles once — the paged-KV pointer
chasing of GPU systems is replaced by whole-slot gathers, which XLA
turns into efficient dynamic-slice DMAs.

Decode-only ticks skip even the gather: the arena-resident decode path
(DESIGN.md §5) hands the arena pytree itself to the executor, the
kernel indexes the slot axis through a scalar-prefetched slot map, and
:meth:`KVArena.replace` swaps the (donated, in-place) result back —
per-token HBM traffic is O(cached_len), not O(S_max) slot copies.
Packed prefill / mixed / chunk ticks do the same (DESIGN.md §6): the
whole-slot gather/scatter survives only as the dense fallback for
SSM/SWA architectures and off-ladder batches, and the
``gather_calls`` / ``scatter_calls`` counters prove the hot paths
never touch it.
"""
from __future__ import annotations

from collections import OrderedDict
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.models import transformer as tr
from repro.models.config import ModelConfig


class KVArena:
    def __init__(self, cfg: ModelConfig, num_slots: int, max_len: int,
                 dtype=None, swa_depth: Optional[int] = None,
                 scratch_slot: bool = False):
        self.cfg = cfg
        self.num_slots = num_slots
        self.max_len = max_len
        # swa_depth: attention-slot depth for sliding-window configs —
        # the §7 rolling arena passes window + margin; the dense
        # baseline passes max_len (window masked, not rolled); None
        # keeps the legacy min(max_len, window) clamp
        self.swa_depth = swa_depth
        # scratch_slot: allocate ONE extra slot that sessions can never
        # claim — rolling KV slots have no spare park row and SSM state
        # has no park position, so pad rows/segments target this slot
        # instead of aliasing a live one (DESIGN.md §7)
        self.scratch: Optional[int] = num_slots if scratch_slot else None
        alloc_slots = num_slots + (1 if scratch_slot else 0)
        # build per-slot cache then add the slot axis via the batch dim:
        # init_cache already produces (G, B, ...) — treat B as slots
        self.arena = tr.init_cache(cfg, alloc_slots, max_len, dtype,
                                   swa_depth=swa_depth)
        self._free: List[int] = list(range(num_slots))
        self._session_slot: Dict[int, int] = {}
        self.lengths: Dict[int, int] = {}          # session -> tokens cached
        # whole-slot copy counters: the arena-resident paths (decode §5,
        # packed prefill §6/§7) must keep these at ZERO on their hot
        # ticks — the acceptance proof that no O(S_max) round-trips
        # survive
        self.gather_calls = 0
        self.scatter_calls = 0

    # ----------------------------------------------------------- slots
    def alloc(self, session: int) -> int:
        if session in self._session_slot:
            return self._session_slot[session]
        if not self._free:
            raise RuntimeError("KV arena exhausted")
        slot = self._free.pop()
        self._session_slot[session] = slot
        self.lengths[session] = 0
        return slot

    def free(self, session: int) -> None:
        slot = self._session_slot.pop(session, None)
        if slot is not None:
            self._free.append(slot)
            self.lengths.pop(session, None)

    def slot_of(self, session: int) -> Optional[int]:
        return self._session_slot.get(session)

    def length(self, session: int) -> int:
        return self.lengths.get(session, 0)

    def set_length(self, session: int, n: int) -> None:
        if n > self.max_len - 2:
            raise RuntimeError(
                f"session {session} overflows arena ({n} > {self.max_len - 2})")
        self.lengths[session] = n

    def truncate(self, session: int, n: int) -> None:
        """Speculative rollback (DESIGN.md §10): drop cached rows past
        ``n``.  The slot layout needs no data movement — rows beyond the
        valid length are unreachable by invariant (attention masks to
        kv_length, the next append overwrites them in place) — so
        truncate is pure length bookkeeping here; the paged arena's
        version releases pages and de-indexes the radix suffix."""
        h = self.lengths.get(session, 0)
        if not 0 <= n <= h:
            raise ValueError(
                f"truncate session {session} to {n} outside [0, {h}]")
        self.lengths[session] = n

    @property
    def free_slots(self) -> int:
        return len(self._free)

    # ---------------------------------------------------------- gather
    def gather(self, slots: List[int]) -> Any:
        self.gather_calls += 1
        idx = jnp.asarray(slots, jnp.int32)
        return jax.tree.map(lambda a: jnp.take(a, idx, axis=1), self.arena)

    def scatter(self, slots: List[int], batch_cache: Any) -> None:
        self.scatter_calls += 1
        idx = jnp.asarray(slots, jnp.int32)
        self.arena = jax.tree.map(
            lambda a, b: a.at[:, idx].set(b.astype(a.dtype)),
            self.arena, batch_cache)

    # ------------------------------------------------------- in-place use
    def replace(self, new_arena: Any) -> None:
        """Swap in the arena pytree returned by an arena-resident step.

        The arena-resident decode path reads the arena IN PLACE (the
        kernel indexes the slot axis through a slot map) and returns the
        updated buffers — under donation the same memory, just a new
        handle.  No gather/scatter bookkeeping happens here; lengths are
        advanced by the engine per session."""
        self.arena = new_arena

    # ----------------------------------------------------------- handoff
    def export_slot(self, session: int) -> Any:
        """Handoff source (DESIGN.md §9): slice the session's cached rows
        as DEVICE arrays — one dynamic-slice per leaf, no host transfer.
        Only valid for pure-attention, non-rolling layouts (seq axis 2)."""
        slot = self._session_slot[session]
        h = self.lengths[session]
        return jax.tree.map(lambda a: a[:, slot, :h], self.arena)

    def import_slot(self, session: int, kv: Any, n_tokens: int) -> int:
        """Handoff destination: allocate a slot and device-copy the
        exported rows into it.  Returns the slot."""
        assert session not in self._session_slot, \
            f"import into live session {session}"
        slot = self.alloc(session)
        if n_tokens:
            self.arena = jax.tree.map(
                lambda a, b: a.at[:, slot, :n_tokens].set(
                    jax.device_put(b, a.sharding).astype(a.dtype)),
                self.arena, kv)
        self.set_length(session, n_tokens)
        return slot


class _RadixNode:
    """One edge of the prefix trie: a page_size-token chunk → one page.

    ``state_page`` (hybrid configs, DESIGN.md §12) optionally names a
    page holding the SSM boundary-state CHECKPOINT after this chunk —
    the recurrent state a session would hold having processed exactly
    the root→here token path.  The node owns one refcount on it."""
    __slots__ = ("children", "parent", "chunk", "page", "last_use",
                 "state_page")

    def __init__(self, parent: Optional["_RadixNode"] = None,
                 chunk: Optional[Tuple[int, ...]] = None, page: int = -1):
        self.children: Dict[Tuple[int, ...], "_RadixNode"] = {}
        self.parent = parent
        self.chunk = chunk
        self.page = page
        self.last_use = 0
        self.state_page: Optional[int] = None


class RadixPageIndex:
    """Radix/trie prefix index over page_size-token chunks.

    Maps token-id prefixes to the KV pages that hold them, at PAGE
    granularity: an edge at depth i is the tuple of token ids
    ``tokens[i·ps : (i+1)·ps]`` and names the physical page caching that
    chunk's KV.  Only FULL pages are indexed — a prefix is shareable
    exactly up to its last page boundary, which is also what makes
    sharing safe: sessions append at positions ≥ their committed length,
    so an indexed (full) page is never written again (see
    PagedKVArena.prepare_extend for the one COW exception, fork-shared
    partial pages, which by construction are never in this index).

    The index holds its own reference on every indexed page; eviction
    (LRU over leaf nodes) drops that reference so cold cached prefixes
    return to the free pool once no session holds them either.
    """

    def __init__(self, page_size: int):
        self.page_size = page_size
        self.root = _RadixNode()
        self._clock = 0
        self._n_pages = 0

    def _tick(self) -> int:
        self._clock += 1
        return self._clock

    def match(self, tokens: Sequence[int],
              touch: bool = True) -> List[int]:
        """Longest indexed prefix of ``tokens`` in full-page chunks.

        Returns the page ids caching ``tokens[:len(result)·ps]``.  Never
        matches past ``len(tokens) − 1``: the caller must keep ≥ 1 token
        of true suffix to prefill (attention needs a query row to
        produce this turn's logits).
        """
        ps = self.page_size
        limit = max(len(tokens) - 1, 0) // ps
        node, pages = self.root, []
        now = self._tick() if touch else self._clock
        for i in range(limit):
            child = node.children.get(tuple(tokens[i * ps:(i + 1) * ps]))
            if child is None:
                break
            if touch:
                child.last_use = now
            pages.append(child.page)
            node = child
        return pages

    def insert(self, tokens: Sequence[int],
               pages: Sequence[int]) -> List[int]:
        """Index every full-page chunk of ``tokens``; return the page ids
        NEWLY referenced (the caller owns refcounts).  Chunks already
        indexed keep their existing page — the duplicate stays private
        to its session."""
        ps = self.page_size
        node, newly = self.root, []
        now = self._tick()
        for i in range(len(tokens) // ps):
            chunk = tuple(tokens[i * ps:(i + 1) * ps])
            child = node.children.get(chunk)
            if child is None:
                child = _RadixNode(parent=node, chunk=chunk, page=pages[i])
                node.children[chunk] = child
                newly.append(pages[i])
                self._n_pages += 1
            child.last_use = now
            node = child
        return newly

    def pages(self) -> List[int]:
        out, stack = [], [self.root]
        while stack:
            n = stack.pop()
            if n is not self.root:
                out.append(n.page)
            stack.extend(n.children.values())
        return out

    def leaves(self) -> Iterable[_RadixNode]:
        stack = [self.root]
        while stack:
            n = stack.pop()
            if n is not self.root and not n.children:
                yield n
            stack.extend(n.children.values())

    def remove(self, node: _RadixNode) -> int:
        """Unlink a LEAF node; returns its page (caller drops the ref)."""
        assert not node.children and node.parent is not None
        del node.parent.children[node.chunk]
        self._n_pages -= 1
        return node.page

    def __len__(self) -> int:
        return self._n_pages


class PagedKVArena:
    """Paged KV cache: fixed-size pages in a shared pool + per-session
    page tables, with radix-tree prefix reuse, COW forks, and LRU
    eviction (DESIGN.md §8).

    Layout per layer-pattern position: k/v ``(G, N_pages + 1, page_size,
    Hkv, D)`` — init_cache's batch axis becomes the PAGE axis, so the
    paged kernels read ``(1, page_size, Hkv, D)`` blocks exactly like the
    slot kernels read arena blocks.  Page ``N_pages`` is the reserved
    SCRATCH page (the §6/§7 scratch-row/slot invariant at page
    granularity): it is never allocated, never indexed, and pad stream
    rows write at (scratch, page_size − 1).

    Sessions own ORDERED page lists (logical page i = positions
    [i·ps, (i+1)·ps)).  Pages are shared in two ways:

      * radix-tree prefix reuse — ``match_prefix`` maps a new session's
        token ids onto the pages of any previously committed identical
        prefix, so only the new suffix is prefilled;
      * COW forks — ``fork`` clones a session's table for n-best /
        tool-use branches; both branches share every page until one
        writes into the (partial) boundary page, which
        ``prepare_extend`` then copies.

    ``refcount[p]`` = #sessions whose table holds p, + 1 if the radix
    index holds p.  Append-only writes land at positions ≥ the committed
    length, so full (indexed, shareable) pages are never written; the
    only write into a shared page would be the fork-shared partial
    boundary page, and that is exactly the COW trigger.  A page returns
    to the free pool when its refcount drops to zero; when the pool runs
    dry, LRU leaf pages held only by the index are evicted
    (oversubscription: the index may cache far more prefix than live
    sessions could pin).

    Three layout extensions ride on the same pool (DESIGN.md §12):

      * ``ring_pages=n`` — RING tables for sliding-window configs: the
        session's page list is a ring of at most ``n`` logical blocks;
        position p lives on ring page ``(p // ps) % n`` (the engine
        computes the mapping host-side).  Ring pages are overwritten in
        place as the window rolls, so they are never shareable: the
        radix index is disabled, refcounts stay 1, and forks are
        rejected.
      * ``state_slots=True`` — hybrid (SSM) configs: each session gets
        one STATE page from the same pool (the SSM leaves of the arena
        pytree use the page axis as the state-slot axis).  ``commit``
        checkpoints the live state into a fresh page attached to the
        radix node whenever the committed length lands on a page
        boundary, and ``match_prefix`` clamps adoption to the deepest
        matched ancestor that carries such a checkpoint.
      * ``host_pool_bytes>0`` — host spill tier: eviction DEMOTES
        index-only LRU pages to a bounded host-side pool (one
        ``device_get`` on the victim) instead of dropping them;
        ``match_prefix`` / ``match_extend`` promote entries back into
        fresh device pages on hit.  Session-pinned pages (rc > 1) are
        never spill candidates, and state checkpoints do not survive
        demotion (a promoted page re-enters the index KV-only).

    ``cfg=None`` builds a bookkeeping-only arena (no device arrays) for
    property tests of the share/fork/evict/spill/write state machine.
    """

    def __init__(self, cfg: Optional[ModelConfig], num_pages: int,
                 page_size: int, max_len: int, dtype=None,
                 prefix_cache: bool = True,
                 ring_pages: Optional[int] = None,
                 state_slots: bool = False,
                 host_pool_bytes: int = 0):
        self.cfg = cfg
        self.num_pages = num_pages
        self.page_size = page_size
        self.max_len = max_len
        self.scratch: int = num_pages          # reserved, never allocated
        self.ring_pages = ring_pages
        self.state_slots = state_slots
        # swa_depth=page_size keeps windowed attn pages FULL page_size
        # deep (init_cache would otherwise clamp them to the window);
        # the ring table, not the page depth, carries the window
        self.arena = (tr.init_cache(cfg, num_pages + 1, page_size, dtype,
                                    swa_depth=page_size)
                      if cfg is not None else None)
        self._free: List[int] = list(range(num_pages))
        self._refcount: List[int] = [0] * num_pages
        self._pages: Dict[int, List[int]] = {}     # session -> page list
        self._tokens: Dict[int, List[int]] = {}    # session -> cached ids
        self.lengths: Dict[int, int] = {}          # session -> tokens cached
        self.state_pages: Dict[int, int] = {}      # session -> SSM state page
        if ring_pages is not None:
            prefix_cache = False               # ring pages are overwritten
        self.index: Optional[RadixPageIndex] = (
            RadixPageIndex(page_size) if prefix_cache else None)
        # host spill tier: full-chunk-path key -> device_get'd page leaves
        # (None payloads in bookkeeping mode); LRU = insertion order
        self.host_pool_bytes = host_pool_bytes
        self._host_pool: "OrderedDict[Tuple, Any]" = OrderedDict()
        self._host_bytes = 0
        if self.arena is not None:
            self._page_bytes = int(sum(
                a[:, 0].nbytes for a in jax.tree.leaves(self.arena)))
        else:
            self._page_bytes = 1               # bookkeeping: count pages
        # proof counters (engine.stats())
        self.prefix_hit_tokens = 0
        self.chunk_hit_tokens = 0
        self.pages_cow_forked = 0
        self.pages_evicted = 0
        self.pages_spilled = 0
        self.pages_promoted = 0
        self.host_pages_dropped = 0
        self.state_checkpoints = 0
        self.handoff_pages_deduped = 0
        # the paged paths never materialize whole sequences: kept for
        # stats() symmetry with KVArena and asserted == 0 by benches
        self.gather_calls = 0
        self.scatter_calls = 0

    # ---------------------------------------------------------- refcounts
    def _ref(self, page: int) -> None:
        self._refcount[page] += 1

    def _unref(self, page: int) -> None:
        rc = self._refcount[page] = self._refcount[page] - 1
        assert rc >= 0, f"page {page} refcount underflow"
        if rc == 0:
            self._free.append(page)

    def _alloc_page(self) -> int:
        if not self._free:
            self._evict(1)
        if not self._free:
            raise RuntimeError("KV page pool exhausted")
        page = self._free.pop()
        self._refcount[page] = 1
        return page

    def _evict(self, need: int) -> None:
        """LRU-evict leaf pages held ONLY by the radix index; with a
        host tier configured the victim is DEMOTED (one device_get)
        instead of dropped, and its state checkpoint (if any) is
        released — checkpoints never survive demotion."""
        if self.index is None:
            return
        freed = 0
        while freed < need:
            victim = None
            for leaf in self.index.leaves():
                if leaf.page < 0:
                    continue                   # mid-promotion placeholder
                if self._refcount[leaf.page] != 1:
                    continue                   # pinned by a live session
                if victim is None or leaf.last_use < victim.last_use:
                    victim = leaf
            if victim is None:
                return
            if self.host_pool_bytes > 0:
                self._spill(self._node_key(victim), victim.page)
            if victim.state_page is not None:
                self._unref(victim.state_page)
                victim.state_page = None
            self._unref(self.index.remove(victim))
            self.pages_evicted += 1
            freed += 1

    # ----------------------------------------------------------- host tier
    @staticmethod
    def _node_key(node: _RadixNode) -> Tuple[Tuple[int, ...], ...]:
        """Full root→node chunk path — the host-pool key (content-
        addressed, so promotion survives page-id recycling)."""
        chunks: List[Tuple[int, ...]] = []
        while node.parent is not None:
            chunks.append(node.chunk)
            node = node.parent
        return tuple(reversed(chunks))

    def _spill(self, key: Tuple, page: int) -> None:
        """Demote one page to the host pool (device_get on the victim
        only); oldest entries age out when the byte budget overflows."""
        if self.arena is not None:
            payload = jax.tree.map(lambda a: jax.device_get(a[:, page]),
                                   self.arena)
        else:
            payload = None
        if key in self._host_pool:             # refreshed content: replace
            self._host_pool.pop(key)
            self._host_bytes -= self._page_bytes
        self._host_pool[key] = payload
        self._host_bytes += self._page_bytes
        self.pages_spilled += 1
        while self._host_bytes > self.host_pool_bytes and self._host_pool:
            self._host_pool.popitem(last=False)
            self._host_bytes -= self._page_bytes
            self.host_pages_dropped += 1

    def _promote(self, key: Tuple, parent: _RadixNode,
                 chunk: Tuple[int, ...]) -> Optional[_RadixNode]:
        """Promote a host-pool entry back into a fresh device page and
        re-link it under ``parent`` in the radix index.  The node is
        linked (page = −1) BEFORE allocating so the alloc's own eviction
        sweep can neither pick it nor orphan ``parent``."""
        if key not in self._host_pool:
            return None
        payload = self._host_pool.pop(key)
        self._host_bytes -= self._page_bytes
        node = _RadixNode(parent=parent, chunk=chunk, page=-1)
        parent.children[chunk] = node
        try:
            page = self._alloc_page()          # ref owned by the index
        except RuntimeError:
            del parent.children[chunk]
            self._host_pool[key] = payload     # put it back; no pool room
            self._host_bytes += self._page_bytes
            return None
        node.page = page
        if self.arena is not None and payload is not None:
            self.arena = jax.tree.map(
                lambda a, b: a.at[:, page].set(jnp.asarray(b, a.dtype)),
                self.arena, payload)
        node.last_use = self.index._tick()
        self.index._n_pages += 1
        self.pages_promoted += 1
        return node

    # ------------------------------------------------------------ sessions
    def open(self, session: int) -> None:
        if session in self._pages:
            return
        self._pages[session] = []
        self._tokens[session] = []
        self.lengths[session] = 0
        if self.state_slots:
            # one SSM state page per session, from the same pool — the
            # SSM leaves of the arena use the page axis as state slots
            self.state_pages[session] = self._alloc_page()

    def free(self, session: int) -> None:
        pages = self._pages.pop(session, None)
        if pages is None:
            return
        for p in pages:
            self._unref(p)
        sp = self.state_pages.pop(session, None)
        if sp is not None:
            self._unref(sp)
        self._tokens.pop(session, None)
        self.lengths.pop(session, None)

    def pages_of(self, session: int) -> List[int]:
        return self._pages.get(session, [])

    def state_of(self, session: int) -> Optional[int]:
        """The session's SSM state page (None for pure-attn configs)."""
        return self.state_pages.get(session)

    def slot_of(self, session: int) -> Optional[int]:
        """KVArena-compatible accessor: for hybrid configs the 'slot'
        carrying per-session recurrent state is the state page."""
        return self.state_pages.get(session)

    def length(self, session: int) -> int:
        return self.lengths.get(session, 0)

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def max_pages_per_seq(self) -> int:
        if self.ring_pages is not None:
            return self.ring_pages
        return self.max_len // self.page_size

    @property
    def host_pool_pages(self) -> int:
        return len(self._host_pool)

    # -------------------------------------------------------- prefix reuse
    def _walk(self, start: _RadixNode, start_key: Tuple,
              tokens: Sequence[int], limit: int, *, pin: bool,
              promote: bool) -> List[_RadixNode]:
        """Follow ``tokens`` chunk by chunk from ``start``, optionally
        promoting host-pool continuations.  ``pin=True`` refs every
        matched page immediately (the caller owns the refs) so a later
        promotion's eviction sweep can never free a page already
        matched this walk."""
        node, key = start, start_key
        out: List[_RadixNode] = []
        now = self.index._tick() if pin else self.index._clock
        ps = self.page_size
        for i in range(limit):
            chunk = tuple(int(t) for t in tokens[i * ps:(i + 1) * ps])
            key = key + (chunk,)
            child = node.children.get(chunk)
            if child is None and promote:
                child = self._promote(key, node, chunk)
            if child is None:
                break
            if pin:
                child.last_use = now
                self._ref(child.page)
            out.append(child)
            node = child
        return out

    def _adoptable(self, nodes: List[_RadixNode]) -> int:
        """How many matched chunks a session can actually ADOPT: all of
        them for pure-attn configs; for hybrids, only up to the deepest
        ancestor carrying an SSM boundary-state checkpoint (the
        recurrent state must be reconstructable, not just the KV)."""
        if not self.state_slots:
            return len(nodes)
        for d in range(len(nodes), 0, -1):
            if nodes[d - 1].state_page is not None:
                return d
        return 0

    def probe_prefix(self, tokens: Sequence[int]) -> int:
        """Tokens a fresh session with this prompt would NOT re-prefill
        (non-adopting; used by the serve loop for length-aware
        scheduling of the true suffix).  Counts device-resident chunks
        AND host-pool continuations — a spilled page is still a hit,
        just one ``swap_in`` away."""
        if self.index is None:
            return 0
        ps = self.page_size
        limit = max(len(tokens) - 1, 0) // ps
        nodes = self._walk(self.index.root, (), tokens, limit,
                           pin=False, promote=False)
        d = self._adoptable(nodes)
        if self.state_slots:
            return d * ps          # host entries carry no checkpoints
        key = tuple(tuple(int(t) for t in tokens[i * ps:(i + 1) * ps])
                    for i in range(d))
        while d < limit:
            key = key + (tuple(int(t)
                               for t in tokens[d * ps:(d + 1) * ps]),)
            if key not in self._host_pool:
                break
            d += 1
        return d * ps

    def match_prefix(self, session: int, tokens: Sequence[int]) -> int:
        """Map the longest indexed prefix of ``tokens`` onto existing
        pages; the session then only prefills ``tokens[matched:]``.
        Host-pool continuations are promoted back to device pages on
        the way.  For hybrid configs the match is clamped to the deepest
        ancestor with an SSM boundary-state checkpoint, and the
        checkpoint content is copied into the session's state page.

        Only valid on an EMPTY session (a turn's full conversation is
        matched once, before its first prefill).  Returns the matched
        token count (multiple of page_size, ≤ len(tokens) − 1).
        """
        self.open(session)
        assert self.lengths[session] == 0 and not self._pages[session], \
            f"match_prefix on non-empty session {session}"
        if self.index is None:
            return 0
        ps = self.page_size
        limit = max(len(tokens) - 1, 0) // ps
        nodes = self._walk(self.index.root, (), tokens, limit,
                           pin=True, promote=True)
        d = self._adoptable(nodes)
        for nd in nodes[d:]:                   # unwind the clamped tail
            self._unref(nd.page)
        nodes = nodes[:d]
        if not nodes:
            return 0
        matched = len(nodes) * ps
        self._pages[session] = [nd.page for nd in nodes]
        self._tokens[session] = [int(t) for t in tokens[:matched]]
        self.lengths[session] = matched
        self.prefix_hit_tokens += matched
        if self.state_slots:
            self._copy_page(nodes[-1].state_page,
                            self.state_pages[session])
        return matched

    def match_extend(self, session: int, tokens: Sequence[int]) -> int:
        """CHUNK-LEVEL prefix matching (DESIGN.md §12): mid-request, map
        the longest indexed continuation of the session's cached history
        onto existing pages, so a long prompt whose cached prefix
        extends past the first chunk skips already-indexed pages at
        every chunk boundary — not just at submit.

        ``tokens`` is the not-yet-cached remainder of the prompt.  Only
        valid when the session sits exactly on a page boundary (chunked
        prefill with page-aligned chunks guarantees this).  Keeps ≥ 1
        token of true suffix.  Returns the adopted token count.
        """
        if self.index is None:
            return 0
        h = self.lengths.get(session, 0)
        ps = self.page_size
        if h == 0 or h % ps:
            return 0
        toks = self._tokens[session]
        # locate the session's frontier node by CONTENT (the session may
        # hold private duplicate pages; the trie is keyed on token ids)
        node, key = self.index.root, ()
        for i in range(h // ps):
            chunk = tuple(toks[i * ps:(i + 1) * ps])
            child = node.children.get(chunk)
            if child is None:
                return 0                       # history not indexed
            key = key + (chunk,)
            node = child
        limit = max(len(tokens) - 1, 0) // ps
        nodes = self._walk(node, key, tokens, limit,
                           pin=True, promote=True)
        # hybrids: the session's live SSM state covers exactly h tokens,
        # so skipping ahead is only sound up to a boundary-state
        # checkpoint that replaces it — clamp like match_prefix
        d = self._adoptable(nodes)
        for nd in nodes[d:]:
            self._unref(nd.page)
        nodes = nodes[:d]
        if not nodes:
            return 0
        matched = len(nodes) * ps
        self._pages[session].extend(nd.page for nd in nodes)
        toks.extend(int(t) for t in tokens[:matched])
        self.lengths[session] = h + matched
        self.prefix_hit_tokens += matched
        self.chunk_hit_tokens += matched
        if self.state_slots:
            self._copy_page(nodes[-1].state_page,
                            self.state_pages[session])
        return matched

    # --------------------------------------------------------------- write
    def prepare_extend(self, session: int, n: int) -> List[int]:
        """Make positions [length, length + n) writable: COW-copy the
        fork-shared partial boundary page (the ONLY shareable page a
        write can touch — full pages are append-safe) and allocate fresh
        pages for the tail.  Returns the session's page list; every page
        overlapping the write range is exclusively owned afterwards."""
        self.open(session)
        h = self.lengths[session]
        if h + n > self.max_len - 2:
            raise RuntimeError(
                f"session {session} overflows arena "
                f"({h + n} > {self.max_len - 2})")
        ps = self.page_size
        pages = self._pages[session]
        if self.ring_pages is not None:
            # ring table (§12): allocate only until the ring is full;
            # past that, writes wrap onto existing ring pages (the
            # engine maps position p to ring slot (p // ps) % n_ring).
            # Ring pages are never shared, so no COW is ever needed.
            last = (h + n - 1) // ps
            while len(pages) <= last and len(pages) < self.ring_pages:
                pages.append(self._alloc_page())
            return pages
        if h % ps and self._refcount[pages[h // ps]] > 1:
            src = pages[h // ps]
            dst = self._alloc_page()
            self._copy_page(src, dst)
            self._unref(src)
            pages[h // ps] = dst
            self.pages_cow_forked += 1
        last = (h + n - 1) // ps
        while len(pages) <= last:
            pages.append(self._alloc_page())
        return pages

    def commit(self, session: int, token_ids: Sequence[int]) -> None:
        """Record ``token_ids`` as written at [length, length + n) (the
        step already scatter-wrote their KV via prepare_extend's pages)
        and index every newly-FULL page for cross-session reuse."""
        toks = self._tokens[session]
        toks.extend(int(t) for t in token_ids)
        self.lengths[session] += len(token_ids)
        if self.index is not None:
            ps = self.page_size
            n_full = self.lengths[session] // ps
            for p in self.index.insert(toks[:n_full * ps],
                                       self._pages[session][:n_full]):
                self._ref(p)
            if (self.state_slots and n_full > 0
                    and self.lengths[session] % ps == 0):
                self._checkpoint_state(session, toks, n_full)

    def _checkpoint_state(self, session: int, toks: List[int],
                          n_full: int) -> None:
        """SSM boundary-state checkpoint (§12): when the committed
        length lands exactly on a page boundary, the session's LIVE
        state equals the state after ``n_full`` chunks — snapshot it
        into a fresh page owned by the radix node at that depth, so a
        later session matching this prefix can adopt it.  Best-effort:
        pool pressure skips the snapshot rather than evicting live
        data for it."""
        ps = self.page_size
        try:
            cp = self._alloc_page()
        except RuntimeError:
            return
        # re-walk AFTER the alloc: its eviction sweep may have dropped
        # the very node we are about to decorate
        node: Optional[_RadixNode] = self.index.root
        for i in range(n_full):
            node = node.children.get(tuple(toks[i * ps:(i + 1) * ps]))
            if node is None:
                break
        if node is None or node is self.index.root \
                or node.state_page is not None:
            self._unref(cp)
            return
        self._copy_page(self.state_pages[session], cp)
        node.state_page = cp
        self.state_checkpoints += 1

    # ------------------------------------------------------------ rollback
    def truncate(self, session: int, n: int) -> None:
        """Speculative rollback (DESIGN.md §10): forget every cached
        token past ``n``.

        Three things unwind, in order:

        1. **Radix de-index** — the session's indexed chunk path is
           walked and suffix nodes covering chunks ≥ ``n // ps`` are
           unlinked deepest-first, including the boundary chunk whose
           page goes full → partial (an indexed page must stay
           append-only; the session will write into the partial page
           again).  A node survives when it still has children (a longer
           indexed prefix — shared, not ours to drop) or when it names a
           different physical page (a private duplicate was never
           indexed); in either case every shallower node survives too,
           and any such still-indexed boundary page keeps rc > 1 so
           ``prepare_extend``'s COW shields it from the re-extend.
        2. **Page-refcount release** — the session unrefs every page
           past ``ceil(n / ps)``; pages held by the index or by a fork
           sibling stay alive (rc > 0), exclusively-owned tails return
           to the free pool.  Pages over-allocated by a speculative
           ``prepare_extend`` (never committed) are released the same
           way even when ``n == length``.
        3. **Token trim** — ``_tokens``/``lengths`` shrink to ``n``.

        ``audit()`` holds afterwards: every unref is mirrored by a table
        or index removal.
        """
        h = self.lengths.get(session, 0)
        if not 0 <= n <= h:
            raise ValueError(
                f"truncate session {session} to {n} outside [0, {h}]")
        self.open(session)
        ps = self.page_size
        toks = self._tokens[session]
        pages = self._pages[session]
        if self.ring_pages is not None:
            # ring tables: pages hold modularly-wrapped history, so the
            # rollback is pure length bookkeeping (rows past ``n`` are
            # unreachable by the window mask and overwritten in place)
            del toks[n:]
            self.lengths[session] = n
            return
        new_full = n // ps
        keep_pages = -(-n // ps)
        if self.index is not None:
            # the session's indexed chain, chunk by chunk
            path: List[_RadixNode] = []
            node = self.index.root
            for i in range(h // ps):
                child = node.children.get(tuple(toks[i * ps:(i + 1) * ps]))
                if child is None:
                    break
                path.append(child)
                node = child
            for i in range(len(path) - 1, new_full - 1, -1):
                nd = path[i]
                if nd.children or nd.page != pages[i]:
                    break
                if nd.state_page is not None:
                    self._unref(nd.state_page)
                    nd.state_page = None
                self._unref(self.index.remove(nd))
        for p in pages[keep_pages:]:
            self._unref(p)
        del pages[keep_pages:]
        del toks[n:]
        self.lengths[session] = n

    # ---------------------------------------------------------------- fork
    def fork(self, parent: int, child: int) -> None:
        """COW-fork: the child shares every page (and the token history)
        of the parent; diverging writes copy the partial boundary page
        on demand (prepare_extend).  Hybrid configs also deep-copy the
        parent's SSM state page (recurrent state diverges immediately)."""
        assert self.ring_pages is None, \
            "ring tables cannot fork (pages are overwritten in place)"
        assert child not in self._pages, f"fork onto live session {child}"
        self.open(child)
        for p in self._pages[parent]:
            self._ref(p)
        self._pages[child] = list(self._pages[parent])
        self._tokens[child] = list(self._tokens[parent])
        self.lengths[child] = self.lengths[parent]
        if self.state_slots:
            self._copy_page(self.state_pages[parent],
                            self.state_pages[child])

    # ------------------------------------------------------------- handoff
    def export_pages(self, session: int) -> Any:
        """Handoff source (DESIGN.md §9): gather the session's page rows
        from the pool as DEVICE arrays (no host transfer)."""
        pages = self._pages.get(session, [])
        if self.arena is None or not pages:
            return None
        idx = jnp.asarray(pages, jnp.int32)
        return jax.tree.map(lambda a: jnp.take(a, idx, axis=1), self.arena)

    def import_session(self, session: int, token_ids: Sequence[int],
                       kv: Any, n_tokens: int) -> List[int]:
        """Handoff destination: allocate fresh pages, device-copy the
        exported pool rows into them, rebuild the session bookkeeping,
        and index every full page — the imported prefix becomes
        shareable here exactly as if it had been prefilled locally.

        DEDUPE (§12): the caller may ``match_prefix`` the incoming
        transcript FIRST — pages the destination's radix index already
        holds are adopted, and only the suffix of the exported payload
        (``kv`` sliced past the matched pages) is copied in.  ``kv`` is
        always the FULL export; the slicing happens here."""
        self.open(session)
        h = self.lengths[session]
        ps = self.page_size
        assert h % ps == 0, \
            f"import into session {session} off a page boundary ({h})"
        assert self._tokens[session] == [int(t) for t in token_ids[:h]], \
            f"import into session {session} with mismatched history"
        if n_tokens > self.max_len - 2:
            raise RuntimeError(
                f"imported session {session} overflows arena "
                f"({n_tokens} > {self.max_len - 2})")
        skip = h // ps
        n_pages = -(-n_tokens // ps) - skip
        pages = [self._alloc_page() for _ in range(n_pages)]
        if self.arena is not None and kv is not None and pages:
            idx = jnp.asarray(pages, jnp.int32)
            # the export may live on a peer engine's device: place it on
            # this pool's own device first (a device-to-device copy)
            self.arena = jax.tree.map(
                lambda a, b: a.at[:, idx].set(
                    jax.device_put(b[:, skip:], a.sharding).astype(a.dtype)),
                self.arena, kv)
        self._pages[session].extend(pages)
        self._tokens[session].extend(int(t) for t in token_ids[h:n_tokens])
        self.lengths[session] = n_tokens
        if skip:
            self.handoff_pages_deduped += skip
        if self.index is not None:
            n_full = n_tokens // ps
            for p in self.index.insert(self._tokens[session][:n_full * ps],
                                       self._pages[session][:n_full]):
                self._ref(p)
        return self._pages[session]

    # ------------------------------------------------------- device arrays
    def _copy_page(self, src: int, dst: int) -> None:
        if self.arena is None:
            return
        self.arena = jax.tree.map(
            lambda a: a.at[:, dst].set(a[:, src]), self.arena)

    def replace(self, new_arena: Any) -> None:
        """Swap in the page pool returned by a paged step (donated)."""
        self.arena = new_arena

    # --------------------------------------------------------------- audit
    def audit(self) -> None:
        """Assert the refcount/free-list/scratch/host-tier invariants
        (tests)."""
        rc = [0] * self.num_pages
        for pages in self._pages.values():
            for p in pages:
                assert p != self.scratch, "scratch page in a session table"
                rc[p] += 1
        for sp in self.state_pages.values():
            assert sp != self.scratch, "scratch page as a state page"
            rc[sp] += 1
        if self.index is not None:
            stack = [self.index.root]
            while stack:
                nd = stack.pop()
                if nd is not self.index.root:
                    assert nd.page != self.scratch, \
                        "scratch page in the radix index"
                    assert nd.page >= 0, "placeholder node leaked"
                    rc[nd.page] += 1
                    if nd.state_page is not None:
                        assert nd.state_page != self.scratch
                        rc[nd.state_page] += 1
                stack.extend(nd.children.values())
        assert self._host_bytes == len(self._host_pool) * self._page_bytes, \
            "host pool byte accounting drift"
        assert self._host_bytes <= max(self.host_pool_bytes, 0) or \
            not self._host_pool, "host pool over budget"
        assert rc == self._refcount, \
            f"refcount drift: counted {rc} != tracked {self._refcount}"
        assert sorted(self._free) == sorted(set(self._free)), \
            "duplicate pages in the free list"
        for p in self._free:
            assert p != self.scratch and self._refcount[p] == 0, \
                f"free page {p} still referenced"
        for p, r in enumerate(self._refcount):
            assert (r == 0) == (p in set(self._free)), \
                f"page {p} rc={r} free-list membership mismatch"
