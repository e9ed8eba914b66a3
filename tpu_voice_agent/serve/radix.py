"""Radix-tree KV reuse over the paged block pool (session prefix caching).

ISSUE 5 / ROADMAP "serve millions of users": voice traffic is overwhelmingly
multi-turn, and every `/parse` for a returning session re-prefills the same
system prompt + conversation history the previous turn already pushed through
the model. The paged plane (serve.paged) shares exactly ONE static refcounted
prefix; this module generalizes that to a *radix tree of refcounted block
chains* keyed by token ids:

- every released request inserts its prompt+generated chain back into the
  tree (one node per pool block, key = that block's ``block_size`` token ids)
- admission runs a longest-prefix match at BLOCK granularity: matched blocks
  are shared read-only into the new slot's table (copy-on-write — new tokens
  always land in freshly allocated blocks, because suffix writes start at
  position ``matched`` which lies past every matched block), and only the
  partial-block tail + new utterance re-prefill
- the static prompt prefix becomes the tree's permanently-pinned root chain
- when ``BlockAllocator.alloc`` would raise ``PoolExhausted``, LRU eviction
  frees unreferenced leaves (refcounts are the single source of truth: a
  node is evictable only when the tree holds the ONLY live ref on its block
  — never a block referenced by a live slot, never the pinned root)

Same reuse-computed-state principle WhisperFlow (arXiv:2412.11272) applies
to streaming ASR ticks, applied to the intent-decode KV plane — and unlike
the planner backend's per-session caches, this composes with continuous
batching: the reused KV lives inside the one paged pool every slot decodes
against.

Correctness contract (tests/test_radix.py): a radix-hit admission is
token-identical to a cold admission — matched blocks hold exactly the KV a
cold prefill would recompute (decode-written and prefill-written KV are
bitwise equal in the bf16 pool; differentially tested), and ``RADIX_ENABLE``
unset keeps the pre-radix paged path byte-identical.
"""

from __future__ import annotations

import heapq
import itertools


class RadixNode:
    """One pool block's worth of cached context. ``key`` is the tuple of
    ``block_size`` token ids whose KV the block holds; the path from the
    root spells the full token prefix."""

    __slots__ = ("key", "block", "children", "parent", "last_use", "pinned",
                 "ns")

    def __init__(self, key, block, parent, pinned: bool = False):
        self.key = key  # tuple[int, ...] | (ns, tuple) | None (root)
        self.block = block  # pool block id | None (root)
        self.children: dict[tuple, "RadixNode"] = {}
        self.parent = parent
        self.last_use = 0
        self.pinned = pinned
        # tenant namespace (ISSUE 18): None = shared; set = the node's key
        # is salted ``(ns, ids)`` and its block counts against the owning
        # tenant's quota
        self.ns: str | None = None


class RadixCache:
    """Token-id-keyed radix tree of refcounted block chains for ONE dp
    group's block range (blocks never cross dp shards, so neither do
    chains; a meshed engine holds one tree per group).

    Ref discipline — ``allocator`` refcounts are the single source of
    truth, and every owner holds exactly one ref per block:

    - the tree takes its own ref when it adopts a block (``insert`` /
      ``pin_root_chain``) and releases it at eviction / ``clear``
    - ``match`` takes one ref per matched block FOR THE CALLER (the slot's
      ``release_slot`` frees it like any other shared block)
    - eviction frees only leaves whose block the tree solely owns
      (refcount == 1) and that are not pinned — a live slot's chain or the
      static prefix can never be freed under it
    """

    def __init__(self, allocator, block_size: int, group: int = 0,
                 max_nodes: int = 4096):
        self.allocator = allocator
        self.block_size = block_size
        self.group = group
        self.max_nodes = max_nodes
        self.root = RadixNode(None, None, None, pinned=True)
        self._n_nodes = 0
        self._clock = itertools.count(1)
        # tenant namespaces (ISSUE 18): per-ns adopted-node counts and an
        # optional quota lookup (the scheduler installs the tenancy plane's
        # ``block_quota``). With no namespaces in play both stay empty and
        # every path below is byte-identical to the pre-tenancy tree.
        self.ns_quota = None  # callable: ns -> block quota (0 = unlimited)
        self._ns_nodes: dict[str, int] = {}
        # host-side stats (the scheduler exports them as radix.* gauges;
        # event counters increment the metrics registry at event time)
        self.lookups = 0
        self.hits = 0
        self.matched_tokens = 0
        self.evictions = 0
        self.inserts = 0

    # ------------------------------------------------------------ admission

    def match(self, ids: list[int], ns: str | None = None
              ) -> tuple[list[int], int]:
        """Longest-prefix match at block granularity. Returns the matched
        block chain (every block ref'd for the caller) and the matched
        token count. Always leaves >= 1 token unmatched: admission needs a
        last REAL token to take first-sample logits from.

        With ``ns`` set (ISSUE 18) the walk prefers the tenant's salted
        nodes and crosses into plain-key nodes only when they are pinned
        (the static prefix stays shared across tenants); another tenant's
        unpinned chain is never served.

        Only ``lookups`` is counted here — the caller reports the hit via
        ``record_hit`` once the chain is actually USED (an admission that
        falls back to full prefill, e.g. no suffix bucket fits, must not
        show up as served-from-cache in the gauges)."""
        bs = self.block_size
        t = next(self._clock)
        self.lookups += 1
        node = self.root
        blocks: list[int] = []
        limit = max(0, (len(ids) - 1) // bs)
        for i in range(limit):
            kt = tuple(ids[i * bs:(i + 1) * bs])
            child = node.children.get((ns, kt)) if ns is not None else None
            if child is None:
                c = node.children.get(kt)
                if c is not None and (ns is None or c.pinned):
                    child = c
            if child is None:
                break
            child.last_use = t
            blocks.append(child.block)
            node = child
        if blocks:
            self.allocator.ref(blocks)
        return blocks, len(blocks) * bs

    def cached_tokens(self, ids: list[int], ns: str | None = None) -> int:
        """Ref-free probe: how many leading tokens of ``ids`` a ``match``
        would serve right now. Takes no allocator refs, bumps no LRU
        clocks, counts no lookup — a pure observation used by the disagg
        stream adopter's post-insert verification (ISSUE 20) where the
        match/free churn of a real lookup would perturb eviction order."""
        bs = self.block_size
        node = self.root
        matched = 0
        limit = max(0, (len(ids) - 1) // bs)
        for i in range(limit):
            kt = tuple(ids[i * bs:(i + 1) * bs])
            child = node.children.get((ns, kt)) if ns is not None else None
            if child is None:
                c = node.children.get(kt)
                if c is not None and (ns is None or c.pinned):
                    child = c
            if child is None:
                break
            matched += bs
            node = child
        return matched

    def record_hit(self, matched: int) -> None:
        """Account a matched chain the engine COMMITTED to (cache-served
        tokens, not merely matchable ones)."""
        self.hits += 1
        self.matched_tokens += matched
        from ..utils import get_metrics

        get_metrics().inc("radix.cached_tokens", float(matched))

    # ------------------------------------------------------------ insertion

    def insert(self, ids: list[int], blocks: list[int],
               ns: str | None = None) -> int:
        """Adopt a released request's chain: ``ids`` is its full token
        history (prompt + generated), ``blocks`` the in-order pool blocks
        covering it. Only FULL blocks are inserted (a partial tail block
        will be rewritten by whoever re-prefills past it). Existing nodes
        are kept (the caller's duplicate block is freed by the caller's own
        release); new nodes take one tree ref. With ``ns`` set (ISSUE 18)
        new nodes are salted into the tenant's namespace, an overlap with
        the pinned static chain rides the shared nodes, and a tenant over
        its block quota evicts its OWN least-recent leaves first — nothing
        evictable of its own means adoption is refused, so one tenant's
        churn never lands on another's warm chains. Returns adopted count."""
        bs = self.block_size
        t = next(self._clock)
        node = self.root
        full = min(len(ids) // bs, len(blocks))
        adopted = 0
        evicted_for_capacity = False
        for i in range(full):
            kt = tuple(ids[i * bs:(i + 1) * bs])
            if ns is not None:
                plain = node.children.get(kt)
                if plain is not None and plain.pinned:
                    # the shared static prefix is never duplicated per tenant
                    plain.last_use = t
                    node = plain
                    continue
                key = (ns, kt)
            else:
                key = kt
            child = node.children.get(key)
            if child is None:
                if ns is not None and self.ns_quota is not None:
                    q = self.ns_quota(ns)
                    if q > 0 and self._ns_nodes.get(ns, 0) >= q:
                        # block quota: the owner's own LRU leaves pay first
                        if not self.evict(1, ns=ns):
                            break  # nothing of its own evictable: refuse
                if self._n_nodes >= self.max_nodes:
                    # ONE batched eviction per insert call (evict walks the
                    # whole tree to build its LRU heap — per-block evict(1)
                    # at a saturated cap would be O(nodes) per block)
                    if evicted_for_capacity or not self.evict(full - i):
                        break  # at capacity with nothing evictable
                    evicted_for_capacity = True
                child = RadixNode(key, blocks[i], node)
                child.ns = ns
                self.allocator.ref([blocks[i]])
                node.children[key] = child
                self._n_nodes += 1
                if ns is not None:
                    self._ns_nodes[ns] = self._ns_nodes.get(ns, 0) + 1
                self.inserts += 1
                adopted += 1
            child.last_use = t
            node = child
        return adopted

    def pin_root_chain(self, ids: list[int], blocks: list[int]) -> None:
        """Install the static prompt prefix as the permanently-pinned root
        chain (``set_prompt_prefix`` calls this with the prefix's FULL
        blocks; the sub-block remainder stays the engine's dense tail)."""
        bs = self.block_size
        t = next(self._clock)
        node = self.root
        for i in range(min(len(ids) // bs, len(blocks))):
            key = tuple(ids[i * bs:(i + 1) * bs])
            child = node.children.get(key)
            if child is None:
                child = RadixNode(key, blocks[i], node, pinned=True)
                self.allocator.ref([blocks[i]])
                node.children[key] = child
                self._n_nodes += 1
            else:
                child.pinned = True
            child.last_use = t
            node = child

    # ------------------------------------------------------------ eviction

    def _evictable(self, node: RadixNode) -> bool:
        return (node is not self.root and not node.children
                and not node.pinned
                and self.allocator.refcount(node.block) == 1)

    def evict(self, need: int, ns: str | None = None) -> int:
        """Free up to ``need`` blocks from least-recently-used unreferenced
        leaves (cascading: a parent whose last child left becomes a
        candidate). With ``ns`` set only that namespace's nodes are
        candidates (quota enforcement — a tenant's churn eats its own cache
        first). Returns how many blocks were actually freed — 0 when
        everything left is pinned or referenced by a live slot."""
        heap: list[tuple[int, int, RadixNode]] = []
        stack = [self.root]
        while stack:
            n = stack.pop()
            stack.extend(n.children.values())
            if self._evictable(n) and (ns is None or n.ns == ns):
                heapq.heappush(heap, (n.last_use, id(n), n))
        freed = 0
        while heap and freed < need:
            _, _, n = heapq.heappop(heap)
            # staleness guard: a parent pushed twice, or state changed
            if (not self._evictable(n) or n.parent is None
                    or n.parent.children.get(n.key) is not n):
                continue
            parent = n.parent
            del parent.children[n.key]
            self.allocator.free([n.block])
            self._n_nodes -= 1
            if n.ns is not None:
                self._ns_nodes[n.ns] = max(0, self._ns_nodes.get(n.ns, 1) - 1)
            self.evictions += 1
            freed += 1
            if self._evictable(parent) and (ns is None or parent.ns == ns):
                heapq.heappush(heap, (parent.last_use, id(parent), parent))
        if freed:
            from ..utils import get_metrics

            get_metrics().inc("radix.evictions", float(freed))
        return freed

    def clear(self) -> None:
        """Drop every node (pinned included) and free the tree's refs.
        Called before the engine reinstalls a prompt prefix — live slots'
        own refs keep any still-attended blocks alive."""
        stack = list(self.root.children.values())
        while stack:
            n = stack.pop()
            stack.extend(n.children.values())
            self.allocator.free([n.block])
        self.root.children.clear()
        self._n_nodes = 0
        self._ns_nodes.clear()

    # ------------------------------------------------------------ stats

    def reclaimable_blocks(self) -> int:
        """Blocks the eviction ladder could hand back under pressure:
        unpinned nodes whose block the tree solely owns (refcount == 1).
        Slight overcount when a sole-owned mid-chain node has a
        live-referenced descendant (cascading eviction stops below it) —
        fine for the shed-pressure signal this feeds: a warm cache is
        HEADROOM, not saturation, and counting it as used made the router
        shed new sessions off exactly the warmest replicas."""
        n = 0
        stack = [self.root]
        while stack:
            node = stack.pop()
            stack.extend(node.children.values())
            if (node is not self.root and not node.pinned
                    and self.allocator.refcount(node.block) == 1):
                n += 1
        return n

    @property
    def nodes(self) -> int:
        return self._n_nodes

    @property
    def hit_rate(self) -> float:
        return self.hits / self.lookups if self.lookups else 0.0


def record_radix_gauges(trees: list["RadixCache"]) -> None:
    """Export the radix plane's occupancy/effectiveness as runtime gauges
    (summed across dp groups). The continuous batcher calls this each chunk
    alongside record_pool_gauges; tests call it directly."""
    from ..utils import get_metrics

    m = get_metrics()
    lookups = sum(t.lookups for t in trees)
    hits = sum(t.hits for t in trees)
    m.set_gauge("radix.nodes", float(sum(t.nodes for t in trees)))
    m.set_gauge("radix.hit_rate", hits / lookups if lookups else 0.0)
