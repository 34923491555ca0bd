"""Host-side block accounting for the paged K/V cache.

The paged cache (vLLM's PagedAttention idea, Kwon et al. 2023, expressed
in this repo's primitives) splits the per-replica K/V buffer into a pool
of fixed-size blocks: `(num_layers, 2, n_blocks, block_size, embed)` on
the device, an int32 block table per active row, and THIS allocator on
the host.  A sequence holds `ceil(tokens / block_size)` blocks instead
of a full `(S_max, embed)` row, so HBM admits as many concurrent
sequences as their actual lengths fit — a worst-case reservation a
sequence is what would cap batch occupancy under mixed-length traffic.

Blocks are interchangeable fixed-size units, so a free list plus a
per-block REFCOUNT is the whole allocator: external fragmentation cannot
exist, and the `fragmentation()` gauge measures the only waste paging
leaves — INTERNAL fragmentation, the allocated-but-unwritten token rows
in each sequence's last block.

Cross-request prefix sharing (SGLang's RadixAttention, Zheng et al.
2023, at block granularity) rides the refcounts: `PrefixCache` below is
a radix tree over FULL-block token runs — node key = the exact
block_size-token tuple, path = the chained prefix — mapping each cached
run to the physical block that already holds its K/V.  A new request
walks its prompt down the tree, `acquire`s every matched block
(refcount + 1) and prefills only the uncached suffix.  Retired blocks
whose refcount hits zero do NOT return to the free list while they are
registered in the tree: they PARK in an LRU pool and are evicted back to
the free list only under allocation pressure, so a hot system prompt
survives across requests.

Speculative decoding (serving/spec.py) rides the same invariants: a
verify round writes a whole k+1-position span, so the engine allocates
(and CoW-copies to exclusive ownership) every block the span lands in
BEFORE the launch, and afterwards REWINDS the tail past the accepted
frontier.  The rewind is a plain `release` per tail block — never a
direct `reclaim` — so a tail block another request acquired meanwhile
loses exactly ONE reference, and a block the prefix index registered
parks instead of returning to the free list.  Only full blocks of
ACCEPTED tokens ever register in the `PrefixCache`; speculative garbage
is structurally unshareable.

Memory TIERING (serving/tiers.py, ``MXNET_SERVE_TIER``) extends the
radix index below HBM: a parked block the LRU evicts is no longer
necessarily destroyed — the engine's eviction hook may SPILL its K/V
to a host-DRAM pool, and the node then converts to HOST residency
(``tier == "host"``, ``block`` holds the host handle) instead of
detaching.  Host-resident nodes only ever appear below device-resident
ones on any path (eviction is leaf-first and live holders pin whole
prefixes, so spills happen bottom-up), which is exactly what makes
`lookup_plan` well-formed: a lookup returns a contiguous DEVICE run
followed by a contiguous HOST run, and the engine restores the host
run into freshly allocated device blocks before acquiring.  A restored
(or freshly re-prefilled) run flips its node back to device residency;
the host copy may be retained as a free re-spill (full blocks are
immutable — CoW keeps writers off registered blocks — so the two
copies cannot diverge).

Block 0 is reserved as the TRASH block: padding decode rows and the
unallocated tail entries of every block table point at it, so gathers
stay in-bounds with fixed shapes and scatters from padding rows land
somewhere no real sequence reads.  It is never handed out.

Allocation runs under the scheduler thread only (same threading contract
as the slot free-list it replaces); `alloc` returning None — pool
exhausted, or the `block_exhaust:P` chaos clause denying the attempt —
is a NORMAL outcome the engine answers with a typed shed / requeue /
preemption, never a hang.

SUB-MESH sharding (docs/serving.md "Sharded replicas") is invisible
here: when a `ServingEngine` spans a device mesh, the pool's embed
axis E is split over the mesh while block ids, the block tables, this
allocator, and the `PrefixCache` stay whole-pool host-side — every
count and refcount below describes LOGICAL blocks, each physically
striped across all shards.  The model's `block_bytes(block_size, shards)`
prices a block in both views.
"""
from __future__ import annotations

from collections import OrderedDict

from .. import chaos
from ..base import MXNetError

TRASH_BLOCK = 0


class BlockAllocator:
    """Refcounted free-list over the device block pool (ids 1..n-1).

    Three disjoint states per usable block, every transition loud:

    * **free**  — on the free list, allocatable (`alloc`).
    * **held**  — refcount >= 1 (`_ref`); `acquire` adds a reader,
      `release` drops one.  A block released to refcount 0 is handed
      BACK to the caller (the engine parks registered prefix blocks,
      `reclaim`s the rest) — the allocator never decides cache policy.
    * **parked** — refcount 0 but retained by the prefix cache; not in
      any allocator structure until `reclaim` returns it to the free
      list (eviction) or `acquire` revives it (a prefix hit).
    """

    def __init__(self, n_blocks, block_size):
        if int(n_blocks) < 2:
            raise MXNetError(
                "BlockAllocator: need >= 2 blocks (one is the reserved "
                "trash block), got %d" % n_blocks)
        if int(block_size) < 1:
            raise MXNetError(
                "BlockAllocator: block_size must be >= 1, got %d"
                % block_size)
        self.n_blocks = int(n_blocks)
        self.block_size = int(block_size)
        self._free = list(range(self.n_blocks - 1, TRASH_BLOCK, -1))
        self._free_set = set(self._free)
        self._ref = {}            # block -> refcount (>= 1)

    @property
    def capacity(self):
        """Usable blocks (pool minus the trash block)."""
        return self.n_blocks - 1

    @property
    def free_blocks(self):
        return len(self._free)

    @property
    def used_blocks(self):
        """Distinct physical blocks with refcount >= 1 (a block shared by
        k sequences counts ONCE)."""
        return len(self._ref)

    @property
    def shared_blocks(self):
        """Physical blocks currently referenced by more than one holder."""
        return sum(1 for c in self._ref.values() if c > 1)

    def refcount(self, block):
        return self._ref.get(block, 0)

    def exclusive(self, block):
        """True when exactly one holder owns ``block`` — the write
        precondition every scatter target must satisfy (the engine
        additionally requires the block to be absent from the prefix
        index: a registered block may gain readers at any moment)."""
        return self._ref.get(block, 0) == 1

    def blocks_for(self, n_tokens):
        """Blocks needed to hold ``n_tokens`` cache rows."""
        return -(-int(n_tokens) // self.block_size)

    def can_serve(self, n):
        """Whether the free list alone could serve ``n`` blocks right
        now.  After a denied `alloc` this distinguishes REAL exhaustion
        (True means the denial was a `block_exhaust` chaos draw — the
        free list was never touched) so the engine's anti-thrash policy
        can stall-and-retry a chaos denial instead of burning a
        preemption, and go hunting for a victim only when the pool is
        genuinely out of room."""
        return int(n) <= len(self._free)

    def alloc(self, n):
        """``n`` fresh block ids at refcount 1, or None when the free list
        cannot serve the request (insufficient free blocks, or a
        `block_exhaust` chaos denial).  Never partial: an allocation
        either fully lands or leaves the free list untouched, so a denied
        admit/growth retries cleanly.  Parked prefix blocks do NOT count
        as free — the engine evicts them explicitly under pressure."""
        n = int(n)
        if n <= 0:
            return []
        if chaos.serve_block_exhaust():
            return None
        if n > len(self._free):
            return None
        blocks = self._free[-n:]
        del self._free[-n:]
        self._free_set.difference_update(blocks)
        for b in blocks:
            self._ref[b] = 1
        return list(reversed(blocks))

    def acquire(self, blocks):
        """Add one reader to each block: a held block's refcount bumps, a
        parked block (refcount 0, retained by the prefix cache) revives
        at refcount 1.  Acquiring a FREE block raises — only blocks the
        prefix index vouches for may gain readers, anything else would
        alias a future allocation."""
        for b in blocks:
            if b == TRASH_BLOCK:
                raise MXNetError("BlockAllocator: acquiring the trash block")
            if b in self._free_set:
                raise MXNetError(
                    "BlockAllocator: acquiring free block %d (stale "
                    "prefix-index entry?)" % b)
            self._ref[b] = self._ref.get(b, 0) + 1

    def release(self, blocks):
        """Drop one reader from each block; returns the blocks whose
        refcount hit ZERO (the caller parks or `reclaim`s them).
        Double-release and trash-release raise: both would let two
        sequences alias one block, which corrupts a neighbour's context
        silently — the one failure mode a paged cache must make loud."""
        zeroed = []
        for b in blocks:
            if b == TRASH_BLOCK:
                raise MXNetError("BlockAllocator: freeing the trash block")
            c = self._ref.get(b)
            if c is None:
                raise MXNetError(
                    "BlockAllocator: double free of block %d" % b)
            if c == 1:
                del self._ref[b]
                zeroed.append(b)
            else:
                self._ref[b] = c - 1
        return zeroed

    def reclaim(self, blocks):
        """Return refcount-0 blocks to the free list (unregistered
        releases, prefix-cache evictions).  Reclaiming a held or
        already-free block raises."""
        for b in blocks:
            if b in self._ref:
                raise MXNetError(
                    "BlockAllocator: reclaiming held block %d" % b)
            if b in self._free_set or b == TRASH_BLOCK:
                raise MXNetError(
                    "BlockAllocator: reclaiming free block %d" % b)
            self._free.append(b)
            self._free_set.add(b)

    def free(self, blocks):
        """Release AND return to the free list in one step (the
        single-owner path: no prefix cache retains refcount-0 blocks).
        Raises exactly like `release` on double/trash frees."""
        self.reclaim(self.release(blocks))

    def reset(self):
        """Forget every allocation (the pool-rebuild recovery path: the
        device buffer was reallocated, so every table is void)."""
        self._free = list(range(self.n_blocks - 1, TRASH_BLOCK, -1))
        self._free_set = set(self._free)
        self._ref.clear()

    def fragmentation(self, used_tokens, cached_blocks=0):
        """Internal fragmentation: the fraction of allocated token rows
        not holding a live token.  ``used_tokens`` must count each
        PHYSICAL block's written rows once — a block shared by k
        sequences contributes its rows one time, not k (the engine
        aggregates per block id) — and must exclude the trash block,
        which is a shape-padding sink, not an allocation.
        ``cached_blocks`` adds the parked prefix pool to the allocated
        capacity (parked blocks are full by construction, so callers
        include ``cached_blocks * block_size`` in ``used_tokens``).
        0.0 with nothing allocated."""
        cap = (len(self._ref) + int(cached_blocks)) * self.block_size
        if cap <= 0:
            return 0.0
        return max(0.0, 1.0 - float(used_tokens) / cap)


class _PrefixNode:
    """One cached full-block token run: `key` is the exact block_size-
    token tuple, `block` the physical location of its K/V — a device
    block id while ``tier == "dev"``, a host-tier handle while
    ``tier == "host"`` — and the parent chain spells the whole prefix.
    ``host`` (dev-resident nodes only) remembers a still-valid host
    copy from an earlier spill/restore cycle, so re-evicting this node
    costs no second device→host transfer."""

    __slots__ = ("key", "block", "parent", "children", "tier", "host")

    def __init__(self, key, block, parent):
        self.key = key
        self.block = block
        self.parent = parent
        self.children = {}
        self.tier = "dev"
        self.host = None


class PrefixCache:
    """Block-aligned radix index over cached K/V prefixes.

    Keys are the exact token tuples of FULL blocks (no lossy hashing:
    a hash collision would silently alias one prompt's K/V into
    another's attention — dict equality on the tuple makes the match
    exact; Python hashes the tuple internally for the walk).  Only full
    blocks participate: a partially-written block's tail is garbage, so
    it can never be shared.

    Lifecycle: the engine `insert`s a sequence's blocks as they FILL
    (eagerly — a concurrent request can share a block its writer still
    holds, which is where copy-on-write earns its keep), `lookup`s the
    longest cached prefix at admission, `park`s registered blocks whose
    refcount hits zero, and `evict`s parked blocks — oldest-first with
    leaf preference, so a prefix's tail dies before its root — only
    under allocation pressure (or past ``pool_cap``).

    TIERING hooks (both optional — absent, behavior is exactly the
    single-tier PR-12 cache):

    * ``spill_hook(block, tokens, node)`` fires when the LRU evicts a
      parked device block, with the block id, the node's full token
      path, and the node itself — the structured eviction metadata any
      observer needs.  Returning a host-tier handle converts the node
      to host residency (the prefix stays findable); returning None
      detaches it exactly as before.  The evicted DEVICE block is
      returned to the caller for reclaim either way.
    * ``host_drop_hook(handle)`` fires whenever the cache drops its own
      reference to a host handle (node detach/orphan paths), so the
      owner can free the host storage.
    """

    def __init__(self, block_size, pool_cap=-1, spill_hook=None,
                 host_drop_hook=None):
        self.block_size = int(block_size)
        self.pool_cap = int(pool_cap)     # parked blocks retained; < 0 = all
        self.spill_hook = spill_hook
        self.host_drop_hook = host_drop_hook
        self._root = _PrefixNode(None, None, None)
        self._by_block = {}               # device block -> node
        self._by_host = {}                # host handle -> node
        self._parked = OrderedDict()      # block -> node, oldest first

    @property
    def cached_blocks(self):
        """Registered DEVICE blocks (live + parked)."""
        return len(self._by_block)

    @property
    def host_count(self):
        """Host-tier handles this index references (host-resident nodes
        plus retained host copies of device-resident ones) — must equal
        the tier's own `used` count, or someone leaked."""
        return len(self._by_host)

    @property
    def parked_count(self):
        """Refcount-0 blocks retained for reuse (the LRU pool)."""
        return len(self._parked)

    def _path_tokens(self, node):
        """The full token path root→``node`` (the exact tokens whose
        K/V the node's block holds) — the eviction hook's metadata."""
        keys = []
        while node is not self._root:
            keys.append(node.key)
            node = node.parent
        out = []
        for k in reversed(keys):
            out.extend(k)
        return out

    def _key(self, tokens, i):
        bs = self.block_size
        return tuple(int(t) for t in tokens[i * bs:(i + 1) * bs])

    def lookup_plan(self, tokens):
        """The tier-aware match: ``(dev_blocks, host_nodes)`` — the
        longest cached FULL-block prefix of ``tokens`` split into its
        leading device-resident run (block ids, acquire-ready) and the
        host-resident run that follows (nodes, each carrying its host
        handle in ``.block`` — the engine's restore-then-acquire plan).
        Host under device is the only legal stacking (spills are
        bottom-up), so the walk flips exactly once; a device node BELOW
        a host one would mean the invariant broke — the walk stops
        there rather than hand out an unreachable plan.  Touches the
        matched parked path so hot prefixes move to the MRU end of the
        eviction order (recency IS the `_parked` OrderedDict order).
        The caller must `acquire` the device run before any operation
        that could evict (a parked match is still parked until
        acquired)."""
        dev, host = [], []
        node = self._root
        last_dev = self._root
        for i in range(len(tokens) // self.block_size):
            child = node.children.get(self._key(tokens, i))
            if child is None:
                break
            if child.tier == "host":
                host.append(child)
            elif host:
                break
            else:
                dev.append(child.block)
                last_dev = child
            node = child
        n = last_dev
        while n is not self._root:
            if n.block in self._parked:
                self._parked.move_to_end(n.block)
            n = n.parent
        return dev, host

    def lookup(self, tokens):
        """Device block ids of the longest cached FULL-block prefix of
        ``tokens`` (the tier-blind view — exactly the PR-12 result;
        tier-aware callers use `lookup_plan`)."""
        return self.lookup_plan(tokens)[0]

    def insert(self, tokens, blocks, n_full):
        """Register the first ``n_full`` blocks of a sequence (its FULL
        blocks) along the tree path of ``tokens``.  A run already cached
        under a DIFFERENT physical device block keeps the existing copy
        (the walk continues through it, so deeper runs still register);
        a run already cached under the SAME block is a no-op.  A run
        cached only on the HOST tier is UPGRADED: the node repoints at
        the freshly prefilled device block and retains the host copy as
        a free re-spill (prefill of the same tokens under the same
        weights is deterministic, so the two copies are bit-identical).
        Returns the number of newly registered device blocks."""
        node = self._root
        added = 0
        for i in range(min(int(n_full), len(blocks))):
            key = self._key(tokens, i)
            child = node.children.get(key)
            if child is None:
                b = blocks[i]
                if b in self._by_block:
                    # this physical block already backs another run (it
                    # must not appear at two tree positions); stop here
                    break
                child = _PrefixNode(key, b, node)
                node.children[key] = child
                self._by_block[b] = child
                added += 1
            elif child.tier == "host":
                b = blocks[i]
                if b in self._by_block:
                    break
                child.host = child.block
                child.tier = "dev"
                child.block = b
                self._by_block[b] = child
                added += 1
            node = child
        return added

    def contains(self, block):
        return block in self._by_block

    def park(self, block):
        """A registered block's refcount hit zero: retain it in the LRU
        pool instead of freeing.  Returns the blocks evicted to honor
        ``pool_cap`` (the caller reclaims them); [] for an unregistered
        block — the caller frees it directly."""
        node = self._by_block.get(block)
        if node is None:
            return None
        self._parked[block] = node
        self._parked.move_to_end(block)
        evicted = []
        if self.pool_cap >= 0:
            while len(self._parked) > self.pool_cap:
                evicted.extend(self._evict_one())
        return evicted

    def unpark(self, blocks):
        """Blocks re-acquired through a prefix hit leave the LRU pool
        (they are live again; `acquire` holds the refcount)."""
        for b in blocks:
            self._parked.pop(b, None)

    def _evict_one(self):
        """Evict the oldest parked DEVICE leaf (a parked node's device
        children are always parked too — a live child would imply a
        live holder of the whole prefix — so device leaves exist
        whenever the pool is non-empty; preferring them keeps prefix
        ROOTS, the shareable part, alive longest; already-spilled host
        children hang below without pinning their parent).  With a
        ``spill_hook``, the node converts to host residency instead of
        detaching — eviction ORDER over device blocks is identical
        either way (regression-tested), only the node's afterlife
        differs."""
        for b, node in self._parked.items():
            if not any(c.tier == "dev" for c in node.children.values()):
                del self._parked[b]
                self._spill_or_detach(node)
                return [b]
        # unreachable while the parked-subtree invariant holds; take the
        # oldest anyway (detaching orphans its subtree: unregistered,
        # parked descendants evicted with it) rather than looping
        b, node = next(iter(self._parked.items()))
        del self._parked[b]
        evicted = [b]
        self._detach(node)
        self._drop_host_handle(node.host)
        node.host = None
        stack = list(node.children.values())
        node.children = {}
        while stack:
            d = stack.pop()
            if d.tier == "host":
                self._by_host.pop(d.block, None)
                self._drop_host_handle(d.block)
            else:
                self._by_block.pop(d.block, None)
                self._drop_host_handle(d.host)
                if self._parked.pop(d.block, None) is not None:
                    evicted.append(d.block)
            stack.extend(d.children.values())
            d.children = {}
        return evicted

    def _spill_or_detach(self, node):
        """A parked device node lost its block to eviction: convert it
        to host residency when a host copy exists (retained from an
        earlier cycle, or minted right now by the spill hook), detach
        it — dropping any orphaned host descendants — otherwise."""
        handle = node.host
        if handle is None and self.spill_hook is not None:
            handle = self.spill_hook(node.block, self._path_tokens(node),
                                     node)
        if handle is None:
            self._detach(node)
            stack = list(node.children.values())
            node.children = {}
            while stack:  # children of an evictable node are all host
                d = stack.pop()
                if d.tier == "host":
                    self._by_host.pop(d.block, None)
                    self._drop_host_handle(d.block)
                else:
                    self._drop_host_handle(d.host)
                    self._by_block.pop(d.block, None)
                stack.extend(d.children.values())
                d.children = {}
            return
        self._by_block.pop(node.block, None)
        node.block = handle
        node.tier = "host"
        node.host = None
        self._by_host[handle] = node

    def _drop_host_handle(self, handle):
        if handle is not None:
            self._by_host.pop(handle, None)
            if self.host_drop_hook is not None:
                self.host_drop_hook(handle)

    def drop_host(self, handle):
        """The host TIER evicted ``handle`` (its storage is already
        gone): detach the index's view of it.  A retained host copy of
        a device-resident node just loses the shortcut; a host-resident
        node detaches with its (host) subtree.  Returns the ORPHANED
        descendant handles for the caller to free from the tier —
        no ``host_drop_hook`` reentry from this path, the tier
        initiated it."""
        node = self._by_host.pop(handle, None)
        if node is None:
            return []
        if node.tier == "dev":
            node.host = None
            return []
        orphans = []
        self._detach(node)
        stack = list(node.children.values())
        node.children = {}
        while stack:
            d = stack.pop()
            if d.tier == "host":
                self._by_host.pop(d.block, None)
                orphans.append(d.block)
            else:  # dev under host: invariant breach — scrub defensively
                self._by_block.pop(d.block, None)
                self._parked.pop(d.block, None)
            stack.extend(d.children.values())
            d.children = {}
        return orphans

    def restore_landed(self, node, handle, dev_block):
        """A restore staged against host ``handle`` finished writing
        ``dev_block``: flip the node back to device residency, keep the
        host copy as a free re-spill.  Returns False when the node was
        upgraded or dropped in the transfer window (the restored block
        stays the sequence's private property — correct either way, the
        bytes came from the tier, not the tree)."""
        if self._by_host.get(handle) is not node or node.tier != "host" \
                or dev_block in self._by_block:
            return False
        node.tier = "dev"
        node.block = dev_block
        node.host = handle
        self._by_block[dev_block] = node
        return True

    def _detach(self, node):
        if node.tier == "dev":
            self._by_block.pop(node.block, None)
        else:
            self._by_host.pop(node.block, None)
        if node.parent is not None:
            node.parent.children.pop(node.key, None)
        node.parent = None

    def invalidate(self, blocks):
        """Detach the nodes backing ``blocks`` (and their entire
        subtrees — a child run's K/V is only meaningful under its
        parent's context) from the index: the integrity-scrub path
        (quantization scale corruption tripping the serving logit
        gate).  Live holders keep their own table entries — refcounts
        are the allocator's business — the runs just stop being
        findable, so no future lookup can re-acquire them.  Host copies
        under detached nodes drop through ``host_drop_hook``.  Returns
        the PARKED device blocks that were detached (refcount 0,
        unreferenced now): the caller reclaims them."""
        out = []
        for b in blocks:
            node = self._by_block.get(b)
            if node is None:
                continue
            self._detach(node)
            stack = [node]
            while stack:
                d = stack.pop()
                if d.tier == "host":
                    self._by_host.pop(d.block, None)
                    self._drop_host_handle(d.block)
                else:
                    self._by_block.pop(d.block, None)
                    self._drop_host_handle(d.host)
                    d.host = None
                    if self._parked.pop(d.block, None) is not None:
                        out.append(d.block)
                stack.extend(d.children.values())
                d.children = {}
        return out

    def evict(self, n):
        """Evict at least ``n`` parked blocks (fewer if the pool runs
        dry); returns their ids for the caller to `reclaim`."""
        out = []
        while len(out) < int(n) and self._parked:
            out.extend(self._evict_one())
        return out

    def clear(self):
        """Drop every cached prefix (the pool-rebuild recovery path:
        the device blocks the tree points at no longer exist).  Host
        references drop too — the owner clears the tier itself (one
        `HostBlockTier.clear`, not a hook storm)."""
        self._root = _PrefixNode(None, None, None)
        self._by_block.clear()
        self._by_host.clear()
        self._parked.clear()
