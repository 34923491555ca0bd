"""Host-DRAM block tier under the paged K/V pool.

PR 10's parked-block LRU keeps hot prefixes alive — but only in HBM.
Under real multi-tenant traffic the hot-prefix working set (system
prompts, few-shot templates, chat histories) vastly exceeds device
memory, and the moment the `PrefixCache` LRU evicts a parked block its
K/V is destroyed: the next request over the same prefix pays a full
prefill recompute.  This module adds the standard answer (vLLM-style
swapping, SGLang-style hierarchical radix caching): a HOST tier.

`HostBlockTier` is the host half of the two-tier design — a bounded
LRU pool of spilled K/V blocks, each a pinned-in-practice numpy array
of one device block's `(num_layers, 2, block_size, embed)` rows:

* **spill**  — when the engine's prefix LRU evicts a parked device
  block (allocation pressure, ``pool_cap`` overflow, or the
  `prefix_evict:P` chaos clause), the `PrefixCache` eviction hook
  copies the block device→host into this pool and the radix node
  CONVERTS to host residency instead of detaching: the prefix stays
  findable, only its bytes moved down a tier.  The device block still
  returns to the free list — spilling frees HBM, that is the point.
* **restore** — a prefix lookup that lands on host-resident nodes
  returns a *restore-then-acquire* plan: the engine allocates fresh
  device blocks, issues an async `jax.device_put` per host block at
  admission, OVERLAPS the transfer with the current decode iteration
  (the same two-stage stage-ahead pattern as `io.DevicePrefetchIter`),
  and completes the restore next iteration with one tiny
  pool-scatter program compiled at warmup (`AotCache` stays frozen —
  the restore's cost is the PCIe copy, not a compile).  A host hit
  therefore costs a transfer instead of a prefill recompute, and a
  miss is never blocked behind someone else's restore.

The tier is content-addressed by the `PrefixCache`'s radix index, not
by this class: handles minted here are opaque ids the cache stores in
its host-resident nodes.  Blocks are immutable once spilled (only FULL
blocks ever register, and copy-on-write keeps writers off registered
blocks), so a host copy can be retained even after a restore — the
node remembers its handle, and a later re-eviction flips back to host
residency without another PCIe copy.

Capacity is ``MXNET_SERVE_HOST_BLOCKS`` blocks with this pool's own
LRU: spilling past capacity evicts the oldest host block, and the
owner (the engine) detaches the corresponding radix node — the
bottom of the memory hierarchy really does forget.  Everything lives
behind ``MXNET_SERVE_TIER`` (default off); ``=0`` restores the PR-12
evict-and-destroy behavior bit for bit.

Threading contract: scheduler thread only, like `BlockAllocator` —
every mutation happens between compiled launches of the engine that
owns the pool the blocks came from.

The tier is SHARD-AGNOSTIC (docs/serving.md "Sharded replicas"): a
sub-mesh engine spills full-embed host copies (reading one block of a
sharded pool assembles the global view) and restores through its own
``_put_run``, which re-splits the embed axis over the mesh — so host
handles minted by a 1-device engine restore fine into a 4-shard one
after a respawn changed the replica's geometry.
"""
from __future__ import annotations

from collections import OrderedDict

import numpy as np

from ..base import MXNetError

__all__ = ["HostBlockTier", "pack_block_run", "check_cache_kind"]


def check_cache_kind(model, what):
    """Block runs are packed and landed as ``(layers, 2, k, block_size,
    embed)`` K/V pairs; a model whose pool is of another kind (`cache_kind`)
    is refused by name here rather than mis-copied."""
    kind = getattr(model, "cache_kind", "kv_pair")
    if kind != "kv_pair":
        raise MXNetError("%s know the kv_pair pool layout only: %s's cache "
                         "kind is %r" % (what, type(model).__name__, kind))


def pack_block_run(model, block_size, arrs, kb):
    """Pack an ordered run of per-block K/V payloads into ONE padded
    `block_run_placeholder` — the single-transfer shape both the
    host-tier restore and the disaggregated prefill→decode handoff
    stage, so one async ``device_put`` (not one per block) carries the
    whole run and one bucketed ``write_block`` scatter lands it.
    ``arrs`` holds host copies of each block's rows — arrays, or the
    (int8 rows, f32 scales) tuple under serving KV quantization, in
    which case the placeholder is the matching tuple and both leaves
    pack in lockstep.  Entries past ``len(arrs)`` stay zero; the
    caller's trash-padded destination ids scatter them into the trash
    block."""
    check_cache_kind(model, "the host tier's block runs")
    data = model.block_run_placeholder(kb, block_size)
    for j, a in enumerate(arrs):
        if isinstance(data, tuple):
            data[0][:, :, j] = a[0]
            data[1][:, :, j] = a[1]
        else:
            data[:, :, j] = a
    return data


class HostBlockTier:
    """Bounded LRU pool of spilled K/V blocks on host DRAM.

    Handles are opaque monotonically increasing ints (they share no id
    space with device block ids — the radix node's ``tier`` field
    disambiguates).  `put` may evict the LRU tail to make room and
    returns the evicted handles so the OWNER can detach their radix
    nodes; this class never calls back into the cache (no reentrancy:
    the spill path is already running inside a cache eviction)."""

    def __init__(self, capacity):
        if int(capacity) < 1:
            raise MXNetError(
                "HostBlockTier: capacity must be >= 1 host blocks, "
                "got %d" % capacity)
        self.capacity = int(capacity)
        self._data = OrderedDict()    # handle -> block payload, LRU order
        self._next = 1
        self.bytes = 0                # host DRAM held (telemetry)

    @staticmethod
    def _nbytes(arr):
        """Bytes of one stored payload: an array, or — under serving
        KV quantization — the (int8 rows, f32 scales) tuple.  The tier
        stores whatever dtype the pool uses, so ``bytes`` directly
        witnesses the quantized-spill footprint (int8 blocks cost ~1/4
        the host DRAM and PCIe restore bytes of f32 ones)."""
        if isinstance(arr, tuple):
            return sum(a.nbytes for a in arr)
        return arr.nbytes

    @property
    def used(self):
        """Host blocks currently resident."""
        return len(self._data)

    def put(self, arr):
        """Store one spilled block; returns ``(handle, evicted)`` where
        ``evicted`` lists the LRU handles pushed out to make room (the
        caller detaches their index entries — their K/V is gone).

        ``arr`` may be a still-in-flight device array whose
        device→host copy was dispatched asynchronously (the spill path
        must never block the admission road on a transfer): `get`
        finalizes it to numpy on first use, by which point the copy
        has long completed."""
        evicted = []
        while len(self._data) >= self.capacity:
            h, old = self._data.popitem(last=False)
            self.bytes -= self._nbytes(old)
            evicted.append(h)
        handle = self._next
        self._next += 1
        self._data[handle] = arr
        self.bytes += self._nbytes(arr)
        return handle, evicted

    def get(self, handle):
        """The block's host array (MRU-touched), or None when the tier
        no longer holds it (evicted in a window — the caller falls back
        to recompute, never an error).  A spill stored as an in-flight
        device array finalizes to numpy here — waiting only on ITS OWN
        transfer (dispatched at least one admission ago), never on the
        device's launch queue."""
        arr = self._data.get(handle)
        if arr is None:
            return None
        if isinstance(arr, tuple):
            if not all(isinstance(a, np.ndarray) for a in arr):
                arr = tuple(np.asarray(a) for a in arr)
                self._data[handle] = arr
        elif not isinstance(arr, np.ndarray):
            arr = np.asarray(arr)
            self._data[handle] = arr
        self._data.move_to_end(handle)
        return arr

    def contains(self, handle):
        return handle in self._data

    def touch(self, handle):
        """MRU-touch without reading (a lookup matched this block)."""
        if handle in self._data:
            self._data.move_to_end(handle)

    def free(self, handle):
        """Drop one block (its index entry is gone).  Idempotent: a
        handle the LRU already evicted is a no-op, so the owner never
        has to care who forgot first."""
        arr = self._data.pop(handle, None)
        if arr is not None:
            self.bytes -= self._nbytes(arr)

    def clear(self):
        """Forget everything (the pool-rebuild recovery path: the
        device pool the index pointed at is gone, and the index was
        cleared with it)."""
        self._data.clear()
        self.bytes = 0
