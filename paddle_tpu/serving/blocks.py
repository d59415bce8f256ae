"""Host-side block allocator + prefix cache for the paged KV pool.

The device side is a flat pool (``models/transformer.init_block_pool``,
[L, num_blocks·block_size, Hkv, Dh]); this module owns the HOST
bookkeeping that decides which aligned ``block_size`` span backs which
logical positions of which request:

- **free list** — blocks never touched or fully released;
- **refcounts** — a block holding a shared prompt prefix is referenced
  by every slot whose page table maps it (prefix hits call
  :meth:`share`); it frees only when the LAST holder releases;
- **prefix cache** — full PROMPT blocks are published under a
  content-chain hash (:func:`chain_hash` over the parent digest + the
  block's token ids, so a hit certifies the whole prefix, not one
  block); a later request whose prompt starts with the same token
  blocks maps them straight into its page table and skips their
  prefill compute entirely;
- **LRU** — a cached block whose refcount drops to 0 parks in an LRU
  instead of the free list: it still serves future hits for free, and
  allocation pressure evicts oldest-first (eviction un-publishes the
  hash — the KV bytes are about to be overwritten).

Everything here is pure-python/numpy host state — no jax — so block
lifecycle is unit-testable without a device
(tests/test_paged_engine.py).
"""

import hashlib
from collections import OrderedDict, deque
from typing import Dict, List, Optional

import numpy as np

# The engines' default KV block: the smallest size the compiled decode
# and chunk-prefill kernels accept (their score-scratch stores need a
# 128-aligned lane offset — ops/pallas/decode.check_compiled_block_size;
# block 16/32 is refused by the v5e compiler). The default prefill chunk
# is one block: chunks must be whole blocks.
DEFAULT_BLOCK_SIZE = 128
DEFAULT_CHUNK_TOKENS = 128

# chain root: the hash of "no prefix" (any constant salt works; a named
# one keeps digests stable across processes for debugging)
ROOT_HASH = b"paddle-tpu-paged-kv-root"


def chain_hash(parent: bytes, tokens) -> bytes:
    """Digest of one full prompt block GIVEN its prefix digest — equal
    digests certify equal (prefix + block) token content, which is what
    makes a cached block's KV reusable verbatim."""
    h = hashlib.blake2b(parent, digest_size=16)
    h.update(np.asarray(tokens, np.int32).tobytes())
    return h.digest()


def prompt_block_hashes(prompt: np.ndarray, block_size: int
                        ) -> List[bytes]:
    """Chain digests of every FULL block of ``prompt`` (the tail partial
    block is never cached — decode keeps writing into it)."""
    out, h = [], ROOT_HASH
    for i in range(len(prompt) // block_size):
        h = chain_hash(h, prompt[i * block_size:(i + 1) * block_size])
        out.append(h)
    return out


class BlockPool:
    """Refcounted allocator over ``num_blocks`` KV blocks with a
    content-addressed prefix cache and LRU eviction of refcount-0
    cached blocks.

    Reservation protocol: the engine reserves a request's worst-case
    block count (prompt + max_new, minus prefix hits) at ADMISSION via
    :meth:`reserve`, then allocates lazily as positions are actually
    written (:meth:`alloc` consumes one reservation). Decode therefore
    never stalls mid-flight on an empty pool — admission is the only
    backpressure point."""

    def __init__(self, num_blocks: int, block_size: int):
        if num_blocks < 1 or block_size < 1:
            raise ValueError(f"need >=1 blocks of >=1 tokens, got "
                             f"{num_blocks}x{block_size}")
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        self._free = deque(range(self.num_blocks))
        self._ref = np.zeros(self.num_blocks, np.int64)
        self._hash: Dict[int, bytes] = {}       # cached block -> digest
        self._index: Dict[bytes, int] = {}      # digest -> cached block
        self._lru: "OrderedDict[int, None]" = OrderedDict()
        self._reserved = 0
        self.evictions = 0                      # lifetime LRU evictions
        # demotion hook: called as on_evict(block, digest) when alloc()
        # evicts a refcount-0 cached block, BEFORE the new holder's
        # refcount is set — the KV bytes still match the digest at that
        # instant (nothing has scattered over them yet), which is what
        # lets a tiered store serialize the block on its way out.
        # unpublish() does NOT fire it: there the bytes are about to
        # stop matching the digest, so there is nothing worth spilling.
        self.on_evict = None

    # -- occupancy ---------------------------------------------------------
    @property
    def free_count(self) -> int:
        """Blocks holding nothing at all (not even cached content)."""
        return len(self._free)

    @property
    def cached_free_count(self) -> int:
        """Refcount-0 blocks parked in the LRU (evictable cache)."""
        return len(self._lru)

    @property
    def allocatable(self) -> int:
        """Blocks an alloc() could return right now (free + evictable)."""
        return len(self._free) + len(self._lru)

    @property
    def in_use(self) -> int:
        """Blocks referenced by at least one live slot."""
        return self.num_blocks - self.allocatable

    @property
    def reserved(self) -> int:
        return self._reserved

    @property
    def cached_count(self) -> int:
        """Blocks published in the prefix cache (any refcount)."""
        return len(self._index)

    def refcount(self, block: int) -> int:
        return int(self._ref[block])

    def lru_oldest(self) -> Optional[int]:
        """The refcount-0 cached block ``alloc()`` would evict next
        (None when the LRU is empty) — lets a bulk adopter
        (``import_prefix``) stop before eating its own chain head."""
        return next(iter(self._lru), None)

    @property
    def idle(self) -> bool:
        """True when no slot holds a block and nothing is reserved —
        the no-leak invariant a drained engine must restore."""
        return self._reserved == 0 and self.in_use == 0

    # -- reservation -------------------------------------------------------
    def can_reserve(self, n: int) -> bool:
        return self._reserved + n <= self.allocatable

    def reserve(self, n: int):
        if not self.can_reserve(n):
            raise RuntimeError(
                f"reserve({n}): only {self.allocatable - self._reserved} "
                f"unreserved blocks left of {self.num_blocks}")
        self._reserved += n

    def unreserve(self, n: int):
        if n > self._reserved:
            raise RuntimeError(f"unreserve({n}) exceeds reservation "
                               f"{self._reserved}")
        self._reserved -= n

    # -- lifecycle ---------------------------------------------------------
    def alloc(self) -> int:
        """One private block (refcount 1), consuming one reservation.
        Prefers never-cached free blocks; under pressure evicts the
        LRU-oldest refcount-0 cached block (un-publishing its hash)."""
        if self._reserved < 1:
            raise RuntimeError("alloc() without a reservation")
        self._reserved -= 1
        if self._free:
            b = self._free.popleft()
        elif self._lru:
            b, _ = self._lru.popitem(last=False)      # oldest first
            h = self._hash.pop(b)
            del self._index[h]
            self.evictions += 1
            if self.on_evict is not None:
                self.on_evict(b, h)
        else:
            raise RuntimeError("block pool exhausted despite reservation")
        self._ref[b] = 1
        return b

    def share(self, block: int):
        """One more holder of ``block`` (a prefix-cache hit). Revives a
        refcount-0 cached block out of the LRU."""
        if self._ref[block] == 0:
            if block not in self._lru:
                raise RuntimeError(f"share({block}): block is free, "
                                   f"not cached")
            del self._lru[block]
        self._ref[block] += 1

    def release(self, block: int):
        """Drop one holder. At refcount 0 a cache-published block parks
        in the LRU (MRU end); a private one returns to the free list."""
        if self._ref[block] < 1:
            raise RuntimeError(f"release({block}): refcount already 0")
        self._ref[block] -= 1
        if self._ref[block] == 0:
            if block in self._hash:
                self._lru[block] = None
            else:
                self._free.append(block)

    # -- prefix cache ------------------------------------------------------
    def cached_digests(self, limit: Optional[int] = None) -> List[bytes]:
        """Digests currently published in the prefix cache, hottest
        first (refcount>0 carriers, then LRU newest-to-oldest) — the
        HBM rows of a fleet cache directory's per-replica listing."""
        hot = [self._hash[b] for b in self._hash if self._ref[b] > 0]
        cold = [self._hash[b] for b in reversed(self._lru)]
        out = hot + cold
        return out[:limit] if limit else out

    def lookup(self, digest: bytes) -> Optional[int]:
        """Cached block for ``digest`` (LRU-parked ones included), or
        None."""
        return self._index.get(digest)

    def publish(self, digest: bytes, block: int):
        """Register ``block`` as the cached carrier of ``digest``.
        No-op when the digest is already cached (first writer wins) or
        the block already carries another digest."""
        if digest in self._index or block in self._hash:
            return
        self._index[digest] = block
        self._hash[block] = digest

    def unpublish(self, block: int):
        """Drop ``block``'s prefix-cache entry, if any. The
        preempt-to-blocks resume path calls this on the revived PARTIAL
        tail block right before decoding writes into it again — its
        bytes are about to stop matching the published digest. A
        refcount-0 LRU-parked block loses its cache-worthiness too and
        returns to the plain free list."""
        h = self._hash.pop(block, None)
        if h is None:
            return
        del self._index[h]
        if block in self._lru:
            del self._lru[block]
            self._free.append(block)
