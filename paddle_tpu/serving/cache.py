# lint-tpu: disable-file=L004 -- serving owns the block-pool device
# buffers directly (like models/); new backend code belongs under core/
# ops/ kernels/ static/ distributed/ (README: Repo lint)
"""Block-based KV-cache pool with content-addressed prefix caching
(PAPERS.md: vLLM's PagedAttention memory manager + RadixAttention-style
prefix reuse, layered on models/llama.py PagedKVCache semantics).

The pool owns per-layer (k, v) device buffers of shape
``[num_blocks, block_size, kv_heads, head_dim]``.  Sequences own
BLOCKS, not contiguous buffer ranges: a free-list allocator hands out
``block_size``-token blocks one at a time as a sequence's frontier
grows, so cache capacity is packed at block granularity instead of
being reserved at worst-case length per request.

Prefix caching adds three structures on top of the free list:

- **refcounts** — ``_owners[block]`` is the SET of request ids holding
  the block, so two requests sharing a system prompt reference the same
  physical blocks (``free`` decrements; the block is recycled only when
  the last owner lets go);
- **chained content hashes** — a full block of prompt tokens is indexed
  by ``hash(parent_hash || block token ids)``, so a block's identity
  encodes its whole prefix: matching block i implies blocks 0..i-1
  matched too, exactly the chain vLLM/SGLang key their prefix caches
  on.  Only FULL blocks are ever registered (a partial tail is private
  to its request);
- **LRU eviction** — a block whose last owner releases it but whose
  content is still indexed parks in an LRU list instead of the free
  list.  It stays matchable for free until ``allocate`` runs dry, at
  which point the least-recently-parked cached block is evicted (index
  entry dropped) and recycled.  Live-referenced blocks are NEVER
  eviction candidates.

Registered blocks are IMMUTABLE: a request that must write inside one
(shared decode tail, or recomputing the last token of a fully-cached
prompt) first breaks the share with :meth:`ensure_writable` — a
copy-on-write device copy into a private block.

Block 0 is a reserved garbage sink: idle engine slots decode with
block-table entries pointing at it, so the compiled step never needs a
host-side branch on "is this slot live" (the write lands in garbage,
attention masks it, and the hot loop stays device-resident — H106).
"""
from __future__ import annotations

import functools
import hashlib
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.kv_quant import (kv_bytes_per_element,
                                kv_scale_bytes_per_block,
                                kv_storage_dtype, resolve_kv_cache_dtype)


def consumed(operands) -> bool:
    """A program took these operands: a donated array among them is
    deleted (``jax.Array.is_deleted``; host arrays are never donated)."""
    return any(isinstance(a, jax.Array) and a.is_deleted()
               for a in jax.tree_util.tree_leaves(operands))


class PoolExhausted(Exception):
    """No free or evictable blocks: the caller must preempt or wait."""


class BlockKVPool:
    def __init__(self, num_layers: int, num_blocks: int, block_size: int,
                 kv_heads: int, head_dim: int, dtype=jnp.float32,
                 enable_prefix_cache: bool = True,
                 kv_cache_dtype: Optional[str] = None,
                 sidecars: Sequence[tuple] = ()):
        if num_blocks < 2:
            raise ValueError("need >= 2 blocks (block 0 is the reserved "
                             "garbage sink)")
        self.num_layers = num_layers
        self.num_blocks = num_blocks
        self.block_size = block_size
        self.kv_heads = kv_heads
        self.head_dim = head_dim
        #: quant scheme: None (full precision) / "int8" / "fp8"
        self.kv_cache_dtype = resolve_kv_cache_dtype(kv_cache_dtype)
        #: the MODEL's kv dtype (what dequant produces / fp32 pools hold)
        self.model_dtype = dtype
        #: the STORAGE dtype the pool arrays actually carry
        self.dtype = kv_storage_dtype(self.kv_cache_dtype) or dtype
        self.enable_prefix_cache = enable_prefix_cache
        # content-hash chains are seeded with the dtype tag, so an int8
        # pool can never match blocks registered under an fp32 config
        # (or the other scheme) — the seed IS the namespace
        self._hash_seed = self.kv_dtype_tag.encode()
        # what a model keeps per cached position beside K and V (the
        # experts its router chose, say): ``(shape, dtype)`` each, one
        # more array an entry, ONE ROW A BLOCK (``[num_blocks,
        # block_size * size]``, a block's positions one after another:
        # a few values a position, laid out ``[num_blocks, block_size,
        # *shape]``, are stored by the device in another order than a
        # row write wants and relaid around every write), written by
        # the model's step programs at the positions they write K/V and
        # moved with its block by copy-on-write
        self._sidecars = [(int(np.prod(shape, dtype=np.int64)), dt)
                          for shape, dt in sidecars]
        self.layers: List[Tuple[jax.Array, ...]] = self._fresh_layers()
        # LIFO free list over blocks 1..n-1 (block 0 reserved)
        self._free: List[int] = list(range(num_blocks - 1, 0, -1))
        # block id -> set of owning request ids (refcount = len)
        self._owners: Dict[int, Set] = {}
        # content index: chain hash -> block id, and its reverse.
        # Invariant: b in _block_hash  <=>  _hash_index[_block_hash[b]] == b
        self._hash_index: Dict[bytes, int] = {}
        self._block_hash: Dict[int, bytes] = {}
        # refcount-0 blocks still holding indexed content, oldest first —
        # matchable for free, evictable when the free list runs dry
        self._cached_free: "OrderedDict[int, None]" = OrderedDict()
        # chain ROOTS (depth-1 hashes), most recently registered last —
        # the cheap recency signal prefix_summary() exposes to a fleet
        # router (every cached prompt family is reachable through one)
        self._roots: "OrderedDict[bytes, None]" = OrderedDict()
        self.evictions = 0
        self.cow_copies = 0

    def _fresh_layers(self) -> List[Tuple[jax.Array, ...]]:
        """Per-layer physical pools — the arrays handed to a compiled
        step and rebound to its outputs every token.  Entries are (k, v)
        for full-precision pools and (k, v, k_scale, v_scale) for
        quantized ones: int8 code pools plus one f32 absmax scale per
        (block, token) row (kernels/kv_quant.py); then the sidecars.
        Every leaf is a buffer of its own: the step programs DONATE the
        pool, and the runtime refuses to donate one buffer twice."""
        rows = (self.num_blocks, self.block_size)

        def entry():
            kv = tuple(jnp.zeros(rows + (self.kv_heads, self.head_dim),
                                 self.dtype) for _ in range(2))
            if self.kv_cache_dtype is not None:
                kv += tuple(jnp.ones(rows, jnp.float32) for _ in range(2))
            return kv + tuple(
                jnp.zeros((self.num_blocks, self.block_size * size), dt)
                for size, dt in self._sidecars)

        return [entry() for _ in range(self.num_layers)]

    def lost(self) -> bool:
        """A step program consumed the pool and gave none back (it
        failed after it took its donated operands): some leaf is a
        deleted array."""
        return consumed(self.layers)

    def reset(self):
        """Fresh zeroed buffers and an empty prefix index: what
        ``Engine.revive()`` does about a lost pool, once no request
        references a block (the cached K/V went with the buffers, so an
        indexed block would serve zeros)."""
        self.check_leaks()
        self.layers = self._fresh_layers()
        self._free = list(range(self.num_blocks - 1, 0, -1))
        self._hash_index.clear()
        self._block_hash.clear()
        self._cached_free.clear()
        self._roots.clear()

    # ------------------------------------------------------- accounting
    @property
    def capacity_blocks(self) -> int:
        """Allocatable blocks (excludes the reserved garbage block)."""
        return self.num_blocks - 1

    @property
    def num_free(self) -> int:
        """Blocks allocatable RIGHT NOW: truly free plus cached-but-
        unreferenced (the latter evict on demand)."""
        return len(self._free) + len(self._cached_free)

    @property
    def num_used(self) -> int:
        """Blocks referenced by at least one live request."""
        return self.capacity_blocks - self.num_free

    @property
    def num_cached(self) -> int:
        """Unreferenced blocks kept alive only by the prefix index."""
        return len(self._cached_free)

    def utilization(self) -> float:
        return self.num_used / self.capacity_blocks

    # --------------------------------------------------- byte accounting
    @property
    def kv_dtype_tag(self) -> str:
        """Stable string identity of this pool's KV storage format —
        the prefix-cache hash namespace and the router's fleet-dtype
        key (``"int8"``, ``"fp8"``, or ``"fp32:<model dtype>"``)."""
        if self.kv_cache_dtype is not None:
            return self.kv_cache_dtype
        return f"fp32:{jnp.dtype(self.model_dtype).name}"

    @staticmethod
    def block_bytes_for(num_layers: int, block_size: int, kv_heads: int,
                        head_dim: int, dtype=jnp.float32,
                        kv_cache_dtype: Optional[str] = None) -> int:
        """HBM bytes ONE logical block costs across all layers (k and v
        pools plus quantized scale sidecars) — computable before the
        pool exists, so the engine can size ``num_blocks`` from a fixed
        ``kv_pool_bytes`` budget per dtype."""
        scheme = resolve_kv_cache_dtype(kv_cache_dtype)
        esize = kv_bytes_per_element(scheme, dtype)
        per_side = block_size * kv_heads * head_dim * esize \
            + kv_scale_bytes_per_block(block_size, scheme)
        return int(num_layers * 2 * per_side)

    def block_bytes(self) -> int:
        """HBM bytes one block costs in THIS pool (all layers, k + v,
        including quantized scale rows)."""
        return self.block_bytes_for(self.num_layers, self.block_size,
                                    self.kv_heads, self.head_dim,
                                    self.model_dtype, self.kv_cache_dtype)

    def capacity_bytes(self) -> int:
        return self.capacity_blocks * self.block_bytes()

    def used_bytes(self) -> int:
        """Bytes referenced by live requests — the quantity degradation
        watermarks compare against :meth:`capacity_bytes` (a quantized
        pool burns ~4x fewer bytes per resident token, so the ladder
        engages later at the same request load)."""
        return self.num_used * self.block_bytes()

    def byte_utilization(self) -> float:
        """Fraction of the pool's KV byte capacity referenced by live
        requests.  Blocks are homogeneous within one pool so this equals
        :meth:`utilization` numerically, but it is the BYTE-denominated
        pressure signal: two pools sized from the same ``kv_pool_bytes``
        budget at different dtypes report comparable pressure per byte,
        not per block."""
        return self.used_bytes() / self.capacity_bytes()

    def blocks_for(self, num_tokens: int) -> int:
        """Blocks needed to hold ``num_tokens`` cache positions."""
        return -(-int(num_tokens) // self.block_size)

    def can_allocate(self, n: int) -> bool:
        return self.num_free >= n

    def owned_by(self, request_id) -> List[int]:
        return [b for b, o in self._owners.items() if request_id in o]

    def refcount(self, block: int) -> int:
        return len(self._owners.get(block, ()))

    def is_shared(self, block: int) -> bool:
        """True when a write into ``block`` would be observable outside
        the writing request: another owner holds it, or the prefix index
        still advertises its content to future requests."""
        return len(self._owners.get(block, ())) > 1 \
            or block in self._block_hash

    # ------------------------------------------------------- allocation
    def allocate(self, request_id, n: int = 1) -> List[int]:
        """Hand ``n`` private blocks to ``request_id``, evicting LRU
        cached blocks if the free list alone cannot cover the request.
        Raises :class:`PoolExhausted` (allocating nothing) otherwise."""
        if self.num_free < n:
            raise PoolExhausted(
                f"need {n} block(s), {len(self._free)} free + "
                f"{len(self._cached_free)} evictable "
                f"(capacity {self.capacity_blocks})")
        blocks = []
        for _ in range(n):
            b = self._free.pop() if self._free else self._evict_lru()
            self._owners[b] = {request_id}
            blocks.append(b)
        return blocks

    def _evict_lru(self) -> int:
        """Drop the least-recently-parked cached block from the prefix
        index and recycle it.  Only refcount-0 blocks ever sit in
        ``_cached_free``, so a live request's block can never be chosen."""
        b, _ = self._cached_free.popitem(last=False)
        h = self._block_hash.pop(b, None)
        if h is not None and self._hash_index.get(h) == b:
            del self._hash_index[h]
            self._roots.pop(h, None)
        self.evictions += 1
        return b

    def evict_parked(self, n: Optional[int] = None) -> int:
        """Eagerly evict up to ``n`` (default: all) PARKED prefix-cache
        blocks, LRU-first, returning them to the free list.  The
        degradation ladder's first rung (serving/overload.py): parked
        blocks already count as allocatable headroom (``num_free``), but
        reclaiming them up front makes the headroom real before a burst
        of allocations has to evict one block at a time — and drops the
        stale prefix index entries with them.  Returns the number
        evicted."""
        count = 0
        while self._cached_free and (n is None or count < n):
            self._free.append(self._evict_lru())
            count += 1
        return count

    def _release_block(self, b: int):
        """Last owner gone: park indexed content in the LRU, recycle the
        rest."""
        self._owners.pop(b, None)
        if self.enable_prefix_cache and b in self._block_hash:
            self._cached_free[b] = None     # LRU tail = most recent
        else:
            self._free.append(b)

    def free(self, blocks: Sequence[int], request_id=None):
        """Drop ``request_id``'s reference on each block (refcount
        decrement); a block with no owners left is recycled.  Without a
        ``request_id`` the block must be singly-owned (the pre-refcount
        call shape); freeing a block the id does not own — or freeing an
        unowned block — is the classic double free, reported with the
        CURRENT owner set to ease debugging."""
        for b in blocks:
            owners = self._owners.get(b)
            if owners is None:
                raise ValueError(
                    f"double free of block {b} (no current owner)")
            if request_id is None:
                if len(owners) > 1:
                    raise ValueError(
                        f"block {b} is shared (owned by "
                        f"{sorted(map(str, owners))}); "
                        f"free(..., request_id=...) required")
                owners.clear()
            else:
                if request_id not in owners:
                    raise ValueError(
                        f"double free of block {b} by {request_id!r} "
                        f"(owned by {sorted(map(str, owners))})")
                owners.discard(request_id)
            if not owners:
                self._release_block(b)

    def free_request(self, request_id):
        """Release every block ``request_id`` references.  A request
        owning nothing (never prefilled, or already released) is a safe
        no-op — retire paths call this unconditionally.

        Blocks release in REVERSE acquisition order, so a prompt
        chain's tail blocks park in the LRU before its head: under
        pressure eviction then consumes leaves first, and the head —
        which ANY extension of the prefix can reuse, where a tail only
        serves exact matches — survives longest (the radix-tree
        leaf-first eviction order of the prefix-caching literature)."""
        blocks = self.owned_by(request_id)
        if not blocks:
            return
        self.free(list(reversed(blocks)), request_id)

    def check_leaks(self):
        """Raise if any block is still owned by a request — used by
        tests and engine shutdown to prove references round-trip.
        Cached-but-unreferenced blocks are NOT leaks (they are
        reclaimable on demand)."""
        if self._owners:
            raise AssertionError(
                "leaked blocks: "
                f"{sorted((b, sorted(map(str, o))) for b, o in self._owners.items())}")

    # ---------------------------------------------------- prefix cache
    @staticmethod
    def _chain_hash(parent: bytes, tokens: np.ndarray) -> bytes:
        h = hashlib.blake2b(parent, digest_size=16)
        h.update(np.ascontiguousarray(tokens, np.int32).tobytes())
        return h.digest()

    def hash_chain(self, tokens) -> List[bytes]:
        """Chained content hashes of every FULL block of ``tokens``:
        ``chain[i] = H(chain[i-1] || tokens[i*bs:(i+1)*bs])``.  A match
        on chain[i] therefore implies the entire prefix matched."""
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        bs = self.block_size
        out: List[bytes] = []
        parent = self._hash_seed
        for i in range(len(tokens) // bs):
            parent = self._chain_hash(parent, tokens[i * bs:(i + 1) * bs])
            out.append(parent)
        return out

    def match_prefix(self, tokens) -> List[int]:
        """Longest indexed prefix of ``tokens``, as a block-id list
        (full blocks only; stops at the first miss).  Pure lookup: no
        refcounts move until :meth:`acquire`."""
        if not self.enable_prefix_cache:
            return []
        out: List[int] = []
        for h in self.hash_chain(tokens):
            b = self._hash_index.get(h)
            if b is None:
                break
            out.append(b)
        return out

    def acquire(self, request_id, blocks: Sequence[int]):
        """Add ``request_id``'s reference to already-populated blocks
        (a prefix-cache hit).  Blocks parked in the LRU come back to
        life; blocks some other request still owns just gain an owner."""
        for b in blocks:
            owners = self._owners.get(b)
            if owners is not None:
                owners.add(request_id)
            elif b in self._cached_free:
                del self._cached_free[b]
                self._owners[b] = {request_id}
            else:
                raise ValueError(
                    f"cannot acquire block {b}: neither owned nor cached")

    def register_prefix(self, request_id, tokens, blocks: Sequence[int]
                        ) -> int:
        """Index ``request_id``'s prompt blocks by content so future
        prompts can reuse them.  Dedupes against existing entries (first
        writer wins — identical content, either block serves) and skips
        blocks the request does not own (defensive: CoW may have
        retired them mid-prefill).  Returns how many entries were added.
        Registered blocks become immutable until evicted."""
        if not self.enable_prefix_cache:
            return 0
        added = 0
        chain = self.hash_chain(tokens)
        for h, b in zip(chain, blocks):
            if h in self._hash_index or b in self._block_hash:
                continue
            owners = self._owners.get(b)
            if owners is None or request_id not in owners:
                continue
            self._hash_index[h] = b
            self._block_hash[b] = h
            added += 1
        # refresh root recency: depth-1 hash of an indexed chain is the
        # entry point any prompt sharing this prefix family matches
        # through (re-registering moves it to most-recent)
        if chain and chain[0] in self._hash_index:
            self._roots.pop(chain[0], None)
            self._roots[chain[0]] = None
        return added

    def ensure_writable(self, request_id, block: int) -> int:
        """Copy-on-write guard: return a block ``request_id`` may write
        in place — ``block`` itself when exclusively owned and not in
        the prefix index, otherwise a fresh private copy (device copy of
        all layers; the request's reference moves to the copy)."""
        owners = self._owners.get(block)
        if owners is None or request_id not in owners:
            raise ValueError(
                f"{request_id!r} does not own block {block}")
        if len(owners) == 1 and block not in self._block_hash:
            return block
        new = self.allocate(request_id, 1)[0]
        self._copy_block(block, new)
        owners.discard(request_id)
        if not owners:
            self._release_block(block)
        self.cow_copies += 1
        return new

    def _copy_block(self, src: int, dst: int):
        # ``layers`` is donated, as to a step program: one live pool
        new = _copy_block_impl(tuple(self.layers), np.int32(src),
                               np.int32(dst))
        self.layers = [tuple(entry) for entry in new]

    def admission_plan(self, tokens, extra_tokens: int = 1):
        """Admission-control view of one prompt: ``(matched_blocks,
        new_blocks_needed, feasible_now)``.  Matched blocks that sit in
        the evictable LRU are NOT double-counted as allocatable — the
        hit consumes them."""
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        matched = self.match_prefix(tokens)
        need = self.blocks_for(len(tokens) + extra_tokens) - len(matched)
        need = max(need, 0)
        from_lru = sum(1 for b in matched if b in self._cached_free)
        return matched, need, need <= self.num_free - from_lru

    def stats(self) -> dict:
        return {
            "capacity_blocks": self.capacity_blocks,
            "used_blocks": self.num_used,
            "free_blocks": self.num_free,
            "cached_blocks": self.num_cached,
            "block_size": self.block_size,
            "utilization": round(self.utilization(), 4),
            "prefix_evictions": self.evictions,
            "cow_copies": self.cow_copies,
            "kv_dtype": self.kv_dtype_tag,
            "block_bytes": self.block_bytes(),
            "used_bytes": self.used_bytes(),
            "capacity_bytes": self.capacity_bytes(),
            "byte_utilization": round(self.byte_utilization(), 4),
        }

    def prefix_summary(self, max_roots: int = 8) -> dict:
        """Host-side summary of the prefix index for a FLEET ROUTER
        (serving/router.py): enough to score a candidate prompt's
        expected cached-token count on this pool WITHOUT reaching into
        pool internals.  ``hashes`` is every indexed chain hash (hex; at
        most ``capacity_blocks`` 16-byte digests, so the summary stays
        cheap); a router chains the prompt with :meth:`hash_chain` and
        counts leading members — the same stop-at-first-miss walk
        :meth:`match_prefix` performs.  ``roots`` are the most recently
        registered depth-1 hashes (recent-first): the coarse "which
        prompt families live here" signal for dashboards and logs."""
        roots = [h.hex() for h in reversed(self._roots)]
        return {
            "block_size": self.block_size,
            "kv_dtype": self.kv_dtype_tag,
            "cached_blocks": self.num_cached,
            "indexed_blocks": len(self._hash_index),
            "roots": roots[:max_roots],
            "hashes": [h.hex() for h in self._hash_index],
        }


@functools.partial(jax.jit, donate_argnums=(0,))
def _copy_block_impl(layers, src, dst):
    # one executable per pool geometry: src/dst ride in as traced
    # scalars.  Entries are (k, v) or (k, v, k_scale, v_scale) — a CoW
    # copy of a quantized block must move the scale rows with the codes
    return [tuple(a.at[dst].set(a[src]) for a in entry)
            for entry in layers]
