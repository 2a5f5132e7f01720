# lint-tpu: disable-file=L004 -- serving owns the block-pool device
# buffers directly (like models/); new backend code belongs under core/
# ops/ kernels/ static/ distributed/ (README: Repo lint)
"""Block-based KV-cache pool with content-addressed prefix caching
(PAPERS.md: vLLM's PagedAttention memory manager + RadixAttention-style
prefix reuse, layered on models/llama.py PagedKVCache semantics).

The pool owns per-layer (k, v) device buffers of shape
``[num_blocks, block_size, kv_heads, head_dim]`` (a LATENT layer, one
array a position with no heads: ``[num_blocks, block_size, lanes]``).  Sequences own
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

The pool is laid out BY LAYER KIND, from the model's own description of
its cache (:class:`LayerCache`, one record a layer; :func:`describe_cache`).
A FULL layer keeps every page of a sequence for the sequence's life: its
pages are the blocks above.  A WINDOW layer sees the last ``window`` keys
only, so its pages are a second GROUP with an allocator of its own
(``pool.window``), far smaller than the full group: a sequence holds at
most ``window_pages_per_seq`` of them and gives pages back, while it
runs, as its window moves past them (:meth:`BlockKVPool.advance_window`).
Each group has its own block table: a released page's entry names the
group's garbage block 0, and the kernels of a window layer start their
walk at the window's first page, so such an entry is never read as a
live page.  One manager owns both groups: ``free_request``,
``check_leaks``, ``reset`` and ``lost`` cover both.
"""
from __future__ import annotations

import functools
import hashlib
import dataclasses
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Sequence, Set, Tuple

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


@dataclasses.dataclass(frozen=True)
class LayerCache:
    """What ONE layer of a served model keeps in the paged pool: the
    model's own description of its cache, from which the engine builds
    the pool (``model.cache_layers()``, :func:`describe_cache`).

    ``window`` ``None`` is a FULL layer, which keeps every page of a
    sequence for its life; a number is a WINDOW layer that sees the last
    ``window`` keys and whose pages come from the window group.
    ``sidecars`` are the ``(shape, dtype)`` a position of what the layer
    keeps beside K and V (the experts its router chose, say); they are
    addressed through the FULL group's table whatever the layer's kind,
    so they outlive a window layer's pages.

    ``value_dim`` makes the record a LATENT one (compressed, MLA): the
    layer keeps ONE array a position and no heads, a key of ``head_dim``
    numbers (``kv_heads`` is 1) whose first ``value_dim`` are the value
    too, and no V beside it.  A latent layer is a full layer (its pages
    are the full group's; prefix reuse, copy-on-write and eviction treat
    them as any page).  Its pool entry is ``[num_blocks, block_size,
    latent_lanes]``: the entry's lanes padded with zeros to whole
    128-lane registers, the only layout the chip's kernels can slice
    (``kernels/latent_attention.py``)."""

    kv_heads: int
    head_dim: int
    dtype: Any
    window: Optional[int] = None
    sidecars: Tuple = ()
    value_dim: Optional[int] = None

    def __post_init__(self):
        if self.value_dim is not None and (
                self.window is not None or self.kv_heads != 1
                or not 0 < self.value_dim <= self.head_dim):
            raise ValueError(
                "a latent record is a full layer of one head-less key "
                f"whose first value_dim lanes are the value, got {self}")

    @property
    def kind(self) -> str:
        if self.value_dim is not None:
            return "latent"
        return "full" if self.window is None else "window"

    @property
    def latent_lanes(self) -> int:
        """Lanes a position of a latent layer takes in the pool."""
        from ..kernels.latent_attention import latent_pool_lanes

        return latent_pool_lanes(self.head_dim)

    def block_bytes(self, block_size: int,
                    kv_cache_dtype: Optional[str] = None) -> int:
        """Bytes this layer adds to ONE block of the full group: its
        entries (K and V with a quantized pool's scale rows; a latent
        record's one array as padded; a window layer's none, its pages
        being the window group's) and its sidecars' rows."""
        side = sum(int(np.prod(shape, dtype=np.int64))
                   * jnp.dtype(dt).itemsize for shape, dt in self.sidecars)
        if self.value_dim is not None:
            entries = block_size * self.latent_lanes \
                * jnp.dtype(self.dtype).itemsize
        elif self.window is not None:
            entries = 0
        else:
            scheme = resolve_kv_cache_dtype(kv_cache_dtype)
            entries = 2 * (
                block_size * self.kv_heads * self.head_dim
                * kv_bytes_per_element(scheme, self.dtype)
                + kv_scale_bytes_per_block(block_size, scheme))
        return int(entries + block_size * side)


def describe_cache(model) -> List[LayerCache]:
    """The model's records, one a layer.  A model that does not describe
    its cache (the tests' small stand-ins) is given full layers of the
    geometry its config states."""
    if hasattr(model, "cache_layers"):
        return list(model.cache_layers())
    from ..models.generation import _cache_dims

    kv_heads, head_dim, dtype = _cache_dims(model)
    return [LayerCache(kv_heads, head_dim, dtype)
            for _ in range(model.config.num_hidden_layers)]


class BlockAllocator:
    """A group's blocks: a LIFO free list over blocks ``1..n-1`` (block 0
    is the group's garbage sink) and, for every block in use, the set of
    request ids that hold it."""

    def __init__(self, num_blocks: int):
        if num_blocks < 2:
            raise ValueError("need >= 2 blocks (block 0 is the reserved "
                             "garbage sink)")
        self.num_blocks = num_blocks
        self._free: List[int] = list(range(num_blocks - 1, 0, -1))
        # block id -> set of owning request ids (refcount = len), for the
        # blocks in use.  A block's set is made once and kept for the
        # group's life (empty while the block is free): a fresh set a
        # block a request is some two thousand containers a long
        # request, each living long enough to reach the collector's
        # oldest generation and to bring its full passes on sooner
        self._owner_sets: List[Set] = [set() for _ in range(num_blocks)]
        self._owners: Dict[int, Set] = {}

    def _own(self, block: int, request_id):
        """``request_id`` the first owner of a block that had none."""
        owners = self._owners[block] = self._owner_sets[block]
        owners.add(request_id)

    @property
    def capacity_blocks(self) -> int:
        """Allocatable blocks (excludes the reserved garbage block)."""
        return self.num_blocks - 1

    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def num_used(self) -> int:
        """Blocks referenced by at least one live request."""
        return self.capacity_blocks - self.num_free

    def can_allocate(self, n: int) -> bool:
        return self.num_free >= n

    def owned_by(self, request_id) -> List[int]:
        return [b for b, o in self._owners.items() if request_id in o]

    def refcount(self, block: int) -> int:
        return len(self._owners.get(block, ()))

    def _take(self) -> int:
        return self._free.pop()

    def _release_block(self, b: int):
        self._owners.pop(b, None)
        self._free.append(b)

    def _exhausted(self, n: int) -> str:
        return (f"need {n} block(s), {self.num_free} free "
                f"(capacity {self.capacity_blocks})")

    def allocate(self, request_id, n: int = 1) -> List[int]:
        """Hand ``n`` private blocks to ``request_id``.  Raises
        :class:`PoolExhausted` (allocating nothing) when the group has
        fewer."""
        if self.num_free < n:
            raise PoolExhausted(self._exhausted(n))
        blocks = []
        for _ in range(n):
            b = self._take()
            self._own(b, request_id)
            blocks.append(b)
        return blocks

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
        """Release every block ``request_id`` references, in REVERSE
        acquisition order.  A request owning nothing is a safe no-op."""
        blocks = self.owned_by(request_id)
        if blocks:
            self.free(list(reversed(blocks)), request_id)

    def check_leaks(self, group: str = ""):
        """Raise if any block is still owned by a request."""
        if self._owners:
            raise AssertionError(
                f"leaked {group}blocks: "
                f"{sorted((b, sorted(map(str, o))) for b, o in self._owners.items())}")

    def reset(self):
        """Every block free again, once no request holds one."""
        self.check_leaks()
        self._free = list(range(self.num_blocks - 1, 0, -1))


class BlockKVPool(BlockAllocator):
    """The manager of the paged pool: the full group's blocks (this
    allocator, with the prefix cache on top), the window group's
    (``window``, where the model has window layers) and the device
    buffers of every layer (``layers``, in the model's layer order)."""

    def __init__(self, num_layers: int, num_blocks: int, block_size: int,
                 kv_heads: int, head_dim: int, dtype=jnp.float32,
                 enable_prefix_cache: bool = True,
                 kv_cache_dtype: Optional[str] = None,
                 sidecars: Sequence[tuple] = (),
                 layer_caches: Optional[Sequence[LayerCache]] = None,
                 window_blocks: int = 0, window_pages_per_seq: int = 0):
        super().__init__(num_blocks)
        if layer_caches is None:
            layer_caches = [LayerCache(kv_heads, head_dim, dtype,
                                       sidecars=tuple(sidecars))
                            for _ in range(num_layers)]
        #: the model's description, a record a layer
        self.layer_caches = list(layer_caches)
        windows = {c.window for c in self.layer_caches} - {None}
        if len(windows) > 1:
            raise ValueError(f"window layers of one size only, got "
                             f"{sorted(windows)}")
        #: the window layers' span in keys, None where every layer is full
        self.window_size: Optional[int] = windows.pop() if windows else None
        #: the window group's allocator (None: every layer is full)
        self.window: Optional[BlockAllocator] = None
        #: the most window pages one sequence ever holds
        self.window_pages_per_seq = window_pages_per_seq
        latent = [c.value_dim is not None for c in self.layer_caches]
        if any(latent) and (kv_cache_dtype is not None
                            or self.window_size is not None
                            or not all(latent)):
            raise ValueError(
                "a pool of latent records has no quantized entries yet, "
                "and latent layers beside K/V or window layers in one "
                "model are later work")
        if self.window_size is not None:
            if enable_prefix_cache or kv_cache_dtype is not None:
                raise ValueError(
                    "a pool with a window group has no prefix cache and "
                    "no quantized entries yet: which layers can reuse a "
                    "block, and a quantized window page, are later work")
            self.window = BlockAllocator(window_blocks)
        self.num_layers = len(self.layer_caches)
        self.block_size = block_size
        self.kv_heads = self.layer_caches[0].kv_heads
        self.head_dim = self.layer_caches[0].head_dim
        dtype = self.layer_caches[0].dtype
        #: quant scheme: None (full precision) / "int8" / "fp8"
        self.kv_cache_dtype = resolve_kv_cache_dtype(kv_cache_dtype)
        #: the MODEL's kv dtype (what dequant produces / fp32 pools hold)
        self.model_dtype = dtype
        #: the STORAGE dtype the pool arrays actually carry
        self.dtype = kv_storage_dtype(self.kv_cache_dtype) or dtype
        # (asked every engine step by the pressure ladder: summed once)
        self._block_bytes = sum(
            c.block_bytes(block_size, self.kv_cache_dtype)
            for c in self.layer_caches)
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
        self.layers: List[Tuple[jax.Array, ...]] = self._fresh_layers()
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
        pool, and the runtime refuses to donate one buffer twice.  A
        window layer's K and V have the window group's blocks; its
        sidecars, like every layer's, the full group's."""

        def entry(c: LayerCache):
            blocks = self.num_blocks if c.window is None \
                else self.window.num_blocks
            rows = (blocks, self.block_size)
            store = kv_storage_dtype(self.kv_cache_dtype) or c.dtype
            if c.value_dim is not None:
                # ONE array a position: the key, whose first lanes are
                # the value
                kv = (jnp.zeros(rows + (c.latent_lanes,), store),)
            else:
                kv = tuple(jnp.zeros(rows + (c.kv_heads, c.head_dim), store)
                           for _ in range(2))
            if self.kv_cache_dtype is not None:
                kv += tuple(jnp.ones(rows, jnp.float32) for _ in range(2))
            return kv + tuple(
                jnp.zeros((self.num_blocks, self.block_size
                           * int(np.prod(shape, dtype=np.int64))), dt)
                for shape, dt in c.sidecars)

        return [entry(c) for c in self.layer_caches]

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
        super().reset()
        if self.window is not None:
            self.window.reset()
        self.layers = self._fresh_layers()
        self._hash_index.clear()
        self._block_hash.clear()
        self._cached_free.clear()
        self._roots.clear()

    # ------------------------------------------------------- accounting
    @property
    def num_free(self) -> int:
        """Blocks allocatable RIGHT NOW: truly free plus cached-but-
        unreferenced (the latter evict on demand)."""
        return len(self._free) + len(self._cached_free)

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
        return num_layers * LayerCache(kv_heads, head_dim, dtype).block_bytes(
            block_size, kv_cache_dtype)

    def block_bytes(self) -> int:
        """HBM bytes one block of the full group costs in THIS pool:
        what its leaves take, every layer's record asked
        (:meth:`LayerCache.block_bytes`)."""
        return self._block_bytes

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
        capacity = self.capacity_bytes()
        # (a model whose every layer is a window layer keeps no K/V in
        # the full group: its blocks still count)
        return self.used_bytes() / capacity if capacity \
            else self.utilization()

    def blocks_for(self, num_tokens: int) -> int:
        """Blocks needed to hold ``num_tokens`` cache positions."""
        return -(-int(num_tokens) // self.block_size)

    def is_shared(self, block: int) -> bool:
        """True when a write into ``block`` would be observable outside
        the writing request: another owner holds it, or the prefix index
        still advertises its content to future requests."""
        return len(self._owners.get(block, ())) > 1 \
            or block in self._block_hash

    # ------------------------------------------------------- allocation
    # (``allocate`` is the group allocator's: a block comes off the free
    # list, or, that dry, the least-recently-parked cached block is
    # evicted and recycled)
    def _take(self) -> int:
        return self._free.pop() if self._free else self._evict_lru()

    def _exhausted(self, n: int) -> str:
        return (f"need {n} block(s), {len(self._free)} free + "
                f"{len(self._cached_free)} evictable "
                f"(capacity {self.capacity_blocks})")

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
        if self.enable_prefix_cache and b in self._block_hash:
            self._owners.pop(b, None)
            self._cached_free[b] = None     # LRU tail = most recent
        else:
            super()._release_block(b)

    def free_request(self, request_id):
        """Release every block ``request_id`` references, in BOTH
        groups.  A request owning nothing (never prefilled, or already
        released) is a safe no-op — retire paths call this
        unconditionally.

        Blocks release in REVERSE acquisition order, so a prompt
        chain's tail blocks park in the LRU before its head: under
        pressure eviction then consumes leaves first, and the head —
        which ANY extension of the prefix can reuse, where a tail only
        serves exact matches — survives longest (the radix-tree
        leaf-first eviction order of the prefix-caching literature)."""
        super().free_request(request_id)
        if self.window is not None:
            self.window.free_request(request_id)

    def check_leaks(self, group: str = ""):
        """Raise if any block of either group is still owned by a
        request — used by tests and engine shutdown to prove references
        round-trip.  Cached-but-unreferenced blocks are NOT leaks (they
        are reclaimable on demand)."""
        super().check_leaks(group)
        if self.window is not None:
            self.window.check_leaks("window-group ")

    # ----------------------------------------------------- window group
    def window_first_page(self, pos: int) -> int:
        """The first page a window layer still needs once the earliest
        query to come sits at position ``pos``: the page of the key at
        ``pos - window + 1``.  Every page before it lies wholly behind
        every future query's window."""
        return max(0, int(pos) - self.window_size + 1) // self.block_size

    def advance_window(self, request_id, pages: Dict[int, int], row,
                       first_query: int, end: int) -> int:
        """Move one sequence's window pages to where its next queries
        need them: pages wholly behind the window of the query at
        ``first_query`` go back to the window group's free list (their
        entries of ``row``, the sequence's row of the window group's
        table, then name the garbage block), and a page is taken for
        every one up to the position before ``end`` that is not held
        yet.  ``pages`` maps page index -> block.  Returns how many
        pages were released; raises :class:`PoolExhausted`, having
        released but taken nothing, when the group has too few."""
        # (pages are taken in rising order and given back from the low
        # end, so the dict's first key is its lowest and its last its
        # highest: each call costs what it moves, not what is held)
        first = self.window_first_page(first_query)
        gone = []
        while pages and next(iter(pages)) < first:
            gone.append(next(iter(pages)))
            self.window.free([pages.pop(gone[-1])], request_id)
        if gone:
            row[gone] = 0
        start = max(first, next(reversed(pages)) + 1) if pages else first
        want = range(start, self.blocks_for(end))
        for p, b in zip(want, self.window.allocate(request_id, len(want))):
            pages[p] = row[p] = b
        return len(gone)

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
                self._own(b, request_id)
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
        if self.window is not None:
            raise RuntimeError("copy-on-write in a pool with a window "
                               "group: nothing shares a block there")
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
        feasible = need <= self.num_free - from_lru
        if self.window is not None:
            # by group: the window group must hold what one sequence holds
            # of it at most
            feasible = feasible and self.window.can_allocate(min(
                self.blocks_for(len(tokens) + extra_tokens),
                self.window_pages_per_seq))
        return matched, need, feasible

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
            **({} if self.window is None else {
                "window_capacity_blocks": self.window.capacity_blocks,
                "window_used_blocks": self.window.num_used,
                "window_pages_per_seq": self.window_pages_per_seq}),
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
    # scalars.  Entries are (k, v), (k, v, k_scale, v_scale) or a latent
    # layer's one array, then the sidecars — a CoW copy of a block must
    # move the scale rows and the sidecars' rows with it
    return [tuple(a.at[dst].set(a[src]) for a in entry)
            for entry in layers]
