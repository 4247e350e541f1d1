"""Paged KV cache: pooled block arena + host-side block allocator.

The vLLM paging model mapped onto the repo's sharded-state conventions:

- **Device side** — one pooled arena per K and per V, shape
  ``[n_layers, n_blocks, block_size, kv_heads, head_dim]`` (each layer's
  slice is the ``[n_blocks, block, heads, head_dim]`` arena of the
  design), held as a *global array* sharded over the tensor-parallel
  axis on the heads dim — the same chop as the tensor-parallel
  attention heads, so every tp rank owns the cache rows of exactly the
  heads it computes.  The arena is **donated** through the decode step
  (``jax.jit(..., donate_argnums=...)``) so XLA updates it in place: a
  non-donated cache would double the single largest HBM tenant of a
  serving chip (analyzer entry ``serving_decode``, rule APX204, audits
  exactly this).  With an **int8** cache (``cache_dtype=jnp.int8``) a
  pair of fp32 *scale arenas* ``[n_layers, n_blocks, block_size,
  kv_heads]`` rides along — one symmetric scale per cached K/V vector,
  stored block-major beside its block (1/``head_dim`` of the cache's
  own footprint) and dequantized inside the paged-attention kernel.
- **Host side** — :class:`BlockAllocator`: a free list of physical
  block ids with **refcounted** ownership.  Allocation is O(1) per
  block and *fragmentation-free by construction*: blocks are fixed-size
  and any free block can serve any request, so the only admission
  question is ``n_free >= blocks_needed`` — never "is there a
  contiguous run".  Copy-on-write prefix sharing rides on the
  refcounts: :meth:`BlockAllocator.share` adds a holder to a live
  block, and :meth:`BlockAllocator.free` *decrements* — the block
  returns to the pool only when its last holder lets go.  (Writes never
  target shared blocks in this engine: prefix hits are block-aligned
  and always leave >= 1 prompt token to recompute, so the private tail
  a request appends into starts past every shared block — the copy
  step of classic CoW is unreachable by construction, and the
  refcounts ARE the invariant.)  Invariants (every block is free XOR
  held by >= 1 owner; double-free and foreign-free raise) are checked
  by :meth:`BlockAllocator.check` and pinned in ``tests/test_serving.py``.
- :class:`PrefixCache` — the token-hash index over shared blocks.  A
  full block of a request's sequence is keyed by the *chain hash* of
  every token up to and including that block, so a lookup walks
  block-sized strides of a new prompt and shares the longest cached
  prefix (capped so at least one token is always recomputed — the
  recompute produces the first sampled token, and it keeps writes off
  shared blocks).  Entries hold their own refcount on the block; a
  finished request's blocks therefore survive it *as cache*, and the
  eviction sweep (:meth:`PrefixCache.evict_one`, LRU) is what finally
  returns them to the free list when the pool runs dry.

**Cache groups** (ISSUE 27): a model whose layers are not all alike keeps
more than one kind of state.  ``KVCacheConfig.groups`` lists them, one
:class:`CacheGroup` per kind of attention layer: the layers it serves, its
K/V head count, a K row width beside a V row width, and an optional
``window``.  Each group has its own pool of blocks, its own
:class:`BlockAllocator` and its own block table per request, all indexed by
the same logical block (``position // block_size``).  A window group keeps
only the blocks a later query can still read: the scheduler hands back the
blocks that lie wholly behind the window (their table entries go stale and
the kernels never reach them).  A group's arenas are one ``(k, v)`` pair
**per layer**, ``[n_blocks, block_size, kv_heads * k_dim]`` beside
``[..., kv_heads * v_dim]``: rows dense on the lanes whatever the head
width, so the kernels read them as they lie and no layer slices or
converts an arena it shares with another (:func:`init_group_arenas`).  A
latent group (ISSUE 33: multi-head latent attention) keeps one arena per
layer, ``[n_blocks, block_size, latent_rank + rotary_dim]`` in whole lane
tiles (:attr:`CacheGroup.row_lanes`): the row every head scores against,
whose leading channels are also every head's values.  A latent group whose
layers attend sparsely (ISSUE 37: learned sparse attention) keeps a second
arena per layer, ``[n_blocks, block_size, index_dim]`` of index keys
(:attr:`CacheGroup.index_dim`), under the same block table and allocator:
one allocation covers both, so the two can never fall out of step.
A configuration without ``groups`` is the one pooled arena described above,
unchanged.

The per-request *block table* (logical block index -> physical block
id) lives with the scheduler's request records; the engine packs the
tables of the active slots into one ``[max_batch, max_blocks]`` int32
device argument each step — churn changes the table *values*, never
any shape, which is what keeps the decode step compile-stable.
"""

from __future__ import annotations

import collections
import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Set, Tuple

import numpy as np

__all__ = [
    "KVCacheConfig",
    "CacheGroup",
    "FREED",
    "BlockAllocator",
    "OutOfBlocksError",
    "PrefixCache",
    "CACHE_OWNER",
    "EXPORT_OWNER",
    "KVExport",
    "ExportLedger",
    "init_kv_arena",
    "init_group_arenas",
    "arena_partition_spec",
    "scale_partition_spec",
]

# the PrefixCache's own hold on a shared block (distinct from any
# request id, so foreign-free checks see the cache as just another
# owner — freeing a cached block with a request's id raises)
CACHE_OWNER = "<prefix-cache>"

# prefix of the composite owner a mid-migration export pin holds blocks
# under: ``(EXPORT_OWNER, rid)`` — distinct from both the request id and
# CACHE_OWNER, so the source request can finish (its own refs free) while
# the exported run stays pinned until the decode side acks receipt
EXPORT_OWNER = "<kv-export>"


# a block-table entry whose block was handed back behind the window
FREED = -1


@dataclasses.dataclass(frozen=True)
class CacheGroup:
    """One kind of cached state: the model ``layers`` it serves, ``kv_heads``
    rows of ``k_dim`` (keys) beside ``v_dim`` (values) per token and layer,
    its pool of ``n_blocks`` blocks, and ``window``: ``None`` keeps every
    token, ``w`` only what a query at position ``i`` reads,
    ``i - w < j <= i``.  A ``latent`` group keeps one row of ``k_dim``
    channels per token and layer and nothing beside it: every head scores
    against the whole row and takes its values from the row's leading
    ``v_dim`` channels, so there is no value arena.  ``index_dim`` (a
    latent group of sparse layers) is the index key a token and layer
    keeps beside its row, in an arena of its own under the same table."""

    layers: Tuple[int, ...]
    kv_heads: int
    k_dim: int
    v_dim: int
    n_blocks: int
    window: Optional[int] = None
    latent: bool = False
    index_dim: int = 0

    @property
    def row_lanes(self) -> int:
        """Lanes of a latent group's arena row: ``k_dim`` rounded up to
        whole 128-lane tiles.  HBM's tiling pads an array's minor dimension
        to that whatever shape is declared (576 channels lie in 640 lanes
        either way); declared, a kernel can copy a page as it lies.  The
        lanes past ``k_dim`` hold zeros."""
        return -(-self.k_dim // 128) * 128

    def first_needed_block(self, query_pos: int, block_size: int) -> int:
        """The first logical block a query at ``query_pos`` (and so any
        later one) can read."""
        if self.window is None:
            return 0
        return max(query_pos - self.window + 1, 0) // block_size

    def blocks_spanned(self, chunk_tokens: int, block_size: int,
                       most: int) -> int:
        """The most blocks one request holds here while a chunk of
        ``chunk_tokens`` lands: ``most`` (its whole table) without a
        window, else what the window and the chunk span wherever they lie
        against the block edges."""
        if self.window is None:
            return most
        return min(most,
                   (self.window + chunk_tokens - 2) // block_size + 2)


class OutOfBlocksError(RuntimeError):
    """The arena cannot serve the requested number of blocks.

    Admission control is expected to check :meth:`BlockAllocator.can_alloc`
    first; hitting this during a decode append means the scheduler's
    grow path (evict, then preempt) failed to raise ``n_free`` — a bug,
    since the submit-time whole-pool check guarantees any single
    request fits."""


@dataclasses.dataclass(frozen=True)
class KVCacheConfig:
    """Static shape of the paged cache.

    ``kv_heads`` is the *global* K/V head count (``config.query_groups``
    of the served model); under tensor parallelism each rank holds
    ``kv_heads / tp`` of them.  ``max_seq`` rounds up to whole blocks;
    ``max_blocks_per_request`` is the per-request block-table width.
    ``dtype`` is the arena storage dtype; ``int8`` additionally
    allocates the per-vector scale arenas (:attr:`quantized`).
    """

    n_layers: int
    n_blocks: int
    block_size: int
    kv_heads: int
    head_dim: int
    max_seq: int
    dtype: Any = np.float32
    # more than one kind of state: see :class:`CacheGroup`.  When given,
    # ``n_layers``/``n_blocks``/``kv_heads``/``head_dim`` describe group 0.
    groups: Tuple[CacheGroup, ...] = ()

    def __post_init__(self):
        if self.block_size < 1 or self.n_blocks < 1:
            raise ValueError(
                f"block_size ({self.block_size}) and n_blocks "
                f"({self.n_blocks}) must be positive")
        if self.max_seq < 1:
            raise ValueError(f"max_seq must be positive, got {self.max_seq}")

    @property
    def quantized(self) -> bool:
        """True when the arena stores int8 (scale arenas ride along)."""
        return np.dtype(self.dtype) == np.dtype(np.int8)

    @property
    def max_blocks_per_request(self) -> int:
        return -(-self.max_seq // self.block_size)

    def blocks_for(self, n_tokens: int) -> int:
        """Number of blocks a sequence of ``n_tokens`` occupies."""
        return -(-n_tokens // self.block_size)

    @property
    def cache_groups(self) -> Tuple[CacheGroup, ...]:
        """The groups, a model of identical layers being the case of one."""
        if self.groups:
            return self.groups
        return (CacheGroup(layers=tuple(range(self.n_layers)),
                           kv_heads=self.kv_heads, k_dim=self.head_dim,
                           v_dim=self.head_dim, n_blocks=self.n_blocks),)


def arena_partition_spec(tp_axis: Optional[str]):
    """PartitionSpec of one arena: heads (dim 3) sharded over ``tp``."""
    from jax.sharding import PartitionSpec as P

    return P(None, None, None, tp_axis, None)


def scale_partition_spec(tp_axis: Optional[str]):
    """PartitionSpec of one int8 scale arena
    ``[n_layers, n_blocks, block_size, kv_heads]`` — the same heads
    chop as the arena it scales (a rank dequantizes only rows it owns)."""
    from jax.sharding import PartitionSpec as P

    if tp_axis is None:
        return P()
    return P(None, None, None, tp_axis)


def init_kv_arena(cfg: KVCacheConfig, mesh=None, tp_axis: Optional[str] = "tp"
                  ) -> Tuple[Any, ...]:
    """Allocate the zeroed arenas as sharded global arrays.

    Returns ``(k, v)`` — or ``(k, v, k_scales, v_scales)`` for an int8
    cache — with shape ``[n_layers, n_blocks, block_size, kv_heads,
    head_dim]`` (scales drop the trailing ``head_dim``), heads sharded
    over ``tp_axis`` when a mesh is given (the same axis the attention
    heads are column-parallel over, so the cache rows a rank reads in
    the paged kernel are exactly the rows it owns).
    """
    import jax
    import jax.numpy as jnp

    shape = (cfg.n_layers, cfg.n_blocks, cfg.block_size, cfg.kv_heads,
             cfg.head_dim)
    arenas = [jnp.zeros(shape, cfg.dtype), jnp.zeros(shape, cfg.dtype)]
    specs = [arena_partition_spec(tp_axis)] * 2
    if cfg.quantized:
        sshape = shape[:-1]
        # on a size-1 tp axis the scale placement is spelled replicated
        # (P() — what jit emits for the step outputs there, so the
        # engine's arena round trip stays jit-cache-stable; at tp > 1
        # the named spec round-trips intact either way)
        s_axis = tp_axis
        if mesh is not None and s_axis is not None \
                and mesh.shape[s_axis] == 1:
            s_axis = None
        arenas += [jnp.ones(sshape, jnp.float32),
                   jnp.ones(sshape, jnp.float32)]
        specs += [scale_partition_spec(s_axis)] * 2
    if mesh is not None and tp_axis is not None:
        from jax.sharding import NamedSharding

        if cfg.kv_heads % mesh.shape[tp_axis]:
            raise ValueError(
                f"kv_heads ({cfg.kv_heads}) not divisible by tp "
                f"({mesh.shape[tp_axis]})")
        arenas = [jax.device_put(a, NamedSharding(mesh, s))
                  for a, s in zip(arenas, specs)]
    return tuple(arenas)


def init_group_arenas(cfg: KVCacheConfig) -> Tuple[Any, ...]:
    """The zeroed arenas of a configuration with ``groups``: for each group
    a tuple, over its layers, of ``(k [n_blocks, block_size, kv_heads *
    k_dim], v [n_blocks, block_size, kv_heads * v_dim])``, or of ``(rows
    [n_blocks, block_size, row_lanes],)`` alone for a latent group, with
    ``index_keys [n_blocks, block_size, index_dim]`` after it where the
    group keeps index keys.  One tuple per layer, each an array of its own:
    a layer's kernel call takes its arena whole and its appended rows land
    in place in the donated buffer."""
    import jax.numpy as jnp

    if cfg.quantized:
        raise NotImplementedError("cache groups hold no int8 arenas yet")

    def layer_arenas(g):
        widths = (g.row_lanes,) if g.latent else (g.k_dim, g.v_dim)
        if g.index_dim:
            widths += (g.index_dim,)
        return tuple(jnp.zeros((g.n_blocks, cfg.block_size, g.kv_heads * w),
                               cfg.dtype) for w in widths)

    return tuple(tuple(layer_arenas(g) for _ in g.layers)
                 for g in cfg.groups)


class BlockAllocator:
    """Refcounted free-list allocator over the physical block pool.

    LIFO free list (recently-freed blocks are reused first — their HBM
    pages are the warmest) plus a per-block holder set: a block is free
    XOR held by one or more owners (a request id, or the prefix cache's
    :data:`CACHE_OWNER`).  :meth:`share` is the copy-on-write incref —
    a prefix hit adds the hitting request as a holder; :meth:`free` is
    the decref — the block returns to the pool only when the last
    holder releases it.  NOT thread-safe: the scheduler owns it from
    one thread, matching the engine's single-threaded step loop.
    """

    def __init__(self, n_blocks: int):
        if n_blocks < 1:
            raise ValueError(f"n_blocks must be positive, got {n_blocks}")
        self.n_blocks = n_blocks
        self._free: List[int] = list(range(n_blocks - 1, -1, -1))
        self._holders: Dict[int, Set[Any]] = {}

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_owned(self) -> int:
        """Blocks with at least one holder (shared blocks count once)."""
        return len(self._holders)

    def refcount(self, block: int) -> int:
        """Holder count of ``block`` (0 = free)."""
        return len(self._holders.get(block, ()))

    def can_alloc(self, n: int) -> bool:
        return n <= len(self._free)

    def alloc(self, n: int, owner: Any = None) -> List[int]:
        """Take ``n`` fresh (refcount-1) blocks for ``owner``; raises
        :class:`OutOfBlocksError` (allocating nothing) when fewer than
        ``n`` are free."""
        if n < 0:
            raise ValueError(f"cannot allocate {n} blocks")
        if n > len(self._free):
            raise OutOfBlocksError(
                f"requested {n} blocks, only {len(self._free)} of "
                f"{self.n_blocks} free")
        blocks = [self._free.pop() for _ in range(n)]
        for b in blocks:
            self._holders[b] = {owner}
        return blocks

    def share(self, block: int, owner: Any) -> None:
        """Copy-on-write incref: add ``owner`` as a holder of a live
        block (a prefix-cache hit, or the cache registering a freshly
        prefilled block).  Sharing a free block or double-sharing by
        the same owner raises — both would corrupt the refcount."""
        holders = self._holders.get(block)
        if holders is None:
            raise ValueError(f"cannot share free block {block}")
        if owner in holders:
            raise ValueError(
                f"owner {owner!r} already holds block {block}")
        holders.add(owner)

    def free(self, blocks: Sequence[int], owner: Any = None) -> None:
        """Release ``owner``'s hold on each block.  A shared block
        merely *decrements* (the other holders — the prefix cache, a
        sibling request — keep it live); the last release returns it to
        the pool.  A block that is already free (double free) or not
        held by ``owner`` (foreign free) raises — silently recycling a
        live request's cache rows is the worst failure mode a paged
        cache has."""
        for b in blocks:
            holders = self._holders.get(b)
            if holders is None:
                raise ValueError(f"double free of block {b}")
            if owner not in holders:
                raise ValueError(
                    f"block {b} owned by {sorted(map(repr, holders))}, "
                    f"freed by {owner!r}")
        for b in blocks:
            holders = self._holders[b]
            holders.discard(owner)
            if not holders:
                del self._holders[b]
                self._free.append(b)

    def check(self) -> None:
        """Assert the pool invariant: free and held partition the pool
        (no leak, no double ownership, no phantom ids, no empty holder
        sets)."""
        free = set(self._free)
        held = set(self._holders)
        if len(free) != len(self._free):
            raise AssertionError("duplicate ids on the free list")
        if free & held:
            raise AssertionError(
                f"blocks both free and held: {sorted(free & held)}")
        if free | held != set(range(self.n_blocks)):
            raise AssertionError(
                f"pool leak: {self.n_blocks - len(free) - len(held)} "
                "blocks neither free nor held")
        empties = [b for b, h in self._holders.items() if not h]
        if empties:
            raise AssertionError(f"held blocks with no holders: {empties}")


@dataclasses.dataclass
class KVExport:
    """One migrating block run, pinned on the source until acked.

    ``blocks`` is the prefix-order physical run covering ``cache_len``
    tokens of ``tokens`` (the request's wire sequence at export time —
    kept so an acked run can be indexed into the prefix cache under its
    chain hash).  The pin holds every block under the composite owner
    ``(EXPORT_OWNER, rid)``; the exporting request's own refs free
    normally when it leaves the scheduler."""

    rid: Any
    blocks: List[int]
    tokens: List[int]
    cache_len: int

    @property
    def owner(self) -> Tuple[str, Any]:
        return (EXPORT_OWNER, self.rid)


class ExportLedger:
    """Pin-until-ack bookkeeping for KV-block migration (ISSUE 16).

    The refcount story of a migration, on the source replica:

    1. :meth:`pin` — every block of the run gains the export owner
       (refcount +1).  The exporting request then leaves the scheduler
       and its own refs free normally; the run survives at refcount 1.
    2. The blocks stream over the wire.  Nothing here can recycle them:
       the pin is a first-class holder, so ``BlockAllocator.check()``
       stays free-XOR-held at every step.
    3. :meth:`release` on the decode side's ack — the run's *full*
       blocks are indexed into the prefix cache (the cache increfs
       before the pin decrefs, so no block ever transits through free),
       turning the shipped prefill into evictable local capacity; the
       partial tail block and, on a failed migration, every block just
       free back to the pool.

    A source that dies mid-migration leaks nothing *by construction*:
    the ledger and pool die with the process, and the decode side either
    committed (it owns its own imported copies) or degrades to
    re-prefill through the router's replay path.  ``release`` is
    idempotent — a duplicate or stale ack (router retry after a
    reconnect) is a no-op, never a double free."""

    def __init__(self, allocator: BlockAllocator,
                 prefix_cache: Optional["PrefixCache"] = None):
        self.allocator = allocator
        self.prefix_cache = prefix_cache
        self._pins: Dict[Any, KVExport] = {}

    def __len__(self) -> int:
        return len(self._pins)

    def pin(self, rid: Any, blocks: Sequence[int],
            tokens: Sequence[int], cache_len: int) -> KVExport:
        """Pin ``blocks`` (the run covering ``cache_len`` tokens) under
        the export owner.  One outstanding export per request id."""
        if rid in self._pins:
            raise ValueError(f"request {rid!r} already has an export "
                             "in flight")
        exp = KVExport(rid=rid, blocks=list(blocks),
                       tokens=[int(t) for t in tokens],
                       cache_len=int(cache_len))
        pinned = []
        try:
            for b in exp.blocks:
                self.allocator.share(b, exp.owner)
                pinned.append(b)
        except ValueError:
            # roll the partial pin back before re-raising: the ledger
            # never holds a half-pinned run
            for b in pinned:
                self.allocator.free([b], owner=exp.owner)
            raise
        self._pins[rid] = exp
        return exp

    def release(self, rid: Any, *, to_cache: bool = True) -> int:
        """Drop the pin on ``rid``'s run.  ``to_cache=True`` (the ack
        path) first indexes the run's full blocks into the prefix
        cache, so the shipped prefill stays hittable locally; the
        failed-migration path (``to_cache=False``) and the partial tail
        block free straight back to the pool.  Returns the number of
        blocks that went into the cache; unknown/duplicate ids are a
        no-op (0)."""
        exp = self._pins.pop(rid, None)
        if exp is None:
            return 0
        cached = 0
        if to_cache and self.prefix_cache is not None:
            before = self.prefix_cache.n_blocks
            self.prefix_cache.insert(exp.tokens, exp.blocks, exp.cache_len)
            cached = self.prefix_cache.n_blocks - before
        self.allocator.free(exp.blocks, owner=exp.owner)
        return cached

    def release_all(self, *, to_cache: bool = False) -> None:
        """Drop every outstanding pin (drain/shutdown path)."""
        for rid in list(self._pins):
            self.release(rid, to_cache=to_cache)

    def check(self) -> None:
        """Every pinned block must be live and held by its export
        owner (the ledger's half of the free-XOR-held invariant)."""
        for exp in self._pins.values():
            for b in exp.blocks:
                holders = self.allocator._holders.get(b)
                if not holders or exp.owner not in holders:
                    raise AssertionError(
                        f"export pin of {exp.rid!r} lost block {b}")


class PrefixCache:
    """Token-hash index of shareable full blocks (copy-on-write prefix
    caching).

    Each entry maps the *chain hash* of a sequence's first
    ``(i + 1) * block_size`` tokens to the physical block holding
    tokens ``[i * block_size, (i + 1) * block_size)`` of that sequence.
    The chain construction means a lookup needs no trie: walk the new
    prompt block by block, rehashing cumulatively, and stop at the
    first miss — every hit is automatically content- AND
    position-consistent with the whole prefix before it.

    The cache holds its own refcount (:data:`CACHE_OWNER`) on every
    indexed block, which is what lets blocks outlive the request that
    wrote them.  ``evict_one`` frees the least-recently-used entry
    whose block the cache is the *sole* holder of — evicting a block a
    live request still shares would free no capacity and lose a hot
    prefix, so such entries are skipped (they re-enter the evictable
    set when their last sharer finishes).
    """

    def __init__(self, allocator: BlockAllocator, block_size: int):
        self.allocator = allocator
        self.block_size = block_size
        # insertion/touch order == LRU order (move_to_end on every hit)
        self._entries: "collections.OrderedDict[int, int]" = \
            collections.OrderedDict()
        self.hits = 0            # blocks served from cache (lifetime)
        self.evictions = 0       # entries evicted for capacity (lifetime)

    def __len__(self) -> int:
        return len(self._entries)

    @property
    def n_blocks(self) -> int:
        return len(self._entries)

    def _block_hash(self, prev_hash: int, tokens: Sequence[int],
                    i: int) -> int:
        """Chain hash of full block ``i`` given the previous block's."""
        chunk = tuple(int(t) for t in
                      tokens[i * self.block_size:(i + 1) * self.block_size])
        return hash((prev_hash, chunk))

    def lookup(self, tokens: Sequence[int], owner: Any,
               *, max_blocks: Optional[int] = None) -> List[int]:
        """Share the longest cached prefix of ``tokens`` with ``owner``.

        Walks full blocks, hashing incrementally and stopping at the
        first miss (O(hit) host work, never O(prompt)).  The cap is
        ENFORCED here, not trusted to callers: at most
        ``(len(tokens) - 1) // block_size`` blocks are ever shared, so
        at least one token is always left to recompute — the recompute
        yields the request's next sampled token, and it keeps every
        write on private blocks (the invariant the whole CoW design
        rests on; a block-aligned prompt fully served from cache would
        otherwise append into a shared block).  ``max_blocks`` can only
        tighten it.  Returns the shared physical blocks in prefix
        order; the caller owns a refcount on each (released through
        the ordinary ``free``)."""
        shared: List[int] = []
        cap = (len(tokens) - 1) // self.block_size
        if max_blocks is not None:
            cap = min(cap, max_blocks)
        h = 0
        for i in range(cap):
            h = self._block_hash(h, tokens, i)
            block = self._entries.get(h)
            if block is None:
                break
            self.allocator.share(block, owner)
            self._entries.move_to_end(h)
            shared.append(block)
        self.hits += len(shared)
        return shared

    def insert(self, tokens: Sequence[int], blocks: Sequence[int],
               upto_tokens: int, *, start_block: int = 0,
               prev_hash: int = 0) -> int:
        """Index the full blocks of ``tokens[:upto_tokens]`` (the part
        whose K/V is already *written* to the arena — indexing a block
        whose content has not landed would let a same-tick hit read
        garbage).  Already-indexed keys are skipped: the first physical
        copy of a prefix wins and duplicates free normally with their
        writer.

        ``start_block``/``prev_hash`` resume the chain where a previous
        call stopped (the scheduler threads them through the request,
        so a prompt advanced chunk by chunk hashes each block ONCE per
        admission instead of re-hashing the whole prefix per chunk).
        Returns the chain hash after the last indexed block — the next
        call's ``prev_hash``."""
        n_full = min(upto_tokens // self.block_size, len(blocks))
        h = prev_hash
        for i in range(start_block, n_full):
            h = self._block_hash(h, tokens, i)
            if h in self._entries:
                continue
            self.allocator.share(blocks[i], CACHE_OWNER)
            self._entries[h] = blocks[i]
        return h

    def evictable(self) -> int:
        """Blocks an eviction sweep could return to the pool right now
        (cache is the sole holder)."""
        return sum(1 for b in self._entries.values()
                   if self.allocator.refcount(b) == 1)

    def evict_many(self, n: int) -> int:
        """Free up to ``n`` LRU sole-holder entries in ONE sweep;
        returns how many blocks went back to the pool.  Entries still
        shared with a live request are skipped (evicting them would
        free no capacity and lose a hot prefix) — and skipped once,
        not once per needed block: the scheduler asks for its whole
        deficit at a time, so pool pressure costs one pass over the
        pinned prefix, not ``n``."""
        freed = 0
        for key in list(self._entries):
            if freed >= n:
                break
            block = self._entries[key]
            if self.allocator.refcount(block) == 1:
                del self._entries[key]
                self.allocator.free([block], owner=CACHE_OWNER)
                self.evictions += 1
                freed += 1
        return freed

    def evict_one(self) -> Optional[int]:
        """Free the LRU sole-holder entry; returns its block id, or
        ``None`` when nothing is evictable (every cached block is
        shared with a live request, or the cache is empty)."""
        for key, block in self._entries.items():
            if self.allocator.refcount(block) == 1:
                del self._entries[key]
                self.allocator.free([block], owner=CACHE_OWNER)
                self.evictions += 1
                return block
        return None

    def check(self) -> None:
        """Every indexed block must be live and held by the cache."""
        for key, block in self._entries.items():
            if self.allocator.refcount(block) < 1:
                raise AssertionError(
                    f"cache entry {key} indexes free block {block}")
