"""Paged quantized KV cache: block-pool storage + per-slot block tables.

The dense cache (kvcache.py) allocates one ``(n_slots, max_seq, H, Dstore)``
slab per precision format — memory scales with ``n_slots × max_seq`` even
when most slots hold short sequences, which caps concurrency long before
the accelerator runs out of compute (the paper's "heavy traffic" regime,
and the motivation behind vLLM/KVmix-style paging).  This module stores KV
in fixed-size *blocks* instead:

Layout
------
* **Block pool**: ``k/v`` are ``([L,] n_blocks, block_size, KVL)`` —
  each token's heads side by side on the lane axis, head ``h`` on lanes
  ``[h*Dstore, (h+1)*Dstore)``, ``KVL = H * Dstore`` rounded up to a
  multiple of 128 — and the per-(token, head) scales are ``([L,]
  n_blocks, 1, SL)`` f32, token ``t`` of head ``h`` on lane
  ``h*block_size + t``, ``SL = H * block_size`` rounded up to 128.
  Values are quantized exactly as in the dense cache (kv4
  nibble-packed 2-per-int8, ``Dstore = head_dim // 2``), so every
  ``FormatSpec`` works unchanged; only where the bytes live differs.
  Pad lanes are never written or read.
* **One layout for the whole step**: a TPU tiles an array's two minor
  dims in (8, 128) words, and both Pallas kernels that touch the pool —
  the attention reader (``kernels/paged_kvattn.py``) and the in-place
  writer (``kernels/pool_write.py``) — need those tiles row-major.
  Rounding the lane dim up to a multiple of 128 makes row-major the
  layout the TPU compiler itself picks for the array, so the pool is
  created, passed into and out of every jit, and read and written by
  the kernels in that one layout, and nothing relayouts or copies it.
  The shape follows from ``(H, Dstore, block_size)`` alone.  With any
  other lane width (kv8 at H = 5, Dstore = 64: 320) the compiler stores
  the pool block-minor, which suits an XLA scatter but not the kernels,
  and every layer then paid pool-sized relayout copies; pinning the
  row-major layout with ``jax.experimental.layout`` instead does not
  survive JAX's persistent compilation cache (an executable loaded from
  it expects the compiler's layout).  The engine's pool is stacked over
  layers, ``(L, ...)``, and the layer scan carries it whole instead of
  slicing it: the cache's scalar ``layer`` names the layer that
  :func:`append_paged`, the attention kernel and :func:`gather_view`
  address (0 for a per-layer pool, whose leaves lack the ``L`` axis).
* **Block table**: ``(n_slots, blocks_per_slot)`` int32, shared by every
  layer.  Entry ``j`` of
  slot ``b``'s row names the pool block holding logical positions
  ``[j*block_size, (j+1)*block_size)`` of that slot.  Unmapped entries hold
  the sentinel ``n_blocks`` (one past the pool): writes through a
  sentinel are dropped, reads clamp to an arbitrary (finite) pool
  element — safe because every position at or beyond a slot's write
  frontier is masked by the causal ``kpos <= pos`` attention mask.
* **Allocator**: `BlockAllocator` is plain host-side Python (the engine
  mutates block tables between jit'd steps, exactly like vLLM's scheduler
  sits outside the CUDA graphs).  Blocks are *refcounted*: the same
  physical block may be mapped into several slots' tables (block-granular
  prefix sharing), and it returns to the pool only when its last holder
  releases it.  Refcount-0 blocks published in a :class:`PrefixIndex`
  are retained in an LRU "cached" state and revived on a prefix hit or
  evicted when the free list runs dry (DESIGN.md §5.2).

The whole cache is a registered-dataclass pytree; its properties
(block_size, n_blocks, ...) are derived from leaf shapes and hold for
per-layer and stacked instances alike.

Equivalence contract (locked down by tests/test_paged_kvcache.py):
``gather_view(append_paged(...))`` returns a dense ``KVCache`` view whose
entries at every written position are *bit-identical* to what the dense
``kvcache.append_per_slot`` path stores — paging is a pure layout change.
"""
from __future__ import annotations

import dataclasses
import hashlib
from collections import OrderedDict
from typing import Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import kvcache as KV
from . import quantize as Q
from .precision import FormatSpec


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class PagedKVCache:
    """Block-pooled quantized KV storage plus per-slot block tables.

    A registered-dataclass pytree whose pool leaves are per-layer or
    stacked over ``L`` layers (the engine's); ``layer`` names the layer a
    stacked pool's readers and writer address.  See the module docstring
    for the layout contract.
    """

    k: jax.Array            # ([L,] n_blocks, block_size, KVL)
    v: jax.Array            # ([L,] n_blocks, block_size, KVL)
    k_scale: jax.Array      # ([L,] n_blocks, 1, SL) f32
    v_scale: jax.Array      # ([L,] n_blocks, 1, SL) f32
    block_table: jax.Array  # (n_slots, blocks_per_slot) int32; n_blocks = unmapped
    #: (n_slots,) int32 — advisory append counter, incremented for every
    #: slot on each append exactly like the dense cache's ``length`` (so
    #: dense/paged views stay leaf-identical).  The engine's host-side
    #: ``positions`` are the authoritative per-slot frontier; attention
    #: masks by position, never by this field.
    length: jax.Array
    #: () int32 — the layer of a stacked pool that appends, attention and
    #: ``gather_view`` address (set by the model's layer scan; 0 and
    #: unused for a per-layer pool)
    layer: jax.Array
    #: KV heads per token and stored values per head (static: the lane
    #: widths above are padded, so the shapes do not carry them)
    kv_heads: int = dataclasses.field(metadata=dict(static=True))
    store_dim: int = dataclasses.field(metadata=dict(static=True))

    @property
    def n_blocks(self) -> int:
        """Pool blocks (the block-table sentinel value is ``n_blocks``)."""
        return self.k.shape[-3]

    @property
    def block_size(self) -> int:
        """Tokens per pool block."""
        return self.k.shape[-2]

    @property
    def n_slots(self) -> int:
        """Decode slots (block-table rows)."""
        return self.block_table.shape[0]

    @property
    def blocks_per_slot(self) -> int:
        """Logical blocks each slot's table row can map."""
        return self.block_table.shape[1]

    @property
    def max_context(self) -> int:
        """Longest per-slot context the block table can map."""
        return self.blocks_per_slot * self.block_size


def lanes(n: int) -> int:
    """``n`` rounded up to whole 128-lane tiles: the pool's lane widths."""
    return -(-int(n) // 128) * 128


class OutOfBlocksError(RuntimeError):
    """Raised when an allocation cannot be satisfied from the free pool."""


class BlockAllocator:
    """Host-side refcounted free-list allocator over ``n_blocks`` blocks.

    Every pool block is in exactly one of three states (the lifecycle
    state machine of DESIGN.md §5.2):

    * **FREE** — on the free list, content meaningless.
    * **LIVE** — refcount >= 1: mapped into one or more slots' block
      tables.  ``alloc`` creates a LIVE block with one reference;
      ``share`` takes another reference on it (prefix sharing maps the
      same physical block into several tables); ``free`` drops one.
    * **CACHED** — refcount 0 but *retained*: the block was marked
      cacheable (its content is published in a :class:`PrefixIndex`), so
      the last ``free`` parked it on an LRU list instead of the free
      list.  ``share`` revives it (prefix hit); ``alloc`` evicts from
      the LRU head when the free list runs dry, notifying ``on_evict``
      so the index drops the dead entry.

    Invariants (locked down by tests/test_paged_kvcache.py):
    * a block is never handed out twice while LIVE or CACHED,
    * ``free`` rejects double-frees; a block frees only at refcount 0,
    * ``alloc`` raises :class:`OutOfBlocksError` rather than over-commit,
    * eviction only ever touches refcount-0 (CACHED) blocks.
    """

    def __init__(self, n_blocks: int,
                 on_evict: Optional[Callable[[int], None]] = None):
        """Create an all-free pool; ``on_evict(block)`` is called when a
        CACHED block is evicted to satisfy an ``alloc``."""
        self.n_blocks = int(n_blocks)
        self.on_evict = on_evict
        self.reset()

    def reset(self) -> None:
        """Return every block to the FREE state and clear all refcounts."""
        self._free: List[int] = list(range(self.n_blocks - 1, -1, -1))
        self._ref: Dict[int, int] = {}
        self._cacheable: set = set()
        self._cached: "OrderedDict[int, None]" = OrderedDict()
        self._peak_live: int = 0

    @property
    def free_count(self) -> int:
        """Strictly-free blocks (excludes CACHED ones)."""
        return len(self._free)

    @property
    def cached_count(self) -> int:
        """Refcount-0 blocks retained for prefix reuse (evictable)."""
        return len(self._cached)

    @property
    def live_count(self) -> int:
        """Blocks with refcount >= 1 (mapped into at least one table)."""
        return len(self._ref)

    @property
    def peak_live(self) -> int:
        """High-water mark of :attr:`live_count` since construction /
        :meth:`reset` — the pool occupancy a sized-down deployment would
        have needed.  The growth benchmarks report this watermark
        instead of sampling ``live_count`` between engine steps (a
        sample can miss the transient peak inside one admission pass)."""
        return self._peak_live

    @property
    def available(self) -> int:
        """Blocks an ``alloc`` could hand out: FREE plus evictable."""
        return len(self._free) + len(self._cached)

    def can_alloc(self, n: int) -> bool:
        """True when ``alloc(n)`` would succeed (possibly by eviction)."""
        return n <= self.available

    def refcount(self, block: int) -> int:
        """Current reference count of ``block`` (0 for FREE/CACHED)."""
        return self._ref.get(block, 0)

    def alloc(self, n: int) -> List[int]:
        """Hand out ``n`` private blocks, each at refcount 1.

        Draws from the free list first, then evicts least-recently-used
        CACHED blocks (calling ``on_evict``).  Raises
        :class:`OutOfBlocksError` — taking nothing — when FREE + CACHED
        cannot cover the request.
        """
        if n > self.available:
            raise OutOfBlocksError(
                f"requested {n} blocks, {len(self._free)} free + "
                f"{len(self._cached)} cached of {self.n_blocks}")
        blocks = []
        for _ in range(n):
            if self._free:
                b = self._free.pop()
            else:
                b, _ = self._cached.popitem(last=False)     # LRU eviction
                self._cacheable.discard(b)
                if self.on_evict is not None:
                    self.on_evict(b)
            self._ref[b] = 1
            blocks.append(b)
        self._peak_live = max(self._peak_live, len(self._ref))
        return blocks

    def share(self, block: int) -> None:
        """Take one more reference on a LIVE block, or revive a CACHED
        block to LIVE (refcount 1).  Raises ``ValueError`` for blocks the
        allocator has not handed out (FREE blocks cannot be shared)."""
        if block in self._ref:
            self._ref[block] += 1
        elif block in self._cached:
            del self._cached[block]
            self._ref[block] = 1
            self._peak_live = max(self._peak_live, len(self._ref))
        else:
            raise ValueError(
                f"block {block} is neither live nor cached (share of a "
                "free block?)")

    def set_cacheable(self, block: int) -> None:
        """Mark a LIVE block as prefix-cacheable: when its refcount hits
        zero it parks on the CACHED LRU instead of the free list."""
        if block not in self._ref:
            raise ValueError(f"block {block} is not live")
        self._cacheable.add(block)

    def free(self, blocks: Sequence[int]) -> None:
        """Drop one reference per block.  A block only leaves the LIVE
        state at refcount 0: cacheable blocks park on the CACHED LRU
        (most-recently-used end), the rest return to the free list.
        Rejects blocks that are not LIVE (double free)."""
        for b in blocks:
            if b not in self._ref:
                raise ValueError(f"block {b} is not allocated (double free?)")
            self._ref[b] -= 1
            if self._ref[b] == 0:
                del self._ref[b]
                if b in self._cacheable:
                    self._cached[b] = None       # MRU end of the LRU list
                else:
                    self._free.append(b)


class PrefixIndex:
    """Content-addressed index of immutable, block-aligned prefix KV.

    Maps a *chain hash* of ``(salt, token ids of blocks 0..j)`` to the
    pool block holding block ``j``'s KV.  The hash of block ``j`` folds
    in the hash of block ``j-1``, so an entry identifies the whole
    prefix, not just one block's tokens — matching walks the chain and
    stops at the first miss.

    ``salt`` must bind everything that determines the *bytes* a block
    holds besides the token ids: the KV ``FormatSpec`` (the same tokens
    quantize differently per format) and the layer set / model identity
    (a pool block spans every layer of the stacked cache, so caches of
    different depth or head geometry are never confusable).  Engines
    derive it from their config; see DESIGN.md §5.2.

    The index stores only host-side ids — the allocator owns block
    lifetime.  ``drop_block`` is wired as the allocator's ``on_evict``
    callback so evicted blocks leave the index atomically.
    """

    def __init__(self, block_size: int, salt: str = ""):
        """Index full blocks of ``block_size`` tokens under ``salt``."""
        self.block_size = int(block_size)
        self._salt = hashlib.sha256(salt.encode()).digest()
        self._by_hash: Dict[bytes, int] = {}
        self._by_block: Dict[int, bytes] = {}

    def __len__(self) -> int:
        """Number of indexed blocks."""
        return len(self._by_hash)

    def chain_hashes(self, tokens: Sequence[int]) -> List[bytes]:
        """Cumulative hash of every *full* block-aligned prefix of
        ``tokens`` — entry ``j`` keys tokens ``[0, (j+1)*block_size)``."""
        bs = self.block_size
        out, h = [], self._salt
        for j in range(len(tokens) // bs):
            m = hashlib.sha256(h)
            m.update(np.asarray(tokens[j * bs:(j + 1) * bs],
                                np.int64).tobytes())
            h = m.digest()
            out.append(h)
        return out

    def match(self, tokens: Sequence[int]) -> List[int]:
        """Longest indexed chain covering a block-aligned prefix of
        ``tokens``; returns the pool blocks in logical order (possibly
        empty).  Does not touch refcounts — callers pin the returned
        blocks via ``BlockAllocator.share`` before using them."""
        return self.match_chain(self.chain_hashes(tokens))

    def match_chain(self, hashes: Sequence[bytes]) -> List[int]:
        """:meth:`match` over precomputed :meth:`chain_hashes` — callers
        that also register later reuse one hash pass per prompt."""
        blocks = []
        for h in hashes:
            b = self._by_hash.get(h)
            if b is None:
                break
            blocks.append(b)
        return blocks

    def register(self, chain_hash: bytes, block: int) -> bool:
        """Publish ``block`` as the holder of ``chain_hash``'s KV.

        Returns False (no-op) when the hash is already served by another
        block — first writer wins; the duplicate stays private — or when
        the block already serves another hash."""
        if chain_hash in self._by_hash or block in self._by_block:
            return False
        self._by_hash[chain_hash] = block
        self._by_block[block] = chain_hash
        return True

    def drop_block(self, block: int) -> None:
        """Forget ``block`` (allocator eviction callback); idempotent."""
        h = self._by_block.pop(block, None)
        if h is not None:
            del self._by_hash[h]


def blocks_needed(n_tokens: int, block_size: int) -> int:
    """Blocks covering ``n_tokens`` tokens (at least one)."""
    return max(1, -(-int(n_tokens) // int(block_size)))


def init_paged(n_slots: int, n_blocks: int, block_size: int, kv_heads: int,
               head_dim: int, spec: FormatSpec,
               blocks_per_slot: Optional[int] = None,
               n_layers: Optional[int] = None) -> PagedKVCache:
    """Zero-initialized pool with an all-sentinel block table.
    ``n_layers`` stacks the pool over that many layers (the engine's
    cache); ``None`` gives a per-layer pool."""
    ds = KV.store_dim(head_dim, spec)
    bps = blocks_per_slot if blocks_per_slot is not None else \
        blocks_needed(n_blocks * block_size, block_size)
    lead = () if n_layers is None else (n_layers,)
    kv = lead + (n_blocks, block_size, lanes(kv_heads * ds))
    sc = lead + (n_blocks, 1, lanes(kv_heads * block_size))
    return PagedKVCache(
        k=jnp.zeros(kv, spec.dtype), v=jnp.zeros(kv, spec.dtype),
        k_scale=jnp.ones(sc, jnp.float32), v_scale=jnp.ones(sc, jnp.float32),
        block_table=jnp.full((n_slots, bps), n_blocks, jnp.int32),
        length=jnp.zeros((n_slots,), jnp.int32),
        layer=jnp.zeros((), jnp.int32), kv_heads=kv_heads, store_dim=ds)


def _stacked(a: jax.Array) -> jax.Array:
    """A pool leaf with its layer axis: per-layer leaves gain ``L = 1``."""
    return a if a.ndim == 4 else a[None]


def _write_plan(cache: PagedKVCache, pos: jax.Array, n: jax.Array, T: int):
    """Where a ``T``-row append lands: one step per (slot, pool block its
    rows can touch), ``J`` per slot.

    Returns ``blk`` (B*J,), the pool block of each step; ``lo``/``hi``
    (B*J,), the block rows it overwrites (``lo == hi``: none); and
    ``first`` (B, J), the chunk row that lands on each block's row 0.
    Slot ``b`` writes rows ``t < n[b]`` at positions ``pos[b] + t``; rows
    past the table, through a sentinel entry, or of an idle slot
    (``n == 0``) write nothing.  A step that writes nothing names the
    block of the last step that does (the first one that does, before
    it), so every block the kernel visits is named by one run of
    consecutive steps — the condition ``kernels/pool_write.py`` needs to
    read each block once and write it back once."""
    bs, bps, nb = cache.block_size, cache.blocks_per_slot, cache.n_blocks
    J = (T + bs - 2) // bs + 1            # most blocks T rows can touch
    lblk = pos[:, None] // bs + jnp.arange(J, dtype=jnp.int32)[None]
    first = lblk * bs - pos[:, None]
    lo = jnp.clip(-first, 0, bs)
    hi = jnp.clip(n[:, None] - first, 0, bs)
    blk = jnp.take_along_axis(cache.block_table,
                              jnp.clip(lblk, 0, bps - 1), axis=1)
    write = ((lblk < bps) & (blk < nb) & (hi > lo)).reshape(-1)
    step = jnp.arange(write.shape[0], dtype=jnp.int32)
    last = jax.lax.cummax(jnp.where(write, step, -1), axis=0)
    src = jnp.where(last >= 0, last, jnp.argmax(write).astype(jnp.int32))
    blk = jnp.minimum(blk.reshape(-1)[src], nb - 1)
    lo = jnp.where(write, lo.reshape(-1), 0)
    hi = jnp.where(write, hi.reshape(-1), 0)
    return blk, lo, hi, first


def append_paged(cache: PagedKVCache, k_new: jax.Array, v_new: jax.Array,
                 pos: jax.Array, spec: FormatSpec,
                 valid=None) -> PagedKVCache:
    """Ragged append through the block table: slot ``b`` quantizes and
    writes its ``T`` new tokens at logical positions ``pos[b] + t`` of
    the layer ``cache.layer`` addresses.

    k_new/v_new: (B, T, H, D) compute dtype; pos: (B,) int32 (a scalar is
    broadcast).  ``valid`` (optional, (B,) int32) masks the write to each
    slot's first ``valid[b]`` tokens — chunk rows past a slot's true
    frontier in a padded mixed prefill/decode step are *dropped* (they
    would otherwise land in live cells of refcounted shared blocks).
    Same quantization path as the dense cache — values land
    bit-identical, only the layout differs.  The rows are written in
    place by the Pallas writer (``kernels/pool_write.py``), which reads
    and writes back only the pool blocks they touch.
    """
    from repro.kernels import ops as kops
    B, T, H, _ = k_new.shape
    bs = cache.block_size
    kq, ks = Q.quantize_kv(k_new, spec)
    vq, vs = Q.quantize_kv(v_new, spec)
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim == 0:
        pos = jnp.broadcast_to(pos, (B,))
    n = jnp.full((B,), T, jnp.int32) if valid is None else \
        jnp.clip(jnp.asarray(valid, jnp.int32), 0, T)
    blk, lo, hi, first = _write_plan(cache, pos, n, T)
    # each step's new rows laid out as its block: block row r holds chunk
    # row first + r (clipped; rows outside [lo, hi) are not written)
    rows = jnp.clip(first[..., None] + jnp.arange(bs, dtype=jnp.int32),
                    0, T - 1).reshape(B, -1, 1)
    G = blk.shape[0]
    kvl, sl = cache.k.shape[-1], cache.k_scale.shape[-1]

    def tiles(x):           # (B, T, H, Ds) → (G, bs, KVL)
        x = jnp.take_along_axis(x.reshape(B, T, -1), rows, axis=1)
        x = x.reshape(G, bs, -1)
        return jnp.pad(x, ((0, 0), (0, 0), (0, kvl - x.shape[-1])))

    def scale_tiles(s):     # (B, T, H, 1) → (G, 1, SL), lane h*bs + r
        x = jnp.take_along_axis(s.astype(jnp.float32).reshape(B, T, H),
                                rows, axis=1)
        x = x.reshape(G, bs, H).transpose(0, 2, 1).reshape(G, 1, H * bs)
        return jnp.pad(x, ((0, 0), (0, 0), (0, sl - H * bs)))

    out = kops.kv_pool_write(
        tuple(_stacked(a) for a in (cache.k, cache.k_scale, cache.v,
                                    cache.v_scale)),
        (tiles(kq), scale_tiles(ks), tiles(vq), scale_tiles(vs)),
        cache.layer, blk, lo, hi, kv_lanes=H * cache.store_dim,
        heads=H)
    k, k_scale, v, v_scale = (o.reshape(a.shape) for o, a in zip(
        out, (cache.k, cache.k_scale, cache.v, cache.v_scale)))
    return dataclasses.replace(cache, k=k, v=v, k_scale=k_scale,
                               v_scale=v_scale, length=cache.length + T)


def live_ctx(cache: PagedKVCache,
             max_live: Optional[int] = None) -> int:
    """Live-context high-water mark in tokens, rounded up to whole blocks
    and clipped to ``max_context`` — the tight ``n_ctx`` for
    :func:`gather_view` fallbacks.

    ``max_live`` (the engine's host-tracked ``max(position) + 1`` over
    running slots) wins when given.  Otherwise the advisory ``length``
    counter is used when it is concrete — an *over*-estimate is safe (it
    only widens the gather), and ``length`` ≥ every true frontier by
    construction.  Under a jit trace with no ``max_live`` the bound is
    unknowable at trace time, so the full ``max_context`` is kept.
    """
    bs = cache.block_size
    if max_live is None:
        if isinstance(cache.length, jax.core.Tracer):
            return cache.max_context
        max_live = int(jnp.max(cache.length)) if cache.length.size else 0
    return min(blocks_needed(max_live, bs) * bs, cache.max_context)


def gather_view(cache: PagedKVCache,
                n_ctx: Optional[int] = None) -> KV.KVCache:
    """Materialize a dense ``(n_slots, n_ctx, H, Dstore)`` view of every
    slot's logical context in the layer ``cache.layer`` addresses, by
    gathering pool blocks through the block table.

    This is the glue between paged storage and the dense attention path:
    the view is a plain :class:`KVCache` in the dense cache's layout, so
    the fused XLA attention consumes it unchanged.  Unmapped positions
    clamp to an arbitrary pool element — finite garbage that the caller's
    causal mask turns into exact zeros.  The view is transient
    (activation memory); only the pool is resident.
    """
    bs, H, ds = cache.block_size, cache.kv_heads, cache.store_dim
    n_ctx = cache.max_context if n_ctx is None else n_ctx
    assert n_ctx % bs == 0, (n_ctx, bs)
    nbp = min(n_ctx // bs, cache.blocks_per_slot)
    tbl = cache.block_table[:, :nbp]                       # (B, nbp)
    flat = (tbl[:, :, None] * bs
            + jnp.arange(bs, dtype=jnp.int32)[None, None]) \
        .reshape(cache.n_slots, nbp * bs)                  # (B, n_ctx)
    if nbp * bs < n_ctx:   # table shorter than requested view: clamp-pad
        flat = jnp.pad(flat, ((0, 0), (0, n_ctx - nbp * bs)))

    def gath(rows):        # (nb * bs, H, d) → (B, n_ctx, H, d)
        out = jnp.take(rows, flat.reshape(-1), axis=0, mode="clip")
        return out.reshape((cache.n_slots, n_ctx) + rows.shape[1:])

    def kv(pool):          # ([L,] nb, bs, KVL) at the addressed layer
        p = _stacked(pool)[cache.layer][..., :H * ds]
        return gath(p.reshape(-1, H, ds))

    def scale(pool):       # ([L,] nb, 1, SL) at the addressed layer
        p = _stacked(pool)[cache.layer][:, 0, :H * bs]
        return gath(p.reshape(-1, H, bs).transpose(0, 2, 1)
                    .reshape(-1, H, 1))

    return KV.KVCache(k=kv(cache.k), v=kv(cache.v),
                      k_scale=scale(cache.k_scale),
                      v_scale=scale(cache.v_scale), length=cache.length)


def copy_block(cache: PagedKVCache, src: jax.Array,
               dst: jax.Array) -> PagedKVCache:
    """Copy one pool block's K/V/scale bytes ``src`` → ``dst``, in every
    layer of a stacked pool.

    The device half of copy-on-write materialization (DESIGN.md §5.2):
    when a slot would append into a *shared* block, the engine allocates
    a private ``dst``, copies the shared block's already-quantized bytes
    (no requantization — COW twins stay bit-identical to a cold prefill),
    and maps ``dst`` into the slot's table instead.  Works on per-layer
    and stacked caches alike (the block axis is third from the minor
    end).  Tables and lengths are untouched.
    """
    src = jnp.asarray(src, jnp.int32)
    dst = jnp.asarray(dst, jnp.int32)

    def cp(pool):
        ax = pool.ndim - 3          # ([L,] n_blocks, ·, ·)
        val = jax.lax.dynamic_index_in_dim(pool, src, axis=ax,
                                           keepdims=True)
        return jax.lax.dynamic_update_slice_in_dim(pool, val, dst, axis=ax)

    return dataclasses.replace(cache, k=cp(cache.k), v=cp(cache.v),
                               k_scale=cp(cache.k_scale),
                               v_scale=cp(cache.v_scale))


def kv_bytes(cache) -> int:
    """Resident bytes of a KV store pytree — paged pool (+ scales +
    tables) or dense slab alike.  Engine.kv_resident_bytes and the
    paged-vs-dense benchmark both report this number."""
    leaves = jax.tree_util.tree_leaves(cache)
    return int(sum(l.size * l.dtype.itemsize for l in leaves))
